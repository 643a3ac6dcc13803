"""The trace reduction on a synthesized trace: busy/idle union, seconds
per operation by stable name, idle gaps attributed to host spans."""
import pytest

import chipbench_tiny  # noqa: F401  (paths, CPU)
from benchmarks.chip import trace as tr

MS = 1_000_000  # ns


def _trace():
    dev = [
        tr.Event("%fusion.3 = bf16[8] fusion(...)", 10 * MS, 20 * MS),
        tr.Event("%paged_attention.1 = bf16[4] custom-call(...)",
                 25 * MS, 10 * MS),                      # overlaps fusion
        tr.Event("%paged_attention.7 = bf16[4] custom-call(...)",
                 60 * MS, 10 * MS),
        tr.Event("%copy.2 = bf16[4] copy(...)", 95 * MS, 20 * MS),  # ends late
    ]
    host = [tr.Event("window", 0, 100 * MS),
            tr.Event("step", 5 * MS, 40 * MS),
            tr.Event("submit", 45 * MS, 10 * MS),
            tr.Event("sleep", 72 * MS, 20 * MS)]
    return tr.TraceData(devices={"/device:TPU:0": dev}, host=host)


def test_busy_is_the_union_inside_the_window():
    red = tr.reduce(_trace())
    # [10, 35] + [60, 70] + [95, 100]: 25 + 10 + 5 ms
    assert red.window_s == pytest.approx(0.100)
    assert red.busy_s == pytest.approx(0.040)
    assert red.idle_share == pytest.approx(0.6)


def test_time_per_operation_by_stable_name():
    red = tr.reduce(_trace())
    assert red.op_s["paged_attention"] == pytest.approx(0.020)
    assert red.op_count["paged_attention"] == 2
    assert red.op_s["fusion"] == pytest.approx(0.020)
    assert red.op_s["copy"] == pytest.approx(0.005)    # clipped at the end


def test_gaps_go_to_the_host_span_they_fall_in():
    red = tr.reduce(_trace())
    # gaps: [0,10] step? no: 5 is the midpoint -> step; [35,60] -> 47.5
    # submit; [70,95] -> 82.5 sleep
    assert red.gaps_s["step"] == pytest.approx(0.010)
    assert red.gaps_s["submit"] == pytest.approx(0.025)
    assert red.gaps_s["sleep"] == pytest.approx(0.025)
    b = red.breakdown()
    assert [k for k, _ in b["device_ops"]][0] in ("paged_attention", "fusion")
    assert len(b["idle_gaps"]) == 3


def test_several_devices_are_averaged():
    td = _trace()
    td.devices["/device:TPU:1"] = [tr.Event("%fusion = f32[] fusion()",
                                            0, 100 * MS)]
    red = tr.reduce(td)
    assert red.busy_s == pytest.approx((0.040 + 0.100) / 2)
    assert red.n_devices == 2


@pytest.mark.parametrize("op,name", [
    ("%paged_attention.12 = bf16[1] custom-call(x)", "paged_attention"),
    ("%convolution_reduce_fusion = bf16[] fusion(y)",
     "convolution_reduce_fusion"),
    ("%copy-done.3 = bf16[2] copy-done(z)", "copy-done"),
])
def test_stable_names(op, name):
    assert tr.stable_name(op) == name


def test_no_window_or_no_device_is_an_error():
    td = _trace()
    with pytest.raises(ValueError):
        tr.reduce(tr.TraceData(devices=td.devices, host=td.host[1:]))
    with pytest.raises(ValueError):
        tr.reduce(tr.TraceData(devices={}, host=td.host))
