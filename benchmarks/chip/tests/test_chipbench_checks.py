"""The comparison that decides ``correct``, driven through the harness on
a tiny copy of every cell on the CPU (the look for a chip left out):
sound runs pass, the timed path broken underneath fails, and the control
(the reference one precision step down) reads far above the program."""
import jax
import pytest

import chipbench_tiny

def _cells():
    """cell -> mode, for every workload file (cells of the benchmark and
    those kept ready for a later one)."""
    return {name: chipbench_tiny._load(chipbench_tiny.CHIP, "workloads",
                                       name + ".json")["mode"]
            for name in chipbench_tiny.workload_files()}


CELLS = _cells()
# the fault each kind of cell can have, planted under the timed path
FAULTS = {"serve": "token", "cure": "answer"}
# the number each kind of cell's control has to fail
CONTROLLED = {"serve": "widest_gap", "cure": "act_err"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return chipbench_tiny.build(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture
def fresh(monkeypatch, cell):
    """A CURing fault is planted in a module global that jitted programs
    close over: restore it, and drop compiled programs, around the test
    (a serving fault is planted in one server and goes with it)."""
    from repro.core import angular, compress
    monkeypatch.setattr(compress, "cur_from_indices",
                        compress.cur_from_indices)
    monkeypatch.setattr(compress, "select_indices", compress.select_indices)
    monkeypatch.setattr(angular, "select_layers", angular.select_layers)
    if CELLS[cell] == "cure":
        jax.clear_caches()
    yield
    if CELLS[cell] == "cure":
        jax.clear_caches()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(root, cell, fresh):
    line = chipbench_tiny.run(root, cell, 2 ** 33 + 17, 2.0)
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_broken_timed_path_is_not_correct(root, cell, fresh):
    line = chipbench_tiny.run(root, cell, 5, 2.0, fault=FAULTS[CELLS[cell]])
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("fault", ["selection", "layers"])
@pytest.mark.parametrize("cell", sorted(c for c in CELLS
                                        if CELLS[c] == "cure"))
def test_broken_selection_is_not_correct(root, cell, fault, fresh):
    """The first r indices in place of DEIM's, or the layers of largest
    angular distance, each fail their own number."""
    line = chipbench_tiny.run(root, cell, 5, 2.0, fault=fault)
    number = {"selection": "sel_growth", "layers": "layers_differ"}[fault]
    check = line["checks"][number]
    assert check["value"] > check["limit"], line["checks"]
    assert line["correct"] is False


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_reads_far_above_the_program(root, cell, fresh):
    import importlib
    name = CONTROLLED[CELLS[cell]]
    for seed in (1, 2):
        ctx = chipbench_tiny.context(root, cell, seed, 1.0, control=True)
        mode = importlib.import_module("benchmarks.chip.modes."
                                       + ctx.workload["mode"])
        out = mode.run(ctx)
        prog = {c.name: c.value for c in out.checks}[name]
        ctl = out.reading["control"][name]
        assert ctl >= 3 * prog, (seed, prog, ctl)
