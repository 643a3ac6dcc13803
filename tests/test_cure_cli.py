"""launch/cure.py end-to-end smoke: init -> calibrate -> compress ->
fold -> checkpoint save -> serving smoke-generate, on one attention arch
(paged continuous-batching runtime) and one mamba arch (legacy-engine
fall-back), with the Table-1-shaped report JSON."""
import json

import pytest

from repro.dist.checkpoint import CheckpointManager
from repro.launch.cure import main

_STAGES = ("init", "calibrate", "plan", "compress", "fold", "save",
           "generate", "total")


@pytest.mark.parametrize("arch,engine", [
    ("olmo-1b", "serving"),
    ("mamba2-1.3b", "legacy"),
])
def test_cure_cli_smoke(arch, engine, tmp_path):
    report = main([
        "--arch", arch, "--smoke", "--layers", "1", "--r-max", "8",
        "--calib-batches", "1", "--calib-batch", "1", "--calib-len", "32",
        "--n-requests", "2", "--prompt-len", "8", "--new-tokens", "4",
        "--max-concurrency", "2",
        "--ckpt-dir", str(tmp_path / "ckpt"),
        "--report", str(tmp_path / "cure.json"),
    ])
    data = json.loads((tmp_path / "cure.json").read_text())
    assert data["arch"] == arch
    for k in _STAGES:
        assert data["stages_s"][k] >= 0.0
    assert data["n_weights"] >= 1
    p = data["params"]
    assert p["after_folded"] < p["after_unfolded"] < p["targeted_before"]
    assert p["after_deployed"] == p["after_folded"]   # default folds
    for w in data["weights"]:
        assert w["rel_fro_err"] >= 0.0
        assert w["bound_on"] == "wanda"               # default selection
    assert data["generate"]["engine"] == engine
    assert data["generate"]["tokens"] > 0
    assert CheckpointManager(str(tmp_path / "ckpt")).latest_valid_step() == 0
    assert report["stages_s"].keys() == data["stages_s"].keys()
    # uniform runs still report the assigned ranks + realized budget
    pl = data["plan"]
    assert pl["source"] == "uniform"
    assert len(pl["ranks"]) == data["n_weights"]
    assert pl["budget"]["requested"] is None
    assert 0.0 < pl["budget"]["realized_fraction"] < 1.0


def _hash_ckpt(d):
    import hashlib
    import os
    h = hashlib.sha256()
    for root, _, files in sorted(os.walk(str(d))):
        for f in sorted(files):
            h.update(f.encode())
            with open(os.path.join(root, f), "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def test_cure_cli_budget_plan_roundtrip(tmp_path):
    """A --budget-* run emits a CompressionPlan; re-running with --plan
    must reproduce the exact same selections and factors (bit-identical
    checkpoint), and both reports carry the allocation + realized vs
    requested budget."""
    common = [
        "--arch", "olmo-1b", "--smoke", "--layers", "1", "--r-max", "16",
        "--calib-batches", "1", "--calib-batch", "1", "--calib-len", "32",
        "--n-requests", "2", "--prompt-len", "8", "--new-tokens", "4",
        "--max-concurrency", "2",
    ]
    rep_a = main(common + [
        "--budget-params", "0.5", "--grid", "4,8,16",
        "--emit-plan", str(tmp_path / "plan.json"),
        "--ckpt-dir", str(tmp_path / "a"),
        "--report", str(tmp_path / "a.json")])
    rep_b = main(common + [
        "--plan", str(tmp_path / "plan.json"),
        "--ckpt-dir", str(tmp_path / "b"),
        "--report", str(tmp_path / "b.json")])

    assert rep_a["plan"]["source"] == "budget"
    assert rep_b["plan"]["source"] == "file"
    assert rep_a["plan"]["ranks"] == rep_b["plan"]["ranks"]
    for rep in (rep_a, rep_b):
        b = rep["plan"]["budget"]
        assert b["kind"] == "params" and b["feasible"]
        assert b["realized"]["params_after"] <= b["requested"] * (1 + 1e-9)
        assert rep["plan"]["solver"] == "greedy"
        assert {w["name"] for w in rep["weights"]} <= {"wq", "wk", "w_gate"}
    assert _hash_ckpt(tmp_path / "a") == _hash_ckpt(tmp_path / "b")


def test_cure_cli_prof_takes_one_capture(tmp_path):
    """--prof takes one capture that holds calibrate, plan and compress's
    spans beside the device's operations; --trace writes the inner
    spans too, while stages_s keeps only the stages."""
    import glob
    import os

    from jax.profiler import ProfileData
    obs_out = tmp_path / "obs"
    report = main([
        "--arch", "olmo-1b", "--smoke", "--layers", "1", "--r-max", "8",
        "--calib-batches", "1", "--calib-batch", "1", "--calib-len", "32",
        "--n-requests", "2", "--prompt-len", "8", "--new-tokens", "4",
        "--max-concurrency", "2", "--prof", "--trace",
        "--obs-out", str(obs_out), "--ckpt-dir", str(tmp_path / "ckpt"),
    ])
    path, = glob.glob(os.path.join(str(obs_out), "jaxprof", "**",
                                   "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    for span in ("calibrate", "calibrate.batch", "plan",
                 "compress.class", "compress.fold.wait"):
        assert "repro." + span in names
    assert not any("." in k for k in report["stages_s"])
    trace = json.loads((obs_out / "trace.json").read_text())
    assert "compress.class" in {e["name"] for e in trace["traceEvents"]}
