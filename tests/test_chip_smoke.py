"""chip_smoke.py off the chip: its phases on olmo-1b-smoke with the
device check left out (kernels in interpret mode, XLA attention paths),
its reading of the compiled steps' kernels, and its refusal to pass
without a TPU or without the repository around it."""
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

from repro.configs import get_smoke

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_run_on_cpu_smoke(cs, tmp_path):
    cfg = get_smoke("olmo-1b")
    errs = cs.phase_kernels(cfg)
    assert len(errs) == 6 and max(errs.values()) < cs.KERNEL_TOL
    args = ["--arch", "olmo-1b", "--smoke"]
    with cs.dump_ir(str(tmp_path / "ir")) as ir_dir:
        rep = cs.phase_cure(args, str(tmp_path), layers=2)
        runs = cs.phase_serve(args, cfg.vocab_size)
    assert rep["params"]["saved_deployed"] > 0
    assert not os.path.exists(tmp_path / "ckpt")
    assert set(runs) == {"dense", "cur_kv"}
    steps = cs.step_kernels(ir_dir)
    # every serving step was compiled, and none holds a TPU kernel here
    assert steps["prefill"] and (steps.get("decode")
                                 or steps.get("decode_scan"))
    assert all(not s for v in steps.values() for s in v)
    # off the chip the registry resolves the XLA references: phase 5
    # must refuse that run
    with pytest.raises(cs.SmokeFailure, match="decode resolved paged_xla"):
        cs.phase_paths(ir_dir, runs)


def _module(kernels):
    calls = "".join(
        f'stablehlo.custom_call @tpu_custom_call(%a) {{kernel_name = "{k}"}}\n'
        for k in kernels)
    return f"module @jit__step {{\n{calls}}}\n"


@pytest.mark.parametrize("prefill,ok", [
    ((["flash_attention", "cur_matmul"], ["flash_attention"]), True),
    ((["flash_attention"], ["flash_attention"]), False),   # no cur_matmul
    ((["cur_matmul"], ["flash_attention"]), False),        # XLA attention
])
def test_paths_phase_reads_kernel_names(cs, tmp_path, prefill, ok):
    files = {"jax_ir0001_jit__prefill_compile.mlir": prefill[0],
             "jax_ir0002_jit__decode_compile.mlir": ["paged_attention"],
             "jax_ir0003_jit__prefill_compile.mlir": prefill[1],
             "jax_ir0004_jit__decode_scan_compile.mlir": ["paged_attention"],
             "jax_ir0005_jit__other_compile.mlir": []}
    for name, kernels in files.items():
        (tmp_path / name).write_text(_module(kernels))
    be = {"paged_decode": "paged_pallas", "paged_prefill": "rank_fold"}
    runs = {"dense": {"attn_backends": be}, "cur_kv": {"attn_backends": be}}
    if ok:
        cs.phase_paths(str(tmp_path), runs)
    else:
        with pytest.raises(cs.SmokeFailure):
            cs.phase_paths(str(tmp_path), runs)


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    r = _run(_SCRIPT, _ROOT)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_refuses_without_the_repo(tmp_path):
    script = shutil.copy(_SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(str(script), str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
