"""Sharding rules: every PartitionSpec produced for every arch must divide
the corresponding dim — validated on an abstract 16x16 mesh without
devices. (The numerical shard_map tests live in test_distributed.py.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.configs.base import CURConfig, OptimizerConfig, SHAPES, \
    shape_applicable
from repro.dist import sharding as shd
from repro.launch import specs as sp
from repro.optim.adamw import AdamW


def _mesh(multi_pod=False):
    if multi_pod:
        return shd.abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    return shd.abstract_mesh((16, 16), ("data", "model"))


def _check_divisible(tree, specs, mesh, tag):
    leaves, tdef = jax.tree_util.tree_flatten(tree)
    slv = tdef.flatten_up_to(specs)
    for leaf, spec in zip(leaves, slv):
        if spec is None:
            continue
        assert len(spec) <= len(leaf.shape), (tag, leaf.shape, spec)
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % size == 0, (tag, leaf.shape, spec)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("multi_pod", [False, True])
def test_param_specs_divisible(arch, multi_pod):
    cfg = get_config(arch)
    mesh = _mesh(multi_pod)
    params = sp.param_specs(cfg)
    specs = shd.param_pspecs(params, cfg, mesh)
    _check_divisible(params, specs, mesh, arch)


@pytest.mark.parametrize("arch", ["deepseek-67b", "kimi-k2-1t-a32b",
                                  "mamba2-1.3b"])
def test_cur_param_specs_divisible(arch):
    cfg = get_config(arch)
    mesh = _mesh()
    params = sp.structural_cur(sp.param_specs(cfg), cfg, CURConfig())
    specs = shd.param_pspecs(params, cfg, mesh)
    _check_divisible(params, specs, mesh, arch)


@pytest.mark.parametrize("arch", ["kimi-k2-1t-a32b", "olmo-1b"])
def test_opt_state_specs_divisible(arch):
    cfg = get_config(arch)
    mesh = _mesh()
    params = sp.param_specs(cfg)
    opt = AdamW(OptimizerConfig(quantized_state=(arch.startswith("kimi"))))
    opt_state = jax.eval_shape(opt.init, params)
    specs = shd.opt_state_pspecs(opt_state, cfg, mesh)
    _check_divisible(opt_state, specs, mesh, arch)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_cache_specs_divisible(arch, shape_name):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applicable(arch, shape) or shape.kind == "train":
        pytest.skip("n/a")
    mesh = _mesh()
    cache = sp.cache_specs(cfg, shape)
    specs = shd.cache_pspecs(cache, cfg, shape, mesh)
    _check_divisible(cache, specs, mesh, f"{arch}/{shape_name}")


def test_tp_sharding_assignments():
    """Spot-check the layout contract (DESIGN.md §4)."""
    cfg = get_config("deepseek-67b")       # fsdp=True
    mesh = _mesh()
    params = sp.param_specs(cfg)
    specs = shd.param_pspecs(params, cfg, mesh)
    blk = specs["groups"][0][0]
    assert blk["wq"] == P(None, "data", "model")
    assert blk["wo"] == P(None, "model", "data")
    assert blk["w_down"] == P(None, "model", "data")
    assert specs["embed"] == P("model", None)

    kimi = get_config("kimi-k2-1t-a32b")
    kp = sp.param_specs(kimi)
    ks = shd.param_pspecs(kp, kimi, mesh)
    moe_blk = ks["groups"][1][0]
    assert moe_blk["w_gate"] == P(None, "model", "data", None)  # EP
    mix = get_config("mixtral-8x22b")
    mp = sp.param_specs(mix)
    ms = shd.param_pspecs(mp, mix, mesh)
    assert ms["groups"][0][0]["w_gate"] == P(None, None, "data", "model")


@pytest.mark.parametrize("arch", ["deepseek-67b", "kimi-k2-1t-a32b",
                                  "mamba2-1.3b"])
def test_cur_folded_param_specs_divisible(arch):
    """The deploy-time folded {CU, R} form must shard like the healing
    form: CU inherits C's (input-dim) layout, R keeps the output dim."""
    cfg = get_config(arch)
    mesh = _mesh()
    cur = sp.structural_cur(sp.param_specs(cfg), cfg, CURConfig())
    folded = sp.fold_cur_struct(cur)
    specs = shd.param_pspecs(folded, cfg, mesh)
    _check_divisible(folded, specs, mesh, arch)
    # spot-check dispatch on one folded leaf
    blk = folded["groups"][0][0]
    sblk = specs["groups"][0][0]
    for t in cfg.cur_targets:
        if t in blk and isinstance(blk[t], dict):
            assert set(blk[t].keys()) == {"CU", "R"}
            cur_blk = cur["groups"][0][0][t]
            cur_spec = shd.param_pspecs(cur, cfg, mesh)["groups"][0][0][t]
            assert sblk[t]["CU"] == cur_spec["C"], t   # same layout as C
            assert sblk[t]["R"] == cur_spec["R"], t
            assert blk[t]["CU"].shape == cur_blk["C"].shape
            break
    else:  # pragma: no cover
        pytest.fail("no CUR dict leaf found")


def test_to_named_roundtrip():
    """to_named must preserve every spec verbatim (None -> replicated) on
    an arbitrary nested pytree, so jit in_shardings see exactly the layout
    contract the divisibility tests validated."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    specs = {
        "groups": [[{"wq": P(None, "data", "model"),
                     "wo": P(None, "model", "data"),
                     "cur": {"C": P(None, "data", None),
                             "U0": None,
                             "R": P(None, None, "model")},
                     "norm": None}]],
        "embed": P("model", None),
        "step": None,
    }
    named = shd.to_named(specs, mesh)
    flat_s = jax.tree.flatten(
        specs, is_leaf=lambda x: x is None or isinstance(x, P))[0]
    flat_n = jax.tree.leaves(named)
    assert len(flat_s) == len(flat_n)
    for s, n in zip(flat_s, flat_n):
        assert isinstance(n, jax.sharding.NamedSharding)
        assert n.mesh.shape == mesh.shape
        assert n.spec == (s if s is not None else P())


def test_recovery_mesh_from_plan():
    from repro.dist.elastic import plan_recovery
    from repro.launch.mesh import make_recovery_mesh

    plan = plan_recovery(total_chips=1, failed_chips=0, tp_width=1,
                         resume_step=0)
    m = make_recovery_mesh(plan)
    assert m.devices.shape == (1, 1)
    assert m.axis_names == ("data", "model")
    big = plan_recovery(total_chips=512, failed_chips=16, tp_width=16,
                        resume_step=7)
    with pytest.raises(RuntimeError):
        make_recovery_mesh(big)   # this host has 1 device, plan needs 256


def test_structural_cur_reduces_params():
    cfg = get_config("deepseek-67b")
    dense = sp.param_specs(cfg)
    cur = sp.structural_cur(dense, cfg, CURConfig(r_max=256))
    assert sp.count_struct_params(cur) < sp.count_struct_params(dense)
    blk = cur["groups"][0][0]
    assert set(blk["wq"].keys()) == {"C", "U0", "dU", "R"}
    # Eq. 2 rank: wq is (8192, 8192) -> r_max cap
    assert blk["wq"]["U0"].shape == (95, 256, 256)


def test_paged_cache_specs_divisible():
    """Paged-pool specs: kv-heads shard over 'model', tables replicate,
    CUR-KV projections replicate; all assignments divisible."""
    mesh = _mesh()
    cfg = get_config("olmo-1b")
    cache, pc = sp.paged_cache_specs(cfg, SHAPES["decode_32k"])
    specs = shd.paged_cache_pspecs(cache, cfg, mesh)
    _check_divisible(cache, specs, mesh, "paged-olmo")
    assert tuple(specs["k"]) == (None, None, "model", None, None)
    toks, table, ctx, active = shd.paged_decode_pspecs(
        cfg, SHAPES["decode_32k"].global_batch, pc.max_blocks_per_seq,
        mesh)
    assert tuple(toks) == ("data", None)
    assert tuple(table) == ("data", None)


def test_paged_cache_specs_kernel_pins_kv_heads():
    """kernel=True (REPRO_PAGED_KERNEL path): the Pallas kernel tiles
    (block, kv-head), so kv-heads is the only shardable pool axis —
    non-divisible kv-heads replicate instead of falling back to the
    rank/block axes (which would split in-kernel tiles)."""
    from repro.configs import get_smoke
    from repro.serving.paged_cache import PagedConfig, init_paged_cache
    mesh = _mesh()
    # real olmo: K=16 divides the 16-way model axis -> same spec both ways
    cfg = get_config("olmo-1b")
    cache, pc = sp.paged_cache_specs(cfg, SHAPES["decode_32k"])
    specs = shd.paged_cache_pspecs(cache, cfg, mesh, kernel=True)
    assert tuple(specs["k"]) == (None, None, "model", None, None)
    # smoke olmo: K=4 does not divide 16; the einsum path falls back to
    # the rank axis, the kernel path must replicate
    scfg = get_smoke("olmo-1b")
    pc = PagedConfig(block_size=16, n_blocks=64, max_blocks_per_seq=8)
    scache = jax.eval_shape(lambda: init_paged_cache(scfg, pc))
    fallback = shd.paged_cache_pspecs(scache, scfg, mesh)
    assert tuple(fallback["k"]) == (None, None, None, None, "model")
    pinned = shd.paged_cache_pspecs(scache, scfg, mesh, kernel=True)
    assert pinned["k"] is None and pinned["v"] is None
    # decode input specs are layout-identical on both paths
    a = shd.paged_decode_pspecs(cfg, 16, 8, mesh)
    b = shd.paged_decode_pspecs(cfg, 16, 8, mesh, kernel=True)
    assert a == b


def test_paged_cache_specs_cur_kv():
    from repro.serving.paged_cache import PagedConfig, init_paged_cache
    mesh = _mesh()
    cfg = get_config("olmo-1b")
    pc = PagedConfig(block_size=128, n_blocks=64, max_blocks_per_seq=8,
                     cur_kv=True, kv_rank=64)
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, pc))
    specs = shd.paged_cache_pspecs(cache, cfg, mesh)
    _check_divisible(cache, specs, mesh, "paged-curkv")
    assert tuple(specs["k"]) == (None, None, "model", None, None)
    assert specs["proj"]["uk"] is None          # replicated
    # CUR-KV pool stores r of head_dim feature columns
    assert cache["k"].shape[-1] == 64
