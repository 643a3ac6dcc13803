"""The Pallas kernels compile for a TPU v5e chip at olmo-1b widths.

Compiled with the installed TPU compiler for one device of a described
(not attached) ``v5e:2x2`` topology: this catches what interpret mode
cannot — block shapes off the (8, 128) tiling, scoped VMEM overruns —
without a chip. Each case also checks that the kernel is in the
compiled program as a ``tpu_custom_call``.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cur_matmul.cur_matmul import cur_matmul
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.paged_attention.paged_attention import paged_attention


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a TPU
    executable written here could not be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.values["jax_enable_compilation_cache"]
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


_BF = jnp.bfloat16


@pytest.mark.parametrize("m,n", [(2048, 8192), (8192, 2048), (2048, 2048)])
def test_cur_matmul_compiles(one_chip, m, n):
    hlo = _compile(cur_matmul, one_chip,
                   ((512, m), _BF), ((m, 256), _BF), ((256, n), _BF))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("r", [128, 64])     # dense pool, CUR-KV at hd/2
def test_paged_attention_compiles(one_chip, r):
    hlo = _compile(paged_attention, one_chip,
                   ((8, 16, 1, r), _BF), ((256, 16, 16, r), _BF),
                   ((256, 16, 16, r), _BF), ((8, 32), jnp.int32),
                   ((8,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    shape = ((1, 16, 512, 128), _BF)
    hlo = _compile(flash_attention, one_chip, shape, shape, shape)
    assert "tpu_custom_call" in hlo
