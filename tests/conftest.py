import os

# Tests must see ONE device (the dry-run sets its own 512-device flag in a
# subprocess); keep CPU math deterministic.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_repro
from repro.models import init_params


@pytest.fixture(scope="session")
def tiny_cfg():
    cfg = get_repro()
    return cfg.replace(
        name="tiny", d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=160, vocab_size=512,
        groups=((cfg.groups[0][0], 4),), scan_layers=False)


@pytest.fixture(scope="session")
def tiny_params(tiny_cfg):
    return init_params(jax.random.PRNGKey(0), tiny_cfg)


def make_batch(cfg, B=2, S=32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    batch = {"labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size)}
    if cfg.input_mode == "tokens":
        batch["tokens"] = jax.random.randint(k1, (B, S), 0, cfg.vocab_size)
    else:
        batch["embeds"] = jax.random.normal(k3, (B, S, cfg.d_model),
                                            jnp.float32)
    return batch
