import os

# Smoke tests and benches must see ONE device; only dryrun.py forces 512.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def make_batch(binputs, seed=1, fill=3):
    """Random batch matching a model's input specs."""
    import jax.numpy as jnp
    batch = {}
    for k, (sds, bd) in binputs.items():
        if np.issubdtype(sds.dtype, np.integer):
            if k in ("ids", "labels"):
                batch[k] = jax.random.randint(
                    jax.random.PRNGKey(seed), sds.shape, 0, 100
                ).astype(sds.dtype)
            elif k == "cache_len":
                batch[k] = jnp.full(sds.shape, 4, sds.dtype)
            else:
                batch[k] = jnp.zeros(sds.shape, sds.dtype) + fill
        else:
            batch[k] = jax.random.normal(
                jax.random.PRNGKey(seed), sds.shape).astype(sds.dtype)
    return batch
