"""The seed words that every random draw of a run is made from.

One ``--seed`` gives the weights, the step keys fed to the program and the
reference's own Lambda draws, each from its own purpose, so that two
purposes never share a stream.  The tokens are the program's own synthetic
stream (`data.make_lm_pipeline`), seeded with ``--seed`` as the driver
seeds it.
"""
from __future__ import annotations

import numpy as np

PURPOSES = ("weights", "step_keys", "ref_lambda", "ctl_lambda")


def seed_words(seed: int, purpose: str) -> np.ndarray:
    """Two uint32 words for one use of ``seed`` (any non-negative int)."""
    ss = np.random.SeedSequence(int(seed),
                                spawn_key=(PURPOSES.index(purpose),))
    return ss.generate_state(2, np.uint32)


def jax_key(seed: int, purpose: str):
    import jax
    import jax.numpy as jnp
    return jax.random.wrap_key_data(
        jnp.asarray(seed_words(seed, purpose), jnp.uint32))
