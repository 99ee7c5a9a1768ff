"""Everything random in a run comes from `--seed`, any whole number
(larger than 32 bits included), through NumPy's SeedSequence: one
stream for the weights and one for the traffic."""
from __future__ import annotations

import numpy as np

WEIGHTS, TRAFFIC = 0, 1


def seed_sequence(seed: int, stream: int) -> np.random.SeedSequence:
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    return np.random.SeedSequence([int(seed), stream])


def weight_key_data(seed: int) -> np.ndarray:
    """Two uint32 words: the raw data of a threefry key."""
    return seed_sequence(seed, WEIGHTS).generate_state(2, dtype=np.uint32)


def weight_key(seed: int):
    import jax
    import jax.numpy as jnp
    return jax.random.wrap_key_data(jnp.asarray(weight_key_data(seed)),
                                    impl="threefry2x32")


def traffic_rng(seed: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(seed_sequence(seed, TRAFFIC)))
