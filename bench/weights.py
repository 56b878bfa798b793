"""What every family's weights share: the PRNG key drawn from the seed.

A family (``bench/arch/<model_type>.py``) makes its weight tree from this
key on the device in one jitted call, in the served dtype, laid out as the
program's parameter tree (which the harness checks).
"""

from __future__ import annotations

import jax


def key_for(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size, 32 bits or more."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
