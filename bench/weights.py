"""Random weights from the seed, made on the device in one jitted call.

The tree is laid out as the serving engine takes it (the program's
parameter tree for a dense decoder whose layers are stacked on a leading
axis):

    embed.table                      (vocab, d)
    decoder.groups.0.norm1.scale     (L, d)        RMSNorm weight minus one
    decoder.groups.0.attn.wq         (L, d, H, D)  bq (L, H, D)
    decoder.groups.0.attn.wk / wv    (L, d, Hkv, D) bk / bv (L, Hkv, D)
    decoder.groups.0.attn.wo         (L, H, D, d)
    decoder.groups.0.norm2.scale     (L, d)
    decoder.groups.0.mlp.wg / wu     (L, d, F)     wd (L, F, d)
    decoder.final_norm.scale         (d,)
    lm_head.table                    (vocab, d)    only when not tied

The norm entries hold the RMSNorm weight minus one: the program multiplies
by ``1 + scale``.  Matrices are drawn with unit variance over their fan-in,
the embedding with the configs' ``initializer_range``, biases and norm
offsets small and nonzero so that every path is exercised, and
everything is cast to the served dtype on the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.work import Sizes


# the published configs' initializer_range: with it the embedding is small
# beside what the layers add, so the last hidden state is not the input
# token's own embedding (which, tied to the output head, would make every
# greedy token repeat its input by a wide margin)
EMBED_STD = 0.02


def key_for(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size, 32 bits or more."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _tree(s: Sizes, key: jax.Array, dtype) -> dict:
    L, d, H, K, D, F = (s.layers, s.d_model, s.heads, s.kv_heads,
                        s.head_dim, s.d_ff)
    keys = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        x = jax.random.normal(next(keys), shape, jnp.float32) * scale
        return x.astype(dtype)

    layer = {
        "norm1": {"scale": normal((L, d), 0.1)},
        "attn": {
            "wq": normal((L, d, H, D), d ** -0.5),
            "wk": normal((L, d, K, D), d ** -0.5),
            "wv": normal((L, d, K, D), d ** -0.5),
            "wo": normal((L, H, D, d), (H * D) ** -0.5),
            "bq": normal((L, H, D), 0.1),
            "bk": normal((L, K, D), 0.1),
            "bv": normal((L, K, D), 0.1),
        },
        "norm2": {"scale": normal((L, d), 0.1)},
        "mlp": {
            "wg": normal((L, d, F), d ** -0.5),
            "wu": normal((L, d, F), d ** -0.5),
            "wd": normal((L, F, d), F ** -0.5),
        },
    }
    tree = {
        "embed": {"table": normal((s.vocab, d), EMBED_STD)},
        "decoder": {"groups": {"0": layer},
                    "final_norm": {"scale": normal((d,), 0.1)}},
    }
    if not s.tied:
        tree["lm_head"] = {"table": normal((s.vocab, d), EMBED_STD)}
    return tree


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(s: Sizes, key: jax.Array, dtype: str) -> dict:
    return _tree(s, key, jnp.dtype(dtype))


def make(s: Sizes, seed: int, dtype: str = "bfloat16") -> dict:
    """The whole tree, on the default device, in ``dtype``."""
    return _make(s, key_for(seed), dtype)
