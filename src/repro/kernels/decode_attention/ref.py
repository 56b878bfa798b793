"""Pure-jnp oracles for the decode-attention kernels.

``decode_attention``: single new query token per sequence attends over a
(possibly ring-buffered) contiguous KV cache.  Slots with k_position == -1
are unfilled and masked; window masking uses absolute positions so ring
buffers work unchanged.

``paged_decode_attention``: same math over a paged cache — K/V are gathered
from one layer of the stacked block pools (layers, N, bs, Hkv*D) through a
per-sequence block table, and key positions are synthesized (gathered
index j == absolute position j), so causal masking hides both the
unwritten tail of the last block and any garbage-block table entries
(their positions all exceed the query's).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


def decode_attention(
    q: jax.Array,          # (B, 1, Hq, D)
    k_cache: jax.Array,    # (B, L, Hkv, D)
    v_cache: jax.Array,    # (B, L, Hkv, D)
    *,
    q_positions: jax.Array,   # (B, 1)
    k_positions: jax.Array,   # (B, L)
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    B, S, Hq, D = q.shape
    Hkv = k_cache.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    scores = jnp.einsum(
        "bshgd,bthd->bhgst", qg, k_cache, preferred_element_type=jnp.float32
    ) / math.sqrt(D)
    if softcap > 0.0:
        scores = jnp.tanh(scores / softcap) * softcap
    valid = (k_positions >= 0) & (k_positions <= q_positions)  # (B, L)
    if window > 0:
        valid = valid & (q_positions - k_positions < window)
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgst,bthd->bshgd", probs.astype(v_cache.dtype), v_cache)
    return o.reshape(B, S, Hq, D)


def paged_decode_attention(
    q: jax.Array,              # (B, 1, Hq, D)
    k_pool: jax.Array,         # (layers, N, bs, Hkv*D) stacked block pools
    v_pool: jax.Array,         # (layers, N, bs, Hkv*D)
    *,
    block_tables: jax.Array,   # (B, max_blocks) int32 pool indices
    q_positions: jax.Array,    # (B, 1)
    layer,                     # int32 scalar: the pools' layer to read
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    B, nb = block_tables.shape
    D = q.shape[-1]
    bs = k_pool.shape[2]
    L = nb * bs
    shape = (B, L, k_pool.shape[3] // D, D)
    k = k_pool[layer, block_tables].reshape(shape)
    v = v_pool[layer, block_tables].reshape(shape)
    k_positions = jnp.broadcast_to(
        jnp.arange(L, dtype=jnp.int32)[None], (B, L))
    return decode_attention(
        q, k, v, q_positions=q_positions, k_positions=k_positions,
        window=window, softcap=softcap)
