"""Jit wrapper for the decode-attention kernel (inference-only, no vjp)."""

from __future__ import annotations

from repro.kernels.decode_attention.decode_attention import (
    decode_attention as _pallas,
)
from repro.kernels.decode_attention.decode_attention import (
    paged_decode_attention as _pallas_paged,
)


def decode_attention(q, k_cache, v_cache, *, q_positions, k_positions,
                     window=0, softcap=0.0, interpret=False):
    return _pallas(
        q, k_cache, v_cache, q_positions, k_positions,
        window=window, softcap=softcap, interpret=interpret,
    )


def paged_decode_attention(q, k_pool, v_pool, *, block_tables, q_positions,
                           layer, window=0, softcap=0.0, interpret=False):
    """Paged variant: kv tiles DMA'd straight from layer ``layer`` of the
    stacked pools (layers, N, bs, Hkv*D) via the scalar-prefetched block
    table (tile size == pool block size)."""
    return _pallas_paged(
        q, k_pool, v_pool, block_tables, q_positions, layer,
        window=window, softcap=softcap, interpret=interpret,
    )
