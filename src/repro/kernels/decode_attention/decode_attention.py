"""Pallas TPU decode attention: one query token per sequence over a long
(possibly ring-buffered) KV cache, plus the paged (block-pool) variant.

TPU-native design:
  * heads ride in the lane dimension: the cache is viewed as (B, L, H*D)
    and a pool is stored as (layers, N, bs, H*D), so every block's last two
    dimensions are (rows, H*D), whole in the lanes, as the TPU compiler
    requires.  The kernel walks the kv heads with static lane slices.  On
    the TPU the contiguous cache's reshape from the model's (B, L, H, D)
    layout is a relayout copy of the whole cache per call (tiles of the
    last two dims differ); the pool is stored lane-dense and read as is.
  * GQA grouping is exploited for MXU utilization: the G query heads that
    share one kv head are processed together as a (G, D) LHS, so the score
    matmul is (G, D) x (D, bk) instead of G separate vector products.
  * grid = (batch, kv_blocks); kv innermost, online-softmax accumulators
    (Hq x D in fp32) in VMEM scratch — the split-K structure of
    FlashDecoding mapped onto the sequential-grid + scratch idiom.
  * the query positions are scalar-prefetched into SMEM; key positions are
    blocked as (B, 1, L) rows.  Ring-buffer validity and windowing come
    from the absolute-position tile, same convention as the flash kernel,
    and a ragged cache length is padded to whole blocks at position -1.

``paged_decode_attention`` reuses the same online-softmax body but reads
K/V straight out of a global block pool stacked over layers: the layer
index and the per-sequence block table are scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index map resolves
logical kv-block ``ki`` of sequence ``b`` to pool block ``(layer,
table[b, ki])`` before the DMA is issued — no slice, gather or copy of the
pool ever materializes.  Key positions are synthesized from the grid
(``ki * block_size + iota``): gathered index == absolute position, so
causal masking hides the unwritten tail and garbage-block table entries.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.flash_attention import block_and_pad

NEG_INF = -2.0 ** 30


def _online_softmax_block(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, valid,
                          *, num_kv_heads, group, head_dim, softcap, scale):
    """Fold one (bk, Hkv*D) K/V block into every head's accumulators;
    ``valid`` is the (1, bk) key mask shared by all heads."""
    for h in range(num_kv_heads):
        rows = pl.ds(h * group, group)
        lanes = pl.ds(h * head_dim, head_dim)
        q = q_ref[0, rows, :]                       # (G, D)
        k = k_ref[0, :, lanes]                      # (bk, D)
        v = v_ref[0, :, lanes]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                   # (G, bk)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[rows, :]                     # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(p, axis=1,
                                                          keepdims=True)
        acc_ref[rows, :] = acc_ref[rows, :] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[rows, :] = m_new


def _init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _finalize(o_ref, acc_ref, l_ref):
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
        o_ref.dtype)


def _kernel(
    q_pos_ref,                  # scalar-prefetch: (B,) int32
    k_pos_ref,                  # (1, 1, bk) int32
    q_ref,                      # (1, Hq, D)
    k_ref, v_ref,               # (1, bk, Hkv*D)
    o_ref,                      # (1, Hq, D)
    acc_ref, m_ref, l_ref,      # VMEM scratch: (Hq, D), (Hq, 1), (Hq, 1) f32
    *,
    window: int,
    num_kv_blocks: int,
    **body,
):
    b, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        _init(acc_ref, m_ref, l_ref)

    q_pos = q_pos_ref[b]
    k_pos = k_pos_ref[0]                        # (1, bk)
    valid = (k_pos >= 0) & (k_pos <= q_pos)
    if window > 0:
        valid = valid & (q_pos - k_pos < window)

    @pl.when(jnp.any(valid))
    def _():
        _online_softmax_block(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                              valid, **body)

    @pl.when(ki == num_kv_blocks - 1)
    def _():
        _finalize(o_ref, acc_ref, l_ref)


def _scratch(Hq, D):
    return [
        pltpu.VMEM((Hq, D), jnp.float32),
        pltpu.VMEM((Hq, 1), jnp.float32),
        pltpu.VMEM((Hq, 1), jnp.float32),
    ]


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "block_kv", "interpret")
)
def decode_attention(
    q: jax.Array,              # (B, 1, Hq, D)
    k_cache: jax.Array,        # (B, L, Hkv, D)
    v_cache: jax.Array,
    q_positions: jax.Array,    # (B, 1) int32
    k_positions: jax.Array,    # (B, L) int32
    *,
    window: int = 0,
    softcap: float = 0.0,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, S, Hq, D = q.shape
    if S != 1:
        raise ValueError(f"decode kernel is single-token, got {S} queries")
    _, L, Hkv, _ = k_cache.shape
    bk, L_pad = block_and_pad(L, block_kv)
    pad = ((0, 0), (0, L_pad - L), (0, 0))
    kf = jnp.pad(k_cache.reshape(B, L, Hkv * D), pad)
    vf = jnp.pad(v_cache.reshape(B, L, Hkv * D), pad)
    kp = jnp.pad(k_positions.astype(jnp.int32), ((0, 0), (0, L_pad - L)),
                 constant_values=-1)[:, None, :]
    nk = L_pad // bk

    kernel = functools.partial(
        _kernel, window=window, num_kv_blocks=nk, num_kv_heads=Hkv,
        group=Hq // Hkv, head_dim=D, softcap=softcap,
        scale=1.0 / math.sqrt(D),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bk), lambda b, ki, qp: (b, 0, ki)),
            pl.BlockSpec((1, Hq, D), lambda b, ki, qp: (b, 0, 0)),
            pl.BlockSpec((1, bk, Hkv * D), lambda b, ki, qp: (b, ki, 0)),
            pl.BlockSpec((1, bk, Hkv * D), lambda b, ki, qp: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, ki, qp: (b, 0, 0)),
        scratch_shapes=_scratch(Hq, D),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(q_positions.reshape(B).astype(jnp.int32), kp, q.reshape(B, Hq, D),
      kf, vf)
    return out.reshape(B, 1, Hq, D)


def _paged_kernel(
    layer_ref,                  # scalar-prefetch: (1,) int32 pool layer
    bt_ref,                     # scalar-prefetch: (B, nb) int32 block table
    q_pos_ref,                  # scalar-prefetch: (B,) int32
    q_ref,                      # (1, Hq, D)
    k_ref, v_ref,               # (1, bs, Hkv*D) — physical block via index map
    o_ref,                      # (1, Hq, D)
    acc_ref, m_ref, l_ref,      # VMEM scratch: (Hq, D), (Hq, 1), (Hq, 1) f32
    *,
    window: int,
    num_kv_blocks: int,
    block_size: int,
    **body,
):
    del layer_ref, bt_ref  # consumed by the index maps
    b, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        _init(acc_ref, m_ref, l_ref)

    q_pos = q_pos_ref[b]
    k_pos = ki * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    valid = k_pos <= q_pos
    if window > 0:
        valid = valid & (q_pos - k_pos < window)

    @pl.when(jnp.any(valid))
    def _():
        _online_softmax_block(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                              valid, **body)

    @pl.when(ki == num_kv_blocks - 1)
    def _():
        _finalize(o_ref, acc_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "interpret")
)
def paged_decode_attention(
    q: jax.Array,              # (B, 1, Hq, D)
    k_pool: jax.Array,         # (layers, N, bs, Hkv*D) stacked block pools
    v_pool: jax.Array,         # (layers, N, bs, Hkv*D)
    block_tables: jax.Array,   # (B, nb) int32 pool indices
    q_positions: jax.Array,    # (B, 1) int32
    layer: jax.Array,          # int32 scalar: the pools' layer to read
    *,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    B, S, Hq, D = q.shape
    if S != 1:
        raise ValueError(f"decode kernel is single-token, got {S} queries")
    _, _, bs, HD = k_pool.shape
    Hkv = HD // D
    nb = block_tables.shape[1]

    kernel = functools.partial(
        _paged_kernel, window=window, num_kv_blocks=nb, block_size=bs,
        num_kv_heads=Hkv, group=Hq // Hkv, head_dim=D, softcap=softcap,
        scale=1.0 / math.sqrt(D),
    )
    kv_map = lambda b, ki, lyr, bt, qp: (lyr[0], bt[b, ki], 0, 0)
    q_map = lambda b, ki, lyr, bt, qp: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, Hq, D), q_map),
            pl.BlockSpec((None, 1, bs, HD), kv_map),
            pl.BlockSpec((None, 1, bs, HD), kv_map),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), q_map),
        scratch_shapes=_scratch(Hq, D),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), block_tables.astype(jnp.int32),
      q_positions.reshape(B).astype(jnp.int32), q.reshape(B, Hq, D),
      k_pool, v_pool)
    return out.reshape(B, 1, Hq, D)
