"""Pallas TPU flash attention (GQA, causal / sliding-window, soft-cap).

TPU-native design (not a CUDA port):
  * grid = (batch, q_blocks, kv_blocks) — kv is the innermost (sequential)
    dimension so the online-softmax accumulators live in VMEM scratch
    across kv steps; batch/q dims are parallel.
  * heads ride in the lane dimension: Q/K/V are viewed as (B, S, H*D)
    (on the TPU a relayout copy of the model's (B, S, H, D) layout), so
    each block's last two dimensions are (rows, H*D) — whole in the lane
    dim, and rows a multiple of 8 or the whole axis, as the TPU compiler
    requires.  The kernel walks the heads with static lane slices; a
    query head reads its kv head's lanes (GQA).
  * positions are blocked so their tiles need no relayout: query positions
    as (B, S, 1) give a (bq, 1) column, key positions as (B, 1, T) a
    (1, bk) row, and the mask is their broadcast.
  * positions-based masking: causality, ring-buffer validity (pos == -1)
    and sliding windows are all expressed on absolute positions, so the
    same kernel serves training, prefill, and windowed layers.  Ragged
    lengths are padded up to whole blocks with keys at position -1.
  * fully-masked kv blocks are skipped per-block using the loaded position
    tiles (a dynamic analogue of the static causal block-skip).
  * softmax statistics in fp32 regardless of input dtype.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
LANES = 128


def block_and_pad(n: int, target: int):
    """``(block, padded_n)`` for an axis of length ``n`` tiled by blocks of
    at most ``target`` rows: the whole axis when it fits in one block,
    otherwise the largest power-of-two multiple of 128 up to ``target``
    that divides ``n`` rounded up to 128."""
    if n <= target:
        return n, n
    padded = -(-n // LANES) * LANES
    block = target
    while padded % block:
        block //= 2
    return block, padded


def _kernel(
    q_pos_ref, k_pos_ref,          # (1, bq, 1) / (1, 1, bk) int32
    q_ref, k_ref, v_ref,           # (1, bq, Hq*D) / (1, bk, Hkv*D)
    o_ref,                         # (1, bq, Hq*D)
    acc_ref, m_ref, l_ref,         # VMEM scratch: (bq, Hq*D), (Hq, bq, 1) x2
    *,
    causal: bool,
    window: int,
    softcap: float,
    scale: float,
    num_kv_blocks: int,
    num_heads: int,
    group: int,
    head_dim: int,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = q_pos_ref[0]                      # (bq, 1)
    k_pos = k_pos_ref[0]                      # (1, bk)

    # dynamic block-skip: can any query in this tile see any key in that tile?
    visible = jnp.any(k_pos >= 0)
    if causal:
        visible = visible & (
            jnp.max(q_pos) >= jnp.min(jnp.where(k_pos < 0, 2**30, k_pos)))
    if window > 0:
        visible = visible & (jnp.min(q_pos) - jnp.max(k_pos) < window)

    @pl.when(visible)
    def _compute():
        valid = k_pos >= 0                               # (1, bk)
        if causal:
            valid = valid & (q_pos >= k_pos)             # (bq, bk)
        if window > 0:
            valid = valid & (q_pos - k_pos < window)
        for h in range(num_heads):
            qs = pl.ds(h * head_dim, head_dim)
            ks = pl.ds((h // group) * head_dim, head_dim)
            q = q_ref[0, :, qs]                          # (bq, D)
            k = k_ref[0, :, ks]                          # (bk, D)
            v = v_ref[0, :, ks]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                    # (bq, bk)
            if softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_ref[h]                            # (bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:, qs] = acc_ref[:, qs] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        for h in range(num_heads):
            qs = pl.ds(h * head_dim, head_dim)
            l = jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, :, qs] = (acc_ref[:, qs] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_kv",
                     "interpret"),
)
def flash_attention(
    q: jax.Array,              # (B, S, Hq, D)
    k: jax.Array,              # (B, T, Hkv, D)
    v: jax.Array,
    q_positions: jax.Array,    # (B, S) int32
    k_positions: jax.Array,    # (B, T) int32
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_kv: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"query heads {Hq} not a multiple of kv heads {Hkv}")
    bq, S_pad = block_and_pad(S, block_q)
    bk, T_pad = block_and_pad(T, block_kv)
    qf = jnp.pad(q.reshape(B, S, Hq * D), ((0, 0), (0, S_pad - S), (0, 0)))
    kf = jnp.pad(k.reshape(B, T, Hkv * D), ((0, 0), (0, T_pad - T), (0, 0)))
    vf = jnp.pad(v.reshape(B, T, Hkv * D), ((0, 0), (0, T_pad - T), (0, 0)))
    # pad queries and keys sit at position -1: a pad key is never visible,
    # and a pad query row's output is sliced off below
    qp = jnp.pad(q_positions.astype(jnp.int32), ((0, 0), (0, S_pad - S)),
                 constant_values=-1)[:, :, None]
    kp = jnp.pad(k_positions.astype(jnp.int32), ((0, 0), (0, T_pad - T)),
                 constant_values=-1)[:, None, :]
    nq, nk = S_pad // bq, T_pad // bk

    kernel = functools.partial(
        _kernel, causal=causal, window=window, softcap=softcap,
        scale=1.0 / math.sqrt(D), num_kv_blocks=nk, num_heads=Hq,
        group=Hq // Hkv, head_dim=D,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, qi, ki: (b, 0, ki)),
            pl.BlockSpec((1, bq, Hq * D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, Hkv * D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, Hkv * D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Hq * D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S_pad, Hq * D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Hq * D), jnp.float32),
            pltpu.VMEM((Hq, bq, 1), jnp.float32),
            pltpu.VMEM((Hq, bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qp, kp, qf, kf, vf)
    return out[:, :S].reshape(B, S, Hq, D)
