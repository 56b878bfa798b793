"""Pallas TPU linear-recurrence kernel: h_t = a_t * h_{t-1} + b_t.

Used by the RG-LRU (RecurrentGemma) recurrent branch.  TPU-native design:

  * grid = (batch, width_blocks, seq_chunks) — seq innermost/sequential; the
    running state h (one (bw,) fp32 vector per width block) persists in VMEM
    scratch across chunk steps.
  * within a chunk the scan is computed in log2(bs) *vectorized* doubling
    passes over the (bs, bw) tile (Blelloch-style inclusive scan on the
    (a, b) semigroup), not a length-bs sequential loop — the VPU sees wide
    elementwise ops only.
  * the chunk is then closed with h_chunk = A ⊙ h_carry + B where A is the
    inclusive decay product, giving the cross-chunk recurrence.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, o_ref, h_ref, *, block_s: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    A = a_ref[0].astype(jnp.float32)          # (bs, bw)
    B = b_ref[0].astype(jnp.float32)
    # inclusive scan on the linear-recurrence semigroup via doubling:
    # (A1,B1) o (A2,B2) = (A1*A2, A2*B1 + B2), combining t with t-2^i.
    steps = max(1, int(math.ceil(math.log2(block_s))))
    for i in range(steps):
        shift = 1 << i
        if shift >= block_s:
            break
        A_prev = jnp.concatenate(
            [jnp.ones((shift, A.shape[1]), A.dtype), A[:-shift]], axis=0)
        B_prev = jnp.concatenate(
            [jnp.zeros((shift, B.shape[1]), B.dtype), B[:-shift]], axis=0)
        B = A * B_prev + B
        A = A * A_prev
    h = A * h_ref[...][None, :] + B           # fold in the carry
    o_ref[0] = h.astype(o_ref.dtype)
    h_ref[...] = h[-1]


@functools.partial(jax.jit, static_argnames=("block_s", "block_w", "interpret"))
def linear_recurrence(
    a: jax.Array,      # (B, S, W) decay in (0, 1]
    b: jax.Array,      # (B, S, W) input
    h0: jax.Array,     # (B, W) initial state
    *,
    block_s: int = 256,
    block_w: int = 512,
    interpret: bool = False,
) -> jax.Array:
    Bb, S, W = a.shape
    bs = min(block_s, S)
    bw = min(block_w, W)
    assert S % bs == 0 and W % bw == 0, (S, bs, W, bw)
    grid = (Bb, W // bw, S // bs)
    kernel = functools.partial(_kernel, block_s=bs)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, bw), lambda bi, wi, si: (bi, wi)),
        ],
        out_specs=pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
        out_shape=jax.ShapeDtypeStruct((Bb, S, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bw,), jnp.float32)],
        interpret=interpret,
        name="linear_recurrence",
    )(a, b, h0)
