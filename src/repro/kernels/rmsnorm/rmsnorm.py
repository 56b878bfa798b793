"""Pallas TPU fused RMSNorm (gemma-style ``x * rsqrt(ms) * (1 + scale)``).

One HBM round-trip: rows stream through VMEM in (block_rows, d) tiles, the
mean-square reduction and the scale multiply fuse into a single kernel
(XLA emits reduce + broadcast-multiply as separate fusions with an extra
intermediate when the row doesn't fit a single fusion).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)            # (br, d)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * (1.0 + s_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(
    x: jax.Array,        # (..., d)
    scale: jax.Array,    # (d,)
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    xf = x.reshape(rows, d)
    br = min(block_rows, rows)
    while rows % br:
        br -= 1
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(xf, scale)
    return out.reshape(orig_shape)
