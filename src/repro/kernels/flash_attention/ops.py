"""Jit wrapper for the flash-attention kernel.

Forward runs the Pallas kernel; backward differentiates the reference
implementation (numerically identical math) via ``custom_vjp`` — the
training path stays end-to-end differentiable with the kernel enabled.
A dedicated backward kernel is a tracked perf-iteration item.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.flash_attention import (
    flash_attention as _pallas_fwd,
)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8)
)
def _attn(q, k, v, q_positions, k_positions, causal, window, softcap, interpret):
    return _pallas_fwd(
        q, k, v, q_positions, k_positions,
        causal=causal, window=window, softcap=softcap, interpret=interpret,
    )


def _attn_fwd(q, k, v, q_positions, k_positions, causal, window, softcap, interpret):
    out = _attn(q, k, v, q_positions, k_positions, causal, window, softcap, interpret)
    return out, (q, k, v, q_positions, k_positions)


def _attn_bwd(causal, window, softcap, interpret, res, g):
    q, k, v, q_positions, k_positions = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention(
            q_, k_, v_, q_positions=q_positions, k_positions=k_positions,
            causal=causal, window=window, softcap=softcap,
        ),
        q, k, v,
    )
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None


_attn.defvjp(_attn_fwd, _attn_bwd)


def flash_attention(q, k, v, *, q_positions, k_positions, causal, window=0,
                    softcap=0.0, interpret=False):
    return _attn(q, k, v, q_positions, k_positions, causal, window, softcap,
                 interpret)
