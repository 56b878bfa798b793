"""Kernel dispatch: route hot-spot ops to Pallas TPU kernels or the pure-jnp
reference implementations.

Backend selection:
  * ``auto``   — Pallas on TPU, reference elsewhere (default).
  * ``pallas`` — force Pallas.  Off the TPU the kernels run in interpret
    mode (used by tests); on the TPU they are always compiled.
  * ``xla``    — force the pure-jnp reference.  The multi-pod dry-run uses
    this so ``compiled.cost_analysis()`` sees real HLO FLOPs (a Pallas call
    is an opaque custom-call to XLA's cost model), and ``chip_smoke.py``
    uses it as the on-chip reference the kernels are compared against.

A kernel never falls back to the reference for a shape it cannot take: it
raises.  The reference implementations live in each kernel's ``ref.py``
and are the oracles the Pallas kernels are tested against.

Under a mesh (``sharding.rules.use_mesh``) the attention kernels run once
per device through ``shard_map``: XLA cannot partition a Pallas call, so
each device gets its batch rows and its heads as the sharding rules place
them (``batch`` and ``act_heads``), and runs the kernel on those alone.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import rules

BACKENDS = ("auto", "pallas", "xla")


class _State(threading.local):
    def __init__(self):
        self.backend = "auto"


_STATE = _State()


def set_backend(backend: str) -> None:
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    _STATE.backend = backend


@contextlib.contextmanager
def use_backend(backend: str):
    prev = _STATE.backend
    set_backend(backend)
    try:
        yield
    finally:
        _STATE.backend = prev


def _use_pallas() -> bool:
    if _STATE.backend == "pallas":
        return True
    if _STATE.backend == "xla":
        return False
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Interpret mode exactly when no TPU compiles the kernel."""
    return jax.default_backend() != "tpu"


def _per_device(kernel, q, k, v, rows, *, layer=None):
    """``kernel(q, k, v, *rows)`` once per device of the ambient mesh.

    ``q`` is (B, S, Hq, D) and ``k``/``v`` carry their kv heads at dim 2;
    ``rows`` are per-sequence (B, ·) operands.  Batch rows and heads split
    as the rules shard ``batch`` and ``act_heads``; the kv heads follow the
    query heads (so a device's query heads find their GQA kv head locally),
    and stay whole when they do not divide.

    With ``layer`` given, ``k``/``v`` are paged pools stacked over layers,
    (layers, N, bs, Hkv*D), and the call is ``kernel(q, k, v, *rows,
    layer)``: a pool is indexed by block, not by row, so only its lanes
    split, by whole kv heads as above."""
    if layer is not None:
        rows = (*rows, jnp.asarray(layer, jnp.int32).reshape(1))
    mesh = rules.current_mesh()
    if mesh is None:
        return kernel(q, k, v, *rows)
    logical = ("batch", None, "act_heads", None)
    batch, _, heads, _ = rules.logical_to_pspec(logical, dims=q.shape,
                                                mesh=mesh)
    n_kv = k.shape[-1] // q.shape[-1] if layer is not None else k.shape[2]
    if rules.logical_to_pspec(logical, dims=(1, 1, n_kv, 1),
                              mesh=mesh)[2] != heads:
        heads = None
    q_spec = P(batch, None, heads, None)
    row_specs = (P(batch, None),) * len(rows)
    if layer is None:
        kv_spec = q_spec
    else:
        kv_spec = P(None, None, None, heads)
        row_specs = row_specs[:-1] + (P(None),)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec) + row_specs,
        out_specs=q_spec, check_vma=False)(q, k, v, *rows)


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------

def flash_attention(q, k, v, *, q_positions, k_positions, causal, window=0,
                    softcap=0.0):
    if _use_pallas():
        from repro.kernels.flash_attention import ops
        return _per_device(
            lambda q, k, v, qp, kp: ops.flash_attention(
                q, k, v, q_positions=qp, k_positions=kp, causal=causal,
                window=window, softcap=softcap, interpret=_interpret()),
            q, k, v, (q_positions, k_positions))
    from repro.kernels.flash_attention import ref
    # avoid materializing S x T fp32 scores for long sequences on the XLA
    # path (threshold lowered 4096^2 -> 2048^2 in EXPERIMENTS §Perf llava
    # iteration 2: the naive path's S x S fp32 score tensors dominated the
    # train_4k memory roofline term ~10x)
    if q.shape[1] * k.shape[1] > 2048 * 2048:
        return ref.attention_chunked(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            causal=causal, window=window, softcap=softcap,
        )
    return ref.attention(
        q, k, v, q_positions=q_positions, k_positions=k_positions,
        causal=causal, window=window, softcap=softcap,
    )


def decode_attention(q, k_cache, v_cache, *, q_positions, k_positions,
                     window=0, softcap=0.0):
    if _use_pallas():
        from repro.kernels.decode_attention import ops
        return _per_device(
            lambda q, k, v, qp, kp: ops.decode_attention(
                q, k, v, q_positions=qp, k_positions=kp, window=window,
                softcap=softcap, interpret=_interpret()),
            q, k_cache, v_cache, (q_positions, k_positions))
    from repro.kernels.decode_attention import ref
    return ref.decode_attention(
        q, k_cache, v_cache, q_positions=q_positions, k_positions=k_positions,
        window=window, softcap=softcap,
    )


def paged_decode_attention(q, k_pool, v_pool, *, block_tables, q_positions,
                           layer, window=0, softcap=0.0):
    """Decode attention over layer ``layer`` of paged (block-pool) KV
    caches stacked over layers, (layers, N, bs, Hkv*D)."""
    if _use_pallas():
        from repro.kernels.decode_attention import ops
        return _per_device(
            lambda q, k, v, bt, qp, lyr: ops.paged_decode_attention(
                q, k, v, block_tables=bt, q_positions=qp, layer=lyr,
                window=window, softcap=softcap, interpret=_interpret()),
            q, k_pool, v_pool, (block_tables, q_positions), layer=layer)
    from repro.kernels.decode_attention import ref
    return ref.paged_decode_attention(
        q, k_pool, v_pool, block_tables=block_tables,
        q_positions=q_positions, layer=layer, window=window, softcap=softcap,
    )


def linear_recurrence(a, b, h0):
    """h_t = a_t * h_{t-1} + b_t over axis 1.  a,b: (B,S,W) fp32; h0: (B,W)."""
    if _use_pallas():
        from repro.kernels.linear_recurrence import ops
        return ops.linear_recurrence(a, b, h0, interpret=_interpret())
    from repro.kernels.linear_recurrence import ref
    return ref.linear_recurrence(a, b, h0)


def rmsnorm(x, scale, eps=1e-6):
    if _use_pallas():
        from repro.kernels.rmsnorm import ops
        return ops.rmsnorm(x, scale, eps=eps, interpret=_interpret())
    from repro.kernels.rmsnorm import ref
    return ref.rmsnorm(x, scale, eps=eps)
