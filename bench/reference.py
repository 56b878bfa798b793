"""The plain reference's shared part: the served-token gap and its control.

A family (``bench/arch/<model_type>.py``) brings its model's forward in
float32 ``jax.numpy``, which imports nothing of the program and takes only
the weight tree the family made from the seed; this file runs it over a
served sequence and reads, for each served token, how far its logit lies
below the reference's best.  Every matrix product runs at ``highest``
precision, so float32 means float32 on the TPU too.

A forward runs one sequence at a time and one layer at a time, on a
sequence padded at its end to a fixed length (causal attention leaves the
real positions unchanged), so that it fits beside nothing and compiles
once.  Weights sharded over a cell's chips are gathered onto the first
device one layer at a time (``one_device``).

``quant="fp8"`` is the control: the same forward with every matrix
product's operands rounded to float8 e4m3 under a per-tensor scale
(``q``), the precision step below the bf16 the configuration states.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 512  # output-head rows per call


def one_device(tree):
    """``tree`` on the first device: a no-op for arrays already there,
    a gather for arrays sharded over a cell's chips."""
    return jax.device_put(tree, jax.devices()[0])


def q(x: jax.Array, quant: str) -> jax.Array:
    """Round a matrix-product operand to the control's precision."""
    x = x.astype(jnp.float32)
    if quant == "none":
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rms(x, offset, eps):
    """RMSNorm with the program's parameterisation: weight ``1 + offset``."""
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + offset.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, final, table, eps: float, quant: str):
    """Logits of a block of rows: final RMSNorm, then the output head."""
    h = q(rms(x, final, eps), quant)
    return jnp.einsum("td,vd->tv", h, q(table, quant), precision="highest")


@jax.jit
def _gap(ref, tokens):
    """Per row: the reference's best logit minus its logit of ``tokens``."""
    got = jnp.take_along_axis(ref, tokens[:, None], axis=1)[:, 0]
    return jnp.max(ref, axis=1) - got


def served_gaps(hidden: Callable, final, table, eps: float,
                prompt: Sequence[int], served: Sequence[int], length: int,
                quant: str = "none") -> np.ndarray:
    """For each served token, how far its logit lies below the best logit
    of the float32 reference at that position, teacher-forced on the
    prompt and the served tokens.  Greedy serving in exact arithmetic
    reads 0 everywhere.

    ``hidden(ids, quant)`` is the family's forward over ``length`` token
    ids to the last hidden state, ``(length, d)`` in float32; ``final``
    (the final norm's offset), ``table`` (the output head) and ``eps``
    give the logits.  With ``quant="fp8"`` (the control) the token
    compared at each position is the one the fp8 forward puts first, and
    the gap is still read from the float32 reference."""
    ids = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])[:-1]
    if len(ids) > length:
        raise ValueError(f"sequence of {len(ids)} exceeds {length}")
    padded = np.zeros(length, np.int32)
    padded[:len(ids)] = ids
    final, table = one_device(final), one_device(table)
    with jax.default_matmul_precision("highest"):
        x = hidden(padded, "none")
        xc = hidden(padded, quant) if quant != "none" else None
        first, n = len(prompt) - 1, len(served)
        x = jnp.pad(x, ((0, ROWS), (0, 0)))
        if xc is not None:
            xc = jnp.pad(xc, ((0, ROWS), (0, 0)))
        out: List[np.ndarray] = []
        for lo in range(first, first + n, ROWS):
            ref = _head(jax.lax.dynamic_slice_in_dim(x, lo, ROWS), final,
                        table, eps, "none")
            k = min(ROWS, first + n - lo)
            tok = np.zeros(ROWS, np.int32)
            if xc is None:
                tok[:k] = np.asarray(served[lo - first:lo - first + k])
            else:
                tok = np.asarray(jnp.argmax(_head(
                    jax.lax.dynamic_slice_in_dim(xc, lo, ROWS), final,
                    table, eps, quant), axis=1), np.int32)
            out.append(np.asarray(_gap(ref, jnp.asarray(tok)))[:k])
    return np.concatenate(out)
