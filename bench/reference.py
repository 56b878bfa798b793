"""The plain reference: a Qwen2-style decoder in float32 ``jax.numpy``.

It imports nothing of the program and takes only the weight tree that
``bench/weights.py`` made from the seed (laid out as that file says).  Its
equations are the published architecture's: pre-RMSNorm blocks, rotary
positions on the first and second half of each head (base
``rope_theta``), grouped-query causal softmax attention with biases on
Q, K and V, a SiLU-gated MLP, a final RMSNorm and the embedding table as
the output head where it is tied.  Every matrix product runs at
``highest`` precision, so float32 means float32 on the TPU too.

It runs one sequence at a time and one layer at a time, on a sequence
padded at its end to a fixed length (causal attention leaves the real
positions unchanged), so that it fits beside nothing and compiles once.

``quant="fp8"`` is the control: the same forward with every matrix
product's operands rounded to float8 e4m3 under a per-tensor scale, the
precision step below the bf16 the configuration states.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 512  # output-head rows per call


@dataclasses.dataclass(frozen=True)
class Arch:
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float

    @classmethod
    def from_config(cls, c: Dict) -> "Arch":
        return cls(heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["hidden_size"] // c["num_attention_heads"],
                   eps=float(c["rms_norm_eps"]),
                   theta=float(c["rope_theta"]))


def _q(x: jax.Array, quant: str) -> jax.Array:
    """Round a matrix-product operand to the control's precision."""
    x = x.astype(jnp.float32)
    if quant == "none":
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, offset, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + offset.astype(jnp.float32))


def _rope(x, theta):
    """x (T, H, D); rotate pairs (i, i + D/2) by position * theta^(-2i/D)."""
    T, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, p, a: Arch, quant: str):
    """One decoder layer over a whole (T, d) sequence."""
    mm = functools.partial(jnp.einsum, precision="highest")
    T = x.shape[0]
    h = _q(_rms(x, p["norm1"]["scale"], a.eps), quant)
    at = p["attn"]
    q = mm("td,dhk->thk", h, _q(at["wq"], quant)) + at["bq"].astype(
        jnp.float32)
    k = mm("td,dhk->thk", h, _q(at["wk"], quant)) + at["bk"].astype(
        jnp.float32)
    v = mm("td,dhk->thk", h, _q(at["wv"], quant)) + at["bv"].astype(
        jnp.float32)
    q, k = _rope(q, a.theta), _rope(k, a.theta)
    g = a.heads // a.kv_heads
    qg = q.reshape(T, a.kv_heads, g, a.head_dim)
    s = mm("tkgd,skd->kgts", _q(qg, quant), _q(k, quant)) / jnp.sqrt(
        jnp.float32(a.head_dim))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = mm("kgts,skd->tkgd", _q(w, quant), _q(v, quant)).reshape(
        T, a.heads, a.head_dim)
    x = x + mm("thk,hkd->td", _q(o, quant), _q(at["wo"], quant))
    h = _q(_rms(x, p["norm2"]["scale"], a.eps), quant)
    m = p["mlp"]
    up = (jax.nn.silu(mm("td,df->tf", h, _q(m["wg"], quant)))
          * mm("td,df->tf", h, _q(m["wu"], quant)))
    return x + mm("tf,fd->td", _q(up, quant), _q(m["wd"], quant))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, final, table, eps: float, quant: str):
    """Logits of a block of rows: final RMSNorm, then the output head."""
    h = _q(_rms(x, final, eps), quant)
    return jnp.einsum("td,vd->tv", h, _q(table, quant), precision="highest")


@jax.jit
def _gap(ref, tokens):
    """Per row: the reference's best logit minus its logit of ``tokens``."""
    got = jnp.take_along_axis(ref, tokens[:, None], axis=1)[:, 0]
    return jnp.max(ref, axis=1) - got


def _hidden(weights, a: Arch, ids: np.ndarray, length: int, quant: str):
    layers = weights["decoder"]["groups"]["0"]
    n_layers = layers["norm1"]["scale"].shape[0]
    padded = np.zeros(length, np.int32)
    padded[:len(ids)] = ids
    x = jnp.take(weights["embed"]["table"], jnp.asarray(padded),
                 axis=0).astype(jnp.float32)
    for i in range(n_layers):
        x = _layer(x, jax.tree.map(lambda t: t[i], layers), a, quant)
    return x


def served_gaps(weights, cfg: Dict, prompt: Sequence[int],
                served: Sequence[int], length: int,
                quant: str = "none") -> np.ndarray:
    """For each served token, how far its logit lies below the best logit
    of the float32 reference at that position, teacher-forced on the
    prompt and the served tokens.  Greedy serving in exact arithmetic
    reads 0 everywhere.

    With ``quant="fp8"`` (the control) the token compared at each position
    is the one the fp8 forward puts first, and the gap is still read from
    the float32 reference."""
    a = Arch.from_config(cfg)
    ids = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])[:-1]
    if len(ids) > length:
        raise ValueError(f"sequence of {len(ids)} exceeds {length}")
    table = weights.get("lm_head", weights["embed"])["table"]
    final = weights["decoder"]["final_norm"]["scale"]
    with jax.default_matmul_precision("highest"):
        x = _hidden(weights, a, ids, length, "none")
        xc = _hidden(weights, a, ids, length, quant) if quant != "none" \
            else None
        first, n = len(prompt) - 1, len(served)
        x = jnp.pad(x, ((0, ROWS), (0, 0)))
        if xc is not None:
            xc = jnp.pad(xc, ((0, ROWS), (0, 0)))
        out: List[np.ndarray] = []
        for lo in range(first, first + n, ROWS):
            ref = _head(jax.lax.dynamic_slice_in_dim(x, lo, ROWS), final,
                        table, a.eps, "none")
            k = min(ROWS, first + n - lo)
            tok = np.zeros(ROWS, np.int32)
            if xc is None:
                tok[:k] = np.asarray(served[lo - first:lo - first + k])
            else:
                tok = np.asarray(jnp.argmax(_head(
                    jax.lax.dynamic_slice_in_dim(xc, lo, ROWS), final,
                    table, a.eps, quant), axis=1), np.int32)
            out.append(np.asarray(_gap(ref, jnp.asarray(tok)))[:k])
    return np.concatenate(out)


def worst_gap(weights, cfg: Dict, sample: Sequence[Tuple], length: int,
              quant: str = "none") -> float:
    """The widest gap over every served token of ``sample``, a list of
    (prompt, served tokens)."""
    return float(max(served_gaps(weights, cfg, p, s, length, quant).max()
                     for p, s in sample))
