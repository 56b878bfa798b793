"""The Qwen2 family (``model_type`` "qwen2"): Qwen1.5 and Qwen2/2.5 dense
decoders, as the harness reads them (``bench/arch/__init__.py``).

A configuration file holds the published ``config.json``'s keys, plus
``arch`` (the program's registry entry whose sizes it replaces) and
``architecture.qkv_bias``.  ``head_dim`` is the file's where it gives one,
else ``hidden_size / num_attention_heads``.

Work counts: attention FLOPs are QK^T and PV, ``4 * heads * head_dim *
keys`` per token per layer; a forward pass multiplies every parameter but
the embedding table's and the output head's once, two FLOPs each.

Weights, made on the device in one jitted call from the seed and laid out
as the program's parameter tree for a dense decoder whose layers are
stacked on a leading axis:

    embed.table                      (vocab, d)
    decoder.groups.0.norm1.scale     (L, d)        RMSNorm weight minus one
    decoder.groups.0.attn.wq         (L, d, H, D)  bq (L, H, D)
    decoder.groups.0.attn.wk / wv    (L, d, Hkv, D) bk / bv (L, Hkv, D)
    decoder.groups.0.attn.wo         (L, H, D, d)
    decoder.groups.0.norm2.scale     (L, d)
    decoder.groups.0.mlp.wg / wu     (L, d, F)     wd (L, F, d)
    decoder.final_norm.scale         (d,)
    lm_head.table                    (vocab, d)    only when not tied

The norm entries hold the RMSNorm weight minus one: the program multiplies
by ``1 + scale``.  Matrices are drawn with unit variance over their fan-in,
the embedding with the configs' ``initializer_range``, biases and norm
offsets small and nonzero so that every path is exercised, and
everything is cast to the served dtype on the device.

The reference's equations are the published architecture's: pre-RMSNorm
blocks, rotary positions on the first and second half of each head (base
``rope_theta``), grouped-query causal softmax attention with biases on
Q, K and V, a SiLU-gated MLP, a final RMSNorm and the embedding table as
the output head where it is tied.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.weights import key_for
from bench.work import ACT_BYTES, KV_BYTES

# the published configs' initializer_range: with it the embedding is small
# beside what the layers add, so the last hidden state is not the input
# token's own embedding (which, tied to the output head, would make every
# greedy token repeat its input by a wide margin)
EMBED_STD = 0.02


def _head_dim(c: Dict) -> int:
    return int(c.get("head_dim")
               or c["hidden_size"] // c["num_attention_heads"])


def program_config(c: Dict):
    """The program's ``ModelConfig``: the registry entry of the file's
    ``arch``, with every size taken from the file."""
    from repro.configs import get_config

    return get_config(c["arch"]).replace(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=_head_dim(c),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        qkv_bias=bool(c["architecture"]["qkv_bias"]),
        dtype=c["torch_dtype"], param_dtype=c["torch_dtype"])


# -- work counts --------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Work:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool

    @property
    def nonembed_params(self) -> int:
        """Parameters a token's forward pass multiplies, without the
        embedding table and the output head: Q/K/V/O projections with the
        Q/K/V biases, the gated MLP, and the RMSNorm weights."""
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        attn = self.d_model * (q + 2 * kv) + q * self.d_model + (q + 2 * kv)
        mlp = 3 * self.d_model * self.d_ff
        return self.layers * (attn + mlp + 2 * self.d_model) + self.d_model

    @property
    def kv_bytes_per_token(self) -> int:
        """Cached keys and values of one token over all layers."""
        return self.layers * 2 * self.kv_heads * self.head_dim * KV_BYTES

    def attn_flops(self, keys: int) -> int:
        """QK^T and PV of one token over ``keys`` keys, all layers."""
        return 4 * self.layers * self.heads * self.head_dim * keys

    @property
    def head_flops(self) -> int:
        """The output head for one sampled position."""
        return 2 * self.d_model * self.vocab

    def token_flops(self, position: int, sampled: bool) -> int:
        """Model FLOPs of one token at ``position``: the weights, attention
        over its ``position + 1`` keys, and the head where it is sampled."""
        return (2 * self.nonembed_params + self.attn_flops(position + 1)
                + (self.head_flops if sampled else 0))

    def prompt_flops(self, n: int) -> int:
        """Model FLOPs of prefilling an ``n``-token prompt: every position,
        with the head at the last (which samples the first output token)."""
        return (n * 2 * self.nonembed_params + self.prompt_attn_flops(n)
                + self.head_flops)

    def prompt_attn_flops(self, n: int) -> int:
        """Causal attention FLOPs of an ``n``-token prompt: position ``p``
        attends to ``p + 1`` keys."""
        return self.attn_flops(n * (n + 1) // 2)

    def prompt_attn_bytes(self, n: int) -> int:
        """Least HBM traffic of that attention: each real token's q, k, v
        and output read or written once."""
        per_tok = (2 * self.heads + 2 * self.kv_heads) * self.head_dim
        return self.layers * n * per_tok * ACT_BYTES

    def decode_attn_flops(self, position: int) -> int:
        """QK^T and PV of one decode row at ``position``."""
        return self.attn_flops(position + 1)

    def decode_kv_bytes(self, position: int) -> int:
        """Least HBM traffic of one decode row's attention at ``position``:
        the ``position + 1`` cached keys and values it reads, plus its
        query and output."""
        qo = 2 * self.heads * self.head_dim * ACT_BYTES
        return (position + 1) * self.kv_bytes_per_token + self.layers * qo


def work(c: Dict) -> Work:
    return Work(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"], head_dim=_head_dim(c),
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                tied=bool(c["tie_word_embeddings"]))


# -- weights ------------------------------------------------------------------

def _tree(s: Work, key: jax.Array, dtype) -> dict:
    L, d, H, K, D, F = (s.layers, s.d_model, s.heads, s.kv_heads,
                        s.head_dim, s.d_ff)
    keys = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        x = jax.random.normal(next(keys), shape, jnp.float32) * scale
        return x.astype(dtype)

    layer = {
        "norm1": {"scale": normal((L, d), 0.1)},
        "attn": {
            "wq": normal((L, d, H, D), d ** -0.5),
            "wk": normal((L, d, K, D), d ** -0.5),
            "wv": normal((L, d, K, D), d ** -0.5),
            "wo": normal((L, H, D, d), (H * D) ** -0.5),
            "bq": normal((L, H, D), 0.1),
            "bk": normal((L, K, D), 0.1),
            "bv": normal((L, K, D), 0.1),
        },
        "norm2": {"scale": normal((L, d), 0.1)},
        "mlp": {
            "wg": normal((L, d, F), d ** -0.5),
            "wu": normal((L, d, F), d ** -0.5),
            "wd": normal((L, F, d), F ** -0.5),
        },
    }
    tree = {
        "embed": {"table": normal((s.vocab, d), EMBED_STD)},
        "decoder": {"groups": {"0": layer},
                    "final_norm": {"scale": normal((d,), 0.1)}},
    }
    if not s.tied:
        tree["lm_head"] = {"table": normal((s.vocab, d), EMBED_STD)}
    return tree


def _make(s: Work, key: jax.Array, dtype: str) -> dict:
    return _tree(s, key, jnp.dtype(dtype))


def weights(c: Dict, seed: int, dtype: str = "bfloat16",
            shardings=None) -> dict:
    """The whole tree in ``dtype``: on the default device, or placed by
    ``shardings``."""
    placed = {} if shardings is None else {"out_shardings": shardings}
    make = jax.jit(_make, static_argnums=(0, 2), **placed)
    return make(work(c), key_for(seed), dtype)


# -- the reference ------------------------------------------------------------

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
                   kv_heads=c["num_key_value_heads"], head_dim=_head_dim(c),
                   eps=float(c["rms_norm_eps"]),
                   theta=float(c["rope_theta"]))


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
    _q, _rms = reference.q, reference.rms
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


def hidden(weights, a: Arch, ids: np.ndarray, quant: str):
    """The last hidden state (T, d) in float32 over the token ids ``ids``,
    one layer at a time on the first device."""
    layers = weights["decoder"]["groups"]["0"]
    n_layers = layers["norm1"]["scale"].shape[0]
    table = reference.one_device(weights["embed"]["table"])
    x = jnp.take(table, jnp.asarray(ids), axis=0).astype(jnp.float32)
    for i in range(n_layers):
        p = reference.one_device(jax.tree.map(lambda t: t[i], layers))
        x = _layer(x, p, a, quant)
    return x


def served_gaps(weights, c: Dict, prompt: Sequence[int],
                served: Sequence[int], length: int,
                quant: str = "none") -> np.ndarray:
    """``reference.served_gaps`` through this family's forward."""
    a = Arch.from_config(c)
    return reference.served_gaps(
        functools.partial(hidden, weights, a),
        weights["decoder"]["final_norm"]["scale"],
        weights.get("lm_head", weights["embed"])["table"], a.eps,
        prompt, served, length, quant)


def worst_gap(weights, c: Dict, sample: Sequence[Tuple], length: int,
              quant: str = "none") -> float:
    """The widest gap over every served token of ``sample``."""
    return float(max(served_gaps(weights, c, p, s, length, quant).max()
                     for p, s in sample))
