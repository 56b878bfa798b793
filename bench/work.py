"""Work the real tokens need, counted from the model's published sizes.

Every count here comes from token counts and positions that the benchmark
knows itself (prompt lengths, and the index of each token it was served),
never from the padded shapes the program hands its kernels: a later kernel
that stops computing padding cannot then read above its roofline, and the
padding computed today shows as a low share.

Conventions: a token at position ``p`` attends to ``p + 1`` keys (itself
and everything before it).  One multiply-add is two FLOPs.  Attention
FLOPs are QK^T and PV: ``4 * heads * head_dim * keys`` per token per layer.
The KV cache holds bf16 keys and values.  No JAX here.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict

PEAKS = Path(__file__).resolve().parent / "peaks.json"
KV_BYTES = 2      # bf16 keys and values
ACT_BYTES = 2     # bf16 queries and attention outputs


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool

    @classmethod
    def from_config(cls, c: Dict) -> "Sizes":
        """From a ``bench/configs/<name>.json`` (Hugging Face key names)."""
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["hidden_size"] // c["num_attention_heads"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   tied=bool(c["tie_word_embeddings"]))


def nonembed_params(s: Sizes) -> int:
    """Parameters a token's forward pass multiplies, without the embedding
    table and the output head: Q/K/V/O projections with the Q/K/V biases,
    the gated MLP, and the RMSNorm weights."""
    q = s.heads * s.head_dim
    kv = s.kv_heads * s.head_dim
    attn = s.d_model * (q + 2 * kv) + q * s.d_model + (q + 2 * kv)
    mlp = 3 * s.d_model * s.d_ff
    return s.layers * (attn + mlp + 2 * s.d_model) + s.d_model


def attn_flops(s: Sizes, keys: int) -> int:
    """QK^T and PV of one token over ``keys`` keys, all layers."""
    return 4 * s.layers * s.heads * s.head_dim * keys


def head_flops(s: Sizes) -> int:
    """The output head for one sampled position."""
    return 2 * s.d_model * s.vocab


def token_flops(s: Sizes, position: int, sampled: bool) -> int:
    """Model FLOPs of one token at ``position``: the weights, attention
    over its ``position + 1`` keys, and the head where it is sampled."""
    return (2 * nonembed_params(s) + attn_flops(s, position + 1)
            + (head_flops(s) if sampled else 0))


def prompt_flops(s: Sizes, n: int) -> int:
    """Model FLOPs of prefilling an ``n``-token prompt: every position,
    with the head at the last (which samples the first output token)."""
    return (n * 2 * nonembed_params(s) + prompt_attn_flops(s, n)
            + head_flops(s))


def prompt_attn_flops(s: Sizes, n: int) -> int:
    """Causal attention FLOPs of an ``n``-token prompt: position ``p``
    attends to ``p + 1`` keys."""
    return attn_flops(s, n * (n + 1) // 2)


def prompt_attn_bytes(s: Sizes, n: int) -> int:
    """Least HBM traffic of that attention: each real token's q, k, v and
    output read or written once."""
    per_tok = (2 * s.heads + 2 * s.kv_heads) * s.head_dim * ACT_BYTES
    return s.layers * n * per_tok


def decode_position(prompt_len: int, index: int) -> int:
    """Position of the token fed to the decode step that emits output
    token ``index`` (>= 1): the previous output token, written at
    ``prompt_len + index - 1``."""
    return prompt_len + index - 1


def decode_kv_bytes(s: Sizes, position: int) -> int:
    """Least HBM traffic of one decode row's attention at ``position``:
    the ``position + 1`` cached keys and values it reads, plus its query
    and output."""
    kv = (position + 1) * 2 * s.kv_heads * s.head_dim * KV_BYTES
    qo = 2 * s.heads * s.head_dim * ACT_BYTES
    return s.layers * (kv + qo)


def peaks(device_kind: str) -> Dict:
    """The chip's published peaks; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Dict):
    """(share of the roofline in %, the bound that applies) for work of
    ``flops`` and ``nbytes`` that took ``seconds`` of kernel time."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
