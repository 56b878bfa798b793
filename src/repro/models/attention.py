"""Grouped-query attention (train / prefill / decode), sliding-window and
cross-attention variants.

The numeric core is routed through ``repro.kernels.dispatch`` so the Pallas
flash kernels can take over on TPU while the pure-jnp reference (which is the
kernels' oracle) runs everywhere else and is what the multi-pod dry-run
lowers (XLA cost analysis needs real HLO, not an opaque custom call).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.models import cache as cache_lib
from repro.models.config import ModelConfig
from repro.models.layers import Maker, P, apply_rope, shard

NEG_INF = -2.0 ** 30  # large-but-finite; avoids NaN from all-masked rows


def make_attention(mk: Maker, cfg: ModelConfig, cross: bool = False) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    p = {
        "wq": mk.normal((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": mk.normal((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": mk.normal((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": mk.normal((h, hd, d), ("heads", "head_dim", "embed"),
                        scale=1.0 / math.sqrt(h * hd)),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = mk.zeros((h, hd), ("heads", "head_dim"))
        p["bk"] = mk.zeros((kv, hd), ("kv_heads", "head_dim"))
        p["bv"] = mk.zeros((kv, hd), ("kv_heads", "head_dim"))
    return p


def _project_q(p, x, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    return shard(q, "batch", None, "act_heads", None)


def _project_kv(p, x, cfg: ModelConfig):
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    return shard(k, "batch", None, "act_kv", None), shard(v, "batch", None, "act_kv", None)


def _out_proj(p, o):
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return shard(y, "batch", None, "act_embed")


def sdpa(
    q: jax.Array,          # (B, S, Hq, D)
    k: jax.Array,          # (B, T, Hkv, D)
    v: jax.Array,          # (B, T, Hkv, D)
    *,
    q_positions: jax.Array,    # (B, S) int32
    k_positions: jax.Array,    # (B, T) int32; -1 marks invalid (unfilled) slots
    causal: bool,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """Masked grouped-query attention, fp32 softmax.  Pure-jnp reference."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    scores = jnp.einsum(
        "bshgd,bthd->bhgst", qg, k, preferred_element_type=jnp.float32
    ) / math.sqrt(D)
    if softcap > 0.0:
        scores = jnp.tanh(scores / softcap) * softcap
    valid = (k_positions >= 0)[:, None, None, None, :]
    if causal:
        valid = valid & (
            q_positions[:, None, None, :, None] >= k_positions[:, None, None, None, :]
        )
    if window > 0:
        valid = valid & (
            q_positions[:, None, None, :, None] - k_positions[:, None, None, None, :]
            < window
        )
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgst,bthd->bshgd", probs.astype(v.dtype), v)
    return o.reshape(B, S, Hq, D)


def apply_attention_train(
    p: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
) -> jax.Array:
    """Full-sequence attention (training / encoder / prefill math)."""
    q = _project_q(p, x, cfg)
    k, v = _project_kv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = dispatch.flash_attention(
        q, k, v,
        q_positions=positions, k_positions=positions,
        causal=causal, window=window, softcap=cfg.logit_softcap,
    )
    return _out_proj(p, o)


def apply_attention_prefill(
    p: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    kv_cache: Dict,
    *,
    window: int = 0,
    block_tables: Optional[jax.Array] = None,
    layer=0,
) -> Tuple[jax.Array, Dict]:
    """Causal attention over the prompt; returns output + filled KV cache.

    The attention math is layout-independent (the prompt is self-contained);
    only the cache write differs: paged entries (``kp`` in the dict) scatter
    K/V into pool blocks of ``layer`` (the index into the stacked pools)
    through ``block_tables``, contiguous/ring entries take the dense fill.
    """
    q = _project_q(p, x, cfg)
    k, v = _project_kv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = dispatch.flash_attention(
        q, k, v,
        q_positions=positions, k_positions=positions,
        causal=True, window=window, softcap=cfg.logit_softcap,
    )
    if "kp" in kv_cache:
        kv_cache = cache_lib.fill_paged_cache(kv_cache, k, v, positions,
                                              block_tables, layer)
    else:
        kv_cache = cache_lib.fill_attn_cache(kv_cache, k, v, positions)
    return _out_proj(p, o), kv_cache


def apply_attention_prefill_chunk(
    p: Dict,
    x: jax.Array,            # (B, C, d) one prompt chunk
    cfg: ModelConfig,
    positions: jax.Array,    # (B, C) absolute positions start..start+C-1
    kv_cache: Dict,
    *,
    window: int = 0,
    block_tables: Optional[jax.Array] = None,
    valid: Optional[jax.Array] = None,
    overwrite_from: Optional[jax.Array] = None,
    layer=0,
) -> Tuple[jax.Array, Dict]:
    """Chunked prefill: the chunk attends to every cached chunk 0..N-1 plus
    itself (causally), then its K/V is appended for chunks N+1.. and decode.

    Contiguous/ring caches attend over (cache-before-append ++ chunk) so a
    chunk longer than a sliding window still sees its own early keys (the
    ring would evict them during the append).  Paged caches append first
    and attend over the gathered pool, where index == absolute position.

    ``valid`` (B, C) bool marks each row's real tokens when ragged per-slot
    chunks are packed into one static-width batch (the unified mixed step):
    pad columns write nothing (paged: routed to the garbage block, whose
    logical positions are acausal; contiguous: key positions forced to -1)
    and their query outputs are garbage the caller discards.

    ``overwrite_from`` (B,) int32, when given, hides *cached* contiguous
    entries at positions >= the row's value from the attention read.  The
    speculative verify step re-writes positions its previous window already
    wrote (rejected draft suffixes are never physically rolled back): the
    stale entries share the chunk's own positions, and without the mask the
    contiguous branch — which attends over cache-before-append ++ chunk —
    would both attend to garbage and double-count the overlap.  The paged
    branch needs no mask: it appends *first*, so the overlap is overwritten
    in the pool before the gather, and stale positions beyond the window
    exceed every query position (causal masking hides them).

    ``layer`` indexes this layer in a paged entry's stacked pools.
    """
    q = _project_q(p, x, cfg)
    k_new, v_new = _project_kv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k_new = apply_rope(k_new, positions, cfg.rope_theta)
    if "kp" in kv_cache:
        kv_cache = cache_lib.append_paged_cache(
            kv_cache, k_new, v_new, positions, block_tables, layer, valid)
        k_all, v_all, k_pos = cache_lib.gather_paged_kv(
            kv_cache, block_tables, layer, cfg.resolved_head_dim)
        o = dispatch.flash_attention(
            q, k_all, v_all, q_positions=positions, k_positions=k_pos,
            causal=True, window=window, softcap=cfg.logit_softcap,
        )
        return _out_proj(p, o), kv_cache
    k_all = jnp.concatenate([kv_cache["k"].astype(k_new.dtype), k_new], axis=1)
    v_all = jnp.concatenate([kv_cache["v"].astype(v_new.dtype), v_new], axis=1)
    chunk_pos = positions if valid is None else jnp.where(valid, positions, -1)
    cache_pos = kv_cache["pos"]
    if overwrite_from is not None:
        cache_pos = jnp.where(
            cache_pos >= overwrite_from[:, None], -1, cache_pos)
    k_pos = jnp.concatenate([cache_pos, chunk_pos], axis=1)
    o = dispatch.flash_attention(
        q, k_all, v_all, q_positions=positions, k_positions=k_pos,
        causal=True, window=window, softcap=cfg.logit_softcap,
    )
    kv_cache = cache_lib.append_attn_cache(kv_cache, k_new, v_new, positions,
                                           valid)
    return _out_proj(p, o), kv_cache


def apply_attention_decode(
    p: Dict,
    x: jax.Array,            # (B, 1, d)
    cfg: ModelConfig,
    positions: jax.Array,    # scalar or (B,) int32: index of the new token
    kv_cache: Dict,
    *,
    window: int = 0,
    block_tables: Optional[jax.Array] = None,
    update_mask: Optional[jax.Array] = None,
    layer=0,
) -> Tuple[jax.Array, Dict]:
    B = x.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B,))
    pos_b = positions[:, None]
    q = _project_q(p, x, cfg)
    k_new, v_new = _project_kv(p, x, cfg)
    q = apply_rope(q, pos_b, cfg.rope_theta)
    k_new = apply_rope(k_new, pos_b, cfg.rope_theta)
    if "kp" in kv_cache:  # paged: append via block table, attend on the pool
        kv_cache = cache_lib.update_paged_cache(
            kv_cache, k_new, v_new, positions, block_tables, layer,
            update_mask)
        o = dispatch.paged_decode_attention(
            q, kv_cache["kp"], kv_cache["vp"],
            block_tables=block_tables, q_positions=pos_b, layer=layer,
            window=window, softcap=cfg.logit_softcap,
        )
        return _out_proj(p, o), kv_cache
    kv_cache = cache_lib.update_attn_cache(kv_cache, k_new, v_new, positions,
                                           update_mask)
    o = dispatch.decode_attention(
        q, kv_cache["k"], kv_cache["v"],
        q_positions=pos_b, k_positions=kv_cache["pos"],
        window=window, softcap=cfg.logit_softcap,
    )
    return _out_proj(p, o), kv_cache


# -- cross attention (encoder-decoder) --------------------------------------

def apply_cross_attention(
    p: Dict,
    x: jax.Array,              # (B, S, d) decoder states
    cfg: ModelConfig,
    memory_kv: Tuple[jax.Array, jax.Array],  # precomputed (B, T, Hkv, D) pair
    memory_valid: Optional[jax.Array] = None,
) -> jax.Array:
    q = _project_q(p, x, cfg)
    k, v = memory_kv
    B, S = x.shape[:2]
    T = k.shape[1]
    q_pos = jnp.zeros((B, S), jnp.int32)
    k_pos = jnp.zeros((B, T), jnp.int32) if memory_valid is None else jnp.where(
        memory_valid, 0, -1
    )
    o = sdpa(q, k, v, q_positions=q_pos, k_positions=k_pos, causal=False)
    return _out_proj(p, o)


def precompute_cross_kv(p: Dict, memory: jax.Array, cfg: ModelConfig):
    """Project encoder memory to K/V once (reused across decode steps)."""
    return _project_kv(p, memory, cfg)
