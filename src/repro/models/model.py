"""The unified decoder / encoder-decoder model.

One implementation covers all ten assigned architectures: the layer stack is
``cfg.block_pattern`` tiled across ``num_layers``; full pattern repetitions
are executed under ``jax.lax.scan`` (params stacked on a leading `layers`
axis — keeps the HLO size O(pattern) instead of O(num_layers), which is what
makes the 64-layer 104B dry-run compile in minutes), remainder layers are
unrolled.

Three entry points per model:
  * ``forward_train``  — full-sequence teacher-forced logits.
  * ``prefill``        — same math, but fills and returns the decode cache.
  * ``decode_step``    — one token against the cache (the TPOT step).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import cache as cache_lib
from repro.models import flags
from repro.models import moe as moe_lib
from repro.models import recurrent as rec_lib
from repro.models.config import ModelConfig
from repro.models.layers import (
    Maker, apply_norm, embed_tokens, make_embedding, make_norm, shard,
    split_params, unembed,
)
from repro.models.mlp import apply_mlp, make_mlp


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def _make_block(mk: Maker, cfg: ModelConfig, kind: str, *, decoder: bool) -> Dict:
    d = cfg.d_model
    if kind in ("attn", "local_attn"):
        p = {
            "norm1": make_norm(mk.fork(), d),
            "attn": attn_lib.make_attention(mk.fork(), cfg),
            "norm2": make_norm(mk.fork(), d),
            "mlp": (moe_lib.make_moe(mk.fork(), cfg) if cfg.is_moe
                    else make_mlp(mk.fork(), d, cfg.d_ff, cfg.mlp_gated)),
        }
        if cfg.parallel_block:
            del p["norm2"]  # single shared pre-norm (Cohere/GPT-J style)
        if cfg.is_encdec and decoder:
            p["norm_c"] = make_norm(mk.fork(), d)
            p["cross"] = attn_lib.make_attention(mk.fork(), cfg, cross=True)
        return p
    if kind == "ffn":
        return {
            "norm": make_norm(mk.fork(), d),
            "mlp": make_mlp(mk.fork(), d, cfg.d_ff, cfg.mlp_gated),
        }
    if kind == "rglru":
        return {
            "norm1": make_norm(mk.fork(), d),
            "rec": rec_lib.make_rglru_block(mk.fork(), cfg),
            "norm2": make_norm(mk.fork(), d),
            "mlp": make_mlp(mk.fork(), d, cfg.d_ff, cfg.mlp_gated),
        }
    if kind == "mlstm":
        return {"norm": make_norm(mk.fork(), d),
                "cell": rec_lib.make_mlstm_block(mk.fork(), cfg)}
    if kind == "slstm":
        return {"norm": make_norm(mk.fork(), d),
                "cell": rec_lib.make_slstm_block(mk.fork(), cfg)}
    raise ValueError(kind)


def _enc_cfg(cfg: ModelConfig) -> ModelConfig:
    """Encoder trunk config (dense MLP, full attention, own d_ff)."""
    return cfg.replace(
        block_pattern=("attn",),
        num_layers=cfg.num_encoder_layers,
        d_ff=cfg.encoder_d_ff or cfg.d_ff,
        num_experts=0, num_experts_per_tok=0,
        num_encoder_layers=0,
    )


def _make_stack(key: jax.Array, cfg: ModelConfig, *, decoder: bool):
    """Returns (params, axes) for a layer stack (scan groups + remainder)."""
    dtype = jnp.dtype(cfg.param_dtype)
    pattern = cfg.block_pattern
    n_groups, n_rest = cfg.layer_groups()
    keys = jax.random.split(key, 3)

    def build_group(k):
        mk = Maker(k, dtype)
        return {
            str(i): _make_block(mk.fork(), cfg, kind, decoder=decoder)
            for i, kind in enumerate(pattern)
        }

    params: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}
    if n_groups > 0:
        gkeys = jax.random.split(keys[0], n_groups)
        params["groups"] = jax.vmap(
            lambda k: split_params(build_group(k))[0]
        )(gkeys)
        g_axes = split_params(build_group(keys[0]))[1]
        axes["groups"] = jax.tree.map(
            lambda ax: ("layers", *ax), g_axes,
            is_leaf=lambda l: isinstance(l, tuple) and all(
                isinstance(a, (str, type(None))) for a in l),
        )
    if n_rest > 0:
        mk = Maker(keys[1], dtype)
        rest = {
            str(i): _make_block(mk.fork(), cfg, kind, decoder=decoder)
            for i, kind in enumerate(pattern[:n_rest])
        }
        params["rest"], axes["rest"] = split_params(rest)
    fn, fn_axes = split_params({"final_norm": make_norm(Maker(keys[2], dtype), cfg.d_model)})
    params.update(fn)
    axes.update(fn_axes)
    return params, axes


def init(cfg: ModelConfig, key: jax.Array) -> Tuple[Dict, Dict]:
    """Build params + logical-axes trees."""
    cfg.validate()
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_dec, k_enc = jax.random.split(key, 3)
    emb_tree = {"embed": make_embedding(Maker(k_emb, dtype), cfg.vocab_size, cfg.d_model)}
    if not cfg.tie_embeddings:
        k_emb2 = jax.random.fold_in(k_emb, 1)
        emb_tree["lm_head"] = make_embedding(Maker(k_emb2, dtype), cfg.vocab_size, cfg.d_model)
    emb, emb_axes = split_params(emb_tree)
    params, axes = dict(emb), dict(emb_axes)
    dec_p, dec_a = _make_stack(k_dec, cfg, decoder=True)
    params["decoder"], axes["decoder"] = dec_p, dec_a
    if cfg.is_encdec:
        enc_p, enc_a = _make_stack(k_enc, _enc_cfg(cfg), decoder=False)
        params["encoder"], axes["encoder"] = enc_p, enc_a
    return params, axes


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _apply_block_seq(
    p: Dict,
    cfg: ModelConfig,
    kind: str,
    x: jax.Array,
    positions: jax.Array,
    cache_entry: Optional[Dict],
    memory: Optional[jax.Array],
    *,
    causal: bool,
    fill_cache: bool,
    block_tables: Optional[jax.Array] = None,
    chunked: bool = False,
    chunk_valid: Optional[jax.Array] = None,
    overwrite_from: Optional[jax.Array] = None,
    layer=0,
) -> Tuple[jax.Array, Optional[Dict]]:
    """Full-sequence block (train / prefill / encoder).

    ``chunked=True`` switches attention blocks to the chunked-prefill path
    (attend over the cache + the chunk instead of a self-contained prompt);
    recurrent and conv blocks already resume from the state carried in
    ``cache_entry``, so they need no chunk-specific handling.

    ``chunk_valid`` (B, S) bool marks per-row valid prefixes when ragged
    chunks are packed into one static-width batch (unified mixed step):
    attention masks pad keys and cache writes, recurrent/conv states take
    identity steps at pads.

    ``layer`` indexes this block's layer in a paged entry's stacked pools.
    """
    new_entry: Optional[Dict] = None
    if kind in ("attn", "local_attn"):
        window = cfg.sliding_window if kind == "local_attn" else 0
        h = apply_norm(p["norm1"], x, cfg.norm_eps)
        if fill_cache:
            if chunked:
                a, self_cache = attn_lib.apply_attention_prefill_chunk(
                    p["attn"], h, cfg, positions, cache_entry["self"],
                    window=window, block_tables=block_tables,
                    valid=chunk_valid, overwrite_from=overwrite_from,
                    layer=layer,
                )
            else:
                a, self_cache = attn_lib.apply_attention_prefill(
                    p["attn"], h, cfg, positions, cache_entry["self"],
                    window=window, block_tables=block_tables, layer=layer,
                )
            new_entry = {"self": self_cache}
        else:
            a = attn_lib.apply_attention_train(
                p["attn"], h, cfg, positions, causal=causal, window=window
            )
        mlp_in = h if cfg.parallel_block else None
        x = x + a
        if "cross" in p:
            h = apply_norm(p["norm_c"], x, cfg.norm_eps)
            mem_kv = attn_lib.precompute_cross_kv(p["cross"], memory, cfg)
            if fill_cache and new_entry is not None:
                new_entry["cross_k"], new_entry["cross_v"] = mem_kv
            x = x + attn_lib.apply_cross_attention(p["cross"], h, cfg, mem_kv)
        if mlp_in is None:
            mlp_in = apply_norm(p["norm2"], x, cfg.norm_eps)
        moe_fn = (moe_lib.apply_moe_blocked if flags.moe_blocked()
                  else moe_lib.apply_moe)
        x = x + (moe_fn(p["mlp"], mlp_in, cfg) if cfg.is_moe
                 else apply_mlp(p["mlp"], mlp_in, cfg.mlp_act))
        return x, new_entry

    if kind == "ffn":
        h = apply_norm(p["norm"], x, cfg.norm_eps)
        x = x + apply_mlp(p["mlp"], h, cfg.mlp_act)
        return x, ({} if fill_cache else None)

    if kind == "rglru":
        h = apply_norm(p["norm1"], x, cfg.norm_eps)
        y, st = rec_lib.apply_rglru_seq(
            p["rec"], h, cfg, cache_entry if fill_cache else None,
            valid=chunk_valid if fill_cache else None,
        )
        x = x + y
        h = apply_norm(p["norm2"], x, cfg.norm_eps)
        x = x + apply_mlp(p["mlp"], h, cfg.mlp_act)
        return x, (st if fill_cache else None)

    if kind in ("mlstm", "slstm"):
        h = apply_norm(p["norm"], x, cfg.norm_eps)
        fn = rec_lib.apply_mlstm_seq if kind == "mlstm" else rec_lib.apply_slstm_seq
        y, st = fn(p["cell"], h, cfg, cache_entry if fill_cache else None,
                   valid=chunk_valid if fill_cache else None)
        return x + y, (st if fill_cache else None)

    raise ValueError(kind)


def _gate_entry(new_entry: Dict, old_entry: Dict,
                update_mask: Optional[jax.Array]) -> Dict:
    """Freeze masked-off rows of a per-slot state entry at their old value.

    Used by the decode path for recurrent/conv states: idle and mid-prefill
    slots run the (garbage) step math for shape stability, but their state
    must not advance — a chunked prefill may be building it concurrently.
    Leaves are (B, ...); scalar bookkeeping leaves pass through.
    """
    if update_mask is None:
        return new_entry
    def gate(new, old):
        if new.ndim == 0:
            return new
        m = update_mask.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)
    return jax.tree.map(gate, new_entry, old_entry)


def _apply_block_decode(
    p: Dict,
    cfg: ModelConfig,
    kind: str,
    x: jax.Array,
    position: jax.Array,
    cache_entry: Dict,
    block_tables: Optional[jax.Array] = None,
    update_mask: Optional[jax.Array] = None,
    layer=0,
) -> Tuple[jax.Array, Dict]:
    if kind in ("attn", "local_attn"):
        window = cfg.sliding_window if kind == "local_attn" else 0
        h = apply_norm(p["norm1"], x, cfg.norm_eps)
        a, self_cache = attn_lib.apply_attention_decode(
            p["attn"], h, cfg, position, cache_entry["self"], window=window,
            block_tables=block_tables, update_mask=update_mask, layer=layer,
        )
        new_entry = dict(cache_entry)
        new_entry["self"] = self_cache
        mlp_in = h if cfg.parallel_block else None
        x = x + a
        if "cross" in p:
            h = apply_norm(p["norm_c"], x, cfg.norm_eps)
            mem_kv = (cache_entry["cross_k"], cache_entry["cross_v"])
            x = x + attn_lib.apply_cross_attention(p["cross"], h, cfg, mem_kv)
        if mlp_in is None:
            mlp_in = apply_norm(p["norm2"], x, cfg.norm_eps)
        moe_fn = (moe_lib.apply_moe_blocked if flags.moe_blocked()
                  else moe_lib.apply_moe)
        x = x + (moe_fn(p["mlp"], mlp_in, cfg) if cfg.is_moe
                 else apply_mlp(p["mlp"], mlp_in, cfg.mlp_act))
        return x, new_entry

    if kind == "ffn":
        h = apply_norm(p["norm"], x, cfg.norm_eps)
        return x + apply_mlp(p["mlp"], h, cfg.mlp_act), {}

    if kind == "rglru":
        h = apply_norm(p["norm1"], x, cfg.norm_eps)
        y, st = rec_lib.apply_rglru_step(p["rec"], h, cfg, cache_entry)
        x = x + y
        h = apply_norm(p["norm2"], x, cfg.norm_eps)
        x = x + apply_mlp(p["mlp"], h, cfg.mlp_act)
        return x, _gate_entry(st, cache_entry, update_mask)

    if kind in ("mlstm", "slstm"):
        h = apply_norm(p["norm"], x, cfg.norm_eps)
        fn = rec_lib.apply_mlstm_step if kind == "mlstm" else rec_lib.apply_slstm_step
        y, st = fn(p["cell"], h, cfg, cache_entry)
        return x + y, _gate_entry(st, cache_entry, update_mask)

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack application
# ---------------------------------------------------------------------------
#
# Paged pools (``kp``/``vp``) are stacked over layers and ride through the
# layer loop in the ``lax.scan`` carry, each layer writing and reading its
# own ``[layer, ...]`` part in place; every other cache entry is per-layer
# state and rides as the scan's ``xs``/``ys``.  A pool fed in as ``xs``
# would be sliced out of the stack and stacked back every layer: two
# copies of the whole pool per layer and step.

def _split_pools(tree):
    """``(entries, pools)``: a cache tree's pool leaves apart from the
    rest, each tree holding ``None`` where the other holds a leaf."""
    def keep(want):
        return lambda path, x: x if cache_lib.is_pool(path) == want else None

    return (jax.tree_util.tree_map_with_path(keep(False), tree),
            jax.tree_util.tree_map_with_path(keep(True), tree))


def _join_pools(entries, pools):
    """Inverse of ``_split_pools``."""
    return jax.tree.map(lambda e, p: e if p is None else p, entries, pools,
                        is_leaf=lambda x: x is None)


def _scan_layers(body, x, params, cache):
    """Run ``body(x, params_g, cache_g, g) -> (x, new_cache_g)`` over the
    ``n`` stacked layer groups, pools in the carry (see above); ``cache``
    may be None.  ``flags.unroll_scans()`` unrolls the loop, same rule."""
    entries, pools = _split_pools(cache)

    def step(carry, xs):
        x, pools = carry
        gp, gc, g = xs
        x, nc = body(x, gp, _join_pools(gc, pools), g)
        nc, pools = _split_pools(nc)
        return (x, pools), nc

    n = jax.tree.leaves(params)[0].shape[0]
    xs = (params, entries, jnp.arange(n, dtype=jnp.int32))
    carry = (x, pools)
    if flags.unroll_scans():
        ys = []
        for g in range(n):
            carry, nc = step(carry, jax.tree.map(lambda t: t[g], xs))
            ys.append(nc)
        ys = jax.tree.map(lambda *ts: jnp.stack(ts), *ys)
    else:
        carry, ys = jax.lax.scan(step, carry, xs)
    x, pools = carry
    return x, _join_pools(ys, pools)


def _apply_stack_seq(
    stack: Dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[Dict],
    memory: Optional[jax.Array],
    *,
    causal: bool,
    remat: bool,
    block_tables: Optional[jax.Array] = None,
    chunked: bool = False,
    chunk_valid: Optional[jax.Array] = None,
    overwrite_from: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    pattern = cfg.block_pattern
    fill = cache is not None
    n_groups, n_rest = cfg.layer_groups()

    def blocks(x, block_params, block_cache, layer, kinds=pattern):
        new_cache = {}
        for i, kind in enumerate(kinds):
            entry = block_cache[str(i)] if fill else None
            x, new_entry = _apply_block_seq(
                block_params[str(i)], cfg, kind, x, positions, entry, memory,
                causal=causal, fill_cache=fill, block_tables=block_tables,
                chunked=chunked, chunk_valid=chunk_valid,
                overwrite_from=overwrite_from, layer=layer,
            )
            if fill:
                new_cache[str(i)] = new_entry
        return x, (new_cache if fill else None)

    group_body = (jax.checkpoint(
        blocks, policy=jax.checkpoint_policies.nothing_saveable)
        if remat else blocks)

    new_cache_tree: Dict[str, Any] = {}
    if n_groups > 0:
        x, group_caches = _scan_layers(
            group_body, x, stack["groups"], cache["groups"] if fill else None)
        if fill:
            new_cache_tree["groups"] = group_caches
    if n_rest > 0:
        # remainder pools carry a unit layer axis (``init_cache``)
        x, new_rest = blocks(x, stack["rest"], cache["rest"] if fill else None,
                             0, pattern[:n_rest])
        if fill:
            new_cache_tree["rest"] = new_rest
    x = apply_norm(stack["final_norm"], x, cfg.norm_eps)
    return x, (new_cache_tree if fill else None)


def _apply_stack_decode(
    stack: Dict,
    cfg: ModelConfig,
    x: jax.Array,
    position: jax.Array,
    cache: Dict,
    block_tables: Optional[jax.Array] = None,
    update_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    pattern = cfg.block_pattern
    n_groups, n_rest = cfg.layer_groups()

    def blocks(x, block_params, block_cache, layer, kinds=pattern):
        nc = {}
        for i, kind in enumerate(kinds):
            x, nc[str(i)] = _apply_block_decode(
                block_params[str(i)], cfg, kind, x, position,
                block_cache[str(i)], block_tables, update_mask, layer=layer
            )
        return x, nc

    new_cache: Dict[str, Any] = {}
    if n_groups > 0:
        x, new_cache["groups"] = _scan_layers(blocks, x, stack["groups"],
                                              cache["groups"])
    if n_rest > 0:
        # remainder pools carry a unit layer axis (``init_cache``)
        x, new_cache["rest"] = blocks(x, stack["rest"], cache["rest"], 0,
                                      pattern[:n_rest])
    x = apply_norm(stack["final_norm"], x, cfg.norm_eps)
    return x, new_cache


# ---------------------------------------------------------------------------
# embedding frontends
# ---------------------------------------------------------------------------

def _embed_inputs(cfg: ModelConfig, params: Dict, batch: Dict) -> jax.Array:
    """Token embedding, with the VLM patch-prefix stub when configured."""
    x = embed_tokens(params["embed"], batch["tokens"], cfg.emb_scale, cfg.d_model)
    if cfg.num_vision_tokens > 0 and "vision_embeds" in batch:
        ve = batch["vision_embeds"].astype(x.dtype)  # (B, N_img, d) precomputed
        x = jnp.concatenate([ve, x], axis=1)
    return x


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def forward_train(
    cfg: ModelConfig, params: Dict, batch: Dict, *, remat: bool = True
) -> jax.Array:
    """Teacher-forced logits (B, S, vocab).

    batch: tokens (B, S) [+ vision_embeds (B, N, d)] [+ enc_embeds (B, T, d)].
    """
    x = _embed_inputs(cfg, params, batch)
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2]
    )
    memory = None
    if cfg.is_encdec:
        enc_x = batch["enc_embeds"].astype(x.dtype)
        enc_pos = jnp.broadcast_to(
            jnp.arange(enc_x.shape[1], dtype=jnp.int32)[None], enc_x.shape[:2]
        )
        memory, _ = _apply_stack_seq(
            params["encoder"], _enc_cfg(cfg), enc_x, enc_pos, None, None,
            causal=False, remat=remat,
        )
    x, _ = _apply_stack_seq(
        params["decoder"], cfg, x, positions, None, memory,
        causal=True, remat=remat,
    )
    return unembed(params.get("lm_head", params["embed"]), x, cfg.logit_softcap)


def param_axes(cfg: ModelConfig):
    """(param ShapeDtypeStruct tree, logical-axes tree) — no allocation."""
    captured = {}

    def f(key):
        params, axes = init(cfg, key)
        captured["axes"] = axes
        return params

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, captured["axes"]


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype,
    *, layout: str = "contiguous", block_size: int = 16,
    num_blocks: int = 0,
) -> Dict:
    """Decode cache for the decoder stack (stacked to mirror param groups).

    ``layout="paged"`` swaps full-context attention entries for global block
    pools (``num_blocks`` x ``block_size``; 0 -> worst-case sizing) shared
    by all slots and addressed through the caller's block tables.  Ring
    (sliding-window), recurrent, and cross-attention entries are identical
    in both layouts.  Every pool is stacked over layers, ``(layers,
    num_blocks, block_size, H*D)``: over the groups like every entry, and
    with a unit layer axis for a remainder layer, so that the paged code
    reads and writes any pool at ``[layer, ...]``.
    """
    assert layout in ("contiguous", "paged"), layout
    pattern = cfg.block_pattern
    n_groups, n_rest = cfg.layer_groups()

    def entry(kind):
        c = cache_lib.init_block_cache(
            cfg, kind, batch, max_len, dtype,
            layout=layout, block_size=block_size, num_blocks=num_blocks)
        if kind in ("attn", "local_attn"):
            c = {"self": c}
            if cfg.is_encdec:
                t_mem = max_len // 2 if max_len > 1 else 1
                hd = cfg.resolved_head_dim
                c["cross_k"] = jnp.zeros((batch, t_mem, cfg.num_kv_heads, hd), dtype)
                c["cross_v"] = jnp.zeros((batch, t_mem, cfg.num_kv_heads, hd), dtype)
        return c

    cache: Dict[str, Any] = {}
    if n_groups > 0:
        cache["groups"] = {
            str(i): jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n_groups, *a.shape)).copy(),
                entry(kind),
            )
            for i, kind in enumerate(pattern)
        }
    if n_rest > 0:
        cache["rest"] = {
            str(i): jax.tree_util.tree_map_with_path(
                lambda path, a: a[None] if cache_lib.is_pool(path) else a,
                entry(kind))
            for i, kind in enumerate(pattern[:n_rest])
        }
    return cache


def prefill(
    cfg: ModelConfig, params: Dict, batch: Dict, cache: Dict, *,
    remat: bool = False, block_tables: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    """Process the prompt, fill the cache; returns last-position logits.

    For a paged cache, ``block_tables`` (B, max_blocks) names the pool
    blocks each row's prompt K/V scatters into.
    """
    x = _embed_inputs(cfg, params, batch)
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2]
    )
    memory = None
    if cfg.is_encdec:
        enc_x = batch["enc_embeds"].astype(x.dtype)
        enc_pos = jnp.broadcast_to(
            jnp.arange(enc_x.shape[1], dtype=jnp.int32)[None], enc_x.shape[:2]
        )
        memory, _ = _apply_stack_seq(
            params["encoder"], _enc_cfg(cfg), enc_x, enc_pos, None, None,
            causal=False, remat=remat,
        )
    x, new_cache = _apply_stack_seq(
        params["decoder"], cfg, x, positions, cache, memory,
        causal=True, remat=remat, block_tables=block_tables,
    )
    logits = unembed(params.get("lm_head", params["embed"]), x[:, -1:],
                     cfg.logit_softcap)[:, 0]
    return logits, new_cache


def prefill_chunk(
    cfg: ModelConfig, params: Dict, batch: Dict, cache: Dict,
    start: jax.Array, *, block_tables: Optional[jax.Array] = None,
    lengths: Optional[jax.Array] = None,
    overwrite_from: Optional[jax.Array] = None,
    all_logits: bool = False,
) -> Tuple[jax.Array, Dict]:
    """Process one prompt chunk (positions ``start..start+C-1``) against a
    cache already holding chunks for positions ``0..start-1``.

    Attention blocks attend over the cached earlier chunks plus the chunk
    itself (causal); recurrent/conv blocks resume from their carried state.
    ``start`` may be a traced scalar — or, for the unified mixed-batch step,
    a per-row (B,) vector — so one compiled executable serves every chunk
    offset of a given chunk width.  Returns the chunk's last-position
    logits (only meaningful for the final chunk) and the updated cache.

    ``lengths`` (B,) int32, when given, marks how many of each row's C
    columns are real tokens (ragged rows packed to one static width):
    pad columns write nothing to the cache, recurrent states take identity
    steps, and the returned logits come from each row's *last valid*
    position (rows with ``lengths == 0`` return garbage logits and leave
    their cache rows untouched).  For a VLM config, pass ``vision_embeds``
    only with the ``start == 0`` chunk and offset later chunk starts by
    ``num_vision_tokens`` — mirroring the prefix handling of ``prefill``;
    ``lengths`` is not supported together with a vision prefix.

    The speculative verify step reuses this multi-token path to score a
    draft window against the live cache: ``overwrite_from`` (B,) hides
    stale contiguous cache entries at positions >= the row's value (a
    previous window's rejected suffix shares the new window's positions —
    see ``apply_attention_prefill_chunk``), and ``all_logits=True``
    returns the full per-position logits (B, C, vocab) instead of each
    row's last-valid-position row — verification needs the target
    distribution *at every window position*, not just the final one.
    """
    x = _embed_inputs(cfg, params, batch)
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        positions = start + jnp.arange(x.shape[1], dtype=jnp.int32)[None]
    else:
        positions = start[:, None] + jnp.arange(x.shape[1], dtype=jnp.int32)[None]
    positions = jnp.broadcast_to(positions, x.shape[:2])
    valid = None
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
        valid = jnp.arange(x.shape[1], dtype=jnp.int32)[None] < lengths[:, None]
    memory = None
    if cfg.is_encdec:
        enc_x = batch["enc_embeds"].astype(x.dtype)
        enc_pos = jnp.broadcast_to(
            jnp.arange(enc_x.shape[1], dtype=jnp.int32)[None], enc_x.shape[:2]
        )
        memory, _ = _apply_stack_seq(
            params["encoder"], _enc_cfg(cfg), enc_x, enc_pos, None, None,
            causal=False, remat=False,
        )
    x, new_cache = _apply_stack_seq(
        params["decoder"], cfg, x, positions, cache, memory,
        causal=True, remat=False, block_tables=block_tables, chunked=True,
        chunk_valid=valid, overwrite_from=overwrite_from,
    )
    if all_logits:
        logits = unembed(params.get("lm_head", params["embed"]), x,
                         cfg.logit_softcap)
        return logits, new_cache
    if lengths is None:
        x_last = x[:, -1:]
    else:
        idx = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    logits = unembed(params.get("lm_head", params["embed"]), x_last,
                     cfg.logit_softcap)[:, 0]
    return logits, new_cache


def decode_step(
    cfg: ModelConfig, params: Dict, token: jax.Array, position: jax.Array,
    cache: Dict, block_tables: Optional[jax.Array] = None,
    update_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    """One decode step.  token (B, 1) int32; position scalar or (B,) int32.
    ``block_tables`` (B, max_blocks) int32 is required for paged caches.
    ``update_mask`` (B,) bool freezes cache/state writes of masked-off rows
    (idle or mid-chunked-prefill slots in the serving engine)."""
    position = jnp.broadcast_to(
        jnp.asarray(position, jnp.int32), (token.shape[0],))
    x = embed_tokens(params["embed"], token, cfg.emb_scale, cfg.d_model)
    x, new_cache = _apply_stack_decode(params["decoder"], cfg, x, position,
                                       cache, block_tables, update_mask)
    logits = unembed(params.get("lm_head", params["embed"]), x, cfg.logit_softcap)[:, 0]
    return logits, new_cache
