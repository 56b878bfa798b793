"""Jitted serving-step builders (shared by the engine and the dry-run).

``make_prefill_step`` / ``make_decode_step`` return pure functions with the
exact signatures the multi-pod dry-run lowers; shardings are attached by the
caller (``launch.dryrun`` / ``serving.engine``).

``make_decode_sample_step`` is the engine's device-resident fast path: one
jitted function fuses the decode forward pass, per-slot sampling, PRNG key
splitting, position/budget bookkeeping and finish detection.  The host feeds
it a small ``state`` dict of per-slot device arrays and reads back a single
packed (3, B) int32 array per step — the only host<->device sync in the
steady-state decode loop.

The fused steps name their parts with ``jax.named_scope`` — ``chunk_pass``
(the packed prefill chunk), ``decode`` (the model forward of the decode
half or the speculative verify) and ``sample`` (sampling, acceptance and
finish detection) — so that a device trace can put each op's time down to
one of them.  Scopes change HLO metadata only, never the computation.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import model as model_lib
from repro.models.config import ModelConfig
from repro.serving.sampling import sample_slots_keyed, verify_slots_keyed


def make_prefill_step(cfg: ModelConfig) -> Callable:
    def prefill_step(params, batch, cache):
        return model_lib.prefill(cfg, params, batch, cache)

    return prefill_step


def make_decode_step(cfg: ModelConfig) -> Callable:
    def decode_step(params, token, position, cache):
        return model_lib.decode_step(cfg, params, token, position, cache)

    return decode_step


def make_serve_step(cfg: ModelConfig) -> Callable:
    """The dry-run `serve_step`: one new token against a seq_len KV cache."""
    return make_decode_step(cfg)


def init_slot_state(max_batch: int, seed: int = 0,
                    max_blocks: int = 0, spec_k: int = 0) -> Dict[str, jax.Array]:
    """Device-resident per-slot scheduler state for ``decode_sample_step``.

    tokens       (B, 1) int32  — next input token per slot
    positions    (B,)   int32  — next cache write position per slot
    active       (B,)   bool   — slot is serving a live request
    remaining    (B,)   int32  — new-token budget left (max_new minus emitted)
    temperature  (B,)   f32    — per-slot sampling temperature (<=0 greedy)
    top_k        (B,)   int32  — per-slot top-k (0 = no filter)
    eos          (B,)   int32  — per-slot EOS id (-1 = never)
    keys         (B, 2) uint32 — per-slot PRNG key chain, split on device
                 only when the slot emits a token (so a request's draws are
                 a pure function of its own key + emitted-token index,
                 independent of scheduling)
    block_tables (B, max_blocks) int32 — paged layout only (max_blocks > 0):
                 pool block per (slot, logical block); 0 = garbage block
    draft        (B, spec_k) int32 — speculative engines only (spec_k > 0):
                 the host drafter's proposed continuation tokens, replaced
                 wholesale before every verify dispatch
    draft_len    (B,)   int32  — valid leading draft tokens per slot
    """
    B = max_batch
    base = jax.random.PRNGKey(seed)
    state = {
        "tokens": jnp.zeros((B, 1), jnp.int32),
        "positions": jnp.zeros((B,), jnp.int32),
        "active": jnp.zeros((B,), jnp.bool_),
        "remaining": jnp.zeros((B,), jnp.int32),
        "temperature": jnp.zeros((B,), jnp.float32),
        "top_k": jnp.zeros((B,), jnp.int32),
        "eos": jnp.full((B,), -1, jnp.int32),
        "keys": jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(B)),
    }
    if max_blocks > 0:
        state["block_tables"] = jnp.zeros((B, max_blocks), jnp.int32)
    if spec_k > 0:
        state["draft"] = jnp.zeros((B, spec_k), jnp.int32)
        state["draft_len"] = jnp.zeros((B,), jnp.int32)
    return state


def invalidate_slot(state: Dict[str, jax.Array], slot: int,
                    *, garbage_block: int = 0) -> Dict[str, jax.Array]:
    """Retire one slot's device row between steps (finish or preemption).

    The fused step keeps replaying every slot at a static shape, so a
    retired slot is not removed — it is *masked*: inactive (all cache and
    recurrent-state writes become no-ops), zero remaining budget, and, in
    the paged layout, the whole block-table row pointed back at the
    reserved garbage block so the slot's frozen idle writes can never
    land in a pool block that has been freed or handed to another
    request.  Everything else (token, position, key) is left frozen; the
    next occupant overwrites it when the slot is re-armed.
    """
    state = dict(state)
    state["active"] = state["active"].at[slot].set(False)
    state["remaining"] = state["remaining"].at[slot].set(0)
    if "block_tables" in state:
        state["block_tables"] = (
            state["block_tables"].at[slot].set(garbage_block))
    return state


def donating_jit(fn: Callable, argnums: Tuple[int, ...]) -> Callable:
    """``jax.jit`` with buffer donation.

    Donating the fused step's cache/state buffers lets XLA update the KV
    cache in place instead of allocating a fresh copy every step.  Every
    backend donates, the CPU included, so the tests exercise the same
    buffer lifetimes as the chip: a donated buffer the host still reads
    fails there as it would on the TPU ("Array has been deleted").
    """
    return jax.jit(fn, donate_argnums=argnums)


def make_decode_sample_step(cfg: ModelConfig, max_len: int,
                            k_max: int = 64) -> Callable:
    """Fused decode + sample + finish-detect step (jit once, replay forever).

    Returns ``step(params, state, cache) -> (state', cache', out)`` where
    ``out`` is a packed (3, B) int32 array:

      out[0] — token emitted this step per slot (garbage for idle slots)
      out[1] — 1 where the slot finished on this step (EOS / budget / cap)
      out[2] — 1 where the slot was active and therefore emitted out[0]

    Idle slots keep re-feeding their last token at a frozen position, so the
    compiled executable never changes shape — but all of their cache and
    recurrent-state writes are masked off (``update_mask=active`` threads
    down to every cache kind).  That matters with chunked prefill: a slot
    mid-prefill already owns its cache row / pool blocks, and the chunk
    cursor is concurrently filling them between decode steps.  Each slot
    also carries its own PRNG key chain, advanced only when it emits, so
    sampled streams are invariant to how prefills and decodes interleave.
    """

    def step(params, state: Dict[str, jax.Array], cache) -> Tuple[Dict, Dict, jax.Array]:
        return _decode_sample_body(cfg, max_len, k_max, params, state, cache)

    return step


def _decode_sample_body(cfg: ModelConfig, max_len: int, k_max: int,
                        params, state: Dict[str, jax.Array], cache):
    """Shared decode+sample+finish body of ``make_decode_sample_step`` and
    ``make_engine_step`` (identical math, so fused and split paths emit
    byte-identical streams)."""
    active = state["active"]
    with jax.named_scope("decode"):
        logits, new_cache = model_lib.decode_step(
            cfg, params, state["tokens"], state["positions"], cache,
            block_tables=state.get("block_tables"), update_mask=active)
    with jax.named_scope("sample"):
        split = jax.vmap(jax.random.split)(state["keys"])   # (B, 2, 2)
        tok = sample_slots_keyed(logits, state["temperature"],
                                 state["top_k"], split[:, 0], k_max=k_max)

        act_i = active.astype(jnp.int32)
        tok = jnp.where(active, tok, state["tokens"][:, 0])
        positions = state["positions"] + act_i
        remaining = state["remaining"] - act_i
        hit_eos = (state["eos"] >= 0) & (tok == state["eos"])
        done = active & (hit_eos | (remaining <= 0)
                         | (positions >= max_len - 1))

        new_state = dict(state)  # block_tables etc. pass through untouched
        new_state.update(
            tokens=tok[:, None],
            positions=positions,
            active=active & ~done,
            remaining=remaining,
            keys=jnp.where(active[:, None], split[:, 1], state["keys"]),
        )
        out = jnp.stack([tok, done.astype(jnp.int32), act_i])
    return new_state, new_cache, out


def _spec_verify_body(cfg: ModelConfig, max_len: int, k_max: int, spec_k: int,
                      params, state: Dict[str, jax.Array], cache):
    """Speculative decode: ONE batched multi-token forward scores every
    slot's draft window, then the unrolled acceptance chain emits 1 +
    accepted tokens per slot.

    The verify forward *is* the PR 6 length-masked chunk path: each slot's
    window ``[last_token, draft...]`` rides as a ragged (B, spec_k + 1) row
    (``lengths = draft_len + 1`` for active slots, 0 for idle/prefilling
    ones, whose rows write nothing), starting at the slot's next cache
    write position.  Window K/V is appended where it is computed — accepted
    positions hold exactly the K/V a step-at-a-time decode would have
    written; a rejected suffix's entries are simply re-written by the next
    window (``overwrite_from`` hides them from the contiguous attention
    read in the meantime, and paged reads causally mask them).  Returns
    ``(state', cache', out)`` with ``out`` a packed (B, 2 * (spec_k + 1) +
    1) int32 sync: emitted tokens | emission mask | finished flag.
    """
    active = state["active"]
    with jax.named_scope("decode"):
        window = jnp.concatenate([state["tokens"], state["draft"]], axis=1)
        lengths = jnp.where(active, state["draft_len"] + 1, 0)
        logits, new_cache = model_lib.prefill_chunk(
            cfg, params, {"tokens": window}, cache, state["positions"],
            block_tables=state.get("block_tables"), lengths=lengths,
            overwrite_from=state["positions"], all_logits=True)
    with jax.named_scope("sample"):
        res = verify_slots_keyed(
            logits, state["draft"], state["draft_len"],
            state["temperature"], state["top_k"], state["keys"],
            active=active, tokens0=state["tokens"][:, 0],
            positions=state["positions"], remaining=state["remaining"],
            eos=state["eos"], max_len=max_len, k_max=k_max)
        new_state = dict(state)  # block_tables / draft ride through
        new_state.update(
            tokens=res["last_token"][:, None],
            positions=res["positions"],
            active=res["active"],
            remaining=res["remaining"],
            keys=res["keys"],
        )
        out = jnp.concatenate([
            res["tokens"],
            res["emit"].astype(jnp.int32),
            res["done"].astype(jnp.int32)[:, None],
        ], axis=1)
    return new_state, new_cache, out


def make_spec_decode_step(cfg: ModelConfig, max_len: int, k_max: int = 64,
                          spec_k: int = 4) -> Callable:
    """Fused speculative verify + accept + finish-detect step: the
    drop-in replacement for ``make_decode_sample_step`` when the engine
    runs with prompt-lookup drafting (``out`` is the packed spec sync of
    ``_spec_verify_body`` instead of the (3, B) decode sync)."""

    def step(params, state: Dict[str, jax.Array], cache):
        return _spec_verify_body(cfg, max_len, k_max, spec_k,
                                 params, state, cache)

    return step


def make_engine_step(cfg: ModelConfig, max_len: int,
                     k_max: int = 64, spec_k: int = 0) -> Callable:
    """The unified mixed prefill/decode step: ONE jitted device dispatch per
    engine step, however many prefill cursors are in flight.

    Returns ``step(params, state, chunk, cache) -> (state', cache', out,
    chunk_logits)``.  ``chunk`` is the packed FCFS cursor frontier, slot-
    aligned at a static width W:

      tokens (B, W) int32 — row s holds slot s's next prompt-chunk tokens
      start  (B,)   int32 — each row's absolute start position
      length (B,)   int32 — valid tokens in the row (0 = slot has no cursor)

    The chunk advance runs first (masked appends via ``prefill_chunk``'s
    ``lengths`` path — rows with length 0 write nothing), then the decode+
    sample+finish body runs over the chunk-updated cache exactly as in
    ``make_decode_sample_step`` — mirroring the legacy engine's
    chunks-then-decode ordering within a step, so token streams are
    byte-identical to the per-chunk dispatch path.  ``out`` is the same
    packed (3, B) int32 sync; ``chunk_logits`` (B, vocab) holds each row's
    last-valid-position logits, from which the host samples a finishing
    cursor's first token (rows mid-prompt or without a cursor are garbage
    and ignored).  A prefilling slot is inactive in ``state``, so the
    decode half's ``update_mask`` keeps it from disturbing the freshly
    appended chunk K/V — same invariant as the split path.

    ``spec_k > 0`` swaps the decode half for the speculative verify body
    (``_spec_verify_body``): the frontier advance and the batched draft
    verification stay ONE fused dispatch, so speculation preserves the
    <= 2 dispatches/step bound; ``out`` becomes the packed spec sync.
    """

    def step(params, state: Dict[str, jax.Array], chunk: Dict[str, jax.Array],
             cache):
        with jax.named_scope("chunk_pass"):
            chunk_logits, cache = model_lib.prefill_chunk(
                cfg, params, {"tokens": chunk["tokens"]}, cache,
                chunk["start"], block_tables=state.get("block_tables"),
                lengths=chunk["length"])
        if spec_k > 0:
            new_state, new_cache, out = _spec_verify_body(
                cfg, max_len, k_max, spec_k, params, state, cache)
        else:
            new_state, new_cache, out = _decode_sample_body(
                cfg, max_len, k_max, params, state, cache)
        return new_state, new_cache, out, chunk_logits

    return step
