"""Decode-time caches: full KV, paged (block-pool) KV, ring-buffer (sliding
window) KV, recurrent state, and cross-attention memory.

A cache entry is a plain dict of arrays so the whole cache is a pytree that
rides through ``jax.jit`` / ``lax.scan``.  Two layouts for full-context
attention KV:

* **contiguous** — per-slot ``(batch, max_len, H, D)`` regions with explicit
  absolute key positions (``pos``; -1 = unfilled), which makes ring buffers,
  masking, and RoPE-at-write-time uniform across cache kinds.
* **paged** — a global block pool ``kp``/``vp`` of shape ``(num_blocks,
  block_size, H*D)`` shared by every slot, addressed through an int32
  block table ``(batch, max_blocks_per_slot)``.  Token at absolute position
  ``p`` of slot ``s`` lives at ``pool[table[s, p // bs], p % bs]``, so a
  slot only consumes the blocks its actual length needs instead of a
  worst-case ``max_len`` stripe.  Block 0 is a reserved garbage block:
  idle slots keep writing their frozen token there (static-shape decode),
  and freed slots point their whole table row back at it.  No ``pos``
  array is needed — gathered key index ``j`` *is* absolute position ``j``,
  and causal masking hides everything past the slot's length.

  The heads ride in the lanes (``H*D`` last), the layout the paged decode
  kernel reads, and the model stacks each pool over layers, ``(layers,
  num_blocks, block_size, H*D)``.  Every paged function here takes that
  stacked pool and a ``layer`` index and reads or writes it in place at
  ``[layer, block, offset]``: a pool is never sliced per layer nor
  reshaped, either of which is a copy of the whole pool on the TPU.

Sliding-window (``local_attn``) caches keep the ring layout in both modes:
their memory is already bounded by the window, so paging buys nothing.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

GARBAGE_BLOCK = 0  # pool block reserved for idle-slot writes; never allocated


def blocks_per_slot(max_len: int, block_size: int) -> int:
    """Block-table width needed to address ``max_len`` tokens."""
    return -(-max_len // block_size)


def default_num_blocks(batch: int, max_len: int, block_size: int) -> int:
    """Worst-case pool: every slot full, plus the reserved garbage block."""
    return batch * blocks_per_slot(max_len, block_size) + 1


def suggest_num_blocks(
    seq_lens, block_size: int, max_len: int, max_batch: int,
    concurrency: int = 0, q: float = 95.0,
) -> int:
    """Workload-sized pool suggestion (``--kv-num-blocks auto``).

    Instead of the worst case (every slot at ``max_len``), size the pool
    for the observed load: the ``q``-th percentile of the trace's total
    sequence lengths (prompt + decode budget, clamped to ``max_len``)
    times the expected number of concurrently live slots, plus one slack
    block per slot (bucketing / partial-tail rounding) and the reserved
    garbage block.  ``concurrency`` defaults to ``max_batch`` (the
    saturated case — exactly when pool sizing matters); pass an estimate
    from the trace (``serving.workload.estimate_concurrency``) for lighter
    open-loop load.

    The suggestion is clamped to ``[one worst-case request + garbage,
    worst case]``: below the floor a single long request could never
    finish, and above the ceiling the extra blocks are unreachable.  A
    pool sized this way can still overcommit on a bursty tail — pair it
    with ``preemption="recompute"`` so pressure preempts instead of
    failing.
    """
    lens = sorted(min(int(n), max_len) for n in seq_lens)
    if not lens:
        return default_num_blocks(max_batch, max_len, block_size)
    k = max(int(-(-len(lens) * q // 100)), 1) - 1
    p_len = lens[min(k, len(lens) - 1)]
    slots = min(max(int(concurrency) or max_batch, 1), max_batch)
    want = slots * (blocks_per_slot(p_len, block_size) + 1) + 1
    floor = blocks_per_slot(max_len, block_size) + 1
    return min(max(want, floor), default_num_blocks(max_batch, max_len, block_size))


# -- host-side block-pool bookkeeping (paged layout) -------------------------

_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3
_MASK64 = (1 << 64) - 1


def hash_token_blocks(tokens, block_size: int) -> List[int]:
    """Chained FNV-1a hash per *full* ``block_size`` block of ``tokens``.

    ``hashes[i]`` covers ``tokens[0 : (i+1) * block_size]`` — block ``i``'s
    hash folds in block ``i-1``'s, so a match at index ``i`` implies (up to
    hash collision) the whole token prefix matches, and with it the K/V
    content of pool blocks ``0..i`` (the prompt occupies absolute positions
    from 0, so block index determines the RoPE positions baked into the
    keys).  A trailing partial block is not hashed: it is still being
    written to (by the rest of the prompt or by decode) and must never be
    shared."""
    hashes: List[int] = []
    h = _FNV_OFFSET
    for i in range(len(tokens) // block_size):
        for t in tokens[i * block_size:(i + 1) * block_size]:
            h = ((h ^ (int(t) & _MASK64)) * _FNV_PRIME) & _MASK64
        hashes.append(h)
    return hashes


class BlockPool:
    """Host-side bookkeeping for the paged KV block pool: the LIFO free
    stack, plus — for block-level prefix caching — per-block refcounts, the
    ``hash -> block`` registry, and an LRU pool of evictable cached blocks.

    A block's lifecycle::

        free stack --allocate--> private (owned by one request)
          private --register--> shared (refcount = live readers)
          shared --lookup hit--> refcount += 1 (another reader)
          shared --freed by last reader--> evictable LRU (content intact)
          evictable --lookup hit--> shared again (refcount 1)
          evictable --pool pressure--> evicted: unregistered, reallocated
          private --freed--> free stack

    Blocks never sit in two places: ``free_stack``, ``evictable``, and the
    engine's live slot tables partition blocks ``1..num_blocks-1`` (block 0
    is the reserved garbage block).  A registered block becomes visible to
    ``lookup`` only once ``mark_ready`` confirms its K/V was fully written
    (a chunked prefill registers at admission but fills over many steps).
    """

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        # LIFO free stack over blocks 1..N-1 (0 = reserved garbage block)
        self.free_stack: List[int] = list(range(num_blocks - 1, 0, -1))
        self.refs: Dict[int, int] = {}        # registered block -> live readers
        self.block_of: Dict[int, int] = {}    # prefix hash -> block id
        self.hash_of: Dict[int, int] = {}     # block id -> prefix hash
        self.ready: set = set()               # registered blocks fully written
        self.evictable: "OrderedDict[int, None]" = OrderedDict()  # LRU
        self.evictions = 0
        # per-prefix-hash counters for tuning the evictable LRU:
        # hash -> [hits, misses, evictions].  A hit/miss is attributed by
        # ``lookup`` (``peek`` is a budget probe and never counts); an
        # eviction is attributed to the evicted block's hash.
        self.prefix_stats: Dict[int, List[int]] = {}

    def _stat(self, h: int) -> List[int]:
        return self.prefix_stats.setdefault(h, [0, 0, 0])

    @property
    def available(self) -> int:
        """Blocks an admission may claim: free plus evictable-cached."""
        return len(self.free_stack) + len(self.evictable)

    @property
    def in_use(self) -> int:
        """Blocks owned by live requests (excludes free and cached-idle)."""
        return max(self.num_blocks - 1, 0) - self.available

    def shard_accounting(self, n_devices: int) -> List[Dict[str, int]]:
        """Per-device block accounting for a tensor-parallel sharded pool.

        The pool shards the KV *feature* dims (heads x head_dim), never the
        block axis: every device holds its head-shard of every block, and the
        host-managed block tables index each device's pool identically.  So
        device ``d``'s pool mirrors the logical partition exactly — a block
        live for request ``r`` is live for ``r`` on every device (no
        cross-device aliasing), and ``free + in_use + evictable`` tiles the
        allocatable blocks ``1..num_blocks-1`` on each shard.
        """
        assert n_devices >= 1, n_devices
        allocatable = max(self.num_blocks - 1, 0)
        free = len(self.free_stack)
        evictable = len(self.evictable)
        in_use = allocatable - free - evictable
        view = {"free": free, "in_use": in_use, "evictable": evictable,
                "allocatable": allocatable}
        return [dict(view) for _ in range(n_devices)]

    def allocate(self, n: int) -> List[int]:
        """Pop ``n`` blocks, evicting LRU cached blocks under pressure."""
        assert n <= self.available, (
            f"allocate({n}) with only {self.available} blocks available")
        out = []
        for _ in range(n):
            if self.free_stack:
                out.append(self.free_stack.pop())
            else:
                out.append(self._evict_lru())
        return out

    def _evict_lru(self) -> int:
        blk, _ = self.evictable.popitem(last=False)
        # an evictable block by construction has no live readers
        assert self.refs.get(blk, 0) == 0, f"evicting live block {blk}"
        h = self.hash_of.get(blk)
        if h is not None:
            self._stat(h)[2] += 1
        self._unregister(blk)
        self.evictions += 1
        return blk

    def _unregister(self, blk: int) -> None:
        h = self.hash_of.pop(blk, None)
        if h is not None:
            del self.block_of[h]
        self.refs.pop(blk, None)
        self.ready.discard(blk)

    def register(self, h: int, blk: int) -> bool:
        """Claim hash ``h`` for ``blk`` (owner holds one ref; not yet
        ready).  False if the hash is already registered — the caller's
        block then simply stays private."""
        if h in self.block_of:
            return False
        self.block_of[h] = blk
        self.hash_of[blk] = h
        self.refs[blk] = 1
        return True

    def mark_ready(self, blk: int) -> None:
        """Make a registered block's content visible to ``lookup``."""
        if blk in self.hash_of:
            self.ready.add(blk)

    def peek(self, hashes: List[int]) -> int:
        """Conservative hit estimate for admission budgeting: leading
        blocks that are registered, ready, and currently referenced.  An
        evictable block is *not* counted — an interleaved allocation could
        evict it before the admission commits — so ``peek`` never
        overstates what ``lookup`` will find."""
        n = 0
        for h in hashes:
            blk = self.block_of.get(h)
            if blk is None or blk not in self.ready or self.refs.get(blk, 0) <= 0:
                break
            n += 1
        return n

    def lookup(self, hashes: List[int]) -> List[int]:
        """Longest ready cached prefix of ``hashes``; increfs each matched
        block (resurrecting evictable ones) and returns their ids in
        prefix order."""
        out: List[int] = []
        for h in hashes:
            blk = self.block_of.get(h)
            if blk is None or blk not in self.ready:
                self._stat(h)[1] += 1  # first break ends the usable prefix
                break
            if self.refs[blk] == 0:
                del self.evictable[blk]  # resurrected before eviction
            self.refs[blk] += 1
            self._stat(h)[0] += 1
            out.append(blk)
        return out

    def free(self, blocks: List[int]) -> None:
        """Return a request's blocks.  Shared blocks decref — the last
        reader parks the block (content and registration intact) on the
        evictable LRU; a registered-but-never-ready block (its request
        finished mid-prefill) is useless to future readers and is
        unregistered outright.  Private blocks go back on the free stack.

        Parking walks the table in *reverse* so a chain's tail blocks are
        LRU-oldest and evict first: lookups match a leading run of the
        chained hashes, so evicting a chain head would strand the rest of
        the cached chain as unmatchable dead weight, while evicting tails
        degrades a cached prefix gracefully from the right."""
        for blk in reversed(blocks):
            if blk in self.hash_of:
                self.refs[blk] -= 1
                assert self.refs[blk] >= 0, f"double free of block {blk}"
                if self.refs[blk] == 0:
                    if blk in self.ready:
                        self.evictable[blk] = None
                    else:
                        self._unregister(blk)
                        self.free_stack.append(blk)
            else:
                self.free_stack.append(blk)


def init_attn_cache(
    batch: int,
    max_len: int,
    n_kv: int,
    head_dim: int,
    dtype,
    window: int = 0,
) -> Dict:
    length = min(window, max_len) if window > 0 else max_len
    return {
        "k": jnp.zeros((batch, length, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, length, n_kv, head_dim), dtype),
        "pos": jnp.full((batch, length), -1, jnp.int32),  # per-row positions
        "ring": jnp.asarray(1 if (window > 0 and window < max_len) else 0, jnp.int32),
    }


def fill_attn_cache(cache: Dict, k: jax.Array, v: jax.Array, positions: jax.Array) -> Dict:
    """Write a full prefill's K/V (B, S, H, D) into the cache.

    For ring caches only the last ``L`` timesteps are kept.  ``positions`` is
    (B, S) but all rows are identical in the batched-serving setting; row 0 is
    used for the slot bookkeeping.
    """
    B, S = k.shape[:2]
    L = cache["k"].shape[1]
    pos_row = positions[0].astype(jnp.int32)
    if S >= L:
        k_tail, v_tail, p_tail = k[:, S - L:], v[:, S - L:], pos_row[S - L:]
    else:
        pad = L - S
        k_tail = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_tail = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        p_tail = jnp.pad(pos_row, (0, pad), constant_values=-1)
    slots = jnp.where(p_tail >= 0, p_tail % L, jnp.arange(L) % L)
    k_new = cache["k"].at[:, slots].set(k_tail)
    v_new = cache["v"].at[:, slots].set(v_tail)
    pos_new = cache["pos"].at[:, slots].set(p_tail[None, :])
    return {"k": k_new, "v": v_new, "pos": pos_new, "ring": cache["ring"]}


def update_attn_cache(cache: Dict, k_new: jax.Array, v_new: jax.Array,
                      positions: jax.Array,
                      update_mask: jax.Array = None) -> Dict:
    """Write one decoded token's K/V (B, 1, H, D) at per-row ``positions`` (B,).

    ``update_mask`` (B,) bool, when given, turns masked-off rows into no-op
    writes (the current cache content is written back).  The fused serving
    step uses it so idle and mid-prefill slots never clobber ring entries
    that a chunked prefill is concurrently filling.
    """
    B, L = cache["pos"].shape
    positions = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B,))
    slot = positions % L
    rows = jnp.arange(B)
    k_w, v_w, p_w = k_new[:, 0], v_new[:, 0], positions
    if update_mask is not None:
        m = update_mask.reshape(B, 1, 1)
        k_w = jnp.where(m, k_w, cache["k"][rows, slot])
        v_w = jnp.where(m, v_w, cache["v"][rows, slot])
        p_w = jnp.where(update_mask, p_w, cache["pos"][rows, slot])
    k = cache["k"].at[rows, slot].set(k_w)
    v = cache["v"].at[rows, slot].set(v_w)
    pos = cache["pos"].at[rows, slot].set(p_w)
    return {"k": k, "v": v, "pos": pos, "ring": cache["ring"]}


def append_attn_cache(cache: Dict, k: jax.Array, v: jax.Array,
                      positions: jax.Array,
                      valid: jax.Array = None) -> Dict:
    """Write a prompt chunk's K/V (B, C, H, D) at absolute ``positions``
    (B, C) into a contiguous or ring cache, preserving existing entries.

    Unlike ``fill_attn_cache`` (whole-prompt, fresh cache) this scatters
    only the chunk's own C columns, so chunk N lands next to chunks
    0..N-1.  A chunk longer than a ring keeps its tail (earlier chunk
    positions would be evicted immediately anyway).

    ``valid`` (B, C) bool, when given, turns masked-off entries into no-op
    writes (the current cache content is written back) — the unified
    mixed-batch step packs ragged per-slot chunks into one static-width
    batch, and pad columns must not clobber live entries."""
    B, C = k.shape[:2]
    L = cache["k"].shape[1]
    if C > L and valid is None:
        # ring shorter than the chunk: only the tail survives
        k, v, positions = k[:, C - L:], v[:, C - L:], positions[:, C - L:]
        C = L
    elif C > L:
        # ragged rows: keep each row's last <= L *valid* entries (a static
        # tail slice would drop live entries of rows shorter than C).
        # Chunk positions are consecutive within a row, so recomputing
        # them arithmetically keeps the gathered window's slots distinct
        # even where the gather index saturates at C - 1 (those entries
        # are masked invalid and write back the old cache values).
        n = valid.sum(axis=1, dtype=jnp.int32)            # (B,)
        base = jnp.maximum(n - L, 0)                      # window start
        idx = base[:, None] + jnp.arange(L, dtype=jnp.int32)[None]
        gat = jnp.minimum(idx, C - 1)[..., None, None]
        k = jnp.take_along_axis(k, gat, axis=1)
        v = jnp.take_along_axis(v, gat, axis=1)
        positions = positions[:, :1] + idx
        valid = idx < n[:, None]
        C = L
    rows = jnp.arange(B)[:, None]
    slots = positions % L
    k = k.astype(cache["k"].dtype)
    v = v.astype(cache["v"].dtype)
    if valid is not None:
        # within a row the C slots are distinct (consecutive positions mod
        # L with C <= L), so write-back of the old value is a sound no-op
        m = valid[..., None, None]
        k = jnp.where(m, k, cache["k"][rows, slots])
        v = jnp.where(m, v, cache["v"][rows, slots])
        positions = jnp.where(valid, positions, cache["pos"][rows, slots])
    return {
        "k": cache["k"].at[rows, slots].set(k),
        "v": cache["v"].at[rows, slots].set(v),
        "pos": cache["pos"].at[rows, slots].set(positions),
        "ring": cache["ring"],
    }


# -- paged (block-pool) attention cache --------------------------------------

def init_paged_attn_cache(
    num_blocks: int, block_size: int, n_kv: int, head_dim: int, dtype
) -> Dict:
    """One layer's pools, heads in the lanes: ``(num_blocks, block_size,
    n_kv * head_dim)``.  ``model.init_cache`` stacks them over layers."""
    shape = (num_blocks, block_size, n_kv * head_dim)
    return {"kp": jnp.zeros(shape, dtype), "vp": jnp.zeros(shape, dtype)}


def is_pool(path) -> bool:
    """Whether a cache-tree path (``jax.tree_util`` keys) ends at a paged
    pool leaf: shared by every slot, so it never takes a per-slot slice."""
    return getattr(path[-1], "key", None) in ("kp", "vp")


def _rows(x: jax.Array, pool: jax.Array) -> jax.Array:
    """(..., H, D) K or V as pool rows (..., H*D) in the pool's dtype."""
    return x.reshape(*x.shape[:-2], -1).astype(pool.dtype)


def fill_paged_cache(
    cache: Dict, k: jax.Array, v: jax.Array, positions: jax.Array,
    block_tables: jax.Array, layer,
) -> Dict:
    """Scatter a full prefill's K/V (B, S, H, D) into pool blocks of
    ``layer``.

    The prompt occupies absolute positions 0..S-1, so row ``b`` fills table
    entries ``0..ceil(S/bs)-1`` of ``block_tables[b]`` in order.  S is
    padded up to a whole number of blocks; the pad tail lands at positions
    >= S inside the last block and is hidden by causal masking.
    """
    del positions  # prompt positions are 0..S-1 by construction
    B, S = k.shape[:2]
    bs = cache["kp"].shape[2]
    nb = -(-S // bs)
    pad = nb * bs - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    idx = block_tables[:, :nb].reshape(-1)
    kb = _rows(k, cache["kp"]).reshape(B * nb, bs, -1)
    vb = _rows(v, cache["vp"]).reshape(B * nb, bs, -1)
    return {"kp": cache["kp"].at[layer, idx].set(kb),
            "vp": cache["vp"].at[layer, idx].set(vb)}


def update_paged_cache(
    cache: Dict, k_new: jax.Array, v_new: jax.Array, positions: jax.Array,
    block_tables: jax.Array, layer, update_mask: jax.Array = None,
) -> Dict:
    """Write one decoded token's K/V (B, 1, H, D) of ``layer`` at per-row
    ``positions``.

    Active slots always have the covering block allocated (admission
    reserves blocks for prompt + budget); idle slots' tables point at the
    garbage block, so their static-shape writes land in trash.
    ``update_mask`` (B,) bool additionally routes masked-off rows to the
    garbage block regardless of their table row — the engine arms a slot's
    real table row when it becomes decode-eligible, and only the chunked
    prefill may write its blocks before that.
    """
    B = block_tables.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B,))
    bs = cache["kp"].shape[2]
    rows = jnp.arange(B)
    blk = block_tables[rows, positions // bs]
    if update_mask is not None:
        blk = jnp.where(update_mask, blk, GARBAGE_BLOCK)
    off = positions % bs
    kp = cache["kp"].at[layer, blk, off].set(_rows(k_new[:, 0], cache["kp"]))
    vp = cache["vp"].at[layer, blk, off].set(_rows(v_new[:, 0], cache["vp"]))
    return {"kp": kp, "vp": vp}


def append_paged_cache(
    cache: Dict, k: jax.Array, v: jax.Array, positions: jax.Array,
    block_tables: jax.Array, layer, valid: jax.Array = None,
) -> Dict:
    """Scatter a prompt chunk's K/V (B, C, H, D) of ``layer`` at absolute
    ``positions`` (B, C) into pool blocks through the block tables.

    Unlike ``fill_paged_cache`` (whole prompt, block-aligned from position
    0) the chunk may start and end anywhere inside a block, so each token
    is routed individually: position ``p`` lands at
    ``pool[layer, table[b, p // bs], p % bs]``.

    ``valid`` (B, C) bool, when given, routes masked-off entries to the
    garbage block: the unified mixed-batch step packs ragged per-slot
    chunks into one static-width batch, and a pad column's position may
    exceed the row's allocation (or the whole row may be idle)."""
    bs = cache["kp"].shape[2]
    idx = positions // bs
    if valid is not None:
        # pad positions can run past the table width; clamp before gather
        idx = jnp.clip(idx, 0, block_tables.shape[1] - 1)
    blk = jnp.take_along_axis(block_tables, idx, axis=1)  # (B, C)
    if valid is not None:
        blk = jnp.where(valid, blk, GARBAGE_BLOCK)
    off = positions % bs
    kp = cache["kp"].at[layer, blk, off].set(_rows(k, cache["kp"]))
    vp = cache["vp"].at[layer, blk, off].set(_rows(v, cache["vp"]))
    return {"kp": kp, "vp": vp}


def gather_paged_kv(cache: Dict, block_tables: jax.Array, layer,
                    head_dim: int):
    """Materialize each row's pool blocks of ``layer`` as dense K/V plus key
    positions.

    Returns ``(k, v, k_positions)`` of shapes (B, M*bs, H, D) x2 and
    (B, M*bs) where M is the block-table width.  Gathered index ``j`` *is*
    absolute position ``j``; table entries beyond the row's allocation
    point at the garbage block, whose logical positions exceed every
    prompt position and are hidden by causal masking.  Used by the chunked
    prefill (chunk N attends to cached chunks 0..N-1 plus itself);
    decode-side reads go through the scalar-prefetch Pallas kernel
    instead, which never materializes this gather.  One gather reads the
    stacked pool at ``(layer, table)``, so no layer is sliced out first."""
    kp, vp = cache["kp"], cache["vp"]
    B, M = block_tables.shape
    bs = kp.shape[2]
    shape = (B, M * bs, kp.shape[3] // head_dim, head_dim)
    k = kp[layer, block_tables].reshape(shape)
    v = vp[layer, block_tables].reshape(shape)
    pos = jnp.broadcast_to(jnp.arange(M * bs, dtype=jnp.int32)[None], (B, M * bs))
    return k, v, pos


# -- recurrent states --------------------------------------------------------

def init_rglru_state(batch: int, width: int, conv_width: int, dtype) -> Dict:
    return {
        "h": jnp.zeros((batch, width), jnp.float32),
        "conv": jnp.zeros((batch, conv_width - 1, width), dtype),
    }


def init_mlstm_state(
    batch: int, heads: int, dk: int, dv: int, conv_width: int = 0, dtype=jnp.float32
) -> Dict:
    st = {
        "C": jnp.zeros((batch, heads, dk, dv), jnp.float32),
        "n": jnp.zeros((batch, heads, dk), jnp.float32),
        "m": jnp.full((batch, heads), -1e30, jnp.float32),
    }
    if conv_width > 0:
        st["conv"] = jnp.zeros((batch, conv_width - 1, heads * dv), dtype)
    return st


def init_slstm_state(batch: int, heads: int, dh: int, conv_width: int, dtype) -> Dict:
    return {
        "c": jnp.zeros((batch, heads, dh), jnp.float32),
        "n": jnp.zeros((batch, heads, dh), jnp.float32),
        "m": jnp.full((batch, heads, dh), -1e30, jnp.float32),
        "h": jnp.zeros((batch, heads, dh), jnp.float32),
        "conv": jnp.zeros((batch, conv_width - 1, heads * dh), dtype),
    }


# -- per-block cache constructors -------------------------------------------

def init_block_cache(
    cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype,
    *, layout: str = "contiguous", block_size: int = 16,
    num_blocks: int = 0,
) -> Dict:
    hd = cfg.resolved_head_dim
    if kind == "ffn":
        return {}
    if kind == "attn":
        if layout == "paged":
            n = num_blocks or default_num_blocks(batch, max_len, block_size)
            return init_paged_attn_cache(n, block_size, cfg.num_kv_heads, hd, dtype)
        return init_attn_cache(batch, max_len, cfg.num_kv_heads, hd, dtype)
    if kind == "local_attn":
        return init_attn_cache(
            batch, max_len, cfg.num_kv_heads, hd, dtype, window=cfg.sliding_window
        )
    if kind == "rglru":
        return init_rglru_state(
            batch, cfg.resolved_lru_width, cfg.rglru_conv_width, dtype
        )
    if kind == "mlstm":
        w = int(cfg.d_model * cfg.mlstm_proj_factor)
        h = cfg.resolved_rec_heads
        return init_mlstm_state(batch, h, w // h, w // h, cfg.rglru_conv_width, dtype)
    if kind == "slstm":
        h = cfg.resolved_rec_heads
        return init_slstm_state(batch, h, cfg.d_model // h, cfg.rglru_conv_width, dtype)
    raise ValueError(f"no cache for block kind {kind!r}")


def cache_bytes(cache) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
