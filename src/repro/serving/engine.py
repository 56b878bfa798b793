"""Device-resident continuous-batching serving engine (vLLM-lite).

One fused jitted step (decode + per-slot sampling + finish detection, one
host sync per step), a paged block-pool KV cache with a host-managed free
stack, batched multi-slot admission, Sarathi-style chunked prefill, and
block-level prefix caching (``prefix_cache=True``): full prompt blocks are
content-hashed and shared read-only across requests through refcounts, so
a request whose prefix is already resident skips straight to its first
non-cached block.  With ``preemption="recompute"`` the engine stays
correct under pool *overcommit*: blocks are reserved lazily and grown as
decodes cross block boundaries, and when the pool runs dry the newest
admitted request (never the head-of-line) is preempted — its private
blocks freed, the request parked — and later re-admitted by recomputing
its prompt + generated-so-far prefix through the chunked-prefill path.
Sampling is scheduling-invariant (per-request PRNG chains, restored
exactly on resume), so every layout/scheduling/preemption combination
emits byte-identical token streams for the same seed.

The full design guide — request lifecycle, pool/refcount bookkeeping, and
the invariants the test suites hold — lives in ``docs/serving.md``.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.energy import PowerMonitor
from repro.core.trace import Spans
from repro.models import cache as cache_lib
from repro.models import model as model_lib
from repro.models.config import ModelConfig
from repro.sharding import partition as partition_lib
from repro.sharding import rules as rules_lib
from repro.serving.sampling import SamplingParams, sample
from repro.serving.step import (init_slot_state, invalidate_slot,
                                make_decode_sample_step, make_engine_step,
                                make_spec_decode_step, donating_jit)

_RING = 64  # host-side token ring buffer depth (tokens per slot per flush)


def prompt_lookup_draft(hist: List[int], k: int,
                        ngram_max: int = 3) -> List[int]:
    """Draft-free speculative drafting by prompt lookup: propose the ``k``
    tokens that followed an earlier occurrence of the stream's trailing
    n-gram (longest n first, ``ngram_max`` down to 1).

    Among the occurrences of the longest matching n-gram, the most recent
    one with a full ``k``-token continuation wins (recent context best
    predicts a loop or a template being re-instantiated); if every match
    sits too close to the end for ``k`` tokens, the longest available
    continuation wins instead.  Returns ``[]`` when nothing matches — the
    verify step then degrades to a plain one-token decode.  Draft content
    only ever affects how many tokens a verify dispatch may emit, never
    *which* tokens, so this heuristic is pure performance tuning."""
    L = len(hist)
    if k <= 0 or L < 2:
        return []
    for n in range(min(ngram_max, L - 1), 0, -1):
        pat = hist[L - n:]
        best = None  # (continuation length, match index)
        for i in range(L - n - 1, -1, -1):
            if hist[i:i + n] == pat:
                c = min(k, L - i - n)
                if c == k:
                    best = (c, i)
                    break
                if best is None or c > best[0]:
                    best = (c, i)
        if best is not None:
            c, i = best
            return hist[i + n:i + n + c]
    return []


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    # default_factory: a shared default instance would alias any future
    # mutable sampling fields across every request that omitted params
    params: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    # filled by the engine, on time.perf_counter: receipt (the HTTP
    # handler's entry; the submission where no front end stamps it),
    # submission, admission (slot and cursor taken), the first dispatch
    # that carries any of its prompt, first token, finish
    recv_time: float = 0.0
    submit_time: float = 0.0
    admit_time: float = 0.0
    first_chunk_time: float = 0.0
    first_token_time: float = 0.0
    finish_time: float = 0.0
    output_tokens: List[int] = dataclasses.field(default_factory=list)
    truncated: bool = False
    joules: float = 0.0
    # preemption priority: order of *first* admission (kept across
    # re-admissions so the oldest in-flight request — the head-of-line —
    # is stable and can never be picked as a victim); -1 = never admitted
    admit_seq: int = -1
    preemptions: int = 0
    # memoized (plen, block hashes) — the prompt and its bucket never
    # change, and admission may probe a backpressured request every step
    _hash_cache: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def ttft_s(self) -> float:
        return self.first_token_time - self.submit_time

    @property
    def ttlt_s(self) -> float:
        return self.finish_time - self.submit_time

    @property
    def tpot_s(self) -> float:
        # a request that emitted <= 1 token has no inter-token interval,
        # and one that never started/finished has meaningless timestamps —
        # report 0.0 instead of dividing into garbage
        n = len(self.output_tokens) - 1
        if n <= 0 or self.finish_time <= self.first_token_time:
            return 0.0
        return (self.finish_time - self.first_token_time) / n


def _percentile(xs: List[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]); 0.0 for an empty list."""
    if not xs:
        return 0.0
    ys = sorted(xs)
    k = max(int(np.ceil(len(ys) * q / 100.0)), 1) - 1
    return ys[min(k, len(ys) - 1)]


@dataclasses.dataclass
class _PrefillCursor:
    """Per-slot chunked-prefill progress (the third scheduler state)."""
    req: Request
    tokens: np.ndarray            # (plen,) bucketed, left-padded prompt
    plen: int                     # bucketed prompt length
    next: int = 0                 # next prompt position to prefill
    tables_np: Optional[np.ndarray] = None  # (max_blocks,) paged table row
    # prefix cache: (end position, block) pairs this cursor registered;
    # each block is marked ready once the cursor passes its end
    pending_ready: List = dataclasses.field(default_factory=list)
    # preemption recompute: number of tokens the request had already
    # emitted when it was preempted.  0 = a fresh admission (sample the
    # first token from the final chunk's logits); > 0 = a resumed request
    # (the next token is already known — re-arm the slot instead)
    resume_n: int = 0


class ServingEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_batch: int = 4,
        max_len: int = 512,
        prompt_bucket: int = 32,
        seed: int = 0,
        monitor: Optional[PowerMonitor] = None,
        top_k_max: int = 64,
        cache_layout: str = "contiguous",
        kv_block_size: int = 16,
        kv_num_blocks: int = 0,
        prefill_chunk: int = 0,
        prefill_budget: int = 0,
        prefix_cache: bool = False,
        preemption: str = "off",
        unified_step: bool = True,
        pad_side: str = "left",
        speculative: str = "off",
        spec_tokens: int = 4,
        mesh=None,
        shard_rules=None,
        param_axes=None,
    ):
        assert cache_layout in ("contiguous", "paged"), cache_layout
        assert preemption in ("off", "recompute"), preemption
        assert pad_side in ("left", "right"), pad_side
        if speculative not in ("off", "lookup"):
            raise ValueError(
                f"speculative must be 'off' or 'lookup', got {speculative!r}")
        self.speculative = speculative
        self.spec_k = int(spec_tokens) if speculative != "off" else 0
        if speculative != "off":
            if self.spec_k < 1:
                raise ValueError(
                    f"--spec-tokens={spec_tokens} must be >= 1 when "
                    f"--speculative is on")
            bad = sorted({k for k in cfg.blocks() if k not in ("attn", "ffn")})
            if bad or cfg.is_encdec or cfg.num_vision_tokens:
                raise ValueError(
                    f"speculative='lookup' relies on rejected draft "
                    f"suffixes being re-writable cache positions, which "
                    f"only full-attention KV supports; {cfg.name!r} "
                    f"carries per-slot state that cannot rewind "
                    f"({', '.join(bad) or 'cross-attention/vision prefix'})")
        if pad_side == "right" and (cfg.is_encdec or cfg.num_vision_tokens):
            raise ValueError(
                f"pad_side='right' realigns the bucketed prompt row so "
                f"variable-length suffixes of a shared prefix land on the "
                f"same block boundaries; {cfg.name!r} carries an "
                f"encoder/vision prefix whose position bookkeeping assumes "
                f"the whole padded row is computed")
        self.pad_side = pad_side
        if preemption != "off":
            if cache_layout != "paged":
                raise ValueError(
                    "preemption requires cache_layout='paged': only a block "
                    "pool can run dry mid-decode and reclaim a victim's "
                    "blocks")
            if cfg.is_encdec or cfg.num_vision_tokens:
                raise ValueError(
                    f"preemption='recompute' replays a request's prompt + "
                    f"generated tokens through the chunked-prefill path; "
                    f"{cfg.name!r} carries an encoder/vision prefix whose "
                    f"replay length would differ from the original "
                    f"admission")
        self.preemption = preemption
        if prefix_cache:
            if cache_layout != "paged":
                raise ValueError(
                    "prefix_cache requires cache_layout='paged': only pool "
                    "blocks can be shared read-only across requests")
            bad = sorted({k for k in cfg.blocks() if k not in ("attn", "ffn")})
            if bad or cfg.is_encdec or cfg.num_vision_tokens:
                raise ValueError(
                    f"prefix_cache shares paged full-attention KV blocks "
                    f"only; {cfg.name!r} carries per-slot state that a "
                    f"skipped prefill would leave stale "
                    f"({', '.join(bad) or 'cross-attention/vision prefix'})")
        self.prefix_cache = prefix_cache
        self.cfg = cfg
        # tensor-parallel serving: an engine-owned mesh makes every jitted
        # trace/dispatch run under ``use_mesh`` (see ``_counted``), so the
        # model code's logical-axis ``shard`` constraints resolve against
        # it.  Heads/FFN shard over the ``tp`` axis; slot state replicates,
        # keeping the packed per-step host sync one transfer.
        self._mesh = mesh
        self._rules = shard_rules if shard_rules is not None else (
            rules_lib.TP_SERVE_RULES if mesh is not None else None)
        if mesh is not None and param_axes is not None:
            params = jax.device_put(params, partition_lib.param_shardings(
                param_axes, params, mesh, self._rules))
        elif mesh is not None:
            params = jax.device_put(params, partition_lib.replicated(mesh))
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.prompt_bucket = prompt_bucket
        self.layout = cache_layout
        # chunked prefill: 0 disables (whole-prompt admission); the budget
        # is prompt tokens of chunk work per engine step (default: one
        # chunk).  Clamped to >= one chunk — a smaller budget would never
        # fit the head cursor's next chunk and stall its request forever.
        self.chunk = max(int(prefill_chunk), 0)
        self.chunk_budget = max(int(prefill_budget) or self.chunk, self.chunk)
        # static bound on per-request top-k inside the fused step (a full
        # per-slot vocab sort would dominate it); requests asking for more
        # are clamped — consistently, first token included
        self.top_k_max = min(top_k_max, cfg.vocab_size)
        # per-request sampling keys derive from this by uid (fold_in), so
        # streams do not depend on admission scheduling
        self._base_key = jax.random.PRNGKey(seed)
        dtype = jnp.dtype(cfg.dtype)
        self._dtype = dtype

        # paged block-pool bookkeeping (host-managed free stack)
        self.block_size = kv_block_size
        self.max_blocks_per_slot = cache_lib.blocks_per_slot(max_len, kv_block_size)
        if cache_layout == "paged":
            self.num_blocks = kv_num_blocks or cache_lib.default_num_blocks(
                max_batch, max_len, kv_block_size)
            min_blocks = self.max_blocks_per_slot + 1
            if self.num_blocks < min_blocks:
                raise ValueError(
                    f"--kv-num-blocks={self.num_blocks} is too small: "
                    f"max_len={max_len} at block size {kv_block_size} needs "
                    f"{self.max_blocks_per_slot} blocks for one worst-case "
                    f"request, plus the reserved garbage block 0 — pass "
                    f"--kv-num-blocks >= {min_blocks} (or 0 for the "
                    f"worst-case default of "
                    f"{cache_lib.default_num_blocks(max_batch, max_len, kv_block_size)})")
            self._pool = cache_lib.BlockPool(self.num_blocks)
        else:
            self.num_blocks = 0
            self._pool = cache_lib.BlockPool(1)  # empty pool, no free blocks
        self._slot_blocks: List[List[int]] = [[] for _ in range(max_batch)]
        self.peak_blocks_in_use = 0
        # prefix-cache counters (reported by latency_summary)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_blocks_reused = 0
        self.prefill_tokens_skipped = 0
        # preemption: parked requests (sorted by admit_seq — re-admitted
        # oldest-first, and always ahead of the waiting queue), counters,
        # the host mirror of each decoding slot's next write position
        # (drives decode-time block growth), and per-step pool-occupancy
        # samples for the latency_summary percentiles
        self._preempted: List[Request] = []
        self._admit_seq = 0
        self.preemptions = 0
        self.recompute_tokens = 0
        self._next_pos = np.zeros(max_batch, np.int64)
        self._occ_samples: List[float] = []
        # device-dispatch accounting: every jitted callable is wrapped by
        # ``_counted`` so ``_dispatches`` counts its launches (the per-step
        # deltas hold the <= 2 programs/step bound); ``_launches`` counts
        # every device call the engine makes: those programs, and the eager
        # writes, uploads, PRNG folds and first-token draws outside them
        # (a library call counts once).  Its per-step deltas, in the step
        # records, feed the dispatches_per_step percentiles.
        self._dispatches = 0
        self._dispatch_samples: List[int] = []
        self._launches = 0
        self._admissions = 0
        # host spans and per-step records (core/trace.py)
        self.spans = Spans()
        # decode-side economics: device-emitted decode tokens over the
        # dispatches that carried them (speculation pushes the ratio past
        # the batch size), plus drafter accounting for the accept rate
        self._decode_tokens = 0
        self._decode_dispatches = 0
        self._drafted_tokens = 0
        self._accepted_tokens = 0
        self._spec_verifies = 0
        # host mirror of each slot's uploaded draft length: block growth
        # must cover the verify window's cache writes, not just next_pos
        self._draft_len_host = np.zeros(max_batch, np.int64)
        self._steps_done = 0
        self._steps_t0: Optional[float] = None
        self._steps_t1 = 0.0
        # PRNG chain fast-forward for resume: n rides as a traced scalar,
        # so restoring a chain is one dispatch regardless of how many
        # tokens the parked request had emitted
        self._advance_chain = self._counted(jax.jit(
            lambda key, n: jax.lax.fori_loop(
                0, n, lambda _, k: jax.random.split(k)[1], key)))

        self.cache = model_lib.init_cache(
            cfg, max_batch, max_len, dtype, layout=cache_layout,
            block_size=kv_block_size, num_blocks=self.num_blocks)
        if mesh is not None:
            # KV shards live on their device: heads-sharded pool/cache rows
            # (block axes never shard — the host-managed tables index every
            # device's pool identically)
            self.cache = jax.device_put(
                self.cache, partition_lib.cache_shardings(
                    self.cache, mesh, num_kv_heads=cfg.num_kv_heads))
        self.slots: List[Optional[Request]] = [None] * max_batch
        # chunked-prefill cursors: _cursors[s] is set while slot s is in the
        # *prefilling* state; _prefill_order is the FCFS service order
        self._cursors: List[Optional[_PrefillCursor]] = [None] * max_batch
        self._prefill_order: List[int] = []
        # slot rows admitted this step, reset in one batched dispatch
        self._pending_reset: List[int] = []
        self.queue: deque = deque()
        self.finished: List[Request] = []
        self._uid = 0

        # device-resident scheduler state + fused step (cache/state donated
        # into the step on backends that support it)
        self._state = init_slot_state(
            max_batch, seed=seed + 1,
            max_blocks=self.max_blocks_per_slot if cache_layout == "paged" else 0,
            spec_k=self.spec_k)
        if mesh is not None:
            # per-slot sampling/PRNG state replicates across the mesh so the
            # packed host sync stays a single fully-replicated transfer
            self._state = jax.device_put(
                self._state, partition_lib.replicated(mesh))
        if self.spec_k:
            self._step = self._counted(donating_jit(
                make_spec_decode_step(cfg, max_len, k_max=self.top_k_max,
                                      spec_k=self.spec_k), (1, 2)))
        else:
            self._step = self._counted(donating_jit(
                make_decode_sample_step(cfg, max_len, k_max=self.top_k_max),
                (1, 2)))
        # unified mixed prefill/decode step: one dispatch advances the whole
        # packed cursor frontier AND decodes every armed slot.  Not taken
        # for encoder-decoder / vision configs (their prefix embeddings ride
        # per-chunk) — those fall back to the per-chunk dispatch path.
        self.unified = (bool(unified_step) and self.chunk > 0
                        and not cfg.is_encdec and not cfg.num_vision_tokens)
        if self.unified:
            # static packed-frontier width: the budget bounds per-step chunk
            # work, and no cursor can hold more than max_len - 1 tokens
            self._chunk_width = min(self.chunk_budget, max(max_len - 1, 1))
            self._unified = self._counted(donating_jit(
                make_engine_step(cfg, max_len, k_max=self.top_k_max,
                                 spec_k=self.spec_k), (1, 3)))
        # admission prefill: the n-row cache template is built *inside* the
        # jitted function (from the traced batch shape), so its zeros are
        # materialized on demand by XLA instead of living as per-batch-size
        # device-resident templates on the host
        self._prefill = self._counted(jax.jit(
            lambda p, batch: model_lib.prefill(
                cfg, p, batch, self._admit_template(batch))))
        self._prefill_paged = self._counted(jax.jit(
            lambda p, batch, live_cache, tables: model_lib.prefill(
                cfg, p, batch,
                self._graft_pools(self._admit_template(batch), live_cache),
                block_tables=tables)))

        # chunked prefill: one chunk of one slot against the live cache.
        # ``start`` and ``slot`` ride as traced scalars, so the executable
        # is compiled once per chunk *width* and replayed for every offset
        # and slot.  The slot's row is sliced out, the chunk is applied
        # (appending K/V mid-prompt), and the row is scattered back; pool
        # leaves pass through whole — the append already wrote into them
        # through the block table.
        def _chunk_body(p, batch, start, slots, cache, tables, lengths=None):
            part = self._slice_slots(cache, slots)
            logits, part = model_lib.prefill_chunk(
                cfg, p, batch, part, start, block_tables=tables,
                lengths=lengths)
            return logits, self._merge_admitted(cache, part, slots)

        self._chunk_contig = self._counted(donating_jit(
            lambda p, batch, start, slots, cache, lengths=None: _chunk_body(
                p, batch, start, slots, cache, None, lengths), (4,)))
        self._chunk_paged = self._counted(donating_jit(_chunk_body, (4,)))
        # admission-time reset of one slot's cache row to init values (the
        # unchunked path resets implicitly by overwriting the whole row at
        # prefill; a chunk only writes its own span, so stale positions /
        # recurrent state from the previous occupant must be cleared first)
        self._reset_rows = self._counted(donating_jit(
            lambda cache, slots: self._merge_admitted(
                cache,
                self._graft_pools(
                    self._admit_template({"tokens": jnp.zeros(
                        (slots.shape[0], 1), jnp.int32)}), cache),
                slots), (0,)))

        # host-side token ring buffer: (max_batch, _RING) plus fill counts
        self._ring = np.zeros((max_batch, _RING), np.int32)
        self._ring_n = np.zeros(max_batch, np.int64)

        # energy attribution
        self.monitor = monitor
        self._win_t0: Optional[float] = None
        self._win_tokens: Dict[int, int] = {}
        self.attributed_joules = 0.0

        # token streaming: called from inside the per-step host sync with
        # (uid, new_tokens, finished) the moment tokens leave the device —
        # before the ring buffer defers them — so an HTTP front-end can
        # stream SSE chunks with per-step latency (serving/server.py)
        self.stream_hook: Optional[Callable[[int, List[int], bool], None]] = None

    def _counted(self, fn):
        """Wrap a jitted callable so every launch bumps ``_dispatches`` —
        and, on a tensor-parallel engine, runs under the engine's mesh so
        both tracing and replay see the sharding rules."""

        def run(*args):
            self._dispatches += 1
            self._launches += 1
            if self._mesh is not None:
                with rules_lib.use_mesh(self._mesh, self._rules):
                    return fn(*args)
            return fn(*args)

        return run

    def _put(self, x, dtype=None):
        """Upload a host array for a device call: one launch."""
        self._launches += 1
        return jnp.asarray(x, dtype)

    def _set_table_row(self, slot: int, tables_np: np.ndarray) -> None:
        """Point ``slot``'s device block-table row at its pool blocks (one
        eager write, outside any jitted program)."""
        self._launches += 1
        self._state["block_tables"] = (
            self._state["block_tables"].at[slot].set(tables_np))

    def _invalidate(self, slot: int) -> None:
        """Mask ``slot``'s device row (``invalidate_slot``'s eager writes)."""
        self._launches += 3 if "block_tables" in self._state else 2
        self._state = invalidate_slot(self._state, slot,
                                      garbage_block=cache_lib.GARBAGE_BLOCK)

    def _decode_rows(self) -> int:
        """Slots the step's decode half serves: live and not prefilling."""
        return sum(req is not None and cur is None
                   for req, cur in zip(self.slots, self._cursors))

    # -- public API -----------------------------------------------------------
    def submit(self, prompt: np.ndarray,
               params: Optional[SamplingParams] = None) -> int:
        params = params or SamplingParams()
        if params.top_k > self.top_k_max:
            params = dataclasses.replace(params, top_k=self.top_k_max)
        req = Request(uid=self._uid, prompt=np.asarray(prompt, np.int32),
                      params=params)
        req.submit_time = req.recv_time = time.perf_counter()
        self._uid += 1
        self.queue.append(req)
        return req.uid

    @property
    def busy(self) -> bool:
        return (bool(self.queue) or bool(self._preempted)
                or any(s is not None for s in self.slots))

    def step(self) -> bool:
        """One admit + chunk + decode round; returns True if work was done.

        On the unified path the chunk advance and the decode are one fused
        dispatch (``make_engine_step``): the FCFS frontier is *picked* on
        the host first (no device work), block growth/preemption runs, and
        then a single launch advances every cursor row and decodes every
        armed slot.  The legacy path dispatches one chunk per cursor
        quantum plus a separate decode step."""
        if not self.busy:
            return False
        span = self.spans.span
        d0, l0 = self._dispatches, self._launches
        with self.spans.step(self._steps_done) as rec:
            a0 = self._admissions
            with span("engine.admit"):
                self._admit()
                self._flush_resets()  # one batched row-reset dispatch
            rec.args["admitted"] = self._admissions - a0
            if self.unified:
                with span("engine.pick"):
                    frontier = self._pick_frontier()
                if self.spec_k:
                    with span("engine.draft"):
                        self._arm_drafts()
                with span("engine.grow"):
                    self._grow_decode_blocks()
                rec.args.update(
                    chunk_rows=len(frontier),
                    chunk_tokens=sum(c for _, _, c in frontier),
                    decode_rows=self._decode_rows())
                self._unified_once(frontier)
            else:
                with span("engine.chunks"):
                    self._advance_chunks()
                if self.spec_k:
                    with span("engine.draft"):
                        self._arm_drafts()
                with span("engine.grow"):
                    self._grow_decode_blocks()
                rec.args["decode_rows"] = self._decode_rows()
                self._decode_once()
            if self.layout == "paged":
                self._occ_samples.append(
                    self._pool.in_use / max(self.num_blocks - 1, 1))
            rec.launches = self._launches - l0
        if self._steps_t0 is None:
            self._steps_t0 = rec.t0
        self._steps_t1 = rec.t1
        self._steps_done += 1
        self._dispatch_samples.append(self._dispatches - d0)
        return True

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Drive until queue + slots drain (or step budget); returns finished."""
        steps = 0
        while self.busy and steps < max_steps:
            self.step()
            steps += 1
        self.flush()
        return self.finished

    def flush(self) -> None:
        """Drain host-side buffers: ring-buffered tokens of still-running
        requests (so ``output_tokens`` is complete even on a step-budget
        exit) and the open energy-attribution window."""
        for slot in range(self.max_batch):
            self._flush_ring(slot)
        self._flush_energy()

    def attach_monitor(self, monitor: PowerMonitor) -> None:
        """Start attributing the monitor's energy to requests from now on."""
        self.monitor = monitor
        self._win_t0 = None
        self._win_tokens = {}


    # -- internals --------------------------------------------------------------
    def _flush_resets(self) -> None:
        """Run the step's deferred admission row resets as one dispatch."""
        if self._pending_reset:
            slots, self._pending_reset = self._pending_reset, []
            self.cache = self._reset_rows(
                self.cache, self._put(slots, jnp.int32))

    def _bucketed(self, n: int) -> int:
        b = self.prompt_bucket
        return min(self.max_len - 1, ((n + b - 1) // b) * b)

    def _blocks_for(self, plen: int, max_new: int) -> int:
        """Pool blocks reserved at admission.

        With preemption off the full prompt + decode budget is reserved up
        front, so the fused step's append never has to allocate — but a
        pool smaller than the worst case then refuses load it could have
        served (most requests stop early).  Under ``preemption=
        "recompute"`` reservation is *lazy*: only the prompt plus the
        first decode write position, with later blocks grown on demand by
        ``_grow_decode_blocks`` (preempting a victim when the pool runs
        dry)."""
        budget = 1 if self.preemption != "off" else max_new
        tokens = min(plen + budget, self.max_len)
        return min(cache_lib.blocks_per_slot(tokens, self.block_size),
                   self.max_blocks_per_slot)

    @property
    def _free_blocks(self) -> List[int]:
        """The pool's LIFO free stack (read-only view for tests/metrics)."""
        return self._pool.free_stack

    @property
    def blocks_in_use(self) -> int:
        """Blocks owned by live requests.  Evictable cached blocks (kept
        only for future prefix hits, reclaimed on pressure) don't count."""
        if self.layout != "paged":
            return 0
        return self._pool.in_use

    # -- prefix cache ------------------------------------------------------------
    def _padded_prompt(self, req: Request, plen: int) -> np.ndarray:
        """The bucketed token row admission actually prefills (prompts
        longer than the bucket keep their newest context).

        ``pad_side="left"`` (default) zero-pads on the left, so the real
        tokens always end at the bucket boundary.  ``pad_side="right"``
        puts the content first: variable-length prompts sharing a prefix
        then hash to the *same* block chain regardless of their suffix
        length, so the prefix cache can share their blocks — at the cost
        of the row carrying a true span shorter than the bucket (pad
        positions past the span are never computed)."""
        use = req.prompt
        if len(use) > plen:
            use = use[-plen:]
            req.truncated = True
        toks = np.zeros(plen, np.int32)
        if self.pad_side == "left":
            toks[-len(use):] = use
        else:
            toks[:len(use)] = use
        return toks

    def _true_span(self, req: Request, plen: int) -> int:
        """Positions of the bucketed row that are actually computed: the
        whole row when left-padded, only the content prefix when
        right-padded."""
        if self.pad_side == "left":
            return plen
        return min(len(req.prompt), plen)

    def _lookup_width(self, span: int) -> int:
        """Cacheable-prefix cap: the block holding the last prompt position
        is always recomputed, so the final chunk's logits (which seed the
        first sampled token) exist even on a full-prefix hit."""
        return (span - 1) // self.block_size

    def _hashes_for(self, req: Request, plen: int) -> List[int]:
        """The request's full-block hash chain, memoized on the request —
        a backpressured queue head is re-probed every engine step."""
        if req._hash_cache is None or req._hash_cache[0] != plen:
            req._hash_cache = (plen, cache_lib.hash_token_blocks(
                self._padded_prompt(req, plen), self.block_size))
        return req._hash_cache[1]

    def _peek_hit(self, req: Request, plen: int) -> int:
        """Conservative admission-budget estimate of reusable blocks."""
        if not self.prefix_cache:
            return 0
        hashes = self._hashes_for(req, plen)
        span = self._true_span(req, plen)
        return self._pool.peek(hashes[:self._lookup_width(span)])

    def _admit(self) -> None:
        # preempted requests re-admit first, oldest admission first; a
        # parked head that does not fit blocks the waiting queue too —
        # new arrivals must not starve a request that already holds
        # emitted tokens (head-of-line progress guarantees drain)
        while self._preempted:
            if not self._try_readmit():
                return
        while self.queue:
            free = [s for s in range(self.max_batch) if self.slots[s] is None]
            if not free:
                return
            # the head of the queue defines the prompt bucket; batch every
            # queued request sharing it, in FCFS order, up to the free slots
            # and (paged) the free-stack budget.  A head that doesn't fit in
            # the pool blocks admission entirely — strict FCFS backpressure.
            plen = self._bucketed(len(self.queue[0].prompt))
            picked: List[Request] = []
            blocks_reserved = 0
            for req in self.queue:
                if len(picked) == len(free):
                    break
                if self._bucketed(len(req.prompt)) != plen:
                    continue
                if self.layout == "paged":
                    # prefix hits shrink the new-block need; _peek_hit is
                    # conservative (never counts a block an interleaved
                    # allocation could evict), so commit-time lookup can
                    # only find more hits than budgeted here, never fewer
                    span = self._true_span(req, plen)
                    nb = (self._blocks_for(span, req.params.max_new_tokens)
                          - self._peek_hit(req, plen))
                    if blocks_reserved + nb > self._pool.available:
                        break
                    blocks_reserved += nb
                picked.append(req)
            if not picked:
                return  # pool backpressure: wait for finishes to free blocks
            picked_ids = {id(r) for r in picked}
            self.queue = deque(
                r for r in self.queue if id(r) not in picked_ids)
            now = time.perf_counter()
            for req in picked:
                req.admit_seq = self._admit_seq
                self._admit_seq += 1
                req.admit_time = now
            self._admissions += len(picked)
            slots_for = free[:len(picked)]
            if self.chunk > 0:
                self._admit_chunked(picked, slots_for, plen)
            elif self.pad_side == "right":
                # right-padded rows carry per-request true spans, which the
                # batched whole-row prefill can't express; admit through the
                # cursor path and run each span as one masked chunk
                self._admit_right_unchunked(picked, slots_for, plen)
            else:
                self._admit_batch(picked, slots_for, plen)

    def _admit_batch(self, reqs: List[Request], slots_for: List[int],
                     plen: int) -> None:
        """One batched prefill for ``reqs`` (all bucketed to ``plen``).

        With the prefix cache on, requests whose hashed prompt prefix is
        already resident are peeled off first and admitted through the
        suffix-only path (``_admit_prefix_hit``); the rest prefill cold in
        one batched call and register their full prompt blocks for future
        sharers.  Two same-prefix requests inside one cold batch register
        first-come-first-served — the loser's blocks simply stay private."""
        padded = [self._padded_prompt(r, plen) for r in reqs]
        hashes: List[Optional[List[int]]] = [None] * len(reqs)
        if self.prefix_cache:
            keep: List[int] = []
            for i, (req, slot) in enumerate(zip(reqs, slots_for)):
                hashes[i] = self._hashes_for(req, plen)
                hit = self._pool.lookup(hashes[i][:self._lookup_width(plen)])
                self.prefix_lookups += 1
                if hit:
                    self._admit_prefix_hit(req, slot, plen, padded[i],
                                           hashes[i], hit)
                else:
                    keep.append(i)
            if not keep:
                return
            reqs = [reqs[i] for i in keep]
            slots_for = [slots_for[i] for i in keep]
            padded = [padded[i] for i in keep]
            hashes = [hashes[i] for i in keep]
        n = len(reqs)
        now = time.perf_counter()
        for req in reqs:
            req.first_chunk_time = req.first_chunk_time or now
        batch = {"tokens": self._put(np.stack(padded))}
        if self.cfg.is_encdec:
            self._launches += 1
            batch["enc_embeds"] = jnp.zeros(
                (n, max(plen // 2, 1), self.cfg.d_model), self._dtype)
        if self.cfg.num_vision_tokens:
            self._launches += 1
            batch["vision_embeds"] = jnp.zeros(
                (n, self.cfg.num_vision_tokens, self.cfg.d_model), self._dtype)

        if self.layout == "paged":
            tables_np = np.zeros((n, self.max_blocks_per_slot), np.int32)
            for r, (req, slot) in enumerate(zip(reqs, slots_for)):
                nb = self._blocks_for(plen, req.params.max_new_tokens)
                blocks = self._pool.allocate(nb)
                tables_np[r, :nb] = blocks
                self._slot_blocks[slot] = blocks
                if self.prefix_cache:
                    # whole-prompt prefill lands below; the blocks are
                    # ready the moment any later admission could read them
                    for i in range(plen // self.block_size):
                        if self._pool.register(hashes[r][i], blocks[i]):
                            self._pool.mark_ready(blocks[i])
            self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                          self.blocks_in_use)
            tables = self._put(tables_np)
            logits, filled = self._prefill_paged(
                self.params, batch, self.cache, tables)
        else:
            logits, filled = self._prefill(self.params, batch)
        self._launches += 1  # the eager merge, counted once
        self.cache = self._merge_admitted(self.cache, filled, slots_for)

        for r, (req, slot) in enumerate(zip(reqs, slots_for)):
            self.slots[slot] = req
            self._start_decoding(
                req, slot, plen, logits[r:r + 1],
                tables_np[r] if self.layout == "paged" else None)

    def _claim_prefix_blocks(self, req: Request, slot: int, span: int,
                             hashes: List[int], hit: List[int],
                             nb: Optional[int] = None):
        """Commit one admission's pool blocks: reused prefix blocks first
        (already increfed by ``lookup``), freshly allocated ones after, in
        table order.  Full prompt blocks past the hit are registered for
        future sharers (not yet ready — the caller fills them).  ``span``
        is the computed extent of the row (== the bucket when left-padded;
        the content prefix when right-padded).  Returns ``(tables_np,
        start, pending)``: the slot's table row, the first position
        prefill must compute, and the (end, block) pairs to mark ready as
        the fill passes them.  ``nb`` overrides the block count (recompute
        re-admission covers prompt + generated tokens)."""
        h = len(hit)
        if nb is None:
            nb = self._blocks_for(span, req.params.max_new_tokens)
        blocks = hit + self._pool.allocate(nb - h)
        tables_np = np.zeros(self.max_blocks_per_slot, np.int32)
        tables_np[:nb] = blocks
        self._slot_blocks[slot] = blocks
        pending = []
        for i in range(h, span // self.block_size):
            if self._pool.register(hashes[i], blocks[i]):
                pending.append(((i + 1) * self.block_size, blocks[i]))
        if h:
            self.prefix_hits += 1
        self.prefix_blocks_reused += h
        start = h * self.block_size
        self.prefill_tokens_skipped += start
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)
        return tables_np, start, pending

    def _admit_prefix_hit(self, req: Request, slot: int, plen: int,
                          toks: np.ndarray, hashes: List[int],
                          hit: List[int]) -> None:
        """Unchunked admission of a request with resident prefix blocks:
        only the suffix (first non-cached block onward) is prefilled, as a
        single chunk against the live pool — the reused blocks feed the
        suffix's attention through the block table, and the partial tail
        block is recomputed privately so decode writes never touch a
        shared block."""
        tables_np, start, pending = self._claim_prefix_blocks(
            req, slot, plen, hashes, hit)
        self.slots[slot] = req
        cur = _PrefillCursor(req=req, tokens=toks, plen=plen, next=start,
                             tables_np=tables_np)
        logits = self._run_chunk(slot, cur, plen - start)
        for _, blk in pending:  # suffix fully written: publish its blocks
            self._pool.mark_ready(blk)
        self._start_decoding(req, slot, plen, logits, tables_np)

    def _admit_chunked(self, reqs: List[Request], slots_for: List[int],
                       plen: int) -> None:
        """Admission with chunked prefill: reserve the slot (and pool
        blocks) and set up a chunk cursor; no prompt work happens yet, so
        admission never stalls in-flight decodes.  The slot's cache row is
        reset to init values — chunk writes only cover the prompt span,
        and stale positions / recurrent state from the previous occupant
        would otherwise leak into the chunk's attention and state."""
        for req, slot in zip(reqs, slots_for):
            toks = self._padded_prompt(req, plen)
            span = self._true_span(req, plen)
            tables_np = None
            start = 0
            pending: List = []
            if self.layout == "paged" and self.prefix_cache:
                # reuse resident prefix blocks: the cursor starts at the
                # first non-cached block and its chunks attend to the
                # shared blocks through the block table
                hashes = self._hashes_for(req, plen)
                hit = self._pool.lookup(hashes[:self._lookup_width(span)])
                self.prefix_lookups += 1
                tables_np, start, pending = self._claim_prefix_blocks(
                    req, slot, span, hashes, hit)
            elif self.layout == "paged":
                nb = self._blocks_for(span, req.params.max_new_tokens)
                blocks = self._pool.allocate(nb)
                tables_np = np.zeros(self.max_blocks_per_slot, np.int32)
                tables_np[:nb] = blocks
                self._slot_blocks[slot] = blocks
            self.slots[slot] = req
            self._cursors[slot] = _PrefillCursor(
                req=req, tokens=toks, plen=span, next=start,
                tables_np=tables_np, pending_ready=pending)
            self._prefill_order.append(slot)
            if tables_np is not None:
                # arm the table row now: the unified step's packed chunk
                # routes through state["block_tables"] (inert on the
                # per-chunk path — tables ride as an explicit argument)
                self._set_table_row(slot, tables_np)
        if self.layout == "paged":
            self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                          self.blocks_in_use)
        # defer the row resets: every admission of the step lands in ONE
        # batched _reset_rows dispatch (flushed before any chunk runs),
        # keeping the unified path at <= 2 dispatches per engine step
        self._pending_reset.extend(slots_for)

    def _admit_right_unchunked(self, reqs: List[Request],
                               slots_for: List[int], plen: int) -> None:
        """Unchunked admission of right-padded rows: reserve through the
        chunked path (which already handles per-request true spans and
        prefix hits), then immediately run each request's whole span as
        one masked chunk padded to the bucket width — so the request is
        decode-eligible in the same step, matching the left-padded
        unchunked admission's semantics, while every bucket still
        compiles a single chunk executable."""
        self._admit_chunked(reqs, slots_for, plen)
        self._flush_resets()  # the spans run now, not at the step's flush
        for slot in list(slots_for):
            cur = self._cursors[slot]
            if cur is None:
                continue
            c = cur.plen - cur.next
            logits = self._run_chunk(slot, cur, c, pad_to=plen)
            cur.next = cur.plen
            while cur.pending_ready:
                self._pool.mark_ready(cur.pending_ready.pop(0)[1])
            self._prefill_order.remove(slot)
            self._cursors[slot] = None
            self._start_decoding(cur.req, slot, cur.plen, logits,
                                 cur.tables_np)

    def _advance_chunks(self) -> None:
        """Spend the per-step prefill budget on cursors, FCFS.  A cursor's
        next chunk runs only if it fits the remaining budget, bounding the
        prompt work any single engine step (and therefore any in-flight
        decode token) waits on."""
        budget = self.chunk_budget
        while budget > 0 and self._prefill_order:
            slot = self._prefill_order[0]
            cur = self._cursors[slot]
            c = min(self.chunk, cur.plen - cur.next)
            if c > budget:
                return
            budget -= c
            logits = self._run_chunk(slot, cur, c)
            cur.next += c
            # publish registered blocks the cursor has fully written, so
            # later admissions can share this still-prefilling prompt
            while cur.pending_ready and cur.pending_ready[0][0] <= cur.next:
                self._pool.mark_ready(cur.pending_ready.pop(0)[1])
            if cur.next == cur.plen:  # final chunk landed: decode-eligible
                self._prefill_order.pop(0)
                self._cursors[slot] = None
                if cur.resume_n > 0:
                    self._resume_decoding(cur.req, slot, cur.plen,
                                          cur.resume_n, cur.tables_np)
                else:
                    self._start_decoding(cur.req, slot, cur.plen, logits,
                                         cur.tables_np)

    def _run_chunk(self, slot: int, cur: _PrefillCursor, c: int,
                   pad_to: int = 0):
        """One chunk of one slot's prompt through the jitted chunk step.

        ``pad_to > c`` zero-pads the token row to a static width and
        threads the true length through the masked-append path (used by
        right-padded unchunked admission, so every bucket width compiles
        one executable regardless of each prompt's true span)."""
        cur.req.first_chunk_time = (cur.req.first_chunk_time
                                    or time.perf_counter())
        toks = cur.tokens[cur.next:cur.next + c]
        lengths = None
        if pad_to > c:
            toks = np.concatenate([toks, np.zeros(pad_to - c, np.int32)])
            lengths = self._put([c], jnp.int32)
        batch = {"tokens": self._put(toks[None])}
        start = cur.next
        nv = self.cfg.num_vision_tokens
        if self.cfg.is_encdec:
            self._launches += 1
            batch["enc_embeds"] = jnp.zeros(
                (1, max(cur.plen // 2, 1), self.cfg.d_model), self._dtype)
        if nv:
            # the VLM patch prefix rides with chunk 0; later chunks shift
            # past it — mirroring the unchunked prefill's concatenation
            if start == 0:
                self._launches += 1
                batch["vision_embeds"] = jnp.zeros(
                    (1, nv, self.cfg.d_model), self._dtype)
            else:
                start += nv
        slots = self._put([slot], jnp.int32)
        if self.layout == "paged":
            logits, self.cache = self._chunk_paged(
                self.params, batch, start, slots, self.cache,
                self._put(cur.tables_np[None]), lengths)
        else:
            logits, self.cache = self._chunk_contig(
                self.params, batch, start, slots, self.cache, lengths)
        return logits

    # -- unified mixed prefill/decode step ---------------------------------------
    def _pick_frontier(self) -> List[tuple]:
        """The FCFS cursor frontier one unified step will advance: exactly
        the chunks ``_advance_chunks`` would run, but *picked* instead of
        dispatched, with consecutive quanta of the same head cursor
        coalesced into one packed row (their positions are consecutive, so
        one masked row of width <= budget covers them).  Returns
        ``[(slot, cursor, n_tokens)]``; budget semantics are identical to
        the legacy loop — a head chunk that doesn't fit the remaining
        budget stops the scan."""
        budget = self.chunk_budget
        frontier: List[tuple] = []
        for slot in self._prefill_order:
            cur = self._cursors[slot]
            take = 0
            while True:
                c = min(self.chunk, cur.plen - cur.next - take)
                if c <= 0 or c > budget:
                    break
                take += c
                budget -= c
            if take:
                frontier.append((slot, cur, take))
            if cur.next + take < cur.plen:
                break  # head cursor unfinished: no budget flows past it
        return frontier

    def _unified_once(self, frontier: List[tuple]) -> None:
        """One fused device dispatch: advance the packed frontier and run
        decode+sample+finish for every armed slot.  With no frontier this
        degrades to the plain decode step (still one dispatch)."""
        if not frontier:
            self._decode_once()
            return
        span = self.spans.span
        with span("engine.launch"):
            now = time.perf_counter()
            W = self._chunk_width
            tokens = np.zeros((self.max_batch, W), np.int32)
            starts = np.zeros(self.max_batch, np.int32)
            lens = np.zeros(self.max_batch, np.int32)
            for slot, cur, c in frontier:
                tokens[slot, :c] = cur.tokens[cur.next:cur.next + c]
                starts[slot] = cur.next
                lens[slot] = c
                cur.req.first_chunk_time = cur.req.first_chunk_time or now
            chunk = {"tokens": self._put(tokens), "start": self._put(starts),
                     "length": self._put(lens)}
            self._state, self.cache, out, chunk_logits = self._unified(
                self.params, self._state, chunk, self.cache)
        with span("engine.sync"):
            # the single packed host<->device sync of the step
            out_np, logits_np = jax.device_get((out, chunk_logits))
        with span("engine.emit"):
            for slot, cur, c in frontier:
                if self._cursors[slot] is not cur:
                    # the slot was preempted between frontier pick and
                    # dispatch (_grow_decode_blocks ran dry): its table row
                    # was pointed at the garbage block before the launch,
                    # so the chunk's writes landed in trash — drop the
                    # stale advance
                    continue
                cur.next += c
                while (cur.pending_ready
                       and cur.pending_ready[0][0] <= cur.next):
                    self._pool.mark_ready(cur.pending_ready.pop(0)[1])
                if cur.next == cur.plen:  # final chunk: decode-eligible
                    self._prefill_order.remove(slot)
                    self._cursors[slot] = None
                    if cur.resume_n > 0:
                        self._resume_decoding(cur.req, slot, cur.plen,
                                              cur.resume_n, cur.tables_np)
                    else:
                        self._start_decoding(cur.req, slot, cur.plen,
                                             logits_np[slot:slot + 1],
                                             cur.tables_np)
            self._process_out(out_np)

    # -- speculative decoding ----------------------------------------------------
    def _arm_drafts(self) -> None:
        """Upload each decoding slot's prompt-lookup draft for this step's
        verify dispatch.  Drafting is pure host work over tokens the
        request already owns (prompt + emitted, including the unflushed
        ring tail), so it costs no device dispatch.  The draft length is
        clamped so the verify window — which writes K/V at the last
        emitted token's pending position plus one per draft token, and may
        emit up to ``draft_len + 1`` tokens — can never outrun the
        request's new-token budget or the cache length bound.  Both
        arrays are rebuilt from zero every step, so a slot that was
        re-armed, preempted, or finished can never replay a stale draft."""
        K = self.spec_k
        draft_np = np.zeros((self.max_batch, K), np.int32)
        self._draft_len_host[:] = 0
        for slot in range(self.max_batch):
            req = self.slots[slot]
            if req is None or self._cursors[slot] is not None:
                continue
            n_ring = int(self._ring_n[slot])
            emitted = len(req.output_tokens) + n_ring
            p = int(self._next_pos[slot])
            cap = min(K, req.params.max_new_tokens - emitted - 1,
                      self.max_len - 2 - p)
            if cap <= 0:
                continue
            hist = ([int(t) for t in req.prompt] + req.output_tokens
                    + [int(t) for t in self._ring[slot, :n_ring]])
            d = prompt_lookup_draft(hist, cap)
            if not d:
                continue
            draft_np[slot, :len(d)] = d
            self._draft_len_host[slot] = len(d)
            self._drafted_tokens += len(d)
        self._state["draft"] = self._put(draft_np)
        self._state["draft_len"] = self._put(
            self._draft_len_host.astype(np.int32))

    def _process_spec_out(self, out_np: np.ndarray) -> None:
        """Host-side bookkeeping of one verify's packed (B, 2*(k+1)+1)
        output: per slot, the emission mask is a prefix of the window
        (the acceptance chain only ever shuts off), so the first ``n``
        token columns are the slot's emitted tokens in stream order."""
        K1 = self.spec_k + 1
        tokens = out_np[:, :K1]
        emit = out_np[:, K1:2 * K1]
        done = out_np[:, 2 * K1]
        any_emit = False
        for slot in range(self.max_batch):
            req = self.slots[slot]
            n = int(emit[slot].sum())
            if req is None or n == 0:
                continue  # idle slot, or freed on the host side
            any_emit = True
            self._spec_verifies += 1
            self._accepted_tokens += n - 1
            self._decode_tokens += n
            for i in range(n):
                self._next_pos[slot] += 1  # the device wrote K/V there
                rn = int(self._ring_n[slot])
                self._ring[slot, rn] = tokens[slot, i]
                self._ring_n[slot] = rn + 1
                if rn + 1 == _RING:
                    self._flush_ring(slot)
                self._count_token(req)
            self._notify_stream(req, [int(t) for t in tokens[slot, :n]])
            if done[slot]:
                self._finish(slot)
            elif self.preemption != "off":
                self._rollback_spec_blocks(slot)
        if any_emit:
            self._decode_dispatches += 1

    def _rollback_spec_blocks(self, slot: int) -> None:
        """Free the lazily grown blocks a rejected draft suffix no longer
        needs (``preemption="recompute"`` only — with up-front reservation
        the window never grew past the admission grant).  The rejected
        K/V itself is never rolled back: entries within the next window's
        span are overwritten before they are read, entries beyond it sit
        at positions above every query and are causally masked, and a
        freed block handed to another request exposes only positions that
        request has not reached yet."""
        keep = int(self._next_pos[slot]) // self.block_size + 1
        blocks = self._slot_blocks[slot]
        if len(blocks) <= keep:
            return
        extra = blocks[keep:]
        self._slot_blocks[slot] = blocks[:keep]
        self._pool.free(extra)
        self._launches += 1
        self._state["block_tables"] = (
            self._state["block_tables"].at[slot, keep:keep + len(extra)].set(
                cache_lib.GARBAGE_BLOCK))

    # -- preemption + recompute ------------------------------------------------
    def _grow_decode_blocks(self) -> None:
        """Lazy block growth (``preemption="recompute"`` only): before the
        fused step runs, every decoding slot whose next write position
        crosses into an unallocated block gets one.  When the pool is dry
        (free stack and evictable LRU both empty) the newest-admitted
        in-flight request is preempted — possibly the growing slot itself
        — and its reclaimed blocks satisfy the growth.  The head-of-line
        (oldest ``admit_seq``) is never a victim, so it always progresses
        and the engine is guaranteed to drain."""
        if self.layout != "paged" or self.preemption == "off":
            return
        bs = self.block_size
        for slot in range(self.max_batch):
            req = self.slots[slot]
            if req is None or self._cursors[slot] is not None:
                continue
            # the verify window writes draft_len positions past next_pos,
            # so speculative growth must cover the whole window up front
            need = (int(self._next_pos[slot])
                    + int(self._draft_len_host[slot])) // bs + 1
            while len(self._slot_blocks[slot]) < need:
                if self.slots[slot] is not req:
                    break  # the growing slot itself was preempted
                if self._pool.available == 0:
                    victim = self._pick_victim()
                    assert victim is not None, (
                        "pool dry with no preemptible victim — the pool "
                        "is smaller than one worst-case request")
                    self._preempt(victim)
                    continue
                blk = self._pool.allocate(1)[0]
                self._slot_blocks[slot].append(blk)
                idx = len(self._slot_blocks[slot]) - 1
                self._launches += 1
                self._state["block_tables"] = (
                    self._state["block_tables"].at[slot, idx].set(blk))
            self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                          self.blocks_in_use)

    def _pick_victim(self) -> Optional[int]:
        """LIFO victim selection: the newest-admitted in-flight request,
        never the head-of-line (the oldest)."""
        live = [s for s in range(self.max_batch) if self.slots[s] is not None]
        if len(live) < 2:
            return None
        head = min(live, key=lambda s: self.slots[s].admit_seq)
        return max((s for s in live if s != head),
                   key=lambda s: self.slots[s].admit_seq)

    def _preempt(self, slot: int) -> None:
        """Park one in-flight request: flush its emitted tokens, reclaim
        its blocks (shared prefix blocks only decref — a block with other
        live readers is never reclaimed), mask the device row, and queue
        it for recompute re-admission."""
        req = self.slots[slot]
        assert req is not None
        assert any(r is not None and r.admit_seq < req.admit_seq
                   for r in self.slots), "head-of-line request preempted"
        self._flush_ring(slot)
        if self._cursors[slot] is not None:  # parked mid-prefill
            self._cursors[slot] = None
            self._prefill_order.remove(slot)
        self.slots[slot] = None
        self._invalidate(slot)
        if self._slot_blocks[slot]:
            self._pool.free(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
        self.preemptions += 1
        req.preemptions += 1
        self._preempted.append(req)
        self._preempted.sort(key=lambda r: r.admit_seq)

    def _try_readmit(self) -> bool:
        """Re-admit the oldest parked request if a slot and enough blocks
        are available: its prompt plus every token generated before the
        preemption are recomputed through the chunked-prefill path (one
        chunk in unchunked mode), then the slot is re-armed exactly where
        it left off.  Resident shared-prefix blocks are reused like any
        admission, so a preempted sharer recomputes only its private
        suffix."""
        req = self._preempted[0]
        free = [s for s in range(self.max_batch) if self.slots[s] is None]
        if not free:
            return False
        plen = self._bucketed(len(req.prompt))
        span = self._true_span(req, plen)
        n = len(req.output_tokens)
        total = span + max(n - 1, 0)  # positions to recompute: 0..total-1
        nb = min(cache_lib.blocks_per_slot(min(total + 1, self.max_len),
                                           self.block_size),
                 self.max_blocks_per_slot)
        if nb - self._peek_hit(req, plen) > self._pool.available:
            return False
        self._preempted.pop(0)
        self._admissions += 1
        slot = free[0]
        toks = self._padded_prompt(req, plen)[:span]
        if n > 1:
            toks = np.concatenate(
                [toks, np.asarray(req.output_tokens[:n - 1], np.int32)])
        start = 0
        pending: List = []
        if self.prefix_cache:
            hashes = self._hashes_for(req, plen)
            hit = self._pool.lookup(hashes[:self._lookup_width(span)])
            self.prefix_lookups += 1
            tables_np, start, pending = self._claim_prefix_blocks(
                req, slot, span, hashes, hit, nb=nb)
        else:
            blocks = self._pool.allocate(nb)
            tables_np = np.zeros(self.max_blocks_per_slot, np.int32)
            tables_np[:nb] = blocks
            self._slot_blocks[slot] = blocks
            self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                          self.blocks_in_use)
        self.slots[slot] = req
        self.recompute_tokens += total - start
        # the slot row may have held another request since: stale positions
        # / recurrent state must be cleared before the replay scatters into
        # it (deferred into the step's single batched reset dispatch)
        self._pending_reset.append(slot)
        cur = _PrefillCursor(req=req, tokens=toks, plen=total, next=start,
                             tables_np=tables_np, pending_ready=pending,
                             resume_n=n)
        if self.chunk > 0:
            self._cursors[slot] = cur
            self._prefill_order.append(slot)
            # arm the table row for the unified step's packed chunk (the
            # per-chunk path passes tables explicitly; harmless there)
            self._set_table_row(slot, tables_np)
        else:
            self._flush_resets()  # the replay chunk runs right now
            logits = self._run_chunk(slot, cur, total - start)
            for _, blk in pending:
                self._pool.mark_ready(blk)
            if n > 0:
                self._resume_decoding(req, slot, total, n, tables_np)
            else:
                self._start_decoding(req, slot, total, logits, tables_np)
        return True

    def _resume_decoding(self, req: Request, slot: int, position: int,
                         n: int, tables_np: Optional[np.ndarray]) -> None:
        """Re-arm a recomputed slot exactly where the preemption cut it
        off.  The next input token is the last one emitted before parking
        (its K/V lands on the next fused step, like any decode write), so
        no logits are consumed and nothing is re-sampled.  The per-slot
        PRNG chain is restored to the same point — the chain seed split
        once per device-emitted token (``n - 1`` of them: the first token
        came from the host-side admission draw) — so the resumed stream
        is byte-identical to an unpreempted run."""
        rk = jax.random.fold_in(self._base_key, req.uid)
        key = self._advance_chain(jax.random.fold_in(rk, 1), n - 1)
        self._launches += 2  # the two folds
        remaining = req.params.max_new_tokens - n
        # a live preempted request always has budget and headroom left
        # (finish flags are processed before preemption can run); guard
        # anyway so a corrupt resume finishes instead of decoding forever
        active = remaining > 0 and position < self.max_len - 1
        self._write_slot_state(
            slot, token=req.output_tokens[-1], position=position,
            remaining=remaining, params=req.params, active=active, key=key)
        if tables_np is not None:
            self._set_table_row(slot, tables_np)
        self._next_pos[slot] = position
        if not active:
            self._finish(slot)

    def _start_decoding(self, req: Request, slot: int, plen: int,
                        logits, tables_np: Optional[np.ndarray]) -> None:
        """Transition a slot to the decoding state: sample the first token
        from the prefill's last-position logits and arm the device row.
        Shared by unchunked admission and final-chunk completion."""
        rk = jax.random.fold_in(self._base_key, req.uid)
        first = int(sample(logits, req.params, jax.random.fold_in(rk, 0))[0])
        self._launches += 4  # three folds and the draw
        req.first_token_time = time.perf_counter()
        req.output_tokens.append(first)
        self._count_token(req)
        self._notify_stream(req, [first])

        done = (req.params.max_new_tokens <= 1
                or (req.params.eos_token >= 0
                    and first == req.params.eos_token)
                or plen >= self.max_len - 1)
        self._write_slot_state(
            slot, token=first, position=plen,
            remaining=req.params.max_new_tokens - 1,
            params=req.params, active=not done,
            key=jax.random.fold_in(rk, 1))
        self._next_pos[slot] = plen
        if self.layout == "paged" and tables_np is not None:
            self._set_table_row(slot, tables_np)
        if done:
            self._finish(slot)

    def _write_slot_state(self, slot: int, *, token: int, position: int,
                          remaining: int, params: SamplingParams,
                          active: bool, key) -> None:
        """Admission-time write of one slot's device state (eight eager
        device writes)."""
        self._launches += 8
        s = self._state
        s["tokens"] = s["tokens"].at[slot, 0].set(token)
        s["positions"] = s["positions"].at[slot].set(position)
        s["remaining"] = s["remaining"].at[slot].set(remaining)
        s["temperature"] = s["temperature"].at[slot].set(params.temperature)
        s["top_k"] = s["top_k"].at[slot].set(params.top_k)
        s["eos"] = s["eos"].at[slot].set(params.eos_token)
        s["active"] = s["active"].at[slot].set(active)
        s["keys"] = s["keys"].at[slot].set(key)

    def _admit_template(self, batch: Dict) -> Dict:
        """Fresh prefill cache for an admitted batch (traced under jit)."""
        n = batch["tokens"].shape[0]
        return model_lib.init_cache(
            self.cfg, n, self.max_len, self._dtype, layout=self.layout,
            block_size=self.block_size,
            # dummy 1-block pools; the live pools are grafted in per admit
            num_blocks=1 if self.layout == "paged" else 0)

    @staticmethod
    def _graft_pools(tmpl: Dict, live_cache: Dict) -> Dict:
        """Swap the template's dummy pools for the live shared pools."""

        def pick(path, t, live):
            return live if cache_lib.is_pool(path) else t

        return jax.tree_util.tree_map_with_path(pick, tmpl, live_cache)

    def _slice_slots(self, cache, slots):
        """Gather ``slots`` rows of the live cache into an n-row cache.

        Mirror image of ``_merge_admitted``: pool leaves (``kp``/``vp``)
        are shared across slots and pass through whole; per-slot leaves
        take the batch-axis gather (axis 1 under ``groups``, 0 under
        ``rest``); scalar bookkeeping passes through."""

        def pick(path, leaf):
            if cache_lib.is_pool(path):
                return leaf
            axis = 1 if path[0].key == "groups" else 0
            if leaf.ndim <= axis:
                return leaf
            return jnp.take(leaf, slots, axis=axis)

        return jax.tree_util.tree_map_with_path(pick, cache)

    def _merge_admitted(self, full_cache, part_cache, slots_for: List[int]):
        """Write a freshly prefilled ``len(slots_for)``-row cache into the
        decode cache: row ``r`` lands in slot ``slots_for[r]``.

        Pool leaves (``kp``/``vp``) already *are* the updated shared pools
        (prefill scattered into them through the block tables) and pass
        through; per-slot leaves land in one scatter per leaf (not one
        copy per admitted row).  Leaves under ``groups`` carry a leading
        scan-group axis, so the batch dim is axis 1 there and axis 0
        under ``rest``.
        """
        slots = jnp.asarray(slots_for, jnp.int32)

        def merge(path, full, part):
            if cache_lib.is_pool(path):
                return part
            axis = 1 if path[0].key == "groups" else 0
            if full.ndim <= axis:
                return full  # scalars / shared bookkeeping (e.g. `ring`)
            part = part.astype(full.dtype)
            if axis == 0:
                return full.at[slots].set(part)
            return full.at[:, slots].set(part)

        return jax.tree_util.tree_map_with_path(merge, full_cache, part_cache)

    def _decode_once(self) -> None:
        # prefilling slots (open cursor) are not decode-eligible: their
        # first token is sampled only once the final chunk lands
        if not self._decode_rows():
            return
        span = self.spans.span
        with span("engine.launch"):
            self._state, self.cache, out = self._step(
                self.params, self._state, self.cache)
        with span("engine.sync"):
            out_np = np.asarray(out)  # single host sync
        with span("engine.emit"):
            self._process_out(out_np)

    def _process_out(self, out_np: np.ndarray) -> None:
        """Route one step's packed device sync to the right parser: the
        (3, B) decode sync or the (B, 2*(k+1)+1) speculative verify sync."""
        if self.spec_k:
            self._process_spec_out(out_np)
        else:
            self._process_decode_out(out_np)

    def _process_decode_out(self, out_np: np.ndarray) -> None:
        """Host-side bookkeeping of one decode's packed (3, B) output
        (shared by the split and unified step paths)."""
        tokens, done, emitted = out_np[0], out_np[1], out_np[2]
        any_emit = False
        for slot in np.nonzero(emitted)[0]:
            req = self.slots[slot]
            if req is None:
                continue  # stale flag for a slot freed on the host side
            any_emit = True
            self._decode_tokens += 1
            self._next_pos[slot] += 1  # the device wrote K/V there
            n = int(self._ring_n[slot])
            self._ring[slot, n] = tokens[slot]
            self._ring_n[slot] = n + 1
            if n + 1 == _RING:
                self._flush_ring(slot)
            self._count_token(req)
            self._notify_stream(req, [int(tokens[slot])])
            if done[slot]:
                self._finish(slot)
        if any_emit:
            self._decode_dispatches += 1

    def _notify_stream(self, req: Request, tokens: List[int],
                       finished: bool = False) -> None:
        """Push freshly emitted tokens (and the finish edge) to the
        streaming hook.  Called at emission time — recompute re-admission
        replays tokens through the *prefill* path, so a preempted request
        never re-notifies tokens it already streamed."""
        if self.stream_hook is not None:
            self.stream_hook(req.uid, tokens, finished)

    def _flush_ring(self, slot: int) -> None:
        n = int(self._ring_n[slot])
        req = self.slots[slot]
        if req is not None and n:
            req.output_tokens.extend(int(t) for t in self._ring[slot, :n])
        self._ring_n[slot] = 0

    def _finish(self, slot: int) -> None:
        req = self.slots[slot]
        if req is None:
            return
        if self._cursors[slot] is not None:  # abandoned mid-prefill
            self._cursors[slot] = None
            self._prefill_order.remove(slot)
        self._flush_ring(slot)
        req.finish_time = time.perf_counter()
        self.finished.append(req)
        self.slots[slot] = None
        # mask the device row (active already cleared by the fused step for
        # decode finishes; admission-time finishes need it explicitly) and
        # point the paged table row at the garbage block so idle writes
        # land in trash
        self._invalidate(slot)
        if self.layout == "paged" and self._slot_blocks[slot]:
            # return the slot's blocks: shared blocks decref and park on
            # the evictable LRU; private ones hit the free stack
            self._pool.free(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
        self._flush_energy()
        # after _flush_energy: the finish notification carries the
        # request's final joules share with it
        self._notify_stream(req, [], finished=True)

    # -- memory accounting -------------------------------------------------------
    def kv_bytes_in_use(self, peak: bool = False) -> int:
        """Full-context attention KV bytes the engine actually holds.

        Paged: blocks in use (or the high-water mark with ``peak=True``)
        times per-block bytes across the paged layers.  Contiguous: the
        worst-case ``(max_batch, max_len)`` stripes — allocated up front
        regardless of load, which is exactly what paging removes.
        """
        if self.layout == "paged":
            blocks = self.peak_blocks_in_use if peak else self.blocks_in_use
            return self._n_attn_layers * blocks * self.block_size * self._kv_tok_bytes
        return self.kv_bytes_worst_case

    @property
    def kv_bytes_worst_case(self) -> int:
        """Contiguous-layout footprint: every slot at ``max_len``."""
        return self._n_attn_layers * self.max_batch * self.max_len * self._kv_tok_bytes

    @property
    def _n_attn_layers(self) -> int:
        return sum(1 for kind in self.cfg.blocks() if kind == "attn")

    @property
    def _kv_tok_bytes(self) -> int:
        cfg = self.cfg
        return 2 * cfg.num_kv_heads * cfg.resolved_head_dim * self._dtype.itemsize

    @property
    def n_devices(self) -> int:
        """Mesh devices the engine shards over (1 without a mesh)."""
        if self._mesh is None:
            return 1
        return int(np.prod(list(self._mesh.shape.values())))

    def kv_bytes_by_device(self, peak: bool = False) -> List[int]:
        """Physically resident attention-KV bytes per mesh device.

        Computed from the live cache leaves' actual shard shapes, so it
        reports what each device truly holds: when the KV heads dim shards
        over ``tp`` the per-device values sum exactly to
        ``kv_bytes_in_use``; a leaf whose heads don't divide the axis is
        replicated, and then every device carries its full copy (the sum
        exceeds the logical aggregate by design — replication is real
        memory).  Scope matches the aggregate: paged pool leaves
        (``kp``/``vp``) scaled by blocks in use (or the high-water mark
        with ``peak=True``); contiguous ``k``/``v`` stripes whole.
        """
        if self._mesh is None:
            return [self.kv_bytes_in_use(peak)]
        devices = list(self._mesh.devices.flat)
        per = {d.id: 0 for d in devices}
        blocks = self.peak_blocks_in_use if peak else self.blocks_in_use

        def visit(path, leaf):
            name = str(getattr(path[-1], "key",
                               getattr(path[-1], "idx", path[-1])))
            itemsize = jnp.dtype(leaf.dtype).itemsize
            if self.layout == "paged":
                if name not in ("kp", "vp"):
                    return
                for sh in leaf.addressable_shards:
                    if sh.device.id in per:
                        # the block axis never shards: each device holds
                        # size/num_blocks elements per block of this leaf
                        per[sh.device.id] += (
                            sh.data.size // self.num_blocks) * blocks * itemsize
            else:
                if name not in ("k", "v"):
                    return
                for sh in leaf.addressable_shards:
                    if sh.device.id in per:
                        per[sh.device.id] += sh.data.size * itemsize

        jax.tree_util.tree_map_with_path(visit, self.cache)
        return [per[d.id] for d in devices]

    def pool_accounting_by_device(self) -> List[Dict[str, int]]:
        """Per-device block accounting (see ``BlockPool.shard_accounting``):
        block tables are host-managed and shared, so each device's pool
        holds the same free/in-use/evictable partition of its KV shard."""
        return self._pool.shard_accounting(self.n_devices)

    # -- energy attribution ------------------------------------------------------
    def _count_token(self, req: Request) -> None:
        if self.monitor is None:
            return
        if self._win_t0 is None:
            t0 = self.monitor.window[0]
            self._win_t0 = t0 if t0 > 0.0 else time.perf_counter()
        self._win_tokens[req.uid] = self._win_tokens.get(req.uid, 0) + 1

    def _flush_energy(self) -> None:
        """Close the current window: split its joules by token counts."""
        if self.monitor is None or self._win_t0 is None:
            return
        t1 = time.perf_counter()
        joules = self.monitor.joules_between(self._win_t0, t1)
        total = sum(self._win_tokens.values())
        if total > 0 and joules > 0.0:
            by_uid = {r.uid: r for r in self.finished}
            for s in list(self.slots) + self._preempted:
                if s is not None:
                    by_uid[s.uid] = s
            for uid, n in self._win_tokens.items():
                share = joules * n / total
                if uid in by_uid:
                    by_uid[uid].joules += share
                self.attributed_joules += share
        self._win_t0 = t1
        self._win_tokens = {}

    # -- metrics -----------------------------------------------------------------
    def latency_summary(self) -> Dict[str, float]:
        if not self.finished:
            return {}
        ttfts = [r.ttft_s for r in self.finished]
        tpots = [r.tpot_s for r in self.finished]
        ttlts = [r.ttlt_s for r in self.finished]
        mean = lambda xs: sum(xs) / len(xs)
        out_tokens = sum(len(r.output_tokens) for r in self.finished)
        t_first = min(r.submit_time for r in self.finished)
        t_last = max(r.finish_time for r in self.finished)
        span = max(t_last - t_first, 1e-9)
        # decode vs prefill throughput: emitted tokens and processed prompt
        # tokens over the same request span (prompts are clipped to the
        # computed extent, matching what the prefill path actually ran)
        prefill_tokens = sum(min(len(r.prompt), self.max_len - 1)
                             for r in self.finished)
        summary = {
            "requests": len(self.finished),
            "truncated": sum(1 for r in self.finished if r.truncated),
            "output_tokens": out_tokens,
            "tokens_per_sec": out_tokens / span,
            "prefill_tokens_per_sec": prefill_tokens / span,
            "tokens_per_dispatch": (
                self._decode_tokens / max(self._decode_dispatches, 1)),
            "ttft_ms": mean(ttfts) * 1e3,
            "tpot_ms": mean(tpots) * 1e3,
            "ttlt_ms": mean(ttlts) * 1e3,
        }
        for name, xs in (("ttft", ttfts), ("tpot", tpots), ("ttlt", ttlts)):
            for q in (50, 95, 99):
                summary[f"{name}_p{q}_ms"] = _percentile(xs, q) * 1e3
        # where a request waited: for the engine thread to take its
        # submission (behind the running step), for a slot, and behind the
        # head prefill cursor
        for name, xs in (
                ("submit_wait", [r.submit_time - r.recv_time
                                 for r in self.finished]),
                ("queue_wait", [r.admit_time - r.submit_time
                                for r in self.finished]),
                ("prefill_wait", [r.first_chunk_time - r.admit_time
                                  for r in self.finished])):
            for q in (50, 95):
                summary[f"{name}_p{q}_ms"] = _percentile(xs, q) * 1e3
        summary["compilations"] = self.spans.compiles
        summary["kv_bytes_peak"] = self.kv_bytes_in_use(peak=True)
        summary["kv_bytes_worst_case"] = self.kv_bytes_worst_case
        if self._mesh is not None:
            summary["tp_devices"] = self.n_devices
            summary["kv_bytes_peak_per_device"] = self.kv_bytes_by_device(
                peak=True)
            if self.layout == "paged":
                summary["pool_blocks_in_use_per_device"] = [
                    v["in_use"] for v in self.pool_accounting_by_device()]
        if self._steps_done:
            wall = max(self._steps_t1 - (self._steps_t0 or 0.0), 1e-9)
            summary["steps_per_sec"] = self._steps_done / wall
            # every device launch, over the steps the span ring still holds
            launches = [r.launches for r in self.spans.steps]
            summary["dispatches_per_step_p50"] = _percentile(launches, 50)
            summary["dispatches_per_step_p95"] = _percentile(launches, 95)
        if self.spec_k:
            summary["drafted_tokens"] = self._drafted_tokens
            summary["accepted_tokens"] = self._accepted_tokens
            summary["spec_accept_rate"] = (
                self._accepted_tokens / max(self._drafted_tokens, 1))
        if self.layout == "paged":
            summary["preemptions"] = self.preemptions
            summary["recompute_tokens"] = self.recompute_tokens
            summary["pool_occupancy_p50"] = _percentile(self._occ_samples, 50)
            summary["pool_occupancy_p95"] = _percentile(self._occ_samples, 95)
        if self.prefix_cache:
            summary["prefix_lookups"] = self.prefix_lookups
            summary["prefix_hit_rate"] = (
                self.prefix_hits / max(self.prefix_lookups, 1))
            summary["prefix_blocks_reused"] = self.prefix_blocks_reused
            summary["prefill_tokens_skipped"] = self.prefill_tokens_skipped
            # per-prefix residency: the pool attributes block-granular
            # hits/misses/evictions to each registered content hash
            stats = self._pool.prefix_stats.values()
            summary["prefix_block_hits"] = sum(s[0] for s in stats)
            summary["prefix_block_misses"] = sum(s[1] for s in stats)
            summary["prefix_block_evictions"] = sum(s[2] for s in stats)
            summary["prefix_hashes_tracked"] = len(self._pool.prefix_stats)
            summary["prefix_blocks_resident"] = len(self._pool.ready)
        if self.monitor is not None:
            total_j = sum(r.joules for r in self.finished)
            summary["joules_total"] = total_j
            summary["joules_source"] = self.monitor.source
            summary["joules_per_request"] = total_j / max(
                len(self.finished), 1)
            summary["joules_per_token"] = total_j / max(out_tokens, 1)
            # achieved sampler health: the >= 5-10 Hz protocol requirement
            # is verifiable from the summary, and gaps the step function
            # backfilled with stale power are counted, not hidden
            res = self.monitor.result()
            summary["power_samples_per_sec"] = res.samples_per_sec
            summary["power_reads_dropped"] = res.dropped_reads
            # per-device split when the monitor keeps per-device ledgers
            # (DeviceMonitorGroup): each device's windowed integral over
            # the group window, so the list sums to result().joules — a
            # device that dropped every read contributes 0.0 J and its
            # drop count, never a crash
            by_dev = getattr(self.monitor, "result_by_device", None)
            if callable(by_dev):
                dev_results = by_dev()
                summary["joules_per_device"] = [
                    r.joules for r in dev_results]
                summary["power_samples_per_sec_per_device"] = [
                    r.samples_per_sec for r in dev_results]
                summary["power_reads_dropped_per_device"] = [
                    r.dropped_reads for r in dev_results]
        return summary
