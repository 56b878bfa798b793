"""Serving decode-loop benchmark: fused device-resident step (contiguous,
donated, and paged KV layouts) vs the legacy per-slot host loop, plus an
engine-level KV-memory comparison under a short-heavy workload.

The legacy path (the seed engine's ``_decode_once``) ran one jitted decode,
then for every slot dispatched a separate ``sample`` call and synced
``int(t[0])`` to the host — O(batch) device round-trips per step.  The
fused path (``serving.step.make_decode_sample_step``) samples all slots,
advances positions/budgets and detects finishes inside one jitted call,
then syncs a single packed (3, B) array.  Decode steps/sec should improve
measurably from ``max_batch >= 4`` on CPU.

Two regression guards ride along:

* **Donation** (``donating_jit``): donating the cache/state buffers into
  the fused step must not cost throughput — asserted at >= 0.75x the
  non-donated fused rate (0.4x on the CPU, whose timings are noisy).
* **Paged KV**: the block-pool layout must stay within striking distance
  of the contiguous fused path (reported as a ratio), while the engine
  section shows the point of paging — peak KV bytes actually allocated for
  a short-heavy mixed-length workload vs the contiguous worst case.
* **Chunked prefill / TTFT interference**: while a long prompt admits,
  the p95 inter-token gap of in-flight decode slots must be no worse with
  chunking than with whole-prompt admission (and should improve: chunking
  bounds the per-step prompt work a decode token waits on).
* **Prefix caching**: a warm shared-prefix request (prefix blocks
  resident from an earlier sharer) must reach its first token >= 2x
  faster than a cold one — it prefills only the suffix tail.
* **Pool overcommit**: with the paged pool capped at ~50% of the worst
  case on a bursty trace, ``preemption="recompute"`` must still complete
  every request (preempting/recomputing as the pool breathes) with
  goodput within 2x of the uncontended full-pool run.
"""

from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import report
from repro.models import cache as cache_lib
from repro.models import model as model_lib
from repro.serving.engine import ServingEngine, _percentile
from repro.serving.sampling import SamplingParams, sample
from repro.serving.step import (init_slot_state, make_decode_sample_step,
                                donating_jit)
from repro.serving.workload import (bursty_trace, interference_trace,
                                    lookup_friendly_trace)

ARCH = "qwen1.5-0.5b"
BATCHES = (1, 4, 8)
MAX_LEN = 128
BLOCK_SIZE = 16
STEPS = 30
WARMUP = 3


def _per_slot_reference_steps(decode, params, cache, B, n_steps, params_s):
    """The seed engine's decode loop: jitted decode + per-slot host sampling."""
    next_tokens = np.zeros((B, 1), np.int32)
    positions = np.full(B, 16, np.int64)
    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        tok = jnp.asarray(next_tokens)
        pos = jnp.asarray(positions, jnp.int32)
        logits, cache = decode(params, tok, pos, cache)
        key, k = jax.random.split(key)
        for slot in range(B):
            t = sample(logits[slot:slot + 1], params_s,
                       jax.random.fold_in(k, slot))
            next_tokens[slot, 0] = int(t[0])      # per-slot host sync
            positions[slot] += 1
    jax.block_until_ready(logits)
    return time.perf_counter() - t0, cache


def _make_state(B, params_s, tables=None):
    state = init_slot_state(B, max_blocks=0 if tables is None
                            else tables.shape[1])
    state["active"] = jnp.ones((B,), jnp.bool_)
    state["positions"] = jnp.full((B,), 16, jnp.int32)
    state["remaining"] = jnp.full((B,), 10 ** 6, jnp.int32)
    state["temperature"] = jnp.full((B,), params_s.temperature, jnp.float32)
    state["top_k"] = jnp.full((B,), params_s.top_k, jnp.int32)
    if tables is not None:
        state["block_tables"] = tables
    return state


def _fused_steps(step, params, cache, B, n_steps, params_s, tables=None):
    state = _make_state(B, params_s, tables)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, cache, out = step(params, state, cache)
        np.asarray(out)                           # the single host sync
    return time.perf_counter() - t0, cache


def _time_fused(step, cfg, params, B, params_s, *, layout="contiguous",
                repeats=3):
    """Warmup + best-of-``repeats`` timed runs (suppresses scheduler noise),
    each on a fresh cache (donation-safe)."""
    mk = lambda: model_lib.init_cache(cfg, B, MAX_LEN, jnp.dtype(cfg.dtype),
                                      layout=layout, block_size=BLOCK_SIZE)
    tables = None
    if layout == "paged":
        nb = MAX_LEN // BLOCK_SIZE
        tables = jnp.asarray(  # slot s owns blocks [1 + s*nb, 1 + (s+1)*nb)
            1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb))
    _fused_steps(step, params, mk(), B, WARMUP, params_s, tables)
    best = min(_fused_steps(step, params, mk(), B, STEPS, params_s, tables)[0]
               for _ in range(repeats))
    return STEPS / best


def _engine_kv_section(cfg, params, csv_rows: List[str]) -> str:
    """Short-heavy mixed-length workload: paged peak KV bytes vs the
    contiguous worst case (the 2x-minimum saving the paging PR targets)."""
    rng = np.random.default_rng(0)
    plens = [int(n) for n in
             np.clip(rng.lognormal(np.log(20.0), 0.6, 12), 4, 192)]
    engines = {}
    for layout in ("contiguous", "paged"):
        eng = ServingEngine(cfg, params, max_batch=4, max_len=256,
                            prompt_bucket=16, cache_layout=layout,
                            kv_block_size=BLOCK_SIZE)
        for p in plens:
            eng.submit(rng.integers(0, cfg.vocab_size, p),
                       SamplingParams(max_new_tokens=8))
        eng.run()
        engines[layout] = eng
    worst = engines["contiguous"].kv_bytes_worst_case
    paged = engines["paged"].kv_bytes_in_use(peak=True)
    saving = worst / max(paged, 1)
    assert saving >= 2.0, (
        f"paged KV allocated {paged}B vs contiguous worst case {worst}B — "
        f"expected >= 2x saving for a short-heavy workload, got {saving:.2f}x")
    csv_rows.append(f"serving_paged_kv_bytes,{paged},saving={saving:.2f}x")
    md = report.to_markdown([{
        "workload": "12 reqs, lognormal prompts (mean~20), max_new=8",
        "contiguous worst case": f"{worst / 1e6:.2f} MB",
        "paged peak allocated": f"{paged / 1e6:.2f} MB",
        "saving": f"{saving:.1f}x",
    }])
    return ("## Engine KV memory: paged blocks-in-use vs contiguous "
            f"worst case\n\n{md}")


def _interference_p95(cfg, params, *, prefill_chunk: int,
                      windows: int = 6) -> float:
    """p95 inter-token gap (s) of in-flight decode slots while one long
    prompt admits; best of ``windows`` admissions (suppresses scheduler
    noise, like the best-of-repeats decode timings above).

    The engine decodes every active slot once per ``step()``, so the
    wall-clock duration of each engine step during the admission window
    *is* the victims' inter-token gap for that token.  The scenario runs
    once as a warm-up (compiles the prefill/chunk shapes); then, with the
    victims decoding throughout, a long prompt is admitted ``windows``
    times and the steps up to each first token are timed.
    """
    max_len, long_plen = 512, 448
    arrivals = interference_trace(cfg.vocab_size, long_plen=long_plen)
    victims, long_arr = arrivals[:-1], arrivals[-1]
    eng = ServingEngine(cfg, params, max_batch=4, max_len=max_len,
                        prompt_bucket=64, prefill_chunk=prefill_chunk)
    # warm-up: compile the victim-bucket prefill, chunk/long-prefill and
    # decode shapes outside the timed windows
    eng.submit(long_arr.prompt, SamplingParams(max_new_tokens=1))
    eng.submit(victims[0].prompt, SamplingParams(max_new_tokens=1))
    eng.run()
    eng.finished.clear()

    for a in victims:
        eng.submit(a.prompt, a.params)
    for _ in range(3):  # victims admitted and decoding
        eng.step()
    p95s = []
    for _ in range(windows):
        eng.submit(long_arr.prompt, long_arr.params)
        long_req = eng.queue[-1]
        gaps = []
        while long_req.first_token_time == 0.0 and len(gaps) < 200:
            t0 = time.perf_counter()
            eng.step()
            gaps.append(time.perf_counter() - t0)
        assert long_req.first_token_time > 0.0, "long prompt never admitted"
        p95s.append(_percentile(gaps, 95))
        # drain the long request so its slot frees for the next window
        # (the victims keep decoding: their budgets outlast every window)
        for _ in range(200):
            if all(s is None or s.uid != long_req.uid for s in eng.slots):
                break
            eng.step()
    return min(p95s)


def _interference_section(cfg, params, csv_rows: List[str]) -> str:
    """TTFT-interference row: p95 in-flight TPOT during a long-prompt
    admission, whole-prompt vs chunked admission."""
    p95 = {
        label: _interference_p95(cfg, params, prefill_chunk=chunk)
        for label, chunk in (("unchunked", 0), ("chunked", 64))
    }
    ratio = p95["unchunked"] / max(p95["chunked"], 1e-9)
    # regression gate: chunking must not make the interference worse
    # (slack for CI timer noise); the reported ratio shows the win
    assert p95["chunked"] <= 1.15 * p95["unchunked"], (
        f"chunked prefill worsened p95 in-flight TPOT under admission: "
        f"{p95['chunked'] * 1e3:.2f}ms vs {p95['unchunked'] * 1e3:.2f}ms")
    csv_rows.append(
        f"serving_chunked_interference_p95,{p95['chunked'] * 1e6:.1f},"
        f"x{ratio:.2f}_vs_unchunked")
    md = report.to_markdown([{
        "scenario": "3 victims decoding, 448-token prompt admits "
                    "(chunk=64)",
        "unchunked p95 gap": f"{p95['unchunked'] * 1e3:.2f} ms",
        "chunked p95 gap": f"{p95['chunked'] * 1e3:.2f} ms",
        "improvement": f"{ratio:.1f}x",
    }])
    return ("## TTFT interference: p95 in-flight inter-token gap during "
            f"long-prompt admission\n\n{md}")


def _prefix_ttft_section(cfg, params, csv_rows: List[str]) -> str:
    """Shared-prefix TTFT, cold vs warm: the first request with a given
    432-token system prompt pays the full chunked prefill; later sharers
    reuse its resident pool blocks and prefill only the 16-token suffix
    tail.  Gated: best-of warm TTFT must improve >= 2x over best-of cold
    (expected ~7x from the chunk-step count alone).

    One engine serves every round (compiles amortize like the
    interference scenario above); best-of-4 on each side suppresses
    scheduler noise and keeps one-off compiles (the warm path's
    suffix-width chunk) out of the gated numbers."""
    prefix_len, suffix_len, max_len = 432, 16, 512
    eng = ServingEngine(cfg, params, max_batch=2, max_len=max_len,
                        prompt_bucket=64, cache_layout="paged",
                        kv_block_size=BLOCK_SIZE,
                        # pool big enough to keep all 5 prefixes resident
                        # (no eviction between the cold and warm rounds)
                        kv_num_blocks=1 + 8 * (max_len // BLOCK_SIZE),
                        prefill_chunk=64, prefix_cache=True)
    rng = np.random.default_rng(0)
    prefixes = [rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
                for _ in range(5)]

    def serve_one(pid: int) -> float:
        prompt = np.concatenate([
            prefixes[pid],
            rng.integers(0, cfg.vocab_size, suffix_len).astype(np.int32)])
        eng.submit(prompt, SamplingParams(max_new_tokens=2))
        req = eng.queue[-1]
        eng.run()
        return req.ttft_s

    serve_one(0)  # warm-up: compiles the 64-wide chunk + decode shapes
    serve_one(0)  # warm-up: compiles the warm path's 16-wide suffix chunk
    cold = [serve_one(pid) for pid in (1, 2, 3, 4)]
    warm = [serve_one(pid) for pid in (1, 2, 3, 4)]
    assert eng.prefix_hits >= 5, f"warm rounds missed: {eng.prefix_hits} hits"
    skipped = eng.prefill_tokens_skipped // eng.prefix_hits
    ratio = min(cold) / max(min(warm), 1e-9)
    assert ratio >= 2.0, (
        f"prefix-cache warm TTFT regression: cold {min(cold)*1e3:.2f}ms vs "
        f"warm {min(warm)*1e3:.2f}ms ({ratio:.2f}x, expected >= 2x)")
    csv_rows.append(
        f"serving_prefix_warm_ttft,{min(warm) * 1e6:.1f},x{ratio:.2f}_vs_cold")
    md = report.to_markdown([{
        "scenario": f"{prefix_len}-token shared prefix + {suffix_len}-token "
                    f"suffix (chunk=64, block={BLOCK_SIZE})",
        "cold TTFT": f"{min(cold) * 1e3:.2f} ms",
        "warm TTFT": f"{min(warm) * 1e3:.2f} ms",
        "speedup": f"{ratio:.1f}x",
        "prefill tokens skipped/hit": skipped,
    }])
    return ("## Prefix-cache TTFT: cold vs warm shared-prefix workload\n\n"
            f"{md}")


def _overcommit_section(cfg, params, csv_rows: List[str]) -> str:
    """Pool overcommit row: a bursty trace against a pool capped at ~50%
    of the worst case, with preemption + recompute, vs the same trace on
    a full pool.  Gated: every request completes, preemptions actually
    happened, greedy streams stay identical, and goodput (tokens/sec of
    the drain) is within 2x of the uncontended run.

    Each engine serves the trace twice — the first pass warms the jit
    caches (recompute re-admissions compile per distinct chunk width),
    the second is timed.  Greedy sampling keeps the second pass's streams
    independent of the uids it draws."""
    max_batch, max_len, plen, max_new = 4, 128, 48, 32
    worst = cache_lib.default_num_blocks(max_batch, max_len, BLOCK_SIZE)
    half = worst // 2 + 1  # 17 of 33: ~50%
    arrivals = bursty_trace(cfg.vocab_size, bursts=2, burst_size=4,
                            prompt_len=plen, max_new=max_new)
    prompts = [a.prompt for a in arrivals]

    def serve(num_blocks):
        eng = ServingEngine(cfg, params, max_batch=max_batch,
                            max_len=max_len, prompt_bucket=16,
                            cache_layout="paged", kv_block_size=BLOCK_SIZE,
                            kv_num_blocks=num_blocks, prefill_chunk=16,
                            preemption="recompute")
        results = []
        for _ in range(2):  # warm pass, then the timed pass
            start = len(eng.finished)
            # per-pass counter deltas: the reported (and gated) numbers
            # must describe the timed pass, not the warm-up too
            pre0, rec0 = eng.preemptions, eng.recompute_tokens
            eng._occ_samples.clear()
            for p in prompts:
                eng.submit(p, SamplingParams(max_new_tokens=max_new))
            t0 = time.perf_counter()
            eng.run()
            dt = time.perf_counter() - t0
            done = eng.finished[start:]
            results.append((
                [list(r.output_tokens) for r in
                 sorted(done, key=lambda r: r.uid)],
                sum(len(r.output_tokens) for r in done) / dt,
                eng.preemptions - pre0, eng.recompute_tokens - rec0))
        streams, tps, npre, nrec = results[-1]
        assert len(streams) == len(prompts), (
            f"overcommit run lost requests: {len(streams)}/{len(prompts)}")
        return eng, streams, tps, npre, nrec

    full_eng, full_streams, full_tps, full_pre, _ = serve(worst)
    over_eng, over_streams, over_tps, over_pre, over_rec = serve(half)
    assert full_pre == 0, "full pool should never preempt"
    assert over_pre > 0, (
        "half-sized pool never preempted — the overcommit row is vacuous")
    assert over_streams == full_streams, (
        "preemption/recompute changed greedy token streams")
    ratio = full_tps / max(over_tps, 1e-9)
    assert ratio <= 2.0, (
        f"overcommit goodput regression: {over_tps:.1f} tok/s at "
        f"{half}/{worst} blocks vs {full_tps:.1f} uncontended "
        f"({ratio:.2f}x, gated <= 2x)")
    occ_p95 = _percentile(over_eng._occ_samples, 95)  # timed pass only
    csv_rows.append(
        f"serving_overcommit_goodput,{1e6 / over_tps:.1f},"
        f"x{over_tps / full_tps:.2f}_vs_full_pool")
    md = report.to_markdown([{
        "scenario": f"2 waves x 4 reqs ({plen}+{max_new} tokens), "
                    f"pool {half}/{worst} blocks",
        "uncontended tok/s": f"{full_tps:.1f}",
        "overcommit tok/s": f"{over_tps:.1f}",
        "goodput": f"{over_tps / full_tps:.2f}x (gated >= 0.5x)",
        "preemptions": over_pre,
        "recompute tokens": over_rec,
        "occupancy p95": f"{occ_p95:.2f}",
    }])
    return ("## Pool overcommit: bursty trace at ~50% of worst-case "
            f"blocks, preemption + recompute\n\n{md}")


def _speculative_section(cfg, params, csv_rows: List[str]) -> str:
    """Speculative decoding row: prompt-lookup drafting on the
    lookup-friendly trace (tiled-motif prompts whose greedy continuation
    keeps cycling the motif) vs the same engine with speculation off.
    Gated: greedy streams byte-identical, tokens/dispatch > 1 (verifies
    actually emit multi-token), and decode tokens/sec >= 1.5x the
    non-speculative run.

    Batch 1 on purpose: speculation is a latency technique — at high
    batch the dispatch already amortizes over the slots and the verify
    window's extra positions eat the win (especially on CPU, where the
    k+1-wide verify pays k+1 decode-equivalents of compute).  Each engine
    serves the trace twice — the first pass warms the jit caches, the
    second is timed; greedy sampling keeps both passes' streams equal."""
    max_new, max_len, spec_k = 80, 160, 6
    arrivals = lookup_friendly_trace(cfg.vocab_size, num_requests=4,
                                     motif_len=8, repeats=4, max_new=max_new)
    prompts = [a.prompt for a in arrivals]

    def serve(speculative):
        eng = ServingEngine(cfg, params, max_batch=1, max_len=max_len,
                            prompt_bucket=16, prefill_chunk=16,
                            speculative=speculative, spec_tokens=spec_k)
        results = []
        for _ in range(2):  # warm pass, then the timed pass
            start = len(eng.finished)
            for p in prompts:
                eng.submit(p, SamplingParams(max_new_tokens=max_new))
            t0 = time.perf_counter()
            eng.run()
            dt = time.perf_counter() - t0
            done = sorted(eng.finished[start:], key=lambda r: r.uid)
            results.append((
                [list(r.output_tokens) for r in done],
                sum(len(r.output_tokens) for r in done) / dt))
        streams, tps = results[-1]
        assert len(streams) == len(prompts)
        return eng, streams, tps

    base_eng, base_streams, base_tps = serve("off")
    spec_eng, spec_streams, spec_tps = serve("lookup")
    assert spec_streams == base_streams, (
        "speculative decoding changed greedy token streams")
    s = spec_eng.latency_summary()
    assert s["tokens_per_dispatch"] > 1.0, (
        f"verify dispatches never emitted multi-token "
        f"(tokens/dispatch {s['tokens_per_dispatch']:.2f})")
    ratio = spec_tps / max(base_tps, 1e-9)
    assert ratio >= 1.5, (
        f"speculative decode too slow: {spec_tps:.1f} tok/s vs plain "
        f"{base_tps:.1f} ({ratio:.2f}x, gated >= 1.5x)")
    csv_rows.append(
        f"serving_speculative,{1e6 / spec_tps:.1f},"
        f"x{ratio:.2f}_vs_plain_decode")
    md = report.to_markdown([{
        "scenario": f"4 reqs, 8-token motif x4 prompts, max_new={max_new}, "
                    f"k={spec_k}, batch 1",
        "plain tok/s": f"{base_tps:.1f}",
        "speculative tok/s": f"{spec_tps:.1f}",
        "speedup": f"{ratio:.2f}x (gated >= 1.5x)",
        "accept rate": f"{s['spec_accept_rate']:.2f}",
        "tokens/dispatch": f"{s['tokens_per_dispatch']:.1f}",
        "drafted": s["drafted_tokens"],
        "accepted": s["accepted_tokens"],
    }])
    return ("## Speculative decoding: prompt-lookup drafts, one batched "
            f"verify dispatch\n\n{md}")


def _mixed_batch_section(cfg, params, csv_rows: List[str]) -> str:
    """Mixed prefill/decode batch row: engine steps/sec and p95 TPOT under
    sustained prompt admission, unified single-dispatch step vs the
    per-chunk dispatch path.  Greedy streams must match, and the unified
    step must clear >= 1.3x steps/sec — the win is pure dispatch economics
    (>= 2 launches per step collapse into one fused launch while cursors
    are in flight).

    Each engine serves the trace twice — the first pass warms the jit
    caches (the unified path compiles one packed-frontier executable, the
    legacy path one per chunk width), the second is timed.

    Shape: a multi-quantum budget (budget = 8 x chunk) makes the legacy
    path pay ~9 launches per step while the unified engine folds the same
    frontier into a single packed dispatch."""
    max_batch, max_len, plen, max_new = 2, 128, 100, 4
    chunk, budget = 4, 32
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
               for _ in range(12)]

    def serve(unified):
        eng = ServingEngine(cfg, params, max_batch=max_batch,
                            max_len=max_len, prompt_bucket=16,
                            prefill_chunk=chunk, prefill_budget=budget,
                            unified_step=unified)
        results = []
        for _ in range(2):  # warm pass, then the timed pass
            start = len(eng.finished)
            steps0, disp0 = eng._steps_done, eng._dispatches
            for p in prompts:
                eng.submit(p, SamplingParams(max_new_tokens=max_new))
            t0 = time.perf_counter()
            eng.run()
            dt = time.perf_counter() - t0
            done = sorted(eng.finished[start:], key=lambda r: r.uid)
            nsteps = eng._steps_done - steps0
            results.append((
                [list(r.output_tokens) for r in done],
                nsteps / dt,
                _percentile([r.tpot_s for r in done], 95),
                (eng._dispatches - disp0) / max(nsteps, 1)))
        assert len(results[-1][0]) == len(prompts)
        return results[-1]

    uni_streams, uni_sps, uni_tpot, uni_dps = serve(True)
    leg_streams, leg_sps, leg_tpot, leg_dps = serve(False)
    assert uni_streams == leg_streams, (
        "unified step changed greedy token streams")
    ratio = uni_sps / max(leg_sps, 1e-9)
    assert ratio >= 1.3, (
        f"unified mixed step too slow: {uni_sps:.1f} steps/s vs per-chunk "
        f"{leg_sps:.1f} ({ratio:.2f}x, gated >= 1.3x)")
    csv_rows.append(
        f"serving_unified_step,{1e6 / uni_sps:.1f},"
        f"x{ratio:.2f}_vs_per_chunk")
    md = report.to_markdown([{
        "scenario": f"12 reqs, {plen}-token prompts (chunk={chunk}, "
                    f"budget={budget}), max_new={max_new}",
        "per-chunk steps/s": f"{leg_sps:.1f}",
        "unified steps/s": f"{uni_sps:.1f}",
        "speedup": f"{ratio:.2f}x (gated >= 1.3x)",
        "per-chunk p95 TPOT": f"{leg_tpot * 1e3:.2f} ms",
        "unified p95 TPOT": f"{uni_tpot * 1e3:.2f} ms",
        "dispatches/step": f"{uni_dps:.2f} vs {leg_dps:.2f}",
    }])
    return ("## Unified mixed prefill/decode step: one dispatch per engine "
            f"step vs per-chunk dispatches\n\n{md}")


def _sharded_section(cfg, params, axes, csv_rows: List[str]) -> str:
    """Tensor-parallel row: the same greedy paged workload served at tp=2
    (heads/FFN sharded over a ``(tp,)`` mesh) vs the single-device engine.
    Gated: streams byte-identical (sharding moves the math, never the
    tokens) and tp=2 steps/sec within 2x of tp=1 — on a forced CPU host
    the "devices" share the same silicon, so sharding only pays dispatch
    overhead; the gate catches that overhead exploding.

    Skips gracefully on a single-device host: the bench-smoke leg sets
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""
    from repro.launch.mesh import make_tp_mesh

    title = "## Tensor-parallel serving: tp=2 vs tp=1 (forced host)"
    if len(jax.devices()) < 2:
        return (f"{title}\n\n(skipped: single-device host — set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    max_new, plen = 24, 48
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
               for _ in range(8)]

    def serve(tp):
        # batch 4 on purpose: the forced-host "devices" share one CPU, so
        # sharding buys no compute — it costs a roughly fixed per-step
        # multi-device dispatch overhead, which a heavier step amortizes
        mesh = make_tp_mesh(tp) if tp > 1 else None
        eng = ServingEngine(cfg, params, max_batch=4, max_len=MAX_LEN,
                            prompt_bucket=16, prefill_chunk=8,
                            cache_layout="paged", kv_block_size=BLOCK_SIZE,
                            mesh=mesh,
                            param_axes=axes if mesh is not None else None)
        results = []
        for _ in range(3):  # warm pass, then best-of-2 timed passes
            start = len(eng.finished)
            steps0 = eng._steps_done
            for p in prompts:
                eng.submit(p, SamplingParams(max_new_tokens=max_new))
            t0 = time.perf_counter()
            eng.run()
            dt = time.perf_counter() - t0
            done = sorted(eng.finished[start:], key=lambda r: r.uid)
            results.append(([list(r.output_tokens) for r in done],
                            (eng._steps_done - steps0) / dt))
        streams = results[-1][0]
        sps = max(r[1] for r in results[1:])
        assert len(streams) == len(prompts)
        return eng, streams, sps

    _, base_streams, base_sps = serve(1)
    tp_eng, tp_streams, tp_sps = serve(2)
    assert tp_streams == base_streams, (
        "tp=2 sharding changed greedy token streams")
    ratio = base_sps / max(tp_sps, 1e-9)
    assert ratio <= 2.0, (
        f"sharded engine too slow: {tp_sps:.1f} steps/s at tp=2 vs "
        f"{base_sps:.1f} at tp=1 ({ratio:.2f}x slowdown, gated <= 2x)")
    per = tp_eng.kv_bytes_by_device(peak=True)
    assert sum(per) == tp_eng.kv_bytes_in_use(peak=True)
    csv_rows.append(
        f"serving_sharded_tp2,{1e6 / tp_sps:.1f},"
        f"x{tp_sps / base_sps:.2f}_vs_tp1")
    md = report.to_markdown([{
        "scenario": f"8 reqs, {plen}+{max_new} tokens, batch 4, paged "
                    f"(block={BLOCK_SIZE}), chunk=8",
        "tp=1 steps/s": f"{base_sps:.1f}",
        "tp=2 steps/s": f"{tp_sps:.1f}",
        "slowdown": f"{ratio:.2f}x (gated <= 2x)",
        "streams": "byte-identical",
        "KV bytes by device": " / ".join(str(b) for b in per),
    }])
    return f"{title}\n\n{md}"


def _server_section(cfg, params, csv_rows: List[str]) -> str:
    """Client-vs-engine steady state: drive the engine through the
    OpenAI-compatible HTTP front-end with the closed-loop generator and
    compare the latencies the *client* observed against the engine's own
    ledger for the same requests.  Gates: the energy ledger must tile
    exactly (sum of per-request ``joules_between`` windows == run total)
    and the client-minus-engine TTFT/TPOT deltas must stay within the
    serving overhead budget — if HTTP + queueing ever costs more than
    250 ms of TTFT on an idle box, the front-end has rotted."""
    try:
        import aiohttp  # noqa: F401
    except ImportError:
        return ("## Serving over HTTP: client vs engine steady state\n\n"
                "(skipped: aiohttp not installed)")
    import math

    from repro.core.energy import PowerMonitor, SyntheticReader
    from repro.serving.loadgen import LoadSpec, prewarm_engine, run_load
    from repro.serving.server import start_http_server

    eng = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                        prefill_chunk=16)
    mon = PowerMonitor(
        SyntheticReader(lambda t: 40.0 + 10.0 * math.sin(t * 7.0)),
        interval_s=0.05)
    eng.attach_monitor(mon)
    prewarm_engine(eng, prompt_len=12, concurrency=2,
                   vocab_size=cfg.vocab_size)
    handle = start_http_server(eng, model_name=cfg.name)
    try:
        spec = LoadSpec(mode="closed", concurrency=2, warmup_s=1.0,
                        duration_s=2.5, prompt_len=12, max_new=8,
                        vocab_size=cfg.vocab_size)
        res = run_load(handle.url, spec, monitor=mon)
    finally:
        handle.close()
    s = res.summary
    assert s["steady_requests"] >= 2, (
        f"steady-state window saw only {s['steady_requests']} requests")
    assert abs(s["joules_attributed"] - s["joules_total"]) <= (
        1e-9 * max(s["joules_total"], 1.0)), (
        f"energy ledger drift: {s['joules_attributed']!r} J attributed vs "
        f"{s['joules_total']!r} J total")
    assert -1.0 <= s["ttft_client_minus_engine_ms"] <= 250.0, (
        f"client-vs-engine TTFT delta {s['ttft_client_minus_engine_ms']:.1f}"
        f" ms out of bounds")
    assert abs(s["tpot_client_minus_engine_ms"]) <= 50.0, (
        f"client-vs-engine TPOT delta {s['tpot_client_minus_engine_ms']:.2f}"
        f" ms out of bounds")
    rows = [{
        "requests": int(s["steady_requests"]),
        "req/s": round(s["achieved_qps"], 1),
        "client TTFT(ms)": round(s["client_ttft_ms"], 1),
        "TTFT delta(ms)": round(s["ttft_client_minus_engine_ms"], 1),
        "client TPOT(ms)": round(s["client_tpot_ms"], 2),
        "TPOT delta(ms)": round(s["tpot_client_minus_engine_ms"], 2),
        "J/req": round(s["joules_per_request"], 2),
        "sample Hz": round(s["power_samples_per_sec"], 1),
    }]
    csv_rows.append(
        f"serving_http_ttft_delta,{s['ttft_client_minus_engine_ms']:.1f},"
        f"tpot_delta={s['tpot_client_minus_engine_ms']:.2f}ms")
    return ("## Serving over HTTP: client vs engine steady state "
            "(closed loop, energy ledger exact)\n\n"
            + report.to_markdown(rows))


def run(csv_rows: List[str]) -> str:
    cfg = get_config(ARCH, smoke=True)
    params, _ = model_lib.init(cfg, jax.random.PRNGKey(0))
    params_s = SamplingParams(temperature=0.8, top_k=20)
    rows = []
    for B in BATCHES:
        cache = model_lib.init_cache(cfg, B, MAX_LEN, jnp.dtype(cfg.dtype))
        # compile once per batch size, outside the timed regions
        decode = jax.jit(lambda p, tok, pos, c:
                         model_lib.decode_step(cfg, p, tok, pos, c))
        step_fn = make_decode_sample_step(cfg, MAX_LEN)
        fused = jax.jit(step_fn)
        cpu = jax.default_backend() == "cpu"
        _per_slot_reference_steps(decode, params, cache, B, WARMUP, params_s)
        ref_s, _ = _per_slot_reference_steps(
            decode, params, cache, B, STEPS, params_s)
        ref_sps = STEPS / ref_s
        fused_sps = _time_fused(fused, cfg, params, B, params_s)
        donated = donating_jit(step_fn, (1, 2))
        donated_sps = _time_fused(donated, cfg, params, B, params_s)
        paged_sps = _time_fused(fused, cfg, params, B, params_s,
                                layout="paged")
        # regression gates.  On CPU the paged path pays an XLA gather the
        # TPU kernel avoids via scalar prefetch, so CPU only guards against
        # catastrophic rot; accelerators get the real bounds (donation must
        # not drop throughput, paged stays within ~10% of fused).
        don_floor, paged_floor = (0.4, 0.4) if cpu else (0.75, 0.9)
        assert donated_sps >= don_floor * fused_sps, (
            f"donation regression at B={B}: {donated_sps:.1f} vs "
            f"{fused_sps:.1f} steps/s")
        assert paged_sps >= paged_floor * fused_sps, (
            f"paged decode regression at B={B}: {paged_sps:.1f} vs "
            f"{fused_sps:.1f} steps/s")
        rows.append({
            "batch": B,
            "per-slot steps/s": round(ref_sps, 1),
            "fused steps/s": round(fused_sps, 1),
            "donated steps/s": round(donated_sps, 1),
            "paged steps/s": round(paged_sps, 1),
            "speedup": round(fused_sps / ref_sps, 2),
            "paged/fused": round(paged_sps / fused_sps, 2),
        })
        csv_rows.append(
            f"serving_fused_b{B},{1e6 / fused_sps:.1f},"
            f"x{fused_sps / ref_sps:.2f}_vs_per_slot")
        csv_rows.append(
            f"serving_paged_b{B},{1e6 / paged_sps:.1f},"
            f"x{paged_sps / fused_sps:.2f}_vs_fused")
    md = report.to_markdown(rows)
    section = (f"## Serving decode loop: per-slot reference vs fused step "
               f"(contiguous / donated / paged)\n\n{md}")
    return (section
            + "\n\n" + _engine_kv_section(cfg, params, csv_rows)
            + "\n\n" + _mixed_batch_section(cfg, params, csv_rows)
            + "\n\n" + _interference_section(cfg, params, csv_rows)
            + "\n\n" + _prefix_ttft_section(cfg, params, csv_rows)
            + "\n\n" + _overcommit_section(cfg, params, csv_rows)
            + "\n\n" + _server_section(cfg, params, csv_rows))
