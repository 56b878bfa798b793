"""Steady-state load generator for the HTTP serving path.

Implements the measurement protocol the serving literature converged on
(vLLM's benchmark serving flow; TokenPowerBench; The Price of Prompting):

1. **Warmup** — drive the server for ``warmup_s`` before measuring, so
   JIT compilation, cache population, and ramp-up never pollute the
   numbers.
2. **Steady-state window** — a fixed ``duration_s`` window; only
   requests *sent* inside it count.  The ``PowerMonitor`` is entered at
   the window's start edge and exited at its end edge, so the monitor's
   ``result()`` total is the energy of exactly the measured window.
3. **Drive modes** — closed-loop (``concurrency`` workers, each sending
   its next request the moment the previous finishes: the server always
   sees N in flight) or open-loop (Poisson arrivals at ``qps``,
   independent of completion times: models real traffic and exposes
   queueing delay that closed-loop hides).
4. **Energy attribution** — the steady-state window is tiled with
   contiguous per-request sub-windows whose widths are proportional to
   completion token counts, and each request's share is
   ``monitor.joules_between`` over its tile.  Because the step-function
   integral is additive over adjacent windows, the shares sum to
   ``monitor.result().joules`` exactly — one ledger, no drift.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.serving.client import ClientRecord, stream_completion

try:
    import aiohttp
except ImportError:  # pragma: no cover - exercised only without aiohttp
    aiohttp = None


@dataclasses.dataclass
class LoadSpec:
    mode: str = "closed"        # "closed" (concurrency-N) | "open" (Poisson)
    concurrency: int = 2        # closed-loop: requests in flight
    qps: float = 4.0            # open-loop: mean Poisson arrival rate
    warmup_s: float = 1.0       # unmeasured ramp before the window
    duration_s: float = 5.0     # steady-state measurement window
    max_requests: int = 10_000  # safety cap across the whole run
    prompt_len: int = 16
    prompt_pool: int = 8        # distinct prompts cycled through
    max_new: int = 16
    temperature: float = 0.0
    top_k: int = 0
    vocab_size: int = 128
    seed: int = 0


@dataclasses.dataclass
class LoadResult:
    records: List[ClientRecord]          # steady-state, error-free
    all_records: List[ClientRecord]      # including warmup / late / errors
    window: Tuple[float, float]          # steady-state [start, end)
    summary: Dict[str, float]


def prewarm_engine(engine, *, prompt_len: int, concurrency: int,
                   vocab_size: int, max_new: int = 4, seed: int = 0) -> None:
    """Compile the executables the load will exercise *before* the server
    starts: prefill at the load's prompt bucket and the step function at
    the load's slot occupancy.  JAX compiles lazily per shape, so without
    this the first requests pay seconds of compile inside the warmup
    phase (or worse, inside the measured window on short runs).  Call it
    before ``start_http_server`` — afterwards the engine thread owns the
    engine."""
    from repro.serving.sampling import SamplingParams

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab_size, prompt_len).astype(np.int32)
               for _ in range(max(concurrency, 1))]
    # staggered admission: each later request lands while the earlier ones
    # are mid-decode, so the *mixed* prefill+decode step shape compiles
    # too — simultaneous submission would only ever see prefill-only and
    # decode-only steps, leaving a multi-second compile stall for the
    # first staggered arrival of the real load
    engine.submit(prompts[0], SamplingParams(max_new_tokens=max_new))
    for p in prompts[1:]:
        engine.step()
        engine.submit(p, SamplingParams(max_new_tokens=max_new))
    engine.run()


def _percentile(xs: List[float], q: float) -> float:
    """Nearest-rank percentile (same convention as the engine summary)."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    idx = min(int(round(q / 100.0 * (len(xs) - 1))), len(xs) - 1)
    return xs[idx]


def attribute_energy(records: List[ClientRecord], monitor) -> float:
    """Tile ``monitor.window`` with per-request sub-windows proportional
    to completion token counts (ordered by first-chunk time); each
    request's ``joules`` is ``joules_between`` over its tile.  Additivity
    of the step-function integral makes the shares sum to
    ``monitor.result().joules`` exactly."""
    t0, t1 = monitor.window
    ordered = sorted(records, key=lambda r: r.first_chunk_time)
    toks = [len(r.tokens) for r in ordered]
    total = sum(toks)
    if total == 0 or t1 <= t0:
        return 0.0
    attributed = 0.0
    cur = t0
    acc = 0
    for i, (rec, n) in enumerate(zip(ordered, toks)):
        acc += n
        # the last edge lands *exactly* on t1 so the tiles cover the
        # window with shared edges — the precondition for exactness
        nxt = t1 if i == len(ordered) - 1 else t0 + (t1 - t0) * (acc / total)
        rec.joules = monitor.joules_between(cur, nxt)
        attributed += rec.joules
        cur = nxt
    return attributed


def summarize(records: List[ClientRecord], window: Tuple[float, float],
              monitor=None) -> Dict[str, float]:
    ws, we = window
    dur = max(we - ws, 1e-9)
    total_tokens = sum(len(r.tokens) for r in records)
    ttft = [r.client_ttft_s * 1e3 for r in records if r.tokens]
    tpot = [r.client_tpot_s * 1e3 for r in records if len(r.tokens) >= 2]
    ttlt = [r.client_ttlt_s * 1e3 for r in records if r.tokens]
    summary: Dict[str, float] = {
        "steady_requests": float(len(records)),
        "steady_window_s": dur,
        "achieved_qps": len(records) / dur,
        "client_tokens_per_sec": total_tokens / dur,
    }
    for name, xs in (("ttft", ttft), ("tpot", tpot), ("ttlt", ttlt)):
        summary[f"client_{name}_ms"] = float(np.mean(xs)) if xs else 0.0
        summary[f"client_{name}_p50_ms"] = _percentile(xs, 50)
        summary[f"client_{name}_p95_ms"] = _percentile(xs, 95)
    # client-vs-engine deltas: both sides stamp the same monotonic clock,
    # so the delta is the HTTP + submission-queue overhead, always >= 0
    d_ttft = [(r.client_ttft_s - r.engine_ttft_s) * 1e3
              for r in records if r.engine]
    d_tpot = [(r.client_tpot_s - r.engine_tpot_s) * 1e3
              for r in records if r.engine and len(r.tokens) >= 2]
    summary["ttft_client_minus_engine_ms"] = (
        float(np.mean(d_ttft)) if d_ttft else 0.0)
    summary["ttft_client_minus_engine_p95_ms"] = _percentile(d_ttft, 95)
    summary["tpot_client_minus_engine_ms"] = (
        float(np.mean(d_tpot)) if d_tpot else 0.0)
    if monitor is not None:
        res = monitor.result()
        attributed = attribute_energy(records, monitor)
        summary["joules_total"] = res.joules
        summary["joules_source"] = monitor.source
        summary["joules_attributed"] = attributed
        summary["avg_watts"] = res.avg_watts
        summary["joules_per_request"] = res.joules / max(len(records), 1)
        summary["joules_per_token"] = res.joules / max(total_tokens, 1)
        summary["power_samples_per_sec"] = res.samples_per_sec
        summary["power_reads_dropped"] = float(res.dropped_reads)
    return summary


async def _run_load_async(base_url: str, spec: LoadSpec,
                          monitor=None) -> LoadResult:
    if aiohttp is None:  # pragma: no cover
        raise RuntimeError("aiohttp is required for the load generator")
    rng = np.random.default_rng(spec.seed)
    pool = [rng.integers(0, spec.vocab_size, spec.prompt_len).tolist()
            for _ in range(max(spec.prompt_pool, 1))]
    all_records: List[ClientRecord] = []
    stop = asyncio.Event()
    t_start = time.perf_counter()
    ws = t_start + spec.warmup_s
    we = ws + spec.duration_s
    window_open: List[float] = [ws, we]  # actual monitor edges

    async def phase_clock() -> None:
        # the monitor brackets exactly the steady-state window, so the
        # run total and the per-request tiles share the same [t0, t1]
        await asyncio.sleep(max(ws - time.perf_counter(), 0.0))
        if monitor is not None:
            monitor.__enter__()
            window_open[0] = monitor.window[0]
        await asyncio.sleep(max(we - time.perf_counter(), 0.0))
        if monitor is not None:
            monitor.__exit__(None, None, None)
            window_open[1] = monitor.window[1]
        stop.set()

    async def one(idx: int, session) -> None:
        rec = await stream_completion(
            session, base_url, pool[idx % len(pool)],
            max_tokens=spec.max_new, temperature=spec.temperature,
            top_k=spec.top_k)
        all_records.append(rec)

    async def closed_worker(wid: int, session) -> None:
        i = 0
        while not stop.is_set() and len(all_records) < spec.max_requests:
            await one(wid + i * spec.concurrency, session)
            i += 1

    async def open_driver(session) -> None:
        tasks = []
        t = t_start
        k = 0
        while k < spec.max_requests:
            t += float(rng.exponential(1.0 / max(spec.qps, 1e-9)))
            if t >= we:
                break
            await asyncio.sleep(max(t - time.perf_counter(), 0.0))
            if stop.is_set():
                break
            tasks.append(asyncio.create_task(one(k, session)))
            k += 1
        if tasks:
            await asyncio.gather(*tasks)

    clock = asyncio.create_task(phase_clock())
    async with aiohttp.ClientSession() as session:
        if spec.mode == "open":
            await open_driver(session)
        else:
            await asyncio.gather(*(closed_worker(w, session)
                                   for w in range(spec.concurrency)))
    await clock

    w0, w1 = window_open
    steady = [r for r in all_records
              if not r.error and w0 <= r.send_time < w1]
    summary = summarize(steady, (w0, w1), monitor=monitor)
    summary["warmup_excluded"] = float(
        sum(1 for r in all_records if r.send_time < w0))
    summary["errors"] = float(sum(1 for r in all_records if r.error))
    return LoadResult(records=steady, all_records=all_records,
                      window=(w0, w1), summary=summary)


def run_load(base_url: str, spec: LoadSpec,
             monitor=None) -> LoadResult:
    """Blocking entry point: drive ``base_url`` per ``spec``; if a
    ``PowerMonitor`` is given it is entered/exited at the steady-state
    window edges and the summary carries the energy ledger."""
    return asyncio.run(_run_load_async(base_url, spec, monitor=monitor))
