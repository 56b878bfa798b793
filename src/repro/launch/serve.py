"""Production serving driver: open-loop traffic against the device-resident
continuous-batching engine, with per-request energy attribution.

    python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --arrival-rate 4 --requests 8 --max-new 16 --max-batch 4

``--arrival-rate 0`` submits every request up front (the legacy closed-loop
mode); otherwise arrivals follow a Poisson process at the given rate.
``--replay t:plen:max_new,t:plen:max_new,...`` replays a deterministic
schedule instead.  Energy is sampled by a ``core.energy`` power reader
(``--power-reader proc|model|synthetic|none``) and attributed to requests
proportionally to the tokens each emitted within every measured window.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import report
from repro.core.energy import (DeviceMonitorGroup, ModelReader, PowerMonitor,
                               ProcStatReader, SyntheticReader)
from repro.core.hardware import hardware_for_device
from repro.launch.cache import setup_compilation_cache
from repro.launch.mesh import make_tp_mesh
from repro.models import model as model_lib
from repro.serving.engine import ServingEngine
from repro.models import cache as cache_lib
from repro.serving.workload import (LengthDist, OpenLoopDriver, WorkloadSpec,
                                    bursty_trace, estimate_concurrency,
                                    lookup_friendly_trace, poisson_trace,
                                    replay_trace, shared_prefix_trace)


def _make_reader(kind: str):
    if kind == "proc":
        return ProcStatReader()
    if kind == "model":
        # idle/TDP watts of the device this process serves on
        hw = hardware_for_device()
        return ModelReader(idle_watts=hw.idle_watts, tdp_watts=hw.tdp_watts)
    if kind == "synthetic":
        return SyntheticReader(lambda t: 42.0)
    return None


def _make_monitor(kind: str, n_devices: int):
    """One PowerMonitor, or — under --tp — a per-device monitor group whose
    windowed joules tile exactly to the aggregate (on CPU each per-device
    reader is a proxy; real NVML/jtop readers bind one device each)."""
    if kind == "none":
        return None
    if n_devices > 1:
        return DeviceMonitorGroup([_make_reader(kind)
                                   for _ in range(n_devices)])
    return PowerMonitor(_make_reader(kind))


def _parse_replay(text: str):
    rows = []
    for item in text.split(","):
        try:
            t, plen, max_new = item.split(":")
            rows.append((float(t), int(plen), int(max_new)))
        except ValueError:
            raise ValueError(
                f"bad --replay item {item!r}: expected t:plen:max_new")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrivals/sec; 0 = submit all up front")
    ap.add_argument("--prompt-len-dist", default="uniform",
                    choices=["fixed", "uniform", "lognormal"])
    ap.add_argument("--prompt-len-mean", type=float, default=24.0)
    ap.add_argument("--replay", default="",
                    help="deterministic schedule t:plen:max_new,... "
                         "(overrides --arrival-rate)")
    ap.add_argument("--power-reader", default="proc",
                    choices=["proc", "model", "synthetic", "none"])
    ap.add_argument("--http-port", type=int, default=0,
                    help="serve over HTTP instead of replaying a trace: "
                         "start the OpenAI-compatible server (POST "
                         "/v1/completions with stream=true SSE, /v1/models, "
                         "/metrics) on this port and run until Ctrl-C "
                         "(0 = off; workload flags are ignored)")
    ap.add_argument("--http-host", default="127.0.0.1",
                    help="bind address for --http-port")
    ap.add_argument("--cache-layout", default="contiguous",
                    choices=["contiguous", "paged"],
                    help="KV layout: worst-case contiguous slots or a "
                         "shared block pool with per-slot block tables")
    ap.add_argument("--kv-block-size", type=int, default=16)
    ap.add_argument("--kv-num-blocks", default="0",
                    help="paged pool size in blocks; 0 = worst case, "
                         "'auto' = size from the workload trace (p95 "
                         "sequence length x estimated concurrency, "
                         "cache.suggest_num_blocks — pair with "
                         "--preemption recompute so a bursty tail "
                         "preempts instead of failing); smaller pools "
                         "trade pressure handling for device memory")
    ap.add_argument("--preemption", default="off",
                    choices=["off", "recompute"],
                    help="KV pool overcommit policy (paged layout only): "
                         "'off' reserves each request's worst case at "
                         "admission and backpressures; 'recompute' "
                         "reserves lazily, grows per decode step, and on "
                         "a dry pool preempts the newest in-flight "
                         "request (never the head-of-line), re-admitting "
                         "it later by recomputing its prompt + generated "
                         "prefix")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: split prompt prefills into "
                         "chunks of this many tokens, interleaved with "
                         "decode steps (0 = whole-prompt admission); bounds "
                         "how long in-flight decodes stall on a new prompt")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="prompt tokens of chunk work per engine step "
                         "(0 = one chunk; clamped to >= --prefill-chunk); "
                         "only meaningful with --prefill-chunk")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="block-level prefix caching (paged layout only): "
                         "hash full prompt blocks and share resident "
                         "read-only pool blocks across requests with a "
                         "common prefix, skipping their prefill")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="generate a shared-prefix workload instead of "
                         "independent prompts: every request starts with "
                         "one of --shared-prefixes fixed system prompts of "
                         "this many tokens (0 = off)")
    ap.add_argument("--shared-prefixes", type=int, default=2,
                    help="number of distinct system prompts in the "
                         "shared-prefix mixture")
    ap.add_argument("--shared-suffix-len", type=int, default=16,
                    help="user-suffix tokens appended to each shared "
                         "prefix (fixed: equal padded lengths are what "
                         "lets prefix blocks match); the --prompt-len-* "
                         "flags are ignored in shared-prefix mode")
    ap.add_argument("--unified-step", default="on", choices=["on", "off"],
                    help="fuse the packed chunked-prefill frontier and the "
                         "decode+sample step into ONE device dispatch per "
                         "engine step (needs --prefill-chunk > 0; 'off' "
                         "dispatches one chunk per cursor plus a decode "
                         "step — the pre-fusion path, kept for A/B runs)")
    ap.add_argument("--pad-side", default="left", choices=["left", "right"],
                    help="prompt-bucket padding side: 'right' keeps content "
                         "at the row start so variable-length suffixes of a "
                         "shared prefix land on the same cached block "
                         "boundaries (better --prefix-cache hit rates; "
                         "token streams differ from 'left' because RoPE "
                         "positions shift)")
    ap.add_argument("--speculative", default="off",
                    choices=["off", "lookup"],
                    help="speculative decoding: 'lookup' drafts each "
                         "request's next tokens from its own prompt + "
                         "generated history (prompt-lookup n-grams, no "
                         "draft model) and verifies the whole window in "
                         "ONE batched dispatch — token streams stay "
                         "byte-identical to 'off'; only the tokens-per-"
                         "dispatch economics change")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="max draft tokens per verify window with "
                         "--speculative lookup (the window scores "
                         "k + 1 positions; see docs/tuning.md for "
                         "choosing k)")
    ap.add_argument("--lookup-friendly", action="store_true",
                    help="generate the self-similar workload speculation "
                         "thrives on (each prompt is one motif tiled; "
                         "generation keeps cycling it, so prompt-lookup "
                         "drafts verify at accept rates near 1)")
    ap.add_argument("--motif-len", type=int, default=8,
                    help="motif tokens per --lookup-friendly prompt")
    ap.add_argument("--motif-repeats", type=int, default=4,
                    help="times each --lookup-friendly motif is tiled")
    ap.add_argument("--bursty", action="store_true",
                    help="generate the bursty overcommit workload "
                         "(waves of simultaneous arrivals) instead of "
                         "Poisson traffic — the scenario --preemption "
                         "recompute exists for")
    ap.add_argument("--burst-size", type=int, default=4,
                    help="requests per wave of the --bursty trace")
    ap.add_argument("--burst-gap", type=float, default=0.25,
                    help="seconds between --bursty waves")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel devices: shard heads/FFN over a "
                         "(tp,) mesh inside the fused engine step, with "
                         "per-device KV shards and per-device power "
                         "monitors (token streams stay byte-identical to "
                         "--tp 1; on CPU force a multi-device host with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N)")
    args = ap.parse_args(argv)
    setup_compilation_cache()
    if args.prefix_cache and args.cache_layout != "paged":
        ap.error("--prefix-cache requires --cache-layout paged")
    if args.preemption != "off" and args.cache_layout != "paged":
        ap.error("--preemption recompute requires --cache-layout paged")
    if args.kv_num_blocks != "auto":
        try:
            args.kv_num_blocks = int(args.kv_num_blocks)
        except ValueError:
            ap.error("--kv-num-blocks takes an integer or 'auto'")

    cfg = get_config(args.arch, smoke=args.smoke)
    plo = max(int(args.prompt_len_mean // 4), 1)
    phi = max(int(args.prompt_len_mean * 2), plo + 1)
    spec = WorkloadSpec(
        arrival_rate=args.arrival_rate,
        num_requests=args.requests,
        prompt_len=LengthDist(kind=args.prompt_len_dist,
                              mean=args.prompt_len_mean, low=plo, high=phi),
        output_len=LengthDist(kind="fixed", mean=args.max_new,
                              low=1, high=max(args.max_new, 1)),
        temperature=args.temperature,
        seed=args.seed,
    )
    if args.replay:
        try:
            schedule = _parse_replay(args.replay)
        except ValueError as e:
            ap.error(str(e))
        arrivals = replay_trace(schedule, cfg.vocab_size,
                                seed=args.seed,
                                temperature=args.temperature, top_k=20)
    elif args.bursty:
        bursts = max(-(-args.requests // max(args.burst_size, 1)), 1)
        arrivals = bursty_trace(
            cfg.vocab_size, bursts=bursts, burst_size=args.burst_size,
            gap_s=args.burst_gap,
            prompt_len=max(int(args.prompt_len_mean), 1),
            max_new=args.max_new, seed=args.seed,
            temperature=args.temperature, top_k=20)[:args.requests]
    elif args.lookup_friendly:
        arrivals = lookup_friendly_trace(
            cfg.vocab_size, num_requests=args.requests,
            motif_len=args.motif_len, repeats=args.motif_repeats,
            max_new=args.max_new, arrival_rate=args.arrival_rate,
            seed=args.seed, temperature=args.temperature, top_k=20)
    elif args.shared_prefix_len > 0:
        arrivals = shared_prefix_trace(
            cfg.vocab_size, num_requests=args.requests,
            shared_prefix_len=args.shared_prefix_len,
            num_prefixes=args.shared_prefixes,
            suffix_len=args.shared_suffix_len,
            max_new=args.max_new, arrival_rate=args.arrival_rate,
            seed=args.seed, temperature=args.temperature, top_k=20)
    else:
        arrivals = poisson_trace(spec, cfg.vocab_size)

    kv_num_blocks = args.kv_num_blocks
    if kv_num_blocks == "auto":
        if args.cache_layout != "paged":
            ap.error("--kv-num-blocks auto requires --cache-layout paged")
        seq_lens = [len(a.prompt) + a.params.max_new_tokens
                    for a in arrivals]
        kv_num_blocks = cache_lib.suggest_num_blocks(
            seq_lens, args.kv_block_size, args.max_len, args.max_batch,
            concurrency=estimate_concurrency(arrivals, args.max_batch))
        worst = cache_lib.default_num_blocks(
            args.max_batch, args.max_len, args.kv_block_size)
        print(f"# --kv-num-blocks auto -> {kv_num_blocks} blocks "
              f"(worst case {worst}); pair with --preemption recompute "
              f"to survive a bursty tail")

    monitor = _make_monitor(args.power_reader, args.tp)
    # --tp > 1: the engine owns its (tp,) mesh (entered around every
    # trace/dispatch); --tp 1 runs unsharded on the first device
    tp_mesh = make_tp_mesh(args.tp) if args.tp > 1 else None
    params, param_axes = model_lib.init(cfg, jax.random.PRNGKey(args.seed))
    engine = ServingEngine(cfg, params, max_batch=args.max_batch,
                           max_len=args.max_len, seed=args.seed,
                           mesh=tp_mesh,
                           param_axes=(param_axes if tp_mesh is not None
                                       else None),
                           cache_layout=args.cache_layout,
                           kv_block_size=args.kv_block_size,
                           kv_num_blocks=kv_num_blocks,
                           prefill_chunk=args.prefill_chunk,
                           prefill_budget=args.prefill_budget,
                           prefix_cache=args.prefix_cache,
                           preemption=args.preemption,
                           unified_step=args.unified_step == "on",
                           pad_side=args.pad_side,
                           speculative=args.speculative,
                           spec_tokens=args.spec_tokens)
    if args.http_port:
        from repro.serving.server import start_http_server

        if monitor is not None:
            engine.attach_monitor(monitor)
            monitor.__enter__()
        handle = start_http_server(engine, host=args.http_host,
                                   port=args.http_port,
                                   model_name=cfg.name)
        print(f"# serving {cfg.name} at {handle.url} "
              f"(POST /v1/completions; Ctrl-C to stop)")
        try:
            while True:
                time.sleep(1.0)
        except KeyboardInterrupt:
            pass
        handle.close()
        if monitor is not None:
            monitor.__exit__(None, None, None)
        summary = handle.server.summary()
        print(json.dumps(summary, indent=2, default=float))
        print("\n## Latency percentiles\n")
        print(report.to_markdown(report.serving_summary_rows(summary)))
        return 0
    driver = OpenLoopDriver(engine, arrivals)
    if monitor is not None:
        engine.attach_monitor(monitor)
        with monitor:
            finished = driver.run()
    else:
        finished = driver.run()

    summary = engine.latency_summary()
    print(json.dumps(summary, indent=2))
    print("\n## Latency percentiles\n")
    print(report.to_markdown(report.serving_summary_rows(summary)))
    throughput = report.serving_throughput_rows(summary)
    if throughput:
        print("\n## Step economics\n")
        print(report.to_markdown(throughput))
    print(f"\n## Per-request ({summary.get('joules_source', 'no')} energy "
          f"attributed per token window)\n")
    print(report.to_markdown(report.serving_request_rows(
        sorted(finished, key=lambda r: r.uid))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
