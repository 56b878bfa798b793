"""Steady-state serving benchmark over HTTP.

Stands up the OpenAI-compatible server on an in-process engine, drives
it with the closed/open-loop load generator (warmup, then a fixed
steady-state window with the power monitor bracketing exactly that
window), and reports client-side latencies next to the engine's own —
plus the energy ledger, where the sum of per-request token-weighted
``joules_between`` windows must equal ``PowerMonitor.result().joules``
exactly under the step-function model.

    python -m repro.launch.bench_serve --arch qwen1.5-0.5b --smoke \
        --mode closed --concurrency 2 --warmup-s 1 --duration-s 3 \
        --max-new 8 --power-reader synthetic --check

``--check`` turns the measurement-protocol acceptance criteria into hard
assertions (non-zero exit on violation): steady-state requests were
measured, client TTFT/TPOT agree with engine-side within
``--ttft-tolerance-ms``, the energy ledger tiles exactly, and the
achieved power sample rate is at least half the configured target.
"""

from __future__ import annotations

import argparse
import json

import jax

from repro.configs import get_config
from repro.core import report
from repro.core.energy import (DeviceMonitorGroup, ModelReader, PowerMonitor,
                               ProcStatReader, SyntheticReader)
from repro.core.hardware import hardware_for_device
from repro.launch.cache import setup_compilation_cache
from repro.launch.mesh import make_tp_mesh
from repro.models import model as model_lib
from repro.serving.engine import ServingEngine
from repro.serving.loadgen import LoadSpec, prewarm_engine, run_load
from repro.serving.server import start_http_server


def _make_reader(kind: str):
    if kind == "proc":
        return ProcStatReader()
    if kind == "model":
        # idle/TDP watts of the device this process serves on
        hw = hardware_for_device()
        return ModelReader(idle_watts=hw.idle_watts, tdp_watts=hw.tdp_watts)
    if kind == "synthetic":
        import math

        return SyntheticReader(lambda t: 40.0 + 10.0 * math.sin(t * 7.0))
    return None


def _check(summary, args) -> None:
    """Measurement-protocol gates (ISSUE acceptance criteria)."""
    fails = []
    if summary["steady_requests"] < 1:
        fails.append("no requests completed inside the steady-state window "
                     "(increase --duration-s or lower --warmup-s)")
    d_ttft = summary["ttft_client_minus_engine_ms"]
    if not (-1.0 <= d_ttft <= args.ttft_tolerance_ms):
        fails.append(f"client-vs-engine TTFT delta {d_ttft:.1f} ms outside "
                     f"[-1, {args.ttft_tolerance_ms}] ms")
    d_tpot = summary["tpot_client_minus_engine_ms"]
    if abs(d_tpot) > args.ttft_tolerance_ms / 5.0:
        fails.append(f"client-vs-engine TPOT delta {d_tpot:.2f} ms beyond "
                     f"{args.ttft_tolerance_ms / 5.0:.0f} ms")
    if "joules_total" in summary:
        total = summary["joules_total"]
        attributed = summary["joules_attributed"]
        if abs(attributed - total) > 1e-9 * max(abs(total), 1.0):
            fails.append(f"energy ledger drift: per-request windows sum to "
                         f"{attributed!r} J but the run total is {total!r} J")
        min_rate = 0.5 / args.power_interval
        if summary["power_samples_per_sec"] < min_rate:
            fails.append(f"power sampler achieved "
                         f"{summary['power_samples_per_sec']:.1f} Hz, below "
                         f"{min_rate:.1f} Hz (half the configured target)")
    if fails:
        raise SystemExit("--check failed:\n  - " + "\n  - ".join(fails))
    print("# --check passed: steady-state protocol + energy ledger OK")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prefill-chunk", type=int, default=0)
    ap.add_argument("--mode", default="closed", choices=["closed", "open"],
                    help="closed = concurrency-N workers (next request the "
                         "moment the previous finishes); open = Poisson "
                         "arrivals at --qps independent of completions")
    ap.add_argument("--concurrency", type=int, default=2,
                    help="closed-loop requests in flight")
    ap.add_argument("--qps", type=float, default=4.0,
                    help="open-loop mean arrival rate")
    ap.add_argument("--warmup-s", type=float, default=1.0,
                    help="unmeasured ramp (JIT compilation, cache fill) "
                         "before the steady-state window opens")
    ap.add_argument("--duration-s", type=float, default=5.0,
                    help="steady-state measurement window; only requests "
                         "sent inside it are counted")
    ap.add_argument("--max-requests", type=int, default=10_000)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--power-reader", default="synthetic",
                    choices=["proc", "model", "synthetic", "none"])
    ap.add_argument("--power-interval", type=float, default=0.1,
                    help="power sample interval in seconds (0.1 = the "
                         "paper's 10 Hz)")
    ap.add_argument("--check", action="store_true",
                    help="assert the measurement protocol held: client/"
                         "engine latency agreement, exact energy-ledger "
                         "tiling, achieved sampler rate")
    ap.add_argument("--ttft-tolerance-ms", type=float, default=250.0,
                    help="--check bound on mean client-minus-engine TTFT")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel devices: shard the served model "
                         "over a (tp,) mesh with one power monitor per "
                         "device (streams stay byte-identical to --tp 1; "
                         "on CPU force a multi-device host with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    args = ap.parse_args(argv)
    setup_compilation_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.power_reader == "none":
        monitor = None
    elif args.tp > 1:
        monitor = DeviceMonitorGroup(
            [_make_reader(args.power_reader) for _ in range(args.tp)],
            interval_s=args.power_interval)
    else:
        monitor = PowerMonitor(_make_reader(args.power_reader),
                               interval_s=args.power_interval)

    tp_mesh = make_tp_mesh(args.tp) if args.tp > 1 else None
    params, param_axes = model_lib.init(cfg, jax.random.PRNGKey(args.seed))
    engine = ServingEngine(cfg, params, max_batch=args.max_batch,
                           max_len=args.max_len, seed=args.seed,
                           mesh=tp_mesh,
                           param_axes=(param_axes if tp_mesh is not None
                                       else None),
                           prefill_chunk=args.prefill_chunk)
    if monitor is not None:
        engine.attach_monitor(monitor)
    prewarm_engine(engine, prompt_len=args.prompt_len,
                   concurrency=min(args.concurrency, args.max_batch),
                   vocab_size=cfg.vocab_size, seed=args.seed)
    handle = start_http_server(engine, model_name=cfg.name)
    spec = LoadSpec(mode=args.mode, concurrency=args.concurrency,
                    qps=args.qps, warmup_s=args.warmup_s,
                    duration_s=args.duration_s,
                    max_requests=args.max_requests,
                    prompt_len=args.prompt_len, max_new=args.max_new,
                    temperature=args.temperature,
                    vocab_size=cfg.vocab_size, seed=args.seed)
    print(f"# driving {handle.url} : mode={spec.mode} "
          f"warmup={spec.warmup_s}s window={spec.duration_s}s")
    try:
        result = run_load(handle.url, spec, monitor=monitor)
        engine_summary = handle.server.summary()
    finally:
        handle.close()

    summary = result.summary
    print(json.dumps(summary, indent=2, default=float))
    print("\n## Client-side steady state\n")
    print(report.to_markdown(report.serving_client_rows(summary)))
    print("\n## Engine-side (same run, via /metrics ledger)\n")
    print(report.to_markdown(report.serving_summary_rows(engine_summary)))
    if args.check:
        _check(summary, args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
