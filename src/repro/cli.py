"""The ``elana`` command-line interface (paper §1: "run a command from the
terminal without modifying the code").

    elana archs
    elana size    --arch llama3.1-8b
    elana cache   --arch nemotron-h-8b --batch 128 --seq-len 2048
    elana latency --arch tinyllama-1.1b --smoke --batch 1 --prompt 64 --gen 16
    elana energy  --arch tinyllama-1.1b --smoke --batch 1 --prompt 64 --gen 16
    elana estimate --arch qwen2.5-7b --hardware a6000 --batch 1 --prompt 512 --gen 512
    elana trace   --arch llama3.1-8b --hardware tpu-v5e --out trace.json
    elana report  --hardware a6000
    elana dryrun  --arch minitron-4b --shape train_4k --multi-pod
"""

from __future__ import annotations

import argparse
import json
import sys

from repro.launch.cache import setup_compilation_cache


def _add_common(p, smoke_default=False):
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true", default=smoke_default,
                   help="use the reduced (CPU-runnable) config variant")
    p.add_argument("--unit", default="GB", help="GB (SI, default) or GiB")


def cmd_archs(args) -> int:
    from repro.configs import ASSIGNED, PAPER

    print("assigned pool:")
    for a in ASSIGNED:
        print(f"  {a}")
    print("paper models:")
    for a in PAPER:
        print(f"  {a}")
    return 0


def cmd_size(args) -> int:
    from repro.core.profiler import Elana

    rep = Elana(args.arch, smoke=args.smoke).size_report()
    print(rep.fmt(args.unit))
    return 0


def cmd_cache(args) -> int:
    from repro.core.profiler import Elana

    rep = Elana(args.arch, smoke=args.smoke).cache_report(args.batch, args.seq_len)
    print(rep.fmt(args.unit))
    return 0


def cmd_latency(args) -> int:
    from repro.core.profiler import Elana

    setup_compilation_cache()
    out = Elana(args.arch, smoke=args.smoke).measure(
        batch=args.batch, prompt_len=args.prompt, gen_len=args.gen,
        iters=args.iters,
    )
    print(json.dumps(out, indent=2))
    return 0


def cmd_energy(args) -> int:
    from repro.core import energy as energy_lib
    from repro.core.hardware import get_hardware, hardware_for_device
    from repro.core.profiler import Elana

    setup_compilation_cache()
    # the spec of the device this run measures on, unless named explicitly
    hw = get_hardware(args.hardware) if args.hardware else hardware_for_device()
    reader = energy_lib.ProcStatReader(hw.idle_watts, hw.tdp_watts) \
        if hw.kind == "cpu" else energy_lib.ModelReader(
            hw.idle_watts, hw.tdp_watts)
    out = Elana(args.arch, smoke=args.smoke).measure(
        batch=args.batch, prompt_len=args.prompt, gen_len=args.gen,
        iters=args.iters, power_reader=reader,
    )
    print(json.dumps(out, indent=2))
    return 0


def cmd_estimate(args) -> int:
    from repro.core import report
    from repro.core.profiler import Elana

    est = Elana(args.arch, smoke=args.smoke).estimate(
        hardware=args.hardware, n_devices=args.n_devices, mode=args.mode,
        batch=args.batch, prompt_len=args.prompt, gen_len=args.gen,
    )
    print(report.to_markdown(report.table3_rows([est])))
    for ph in (est.ttft, est.tpot):
        print(f"  {ph.name}: bound={ph.bound} compute={ph.compute_s*1e3:.2f}ms "
              f"memory={ph.memory_s*1e3:.2f}ms coll={ph.collective_s*1e3:.2f}ms "
              f"avg_watts={ph.avg_watts:.0f}")
    return 0


def cmd_trace(args) -> int:
    from repro.core.profiler import Elana

    summary = Elana(args.arch, smoke=args.smoke).trace(
        args.out, hardware=args.hardware, phase=args.phase,
        batch=args.batch, seq_len=args.seq_len,
    )
    print(f"wrote {args.out} (open at https://ui.perfetto.dev)")
    print(json.dumps(summary, indent=2))
    return 0


def cmd_report(args) -> int:
    from repro.core import report
    from repro.core.profiler import Elana
    from repro.configs import PAPER

    archs = args.archs.split(",") if args.archs else PAPER
    sizes, caches, ests = [], {}, []
    for a in archs:
        e = Elana(a)
        sizes.append(e.size_report())
        caches[e.cfg.name] = {
            (1, 1024): e.cache_report(1, 1024),
            (128, 1024): e.cache_report(128, 1024),
            (128, 2048): e.cache_report(128, 2048),
        }
        ests.append(e.estimate(hardware=args.hardware, batch=1,
                               prompt_len=512, gen_len=512))
    print("## Table 2: model + cache size")
    print(report.to_markdown(report.table2_rows(sizes, caches)))
    print()
    print(f"## Table 3-style: latency/energy on {args.hardware} (estimator)")
    print(report.to_markdown(report.table3_rows(ests)))
    return 0


def cmd_dryrun(args) -> int:
    # Heavy import chain + XLA_FLAGS env var: delegate to the launch module
    # in a fresh interpreter so device count forcing works.  The dry run
    # analyses 512 forced host devices on the CPU: it never takes a chip
    # (this parent has not imported JAX, and the child is held to the CPU).
    import os
    import subprocess

    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", args.arch,
           "--shape", args.shape]
    if args.multi_pod:
        cmd.append("--multi-pod")
    return subprocess.call(cmd, env={**os.environ, "JAX_PLATFORMS": "cpu"})


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="elana",
        description="ELANA-JAX: energy & latency analyzer for LLMs (TPU-native)",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    sub.add_parser("archs").set_defaults(fn=cmd_archs)

    p = sub.add_parser("size")
    _add_common(p)
    p.set_defaults(fn=cmd_size)

    p = sub.add_parser("cache")
    _add_common(p)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--seq-len", type=int, default=1024)
    p.set_defaults(fn=cmd_cache)

    for name, fn in (("latency", cmd_latency), ("energy", cmd_energy)):
        p = sub.add_parser(name)
        _add_common(p)
        p.add_argument("--batch", type=int, default=1)
        p.add_argument("--prompt", type=int, default=64)
        p.add_argument("--gen", type=int, default=16)
        p.add_argument("--iters", type=int, default=5)
        p.set_defaults(fn=fn)
    p.add_argument("--hardware", default=None,
                   help="spec whose idle/TDP watts model the energy "
                        "(default: the device_kind JAX reports)")

    p = sub.add_parser("estimate")
    _add_common(p)
    p.add_argument("--hardware", default="tpu-v5e")
    p.add_argument("--n-devices", type=int, default=1)
    p.add_argument("--mode", default="tp", choices=["tp", "dp", "naive_pp"])
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--prompt", type=int, default=512)
    p.add_argument("--gen", type=int, default=512)
    p.set_defaults(fn=cmd_estimate)

    p = sub.add_parser("trace")
    _add_common(p)
    p.add_argument("--hardware", default="tpu-v5e")
    p.add_argument("--phase", default="decode", choices=["decode", "prefill"])
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--out", default="elana_trace.json")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser("report")
    p.add_argument("--archs", default="")
    p.add_argument("--hardware", default="a6000")
    p.set_defaults(fn=cmd_report)

    p = sub.add_parser("dryrun")
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", default="train_4k")
    p.add_argument("--multi-pod", action="store_true")
    p.set_defaults(fn=cmd_dryrun)
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
