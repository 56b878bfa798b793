#!/usr/bin/env python3
"""Find a cell's knee once, by a sweep of offered rates in one process.

    python bench/sweep.py --workload <cell> --rates 1 2 3 --seconds 20 \
        [--set max_batch=16 ...]

One engine is set up as ``bench/run.py`` sets it up, then each rate runs
the cell's open-loop traffic (its ramp, a window of ``--seconds``, the
drain) and waits for the engine to fall idle before the next.  A rate is
sustained when both hold:

- output tokens arrive at 95% or more of the rate the requests offer
  (rate times the mix's mean output length);
- the TTFT p90 of the requests due in the window's last third is at most
  1.5 times that of its first third: no backlog grows.

The knee is the highest sustained rate; the cell runs at 0.8 times it.
The sweep stops after the first rate that is not sustained.  ``--set``
replaces engine settings of the cell's file for the sweep alone.  It prints a
line per rate and last a JSON line with them all.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
IDLE_WAIT_S = 20.0  # at most, for the engine to fall idle between rates


def _third(records, lo, hi, deadline):
    from bench import window

    due = window.in_window(records, lo, hi)
    return (window.percentile([window.ttft_s(r, deadline) for r in due], 90)
            if due else float("nan"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--ramp", type=float, default=None,
                    help="seconds of load before each window "
                         "(default: the cell's ramp_s)")
    ap.add_argument("--set", nargs="*", default=[], metavar="KEY=VALUE",
                    help="engine settings to replace (integers)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import numpy as np

    from bench import client, gen, harness, window

    c = harness.Cell.load(args.workload)
    for kv in args.set:
        key, value = kv.split("=", 1)
        c.cell["engine"][key] = int(value)
    harness.use_checkout_cache()
    ctx = multiprocessing.get_context("spawn")
    conn, child = ctx.Pipe()
    proc = ctx.Process(target=client.serve_jobs, args=(child,), daemon=True)
    proc.start()
    mean_out = float(np.mean(gen.quantiles(c.mix["output"], 4096)))
    rows = []
    try:
        s = harness.serve(c, args.seed)
        for rate in sorted(args.rates):
            load = dict(c.cell["load"], rate=rate)
            if args.ramp is not None:
                load["ramp_s"] = args.ramp
            _, recs, t0, t1, dl = harness.offer(s, c, conn, load, args.seed,
                                                args.seconds)
            summ = window.summarize(recs, t0, t1, dl)
            third = (t1 - t0) / 3
            first = _third(recs, t0, t0 + third, dl)
            last = _third(recs, t1 - third, t1, dl)
            tput = summ["output_tokens_per_s"] / (rate * mean_out)
            ok = tput >= 0.95 and last <= 1.5 * first
            rows.append(dict(rate=rate, sustained=ok, throughput_share=tput,
                             ttft_p90_first_third_s=first,
                             ttft_p90_last_third_s=last, **summ))
            print(json.dumps(rows[-1]), flush=True)
            t = time.perf_counter()
            while s.engine.busy and time.perf_counter() - t < IDLE_WAIT_S:
                time.sleep(0.5)
            if not ok:
                break
        s.handle.close()
    finally:
        conn.send(("stop",))
        proc.join(timeout=30)
        if proc.is_alive():
            proc.kill()
            proc.join()
    knee = max((r["rate"] for r in rows if r["sustained"]), default=None)
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "engine": c.cell["engine"], "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
