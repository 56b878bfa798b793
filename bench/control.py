#!/usr/bin/env python3
"""Readings that a cell's limit on ``max_logit_gap`` is set from.

    python bench/control.py --workload <cell> --seeds 1 2 3 --seconds 20

For each seed, one run of the cell as ``bench/run.py`` makes it (set-up,
a window at the cell's own load, the reference over a sample of the
requests the window finished), all in this one process, and then the
control on the same sample: the reference put in the program's place in
float8 (e4m3, per-tensor scales), the precision step below the bf16 the
configuration states, judged by the harness's own check.  It prints, per
seed, the program's widest gap and ``correct`` and the control's, and
last a JSON line with them all.  The benchmark's own
runs do not run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    c = harness.Cell.load(args.workload)
    harness.use_checkout_cache()
    rows = []
    for seed in args.seeds:
        r = harness.measure(c, seed, args.seconds, False,
                            started=time.perf_counter(), control=True)
        d = r["detail"]
        rows.append({
            "seed": seed,
            "program": r["checks"]["max_logit_gap"]["value"],
            "program_correct": r["correct"],
            "control": d["control"]["checks"]["max_logit_gap"]["value"],
            "control_correct": d["control"]["correct"],
            "sample_requests": d["sample_requests"],
            "sample_tokens": d["sample_tokens"],
            "attempted": r["attempted"], "failed": r["failed"]})
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
