#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout.  The run needs a TPU with at least as many chips as the cell
asks for; without one it exits non-zero and prints no result.  The last
line of standard output is the result: one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, then ``schedule`` (the client's sends off the cell's
schedule, ``bench/window.py:lateness``), and last ``checks``, each number
compared beside its limit.
``--out`` names a directory for the run's file of details (default
``bench_runs/`` in the checkout).

This module imports only the standard library at its top level: the
client's spawned process re-imports it and must never import JAX.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

STARTED = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=str(ROOT / "bench_runs"))
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness

    return harness.main(args, started=STARTED)


if __name__ == "__main__":
    sys.exit(main())
