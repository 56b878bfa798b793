"""Benchmark harness: one module per paper table/figure + the roofline
aggregation.  Prints per-benchmark ``name,us_per_call,derived`` CSV at the
end and writes the rendered tables to ``benchmarks/RESULTS.md``.

    PYTHONPATH=src python -m benchmarks.run [--only table2,roofline]
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import List

from benchmarks import (kernel_bench, measured_cpu, roofline, serving_bench,
                        sharded_bench, speculative_bench, table2_size,
                        table3_latency_energy, table4_jetson, trace_demo)

MODULES = {
    "table2": table2_size,            # paper Table 2
    "table3": table3_latency_energy,  # paper Table 3
    "table4": table4_jetson,          # paper Table 4
    "trace": trace_demo,              # paper Figure 1
    "measured": measured_cpu,         # §2.3/2.4 measured mode
    "kernels": kernel_bench,          # Pallas kernel reference timings
    "serving": serving_bench,         # fused vs per-slot decode loop
    "speculative": speculative_bench,  # prompt-lookup drafting vs plain decode
    "sharded": sharded_bench,         # tp=2 vs tp=1 sharding equivalence
    "roofline": roofline,             # assignment §Roofline (from dry-run JSONs)
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated module keys")
    args = ap.parse_args(argv)
    keys = ([k.strip() for k in args.only.split(",") if k.strip()]
            if args.only else list(MODULES))
    unknown = sorted(set(keys) - set(MODULES))
    if unknown:
        ap.error(f"unknown module key(s): {', '.join(unknown)} "
                 f"(available: {', '.join(MODULES)})")

    csv_rows: List[str] = []
    sections: List[str] = []
    failed: List[str] = []
    for key in keys:
        mod = MODULES[key]
        print(f"[bench] {key} ...", flush=True)
        t0 = time.perf_counter()
        try:
            sections.append(mod.run(csv_rows))
        except Exception as e:  # run the rest, record it, exit non-zero
            sections.append(f"## {key}: FAILED\n```\n{e!r}\n```")
            csv_rows.append(f"{key},0,FAILED")
            failed.append(key)
        print(f"[bench] {key} done in {time.perf_counter()-t0:.1f}s", flush=True)

    out_md = os.path.join(os.path.dirname(__file__), "RESULTS.md")
    with open(out_md, "w") as f:
        f.write("\n\n".join(sections) + "\n")

    print("\n\n".join(sections))
    print("\n=== CSV (name,us_per_call,derived) ===")
    print("name,us_per_call,derived")
    for row in csv_rows:
        print(row)
    print(f"\nwrote {out_md}")
    if failed:
        print(f"[bench] FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
