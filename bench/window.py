"""End-to-end metrics of one window, from the client's records.

A record (what ``bench/client.py`` returns for one request) is a dict:

    due      perf_counter time the request was due
    sent     perf_counter time its HTTP request was written
    arrivals [(t, n_tokens), ...] perf_counter arrival of each token chunk
    tokens   the token ids served, in order
    uid      the engine's request id (from the chunk ids), or -1
    done     True once the final chunk arrived
    error    "" or what went wrong

``perf_counter`` is CLOCK_MONOTONIC, one clock for every process of the
machine, so the client's stamps, the engine's and the harness's compare.
No JAX here.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

LATE_S = 0.1  # a send later than this is off the schedule (a few steps)


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile, ``q`` in [0, 100]: the smallest value with
    at least ``q`` percent of the sample at or below it."""
    if not xs:
        raise ValueError("percentile of an empty sample")
    ys = sorted(xs)
    k = max(int(math.ceil(len(ys) * q / 100.0)), 1) - 1
    return ys[min(k, len(ys) - 1)]


def in_window(records: Sequence[Dict], t0: float, t1: float) -> List[Dict]:
    """The requests due in ``[t0, t1)``: what the window attempted."""
    return [r for r in records if t0 <= r["due"] < t1]


def failed(rec: Dict, deadline: float) -> bool:
    """Erred, or no first token by ``deadline`` (the end of the drain)."""
    return bool(rec["error"]) or not rec["arrivals"] or (
        rec["arrivals"][0][0] > deadline)


def ttft_s(rec: Dict, deadline: float) -> float:
    """First token's arrival minus the due time.  A failed request has no
    first token: it counts as arriving at the deadline, later than every
    request that succeeded, so it sits in the tail it caused."""
    if failed(rec, deadline):
        return max(deadline - rec["due"], 0.0)
    return rec["arrivals"][0][0] - rec["due"]


def tpot_s(rec: Dict, t0: float, t1: float):
    """Mean gap between the request's tokens that arrived in ``[t0, t1]``:
    (last - first arrival) / (tokens - 1), or None under two tokens."""
    inside = [(t, n) for t, n in rec["arrivals"] if t0 <= t <= t1]
    n = sum(k for _, k in inside)
    if n < 2:
        return None
    return (inside[-1][0] - inside[0][0]) / (n - 1)


def tokens_in(records: Sequence[Dict], t0: float, t1: float) -> int:
    """Output tokens that arrived at the client in ``[t0, t1]``."""
    return sum(n for r in records for t, n in r["arrivals"] if t0 <= t <= t1)


def summarize(records: Sequence[Dict], t0: float, t1: float,
              deadline: float) -> Dict:
    """The window's end-to-end metrics and counts.

    TTFT is over every request due in the window; TPOT over every request
    with two or more tokens inside it, whenever it was due; tokens/s over
    the tokens that arrived inside it."""
    due = in_window(records, t0, t1)
    if not due:
        raise RuntimeError("no request was due in the window")
    ttfts = [ttft_s(r, deadline) for r in due]
    tpots = [x for x in (tpot_s(r, t0, t1) for r in records)
             if x is not None]
    out = {
        "attempted": len(due),
        "failed": sum(failed(r, deadline) for r in due),
        "ttft_p90_ms": percentile(ttfts, 90) * 1e3,
        "ttft_p50_ms": percentile(ttfts, 50) * 1e3,
        "output_tokens_per_s": tokens_in(records, t0, t1) / (t1 - t0),
        "tpot_requests": len(tpots),
    }
    if tpots:
        out["tpot_p90_ms"] = percentile(tpots, 90) * 1e3
        out["tpot_p50_ms"] = percentile(tpots, 50) * 1e3
    return out


def lateness(records: Sequence[Dict]) -> Dict:
    """How late the client sent against the due times, and how many sends
    were more than ``LATE_S`` late: each of those left the cell's
    schedule, so the run offered other load than the cell states."""
    late = sorted(r["sent"] - r["due"] for r in records if r["sent"] > 0)
    if not late:
        return {"late_p50_ms": 0.0, "late_p99_ms": 0.0, "late_max_ms": 0.0,
                "late_sends": 0}
    return {"late_p50_ms": percentile(late, 50) * 1e3,
            "late_p99_ms": percentile(late, 99) * 1e3,
            "late_max_ms": late[-1] * 1e3,
            "late_sends": sum(x > LATE_S for x in late)}
