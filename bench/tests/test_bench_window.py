"""The harness's window arithmetic on synthetic records, and the traffic
generator's determinism and distributions.  No JAX, no chip."""

import statistics
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import gen, window  # noqa: E402


def rec(due, arrivals, sent=None, error="", done=True, tokens=None):
    n = sum(k for _, k in arrivals)
    return {"idx": 0, "due": due, "sent": due if sent is None else sent,
            "arrivals": list(arrivals), "tokens": tokens or [0] * n,
            "uid": 0, "done": done, "error": error}


def test_ttft_runs_from_the_due_time_not_the_send():
    # due at 1.0, sent late at 1.5 (the client stalled), first token at 1.7
    r = rec(1.0, [(1.7, 1)], sent=1.5)
    assert window.ttft_s(r, deadline=20.0) == pytest.approx(0.7)
    s = window.summarize([r], 0.0, 10.0, 15.0)
    assert s["ttft_p90_ms"] == pytest.approx(700.0)


def test_tokens_per_s_counts_only_arrivals_inside_the_window():
    early = rec(-2.0, [(-1.0, 1), (0.5, 1), (1.0, 1)])   # 2 inside
    late = rec(9.0, [(9.5, 1), (10.5, 1), (11.0, 3)])    # 1 inside
    s = window.summarize([early, late], 0.0, 10.0, 15.0)
    assert s["output_tokens_per_s"] == pytest.approx(3 / 10.0)
    assert window.tokens_in([early, late], 0.0, 10.0) == 3


def test_tpot_uses_only_in_window_gaps():
    # a request from the ramp: tokens at -1, 0.2, 0.4, 0.6 and, after the
    # window, 10.5 and 12: only the three inside count, two gaps of 0.2
    r = rec(-3.0, [(-1.0, 1), (0.2, 1), (0.4, 1), (0.6, 1), (10.5, 1),
                   (12.0, 1)])
    assert window.tpot_s(r, 0.0, 10.0) == pytest.approx(0.2)
    assert window.tpot_s(rec(1.0, [(2.0, 1)]), 0.0, 10.0) is None
    # a chunk of three tokens at once: (last - first) / (tokens - 1)
    r3 = rec(1.0, [(2.0, 1), (2.4, 3)])
    assert window.tpot_s(r3, 0.0, 10.0) == pytest.approx(0.4 / 3)


@pytest.mark.parametrize("kind", ["drained", "errored", "no_token"])
def test_drained_and_errored_requests_count_as_failed(kind):
    ok = rec(1.0, [(1.1, 1), (1.2, 1)])
    bad = {"drained": rec(2.0, [(16.0, 1)], done=False),
           "errored": rec(2.0, [], error="HTTP 500", done=False),
           "no_token": rec(2.0, [], done=False)}[kind]
    s = window.summarize([ok, bad], 0.0, 10.0, deadline=15.0)
    assert s["attempted"] == 2 and s["failed"] == 1
    # the failed request sits in the tail at the deadline, not below it
    assert window.ttft_s(bad, 15.0) == pytest.approx(13.0)
    assert s["ttft_p90_ms"] == pytest.approx(13000.0)


def test_requests_due_outside_the_window_are_not_attempted():
    rs = [rec(-0.5, [(0.1, 1)]), rec(0.0, [(0.1, 1)]),
          rec(9.99, [(10.2, 1)]), rec(10.0, [(10.1, 1)])]
    assert [r["due"] for r in window.in_window(rs, 0.0, 10.0)] == [0.0, 9.99]


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert window.percentile(xs, 90) == 90
    assert window.percentile(xs, 50) == 50
    assert window.percentile([5.0], 90) == 5.0


def test_lateness_reports_the_client_falling_behind():
    late = window.lateness([rec(1.0, [], sent=1.002), rec(2.0, [], sent=2.1)])
    assert late["late_max_ms"] == pytest.approx(100.0)


def test_lateness_counts_the_sends_off_the_schedule():
    recs = [rec(1.0, [], sent=1.002), rec(2.0, [], sent=2.05),
            rec(3.0, [], sent=4.65), rec(4.0, [], sent=4.65)]
    late = window.lateness(recs)
    assert late["late_sends"] == 2
    assert late["late_max_ms"] == pytest.approx(1650.0)
    assert window.lateness([])["late_sends"] == 0


MIXES = {
    "chat": {"dist": "lognormal", "mean": 512, "sigma": 0.8, "low": 32,
             "high": 3072},
    "short": {"dist": "lognormal", "mean": 64, "sigma": 0.6, "low": 8,
              "high": 256},
    "uniform": {"dist": "uniform", "low": 10, "high": 20},
}


@pytest.mark.parametrize("name", sorted(MIXES))
def test_lengths_match_their_mean_and_clamps(name):
    d = MIXES[name]
    x = gen.quantiles(d, 4000)
    assert x.min() >= d["low"] and x.max() <= d["high"]
    want = d.get("mean", (d["low"] + d["high"]) / 2)
    # clamping the lognormal's tails moves the mean by a few percent
    assert statistics.fmean(x) == pytest.approx(want, rel=0.05)


def _mix(trace=1):
    return {"prompt": MIXES["chat"],
            "output": {"dist": "lognormal", "mean": 192, "sigma": 0.8,
                       "low": 8, "high": 768},
            "trace": trace}


@pytest.mark.parametrize("rate", [0.5, 3.0])
def test_schedule_is_deterministic_per_seed(rate):
    load = {"rate": rate, "ramp_s": 5}
    kw = dict(seconds=20, drain=5, vocab=1000)
    a = gen.schedule(_mix(), load, seed=2 ** 40 + 3, **kw)
    b = gen.schedule(_mix(), load, seed=2 ** 40 + 3, **kw)
    c = gen.schedule(_mix(), load, seed=2 ** 40 + 4, **kw)
    assert [(p.due, p.max_new, p.prompt.tolist()) for p in a] == \
        [(p.due, p.max_new, p.prompt.tolist()) for p in b]
    assert [p.prompt.tolist() for p in a] != [p.prompt.tolist() for p in c]


def test_every_seed_offers_the_same_work_in_another_order():
    # the same sizes at the same times for every seed; what the seed
    # changes is the token ids, and so the answers
    load = {"rate": 3.0, "ramp_s": 5}
    kw = dict(seconds=51, drain=5, vocab=1000)
    a = gen.schedule(_mix(), load, seed=1, **kw)
    b = gen.schedule(_mix(), load, seed=2, **kw)
    assert [(p.due, len(p.prompt), p.max_new) for p in a] == \
        [(p.due, len(p.prompt), p.max_new) for p in b]
    assert all(not np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))
    # another trace key is another order of the same sizes
    c = gen.schedule(_mix(trace=2), load, seed=1, **kw)
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in c)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in c]
    # arrivals span the ramp, the window and the drain at the stated rate
    assert a[0].due == -5.0 and a[-1].due < 51 + 5
    assert len(a) == int(np.ceil(3.0 * (5 + 51 + 5)))


@pytest.mark.parametrize("rate", [0.48, 4.0])
def test_the_window_holds_the_same_requests_for_every_seed(rate):
    # the window's arrivals are its own stratified set: the same count
    # and sizes whatever the ramp holds, the first due as it opens
    kw = dict(seconds=51, drain=5, vocab=1000)
    wins = [[p for p in gen.schedule(_mix(), {"rate": rate, "ramp_s": ramp},
                                     seed=s, **kw) if 0 <= p.due < 51]
            for s, ramp in ((7, 60), (2 ** 31 + 11, 20), (2 ** 40 + 5, 0))]
    n = round(rate * 51)
    assert [len(w) for w in wins] == [n, n, n]
    assert [w[0].due for w in wins] == [0.0, 0.0, 0.0]
    sizes = [[(p.due, len(p.prompt), p.max_new) for p in w] for w in wins]
    assert sizes[0] == sizes[1] == sizes[2]
    assert sorted(x[1] for x in sizes[0]) == sorted(
        gen.quantiles(MIXES["chat"], n).tolist())


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 1])
def test_arrivals_cluster_as_poisson_arrivals_do(seed):
    # Poisson arrivals at rate r put 8 arrivals within 4 / r seconds (half
    # their mean span) with probability P(Gamma(8, 1) < 4) = 0.051; an
    # order that spread the short gaps out would almost never do so
    # the seed names the trace here: two fixed orders of the same gaps
    load = {"rate": 10.0, "ramp_s": 0}
    plan = gen.schedule(_mix(trace=seed), load, seed=1, seconds=200,
                        drain=0, vocab=10)
    due = np.array([p.due for p in plan])
    span8 = due[8:] - due[:-8]
    share = float(np.mean(span8 < 4 / 10.0))
    assert 0.02 < share < 0.10
    gaps = np.diff(due)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)
