"""The engine's own spans, stamps and counters (``core/trace.py``).

Each engine step leaves a record: its phases on ``time.perf_counter``,
nested inside the step; the device launches it issued; the backend
compilations that ended in it.  Each request carries its stamps from
receipt to finish, in order.  The fused step names its parts with
``jax.named_scope`` so that a device trace can put op time down to them.
"""

import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.trace import COMPILE_EVENT, STEP_RING, Spans
from repro.models import model as model_lib
from repro.serving.engine import ServingEngine
from repro.serving.sampling import SamplingParams
from repro.serving.step import make_engine_step

PHASES = {"engine.admit", "engine.pick", "engine.draft", "engine.grow",
          "engine.chunks", "engine.launch", "engine.sync", "engine.emit"}


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    params, _ = model_lib.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _run(cfg, params, lengths=(20, 5, 33, 12), max_new=4, **kw):
    kw = {"max_batch": 2, "max_len": 64, "prompt_bucket": 1,
          "cache_layout": "paged", "prefill_chunk": 8, **kw}
    eng = ServingEngine(cfg, params, **kw)
    rng = np.random.default_rng(0)
    for n in lengths:
        eng.submit(rng.integers(1, cfg.vocab_size, n),
                   SamplingParams(max_new_tokens=max_new))
    eng.run()
    return eng


ENGINES = {
    "unified": {},
    "per_chunk": {"unified_step": False},
    "unchunked": {"prefill_chunk": 0, "cache_layout": "contiguous"},
    "speculative": {"speculative": "lookup", "spec_tokens": 2},
}


@pytest.mark.parametrize("path", sorted(ENGINES))
def test_request_stamps_are_in_order(small_model, path):
    eng = _run(*small_model, **ENGINES[path])
    assert len(eng.finished) == 4
    for r in eng.finished:
        stamps = [r.recv_time, r.submit_time, r.admit_time,
                  r.first_chunk_time, r.first_token_time, r.finish_time]
        assert all(t > 0 for t in stamps), (path, stamps)
        assert stamps == sorted(stamps), (path, stamps)


@pytest.mark.parametrize("path", sorted(ENGINES))
def test_step_records_nest_their_phases(small_model, path):
    eng = _run(*small_model, **ENGINES[path])
    recs = list(eng.spans.steps)
    assert [r.index for r in recs] == list(range(len(recs)))
    assert len(recs) == len(eng._dispatch_samples)
    for r, programs in zip(recs, eng._dispatch_samples):
        assert r.t0 <= r.t1
        names = [n for n, _, _ in r.spans]
        assert set(names) <= PHASES and names[0] == "engine.admit"
        last = r.t0
        for _, a, b in r.spans:   # in order, inside the step, disjoint
            assert last <= a <= b <= r.t1
            last = b
        assert {"admitted", "decode_rows"} <= set(r.args)
        assert r.launches >= programs
    for a, b in zip(recs, recs[1:]):
        assert a.t1 <= b.t0


def test_launches_count_the_eager_writes(small_model):
    eng = _run(*small_model)
    recs = list(eng.spans.steps)
    # a step that only decodes launches the fused step, and three eager
    # writes mask the row of each request it finishes
    decode_only = [r for r in recs if r.args["admitted"] == 0
                   and r.args["chunk_rows"] == 0]
    assert decode_only
    for r in decode_only:
        done = sum(r.t0 <= q.finish_time <= r.t1 for q in eng.finished)
        assert r.launches == 1 + 3 * done, (r, done)
    # admission writes the slot's table row and resets it outside the
    # jitted step; a finishing chunk arms the slot with eager writes
    admits = [r for r in recs if r.args["admitted"] > 0]
    assert admits and all(r.launches > 2 for r in admits)
    s = eng.latency_summary()
    assert s["dispatches_per_step_p95"] > max(eng._dispatch_samples)
    assert s["dispatches_per_step_p50"] <= s["dispatches_per_step_p95"]


def test_compiles_are_counted_on_the_step_that_compiled(small_model):
    ends = []
    lock = threading.Lock()

    def on(event, duration, **kw):
        if event == COMPILE_EVENT:
            with lock:
                ends.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        # a configuration no other test compiles, so its first steps must
        eng = _run(*small_model, lengths=(9, 17, 4, 30, 6, 22), max_new=6,
                   max_batch=3, prefill_chunk=6)
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    recs = list(eng.spans.steps)
    for r in recs:
        inside = sum(r.t0 <= t <= r.t1 for t in ends)
        assert r.compiles == inside, (r.index, r.compiles, inside)
    assert recs[0].compiles > 0
    assert any(r.compiles == 0 for r in recs)
    assert eng.latency_summary()["compilations"] >= sum(
        r.compiles for r in recs)


def test_summary_reports_waits(small_model):
    s = _run(*small_model).latency_summary()
    assert "decode_tokens_per_sec" not in s
    for name in ("submit_wait", "queue_wait", "prefill_wait"):
        assert 0.0 <= s[f"{name}_p50_ms"] <= s[f"{name}_p95_ms"], name
    # two slots, four requests: later requests wait for a slot
    assert s["queue_wait_p95_ms"] > 0.0


def test_the_span_ring_is_bounded():
    spans = Spans()
    n = STEP_RING + 2
    for i in range(n):
        with spans.step(i) as rec:
            with spans.span("engine.admit"):
                pass
            rec.args["admitted"] = i
    assert [r.index for r in spans.steps] == list(range(2, n))
    assert [r.args["admitted"] for r in spans.steps] == list(range(2, n))
    assert all(len(r.spans) == 1 for r in spans.steps)
    with spans.span("outside"):   # no step open: only a profiler sees it
        pass
    assert all(len(r.spans) == 1 for r in spans.steps)


def test_the_fused_step_names_its_scopes(small_model):
    cfg, params = small_model
    eng = ServingEngine(cfg, params, max_batch=2, max_len=64,
                        prompt_bucket=1, cache_layout="paged",
                        prefill_chunk=8)
    chunk = {"tokens": np.zeros((2, 8), np.int32),
             "start": np.zeros(2, np.int32), "length": np.zeros(2, np.int32)}
    step = make_engine_step(cfg, 64, k_max=eng.top_k_max)
    text = jax.jit(step).lower(params, eng._state, chunk, eng.cache).as_text(
        debug_info=True)
    for scope in ("jit(step)/chunk_pass/", "jit(step)/decode/",
                  "jit(step)/sample/"):
        assert scope in text, scope
