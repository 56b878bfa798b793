"""Tensor-parallel sharded serving equivalence suite.

The contract under test: sharding heads/FFN over a ``(tp,)`` mesh inside
the fused engine step changes *where* the math runs, never *what tokens
come out*.  On a forced multi-device CPU host
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``; the equivalence
tests skip without it), greedy AND sampled streams at ``tp=2`` and
``tp=4`` must be byte-identical to the single-device engine across
{contiguous, paged} x {chunked, unchunked} x {preemption on/off} x
{speculative on/off}, with the ≤ 2 dispatches/step bound intact.  The
per-device ledgers ride along: per-device KV bytes sum to the aggregate
when heads shard evenly, per-device block accounting partitions each
shard, and per-device joules tile exactly to the run total — including
when one device's power reader drops every read.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.energy import (DeviceMonitorGroup, PowerReader,
                               SyntheticReader)
from repro.launch.mesh import make_tp_mesh
from repro.models import model as model_lib
from repro.serving.engine import ServingEngine
from repro.serving.sampling import SamplingParams
from repro.serving.workload import LengthDist, WorkloadSpec, poisson_trace

pytestmark = pytest.mark.sharded

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs a forced multi-device host: "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4")


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    params, axes = model_lib.init(cfg, jax.random.PRNGKey(0))
    return cfg, params, axes


def _arrivals(cfg, n=6, temperature=0.0, seed=2):
    spec = WorkloadSpec(
        arrival_rate=0.0, num_requests=n,
        prompt_len=LengthDist(kind="lognormal", mean=16.0, low=2, high=48),
        output_len=LengthDist(kind="uniform", low=2, high=9),
        temperature=temperature, top_k=8, seed=seed,
    )
    return poisson_trace(spec, cfg.vocab_size)


def _engine(cfg, params, axes, tp, **kw):
    mesh = make_tp_mesh(tp) if tp > 1 else None
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_bucket", 8)
    return ServingEngine(cfg, params, mesh=mesh,
                         param_axes=axes if mesh is not None else None, **kw)


def _streams(cfg, params, axes, arrivals, tp, **kw):
    eng = _engine(cfg, params, axes, tp, **kw)
    for a in arrivals:
        eng.submit(a.prompt, a.params)
    finished = eng.run()
    return eng, {r.uid: list(r.output_tokens) for r in finished}


# -- the equivalence matrix ---------------------------------------------------

@multidevice
@pytest.mark.parametrize("temperature", [0.0, 0.7])
@pytest.mark.parametrize("layout,chunk,spec", [
    ("contiguous", 0, "off"),
    ("contiguous", 8, "off"),
    ("contiguous", 0, "lookup"),
    ("contiguous", 8, "lookup"),
    ("paged", 0, "off"),
    ("paged", 8, "off"),
    ("paged", 0, "lookup"),
    ("paged", 8, "lookup"),
])
def test_tp_stream_equivalence(small_model, layout, chunk, spec, temperature):
    """tp=2 and tp=4 streams byte-identical to tp=1 for every layout x
    chunking x speculation combination, greedy and sampled."""
    cfg, params, axes = small_model
    arrivals = _arrivals(cfg, temperature=temperature)
    kw = dict(cache_layout=layout, prefill_chunk=chunk, speculative=spec)
    _, base = _streams(cfg, params, axes, arrivals, 1, **kw)
    assert len(base) == len(arrivals)
    for tp in (2, 4):
        _, got = _streams(cfg, params, axes, arrivals, tp, **kw)
        assert got == base, (tp, layout, chunk, spec, temperature)


@multidevice
@pytest.mark.parametrize("layout,chunk", [
    ("contiguous", 0), ("contiguous", 8), ("paged", 8)])
def test_tp_pallas_kernels_per_device(small_model, layout, chunk):
    """The Pallas attention kernels run once per device under the tp mesh
    (``dispatch._per_device``): tp=2 streams with the kernels match tp=1
    with the kernels, for every kernel the engine calls."""
    from repro.kernels import dispatch

    cfg, params, axes = small_model
    arrivals = _arrivals(cfg, n=4)
    kw = dict(cache_layout=layout, prefill_chunk=chunk)
    with dispatch.use_backend("pallas"):
        _, base = _streams(cfg, params, axes, arrivals, 1, **kw)
        _, got = _streams(cfg, params, axes, arrivals, 2, **kw)
    assert len(base) == len(arrivals)
    assert got == base, (layout, chunk)


@multidevice
@pytest.mark.parametrize("spec", ["off", "lookup"])
def test_tp_preemption_equivalence(small_model, spec):
    """An overcommitted pool preempts and recomputes identically under a
    sharded engine: streams match the uncontended single-device run, and
    preemptions actually fire on every tp setting."""
    cfg, params, axes = small_model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(rng.integers(10, 25)))
               for _ in range(8)]

    def run(tp, **kw):
        eng = _engine(cfg, params, axes, tp, max_batch=3, seed=3,
                      cache_layout="paged", prefill_chunk=4, kv_block_size=8,
                      speculative=spec, **kw)
        for p in prompts:
            eng.submit(p, SamplingParams(max_new_tokens=10, temperature=0.8))
        return {r.uid: list(r.output_tokens) for r in eng.run()}, eng

    base, _ = run(1)
    for tp in (2, 4):
        got, eng = run(tp, preemption="recompute", kv_num_blocks=10)
        assert got == base, (tp, spec)
        assert eng.preemptions > 0, "pool never ran dry: test lost its teeth"


@multidevice
def test_tp_prefix_cache_equivalence(small_model):
    """Prefix-cached admissions reuse the same sharded pool blocks: warm
    streams match tp=1, and blocks are actually reused."""
    cfg, params, axes = small_model
    shared = np.arange(1, 17)
    prompts = [np.concatenate([shared, [60 + i, 70 + i]]) for i in range(4)]

    def run(tp):
        eng = _engine(cfg, params, axes, tp, cache_layout="paged",
                      prefill_chunk=4, kv_block_size=4, prefix_cache=True)
        for p in prompts:
            eng.submit(p, SamplingParams(max_new_tokens=4, temperature=0.7))
        return {r.uid: list(r.output_tokens) for r in eng.run()}, eng

    base, _ = run(1)
    for tp in (2, 4):
        got, eng = run(tp)
        assert got == base, tp
        assert eng.latency_summary()["prefix_blocks_reused"] > 0


@multidevice
def test_tp_dispatch_bound(small_model):
    """Sharding does not break the unified-step economics: a chunked
    non-preemptive sharded engine stays at <= 2 dispatches per step."""
    cfg, params, axes = small_model
    arrivals = _arrivals(cfg, n=8, temperature=0.7, seed=9)
    for tp in (2, 4):
        for layout in ("contiguous", "paged"):
            eng, _ = _streams(cfg, params, axes, arrivals, tp,
                              cache_layout=layout, prefill_chunk=4,
                              prefill_budget=12)
            assert eng._dispatch_samples, "no steps recorded"
            assert max(eng._dispatch_samples) <= 2, (
                tp, layout, eng._dispatch_samples)


# -- per-device ledgers -------------------------------------------------------

@multidevice
def test_tp_kv_bytes_by_device_sum_to_aggregate(small_model):
    """Heads divide evenly on the smoke config, so each device holds an
    equal KV shard and the per-device bytes sum exactly to the aggregate;
    per-device block accounting partitions every shard identically."""
    cfg, params, axes = small_model
    arrivals = _arrivals(cfg, n=4)
    for tp in (2, 4):
        eng, _ = _streams(cfg, params, axes, arrivals, tp,
                          cache_layout="paged", prefill_chunk=8)
        per = eng.kv_bytes_by_device(peak=True)
        assert len(per) == tp
        assert sum(per) == eng.kv_bytes_in_use(peak=True)
        assert len(set(per)) == 1, per  # 4 kv heads shard evenly
        for view in eng.pool_accounting_by_device():
            assert (view["free"] + view["in_use"] + view["evictable"]
                    == view["allocatable"])
            assert view["in_use"] == eng._pool.in_use
        s = eng.latency_summary()
        assert s["tp_devices"] == tp
        assert s["kv_bytes_peak_per_device"] == per
        assert s["pool_blocks_in_use_per_device"] == [0] * tp  # drained

    # contiguous: per-device stripes of the worst-case reservation
    eng, _ = _streams(cfg, params, axes, _arrivals(cfg, n=3), 2,
                      cache_layout="contiguous")
    per = eng.kv_bytes_by_device()
    assert sum(per) == eng.kv_bytes_worst_case


class _DeadReader(PowerReader):
    """Every read raises — a device whose power sensor is offline."""

    def read_watts(self):
        raise RuntimeError("sensor offline")


def _run_with_monitor(cfg, params, axes, monitor, expect_warning):
    eng = _engine(cfg, params, axes, 1, monitor=monitor,
                  cache_layout="paged", prefill_chunk=8)
    rng = np.random.default_rng(0)
    for _ in range(3):
        eng.submit(rng.integers(1, cfg.vocab_size, 12),
                   SamplingParams(max_new_tokens=6))
    if expect_warning:
        with pytest.warns(RuntimeWarning, match="dropped"):
            with monitor:
                eng.run()
    else:
        with monitor:
            eng.run()
    return eng


def test_tp_per_device_joules_tile_to_total(small_model):
    """The per-device ledger keys: each device's windowed integral over
    the group window, summing exactly to ``result().joules`` (same
    step-function ledger, grouped per device).  Needs no mesh — the
    monitor group is pure host-side instrumentation."""
    cfg, params, axes = small_model
    group = DeviceMonitorGroup(
        [SyntheticReader(lambda t, w=20.0 + 10.0 * i: w) for i in range(4)],
        interval_s=0.01)
    eng = _run_with_monitor(cfg, params, axes, group, expect_warning=False)
    s = eng.latency_summary()
    total = group.result().joules
    assert len(s["joules_per_device"]) == 4
    assert sum(s["joules_per_device"]) == pytest.approx(
        total, rel=1e-9, abs=1e-12)
    assert all(j > 0.0 for j in s["joules_per_device"])
    # request-windowed tilings per device sum to the aggregate windows
    t0, t1 = group.window
    mid = (t0 + t1) / 2.0
    tiled = (sum(group.joules_between_by_device(t0, mid))
             + sum(group.joules_between_by_device(mid, t1)))
    assert tiled == pytest.approx(total, rel=1e-9, abs=1e-12)


def test_tp_summary_survives_dead_device(small_model):
    """Satellite regression: one device dropping every power read must
    degrade the summary gracefully — 0.0 J for that device, its drops
    counted in ``power_reads_dropped``, no zero-division, and the live
    devices' tiling still exact."""
    cfg, params, axes = small_model
    group = DeviceMonitorGroup(
        [SyntheticReader(lambda t: 25.0), _DeadReader()], interval_s=0.01)
    eng = _run_with_monitor(cfg, params, axes, group, expect_warning=True)
    s = eng.latency_summary()
    assert s["power_reads_dropped"] >= 1
    assert s["power_reads_dropped_per_device"][1] == s["power_reads_dropped"]
    assert s["joules_per_device"][1] == 0.0
    assert s["joules_per_device"][0] > 0.0
    assert s["power_samples_per_sec_per_device"][1] == 0.0
    assert sum(s["joules_per_device"]) == pytest.approx(
        group.result().joules, rel=1e-9, abs=1e-12)
    assert s["joules_total"] >= 0.0


def test_tp_all_devices_dead_summary_does_not_crash(small_model):
    """Even a group whose every reader fails yields a well-formed summary:
    zero joules, all drops counted — mirroring the single-monitor
    power_reads_dropped handling."""
    cfg, params, axes = small_model
    group = DeviceMonitorGroup([_DeadReader(), _DeadReader()],
                               interval_s=0.01)
    eng = _run_with_monitor(cfg, params, axes, group, expect_warning=True)
    s = eng.latency_summary()
    assert s["joules_total"] == 0.0
    assert s["joules_per_token"] == 0.0
    assert s["joules_per_device"] == [0.0, 0.0]
    assert s["power_reads_dropped"] >= 2
