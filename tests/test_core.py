"""ELANA core analyzer tests: units, size (paper Table 2 exact), cache,
latency semantics, energy monitor, estimator, HLO parsing, trace export."""

import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import cache as cache_prof
from repro.core import energy as energy_lib
from repro.core import estimator as est_lib
from repro.core import hlo as hlo_lib
from repro.core import size as size_prof
from repro.core import trace as trace_lib
from repro.core import units
from repro.core.hardware import get_hardware
from repro.core.profiler import Elana


# -- units (paper §2.2: SI default, binary optional) -------------------------

def test_units_si_vs_binary():
    n = 16_060_000_000
    assert abs(units.convert(n, "GB") - 16.06) < 1e-9
    assert units.convert(n, "GiB") == pytest.approx(n / 1024**3)
    assert units.convert(1024**3, "GiB") == 1.0
    assert units.fmt_bytes(1_000_000_000, "GB") == "1.00 GB"


def test_units_auto():
    assert units.auto_unit(500) == "B"
    assert units.auto_unit(5_000_000) == "MB"
    assert units.auto_unit(5 * 1024**3, binary=True) == "GiB"


# -- model size: exact reproduction of paper Table 2 -------------------------

PAPER_TABLE2 = {
    # model: (param_GB, kv(1,1024), kv(128,1024), kv(128,2048))  [SI GB]
    "llama3.1-8b": (16.06, 0.13, 17.18, 34.36),
    "qwen2.5-7b": (15.23, 0.06, 7.52, 15.03),
}


@pytest.mark.parametrize("arch,expected", PAPER_TABLE2.items())
def test_table2_exact(arch, expected):
    e = Elana(arch)
    s = e.size_report()
    assert round(s.param_bytes / 1e9, 2) == expected[0]
    for (b, L), exp in zip([(1, 1024), (128, 1024), (128, 2048)], expected[1:]):
        rep = e.cache_report(b, L)
        assert round(rep.kv_bytes / 1e9, 2) == exp, (arch, b, L)


def test_table2_nemotron_within_tolerance():
    """Hybrid stand-in: params within 2%, KV within 5% of the paper."""
    e = Elana("nemotron-h-8b")
    s = e.size_report()
    assert abs(s.param_bytes / 1e9 - 16.20) / 16.20 < 0.02
    rep = e.cache_report(128, 2048)
    assert abs(rep.kv_bytes / 1e9 - 6.64) / 6.64 < 0.05
    assert rep.state_bytes > 0  # recurrent states are reported separately


def test_moe_active_params():
    s = size_prof.profile_size(get_config("qwen3-moe-30b-a3b"))
    assert 28e9 < s.param_count < 33e9        # "30B"
    assert 2.5e9 < s.active_param_count < 4e9  # "A3B"


def test_cache_analytic_matches_eval_shape():
    for arch in ("llama3.1-8b", "recurrentgemma-2b", "nemotron-h-8b"):
        cfg = get_config(arch)
        rep = cache_prof.profile_cache(cfg, 4, 4096)
        analytic = cache_prof.analytic_kv_bytes(cfg, 4, 4096, itemsize=2)
        assert rep.kv_bytes == analytic, arch


def test_cache_sliding_window_caps():
    cfg = get_config("recurrentgemma-2b")
    small = cache_prof.profile_cache(cfg, 1, 1024)
    big = cache_prof.profile_cache(cfg, 1, 524_288)
    # windowed KV is capped by the 2048 window: cache barely grows with L
    assert big.kv_bytes == cache_prof.analytic_kv_bytes(cfg, 1, 524_288)
    assert big.kv_bytes <= small.kv_bytes * 2 + 1
    assert big.state_bytes == small.state_bytes


# -- energy monitor -----------------------------------------------------------

def test_power_monitor_integrates_constant_power():
    reader = energy_lib.SyntheticReader(lambda t: 100.0, n_devices=2)
    with energy_lib.PowerMonitor(reader, interval_s=0.02) as mon:
        time.sleep(0.25)
    res = mon.result()
    assert res.n_devices == 2
    assert res.avg_watts == pytest.approx(200.0, rel=0.01)  # summed devices
    assert res.joules == pytest.approx(200.0 * res.duration_s, rel=0.01)


def test_power_monitors_label_what_their_joules_are():
    model = energy_lib.ModelReader(idle_watts=60.0, tdp_watts=170.0)
    host = energy_lib.ProcStatReader()
    assert energy_lib.PowerMonitor(model).source == "modelled"
    assert energy_lib.PowerMonitor(host).source == "host-cpu"
    assert energy_lib.DeviceMonitorGroup(
        [model, host]).source == "host-cpu/modelled"


def test_power_monitor_window_average():
    # power ramps 0 -> 100 W linearly over the window: average ~50 W
    reader = energy_lib.SyntheticReader(lambda t: min(t / 0.2, 1.0) * 100.0)
    with energy_lib.PowerMonitor(reader, interval_s=0.01) as mon:
        time.sleep(0.2)
    res = mon.result()
    assert 30.0 < res.avg_watts < 70.0


def test_power_monitor_result_equals_joules_between():
    """Run-level and per-request energy share one ledger: result() is the
    same step-function integral joules_between computes, so tiling the
    window with sub-windows reproduces the total exactly."""
    reader = energy_lib.SyntheticReader(lambda t: 40.0 + 30.0 * (t % 0.05))
    with energy_lib.PowerMonitor(reader, interval_s=0.01) as mon:
        time.sleep(0.2)
    res = mon.result()
    t0, t1 = mon.window
    assert res.joules == mon.joules_between(t0, t1)
    tm = t0 + (t1 - t0) / 3.0
    assert mon.joules_between(t0, tm) + mon.joules_between(tm, t1) == (
        pytest.approx(res.joules, rel=1e-9))
    assert res.samples_per_sec > 0.0


class _FlakyReader(energy_lib.PowerReader):
    """Raises on every other read."""

    def __init__(self):
        self.calls = 0

    def read_watts(self):
        self.calls += 1
        if self.calls % 2 == 0:
            raise RuntimeError("transient sensor failure")
        return [50.0]


def test_power_monitor_counts_and_warns_on_dropped_reads():
    mon = energy_lib.PowerMonitor(_FlakyReader(), interval_s=0.01)
    with pytest.warns(RuntimeWarning, match="dropped"):
        with mon:
            time.sleep(0.15)
    res = mon.result()
    assert res.dropped_reads >= 1
    assert mon.dropped_reads == res.dropped_reads
    # the good half of the reads still integrates to a sane total
    assert res.joules == pytest.approx(50.0 * res.duration_s, rel=0.05)


class _SlowReader(energy_lib.PowerReader):
    """A read that takes longer than the idle budget (like NVML on a busy
    box) — sleep-after-read scheduling would halve the achieved rate."""

    def read_watts(self):
        time.sleep(0.03)
        return [42.0]


def test_power_monitor_absolute_deadline_rate():
    with energy_lib.PowerMonitor(_SlowReader(), interval_s=0.05) as mon:
        time.sleep(0.5)
    res = mon.result()
    # deadline scheduling: read latency eats the idle wait, not the
    # cadence.  The drifting sampler achieved ~1/(0.05+0.03) = 12.5 Hz;
    # the deadline sampler holds ~20 Hz.
    assert res.samples_per_sec >= 0.7 / 0.05
    assert res.dropped_reads == 0


def test_procstat_reader_runs():
    r = energy_lib.ProcStatReader(idle_watts=10, tdp_watts=65)
    w = r.read_watts()
    assert len(w) == 1 and 0 <= w[0] <= 65.0


# -- estimator ---------------------------------------------------------------

def test_estimator_paper_table3_decode_accuracy():
    """TPOT / J-per-token on A6000 must match the paper within 10%."""
    paper = {"llama3.1-8b": (24.84, 6.80), "qwen2.5-7b": (23.15, 6.44)}
    for arch, (tpot_ms, j_tok) in paper.items():
        est = Elana(arch).estimate(hardware="a6000", batch=1,
                                   prompt_len=512, gen_len=512)
        assert abs(est.tpot.latency_s * 1e3 - tpot_ms) / tpot_ms < 0.10, arch
        assert abs(est.tpot.joules - j_tok) / j_tok < 0.10, arch


def test_estimator_ttlt_decomposition():
    est = Elana("llama3.1-8b").estimate(hardware="a6000", batch=1,
                                        prompt_len=512, gen_len=512)
    expected = est.ttft.latency_s + 511 * est.tpot.latency_s
    assert est.ttlt.latency_s == pytest.approx(expected, rel=1e-6)


def test_estimator_monotonic_in_batch():
    e = Elana("qwen2.5-7b")
    lat1 = e.estimate(hardware="tpu-v5e", batch=1).ttft.latency_s
    lat8 = e.estimate(hardware="tpu-v5e", batch=8).ttft.latency_s
    assert lat8 > lat1


def test_estimator_naive_pp_power_model():
    """Multi-GPU naive pipeline: only one GPU busy -> watts ~ 1 busy + idle."""
    est = est_lib.estimate_workload(
        get_config("llama3.1-8b"), hardware="a6000", n_devices=4,
        mode="naive_pp", batch=1, prompt_len=512, gen_len=64)
    hw = get_hardware("a6000")
    assert est.tpot.avg_watts < 1.5 * hw.tdp_watts  # not 4 busy GPUs


def test_hardware_for_device_keys_on_device_kind():
    from types import SimpleNamespace

    from repro.core.hardware import TPU_V5E, hardware_for_device

    assert hardware_for_device(
        SimpleNamespace(device_kind="TPU v5 lite")) is TPU_V5E
    assert hardware_for_device().name == "cpu"  # the test host's device
    with pytest.raises(KeyError, match="no hardware spec"):
        hardware_for_device(SimpleNamespace(device_kind="TPU v99"))


# -- HLO parsing ---------------------------------------------------------------

HLO_SAMPLE = """
ENTRY %main {
  %ar = bf16[1024,512]{1,0} all-reduce(bf16[1024,512]{1,0} %x), replica_groups={}
  %ag = f32[2048]{0} all-gather(f32[128]{0} %y), dimensions={0}
  %rs.1 = bf16[64,64]{1,0} reduce-scatter(bf16[1024,64]{1,0} %z), dimensions={0}
  %cp = u32[16]{0} collective-permute(u32[16]{0} %w), source_target_pairs={{0,1}}
  %aa = (f32[32]{0}, f32[32]{0}) all-to-all(f32[32]{0} %a, f32[32]{0} %b)
  %done = f32[8]{0} all-reduce-done(f32[8]{0} %start)
}
"""


def test_collective_parsing():
    stats = hlo_lib.collective_stats(HLO_SAMPLE)
    assert stats.counts["all-reduce"] == 1
    assert stats.bytes_by_kind["all-reduce"] == 1024 * 512 * 2
    assert stats.bytes_by_kind["all-gather"] == 2048 * 4
    assert stats.bytes_by_kind["reduce-scatter"] == 64 * 64 * 2
    assert stats.bytes_by_kind["all-to-all"] == 2 * 32 * 4
    assert stats.counts["collective-permute"] == 1


def test_cost_summary_from_compiled():
    f = jax.jit(lambda x: (x @ x).sum())
    compiled = f.lower(jnp.ones((128, 128))).compile()
    s = hlo_lib.summarize_compiled(compiled)
    assert s.flops >= 2 * 128**3 * 0.9
    assert s.collectives.total_count == 0


# -- trace export ---------------------------------------------------------------

def test_trace_chrome_export(tmp_path):
    e = Elana("tinyllama-1.1b")
    path = str(tmp_path / "trace.json")
    summary = e.trace(path, hardware="tpu-v5e", phase="decode", seq_len=512)
    assert os.path.exists(path)
    data = json.load(open(path))
    assert len(data["traceEvents"]) > 22  # >= one event per layer
    assert summary["total_s"] > 0
    assert 0.9 < summary["memory_bound_frac"] <= 1.0  # bs=1 decode is mem-bound


def test_trace_prefill_compute_bound():
    ev = trace_lib.estimated_timeline(
        get_config("llama3.1-8b"), hardware="a6000", phase="prefill",
        batch=4, seq_len=2048)
    s = trace_lib.timeline_summary(ev)
    assert s["memory_bound_frac"] < 0.35  # large prefill is compute-bound
