"""Energy profiling (paper §2.4), JAX/TPU adaptation.

The paper's method: a separate process samples instantaneous power at 10 Hz
(pynvml on server GPUs, jtop on Jetson), the average power over the latency
window is multiplied by the measured latency, and multi-GPU powers are
summed.  We reproduce the method exactly with a pluggable ``PowerReader``:

* ``NvmlReader``      — NVIDIA GPUs via pynvml (when available).
* ``JtopReader``      — Jetson on-board sensors via jetson-stats (when available).
* ``ProcStatReader``  — CPU dev rig: /proc/stat utilization × TDP model.
* ``ModelReader``     — utilization-scaled TDP model for hardware without a
  userspace power API (TPUs) or for estimator-mode accounting.
* ``SyntheticReader`` — deterministic waveform for tests.

``PowerMonitor`` runs the sampler in a background thread (the in-process
analogue of the paper's sampler process — JAX dispatch releases the GIL, so
a thread gives the same 10 Hz cadence without pickling device handles).
"""

from __future__ import annotations

import bisect
import dataclasses
import threading
import time
import warnings
from typing import Callable, List, Optional, Sequence, Tuple


class PowerReader:
    """Interface: instantaneous power in watts, one value per device.

    ``source`` labels what the joules integrated from it are: ``measured``
    from a power sensor, ``modelled`` from a utilization x TDP model,
    ``host-cpu`` from the host's CPU load, ``synthetic`` from a test
    waveform.  Reports carry it next to every joule figure."""

    source = "unknown"

    def read_watts(self) -> Sequence[float]:  # pragma: no cover - interface
        raise NotImplementedError

    def close(self) -> None:
        pass


class SyntheticReader(PowerReader):
    source = "synthetic"

    def __init__(self, fn: Callable[[float], float], n_devices: int = 1):
        self._fn = fn
        self._n = n_devices
        self._t0 = time.perf_counter()

    def read_watts(self) -> Sequence[float]:
        w = self._fn(time.perf_counter() - self._t0)
        return [w] * self._n


class ModelReader(PowerReader):
    """Utilization-scaled TDP model (TPU has no userspace power API)."""

    source = "modelled"

    def __init__(self, idle_watts: float, tdp_watts: float,
                 utilization_fn: Optional[Callable[[], float]] = None,
                 n_devices: int = 1):
        self.idle = idle_watts
        self.tdp = tdp_watts
        self.util_fn = utilization_fn or (lambda: 1.0)
        self._n = n_devices

    def read_watts(self) -> Sequence[float]:
        u = min(max(self.util_fn(), 0.0), 1.0)
        return [self.idle + (self.tdp - self.idle) * u] * self._n


class ProcStatReader(PowerReader):
    """CPU package power proxy from /proc/stat busy fraction × TDP."""

    source = "host-cpu"

    def __init__(self, idle_watts: float = 10.0, tdp_watts: float = 65.0):
        self.idle = idle_watts
        self.tdp = tdp_watts
        self._last = self._read_stat()

    @staticmethod
    def _read_stat() -> Tuple[float, float]:
        with open("/proc/stat") as f:
            parts = f.readline().split()[1:]
        vals = [float(x) for x in parts[:8]]
        idle = vals[3] + vals[4]
        total = sum(vals)
        return idle, total

    def read_watts(self) -> Sequence[float]:
        idle, total = self._read_stat()
        last_idle, last_total = self._last
        self._last = (idle, total)
        d_total = total - last_total
        busy = 1.0 - (idle - last_idle) / d_total if d_total > 0 else 0.0
        return [self.idle + (self.tdp - self.idle) * busy]


class NvmlReader(PowerReader):  # pragma: no cover - needs NVIDIA hardware
    source = "measured"

    def __init__(self, device_indices: Optional[Sequence[int]] = None):
        import pynvml

        self._nvml = pynvml
        pynvml.nvmlInit()
        n = pynvml.nvmlDeviceGetCount()
        idx = list(device_indices) if device_indices else list(range(n))
        self._handles = [pynvml.nvmlDeviceGetHandleByIndex(i) for i in idx]

    def read_watts(self) -> Sequence[float]:
        return [self._nvml.nvmlDeviceGetPowerUsage(h) / 1000.0
                for h in self._handles]

    def close(self) -> None:
        self._nvml.nvmlShutdown()


class JtopReader(PowerReader):  # pragma: no cover - needs Jetson hardware
    source = "measured"

    def __init__(self):
        from jtop import jtop

        self._jtop = jtop()
        self._jtop.start()

    def read_watts(self) -> Sequence[float]:
        power = self._jtop.power
        return [power["rail"]["GPU"]["power"] / 1000.0]

    def close(self) -> None:
        self._jtop.close()


@dataclasses.dataclass
class EnergyResult:
    duration_s: float
    avg_watts: float            # summed across devices (paper: multi-GPU sum)
    joules: float
    samples: List[Tuple[float, List[float]]]  # (t, per-device watts)
    n_devices: int
    # achieved sampler rate over the window — the >= 5-10 Hz protocol
    # requirement is verifiable from the result, not assumed
    samples_per_sec: float = 0.0
    # reads that raised or returned empty (each leaves a gap the step
    # function backfills with the previous sample's power)
    dropped_reads: int = 0

    def per(self, count: int) -> float:
        """J/Token, J/Prompt, J/Request — divide by the unit count."""
        return self.joules / max(count, 1)


def integrate_joules(
    samples: Sequence[Tuple[float, Sequence[float]]], t0: float, t1: float
) -> float:
    """Energy over [t0, t1] treating the samples as a step function.

    Power at time t is the (device-summed) watts of the latest sample at or
    before t (the first sample extends backwards).  Because the step
    function is fixed, the integral is *additive* over adjacent windows:
    tiling [t0, t1] with sub-windows and summing reproduces the total
    exactly — the property per-request energy attribution relies on.
    """
    if t1 <= t0 or not samples:
        return 0.0
    ts = [t for t, _ in samples]
    ws = [sum(w) for _, w in samples]
    total = 0.0
    cur = t0
    # index of the sample governing time `cur`
    i = max(bisect.bisect_right(ts, cur) - 1, 0)
    while cur < t1:
        nxt = ts[i + 1] if i + 1 < len(ts) else t1
        seg_end = min(max(nxt, cur), t1)
        total += ws[i] * (seg_end - cur)
        cur = seg_end
        if i + 1 < len(ts) and ts[i + 1] <= cur:
            i += 1
    return total


class PowerMonitor:
    """10 Hz sampler thread; use as a context manager around a workload."""

    def __init__(self, reader: PowerReader, interval_s: float = 0.1):
        self.reader = reader
        self.interval_s = interval_s
        self._samples: List[Tuple[float, List[float]]] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._t0 = 0.0
        self._t1 = 0.0
        self.dropped_reads = 0

    @property
    def source(self) -> str:
        """What the joules are (see ``PowerReader.source``)."""
        return self.reader.source

    def _loop(self):
        # absolute-deadline scheduling: waiting ``interval_s`` *after* each
        # read lets slow reads (NVML can take ~ms) drift the achieved rate
        # below target; instead each wait targets t0 + k*interval, so read
        # latency eats into the idle wait, not the cadence
        deadline = self._t0 + self.interval_s
        while not self._stop.is_set():
            t = time.perf_counter()
            try:
                watts = list(self.reader.read_watts())
            except Exception:
                watts = []
            if watts:
                self._samples.append((t, watts))
            else:
                # a dropped read leaves a gap the step-function integral
                # backfills with stale power — count it, don't hide it
                self.dropped_reads += 1
            now = time.perf_counter()
            while deadline <= now:  # reads slower than the interval: skip
                deadline += self.interval_s
            self._stop.wait(deadline - now)

    def __enter__(self) -> "PowerMonitor":
        self._samples.clear()
        self.dropped_reads = 0
        self._stop.clear()
        self._t0 = time.perf_counter()
        self._t1 = 0.0
        # one synchronous sample so even sub-interval windows are covered
        try:
            self._samples.append((self._t0, list(self.reader.read_watts())))
        except Exception:
            self.dropped_reads += 1
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._t1 = time.perf_counter()
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        if self.dropped_reads:
            warnings.warn(
                f"PowerMonitor dropped {self.dropped_reads} power reads "
                f"(reader raised or returned empty); the step-function "
                f"integral backfills those gaps with the previous sample",
                RuntimeWarning, stacklevel=2)

    @property
    def window(self) -> Tuple[float, float]:
        """(enter, exit) perf_counter stamps (exit == now while running)."""
        t1 = self._t1 if self._t1 > self._t0 else time.perf_counter()
        return self._t0, t1

    def joules_between(self, t0: float, t1: float) -> float:
        """Step-function energy over [t0, t1] (additive across windows)."""
        return integrate_joules(self._samples, t0, t1)

    def result(self) -> EnergyResult:
        t0, t1 = self.window
        duration = max(t1 - t0, 1e-9)
        window = [(t, w) for t, w in self._samples if t0 <= t <= t1 + 1e-3]
        if not window:
            window = self._samples[-1:] or [(t0, [0.0])]
        n_dev = max(len(w) for _, w in window)
        # one ledger: the run total is the same step-function integral
        # per-request attribution uses (``joules_between``), so tiling the
        # window with per-request sub-windows reproduces it exactly.  An
        # unweighted sample mean times the duration disagrees under
        # sampling jitter — the sub-windows then don't sum to the total.
        joules = integrate_joules(self._samples, t0, t1)
        return EnergyResult(
            duration_s=duration,
            avg_watts=joules / duration,
            joules=joules,
            samples=window,
            n_devices=n_dev,
            samples_per_sec=len(self._samples) / duration,
            dropped_reads=self.dropped_reads,
        )


class DeviceMonitorGroup:
    """One ``PowerMonitor`` per device under a single measurement window.

    The paper sums multi-GPU powers; this keeps the per-device ledgers
    intact instead of summing at the reader.  The group quacks like a
    ``PowerMonitor`` where the serving engine needs it (``window`` /
    ``joules_between`` / ``result`` / ``dropped_reads``) and adds the
    per-device split: ``joules_between_by_device`` for request-windowed
    tilings and ``result_by_device`` for run totals.  Every integral — per
    device, per window, aggregate — is the same step function, so

        sum_d integrate_d(t0, t1)  ==  group.joules_between(t0, t1)
        sum_d result_by_device()[d].joules  ==  result().joules

    and tiling the run window with request sub-windows reproduces the
    aggregate, exactly as in the single-monitor ledger.

    A device whose reader drops every read degrades gracefully: it
    contributes 0 J (no samples means no steps to integrate), its drops are
    counted in the aggregate ``dropped_reads``, and the other devices'
    ledgers are untouched.
    """

    def __init__(self, readers: Sequence[PowerReader], interval_s: float = 0.1):
        assert readers, "DeviceMonitorGroup needs at least one reader"
        self.monitors = [PowerMonitor(r, interval_s) for r in readers]
        self.interval_s = interval_s
        self._t0 = 0.0
        self._t1 = 0.0

    @property
    def n_devices(self) -> int:
        return len(self.monitors)

    @property
    def dropped_reads(self) -> int:
        return sum(m.dropped_reads for m in self.monitors)

    @property
    def source(self) -> str:
        return "/".join(sorted({m.source for m in self.monitors}))

    def __enter__(self) -> "DeviceMonitorGroup":
        # one clock for the group window; the per-device monitors stamp
        # their own t0 microseconds later, and their first synchronous
        # sample extends backwards over the gap (step-function semantics)
        self._t0 = time.perf_counter()
        self._t1 = 0.0
        for m in self.monitors:
            m.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._t1 = time.perf_counter()
        for m in self.monitors:
            m.__exit__(*exc)

    @property
    def window(self) -> Tuple[float, float]:
        """(enter, exit) perf_counter stamps (exit == now while running)."""
        t1 = self._t1 if self._t1 > self._t0 else time.perf_counter()
        return self._t0, t1

    def joules_between(self, t0: float, t1: float) -> float:
        """Aggregate step-function energy over [t0, t1] (additive)."""
        return sum(self.joules_between_by_device(t0, t1))

    def joules_between_by_device(self, t0: float, t1: float) -> List[float]:
        return [m.joules_between(t0, t1) for m in self.monitors]

    def result_by_device(self) -> List[EnergyResult]:
        """Per-device results over the *group* window, so their joules sum
        exactly to ``result().joules``."""
        t0, t1 = self.window
        duration = max(t1 - t0, 1e-9)
        out = []
        for m in self.monitors:
            window = [(t, w) for t, w in m._samples if t0 <= t <= t1 + 1e-3]
            if not window:
                window = m._samples[-1:] or [(t0, [0.0])]
            joules = integrate_joules(m._samples, t0, t1)
            out.append(EnergyResult(
                duration_s=duration,
                avg_watts=joules / duration,
                joules=joules,
                samples=window,
                n_devices=max(len(w) for _, w in window),
                samples_per_sec=len(m._samples) / duration,
                dropped_reads=m.dropped_reads,
            ))
        return out

    def result(self) -> EnergyResult:
        per = self.result_by_device()
        duration = per[0].duration_s
        joules = sum(r.joules for r in per)
        # interleaved per-device samples, sorted by time — for inspection
        # only; the integrable ledgers live in the per-device monitors
        samples = sorted((s for m in self.monitors for s in m._samples),
                         key=lambda tw: tw[0])
        return EnergyResult(
            duration_s=duration,
            avg_watts=joules / duration,
            joules=joules,
            samples=samples,
            n_devices=len(self.monitors),
            # mean per-device achieved rate: one dead device lowers the
            # aggregate instead of zeroing it (its own rate is visible in
            # result_by_device)
            samples_per_sec=len(samples) / duration / len(self.monitors),
            dropped_reads=self.dropped_reads,
        )


def measure_energy(
    fn: Callable[[], object], reader: PowerReader, interval_s: float = 0.1
) -> EnergyResult:
    """Run ``fn`` under the sampler; energy = window-average power × latency."""
    import jax

    with PowerMonitor(reader, interval_s) as mon:
        jax.block_until_ready(fn())
    return mon.result()
