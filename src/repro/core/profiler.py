"""The ELANA public API: one object per model, all paper metrics behind it.

    from repro.core.profiler import Elana
    e = Elana("llama3.1-8b")                      # any registered arch
    e.size_report()                               # §2.2 model size
    e.cache_report(batch=128, seq_len=2048)       # §2.2 KV/SSM cache
    e.estimate(hardware="a6000", batch=1, ...)    # §2.3/2.4 estimator mode
    e.measure(batch=1, prompt_len=64, gen_len=16) # §2.3/2.4 measured mode
    e.trace(path="trace.json")                    # §2.5 Perfetto timeline

Custom architectures plug in exactly like the paper's
``_build_model_and_tokenizer`` hook: pass a ``ModelConfig`` (or a
``builder`` returning ``(cfg, params)``) instead of an arch name.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import cache as cache_prof
from repro.core import energy as energy_lib
from repro.core import estimator as est_lib
from repro.core import latency as lat_lib
from repro.core import size as size_prof
from repro.core import trace as trace_lib
from repro.core.hardware import get_hardware
from repro.models import model as model_lib
from repro.models.config import ModelConfig


class Elana:
    def __init__(
        self,
        arch: Optional[str] = None,
        *,
        config: Optional[ModelConfig] = None,
        builder: Optional[Callable[[], Tuple[ModelConfig, Dict]]] = None,
        smoke: bool = False,
        seed: int = 0,
    ):
        if builder is not None:
            self.cfg, self._params = builder()
        else:
            if config is not None:
                self.cfg = config
            else:
                from repro.configs import get_config

                assert arch is not None, "need arch, config= or builder="
                self.cfg = get_config(arch, smoke=smoke)
            self._params = None
        self._seed = seed
        self._lat: Optional[lat_lib.LatencyProfiler] = None

    # -- lazy param materialization (measured mode only) ----------------------
    @property
    def params(self):
        if self._params is None:
            self._params, _ = model_lib.init(self.cfg, jax.random.PRNGKey(self._seed))
        return self._params

    def _latency_profiler(self) -> lat_lib.LatencyProfiler:
        if self._lat is None:
            self._lat = lat_lib.LatencyProfiler(self.cfg, self.params, seed=self._seed)
        return self._lat

    # -- §2.2 sizes ------------------------------------------------------------
    def size_report(self) -> size_prof.SizeReport:
        return size_prof.profile_size(self.cfg, self._params)

    def cache_report(self, batch: int, seq_len: int) -> cache_prof.CacheReport:
        return cache_prof.profile_cache(self.cfg, batch, seq_len)

    # -- §2.3 measured latency ---------------------------------------------------
    def measure(
        self,
        batch: int = 1,
        prompt_len: int = 64,
        gen_len: int = 16,
        iters: int = 5,
        power_reader: Optional[energy_lib.PowerReader] = None,
    ) -> Dict[str, float]:
        """Measured TTFT/TPOT/TTLT (+ energy when a PowerReader is given)."""
        lp = self._latency_profiler()
        out: Dict[str, float] = {}
        if power_reader is None:
            ttft = lp.ttft(batch, prompt_len, iters=iters)
            tpot = lp.tpot(batch, prompt_len, gen_len=max(gen_len, 4))
            ttlt = lp.ttlt(batch, prompt_len, gen_len, iters=max(2, iters // 2))
            out.update(ttft_ms=ttft.mean_ms, tpot_ms=tpot.mean_ms,
                       ttlt_ms=ttlt.mean_ms,
                       ttft_p95_ms=ttft.p95_s * 1e3, tpot_p95_ms=tpot.p95_s * 1e3)
        else:
            mon = energy_lib.PowerMonitor(power_reader)
            out["energy_source"] = mon.source
            with mon:
                ttft = lp.ttft(batch, prompt_len, iters=iters)
            e = mon.result()
            out.update(ttft_ms=ttft.mean_ms,
                       j_per_prompt=e.joules / (iters * batch))
            with mon:
                tpot = lp.tpot(batch, prompt_len, gen_len=max(gen_len, 4))
            e = mon.result()
            out.update(tpot_ms=tpot.mean_ms,
                       j_per_token=e.joules / (max(gen_len, 4)))
            with mon:
                ttlt = lp.ttlt(batch, prompt_len, gen_len, iters=2)
            e = mon.result()
            out.update(ttlt_ms=ttlt.mean_ms, j_per_request=e.joules / 2)
        return out

    # -- §2.3/2.4 estimator mode --------------------------------------------------
    def estimate(
        self,
        hardware: str = "tpu-v5e",
        n_devices: int = 1,
        mode: str = "tp",
        batch: int = 1,
        prompt_len: int = 512,
        gen_len: int = 512,
    ) -> est_lib.WorkloadEstimate:
        return est_lib.estimate_workload(
            self.cfg, hardware=hardware, n_devices=n_devices, mode=mode,
            batch=batch, prompt_len=prompt_len, gen_len=gen_len,
        )

    # -- §2.5 kernel-level trace ---------------------------------------------------
    def trace(
        self,
        path: str,
        hardware: str = "tpu-v5e",
        phase: str = "decode",
        batch: int = 1,
        seq_len: int = 1024,
    ) -> Dict[str, float]:
        events = trace_lib.estimated_timeline(
            self.cfg, hardware=hardware, phase=phase, batch=batch, seq_len=seq_len,
        )
        trace_lib.to_chrome_trace(events, path, meta={
            "arch": self.cfg.name, "hardware": hardware, "phase": phase,
            "batch": batch, "seq_len": seq_len,
        })
        return trace_lib.timeline_summary(events)
