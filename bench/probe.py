"""What the benchmark sees of the engine, through its public surface only.

``Probe`` replaces three instance attributes of a ``ServingEngine``:
``step`` and ``submit`` with timed wrappers (each inside a
``jax.profiler.TraceAnnotation``, so that a trace shows them as host
spans), and ``stream_hook`` with a wrapper that counts each request's
tokens as they leave the device.  It reads ``engine.queue`` to see which
requests a step admitted.  It patches no private method, so a refactor of
the engine's internals cannot move the yardstick.

``Compiles`` counts JAX's backend compilations (a persistent-cache hit
included) by the time they ended, from ``jax.monitoring`` events.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    # (uid, index of the first token in this emission, tokens emitted)
    emitted: List[Tuple[int, int, int]]


class Probe:
    def __init__(self, engine):
        self.engine = engine
        self.steps: List[Step] = []
        self.prompt_len: Dict[int, int] = {}
        self.submit_s: Dict[int, float] = {}
        self.admit_s: Dict[int, float] = {}
        self.first_s: Dict[int, float] = {}
        self._count: Dict[int, int] = {}
        self._emitted: List[Tuple[int, int, int]] = []
        # traced runs: the profile covers the steps that start from
        # ``_trace[0]`` on, up to the first that ends at ``_trace[1]``
        self._trace: Optional[Tuple[float, float, str]] = None
        self._tracing = False
        self.traced: List[Step] = []
        self._step, self._submit = engine.step, engine.submit
        self._hook: Optional[Callable] = None
        engine.step, engine.submit = self.step, self.submit

    def wrap_hook(self) -> None:
        """Count tokens through ``engine.stream_hook``: call once whatever
        consumes the stream (the HTTP server) has installed its hook."""
        self._hook = self.engine.stream_hook
        self.engine.stream_hook = self.hook

    def reset(self) -> None:
        """Forget the steps and requests seen so far (after warm-up)."""
        self.steps.clear()
        for d in (self.prompt_len, self.submit_s, self.admit_s,
                  self.first_s, self._count):
            d.clear()

    def trace_between(self, start: float, stop: float, log_dir: str) -> None:
        """Profile from the first step that starts at ``start`` or later to
        the first step that ends at ``stop`` or later, into ``log_dir``."""
        self._trace = (start, stop, log_dir)

    def finish_trace(self) -> None:
        """Stop a profile the steps did not stop (the engine fell idle)."""
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False

    def submit(self, prompt, params=None) -> int:
        with jax.profiler.TraceAnnotation("bench.submit"):
            uid = self._submit(prompt, params)
        req = self.engine.queue[-1]
        self.prompt_len[uid] = len(req.prompt)
        self.submit_s[uid] = req.submit_time
        return uid

    def hook(self, uid: int, tokens, finished: bool) -> None:
        n = len(tokens)
        if n:
            k = self._count.get(uid, 0)
            if k == 0:
                self.first_s[uid] = time.perf_counter()
            self._count[uid] = k + n
            self._emitted.append((uid, k, n))
        if self._hook is not None:
            self._hook(uid, tokens, finished)

    def step(self) -> bool:
        if (self._trace is not None and not self._tracing
                and not self.traced
                and time.perf_counter() >= self._trace[0]):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # host spans are the TraceMe's
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._trace[2], profiler_options=opts)
            self._tracing = True
        waiting = [r.uid for r in self.engine.queue]
        self._emitted = []
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            did = self._step()
        t1 = time.perf_counter()
        still = {r.uid for r in self.engine.queue}
        for uid in waiting:
            if uid not in still:
                self.admit_s[uid] = t0
        step = Step(t0, t1, self._emitted)
        self.steps.append(step)
        if self._tracing:
            self.traced.append(step)
            if t1 >= self._trace[1]:
                jax.profiler.stop_trace()
                self._tracing = False
        return did

    def steps_in(self, t0: float, t1: float) -> List[Step]:
        """Steps that started in ``[t0, t1)``."""
        return [s for s in self.steps if t0 <= s.t0 < t1]


class Compiles:
    """End times of backend compilations, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.ends: List[float] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            with self._lock:
                self.ends.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        with self._lock:
            return sum(t0 <= t <= t1 for t in self.ends)
