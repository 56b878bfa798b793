"""Timelines: the serving engine's own spans, and an estimated op timeline.

* ``Spans`` — the host spans of a running engine.  Each ``span`` opens a
  ``jax.profiler.TraceAnnotation`` (so a profiler trace shows it on the
  host plane, on the device trace's clock) and notes its
  ``time.perf_counter`` start and end in the open step's ``StepRecord``.
  Always on: with no profiler running a span costs a TraceMe check and
  two clock reads.  The last ``STEP_RING`` steps are kept in memory.
* ``estimated_timeline`` — op-granular roofline timeline derived from the
  model structure + hardware spec, exported as chrome-trace JSON
  (``ui.perfetto.dev`` opens it directly).  This works on the CPU dev
  container and is also the visual companion of the §Roofline numbers:
  each op event carries its FLOPs, bytes and bound-ness in ``args``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import threading
import time
from collections import deque
from typing import Deque, Dict, Iterator, List, Optional, Tuple

import jax

from repro.core.hardware import HardwareSpec, get_hardware
from repro.models.config import ModelConfig

STEP_RING = 4096  # engine steps whose records are kept
STEP_SPAN = "engine.step"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Compilations:
    """Backend compilations that ended in this process (a persistent-cache
    hit included), counted by one ``jax.monitoring`` listener.  JAX's
    listeners are process-wide, so the count is too: a recorder reads it
    before and after each step."""

    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
        self._listening = False

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self._n += 1

    def count(self) -> int:
        with self._lock:
            if not self._listening:
                jax.monitoring.register_event_duration_secs_listener(self._on)
                self._listening = True
            return self._n


_COMPILATIONS = _Compilations()


@dataclasses.dataclass
class StepRecord:
    """One engine step as the program saw it, on ``time.perf_counter``.

    ``spans`` are the step's phases, ``(name, start, end)`` in the order
    they closed; ``args`` are the step span's arguments (what the step
    carried); ``launches`` the device launches the engine issued in it;
    ``compiles`` the backend compilations that ended during it."""
    index: int
    t0: float
    t1: float = 0.0
    spans: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)
    args: Dict[str, int] = dataclasses.field(default_factory=dict)
    launches: int = 0
    compiles: int = 0


class Spans:
    """Per-step host spans of one serving engine, kept in a ring."""

    def __init__(self):
        self.steps: Deque[StepRecord] = deque(maxlen=STEP_RING)
        self._open: Optional[StepRecord] = None
        self._compiles0 = _COMPILATIONS.count()

    @property
    def compiles(self) -> int:
        """Backend compilations since this recorder was made."""
        return _COMPILATIONS.count() - self._compiles0

    @contextlib.contextmanager
    def step(self, index: int) -> Iterator[StepRecord]:
        """The ``engine.step`` span; the caller fills the record's ``args``
        and ``launches``, which the span carries as its arguments."""
        rec = StepRecord(index, time.perf_counter())
        c0 = _COMPILATIONS.count()
        self._open = rec
        try:
            with jax.profiler.TraceAnnotation(STEP_SPAN, step=index) as ann:
                yield rec
                ann.set_metadata(**rec.args)
        finally:
            self._open = None
            rec.t1 = time.perf_counter()
            rec.compiles = _COMPILATIONS.count() - c0
            self.steps.append(rec)

    @contextlib.contextmanager
    def span(self, name: str, **args) -> Iterator[None]:
        """One phase of the open step (or a span outside any step, which
        only the profiler sees)."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **args):
                yield
        finally:
            if self._open is not None:
                self._open.spans.append((name, t0, time.perf_counter()))


@dataclasses.dataclass
class OpEvent:
    name: str
    dur_s: float
    flops: float
    bytes_moved: float
    bound: str
    category: str


def _op_time(hw: HardwareSpec, flops: float, bytes_moved: float):
    ct = flops / (hw.peak_flops_bf16 * hw.eta_compute)
    mt = bytes_moved / (hw.hbm_bw * hw.eta_memory)
    return max(ct, mt), ("compute" if ct >= mt else "memory")


def _block_ops(cfg: ModelConfig, kind: str, tokens: int, kv_len: int,
               decode: bool, itemsize: int = 2) -> List[Dict]:
    """Analytic (flops, bytes) per op inside one block."""
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    ops: List[Dict] = []

    def op(name, flops, bytes_moved, cat):
        ops.append(dict(name=name, flops=flops, bytes=bytes_moved, cat=cat))

    norm_bytes = 2 * tokens * d * itemsize
    if kind in ("attn", "local_attn"):
        wq = d * h * hd
        wkv = 2 * d * kv * hd
        wo = h * hd * d
        op("rmsnorm", 6.0 * tokens * d, norm_bytes, "norm")
        op("qkv_proj", 2.0 * tokens * (wq + wkv),
           (wq + wkv) * itemsize + tokens * d * itemsize, "gemm")
        ctx = min(cfg.sliding_window, kv_len) if kind == "local_attn" and \
            cfg.sliding_window else kv_len
        a_flops = 4.0 * tokens * h * hd * (ctx if decode else ctx / 2)
        # flash-tiled KV traffic: the KV stream is re-read once per q block
        batch = max(tokens // max(kv_len, 1), 1) if not decode else tokens
        q_passes = 1 if decode else max((tokens // batch) // 1024, 1)
        a_bytes = (2 * batch * ctx * kv * hd * itemsize * q_passes
                   + 2 * tokens * h * hd * itemsize)  # + Q read / O write
        op("attention", a_flops, a_bytes, "attn")
        op("out_proj", 2.0 * tokens * wo, wo * itemsize + tokens * d * itemsize, "gemm")
        if cfg.is_moe:
            k = cfg.num_experts_per_tok
            wff = 3 * d * cfg.d_ff
            op("rmsnorm", 6.0 * tokens * d, norm_bytes, "norm")
            op("moe_route", 2.0 * tokens * d * cfg.num_experts,
               tokens * cfg.num_experts * 4, "gemm")
            active_w = wff * min(cfg.num_experts, k * max(tokens, 1)) \
                if decode else wff * cfg.num_experts
            op("moe_experts", 2.0 * tokens * k * wff, active_w * itemsize, "gemm")
            if cfg.num_shared_experts:
                wsh = 3 * d * cfg.d_ff * cfg.num_shared_experts
                op("moe_shared", 2.0 * tokens * wsh, wsh * itemsize, "gemm")
        else:
            wff = (3 if cfg.mlp_gated else 2) * d * cfg.d_ff
            op("rmsnorm", 6.0 * tokens * d, norm_bytes, "norm")
            op("mlp", 2.0 * tokens * wff, wff * itemsize + tokens * d * itemsize, "gemm")
    elif kind == "ffn":
        wff = (3 if cfg.mlp_gated else 2) * d * cfg.d_ff
        op("rmsnorm", 6.0 * tokens * d, norm_bytes, "norm")
        op("mlp", 2.0 * tokens * wff, wff * itemsize + tokens * d * itemsize, "gemm")
    elif kind == "rglru":
        W = cfg.resolved_lru_width
        op("rmsnorm", 6.0 * tokens * d, norm_bytes, "norm")
        op("rglru_proj", 2.0 * tokens * 2 * d * W, 2 * d * W * itemsize, "gemm")
        op("rglru_scan", 10.0 * tokens * W, 3 * tokens * W * 4, "scan")
        op("rglru_out", 2.0 * tokens * W * d, W * d * itemsize, "gemm")
        wff = (3 if cfg.mlp_gated else 2) * d * cfg.d_ff
        op("mlp", 2.0 * tokens * wff, wff * itemsize, "gemm")
    elif kind in ("mlstm", "slstm"):
        W = int(d * cfg.mlstm_proj_factor) if kind == "mlstm" else d
        H = cfg.resolved_rec_heads
        Dh = W // H
        op("rmsnorm", 6.0 * tokens * d, norm_bytes, "norm")
        op(f"{kind}_proj", 2.0 * tokens * (2 * d * W + 3 * W * Dh),
           (2 * d * W + 3 * H * Dh * Dh) * itemsize, "gemm")
        state = H * Dh * Dh * 4
        op(f"{kind}_cell", 8.0 * tokens * H * Dh * Dh / max(1, 1),
           (tokens * W * 4 + 2 * state * (tokens if decode else tokens / 64)), "scan")
        op(f"{kind}_out", 2.0 * tokens * W * d, W * d * itemsize, "gemm")
    return ops


def estimated_timeline(
    cfg: ModelConfig,
    *,
    hardware: str = "tpu-v5e",
    phase: str = "decode",
    batch: int = 1,
    seq_len: int = 1024,
) -> List[OpEvent]:
    hw = get_hardware(hardware)
    decode = phase == "decode"
    tokens = batch * (1 if decode else seq_len)
    events: List[OpEvent] = []
    emb_bytes = cfg.vocab_size * cfg.d_model * 2
    emb_dur, _ = _op_time(hw, 0, tokens * cfg.d_model * 2)
    events.append(OpEvent("embed", emb_dur, 0, tokens * cfg.d_model * 2,
                          "memory", "gather"))
    for li, kind in enumerate(cfg.blocks()):
        for o in _block_ops(cfg, kind, tokens, seq_len, decode):
            dur, bound = _op_time(hw, o["flops"], o["bytes"])
            events.append(OpEvent(
                f"L{li:02d}/{o['name']}", dur, o["flops"], o["bytes"], bound,
                o["cat"],
            ))
    lm_flops = 2.0 * tokens * cfg.d_model * cfg.vocab_size
    dur, bound = _op_time(hw, lm_flops, emb_bytes)
    events.append(OpEvent("lm_head", dur, lm_flops, emb_bytes, bound, "gemm"))
    return events


def to_chrome_trace(events: List[OpEvent], path: str,
                    meta: Optional[Dict] = None) -> str:
    """Write a Perfetto-loadable chrome-trace JSON; returns the path."""
    trace = {"traceEvents": [], "displayTimeUnit": "ns",
             "metadata": meta or {}}
    ts = 0.0
    for ev in events:
        trace["traceEvents"].append({
            "name": ev.name, "ph": "X", "ts": ts * 1e6, "dur": ev.dur_s * 1e6,
            "pid": 0, "tid": 0, "cat": ev.category,
            "args": {"flops": ev.flops, "bytes": ev.bytes_moved,
                     "bound": ev.bound},
        })
        ts += ev.dur_s
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


def timeline_summary(events: List[OpEvent]) -> Dict[str, float]:
    total = sum(e.dur_s for e in events)
    by_cat: Dict[str, float] = {}
    for e in events:
        by_cat[e.category] = by_cat.get(e.category, 0.0) + e.dur_s
    out = {"total_s": total}
    out.update({f"{k}_s": v for k, v in sorted(by_cat.items())})
    out["memory_bound_frac"] = sum(
        e.dur_s for e in events if e.bound == "memory") / max(total, 1e-12)
    return out

