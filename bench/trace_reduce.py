"""Reduce a profiler trace (``.xplane.pb``) to device time.

What the TPU's trace holds (as recorded on a TPU v5 lite with JAX 0.9):
a plane per chip named ``/device:TPU:<n>``, whose ``XLA Ops`` line has one
event per operation that ran, named by its HLO text
(``%fusion.315 = bf16[8192,16,16,64]{...} fusion(...)``; a Pallas kernel
by the function that wraps its ``pallas_call``:
``%flash_attention.12 = ...``, ``%paged_decode_attention.9 = ...``), a
``while`` loop as one event over the ops of its body, and an ``XLA
Modules`` line with one event per program run.  The ``/host:CPU`` plane
has a line per host thread; the benchmark's ``bench.step`` and
``bench.submit`` annotations are on the engine's.  All times are
nanoseconds on one clock.

``reduce`` takes the window from the first ``bench.step`` span's start
to the last one's end, and gives within it: the busy time (the union of
the op intervals, averaged over the chips that ran any), the time of each
op and of each op family (the name without its instance number; loops
left out, since their ops count already), and the idle gaps with what
the engine's thread was doing in each.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_SPAN = "bench.step"
LOOPS = ("while", "conditional", "call")
_SUFFIX = re.compile(r"\.\d+$")
_HLO = re.compile(r"%?([^\s=]+)(?: = (\S+))?")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event."""
    return _HLO.match(event_name).group(1)


def op_label(event_name: str) -> str:
    """The instruction name with its result shape (without the layout),
    e.g. ``fusion.315 bf16[8192,16,16,64]``: readable, and stable for one
    compiled program."""
    m = _HLO.match(event_name)
    shape = (m.group(2) or "").split("{")[0]
    return f"{m.group(1)} {shape}".strip()


def op_family(name: str) -> str:
    """An op name without its HLO instance number: ``fusion.12`` ->
    ``fusion``, ``flash_attention.3`` -> ``flash_attention``."""
    return _SUFFIX.sub("", op_name(name))


def union_length(intervals: Sequence[Tuple[int, int]]) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _events(pd):
    """(device op events per chip, program runs, host events), as plain
    tuples."""
    dev: Dict[str, List[Tuple[str, int, int]]] = {}
    mods: List[Tuple[str, int, int]] = []
    host: List[Tuple[str, int, int, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.end_ns))
                       for e in line.events]
                if line.name == OPS_LINE:
                    dev.setdefault(plane.name, []).extend(evs)
                elif line.name == MODULES_LINE:
                    mods.extend(evs)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.end_ns),
                             line.name) for e in line.events)
    return dev, mods, host


def _label_gaps(host, spans: List[Tuple[int, int]]) -> List[str]:
    """For each gap (in time order), what the host was doing at its
    midpoint: the innermost host span open then (the one that started
    last) other than the whole step; else whether a step was open."""
    events = sorted((a, b, name) for name, a, b, _ in host)
    active: List[Tuple[int, int, str]] = []
    labels, i = [], 0
    for lo, hi in spans:
        t = (lo + hi) // 2
        while i < len(events) and events[i][0] <= t:
            active.append(events[i])
            i += 1
        active = [e for e in active if e[1] > t]
        inner = [e for e in active if e[2] != STEP_SPAN]
        if inner:
            labels.append(max(inner)[2])
        elif active:
            labels.append("in bench.step")
        else:
            labels.append("between steps")
    return labels


def reduce(pd, top: int = 10) -> Dict:
    """Busy time, op times and idle gaps of one trace (a ``ProfileData``).

    Returns ``window_s``, ``busy_s`` (mean over chips with ops),
    ``op_s`` ({op family: seconds, summed over chips}), and the ``top``
    largest of ``programs`` (device time per compiled program),
    ``device_ops`` (per op) and ``idle_gaps`` (per host activity), each
    ``[[name, seconds], ...]``."""
    dev, mods, host = _events(pd)
    steps = [(a, b, line) for name, a, b, line in host if name == STEP_SPAN]
    if not steps:
        raise RuntimeError(f"no {STEP_SPAN!r} span in the trace")
    if not dev:
        raise RuntimeError("no device op ran in the trace")
    lo, hi = min(a for a, _, _ in steps), max(b for _, b, _ in steps)
    engine_thread = {line for _, _, line in steps}
    busy = []
    op_s, family_s, idle = (collections.Counter(), collections.Counter(),
                            collections.Counter())
    for chip, evs in dev.items():
        ivs = []
        for name, a, b in evs:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            ivs.append((a, b))
            family = op_family(name)
            if family not in LOOPS:
                op_s[op_label(name)] += (b - a) * 1e-9
                family_s[family] += (b - a) * 1e-9
        busy.append(union_length(ivs))
        if chip == min(dev):
            spans = gaps(ivs, lo, hi)
            mine = [h for h in host if h[3] in engine_thread]
            for (a, b), name in zip(spans, _label_gaps(mine, spans)):
                idle[name] += (b - a) * 1e-9
    programs = collections.Counter()
    for name, a, b in mods:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            programs[name.split("(")[0]] += (b - a) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "op_s": dict(family_s),
        "programs": [[k, v] for k, v in programs.most_common(top)],
        "device_ops": [[k, v] for k, v in op_s.most_common(top)],
        "idle_gaps": [[k, v] for k, v in idle.most_common(top)],
    }


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)

