"""Device time by program scope and by engine step, from a profiler trace.

``bench/trace_reduce.py`` reads the trace through
``jax.profiler.ProfileData``, which gives each event's own stats but not
those of its metadata, where the TPU's trace keeps what XLA knows of an op:
``tf_op`` (the JAX name stack and op, e.g.
``jit(step)/decode/while/body/dynamic_slice:``), ``hlo_category`` (``data
formatting`` for a relayout, ``custom-call``, ``loop fusion``, ...) and
``source``.  This module parses the ``.xplane.pb`` itself, with
``google.protobuf`` and the few messages of XLA's ``xplane.proto`` it needs
declared below, and reduces it over the same window and ops as
``trace_reduce.reduce`` (ops clipped to the window, loops left out):

- ``scope_s``: device seconds per bucket.  An op whose name stack holds
  one of the fused step's scopes (``serving/step.py``: ``chunk_pass``,
  ``decode``, ``sample``) lands in it; an op with a name stack but none of
  them in ``step/other``; an op with none (XLA's own copies of the whole
  pool, say) in ``unscoped/<hlo_category>``.  Each op lands in one bucket.
- ``category_s``: device seconds per ``hlo_category``.
- ``steps``: per ``engine.step`` span, its index, start and end (seconds on
  the trace's clock), its arguments, its device seconds per bucket (ops
  that start between its start and the next step's) and the XLA module
  runs that start there.
- ``idle_gaps``: the device's idle time, labelled by the innermost program
  span (``engine.*``) open at each gap's midpoint, with JAX's own span
  inside it after a ``/`` (``engine.sync/np.asarray(jax.Array)``).
- ``join``: with the engine's step records (``core/trace.py``), the
  offset from each record's ``perf_counter`` start to its span's start,
  which puts the program's stamps on the trace's clock.

    python bench/xplane.py <trace.xplane.pb[.gz]>   # prints the reduction
"""

from __future__ import annotations

import bisect
import collections
import gzip
import json
import statistics
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace_reduce as tr  # noqa: E402

SCOPES = ("chunk_pass", "decode", "sample")
OTHER = "step/other"
UNSCOPED = "unscoped/"
RELAYOUT = "data formatting"
PROGRAM = "engine."
STEP_SPAN = "engine.step"
BENCH_STEP = tr.STEP_SPAN
DEVICE = "/device:TPU:"
HOST = "/host:CPU"


def _messages():
    """The XSpace messages, declared from ``xplane.proto`` (the fields this
    module reads; unknown fields are skipped by the parser)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, dbl = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    txt, raw, msg = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def add(into, name, fields, entry=False):
        m = into.add(name=name)
        if entry:
            m.options.map_entry = True
        for fname, num, typ, label, ref in fields:
            f = m.field.add(name=fname, number=num, type=typ, label=label)
            if ref:
                f.type_name = ".bench_xplane." + ref
        return m

    stat = add(fd.message_type, "XStat", [
        ("metadata_id", 1, i64, one, None), ("double_value", 2, dbl, one, None),
        ("uint64_value", 3, u64, one, None), ("int64_value", 4, i64, one, None),
        ("str_value", 5, txt, one, None), ("bytes_value", 6, raw, one, None),
        ("ref_value", 7, u64, one, None)])
    stat.oneof_decl.add(name="value")
    for f in stat.field[1:]:
        f.oneof_index = 0
    add(fd.message_type, "XEvent", [
        ("metadata_id", 1, i64, one, None), ("offset_ps", 2, i64, one, None),
        ("duration_ps", 3, i64, one, None),
        ("stats", 4, msg, many, "XStat")])
    add(fd.message_type, "XLine", [
        ("id", 1, i64, one, None), ("name", 2, txt, one, None),
        ("timestamp_ns", 3, i64, one, None),
        ("events", 4, msg, many, "XEvent")])
    add(fd.message_type, "XEventMetadata", [
        ("id", 1, i64, one, None), ("name", 2, txt, one, None),
        ("stats", 5, msg, many, "XStat")])
    add(fd.message_type, "XStatMetadata", [
        ("id", 1, i64, one, None), ("name", 2, txt, one, None)])
    plane = add(fd.message_type, "XPlane", [
        ("id", 1, i64, one, None), ("name", 2, txt, one, None),
        ("lines", 3, msg, many, "XLine"),
        ("event_metadata", 4, msg, many, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, msg, many, "XPlane.StatMetadataEntry")])
    add(plane.nested_type, "EventMetadataEntry", [
        ("key", 1, i64, one, None),
        ("value", 2, msg, one, "XEventMetadata")], entry=True)
    add(plane.nested_type, "StatMetadataEntry", [
        ("key", 1, i64, one, None),
        ("value", 2, msg, one, "XStatMetadata")], entry=True)
    add(fd.message_type, "XSpace", [("planes", 1, msg, many, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def parse(raw: bytes):
    """An ``XSpace`` message from a trace's bytes (gzipped or not)."""
    if raw[:2] == b"\x1f\x8b":
        raw = gzip.decompress(raw)
    space = _messages()()
    space.ParseFromString(raw)
    return space


def load(path: str):
    return parse(Path(path).read_bytes())


def _stats(stats, names: Dict[int, str]) -> Dict[str, object]:
    """Stats by name; a ``ref_value`` is the name of a stat metadata."""
    out = {}
    for s in stats:
        kind = s.WhichOneof("value")
        v = getattr(s, kind) if kind else None
        out[names.get(s.metadata_id, str(s.metadata_id))] = (
            names.get(v, "") if kind == "ref_value" else v)
    return out


# (name, start ns, end ns, tf_op, hlo_category)
Op = Tuple[str, float, float, str, str]
# (name, start ns, end ns, line, args)
Span = Tuple[str, float, float, str, Dict[str, object]]


def _times(line, ev) -> Tuple[float, float]:
    start = line.timestamp_ns + ev.offset_ps * 1e-3
    return start, start + ev.duration_ps * 1e-3


def events(space) -> Tuple[Dict[str, List[Op]], List[Tuple[str, float, float]],
                           List[Span]]:
    """(device ops per chip, XLA module runs, host spans)."""
    dev: Dict[str, List[Op]] = {}
    mods: List[Tuple[str, float, float]] = []
    host: List[Span] = []
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = plane.event_metadata
        if plane.name.startswith(DEVICE):
            info = {}
            for k, m in meta.items():
                st = _stats(m.stats, names)
                info[k] = (m.name, str(st.get("tf_op", "")),
                           str(st.get("hlo_category", "")))
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops = dev.setdefault(plane.name, [])
                    for ev in line.events:
                        name, tf_op, cat = info[ev.metadata_id]
                        ops.append((name, *_times(line, ev), tf_op, cat))
                elif line.name == tr.MODULES_LINE:
                    mods.extend((info[ev.metadata_id][0], *_times(line, ev))
                                for ev in line.events)
        elif plane.name.startswith(HOST):
            for line in plane.lines:
                for ev in line.events:
                    host.append((meta[ev.metadata_id].name,
                                 *_times(line, ev), line.name,
                                 _stats(ev.stats, names)))
    return dev, mods, host


def bucket(tf_op: str, category: str) -> str:
    """The scope bucket of one op (see the module's docstring)."""
    if not tf_op:
        return UNSCOPED + (category or "none")
    for part in tf_op.split(":")[0].split("/"):
        if part in SCOPES:
            return part
    return OTHER


def _label_gaps(host: Sequence[Span], spans: Sequence[Tuple[float, float]]
                ) -> List[str]:
    """What the engine's thread was doing at each gap's midpoint (the gaps
    in time order): the innermost program phase open then, else the step
    span (the engine's, else the benchmark's), then JAX's innermost span
    inside it after a ``/``."""
    evs = sorted((a, b, name) for name, a, b, _, _ in host)
    active: List[Tuple[float, float, str]] = []
    labels, i = [], 0
    for lo, hi in spans:
        t = (lo + hi) / 2
        while i < len(evs) and evs[i][0] <= t:
            active.append(evs[i])
            i += 1
        active = [e for e in active if e[1] > t]
        mine = [e for e in active if e[2].startswith(PROGRAM)]
        phases = [e for e in mine if e[2] != STEP_SPAN]
        outer = [e for e in active if e[2] == BENCH_STEP]
        where = max(phases or mine or outer, default=None)
        if where is None:
            labels.append("between steps")
            continue
        inner = [e for e in active if e[0] >= where[0]
                 and not e[2].startswith(PROGRAM) and e[2] != BENCH_STEP]
        label = where[2] if where[2] != BENCH_STEP else "in " + BENCH_STEP
        labels.append(label + ("/" + max(inner)[2] if inner else ""))
    return labels


def reduce(space, records: Optional[Iterable] = None, top: int = 10) -> Dict:
    """The reduction of one trace (an ``XSpace``); ``records`` are the
    engine's step records (``StepRecord`` or their dicts), for ``join``."""
    dev, mods, host = events(space)
    marks = [h for h in host if h[0] == BENCH_STEP] or [
        h for h in host if h[0] == STEP_SPAN]
    if not marks:
        raise RuntimeError(f"no {BENCH_STEP!r} or {STEP_SPAN!r} span in "
                           f"the trace")
    if not dev:
        raise RuntimeError("no device op ran in the trace")
    lo, hi = min(h[1] for h in marks), max(h[2] for h in marks)
    steps = sorted((h for h in host if h[0] == STEP_SPAN),
                   key=lambda h: h[1])
    starts = [h[1] for h in steps]
    thread = {h[3] for h in marks}

    def step_of(t: float) -> int:   # the last step starting by t, or -1
        return bisect.bisect_right(starts, t) - 1

    scope_s, category_s = collections.Counter(), collections.Counter()
    per_step = [collections.Counter() for _ in steps]
    busy, idle = [], collections.Counter()
    for chip in sorted(dev):
        ivs = []
        for name, a, b, tf_op, cat in dev[chip]:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            ivs.append((a, b))
            if tr.op_family(name) in tr.LOOPS:
                continue
            s = (b - a) * 1e-9
            where = bucket(tf_op, cat)
            scope_s[where] += s
            category_s[cat or "none"] += s
            k = step_of(a)
            if k >= 0:
                per_step[k][where] += s
        busy.append(tr.union_length(ivs))
        if chip == min(dev):
            spans = tr.gaps(ivs, lo, hi)
            mine = [h for h in host if h[3] in thread]
            for (a, b), label in zip(spans, _label_gaps(mine, spans)):
                idle[label] += (b - a) * 1e-9
    runs = collections.Counter(step_of(a) for _, a, _ in mods)
    out = {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "scope_s": dict(scope_s),
        "category_s": dict(category_s),
        "steps": [{"index": int(args.get("step", -1)),
                   "start_s": a * 1e-9, "end_s": b * 1e-9,
                   "args": {k: v for k, v in args.items() if k != "step"},
                   "scope_s": dict(c), "modules": runs.get(k, 0)}
                  for k, ((_, a, b, _, args), c)
                  in enumerate(zip(steps, per_step))],
        "idle_gaps": [[k, v] for k, v in idle.most_common(top)],
    }
    if records is not None:
        out["join"] = join(out["steps"], records)
    return out


def join(steps: List[Dict], records: Iterable) -> Dict:
    """Each traced step span against the program's record of that index:
    how many joined, and the offset from ``perf_counter`` to the trace's
    clock (median and spread, max - min)."""
    t0 = {}
    for r in records:
        r = r if isinstance(r, dict) else vars(r)
        t0[int(r["index"])] = float(r["t0"])
    off = [s["start_s"] - t0[s["index"]] for s in steps if s["index"] in t0]
    return {"spans": len(steps), "joined": len(off),
            "offset_s": statistics.median(off) if off else None,
            "offset_spread_ms": 1e3 * (max(off) - min(off)) if off else None}


# -- the per-layer quantities this reduction gives ---------------------------

def chunk_pass_ms(r: Dict) -> Optional[float]:
    """Mean device ms in the ``chunk_pass`` bucket over the traced steps
    that carried a chunk."""
    xs = [s["scope_s"].get("chunk_pass", 0.0) for s in r["steps"]
          if s["args"].get("chunk_rows", 0) > 0]
    return 1e3 * sum(xs) / len(xs) if xs else None


def decode_half_ms(r: Dict) -> Optional[float]:
    """Mean device ms in the ``decode`` bucket per traced step."""
    xs = [s["scope_s"].get("decode", 0.0) for s in r["steps"]]
    return 1e3 * sum(xs) / len(xs) if xs else None


def relayout_share(r: Dict) -> Optional[float]:
    """% of the device's busy time in ops XLA categorises as data
    formatting (relayouts), whatever their scope."""
    if r["busy_s"] <= 0:
        return None
    return 100.0 * r["category_s"].get(RELAYOUT, 0.0) / r["busy_s"]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.rsplit("\n\n", 1)[-1].strip(), file=sys.stderr)
        return 2
    r = reduce(load(argv[0]))
    r.update(chunk_pass_ms=chunk_pass_ms(r), decode_half_ms=decode_half_ms(r),
             relayout_share=relayout_share(r))
    print(json.dumps(r, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
