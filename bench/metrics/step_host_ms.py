"""Fused step: the host's own time in a step, with the device waiting.

Mean, over the engine's step records (``engine.spans``) of the traced
steps, of the ``engine.step`` span's length minus its ``engine.sync``
phases (the wait for the step's packed device result).  What remains is
admission, the frontier pick, block growth, input packing and launch,
and the emission of tokens to the stream hooks.  A program without step
records reads nothing."""

SYNC = "engine.sync"


def read(run):
    spans = getattr(run.probe.engine, "spans", None)
    if spans is None or not run.traced:
        return None
    lo, hi = run.traced[0].t0, run.traced[-1].t1
    xs = [(r.t1 - r.t0) - sum(b - a for name, a, b in r.spans
                              if name == SYNC)
          for r in spans.steps if lo <= r.t0 and r.t1 <= hi]
    return 1e3 * sum(xs) / len(xs) if xs else None
