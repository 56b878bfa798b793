"""Scheduler (``serving/engine.py``): how long requests wait for a slot.

Mean, over the requests due in the window that were admitted, of the
engine's submission stamp to the start of the step that took the request
out of ``engine.queue`` (seen by the benchmark's wrapper of
``engine.step``)."""

from bench import window


def read(run):
    p = run.probe
    xs = [p.admit_s[r["uid"]] - p.submit_s[r["uid"]]
          for r in window.in_window(run.records, run.t0, run.t1)
          if r["uid"] in p.admit_s and r["uid"] in p.submit_s]
    return 1e3 * sum(xs) / len(xs) if xs else None
