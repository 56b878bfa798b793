"""Scheduler (``serving/engine.py``): how long an admitted prompt waits
behind the head prefill cursor.

Mean, over the requests due in the window that the engine stamped, of
``Request.first_chunk_time - Request.admit_time``: from the admission that
took its slot and cursor to the first dispatch that carries any of its
prompt.  With chunked prefill a cursor's chunks run only once every
cursor ahead of it is done, so this is most of the scheduler's wait
(``queue_wait_ms`` times the slot alone).  A program without the stamps
reads nothing."""

from bench import window


def read(run):
    eng = run.probe.engine
    reqs = {r.uid: r for r in [*eng.finished, *eng.queue, *eng.slots]
            if r is not None}
    xs = []
    for rec in window.in_window(run.records, run.t0, run.t1):
        r = reqs.get(rec["uid"])
        admit = getattr(r, "admit_time", 0.0)
        first = getattr(r, "first_chunk_time", 0.0)
        if admit > 0 and first > 0:
            xs.append(first - admit)
    return 1e3 * sum(xs) / len(xs) if xs else None
