"""Front end (``serving/server.py``): how long a request waits for the
engine thread to take its submission.

Mean, over the requests due in the window that the engine stamped, of
``Request.submit_time - Request.recv_time``: from the HTTP handler's entry
to ``engine.submit``.  The engine thread drains the submission queue only
between steps, so a request that arrives during a step waits for its end.
A program without the stamps reads nothing."""

from bench import window


def read(run):
    eng = run.probe.engine
    reqs = {r.uid: r for r in [*eng.finished, *eng.queue, *eng.slots]
            if r is not None}
    xs = []
    for rec in window.in_window(run.records, run.t0, run.t1):
        r = reqs.get(rec["uid"])
        recv = getattr(r, "recv_time", 0.0)
        if recv > 0 and r.submit_time > 0:
            xs.append(r.submit_time - recv)
    return 1e3 * sum(xs) / len(xs) if xs else None
