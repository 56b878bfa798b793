"""Front end (``serving/server.py``): what HTTP and the submission queue add
to the time to first token.

Mean, over the requests due in the window that got a first token, of the
client's time to first token from its send, minus the engine's from the
request's submission to its first token leaving the device (both on the
one monotonic clock).  The engine thread drains the submission queue once
per step, so a request that arrives during a step waits for its end: that
wait is part of this number."""

from bench import window


def read(run):
    xs = []
    for r in window.in_window(run.records, run.t0, run.t1):
        uid = r["uid"]
        if (r["arrivals"] and uid in run.probe.first_s
                and uid in run.probe.submit_s):
            client = r["arrivals"][0][0] - r["sent"]
            engine = run.probe.first_s[uid] - run.probe.submit_s[uid]
            xs.append(client - engine)
    return 1e3 * sum(xs) / len(xs) if xs else None
