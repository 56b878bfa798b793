"""Fused step (``serving/step.py`` through ``engine.step``): mean wall time
of the engine steps that started in the window.  A step ends in its packed
``device_get``, so this includes the device's time."""


def read(run):
    steps = run.probe.steps_in(run.t0, run.t1)
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
