"""Fused step: the model FLOPs that the window's real work needs, over the
steps' wall time at the chip's bf16 peak, in %.

Real work is every decode token the steps emitted, each at its own
position (the family's ``token_flops`` with the output head), plus every
prompt whose first token a step emitted, at all of its positions
(``prompt_flops``), divided by the cell's chips (``bench/work.py``).  The
time is the sum of the wall times of the steps that started in the window,
so idle time between steps does not count."""

from bench import work


def read(run):
    steps = run.probe.steps_in(run.t0, run.t1)
    if not steps:
        return None
    w, plen = run.work, run.probe.prompt_len
    flops = 0
    for st in steps:
        for uid, k, n in st.emitted:
            for j in range(k, k + n):
                if j == 0:
                    flops += w.prompt_flops(plen[uid])
                else:
                    flops += w.token_flops(
                        work.decode_position(plen[uid], j), True)
    if flops == 0:
        return None
    wall = sum(st.t1 - st.t0 for st in steps)
    per_chip = flops / run.chips
    return 100.0 * per_chip / (wall * run.peak["bf16_flops_per_s"])
