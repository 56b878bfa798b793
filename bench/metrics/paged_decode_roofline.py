"""Kernels (``kernels/decode_attention``, ``paged_decode_attention``): the
least time the decode rows' attention needs, over the paged decode
kernel's device time in the trace, in %.

The need is what each decode token emitted in the traced steps reads at its
own position: its cache entries, and its query and output (the family's
``decode_kv_bytes``); it is bound by memory.  The kernel's time is the
summed device time of its ops, which the chip's trace names
``paged_decode_attention.<k>`` (the function that wraps its
``pallas_call``).  Need and time are both summed over the chips, so the
share is one chip's (``bench/work.py``)."""

from bench import work

KERNEL = "paged_decode_attention"


def read(run):
    if run.trace is None:
        return None
    t = run.trace["op_s"].get(KERNEL, 0.0)
    w, plen = run.work, run.probe.prompt_len
    flops = nbytes = 0
    for st in run.traced:
        for uid, k, n in st.emitted:
            for j in range(max(k, 1), k + n):
                pos = work.decode_position(plen[uid], j)
                flops += w.decode_attn_flops(pos)
                nbytes += w.decode_kv_bytes(pos)
    if t <= 0 or nbytes == 0:
        return None
    return work.roofline_share(flops, nbytes, t, run.peak)[0]
