"""Kernels (``kernels/decode_attention``, ``paged_decode_attention``): the
least time the decode rows' attention needs, over the paged decode
kernel's device time in the trace, in %.

The need is what each decode token emitted in the traced steps reads at its
own position: its cached keys and values, and its query and output
(``bench/work.py:decode_kv_bytes``); it is bound by memory.  The kernel's
time is the summed device time of its ops, which the chip's trace names
``paged_decode_attention.<k>`` (the function that wraps its
``pallas_call``)."""

from bench import work

KERNEL = "paged_decode_attention"


def read(run):
    if run.trace is None:
        return None
    t = run.trace["op_s"].get(KERNEL, 0.0)
    s, plen = run.sizes, run.probe.prompt_len
    flops = nbytes = 0
    for st in run.traced:
        for uid, k, n in st.emitted:
            for j in range(max(k, 1), k + n):
                pos = work.decode_position(plen[uid], j)
                flops += work.attn_flops(s, pos + 1)
                nbytes += work.decode_kv_bytes(s, pos)
    if t <= 0 or nbytes == 0:
        return None
    return work.roofline_share(flops, nbytes, t, run.peak)[0]
