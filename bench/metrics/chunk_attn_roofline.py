"""Kernels (``kernels/flash_attention`` in the chunk pass): the least time
the prompts' causal attention needs, over the flash kernel's device time
in the trace, in %.

The need is the causal attention of every prompt whose first token a
traced step emitted, each real token over its real context
(the family's ``prompt_attn_flops``, with ``prompt_attn_bytes``); for
prompts of more than a few dozen tokens it is bound by compute.  A prompt
prefilled across the trace's first or last step counts whole or not at
all, by where its first token fell.  The kernel's time is the summed
device time of its ops, which the chip's trace names
``flash_attention.<k>`` (the function that wraps its ``pallas_call``); on
the paged path it runs only in the chunk pass.  Need and time are both
summed over the chips, so the share is one chip's (``bench/work.py``)."""

from bench import work

KERNEL = "flash_attention"


def read(run):
    if run.trace is None:
        return None
    t = run.trace["op_s"].get(KERNEL, 0.0)
    w, plen = run.work, run.probe.prompt_len
    flops = nbytes = 0
    for st in run.traced:
        for uid, k, n in st.emitted:
            if k == 0:
                flops += w.prompt_attn_flops(plen[uid])
                nbytes += w.prompt_attn_bytes(plen[uid])
    if t <= 0 or flops == 0:
        return None
    return work.roofline_share(flops, nbytes, t, run.peak)[0]
