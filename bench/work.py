"""Work the real tokens need: the conventions every family counts by, and
the helpers every family shares.

A family (``bench/arch/<model_type>.py``) counts a model's work from its
published sizes in ``work(c)``; the readers in ``bench/metrics`` call those
counts and the helpers here.  Every count comes from token counts and
positions that the benchmark knows itself (prompt lengths, and the index
of each token it was served), never from the padded shapes the program
hands its kernels: a later kernel that stops computing padding cannot then
read above its roofline, and the padding computed today shows as a low
share.

Conventions: a token at position ``p`` attends to ``p + 1`` keys (itself
and everything before it).  One multiply-add is two FLOPs.  The KV cache
holds bf16 entries, and queries and attention outputs are bf16.

Per chip: a family's counts are the whole cell's work.  A share of a
peak over wall time (``step_mfu``) divides that total by the cell's
``chips`` and sets it against one chip's peaks.  A share of a roofline
needs no division: its need is the whole cell's and its kernel time is
summed over the chips (as ``bench/trace_reduce.py`` gives it), so the
ratio is the same as one chip's need over one chip's time.  No JAX here.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS = Path(__file__).resolve().parent / "peaks.json"
KV_BYTES = 2      # bf16 cache entries
ACT_BYTES = 2     # bf16 queries and attention outputs


def decode_position(prompt_len: int, index: int) -> int:
    """Position of the token fed to the decode step that emits output
    token ``index`` (>= 1): the previous output token, written at
    ``prompt_len + index - 1``."""
    return prompt_len + index - 1


def peaks(device_kind: str) -> Dict:
    """The chip's published peaks; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Dict):
    """(share of the roofline in %, the bound that applies) for work of
    ``flops`` and ``nbytes`` that took ``seconds`` of kernel time on one
    chip of ``peak``."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
