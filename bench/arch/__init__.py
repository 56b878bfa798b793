"""Model families: what the harness reads of a model, found by the
configuration file's ``model_type``.

A family is one file, ``bench/arch/<model_type>.py``; nothing else of the
benchmark names an architecture, its keys or its formulas, so a model of
another architecture needs new files only.  A family module gives, for a
configuration file ``c`` (the dict of its keys):

- ``program_config(c)``: the program's ``ModelConfig``;
- ``work(c)``: the whole cell's work counts (``bench/work.py`` says by
  which conventions, and how a reader takes them per chip): ``vocab``,
  ``kv_bytes_per_token``, ``token_flops(position, sampled)``,
  ``prompt_flops(n)``, ``decode_attn_flops(position)``,
  ``decode_kv_bytes(position)``, ``prompt_attn_flops(n)``,
  ``prompt_attn_bytes(n)``;
- ``weights(c, seed, dtype, shardings=None)``: the weight tree from the
  seed (``bench/weights.py:key_for``), made on the device in one jitted
  call and laid out as the program's parameter tree; ``shardings`` (a tree
  of the program's shardings over a cell's chips) places each leaf as it
  is made;
- ``worst_gap(weights, c, sample, length, quant="none")``: the plain
  reference's widest served-token gap over ``sample``, a list of (prompt,
  served tokens), each padded to ``length``; ``quant="fp8"`` is the
  control (``bench/reference.py``).
"""

from __future__ import annotations

import importlib
import re
from types import ModuleType

_NAME = re.compile(r"[A-Za-z0-9_]+")


def family(model_type: str) -> ModuleType:
    """The family module ``bench.arch.<model_type>``.  An unknown
    ``model_type`` is an error that names the file to add."""
    rel = f"bench/arch/{model_type}.py"
    missing = LookupError(f"no model family for model_type {model_type!r}: "
                          f"add {rel}")
    if not (isinstance(model_type, str) and _NAME.fullmatch(model_type)):
        raise missing
    name = f"{__name__}.{model_type}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise missing from None
