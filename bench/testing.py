"""Small cells for the benchmark's CPU tests: the real harness, engine and
reference, at sizes a test run holds."""

from __future__ import annotations

import json
from typing import Dict

from bench import harness

ROOT = harness.ROOT


def config(name: str, **sizes) -> Dict:
    """A configuration file of the benchmark with some sizes replaced."""
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    c.update(sizes)
    return c


SMOKE = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=256,
             torch_dtype="float32")


def cell(cfg: Dict, *, limit: float = 1e-3, chips: int = 1) -> harness.Cell:
    """A tiny cell over ``cfg`` on ``chips`` devices with the benchmark's
    metric lists."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.Cell(
        name="tiny", chips=chips, config=cfg,
        mix={"prompt": {"dist": "lognormal", "mean": 24, "sigma": 0.8,
                        "low": 4, "high": 64},
             "output": {"dist": "lognormal", "mean": 8, "sigma": 0.8,
                        "low": 2, "high": 32},
             "trace": 1},
        cell={"engine": {"cache_layout": "paged", "kv_block_size": 16,
                         "kv_num_blocks": 0, "max_batch": 4, "max_len": 128,
                         "prompt_bucket": 1, "prefill_chunk": 32,
                         "prefill_budget": 32, "preemption": "off"},
              "load": {"rate": 10.0, "ramp_s": 0.3},
              "limits": {"max_logit_gap": limit}},
        end_to_end=spec["end_to_end"], per_layer=spec["per_layer"])
