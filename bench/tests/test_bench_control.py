"""The control of ``max_logit_gap`` at a size a test run holds: the
reference in float8 in the program's place fails the cell's limit, where
the program serving in bf16 passes it.

qwen1.5-0.5b's widths (16 heads of 64, MLP 2816) at 2 layers and a
4,096-token vocabulary, served by the real engine through the harness on
the CPU; ``bench/control.py`` reads the same at the cell's full size on
the chip."""

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, testing  # noqa: E402

CELL = "qwen05-chat"


@pytest.mark.parametrize("seed", [1, 2])
def test_fp8_control_fails_the_limit_the_program_passes(monkeypatch, seed):
    limit = json.loads((ROOT / "bench" / "cells" / f"{CELL}.json")
                       .read_text())["limits"]["max_logit_gap"]
    monkeypatch.setattr(harness, "DRAIN_S", 1.0)
    cfg = testing.config("qwen1.5-0.5b", num_hidden_layers=2,
                         vocab_size=4096)
    c = testing.cell(cfg, limit=limit)
    r = harness.measure(c, seed, 2.0, False, started=time.perf_counter(),
                        require_tpu=False, control=True)
    program = r["checks"]["max_logit_gap"]["value"]
    control = r["detail"]["control"]
    assert r["correct"] is True and program <= limit
    assert control["correct"] is False
    assert control["checks"]["max_logit_gap"]["value"] > limit
