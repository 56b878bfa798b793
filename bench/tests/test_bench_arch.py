"""Model families: the qwen2 family reproduces the harness's Qwen2 code as it
was before families existed, a family is found by its configuration's
``model_type`` from files alone, and an unknown one names the file to add.
On the CPU, at smoke sizes."""

import hashlib
import json
import re
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import arch, harness, testing  # noqa: E402

qwen2 = arch.family("qwen2")
SEED = 2 ** 35 + 11

# Recorded from the Qwen2 code of the harness before it moved into
# bench/arch/qwen2.py (bench/weights.py:make and bench/reference.py:
# worst_gap), at testing.SMOKE on qwen1.5-0.5b's file, on the CPU.
PARENT_DIGEST = {
    "float32": "f00d553daf950f8ae4b3cb5410021c3084c43d5cbe9e919e7f33f87f7a050410",
    "bfloat16": "e58c4086aca6f5b2ebbe45952be2d42407ea938de2262bc3c03c9ee26f7ebb13",
}
PARENT_GAP = {"none": 1.008489966392517, "fp8": 0.06276863813400269}


def digest(tree) -> str:
    """sha256 over every leaf's path, shape, dtype and bytes."""
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda x: jax.tree_util.keystr(x[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def smoke():
    return testing.config("qwen1.5-0.5b", **testing.SMOKE)


@pytest.mark.parametrize("dtype", sorted(PARENT_DIGEST))
def test_qwen2_weights_are_bit_identical_to_the_parents(dtype):
    assert digest(qwen2.weights(smoke(), SEED, dtype)) == PARENT_DIGEST[dtype]


@pytest.mark.parametrize("quant", sorted(PARENT_GAP))
def test_qwen2_worst_gap_equals_the_parents(quant):
    # served tokens drawn at random: the gaps are far from 0, so any
    # change to the forward, the head or the control would show
    params = qwen2.weights(smoke(), 7, "float32")
    rng = np.random.default_rng(0)
    sample = [(rng.integers(0, 256, n).tolist(),
               rng.integers(0, 256, m).tolist())
              for n, m in ((40, 20), (7, 30), (90, 5))]
    got = qwen2.worst_gap(params, smoke(), sample, 128, quant=quant)
    assert got == PARENT_GAP[quant]


def test_the_configuration_names_its_family():
    assert json.loads((ROOT / "bench" / "configs" / "qwen1.5-0.5b.json")
                      .read_text())["model_type"] == "qwen2"
    c = harness.Cell.load("qwen05-chat")
    assert c.family is qwen2
    assert Path(c.family.__file__) == ROOT / "bench" / "arch" / "qwen2.py"


@pytest.mark.parametrize("model_type", ["mamba9", "../arch/qwen2", None])
def test_an_unknown_model_type_names_the_file_to_add(model_type):
    cfg = dict(smoke(), model_type=model_type)
    with pytest.raises(LookupError,
                       match=re.escape(f"add bench/arch/{model_type}.py")):
        testing.cell(cfg)


# A family that only this test has: qwen2's arithmetic under another
# model_type, which records that the harness called it.
TOY_FAMILY = '''"""Test-only family: qwen2's arithmetic, found as "toy"."""
from bench import arch

_qwen2 = arch.family("qwen2")
CALLS = []


def program_config(c):
    CALLS.append("program_config")
    return _qwen2.program_config(c)


def work(c):
    CALLS.append("work")
    return _qwen2.work(c)


def weights(c, seed, dtype="bfloat16", shardings=None):
    CALLS.append("weights")
    return _qwen2.weights(c, seed, dtype, shardings)


def worst_gap(weights, c, sample, length, quant="none"):
    CALLS.append("worst_gap")
    return _qwen2.worst_gap(weights, c, sample, length, quant)
'''


def _tree_with_a_new_family(tmp: Path) -> Path:
    """The files a new architecture brings, in a tree of their own: its
    family, configuration, cell and mix, and ``BENCHMARK.json`` with the
    entries it gains."""
    for d in ("arch", "configs", "cells", "traffic"):
        (tmp / "bench" / d).mkdir(parents=True)
    (tmp / "bench" / "arch" / "toy.py").write_text(TOY_FAMILY)
    (tmp / "bench" / "configs" / "toy-1.json").write_text(json.dumps(
        dict(smoke(), model_type="toy")))
    tiny = testing.cell(smoke())
    (tmp / "bench" / "cells" / "toy-chat.json").write_text(
        json.dumps(tiny.cell))
    (tmp / "bench" / "traffic" / "toy-chat.json").write_text(
        json.dumps(tiny.mix))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy-1", "source": "test",
                            "file": "bench/configs/toy-1.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "toy-chat", "config": "toy-1",
                              "traffic": "toy-chat", "chips": 1,
                              "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def test_a_family_is_added_by_files_alone(monkeypatch, tmp_path):
    root = _tree_with_a_new_family(tmp_path)
    toy = root / "bench" / "arch" / "toy.py"
    # the toy family's directory joins the package's search path, as its
    # file would join bench/arch/ in a checkout
    monkeypatch.setattr(arch, "__path__", [*arch.__path__, str(toy.parent)])
    monkeypatch.setattr(harness, "DRAIN_S", 1.0)
    try:
        c = harness.Cell.load("toy-chat", root=root)
        assert Path(c.family.__file__) == toy
        r = harness.measure(c, SEED, 2.0, False,
                            started=time.perf_counter(), require_tpu=False)
    finally:
        sys.modules.pop("bench.arch.toy", None)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(c.family.CALLS) == {"program_config", "work", "weights",
                                   "worst_gap"}
