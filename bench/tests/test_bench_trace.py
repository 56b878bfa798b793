"""The trace reduction, on intervals and on a trace recorded on the chip.

``data/qwen05_chat_decode_steps.xplane.pb.gz`` is three decode steps of
the qwen05-chat engine as first configured (32 slots, a 2048-block pool,
four requests in flight) recorded on one TPU v5 lite with JAX 0.9, each step inside a
``bench.step`` span."""

import gzip
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import trace_reduce as tr  # noqa: E402


def test_union_and_gaps_of_intervals():
    ivs = [(0, 10), (5, 15), (20, 30), (25, 26), (40, 50)]
    assert tr.union_length(ivs) == 15 + 10 + 10
    assert tr.gaps(ivs, 0, 60) == [(15, 20), (30, 40), (50, 60)]
    assert tr.gaps(ivs, 2, 45) == [(15, 20), (30, 40)]
    assert tr.gaps([], 3, 7) == [(3, 7)]


def test_op_names_from_hlo_text():
    e = ("%paged_decode_attention.9 = bf16[32,16,64]{2,1,0:T(8,128)(2,1)} "
         "custom-call(s32[32,256]{1,0} %copy-done.1)")
    assert tr.op_name(e) == "paged_decode_attention.9"
    assert tr.op_family(e) == "paged_decode_attention"
    assert tr.op_label(e) == "paged_decode_attention.9 bf16[32,16,64]"
    assert tr.op_family("%while.6 = (s32[]) while(...)") == "while"


@pytest.fixture(scope="module")
def chip_trace():
    from jax.profiler import ProfileData

    raw = gzip.decompress(
        (HERE / "data" / "qwen05_chat_decode_steps.xplane.pb.gz").read_bytes())
    return ProfileData.from_serialized_xspace(raw)


def test_reduce_a_chip_trace(chip_trace):
    r = tr.reduce(chip_trace)
    # three steps of ~114 ms, the device busy through nearly all of them
    assert r["window_s"] == pytest.approx(0.3418, abs=1e-3)
    assert r["busy_s"] == pytest.approx(0.3348, abs=1e-3)
    assert 0 < r["busy_s"] < r["window_s"]
    # the paged decode kernel, found by the name its wrapper gives it
    assert r["op_s"]["paged_decode_attention"] == pytest.approx(0.1371,
                                                                abs=1e-3)
    assert "while" not in r["op_s"]
    assert r["programs"][0][0] == "jit_step"
    assert r["programs"][0][1] == pytest.approx(r["busy_s"], rel=0.01)
    # op times never exceed the busy time (loops are not counted twice)
    assert sum(r["op_s"].values()) <= r["busy_s"] * 1.0001
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) >= 1
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-9
