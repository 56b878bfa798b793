"""The trace reductions on traces recorded on the chip, and the readers of
the engine's own spans and stamps.

``data/qwen05_chat_decode_steps.xplane.pb.gz`` (see ``test_bench_trace.py``)
predates the program's spans and scopes: three decode steps inside
``bench.step`` spans only.  ``data/qwen05_chat_spans.xplane.pb.gz`` is
three steps of the qwen05-chat engine as committed (16 slots, a 2048-block
pool) on one TPU v5 lite with JAX 0.9, each inside a ``bench.step`` span as
the benchmark's probe makes it: three requests decode while a 1500-token
prompt is admitted and prefilled, so every step carries a chunk.  Its
``/host:metadata`` plane (the programs' HLO, which nothing here reads) was
dropped to keep the file small; both reductions read the rest as they read
the whole.  ``qwen05_chat_spans.records.json`` holds the engine's records
of the same steps (``engine.spans.steps``)."""

import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import harness, trace_reduce as tr, xplane as xp  # noqa: E402

OLD = HERE / "data" / "qwen05_chat_decode_steps.xplane.pb.gz"
NEW = HERE / "data" / "qwen05_chat_spans.xplane.pb.gz"
RECORDS = HERE / "data" / "qwen05_chat_spans.records.json"


@pytest.fixture(scope="module")
def old_raw():
    return gzip.decompress(OLD.read_bytes())


@pytest.fixture(scope="module")
def old_reduced(old_raw):
    from jax.profiler import ProfileData
    return tr.reduce(ProfileData.from_serialized_xspace(old_raw))


def test_the_existing_reduction_reads_as_before(old_reduced):
    r = old_reduced
    assert r["programs"] == [["jit_step", pytest.approx(0.334774, abs=1e-5)]]
    ops = dict(r["device_ops"])
    assert [k for k, _ in r["device_ops"][:3]] == [
        "paged_decode_attention.9 bf16[32,16,64]",
        "copy.85 bf16[1,2048,16,16,64]", "copy.82 bf16[1,2048,16,16,64]"]
    for name, s in (("paged_decode_attention.9 bf16[32,16,64]", 0.137063),
                    ("copy.85 bf16[1,2048,16,16,64]", 0.029824),
                    ("copy.128 bf16[24,2048,16,16,64]", 0.014713),
                    ("copy.88 bf16[2048,16,1024]", 0.008337)):
        assert ops[name] == pytest.approx(s, abs=1e-6), name
    for family, s in (("copy", 0.161594), ("fusion", 0.006678),
                      ("constant_dynamic-update-slice_fusion", 0.014763),
                      ("constant_dynamic-slice_fusion", 0.012829)):
        assert r["op_s"][family] == pytest.approx(s, abs=1e-6), family
    assert r["idle_gaps"] == [["$array.py:631 _value",
                               pytest.approx(0.007024, abs=1e-6)]]


def test_op_metadata_on_a_chip_trace(old_raw):
    dev, mods, _ = xp.events(xp.parse(old_raw))
    assert list(dev) == ["/device:TPU:0"] and len(mods) == 3
    meta = {tr.op_name(name): (tf_op, cat)
            for name, _, _, tf_op, cat in dev["/device:TPU:0"]}
    # the per-layer pool copies carry the JAX name stack ...
    for op in ("copy.82", "copy.85"):
        assert meta[op] == ("jit(step)/while/body/dynamic_slice:",
                            "data formatting")
    for op in ("copy.84", "copy.88"):
        assert meta[op][0].endswith("jit(paged_decode_attention)/reshape:")
        assert meta[op][1] == "data formatting"
    # ... the whole-pool copies none
    assert meta["copy.127"] == meta["copy.128"] == ("", "data formatting")
    assert meta["paged_decode_attention.9"][1] == "custom-call"


def test_buckets_sum_to_the_op_time(old_raw, old_reduced):
    r = xp.reduce(xp.parse(old_raw))
    assert r["window_s"] == pytest.approx(old_reduced["window_s"], rel=1e-6)
    assert r["busy_s"] == pytest.approx(old_reduced["busy_s"], rel=1e-6)
    ops = sum(old_reduced["op_s"].values())
    assert sum(r["scope_s"].values()) == pytest.approx(ops, rel=0.01)
    assert sum(r["category_s"].values()) == pytest.approx(ops, rel=0.01)
    # no program scope yet: 91% of the op time has a name stack
    assert set(r["scope_s"]) - {xp.OTHER} == {
        k for k in r["scope_s"] if k.startswith(xp.UNSCOPED)}
    assert r["scope_s"][xp.OTHER] / ops == pytest.approx(0.912, abs=0.002)
    assert r["scope_s"]["unscoped/data formatting"] == pytest.approx(
        0.029425, abs=1e-5)
    assert xp.relayout_share(r) == pytest.approx(48.27, abs=0.05)
    assert r["steps"] == [] and xp.decode_half_ms(r) is None
    assert r["idle_gaps"][0][0] == "in bench.step/$array.py:631 _value"


def test_bucket_of_an_op():
    assert xp.bucket("jit(step)/chunk_pass/while/body/dot_general:",
                     "convolution fusion") == "chunk_pass"
    assert xp.bucket("jit(step)/decode/jit(paged_decode_attention)/"
                     "paged_decode_attention/pallas_call:",
                     "custom-call") == "decode"
    assert xp.bucket("jit(step)/sample/argmax:", "loop fusion") == "sample"
    assert xp.bucket("jit(_lambda)/scatter:", "loop fusion") == xp.OTHER
    assert xp.bucket("", "data formatting") == "unscoped/data formatting"


@pytest.fixture(scope="module")
def spans_trace():
    records = json.loads(RECORDS.read_text())
    return xp.reduce(xp.load(NEW), records), records


def test_the_spans_trace_is_small():
    assert NEW.stat().st_size < 300_000


def test_every_step_span_joins_its_record(spans_trace):
    r, records = spans_trace
    assert [s["index"] for s in r["steps"]] == [x["index"] for x in records]
    j = r["join"]
    assert j["spans"] == j["joined"] == 3
    assert j["offset_spread_ms"] < 1.0
    for s, rec in zip(r["steps"], records):
        assert s["args"] == rec["args"]
        assert s["end_s"] - s["start_s"] == pytest.approx(
            rec["t1"] - rec["t0"], abs=1e-3)


def test_every_scope_has_device_time(spans_trace):
    r, _ = spans_trace
    for bucket in (*xp.SCOPES, xp.OTHER):
        assert r["scope_s"].get(bucket, 0.0) > 0, bucket
    assert any(k.startswith(xp.UNSCOPED) for k in r["scope_s"])
    for s in r["steps"]:
        assert s["args"]["chunk_rows"] == 1 and s["args"]["decode_rows"] == 3
        for bucket in xp.SCOPES:
            assert s["scope_s"].get(bucket, 0.0) > 0, (s["index"], bucket)
        assert s["modules"] >= 1
    from jax.profiler import ProfileData
    old = tr.reduce(ProfileData.from_serialized_xspace(
        gzip.decompress(NEW.read_bytes())))
    ops = sum(old["op_s"].values())
    assert sum(r["scope_s"].values()) == pytest.approx(ops, rel=0.01)
    assert 0 < xp.chunk_pass_ms(r) and 0 < xp.decode_half_ms(r)
    assert 0 < xp.relayout_share(r) < 100


def test_idle_gaps_name_a_program_phase(spans_trace):
    r, _ = spans_trace
    idle = sum(s for _, s in r["idle_gaps"])
    named = sum(s for k, s in r["idle_gaps"] if k.startswith(xp.PROGRAM))
    assert idle > 0 and named >= 0.9 * idle


# -- the readers of the program's spans and stamps ----------------------------

def _served():
    """A tiny engine behind the probe, driven straight (no HTTP), with the
    client records a window of it would have."""
    import jax

    from bench import probe as probe_lib
    from bench import testing
    from repro.models import model as model_lib
    from repro.serving.engine import ServingEngine
    from repro.serving.sampling import SamplingParams

    c = testing.cell(testing.config("qwen1.5-0.5b", **testing.SMOKE))
    cfg = c.family.program_config(c.config)
    params, _ = model_lib.init(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, seed=0, **c.cell["engine"])
    probe = probe_lib.Probe(engine)
    probe.wrap_hook()
    rng = np.random.default_rng(0)
    uids = [engine.submit(rng.integers(0, 256, n, dtype=np.int32),
                          SamplingParams(max_new_tokens=5))
            for n in (40, 9, 70, 12, 33, 5)]
    engine.run()
    records = [{"uid": u, "due": 1.0 + i} for i, u in enumerate(uids)]
    run = harness.Run(records=records, t0=0.0, t1=100.0, deadline=105.0,
                      probe=probe, work=None, peak={}, compiles=0,
                      traced=probe.steps)
    return run, engine


def _read(name, run):
    return harness._reader(name)(run)


def test_the_span_readers_read_the_engine():
    run, engine = _served()
    done = engine.finished
    submit = _read("submit_wait_ms", run)
    assert submit == pytest.approx(
        1e3 * np.mean([r.submit_time - r.recv_time for r in done]))
    prefill = _read("prefill_wait_ms", run)
    assert prefill == pytest.approx(
        1e3 * np.mean([r.first_chunk_time - r.admit_time for r in done]))
    assert prefill > 0   # four slots, six requests: some wait for a cursor
    host = _read("step_host_ms", run)
    walls = [s.t1 - s.t0 for s in run.traced]
    assert 0 < host < 1e3 * np.mean(walls)


def test_the_span_readers_read_nothing_from_an_engine_without_spans():
    run, engine = _served()
    for r in engine.finished:   # a program that stamps none of these
        del r.recv_time, r.admit_time, r.first_chunk_time
    del engine.spans
    for name in ("submit_wait_ms", "prefill_wait_ms", "step_host_ms"):
        assert _read(name, run) is None, name


def test_the_work_readers_count_per_chip():
    # the family's counts are the whole cell's: the same steps in the same
    # wall time read, on four chips, a quarter of one chip's share of the
    # peak
    from bench import testing, work

    run, _ = _served()
    c = testing.config("qwen1.5-0.5b", **testing.SMOKE)
    run.work = testing.cell(c).family.work(c)
    run.peak = work.peaks("TPU v5 lite")
    run.t0, run.t1 = run.traced[0].t0, run.traced[-1].t1
    read = {}
    for chips in (1, 4):
        run.chips = chips
        read[chips] = _read("step_mfu", run)
    assert read[1] is not None and read[1] > 0, read
    assert read[4] == pytest.approx(read[1] / 4)
