"""OpenAI-compatible HTTP server: SSE streaming order, client-vs-engine
timestamps, metrics surface, and the steady-state loadgen energy ledger."""

import asyncio
import json
import math
import time

import jax
import numpy as np
import pytest

aiohttp = pytest.importorskip("aiohttp")

from repro.core.energy import PowerMonitor, SyntheticReader  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.serving.client import fetch_metrics, stream_completion  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402
from repro.serving.loadgen import (LoadSpec, attribute_energy,  # noqa: E402
                                   prewarm_engine, run_load)
from repro.serving.sampling import SamplingParams  # noqa: E402
from repro.serving.server import encode_prompt, start_http_server  # noqa: E402

pytestmark = pytest.mark.server


def _tiny_cfg():
    return ModelConfig(
        name="srv", num_layers=2, d_model=32, num_heads=2,
        num_kv_heads=2, d_ff=64, vocab_size=128,
        dtype="float32", param_dtype="float32",
    ).validate()


@pytest.fixture(scope="module")
def server():
    """One server over a prewarmed tiny engine, shared across tests."""
    cfg = _tiny_cfg()
    params, _ = model_lib.init(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_batch=2, max_len=64)
    prewarm_engine(engine, prompt_len=8, concurrency=2,
                   vocab_size=cfg.vocab_size)
    handle = start_http_server(engine, model_name=cfg.name)
    yield handle, cfg, params
    handle.close()


async def _collect_sse(url, payload):
    """Raw SSE chunk stream with per-chunk arrival timestamps."""
    events = []
    async with aiohttp.ClientSession() as session:
        async with session.post(f"{url}/v1/completions", json=payload) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/event-stream")
            async for raw in r.content:
                line = raw.strip()
                if not line.startswith(b"data:"):
                    continue
                data = line[5:].strip()
                if data == b"[DONE]":
                    events.append((time.perf_counter(), "[DONE]"))
                    break
                events.append((time.perf_counter(), json.loads(data)))
    return events


def test_stream_order_and_timestamps(server):
    handle, cfg, _ = server
    send = time.perf_counter()
    events = asyncio.run(_collect_sse(handle.url, {
        "prompt": [1, 2, 3, 4, 5], "max_tokens": 6, "stream": True}))
    # terminal sentinel, exactly once, last
    assert [e for _, e in events].count("[DONE]") == 1
    assert events[-1][1] == "[DONE]"
    chunks = [e for _, e in events[:-1]]
    token_chunks = [c for c in chunks if c["choices"][0]["finish_reason"] is None]
    final = chunks[-1]
    # token chunks are contiguous and in order; the final chunk closes
    streamed = []
    for c in token_chunks:
        assert c["elana"]["first_index"] == len(streamed)
        streamed.extend(c["elana"]["tokens"])
    assert len(streamed) == 6
    assert final["choices"][0]["finish_reason"] == "length"
    assert final["usage"] == {"prompt_tokens": 5, "completion_tokens": 6,
                              "total_tokens": 11}
    # engine-side stamps ride the final chunk and order correctly against
    # the client's own clock (same CLOCK_MONOTONIC domain)
    ext = final["elana"]
    assert send < ext["engine_submit_s"] <= ext["engine_first_token_s"]
    assert ext["engine_first_token_s"] <= ext["engine_finish_s"]
    first_arrival = events[0][0]
    assert ext["engine_first_token_s"] <= first_arrival
    # arrivals are monotonic and every emit stamp precedes its arrival
    arrivals = [t for t, c in events[:-1]]
    assert arrivals == sorted(arrivals)
    for (arrival, c) in events[:-1]:
        if isinstance(c, dict) and c["choices"][0]["finish_reason"] is None:
            assert c["elana"]["emit_s"] <= arrival


def test_stream_matches_direct_engine(server):
    """Greedy decoding through HTTP is byte-identical to driving a fresh
    engine directly with the same prompt."""
    handle, cfg, params = server
    prompt = [7, 11, 13, 17, 19, 23, 29, 31]

    async def go():
        async with aiohttp.ClientSession() as s:
            return await stream_completion(s, handle.url, prompt,
                                           max_tokens=8)

    rec = asyncio.run(go())
    assert not rec.error
    ref = ServingEngine(cfg, params, max_batch=2, max_len=64)
    ref.submit(np.asarray(prompt, np.int32),
               SamplingParams(max_new_tokens=8))
    done = ref.run()
    assert rec.tokens == list(done[0].output_tokens)


def test_client_record_latency_ordering(server):
    handle, _, _ = server

    async def go():
        async with aiohttp.ClientSession() as s:
            return await stream_completion(s, handle.url, [3, 1, 4, 1, 5],
                                           max_tokens=5)

    rec = asyncio.run(go())
    assert not rec.error
    assert rec.finish_reason == "length"
    assert len(rec.tokens) == 5
    assert rec.send_time < rec.first_chunk_time <= rec.last_chunk_time
    # client-observed latencies bound the engine's own from above
    assert rec.client_ttft_s >= rec.engine_ttft_s > 0.0
    assert rec.client_ttlt_s >= rec.client_ttft_s
    assert rec.usage["completion_tokens"] == 5


def test_non_streaming_completion(server):
    handle, _, _ = server

    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{handle.url}/v1/completions", json={
                    "prompt": [1, 2, 3], "max_tokens": 4}) as r:
                assert r.status == 200
                return await r.json()

    body = asyncio.run(go())
    assert body["object"] == "text_completion"
    assert body["choices"][0]["finish_reason"] == "length"
    assert body["usage"]["completion_tokens"] == 4
    assert len(body["elana"]["tokens"]) == 4


def test_bad_requests_rejected(server):
    handle, _, _ = server

    async def go():
        out = []
        async with aiohttp.ClientSession() as s:
            for payload in ({"prompt": [], "max_tokens": 4},
                            {"prompt": [999999], "max_tokens": 4},
                            {"prompt": [1, 2], "max_tokens": 0}):
                async with s.post(f"{handle.url}/v1/completions",
                                  json=payload) as r:
                    out.append((r.status, await r.json()))
        return out

    for status, body in asyncio.run(go()):
        assert status == 400
        assert "error" in body


def test_models_and_metrics_endpoints(server):
    handle, cfg, _ = server

    async def go():
        async with aiohttp.ClientSession() as s:
            await stream_completion(s, handle.url, [2, 4, 6], max_tokens=3)
            async with s.get(f"{handle.url}/v1/models") as r:
                models = await r.json()
            return models, await fetch_metrics(s, handle.url)

    models, metrics = asyncio.run(go())
    assert [m["id"] for m in models["data"]] == [cfg.name]
    # engine ledger + server counters in one scrape
    assert metrics["requests"] >= 1
    for key in ("ttft_ms", "tpot_ms", "ttlt_ms", "tokens_per_sec",
                "submit_wait_p50_ms", "queue_wait_p95_ms",
                "prefill_wait_p50_ms", "compilations",
                "server_requests_received", "server_chunks_streamed",
                "server_in_flight", "server_uptime_s"):
        assert key in metrics, key
    assert metrics["server_requests_received"] >= 1
    assert metrics["server_chunks_streamed"] >= 3


def test_receipt_is_stamped_before_submission(server):
    """The handler stamps a request's receipt; the engine thread takes the
    submission later, between steps."""
    handle, _, _ = server

    async def go():
        async with aiohttp.ClientSession() as s:
            return await stream_completion(s, handle.url, [5, 3, 5],
                                           max_tokens=3)

    rec = asyncio.run(go())
    assert not rec.error
    req = max(handle.server.engine.finished, key=lambda r: r.uid)
    assert rec.send_time <= req.recv_time <= req.submit_time
    assert req.submit_time <= req.admit_time <= req.first_chunk_time
    assert req.first_chunk_time <= req.first_token_time <= req.finish_time


def test_concurrent_streams_complete(server):
    handle, _, _ = server

    async def go():
        async with aiohttp.ClientSession() as s:
            return await asyncio.gather(*[
                stream_completion(s, handle.url, [i + 1, i + 2, i + 3],
                                  max_tokens=4)
                for i in range(4)])

    recs = asyncio.run(go())
    assert all(not r.error for r in recs)
    assert all(len(r.tokens) == 4 for r in recs)
    assert all(r.client_ttft_s >= r.engine_ttft_s for r in recs)


def test_encode_prompt():
    assert encode_prompt([1, 2, 3], 128).tolist() == [1, 2, 3]
    assert encode_prompt("AB", 128).tolist() == [65, 66]
    with pytest.raises(ValueError):
        encode_prompt([128], 128)
    with pytest.raises(ValueError):
        encode_prompt([], 128)


def test_loadgen_steady_state_energy_ledger(server):
    """The ISSUE acceptance criterion: over a warmup-excluded steady-state
    window, client and engine latencies agree within tolerance AND the sum
    of per-request ``joules_between`` windows equals the monitor's run
    total (exact under the step-function model)."""
    handle, cfg, _ = server
    mon = PowerMonitor(
        SyntheticReader(lambda t: 40.0 + 10.0 * math.sin(t * 7.0)),
        interval_s=0.02)
    handle.server.engine.attach_monitor(mon)
    spec = LoadSpec(mode="closed", concurrency=2, warmup_s=0.4,
                    duration_s=1.2, prompt_len=8, max_new=6,
                    vocab_size=cfg.vocab_size)
    res = run_load(handle.url, spec, monitor=mon)
    s = res.summary
    assert s["steady_requests"] >= 2
    assert s["errors"] == 0
    # ledger exactness: tiles reproduce the total
    assert s["joules_attributed"] == pytest.approx(
        s["joules_total"], rel=1e-9, abs=1e-9)
    assert sum(r.joules for r in res.records) == pytest.approx(
        s["joules_total"], rel=1e-9)
    # re-tiling after the fact agrees too (attribution is deterministic)
    assert attribute_energy(res.records, mon) == pytest.approx(
        s["joules_total"], rel=1e-9)
    # client and engine views of the same requests agree within tolerance
    assert -1.0 <= s["ttft_client_minus_engine_ms"] <= 250.0
    assert abs(s["tpot_client_minus_engine_ms"]) <= 50.0
    # the protocol's sample-rate floor is verifiable from the summary
    assert s["power_samples_per_sec"] >= 0.5 / 0.02
    assert s["power_reads_dropped"] == 0
    # every steady record carries the engine's stamps
    assert all(r.engine for r in res.records)


def test_loadgen_open_loop(server):
    handle, cfg, _ = server
    spec = LoadSpec(mode="open", qps=6.0, warmup_s=0.3, duration_s=1.0,
                    prompt_len=8, max_new=4, vocab_size=cfg.vocab_size)
    res = run_load(handle.url, spec)
    s = res.summary
    assert s["steady_requests"] >= 1
    assert s["errors"] == 0
    # open loop: arrivals are schedule-driven, so the achieved rate stays
    # in the neighbourhood of the target even as completions vary
    assert 0.5 <= s["achieved_qps"] <= 12.0
