"""OpenAI-compatible streaming HTTP front-end for the serving engine.

The engine is single-threaded by construction (one fused dispatch per
step, host-side scheduler state), so the server keeps it that way: a
dedicated *engine thread* owns the ``ServingEngine`` exclusively and runs
the admit/step loop, while an asyncio ``aiohttp`` application accepts
requests on its own event loop.  The two sides meet at exactly two
points:

* a thread-safe **submission queue** — each ``POST /v1/completions``
  enqueues ``(prompt, params, stream-handle)``; the engine thread drains
  it before every step and maps the engine-assigned uid back to the
  handle;
* the engine's **stream hook** — tokens are pushed to the request's
  asyncio queue from inside the per-step host sync (the moment they
  leave the device, before the ring buffer defers them), so SSE chunks
  carry per-step latency, and the finish edge carries the request's
  engine-side timestamps and attributed joules.

Endpoints (OpenAI completions shape, minus a tokenizer — prompts are
token-id lists, or strings byte-encoded into the vocab):

* ``POST /v1/completions`` — ``stream=true`` for SSE chunks terminated
  by ``data: [DONE]``; ``stream=false`` for one JSON body.  Each chunk's
  ``elana`` extension carries the raw token ids and emit timestamp; the
  final chunk's carries engine-side submit/first-token/finish stamps so
  a same-host client can compute client-vs-engine latency deltas
  (``time.perf_counter`` is CLOCK_MONOTONIC: one clock per machine).
* ``GET /v1/models`` — the single served model.
* ``GET /metrics`` — ``engine.latency_summary()`` plus server counters,
  as JSON.

``start_http_server`` wires it all up on an ephemeral port and returns a
handle; ``launch/serve.py --http-port`` and ``launch/bench_serve.py``
are the CLI entry points.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import queue
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from repro.serving.engine import Request, ServingEngine
from repro.serving.sampling import SamplingParams

try:  # aiohttp is a dev/serving extra, not a core runtime dependency
    from aiohttp import web
except ImportError:  # pragma: no cover - exercised only without aiohttp
    web = None


def encode_prompt(prompt, vocab_size: int) -> np.ndarray:
    """Token-id list passed through (validated), or a string byte-encoded
    into the vocab (this repo has no tokenizer — the id stream *is* the
    text)."""
    if isinstance(prompt, str):
        ids = [ord(c) % vocab_size for c in prompt]
    else:
        ids = [int(t) for t in prompt]
        bad = [t for t in ids if not 0 <= t < vocab_size]
        if bad:
            raise ValueError(
                f"prompt token(s) out of range [0, {vocab_size}): {bad[:5]}")
    if not ids:
        raise ValueError("prompt must contain at least one token")
    return np.asarray(ids, np.int32)


class _Stream:
    """Engine-thread -> event-loop bridge for one request's chunks."""

    def __init__(self, loop: asyncio.AbstractEventLoop):
        self._loop = loop
        self.q: asyncio.Queue = asyncio.Queue()
        self.uid: Optional[int] = None

    def push(self, item) -> None:
        try:
            self._loop.call_soon_threadsafe(self.q.put_nowait, item)
        except RuntimeError:  # event loop shut down mid-request
            pass


@dataclasses.dataclass
class _Submission:
    prompt: np.ndarray
    params: SamplingParams
    stream: _Stream
    recv_time: float  # the handler's entry, on time.perf_counter


class EngineServer:
    """The aiohttp application + the engine thread that feeds it."""

    def __init__(self, engine: ServingEngine, *, model_name: str = "elana",
                 idle_wait_s: float = 0.01):
        if web is None:  # pragma: no cover
            raise RuntimeError(
                "aiohttp is required for the HTTP server "
                "(pip install aiohttp)")
        self.engine = engine
        self.model_name = model_name
        self.idle_wait_s = idle_wait_s
        self._subq: "queue.Queue[_Submission]" = queue.Queue()
        self._streams: Dict[int, _Stream] = {}
        self._reqs: Dict[int, Request] = {}
        # engine exclusivity: the engine thread holds it across step();
        # metrics scrapes hold it across latency_summary()
        self._lock = threading.Lock()
        self._run = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._t_started = time.perf_counter()
        self.requests_received = 0
        self.chunks_streamed = 0
        engine.stream_hook = self._on_tokens

    # -- engine thread ---------------------------------------------------------
    def _on_tokens(self, uid: int, tokens: List[int], finished: bool) -> None:
        """``engine.stream_hook`` — runs on the engine thread mid-step."""
        h = self._streams.get(uid)
        if h is None:
            return
        now = time.perf_counter()
        if tokens:
            h.push(("tokens", list(tokens), now))
        if finished:
            req = self._reqs.pop(uid, None)
            self._streams.pop(uid, None)
            h.push(("end", self._final_payload(req), now))

    @staticmethod
    def _final_payload(req: Optional[Request]) -> Dict:
        if req is None:  # pragma: no cover - submit/finish race guard
            return {}
        return {
            "engine_submit_s": req.submit_time,
            "engine_first_token_s": req.first_token_time,
            "engine_finish_s": req.finish_time,
            "engine_ttft_s": req.ttft_s,
            "engine_tpot_s": req.tpot_s,
            "prompt_tokens": len(req.prompt),
            "completion_tokens": len(req.output_tokens),
            "joules": req.joules,
            "truncated": req.truncated,
            "preemptions": req.preemptions,
        }

    def _admit(self, sub: _Submission) -> None:
        with self._lock:
            uid = self.engine.submit(sub.prompt, sub.params)
            req = self.engine.queue[-1]
            req.recv_time = sub.recv_time
        sub.stream.uid = uid
        self._reqs[uid] = req
        self._streams[uid] = sub.stream
        sub.stream.push(("begin", uid, req.submit_time))

    def _engine_loop(self) -> None:
        eng = self.engine
        while self._run.is_set():
            while True:  # drain every pending submission before the step
                try:
                    self._admit(self._subq.get_nowait())
                except queue.Empty:
                    break
            if eng.busy:
                with self._lock:
                    eng.step()
            else:
                try:  # idle: block on the queue instead of spinning
                    sub = self._subq.get(timeout=self.idle_wait_s)
                except queue.Empty:
                    continue
                self._admit(sub)
        with self._lock:
            eng.flush()

    def start_engine(self) -> None:
        self._run.set()
        self._thread = threading.Thread(
            target=self._engine_loop, daemon=True, name="elana-engine")
        self._thread.start()

    def stop_engine(self) -> None:
        """Stop the engine loop and wait for it to end: the step in flight
        finishes, with whatever its caller hooked into it (a profiler
        stop that exports a trace can take many seconds), before the
        engine is handed back."""
        self._run.clear()
        if self._thread is not None:
            self._thread.join()

    def summary(self) -> Dict:
        """Engine ``latency_summary()`` + server-side counters."""
        with self._lock:
            out = dict(self.engine.latency_summary())
        out.update({
            "server_requests_received": self.requests_received,
            "server_chunks_streamed": self.chunks_streamed,
            "server_in_flight": len(self._streams),
            "server_uptime_s": time.perf_counter() - self._t_started,
        })
        return out

    # -- handlers --------------------------------------------------------------
    def build_app(self) -> "web.Application":
        app = web.Application()
        app.router.add_post("/v1/completions", self.handle_completions)
        app.router.add_get("/v1/models", self.handle_models)
        app.router.add_get("/metrics", self.handle_metrics)
        return app

    async def handle_models(self, request: "web.Request") -> "web.Response":
        return web.json_response({
            "object": "list",
            "data": [{"id": self.model_name, "object": "model",
                      "owned_by": "elana"}],
        })

    async def handle_metrics(self, request: "web.Request") -> "web.Response":
        return web.json_response(
            self.summary(),
            dumps=lambda o: json.dumps(o, default=float))

    async def handle_completions(self, request: "web.Request"):
        recv_time = time.perf_counter()
        try:
            body = await request.json()
        except Exception:
            return web.json_response(
                {"error": {"message": "body must be JSON"}}, status=400)
        try:
            prompt = encode_prompt(body.get("prompt", ""),
                                   self.engine.cfg.vocab_size)
            params = SamplingParams(
                temperature=float(body.get("temperature", 0.0)),
                top_k=int(body.get("top_k", 0)),
                eos_token=int(body.get("eos_token", -1)),
                max_new_tokens=int(body.get("max_tokens", 16)))
            if params.max_new_tokens < 1:
                raise ValueError("max_tokens must be >= 1")
        except (TypeError, ValueError) as e:
            return web.json_response(
                {"error": {"message": str(e)}}, status=400)

        self.requests_received += 1
        handle = _Stream(asyncio.get_running_loop())
        self._subq.put(_Submission(prompt, params, handle, recv_time))
        _, uid, _submit_time = await handle.q.get()  # ("begin", uid, t)
        cid = f"cmpl-{uid}"
        created = int(time.time())

        if bool(body.get("stream", False)):
            return await self._stream_response(request, handle, cid, created,
                                               params.max_new_tokens)
        tokens: List[int] = []
        while True:
            item = await handle.q.get()
            if item[0] == "tokens":
                tokens.extend(item[1])
            else:
                payload = item[1]
                break
        return web.json_response({
            "id": cid, "object": "text_completion", "created": created,
            "model": self.model_name,
            "choices": [{
                "index": 0,
                "text": "".join(f" {t}" for t in tokens),
                "finish_reason": self._finish_reason(
                    payload, params.max_new_tokens),
            }],
            "usage": {
                "prompt_tokens": payload.get("prompt_tokens", 0),
                "completion_tokens": payload.get("completion_tokens", 0),
                "total_tokens": (payload.get("prompt_tokens", 0)
                                 + payload.get("completion_tokens", 0)),
            },
            "elana": {**payload, "tokens": tokens},
        })

    @staticmethod
    def _finish_reason(payload: Dict, max_tokens: int) -> str:
        return ("length" if payload.get("completion_tokens", 0) >= max_tokens
                else "stop")

    async def _stream_response(self, request, handle: _Stream, cid: str,
                               created: int, max_tokens: int):
        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "Connection": "keep-alive",
        })
        await resp.prepare(request)
        index = 0
        alive = True  # keep draining after a client disconnect: the
        # engine runs the request to completion either way, and the end
        # event is what unregisters this stream's bookkeeping

        async def write(data: bytes) -> None:
            nonlocal alive
            if not alive:
                return
            try:
                await resp.write(data)
            except (ConnectionResetError, ConnectionError):
                alive = False

        while True:
            item = await handle.q.get()
            if item[0] == "tokens":
                _, toks, t_emit = item
                chunk = {
                    "id": cid, "object": "text_completion",
                    "created": created, "model": self.model_name,
                    "choices": [{"index": 0,
                                 "text": "".join(f" {t}" for t in toks),
                                 "finish_reason": None}],
                    "elana": {"tokens": toks, "first_index": index,
                              "emit_s": t_emit},
                }
                index += len(toks)
                await write(b"data: " + json.dumps(chunk).encode() + b"\n\n")
                self.chunks_streamed += 1
            else:  # ("end", payload, t)
                _, payload, _ = item
                final = {
                    "id": cid, "object": "text_completion",
                    "created": created, "model": self.model_name,
                    "choices": [{"index": 0, "text": "",
                                 "finish_reason": self._finish_reason(
                                     payload, max_tokens)}],
                    "usage": {
                        "prompt_tokens": payload.get("prompt_tokens", 0),
                        "completion_tokens": payload.get(
                            "completion_tokens", 0),
                        "total_tokens": (
                            payload.get("prompt_tokens", 0)
                            + payload.get("completion_tokens", 0)),
                    },
                    "elana": payload,
                }
                await write(b"data: " + json.dumps(final).encode() + b"\n\n")
                await write(b"data: [DONE]\n\n")
                break
        if alive:
            await resp.write_eof()
        return resp


@dataclasses.dataclass
class ServerHandle:
    """A running server: engine thread + aiohttp site on its own loop."""
    url: str
    server: EngineServer
    _loop: asyncio.AbstractEventLoop
    _runner: "web.AppRunner"
    _thread: threading.Thread

    def close(self) -> None:
        """Graceful shutdown: stop the engine loop (finishes its step,
        flushes buffers), tear down the HTTP site, stop and join the
        event-loop thread."""
        self.server.stop_engine()
        fut = asyncio.run_coroutine_threadsafe(
            self._runner.cleanup(), self._loop)
        try:
            fut.result(timeout=10.0)
        except Exception:  # pragma: no cover - best-effort teardown
            pass
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=10.0)

    def __enter__(self) -> "ServerHandle":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def start_http_server(engine: ServingEngine, *, host: str = "127.0.0.1",
                      port: int = 0, model_name: str = "elana"
                      ) -> ServerHandle:
    """Serve ``engine`` over HTTP; ``port=0`` picks an ephemeral port.

    Spins up one event-loop thread for aiohttp and one engine thread for
    the admit/step loop, and returns once both are accepting work."""
    srv = EngineServer(engine, model_name=model_name)
    loop = asyncio.new_event_loop()
    started = threading.Event()
    box: Dict[str, object] = {}

    def run() -> None:
        asyncio.set_event_loop(loop)

        async def setup():
            runner = web.AppRunner(srv.build_app())
            await runner.setup()
            site = web.TCPSite(runner, host, port)
            await site.start()
            box["runner"] = runner
            box["port"] = runner.addresses[0][1]

        loop.run_until_complete(setup())
        started.set()
        loop.run_forever()

    thread = threading.Thread(target=run, daemon=True, name="elana-http")
    thread.start()
    if not started.wait(timeout=10.0):  # pragma: no cover
        raise RuntimeError("HTTP server failed to start within 10s")
    srv.start_engine()
    return ServerHandle(url=f"http://{host}:{box['port']}", server=srv,
                        _loop=loop, _runner=box["runner"], _thread=thread)
