"""The benchmark's streaming HTTP client, run in a process of its own.

The harness starts ``serve_jobs`` in a spawned child, so that the client's
JSON parsing never holds the engine process's interpreter lock, and hands
it jobs over a pipe.  The child never imports JAX: the chip belongs to the
harness's process.

A job is a tuple:

    ("open", url, plan, stop, deadline)   plan: [(due, body, idx), ...]
    ("stop",)

The loop is open: each request is sent at its due time (``perf_counter``),
whatever the server is doing.  No request is sent after ``stop``; at
``deadline`` every stream still open is closed, and what had arrived by
then is kept.  The reply is the list of records that ``bench/window.py``
describes, with ``idx`` naming the request in the caller's schedule.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Dict, List

import aiohttp


def request_body(prompt, max_new: int) -> bytes:
    """A greedy streamed completion of ``prompt`` (token ids)."""
    return json.dumps({"prompt": [int(t) for t in prompt],
                       "max_tokens": int(max_new), "temperature": 0.0,
                       "stream": True}).encode()


def _record(idx: int, due: float) -> Dict:
    return {"idx": idx, "due": due, "sent": 0.0, "arrivals": [],
            "tokens": [], "uid": -1, "done": False, "error": ""}


async def _stream(session, url: str, body: bytes, rec: Dict) -> None:
    rec["sent"] = time.perf_counter()
    try:
        async with session.post(
                url + "/v1/completions", data=body,
                headers={"Content-Type": "application/json"}) as resp:
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}: {await resp.text()}"
                return
            async for line in resp.content:
                now = time.perf_counter()
                if not line.startswith(b"data: "):
                    continue
                data = line[6:].strip()
                if data == b"[DONE]":
                    break
                chunk = json.loads(data)
                toks = chunk.get("elana", {}).get("tokens")
                if rec["uid"] < 0:
                    rec["uid"] = int(chunk["id"].rsplit("-", 1)[1])
                if toks is None:  # the final chunk: engine stamps only
                    rec["done"] = True
                elif toks:
                    rec["arrivals"].append((now, len(toks)))
                    rec["tokens"].extend(toks)
    except (aiohttp.ClientError, ValueError, KeyError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"


async def _until(tasks, deadline: float) -> None:
    """Wait for ``tasks`` until ``deadline``, then cancel what is left."""
    if tasks:
        await asyncio.wait(tasks, timeout=max(deadline - time.perf_counter(),
                                              0.0))
    for t in tasks:
        t.cancel()
    if tasks:
        await asyncio.gather(*tasks, return_exceptions=True)


def _session() -> aiohttp.ClientSession:
    return aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=0),
        timeout=aiohttp.ClientTimeout(total=None))


async def run_open(url: str, plan, stop: float, deadline: float
                   ) -> List[Dict]:
    records, tasks = [], []
    async with _session() as session:
        for due, body, idx in sorted(plan, key=lambda p: p[0]):
            if due >= stop:
                break
            wait = due - time.perf_counter()
            if wait > 0:
                await asyncio.sleep(wait)
            rec = _record(idx, due)
            records.append(rec)
            tasks.append(asyncio.create_task(
                _stream(session, url, body, rec)))
        await _until(tasks, deadline)
    return records


def serve_jobs(conn) -> None:
    """Child-process entry: run jobs from ``conn`` until ``("stop",)``."""
    while True:
        job = conn.recv()
        if job[0] == "stop":
            break
        if job[0] == "open":
            conn.send(asyncio.run(run_open(*job[1:])))
        else:
            raise ValueError(f"unknown job {job[0]!r}")
    conn.close()
