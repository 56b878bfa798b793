#!/usr/bin/env python3
"""Bring-up smoke run of the serving path on a TPU.

    python chip_smoke.py            # one chip: phases (a)-(f)
    python chip_smoke.py --tp 4     # four chips: tp=4 against tp=1 only

Drives the full-width ``qwen1.5-0.5b`` (24 layers, d_model 1024, 16 heads
of 64, vocab 151,936, bf16; weights drawn from ``--seed``) through the
entry points a user calls, all in this one process:

  (a) device      platform, device_kind and count as JAX reports them
  (b) offline     ``repro.launch.serve.main``: 16 lognormal prompts (~200
                  tokens) through the paged, chunked engine, then a short
                  contiguous run (whole-prompt prefill + contiguous decode)
  (c) HTTP        ``repro.launch.bench_serve.main --check``: closed-loop
                  SSE load against the in-process server
  (d) reference   the same prompts through the same engine configuration
                  with the Pallas kernels and with the jnp reference
                  attention (``dispatch.use_backend("xla")``): first-step
                  logits within ``LOGIT_RTOL``, greedy agreement reported
  (e) profiler    ``Elana.measure``: the paper's TTFT/TPOT/TTLT
  (f) kernels     the engine step's compiled HLO calls the Pallas kernels

``--tp 4`` serves the same greedy prompts at tp=4 over four chips and at
tp=1 on one, compares them the same way, and prints the KV bytes each chip
holds.  No other phase runs then.

Any failure raises and exits non-zero.  The last line of stdout, printed
only when every phase passed, is ``{"ok": true, "device": {...}}``.  The
times printed are of this one run on the reported device: a smoke run, not
a benchmark.  Compile seconds are JAX's backend-compile time; a second run
with the persistent compilation cache in place shows fewer of them.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen1.5-0.5b"
# First-step logits of the Pallas path may differ from the jnp reference by
# at most this fraction of the reference's largest |logit|.  Both run in
# bf16, whose unit roundoff is 2^-8 (0.4%).  The kernels and the reference
# round at different points in each of the 24 layers (online softmax with
# f32 accumulators against a whole-row softmax whose probabilities are cast
# to bf16 before the PV matmul), and such differences add up over layers to
# a few units of roundoff.  3% is about eight units: room for that, and far
# below the O(1) change a wrong mask, position or head mapping makes.
LOGIT_RTOL = 0.03
# the engine configuration of phases (b), (d) and (f)
MAX_BATCH, MAX_LEN, CHUNK, BLOCK = 8, 1024, 128, 16
N_REQUESTS, PROMPT_MEAN, MAX_NEW = 16, 200, 32


class _Compiles:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is counted inside the compile event
    with only its retrieval time)."""

    def __init__(self):
        import jax

        self.seconds, self.count, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.seconds, self.count, self.hits


@contextlib.contextmanager
def _phase(name: str, compiles: _Compiles, lines: list):
    print(f"\n===== ({name}) =====", flush=True)
    s0, n0, h0 = compiles.snapshot()
    t0 = time.perf_counter()
    notes: list = []
    yield notes
    s1, n1, h1 = compiles.snapshot()
    line = (f"({name}) ok: {'; '.join(notes)} | wall "
            f"{time.perf_counter() - t0:.1f} s, compile {s1 - s0:.1f} s in "
            f"{n1 - n0} compiles ({h1 - h0} from the persistent cache)")
    lines.append(line)
    print(line, flush=True)


def _captured(main, argv):
    """Run an entry point's ``main(argv)``, echo its stdout, and return the
    first JSON object it printed (its summary)."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    text = buf.getvalue()
    print(text, flush=True)
    if rc != 0:
        raise RuntimeError(f"{main.__module__}.main returned {rc}")
    return json.JSONDecoder().raw_decode(text[text.index("{"):])[0]


def _finite(summary, keys):
    import math

    for k in keys:
        if not (isinstance(summary.get(k), (int, float))
                and math.isfinite(summary[k]) and summary[k] >= 0):
            raise RuntimeError(f"summary[{k!r}] = {summary.get(k)!r}")


def _served(summary, requests, tokens):
    if summary["requests"] != requests or summary["output_tokens"] != tokens:
        raise RuntimeError(
            f"served {summary['requests']} requests / "
            f"{summary['output_tokens']} tokens, expected {requests} / "
            f"{tokens}")
    if summary.get("joules_source") != "modelled":
        raise RuntimeError(f"joules_source {summary.get('joules_source')!r}")
    _finite(summary, ("ttft_p50_ms", "tpot_p50_ms", "tokens_per_sec",
                      "joules_total"))


def _prompts(vocab_size: int, seed: int, n: int = 8):
    from repro.serving.workload import LengthDist, WorkloadSpec, poisson_trace

    spec = WorkloadSpec(
        arrival_rate=0.0, num_requests=n,
        prompt_len=LengthDist(kind="lognormal", mean=PROMPT_MEAN, low=50,
                              high=400),
        output_len=LengthDist(kind="fixed", mean=MAX_NEW),
        temperature=0.0, seed=seed)
    return [a.prompt for a in poisson_trace(spec, vocab_size)]


def _model(seed: int):
    """The full-width model with random weights, its logical param axes,
    eight greedy prompts, and the engine configuration of (b)."""
    import jax

    from repro.configs import get_config
    from repro.models import model as model_lib

    cfg = get_config(ARCH)
    params, axes = model_lib.init(cfg, jax.random.PRNGKey(seed))
    engine_kw = dict(max_batch=MAX_BATCH, max_len=MAX_LEN, seed=seed,
                     cache_layout="paged", kv_block_size=BLOCK,
                     prefill_chunk=CHUNK)
    return cfg, params, axes, _prompts(cfg.vocab_size, seed), engine_kw


def _serve_greedy(cfg, params, prompts, **engine_kw):
    """Serve ``prompts`` greedily; returns (engine, first-step logits by
    uid, output tokens by uid).  The first-step logits are the ones the
    engine samples each request's first token from."""
    import numpy as np

    from repro.serving.engine import ServingEngine
    from repro.serving.sampling import SamplingParams

    eng = ServingEngine(cfg, params, **engine_kw)
    logits = {}
    start_decoding = eng._start_decoding

    def record(req, slot, plen, step_logits, tables_np):
        logits[req.uid] = np.asarray(step_logits, np.float32)[0]
        return start_decoding(req, slot, plen, step_logits, tables_np)

    eng._start_decoding = record
    for p in prompts:
        eng.submit(p, SamplingParams(max_new_tokens=MAX_NEW))
    finished = eng.run()
    if len(finished) != len(prompts):
        raise RuntimeError(f"{len(finished)} of {len(prompts)} finished")
    return eng, logits, {r.uid: list(r.output_tokens) for r in finished}


def _compare(what, ref, got, notes):
    """Hold ``got``'s first-step logits to ``ref``'s within LOGIT_RTOL and
    report greedy token agreement."""
    import numpy as np

    (ref_logits, ref_tokens), (got_logits, got_tokens) = ref, got
    worst = 0.0
    for uid, r in ref_logits.items():
        g = got_logits[uid]
        if g.shape != r.shape or not np.isfinite(g).all():
            raise RuntimeError(f"request {uid}: logits {g.shape}, finite="
                               f"{bool(np.isfinite(g).all())}")
        worst = max(worst, float(np.abs(g - r).max() / np.abs(r).max()))
    same = total = 0
    first_div = []
    for uid, r in ref_tokens.items():
        g = got_tokens[uid]
        total += len(r)
        same += sum(a == b for a, b in zip(r, g))
        div = next((i for i, (a, b) in enumerate(zip(r, g)) if a != b), None)
        if div is not None:
            first_div.append((uid, div))
    notes.append(f"{what}: max |d logit| / max |ref logit| = {worst:.5f} "
                 f"(limit {LOGIT_RTOL})")
    notes.append(f"greedy tokens agree {same}/{total}; first divergence "
                 + (", ".join(f"request {u} at token {i}"
                              for u, i in first_div) or "none"))
    if worst > LOGIT_RTOL:
        raise RuntimeError(f"{what}: first-step logits differ by {worst:.5f} "
                           f"of the largest logit, above {LOGIT_RTOL}")


def _one_chip(seed: int, compiles: _Compiles, lines: list) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.profiler import Elana
    from repro.kernels import dispatch
    from repro.launch import bench_serve, serve
    from repro.models import cache as cache_lib
    from repro.models import model as model_lib
    from repro.serving.step import (donating_jit, init_slot_state,
                                    make_engine_step)

    kind = jax.devices()[0].device_kind
    label = f"smoke run on one {kind}, not a benchmark"
    common = ["--arch", ARCH, "--seed", str(seed), "--temperature", "0",
              "--power-reader", "model"]

    with _phase("b: offline serving, paged + chunked", compiles,
                lines) as notes:
        s = _captured(serve.main, common + [
            "--cache-layout", "paged", "--prefill-chunk", str(CHUNK),
            "--max-batch", str(MAX_BATCH), "--max-len", str(MAX_LEN),
            "--requests", str(N_REQUESTS), "--prompt-len-dist", "lognormal",
            "--prompt-len-mean", str(PROMPT_MEAN),
            "--max-new", str(MAX_NEW)])
        _served(s, N_REQUESTS, N_REQUESTS * MAX_NEW)
        notes.append(f"{label}: {s['requests']} requests, TTFT p50 "
                     f"{s['ttft_p50_ms']:.1f} ms, TPOT p50 "
                     f"{s['tpot_p50_ms']:.2f} ms, {s['tokens_per_sec']:.1f} "
                     f"tokens/s, {s['joules_total']:.1f} J modelled")

    with _phase("b: offline serving, contiguous", compiles, lines) as notes:
        s = _captured(serve.main, common + [
            "--cache-layout", "contiguous", "--max-batch", "4",
            "--max-len", "256", "--requests", "4",
            "--prompt-len-dist", "fixed", "--prompt-len-mean", "64",
            "--max-new", "16"])
        _served(s, 4, 4 * 16)
        notes.append(f"{label}: TTFT p50 {s['ttft_p50_ms']:.1f} ms, TPOT p50 "
                     f"{s['tpot_p50_ms']:.2f} ms")

    with _phase("c: HTTP closed loop", compiles, lines) as notes:
        s = _captured(bench_serve.main, [
            "--arch", ARCH, "--seed", str(seed), "--max-batch", "4",
            "--max-len", "512", "--prefill-chunk", str(CHUNK),
            "--concurrency", "4", "--prompt-len", "128",
            "--max-new", str(MAX_NEW), "--warmup-s", "5",
            "--duration-s", "8", "--power-reader", "model", "--check"])
        _finite(s, ("client_ttft_ms", "client_tpot_ms",
                    "client_tokens_per_sec"))
        notes.append(f"{label}: {s['steady_requests']} steady requests, "
                     f"client TTFT {s['client_ttft_ms']:.1f} ms, client TPOT "
                     f"{s['client_tpot_ms']:.2f} ms, "
                     f"{s['client_tokens_per_sec']:.1f} tokens/s")

    cfg, params, _, prompts, engine_kw = _model(seed)
    with _phase("d: Pallas against the jnp reference", compiles,
                lines) as notes:
        _, *pallas = _serve_greedy(cfg, params, prompts, **engine_kw)
        with dispatch.use_backend("xla"):
            _, *xla = _serve_greedy(cfg, params, prompts, **engine_kw)
        _compare(f"{len(prompts)} prompts, paged + chunked", xla, pallas,
                 notes)

    with _phase("e: profiler", compiles, lines) as notes:
        out = Elana(ARCH, seed=seed).measure(batch=1, prompt_len=128,
                                             gen_len=32)
        _finite(out, ("ttft_ms", "tpot_ms", "ttlt_ms"))
        notes.append(f"{label}: TTFT {out['ttft_ms']:.2f} ms, TPOT "
                     f"{out['tpot_ms']:.3f} ms, TTLT {out['ttlt_ms']:.1f} ms "
                     f"(batch 1, prompt 128, 32 new)")

    with _phase("f: kernels in the engine step", compiles, lines) as notes:
        num_blocks = cache_lib.default_num_blocks(MAX_BATCH, MAX_LEN, BLOCK)
        cache = jax.eval_shape(lambda: model_lib.init_cache(
            cfg, MAX_BATCH, MAX_LEN, jnp.dtype(cfg.dtype), layout="paged",
            block_size=BLOCK, num_blocks=num_blocks))
        state = jax.eval_shape(lambda: init_slot_state(
            MAX_BATCH, max_blocks=cache_lib.blocks_per_slot(MAX_LEN, BLOCK)))
        row = jax.ShapeDtypeStruct((MAX_BATCH,), jnp.int32)
        chunk = {"tokens": jax.ShapeDtypeStruct((MAX_BATCH, CHUNK),
                                                jnp.int32),
                 "start": row, "length": row}
        hlo = donating_jit(make_engine_step(cfg, MAX_LEN), (1, 3)).lower(
            params, state, chunk, cache).compile().as_text()
        calls = hlo.count("tpu_custom_call")
        # one flash-attention call for the chunk rows, one paged decode
        if calls < 2:
            raise RuntimeError(f"engine step has {calls} tpu_custom_call(s)")
        notes.append(f"compiled engine step has {calls} tpu_custom_call(s)")


def _four_chips(seed: int, compiles: _Compiles, lines: list) -> None:
    from repro.launch.mesh import make_tp_mesh

    cfg, params, axes, prompts, engine_kw = _model(seed)
    with _phase("tp: tp=4 over four chips against tp=1 on one", compiles,
                lines) as notes:
        _, *one = _serve_greedy(cfg, params, prompts, **engine_kw)
        eng, *four = _serve_greedy(cfg, params, prompts,
                                   mesh=make_tp_mesh(4), param_axes=axes,
                                   **engine_kw)
        _compare(f"{len(prompts)} prompts, paged + chunked", one, four, notes)
        per_dev = eng.kv_bytes_by_device(peak=True)
        total = eng.kv_bytes_in_use(peak=True)
        notes.append(f"kv_bytes_by_device (peak) = {per_dev}, "
                     f"aggregate {total}")
        if sum(per_dev) != total or len(set(per_dev)) != 1:
            raise RuntimeError(f"KV is not split evenly over the four "
                               f"chips: {per_dev} (aggregate {total})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", type=int, default=1, choices=[1, 4],
                    help="4: run only the tensor-parallel phase, on four "
                         "chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke.py: no repro package under {SRC}; "
                         f"run it from a checkout of the repository")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX reports platform "
                         f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < args.tp:
        raise SystemExit(f"--tp {args.tp} needs {args.tp} chips; JAX sees "
                         f"{len(devices)}")
    from repro.launch.cache import setup_compilation_cache

    cache_dir = setup_compilation_cache()
    compiles = _Compiles()
    lines: list = []
    with _phase("a: device", compiles, lines) as notes:
        notes.append(f"platform={devices[0].platform} device_kind="
                     f"{devices[0].device_kind!r} count={len(devices)}; "
                     f"compilation cache at {cache_dir}")
    if args.tp == 4:
        _four_chips(args.seed, compiles, lines)
    else:
        _one_chip(args.seed, compiles, lines)
    print("\n===== summary =====")
    print("\n".join(lines))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
