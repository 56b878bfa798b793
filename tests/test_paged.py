"""Paged KV cache: paged-vs-contiguous token equivalence (greedy and
sampled, mixed-length Poisson workloads, sliding-window interaction),
block free/reuse after finish, pool-exhaustion admission backpressure,
and batched multi-slot admission."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as model_lib
from repro.models.config import ModelConfig
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampling import SamplingParams
from repro.serving.workload import LengthDist, WorkloadSpec, poisson_trace


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("tinyllama-1.1b", smoke=True)
    params, _ = model_lib.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def hybrid_model():
    """Tiny stack mixing full attention with sliding-window layers."""
    cfg = ModelConfig(
        name="toy-hybrid", num_layers=4, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=256,
        block_pattern=("attn", "local_attn"), sliding_window=12,
        dtype="float32", param_dtype="float32",
    ).validate()
    params, _ = model_lib.init(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _run_engine(cfg, params, arrivals, layout, **kw):
    eng = ServingEngine(cfg, params, max_batch=2, max_len=64,
                        prompt_bucket=8, cache_layout=layout, **kw)
    for a in arrivals:
        eng.submit(a.prompt, a.params)
    finished = eng.run()
    return eng, {r.uid: list(r.output_tokens) for r in finished}


def _poisson_arrivals(cfg, n=6, temperature=0.7, seed=2):
    spec = WorkloadSpec(
        arrival_rate=0.0, num_requests=n,
        prompt_len=LengthDist(kind="lognormal", mean=16.0, low=2, high=48),
        output_len=LengthDist(kind="uniform", low=2, high=9),
        temperature=temperature, top_k=8, seed=seed,
    )
    return poisson_trace(spec, cfg.vocab_size)


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_paged_matches_contiguous_mixed_length_poisson(small_model, temperature):
    """Identical token streams across layouts for the same seed/config,
    under a mixed-length Poisson-sampled workload with queue pressure."""
    cfg, params = small_model
    arrivals = _poisson_arrivals(cfg, temperature=temperature)
    _, out_c = _run_engine(cfg, params, arrivals, "contiguous")
    eng_p, out_p = _run_engine(cfg, params, arrivals, "paged")
    assert set(out_c) == set(out_p) and len(out_c) == len(arrivals)
    for uid in out_c:
        assert out_c[uid] == out_p[uid], f"request {uid} diverged"
    assert eng_p.blocks_in_use == 0  # everything returned at drain


def test_paged_matches_contiguous_with_sliding_window(hybrid_model):
    """local_attn layers keep their ring buffers under the paged layout;
    mixed attn/local_attn stacks stay stream-identical across layouts."""
    cfg, params = hybrid_model
    arrivals = _poisson_arrivals(cfg, n=5, temperature=0.0, seed=7)
    _, out_c = _run_engine(cfg, params, arrivals, "contiguous")
    _, out_p = _run_engine(cfg, params, arrivals, "paged")
    assert out_c == out_p and len(out_c) == 5


@pytest.mark.parametrize("chunk,unroll", [(8, False), (0, True)])
def test_paged_remainder_layer_and_unrolled_loop(chunk, unroll):
    """The pools ride the layer loop's carry in the scanned and the
    unrolled loop alike, and a remainder layer's pool (a unit layer axis)
    is written in place too: streams stay identical to contiguous."""
    from repro.models import flags

    cfg = ModelConfig(
        name="toy-remainder", num_layers=3, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=256,
        block_pattern=("attn", "ffn"), dtype="float32",
        param_dtype="float32",
    ).validate()
    assert cfg.layer_groups() == (1, 1)  # one scanned group, attn left over
    params, _ = model_lib.init(cfg, jax.random.PRNGKey(3))
    arrivals = _poisson_arrivals(cfg, n=4, temperature=0.0, seed=5)
    with flags.use_unroll(unroll):
        _, out_c = _run_engine(cfg, params, arrivals, "contiguous",
                               prefill_chunk=chunk)
        eng, out_p = _run_engine(cfg, params, arrivals, "paged",
                                 prefill_chunk=chunk)
    assert eng.cache["rest"]["0"]["self"]["kp"].shape[0] == 1
    assert out_c == out_p and len(out_c) == 4


def test_blocks_freed_and_reused_after_finish(small_model):
    cfg, params = small_model
    eng = ServingEngine(cfg, params, max_batch=2, max_len=64,
                        prompt_bucket=8, cache_layout="paged",
                        kv_block_size=16)
    total_free = len(eng._free_blocks)
    rng = np.random.default_rng(0)
    for i in range(5):
        eng.submit(rng.integers(0, cfg.vocab_size, 8),
                   SamplingParams(max_new_tokens=4))
    finished = eng.run()
    assert len(finished) == 5
    # every block came back to the free stack ...
    assert eng.blocks_in_use == 0
    assert len(eng._free_blocks) == total_free
    assert all(not b for b in eng._slot_blocks)
    # ... and 5 requests through 2 slots can only fit by reusing blocks:
    # each needs 1 block (8 prompt + 4 new <= 16), peak is bounded by slots
    assert 1 <= eng.peak_blocks_in_use <= 2
    # freed slots point their table rows back at the garbage block
    assert int(jnp.sum(eng._state["block_tables"])) == 0


def test_pool_exhaustion_backpressure(small_model):
    """A pool that fits one worst-case request at a time forces queueing,
    but every request still completes with the right output length."""
    cfg, params = small_model
    blocks_per_req = 64 // 16
    eng = ServingEngine(cfg, params, max_batch=2, max_len=64,
                        prompt_bucket=8, cache_layout="paged",
                        kv_block_size=16, kv_num_blocks=1 + blocks_per_req)
    rng = np.random.default_rng(1)
    for i in range(3):
        # max_new=60 books the full 64-token budget -> 4 blocks each
        eng.submit(rng.integers(0, cfg.vocab_size, 8),
                   SamplingParams(max_new_tokens=60))
    eng.step()  # first admit: exactly one request fits the pool
    assert sum(s is not None for s in eng.slots) == 1
    assert len(eng.queue) == 2
    assert eng.blocks_in_use == blocks_per_req
    finished = eng.run()
    assert len(finished) == 3
    assert all(len(r.output_tokens) > 0 for r in finished)
    assert eng.peak_blocks_in_use == blocks_per_req  # never over-admitted
    assert eng.blocks_in_use == 0


def test_pool_too_small_for_one_request_rejected(small_model):
    cfg, params = small_model
    # the error is actionable: it names the flag and the computed minimum
    with pytest.raises(ValueError, match=r"--kv-num-blocks.*>= 5"):
        ServingEngine(cfg, params, max_batch=2, max_len=64,
                      cache_layout="paged", kv_block_size=16,
                      kv_num_blocks=2)


def test_batched_admission_single_prefill_per_bucket(small_model):
    """Requests sharing a prompt bucket are prefilled in one batched call."""
    cfg, params = small_model
    eng = ServingEngine(cfg, params, max_batch=4, max_len=64, prompt_bucket=8)
    shapes = []
    orig = eng._prefill
    eng._prefill = lambda p, b: (shapes.append(tuple(b["tokens"].shape)),
                                 orig(p, b))[1]
    rng = np.random.default_rng(4)
    for _ in range(3):
        eng.submit(rng.integers(0, cfg.vocab_size, 6),
                   SamplingParams(max_new_tokens=3))
    eng.step()
    assert shapes == [(3, 8)]  # one prefill, batch=3, bucketed plen=8
    finished = eng.run()
    assert len(finished) == 3


def test_request_params_default_not_shared():
    """dataclass default_factory: each Request gets its own SamplingParams."""
    a = Request(uid=0, prompt=np.zeros(1, np.int32))
    b = Request(uid=1, prompt=np.zeros(1, np.int32))
    assert a.params is not b.params
    assert dataclasses.fields(Request)[2].default is dataclasses.MISSING


def test_paged_cache_size_reporting():
    """core.cache classifies pool leaves as kv and the paged analytic
    undercuts the contiguous worst case for short-heavy lengths."""
    from repro.core.cache import analytic_kv_bytes, paged_kv_bytes, profile_cache

    cfg = get_config("tinyllama-1.1b", smoke=True)
    rep = profile_cache(cfg, 4, 128, layout="paged", block_size=16)
    assert rep.kv_bytes > 0
    # worst-case pool ~= contiguous worst case (+1 garbage block per layer)
    contig = profile_cache(cfg, 4, 128)
    assert rep.kv_bytes >= contig.kv_bytes
    lengths = [24, 16, 40, 8]
    paged = paged_kv_bytes(cfg, lengths, 16)
    worst = analytic_kv_bytes(cfg, len(lengths), 128)
    assert paged * 2 <= worst
