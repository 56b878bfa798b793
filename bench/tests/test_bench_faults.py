"""A whole run of the harness on the CPU, past its look for a chip, at a
tiny size: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false.

The faults a served cell on one chip can have: a token altered where it
is produced (the fused step's sampled token), and a step that returns its
state unchanged (the decode step emits its token but hands back the slot
state and the KV cache it was given, so no request advances).  The
exchange between chips does not exist on one chip, and serving takes no
mean over a batch."""

import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, testing  # noqa: E402
from repro.serving import step as step_lib  # noqa: E402


def _run(monkeypatch, seed=2 ** 35 + 11):
    monkeypatch.setattr(harness, "DRAIN_S", 1.0)
    c = testing.cell(testing.config("qwen1.5-0.5b", **testing.SMOKE))
    r = harness.measure(c, seed, 2.0, False,
                        started=time.perf_counter(), require_tpu=False)
    r.pop("detail")
    return r


def _altered_token(orig):
    def body(cfg, max_len, k_max, params, state, cache):
        new_state, new_cache, out = orig(cfg, max_len, k_max, params, state,
                                         cache)
        tok = jnp.where(out[2] > 0, (out[0] + 1) % cfg.vocab_size, out[0])
        return dict(new_state, tokens=tok[:, None]), new_cache, out.at[0].set(
            tok)
    return body


def _state_unchanged(orig):
    def body(cfg, max_len, k_max, params, state, cache):
        _, _, out = orig(cfg, max_len, k_max, params, state, cache)
        return state, cache, out
    return body


@pytest.mark.parametrize("seed", [2 ** 35 + 11, 5])
def test_sound_run_is_correct(monkeypatch, seed):
    r = _run(monkeypatch, seed)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms",
                                 "output_tokens_per_s", "setup_s"}
    assert list(r)[-1] == "checks"
    assert r["checks"]["max_logit_gap"]["value"] <= 1e-3
    assert set(r["schedule"]) == {"late_sends", "late_max_ms", "limit_ms"}


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged])
def test_broken_step_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(step_lib, "_decode_sample_body",
                        fault(step_lib._decode_sample_body))
    r = _run(monkeypatch)
    assert r["correct"] is False
    assert any(v["value"] is None or v["value"] > v["limit"]
               for v in r["checks"].values())


def test_no_chip_is_refused():
    c = testing.cell(testing.config("qwen1.5-0.5b", **testing.SMOKE))
    with pytest.raises(harness.NoChip):
        harness.measure(c, 1, 1.0, False, started=time.perf_counter())
