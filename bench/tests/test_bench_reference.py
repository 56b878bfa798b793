"""The qwen2 family's float32 reference against the program's model, in
float32 at each configuration's smoke size, on the CPU."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import arch, harness, reference, testing  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import model as model_lib  # noqa: E402

qwen2 = arch.family("qwen2")


def smoke_config(name):
    """The benchmark's configuration file, on the program's registry entry
    ``name`` (MHA qwen1.5-0.5b, GQA qwen2.5-1.5b) at its smoke sizes."""
    s = get_config(name, smoke=True)
    return testing.config(
        "qwen1.5-0.5b", arch=name, hidden_size=s.d_model, intermediate_size=s.d_ff,
        num_attention_heads=s.num_heads, num_key_value_heads=s.num_kv_heads,
        num_hidden_layers=s.num_layers, vocab_size=s.vocab_size,
        torch_dtype="float32")


@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "qwen2.5-1.5b"])
def test_reference_matches_the_program_in_float32(name):
    c = smoke_config(name)
    cfg = qwen2.program_config(c)
    params = qwen2.weights(c, 7, "float32")
    harness._check_tree(cfg, params)
    ids = np.random.default_rng(0).integers(0, c["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        want = model_lib.forward_train(
            cfg, params, {"tokens": jnp.asarray(ids[None], jnp.int32)},
            remat=False)[0]
        a = qwen2.Arch.from_config(c)
        x = qwen2.hidden(params, a, np.pad(ids, (0, 24)), "none")[:40]
        got = reference._head(x, params["decoder"]["final_norm"]["scale"],
                              params["embed"]["table"], a.eps, "none")
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4 * scale)


def test_served_gap_is_zero_for_the_references_own_greedy_tokens():
    c = smoke_config("qwen2.5-1.5b")
    params = qwen2.weights(c, 3, "float32")
    a = qwen2.Arch.from_config(c)
    prompt = list(np.random.default_rng(1).integers(0, 256, 12))
    served = []
    for _ in range(6):  # greedy decode by re-running the whole forward
        ids = np.asarray(prompt + served, np.int32)
        x = qwen2.hidden(params, a, np.pad(ids, (0, 64 - len(ids))), "none")
        logits = reference._head(
            x, params["decoder"]["final_norm"]["scale"],
            params["embed"]["table"], a.eps, "none")
        served.append(int(jnp.argmax(logits[len(prompt) + len(served) - 1])))
    gaps = qwen2.served_gaps(params, c, prompt, served, 64)
    assert gaps.shape == (6,) and float(gaps.max()) == 0.0
    wrong = [(t + 1) % 256 for t in served]
    assert float(qwen2.served_gaps(params, c, prompt, wrong, 64).max()) > 0.0
