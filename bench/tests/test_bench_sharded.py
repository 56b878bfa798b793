"""A cell on four chips, through the whole harness, on a forced four-device
CPU host: it serves the same tokens as the same cell on one chip, and its
check reads ``correct: true``.

The four-chip cell builds a ``(4,)`` mesh with the axis ``tp``, makes its
weights sharded over it, serves through the engine's tensor-parallel path
and runs the reference after the engine is freed, gathering one layer at a
time onto one device.  JAX fixes its device count when it starts, so both
runs go in a child process whose ``XLA_FLAGS`` force four CPU devices,
as a ``sharded`` job would set them."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

pytestmark = pytest.mark.sharded

SEED = 2 ** 33 + 5

SCRIPT = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    from bench import harness, testing

    assert len(jax.devices()) >= 4, jax.devices()
    harness.DRAIN_S = 1.0
    served, spans = {{}}, {{}}
    offer = harness.offer

    def keep(s, c, *args, **kw):
        plan, records, *rest = offer(s, c, *args, **kw)
        served[c.chips] = {{r["idx"]: r["tokens"] for r in records
                            if r["done"]}}
        spans[c.chips] = max(len(a.sharding.device_set)
                             for a in jax.tree.leaves(s.engine.params))
        return (plan, records, *rest)

    harness.offer = keep
    # qwen1.5-0.5b's MHA (as many KV heads as query heads) at smoke widths,
    # so that every head divides over four chips
    cfg = testing.config("qwen1.5-0.5b", **dict(testing.SMOKE,
                                                 num_key_value_heads=4))
    out = {{}}
    for chips in (1, 4):
        r = harness.measure(testing.cell(cfg, chips=chips), {seed}, 2.0,
                            False, started=time.perf_counter(),
                            require_tpu=False)
        out[chips] = {{"correct": r["correct"], "checks": r["checks"],
                       "count": r["device"]["count"],
                       "weights_span": spans[chips],
                       "served": served[chips]}}
    print(json.dumps(out))
""")


def test_four_chip_cell_serves_the_one_chip_tokens_and_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"), seed=SEED)
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    one, four = out["1"], out["4"]
    assert four["count"] == 4
    # the weights lie on one device, or across the four of the mesh
    assert (one["weights_span"], four["weights_span"]) == (1, 4)
    assert one["correct"] is True, one["checks"]
    assert four["correct"] is True, four["checks"]
    both = sorted(set(one["served"]) & set(four["served"]))
    assert len(both) >= 10
    assert [one["served"][i] for i in both] == [four["served"][i]
                                                for i in both]
