"""Sharding rules + partition helpers.  Multi-device behavior runs in a
subprocess with forced host device count (the main pytest process must keep
seeing 1 device per the assignment)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec

from repro.sharding import rules

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.parametrize("build", [
    lambda m: m.make_host_mesh(),
    lambda m: m.make_tp_mesh(1),
    lambda m: m.make_mesh((1, 1), ("data", "model")),
])
def test_meshes_have_only_auto_axes(build):
    """``with_sharding_constraint`` (``rules.shard``) only accepts Auto mesh
    axes; ``jax.make_mesh`` defaults to Explicit ones."""
    from jax.sharding import AxisType
    from repro.launch import mesh as mesh_lib

    assert set(build(mesh_lib).axis_types) == {AxisType.Auto}


@pytest.mark.parametrize("multi_pod", [False, True])
def test_production_mesh_asks_for_auto_axes(monkeypatch, multi_pod):
    """The 256/512-chip production meshes cannot be built here; check the
    axis types they ask ``jax.make_mesh`` for."""
    from jax.sharding import AxisType
    from repro.launch import mesh as mesh_lib

    asked = []
    monkeypatch.setattr(mesh_lib.jax, "make_mesh",
                        lambda shape, axes, **kw: asked.append((axes, kw)))
    mesh_lib.make_production_mesh(multi_pod=multi_pod)
    (axes, kw), = asked
    assert kw["axis_types"] == (AxisType.Auto,) * len(axes)


def test_logical_to_pspec_no_mesh_is_empty():
    assert rules.logical_to_pspec(("embed", "ffn")) == PartitionSpec()


def test_dryrun_bookkeeping_logic():
    from repro.configs import SHAPES, get_config
    from repro.launch.dryrun import input_specs, should_skip

    # long_500k skips full-attention archs, runs ssm/hybrid
    assert should_skip(get_config("llama3.1-8b"), SHAPES["long_500k"])
    assert should_skip(get_config("qwen3-moe-30b-a3b"), SHAPES["long_500k"])
    assert should_skip(get_config("xlstm-1.3b"), SHAPES["long_500k"]) is None
    assert should_skip(get_config("recurrentgemma-2b"), SHAPES["long_500k"]) is None
    # every non-skip cell produces well-formed specs
    for arch in ("minitron-4b", "llava-next-34b", "seamless-m4t-large-v2"):
        cfg = get_config(arch)
        b = input_specs(cfg, SHAPES["train_4k"])
        assert b["tokens"].shape[0] == 256
        total = b["tokens"].shape[1] + (
            cfg.num_vision_tokens or (b["tokens"].shape[1] if cfg.is_encdec else 0))
        assert total == 4096
        d = input_specs(cfg, SHAPES["decode_32k"])
        assert d["token"].shape == (128, 1)
        assert d["positions"].shape == (128,)


_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import model as model_lib
    from repro.sharding import partition, rules
    from repro.training import step as step_lib, checkpoint as ckpt_lib
    from repro.training.optimizer import AdamW, constant_schedule

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_config("tinyllama-1.1b", smoke=True).replace(
        d_model=64, num_heads=8, num_kv_heads=4, d_ff=128)
    out = {}

    with rules.use_mesh(mesh):
        shapes, axes = model_lib.param_axes(cfg)
        sh = partition.param_shardings(axes, shapes, mesh)
        # ffn weights shard on model, embed dim on data
        wg = sh["decoder"]["groups"]["0"]["mlp"]["wg"]
        out["wg_spec"] = str(wg.spec)
        emb = sh["embed"]["table"]
        out["emb_spec"] = str(emb.spec)

        # compile + run one sharded train step on the 2x4 mesh
        opt = AdamW(schedule=constant_schedule(1e-3))
        state, _ = step_lib.init_state(cfg, opt, jax.random.PRNGKey(0))
        step = jax.jit(step_lib.make_train_step(cfg, opt, remat=False))
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16))),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16))),
        }
        state, metrics = step(state, batch)
        out["loss"] = float(metrics["loss"])

        # elastic restore: save under 2x4, restore under 8x1
        import tempfile
        d = tempfile.mkdtemp()
        ckpt_lib.save(d, 1, {"params": state.params})

    mesh2 = make_mesh((8,), ("data",))
    with rules.use_mesh(mesh2):
        shapes2, axes2 = model_lib.param_axes(cfg)
        sh2 = partition.param_shardings(axes2, shapes2, mesh2)
        restored, _ = ckpt_lib.restore(d, {"params": shapes2},
                                       shardings={"params": sh2})
        diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree.leaves(restored["params"]),
            jax.tree.leaves(state.params)))
        out["elastic_restore_diff"] = diff
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_sharded_train_and_elastic_restore_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                          capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "model" in out["wg_spec"]
    assert out["elastic_restore_diff"] == 0.0
    assert out["loss"] > 0
