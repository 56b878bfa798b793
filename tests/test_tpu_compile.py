"""Compile-only checks of the main-path attention kernels for a TPU v5e.

The TPU compiler is installed without a chip; it compiles for a described
``v5e:2x2`` topology and refuses what the chip would refuse (block shapes
off the (8, 128) tiling, too much VMEM), which interpret mode cannot see.
Shapes are ``qwen1.5-0.5b``'s in bf16 at the engine's serving sizes.
Nothing runs: these tests say a kernel compiles, not that it is right or
fast (``test_kernels.py`` checks results in interpret mode).

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and under
several test workers only the worker given this file may load it.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hardware import TPU_V5E, hardware_for_device
from repro.kernels.decode_attention import ops as decode_ops
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.rmsnorm import ops as rmsnorm_ops

B, H, D = 8, 16, 64          # qwen1.5-0.5b: 16 MHA heads of 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_hlo(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _named(hlo, kernel):
    """The kernel's op carries its ``pallas_call`` name, which the
    benchmark's trace reduction finds it by."""
    return re.search(rf"%{kernel}(\.\d+)? = ", hlo) is not None


def _flash(q, k, v, qp, kp):
    return flash_ops.flash_attention(q, k, v, q_positions=qp, k_positions=kp,
                                     causal=True)


BF, I32 = jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("S,T", [
    (128, 128),           # a 128-token prompt prefill
    (32, 1024 + 32),      # a packed chunk row: cache plus chunk keys
    (255, 256 + 255),     # the unified step's frontier at --max-len 256
])
def test_flash_attention_compiles_for_v5e(one_chip, S, T):
    hlo = _compiled_hlo(_flash, one_chip, ((B, S, H, D), BF),
                        ((B, T, H, D), BF), ((B, T, H, D), BF),
                        ((B, S), I32), ((B, T), I32))
    assert "tpu_custom_call" in hlo
    assert _named(hlo, "flash_attention")


def test_decode_attention_compiles_for_v5e(one_chip):
    L = 1024              # contiguous cache at --max-len 1024
    hlo = _compiled_hlo(
        lambda q, k, v, qp, kp: decode_ops.decode_attention(
            q, k, v, q_positions=qp, k_positions=kp),
        one_chip, ((B, 1, H, D), BF), ((B, L, H, D), BF),
        ((B, L, H, D), BF), ((B, 1), I32), ((B, L), I32))
    assert "tpu_custom_call" in hlo
    assert _named(hlo, "decode_attention")


def test_paged_decode_attention_compiles_for_v5e(one_chip):
    bs, nb = 16, 64       # 16-token blocks, 1024 tokens per row
    N = B * nb + 1        # worst-case pool plus the garbage block
    layers = 24           # the pools of every layer, stacked and lane-dense
    hlo = _compiled_hlo(
        lambda q, k, v, bt, qp, lyr: decode_ops.paged_decode_attention(
            q, k, v, block_tables=bt, q_positions=qp, layer=lyr),
        one_chip, ((B, 1, H, D), BF), ((layers, N, bs, H * D), BF),
        ((layers, N, bs, H * D), BF), ((B, nb), I32), ((B, 1), I32),
        ((), I32))
    assert "tpu_custom_call" in hlo
    assert _named(hlo, "paged_decode_attention")


def _copies_at_least(hlo, elements):
    """The ``copy`` ops whose output holds ``elements`` or more."""
    out = []
    for m in re.finditer(r"%(copy[\w.]*) = \w+\[([\d,]*)\]", hlo):
        n = 1
        for d in m.group(2).split(","):
            n *= int(d) if d else 1
        if n >= elements:
            out.append(m.group(0))
    return out


@pytest.mark.parametrize("which", ["decode", "unified"])
def test_fused_step_keeps_the_pool_in_place_for_v5e(one_chip, monkeypatch,
                                                   which):
    """The paged fused steps read and write the KV pool in place: no
    ``copy`` in the compiled program is as large as one layer's pool.

    ``qwen1.5-0.5b`` widths cut to 2 layers, 4 slots of 256 tokens, a
    worst-case pool of 65 blocks of 16, chunk width 64, Pallas kernels,
    state and cache donated as the engine donates them.  Before the pool
    was stored lane-dense and carried through the layer loop, the same
    program at the benchmark cell's sizes (24 layers, 16 slots, 2048
    blocks, chunk width 256) compiled 8 such copies per layer in the
    unified step and 8 per layer in the decode step (4 each of K and V:
    the layer's slice out of the stack, a scatter relayout, the kernel's
    lane reshape, the update back into the stack), plus 2 whole-pool
    copies after the decode step's loop; at this test's sizes 4 and 6.
    """
    from repro.configs import get_config
    from repro.kernels import dispatch
    from repro.models import model as model_lib
    from repro.serving import step as step_lib

    # the kernels compile for the described chip, not for the interpreter
    monkeypatch.setattr(dispatch, "_interpret", lambda: False)
    cfg = get_config("qwen1.5-0.5b").replace(num_layers=2)
    slots, max_len, bs, blocks, width = 4, 256, 16, 65, 64
    params, _ = model_lib.param_axes(cfg)
    cache = jax.eval_shape(lambda: model_lib.init_cache(
        cfg, slots, max_len, jnp.dtype(cfg.dtype), layout="paged",
        block_size=bs, num_blocks=blocks))
    state = jax.eval_shape(lambda: step_lib.init_slot_state(
        slots, max_blocks=max_len // bs))
    chunk = {"tokens": jax.ShapeDtypeStruct((slots, width), I32),
             "start": jax.ShapeDtypeStruct((slots,), I32),
             "length": jax.ShapeDtypeStruct((slots,), I32)}
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    if which == "decode":
        fn = step_lib.make_decode_sample_step(cfg, max_len)
        args, donate = (params, state, cache), (1, 2)
    else:
        fn = step_lib.make_engine_step(cfg, max_len)
        args, donate = (params, state, chunk, cache), (1, 3)
    with dispatch.use_backend("pallas"):
        hlo = jax.jit(fn, donate_argnums=donate).lower(
            *map(on_chip, args)).compile().as_text()
    assert _named(hlo, "paged_decode_attention")
    one_layer = blocks * bs * cfg.num_kv_heads * cfg.resolved_head_dim
    assert _copies_at_least(hlo, one_layer) == []


def test_rmsnorm_carries_its_name(one_chip):
    hlo = _compiled_hlo(lambda x, s: rmsnorm_ops.rmsnorm(x, s), one_chip,
                        ((B, 256, 1024), BF), ((1024,), BF))
    assert _named(hlo, "rmsnorm")


def test_described_v5e_has_a_hardware_spec(topo):
    assert hardware_for_device(topo.devices[0]) is TPU_V5E
