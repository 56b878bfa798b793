"""Partitioning helpers: params/opt-state PartitionSpecs from the logical
axes tree, batch sharding for inputs, and jit wrappers with shardings.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.sharding.rules import logical_to_pspec, tree_pspecs


def _is_axes_leaf(l) -> bool:
    return isinstance(l, tuple) and all(isinstance(a, (str, type(None))) for a in l)


def param_pspecs(axes_tree, shape_tree, mesh: Mesh, rules=None):
    """PartitionSpec tree for params (divisibility-checked against shapes)."""
    shapes = jax.tree.map(lambda s: tuple(s.shape), shape_tree)
    return jax.tree.map(
        lambda ax, shp: logical_to_pspec(ax, dims=shp, mesh=mesh, rules=rules),
        axes_tree, shapes, is_leaf=_is_axes_leaf,
    )


def param_shardings(axes_tree, shape_tree, mesh: Mesh, rules=None):
    specs = param_pspecs(axes_tree, shape_tree, mesh, rules)
    return jax.tree.map(
        lambda ps: NamedSharding(mesh, ps),
        specs, is_leaf=lambda l: isinstance(l, PartitionSpec),
    )


def opt_state_shardings(param_sh, opt_state_shapes, mesh: Mesh):
    """Moments shard like their params; scalars replicate."""
    replicated = NamedSharding(mesh, PartitionSpec())

    def match(path_shape):
        return path_shape

    # OptState(mu, nu, count): mirror params for mu/nu.
    return type(opt_state_shapes)(
        mu=param_sh, nu=param_sh,
        count=replicated,
    )


def batch_pspec(mesh: Mesh, extra_dims: int = 1) -> PartitionSpec:
    """Inputs: batch on (pod, data), everything else replicated."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return PartitionSpec(axes if len(axes) > 1 else (axes[0] if axes else None),
                         *([None] * extra_dims))


def batch_shardings(batch_shapes, mesh: Mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in axes:
        dp *= mesh.shape[a]
    data_axes = axes if len(axes) > 1 else (axes[0] if axes else None)

    def leaf(s):
        nd = len(s.shape)
        if nd == 0 or dp <= 1 or s.shape[0] % dp != 0:
            return NamedSharding(mesh, PartitionSpec(*([None] * nd)))
        return NamedSharding(mesh, PartitionSpec(data_axes, *([None] * (nd - 1))))

    return jax.tree.map(leaf, batch_shapes)


def cache_shardings(cache_shapes, mesh: Mesh, num_kv_heads: int = 0):
    """KV-cache: batch dim on (pod, data).

    Leaves under ``groups`` are scan-stacked — batch sits at axis 1; under
    ``rest`` it is axis 0.  Uneven batch dims fall back to replication.

    On a tensor-parallel serving mesh (axis ``tp``) KV heads shard over the
    tp axis — including the paged pool leaves ``kp``/``vp``, (layers, N,
    bs, Hkv*D), whose lanes split by whole kv heads when ``num_kv_heads``
    divides the axis and which stay whole otherwise (the decode kernel's
    per-device view, ``kernels.dispatch._per_device``).  Their *block*
    axis is never sharded (block tables are host-managed and index every
    device's pool identically; each device holds its head-shard of every
    block).  On the training mesh, heads dims stay replicated across
    ``model`` by default — the serve-path hillclimb (EXPERIMENTS §Perf)
    revisits this.
    """
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    data_axes = axes if len(axes) > 1 else (axes[0] if axes else None)
    dp = 1
    for a in axes:
        dp *= mesh.shape[a]

    mp_name = "model" if "model" in mesh.axis_names else "tp"
    model_size = mesh.shape.get(mp_name, 1)
    has_model = model_size > 1

    def leaf(path, s):
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        b_axis = 1 if (keys and keys[0] == "groups") else 0
        name = keys[-1]
        nd = len(s.shape)
        spec: list = [None] * nd
        if name in ("kp", "vp"):
            if has_model and num_kv_heads and num_kv_heads % model_size == 0:
                spec[nd - 1] = mp_name
            return NamedSharding(mesh, PartitionSpec(*spec))
        if dp > 1 and nd > b_axis and s.shape[b_axis] % dp == 0:
            spec[b_axis] = data_axes

        def try_model(*idxs):
            """First dim (in preference order) divisible by the model axis."""
            for i in idxs:
                if 0 <= i < nd and spec[i] is None and \
                        s.shape[i] % model_size == 0 and s.shape[i] >= model_size:
                    spec[i] = mp_name
                    return

        # model-parallel dim: kv heads when they divide, else the KV length
        # (sequence-parallel cache — flash-decoding-style partial softmax);
        # recurrent heads, else the state feature dim
        if has_model:
            if name in ("k", "v", "cross_k", "cross_v") and nd >= b_axis + 4:
                try_model(nd - 2, b_axis + 1)      # H, else L
            elif name == "pos" and nd == b_axis + 2:
                pass                               # must mirror k/v L-sharding? kept replicated
            elif name in ("C", "n", "m", "c", "h"):
                if name == "h" and nd == b_axis + 2:
                    try_model(nd - 1)              # rglru h: (..., B, W)
                elif nd >= b_axis + 2:
                    try_model(b_axis + 1, b_axis + 2)  # H, else Dk/Dh
            elif name == "conv" and nd >= b_axis + 3:
                try_model(nd - 1)                  # (..., B, K-1, W)
        return NamedSharding(mesh, PartitionSpec(*spec))

    return jax.tree_util.tree_map_with_path(leaf, cache_shapes)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, PartitionSpec())
