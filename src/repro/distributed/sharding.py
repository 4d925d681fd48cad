"""Divisibility-aware sharding rules (DESIGN.md §4).

Two mechanisms:

* **Activation constraints** — :func:`constrain` annotates intermediate
  values with logical axes ("batch", "model", "expert", ...) resolved
  against the mesh *currently in context*. Resolution is
  divisibility-aware: a logical axis whose dimension does not divide the
  mesh axis silently falls back to replication (e.g. smollm's 9 heads on
  a 16-way model axis). Outside a mesh context it is a no-op, so the
  same model code runs single-device smoke tests and 512-device
  dry-runs.

* **Parameter shardings** — :func:`param_shardings` maps a params pytree
  (by path) to NamedShardings using the same logical rules, for
  jit in_shardings. Stacked-layer params ([L, ...]) keep dim 0
  unsharded.

Logical axis -> mesh axes:
    batch   -> ("pod", "data")   (whichever exist in the mesh)
    data    -> ("data",)
    model   -> ("model",)        tensor-parallel dimension
    expert  -> ("model",)        MoE expert parallelism
    zero    -> ("data",)         optimizer-state / ZeRO-1 sharding
"""

from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LOGICAL_RULES = {
    "batch": ("pod", "data"),
    "data": ("data",),
    "model": ("model",),
    "expert": ("model",),
    "residual": ("model",),   # d dim of the between-layer carry
    "zero": ("data",),
    None: (),
}

# pure-data-parallel rule set: small models spread the batch over the
# model axis too and keep every tensor dimension unsharded (the
# EXPERIMENTS.md §Perf 'dp-all' layout)
DP_ALL_RULES = {
    "batch": ("pod", "data", "model"),
    "data": ("data",),
    "model": (),
    "expert": (),
    "residual": (),
    "zero": ("data",),
    None: (),
}

# Megatron-style: residual stream replicated on d between layers; the
# block-internal heads/d_ff stay model-sharded, so each block costs one
# row-parallel all-reduce instead of a resharding cycle (§Perf)
MEGATRON_RULES = dict(LOGICAL_RULES, residual=())

_RULE_SETS = {"default": LOGICAL_RULES, "dp-all": DP_ALL_RULES,
              "megatron": MEGATRON_RULES}
_active_rules = LOGICAL_RULES


def set_logical_mode(mode: str) -> None:
    global _active_rules
    _active_rules = _RULE_SETS[mode]


class logical_mode:
    """Context manager: swap the activation-constraint rule set while
    tracing/lowering a variant layout."""

    def __init__(self, mode: str):
        self.mode = mode

    def __enter__(self):
        self.prev = _active_rules
        set_logical_mode(self.mode)

    def __exit__(self, *exc):
        global _active_rules
        _active_rules = self.prev


def _current_mesh() -> Optional[Mesh]:
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            # fall back to the physical mesh context manager
            from jax.interpreters import pxla
            env_mesh = pxla.thread_resources.env.physical_mesh
            return None if env_mesh.empty else env_mesh
        return mesh
    except Exception:
        return None


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis Auto. The model code annotates shardings
    and lets XLA propagate them; on a mesh with Explicit axes (what
    ``jax.make_mesh`` builds by default on current JAX) shardings live
    in the types instead, and the embedding gather fails with a
    duplicate-axis spec."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names))


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    dims: Sequence[int],
    mesh,
) -> P:
    """Resolve logical axes to a PartitionSpec, dropping any mesh axis
    that does not divide the corresponding dimension."""
    axis_sizes = dict(mesh.shape)
    spec = []
    used = set()
    for logical, dim in zip(logical_axes, dims):
        mesh_axes = _active_rules.get(logical, ())
        chosen = []
        total = 1
        for ax in mesh_axes:
            if ax not in axis_sizes or ax in used:
                continue
            size = axis_sizes[ax]
            if dim % (total * size) == 0:
                chosen.append(ax)
                total *= size
        for ax in chosen:
            used.add(ax)
        if not chosen:
            spec.append(None)
        elif len(chosen) == 1:
            spec.append(chosen[0])
        else:
            spec.append(tuple(chosen))
    return P(*spec)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint against the ambient mesh (no-op without)."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(f"{len(logical_axes)} axes for rank-{x.ndim} value")
    spec = logical_to_spec(logical_axes, x.shape, mesh)
    try:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except ValueError:
        # abstract mesh from context: constraint via spec directly
        return jax.lax.with_sharding_constraint(x, spec)


def batch_spec(mesh) -> P:
    """Input-batch sharding: batch dim over (pod, data)."""
    axes = [a for a in ("pod", "data") if a in dict(mesh.shape)]
    return P(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))


# ---------------------------------------------------------------------------
# Parameter sharding rules, by path regex. First match wins.
# Conventions: stacked layer params have a leading L dim (rule specs are
# for the *trailing* dims; leading dims padded with None).
# ---------------------------------------------------------------------------

# (pattern, logical axes for trailing dims)
PARAM_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    # embeddings / unembedding: vocab on model
    (r"(embed|lm_head)/table$", ("model", None)),
    # attention projections: wq [d, H, hd] heads on model; wkv [d, Hk, hd]
    # kv-heads on model if divisible else head_dim on model (rule resolution
    # handles the fallback by trying 'model' on the hd axis).
    (r"attn/wq$", (None, "model", None)),
    (r"attn/wk$", (None, "model", "model_fallback")),
    (r"attn/wv$", (None, "model", "model_fallback")),
    (r"attn/wo$", ("model", None, None)),
    # dense MLP: d_ff on model
    (r"mlp/w(1|3)$", (None, "model")),
    (r"mlp/w2$", ("model", None)),
    # MoE: experts on model when divisible (expert parallelism); router repl.
    (r"moe/w(1|3)$", ("expert", None, "model_fallback")),
    (r"moe/w2$", ("expert", "model_fallback", None)),
    (r"moe/router$", (None, None)),
    # Mamba2 split projections: the d_inner-sized z/x columns on model
    # (head-aligned); the small B/C/dt projections stay replicated
    (r"ssm/w_(z|x)$", (None, "model")),
    (r"ssm/out_proj$", ("model", None)),
    # norms / scalars / conv / everything else: replicated
)


def _rule_for(path: str):
    for pat, axes in PARAM_RULES:
        if re.search(pat, path):
            return axes
    return None


def _spec_for_param(path: str, shape: Tuple[int, ...], mesh) -> P:
    axes = _rule_for(path)
    if axes is None:
        return P()
    # pad leading dims (layer stacking) with None
    n_trail = len(axes)
    if len(shape) < n_trail:
        # rule longer than rank (unstacked edge case): trim from the left
        axes = axes[len(axes) - len(shape):]
        n_trail = len(axes)
    full = [None] * (len(shape) - n_trail) + list(axes)

    axis_sizes = dict(mesh.shape)
    model_size = axis_sizes.get("model", 1)
    resolved = []
    used = set()
    for logical, dim in zip(full, shape):
        if logical == "model_fallback":
            # only shard if the *primary* model-axis slot upstream failed
            # and this dim divides
            if "model" not in used and dim % model_size == 0 and "model" in axis_sizes:
                resolved.append("model")
                used.add("model")
            else:
                resolved.append(None)
        elif logical in ("model", "expert"):
            if "model" not in used and "model" in axis_sizes and dim % model_size == 0:
                resolved.append("model")
                used.add("model")
            else:
                resolved.append(None)
        else:
            resolved.append(None)
    return P(*resolved)


def param_shardings(params, mesh, *, zero_axis: Optional[str] = None):
    """NamedShardings for a params pytree.

    ``zero_axis``: additionally shard the *largest* divisible dim of each
    param over the data axis (ZeRO-3-style fully-sharded params) — used
    for the huge MoE configs where replicated-over-data params would not
    fit HBM."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def path_str(kp):
        return "/".join(
            getattr(k, "key", getattr(k, "idx", None)).__str__() for k in kp
        )

    out = {}
    for kp, leaf in flat:
        path = path_str(kp)
        spec = _spec_for_param(path, leaf.shape, mesh)
        if zero_axis is not None and zero_axis in dict(mesh.shape):
            spec = _add_zero_axis(spec, leaf.shape, mesh, zero_axis)
        out[path] = NamedSharding(mesh, spec)

    def map_fn(kp, leaf):
        return out[path_str(kp)]

    return jax.tree_util.tree_map_with_path(map_fn, params), out


def _add_zero_axis(spec: P, shape: Tuple[int, ...], mesh, zero_axis: str) -> P:
    """Add the data axis onto the largest still-unsharded divisible dim."""
    axis_sizes = dict(mesh.shape)
    zsize = axis_sizes[zero_axis]
    parts = list(spec) + [None] * (len(shape) - len(spec))
    best, best_dim = None, 0
    for i, (p, dim) in enumerate(zip(parts, shape)):
        if p is None and dim % zsize == 0 and dim > best_dim:
            best, best_dim = i, dim
        elif p is not None and not isinstance(p, tuple):
            # existing sharding: can we append zero axis on the same dim?
            shard = dim // axis_sizes.get(p, 1)
            if shard % zsize == 0 and dim > best_dim:
                pass  # prefer a clean dim first; handled only if none found
    if best is not None:
        parts[best] = zero_axis
        return P(*parts)
    # fall back: stack onto an already-sharded dim if divisible
    for i, (p, dim) in enumerate(zip(parts, shape)):
        if p is not None and not isinstance(p, tuple):
            shard = dim // axis_sizes.get(p, 1)
            if shard % zsize == 0:
                parts[i] = (p, zero_axis)
                return P(*parts)
    return P(*parts)
