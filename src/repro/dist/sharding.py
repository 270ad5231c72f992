"""Sharding-rule resolution and in-model sharding constraints.

Rules are (path-substring, logical-axes) pairs resolved against a mesh:

  * axis names absent from the mesh resolve to ``None`` (the same rule
    set drives a 1-device CPU run and the 512-chip production mesh);
  * a dimension whose size does not divide the mesh axis resolves to
    ``None`` (divisibility guard — reduced test models never trip the
    compiler);
  * rules are written for the weight's own dims; layer-stacked arrays
    (scan-over-layers layouts) are LEFT-padded with ``None``.

The ``constrain*`` helpers used inside model code are no-ops unless a
``mesh_context`` is active, so the same model code runs un-jitted on one
device and sharded under pjit.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "tree_paths", "ShardingRules", "lm_rules", "mesh_context",
    "residual_sharding", "constrain", "constrain_residual",
    "constrain_attn_qkv", "batch_spec", "cache_spec", "zero1_spec",
]

Axis = Union[None, str, Tuple[str, ...]]

# stacks, innermost last (plain lists: jit traces run single-threaded)
_MESH_STACK: List[Mesh] = []
_RESIDUAL_STACK: List[Tuple[Axis, ...]] = [("data", None, None)]


def tree_paths(tree: Any) -> Any:
    """Same-structure tree whose leaves are 'a/b/0'-style path strings."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = [jax.tree_util.keystr(kp, simple=True, separator="/")
             for kp, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, paths)


def _axis_names(ax: Axis) -> Tuple[str, ...]:
    if ax is None:
        return ()
    if isinstance(ax, tuple):
        return ax
    return (ax,)


def _resolve(axes: Sequence[Axis], mesh: Mesh,
             shape: Optional[Sequence[int]] = None) -> P:
    """Resolve logical axes to a PartitionSpec valid on ``mesh``."""
    out: List[Axis] = []
    for i, ax in enumerate(axes):
        names = tuple(n for n in _axis_names(ax) if n in mesh.shape)
        if not names:
            out.append(None)
            continue
        size = math.prod(mesh.shape[n] for n in names)
        if shape is not None and i < len(shape) and shape[i] % size != 0:
            out.append(None)
            continue
        out.append(names if len(names) > 1 else names[0])
    return P(*out)


def _fit(axes: Sequence[Axis], ndim: int) -> Tuple[Axis, ...]:
    """Left-pad (layer-stacked arrays) or left-trim rule axes to ndim."""
    axes = tuple(axes)
    if len(axes) < ndim:
        return (None,) * (ndim - len(axes)) + axes
    if len(axes) > ndim:
        return axes[len(axes) - ndim:]
    return axes


@dataclass(frozen=True)
class ShardingRules:
    """Ordered (path-substring, axes) rules; first match wins."""

    rules: Tuple[Tuple[str, Tuple[Axis, ...]], ...]

    def axes_for(self, path: str, ndim: int) -> Tuple[Axis, ...]:
        for pattern, axes in self.rules:
            if pattern in path:
                return _fit(axes, ndim)
        return (None,) * ndim

    def spec(self, path: str, ndim: int, mesh: Mesh,
             shape: Optional[Sequence[int]] = None) -> NamedSharding:
        return NamedSharding(mesh,
                             _resolve(self.axes_for(path, ndim), mesh, shape))

    def tree(self, params: Any, mesh: Mesh) -> Any:
        paths = tree_paths(params)
        return jax.tree.map(
            lambda leaf, path: self.spec(path, len(leaf.shape), mesh,
                                         tuple(leaf.shape)),
            params, paths)


def lm_rules(family: str, *, two_d_experts: bool = False) -> ShardingRules:
    """Megatron-style tensor-parallel rules for the model zoo.

    Experts shard on 'model'; ``two_d_experts`` additionally shards the
    expert FFN dim on 'data' (2D expert sharding for >200B MoE).
    """
    rules: List[Tuple[str, Tuple[Axis, ...]]] = [
        ("embed", ("model", None)),
        ("moe/router", (None, None)),
        ("moe/w_down", ("model", "data", None) if two_d_experts
         else ("model", None, None)),
        ("moe/w_gate", ("model", None, "data") if two_d_experts
         else ("model", None, None)),
        ("moe/w_up", ("model", None, "data") if two_d_experts
         else ("model", None, None)),
        ("attn/wq", (None, "model")),
        ("attn/wk", (None, "model")),
        ("attn/wv", (None, "model")),
        ("attn/wo", ("model", None)),
        ("mlp/w_up", (None, "model")),
        ("mlp/w_gate", (None, "model")),
        ("mlp/w_down", ("model", None)),
        ("ssm/in_proj", (None, "model")),
        ("ssm/out_proj", ("model", None)),
        ("in_proj", (None, "model")),
        ("out_proj", ("model", None)),
    ]
    return ShardingRules(rules=tuple(rules))


# ----------------------------------------------------------------------
# Contexts + in-model constraints
# ----------------------------------------------------------------------
@contextlib.contextmanager
def mesh_context(mesh: Mesh):
    """Activate ``mesh`` for the ``constrain*`` helpers (and for named
    specs inside jit, via the Mesh context manager)."""
    _MESH_STACK.append(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _MESH_STACK.pop()


@contextlib.contextmanager
def residual_sharding(axes: Tuple[Axis, ...]):
    """Override the residual-activation spec (e.g. ('data', 'model',
    None) for sequence parallelism) within the context."""
    _RESIDUAL_STACK.append(tuple(axes))
    try:
        yield
    finally:
        _RESIDUAL_STACK.pop()


def _active_mesh() -> Optional[Mesh]:
    return _MESH_STACK[-1] if _MESH_STACK else None


def constrain(x, axes: Sequence[Axis]):
    """with_sharding_constraint against the active mesh; identity when
    no mesh_context is active (single-device runs)."""
    mesh = _active_mesh()
    if mesh is None:
        return x
    spec = _resolve(_fit(axes, x.ndim), mesh, tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_residual(x):
    """(B, S, D) residual stream: data-parallel batch (+ optional
    sequence parallelism from ``residual_sharding``)."""
    return constrain(x, _RESIDUAL_STACK[-1])


def constrain_attn_qkv(q, k, v):
    """(B, S, H, hd) attention activations: heads on 'model'."""
    axes = (("pod", "data"), None, "model", None)
    return (constrain(q, axes), constrain(k, axes), constrain(v, axes))


# ----------------------------------------------------------------------
# Input/optimizer shardings (launch-time)
# ----------------------------------------------------------------------
def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_spec(bspecs: Any, mesh: Mesh) -> Any:
    """Shard every batch leaf's leading dim over the data axes."""
    axes = _data_axes(mesh)

    def leaf(spec):
        if not axes or not spec.shape:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, _resolve(
            (axes,) + (None,) * (len(spec.shape) - 1), mesh,
            tuple(spec.shape)))

    return jax.tree.map(leaf, bspecs)


def cache_spec(cache_specs: Any, mesh: Mesh, *,
               seq_shard: bool = False) -> Any:
    """KV/state-cache shardings: batch over data axes; for batch-1
    decode (``seq_shard``) the sequence dim shards over 'model'."""
    axes = _data_axes(mesh)

    def leaf(spec):
        shape = tuple(spec.shape)
        if not shape:
            return NamedSharding(mesh, P())
        dims: List[Axis] = [None] * len(shape)
        if seq_shard and len(shape) >= 2:
            dims[1] = "model"
        elif axes:
            dims[0] = axes
        return NamedSharding(mesh, _resolve(tuple(dims), mesh, shape))

    return jax.tree.map(leaf, cache_specs)


def zero1_spec(param_sh: NamedSharding, shape: Tuple[int, ...],
               mesh: Mesh) -> NamedSharding:
    """ZeRO-1 optimizer-moment sharding: keep the param's spec and
    additionally shard the first still-replicated, divisible dim over
    the data axes."""
    axes = _data_axes(mesh)
    if not axes or not shape:
        return param_sh
    size = math.prod(mesh.shape[a] for a in axes)
    dims = list(_fit(tuple(param_sh.spec), len(shape)))
    for i, (ax, dim) in enumerate(zip(dims, shape)):
        if ax is None and dim % size == 0:
            dims[i] = axes if len(axes) > 1 else axes[0]
            break
    return NamedSharding(mesh, P(*dims))
