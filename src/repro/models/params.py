"""Lightweight parameter-spec system (no flax).

A model declares its parameters as a pytree of :class:`Spec` leaves; the
framework can then materialize real arrays (smoke tests / real training),
abstract ``ShapeDtypeStruct`` trees (multi-pod dry-run), or
``NamedSharding`` trees (pjit in_shardings) from the same declaration —
guaranteeing the three never drift apart.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


class Spec(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis names, len == len(shape)
    init: str = "normal"              # normal | zeros | ones | const | embed
    scale: float = 1.0                # stddev multiplier / const value
    dtype: Optional[str] = None       # per-leaf dtype override (e.g. 'int32')
    # axes the projection contracts, as negative indices (so a stacked
    # leading 'layers' dim keeps them valid); None: shape[-2] (2-D and
    # expert-batched matrices), or shape[-1] for vectors
    fan_in_axes: Optional[Tuple[int, ...]] = None

    def fan_in_scale(self) -> float:
        if self.fan_in_axes is not None:
            fan_in = math.prod(self.shape[a] for a in self.fan_in_axes)
        else:
            fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
        return 1.0 / math.sqrt(max(fan_in, 1))


def is_spec(x) -> bool:
    return isinstance(x, Spec)


def _leaf_init(spec: Spec, key, dtype):
    if spec.dtype is not None:
        dtype = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "const":
        return jnp.full(spec.shape, spec.scale, dtype)
    if spec.init == "embed":
        return (jax.random.normal(key, spec.shape, jnp.float32) * 0.02
                ).astype(dtype)
    # 'normal': truncated-normal-ish fan-in scaled
    std = spec.scale * spec.fan_in_scale()
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)


def init_tree(specs, key, dtype=jnp.float32):
    """Materialize real parameters from a spec tree."""
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [_leaf_init(s, k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def abstract_tree(specs, dtype=jnp.bfloat16):
    """ShapeDtypeStruct stand-ins — no allocation; used by the dry-run."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.dtype(s.dtype) if s.dtype else dtype),
        specs, is_leaf=is_spec)


# Logical-axis -> mesh-axis rules.  A mesh axis is applied to a dim only when
# the dim size is divisible by the mesh axis size (whisper's 12 heads on a
# 16-way model axis fall back to replication); each mesh axis is used at most
# once per tensor.
DEFAULT_RULES = {
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "experts": (),            # tensor-parallel inside experts by default
    "embed": (),
    "layers": (),
    "lora_r": (),
    "state": (),
    # 'batch' maps to the (composite) data-parallel axes; see partition_spec
    "batch": (("pod", "data"), ("data",)),
}


def partition_spec(spec: Spec, mesh: Mesh, rules=None) -> PartitionSpec:
    rules = rules or DEFAULT_RULES
    used = set()
    out = []
    for dim, logical in zip(spec.shape, spec.axes):
        assigned = None
        for cand in rules.get(logical, ()) if logical else ():
            axes = cand if isinstance(cand, tuple) else (cand,)
            if any(a in used or a not in mesh.shape for a in axes):
                continue
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if dim % size == 0:
                assigned = cand
                used.update(axes)
                break
        out.append(assigned)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def tree_shardings(specs, mesh: Mesh, rules=None):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, partition_spec(s, mesh, rules)),
        specs, is_leaf=is_spec)


def tree_pspecs(specs, mesh: Mesh, rules=None):
    return jax.tree_util.tree_map(
        lambda s: partition_spec(s, mesh, rules), specs, is_leaf=is_spec)


def count_params(specs) -> int:
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=is_spec)
    return int(sum(np.prod(s.shape) for s in leaves))
