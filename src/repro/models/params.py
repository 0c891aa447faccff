"""Parameter specs: one declarative tree drives init, sharding and dry-run.

Each leaf is a ``ParamSpec`` with a GLOBAL shape and per-dim mesh-axis
assignment ("model" = TP, "data" = FSDP/ZeRO-3, None = replicated; params
are never sharded over "pod" — the pod axis is pure DP).  From the tree we
derive:

* ``PartitionSpec`` per leaf                (jit in_shardings / dry-run)
* global ``ShapeDtypeStruct`` per leaf      (AOT lowering without allocation)
* shard-local init inside ``shard_map``     (keys folded by shard indices)
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.axes import axis_size_or_1, has_axis

Tree = dict[str, Any]   # nested dict of ParamSpec


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    dims: tuple[str | None, ...]
    init: str = "normal"      # normal | zeros | ones | scaled(fan-in)
    scale: float | None = None
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.dims), (self.shape, self.dims)

    def pspec(self) -> P:
        return P(*self.dims)

    def global_sds(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, jnp.dtype(self.dtype))

    def local_shape(self, sizes: dict[str, int]) -> tuple[int, ...]:
        out = []
        for s, d in zip(self.shape, self.dims):
            div = sizes.get(d, 1) if d else 1
            assert s % div == 0, f"dim {s} not divisible by {d}={div}"
            out.append(s // div)
        return tuple(out)


def stacked(n: int, spec: ParamSpec) -> ParamSpec:
    """Prepend a scan-stack dimension (replicated)."""
    return ParamSpec((n,) + spec.shape, (None,) + spec.dims, spec.init,
                     spec.scale, spec.dtype)


def tree_map_specs(fn, tree: Tree):
    return jax.tree.map(fn, tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def tree_pspecs(tree: Tree):
    return tree_map_specs(lambda s: s.pspec(), tree)


def tree_global_sds(tree: Tree):
    return tree_map_specs(lambda s: s.global_sds(), tree)


def tree_nbytes(tree: Tree) -> int:
    leaves = jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    total = 0
    for s in leaves:
        n = 1
        for d in s.shape:
            n *= d
        total += n * jnp.dtype(s.dtype).itemsize
    return total


def _init_leaf(spec: ParamSpec, key, sizes: dict[str, int]):
    shape = spec.local_shape(sizes)
    dt = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(shape, dt)
    if spec.init == "ones":
        return jnp.ones(shape, dt)
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    std = spec.scale if spec.scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)


def _shard_fold(spec: ParamSpec, sizes: dict[str, int]):
    """This shard's index among the shards of ``spec``: the indices of the
    bound axes the leaf is split over, row-major.  Replicas along every
    other axis share it, so a replicated leaf starts equal on all of
    them."""
    fold = 0
    for ax in sizes:
        if ax in spec.dims and has_axis(ax):
            fold = fold * sizes[ax] + jax.lax.axis_index(ax)
    return fold


def init_tree(tree: Tree, key):
    """Initialize shard-local params.  Call INSIDE shard_map (or outside it
    for unsharded params): each leaf's key is folded with its own shard
    index, so different shards hold different random slices while
    replicas, pods included, hold the same values."""
    sizes = {"model": axis_size_or_1("model"),
             "data": axis_size_or_1("data")}
    flat, treedef = jax.tree.flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    leaves = []
    for i, (path, spec) in enumerate(flat):
        k = jax.random.fold_in(jax.random.fold_in(key, i),
                               _shard_fold(spec, sizes))
        leaves.append(_init_leaf(spec, k, sizes))
    return jax.tree.unflatten(treedef, leaves)
