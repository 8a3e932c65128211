"""Parameter/cache spec trees.

A model is *described* first (nested dict of TensorSpec) and only then
materialized. The same spec tree drives: real initialization (smoke tests),
ShapeDtypeStruct stand-ins (dry-run), and NamedShardings (logical axes →
mesh axes via sharding rules, with optional pinned_host memory kinds for
offloaded layer stacks).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.rules import Rules, named_sharding
from jax.sharding import Mesh, NamedSharding


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    shape: tuple[int, ...]
    logical: tuple[str | None, ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"  # normal | zeros | ones
    fan_in_axes: tuple[int, ...] = (0,)  # axes treated as fan-in for scaling

    def stacked(self, n: int) -> "TensorSpec":
        return dataclasses.replace(
            self, shape=(n, *self.shape), logical=("stack", *self.logical),
            fan_in_axes=tuple(a + 1 for a in self.fan_in_axes),
        )


SpecTree = Any  # nested dict of TensorSpec
ArrayTree = Any


def tree_map_spec(fn: Callable[[TensorSpec], Any], tree: SpecTree) -> Any:
    return jax.tree.map(fn, tree, is_leaf=lambda x: isinstance(x, TensorSpec))


def abstract(tree: SpecTree) -> ArrayTree:
    return tree_map_spec(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree)


def shardings(
    tree: SpecTree, mesh: Mesh, rules: Rules,
    memory_kind_fn: Callable[[tuple], str | None] | None = None,
) -> Any:
    """NamedSharding tree. memory_kind_fn(path)-> kind lets the offload plan
    mark specific subtrees pinned_host."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, TensorSpec))
    out = []
    for path, spec in flat:
        kind = memory_kind_fn(path) if memory_kind_fn else None
        out.append(named_sharding(mesh, rules, spec.shape, spec.logical, kind))
    return jax.tree.unflatten(treedef, out)


def abstract_with_sharding(tree: SpecTree, mesh: Mesh, rules: Rules,
                           memory_kind_fn=None) -> ArrayTree:
    shd = shardings(tree, mesh, rules, memory_kind_fn)
    ab = abstract(tree)
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), ab, shd)


@functools.partial(jax.jit, static_argnums=(1, 3))
def _normal_leaf(key: jax.Array, shape: tuple[int, ...], scale: jax.Array,
                 dtype: Any) -> jax.Array:
    # one program per leaf, so the scale and the cast fuse into one pass
    # over the float32 draw: done op by op, qwen2.5-3b's [36, 2048, 11008]
    # MLP stack held two float32 copies (6.5 GB) beside the weights already
    # made; here one (3.2 GB). The barrier keeps XLA from fusing the scale
    # into the draw's own arithmetic, which rounds differently from the
    # op-by-op values.
    z = jax.lax.optimization_barrier(jax.random.normal(key, shape, jnp.float32))
    return (z * scale).astype(dtype)


def initialize(tree: SpecTree, key: jax.Array) -> ArrayTree:
    flat, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, TensorSpec))
    keys = jax.random.split(key, len(flat))
    out = []
    for spec, k in zip(flat, keys):
        if spec.init == "zeros":
            out.append(jnp.zeros(spec.shape, spec.dtype))
        elif spec.init == "ones":
            out.append(jnp.ones(spec.shape, spec.dtype))
        else:
            fan_in = max(int(np.prod([spec.shape[a] for a in spec.fan_in_axes])), 1)
            out.append(_normal_leaf(k, spec.shape, np.float32(1.0 / np.sqrt(fan_in)),
                                    spec.dtype))
    return jax.tree.unflatten(treedef, out)


def count_params(tree: SpecTree) -> int:
    flat = jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, TensorSpec))
    return sum(int(np.prod(s.shape)) for s in flat)


def tree_bytes(tree: SpecTree) -> int:
    flat = jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, TensorSpec))
    return sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize for s in flat)
