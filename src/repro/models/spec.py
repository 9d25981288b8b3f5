"""ParamSpec: abstract parameter descriptions (shape + logical axes + init).

Models are built in two phases:
  1. ``*_specs(cfg)``     -> pytree of ParamSpec (no allocation; drives both
                             the dry-run via ShapeDtypeStruct and sharding)
  2. ``init_params``      -> materialize real arrays from the spec tree
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class ParamSpec(NamedTuple):
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]           # logical axis names per dim
    dtype: Any = jnp.float32
    init: str = "normal"                      # normal | zeros | ones | eye_conv
    stddev: float = 0.02


def is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def spec_map(fn, tree: Any) -> Any:
    return jax.tree_util.tree_map(fn, tree, is_leaf=is_spec)


def abstract_params(spec_tree: Any, shardings: Any = None) -> Any:
    """ShapeDtypeStruct tree for lowering without allocation."""
    if shardings is None:
        return spec_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), spec_tree)
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        spec_tree, shardings, is_leaf=is_spec)


def init_params(spec_tree: Any, key: jax.Array) -> Any:
    """Materialize parameters. Deterministic per-leaf via path folding."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        spec_tree, is_leaf=is_spec)
    leaves = []
    for path, spec in flat:
        path_hash = _stable_hash("/".join(str(p) for p in path))
        k = jax.random.fold_in(key, path_hash)
        leaves.append(_init_one(spec, k))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _init_one(spec: ParamSpec, key: jax.Array) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    # the barrier keeps XLA from folding the scale into the sampler's own
    # constants under jit, so a jitted init rounds exactly like an eager one
    if spec.init == "normal":
        x = jax.random.normal(key, spec.shape, jnp.float32)
        return (jax.lax.optimization_barrier(x) * spec.stddev).astype(spec.dtype)
    if spec.init == "a_log":  # mamba: A in [1, 16), stored as log
        a = jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(jax.lax.optimization_barrier(a)).astype(spec.dtype)
    raise ValueError(f"unknown init {spec.init!r}")


def _stable_hash(s: str) -> int:
    h = 2166136261
    for ch in s.encode():
        h = (h ^ ch) * 16777619 % (1 << 31)
    return h


def stack_specs(spec_tree: Any, n: int) -> Any:
    """Add a leading scanned-layers dim (logical axis "stacked")."""
    return spec_map(
        lambda s: ParamSpec((n,) + s.shape, ("stacked",) + s.axes,
                            s.dtype, s.init, s.stddev),
        spec_tree)


def num_params(spec_tree: Any) -> int:
    return sum(int(np.prod(s.shape)) for s in
               jax.tree_util.tree_leaves(spec_tree, is_leaf=is_spec)
               if is_spec(s))
