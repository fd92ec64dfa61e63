"""Parameter-spec system: one tree of ``Spec`` drives init, abstract
(ShapeDtypeStruct) instantiation for the dry-run, and sharding resolution.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import ShardingRules, sharding_for


@dataclasses.dataclass(frozen=True)
class Spec:
    """Declarative parameter: shape + logical axes + init."""
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    dtype: Any = jnp.float32
    init: str = "normal"          # normal | zeros | ones | small_normal
    fan_in: int = 0               # for scaled init; 0 -> shape[0] heuristic

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)

    def materialize(self, key) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        if self.init == "neg_ones":
            return jnp.full(self.shape, -1, self.dtype)
        fan = self.fan_in or (self.shape[-2] if len(self.shape) >= 2 else self.shape[-1])
        scale = 1.0 / math.sqrt(max(fan, 1))
        if self.init == "small_normal":
            scale *= 0.1
        x = jax.random.normal(key, self.shape, jnp.float32) * scale
        return x.astype(self.dtype)

    def abstract(self, mesh=None, rules=None, memory_kind=None) -> jax.ShapeDtypeStruct:
        if mesh is None:
            return jax.ShapeDtypeStruct(self.shape, self.dtype)
        sh = sharding_for(self.logical, self.shape, mesh, rules, memory_kind)
        return jax.ShapeDtypeStruct(self.shape, self.dtype, sharding=sh)


def is_spec(x) -> bool:
    return isinstance(x, Spec)


def tree_init(specs, key) -> Any:
    """Materialize a Spec tree into a param pytree (deterministic key split)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [s.materialize(k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def tree_abstract(specs, mesh=None, rules=None, memory_kind=None) -> Any:
    return jax.tree.map(
        lambda s: s.abstract(mesh, rules, memory_kind), specs, is_leaf=is_spec)


def tree_shardings(specs, mesh, rules=None, memory_kind=None) -> Any:
    return jax.tree.map(
        lambda s: sharding_for(s.logical, s.shape, mesh, rules, memory_kind),
        specs, is_leaf=is_spec)


def tree_bytes(specs) -> int:
    return sum(
        int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
        for s in jax.tree.leaves(specs, is_leaf=is_spec))


def tree_param_count(specs) -> int:
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(specs, is_leaf=is_spec))


def stack_specs(spec_tree, n: int, stack_logical: str = "stack"):
    """Prepend a stacked-layer dim of size n to every Spec in a tree."""
    def f(s: Spec) -> Spec:
        return Spec((n,) + s.shape, (stack_logical,) + s.logical, s.dtype, s.init, s.fan_in)
    return jax.tree.map(f, spec_tree, is_leaf=is_spec)


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


class LayerFetch:
    """Where a layer scan finds repeat ``r``'s params: ``fetch(r)`` returns
    them, and ``fetch.scan`` runs a layer function over every repeat.

    ``sources`` are one or two functions of ``r``; with two, ``pick[r]``
    (bool) chooses the second through a ``lax.cond``. Weights returned out
    of that cond share one layout, and the compiler copies a whole device
    stack into it to slice one layer out; so ``scan`` runs the whole layer
    inside each branch instead."""

    def __init__(self, *sources, pick=None):
        assert len(sources) == (1 if pick is None else 2)
        self.sources = sources
        self.pick = pick

    @classmethod
    def stacked(cls, blocks) -> "LayerFetch":
        """Dynamic index into stacked ``blocks`` (leaves [R, ...])."""
        return cls(lambda r: jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, r, keepdims=False),
            blocks))

    def __call__(self, r):
        if self.pick is None:
            return self.sources[0](r)
        return jax.lax.cond(self.pick[r], self.sources[1], self.sources[0], r)

    def scan(self, layer_fn, carry, xs):
        """``lax.scan`` of ``layer_fn(params_r, carry, *xs_r) -> (carry,
        ys_r)`` over the repeats, ``xs`` a tuple of [R, ...] arrays. Under
        the cond each branch slices ``xs`` itself: slices passed into the
        cond would be copied once for each branch."""
        n = xs[0].shape[0]
        if self.pick is None:
            def body(c, xs_r):
                *xs_r, r = xs_r
                return layer_fn(self.sources[0](r), c, *xs_r)
            return jax.lax.scan(body, carry, (*xs, jnp.arange(n)))

        def branch(source):
            def run(r, c, xs):
                return layer_fn(source(r), c, *(
                    jax.lax.dynamic_index_in_dim(a, r, keepdims=False)
                    for a in xs))
            return run

        def body(c, r):
            return jax.lax.cond(self.pick[r], branch(self.sources[1]),
                                branch(self.sources[0]), r, c, xs)
        return jax.lax.scan(body, carry, jnp.arange(n))
