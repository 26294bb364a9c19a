"""Seeded weights, made on the device in one jitted call, in the type they
are served in. (The neutral view of them that a plain reference reads is
its architecture's own file, ``benchmark/views/<name>.py``.)

The program's parameter tree (``CausalLM.init``'s layout) is taken as
SHAPES only (``jax.eval_shape``): nothing is initialised by the program,
no float32 copy of a 7 GB tree exists on the host or the device.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


def _leaf_path(path: Tuple[Any, ...]) -> List[str]:
    return [str(getattr(k, "key", getattr(k, "name", k))) for k in path]


def _fan_in(names: List[str], shape: Tuple[int, ...]) -> int:
    # q/k/v kernels are [D, heads, head]; every other kernel contracts all
    # but its last axis ([heads, head, D] for the output projection).
    if names[-2] in ("q", "k", "v"):
        return shape[0]
    return int(math.prod(shape[:-1]))


Seeding = Callable[[List[str], Tuple[int, ...]],
                   Optional[Tuple[float, float]]]


def make_params(model: Any, seed: int, dtype: Any,
                seeding: Optional[Seeding] = None) -> Any:
    """The served tree: kernels and embeddings normal with std
    1/sqrt(fan-in) (embeddings 1/sqrt(width)), norm scales one, biases
    normal with std 0.02 so that a bias the arithmetic drops would show.
    That is the common table; ``seeding`` (a view file's, see
    ``benchmark/views``) is asked first with a leaf's path and shape, and
    where it answers ``(mean, std)`` the leaf is ``mean + std x normal``.
    A leaf nobody has a rule for raises.

    Leaves that differ only in their layer (same parameter name, same
    shape) are drawn by ONE random call and split: some fifteen random
    operations instead of three hundred keep the program small enough to
    compile in seconds and to load from the cache in one."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    groups: Dict[Tuple[Any, ...], List[int]] = {}
    for i, (path, sds) in enumerate(flat):
        names = _leaf_path(path)
        rule = seeding(names, tuple(sds.shape)) if seeding else None
        groups.setdefault(
            (names[-2], names[-1], sds.shape, rule), []).append(i)

    def build(key: jax.Array) -> List[jax.Array]:
        leaves: List[Any] = [None] * len(flat)
        for g, ((parent, kind, shape, rule), members) in enumerate(
                groups.items()):
            k = jax.random.fold_in(key, g)
            stacked = (len(members),) + tuple(shape)
            if rule is not None:
                mean, std = rule
                block = jnp.full(stacked, mean, dtype)
                if std:
                    block = block + std * jax.random.normal(k, stacked, dtype)
            elif kind == "scale":
                block = jnp.ones(stacked, dtype)
            elif kind == "bias":
                block = 0.02 * jax.random.normal(k, stacked, dtype)
            elif kind == "embedding":
                block = jax.random.normal(k, stacked, dtype) / math.sqrt(
                    shape[-1])
            elif kind == "kernel":
                block = jax.random.normal(k, stacked, dtype) / math.sqrt(
                    _fan_in([parent, kind], shape))
            else:
                raise ValueError(f"no rule for parameter {parent}/{kind}")
            for j, i in enumerate(members):
                leaves[i] = block[j].astype(dtype)
        return leaves

    # --seed may exceed 32 signed bits: fold it into the key in two halves.
    seed = int(seed)
    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)
    leaves = jax.jit(build)(key)
    return jax.tree_util.tree_unflatten(treedef, leaves)
