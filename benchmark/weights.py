"""Seeded weights, made on the device in one jitted call, in the type they
are served in — and the neutral view of them the plain references read.

The program's parameter tree (``CausalLM.init``'s layout) is taken as
SHAPES only (``jax.eval_shape``): nothing is initialised by the program,
no float32 copy of a 7 GB tree exists on the host or the device.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

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


def make_params(model: Any, seed: int, dtype: Any) -> Any:
    """The served tree: kernels and embeddings normal with std
    1/sqrt(fan-in) (embeddings 1/sqrt(width)), norm scales one, biases
    normal with std 0.02 so that a bias the arithmetic drops would show.

    Leaves that differ only in their layer (same parameter name, same
    shape) are drawn by ONE random call and split: some fifteen random
    operations instead of three hundred keep the program small enough to
    compile in seconds and to load from the cache in one."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    groups: Dict[Tuple[Any, ...], List[int]] = {}
    for i, (path, sds) in enumerate(flat):
        names = _leaf_path(path)
        groups.setdefault((names[-2], names[-1], sds.shape), []).append(i)

    def build(key: jax.Array) -> List[jax.Array]:
        leaves: List[Any] = [None] * len(flat)
        for g, ((parent, kind, shape), members) in enumerate(groups.items()):
            k = jax.random.fold_in(key, g)
            stacked = (len(members),) + tuple(shape)
            if kind == "scale":
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


def neutral_view(params: Any, num_layers: int) -> Dict[str, Any]:
    """The same arrays under architecture-neutral names: what
    ``benchmark/reference/*`` read. No array is copied or reshaped here
    (a reshaped copy of 7B projections would not fit beside the served
    model): projections keep the program's [D, heads, head] and
    [heads, head, D] layouts and the references flatten them inside their
    jitted layer. This is the one place that knows the program's names."""
    p = params["params"]
    layers = []
    for i in range(num_layers):
        lp = p[f"layer{i}"]
        w = {
            "ln1_g": lp["attn_norm"]["scale"],
            "ln1_b": lp["attn_norm"].get("bias"),
            "wq": lp["q"]["kernel"], "bq": lp["q"].get("bias"),
            "wk": lp["k"]["kernel"], "bk": lp["k"].get("bias"),
            "wv": lp["v"]["kernel"], "bv": lp["v"].get("bias"),
            "wo": lp["o"]["kernel"], "bo": lp["o"].get("bias"),
            "ln2_g": lp["mlp_norm"]["scale"],
            "ln2_b": lp["mlp_norm"].get("bias"),
            "w_up": lp["mlp_up"]["kernel"], "b_up": lp["mlp_up"].get("bias"),
            "w_down": lp["mlp_down"]["kernel"],
            "b_down": lp["mlp_down"].get("bias"),
        }
        if "mlp_gate" in lp:
            w["w_gate"] = lp["mlp_gate"]["kernel"]
        layers.append({k: v for k, v in w.items() if v is not None})
    out = {
        "wte": p["tok_embed"]["embedding"],
        "layers": layers,
        "lnf_g": p["final_norm"]["scale"],
    }
    if "bias" in p["final_norm"]:
        out["lnf_b"] = p["final_norm"]["bias"]
    if "pos_embed" in p:
        out["wpe"] = p["pos_embed"]["embedding"]
    if "lm_head" in p:
        out["lm_head"] = p["lm_head"]["kernel"]
    return out
