"""Random weights for a served configuration, made on the device in one
jitted call from ``--seed``, in the type they are served in.

The tree's structure (names and shapes) is the program's parameter tree;
the values follow the rule of the configuration's reference module
(``references.<reference>.init_std``), which gives each leaf its standard
deviation by its name and shape.
"""
from __future__ import annotations

from types import ModuleType
from typing import Any, Mapping

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in p)


def _std(ref: ModuleType, path: str, shape, cfg: Mapping[str, Any]) -> float:
    try:
        return ref.init_std(path, shape, cfg)
    except KeyError as e:
        raise KeyError(f"{ref.__name__}: {e.args[0]}") from e


def make_params(api, cfg: Mapping[str, Any], seed: int,
                ref: ModuleType) -> Any:
    """Every weight, drawn on the device by one compiled program, each
    leaf with the deviation ``ref.init_std`` gives it."""
    key = seed_key(seed)
    shapes = jax.eval_shape(api.init, key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    stds = [_std(ref, _path(p), s.shape, cfg) for p, s in flat]

    def init(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree.unflatten(treedef, [
            (jax.random.normal(k, s.shape, jnp.float32) * std).astype(s.dtype)
            for k, (_, s), std in zip(keys, flat, stds)])

    return jax.block_until_ready(jax.jit(init)(key))
