"""Random weights for a served configuration, made on the device in one
jitted call from ``--seed``, in the type they are served in.

The tree's structure (names and shapes) is the program's parameter tree;
the values follow the benchmark's own rule: every matrix is drawn with the
standard deviation 1/sqrt(fan-in) of the dimensions it contracts, norm
scales and biases are small and nonzero, and the embedding's deviation is
``EMBED / d_model``. With the fan-in counted right, attention stays soft
and a rounding difference does not grow from layer to layer, so the logits
of a bf16 program can be compared with a float32 reference at full depth.
A tied head scores each token by its embedding's product with the final
hidden state, in which the input token's own embedding survives; at this
scale it stands about 1.4 deviations above the rest at d_model 2048 (at
unit scale, 14), so the top tokens are close and a less precise step picks
others, as a trained model's would.
"""
from __future__ import annotations

import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp

BIAS_STD = 0.5
NORM_STD = 0.1
#: the embedding's standard deviation, times 1/d_model
EMBED = 4.5


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


def _std(path: str, shape, cfg: Mapping[str, Any]) -> float:
    """Standard deviation of one leaf, by its name in the program's tree
    and its shape."""
    D = cfg["d_model"]
    name = path.rsplit("/", 1)[-1]
    parent = path.rsplit("/", 2)[-2] if path.count("/") >= 1 else ""
    if name.startswith("ln_") or name == "final_norm":
        return NORM_STD
    if name in ("bq", "bk", "bv"):
        return BIAS_STD
    if name == "embedding":
        return EMBED / D
    if name == "unembedding":
        return 1.0 / math.sqrt(D)
    if name in ("wq", "wk", "wv", "wi_gate", "wi_up"):
        return 1.0 / math.sqrt(D)
    if name == "wo" and parent == "attn":      # (L, H, h, D)
        return 1.0 / math.sqrt(shape[1] * shape[2])
    if name == "wo" and parent == "mlp":       # (L, F, D)
        return 1.0 / math.sqrt(shape[1])
    raise KeyError(f"no initialisation rule for parameter {path!r}")


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in p)


def make_params(api, cfg: Mapping[str, Any], seed: int) -> Any:
    """Every weight, drawn on the device by one compiled program."""
    key = seed_key(seed)
    shapes = jax.eval_shape(api.init, key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    stds = [_std(_path(p), s.shape, cfg) for p, s in flat]

    def init(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree.unflatten(treedef, [
            (jax.random.normal(k, s.shape, jnp.float32) * std).astype(s.dtype)
            for k, (_, s), std in zip(keys, flat, stds)])

    return jax.block_until_ready(jax.jit(init)(key))
