"""jit'd wrapper for the fused int8-KV quantize + EXTENT store."""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import write_driver
from repro.core.priority import Priority
from repro.kernels.kv_quant import kernel as K
from repro.kernels.kv_quant import ref as R


@functools.lru_cache(maxsize=8)
def _thresholds(level: Priority) -> jax.Array:
    """(8,) per-bit failure thresholds for the int8 payload: the top bit
    (sign) rides the next level up — a sign flip is the int8 'exponent'."""
    table = write_driver.level_table()
    lvl = int(Priority.coerce(level))
    codes = np.full((8,), lvl, np.int32)
    codes[7] = min(lvl + 1, int(Priority.EXACT))  # protect the sign bit
    wer = np.asarray(table["wer01"])[codes]
    thr = (np.clip(wer, 0.0, 1.0) * 2**32).astype(np.uint64)
    return jnp.asarray(thr.clip(0, 2**32 - 1).astype(np.uint32))


def kv_quant_store(
    key: jax.Array,
    kv: jax.Array,                       # any shape, f32/bf16
    *,
    level: Priority = Priority.MID,
    block: Tuple[int, int] = (64, 128),
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Quantize + approximately store a KV tensor.

    Returns (q_int8 (same shape), scales (gr, gc), stats). Dequantize with
    ``kv_dequant``. Padding rows quantize to 0 and cannot fail (0 bits set).

    Default level is MID, not LOW: every int8 payload bit is significant
    (quantization already dropped the LOW-tolerance mantissa tail), so MID
    keeps the stochastic-write error at ~the quantization-noise floor
    (rel-err 1.3% vs 1.0% pure-quant; LOW would be 17%).
    """
    thr = _thresholds(Priority.coerce(level))
    seed = jax.random.bits(key, (1,), jnp.uint32)
    flat = kv.reshape(-1)
    n = flat.size
    bc = block[0] * block[1]
    pad = (-n) % bc
    xp = jnp.concatenate([flat.astype(jnp.float32),
                          jnp.zeros((pad,), jnp.float32)])
    rows = xp.size // block[1]
    x2 = xp.reshape(rows, block[1])
    blk = (min(block[0], rows), block[1])
    if use_kernel:
        q2, scale_tiles, error_tiles = K.kv_quant_kernel(
            x2, seed, thr, block=blk, interpret=interpret)
        th, tw = K.STATS_TILE  # one value per block tile
        scales, errors = scale_tiles[::th, ::tw], error_tiles[::th, ::tw]
    else:
        q2, scales, errors = R.kv_quant_ref(x2, seed, thr, blk)
    q = q2.reshape(-1)[:n].reshape(kv.shape)
    stats = {"errors": jnp.sum(errors),
             "bytes_stored": jnp.asarray(n, jnp.int32),
             "bytes_saved": jnp.asarray(
                 n * (kv.dtype.itemsize - 1), jnp.int32)}
    return q, scales, stats


def kv_dequant(q: jax.Array, scales: jax.Array,
               block: Tuple[int, int] = (64, 128),
               out_dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of kv_quant_store's layout: broadcast per-block scales."""
    shape = q.shape
    flat = q.reshape(-1)
    n = flat.size
    bc = block[0] * block[1]
    pad = (-n) % bc
    qp = jnp.concatenate([flat, jnp.zeros((pad,), q.dtype)])
    rows = qp.size // block[1]
    blk_r = min(block[0], rows)
    q2 = qp.reshape(rows // blk_r, blk_r, -1, block[1])
    out = q2.astype(jnp.float32) * scales[:, None, :, None]
    return out.reshape(-1)[:n].reshape(shape).astype(out_dtype)
