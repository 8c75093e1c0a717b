"""Pallas-TPU kernel: fused int8 KV quantize + EXTENT approximate store.

The §Perf llama4-decode iteration-3 lever made concrete: KV entries are
stored as int8 payloads (per-(row-block) symmetric scale kept EXACT in a
side tensor) and the int8 payload is written through the EXTENT LOW/MID
driver — quantization *is* the bit-plane priority map taken to its
conclusion (drop 8 mantissa bits entirely, approximate the rest).

Fusion: bf16/f32 KV values stream HBM->VMEM once; absmax reduction,
scaling, rounding, the stochastic write-failure draw (same murmur3 counter
RNG as extent_write) and the int8 pack all happen in VREGs; HBM sees only
the int8 payload + per-block scales. Unfused, the quantize and the
approximate-store each round-trip the tensor.

Layout: input (R, C) float lanes; each grid block writes its scale and its
failed-bit count as one (8, 128) f32 / i32 tile (the chip stores vectors to
VMEM, never scalars) and ops.py takes one value per tile, giving
per-row-block (grid_r, grid_c) arrays. The seed and the per-bit thresholds
are scalar operands, in SMEM. Dequant lives in ops.py (one multiply at read time — decode attention
consumes int8 K/V against f32 scales).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.extent_write.kernel import STATS_TILE, uniform_bits
from repro.kernels.platform import resolve_interpret

DEFAULT_BLOCK = (256, 512)
QMAX = 127.0


def _kernel(x_ref, seed_ref, thr_ref, stored_ref, scale_ref, errors_ref,
            *, block: Tuple[int, int], cols_total: int):
    r, c = pl.program_id(0), pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    seed = seed_ref[0]

    absmax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(absmax, 1e-12) / QMAX
    q = jnp.clip(jnp.round(x / scale), -QMAX, QMAX).astype(jnp.int32)

    # EXTENT stochastic store of the int8 payload: erased-row write model
    # (old = 0), so only set bits can fail (0->1 weak direction). thr is the
    # (8,) per-bit failure threshold vector for the chosen driver level.
    rows = jax.lax.broadcasted_iota(jnp.uint32, block, 0) + jnp.uint32(
        r * block[0])
    cols = jax.lax.broadcasted_iota(jnp.uint32, block, 1) + jnp.uint32(
        c * block[1])
    elem = rows * jnp.uint32(cols_total) + cols

    qu = q.astype(jnp.uint32) & jnp.uint32(0xFF)  # two's-complement byte
    fail_acc = jnp.zeros(block, jnp.uint32)
    nerr = jnp.zeros(block, jnp.uint32)
    one = jnp.uint32(1)
    for b in range(8):
        bitmask = one << b
        is_set = (qu & bitmask) != 0
        u = uniform_bits(seed, elem, b)
        fail = is_set & (u < thr_ref[b])
        fail_acc = fail_acc | jnp.where(fail, bitmask, jnp.uint32(0))
        nerr = nerr + fail.astype(jnp.uint32)

    stored_u = qu ^ fail_acc
    # sign-extend back to int32 then truncate to int8 semantics
    stored = (stored_u.astype(jnp.int32) ^ 0x80) - 0x80
    stored_ref[...] = stored.astype(jnp.int8)
    scale_ref[...] = jnp.full(STATS_TILE, scale, jnp.float32)
    errors_ref[...] = jnp.full(STATS_TILE, jnp.sum(nerr.astype(jnp.int32)),
                               jnp.int32)


def kv_quant_kernel(
    x: jax.Array,           # (R, C) f32/bf16 lanes, R % block[0] == 0
    seed: jax.Array,        # (1,) uint32
    thr: jax.Array,         # (8,) uint32 per-bit failure thresholds
    *,
    block: Tuple[int, int] = DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
):
    """Returns (q_int8 (R, C), scale tiles (gr * 8, gc * 128) f32, error
    tiles (gr * 8, gc * 128) i32): every element of a block's (8, 128)
    tile holds that block's scale / failed-bit count."""
    R, C = x.shape
    assert R % block[0] == 0 and C % block[1] == 0, (x.shape, block)
    grid = (R // block[0], C // block[1])
    tiles = (grid[0] * STATS_TILE[0], grid[1] * STATS_TILE[1])
    tile_spec = pl.BlockSpec(STATS_TILE, lambda r, c: (r, c))
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, block=block, cols_total=C),
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda r, c: (r, c)), smem_spec,
                  smem_spec],
        out_specs=[pl.BlockSpec(block, lambda r, c: (r, c)), tile_spec,
                   tile_spec],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), jnp.int8),
            jax.ShapeDtypeStruct(tiles, jnp.float32),
            jax.ShapeDtypeStruct(tiles, jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(x, seed, thr)
