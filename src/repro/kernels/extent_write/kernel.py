"""Pallas-TPU kernel for the EXTENT approximate write path.

Fuses, in one HBM pass over (old, new):
    XOR bit-diff -> per-bit-plane stochastic write failure -> stored word
    + per-block, per-bit-plane flip / error counts.

Why a kernel: the write path is purely memory-bound (O(bytes) work, zero
matmul). Composed as jnp ops it materializes the (elements x nbits)
unpacked bit tensor (16-32x write amplification through HBM); fused it
runs at HBM streaming bandwidth with all bit algebra in VREGs. This is the
TPU re-thinking of the paper's per-row driver bank: the "64 parallel
drivers per word" become lane-parallel bit ops over a (block_r, block_c)
VMEM tile.

RNG: counter-based murmur3-style hash of (seed, element index, bit plane) —
no state, identical on TPU hardware and in interpret mode, and reproducible
from ref.py (the pure-jnp oracle implements the same hash bit-exactly).

Stats: each grid block writes one (8, 128)-aligned int32 tile of
per-bit-plane counts (row 0: 0->1 flips, row 1: 1->0 flips, row 2:
failures; lane b = bit plane b). ops.py sums the tiles and prices the
counts once (``ref.plane_stats``). Integer counts add exactly in any
order, so the stats — energy included — are independent of the block
partition and identical to the pure-jnp lane reference on every platform.
The seed and the per-plane thresholds are scalar operands, so they live in
SMEM.

Layout: operands are bitcast to uint32 lanes *outside* the kernel (ops.py):
uint32 is the native VPU lane width; bf16 tensors pack pairs of elements
into one lane, f32 maps 1:1. Block shape defaults to (256, 512) lanes =
512 KiB per uint32 buffer. The bit planes are a rolled loop, so one
plane's temporaries are live at a time: a 32-plane unroll of that block
asks Mosaic for more VMEM than a v5e kernel may use (refused above
(64, 512) rows x lanes), the loop compiles at (256, 512) in under a second
(tests/test_tpu_compile.py). The RNG hashes the *flat* lane index of the
whole tensor, so results are invariant to how ops.py partitions the lane
vector into a (rows, cols) grid — small tensors get right-sized grids
instead of full-block padding, bit-identical to any other partition.
The operands are (G, rows, cols): G runs of the tensor's lanes, placed in
it by a stride and a lane offset, so one device's shard of a tensor split
over a mesh (a die's slots) hashes the indices the whole tensor would.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from repro.kernels.platform import resolve_interpret

# murmur3 finalizer constants (numpy scalars: safe to close over in a
# pallas kernel body — jnp arrays would be captured consts, which is an error)
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_K_ELEM = np.uint32(2654435761)   # Knuth multiplicative hash
_K_BIT = np.uint32(0x9E3779B9)    # golden-ratio increment per bit plane

DEFAULT_BLOCK = (256, 512)
# per-block stats tile: one native (sublane, lane) int32 tile
STATS_TILE = (8, 128)
ROW_F01, ROW_F10, ROW_ERR = 0, 1, 2


def _hash_u32(x: jax.Array) -> jax.Array:
    """murmur3 fmix32: avalanching 32-bit hash, vectorizes on the VPU."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def uniform_bits(seed: jax.Array, elem_idx: jax.Array, bit) -> jax.Array:
    """Deterministic uniform uint32 for (seed, element, bit-plane)."""
    h = (elem_idx.astype(jnp.uint32) * _K_ELEM
         ^ (jnp.asarray(bit).astype(jnp.uint32) * _K_BIT)
         ^ seed.astype(jnp.uint32))
    return _hash_u32(h)


def flat_lane_index(block: Tuple[int, int], cols_total: int, stride: int,
                    lane0: jax.Array) -> jax.Array:
    """Flat lane index, in the whole tensor, of every lane of the current
    grid block — the counter the RNG hashes. The operand is (G, rows,
    cols_total); lane (row, col) of group g is lane
    ``g * stride + lane0 + row * cols_total + col`` of the whole tensor
    (one group, ``lane0`` 0: the operand is the whole tensor)."""
    g, r, c = (pl.program_id(i).astype(jnp.uint32) for i in range(3))
    base = (g * jnp.uint32(stride) + lane0
            + r * jnp.uint32(block[0] * cols_total) + c * jnp.uint32(block[1]))
    rows = jax.lax.broadcasted_iota(jnp.uint32, block, 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, block, 1)
    return base + rows * jnp.uint32(cols_total) + cols


def rewrite_planes(seed, elem, target, change, thr01_ref, thr10_ref, *,
                   nbits: int, block: Tuple[int, int]):
    """Stochastic re-write of the ``change`` bits toward ``target``, one
    bit plane at a time: a bit written to 1 fails below ``thr01[b]``, to 0
    below ``thr10[b]``. Returns (failure mask, per-plane stats tile)."""
    sub = jax.lax.broadcasted_iota(jnp.int32, STATS_TILE, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, STATS_TILE, 1)

    def plane(b, carry):
        fail_acc, tile = carry
        bitmask = jnp.uint32(1) << b.astype(jnp.uint32)
        flip = (change & bitmask) != 0
        to_ap = flip & ((target & bitmask) != 0)       # written to 1
        u = uniform_bits(seed, elem, b)
        thr = jnp.where(to_ap, thr01_ref[b], thr10_ref[b])
        fail = flip & (u < thr)
        fail_acc = fail_acc | jnp.where(fail, bitmask, jnp.uint32(0))
        at_b = lane == b
        for row, bits in ((ROW_F01, to_ap), (ROW_F10, flip & ~to_ap),
                          (ROW_ERR, fail)):
            tile = jnp.where(at_b & (sub == row),
                             jnp.sum(bits.astype(jnp.int32)), tile)
        return fail_acc, tile

    # a loop, not an unroll: one plane's temporaries live at a time
    fail_acc, tile = jax.lax.fori_loop(
        0, nbits, plane, (jnp.zeros(block, jnp.uint32),
                          jnp.zeros(STATS_TILE, jnp.int32)))
    return fail_acc, tile


def _kernel(old_ref, new_ref, seed_ref, lane0_ref, thr01_ref, thr10_ref,
            stored_ref, stats_ref, *, nbits: int, block: Tuple[int, int],
            cols_total: int, stride: int):
    new = new_ref[...]
    fail, stats_ref[...] = rewrite_planes(
        seed_ref[0], flat_lane_index(block, cols_total, stride, lane0_ref[0]),
        new, old_ref[...] ^ new, thr01_ref, thr10_ref, nbits=nbits,
        block=block)
    stored_ref[...] = new ^ fail


def lane_call(kernel, data_in: Tuple[jax.Array, ...], scalars, n_data_out,
              *, nbits: int, block: Tuple[int, int], stride: int,
              interpret: Optional[bool]):
    """The pallas_call both lane kernels share: ``data_in`` (G, R, C)
    uint32 operands blocked ``(1, *block)`` over the grid
    (G, R / block_r, C / block_c); ``scalars`` (seed, lane0, thresholds)
    whole in SMEM; then ``n_data_out`` (G, R, C) uint32 outputs and the
    per-block stats tiles (G, (R / block_r) * 8, (C / block_c) * 128)
    int32. ``stride`` and ``lane0`` place the groups in the whole tensor
    (``flat_lane_index``)."""
    G, R, C = data_in[0].shape
    assert R % block[0] == 0 and C % block[1] == 0, (data_in[0].shape, block)
    grid = (G, R // block[0], C // block[1])
    data_spec = pl.BlockSpec((None, *block), lambda g, r, c: (g, r, c))
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(kernel, nbits=nbits, block=block, cols_total=C,
                          stride=stride),
        grid=grid,
        in_specs=[data_spec] * len(data_in) + [smem_spec] * len(scalars),
        out_specs=[data_spec] * n_data_out + [
            pl.BlockSpec((None, *STATS_TILE), lambda g, r, c: (g, r, c))],
        out_shape=[jax.ShapeDtypeStruct((G, R, C), jnp.uint32)] * n_data_out
        + [jax.ShapeDtypeStruct((G, grid[1] * STATS_TILE[0],
                                 grid[2] * STATS_TILE[1]), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(*data_in, *scalars)


def extent_write_kernel(
    old_u32: jax.Array,      # (G, R, C) uint32 lanes
    new_u32: jax.Array,
    seed: jax.Array,         # (1,) uint32
    thr01: jax.Array,        # (nbits,) uint32 failure thresholds (wer * 2^32)
    thr10: jax.Array,
    *,
    nbits: int,
    block: Tuple[int, int] = DEFAULT_BLOCK,
    lane0: Optional[jax.Array] = None,
    stride: int = 0,
    interpret: Optional[bool] = None,
):
    """Returns (stored (G, R, C) uint32, stats tiles (G, gr * 8, gc * 128)
    i32): per block, rows ROW_F01 / ROW_F10 / ROW_ERR of its (8, 128)
    tile hold the per-bit-plane counts in lanes [0, nbits). The operands
    are G runs of the whole tensor's lanes, run g starting at lane
    ``g * stride + lane0`` ((1,) uint32, default 0: with one group, the
    operands are the whole tensor). ``interpret=None`` follows the
    platform (``repro.kernels.platform``)."""
    if lane0 is None:
        lane0 = jnp.zeros((1,), jnp.uint32)
    return lane_call(_kernel, (old_u32, new_u32), (seed, lane0, thr01, thr10),
                     1, nbits=nbits, block=block, stride=stride,
                     interpret=interpret)
