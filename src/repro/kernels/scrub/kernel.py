"""Pallas-TPU kernel for the STT-RAM scrub (corrective re-write) pass.

A scrub pass walks stored data whose bits may have decayed since the last
write (retention failures accumulate in a per-leaf decay *mask* — bit i set
means stored bit i currently differs from the value the write intended) and
re-writes exactly those bits: read + ECC-correct + write-back, the standard
MRAM scrubbing loop. Fused, in one HBM pass over (stored, mask):

    corrected = stored XOR mask  ->  stochastic re-write of the mask bits
    -> scrubbed word + RESIDUAL mask (re-writes that failed stay decayed and
       are retried on the next pass) + per-block, per-bit-plane flip/error
       counts.

The re-write obeys the same EXTENT driver semantics as the write path: each
corrected bit pays the level's per-direction flip energy and fails with the
level's direction WER (a failed correction leaves the decayed value — the
cell kept its wrong state). Words with an all-zero mask are untouched at
zero energy, the CMP redundant-write elimination applied to scrubbing.

RNG/layout/stats contract: identical to ``kernels/extent_write`` (the two
kernels share its per-plane body and pallas_call) — counter hash of (seed,
FLAT lane index, bit plane), so results are invariant to how ops.py
partitions the lane vector into a grid, and ``ref.py`` reproduces the kernel
bit-exactly in pure jnp, energy included.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.extent_write.kernel import (DEFAULT_BLOCK, flat_lane_index,
                                               lane_call, rewrite_planes)


def _kernel(stored_ref, mask_ref, seed_ref, lane0_ref, thr01_ref, thr10_ref,
            scrubbed_ref, residual_ref, stats_ref, *, nbits: int,
            block: Tuple[int, int], cols_total: int, stride: int):
    mask = mask_ref[...]
    corrected = stored_ref[...] ^ mask
    fail, stats_ref[...] = rewrite_planes(
        seed_ref[0], flat_lane_index(block, cols_total, stride, lane0_ref[0]),
        corrected, mask, thr01_ref, thr10_ref, nbits=nbits, block=block)
    scrubbed_ref[...] = corrected ^ fail  # failed bits stay decayed
    residual_ref[...] = fail              # retried on the next pass


def scrub_kernel(
    stored_u32: jax.Array,   # (G, R, C) uint32 lanes
    mask_u32: jax.Array,     # decayed-bit mask, same shape
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
    """Returns (scrubbed (G, R, C) u32, residual_mask (G, R, C) u32, stats
    tiles — the write kernel's per-plane count layout); ``lane0`` and
    ``stride`` place the groups as in ``extent_write_kernel``."""
    if lane0 is None:
        lane0 = jnp.zeros((1,), jnp.uint32)
    return lane_call(_kernel, (stored_u32, mask_u32),
                     (seed, lane0, thr01, thr10), 2, nbits=nbits,
                     block=block, stride=stride, interpret=interpret)
