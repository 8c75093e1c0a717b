"""Pure-jnp oracle for the scrub Pallas kernel.

Identical semantics (same murmur3 counter RNG over flat lane indices, same
bit algebra, same stats) with plain jnp ops over the unpacked
(lanes x nbits) tensor — the reference every scrub-kernel test asserts
against bit-exactly.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.extent_write.ref import (_uniform_bits_all,
                                            plane_counts, plane_stats)


def scrub_ref(
    stored_u32: jax.Array,   # (R, C) uint32 lanes
    mask_u32: jax.Array,     # (R, C) uint32 decayed-bit mask
    seed: jax.Array,         # (1,) uint32
    thr01: jax.Array,        # (nbits,) uint32
    thr10: jax.Array,
    e01: jax.Array,          # (nbits,) f32
    e10: jax.Array,
    *,
    nbits: int,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Returns (scrubbed, residual_mask, stats) — see kernel.py for the
    corrective-re-write semantics."""
    R, C = stored_u32.shape
    elem = (jnp.arange(R, dtype=jnp.uint32)[:, None] * jnp.uint32(C)
            + jnp.arange(C, dtype=jnp.uint32)[None, :])

    bits = jnp.arange(nbits, dtype=jnp.uint32)
    bitmask = (jnp.uint32(1) << bits)                       # (nbits,)
    corrected = stored_u32 ^ mask_u32
    rewrite = (mask_u32[..., None] & bitmask) != 0          # (R,C,nbits)
    to_ap = rewrite & ((corrected[..., None] & bitmask) != 0)

    u = _uniform_bits_all(seed[0], elem, nbits)
    thr = jnp.where(to_ap, thr01, thr10)
    fail = rewrite & (u < thr)

    fail_mask = jnp.sum(jnp.where(fail, bitmask, jnp.uint32(0)), axis=-1,
                        dtype=jnp.uint32)
    scrubbed = corrected ^ fail_mask

    return scrubbed, fail_mask, plane_stats(
        *plane_counts(rewrite, to_ap, fail), e01, e10)
