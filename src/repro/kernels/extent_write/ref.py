"""Pure-jnp oracle for the extent_write Pallas kernel.

Implements the identical semantics (same murmur3 counter RNG, same bit
algebra, same stats) with plain jnp ops over the unpacked (elements x nbits)
tensor — the slow-but-obviously-correct reference every kernel test
asserts against bit-exactly.

``plane_stats`` is the one place per-bit-plane counts become stats: the
kernel wrappers (ops.py) and these references both reduce to exact integer
counts per plane and price them here, so energy agrees bit-for-bit across
the kernel, the reference and any block partition.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.extent_write.kernel import _hash_u32, _K_BIT, _K_ELEM


def _uniform_bits_all(seed: jax.Array, elem: jax.Array,
                      nbits: int) -> jax.Array:
    """(R, C, nbits) counter-RNG draws — the vectorized form of the
    kernel's per-bit-plane ``uniform_bits``, bit-identical by construction
    (same hash over (seed, flat element index, bit plane))."""
    bits = jnp.arange(nbits, dtype=jnp.uint32)
    h = (elem.astype(jnp.uint32)[..., None] * _K_ELEM
         ^ (bits * _K_BIT) ^ seed.astype(jnp.uint32))
    return _hash_u32(h)


def plane_stats(c01: jax.Array, c10: jax.Array, err: jax.Array,
                e01: jax.Array, e10: jax.Array) -> Dict[str, jax.Array]:
    """Stats from (nbits,) int32 per-plane counts of 0->1 flips, 1->0 flips
    and failures. Energy is priced per plane and summed plane by plane in
    a fixed order (an unrolled chain, never a reduction whose order the
    compiler picks), so equal counts give bit-equal energy everywhere."""
    terms = (c01.astype(jnp.float32) * e01.astype(jnp.float32)
             + c10.astype(jnp.float32) * e10.astype(jnp.float32))
    energy = terms[0]
    for b in range(1, terms.shape[0]):
        energy = energy + terms[b]
    return {"energy_pj": energy,
            "flips01": jnp.sum(c01, dtype=jnp.int32),
            "flips10": jnp.sum(c10, dtype=jnp.int32),
            "errors": jnp.sum(err, dtype=jnp.int32)}


def plane_counts(rewrite, to_ap, fail):
    """(R, C, nbits) bool planes -> (nbits,) int32 (0->1, 1->0, failed)."""
    count = lambda m: jnp.sum(m, axis=(0, 1), dtype=jnp.int32)
    return count(to_ap), count(rewrite & ~to_ap), count(fail)


def extent_write_ref(
    old_u32: jax.Array,   # (R, C) uint32
    new_u32: jax.Array,
    seed: jax.Array,      # (1,) uint32
    thr01: jax.Array,     # (nbits,) uint32
    thr10: jax.Array,
    e01: jax.Array,       # (nbits,) f32
    e10: jax.Array,
    *,
    nbits: int,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    R, C = old_u32.shape
    elem = (jnp.arange(R, dtype=jnp.uint32)[:, None] * jnp.uint32(C)
            + jnp.arange(C, dtype=jnp.uint32)[None, :])

    bits = jnp.arange(nbits, dtype=jnp.uint32)
    mask = (jnp.uint32(1) << bits)                       # (nbits,)
    diff = old_u32 ^ new_u32
    flip = (diff[..., None] & mask) != 0                  # (R,C,nbits)
    to_ap = flip & ((new_u32[..., None] & mask) != 0)

    u = _uniform_bits_all(seed[0], elem, nbits)
    thr = jnp.where(to_ap, thr01, thr10)
    fail = flip & (u < thr)

    fail_mask = jnp.sum(jnp.where(fail, mask, jnp.uint32(0)), axis=-1,
                        dtype=jnp.uint32)
    stored = new_u32 ^ fail_mask

    return stored, plane_stats(*plane_counts(flip, to_ap, fail), e01, e10)
