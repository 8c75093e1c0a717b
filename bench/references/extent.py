"""Plain reference of the EXTENT approximate write, as the served cache
uses it (arXiv:2208.07838, section III; the configuration's driver levels).

A write changes only the bits that differ between the old word and the
new one. Each changed bit is driven at its bit plane's priority level:
a bit written 0 -> 1 costs ``E01[level]`` pJ and fails (keeps 0) with
probability ``WER01[level]``. Sign and exponent bits are at the exact
level; the mantissa of K is at MID in its upper half and LOW in its lower
half, the mantissa of V at LOW.

The numbers below are the driver levels of the configuration the cells
serve (the program's default driver, calibrated to the paper's Table 1),
written out here so that the comparison depends on no table the program
makes. A decode step writes a column that the admission zeroed, so every
1 bit of the new word is a 0 -> 1 write, which the store keeps with
probability ``1 - WER01``. ``column_expectation`` prices such columns from
the words the store holds; ``mantissa_losses`` counts the 1 bits that the
store lost against the reference's words, in V's upper mantissa
(``LOSS_PLANES``), whose failures (at LOW) stand out from the program's
rounding. K's upper mantissa fails at MID, too rarely to read there, and
the lowest planes of both are set by the program's bf16 rounding (a sum
of two bf16 words is often a tie, which rounds to an even last bit).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

# per priority code 0 (LOW) .. 3 (EXACT)
WER01 = (0.06317764520645142, 0.0015273094177246094,
         2.3186206817626953e-05, 5.960464477539063e-08)
E01 = (8.510912895202637, 9.976593017578125, 12.203871726989746,
       12.956459045410156)
# priority code of each bit of a bf16 word, least significant first
PLANES = {"k": (0, 0, 0, 1, 1, 1, 1) + (3,) * 9,
          "v": (0,) * 7 + (3,) * 9}
MANTISSA = 7
# the V planes ``mantissa_losses`` reads
LOSS_PLANES = (3, 4, 5, 6)


def _bits(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16),
                                        jnp.uint16).astype(jnp.int32)


def _plane(words: jax.Array, b: int) -> jax.Array:
    return (words >> b) & 1


def column_expectation(leaf: str, stored: jax.Array, where: jax.Array
                       ) -> Dict[str, jax.Array]:
    """Energy (pJ) and failed bits that writing the new words over zeros
    cost, predicted from the words ``stored`` after the write, summed over
    the elements where ``where`` holds: a plane's stored 1 bits are the
    new 1 bits that did not fail."""
    w = _bits(stored)
    energy = jnp.zeros((), jnp.float32)
    errors = jnp.zeros((), jnp.float32)
    for b, code in enumerate(PLANES[leaf]):
        new_ones = jnp.sum(_plane(w, b) * where, dtype=jnp.float32) / (
            1.0 - WER01[code])
        energy = energy + new_ones * E01[code]
        errors = errors + new_ones * WER01[code]
    return {"energy_pj": energy, "errors": errors}


def mantissa_losses(ref: jax.Array, stored: jax.Array, where: jax.Array
                    ) -> Dict[str, jax.Array]:
    """Over V's planes ``LOSS_PLANES`` of the elements where ``where``
    holds and the stored sign and exponent equal the reference's:
    ``net``, the 1 bits of the reference that the store holds as 0, less
    the 0 bits it holds as 1; and ``expected``, the failed 0 -> 1 writes
    the driver levels predict for the reference's 1 bits. Rounding moves
    these bits both ways alike, so ``net`` counts the write's failures."""
    r, s = _bits(ref), _bits(stored)
    m = where & ((r >> MANTISSA) == (s >> MANTISSA))
    net = jnp.zeros((), jnp.float32)
    expected = jnp.zeros((), jnp.float32)
    for b in LOSS_PLANES:
        rb, sb = _plane(r, b), _plane(s, b)
        net = net + jnp.sum(m * (rb - sb), dtype=jnp.float32)
        expected = expected + WER01[PLANES["v"][b]] * jnp.sum(
            m * rb, dtype=jnp.float32)
    return {"net": net, "expected": expected}
