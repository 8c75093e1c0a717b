"""Faults planted in the timed path, each of which the comparison that
decides ``correct`` must catch. The tests plant them on the CPU at a
reduced size (``bench/tests/test_rehearsal.py``); ``bench/tests/
chip_readings.py`` plants them on the chip at a cell's own size, where
their readings set the upper ends of the limits.

A fault is a ``ServeConfig`` override (``serve``) and a ``patch`` of the
built engine (or None)."""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp


class Fault(NamedTuple):
    serve: Dict[str, Any]
    patch: Optional[Callable[[Any], None]]


def _state_unchanged(eng):
    """Each burst returns the cache it was given."""
    real = eng._burst

    def burst(params, tok, cache, *a, **k):
        out = real(params, tok, cache, *a, **k)
        return (out[0], cache) + tuple(out[2:])
    eng._burst = burst


def _token_altered(eng):
    """Each burst's tokens are changed where they are produced."""
    real = eng._burst
    vocab = eng.cfg.vocab_size

    def burst(*a, **k):
        out = real(*a, **k)
        return tuple(out[:-1]) + ((out[-1] + 1) % vocab,)
    eng._burst = burst


def _first_token_altered(eng):
    """Each admission's first token is changed where it is produced."""
    real = eng._admit_fused
    vocab = eng.cfg.vocab_size

    def admit(*a, **k):
        tok, rows, key, acc = real(*a, **k)
        return (tok + 1) % vocab, rows, key, acc
    eng._admit_fused = admit


@jax.jit
def _short_mantissa(cache):
    """bf16 words cut to the three mantissa bits a float8 (e4m3) word
    keeps. Done on the bits: XLA may fold a round trip of conversions
    through float8 into nothing."""
    def cut(x):
        if x.dtype != jnp.bfloat16:
            return x
        w = jax.lax.bitcast_convert_type(x, jnp.uint16)
        return jax.lax.bitcast_convert_type(w & jnp.uint16(0xFFF0), x.dtype)
    return jax.tree.map(cut, cache)


def _fp8_store(eng):
    """The store keeps K/V at float8 (e4m3) precision: after each burst
    every cached word loses its four lowest mantissa bits."""
    real = eng._burst

    def burst(params, tok, cache, *a, **k):
        out = real(params, tok, cache, *a, **k)
        return (out[0], _short_mantissa(out[1])) + tuple(out[2:])
    eng._burst = burst


FAULTS: Dict[str, Fault] = {
    "state_unchanged": Fault({}, _state_unchanged),
    "token_altered": Fault({}, _token_altered),
    "first_token_altered": Fault({}, _first_token_altered),
    # the write stores the new words as they are: no approximation
    "exact_write": Fault({"backend": "exact"}, None),
    "fp8_store": Fault({}, _fp8_store),
}
