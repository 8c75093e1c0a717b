"""Three-way parity: Pallas kernel vs. lane ref vs. the eager bit-unpacked
oracle (``approx_store.approx_write_with_stats``) — on shapes that are NOT
block multiples, so the padding lanes and the 2-elements-per-uint32-lane
packing of 16-bit dtypes are exercised.

Kernel and ref share the counter RNG, so those two must agree bit-exactly.
The eager oracle draws from ``jax.random`` instead, so parity with it is
asserted on every RNG-independent quantity: flip counts (by direction),
bits_written/bits_total, and energy (deterministic given the flips); plus
the write-semantics invariant that every stored bit comes from old or new.

The second half runs the same contract through the ``repro.memory``
substrate: a backend-parity matrix (oracle vs lanes_ref vs pallas) over
ragged shapes and bf16/f32/int8 — one unified WriteStats schema, exact
flip/energy equality across ALL modeled backends.
"""
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import memory
from repro.core import approx_store as aps
from repro.core.priority import Priority, uint_type
from repro.kernels.extent_write import extent_write

# deliberately ragged: odd element counts (odd u16 lane pairing for bf16,
# odd u8 quads for int8), sizes far from the (8, 128) test block =
# 1024-lane multiples
RAGGED_SHAPES = [(5,), (33,), (7, 19), (3, 5, 11), (129,), (100, 3)]
DTYPES = [jnp.float32, jnp.bfloat16]
BLOCK = (8, 128)

MODELED_BACKENDS = ("oracle", "lanes_ref", "pallas")


def _case_key(*case) -> jax.Array:
    """PRNG key from a stable digest of the test case (``hash`` of a str
    is salted per process, so each xdist worker would draw other data)."""
    return jax.random.PRNGKey(zlib.crc32(repr(case).encode()) % 2**31)


def _f32_sum_rtol(n_terms: int) -> float:
    """How far two f32 sums of the same ``n_terms`` non-negative terms, in
    different orders, can differ relatively: each is within (n - 1) unit
    roundoffs of the exact sum, so together within (n - 1) * eps."""
    return max(n_terms - 1, 1) * float(np.finfo(np.float32).eps)


def _rand_pair(shape, dtype, key):
    k1, k2 = jax.random.split(key)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        lo, hi = jnp.iinfo(dtype).min, jnp.iinfo(dtype).max + 1
        return (jax.random.randint(k1, shape, lo, hi, jnp.int32
                                   ).astype(dtype),
                jax.random.randint(k2, shape, lo, hi, jnp.int32
                                   ).astype(dtype))
    return (jax.random.normal(k1, shape).astype(dtype),
            jax.random.normal(k2, shape).astype(dtype))


@pytest.mark.parametrize("shape", RAGGED_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("level", [Priority.LOW, Priority.MID])
def test_kernel_ref_oracle_parity(shape, dtype, level):
    key = _case_key(shape, jnp.dtype(dtype).name, int(level))
    k1, k2, k3 = jax.random.split(key, 3)
    old = jax.random.normal(k1, shape).astype(dtype)
    new = jax.random.normal(k2, shape).astype(dtype)

    s_k, st_k = extent_write(k3, old, new, level=level, use_kernel=True,
                             block=BLOCK)
    s_r, st_r = extent_write(k3, old, new, level=level, use_kernel=False,
                             block=BLOCK)
    _, st_o = aps.approx_write_with_stats(k3, old, new, level)

    # kernel vs ref: same RNG -> bit-exact store, identical stats (both
    # reduce to exact per-plane counts and price them the same way)
    assert s_k.shape == shape and s_k.dtype == jnp.dtype(dtype)
    assert bool(jnp.all(s_k == s_r))
    for k in st_k:
        assert float(st_k[k]) == float(st_r[k]), k

    # vs the eager oracle: all deterministic accounting must agree exactly
    assert int(st_k["flips01"]) == int(st_o.flips_0to1)
    assert int(st_k["flips10"]) == int(st_o.flips_1to0)
    assert int(st_k["bits_written"]) == int(st_o.bits_written)
    assert int(st_k["bits_total"]) == int(st_o.bits_total)
    # the oracle sums one f32 energy per flipped bit in its own order
    np.testing.assert_allclose(
        float(st_k["energy_pj"]), float(st_o.energy_pj),
        rtol=_f32_sum_rtol(int(st_k["bits_written"])))

    # write semantics: stored bits come from old or new, never elsewhere
    ut = uint_type(dtype)
    o = jax.lax.bitcast_convert_type(old, ut)
    n = jax.lax.bitcast_convert_type(new, ut)
    s = jax.lax.bitcast_convert_type(s_k, ut)
    assert bool(jnp.all((s ^ n) & (s ^ o) == 0))
    assert int(st_k["errors"]) <= int(st_k["bits_written"])


def test_error_rate_tracks_oracle_statistically():
    """Different RNG streams, same thresholds: realized error rates of the
    lane path and the eager oracle must agree within sampling noise on a
    large tensor (LOW level, ~65k flips -> ~1/sqrt(N) ≈ 2%)."""
    key = jax.random.PRNGKey(99)
    k1, k2, k3 = jax.random.split(key, 3)
    old = jax.random.normal(k1, (4096,)).astype(jnp.float32)
    new = jax.random.normal(k2, (4096,)).astype(jnp.float32)
    _, st_l = extent_write(k3, old, new, level=Priority.LOW,
                           use_kernel=False, block=BLOCK)
    _, st_o = aps.approx_write_with_stats(k3, old, new, Priority.LOW)
    ber_lane = float(st_l["errors"]) / float(st_l["bits_written"])
    ber_oracle = float(st_o.bit_errors) / float(st_o.bits_written)
    np.testing.assert_allclose(ber_lane, ber_oracle, rtol=0.2)


def test_bits_total_survives_huge_tensors():
    """bits_total is f32 shape metadata: a tensor holding >= 2^31 bits must
    trace without an int32 OverflowError (256 MiB+ cache leaves exist)."""
    big = jax.eval_shape(lambda: jnp.zeros((1 << 28,), jnp.float32))
    out = jax.eval_shape(
        lambda a, b: extent_write(jax.random.PRNGKey(0), a, b,
                                  level=Priority.LOW,
                                  use_kernel=False)[1]["bits_total"],
        big, big)
    assert out.dtype == jnp.float32


# ---------------------------------------------------------------------------
# the substrate API: backend-parity matrix over ragged shapes x dtypes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(5,), (33,), (7, 19), (129,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("level", [Priority.LOW, Priority.MID])
def test_backend_parity_matrix(shape, dtype, level):
    """oracle vs lanes_ref vs pallas through repro.memory.write: identical
    stats schema, bit-exact flip counts and (to f32 reduction order) equal
    energy; lanes_ref and pallas share the counter RNG so their stored
    tensors and realized errors are bit-identical too."""
    key = _case_key(shape, jnp.dtype(dtype).name, int(level))
    k1, k2 = jax.random.split(key)
    old, new = _rand_pair(shape, dtype, k1)

    out = {}
    for name in MODELED_BACKENDS:
        stored, st = memory.write(k2, old, new, level=level, backend=name)
        assert isinstance(st, memory.WriteStats)  # ONE schema everywhere
        assert stored.shape == shape and stored.dtype == jnp.dtype(dtype)
        # write semantics: every stored bit comes from old or new
        ut = uint_type(dtype)
        o = jax.lax.bitcast_convert_type(old, ut)
        n = jax.lax.bitcast_convert_type(new, ut)
        s = jax.lax.bitcast_convert_type(stored, ut)
        assert bool(jnp.all((s ^ n) & (s ^ o) == 0)), name
        out[name] = (stored, st)

    ref = out["oracle"][1]
    for name in ("lanes_ref", "pallas"):
        st = out[name][1]
        assert int(st.flips01) == int(ref.flips01), name
        assert int(st.flips10) == int(ref.flips10), name
        assert float(st.bits_total) == float(ref.bits_total) == float(
            np.prod(shape) * jnp.dtype(dtype).itemsize * 8)
        np.testing.assert_allclose(float(st.energy_pj),
                                   float(ref.energy_pj), rtol=1e-5,
                                   err_msg=name)
        assert int(st.errors) <= int(st.bits_written)
    # same counter RNG: lanes_ref == pallas bit-for-bit, errors included
    assert bool(jnp.all(out["lanes_ref"][0] == out["pallas"][0]))
    assert int(out["lanes_ref"][1].errors) == int(out["pallas"][1].errors)


def test_exact_backend_is_passthrough():
    old, new = _rand_pair((33,), jnp.bfloat16, jax.random.PRNGKey(3))
    stored, st = memory.write(jax.random.PRNGKey(4), old, new,
                              level=Priority.LOW, backend="exact")
    assert bool(jnp.all(stored == new))
    h = st.host_dict()
    assert h["energy_pj"] == 0.0 and h["bits_written"] == 0
    assert h["bit_errors"] == 0 and h["bits_total"] == 33 * 16


def test_unknown_backend_raises_with_listing():
    with pytest.raises(KeyError, match="lanes_ref"):
        memory.get_backend("no_such_backend")


def test_write_stats_schema_and_reduction():
    """WriteStats adds losslessly (counters/energy sum, latency max) and
    the schema is identical across backends."""
    old, new = _rand_pair((64,), jnp.float32, jax.random.PRNGKey(5))
    _, a = memory.write(jax.random.PRNGKey(6), old, new,
                        level=Priority.LOW, backend="lanes_ref")
    _, b = memory.write(jax.random.PRNGKey(7), old, new,
                        level=Priority.EXACT, backend="oracle")
    assert {f.name for f in dataclasses.fields(a)} == {
        f.name for f in dataclasses.fields(b)}
    tot = a + b
    assert int(tot.flips01) == int(a.flips01) + int(b.flips01)
    # energy adds in f32 on device: compare at f32 resolution
    np.testing.assert_allclose(float(tot.energy_pj),
                               float(a.energy_pj) + float(b.energy_pj),
                               rtol=1e-6)
    assert float(tot.latency_ns) == max(float(a.latency_ns),
                                        float(b.latency_ns))
    assert float(tot.bits_total) == float(a.bits_total) + float(b.bits_total)


def test_legacy_wrapper_matches_oracle_backend_bit_exactly():
    """approx_write_with_stats (the seed API) and the oracle backend draw
    the same RNG and must produce identical stored bits and accounting."""
    key = jax.random.PRNGKey(8)
    old, new = _rand_pair((40, 9), jnp.bfloat16, jax.random.PRNGKey(9))
    s1, st1 = aps.approx_write_with_stats(key, old, new, Priority.LOW)
    s2, st2 = memory.write(key, old, new, level=Priority.LOW,
                           backend="oracle")
    assert bool(jnp.all(s1 == s2))
    assert float(st1.energy_pj) == float(st2.energy_pj)
    assert int(st1.bit_errors) == int(st2.errors)
    assert int(st1.bits_written) == int(st2.bits_written)
    assert float(st1.latency_ns) == float(st2.latency_ns)


def test_bf16_odd_element_count_roundtrips():
    """Odd bf16 element counts pad half a lane; the pad must never leak
    into the stored tensor nor the accounting."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(jax.random.PRNGKey(6), (33,)).astype(jnp.bfloat16)
    stored, st = extent_write(key, x, x, level=Priority.LOW, block=BLOCK)
    assert bool(jnp.all(stored == x))         # identical write: CMP skips all
    assert int(st["bits_written"]) == 0
    assert float(st["energy_pj"]) == 0.0
    assert int(st["errors"]) == 0
    assert int(st["bits_total"]) == 33 * 16   # real bits only, no pad lanes
