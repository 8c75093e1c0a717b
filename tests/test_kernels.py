"""Pallas extent_write kernel: interpret-mode sweeps vs. the pure-jnp oracle.

Every (shape x dtype x level) cell asserts bit-exact agreement of the stored
tensor and exact agreement of the stats — kernel and ref share the counter
RNG, so there is no tolerance to hide behind.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.priority import Priority
from repro.kernels.extent_write import (extent_write, extent_write_kernel,
                                        extent_write_ref)
from repro.kernels.extent_write import ops as X

SHAPES = [(8,), (128,), (100, 130), (64, 128), (7, 3, 11), (256, 512),
          (1, 1), (513,)]
DTYPES = [jnp.bfloat16, jnp.float16, jnp.float32]
LEVELS = [Priority.LOW, Priority.MID, Priority.HIGH, Priority.EXACT]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_kernel_matches_ref(shape, dtype):
    key = jax.random.PRNGKey(
        zlib.crc32(repr((shape, jnp.dtype(dtype).name)).encode()) % 2**31)
    k1, k2, k3 = jax.random.split(key, 3)
    old = jax.random.normal(k1, shape).astype(dtype)
    new = jax.random.normal(k2, shape).astype(dtype)
    sk, stk = extent_write(k3, old, new, level=Priority.LOW,
                           use_kernel=True, block=(64, 128))
    sr, st_r = extent_write(k3, old, new, level=Priority.LOW,
                            use_kernel=False, block=(64, 128))
    assert sk.shape == shape and sk.dtype == old.dtype
    assert bool(jnp.all(sk == sr)), "stored tensors must match bit-exactly"
    for k in stk:
        # both reduce to exact per-plane counts priced by one function:
        # every stat, energy included, agrees exactly
        assert float(stk[k]) == float(st_r[k]), k


@pytest.mark.parametrize("level", LEVELS)
def test_levels(level):
    key = jax.random.PRNGKey(0)
    old = jax.random.normal(jax.random.PRNGKey(1), (64, 256)).astype(jnp.bfloat16)
    new = jax.random.normal(jax.random.PRNGKey(2), (64, 256)).astype(jnp.bfloat16)
    sk, stk = extent_write(key, old, new, level=level, use_kernel=True,
                           block=(64, 128))
    sr, st_r = extent_write(key, old, new, level=level, use_kernel=False,
                            block=(64, 128))
    assert bool(jnp.all(sk == sr))
    if level == Priority.EXACT:
        assert int(stk["errors"]) == 0 and bool(jnp.all(sk == new))


def test_error_rate_ordering_across_levels():
    key = jax.random.PRNGKey(3)
    old = jax.random.normal(jax.random.PRNGKey(4), (256, 512)).astype(jnp.bfloat16)
    new = jax.random.normal(jax.random.PRNGKey(5), (256, 512)).astype(jnp.bfloat16)
    errs = []
    for level in LEVELS:
        _, st = extent_write(key, old, new, level=level, block=(64, 128))
        errs.append(int(st["errors"]))
    assert errs[0] > errs[1] > errs[2] >= errs[3] == 0


def test_determinism_same_key():
    key = jax.random.PRNGKey(6)
    old = jax.random.normal(jax.random.PRNGKey(7), (128, 128)).astype(jnp.float32)
    new = jax.random.normal(jax.random.PRNGKey(8), (128, 128)).astype(jnp.float32)
    a, _ = extent_write(key, old, new, level=Priority.LOW, block=(64, 128))
    b, _ = extent_write(key, old, new, level=Priority.LOW, block=(64, 128))
    assert bool(jnp.all(a == b))
    c, _ = extent_write(jax.random.PRNGKey(9), old, new, level=Priority.LOW,
                        block=(64, 128))
    assert not bool(jnp.all(a == c)), "different keys -> different draws"


def test_block_row_partition_invariance():
    """Same lane layout (same block width) -> identical results regardless
    of how rows are partitioned into grid blocks."""
    key = jax.random.PRNGKey(10)
    old = jax.random.normal(jax.random.PRNGKey(11), (256, 128)).astype(jnp.float32)
    new = jax.random.normal(jax.random.PRNGKey(12), (256, 128)).astype(jnp.float32)
    a, sa = extent_write(key, old, new, level=Priority.MID, block=(32, 128))
    b, sb = extent_write(key, old, new, level=Priority.MID, block=(128, 128))
    assert bool(jnp.all(a == b))
    assert {k: float(v) for k, v in sa.items()} == {
        k: float(v) for k, v in sb.items()}


def test_padding_lanes_are_free():
    """Ragged sizes pad to block multiples; padding lanes (0 -> 0) must add
    no flips, no energy, no errors."""
    key = jax.random.PRNGKey(13)
    x = jax.random.normal(jax.random.PRNGKey(14), (100,)).astype(jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(15), (100,)).astype(jnp.float32)
    _, st = extent_write(key, x, y, level=Priority.EXACT, block=(8, 128))
    # flips must equal the exact popcount of the xor on the 100 real lanes
    xu = np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint32))
    yu = np.asarray(jax.lax.bitcast_convert_type(y, jnp.uint32))
    flips = int(sum(bin(int(a ^ b)).count("1") for a, b in zip(xu, yu)))
    assert int(st["flips01"] + st["flips10"]) == flips


def test_raw_kernel_call_shapes():
    """Direct pallas_call: one (8, 128) per-plane count tile per grid
    block comes back, and pricing the tiles gives the energy."""
    from repro.kernels.extent_write.kernel import ROW_ERR, ROW_F01, ROW_F10
    R, C, block = 128, 256, (64, 128)
    old = jnp.zeros((1, R, C), jnp.uint32)   # one group: the whole tensor
    new = jnp.full((1, R, C), 0xF, jnp.uint32)
    thr = jnp.zeros((32,), jnp.uint32)
    e = jnp.ones((32,), jnp.float32)
    seed = jnp.zeros((1,), jnp.uint32)
    stored, tiles = extent_write_kernel(old, new, seed, thr, thr, nbits=32,
                                        block=block)
    gr, gc = R // block[0], C // block[1]
    assert stored.shape == (1, R, C)
    assert tiles.shape == (1, gr * 8, gc * 128)
    counts = np.asarray(tiles).reshape(gr, 8, gc, 128).sum(axis=(0, 2))
    # planes 0-3 flip 0->1 in every lane, no other plane moves
    np.testing.assert_array_equal(counts[ROW_F01, :32],
                                  [R * C] * 4 + [0] * 28)
    assert int(counts[ROW_F10].sum()) == int(counts[ROW_ERR].sum()) == 0
    assert int(counts[3:].sum()) == int(counts[:, 32:].sum()) == 0
    st = X.tile_stats(tiles, 32, e, e)
    assert int(st["flips01"]) == R * C * 4  # 4 bits set per lane
    assert int(st["errors"]) == 0
    assert float(st["energy_pj"]) == R * C * 4.0


def test_uniform_bits_distribution():
    """Counter RNG sanity: mean/std of the 24 high bits ~ U[0, 2^32)."""
    from repro.kernels.extent_write.kernel import uniform_bits
    idx = jnp.arange(65536, dtype=jnp.uint32).reshape(256, 256)
    u = uniform_bits(jnp.uint32(1234), idx, 3).astype(jnp.float32) * np.float32(2.0 ** -32)
    assert abs(float(u.mean()) - 0.5) < 0.01
    assert abs(float(u.std()) - (1 / 12) ** 0.5) < 0.01


@pytest.mark.parametrize("platform,interpret", [("tpu", False),
                                                ("cpu", True),
                                                ("gpu", True)])
def test_interpret_follows_the_platform(monkeypatch, platform, interpret):
    """One platform check decides every entry point's default: native on
    a TPU, the interpreter elsewhere; an explicit choice passes through."""
    from repro.kernels import platform as P
    monkeypatch.setattr(P.jax, "default_backend", lambda: platform)
    assert P.resolve_interpret() is interpret
    assert P.resolve_interpret(None) is interpret
    assert P.resolve_interpret(True) is True
    assert P.resolve_interpret(False) is False


@pytest.mark.parametrize("n_shards", [2, 4])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_a_shard_stores_the_whole_tensors_bits(n_shards, dtype):
    """A kernel call on one shard of a tensor split along its slot axis,
    told where the shard sits (``Shard``), stores exactly the bits the
    whole-tensor call stores there, and the shards' counts add up to the
    whole call's — the write and the scrub kernel alike."""
    from repro.core.priority import uint_type
    from repro.kernels.scrub.ops import scrub_write
    shape = (3, 4, 5, 2, 8)          # (layers, slots, seq, heads, dim)
    k1, k2, k3, key = jax.random.split(jax.random.PRNGKey(n_shards), 4)
    old = jax.random.normal(k1, shape).astype(dtype)
    new = jax.random.normal(k2, shape).astype(dtype)
    ut = uint_type(dtype)
    mask = (jax.random.bits(k3, shape, ut) & jax.random.bits(k1, shape, ut))
    vec = X.level_vectors(jnp.dtype(dtype), Priority.LOW)
    # stored words are compared as bits, viewed before any float op
    # touches them (a scrubbed f32 word may be a NaN or a subnormal)
    bits = lambda a: jax.lax.bitcast_convert_type(a, uint_type(a.dtype))

    def both(old, new, mask, shard=None):
        w_stored, w_st = extent_write(key, old, new, vectors=vec, shard=shard)
        s_stored, residual, s_st = scrub_write(key, old, mask, vectors=vec,
                                               shard=shard)
        return (bits(w_stored), bits(s_stored), residual), (w_st, s_st)

    whole, whole_stats = both(old, new, mask)
    per = shape[1] // n_shards
    inner = int(np.prod(shape[2:]))
    counts = np.zeros((2, 3), np.int64)
    names = ("flips01", "flips10", "errors")
    for k in range(n_shards):
        cut = lambda a: a[:, k * per:(k + 1) * per]
        shard = X.Shard(groups=shape[0], stride=shape[1] * inner,
                        first=jnp.int32(k * per * inner))
        got, stats = both(cut(old), cut(new), cut(mask), shard)
        for g, w in zip(got, whole):
            assert bool(jnp.all(g == cut(w)))
        counts += [[int(st[n]) for n in names] for st in stats]
    assert counts.tolist() == [[int(st[n]) for n in names]
                               for st in whole_stats]


@pytest.fixture(scope="module")
def slot_mesh_check():
    """tests/sharded_write_check.py in a process of four virtual CPU
    devices (this process keeps its one device)."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path
    root = Path(__file__).parent.parent
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run(
        [sys.executable, "tests/sharded_write_check.py"], cwd=str(root),
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("op", ["write_columns", "write", "admission_row",
                                "scrub"])
def test_kernels_over_a_slot_mesh_equal_one_device(slot_mesh_check, op):
    """Mapped over a four-device slot mesh, the kernels store the bits and
    count the stats of the one-device call (equal inputs)."""
    assert slot_mesh_check[op] is True


def test_column_write_over_a_slot_mesh_gathers_nothing(slot_mesh_check):
    """Each device writes its own slots: the compiled column write over a
    four-device slot mesh holds no all-gather."""
    assert slot_mesh_check["all_gathers_in_column_write"] == 0
