"""Public jit'd wrapper for the extent_write kernel.

Handles dtype bitcasting (int8/uint8 pack 4 elements per uint32 lane,
bf16/f16 pack 2, f32/int32 map 1:1), padding to block multiples,
level-table -> threshold conversion, and reduction of the per-block
per-plane count tiles. ``use_kernel=False`` routes to the ref oracle (same
semantics, same stats bit-for-bit). ``interpret=None`` follows the
platform: native on a TPU, the Pallas interpreter elsewhere
(``repro.kernels.platform``).

This module is kernel-internal plumbing: everything outside
``repro/kernels`` and ``repro/memory`` goes through the backend registry in
``repro.memory`` instead of calling ``extent_write`` directly.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import write_driver
from repro.core.priority import Priority, bitplane_priorities
from repro.kernels.extent_write import kernel as K
from repro.kernels.extent_write import ref as R
from repro.kernels.platform import resolve_interpret


@functools.lru_cache(maxsize=64)
def level_vectors(dtype, level: Priority,
                  cfg: Optional[write_driver.DriverConfig] = None):
    """Per-bit-plane (thr01, thr10, e01, e10) for one element dtype, with the
    bit-plane priority policy applied, then widened to the uint32 lane
    layout (2x16-bit elements per lane for 16-bit dtypes).

    Public so jit-resident callers (serve engine, approx_store's lane path)
    can resolve a tensor's priority to driver vectors once, outside the
    traced region: the vectors are plain arrays, so changing a tensor's
    priority swaps constants without retracing the write computation.
    The driver calibration is Python-float code, so it is forced to
    compile-time evaluation — safe to call (via lru_cache, once) even while
    tracing an enclosing jit."""
    with jax.ensure_compile_time_eval():
        table = write_driver.level_table(cfg or write_driver.DriverConfig())
        codes = bitplane_priorities(dtype, Priority.coerce(level))  # (ebits,)
        wer01 = np.asarray(table["wer01"])[codes]
        wer10 = np.asarray(table["wer10"])[codes]
        e01 = np.asarray(table["e01"])[codes]
        e10 = np.asarray(table["e10"])[codes]
        ebits = codes.shape[0]
        if ebits in (8, 16):  # 4 (or 2) elements per uint32 lane: tile the
            reps = 32 // ebits  # per-element bit pattern across the lane
            wer01 = np.tile(wer01, reps)
            wer10 = np.tile(wer10, reps)
            e01 = np.tile(e01, reps)
            e10 = np.tile(e10, reps)
        to_thr = lambda w: (np.clip(w, 0.0, 1.0) * 2**32).astype(
            np.uint64).clip(0, 2**32 - 1).astype(np.uint32)
        return (jnp.asarray(to_thr(wer01)), jnp.asarray(to_thr(wer10)),
                jnp.asarray(e01, jnp.float32), jnp.asarray(e10, jnp.float32))


_level_vectors = level_vectors  # backwards-compatible alias


def _words(x: jax.Array, ut) -> jax.Array:
    """``x``'s words as unsigned ints, fenced so that nothing moves them as
    floats. A stored word may be a NaN with a payload or a subnormal, and
    XLA on a TPU may carry a reshape or slice past a bitcast and run it as
    float arithmetic (a reshape compiled to an f32 add-reduce), which
    canonicalizes NaNs and flushes subnormals."""
    return jax.lax.optimization_barrier(jax.lax.bitcast_convert_type(x, ut))


def _view(words: jax.Array, shape, dtype) -> jax.Array:
    """Unsigned words, shaped as integers, then viewed as ``dtype``
    (the other side of ``_words``)."""
    return jax.lax.bitcast_convert_type(
        jax.lax.optimization_barrier(words.reshape(shape)), dtype)


def _to_lanes(x: jax.Array) -> Tuple[jax.Array, int]:
    """Bitcast any 1/2/4-byte tensor into a flat uint32 lane vector
    (little-endian element packing for the sub-word dtypes)."""
    nbytes = jnp.dtype(x.dtype).itemsize
    if nbytes == 4:
        return _words(x, jnp.uint32).reshape(-1), x.size
    if nbytes == 2:
        u16 = _words(x, jnp.uint16).reshape(-1)
        if u16.size % 2:
            u16 = jnp.concatenate([u16, jnp.zeros((1,), jnp.uint16)])
        pair = u16.reshape(-1, 2).astype(jnp.uint32)
        return pair[:, 0] | (pair[:, 1] << 16), x.size
    assert nbytes == 1, x.dtype
    u8 = _words(x, jnp.uint8).reshape(-1)
    pad = (-u8.size) % 4
    if pad:
        u8 = jnp.concatenate([u8, jnp.zeros((pad,), jnp.uint8)])
    quad = u8.reshape(-1, 4).astype(jnp.uint32)
    return (quad[:, 0] | (quad[:, 1] << 8) | (quad[:, 2] << 16)
            | (quad[:, 3] << 24)), x.size


def _from_lanes(u: jax.Array, shape, dtype) -> jax.Array:
    nbytes = jnp.dtype(dtype).itemsize
    n = int(np.prod(shape))
    if nbytes == 4:
        return _view(u[:n], shape, dtype)
    if nbytes == 2:
        lo = (u & 0xFFFF).astype(jnp.uint16)
        hi = (u >> 16).astype(jnp.uint16)
        return _view(jnp.stack([lo, hi], axis=-1).reshape(-1)[:n], shape,
                     dtype)
    assert nbytes == 1, dtype
    u8 = jnp.stack([(u >> (8 * k)).astype(jnp.uint8) for k in range(4)],
                   axis=-1).reshape(-1)[:n]
    return _view(u8, shape, dtype)


def tile_stats(tiles: jax.Array, nbits: int, e01: jax.Array,
               e10: jax.Array, axis: Optional[str] = None
               ) -> Dict[str, jax.Array]:
    """Sum the kernels' per-block (8, 128) count tiles (exact int32 adds),
    over the shards of mesh axis ``axis`` too when given, and price the
    per-plane totals like the reference does."""
    th, tw = K.STATS_TILE
    counts = jnp.sum(tiles.reshape(-1, th, tiles.shape[-1] // tw, tw),
                     axis=(0, 2), dtype=jnp.int32)[:, :nbits]
    if axis is not None:
        counts = jax.lax.psum(counts, axis)
    return R.plane_stats(counts[K.ROW_F01], counts[K.ROW_F10],
                         counts[K.ROW_ERR], e01, e10)


class Shard(NamedTuple):
    """The operands are one device's shard of a tensor split along one
    axis (a slot pool split over dies): ``groups`` equal runs of elements,
    run ``g`` starting at element ``g * stride + first`` of the whole
    tensor. The RNG then hashes the whole tensor's lane indices, so each
    shard stores the bits the whole-tensor call would; the per-plane
    counts are summed over mesh axis ``axis`` before pricing, so every
    shard returns the whole tensor's stats (``axis`` None: this shard's
    own)."""
    groups: int
    stride: int
    first: jax.Array  # () int32, e.g. from jax.lax.axis_index
    axis: Optional[str] = None


def _layout(shard: Optional[Shard], x: jax.Array):
    """Static (groups, lane stride, axis) and the traced lane offset of a
    shard, in uint32 lanes of ``x``'s dtype."""
    if shard is None:
        return None, None
    per = 4 // min(4, jnp.dtype(x.dtype).itemsize)  # elements per lane
    assert x.size % (shard.groups * per) == 0 and shard.stride % per == 0, (
        "a shard's runs must be whole uint32 lanes", x.shape, shard)
    lane0 = (jnp.asarray(shard.first, jnp.int32) // per).astype(jnp.uint32)
    return (shard.groups, shard.stride // per, shard.axis), lane0.reshape(1)


def lane_kernel(kernel_fn, lanes: Sequence[jax.Array], seed, vectors, *,
                block: Tuple[int, int], interpret: bool, layout, lane0):
    """Run a lane kernel (``extent_write_kernel`` / ``scrub_kernel``) on
    flat uint32 lane vectors: each of the ``layout`` groups (one without a
    shard) is laid out as (rows, cols) with only its rows padded, to the
    row block. Padding lanes change no bit, so they cost nothing. Returns
    the flat lane outputs and the priced stats."""
    thr01, thr10, e01, e10 = vectors
    nbits = int(thr01.shape[0])
    groups, stride, axis = layout if layout is not None else (1, 0, None)
    q = lanes[0].size // groups
    cols = block[1]
    rows_used = max(1, -(-q // cols))
    block_r = min(block[0], rows_used)
    rows = -(-rows_used // block_r) * block_r
    pad = rows * cols - q
    grid = [jnp.pad(x.reshape(groups, q), ((0, 0), (0, pad))).reshape(
        groups, rows, cols) for x in lanes]
    *outs, tiles = kernel_fn(*grid, seed, thr01, thr10, nbits=nbits,
                             block=(block_r, cols), lane0=lane0,
                             stride=stride, interpret=interpret)
    outs = [o.reshape(groups, rows * cols)[:, :q].reshape(-1) for o in outs]
    return outs, tile_stats(tiles, nbits, e01, e10, axis)


def extent_write(
    key: jax.Array,
    old: jax.Array,
    new: jax.Array,
    *,
    level: Priority = Priority.LOW,
    block: Tuple[int, int] = K.DEFAULT_BLOCK,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
    vectors: Optional[Tuple[jax.Array, jax.Array, jax.Array, jax.Array]] = None,
    shard: Optional[Shard] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """EXTENT approximate write of ``new`` over ``old`` (same shape/dtype).

    Returns (stored, stats{energy_pj, flips01, flips10, errors,
    bits_written, bits_total}). ``bits_total`` counts only real element
    bits — padding lanes added to reach block multiples are excluded, so
    partial blocks account exactly like full ones.

    The driver level table is resolved eagerly (it is Python-float
    calibration code); the data path below is jitted. Callers already
    inside a jit trace may pass precomputed ``vectors`` (see
    ``level_vectors``) to route per-tensor priorities without touching the
    level table: the vectors are ordinary operands, so two tensors at
    different priorities share one compiled computation.

    ``shard`` (kernel only, inside a ``shard_map``): ``old``/``new`` are
    one device's shard of a larger tensor (see ``Shard``); the stored
    shard and the stats equal the whole-tensor call's.
    """
    assert old.shape == new.shape and old.dtype == new.dtype
    assert shard is None or use_kernel, "only the kernel writes a shard"
    if vectors is None:
        vectors = level_vectors(old.dtype, Priority.coerce(level))
    thr01, thr10, e01, e10 = vectors
    layout, lane0 = _layout(shard, old)
    return _extent_write_jit(key, old, new, thr01, thr10, e01, e10, lane0,
                             block=block, use_kernel=use_kernel,
                             interpret=resolve_interpret(interpret),
                             layout=layout)


@functools.partial(jax.jit, static_argnames=("block", "use_kernel",
                                             "interpret", "layout"))
def _extent_write_jit(
    key, old, new, thr01, thr10, e01, e10, lane0, *,
    block: Tuple[int, int], use_kernel: bool, interpret: bool, layout,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    nbits = int(thr01.shape[0])
    seed = jax.random.bits(key, (1,), jnp.uint32)

    old_u, _ = _to_lanes(old)
    new_u, _ = _to_lanes(new)
    if use_kernel:
        # The counter RNG hashes the *flat* lane index, so the (rows, cols)
        # layout is free to follow the tensor: results are bit-identical
        # for any block partition, and small tensors (a serving cache leaf
        # is a few thousand lanes) do not pay a full (256 x 512)-lane pad.
        (stored_u,), stats = lane_kernel(
            K.extent_write_kernel, (old_u, new_u), seed,
            (thr01, thr10, e01, e10), block=block, interpret=interpret,
            layout=layout, lane0=lane0)
    else:
        # pure-jnp ref: no grid constraints at all, one row, zero pad
        stored2, stats = R.extent_write_ref(
            old_u.reshape(1, -1), new_u.reshape(1, -1), seed, thr01, thr10,
            e01, e10, nbits=nbits)
        stored_u = stored2.reshape(-1)

    # partial-block accounting: padding lanes are (0 -> 0) writes, so they
    # contribute no flips/energy/errors above; bits_total likewise counts
    # only the real element bits, never the pad. f32 (not i32): a >=256 MiB
    # tensor holds >=2^31 bits, which would overflow at trace time.
    stats = dict(stats)
    stats["bits_written"] = stats["flips01"] + stats["flips10"]
    stats["bits_total"] = jnp.asarray(
        float(old.size * jnp.dtype(old.dtype).itemsize * 8), jnp.float32)

    stored = _from_lanes(stored_u, old.shape, old.dtype)
    return stored, stats
