"""Public jit'd wrapper for the scrub kernel.

Mirrors ``kernels/extent_write/ops.py``: dtype bitcasting into uint32 lanes
(shared ``_to_lanes``/``_from_lanes`` plumbing), right-sized grids with
row-block padding only, threshold/energy vector operands, the per-plane
count-tile reduction (``tile_stats``), and ``interpret=None`` following the
platform (``repro.kernels.platform``).

The decay *mask* rides in element space (``uint_type(data.dtype)``, same
shape as the data — maintained by ``repro.reliability.lifetime``) and is
lane-packed here exactly like the data, so the kernel sees matching lanes.

This module is kernel-internal plumbing: everything outside
``repro/kernels`` and ``repro/memory`` reaches scrubbing through the
backend registry (``Backend.leaf_scrub``) or ``repro.reliability.scrub``.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.extent_write import kernel as WK
from repro.kernels.extent_write.ops import (Shard, _from_lanes, _layout,
                                           _to_lanes, lane_kernel)
from repro.kernels.platform import resolve_interpret
from repro.kernels.scrub import kernel as K
from repro.kernels.scrub import ref as R

from repro.core.priority import uint_type


def scrub_write(
    key: jax.Array,
    stored: jax.Array,
    mask: jax.Array,
    *,
    vectors: Tuple[jax.Array, jax.Array, jax.Array, jax.Array],
    block: Tuple[int, int] = WK.DEFAULT_BLOCK,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
    shard: Optional[Shard] = None,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Corrective re-write of the decayed bits of ``stored`` (see kernel.py).

    ``mask`` is the element-space decayed-bit mask (``uint_type`` of the
    stored dtype, same shape). ``vectors`` is the lane-tiled
    (thr01, thr10, e01, e10) quadruple from
    ``kernels.extent_write.ops.level_vectors`` — the same driver operands
    the write path uses, so a scrub pays write-path prices.

    Returns (scrubbed, residual_mask, stats{energy_pj, flips01, flips10,
    errors, bits_written, bits_total}); ``residual_mask`` holds the
    corrections that FAILED (still-decayed bits, retried next pass);
    ``bits_total`` counts the scanned element bits, never the lane padding.
    ``shard``: as in ``extent_write`` (kernel only).
    """
    assert stored.shape == mask.shape, (stored.shape, mask.shape)
    assert jnp.dtype(mask.dtype) == jnp.dtype(uint_type(stored.dtype)), (
        mask.dtype, stored.dtype)
    assert shard is None or use_kernel, "only the kernel scrubs a shard"
    thr01, thr10, e01, e10 = vectors
    layout, lane0 = _layout(shard, stored)
    return _scrub_jit(key, stored, mask, thr01, thr10, e01, e10, lane0,
                      block=block, use_kernel=use_kernel,
                      interpret=resolve_interpret(interpret), layout=layout)


@functools.partial(jax.jit, static_argnames=("block", "use_kernel",
                                             "interpret", "layout"))
def _scrub_jit(
    key, stored, mask, thr01, thr10, e01, e10, lane0, *,
    block: Tuple[int, int], use_kernel: bool, interpret: bool, layout,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    nbits = int(thr01.shape[0])
    seed = jax.random.bits(key, (1,), jnp.uint32)

    stored_u, _ = _to_lanes(stored)
    mask_u, _ = _to_lanes(mask)

    # same layout policy as extent_write: the counter RNG hashes the FLAT
    # lane index, so any (rows, cols) partition is bit-identical; padding
    # lanes have mask == 0 -> no re-writes, no energy, no failures
    if use_kernel:
        (scrubbed_u, residual_u), stats = lane_kernel(
            K.scrub_kernel, (stored_u, mask_u), seed,
            (thr01, thr10, e01, e10), block=block, interpret=interpret,
            layout=layout, lane0=lane0)
    else:
        scrubbed2, residual2, stats = R.scrub_ref(
            stored_u.reshape(1, -1), mask_u.reshape(1, -1), seed, thr01,
            thr10, e01, e10, nbits=nbits)
        scrubbed_u, residual_u = scrubbed2.reshape(-1), residual2.reshape(-1)

    stats = dict(stats)
    stats["bits_written"] = stats["flips01"] + stats["flips10"]
    # f32 (not i32): a >=256 MiB region holds >=2^31 bits (trace overflow)
    stats["bits_total"] = jnp.asarray(
        float(stored.size * jnp.dtype(stored.dtype).itemsize * 8),
        jnp.float32)

    ut = uint_type(stored.dtype)
    scrubbed = _from_lanes(scrubbed_u, stored.shape, stored.dtype)
    residual = _from_lanes(residual_u, mask.shape, ut)
    return scrubbed, residual, stats
