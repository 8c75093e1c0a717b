"""Pluggable write-path backends + the string-keyed registry.

The paper's Fig. 11 puts ONE controller between applications and the
STT-RAM array; this module is that boundary for the reproduction. Every
implementation of the EXTENT write (the eager bit-unpacked oracle, the
lane-packed pure-jnp reference, the Pallas kernel, the exact passthrough)
is a ``Backend`` behind one protocol:

    stored, stats = backend.leaf_write(key, old, new, leaf_vectors)

where ``leaf_vectors`` is the resolve-once operand bundle built by
``repro.memory.plan.leaf_vectors`` (per-bit WER/energy/latency for the
oracle, lane-packed thresholds for the kernel paths). Because every driver
parameter is an array OPERAND, swapping priorities/floors/backends never
retraces the surrounding jit.

Selection is by name (``get_backend("lanes_ref")``) — the registry replaces
every scattered ``use_kernel=``/``interpret=`` boolean that used to be
duplicated across serve/train/examples/benchmarks, and is trivially
extensible: register a new name, and every consumer (ServeConfig, the
launchers, the benchmarks, the CI smoke lane) can reach it.

Parity contract (tests/test_extent_parity.py): flips and energy are
RNG-independent, so ALL backends agree on them bit-exactly; realized error
counts differ only by RNG stream (oracle: ``jax.random``; lanes/pallas: the
shared counter hash — those two are bit-identical to each other).
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp

from repro.core import approx_store as _oracle
from repro.kernels.platform import resolve_interpret
from repro.memory.stats import WriteStats


class LeafVectors(NamedTuple):
    """Resolved driver operands for one (dtype, effective level) pair.

    Per-bit-plane vectors drive the oracle; the lane-packed quadruple
    (``thr01``..``le10``) drives the kernel paths and is ``None`` for
    element widths without lane packing (the backends then fall back to the
    oracle data path, still jit-resident)."""
    wer01: jax.Array            # (ebits,) f32 failure prob per bit, 0->1
    wer10: jax.Array            # (ebits,) f32 failure prob per bit, 1->0
    eb01: jax.Array             # (ebits,) f32 energy per flip (pJ), 0->1
    eb10: jax.Array             # (ebits,) f32 energy per flip (pJ), 1->0
    lat: jax.Array              # (ebits,) f32 driver latency per bit (ns)
    lat_max: jax.Array          # () f32: slowest driver in this plan entry
    thr01: Optional[jax.Array]  # (lane_bits,) u32 thresholds (wer * 2^32)
    thr10: Optional[jax.Array]
    le01: Optional[jax.Array]   # (lane_bits,) f32 lane-layout energies
    le10: Optional[jax.Array]


class Backend(Protocol):
    """One EXTENT write-path implementation behind the substrate API."""
    name: str

    def leaf_write(self, key: jax.Array, old: jax.Array, new: jax.Array,
                   lv: LeafVectors) -> Tuple[jax.Array, WriteStats]:
        """Write ``new`` over ``old`` (same shape/dtype); return the stored
        tensor and device-resident unified WriteStats. Must be jit-safe.
        A backend whose ``maps_shards`` is true also takes ``shard=``
        (its operands are one device's shard; ``WritePlan.leaf_call``)."""
        ...

    def leaf_scrub(self, key: jax.Array, stored: jax.Array,
                   mask: jax.Array, lv: LeafVectors
                   ) -> Tuple[jax.Array, jax.Array, WriteStats]:
        """Corrective re-write of the decayed bits of ``stored`` (``mask``
        is the element-space decayed-bit mask — ``uint_type`` view of the
        stored dtype, same shape; see ``repro.reliability``). Returns
        (scrubbed, residual_mask, WriteStats); corrections that fail stay
        decayed in ``residual_mask``. Must be jit-safe."""
        ...


def _planes_scrub(stored, mask, lv: LeafVectors):
    """Deterministic element-space scrub fallback for widths without lane
    packing: perfect correction (no stochastic failure modeled), per-plane
    energy accounting from the bit-plane vectors. Keeps the scrub protocol
    total over every dtype the write path accepts."""
    from repro.core.priority import uint_type
    ut = uint_type(stored.dtype)
    nbits = jnp.dtype(ut).itemsize * 8
    stored_u = jax.lax.bitcast_convert_type(stored, ut)
    corrected_u = stored_u ^ mask
    shift = jnp.arange(nbits, dtype=ut)
    rewrite = ((mask[..., None] >> shift) & ut(1)) != 0
    to_ap = rewrite & (((corrected_u[..., None] >> shift) & ut(1)) == ut(1))
    f01 = jnp.sum(to_ap, dtype=jnp.int32)
    f10 = jnp.sum(rewrite & ~to_ap, dtype=jnp.int32)
    energy = jnp.sum(jnp.where(to_ap, lv.eb01,
                               jnp.where(rewrite, lv.eb10, 0.0)),
                     dtype=jnp.float32)
    st = WriteStats.for_bits(
        stored.size * nbits, energy_pj=energy,
        latency_ns=jnp.where(f01 + f10 > 0, lv.lat_max, 0.0),
        flips01=f01, flips10=f10)
    return (jax.lax.bitcast_convert_type(corrected_u, stored.dtype),
            jnp.zeros_like(mask), st)


class _CounterScrub:
    """Shared ``leaf_scrub`` over the counter-RNG scrub kernel/oracle.

    Unlike the write path (where the eager oracle draws from ``jax.random``)
    the scrub path uses ONE RNG contract for every backend — the flat-lane
    counter hash — so all registered backends agree on a scrub's realized
    residuals bit-exactly, not just on flips/energy."""
    _scrub_use_kernel = False
    _scrub_interpret: Optional[bool] = None

    def leaf_scrub(self, key, stored, mask, lv: LeafVectors, shard=None):
        if lv.thr01 is None:  # no lane packing for this element width
            return _planes_scrub(stored, mask, lv)
        from repro.kernels.scrub import ops as sops
        scrubbed, residual, st = sops.scrub_write(
            key, stored, mask,
            vectors=(lv.thr01, lv.thr10, lv.le01, lv.le10),
            use_kernel=self._scrub_use_kernel,
            interpret=self._scrub_interpret, shard=shard)
        flips = st["flips01"] + st["flips10"]
        return scrubbed, residual, WriteStats.for_bits(
            stored.size * jnp.dtype(stored.dtype).itemsize * 8,
            energy_pj=st["energy_pj"],
            latency_ns=jnp.where(flips > 0, lv.lat_max, 0.0),
            flips01=st["flips01"], flips10=st["flips10"],
            errors=st["errors"])


class OracleBackend(_CounterScrub):
    """Eager bit-unpacked reference (``jax.random`` RNG stream): draws one
    uniform per (element, bit) — the 16-32x write-amplified ground truth
    every other backend's accounting is asserted against."""
    name = "oracle"

    def leaf_write(self, key, old, new, lv: LeafVectors):
        stored, d = _oracle.oracle_write(key, old, new, lv.wer01, lv.wer10,
                                         lv.eb01, lv.eb10, lv.lat)
        return stored, WriteStats.for_bits(
            old.size * jnp.dtype(old.dtype).itemsize * 8,
            energy_pj=d["energy_pj"], latency_ns=d["latency_ns"],
            flips01=d["flips01"], flips10=d["flips10"], errors=d["errors"])


class LaneBackend(_CounterScrub):
    """Lane-packed fused path (counter RNG over flat lane indices):
    ``use_kernel=False`` is the pure-jnp lane reference, ``use_kernel=True``
    the Pallas kernel (write AND scrub kernels). ``interpret=None`` resolves
    at construction through ``repro.kernels.platform``: native on a TPU
    (compiled and run on a v5e), the Pallas interpreter on other hosts.

    The kernel cannot be partitioned by XLA: ``maps_shards`` tells
    ``WritePlan.leaf_call`` to map it over a slot mesh itself, passing
    each device's ``shard`` (``kernels.extent_write.ops.Shard``)."""

    def __init__(self, name: str, use_kernel: bool,
                 interpret: Optional[bool] = None):
        self.name = name
        self.use_kernel = use_kernel
        self.maps_shards = use_kernel
        self._scrub_use_kernel = use_kernel
        self.interpret = resolve_interpret(interpret)
        self._scrub_interpret = self.interpret
        self._oracle = OracleBackend()

    def leaf_write(self, key, old, new, lv: LeafVectors, shard=None):
        if lv.thr01 is None:  # no lane packing for this element width
            return self._oracle.leaf_write(key, old, new, lv)
        from repro.kernels.extent_write import ops as xops
        stored, st = xops.extent_write(
            key, old, new, vectors=(lv.thr01, lv.thr10, lv.le01, lv.le10),
            use_kernel=self.use_kernel, interpret=self.interpret,
            shard=shard)
        flips = st["flips01"] + st["flips10"]
        return stored, WriteStats.for_bits(
            old.size * jnp.dtype(old.dtype).itemsize * 8,
            energy_pj=st["energy_pj"],
            # lane stats are reduced per block, not per bit plane: report
            # the plan entry's slowest driver whenever anything flipped
            latency_ns=jnp.where(flips > 0, lv.lat_max, 0.0),
            flips01=st["flips01"], flips10=st["flips10"],
            errors=st["errors"])


class ExactBackend:
    """Passthrough: no approximation modeling at all. ``stored == new``,
    zero flips/energy/errors; only ``bits_total`` (the addressed traffic)
    is counted so reports stay dimensionally comparable."""
    name = "exact"

    def leaf_write(self, key, old, new, lv: LeafVectors):
        del key, lv
        assert old.shape == new.shape and old.dtype == new.dtype
        bits = new.size * jnp.dtype(new.dtype).itemsize * 8
        return new, WriteStats.for_bits(bits)

    def leaf_scrub(self, key, stored, mask, lv: LeafVectors):
        """Perfect, free correction (no approximation model): the decayed
        bits are restored, residual cleared, only addressed bits counted."""
        del key, lv
        from repro.core.priority import uint_type
        ut = uint_type(stored.dtype)
        corrected = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(stored, ut) ^ mask, stored.dtype)
        bits = stored.size * jnp.dtype(stored.dtype).itemsize * 8
        return corrected, jnp.zeros_like(mask), WriteStats.for_bits(bits)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_FACTORIES: Dict[str, Callable[[], Backend]] = {}
_INSTANCES: Dict[str, Backend] = {}


def register_backend(name: str, factory: Callable[[], Backend]) -> None:
    """Install (or override) a backend under ``name``. Factories are
    instantiated lazily, once, on first ``get_backend``."""
    _FACTORIES[name] = factory
    _INSTANCES.pop(name, None)


def get_backend(name: str) -> Backend:
    if name not in _FACTORIES:
        raise KeyError(
            f"unknown memory backend {name!r}; registered: "
            f"{', '.join(available_backends())}")
    if name not in _INSTANCES:
        _INSTANCES[name] = _FACTORIES[name]()
    return _INSTANCES[name]


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_FACTORIES))


register_backend("oracle", OracleBackend)
register_backend("lanes_ref", lambda: LaneBackend("lanes_ref",
                                                  use_kernel=False))
register_backend("pallas", lambda: LaneBackend("pallas", use_kernel=True))
register_backend("exact", ExactBackend)
