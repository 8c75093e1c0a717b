"""Device meshes: the serve-side die mesh and the training pod meshes.

``DieMesh`` is the serving stack's sharding layer: one logical STT-RAM
slot-pool memory laid out across ``n_dies`` physical dies, partitioned
along the SLOT axis. Die ``d`` owns the contiguous slot block
``[d * slots_per_die, (d + 1) * slots_per_die)`` — and, because every
per-slot structure in the stack is slot-major (the pool cache's batch
axis, the ``slot_acc`` attribution ledgers, the ``(L, G)`` row-group wear
counters with ``G = capacity * groups_per_slot``), a die's entire state is
a contiguous slice of the pool-wide arrays. Per-die ledgers are therefore
pure reshape-reductions and never add device work to the decode scan.

The load-bearing invariant (tests/test_shard_serve.py): the extent-write /
retention RNG hashes FLAT logical element and lane indices, so the shard
count is a *layout* choice — an N-die run is bit-identical (tokens, flips,
energy, WER) to the 1-die run. The stack keeps ONE full-pool compiled
burst regardless of ``n_dies``; per-die divergence (ambient temperature,
scrub cadence, admission steering) enters exclusively through *operands*
(per-slot threshold rows, per-die slot masks, admission score biases) that
collapse to the legacy uniform shapes while the dies are indistinguishable.
Inside the scan every slot's lane work and decay sampling touches only
that slot's rows, which is what lets decode throughput scale with dies
(each die advances its shard without waiting on another's data; the
shard-locality lint rule keeps explicit collectives out of serve and
reliability code). What crosses dies is the write statistics: the Pallas
kernels, mapped per die by ``WritePlan.leaf_call``, sum their per-plane
counts across dies (one small all-reduce per leaf and step on four v5e
chips).

``make_production_mesh`` / ``make_host_mesh`` are the training-side pod
meshes (formerly ``repro.launch.mesh``), kept as functions so importing
this module never touches jax device state.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

#: the named mesh axis the slot dimension is sharded over
DIE_AXIS = "die"


def _largest_divisor_leq(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= ``cap`` (>= 1)."""
    for k in range(min(n, cap), 0, -1):
        if n % k == 0:
            return k
    return 1


@dataclasses.dataclass(frozen=True)
class DieMesh:
    """Slot-axis partition of a ``capacity``-slot pool over ``n_dies``.

    Pure host metadata: construction touches no device state. The jax
    mesh/placement methods materialize a ``jax.sharding.Mesh`` over the
    ``die`` axis lazily, folding the dies onto however many devices the
    host actually has (every die still *simulates* independently on a
    1-CPU host; on real hardware the same NamedSharding spreads them)."""
    n_dies: int
    capacity: int

    def __post_init__(self):
        assert self.n_dies >= 1, self.n_dies
        assert self.capacity % self.n_dies == 0, (
            f"pool capacity {self.capacity} must divide evenly over "
            f"{self.n_dies} dies — shard count is a layout choice, and a "
            "ragged last die would break the contiguous-slice layout")

    # ------------------------------------------------------------ layout
    @property
    def slots_per_die(self) -> int:
        return self.capacity // self.n_dies

    def die_of_slot(self, slot: int) -> int:
        return int(slot) // self.slots_per_die

    def slot_slice(self, die: int) -> slice:
        s = self.slots_per_die
        return slice(die * s, (die + 1) * s)

    def die_ids(self) -> np.ndarray:
        """(capacity,) i32 die index of every slot."""
        return np.repeat(np.arange(self.n_dies, dtype=np.int32),
                         self.slots_per_die)

    @functools.lru_cache(maxsize=None)
    def slot_mask(self, die: int) -> jax.Array:
        """(capacity,) bool device operand selecting one die's slots —
        the per-die scrub-pass mask."""
        return jnp.asarray(self.die_ids() == die)

    # ----------------------------------------------------- per-die views
    def reduce_slots(self, per_slot: Any, op=np.sum) -> np.ndarray:
        """(capacity,)-leading host array -> (n_dies,) per-die reduction
        (the per-die ledger: energy/flips/errors from ``slot_acc``,
        decayed bits from the lifetime masks)."""
        a = np.asarray(per_slot)
        return op(a.reshape(self.n_dies, self.slots_per_die, *a.shape[1:]),
                  axis=1)

    def reduce_wear(self, wear: Any, op=np.max) -> np.ndarray:
        """(L, G) host row-group wear counters -> (n_dies,) per-die
        reduction. ``G`` is slot-major (``capacity * groups_per_slot``,
        possibly padded), so each die's groups are one contiguous slice."""
        w = np.asarray(wear)
        gps = w.shape[1] // self.capacity  # padding beyond B*gps is zero
        w = w[:, :self.capacity * gps]
        return op(w.reshape(w.shape[0], self.n_dies, -1), axis=(0, 2))

    def per_slot(self, per_die: Sequence) -> np.ndarray:
        """(n_dies,) per-die values -> (capacity,) per-slot broadcast
        (admission score biases, per-slot operand rows)."""
        v = np.asarray(per_die)
        assert v.shape[0] == self.n_dies, (v.shape, self.n_dies)
        return np.repeat(v, self.slots_per_die, axis=0)

    # ------------------------------------------------------- jax sharding
    def device_mesh(self) -> Mesh:
        """See ``die_device_mesh``."""
        return die_device_mesh(self.n_dies)

    def sharding_for(self, ndim: int, slot_axis: int) -> NamedSharding:
        """NamedSharding placing an array's ``slot_axis`` on the die
        axis, every other axis replicated."""
        spec = [None] * ndim
        spec[slot_axis] = DIE_AXIS
        return NamedSharding(self.device_mesh(), PartitionSpec(*spec))

    def shard_slots(self, tree: Any, slot_axis: int) -> Any:
        """Place every leaf of a slot-major pytree through the die mesh
        (``jax.device_put`` — value-preserving, so shard placement never
        perturbs the bit-identity contract)."""
        return jax.tree.map(
            lambda a: jax.device_put(
                a, self.sharding_for(a.ndim, slot_axis)), tree)


def die_device_mesh(n_dies: int) -> Mesh:
    """1-D ``jax.sharding.Mesh`` over the ``die`` axis for ``n_dies`` dies.
    The axis size is the largest divisor of ``n_dies`` the host's device
    count admits (1 on a single-CPU host), so placement always succeeds
    and dies fold evenly onto devices."""
    devices = jax.devices()
    k = _largest_divisor_leq(n_dies, len(devices))
    return Mesh(np.asarray(devices[:k]), (DIE_AXIS,))


def uniform(values: Sequence) -> bool:
    """True when per-die values are indistinguishable — the condition
    under which every per-die operand collapses to its legacy pool-wide
    shape and the N-die stack runs the 1-die compiled executables."""
    vals = list(values)
    return len(set(vals)) <= 1


# --------------------------------------------------------------------------
# training pod meshes (absorbed from the retired repro.launch.mesh)

def make_production_mesh(*, multi_pod: bool = False,
                         shape=None) -> Mesh:
    """Default single-pod (data=16, model=16); multi-pod (pod=2, 16, 16).
    `shape` overrides the intra-pod (data, model) split for §Perf strategy
    validation — e.g. (64, 4) — chip count must stay 256 per pod."""
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    elif multi_pod:
        shape = (2,) + tuple(shape)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, found {len(devices)};"
            " the dry-run launcher must set"
            " XLA_FLAGS=--xla_force_host_platform_device_count=512 before any"
            " jax import")
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_host_mesh() -> Mesh:
    """1-device mesh for CPU smoke tests: same axis names, size 1."""
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
