"""WritePlan: resolve-once write policy for a pytree of memory regions.

The plan is the reproduction's EXTENT table row + 4-driver bank for one
cache/state *shape*: built exactly once (from abstract leaves — no device
data needed), it captures

  * which leaves go through the approximate driver and at which static
    level (the pytree policy, e.g. K@MID / V@LOW / recurrent-state EXACT),
  * the calibrated per-bit driver vectors for every (leaf, quality-floor)
    combination — plain array OPERANDS of the compiled write, so an
    ``ExtentTable``/``QualityController`` floor change between bursts swaps
    constants and NEVER retraces,
  * the RNG stream layout: leaf ``i`` folds ``i`` into the step key, and
    the lane backends hash FLAT lane indices, so results are invariant to
    block partitioning (the bit-parity contract continuous batching rests
    on — see tests/test_extent_parity.py),
  * the column-scoped decode write: leaves with a sequence axis write only
    the ring column at ``pos % C`` per slot — O(token) lane work per decode
    step instead of O(cache), with accounting identical to the full diff
    (everything outside the column is bit-unchanged => zero under CMP),
  * the slot mesh: over a pool whose slot axis is split across devices,
    a backend XLA cannot partition (the Pallas kernels) runs as a
    ``shard_map`` in which each device writes its own slots
    (``WritePlan.leaf_call``),
  * an optional post-write soft-error hook (retention upsets at
    ``soft_error_ber``; the hardened driver protects sign/exponent bits),
    surfaced through ``WriteStats.soft_strikes``.

Composition rule for floors: effective level = max(static policy, floor) —
quality hints RAISE fidelity above the static policy, never lower it, and
EXACT-pinned leaves are not in the plan at all.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import write_driver
from repro.core.approx_store import inject_soft_errors
from repro.core.priority import (Priority, bitplane_priorities, bits_of,
                                 kv_cache_policy, uint_type)
from repro.memory import address as addr_mod
from repro.memory.backends import Backend, LeafVectors, get_backend
from repro.memory.rng_streams import SOFT_ERROR_OFFSET as _SOFT_KEY_OFFSET
from repro.memory.stats import WriteStats
from repro.telemetry.scopes import scope


def leaf_vectors(dtype, level, cfg: Optional[write_driver.DriverConfig] = None,
                 *, per_bit: bool = True) -> LeafVectors:
    """Resolve one (element dtype, effective level) pair to driver operands.

    lru-cached and forced to compile-time evaluation (driver calibration is
    Python-float code), so it is safe to call while tracing an enclosing
    jit — the resolve-once half of the WritePlan contract."""
    return _leaf_vectors(jnp.dtype(dtype), Priority.coerce(level), cfg,
                         per_bit)


@functools.lru_cache(maxsize=512)
def _leaf_vectors(dtype, level: Priority,
                  cfg: Optional[write_driver.DriverConfig],
                  per_bit: bool) -> LeafVectors:
    with jax.ensure_compile_time_eval():
        table = write_driver.level_table(cfg or write_driver.DriverConfig())
        tb = {k: np.asarray(v) for k, v in table.items()}
        nbits = bits_of(dtype)
        if per_bit:
            codes = bitplane_priorities(dtype, level)
        else:
            codes = np.full((nbits,), int(level), np.int32)
        lat = tb["lat"][codes]
        lanes: Tuple[Optional[jax.Array], ...] = (None, None, None, None)
        if per_bit and dtype.itemsize in (1, 2, 4):
            from repro.kernels.extent_write import ops as xops
            lanes = xops.level_vectors(dtype, level, cfg)
        return LeafVectors(
            wer01=jnp.asarray(tb["wer01"][codes], jnp.float32),
            wer10=jnp.asarray(tb["wer10"][codes], jnp.float32),
            eb01=jnp.asarray(tb["e01"][codes], jnp.float32),
            eb10=jnp.asarray(tb["e10"][codes], jnp.float32),
            lat=jnp.asarray(lat, jnp.float32),
            lat_max=jnp.asarray(float(lat.max()), jnp.float32),
            thr01=lanes[0], thr10=lanes[1], le01=lanes[2], le10=lanes[3])


def _default_approx_if(leaf, tag: Priority) -> bool:
    """Engine rule: floating leaves below EXACT go through the approximate
    driver; integer/control leaves and EXACT-pinned leaves bypass it."""
    return jnp.issubdtype(leaf.dtype, jnp.floating) and tag != Priority.EXACT


def _stuck_gate(old, new, worn):
    """Stuck-at gating for worn physical rows: elements under ``worn``
    keep their stored value (the row no longer accepts writes) and every
    bit the gated write *would* have changed counts as a failed write.
    Returns (gated_new, lost_bit_count). Because the gated new equals the
    stored old on worn rows, the downstream CMP diff write charges zero
    flips/energy there — the controller skips rows its bad-row table
    names, but the data loss is booked in ``WriteStats.errors``."""
    ut = uint_type(old.dtype)
    d = (jax.lax.bitcast_convert_type(old, ut)
         ^ jax.lax.bitcast_convert_type(new, ut))
    lost = jnp.sum(jnp.where(worn, jax.lax.population_count(d), ut(0))
                   .astype(jnp.int32), dtype=jnp.int32)
    return jnp.where(worn, old, new), lost


def _soft_error_hook(key, x, ber: float, hardened: bool):
    """Post-write retention upsets + the strike count (popcount of the
    flipped-bit mask)."""
    y = inject_soft_errors(key, x, ber, protect_exponent=hardened)
    ut = uint_type(x.dtype)
    d = (jax.lax.bitcast_convert_type(x, ut)
         ^ jax.lax.bitcast_convert_type(y, ut))
    strikes = jnp.sum(jax.lax.population_count(d).astype(jnp.int32),
                      dtype=jnp.int32)
    return y, strikes


@dataclasses.dataclass
class WritePlan:
    """Resolved write policy for one pytree structure (see module doc)."""
    backend: Backend
    treedef: Any
    leaf_levels: Tuple[Optional[Priority], ...]
    leaf_seq_axis: Tuple[Optional[int], ...]
    batch_axis: int = 1
    soft_error_ber: float = 0.0
    soft_error_hardened: bool = True
    #: physical addressing layer (repro.memory.address): None = no remap,
    #: no stuck-at gating — the exact pre-address data path.
    address_spec: Optional[addr_mod.AddressSpec] = None
    #: 1-D device mesh the slot (batch) axis is split over, or None
    slot_mesh: Optional[Mesh] = None
    floor_vectors: Dict[Priority, Tuple[Optional[LeafVectors], ...]] = (
        dataclasses.field(default_factory=dict))
    _jit_write: Any = dataclasses.field(default=None, repr=False,
                                        compare=False)

    # ------------------------------------------------------------ construction
    @classmethod
    def for_tree(cls, tree: Any, *,
                 policy: Callable[..., Any] = kv_cache_policy,
                 backend: str | Backend = "lanes_ref",
                 axes: Any = None,
                 batch_axis: int = 1,
                 soft_error_ber: float = 0.0,
                 soft_error_hardened: bool = True,
                 address_spec: Optional[addr_mod.AddressSpec] = None,
                 slot_mesh: Optional[Mesh] = None,
                 driver_cfg: Optional[write_driver.DriverConfig] = None,
                 approx_if: Callable[[Any, Priority], bool]
                 = _default_approx_if) -> "WritePlan":
        """Resolve ``policy`` over ``tree`` (arrays or ShapeDtypeStructs —
        only structure/shape/dtype are read) into a plan.

        ``axes``: optional same-structure tree of logical-axis tuples (the
        model API's ``cache_axes()``); leaves whose tuple contains
        ``"kv_seq"`` get the column-scoped decode write. ``backend`` is a
        registry name or an instance.
        """
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        levels = []
        for path, leaf in flat:
            tag = Priority.coerce(policy(path, leaf))
            levels.append(tag if approx_if(leaf, tag) else None)
        if axes is not None:
            flat_axes = treedef.flatten_up_to(axes)
            seq_axis = tuple(
                ax.index("kv_seq")
                if isinstance(ax, tuple) and "kv_seq" in ax else None
                for ax in flat_axes)
        else:
            seq_axis = (None,) * len(flat)
        floor_vectors = {
            floor: tuple(
                leaf_vectors(leaf.dtype, max(lvl, floor), driver_cfg)
                if lvl is not None else None
                for (_, leaf), lvl in zip(flat, levels))
            for floor in Priority}
        be = backend if not isinstance(backend, str) else get_backend(backend)
        return cls(backend=be, treedef=treedef, leaf_levels=tuple(levels),
                   leaf_seq_axis=seq_axis, batch_axis=batch_axis,
                   soft_error_ber=soft_error_ber,
                   soft_error_hardened=soft_error_hardened,
                   address_spec=address_spec, slot_mesh=slot_mesh,
                   floor_vectors=floor_vectors)

    # ------------------------------------------------------ address layer
    def rotatable(self) -> Tuple[bool, ...]:
        """Per-leaf flag: does the wear-leveling rotation apply? Only
        approximate leaves with a ring (sequence) axis have a column
        permutation to rotate."""
        return tuple(lvl is not None and ax is not None
                     for lvl, ax in zip(self.leaf_levels,
                                        self.leaf_seq_axis))

    def identity_address(self) -> addr_mod.AddressState:
        """The identity permutation for this plan's leaf count —
        bit-identical to running with no address layer at all."""
        return addr_mod.AddressState.identity(len(self.leaf_levels))

    def _worn_elem(self, i: int, leaf, shifts, worn) -> Optional[jax.Array]:
        """Element-space stuck-at mask for leaf ``i`` under the address
        operands, or None when gating is off/irrelevant."""
        if worn is None or self.address_spec is None:
            return None
        return addr_mod.worn_element_mask(
            worn[i], shifts[i], leaf.shape, self.leaf_seq_axis[i],
            self.batch_axis, self.address_spec)

    def migration_cost(self, tree: Any) -> Tuple[float, int]:
        """Host constants (energy_pj, bits) of ONE start-gap migration:
        one ``group_cols``-wide row group per ring leaf copied through the
        controller's row buffer, every bit re-driven at the mean of the
        plan's static 0→1/1→0 per-plane write prices. The ONE source of
        the remap pricing — the serving scheduler and the endurance
        benchmark both book rotations through it."""
        assert self.address_spec is not None, "plan has no address layer"
        import numpy as np
        vectors = self.vectors_for(Priority.LOW)
        pj, bits = 0.0, 0
        flat = jax.tree.leaves(tree)
        for i, (leaf, lvl, ax) in enumerate(zip(flat, self.leaf_levels,
                                                self.leaf_seq_axis)):
            if lvl is None or ax is None:
                continue
            C = leaf.shape[ax]
            elems = leaf.size // C * min(self.address_spec.group_cols, C)
            eb = (np.asarray(vectors[i].eb01)
                  + np.asarray(vectors[i].eb10)) / 2.0
            pj += float(elems) * float(eb.sum())
            bits += elems * bits_of(leaf.dtype)
        return pj, bits

    def alias_saving(self, tree: Any, cols: int) -> Tuple[float, int]:
        """Host constants (energy_pj, bits) of driving ``cols`` leading
        ring columns of ONE slot across the sequence-axis leaves, every
        bit priced at the mean of the plan's static 0→1/1→0 per-plane
        write prices — the modeled full-drive cost of the columns a
        prefix link skips. The ONE source of the prefix-cache pricing:
        the serving scheduler books both its *saved-write* estimate and
        its *copy-on-write* materialization charge through this (the same
        columns, the same price — a CoW pays back exactly what the link
        was credited)."""
        import numpy as np
        vectors = self.vectors_for(Priority.LOW)
        pj, bits = 0.0, 0
        flat = jax.tree.leaves(tree)
        for i, (leaf, lvl, ax) in enumerate(zip(flat, self.leaf_levels,
                                                self.leaf_seq_axis)):
            if lvl is None or ax is None:
                continue
            C = leaf.shape[ax]
            B = leaf.shape[self.batch_axis]
            elems = leaf.size // (C * B) * min(int(cols), C)
            eb = (np.asarray(vectors[i].eb01)
                  + np.asarray(vectors[i].eb10)) / 2.0
            pj += float(elems) * float(eb.sum())
            bits += elems * bits_of(leaf.dtype)
        return pj, bits

    # -------------------------------------------------------------- operands
    def vectors_for(self, floor: Priority = Priority.LOW
                    ) -> Tuple[Optional[LeafVectors], ...]:
        """Per-leaf driver-vector operands for one quality floor. LOW is
        the identity floor: the static policy alone. The tuples share one
        pytree structure across floors, so swapping them between compiled
        calls never retraces."""
        return self.floor_vectors[Priority.coerce(floor)]

    # ----------------------------------------------------------- write paths
    def leaf_call(self, method: str, key, a, b, lv: LeafVectors):
        """``backend.<method>(key, a, b, lv)`` on one leaf, for
        ``leaf_write`` and ``leaf_scrub``.

        XLA cannot partition a Pallas kernel. Over a ``slot_mesh`` of
        several devices, a backend that says so (``maps_shards``) runs as a
        ``shard_map`` over the leaf's slot axis: each device handles its
        own slots, told where they sit in the whole leaf (a kernel
        ``Shard``), so the flat-lane RNG hashes the one-device indices, and
        the per-plane counts are summed over the mesh before pricing. The
        stored bits and the stats equal the one-device call's, with no
        gather of the leaf. A leaf whose slots do not split evenly into
        whole 32-bit lanes (an admission's one slot) runs whole on every
        device."""
        call = getattr(self.backend, method)
        mesh = self.slot_mesh
        if (mesh is None or mesh.size == 1 or lv.thr01 is None
                or not getattr(self.backend, "maps_shards", False)):
            return call(key, a, b, lv)
        from repro.kernels.extent_write.ops import Shard
        axis, n, bx = mesh.axis_names[0], mesh.size, self.batch_axis
        B = a.shape[bx]
        inner = int(np.prod(a.shape[bx + 1:]))
        run = B // n * inner
        spec, local = P(), call
        if B % n == 0 and run * jnp.dtype(a.dtype).itemsize % 4 == 0:
            spec = P(*([None] * bx), axis)

            def local(key, a, b, lv):
                return call(key, a, b, lv, shard=Shard(
                    groups=int(np.prod(a.shape[:bx])), stride=B * inner,
                    first=jax.lax.axis_index(axis) * run, axis=axis))
        n_data = 1 if method == "leaf_write" else 2
        *data, st = jax.shard_map(
            local, mesh=mesh, in_specs=(P(), spec, spec, P()),
            out_specs=(spec,) * n_data + (P(),), check_vma=False)(
                key, a, b, lv)
        whole = WriteStats.for_bits(a.size * bits_of(a.dtype))
        return (*data, dataclasses.replace(st, bits_hi=whole.bits_hi,
                                           bits_lo=whole.bits_lo))

    def _leaf_write(self, key, i: int, old, new,
                    lv: LeafVectors) -> Tuple[jax.Array, WriteStats]:
        """One leaf through the backend + the optional soft-error hook —
        the single place the per-leaf write protocol (RNG fold-in schedule
        included) lives."""
        with scope("extent_lanes"):
            stored, st = self.leaf_call(
                "leaf_write", jax.random.fold_in(key, i), old, new, lv)
            if self.soft_error_ber > 0.0:
                k_soft = jax.random.fold_in(key, _SOFT_KEY_OFFSET + i)
                stored, strikes = _soft_error_hook(
                    k_soft, stored, self.soft_error_ber,
                    self.soft_error_hardened)
                st = dataclasses.replace(
                    st, soft_strikes=st.soft_strikes + strikes)
        return stored, st

    def _alias_keep(self, i: int, leaf, alias_cols) -> Optional[jax.Array]:
        """Column-alias mask for leaf ``i``: True on the leading
        ``alias_cols[slot]`` ring columns (broadcastable to the leaf).
        Aliased columns are *linked* to columns already resident elsewhere
        in the array (serve/prefix.py): the write carries the stored value
        through unchanged, so CMP charges zero energy/flips/WER there —
        the skipped write never happens. None when aliasing is off or the
        leaf has no ring axis (nothing to link column-wise)."""
        ax = self.leaf_seq_axis[i]
        if alias_cols is None or ax is None:
            return None
        ishape = [1] * leaf.ndim
        ishape[self.batch_axis] = alias_cols.shape[0]
        return (jax.lax.broadcasted_iota(jnp.int32, leaf.shape, ax)
                < alias_cols.reshape(ishape))

    def write(self, key, old_tree: Any, new_tree: Any,
              vectors: Optional[Sequence] = None,
              addr: Optional[Tuple[jax.Array, Optional[jax.Array]]] = None,
              alias_cols: Optional[jax.Array] = None
              ) -> Tuple[Any, WriteStats]:
        """Jit-resident diff-write of a full tree (or a row subset with the
        same structure); returns (stored_tree, WriteStats). ``vectors`` is
        a per-flat-leaf operand tuple, normally from ``vectors_for``.
        ``addr`` is the optional physical-addressing operand pair
        ``(shifts (L,) i32, worn (L, G) bool-or-None)``: elements backed by
        worn physical row groups are stuck-at (kept old, lost flips booked
        to ``errors``). With identity shifts and no worn rows the stored
        bits and stats are bit-identical to ``addr=None``.

        ``alias_cols`` is the optional (B,) i32 column-alias OPERAND of the
        prefix cache: per slot, the leading ``alias_cols[b]`` ring columns
        of every sequence-axis leaf are column-*linked* — the stored (old)
        value is kept bit-for-bit and the write is skipped, so those
        columns cost exactly zero energy/flips/WER under CMP. The RNG
        streams hash flat logical element indices and every per-element
        decision is element-local, so all NON-aliased elements store bits
        identical to the unaliased call; an all-zero ``alias_cols`` is a
        bit-exact identity with ``alias_cols=None``."""
        if vectors is None:
            vectors = self.vectors_for(Priority.LOW)
        shifts, worn = addr if addr is not None else (None, None)
        flat_old, treedef = jax.tree.flatten(old_tree)
        flat_new = treedef.flatten_up_to(new_tree)
        stored = []
        acc = WriteStats.zero()
        for i, (o, n, lvl) in enumerate(zip(flat_old, flat_new,
                                            self.leaf_levels)):
            if lvl is None:
                stored.append(n)  # EXACT fast path (recurrent states, ints)
                continue
            keep = self._alias_keep(i, o, alias_cols)
            if keep is not None:
                # linked columns re-store the resident bits: identical
                # old/new means the CMP diff write skips them entirely
                n = jnp.where(keep, o, n)
            wm = self._worn_elem(i, o, shifts, worn)
            lost = None
            if wm is not None:
                n, lost = _stuck_gate(o, n, wm)
            s, st = self._leaf_write(key, i, o, n, vectors[i])
            if lost is not None:
                st = dataclasses.replace(st, errors=st.errors + lost)
            stored.append(s)
            acc = acc + st
        return treedef.unflatten(stored), acc

    def write_columns(self, key, old_tree: Any, new_tree: Any,
                      pos: jax.Array,
                      vectors: Optional[Sequence] = None,
                      addr: Optional[Tuple[jax.Array,
                                           Optional[jax.Array]]] = None,
                      alias_cols: Optional[jax.Array] = None
                      ) -> Tuple[Any, WriteStats]:
        """Column-scoped decode diff-write: leaves with a sequence axis
        write only the ring column at ``pos % C`` (per slot along
        ``batch_axis``); other approximate leaves fall back to the full
        diff. Flip/energy stats are identical to ``write`` — the rest of
        the tree is bit-unchanged after a decode step, so CMP contributes
        exactly zero there — but the per-step cost drops from O(cache) to
        O(token) lane work. ``pos`` is the (B,) position vector.

        ``addr``: optional ``(shifts, worn)`` physical-addressing operands
        (see ``write``). The written column's *address* maps through the
        rotation to find its physical row group; a slot whose target group
        is worn has its column write inhibited (stuck-at, lost flips in
        ``errors``). The RNG stream is untouched — it hashes the gathered
        column tensor's flat indices, which do not depend on the address —
        so identity shifts reproduce ``addr=None`` bit-for-bit.

        ``alias_cols``: optional (B,) i32 column-alias operand (see
        ``write``) — a slot whose target column lies inside its linked
        prefix (``pos[b] < alias_cols[b]``) keeps the resident bits and
        skips the write at zero cost. All-zero alias is a bit-exact
        identity with ``alias_cols=None``."""
        if vectors is None:
            vectors = self.vectors_for(Priority.LOW)
        shifts, worn = addr if addr is not None else (None, None)
        gate = worn is not None and self.address_spec is not None
        flat_old, treedef = jax.tree.flatten(old_tree)
        flat_new = treedef.flatten_up_to(new_tree)
        stored = []
        acc = WriteStats.zero()
        for i, (o, n, lvl) in enumerate(zip(flat_old, flat_new,
                                            self.leaf_levels)):
            if lvl is None:
                stored.append(n)
                continue
            ax = self.leaf_seq_axis[i]
            lost = None
            if ax is None:
                wm = self._worn_elem(i, o, shifts, worn)
                if wm is not None:
                    n, lost = _stuck_gate(o, n, wm)
                s, st = self._leaf_write(key, i, o, n, vectors[i])
                if lost is not None:
                    st = dataclasses.replace(st, errors=st.errors + lost)
                stored.append(s)
                acc = acc + st
                continue
            C = o.shape[ax]
            ishape = [1] * o.ndim
            ishape[self.batch_axis] = pos.shape[0]
            idx = (pos % C).reshape(ishape)
            gshape = o.shape[:ax] + (1,) + o.shape[ax + 1:]
            idx_g = jnp.broadcast_to(idx, gshape)
            with scope("extent_gather"):
                o_col = jnp.take_along_axis(o, idx_g, axis=ax)
                n_col = jnp.take_along_axis(n, idx_g, axis=ax)
            if alias_cols is not None:
                keep = (pos < alias_cols).reshape(ishape)
                n_col = jnp.where(keep, o_col, n_col)
            if gate:
                wm = addr_mod.worn_slot_mask(
                    worn[i], pos, shifts[i], C,
                    self.address_spec).reshape(ishape)
                n_col, lost = _stuck_gate(o_col, n_col, wm)
            s_col, st = self._leaf_write(key, i, o_col, n_col, vectors[i])
            if lost is not None:
                st = dataclasses.replace(st, errors=st.errors + lost)
            with scope("extent_store"):
                hit = (jax.lax.broadcasted_iota(jnp.int32, o.shape, ax)
                       == idx)
                stored.append(jnp.where(hit, s_col, n))
            acc = acc + st
        return treedef.unflatten(stored), acc

    def jitted_write(self):
        """Compiled ``write`` (cached on the plan, shared by every
        MemoryRegion that replaces itself functionally around this plan)."""
        if self._jit_write is None:
            self._jit_write = jax.jit(
                lambda k, o, n, v: self.write(k, o, n, v))
        return self._jit_write

    # ------------------------------------------------------- shape metadata
    def approx_bits(self, tree: Any) -> int:
        """Total bits of the approximate leaves — static shape metadata."""
        flat = jax.tree.leaves(tree)
        return sum(l.size * bits_of(l.dtype)
                   for l, lvl in zip(flat, self.leaf_levels)
                   if lvl is not None)

    def decode_bits(self, tree: Any) -> int:
        """Approximate bits one decode step addresses: the written ring
        column per sequence-axis leaf, whole leaves otherwise."""
        flat = jax.tree.leaves(tree)
        total = 0
        for l, lvl, ax in zip(flat, self.leaf_levels, self.leaf_seq_axis):
            if lvl is None:
                continue
            sz = l.size if ax is None else l.size // l.shape[ax]
            total += sz * bits_of(l.dtype)
        return total


# ---------------------------------------------------------------------------
# single-tensor convenience entry (examples, checkpoints, benchmarks, tests)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _leaf_jit(backend_obj: Backend):
    # keyed on the backend INSTANCE, not its registry name: re-registering
    # a name makes get_backend hand out a fresh instance, which gets a
    # fresh jit here — an override is never shadowed by a stale closure
    return jax.jit(backend_obj.leaf_write)


def write(key, old, new, *, level: Priority | int | str = Priority.LOW,
          backend: str = "lanes_ref",
          driver_cfg: Optional[write_driver.DriverConfig] = None
          ) -> Tuple[jax.Array, WriteStats]:
    """Unified single-tensor EXTENT write through a registered backend.

    Returns (stored, WriteStats). The level resolves through the same
    ``leaf_vectors`` cache as WritePlan, and the vectors ride as operands
    of one jitted call per backend — a level sweep reuses one compiled
    executable."""
    lv = leaf_vectors(old.dtype, level, driver_cfg)
    return _leaf_jit(get_backend(backend))(key, old, new, lv)
