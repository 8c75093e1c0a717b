"""Scrub pipeline: corrective re-writes of decayed bits over a pytree.

Drives ``Backend.leaf_scrub`` (the Pallas scrub kernel / its jnp oracle —
selected by the SAME registry name as the write path) across the
approximate leaves of a region, against the decay masks maintained by
``lifetime.LifetimePlan.advance``:

  * every decayed bit is re-written through the EXTENT driver at the
    leaf's (floor-composed) level — the re-write pays write-path energy
    through the unified ``WriteStats`` (charged to a separate stream by
    the callers, so scrubbing shows up honestly in the energy ledger) and
    can itself FAIL with the level's WER: failed corrections stay decayed
    in the residual mask and are retried next pass;
  * leaves with a sequence axis can be scrubbed in **column-scoped
    blocks** (a window of ring columns per pass) so a serving scheduler
    can spread one full-cache scrub over many idle slots instead of
    stalling a burst;
  * ``enabled`` is a static per-leaf gate: policies (``policy.py``) scrub
    HIGH-floor leaves aggressively while letting LOW leaves rot;
  * with the physical addressing layer (``addr=(shifts, worn)``), the
    scrub cursor walks **physical** rows — the window maps through the
    inverse permutation to the logical columns those rows currently back,
    so one full cursor revolution covers every physical row exactly once
    regardless of how often the wear-leveler rotated in between. Worn
    (stuck-at) rows cannot be re-driven: their decayed bits are masked
    out of the corrective write (no energy, no flips) and stay in the
    residual mask. Scrubbed columns book row-group scrub wear — scrub
    re-writes consume the same endurance budget as data writes.

Everything is jit-safe; one compiled executable per (enabled, cols)
signature, with driver/threshold/address vectors as operands.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.memory import address as addr_mod
from repro.memory.rng_streams import SCRUB_OFFSET as _SCRUB_KEY_OFFSET
from repro.memory.stats import WriteStats
from repro.reliability.lifetime import LifetimePlan, LifetimeState


def _take_cols(leaf: jax.Array, ax: int, idx: jax.Array) -> jax.Array:
    return jnp.take(leaf, idx, axis=ax)


def _put_cols(leaf: jax.Array, ax: int, idx: jax.Array,
              window: jax.Array) -> jax.Array:
    return jnp.moveaxis(
        jnp.moveaxis(leaf, ax, 0).at[idx].set(jnp.moveaxis(window, ax, 0)),
        0, ax)


def _worn_cols_mask(plan, spec, i: int, leaf, shifts, worn,
                    idx: Optional[jax.Array]) -> Optional[jax.Array]:
    """Element-space bool mask (broadcastable to the scrubbed span) of
    stuck-at positions, or None when the failure model is off. ``idx`` is
    the logical-column window (None = whole leaf)."""
    if worn is None or spec is None:
        return None
    ax = plan.leaf_seq_axis[i]
    bx = plan.batch_axis
    if ax is None:
        return addr_mod.worn_element_mask(worn[i], shifts[i], leaf.shape,
                                          None, bx, spec)
    C = leaf.shape[ax]
    gc = spec.col_groups(C)
    span = leaf.shape if idx is None else (
        leaf.shape[:ax] + (idx.shape[0],) + leaf.shape[ax + 1:])
    slot = jax.lax.broadcasted_iota(jnp.int32, span, bx)
    col = jax.lax.broadcasted_iota(jnp.int32, span, ax)
    logical = col if idx is None else idx[col]
    g = slot * gc + addr_mod.phys_col(logical, shifts[i],
                                      C) // spec.group_cols
    return worn[i][g]


def scrub_tree(
    key: jax.Array,
    tree: Any,
    state: LifetimeState,
    life_plan: LifetimePlan,
    vectors: Sequence,
    *,
    enabled: Optional[Tuple[bool, ...]] = None,
    cols: Optional[int] = None,
    cursor: Optional[jax.Array] = None,
    addr: Optional[Tuple[jax.Array, Optional[jax.Array]]] = None,
    slot_mask: Optional[jax.Array] = None,
) -> Tuple[Any, LifetimeState, WriteStats]:
    """One scrub pass. ``vectors`` is the WRITE plan's per-leaf operand
    tuple (``WritePlan.vectors_for(floor)``) — scrub re-writes at write
    prices. ``enabled``/``cols`` are static (per-signature executables);
    ``cursor`` is a traced i32 start column for the window mode, in
    PHYSICAL row space when ``addr`` carries the remap shifts. ``addr``
    is the physical-addressing operand pair ``(shifts, worn)`` (see
    ``WritePlan.write``); identity shifts with no worn rows reproduce the
    address-free pass bit-for-bit.

    ``slot_mask`` ((B,) bool operand) scopes the pass to a subset of slot
    rows — the sharded scheduler's per-DIE scrub cadence (hot dies run
    extra masked passes over their own slots only). Excluded slots keep
    their decay in the residual mask and, since zero-mask bits are free
    under the scrub protocol, contribute zero energy/flips — so a
    die-masked pass composes bit-exactly with the other dies' masked
    passes at the same key, and ``slot_mask=None`` (every slot) is the
    legacy whole-pool pass unchanged.

    Returns (scrubbed_tree, state', WriteStats): masks of scrubbed spans
    are replaced by the residual (failed-correction) masks, scrub wear
    counters advance (per leaf, and per physical row group when the plan
    has an address layer), and the pass's stats reduce into one
    WriteStats.
    """
    plan = life_plan.plan
    spec = plan.address_spec
    shifts, worn = addr if addr is not None else (None, None)
    flat, treedef = jax.tree.flatten(tree)
    if enabled is None:
        enabled = tuple(lvl is not None for lvl in plan.leaf_levels)
    masks = list(state.masks)
    out = []
    acc = WriteStats.zero()
    scrubbed_vec = []
    row_scrub = state.row_scrub_count
    for i, leaf in enumerate(flat):
        lvl = plan.leaf_levels[i]
        if lvl is None or not enabled[i] or masks[i] is None:
            out.append(leaf)
            scrubbed_vec.append(0)
            continue
        k = jax.random.fold_in(key, _SCRUB_KEY_OFFSET + i)
        ax = plan.leaf_seq_axis[i]
        bx = plan.batch_axis
        windowed = cols is not None and ax is not None \
            and cols < leaf.shape[ax]
        if windowed:
            C = leaf.shape[ax]
            phys = (cursor + jnp.arange(cols, dtype=jnp.int32)) % C
            # the cursor walks physical rows; scrub the logical columns
            # they currently back (identity without remap shifts)
            idx = phys if shifts is None else addr_mod.logical_col(
                phys, shifts[i], C)
            w_leaf = _take_cols(leaf, ax, idx)
            w_mask = _take_cols(masks[i], ax, idx)
        else:
            idx = None
            w_leaf, w_mask = leaf, masks[i]
        stuck = _worn_cols_mask(plan, spec, i, leaf, shifts, worn, idx)
        if slot_mask is not None:
            # out-of-die slots are withheld from this pass exactly like
            # worn rows: decay held in the residual, zero drive energy
            row = jax.lax.broadcasted_iota(jnp.int32, w_mask.shape, bx)
            excl = ~slot_mask[row]
            stuck = excl if stuck is None else (stuck | excl)
        if stuck is not None:
            # worn rows cannot be re-driven: their decayed bits are
            # withheld from the corrective write (zero-mask bits are free
            # under the scrub protocol) and stay decayed in the residual
            held = jnp.where(stuck, w_mask, jnp.zeros_like(w_mask))
            w_mask = jnp.where(stuck, jnp.zeros_like(w_mask), w_mask)
        s_leaf, residual, st = plan.leaf_call("leaf_scrub", k, w_leaf,
                                              w_mask, vectors[i])
        if stuck is not None:
            residual = residual | held
        if windowed:
            out.append(_put_cols(leaf, ax, idx, s_leaf))
            masks[i] = _put_cols(masks[i], ax, idx, residual)
        else:
            out.append(s_leaf)
            masks[i] = residual
        acc = acc + st
        scrubbed_vec.append(1)
        if spec is not None:
            # book row-group scrub wear: one re-write opportunity per
            # covered column per slot row (physical-space accounting)
            B = leaf.shape[bx]
            G = row_scrub.shape[1]
            if ax is None:
                inc = jnp.zeros((G,), jnp.int32).at[
                    jnp.arange(B, dtype=jnp.int32)].add(1)
            else:
                c0 = cursor if windowed else jnp.zeros((), jnp.int32)
                n_cols = cols if windowed else leaf.shape[ax]
                inc = addr_mod.window_group_counts(
                    c0, n_cols, leaf.shape[ax], B, G, spec)
            if slot_mask is not None:
                # scrub wear is booked only for the covered die's rows
                # (groups are slot-major: group g backs slot g // gc)
                gc = 1 if ax is None else spec.col_groups(leaf.shape[ax])
                sl = jnp.arange(G, dtype=jnp.int32) // gc
                covered = slot_mask[jnp.clip(sl, 0, B - 1)] & (sl < B)
                inc = jnp.where(covered, inc, 0)
            row_scrub = row_scrub.at[i].add(inc)
    scrubbed = jnp.asarray(scrubbed_vec, jnp.int32)
    state2 = dataclasses.replace(
        state, masks=tuple(masks),
        scrub_count=state.scrub_count + scrubbed,
        row_scrub_count=row_scrub,
        last_scrub_step=jnp.where(scrubbed > 0, state.step,
                                  state.last_scrub_step))
    return treedef.unflatten(out), state2, acc


def scrub_span_args(stats: WriteStats, policy, *, cols: int,
                    floor, resident: Sequence[int]) -> dict:
    """Telemetry attribution for one scrub pass's background span
    (``repro.telemetry``): the policy identity, the window width, the
    quality floor the re-writes were driven at, and the co-resident
    requests the pass interferes with. ``stats.energy_pj`` stays a LAZY
    device reference — the tracer resolves it in the one batched
    finalize transfer, never here."""
    return {**policy.describe(), "cols": int(cols or 0),
            "floor": getattr(floor, "name", str(floor)),
            "energy_pj": stats.energy_pj,
            "resident": list(resident)}
