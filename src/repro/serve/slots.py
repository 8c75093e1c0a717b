"""Slot-pool KV cache: a fixed-capacity pool of per-request cache rows.

The pool owns ONE device-resident cache tree whose slot (batch) dimension is
the pool capacity. Requests are admitted into free slots — new prefills land
in rows other slots are still decoding through — and release their slot on
completion. The free-list always hands out the lowest slot ids, so a group
of requests admitted together occupies a contiguous prefix: admitting the
whole pool at once reproduces the monolithic batch layout exactly, which is
what the bit-parity contract with ``ServingEngine.generate`` rests on (the
extent-write RNG hashes flat lane indices, so identical pool/batch shapes
mean identical RNG lanes).

Alongside the cache tree the pool carries the per-slot decode state the
scan-resident burst needs — current token, position, and the per-slot
energy/flip/error attribution accumulators — all on device between
scheduler events. Slot *metadata* (which request occupies a slot, how many
tokens it still owes) is host-side bookkeeping: admission and completion
times are fully host-predictable, so the scheduler never reads device state
to make a scheduling decision.
"""
from __future__ import annotations

import heapq
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.energy_model import zero_slot_stats
from repro.memory import WriteStats
from repro.serve.engine import BATCH_AXIS
from repro.telemetry.scopes import scope


@jax.jit
def _extract_rows(tree: Any, idx: jax.Array) -> Any:
    """Gather slot rows ``idx`` from every leaf along BATCH_AXIS."""
    return jax.tree.map(lambda a: jnp.take(a, idx, axis=BATCH_AXIS), tree)


@jax.jit
def _admission_update(cache: Any, tok: jax.Array, pos: jax.Array,
                      slot_acc: Dict[str, jax.Array],
                      acc_prefill: "WriteStats",
                      rows: Any, tok_new: jax.Array, pos_new: jax.Array,
                      idx: jax.Array, acc: "WriteStats"):
    """ALL device-side admission bookkeeping as ONE compiled call: insert
    the stored rows, install first token + position, reset the admitted
    slots' attribution ledgers to their (even) share of the admission
    write, and fold the write's ``WriteStats`` into the running
    prefill-stream accumulator. Eager ``.at[].set`` dispatches here used to
    dominate the scheduler's event cost — keep any new per-admission
    device math inside this jit."""
    with scope("admit_update"):
        cache = jax.tree.map(
            lambda a, r: jnp.moveaxis(
                jnp.moveaxis(a, BATCH_AXIS, 0).at[idx].set(
                    jnp.moveaxis(r, BATCH_AXIS, 0)), 0, BATCH_AXIS),
            cache, rows)
        tok = tok.at[idx].set(tok_new)
        pos = pos.at[idx].set(pos_new)
        admitted = jnp.zeros(tok.shape, bool).at[idx].set(True)
        m = float(idx.shape[0])
        share = {"energy_pj": acc.energy_pj / m,
                 "flips": (acc.flips01 + acc.flips10).astype(jnp.float32)
                 / m,
                 "errors": acc.errors.astype(jnp.float32) / m}
        slot_acc = {k: jnp.where(admitted, share[k], v)
                    for k, v in slot_acc.items()}
        acc_prefill = acc_prefill + acc
    return cache, tok, pos, slot_acc, acc_prefill


class SlotPool:
    """Fixed-capacity pool of cache rows with free-list admission."""

    def __init__(self, api, capacity: int, max_seq: int):
        self.capacity = capacity
        self.cache = api.init_cache(capacity, max_seq)
        self.tok = jnp.zeros((capacity,), jnp.int32)
        self.pos = jnp.zeros((capacity,), jnp.int32)
        self.slot_acc = zero_slot_stats(capacity)
        #: host metadata: the occupying request (scheduler-defined object)
        self.slot_req: List[Optional[Any]] = [None] * capacity
        self._free: List[int] = list(range(capacity))
        heapq.heapify(self._free)
        # refcounted column ownership (serve/prefix.py): ``col_refs[s]``
        # counts the slots currently *linking* their leading KV columns to
        # slot s's resident columns; a free slot with inbound links is
        # BLOCKED from allocation (overwriting it would corrupt every
        # linker) until its links drop or are copy-on-write detached.
        # ``links[linker] = (owner, cols)`` records the outbound link;
        # ``generation[s]`` bumps per admission so stale prefix-cache
        # entries naming an overwritten slot are droppable by comparison.
        self.col_refs: List[int] = [0] * capacity
        self.links: Dict[int, tuple] = {}
        self.generation: List[int] = [0] * capacity
        # occupancy telemetry for the serve report
        self.admissions = 0
        self.completions = 0
        self.peak_occupancy = 0
        # die mesh (repro.sharding.DieMesh), attached by a sharded
        # scheduler: slot -> die is a pure layout mapping, so per-die
        # occupancy stays free host bookkeeping
        self.mesh: Optional[Any] = None

    # -------------------------------------------------------------- free list
    def free_slots(self) -> int:
        return len(self._free)

    def occupied(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    def busy(self) -> bool:
        return len(self._free) < self.capacity

    def blocked_free(self) -> List[int]:
        """Free slots pinned by inbound prefix links (ascending): holders
        of shared columns that must survive until their linkers complete
        or a copy-on-write detaches them."""
        return sorted(i for i in self._free if self.col_refs[i] > 0)

    def allocatable(self, exclude: Sequence[int] = ()) -> int:
        """Free slots actually claimable right now: not link-blocked, not
        in ``exclude`` (the match owners of an in-flight admission)."""
        avoid = set(exclude)
        return sum(1 for i in self._free
                   if self.col_refs[i] == 0 and i not in avoid)

    def alloc(self, n: int,
              scores: Optional[Sequence[float]] = None,
              exclude: Sequence[int] = ()) -> List[int]:
        """Claim n free slots. Default: the lowest ids (ascending — see
        module doc; the lockstep bit-parity contract rests on it).

        ``scores`` ((capacity,) host floats, higher = worse home) switches
        to wear-aware placement: the freest slots by (score, id) — ties
        fall back to lowest-id, so a uniform score vector reproduces the
        default order exactly. The serving scheduler passes the per-slot
        wear/residual-decay scores from its last wear checkpoint when a
        HIGH-quality request is admitted under the address layer.

        Link-blocked slots (``col_refs > 0``) and ``exclude`` members are
        never handed out — eviction of a shared prefix owner is blocked
        while its refcount is positive. With no links and no exclusions
        (every prefix-off run) the order is bit-identical to the original
        free-list discipline."""
        avoid = {i for i in self._free if self.col_refs[i] > 0}
        avoid.update(exclude)
        if not avoid:
            assert n <= len(self._free), (n, len(self._free))
            if scores is None:
                return [heapq.heappop(self._free) for _ in range(n)]
            ids = sorted(self._free,
                         key=lambda i: (float(scores[i]), i))[:n]
        else:
            cand = [i for i in self._free if i not in avoid]
            assert n <= len(cand), (n, len(cand), sorted(avoid))
            if scores is None:
                ids = sorted(cand)[:n]
            else:
                ids = sorted(cand, key=lambda i: (float(scores[i]), i))[:n]
        taken = set(ids)
        self._free = [i for i in self._free if i not in taken]
        heapq.heapify(self._free)
        return ids

    # ------------------------------------------------------- prefix links
    def link(self, linker: int, owner: int, cols: int) -> None:
        """Record that ``linker``'s leading ``cols`` KV columns are backed
        by ``owner``'s physical columns. The owner's refcount blocks its
        eviction until every linker completes or is CoW-detached."""
        assert linker not in self.links, linker
        if linker == owner:
            return  # re-admission into the owner slot shares nothing new
        self.links[linker] = (owner, cols)
        self.col_refs[owner] += 1

    def unlink(self, linker: int) -> None:
        """Drop ``linker``'s outbound link (completion or CoW): the owner
        loses one inbound ref and may become evictable again."""
        owner, _ = self.links.pop(linker)
        assert self.col_refs[owner] > 0, owner
        self.col_refs[owner] -= 1

    def cow_detach(self, owner: int) -> List[tuple]:
        """Copy-on-write: detach every linker of ``owner`` so its columns
        may be overwritten. The linkers' rows already mirror the shared
        bits on device — physically this is the moment each linker's own
        rows are actually driven, so the caller books one full column
        write per returned ``(linker, cols)`` through the plan's
        ``alias_saving`` pricing (paying back exactly what the link was
        credited) plus the admission wear of those columns."""
        out = [(lk, cols) for lk, (ow, cols) in self.links.items()
               if ow == owner]
        for lk, _ in out:
            self.unlink(lk)
        return sorted(out)

    def release(self, slot_ids: Sequence[int]) -> None:
        """Return slots to the free list — pure host bookkeeping (the
        attribution ledger is reset at the NEXT admission, inside the
        single jitted admission update; a freed slot's stale ledger row is
        never read). Cache rows keep their stale bits on purpose: the next
        admission diffs against them (redundant-write elimination over a
        long-lived shared cache) and the prefix cache may keep *linking*
        new requests to a released slot's resident prefix columns until an
        admission overwrites them (generation check). A completing slot
        drops its own outbound link; inbound links survive release — they
        pin the slot's columns, not its occupancy."""
        for i in slot_ids:
            assert self.slot_req[i] is not None, i
            self.slot_req[i] = None
            if i in self.links:
                self.unlink(i)
            heapq.heappush(self._free, i)
        self.completions += len(slot_ids)

    # ------------------------------------------------------------ device rows
    def extract_rows(self, slot_ids: Sequence[int]) -> Any:
        """Current cache rows for ``slot_ids`` (the admission write's
        ``old``: a freed slot's stale data, or zeros on a cold pool)."""
        return _extract_rows(self.cache, jnp.asarray(list(slot_ids),
                                                     jnp.int32))

    def admit(self, slot_ids: Sequence[int], requests: Sequence[Any],
              stored_rows: Any, first_tok: jax.Array,
              pos0: Sequence[int], acc: WriteStats,
              acc_prefill: WriteStats) -> WriteStats:
        """Install an admission group: stored (post-extent-write) cache
        rows, first sampled token, the decode position of each slot, and
        the group's write stats (per-slot attribution + prefill stream) —
        one compiled call for all of it. Returns the updated prefill
        accumulator."""
        idx = jnp.asarray(list(slot_ids), jnp.int32)
        (self.cache, self.tok, self.pos, self.slot_acc,
         acc_prefill) = _admission_update(
            self.cache, self.tok, self.pos, self.slot_acc, acc_prefill,
            stored_rows, first_tok,
            jnp.asarray(list(pos0), jnp.int32), idx, acc)
        for i, r in zip(slot_ids, requests):
            assert self.slot_req[i] is None, i
            assert self.col_refs[i] == 0, (i, self.col_refs[i])
            self.slot_req[i] = r
            # the slot's previous resident bits are gone: invalidate every
            # prefix-cache entry naming them (generation comparison)
            self.generation[i] += 1
        self.admissions += len(slot_ids)
        self.peak_occupancy = max(self.peak_occupancy,
                                  self.capacity - len(self._free))
        return acc_prefill

    def active_mask(self) -> jax.Array:
        """(capacity,) bool device mask of occupied slots."""
        return jnp.asarray([r is not None for r in self.slot_req], bool)

    def stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "capacity": self.capacity, "admissions": self.admissions,
            "completions": self.completions,
            "peak_occupancy": self.peak_occupancy,
            "occupancy": self.capacity - len(self._free)}
        if self.mesh is not None:
            out["occupancy_by_die"] = [
                sum(1 for i in range(*self.mesh.slot_slice(d).indices(
                    self.capacity)) if self.slot_req[i] is not None)
                for d in range(self.mesh.n_dies)]
        return out

    def telemetry_gauges(self) -> Dict[str, int]:
        """The pool's per-event gauge sample (``repro.telemetry``) — all
        host free-list metadata, no device traffic."""
        return {"serve_pool_occupancy": self.capacity - len(self._free)}
