"""Serving engine: batched prefill + decode with EXTENT-approximate KV writes.

The KV cache is the serving system's LLC: the highest-volume, error-tolerant
write stream (the paper's Fig. 13 analogue — decode writes one fresh KV
entry per layer per token, forever). EXTENT integration exploits a clean
identity: applying the approximate write to (old_cache, new_cache) after a
decode step is *exactly* the paper's write semantics —

  * untouched slots are bit-identical -> CMP redundant-write elimination:
    zero energy, zero error risk;
  * the one freshly-written slot per layer flips bits -> pays level energy
    and carries the level WER.

So the engine needs no hooks inside the models: it diffs cache trees.
Priority policy: K at MID (errors perturb attention patterns), V at LOW
(errors only perturb the payload), recurrent/conv states EXACT (errors
persist in the recurrence — DESIGN.md §4).

The whole write path lives behind the ``repro.memory`` substrate: the
engine resolves ONE ``WritePlan`` for its cache shape at construction
(static policy + per-floor driver vectors + RNG layout, resolved exactly
once) and selects the implementation by ``ServeConfig.backend`` — a
registry name (``"oracle"`` / ``"lanes_ref"`` / ``"pallas"`` / ``"exact"``)
instead of the old scattered kernel/interpret boolean pairs.

The write is **jit-resident and scan-resident**: a decode *burst* of n
tokens is ONE compiled call — ``jax.lax.scan`` over the fused
``decode -> cache diff-write -> sampling -> stats accumulation`` step —
with per-write stats accumulated into ONE device-resident
``repro.memory.WriteStats`` and synced to the ``StepEnergyMeter`` exactly
once per ``generate()`` — the token loop performs zero device->host
transfers.

Continuous batching rides on three extensions, all engineered so that the
lockstep case (every slot admitted together, pool shape == batch shape)
stays **bit-identical** to the monolithic path:

  * per-slot ``pos`` vectors and an ``active`` mask in the burst — finished
    or empty slots carry their cache rows through unchanged, so the CMP
    diff write skips them at zero energy (``jnp.where`` with an all-true
    mask is a bit-exact identity);
  * per-leaf driver vectors are *operands* of the compiled burst, not
    closed-over constants: the quality floor negotiated through the
    ``ExtentTable`` can change between bursts without retracing (the
    extent-write counter RNG hashes flat lane indices, so the write itself
    is layout-invariant — see tests/test_extent_parity.py);
  * admission prefills diff against the *current* pool rows (the freed
    slot's stale bits), which is exactly the long-lived shared-cache
    redundant-write-elimination the paper targets; ``generate()`` diffs
    against zeros, and extracting zero rows from a fresh pool reproduces
    it bit-for-bit.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.approx_store import approx_write_with_stats
from repro.core.energy_model import (StepEnergyMeter, add_slot_stats,
                                     zero_slot_stats)
from repro.core.extent_table import QualityController
from repro.core.priority import Priority, kv_cache_policy
from repro.memory import WritePlan, WriteStats
from repro.models import ModelApi, get_model
from repro.sharding.mesh import die_device_mesh
from repro.telemetry.scopes import scope

#: every family's cache leaves carry the request/slot dimension at axis 1
#: (see ModelApi.cache_axes: ("layers", "batch", ...)).
BATCH_AXIS = 1


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_seq: int = 256
    max_new_tokens: int = 32
    extent_enabled: bool = True
    greedy: bool = True
    temperature: float = 1.0
    seed: int = 0
    # EXTENT write-path backend: a repro.memory registry name. "lanes_ref"
    # (pure-jnp lane path) is the default on every platform; "pallas"
    # selects the write and scrub kernels — the Pallas interpreter off a
    # TPU, native on one (chip_smoke.py runs it on a v5e, bit-identical
    # to lanes_ref in tokens and the whole ledger);
    # "oracle" is the eager bit-unpacked reference; "exact" disables the
    # approximation model while keeping the data path.
    backend: str = "lanes_ref"
    # optional post-write retention-upset hook (paper §III): bit-flip BER
    # applied to freshly stored cache bits; the hardened driver protects
    # sign/exponent planes. Surfaced as soft_strikes in the serve report.
    soft_error_ber: float = 0.0
    soft_error_hardened: bool = True
    # repro.reliability: modeled device dwell (seconds) per decode step —
    # 0.0 disables the retention model entirely (the burst carry and the
    # compiled computation are then IDENTICAL to the pre-reliability
    # engine). With dwell > 0 every stored cache bit decays per step at the
    # Δ(T)-derived rate of its priority level and ambient temperature, and
    # the scheduler may run scrub passes against the accumulated decay.
    retention_scale: float = 0.0
    ambient_k: float = 300.0
    # physical addressing (repro.memory.address): "rotate" enables the
    # wear-leveling remap (the scheduler rotates the logical→physical
    # column permutation when hot-row wear concentrates); endurance_budget
    # > 0 enables the stuck-at failure model (row groups whose wear
    # exhausts the budget stop accepting writes). Either turns the address
    # layer on; with identity shifts and no worn rows the token/energy
    # stream is bit-identical to wear_policy="none".
    wear_policy: str = "none"
    endurance_budget: int = 0
    remap_group_cols: int = 8
    # content-addressable prefix cache (repro.serve.prefix): admission
    # resolves the request's leading prompt chunks against a CAM-style
    # match table and, on a hit, LINKS the leading KV columns to already-
    # resident physical columns instead of re-writing them (zero energy,
    # zero WER exposure for the skipped columns; refcounted ownership +
    # copy-on-write in the slot pool). Off by default — prefix-off runs
    # are bit-identical to an engine without the subsystem.
    prefix_cache: bool = False
    prefix_chunk: int = 8
    prefix_table_size: int = 256
    # sharded serving (repro.sharding.DieMesh): the slot pool spans
    # ``shards`` independently aging STT-RAM dies, partitioned over the
    # slot axis. The burst stays ONE full-pool compiled scan regardless —
    # the flat-logical-index RNG layout makes the shard count a pure
    # layout choice, so any ``shards`` run is bit-identical (tokens,
    # flips, energy, WER) to ``shards=1`` until per-die state (ambients,
    # wear) actually diverges. Pool capacity must divide evenly by it.
    shards: int = 1


def _tag_cache(cache: Any) -> Any:
    """Priority tree for a cache pytree via the KV policy."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: kv_cache_policy(p, l), cache)


def _is_approx_leaf(leaf, tag: Priority) -> bool:
    """Floating leaves below EXACT go through the approximate driver
    (the seed engine's condition — every float width)."""
    return (jnp.issubdtype(leaf.dtype, jnp.floating)
            and tag != Priority.EXACT)


def _row_mask(active: jax.Array, ndim: int) -> jax.Array:
    """(B,) bool -> broadcastable mask over a cache leaf with the slot
    dimension at BATCH_AXIS."""
    shape = [1] * ndim
    shape[BATCH_AXIS] = active.shape[0]
    return active.reshape(shape)


def mask_rows(new_tree: Any, old_tree: Any, active: jax.Array) -> Any:
    """Per-slot select: active rows take the new value, inactive rows keep
    the old — the decode-burst guard that makes finished/empty slots free
    under CMP (their rows never change, so the diff write skips them)."""
    return jax.tree.map(
        lambda n, o: jnp.where(_row_mask(active, n.ndim), n, o),
        new_tree, old_tree)


def eager_extent_cache_write(key, old_cache, new_cache, tags):
    """Eager oracle for the fused cache write (the seed engine's data path).

    Diffs the whole cache through ``approx_write_with_stats`` leaf by leaf
    with host-synced Python accumulation. Kept as the reference the
    benchmarks validate the jit-resident path against — never called from
    the serving loop.
    """
    flat_old, treedef = jax.tree.flatten(old_cache)
    flat_new = treedef.flatten_up_to(new_cache)
    flat_tag = treedef.flatten_up_to(tags)
    stored, agg = [], {"energy_pj": 0.0, "bits_written": 0, "bit_errors": 0,
                       "bits_total": 0}
    for i, (o, n, t) in enumerate(zip(flat_old, flat_new, flat_tag)):
        if _is_approx_leaf(n, t):  # every float width, as the seed did
            s, st = approx_write_with_stats(jax.random.fold_in(key, i),
                                            o, n, t)
            agg["energy_pj"] += float(st.energy_pj)
            agg["bits_written"] += int(st.bits_written)
            agg["bit_errors"] += int(st.bit_errors)
            agg["bits_total"] += int(st.bits_total)
            stored.append(s)
        else:
            stored.append(n)  # EXACT fast path (recurrent states, ints)
    return treedef.unflatten(stored), agg


class ServingEngine:
    """Batched autoregressive serving over any registered architecture.

    One engine instance owns the compiled executables (fused prefill /
    admission / decode burst); both the monolithic ``generate()`` path and
    the continuous-batching scheduler (serve/scheduler.py) drive the SAME
    burst function, which is what makes their write streams bit-comparable.
    """

    def __init__(self, cfg: ModelConfig, serve_cfg: ServeConfig,
                 params: Optional[Any] = None):
        self.cfg = cfg
        self.scfg = serve_cfg
        self.api: ModelApi = get_model(cfg)
        key = jax.random.PRNGKey(serve_cfg.seed)
        self.params = params if params is not None else self.api.init(key)
        self.meter = StepEnergyMeter()
        self.controller = QualityController()
        # the write plan: cache *structure* (not shapes) fixes which leaves
        # are approximate and at which driver level, so it is resolved
        # exactly once here from an abstract cache. The per-floor driver
        # vectors (thresholds/energies) are *operands* of the compiled
        # steps — see WritePlan.vectors_for — so a per-request quality
        # floor swaps levels between bursts without ever retracing.
        cache_sds = jax.eval_shape(lambda: self.api.init_cache(
            1, self.scfg.max_seq))
        # the physical addressing layer (repro.memory.address): on when a
        # wear policy or an endurance budget asks for it. The remap shifts
        # ride the burst as (L,) i32 OPERANDS — a wear-leveling rotation
        # between bursts swaps integers, never retraces.
        self.wear = (serve_cfg.wear_policy != "none"
                     or serve_cfg.endurance_budget > 0)
        addr_spec = None
        if self.wear:
            from repro.memory import AddressSpec
            addr_spec = AddressSpec(
                group_cols=serve_cfg.remap_group_cols,
                endurance_budget=serve_cfg.endurance_budget)
        # a pool split over dies (``shards``) splits the cache's slot axis
        # over this mesh: kernels XLA cannot partition map over it per die
        slot_mesh = (die_device_mesh(serve_cfg.shards)
                     if serve_cfg.shards > 1 else None)
        self.plan = WritePlan.for_tree(
            cache_sds, policy=kv_cache_policy, backend=serve_cfg.backend,
            axes=self.api.cache_axes(), batch_axis=BATCH_AXIS,
            soft_error_ber=serve_cfg.soft_error_ber,
            soft_error_hardened=serve_cfg.soft_error_hardened,
            address_spec=addr_spec, slot_mesh=slot_mesh)
        # the lifetime plan shadows the write plan when retention is on:
        # per-(leaf, floor, ambient) decay thresholds are operands, resolved
        # once — an ambient-temperature schedule swaps arrays between
        # bursts, never retraces (repro.reliability.lifetime). The wear
        # layer needs the lifetime state too (it carries the row-group
        # wear counters); with retention_scale == 0 the plan is immortal —
        # ``advance`` is an identity and no decay RNG runs — but the
        # counters still ride the scan.
        self.life_plan = None
        if serve_cfg.retention_scale > 0.0 or self.wear:
            from repro.reliability import LifetimePlan
            self.life_plan = LifetimePlan.for_tree(
                cache_sds, self.plan, ambient_k=serve_cfg.ambient_k,
                dwell_s=serve_cfg.retention_scale)
            self._scrub_fused = jax.jit(
                self._make_scrub(), static_argnames=("enabled", "cols"))
            self._life_reset = jax.jit(self.life_plan.reset_rows)
            self._slot_scores = jax.jit(self.life_plan.slot_scores)
        self._prefill_fused = jax.jit(self._make_fused_prefill(
            diff_old_rows=False))
        self._admit_fused = jax.jit(self._make_fused_prefill(
            diff_old_rows=True))
        self._burst = jax.jit(self._make_burst(), static_argnames=("n",))
        # prefix-cache admission path (serve/prefix.py). Registered
        # unconditionally — jit compiles lazily, so prefix-off runs never
        # trace these and stay bit-identical to the pre-prefix engine.
        self._admit_linked_fused = jax.jit(self._make_linked_prefill())
        self._splice_rows = jax.jit(self._make_splice())
        if self.life_plan is not None:
            self._life_reset_linked = jax.jit(
                self.life_plan.reset_rows_linked)
            if self.wear:
                self._life_admit = jax.jit(
                    self.life_plan.record_admission_write)

    # ------------------------------------------------------------ write plan
    def vectors_for_floor(self, floor: Priority = Priority.LOW) -> Tuple:
        """Per-leaf driver-vector operands for one quality floor (see
        WritePlan). LOW is the identity floor: the static KV policy alone."""
        return self.plan.vectors_for(floor)

    def retention_vectors_for(self, floor: Priority = Priority.LOW,
                              ambient_k: Optional[float] = None) -> Tuple:
        """Per-leaf decay-threshold operands (LifetimePlan) for one
        (floor, ambient) pair — same operand-swap/no-retrace contract as
        ``vectors_for_floor``. Only valid with retention enabled."""
        assert self.life_plan is not None, "retention_scale == 0"
        return self.life_plan.vectors_for(floor, ambient_k=ambient_k)

    def retention_vectors_for_dies(self, floor: Priority,
                                   ambients: Tuple[float, ...],
                                   slots_per_die: int) -> Tuple:
        """Per-die decay-threshold operands for a die-sharded pool (see
        ``LifetimePlan.vectors_for_dies``): uniform ambients return the
        legacy pool-wide operands (same executables, bit-identical);
        divergent ambients return per-slot ``(B, nbits)`` rows."""
        assert self.life_plan is not None, "retention_scale == 0"
        return self.life_plan.vectors_for_dies(floor, ambients,
                                               slots_per_die)

    def remap_cost(self, tree: Any) -> Tuple[float, int]:
        """Host constants (energy_pj, bits) of ONE wear-leveling rotation
        — delegates to the plan's single migration-pricing source (see
        ``WritePlan.migration_cost``)."""
        return self.plan.migration_cost(tree)

    # ---------------------------------------------------------- fused steps
    def _make_fused_prefill(self, diff_old_rows: bool):
        """Fused prefill -> extent write -> first-token sample.

        ``diff_old_rows=False`` (monolithic generate): the write diffs
        against zeros — a cold cache. ``diff_old_rows=True`` (slot-pool
        admission): the caller passes the pool's current rows for the
        allocated slots, so the write pays only the bits that differ from
        the evicted request's stale data — the long-lived-cache
        redundant-write elimination the slot pool exists for.
        """
        def prefill(params, batch, old_rows, key, vectors):
            key, k_write, k_sample = jax.random.split(key, 3)
            with scope("admit_model"):
                logits, cache = self.api.prefill(params, batch,
                                                 self.scfg.max_seq)
            acc = WriteStats.zero()
            if self.scfg.extent_enabled:
                with scope("extent_write"):
                    old = (old_rows if diff_old_rows
                           else jax.tree.map(jnp.zeros_like, cache))
                    cache, acc = self.plan.write(k_write, old, cache,
                                                 vectors)
            with scope("sample"):
                tok = self._sample(k_sample, logits)
            return tok, cache, key, acc

        if diff_old_rows:
            return prefill
        return lambda params, batch, key, vectors: prefill(
            params, batch, None, key, vectors)

    def _make_linked_prefill(self):
        """Admission prefill with prefix-linked leading columns.

        Identical to the ``diff_old_rows=True`` fused prefill — same RNG
        split schedule, same model prefill, same sampler — except the
        extent write takes ``alias_cols`` ((B,) i32): for slot lane b the
        first ``alias_cols[b]`` ring columns keep their OLD bits (which
        the caller pre-spliced to the link owner's resident columns via
        ``_splice_rows``), so CMP sees zero changed bits there — zero
        write energy, zero WER exposure, exactly the paper's
        redundant-write elimination applied across requests. With
        ``alias_cols == 0`` everywhere the where-mask is empty and the
        computation is bit-identical to ``_admit_fused``.
        """
        def prefill(params, batch, old_rows, key, vectors, alias_cols):
            key, k_write, k_sample = jax.random.split(key, 3)
            with scope("admit_model"):
                logits, cache = self.api.prefill(params, batch,
                                                 self.scfg.max_seq)
            acc = WriteStats.zero()
            if self.scfg.extent_enabled:
                with scope("extent_write"):
                    cache, acc = self.plan.write(
                        k_write, old_rows, cache, vectors,
                        alias_cols=alias_cols)
            with scope("sample"):
                tok = self._sample(k_sample, logits)
            return tok, cache, key, acc

        return prefill

    def _make_splice(self):
        """Graft the link owners' resident prefix columns into extracted
        admission rows: per approximate ring leaf, lane b's columns
        ``[0, alias_cols[b])`` take ``owner_rows``'s bits, the rest keep
        ``old_rows``'s. The spliced tree is the linked prefill's ``old`` —
        its aliased columns are *stored as-is* (the owner's exact current
        bits, realized write errors and decay included) and diff as
        identical under CMP."""
        def splice(old_rows, owner_rows, alias_cols):
            flat_old, treedef = jax.tree.flatten(old_rows)
            flat_own = treedef.flatten_up_to(owner_rows)
            out = []
            for i, (o, w) in enumerate(zip(flat_old, flat_own)):
                keep = self.plan._alias_keep(i, o, alias_cols)
                out.append(o if keep is None else jnp.where(keep, w, o))
            return treedef.unflatten(out)

        return splice

    def _make_burst(self):
        """A decode burst: ``n`` fused steps as ONE ``lax.scan`` call.

        Carries (token, cache, per-slot pos, RNG key, global WriteStats
        accumulator, per-slot attribution accumulator); ``active`` is a
        (B,) bool operand constant across the burst (the scheduler sizes
        bursts so no slot completes mid-scan). Inactive rows keep their
        cache bits, position and token — under an all-true mask every
        guard is a bit-exact identity, so ``generate()`` and the lockstep
        scheduler hit literally the same compiled computation.
        """
        retention = self.life_plan is not None
        wear = self.wear

        def step_body(params, tok, cache, pos, key, acc, slot_acc, active,
                      vectors, life, rvec, shifts=None):
            act_i = active.astype(jnp.int32)
            key, k_write, k_sample = jax.random.split(key, 3)
            with scope("decode_model"):
                logits, new_cache = self.api.decode_step(
                    params, tok, cache, pos, self.scfg.max_seq)
            with scope("mask_rows"):
                new_cache = mask_rows(new_cache, cache, active)
            if self.scfg.extent_enabled:
                if wear:
                    # physical addressing: the written column's address
                    # maps through the remap shifts to its row group —
                    # worn groups are stuck-at, and the write books
                    # per-group endurance wear. Shifts/worn are operands
                    # (worn derives from the carried life state), so a
                    # rotation or a mid-burst failure never retraces.
                    with scope("extent_write"):
                        worn = self.life_plan.worn_groups(life)
                        new_cache, st = self.plan.write_columns(
                            k_write, cache, new_cache, pos, vectors,
                            addr=(shifts, worn))
                    with scope("extent_stats"):
                        life = self.life_plan.record_column_write(
                            life, new_cache, pos, active, shifts)
                else:
                    with scope("extent_write"):
                        new_cache, st = self.plan.write_columns(
                            k_write, cache, new_cache, pos, vectors)
                with scope("extent_stats"):
                    acc = acc + st
                    slot_acc = add_slot_stats(slot_acc, st, active)
            if retention:
                # the step re-wrote the active slots' ring columns: their
                # decay record is void (stale bits would make a later
                # scrub corrupt live data) ...
                with scope("retention_clear"):
                    life = self.life_plan.clear_written(life, pos, active)
                # ... then dwell one step at ambient T: every stored bit
                # of the approximate leaves may decay. The retention
                # sub-streams fold off k_write, so the write/sample RNG
                # schedule is IDENTICAL with retention on or off — a
                # 300 K run (all decay thresholds clamp to zero) is
                # bit-identical to a retention-disabled run.
                with scope("retention_decay"):
                    new_cache, life = self.life_plan.advance(
                        k_write, new_cache, life, rvec)
            with scope("sample"):
                tok2 = self._sample(k_sample, logits)
                tok2 = jnp.where(active, tok2, tok)
            return tok2, new_cache, pos + act_i, key, acc, slot_acc, life

        if wear:
            def burst(params, tok, cache, pos, key, acc, slot_acc, active,
                      vectors, life, rvec, shifts, *, n):
                def body(carry, _):
                    out = step_body(params, *carry[:6], active, vectors,
                                    carry[6], rvec, shifts)
                    return out, out[0]

                carry = (tok, cache, pos, key, acc, slot_acc, life)
                (tok, cache, pos, key, acc, slot_acc, life), toks = (
                    jax.lax.scan(body, carry, None, length=n))
                return tok, cache, pos, key, acc, slot_acc, life, toks
        elif retention:
            def burst(params, tok, cache, pos, key, acc, slot_acc, active,
                      vectors, life, rvec, *, n):
                def body(carry, _):
                    out = step_body(params, *carry[:6], active, vectors,
                                    carry[6], rvec)
                    return out, out[0]

                carry = (tok, cache, pos, key, acc, slot_acc, life)
                (tok, cache, pos, key, acc, slot_acc, life), toks = (
                    jax.lax.scan(body, carry, None, length=n))
                return tok, cache, pos, key, acc, slot_acc, life, toks
        else:
            def burst(params, tok, cache, pos, key, acc, slot_acc, active,
                      vectors, *, n):
                def body(carry, _):
                    out = step_body(params, *carry, active, vectors,
                                    None, None)
                    return out[:6], out[0]

                carry = (tok, cache, pos, key, acc, slot_acc)
                (tok, cache, pos, key, acc, slot_acc), toks = jax.lax.scan(
                    body, carry, None, length=n)
                return tok, cache, pos, key, acc, slot_acc, toks

        return burst

    def _make_scrub(self):
        """Fused scrub pass (repro.reliability.scrub): corrective re-write
        of the accumulated decay through the SAME backend as the write
        path, stats in one device-resident WriteStats. ``enabled``/``cols``
        are static (one executable per policy signature); ``cursor`` and
        every vector are operands."""
        from repro.reliability import scrub_tree

        if self.wear:
            def scrub(key, cache, life, vectors, cursor, shifts,
                      slot_mask=None, *, enabled, cols):
                # the cursor walks PHYSICAL rows; worn rows stay decayed
                with scope("scrub"):
                    worn = self.life_plan.worn_groups(life)
                    return scrub_tree(key, cache, life, self.life_plan,
                                      vectors, enabled=enabled, cols=cols,
                                      cursor=cursor, addr=(shifts, worn),
                                      slot_mask=slot_mask)
        else:
            def scrub(key, cache, life, vectors, cursor, slot_mask=None,
                      *, enabled, cols):
                with scope("scrub"):
                    return scrub_tree(key, cache, life, self.life_plan,
                                      vectors, enabled=enabled, cols=cols,
                                      cursor=cursor, slot_mask=slot_mask)

        return scrub

    # ------------------------------------------------------------- sampling
    def _sample(self, key, logits: jax.Array) -> jax.Array:
        if self.scfg.greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.scfg.temperature, axis=-1).astype(jnp.int32)

    # ------------------------------------------------------------ generation
    def prompt_len(self, batch: Dict[str, jax.Array]) -> int:
        """Decoder position of the first generated token for a prompt."""
        return batch["tokens"].shape[1] + (
            self.cfg.num_image_tokens if self.cfg.family == "vlm" else 0)

    def generate(self, batch: Dict[str, jax.Array],
                 max_new_tokens: Optional[int] = None, *,
                 sync_stats: bool = True, telemetry: Any = None
                 ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Prefill `batch` then decode. Returns (tokens (B, T_new),
        report{energy, errors, tokens/s-shape stats}).

        The decode loop is ONE compiled call: a scan-resident burst of
        ``mnt - 1`` fused steps, every carried value (token, cache,
        positions, RNG key, stat accumulators) on device; the accumulated
        ``WriteStats`` cross to the host once, after the last token. With
        ``sync_stats=False`` even that transfer is skipped and the raw
        device accumulators are returned under ``report["device_stats"]``
        (used by the no-transfer test and by callers batching many
        generates before accounting).

        ``telemetry`` (a ``repro.telemetry.Telemetry``) adds the
        monolithic run's prefill/decode spans and one instrument drain —
        the compiled computation and the RNG schedule are untouched, so
        tokens and stats stay bit-identical with it on or off.
        """
        mnt = max_new_tokens or self.scfg.max_new_tokens
        key = jax.random.PRNGKey(self.scfg.seed + 1)
        B = batch["tokens"].shape[0]
        vectors = self.vectors_for_floor(Priority.LOW)

        tok, cache, key, pre_acc = self._prefill_fused(self.params, batch,
                                                       key, vectors)
        pos = jnp.full((B,), self.prompt_len(batch), jnp.int32)
        active = jnp.ones((B,), bool)
        acc = WriteStats.zero()
        slot_acc = zero_slot_stats(B)
        life = (self.life_plan.init_state(cache)
                if self.life_plan is not None else None)
        if mnt > 1:
            if self.wear:
                # monolithic generate keeps the identity permutation (no
                # scheduler to rotate it) — bit-identical to wear off
                # until a budget exhausts a row group
                rvec = self.retention_vectors_for(Priority.LOW)
                (_, cache, pos, key, acc, slot_acc, life,
                 toks) = self._burst(
                    self.params, tok, cache, pos, key, acc, slot_acc,
                    active, vectors, life, rvec,
                    self.plan.identity_address().shifts, n=mnt - 1)
            elif self.life_plan is not None:
                rvec = self.retention_vectors_for(Priority.LOW)
                (_, cache, pos, key, acc, slot_acc, life,
                 toks) = self._burst(
                    self.params, tok, cache, pos, key, acc, slot_acc,
                    active, vectors, life, rvec, n=mnt - 1)
            else:
                _, cache, pos, key, acc, slot_acc, toks = self._burst(
                    self.params, tok, cache, pos, key, acc, slot_acc,
                    active, vectors, n=mnt - 1)
            tokens = jnp.concatenate([tok[:, None],
                                      jnp.moveaxis(toks, 0, 1)], axis=1)
        else:
            tokens = tok[:, None]

        if telemetry is not None:
            # the batch's span pair on the serve lane plus ONE drain at
            # the end of the generate (the monolithic "event"); energy
            # args stay lazy device refs until finalize
            ins = telemetry.instruments
            ins.bind("serve_prefill_energy_pj_total",
                     lambda: pre_acc.energy_pj)
            ins.bind("serve_decode_energy_pj_total",
                     lambda: acc.energy_pj)
            root = telemetry.tracer.begin(
                f"generate[B={B}]", 0, track="batch", cat="request")
            telemetry.tracer.complete(
                "prefill", 0, 0, track="batch", parent=root,
                energy_pj=pre_acc.energy_pj)
            telemetry.tracer.complete(
                "decode", 0, mnt - 1, track="batch", parent=root,
                steps=mnt - 1, energy_pj=acc.energy_pj)
            telemetry.tracer.end(root, mnt - 1)
            telemetry.event(mnt - 1, serve_pool_occupancy=B,
                            serve_queue_depth=0)
        if not sync_stats:
            rep = {"device_stats": {"kv_prefill": pre_acc,
                                    "kv_decode": acc},
                   "slot_stats": slot_acc}
            if life is not None:
                rep["lifetime_state"] = life
            return tokens, rep
        # everything the report needs crosses the device boundary in one
        # batched transfer — the token loop itself performed zero
        fetch: Dict[str, Any] = {}
        if self.scfg.extent_enabled:
            fetch["streams"] = (pre_acc, acc)
        if life is not None:
            fetch["retention"] = (life.retention_flips,
                                  life.decayed_bits())
            if self.wear:
                worn = self.life_plan.worn_groups(life)
                fetch["wear"] = (life.row_wear(),
                                 None if worn is None else worn.sum())
        # repro: allow(no-host-sync-in-scan): THE once-per-generate sync
        host = jax.device_get(fetch)
        if self.scfg.extent_enabled:
            pre_host, dec_host = host["streams"]
            self.meter.add_stream("kv_prefill", pre_host)
            self.meter.add_stream("kv_decode", dec_host)
        report = self.meter.summary()
        if life is not None:
            flips, decayed = host["retention"]
            report["retention"] = {
                "ambient_k": self.scfg.ambient_k,
                "dwell_s_per_step": self.scfg.retention_scale,
                "flips": int(flips),
                "decayed_bits": int(decayed),
            }
        if self.wear and life is not None:
            wear, worn_sum = host["wear"]
            report["wear"] = {
                "max_group_wear": int(wear.max()),
                "worn_groups": (int(worn_sum)
                                if worn_sum is not None else 0),
                "endurance_budget": self.scfg.endurance_budget,
                "group_cols": self.scfg.remap_group_cols,
            }
        return tokens, report
