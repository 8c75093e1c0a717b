"""Continuous-batching request scheduler over the slot-pool KV cache.

Drives admission and completion over an arrival stream measured in decode
steps (the serving clock): requests arrive at different times, prefill into
free slots while other slots keep decoding, and release their slot when
their token budget is spent. Between scheduler events the pool decodes in
**bursts** — one ``lax.scan``-compiled call for the whole span until the
next arrival or the earliest completion — so scheduling decisions cost one
host round-trip per *event*, never per token.

Scheduling is fully host-predictable: a request's completion time is fixed
at admission (its token budget), so burst lengths are computed from slot
metadata without reading device state. The device work per event is: one
fused admission prefill per prompt-shape group, one fused decode burst.

Per-request EXTENT quality rides the ``QualityController`` handshake: a
request carrying a quality hint tags its application block in the LRU
``ExtentTable``; every admission resolves its block through the table
(hit/miss/eviction stats land in the serve report) and the pool's write
plan composes the strictest active level with the engine's static KV
policy (``max(policy, floor)`` — hints raise fidelity, never lower it).
Driver vectors are burst operands, so a floor change never retraces.

Bit-parity contract: admitting a full pool in one group and decoding to
completion reproduces ``ServingEngine.generate`` on the equivalent
monolithic batch bit-for-bit — same RNG key schedule, same cache layout,
same compiled burst (see tests/test_scheduler.py).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.energy_model import StepEnergyMeter
from repro.core.priority import Priority
from repro.memory import WriteStats, rng_streams
from repro.serve.engine import BATCH_AXIS, ServingEngine
from repro.serve.prefix import PrefixCache, PrefixConfig, PrefixMatch
from repro.serve.slots import SlotPool
from repro.sharding import DieMesh, uniform
from repro.telemetry import LANE_BACKGROUND, Lazy, Telemetry
from repro.telemetry.scopes import phase


@dataclasses.dataclass
class Request:
    """One serving request. ``prompt`` uses the engine's batch dict format
    with a leading batch dim of 1; ``new_tokens`` counts every generated
    token (the prefill-sampled first token included); ``arrival`` is in
    decode steps. ``app_id`` names the application block the quality
    handshake caches on; ``quality`` is the optional EXTENT hint."""
    rid: int
    prompt: Dict[str, jax.Array]
    new_tokens: int
    arrival: int = 0
    app_id: Optional[Hashable] = None
    quality: Optional[Priority] = None
    # workload-trace provenance (repro.workload): ``session`` groups
    # requests of one conversation; ``modal_seed`` is the PRNGKey seed the
    # non-token prompt leaves (vlm/audio) were generated from, recorded so
    # a trace can regenerate them bit-exactly instead of serializing them.
    session: Optional[int] = None
    modal_seed: Optional[int] = None


def synthetic_requests(cfg, n: int, *, prompt_len: int = 12,
                       new_tokens: int = 8, arrival_every: int = 0,
                       seed: int = 0, app_ids: Sequence = (),
                       qualities: Sequence = ()) -> List[Request]:
    """Deterministic random-token arrival stream for benchmarks/tests.
    ``arrival_every=k`` staggers arrivals k decode steps apart (0 = all at
    once); ``app_ids``/``qualities`` are cycled over the requests when
    non-empty (None entries mean unhinted)."""
    out = []
    for i in range(n):
        prompt = {"tokens": jax.random.randint(
            jax.random.PRNGKey(seed + 17 * i), (1, prompt_len), 0,
            cfg.vocab_size)}
        modal_seed = None
        if cfg.family == "vlm":
            modal_seed = seed + 17 * i + 1
            prompt["image_embeds"] = jax.random.normal(
                jax.random.PRNGKey(modal_seed),
                (1, cfg.num_image_tokens, cfg.vision_dim), jnp.float32)
        if cfg.family == "audio":
            modal_seed = seed + 17 * i + 1
            prompt["frames"] = jax.random.normal(
                jax.random.PRNGKey(modal_seed),
                (1, 24, cfg.d_model), jnp.float32)
        out.append(Request(
            rid=i, prompt=prompt, new_tokens=new_tokens,
            arrival=i * arrival_every,
            app_id=app_ids[i % len(app_ids)] if app_ids else None,
            quality=qualities[i % len(qualities)] if qualities else None,
            session=i, modal_seed=modal_seed))
    return out


class ArrivalQueue:
    """The materialized-list arrival source (the scheduler's default).

    ``ContinuousScheduler.run`` consumes arrival streams through a small
    host-side protocol — ``next_arrival()`` (peek the next arrival step,
    None when drained), ``popleft()`` (take the next request in
    (arrival, rid) order), and truthiness — so a trace iterator
    (``repro.workload.replay.TraceSource``) can feed the same loop as a
    plain request list without the scheduler knowing the difference.
    Everything the protocol touches is host metadata; the one-sync-per-
    event discipline is a property of the loop, not of the source."""

    def __init__(self, requests: Sequence[Request]):
        self._q = collections.deque(
            sorted(requests, key=lambda r: (r.arrival, r.rid)))

    def __bool__(self) -> bool:
        return bool(self._q)

    def __len__(self) -> int:
        return len(self._q)

    def next_arrival(self) -> Optional[int]:
        return self._q[0].arrival if self._q else None

    def popleft(self) -> Request:
        return self._q.popleft()


def as_arrival_source(requests) -> Any:
    """Wrap a request list in an ``ArrivalQueue``; objects already
    speaking the arrival-source protocol pass through untouched."""
    if hasattr(requests, "next_arrival") and hasattr(requests, "popleft"):
        return requests
    return ArrivalQueue(requests)


def _prompt_signature(prompt: Dict[str, jax.Array]) -> Tuple:
    return tuple(sorted((k, tuple(v.shape[1:]), str(v.dtype))
                        for k, v in prompt.items()))


def _stack_prompts(requests: Sequence[Request]) -> Dict[str, jax.Array]:
    keys = requests[0].prompt.keys()
    return {k: jnp.concatenate([r.prompt[k] for r in requests], axis=0)
            for k in keys}


class ContinuousScheduler:
    """Admission/completion loop over one engine's slot pool.

    With retention enabled on the engine (``ServeConfig.retention_scale``)
    the scheduler also owns the pool's ``LifetimeState`` and runs the
    optional ``scrub_policy`` as idle-slot background work: after each
    burst the (host-side, sync-free) policy is consulted; a due pass
    re-writes the accumulated decay through the engine's backend, its
    energy charged to the separate ``kv_scrub`` stream so the report's
    *lifetime* ledger (writes + scrubs) stays honest. Scrub-time quality
    re-resolution goes through the EXTENT table under the ``"scrub"``
    scope — serve and scrub table traffic are reported separately.
    ``ambient_schedule`` is an optional piecewise-constant
    [(from_step, kelvin), ...] die-temperature profile; swapping the
    ambient between bursts swaps decay-threshold operands, never retraces.

    Sharded serving (``ServeConfig.shards`` > 1, repro.sharding.DieMesh):
    the pool spans N independently aging dies partitioned over the slot
    axis. The stack keeps ONE full-pool compiled burst — per-die state
    enters only through operands: ``die_ambients`` (``{die: kelvin}``
    overrides on top of the global ambient/schedule) lift the decay
    thresholds to per-slot rows, dies hotter than the coolest run extra
    die-masked scrub passes (their own scrub cadence), and HIGH-quality
    admissions steer toward cool/low-wear dies through a per-die score
    bias. While the dies are indistinguishable every one of these
    collapses to the legacy 1-die path, so any ``shards`` count is
    bit-identical to ``shards=1`` — shard count is a layout choice.
    """

    def __init__(self, engine: ServingEngine, capacity: int,
                 max_burst: Optional[int] = None,
                 scrub_policy: Optional[Any] = None,
                 ambient_schedule: Optional[Sequence[Tuple[int, float]]]
                 = None,
                 wear_policy: Optional[Any] = None,
                 telemetry: Optional[Telemetry] = None,
                 die_ambients: Optional[Dict[int, float]] = None):
        assert capacity >= 1
        self.eng = engine
        self.pool = SlotPool(engine.api, capacity, engine.scfg.max_seq)
        self.mesh = DieMesh(n_dies=max(1, engine.scfg.shards),
                            capacity=capacity)
        self.die_ambients: Dict[int, float] = dict(die_ambients or {})
        assert all(0 <= d < self.mesh.n_dies for d in self.die_ambients)
        if self.mesh.n_dies > 1:
            # place the pool's slot axis through the die mesh
            # (value-preserving device_put; identity on a 1-device host)
            self.pool.cache = self.mesh.shard_slots(self.pool.cache,
                                                    BATCH_AXIS)
            self.pool.mesh = self.mesh
        self.max_burst = max_burst
        self.scrub_policy = scrub_policy
        self.wear_policy = wear_policy
        # observability is strictly additive: with ``telemetry=None`` no
        # instrument/span/drain exists anywhere in the loop, and with it
        # on, the compiled bursts and the RNG key schedule are untouched
        # — tokens and WriteStats stay bit-identical either way
        self.tele = telemetry
        self.ambient_schedule = (sorted(ambient_schedule)
                                 if ambient_schedule else None)
        self.life = None  # LifetimeState, owned per run()
        self.addr = None  # AddressState (remap shifts), owned per run()
        # content-addressable prefix cache (serve/prefix.py): admission
        # resolves prompt-prefix digests against resident slot columns and
        # links matches instead of re-writing them. None = prefix off —
        # every admission takes the pre-prefix code path untouched.
        self.prefix: Optional[PrefixCache] = None
        if engine.scfg.prefix_cache:
            self.prefix = PrefixCache(PrefixConfig(
                chunk=engine.scfg.prefix_chunk,
                table_size=engine.scfg.prefix_table_size))
        self.meter = StepEnergyMeter()
        # per-rid runtime state. Token fragments are kept as LAZY device
        # array references ((array, column, take) tuples) and materialized
        # only at completion — a host sync per admission/burst here would
        # serialize the device pipeline and eat the batching win.
        self._tokens: Dict[int, List[Tuple[Any, int, int]]] = {}
        self._remaining: Dict[int, int] = {}
        self._admitted: Dict[int, int] = {}
        self._level: Dict[int, Priority] = {}
        self._reports: Dict[int, Dict[str, Any]] = {}

    # ----------------------------------------------------------- quality
    def _resolve_quality(self, r: Request) -> Priority:
        """Admission-time handshake through the EXTENT table. Requests with
        neither an app block nor a hint skip the table entirely (no floor,
        no miss-traffic perturbing the hit-rate stats)."""
        if r.app_id is None and r.quality is None:
            return Priority.LOW
        block = r.app_id if r.app_id is not None else ("rid", r.rid)
        return self.eng.controller.resolve_request(block, hint=r.quality)

    def _floor(self) -> Priority:
        """Strictest quality level among active slots — the pool-wide
        write-plan floor (conservative group policy: a shared physical
        write row serves every co-resident request)."""
        floor = Priority.LOW
        for r in self.pool.slot_req:
            if r is not None:
                floor = max(floor, self._level[r.rid])
        return Priority(floor)

    # ----------------------------------------------------------- telemetry
    def _bind_telemetry(self) -> None:
        """Bind the registry's device-resident metrics to the run's
        scan-carried ``WriteStats`` accumulators. The accumulators ARE
        the hot-path instruments — binding adds no device work; each
        per-event ``Telemetry.event`` drain reads these views in one
        batched transfer."""
        ins = self.tele.instruments
        ins.bind("serve_prefill_energy_pj_total",
                 lambda: self._acc_prefill.energy_pj)
        ins.bind("serve_decode_energy_pj_total",
                 lambda: self._acc_decode.energy_pj)
        ins.bind("serve_scrub_energy_pj_total",
                 lambda: self._acc_scrub.energy_pj)
        ins.bind("serve_remap_energy_pj_total",
                 lambda: self._acc_remap.energy_pj)
        # tuple providers: the parts cross in the same batched transfer
        # and sum on host — a drain never dispatches a device op
        ins.bind("serve_flips_total",
                 lambda: (self._acc_prefill.flips01,
                          self._acc_prefill.flips10,
                          self._acc_decode.flips01,
                          self._acc_decode.flips10))
        ins.bind("serve_bit_errors_total",
                 lambda: (self._acc_prefill.errors,
                          self._acc_decode.errors))
        if self.eng.life_plan is not None:
            ins.bind("serve_retention_flips_total",
                     lambda: self.life.retention_flips)

    def _event_gauges(self, clock: int, pending) -> Dict[str, float]:
        ambient = self._ambient_at(clock)
        return {
            **self.pool.telemetry_gauges(),
            "serve_queue_depth": len(pending),
            "serve_ambient_k": (ambient if ambient is not None
                                else self.eng.scfg.ambient_k),
        }

    def _req_track(self, rid: int) -> str:
        return f"req {rid}"

    # ----------------------------------------------------------- reliability
    def _ambient_at(self, clock: int) -> Optional[float]:
        """Piecewise-constant ambient-temperature schedule lookup (None =
        the engine's configured ambient)."""
        if not self.ambient_schedule:
            return None
        t = None
        for step, kelvin in self.ambient_schedule:
            if step <= clock:
                t = kelvin
        return t

    def _die_ambients_at(self, clock: int) -> Tuple[float, ...]:
        """Per-die ambient temperatures at ``clock``: the global
        schedule/config ambient, overridden per die by ``die_ambients``
        (dies heat independently — the per-device variation sharding
        exists to model)."""
        base = self._ambient_at(clock)
        if base is None:
            base = self.eng.scfg.ambient_k
        return tuple(self.die_ambients.get(d, float(base))
                     for d in range(self.mesh.n_dies))

    def _retention_vectors(self, clock: int) -> Tuple:
        """Decay-threshold burst operands for the current clock: the
        legacy pool-wide vectors while every die sits at one temperature
        (bit-identical executables across shard counts), per-slot rows
        once the die ambients diverge."""
        amb = self._die_ambients_at(clock)
        if not uniform(amb):
            return self.eng.retention_vectors_for_dies(
                self._floor(), amb, self.mesh.slots_per_die)
        return self.eng.retention_vectors_for(
            self._floor(), ambient_k=self._ambient_at(clock))

    def _die_bias(self, clock: int) -> Optional[np.ndarray]:
        """(capacity,) admission score bias steering HIGH-quality
        requests toward healthy/cool dies (higher = worse home, the
        ``SlotPool.alloc`` convention). Active only once the dies are
        *observably* unequal — divergent ambients — so uniform runs keep
        the legacy lowest-id admission order and the shard-count
        bit-parity contract. The bias combines each die's kelvin above
        the coolest die with its wear-checkpoint row-group wear above the
        healthiest die's (per-die reductions of the PR 5 ``slot_scores``
        machinery's counters)."""
        if self.mesh.n_dies <= 1:
            return None
        amb = self._die_ambients_at(clock)
        if uniform(amb):
            return None
        # repro: allow(no-host-sync-in-scan): host kelvin tuple, no device operand
        per_die = np.asarray(amb, np.float64) - min(amb)
        if self._die_wear_host is not None:
            per_die = per_die + (self._die_wear_host
                                 - self._die_wear_host.min())
        return self.mesh.per_slot(per_die)

    def _maybe_scrub(self, clock: int, key) -> None:
        """Idle-slot background scrubbing: consult the (host-side) policy;
        when a pass is due, re-write the accumulated decay through the
        engine's backend. One compiled call per pass signature; the pass's
        WriteStats accumulate on device into the scrub stream."""
        eng, policy = self.eng, self.scrub_policy
        if policy is None or eng.life_plan is None:
            return
        enabled = policy.plan_pass(clock, eng.plan.leaf_levels,
                                   idle=self.pool.free_slots() > 0)
        if enabled is None:
            return
        with phase("sched_scrub", clock=clock) as ph:
            span_args = self._scrub_pass(clock, key, enabled)
        if self.tele is not None:
            # scrub interference is visible on the background lane over
            # the same clock; the co-resident requests it contends with
            # are named in the span args. The pass energy is a lazy
            # device ref resolved at finalize — no sync here.
            self.tele.instruments.inc("serve_scrub_passes_total")
            self.tele.tracer.complete(
                "scrub_pass", clock, clock, lane=LANE_BACKGROUND,
                track="scrub", cat="reliability", t0_ns=ph.t0_ns,
                t1_ns=ph.t1_ns, **span_args)

    def _scrub_pass(self, clock: int, key, enabled) -> Optional[Dict]:
        """One due scrub pass (plus the hotter dies' extra passes);
        returns the first pass's span args when telemetry is on."""
        eng, policy = self.eng, self.scrub_policy
        # the scrub controller re-resolves the quality of the blocks it is
        # about to re-write through the SAME LRU table as admissions — its
        # traffic lands in the "scrub" scope so it never inflates the serve
        # hit rate (ExtentTable.scope).
        floor = Priority.LOW
        with eng.controller.table.scope("scrub"):
            for i in self.pool.occupied():
                r = self.pool.slot_req[i]
                if r.app_id is not None or r.quality is not None:
                    block = (r.app_id if r.app_id is not None
                             else ("rid", r.rid))
                    floor = max(floor, eng.controller.resolve_request(block))
        vectors = eng.vectors_for_floor(Priority(floor))
        cols = policy.cols_per_pass or None
        cursor = jnp.asarray(self._scrub_cursor, jnp.int32)
        k = jax.random.fold_in(
            key,
            rng_streams.SCHEDULER_SCRUB_PASS_OFFSET + self._scrub_passes)
        if eng.wear:
            # address-layer scrub: the cursor walks physical rows through
            # the current remap shifts; worn rows keep their decay
            self.pool.cache, self.life, st = eng._scrub_fused(
                k, self.pool.cache, self.life, vectors, cursor,
                self.addr.shifts, enabled=enabled, cols=cols)
        else:
            self.pool.cache, self.life, st = eng._scrub_fused(
                k, self.pool.cache, self.life, vectors, cursor,
                enabled=enabled, cols=cols)
        self._acc_scrub = self._acc_scrub + st
        span_args = None
        if self.tele is not None:
            from repro.reliability.scrub import scrub_span_args
            span_args = scrub_span_args(
                st, policy, cols=cols or 0, floor=Priority(floor),
                resident=[self.pool.slot_req[i].rid
                          for i in self.pool.occupied()])
        policy.record(clock)
        self._scrub_passes += 1
        for d in range(self.mesh.n_dies):
            self._die_scrub_passes[d] += 1
        # per-DIE scrub cadence: a die hotter than the coolest accumulates
        # decay faster, so it earns one extra pass over ITS slots only (a
        # die-masked pass — out-of-die slots are withheld at zero energy).
        # With uniform ambients (every parity configuration) this never
        # fires and the schedule is exactly the legacy global one.
        amb = self._die_ambients_at(clock)
        if not uniform(amb):
            coolest = min(amb)
            for d in [d for d, t in enumerate(amb) if t > coolest]:
                kd = jax.random.fold_in(
                    key, rng_streams.SCHEDULER_SCRUB_PASS_OFFSET
                    + self._scrub_passes)
                mask = self.mesh.slot_mask(d)
                if eng.wear:
                    self.pool.cache, self.life, st = eng._scrub_fused(
                        kd, self.pool.cache, self.life, vectors, cursor,
                        self.addr.shifts, mask, enabled=enabled, cols=cols)
                else:
                    self.pool.cache, self.life, st = eng._scrub_fused(
                        kd, self.pool.cache, self.life, vectors, cursor,
                        mask, enabled=enabled, cols=cols)
                self._acc_scrub = self._acc_scrub + st
                self._scrub_passes += 1
                self._die_scrub_passes[d] += 1
        if cols:
            self._scrub_cursor = (self._scrub_cursor + cols) % \
                eng.scfg.max_seq
        return span_args

    # ------------------------------------------------------- wear leveling
    def _remap_stats(self) -> WriteStats:
        """One rotation's migration write as a WriteStats increment (host
        constants resolved once per run — see ServingEngine.remap_cost)."""
        if self._remap_cost is None:
            self._remap_cost = self.eng.remap_cost(self.pool.cache)
        pj, bits = self._remap_cost
        return WriteStats.for_bits(bits,
                                   energy_pj=jnp.asarray(pj, jnp.float32))

    def _maybe_wear_check(self, clock: int) -> None:
        """Periodic wear checkpoint: sync the (L, G) row-group counters and
        the per-slot placement scores (the ONE device read this subsystem
        costs, amortized over ``check_interval`` steps), then ask the wear
        policy whether the permutation should rotate. A rotation advances
        the remap shifts — burst/scrub OPERANDS, so nothing retraces — and
        books the start-gap migration write into the ``remap`` stream."""
        eng, pol = self.eng, self.wear_policy
        if not eng.wear or self.life is None:
            return
        interval = pol.check_interval if pol is not None else 16
        if clock - self._last_wear_check < max(1, interval):
            return
        self._last_wear_check = clock
        with phase("sched_wear", clock=clock):
            self._wear_check(clock)

    def _wear_check(self, clock: int) -> None:
        """The checkpoint's read, then a rotation where the policy asks."""
        eng, pol = self.eng, self.wear_policy
        # repro: allow(no-host-sync-in-scan): the ONE wear-checkpoint sync,
        wear, scores = jax.device_get(  # amortized over check_interval
            (self.life.row_wear(),
             eng._slot_scores(self.life, self.pool.cache)))
        self._slot_scores_host = scores
        if self.mesh.n_dies > 1:
            # per-die health from the same checkpoint sync: each die's
            # hottest row-group wear (contiguous-slice reduction)
            self._die_wear_host = self.mesh.reduce_wear(wear)
        if self.tele is not None:
            self.tele.tracer.complete(
                "wear_check", clock, clock, lane=LANE_BACKGROUND,
                track="wear", cat="reliability",
                max_group_wear=int(wear.max()))
        if pol is not None and pol.plan_rotation(clock, wear):
            self.addr = self.addr.rotate(self._rotatable, pol.rotate_step)
            self._acc_remap = self._acc_remap + self._remap_stats()
            # the migration's row re-writes consume endurance too: book
            # the gap window (the freshly re-driven physical rows)
            self.life = eng.life_plan.record_migration(
                self.life, self.pool.cache, self._gap_host,
                pol.rotate_step)
            self._gap_host += pol.rotate_step
            pol.record(clock, wear)
            if self.tele is not None:
                self.tele.instruments.inc("serve_wear_rotations_total")
                self.tele.tracer.complete(
                    "remap_rotation", clock, clock,
                    lane=LANE_BACKGROUND, track="wear",
                    cat="reliability", rotate_step=pol.rotate_step,
                    migration_energy_pj=float(self._remap_cost[0]))

    def wear_state(self) -> Dict[str, Any]:
        """Portable wear snapshot — the physical address map and the
        row-group endurance counters, as a plain pytree of arrays a
        ``train.checkpoint.Checkpointer`` can persist. Feed it back via
        ``run(..., wear_state=...)`` so endurance wear survives a serving-
        process restart (physical damage outlives any one arrival
        stream)."""
        assert self.eng.wear and self.life is not None
        return {"shifts": self.addr.shifts,
                "rotations": self.addr.rotations,
                "row_write_count": self.life.row_write_count,
                "row_scrub_count": self.life.row_scrub_count}

    # ---------------------------------------------------------- prefix cache
    def _resolve_prefix(self, group: Sequence[Request]
                        ) -> Tuple[List[Optional[PrefixMatch]], List[Any]]:
        """Match every group member's prompt prefix against the CAM.

        Returns (matches, signature chains), both aligned with ``group``.
        A match names a slot whose resident leading columns are
        bit-identical to what this request's prefill would store there
        (same prefix inputs + causal attention ⇒ identical prefix KV), so
        admission may link instead of write."""
        matches: List[Optional[PrefixMatch]] = []
        sigs: List[Any] = []
        for r in group:
            # ONE admission-time prompt read per request — a
            # host-predictable scheduler event whose cost amortizes over
            # the request's whole decode; the digests feed every prefix
            # decision for this request.
            # repro: allow(no-host-sync-in-scan): once-per-admission read
            host_prompt = jax.device_get(r.prompt)
            s = self.prefix.signatures(host_prompt)
            sigs.append(s)
            matches.append(self.prefix.lookup(
                s, valid=lambda slot, gen:
                    self.pool.generation[slot] == gen,
                max_cols=self.eng.prompt_len(r.prompt)))
        return matches, sigs

    def _alias_price(self, cols: int) -> Tuple[float, int]:
        """Memoized (energy_pj, bits) of ``cols`` linked columns — the ONE
        pricing source (WritePlan.alias_saving) for both the link credit
        and the copy-on-write charge, so they cancel exactly."""
        p = self._alias_cost_cache.get(cols)
        if p is None:
            p = self._alias_cost_cache[cols] = self.eng.plan.alias_saving(
                self.pool.cache, cols)
        return p

    def _cow_owner(self, owner: int, clock: int = 0) -> None:
        """Copy-on-write detach of every linker of ``owner``: the moment
        the linkers' own rows are actually driven. Books one full column
        write per detached linker — energy via the same pricing the link
        was credited at (net zero for the detached share) plus the
        admission endurance wear of the now-owned columns."""
        for linker, cols in self.pool.cow_detach(owner):
            pj, bits = self._alias_price(cols)
            self._acc_cow = self._acc_cow + WriteStats.for_bits(
                bits, energy_pj=jnp.asarray(pj, jnp.float32))
            self._cow_events += 1
            if self.tele is not None:
                self.tele.instruments.inc("serve_cow_events_total")
                self.tele.tracer.complete(
                    "cow_detach", clock, clock, lane=LANE_BACKGROUND,
                    track="prefix", cat="prefix", owner=owner,
                    linker=linker, cols=cols, energy_pj=pj)
            if self.eng.wear and self.life is not None:
                self.life = self.eng._life_admit(
                    self.life, self.pool.cache,
                    jnp.asarray([linker], jnp.int32),
                    jnp.asarray([0], jnp.int32),
                    jnp.asarray([cols], jnp.int32), self.addr.shifts)

    def _make_room(self, n: int, matches: List[Optional[PrefixMatch]],
                   exclude: set, clock: int = 0) -> None:
        """Guarantee ``n`` allocatable slots before ``alloc``: first CoW
        link-blocked free slots (cheapest first = lowest id), then drop
        matches whose owner exclusion is starving capacity. Terminates:
        after every blocked slot is detached and every match dropped,
        allocatable == free_slots ≥ n (the admission bound)."""
        while self.pool.allocatable(exclude) < n:
            blocked = [i for i in self.pool.blocked_free()
                       if i not in exclude]
            if blocked:
                self._cow_owner(blocked[0], clock)
                continue
            dropped = False
            for j, m in enumerate(matches):
                if m is None:
                    continue
                matches[j] = None
                if (m.slot in exclude and not any(
                        mm is not None and mm.slot == m.slot
                        for mm in matches)):
                    exclude.discard(m.slot)
                    if (self.pool.col_refs[m.slot] > 0
                            and self.pool.slot_req[m.slot] is None):
                        # free but still blocked
                        self._cow_owner(m.slot, clock)
                dropped = True
                break
            assert dropped, (n, sorted(exclude))

    # --------------------------------------------------------- event phases
    def _admit(self, pending, clock: int, key) -> Tuple[Any, int]:
        """Admit every arrived request that fits, grouped by prompt shape
        (one fused prefill per group). Returns (key, immediate completions
        handled)."""
        admissible: List[Request] = []
        while len(admissible) < self.pool.free_slots():
            nxt = pending.next_arrival()
            if nxt is None or nxt > clock:
                break
            admissible.append(pending.popleft())
        if not admissible:
            return key, 0
        groups: Dict[Tuple, List[Request]] = collections.OrderedDict()
        for r in admissible:
            groups.setdefault(_prompt_signature(r.prompt), []).append(r)
        n_done = 0
        for group in groups.values():
            with phase("sched_admit", requests=len(group)) as ph:
                key, ids, matches, acc = self._admit_group(group, clock,
                                                           key)
            if self.tele is not None:
                with phase("sched_telemetry"):
                    self._book_admission(group, clock, ids, matches, acc,
                                         ph)
            n_done += self._complete(clock)
        return key, n_done

    def _admit_group(self, group: List[Request], clock: int, key):
        """Prefill one prompt-shape group into free slots and install it
        in the pool. Returns (key, slot ids, prefix matches, the write's
        WriteStats)."""
        for r in group:
            self._level[r.rid] = self._resolve_quality(r)
        # prefix resolution: match each member's prompt chain against
        # the CAM, exclude match owners from allocation (linking to a
        # slot about to be overwritten would be self-defeating), and
        # CoW/drop until the group fits the allocatable slots.
        matches: List[Optional[PrefixMatch]] = [None] * len(group)
        sigs: List[Any] = []
        exclude: set = set()
        if self.prefix is not None:
            matches, sigs = self._resolve_prefix(group)
            exclude = {m.slot for m in matches if m is not None}
            self._make_room(len(group), matches, exclude, clock)
        # wear-aware admission: HIGH-quality requests steer away from
        # slots backed by high-wear / high-residual-decay rows (scores
        # from the last wear checkpoint — no extra sync here). LOW/MID
        # admissions keep the lowest-id order the bit-parity contract
        # rests on.
        scores = None
        high = max(self._level[r.rid] for r in group) >= Priority.HIGH
        if (self.eng.wear and self._slot_scores_host is not None
                and high):
            scores = self._slot_scores_host
        if high:
            # cross-shard steering: once the dies are observably
            # unequal, HIGH requests prefer the healthy/cool dies
            # (per-die bias on top of the per-slot wear scores; ties
            # keep the lowest-id order)
            bias = self._die_bias(clock)
            if bias is None:
                pass
            elif scores is None:
                scores = bias
            else:
                # repro: allow(no-host-sync-in-scan): scores crossed at the wear checkpoint
                scores = np.asarray(scores) + bias
        ids = self.pool.alloc(len(group), scores=scores,
                              exclude=sorted(exclude))
        vectors = self.eng.vectors_for_floor(
            max(self._floor(),
                max(self._level[r.rid] for r in group)))
        batch = _stack_prompts(group)
        old_rows = self.pool.extract_rows(ids)
        pos0 = [self.eng.prompt_len(r.prompt) for r in group]
        any_link = any(m is not None for m in matches)
        if any_link:
            # linked admission: splice the owners' resident prefix
            # columns into the evicted rows, then write with those
            # columns aliased — CMP sees zero changed bits there, so
            # the linked prefix costs zero energy and zero WER
            # exposure. RNG split schedule identical to _admit_fused.
            owner_ids = [m.slot if m is not None else ids[j]
                         for j, m in enumerate(matches)]
            alias_list = [m.cols if m is not None else 0
                          for m in matches]
            alias = jnp.asarray(alias_list, jnp.int32)
            owner_rows = self.pool.extract_rows(owner_ids)
            old_rows = self.eng._splice_rows(old_rows, owner_rows,
                                             alias)
            tok, rows, key, acc = self.eng._admit_linked_fused(
                self.eng.params, batch, old_rows, key, vectors, alias)
        else:
            alias_list = [0] * len(group)
            tok, rows, key, acc = self.eng._admit_fused(
                self.eng.params, batch, old_rows, key, vectors)
        self._acc_prefill = self.pool.admit(
            ids, group, rows, tok, pos0, acc, self._acc_prefill)
        if self.life is not None:
            # the admitted rows were just prefill-written: their decay
            # record restarts from zero (jitted, stays on device) —
            # linked columns instead inherit the owner's decay record
            # (their bits ARE the owner's stored bits, decay included)
            idx = jnp.asarray(ids, jnp.int32)
            if any_link:
                self.life = self.eng._life_reset_linked(
                    self.life, idx,
                    jnp.asarray(owner_ids, jnp.int32),
                    jnp.asarray(alias_list, jnp.int32))
            else:
                self.life = self.eng._life_reset(self.life, idx)
            if self.prefix is not None and self.eng.wear:
                # endurance booking of the prompt-window row drives,
                # minus the linked columns — shared physical columns
                # wear ONCE, at their owner's admission
                self.life = self.eng._life_admit(
                    self.life, self.pool.cache, idx,
                    jnp.asarray(alias_list, jnp.int32),
                    jnp.asarray(pos0, jnp.int32), self.addr.shifts)
        if self.prefix is not None:
            for j, r in enumerate(group):
                m = matches[j]
                if m is not None:
                    self.pool.link(ids[j], m.slot, m.cols)
                    pj, bits = self._alias_price(m.cols)
                    self._saved_pj += pj
                    self._saved_bits += bits
                    self._linked_admissions += 1
                    self._linked_cols += m.cols
                self.prefix.insert(
                    sigs[j], ids[j], self.pool.generation[ids[j]],
                    col_offset=pos0[j]
                    - r.prompt["tokens"].shape[1])
        for j, r in enumerate(group):
            self._tokens[r.rid] = [(tok, j, 1)]
            self._remaining[r.rid] = r.new_tokens - 1
            self._admitted[r.rid] = clock
        return key, ids, matches, acc

    def _book_admission(self, group: List[Request], clock: int,
                        ids: List[int], matches, acc, ph) -> None:
        """Per-request span tree: root (arrival->completion) with queue +
        prefill children. Prefill energy attribution is the group
        accumulator's even split, kept as a lazy device ref until
        finalize; the prefill span carries the admission phase's
        ``perf_counter_ns`` bounds."""
        self.tele.instruments.inc("serve_admissions_total", len(group))
        share = Lazy(lambda e, k=len(group): e / k, acc.energy_pj)
        for j, r in enumerate(group):
            track = self._req_track(r.rid)
            root = self.tele.tracer.begin(
                f"req {r.rid}", r.arrival, track=track,
                cat="request", rid=r.rid, app_id=str(r.app_id),
                quality=self._level[r.rid].name)
            self._req_span[r.rid] = root
            self.tele.tracer.complete(
                "queue", r.arrival, clock, track=track,
                cat="request", parent=root)
            m = matches[j]
            pargs = dict(group=len(group), slot=ids[j], energy_pj=share,
                         t0_ns=ph.t0_ns, t1_ns=ph.t1_ns)
            if m is not None:
                self.tele.instruments.inc("serve_prefix_linked_total")
                pargs.update(m.span_args())
            self.tele.tracer.complete(
                "prefill", clock, clock, track=track,
                cat="prefill", parent=root, **pargs)

    def _materialize_tokens(self, rid: int, memo: Dict[int, np.ndarray]
                            ) -> List[int]:
        """Resolve a request's lazy token fragments to host ints (the one
        place token data crosses to the host). ``memo`` de-duplicates the
        device->host transfer of burst arrays shared between requests."""
        out: List[int] = []
        for arr, col, take in self._tokens[rid]:
            a = memo.get(id(arr))
            if a is None:
                with phase("sched_readback"):
                    # repro: allow(no-host-sync-in-scan): the one place token
                    a = memo[id(arr)] = np.asarray(arr)  # data reaches host
            if a.ndim == 1:  # admission group token vector
                out.append(int(a[col]))
            else:            # burst output (n, capacity)
                out.extend(int(t) for t in a[:take, col])
        return out

    def _complete(self, clock: int) -> int:
        """Retire every active slot whose token budget is spent; their
        attributed energy/flip/error rows come off-device here (one small
        transfer per event, never per token)."""
        done = [i for i in self.pool.occupied()
                if self._remaining[self.pool.slot_req[i].rid] == 0]
        if not done:
            return 0
        with phase("sched_complete", requests=len(done)):
            self._retire(done, clock)
        return len(done)

    def _retire(self, done: List[int], clock: int) -> None:
        """Report the finished requests of slots ``done`` and free them."""
        with phase("sched_readback"):
            # repro: allow(no-host-sync-in-scan): one per-EVENT transfer
            slot_host = jax.device_get(self.pool.slot_acc)
        memo: Dict[int, np.ndarray] = {}
        for i in done:
            r = self.pool.slot_req[i]
            flips = float(slot_host["flips"][i])
            errors = float(slot_host["errors"][i])
            toks = self._materialize_tokens(r.rid, memo)
            self._reports[r.rid] = {
                "rid": r.rid, "slot": i, "app_id": r.app_id,
                "quality": self._level[r.rid].name,
                "tokens": toks,
                "n_tokens": len(toks),
                "arrival_step": r.arrival,
                "admitted_step": self._admitted[r.rid],
                "completed_step": clock,
                "queue_steps": self._admitted[r.rid] - r.arrival,
                "latency_steps": clock - r.arrival,
                "energy_pj": float(slot_host["energy_pj"][i]),
                "flips": flips, "errors": errors,
                "ber": errors / max(flips, 1.0),
            }
            if self.tele is not None:
                rep = self._reports[r.rid]
                ins = self.tele.instruments
                ins.inc("serve_completions_total")
                ins.observe("serve_request_latency_steps",
                            rep["latency_steps"])
                ins.observe("serve_request_queue_steps",
                            rep["queue_steps"])
                root = self._req_span.pop(r.rid, None)
                if root is not None:
                    # slot release IS the eviction: the root span closes
                    # with the request's attributed energy/flips/WER
                    # (host floats — this event's sync already paid)
                    self.tele.tracer.end(
                        root, clock, slot=i, n_tokens=len(toks),
                        energy_pj=rep["energy_pj"], flips=flips,
                        errors=errors, ber=rep["ber"])
            # drop the lazy fragments: retaining them would pin every
            # burst's device token array for the scheduler's lifetime
            del self._tokens[r.rid]
            del self._remaining[r.rid], self._admitted[r.rid]
        self.pool.release(done)

    # ----------------------------------------------------------------- run
    def run(self, requests: Sequence[Request],
            wear_state: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Serve an arrival stream to completion; returns the serve report:
        per-request entries, pool/table statistics, and the aggregate
        energy ledger (streams bit-comparable with ``generate()`` when the
        stream degenerates to one full-pool lockstep batch).

        ``requests`` is either a materialized request list (wrapped in an
        ``ArrivalQueue``) or any object speaking the arrival-source
        protocol — e.g. ``repro.workload.replay.TraceSource``, which
        materializes each prompt only at admission.

        ``wear_state`` (a prior run's ``wear_state()`` snapshot, possibly
        round-tripped through a checkpoint) restores the physical address
        map and the row-group endurance counters — wear is device damage,
        so it persists across serving processes."""
        eng, pool = self.eng, self.pool
        pending = as_arrival_source(requests)
        key = jax.random.PRNGKey(eng.scfg.seed + 1)
        clock = 0
        decode_steps = 0
        bursts = 0
        self._acc_prefill = WriteStats.zero()
        self._acc_decode = WriteStats.zero()
        self._acc_scrub = WriteStats.zero()
        self._acc_remap = WriteStats.zero()
        self._acc_cow = WriteStats.zero()
        self._saved_pj = 0.0
        self._saved_bits = 0
        self._linked_admissions = 0
        self._linked_cols = 0
        self._cow_events = 0
        self._req_span: Dict[int, int] = {}
        if self.tele is not None:
            self._bind_telemetry()
        self._alias_cost_cache: Dict[int, Tuple[float, int]] = {}
        if self.prefix is not None:
            self.prefix.reset_stats()  # same contract as the extent table
        self._scrub_passes = 0
        self._scrub_cursor = 0
        self._last_wear_check = 0
        self._slot_scores_host = None
        self._die_scrub_passes = [0] * self.mesh.n_dies
        self._die_wear_host = None
        self._remap_cost = None
        self._gap_host = 0  # host mirror of the gap (pre-rotation shift)
        if self.scrub_policy is not None:
            self.scrub_policy.reset()  # the serving clock restarts at 0
        if self.wear_policy is not None:
            self.wear_policy.reset()
        self.life = (eng.life_plan.init_state(pool.cache)
                     if eng.life_plan is not None else None)
        self.addr = eng.plan.identity_address() if eng.wear else None
        self._rotatable = (jnp.asarray(eng.plan.rotatable())
                           if eng.wear else None)
        if wear_state is not None:
            assert eng.wear and self.life is not None
            from repro.memory import AddressState
            self.addr = AddressState(
                shifts=jnp.asarray(wear_state["shifts"], jnp.int32),
                rotations=jnp.asarray(wear_state["rotations"], jnp.int32))
            self.life = dataclasses.replace(
                self.life,
                row_write_count=jnp.asarray(
                    wear_state["row_write_count"], jnp.int32),
                row_scrub_count=jnp.asarray(
                    wear_state["row_scrub_count"], jnp.int32))
            # repro: allow(no-host-sync-in-scan): one-off restore-time read
            self._gap_host = int(np.max(np.asarray(wear_state["shifts"])))
            if self.wear_policy is not None:
                # restored historical wear is not wear GAINED this run:
                # without the rebase the first check would fire a
                # spurious (unearned) rotation on every resume
                # repro: allow(no-host-sync-in-scan): one-off restore sync
                wear0 = jax.device_get(self.life.row_wear())
                self.wear_policy.rebase(wear0)
        # engines outlive schedulers: zero the table's traffic counters so
        # THIS run's report never aggregates a previous arrival stream's
        # hits/misses/evictions (cached block->quality entries survive —
        # cross-stream quality inheritance is the table's whole point)
        eng.controller.table.reset_stats()

        while pending or pool.busy():
            nxt = pending.next_arrival()
            if (not pool.busy()) and nxt is not None and nxt > clock:
                clock = nxt  # idle: fast-forward to arrival
            # admit until nothing else fits (immediate completions can free
            # slots for requests already waiting in the queue)
            while True:
                key, n_done = self._admit(pending, clock, key)
                nxt = pending.next_arrival()
                if not (n_done and nxt is not None and nxt <= clock
                        and pool.free_slots()):
                    break
            if not pool.busy():
                if self.tele is not None:
                    with phase("sched_telemetry"):
                        self.tele.event(
                            clock, **self._event_gauges(clock, pending))
                continue
            # burst until the next scheduler event: earliest completion,
            # next arrival, or the optional compile-bounding cap
            active_ids = pool.occupied()
            n = min(self._remaining[pool.slot_req[i].rid]
                    for i in active_ids)
            nxt = pending.next_arrival()
            if nxt is not None and nxt > clock:
                n = min(n, nxt - clock)
            if self.max_burst:
                n = min(n, self.max_burst)
            if self.ambient_schedule and self.life is not None:
                # a temperature breakpoint is a scheduler event too: the
                # decay thresholds are per-burst operands, so the burst
                # must end where the ambient changes or the remainder of
                # the burst would decay at the stale temperature
                for step, _ in self.ambient_schedule:
                    if step > clock:
                        n = min(n, step - clock)
                        break
            n = max(int(n), 1)
            # device ref to the pre-burst decode energy: the burst span's
            # energy delta is computed lazily against it (no sync)
            e_before = (self._acc_decode.energy_pj
                        if self.tele is not None else None)
            with phase("sched_dispatch", steps=n,
                       active=len(active_ids)) as dispatch:
                active = pool.active_mask()
                vectors = eng.vectors_for_floor(self._floor())
                if eng.wear:
                    rvec = self._retention_vectors(clock)
                    (pool.tok, pool.cache, pool.pos, key, self._acc_decode,
                     pool.slot_acc, self.life, toks) = eng._burst(
                        eng.params, pool.tok, pool.cache, pool.pos, key,
                        self._acc_decode, pool.slot_acc, active, vectors,
                        self.life, rvec, self.addr.shifts, n=n)
                elif self.life is not None:
                    rvec = self._retention_vectors(clock)
                    (pool.tok, pool.cache, pool.pos, key, self._acc_decode,
                     pool.slot_acc, self.life, toks) = eng._burst(
                        eng.params, pool.tok, pool.cache, pool.pos, key,
                        self._acc_decode, pool.slot_acc, active, vectors,
                        self.life, rvec, n=n)
                else:
                    (pool.tok, pool.cache, pool.pos, key, self._acc_decode,
                     pool.slot_acc, toks) = eng._burst(
                        eng.params, pool.tok, pool.cache, pool.pos, key,
                        self._acc_decode, pool.slot_acc, active, vectors,
                        n=n)
            for i in active_ids:  # lazy (n, capacity) fragment — no sync
                rid = pool.slot_req[i].rid
                take = min(n, self._remaining[rid])
                self._tokens[rid].append((toks, i, take))
                self._remaining[rid] -= take
            clock += n
            decode_steps += n
            bursts += 1
            if self.tele is not None:
                with phase("sched_telemetry"):
                    ins = self.tele.instruments
                    ins.inc("serve_bursts_total")
                    ins.inc("serve_decode_steps_total", n)
                    ins.observe("serve_burst_steps", n)
                    # Lazy derivations: the delta/split arithmetic runs on
                    # host floats at finalize — the burst path records two
                    # array refs and dispatches nothing
                    e_after = self._acc_decode.energy_pj
                    burst_e = Lazy(lambda a, b: a - b, e_after, e_before)
                    share = Lazy(lambda a, b, k=len(active_ids): (a - b) / k,
                                 e_after, e_before)
                    self.tele.tracer.complete(
                        "burst", clock - n, clock, track="pool",
                        cat="decode", steps=n, active=len(active_ids),
                        energy_pj=burst_e, t0_ns=dispatch.t0_ns,
                        t1_ns=dispatch.t1_ns)
                    for i in active_ids:
                        rid = pool.slot_req[i].rid
                        self.tele.tracer.complete(
                            "decode", clock - n, clock,
                            track=self._req_track(rid), cat="decode",
                            parent=self._req_span.get(rid),
                            steps=n, energy_pj=share)
            self._complete(clock)
            self._maybe_scrub(clock, key)
            self._maybe_wear_check(clock)
            if self.tele is not None:
                with phase("sched_telemetry"):
                    self.tele.event(
                        clock, **self._event_gauges(clock, pending))

        # ----- aggregate ledger: ONE final device->host sync covering the
        # stream accumulators AND the lifetime/wear counters (bits_total
        # rides inside the accumulated WriteStats now)
        fetch: Dict[str, Any] = {
            "streams": (self._acc_prefill, self._acc_decode,
                        self._acc_scrub, self._acc_remap)}
        if self.prefix is not None:
            fetch["cow"] = self._acc_cow
        if self.mesh.n_dies > 1:
            # per-die ledgers ride the SAME final sync: the per-slot
            # attribution and decay vectors cross once and reduce to
            # per-die rows on host (contiguous slices — zero device work)
            fetch["slot_acc"] = pool.slot_acc
            if self.life is not None:
                slot_decay = eng.life_plan.decayed_bits_by_slot(self.life)
                if slot_decay is not None:
                    fetch["slot_decay"] = slot_decay
        if self.life is not None:
            fetch["retention"] = (self.life.retention_flips,
                                  self.life.decayed_bits())
            if eng.wear:
                worn = eng.life_plan.worn_groups(self.life)
                fetch["wear"] = (self.life.row_wear(),
                                 None if worn is None else worn.sum())
        # repro: allow(no-host-sync-in-scan): THE once-per-run report sync
        host = jax.device_get(fetch)
        pre_host, dec_host, scrub_host, remap_host = host["streams"]
        self.meter.add_stream("kv_prefill", pre_host)
        self.meter.add_stream("kv_decode", dec_host)
        if self.life is not None:
            self.meter.add_stream("kv_scrub", scrub_host)
        if eng.wear:
            self.meter.add_stream("kv_remap", remap_host)
        if self.prefix is not None:
            self.meter.add_stream("kv_prefix_cow", host["cow"])
        summary = self.meter.summary()
        summary.update({
            "requests": self._reports,
            "clock_steps": clock,
            "decode_steps": decode_steps,
            "bursts": bursts,
            "pool": pool.stats(),
            "extent_table": eng.controller.table.stats(),
        })
        if self.prefix is not None:
            # the PREFIX ledger: what cross-request linking earned, net of
            # what the mechanism itself cost — CAM search energy plus the
            # copy-on-write writes that paid back detached links
            pstats = self.prefix.stats()
            cow_pj = float(host["cow"].energy_pj)
            summary["prefix"] = {
                "enabled": True,
                "chunk": eng.scfg.prefix_chunk,
                "table_size": eng.scfg.prefix_table_size,
                **pstats,
                "linked_admissions": self._linked_admissions,
                "linked_cols": self._linked_cols,
                "write_energy_saved_pj": self._saved_pj,
                "saved_bits": self._saved_bits,
                "cow_events": self._cow_events,
                "cow_energy_pj": cow_pj,
                "net_energy_saved_pj": (self._saved_pj - cow_pj
                                        - pstats["cam_energy_pj"]),
            }
        if self.life is not None:
            # the LIFETIME ledger: what this stream cost over its whole
            # life — write energy plus the scrub energy spent defending it
            # and the remap energy spent spreading its wear (plus the
            # damage that slipped through, as counters)
            flips, decayed = host["retention"]
            write_pj = (float(pre_host.energy_pj)
                        + float(dec_host.energy_pj))
            scrub_pj = float(scrub_host.energy_pj)
            remap_pj = float(remap_host.energy_pj)
            summary["lifetime"] = {
                "ambient_k": self.eng.scfg.ambient_k,
                "dwell_s_per_step": self.eng.scfg.retention_scale,
                "write_energy_pj": write_pj,
                "scrub_energy_pj": scrub_pj,
                "remap_energy_pj": remap_pj,
                "lifetime_energy_pj": write_pj + scrub_pj + remap_pj,
                "retention_flips": int(flips),
                "residual_decayed_bits": int(decayed),
                "scrub_passes": self._scrub_passes,
                "scrub_policy": (self.scrub_policy.name
                                 if self.scrub_policy else "none"),
            }
        if eng.wear:
            wear, worn_sum = host["wear"]
            summary["wear"] = {
                "policy": (self.wear_policy.name
                           if self.wear_policy else "none"),
                "rotations": (self.wear_policy.rotations
                              if self.wear_policy else 0),
                "remap_energy_pj": float(remap_host.energy_pj),
                "max_group_wear": int(wear.max()),
                "worn_groups": (int(worn_sum)
                                if worn_sum is not None else 0),
                "endurance_budget": eng.scfg.endurance_budget,
                "group_cols": eng.scfg.remap_group_cols,
            }
        if self.mesh.n_dies > 1:
            summary["sharding"] = self._sharding_summary(host, clock)
        if self.tele is not None:
            # the telemetry section rides the summary so every consumer
            # (launcher, workload harness, benchmarks) sees ONE snapshot
            # instead of re-assembling its own
            summary["telemetry"] = self.tele.snapshot()
        return summary

    def _sharding_summary(self, host: Dict[str, Any], clock: int
                          ) -> Dict[str, Any]:
        """Per-die breakdown of the merged serve ledger: every row is a
        contiguous-slice reduction of host arrays the final sync already
        fetched. The pool-wide streams above remain the merged view (and
        the cross-shard-count bit-identity anchor); this section is where
        the dies' independent aging becomes visible."""
        m = self.mesh
        amb = self._die_ambients_at(clock)
        sa = host["slot_acc"]
        energy = m.reduce_slots(sa["energy_pj"])
        flips = m.reduce_slots(sa["flips"])
        errors = m.reduce_slots(sa["errors"])
        decay = (m.reduce_slots(host["slot_decay"])
                 if "slot_decay" in host else None)
        wear_by_die = (m.reduce_wear(host["wear"][0])
                       if "wear" in host else None)
        dies = []
        for d in range(m.n_dies):
            sl = m.slot_slice(d)
            row: Dict[str, Any] = {
                "die": d, "slots": [sl.start, sl.stop],
                "ambient_k": amb[d],
                "energy_pj": float(energy[d]),
                "flips": float(flips[d]),
                "errors": float(errors[d]),
                "scrub_passes": self._die_scrub_passes[d],
            }
            if decay is not None:
                row["decayed_bits"] = int(decay[d])
            if wear_by_die is not None:
                row["max_group_wear"] = int(wear_by_die[d])
            dies.append(row)
        return {"shards": m.n_dies, "slots_per_die": m.slots_per_die,
                "mesh_devices": int(m.device_mesh().devices.size),
                "dies": dies}
