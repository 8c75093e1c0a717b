"""repro.telemetry.scopes: the declared device scopes and host phases.

The declarations are declare-once, as metrics are. Every scope of the
decode burst (all three variants), the admission, the pool update and the
scrub pass reaches the op metadata of the compiled program, so a rename
fails here rather than silently dropping a benchmark metric. A profiler
trace of a served run holds every scheduler phase, ``sched_dispatch`` once
per burst, and the span tracer's ``perf_counter_ns`` bounds sit at one
offset from the phases on the profiler's clock.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.energy_model import zero_slot_stats
from repro.memory import WriteStats
from repro.serve import (ContinuousScheduler, ServeConfig, ServingEngine,
                         synthetic_requests)
from repro.serve import slots
from repro.telemetry import Telemetry
from repro.telemetry.scopes import (MARKS, PHASE, SCOPE, MarkRegistry,
                                    Phase, phase, scope)

STEP_SCOPES = {"decode_model", "mask_rows", "extent_write", "extent_gather",
               "extent_lanes", "extent_store", "extent_stats", "sample"}
RETENTION_SCOPES = {"retention_clear", "retention_decay"}
ADMIT_SCOPES = {"admit_model", "extent_write", "extent_lanes", "sample"}


# ---------------------------------------------------------------------------
# declarations
# ---------------------------------------------------------------------------

class TestDeclarations:
    def test_collision_rejected(self):
        reg = MarkRegistry()
        reg.scope("a_scope", "layer", "operator", "a scope")
        with pytest.raises(ValueError, match="already declared"):
            reg.scope("a_scope", "layer", "operator", "again")
        with pytest.raises(ValueError, match="already declared"):
            reg.phase("a_scope", "layer", "operator", "as a phase either")

    @pytest.mark.parametrize("bad", ["extent.write", "Extent", "a/b", "",
                                     "1st"])
    def test_names_are_one_snake_case_component(self, bad):
        with pytest.raises(ValueError, match="snake_case"):
            MarkRegistry().scope(bad, "layer", "operator", "doc")

    def test_every_mark_names_its_layer_reader_and_doc(self):
        with pytest.raises(ValueError, match="layer"):
            MarkRegistry().phase("p", "", "operator", "doc")
        for m in MARKS.marks().values():
            assert m.layer and m.reads and m.doc, m.name
        assert set(MARKS.names(SCOPE)) >= STEP_SCOPES | RETENTION_SCOPES
        assert {"sched_admit", "sched_dispatch", "sched_complete",
                "sched_readback", "sched_scrub", "sched_wear",
                "sched_telemetry"} == set(MARKS.names(PHASE))

    def test_undeclared_or_wrong_kind_rejected(self):
        with pytest.raises(KeyError, match="not a declared scope"):
            scope("no_such_scope")
        with pytest.raises(KeyError, match="not a declared phase"):
            phase("no_such_phase")
        with pytest.raises(KeyError, match="not a declared scope"):
            scope("sched_dispatch")
        with pytest.raises(KeyError, match="not a declared phase"):
            phase("decode_model")

    def test_phase_args_are_host_numbers(self):
        with pytest.raises(TypeError, match="host number"):
            phase("sched_dispatch", steps=jnp.int32(2))
        with phase("sched_dispatch", steps=2, active=3) as p:
            pass
        assert isinstance(p, Phase)
        assert isinstance(p, jax.profiler.TraceAnnotation)
        assert 0 < p.t0_ns <= p.t1_ns

    def test_scope_is_op_metadata(self):
        def f(x):
            with scope("decode_model"):
                return jnp.sin(x) * 2

        x = jnp.ones(4)
        hlo = jax.jit(f).lower(x).compile().as_text()
        assert "/decode_model/" in hlo


# ---------------------------------------------------------------------------
# the compiled programs carry every scope
# ---------------------------------------------------------------------------

def _engine(**kw):
    cfg = get_config("qwen2.5-3b").reduced()
    return cfg, ServingEngine(cfg, ServeConfig(max_seq=16, max_new_tokens=4,
                                               **kw))


def _scopes_in(hlo: str) -> set:
    """Declared scopes named as a component of some op_name path."""
    names = set(MARKS.names(SCOPE))
    found = set()
    for part in hlo.split('op_name="')[1:]:
        found |= names.intersection(part.split('"', 1)[0].split("/"))
    return found


def _burst_hlo(eng, capacity=2):
    api = eng.api
    cache = api.init_cache(capacity, eng.scfg.max_seq)
    args = [eng.params, jnp.zeros((capacity,), jnp.int32), cache,
            jnp.zeros((capacity,), jnp.int32), jax.random.PRNGKey(0),
            WriteStats.zero(), zero_slot_stats(capacity),
            jnp.ones((capacity,), bool), eng.vectors_for_floor()]
    if eng.life_plan is not None:
        args += [eng.life_plan.init_state(cache), eng.retention_vectors_for()]
    if eng.wear:
        args.append(eng.plan.identity_address().shifts)
    return eng._burst.lower(*args, n=2).compile().as_text()


@pytest.mark.parametrize("variant,kw,expect", [
    ("plain", {}, STEP_SCOPES),
    ("retention", {"retention_scale": 1000.0},
     STEP_SCOPES | RETENTION_SCOPES),
    ("wear", {"wear_policy": "rotate", "remap_group_cols": 4,
              "retention_scale": 1000.0}, STEP_SCOPES | RETENTION_SCOPES),
])
def test_burst_carries_every_step_scope(variant, kw, expect):
    cfg, eng = _engine(**kw)
    found = _scopes_in(_burst_hlo(eng))
    assert found == expect, (variant, expect ^ found)


def test_admission_and_pool_update_carry_their_scopes():
    cfg, eng = _engine()
    prompt = {"tokens": jnp.zeros((1, 6), jnp.int32)}
    rows = eng.api.init_cache(1, eng.scfg.max_seq)
    hlo = eng._admit_fused.lower(eng.params, prompt, rows,
                                 jax.random.PRNGKey(0),
                                 eng.vectors_for_floor()).compile().as_text()
    assert _scopes_in(hlo) == ADMIT_SCOPES
    cache = eng.api.init_cache(2, eng.scfg.max_seq)
    i1 = jnp.zeros((1,), jnp.int32)
    hlo = slots._admission_update.lower(
        cache, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        zero_slot_stats(2), WriteStats.zero(), rows, i1, i1, i1,
        WriteStats.zero()).compile().as_text()
    assert _scopes_in(hlo) == {"admit_update"}


def test_scrub_pass_carries_its_scope():
    cfg, eng = _engine(retention_scale=1000.0)
    cache = eng.api.init_cache(2, eng.scfg.max_seq)
    levels = tuple(lvl is not None for lvl in eng.plan.leaf_levels)
    hlo = eng._scrub_fused.lower(
        jax.random.PRNGKey(0), cache, eng.life_plan.init_state(cache),
        eng.vectors_for_floor(), jnp.int32(0), enabled=levels,
        cols=None).compile().as_text()
    assert "scrub" in _scopes_in(hlo)


# ---------------------------------------------------------------------------
# the scheduler's phases on the profiler's clock
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A reduced served run with scrub, wear and telemetry on, traced by
    the JAX profiler on the CPU."""
    from jax.profiler import ProfileData
    from repro.reliability import make_scrub_policy, make_wear_policy

    cfg, eng = _engine(retention_scale=1000.0, wear_policy="rotate",
                       remap_group_cols=4)

    def scheduler(tele):
        return ContinuousScheduler(
            eng, capacity=2, telemetry=tele,
            scrub_policy=make_scrub_policy("periodic", interval=2),
            wear_policy=make_wear_policy("rotate", check_interval=2,
                                         rotate_step=4, hot_row_wear=2))

    reqs = synthetic_requests(cfg, 4, prompt_len=6, new_tokens=4,
                              arrival_every=2, seed=5)
    scheduler(Telemetry()).run(reqs)  # compile outside the trace
    tele = Telemetry()
    sch = scheduler(tele)
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        rep = sch.run(reqs)
    finally:
        jax.profiler.stop_trace()
    pb = sorted(Path(out).rglob("*.xplane.pb"))[-1]
    host = next(p for p in ProfileData.from_file(str(pb)).planes
                if p.name == "/host:CPU")
    events = sorted((e for line in host.lines for e in line.events
                     if e.name in MARKS.names(PHASE)),
                    key=lambda e: e.start_ns)
    return rep, tele, events


def test_trace_holds_every_phase(traced_run):
    rep, tele, events = traced_run
    assert {e.name for e in events} == set(MARKS.names(PHASE))
    dispatch = [e for e in events if e.name == "sched_dispatch"]
    assert len(dispatch) == rep["bursts"] > 0
    assert [dict(e.stats)["steps"] for e in dispatch] == [
        s.args["steps"] for s in tele.tracer.spans if s.name == "burst"]


def test_span_clock_bounds_match_the_profiler(traced_run):
    rep, tele, events = traced_run
    dispatch = [e for e in events if e.name == "sched_dispatch"]
    bursts = [s for s in tele.tracer.spans if s.name == "burst"]
    assert len(bursts) == len(dispatch)
    starts = [e.start_ns - s.args["t0_ns"] for e, s in zip(dispatch, bursts)]
    ends = [e.end_ns - s.args["t1_ns"] for e, s in zip(dispatch, bursts)]
    assert max(starts) - min(starts) < 1e6
    assert max(ends) - min(ends) < 1e6
    assert abs(starts[0] - ends[0]) < 1e6
    for name in ("prefill", "scrub_pass"):
        spans = [s for s in tele.tracer.spans if s.name == name]
        assert spans, name
        assert all(0 < s.args["t0_ns"] <= s.args["t1_ns"] for s in spans)
