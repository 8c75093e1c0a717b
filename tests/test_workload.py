"""repro.workload: trace format, generators, pressure ramp, replay parity.

The load-bearing invariant (ISSUE 8): replaying a recorded arrival stream
through the scheduler's trace-iterator arrival source reproduces the
original serve report BIT-EXACTLY — same tokens, same energy, same error
counters — on every write-path backend. Everything else (schema
validation, generator determinism across processes, monotone pressure
ordering, the prefix×wear adversarial migration) is behavioral.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config
from repro.memory import available_backends, rng_streams
from repro.serve import (ContinuousScheduler, ServeConfig, ServingEngine,
                         synthetic_requests)
from repro.workload import (TraceSource, build_ramp, load_trace,
                            make_workload, pressure_score,
                            record_requests, save_trace)
from repro.workload.generators import PRESETS
from repro.workload.pressure import assert_monotone, order_ramp
from repro.workload.replay import requests_from_trace
from repro.workload.trace import (TRACE_VERSION, Trace, TraceEvent, dumps,
                                  loads, validate_trace)

FIXTURE = Path(__file__).parent / "fixtures" / "trace_smoke.jsonl"


def _cfg():
    return get_config("qwen2.5-3b").reduced()


# ---------------------------------------------------------------------------
# trace format: round-trip + schema validation
# ---------------------------------------------------------------------------

class TestTraceFormat:
    def test_round_trip_is_byte_identical(self, tmp_path):
        cfg = _cfg()
        for preset in PRESETS:
            t = make_workload(preset, cfg, 5, seed=3)
            text = dumps(t)
            assert dumps(loads(text)) == text, preset
            p = save_trace(t, tmp_path / f"{preset}.jsonl")
            assert load_trace(p) == t

    def test_event_fields_survive(self):
        cfg = _cfg()
        t = make_workload("chat_batch", cfg, 6, seed=1)
        t2 = loads(dumps(t))
        for a, b in zip(t.events, t2.events):
            assert a == b
        assert t2.vocab_size == cfg.vocab_size
        assert t2.meta["preset"] == "chat_batch"

    def test_validation_rejects_bad_traces(self):
        ev = TraceEvent(rid=0, arrival=0, tokens=(1, 2), new_tokens=2)
        ok = Trace(events=(ev,), vocab_size=8)
        validate_trace(ok)
        bad = [
            Trace(events=(), vocab_size=8),                      # empty
            Trace(events=(ev, ev), vocab_size=8),                # dup rid
            Trace(events=(ev,), vocab_size=8, version=99),       # version
            Trace(events=(TraceEvent(0, -1, (1,), 1),)),         # arrival
            Trace(events=(TraceEvent(0, 0, (), 1),)),            # no prompt
            Trace(events=(TraceEvent(0, 0, (9,), 1),),
                  vocab_size=8),                                 # vocab
            Trace(events=(TraceEvent(0, 0, (1,), 0),)),          # decode
            Trace(events=(TraceEvent(0, 0, (1,), 1,
                                     quality="best"),)),         # quality
            Trace(events=(TraceEvent(1, 4, (1,), 1),
                          TraceEvent(0, 2, (1,), 1))),           # unsorted
        ]
        for t in bad:
            with pytest.raises(ValueError):
                validate_trace(t)

    def test_loads_rejects_foreign_files(self):
        with pytest.raises(ValueError):
            loads('{"format": "something-else"}\n')


# ---------------------------------------------------------------------------
# generators: determinism (in-process and across processes)
# ---------------------------------------------------------------------------

class TestGenerators:
    def test_same_seed_same_trace(self):
        cfg = _cfg()
        for preset in PRESETS:
            a = make_workload(preset, cfg, 6, seed=11)
            b = make_workload(preset, cfg, 6, seed=11)
            assert dumps(a) == dumps(b), preset
            c = make_workload(preset, cfg, 6, seed=12)
            assert dumps(a) != dumps(c), preset

    def test_deterministic_across_process_restarts(self):
        """A (preset, seed) pair IS the trace: a fresh interpreter must
        produce byte-identical output (no wall clock, no global RNG)."""
        cfg = _cfg()
        here = dumps(make_workload("bursty", cfg, 5, seed=4))
        prog = (
            "from repro.configs import get_config\n"
            "from repro.workload import make_workload\n"
            "from repro.workload.trace import dumps\n"
            "cfg = get_config('qwen2.5-3b').reduced()\n"
            "import sys\n"
            "sys.stdout.write(dumps(make_workload("
            "'bursty', cfg, 5, seed=4)))\n")
        out = subprocess.run(
            [sys.executable, "-c", prog], capture_output=True, text=True,
            cwd=str(Path(__file__).parent.parent),
            # the parent's environment (JAX_PLATFORMS included), so the
            # child stays on the platform this run chose
            env={**os.environ, "PYTHONPATH": "src"},
            check=True)
        assert out.stdout == here

    def test_shared_prefix_preset_actually_shares(self):
        cfg = _cfg()
        t = make_workload("shared_system_prompt", cfg, 5, seed=2,
                          shared_len=12, tail_len=4)
        heads = {e.tokens[:12] for e in t.events}
        tails = {e.tokens[12:] for e in t.events}
        assert len(heads) == 1
        assert len(tails) == len(t.events)
        assert all(e.prefix_group == 0 for e in t.events)

    def test_unknown_preset_lists_registry(self):
        with pytest.raises(ValueError, match="steady"):
            make_workload("nope", _cfg(), 3)


# ---------------------------------------------------------------------------
# pressure: scoring + the monotone ramp
# ---------------------------------------------------------------------------

class TestPressure:
    def test_ramp_is_monotone_and_full(self):
        ramp = build_ramp(_cfg(), seed=0, n=6)
        assert len(ramp) >= 5
        assert_monotone([m["pressure"] for m in ramp])
        assert [m["mix"] for m in ramp] == list(range(1, len(ramp) + 1))

    def test_assert_monotone_rejects_plateaus_and_dips(self):
        with pytest.raises(AssertionError):
            assert_monotone([1.0, 2.0, 2.0])
        with pytest.raises(AssertionError):
            assert_monotone([1.0, 3.0, 2.0])

    def test_score_moves_with_its_inputs(self):
        cfg = _cfg()
        sparse = make_workload("steady", cfg, 4, seed=0, prompt_len=8,
                               new_tokens=8, arrival_every=8)
        flood = make_workload("steady", cfg, 4, seed=0, prompt_len=16,
                              new_tokens=2, arrival_every=1)
        assert pressure_score(flood) > pressure_score(sparse)

    def test_order_ramp_sorts_by_measurement(self):
        cfg = _cfg()
        mixes = {
            "hot": make_workload("steady", cfg, 4, seed=0, prompt_len=16,
                                 new_tokens=2, arrival_every=1),
            "cold": make_workload("steady", cfg, 4, seed=0, prompt_len=8,
                                  new_tokens=8, arrival_every=8),
        }
        ramp = order_ramp(mixes)
        assert [m["name"] for m in ramp] == ["cold", "hot"]


# ---------------------------------------------------------------------------
# replay: bit-exact parity with the synthetic list path, all backends
# ---------------------------------------------------------------------------

class TestReplayParity:
    @pytest.mark.parametrize("backend", available_backends())
    def test_recorded_synthetic_stream_replays_bit_exactly(self, backend):
        cfg = _cfg()

        def engine():
            return ServingEngine(cfg, ServeConfig(
                max_seq=14, max_new_tokens=5, backend=backend))

        def reqs():
            return synthetic_requests(cfg, 4, prompt_len=8, new_tokens=5,
                                      arrival_every=2, seed=3)

        rep_a = ContinuousScheduler(engine(), capacity=2).run(reqs())
        trace = loads(dumps(record_requests(reqs(), cfg)))
        rep_b = ContinuousScheduler(engine(), capacity=2).run(
            TraceSource(trace, cfg))

        for rid in rep_a["requests"]:
            assert (rep_a["requests"][rid]["tokens"]
                    == rep_b["requests"][rid]["tokens"]), rid
        for k, v in rep_a["total"].items():
            assert rep_b["total"][k] == v, k
        for s in rep_a["streams"]:
            for k in ("energy_pj", "bits_written", "bit_errors"):
                assert (rep_a["streams"][s][k]
                        == rep_b["streams"][s][k]), (s, k)

    def test_trace_source_drains_lazily(self):
        cfg = _cfg()
        t = make_workload("steady", cfg, 4, seed=0, prompt_len=8,
                          new_tokens=3, arrival_every=2)
        src = TraceSource(t, cfg)
        assert len(src) == 4
        assert src.next_arrival() == 0
        r = src.popleft()
        assert r.rid == 0 and len(src) == 3
        assert src.next_arrival() == 2
        for _ in range(3):
            src.popleft()
        assert not src and src.next_arrival() is None

    def test_quality_override_forces_floor(self):
        cfg = _cfg()
        t = make_workload("chat_batch", cfg, 4, seed=0)
        reqs = requests_from_trace(t, cfg, quality_override="high")
        from repro.core.priority import Priority
        assert all(r.quality == Priority.HIGH for r in reqs)

    def test_trace_source_feeds_scheduler(self):
        cfg = _cfg()
        t = make_workload("heavy_tail", cfg, 5, seed=1, min_len=4,
                          max_len=12, new_tokens=3, arrival_every=2)
        eng = ServingEngine(cfg, ServeConfig(
            max_seq=t.max_seq(), max_new_tokens=t.max_new_tokens()))
        rep = ContinuousScheduler(eng, capacity=2).run(
            TraceSource(t, cfg))
        assert sorted(rep["requests"]) == [e.rid for e in t.events]
        assert all(r["n_tokens"] >= 1 for r in rep["requests"].values())


# ---------------------------------------------------------------------------
# prefix×wear adversarial: rotation migrates the pinned hot prefix
# ---------------------------------------------------------------------------

class TestPrefixWearAdversarial:
    def test_rotation_migrates_pinned_prefix_before_stuck_at(self):
        """The shared-system-prompt flood pins one owner's physical
        columns (every prefix hit links the SAME rows; wear-once booking
        keeps charging them). Identity addressing exhausts the endurance
        budget on those rows; the rotate policy must migrate the hot
        prefix first."""
        from benchmarks.workload_mixes import adversarial
        out = adversarial(_cfg(), events=6, seed=0)
        assert out["none"]["linked_admissions"] >= 1
        assert out["rotate"]["linked_admissions"] >= 1
        assert out["none"]["worn_groups"] > 0
        assert out["rotate"]["worn_groups"] == 0
        assert out["rotate"]["rotations"] >= 1
        for name, ok in out["claims"].items():
            assert ok, name


# ---------------------------------------------------------------------------
# RNG registry: the WORKLOAD stream is pinned and range-collision-checked
# ---------------------------------------------------------------------------

class TestWorkloadRngStream:
    def test_workload_offset_pinned(self):
        assert rng_streams.WORKLOAD_OFFSET == 5_000_011
        names = [s.name for s in rng_streams.STREAMS]
        assert "workload-event" in names

    def test_validate_rejects_range_collisions(self):
        """Fold constants landing inside another stream's counter-hash
        index RANGE (not just exact offsets) must be rejected — the
        murmur sub-streams fold ``offset + flat_index``, so two streams
        whose [offset, offset+span) intervals overlap would collide on
        real traffic."""
        s = rng_streams.STREAMS
        base = s[0]
        clash = base._replace(name="intruder",
                              offset=base.offset + base.span // 2)
        with pytest.raises(AssertionError):
            rng_streams.validate(tuple(s) + (clash,))
        # disjoint ranges in the same domain stay legal
        far = base._replace(
            name="far",
            offset=max(x.offset + x.span for x in s
                       if x.domain == base.domain))
        rng_streams.validate(tuple(s) + (far,))


# ---------------------------------------------------------------------------
# committed fixture: the CI workload-smoke lane's trace stays loadable
# ---------------------------------------------------------------------------

class TestFixture:
    def test_smoke_fixture_is_valid_and_replayable(self):
        t = load_trace(FIXTURE)
        assert t.version == TRACE_VERSION
        assert len(t.events) >= 3
        assert pressure_score(t) > 0
        cfg = _cfg()
        assert t.vocab_size == cfg.vocab_size
        reqs = requests_from_trace(t, cfg)
        assert [r.rid for r in reqs] == [e.rid for e in t.events]
