"""Device time per named scope and host phases against the device's idle
time (harness.scopes): on a small hand-made trace whose answers are
known, and on a trace recorded on a TPU v5e
(``fixtures/qwen_scopes.xplane.txt``: 300 ms of a
``qwen2.5-3b.decode_heavy`` window around an admission, each op's
``tf_op`` stat kept)."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from harness import registry, scopes
from harness import trace as tr

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "qwen_scopes.xplane.txt"

# ns on the trace's clock; events are (metadata id, start, duration). The
# burst's while (3) encloses its body's ops 4, 5 and 6; op 7 runs in the
# admission; op 8 carries no op_name path
HAND = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 500000000 }
    events { metadata_id: 2 offset_ps: 700000000 duration_ps: 200000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 100000000 duration_ps: 500000000 }
    events { metadata_id: 4 offset_ps: 150000000 duration_ps: 100000000 }
    events { metadata_id: 5 offset_ps: 250000000 duration_ps: 200000000 }
    events { metadata_id: 6 offset_ps: 450000000 duration_ps: 100000000 }
    events { metadata_id: 7 offset_ps: 700000000 duration_ps: 150000000 }
    events { metadata_id: 8 offset_ps: 850000000 duration_ps: 50000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_burst(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_prefill(12)" } }
  event_metadata { key: 3 value { id: 3 name: "%while.1 = (s32[])"
    stats { metadata_id: 9 str_value: "jit(burst)/while:" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.2 = bf16[8]"
    stats { metadata_id: 9 str_value: "jit(burst)/while/body/closed_call/decode_model/dot_general:" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.3 = bf16[8]"
    stats { metadata_id: 9 str_value: "jit(burst)/while/body/closed_call/extent_write/extent_gather/gather:" } } }
  event_metadata { key: 6 value { id: 6 name: "%select.4 = bf16[8]"
    stats { metadata_id: 9 str_value: "jit(burst)/while/body/closed_call/mask_rows/select_n:" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.5 = u32[8]"
    stats { metadata_id: 9 str_value: "jit(prefill)/extent_write/extent_lanes/jit(_extent_write_jit)/or:" } } }
  event_metadata { key: 8 value { id: 8 name: "%copy.6 = u32[8]" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 50000000 duration_ps: 900000000 }
    events { metadata_id: 2 offset_ps: 580000000 duration_ps: 100000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 60000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "sched_readback" } }
  event_metadata { key: 3 value { id: 3 name: "sched_dispatch" } }
}
"""


def load(text):
    """The reduced trace and its op paths, as a run would have them."""
    pd = ProfileData.from_text_proto(text)
    return (tr.reduce(pd.planes),
            scopes.op_paths(ProfileData.text_proto_to_serialized_xspace(text)))


def rundata(reduced):
    return registry.RunData(cfg={}, mix=None, report={}, setup_s=0.0,
                            window_s=1.0, work={}, peaks={},
                            memory_peak_bytes=None, trace=reduced)


def test_op_paths_read_the_metadata_stat():
    _, paths = load(HAND)
    dev = paths["/device:TPU:0"]
    assert dev["%fusion.3 = bf16[8]"] == (
        "jit(burst)/while/body/closed_call/extent_write/extent_gather/gather")
    assert "%copy.6 = u32[8]" not in dev
    assert scopes.components(dev["%select.4 = bf16[8]"])[-2] == "mask_rows"


def test_self_time_takes_enclosed_ops_out():
    got = {n: ns for _, _, n, ns in scopes.self_times(
        [(100, 600, "while"), (150, 250, "a"), (250, 450, "b"),
         (450, 550, "c"), (700, 850, "d")])}
    assert got == {"while": 100, "a": 100, "b": 200, "c": 100, "d": 150}


def test_hand_made_trace():
    r, paths = load(HAND)
    # window [50, 950] us
    sec = lambda prog, sc: scopes.scope_seconds(r, paths, prog, sc)
    assert sec(r"^jit_burst$", "decode_model") == pytest.approx(100e-6)
    assert sec(r"^jit_burst$", "extent_write") == pytest.approx(200e-6)
    assert sec(r"^jit_burst$", "extent_gather") == pytest.approx(200e-6)
    assert sec(r"^jit_burst$", "mask_rows") == pytest.approx(100e-6)
    assert sec(r"^jit_prefill$", "extent_write") == pytest.approx(150e-6)
    # the while's own 100 us lie under no scope; the copy has no path
    share = scopes.scoped_share(r, paths, r"^jit_(burst|prefill)$",
                                ["decode_model", "extent_write",
                                 "mask_rows"])
    assert share == pytest.approx(550 / 700)
    # device idle in the window: [50, 100], [600, 700] and [900, 950] us;
    # the readback phase [580, 680] covers 80 of those 200 us
    assert scopes.idle_share_under(r, "sched_readback") == pytest.approx(
        80 / 200)


def test_explicit_window_clips_self_time():
    r, paths = load(HAND)
    got = scopes.scope_seconds(r, paths, r"^jit_burst$", "extent_write",
                               window=(300_000, 1_000_000))
    assert got == pytest.approx(150e-6)


def test_missing_scope_phase_or_program_raises():
    r, paths = load(HAND)
    with pytest.raises(LookupError, match="retention_decay"):
        scopes.scope_seconds(r, paths, r"^jit_burst$", "retention_decay")
    with pytest.raises(LookupError, match="decode_model"):
        scopes.scope_seconds(r, paths, r"^jit_prefill$", "decode_model")
    with pytest.raises(LookupError, match="jit_scrub"):
        scopes.scoped_share(r, paths, r"^jit_scrub$", ["scrub"])
    with pytest.raises(LookupError, match="sched_scrub"):
        scopes.idle_share_under(r, "sched_scrub")


def test_readback_reader_raises_without_its_phase():
    text = HAND.replace('name: "sched_readback"', 'name: "sched_admit"')
    r, _ = load(text)
    with pytest.raises(LookupError, match="sched_readback"):
        registry.reader("readback_idle_share").read(rundata(r))


def test_readback_reader_reads_nothing_from_a_program_without_phases(
        monkeypatch):
    r, _ = load(HAND)
    reader = registry.reader("readback_idle_share")
    assert reader.read(rundata(r)) == pytest.approx(100 * 80 / 200)
    monkeypatch.setattr(scopes, "declared", lambda name, kind: False)
    assert reader.read(rundata(r)) is None
    assert reader.read(rundata(None)) is None


@pytest.mark.skipif(not FIXTURE.is_file(), reason="no recorded trace")
def test_recorded_tpu_trace():
    r, paths = load(FIXTURE.read_text())
    assert r["window_s"] == pytest.approx(0.3)
    burst = tr.program_seconds(r, r"^jit_burst$")
    parts = {sc: scopes.scope_seconds(r, paths, r"^jit_burst$", sc)
             for sc in ("decode_model", "extent_write", "mask_rows")}
    assert all(v > 0 for v in parts.values())
    assert sum(parts.values()) <= burst
    # the column write's gathers are most of the burst (PERF.md section 5)
    assert parts["extent_write"] > parts["decode_model"] > parts["mask_rows"]
    assert scopes.scope_seconds(r, paths, r"^jit_prefill$",
                                "extent_write") > 0
    from repro.telemetry.scopes import MARKS
    share = scopes.scoped_share(r, paths, r"^jit_(burst|prefill)$",
                                MARKS.names("scope"))
    assert 0.9 <= share <= 1.0
    assert 0.0 <= scopes.idle_share_under(r, "sched_readback") <= 1.0
    got = registry.reader("readback_idle_share").read(rundata(r))
    assert 0.0 <= got <= 100.0
