"""The reduction from a profiler trace to device times (harness.trace):
on a small hand-made trace whose answers are known, and on a trace
recorded on a TPU v5e (``fixtures/qwen_window.xplane.txt``: 400 ms of a
``qwen2.5-3b.decode_heavy`` window, cut to text by the first chip runs)."""
import dataclasses
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from harness import trace as tr

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "qwen_window.xplane.txt"

# ns on the trace's clock; events are (metadata id, start, duration)
HAND = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 300000000 }
    events { metadata_id: 2 offset_ps: 500000000 duration_ps: 200000000 }
    events { metadata_id: 1 offset_ps: 900000000 duration_ps: 300000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 100000000 duration_ps: 200000000 }
    events { metadata_id: 4 offset_ps: 250000000 duration_ps: 150000000 }
    events { metadata_id: 5 offset_ps: 500000000 duration_ps: 200000000 }
    events { metadata_id: 3 offset_ps: 900000000 duration_ps: 300000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_burst(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_prefill(12)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.1 = bf16[8]" } }
  event_metadata { key: 4 value { id: 4 name: "%while.2 = (s32[])" } }
  event_metadata { key: 5 value { id: 5 name: "%dot.3 = bf16[4]" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 50000000 duration_ps: 1100000000 }
    events { metadata_id: 2 offset_ps: 400000000 duration_ps: 90000000 }
    events { metadata_id: 3 offset_ps: 700000000 duration_ps: 150000000 }
  }
  lines { id: 2 name: "python stack" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 60000000 duration_ps: 1080000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction(prefill)" } }
  event_metadata { key: 3 value { id: 3 name: "device_get" } }
  event_metadata { key: 4 value { id: 4 name: "run" } }
}
"""


def planes(text):
    return list(ProfileData.from_text_proto(text).planes)


def test_hand_made_trace():
    r = tr.reduce(planes(HAND))
    # window [50, 1150] us; ops cover [100,400] + [500,700] + [900,1150]
    assert r["window_s"] == pytest.approx(1100e-6)
    assert r["busy_s"] == pytest.approx((300 + 200 + 250) * 1e-6)
    assert tr.program_seconds(r, r"^jit_burst$") == pytest.approx(550e-6)
    assert tr.program_seconds(r, r"^jit_prefill$") == pytest.approx(200e-6)
    with pytest.raises(LookupError, match="jit_scrub"):
        tr.program_seconds(r, r"^jit_scrub$")
    b = tr.breakdown(r)
    assert b["device_ops"][0] == ["%fusion.1 = bf16[8]", pytest.approx(450e-6)]
    gaps = dict((round(s * 1e6), n) for n, s in b["idle_gaps"])
    assert gaps == {200: "device_get", 100: "PjitFunction(prefill)",
                    50: "run"}


def test_programs_read_share_counts_programs_no_reader_names():
    from harness import registry
    run = registry.RunData(cfg={}, mix=None, report={}, setup_s=0.0,
                           window_s=1.0, work={}, peaks={},
                           memory_peak_bytes=None,
                           trace=tr.reduce(planes(HAND)))
    run.programs_read = [r"^jit_burst$"]
    share = registry.reader("programs_read_share").read(run)
    assert share == pytest.approx(100 * 550 / 750)
    out = registry.read(["burst_ms_per_step", "programs_read_share"],
                        dataclasses.replace(run, report={"decode_steps": 2}))
    assert out["programs_read_share"]["value"] == pytest.approx(share)


def test_explicit_window_clips_events():
    r = tr.reduce(planes(HAND), window=(200_000, 600_000))
    assert r["busy_s"] == pytest.approx(300e-6)
    assert tr.program_seconds(r, r"^jit_burst$") == pytest.approx(200e-6)


def test_merge_overlapping_intervals():
    assert tr.merge([(5, 7), (1, 3), (2, 4), (7, 9)]) == [(1, 4), (5, 9)]


def test_trace_without_a_tpu_plane_is_refused():
    with pytest.raises(ValueError, match="no TPU device plane"):
        tr.reduce(planes('planes { id: 2 name: "/host:CPU" }'))


@pytest.mark.skipif(not FIXTURE.is_file(), reason="no recorded trace")
def test_recorded_tpu_trace():
    p = planes(FIXTURE.read_text())
    dev = next(x for x in p if x.name == "/device:TPU:0")
    ops = [e for line in dev.lines if line.name == "XLA Ops"
           for e in line.events]
    lo = min(e.start_ns for e in ops)
    hi = max(e.end_ns for e in ops)
    r = tr.reduce(p, window=(lo, hi))
    # busy time recomputed the plain way: a 1 us grid over the window
    grid = bytearray(int((hi - lo) // 1000) + 1)
    for e in ops:
        for t in range(int((e.start_ns - lo) // 1000),
                       int((e.end_ns - lo) // 1000)):
            grid[t] = 1
    assert r["busy_s"] == pytest.approx(sum(grid) * 1e-6, rel=0.02)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert tr.program_seconds(r, r"^jit_burst$") > 0
