"""Reduce a JAX profiler trace (``.xplane.pb``) to device times.

A TPU device appears as a plane named ``/device:TPU:<n>``. Its line
``XLA Modules`` holds one event per executed program (``jit_<name>(<id>)``)
and its line ``XLA Ops`` one event per operation. Busy time is the union of
the operation intervals inside the traced window; idle gaps are the
window's time outside that union. Host threads are the plane
``/host:CPU``; the benchmark marks its window there with a
``TraceAnnotation`` named ``WINDOW``.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MODULE_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[int, int]


def find_xplane(root: Path) -> Path:
    files = sorted(Path(root).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return files[-1]


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            yield from line.events


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def module_name(name: str) -> str:
    """``jit_burst(123)`` -> ``jit_burst``."""
    return MODULE_ID.sub("", name)


def reduce(planes, window: Optional[Interval] = None) -> Dict:
    """Device busy time, per-program and per-op device time, idle gaps and
    the host's events, from the planes of one trace.
    ``window`` (ns, on the trace's clock) defaults to the host event named
    ``WINDOW``. Times are in seconds; busy and program times are averaged
    over the devices traced. ``planes`` keeps the trace itself, for a
    reader that needs more than these sums."""
    planes = list(planes)
    devs = [p for p in planes if DEVICE_PLANE.match(p.name)]
    host = [p for p in planes if p.name == HOST_PLANE]
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    host_ev = [(e.name, e.start_ns, e.end_ns) for p in host
               for line in p.lines for e in line.events]
    if window is None:
        marks = [(s, e) for n, s, e in host_ev if n == WINDOW]
        if not marks:
            raise ValueError(f"no host event {WINDOW!r} marks the window")
        window = marks[-1]
    lo, hi = window
    busy = 0
    programs: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    gaps: List[Interval] = []
    for d in devs:
        op_iv = []
        for e in _events(d, "XLA Ops"):
            if e.end_ns > lo and e.start_ns < hi:
                op_iv.append((e.start_ns, e.end_ns))
                ops[e.name] += (min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
        for e in _events(d, "XLA Modules"):
            if e.end_ns > lo and e.start_ns < hi:
                programs[module_name(e.name)] += (
                    min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
        u = _clip(merge(op_iv), lo, hi)
        busy += sum(e - s for s, e in u)
        edges = [lo] + [t for iv in u for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devs)
    return {"devices": n, "window_s": (hi - lo) / 1e9,
            "busy_s": busy / n / 1e9,
            "programs": {k: v / n for k, v in programs.items()},
            "ops": {k: v / n for k, v in ops.items()},
            "gaps": gaps, "host": host_ev, "planes": planes}


def _host_during(host_ev, s: int, e: int) -> str:
    """What the host was doing while the device idled: the innermost
    (shortest) host event that spans the middle of the gap, the benchmark's
    own window mark aside."""
    mid = (s + e) // 2
    best, name = None, "no host event"
    for n, hs, he in host_ev:
        if n != WINDOW and hs <= mid < he and (best is None
                                              or he - hs < best):
            best, name = he - hs, n
    return name


def program_seconds(reduced: Dict, pattern: str) -> float:
    """Device seconds of every program whose name matches ``pattern``.
    Raises where none ran: a renamed program would otherwise drop a
    metric without a word."""
    rx = re.compile(pattern)
    hit = [v for k, v in reduced["programs"].items() if rx.search(k)]
    if not hit:
        raise LookupError(f"no program in the trace matches {pattern!r}; "
                          f"ran: {sorted(reduced['programs'])}")
    return sum(hit)


NAME_CHARS = 160


def breakdown(reduced: Dict, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time (names cut to their
    first ``NAME_CHARS`` characters) and the longest idle gaps, each named
    by what the host was doing."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["gaps"], key=lambda g: -(g[1] - g[0]))[:top]
    return {"device_ops": [[k[:NAME_CHARS], v] for k, v in ops],
            "idle_gaps": [[_host_during(reduced["host"], s, e), (e - s) / 1e9]
                          for s, e in gaps]}


def load(path: Path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path)).planes
