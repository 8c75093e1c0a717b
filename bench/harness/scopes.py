"""Device time per named scope and host time per scheduler phase, from a
profiler trace of the window.

The program marks its layers in ``repro.telemetry.scopes``. A device scope
is one component of each XLA op's ``op_name`` path. A TPU trace keeps that
path as the ``tf_op`` stat of the op's event metadata on the ``XLA Ops``
line, for instance
``jit(burst)/while/body/closed_call/extent_write/extent_gather/jit(take_along_axis)/gather:``.
A host phase is an event on ``/host:CPU`` named after the phase (its args
are the event's stats).

``jax.profiler.ProfileData`` gives an event's own stats but not its
metadata's, so ``op_paths`` reads the paths from the serialized trace
(``.xplane.pb``); the rest works on the planes ``harness.trace.reduce``
keeps.

Device time per scope is self time: an op event's interval, clipped to the
window, less the union of the op events it encloses on the same line (a
``while`` encloses its body's ops, which would otherwise count twice).
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

from harness import trace as tr

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PATH_STAT = "tf_op"

Interval = Tuple[int, int]


# -- the op_name paths, from the serialized trace -------------------------
def _varint(b, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of one protobuf message: ints for varints,
    memoryview slices for length-delimited fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} in the trace")
        yield key >> 3, v


def _map_entry(b):
    key = value = None
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_paths(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Per TPU device plane, each op event's name (its metadata's name, as
    ``ProfileEvent.name`` gives it) -> its ``op_name`` path (the
    ``tf_op`` stat, without the trailing ``:``). Ops without the stat are
    left out. Of ``XSpace`` this reads planes (1); of ``XPlane`` its name
    (2), event metadata (4) and stat metadata (5); of ``XEventMetadata``
    its name (2) and stats (5); of ``XStat`` the metadata id (1) and the
    string value (5)."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(_map_entry(v)[1])
            elif g == 5:
                key, md = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode() for h, x in _fields(md) if h == 2), "")
        if not tr.DEVICE_PLANE.match(name):
            continue
        path_id = next((k for k, n in stat_names.items() if n == PATH_STAT),
                       None)
        paths: Dict[str, str] = {}
        for md in events:
            ev_name, path = "", None
            for h, v in _fields(md):
                if h == 2:
                    ev_name = bytes(v).decode("utf-8", "replace")
                elif h == 5:
                    sid, s = None, None
                    for k, x in _fields(v):
                        if k == 1:
                            sid = x
                        elif k == 5:
                            s = bytes(x).decode("utf-8", "replace")
                    if sid == path_id and s is not None:
                        path = s
            if path is not None:
                paths[ev_name] = path.rstrip(":")
        out[name] = paths
    return out


def components(path: str) -> List[str]:
    """``jit(burst)/while/body/extent_write/gather`` -> its parts."""
    return path.split("/")


# -- self time of the ops of the window ------------------------------------
def window_of(reduced: Mapping) -> Interval:
    """The window ``harness.trace.reduce`` used: its host mark."""
    marks = [(s, e) for n, s, e in reduced["host"] if n == tr.WINDOW]
    if not marks:
        raise ValueError(f"no host event {tr.WINDOW!r} marks the window")
    return marks[-1]


def self_times(events: Iterable[Tuple[int, int, str]]
               ) -> List[Tuple[int, int, str, int]]:
    """(start, end, name, self ns) of each event of one line: its length
    less the union of the events it encloses. An event encloses another
    that starts and ends within it."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out: List[List] = []
    stack: List[int] = []   # indices into out of the open enclosing events
    kids: Dict[int, List[Interval]] = {}
    for s, e, name in evs:
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= out[stack[-1]][1]:
            kids.setdefault(stack[-1], []).append((s, e))
        out.append([s, e, name, e - s])
        stack.append(len(out) - 1)
    for i, iv in kids.items():
        out[i][3] -= sum(b - a for a, b in tr.merge(iv))
    return [tuple(x) for x in out]


def op_self_times(reduced: Dict, window: Optional[Interval] = None
                  ) -> List[Tuple[str, str, int, str]]:
    """(device plane, program, self ns, op name) of each op event of the
    window, its interval clipped to the window. The program is the
    ``XLA Modules`` event (``module_name``) in which the op starts.
    Computed once per window and kept in ``reduced``."""
    window = window if window is not None else window_of(reduced)
    memo = reduced.setdefault("op_self_times", {})
    if window not in memo:
        memo[window] = _op_self_times(reduced, window)
    return memo[window]


def _op_self_times(reduced: Dict, window: Interval):
    lo, hi = window
    out = []
    for plane in reduced["planes"]:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        mods, ops = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                mods = sorted((e.start_ns, e.end_ns, tr.module_name(e.name))
                              for e in line.events)
            elif line.name == OPS_LINE:
                ops = [(max(e.start_ns, lo), min(e.end_ns, hi), e.name)
                       for e in line.events
                       if e.end_ns > lo and e.start_ns < hi]
        starts = [m[0] for m in mods]
        for s, e, name, self_ns in self_times(ops):
            j = bisect.bisect_right(starts, s) - 1
            prog = mods[j][2] if j >= 0 and s < mods[j][1] else ""
            out.append((plane.name, prog, self_ns, name))
    return out


def scope_seconds(reduced: Mapping, paths: Mapping[str, Mapping[str, str]],
                  programs: str, scope: str,
                  window: Optional[Interval] = None) -> float:
    """Self seconds of the ops, in programs matching ``programs``, whose
    path holds ``scope`` as a component, averaged over the devices traced.
    Raises where no such op ran: a renamed scope would otherwise drop a
    metric without a word."""
    rx = re.compile(programs)
    total, hit, devs = 0, False, set()
    for dev, prog, ns, name in op_self_times(reduced, window):
        devs.add(dev)
        if not rx.search(prog):
            continue
        path = paths.get(dev, {}).get(name)
        if path is not None and scope in components(path):
            total += ns
            hit = True
    if not hit:
        raise LookupError(f"no op of a program matching {programs!r} lies "
                          f"under scope {scope!r}")
    return total / max(len(devs), 1) / 1e9


def scoped_share(reduced: Mapping, paths: Mapping[str, Mapping[str, str]],
                 programs: str, scopes: Sequence[str],
                 window: Optional[Interval] = None) -> float:
    """The share of the self time of programs matching ``programs`` that
    lies under some scope of ``scopes``. Raises where no such program
    ran."""
    rx = re.compile(programs)
    names = set(scopes)
    whole = under = 0
    for dev, prog, ns, name in op_self_times(reduced, window):
        if not rx.search(prog):
            continue
        whole += ns
        path = paths.get(dev, {}).get(name)
        if path is not None and names.intersection(components(path)):
            under += ns
    if not whole:
        raise LookupError(f"no op of a program matching {programs!r} ran")
    return under / whole


# -- host phases against the device's idle time ----------------------------
def phase_intervals(reduced: Mapping, phase: str) -> List[Interval]:
    """The merged intervals of the host events named ``phase``."""
    return tr.merge((s, e) for n, s, e in reduced["host"] if n == phase)


def idle_share_under(reduced: Mapping, phase: str) -> float:
    """The share of the device's idle time in the window (``gaps``) that
    overlaps host phase ``phase``. Raises where the phase never ran in
    the window."""
    lo, hi = window_of(reduced)
    ph = [(max(s, lo), min(e, hi))
          for s, e in phase_intervals(reduced, phase) if e > lo and s < hi]
    if not ph:
        raise LookupError(f"no host phase {phase!r} in the window")
    idle = sum(e - s for s, e in reduced["gaps"])
    if not idle:
        return 0.0
    starts = [s for s, _ in ph]
    both = 0
    for gs, ge in reduced["gaps"]:
        j = max(bisect.bisect_right(starts, gs) - 1, 0)
        while j < len(ph) and ph[j][0] < ge:
            both += max(0, min(ge, ph[j][1]) - max(gs, ph[j][0]))
            j += 1
    return both / idle


def declared(name: str, kind: str) -> bool:
    """Whether the program under test declares this scope or phase. A
    program from before the declarations has none: its readers then read
    nothing rather than fail."""
    try:
        from repro.telemetry import scopes
    except ImportError:
        return False
    return name in scopes.MARKS.names(kind)
