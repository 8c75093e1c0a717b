"""Named device scopes and host phases of the served path.

Two kinds of mark put the serving stack's layers on the profiler's clock:

  * a **scope** (``scope(name)``) is a ``jax.named_scope`` around part of a
    compiled program. It costs nothing at run time: the name is op
    metadata, carried into every HLO op's ``op_name`` path (for instance
    ``jit(burst)/while/body/extent_write/extent_gather/gather``), so a
    profiler trace of the device can give each layer its own time.
  * a **phase** (``phase(name, **args)``) is a
    ``jax.profiler.TraceAnnotation`` around host work of the scheduler.
    Without a running profiler it costs a few microseconds; with one it
    lands on ``/host:CPU`` in the same trace as the device ops, so an idle
    gap of the device can be set against what the scheduler was doing.
    Each phase also keeps its ``time.perf_counter_ns()`` bounds
    (``t0_ns``/``t1_ns``) for the span tracer.

Every mark is declared ONCE, here, with its layer, a doc line and what
reads it (a benchmark metric, or ``operator`` where only a person reading
a trace does), as metrics are in ``registry.REGISTRY``. A second
declaration of a name raises at import; using an undeclared name raises.
Names are snake_case without dots, so each reads as one component of the
``op_name`` path.
"""
from __future__ import annotations

import dataclasses
import re
import time
from typing import Dict

import jax

SCOPE = "scope"
PHASE = "phase"
OPERATOR = "operator"

_NAME = re.compile(r"^[a-z][a-z0-9_]*$")


@dataclasses.dataclass(frozen=True)
class Mark:
    """One declared scope or phase."""
    name: str
    kind: str
    layer: str
    reads: str
    doc: str


class MarkRegistry:
    """Declare-once namespace of scopes and phases."""

    def __init__(self):
        self._marks: Dict[str, Mark] = {}

    def _declare(self, mark: Mark) -> Mark:
        if not _NAME.match(mark.name):
            raise ValueError(f"mark {mark.name!r} is not snake_case")
        if mark.name in self._marks:
            raise ValueError(
                f"mark {mark.name!r} already declared "
                f"({self._marks[mark.name].kind}); names are declare-once")
        if not (mark.layer and mark.reads and mark.doc):
            raise ValueError(f"mark {mark.name!r} needs a layer, a reader "
                             f"and a doc line")
        self._marks[mark.name] = mark
        return mark

    def scope(self, name: str, layer: str, reads: str, doc: str) -> Mark:
        return self._declare(Mark(name, SCOPE, layer, reads, doc))

    def phase(self, name: str, layer: str, reads: str, doc: str) -> Mark:
        return self._declare(Mark(name, PHASE, layer, reads, doc))

    def require(self, name: str, kind: str) -> Mark:
        m = self._marks.get(name)
        if m is None or m.kind != kind:
            raise KeyError(f"{name!r} is not a declared {kind}; declared: "
                           f"{sorted(self.names(kind))}")
        return m

    def names(self, kind: str):
        return [n for n, m in self._marks.items() if m.kind == kind]

    def marks(self) -> Dict[str, Mark]:
        return dict(self._marks)


#: The process-wide list of scopes and phases.
MARKS = MarkRegistry()

# --- decode burst (serve/engine.py ``_burst``) --------------------------
MARKS.scope("decode_model", "model step", OPERATOR,
            "the model's decode step over the whole pool")
MARKS.scope("mask_rows", "engine decode burst", OPERATOR,
            "the per-slot select keeping inactive rows' cache bits")
MARKS.scope("extent_write", "EXTENT write", OPERATOR,
            "the EXTENT diff-write: the burst's column write, the "
            "admission's whole-row write")
MARKS.scope("extent_gather", "EXTENT write", OPERATOR,
            "the column write's reads of each slot's old and new column")
MARKS.scope("extent_lanes", "EXTENT write", OPERATOR,
            "one leaf through the write backend (driver levels, write "
            "errors, per-plane counts)")
MARKS.scope("extent_store", "EXTENT write", OPERATOR,
            "the column write's put-back of the stored column")
MARKS.scope("extent_stats", "engine decode burst", OPERATOR,
            "the write's stats into the pool and per-slot accumulators "
            "(and, with wear on, the row-group wear counters)")
MARKS.scope("retention_clear", "retention", OPERATOR,
            "voids the decay record of the re-written columns")
MARKS.scope("retention_decay", "retention", OPERATOR,
            "one step of retention decay over every stored bit")
MARKS.scope("sample", "engine decode burst", OPERATOR,
            "next-token sampling from the step's logits")
# --- admission (``_admit_fused``, ``_admit_linked_fused``) --------------
MARKS.scope("admit_model", "engine admission", OPERATOR,
            "the model's prefill of the admitted prompts")
MARKS.scope("admit_update", "engine admission", OPERATOR,
            "inserts the stored rows, first tokens and ledgers into the "
            "pool (serve/slots.py)")
# --- scrub (``_scrub_fused``) -------------------------------------------
MARKS.scope("scrub", "retention", OPERATOR,
            "one corrective scrub pass over the decayed bits")

# --- scheduler phases (serve/scheduler.py ``ContinuousScheduler.run``) --
MARKS.phase("sched_admit", "scheduler", OPERATOR,
            "one admission group, from grouping to the prefill dispatch")
MARKS.phase("sched_dispatch", "scheduler", OPERATOR,
            "driver-vector selection and the decode burst's dispatch")
MARKS.phase("sched_complete", "scheduler", OPERATOR,
            "retiring the requests whose token budget is spent")
MARKS.phase("sched_readback", "scheduler", "readback_idle_share",
            "a blocking read of device results: slot ledgers or tokens")
MARKS.phase("sched_scrub", "scheduler", OPERATOR,
            "planning and dispatching a scrub pass")
MARKS.phase("sched_wear", "scheduler", OPERATOR,
            "the wear checkpoint's read and a remap rotation")
MARKS.phase("sched_telemetry", "scheduler", OPERATOR,
            "booking spans and instruments, and the event's drain")


def scope(name: str):
    """``jax.named_scope(name)`` for a declared scope."""
    MARKS.require(name, SCOPE)
    return jax.named_scope(name)


class Phase(jax.profiler.TraceAnnotation):
    """A ``TraceAnnotation`` that keeps its ``perf_counter_ns`` bounds."""

    t0_ns = t1_ns = 0

    def __enter__(self):
        self.t0_ns = time.perf_counter_ns()
        return super().__enter__()

    def __exit__(self, *exc):
        out = super().__exit__(*exc)
        self.t1_ns = time.perf_counter_ns()
        return out


def phase(name: str, **args) -> Phase:
    """A host phase for a declared name. ``args`` are host numbers the
    caller already has (a device value would make the profiler read it
    back); they show on the trace event."""
    MARKS.require(name, PHASE)
    for k, v in args.items():
        if not isinstance(v, (int, float, str)):
            raise TypeError(f"phase {name} arg {k!r} is a "
                            f"{type(v).__name__}, not a host number")
    return Phase(name, **args)
