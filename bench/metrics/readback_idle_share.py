"""Scheduler (serve/scheduler.py): the share of the device's idle time in
the window that overlaps the host phase ``sched_readback``, the
scheduler's blocking reads of slot ledgers and tokens
(``repro.telemetry.scopes``). Raises where the program declares the phase
and the trace holds none of it; a program without the declaration (one
from before it) reads nothing."""
from harness import scopes

UNIT = "%"
PHASE = "sched_readback"


def read(run):
    if run.trace is None or not scopes.declared(PHASE, "phase"):
        return None
    return 100.0 * scopes.idle_share_under(run.trace, PHASE)
