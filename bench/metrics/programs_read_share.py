"""Device: the share of the device time spent in programs that some
per-layer reader of the run names (``PROGRAMS``). Work that a change moves
into a program no reader names lowers it, where the readers' own
metrics would only look faster."""
import re

UNIT = "%"


def read(run):
    if run.trace is None:
        return None
    progs = run.trace["programs"]
    rxs = [re.compile(p) for p in run.programs_read]
    read_s = sum(v for k, v in progs.items() if any(r.search(k) for r in rxs))
    return 100.0 * read_s / sum(progs.values())
