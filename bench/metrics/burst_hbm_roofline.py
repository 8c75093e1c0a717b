"""Model step in the decode burst: bytes the window's decode steps need
(weights once per step, the live K/V positions read, one column written
per active request; harness.counts) over the burst program's device time
times the HBM peak."""
from harness.trace import program_seconds

UNIT = "%"
PROGRAMS = r"^jit_burst$"


def read(run):
    if run.trace is None:
        return None
    s = program_seconds(run.trace, PROGRAMS)
    return 100.0 * run.work["decode_bytes"] / (s * run.peaks["hbm_bytes_per_s"])
