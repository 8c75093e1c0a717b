"""Engine decode burst (serve/engine.py ``_burst``): device time of the
burst program per decode step."""
from harness.trace import program_seconds

UNIT = "ms"
PROGRAMS = r"^jit_burst$"


def read(run):
    if run.trace is None or not run.report["decode_steps"]:
        return None
    return 1000.0 * program_seconds(run.trace, PROGRAMS) / run.report[
        "decode_steps"]
