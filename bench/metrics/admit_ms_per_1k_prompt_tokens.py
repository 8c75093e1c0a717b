"""Engine admission (serve/engine.py ``_admit_fused``,
``_admit_linked_fused``: prefill and the whole-prompt EXTENT write):
device time per thousand prompt tokens."""
from harness.trace import program_seconds

UNIT = "ms"
PROGRAMS = r"^jit_prefill$"


def read(run):
    if run.trace is None or not run.work["prompt_tokens"]:
        return None
    return 1e6 * program_seconds(run.trace, PROGRAMS) / run.work[
        "prompt_tokens"]
