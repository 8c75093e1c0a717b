"""Model step (models/transformer.py): model FLOPs of the window's prefill
and decode tokens (harness.counts, from shapes and live context) over the
window times the chip's bf16 peak."""
UNIT = "%"


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.work["flops"] / (run.window_s * run.peaks["bf16_flops"])
