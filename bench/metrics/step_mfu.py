"""Model step (models/transformer.py): the whole decode step's share of
the chip's bf16 peak, the model FLOPs of one decode step of the window
(harness.counts) over ``step_ms`` times the peak. It moves with
``step_ms``, as ``burst_hbm_roofline`` does, and bounds that roofline: a
change that takes work off the burst program leaves the roofline silent
about it, and this share still reads the whole step."""
UNIT = "%"


def read(run):
    steps = run.report["decode_steps"]
    if run.trace is None or not steps:
        return None
    step_s = run.window_s / steps
    flops_per_step = run.work["decode_flops"] / steps
    return 100.0 * flops_per_step / (step_s * run.peaks["bf16_flops"])
