"""Scheduler (serve/scheduler.py): decode steps per compiled burst, from
the serve report."""
UNIT = "steps"


def read(run):
    b = run.report["bursts"]
    return run.report["decode_steps"] / b if b else None
