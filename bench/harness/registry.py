"""Finds a metric's reader, ``bench/metrics/<name>.py``, by the metric's
name in ``BENCHMARK.json``.

A reader module has ``UNIT`` and ``read(run) -> float | None``, where
``run`` is the ``RunData`` of one run. A reader that finds nothing to read
returns None and the metric is left out of the result line. A metric is
added with its reader alone, so ``RunData`` carries everything a run
knows: the configuration, the mix, the serve report, the window's counts,
the trace. A reader of device programs names them by the pattern
``PROGRAMS``; ``RunData.programs_read`` holds the patterns of every reader
of the run, so that device time in a program that none of them reads
shows (``metrics/programs_read_share.py``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Any, Dict, List, Mapping, Optional

METRICS = Path(__file__).resolve().parents[1] / "metrics"


@dataclasses.dataclass
class RunData:
    cfg: Mapping[str, Any]          # the configuration's model sizes
    mix: Any                        # harness.traffic.Mix
    report: Mapping[str, Any]       # ContinuousScheduler.run's report
    setup_s: float
    window_s: float
    work: Mapping[str, int]         # harness.counts.window_work
    peaks: Mapping[str, float]      # this device kind's row of peaks.json
    memory_peak_bytes: Optional[int]
    compiles: Optional[int] = None  # lowerings during the window (traced)
    trace: Optional[Dict[str, Any]] = None  # harness.trace.reduce
    programs_read: List[str] = dataclasses.field(default_factory=list)


def reader(name: str):
    path = METRICS / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(names, run: RunData) -> Dict[str, Dict[str, Any]]:
    mods = {name: reader(name) for name in names}
    run.programs_read = [m.PROGRAMS for m in mods.values()
                         if hasattr(m, "PROGRAMS")]
    out = {}
    for name, mod in mods.items():
        v = mod.read(run)
        if v is not None:
            out[name] = {"value": v, "unit": mod.UNIT}
    return out
