"""Readings that set a cell's limits, taken on the chip at the cell's own
size, in one process (its set-up is paid once per program compiled):

    python bench/tests/chip_readings.py --workload <cell> --seconds 15 \\
        --seeds 1,2,3 --fault-seeds 4,5,6 --faults exact_write,fp8_store \\
        --out <directory>

For each of ``--seeds``, one run with the float8 control beside the
program (``--control 1``): the program's numbers and the control's. For
each fault of ``--faults`` (``harness.faults``) and each of
``--fault-seeds``, one run with the fault planted, serving
``--fault-requests`` requests. Each run prints its numbers to standard
output and appends its result line, with what was run, to
``<out>/<cell>.jsonl``. The benchmark's own runs never run this.
"""
import argparse
import contextlib
import gc
import io
import json
import sys
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run  # noqa: E402
from harness.faults import FAULTS  # noqa: E402


def once(argv, rehearse=None):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(argv, rehearse=rehearse)
    gc.collect()
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]) if rc == 0 and lines else {"rc": rc}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-requests", type=int, default=100)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]
    args.out.mkdir(parents=True, exist_ok=True)
    log = args.out / f"{args.workload}.jsonl"
    base = ["--workload", args.workload, "--seconds", str(args.seconds),
            "--trace", "0"]
    runs = [(s, None) for s in ints(args.seeds)] + [
        (s, f) for f in args.faults.split(",") if f
        for s in ints(args.fault_seeds)]
    for seed, fault in runs:
        argv = base + ["--seed", str(seed)]
        if fault is None:
            res = once(argv + ["--control", "1"])
        else:
            f = FAULTS[fault]
            res = once(argv, types.SimpleNamespace(
                bench=json.loads((BENCH.parent / "BENCHMARK.json")
                                 .read_text()),
                model={}, mix={"max_requests": args.fault_requests,
                               "serve": f.serve},
                peaks=None, limits=None, patch=f.patch))
        row = {"seed": seed, "fault": fault, **res}
        with log.open("a") as fh:
            fh.write(json.dumps(row, default=float) + "\n")
        info = res.get("info", {})
        print(json.dumps({"seed": seed, "fault": fault,
                          "correct": res.get("correct"),
                          "program": info.get("numbers"),
                          "control": info.get("control_numbers"),
                          "checks": res.get("checks"),
                          "check_s": info.get("check_s"),
                          "window_s": info.get("window_s")},
                         default=float), flush=True)


if __name__ == "__main__":
    main()
