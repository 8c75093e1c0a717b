"""Benchmark orchestrator: one module per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]
       [--out-dir DIR]
Prints `name,seconds,key_results` per benchmark plus per-benchmark key
results; exits nonzero if any benchmark fails.

Every run also emits a machine-readable ``BENCH_<n>.json`` into
``--out-dir`` (default: the working directory; ``n`` auto-increments over
existing files so successive runs build a perf trajectory): suite name,
wall time, and per-benchmark {seconds, metrics}. Benchmark modules opt
into rich metrics by exposing ``bench_metrics(out) -> dict`` (see
serving_energy / kernel_bench / retention_sweep); everything else gets its
scalar outputs scraped.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

from benchmarks import (endurance_sweep, fig2_switching, fig6_thermal,
                        fig12_waveform, fig13_access, fig14_energy,
                        fig15_variation, kernel_bench, prefix_reuse,
                        retention_sweep, serving_energy, shard_scaling,
                        table1, workload_mixes)
from repro.launch.compile_cache import use_compile_cache

BENCHES = {
    "table1": lambda fast: table1.run(),
    "fig2_switching": lambda fast: fig2_switching.run(n_mc=32 if fast else 128),
    "fig6_thermal": lambda fast: fig6_thermal.run(),
    "fig12_waveform": lambda fast: fig12_waveform.run(),
    "fig13_access": lambda fast: fig13_access.run(),
    "fig14_energy": lambda fast: fig14_energy.run(),
    "fig15_variation": lambda fast: fig15_variation.run(
        n=200 if fast else 1000),
    "kernel_bench": lambda fast: kernel_bench.run(n_mib=2 if fast else 8),
    "serving_energy": lambda fast: serving_energy.run(
        archs=("qwen2.5-3b",) if fast else ("qwen2.5-3b",
                                            "recurrentgemma-2b"),
        new_tokens=4 if fast else 8),
    "retention_sweep": lambda fast: retention_sweep.run(
        steps=8 if fast else 16,
        shape=(32, 64) if fast else (64, 128)),
    "endurance_sweep": lambda fast: endurance_sweep.run(
        steps=64 if fast else 160,
        shape=(8, 32) if fast else (8, 64)),
    "prefix_reuse": lambda fast: prefix_reuse.run(n=12 if fast else 16),
    "workload_mixes": lambda fast: workload_mixes.run(
        events=4 if fast else 6),
    "shard_scaling": lambda fast: shard_scaling.run(
        n=6 if fast else 8, repeats=2 if fast else 3),
}

#: the --quick profile: the curated sub-minute subset the CI bench-report
#: lane runs on EVERY push, so the BENCH_<n>.json perf trajectory actually
#: accumulates (implies --fast; one invocation, one JSON)
QUICK_BENCHES = ("table1", "fig6_thermal", "kernel_bench",
                 "retention_sweep", "endurance_sweep", "prefix_reuse",
                 "workload_mixes", "shard_scaling")

#: modules exposing ``bench_metrics(out)`` — the registration hook for the
#: machine-readable report
_METRIC_FNS = {
    "serving_energy": serving_energy.bench_metrics,
    "kernel_bench": kernel_bench.bench_metrics,
    "retention_sweep": retention_sweep.bench_metrics,
    "endurance_sweep": endurance_sweep.bench_metrics,
    "prefix_reuse": prefix_reuse.bench_metrics,
    "workload_mixes": workload_mixes.bench_metrics,
    "shard_scaling": shard_scaling.bench_metrics,
}


def _headline(name: str, out) -> str:
    if name == "table1":
        c = out["claims"]
        return (f"energy_saving={c['energy_saving_vs_ranjan']:.4f} "
                f"(paper 0.3304) latency_saving="
                f"{c['latency_saving_vs_quark']:.4f} (paper 0.0547)")
    if name == "fig2_switching":
        return f"mc_vs_eq1 monotone={out['monotone']}"
    if name == "fig6_thermal":
        return (f"tmr_down={out['fig6_tmr_monotone_down']} "
                f"v_down={out['fig7_voltage_monotone_down']}")
    if name == "fig12_waveform":
        return json.dumps(out["checks"])
    if name == "fig13_access":
        return (f"kv_expensive_share="
                f"{out['kv_decode_stream']['expensive_share']:.2f}")
    if name == "fig14_energy":
        return (f"mean_saving_vs_basic={out['mean_saving_vs_basic']:.3f} "
                f"ordering={out['ordering_holds_all_workloads']}")
    if name == "fig15_variation":
        return f"approx_spread_lower={out['fig15_claim_approx_spread_lower']}"
    if name == "kernel_bench":
        return (f"fusion_x={out['fusion_traffic_reduction_x']} "
                f"device={out['device']['kind']} "
                f"hbm_floor_us={out.get('hbm_floor_us_fused', 'n/a')}")
    if name == "serving_energy":
        k = next(iter(out))
        return (f"{k}: saving={out[k]['saving_vs_basic']:.3f} "
                f"skip={out[k]['write_skip_rate']:.3f}")
    if name == "retention_sweep":
        return json.dumps(out["claims"])
    if name == "endurance_sweep":
        return (f"leveling_gain={out['wear_leveling_gain']:.1f}x "
                f"remap_overhead={out['remap_overhead_frac']:.2f}")
    if name == "prefix_reuse":
        return (f"admission_energy_reduction="
                f"{out['admission_energy_reduction']:.3f} "
                f"hit_rate={out['prefix']['hit_rate']:.2f}")
    if name == "workload_mixes":
        adv = out["adversarial"]
        return (f"mixes={len(out['ramp'])} "
                f"pressure={out['ramp'][0]['pressure']:.2f}→"
                f"{out['ramp'][-1]['pressure']:.2f} "
                f"adversarial_worn none={adv['none']['worn_groups']:.0f} "
                f"rotate={adv['rotate']['worn_groups']:.0f}")
    if name == "shard_scaling":
        return (f"bit_identical="
                f"{out['claims']['bit_identical_across_dies']} "
                f"speedup_4die={out['speedup_vs_1die']['4']:.2f}x "
                f"collective_free="
                f"{out['claims']['burst_collective_free']}")
    return ""


def _scrape_metrics(out, prefix: str = "", depth: int = 0) -> dict:
    """Fallback metric extraction: scalar leaves of a (shallow) result
    dict become flat metric entries."""
    metrics = {}
    if not isinstance(out, dict) or depth > 2:
        return metrics
    for k, v in out.items():
        key = f"{prefix}{k}"
        if isinstance(v, bool):
            metrics[key] = v
        elif isinstance(v, (int, float)):
            metrics[key] = float(v)
        elif isinstance(v, dict):
            metrics.update(_scrape_metrics(v, f"{key}.", depth + 1))
    return metrics


def _metrics_for(name: str, out) -> dict:
    fn = _METRIC_FNS.get(name)
    if fn is not None:
        try:
            return {k: (v if isinstance(v, bool) else float(v))
                    for k, v in fn(out).items()}
        except Exception as e:
            # a broken hook must not hide: the trajectory would silently
            # change schema mid-series. Flag the fallback in the report.
            print(f"WARNING: {name}.bench_metrics failed ({e!r}); "
                  f"falling back to scraped metrics", file=sys.stderr)
            return {"_metrics_fallback": True, **_scrape_metrics(out)}
    return _scrape_metrics(out)


def _next_bench_path(out_dir: Path) -> Path:
    """BENCH_<n>.json with n = 1 + the highest existing index — the perf
    trajectory accumulates instead of overwriting."""
    pat = re.compile(r"^BENCH_(\d+)\.json$")
    taken = [int(m.group(1)) for p in out_dir.glob("BENCH_*.json")
             if (m := pat.match(p.name))]
    return out_dir / f"BENCH_{max(taken, default=0) + 1}.json"


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="the CI perf-trajectory profile: the curated "
                         "fast subset in one invocation / one BENCH json")
    ap.add_argument("--out-dir", default=".",
                    help="directory the BENCH_<n>.json report lands in")
    args = ap.parse_args()
    if args.quick:
        args.fast = True
    failures = []
    results = {}
    t_suite = time.time()
    print("name,seconds,key_results")
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        if args.quick and not args.only and name not in QUICK_BENCHES:
            continue
        t0 = time.time()
        try:
            out = fn(args.fast)
            dt = time.time() - t0
            print(f"{name},{dt:.2f},{_headline(name, out)}")
            results[name] = {"seconds": round(dt, 3),
                             "metrics": _metrics_for(name, out)}
        except Exception as e:
            failures.append((name, repr(e)))
            traceback.print_exc()
            print(f"{name},FAIL,{e!r}")
            results[name] = {"seconds": round(time.time() - t0, 3),
                             "failed": True, "error": repr(e)}

    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = _next_bench_path(out_dir)
    path.write_text(json.dumps({
        "suite": "extent-repro-benchmarks",
        "fast": args.fast,
        "quick": args.quick,
        "only": args.only,
        "wall_time_s": round(time.time() - t_suite, 3),
        "benchmarks": results,
    }, indent=1, default=float))
    print(f"wrote {path}")
    if failures:
        raise SystemExit(f"{len(failures)} benchmark(s) failed: {failures}")


if __name__ == "__main__":
    main()
