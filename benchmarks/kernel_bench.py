"""extent_write kernel micro-benchmark + HBM-roofline accounting, driven
through the ``repro.memory`` backend registry.

Off a TPU the Pallas kernel runs in interpret mode (correctness only),
and every wall-time is labelled with the device it ran on (``device``).
What the benchmark reports:

  * bytes moved per write (the kernel's memory-roofline numerator),
  * the fusion win vs. the unfused composition: wall-clock of the
    jit-resident lane backend vs. the eager bit-unpacked oracle
    (``approx_write_with_stats``, which materializes an (elements x nbits)
    u32 intermediate and syncs stats to the host),
  * per-tensor priority without retracing: after the first call, switching
    the driver level swaps threshold/energy vector OPERANDS only — the
    level sweep below reuses one compiled executable per backend (timed to
    show it),
  * on a TPU only: the HBM floor of the fused write, bytes over the
    running device's peak bandwidth (``repro.launch.hw``, keyed by
    ``device_kind``; a TPU kind missing from the table raises). Off a TPU
    no peak applies and the field is absent. It is a bound, not a timing.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro import memory
from repro.core.approx_store import approx_write_with_stats
from repro.core.priority import Priority
from repro.launch import hw


def _timed(fn, reps: int = 3) -> float:
    out = fn()  # warm-up / compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(n_mib: int = 8):
    n = n_mib * 1024 * 1024 // 2  # bf16 elements
    old = jax.random.normal(jax.random.PRNGKey(0), (n,)).astype(jnp.bfloat16)
    new = jax.random.normal(jax.random.PRNGKey(1), (n,)).astype(jnp.bfloat16)
    key = jax.random.PRNGKey(2)

    bytes_fused = 3 * n * 2              # read old+new, write stored
    nbits = 16
    bytes_unfused = bytes_fused + 2 * (n * nbits * 4) * 2  # unpacked u32 x2

    lane_s = _timed(lambda: memory.write(key, old, new, level=Priority.LOW,
                                         backend="lanes_ref")[0])
    eager_s = _timed(lambda: approx_write_with_stats(
        key, old, new, Priority.LOW)[0], reps=1)

    # priority sweep on the already-compiled lane backend: levels swap
    # vector operands, not programs, so per-level cost ~= the LOW cost
    sweep_s = {}
    for level in (Priority.MID, Priority.HIGH, Priority.EXACT):
        sweep_s[level.name] = round(_timed(
            lambda lv=level: memory.write(key, old, new, level=lv,
                                          backend="lanes_ref")[0],
            reps=1), 3)

    t0 = time.perf_counter()
    stored, stats = memory.write(key, old, new, level=Priority.LOW,
                                 backend="pallas")
    jax.block_until_ready(stored)
    interp_s = time.perf_counter() - t0

    dev = jax.devices()[0]
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "tensor_mib": n_mib,
        "bytes_fused": bytes_fused,
        "bytes_unfused_jnp": bytes_unfused,
        "fusion_traffic_reduction_x": round(bytes_unfused / bytes_fused, 1),
        "lane_path_s": round(lane_s, 3),
        "eager_oracle_s": round(eager_s, 3),
        "lane_vs_eager_speedup_x": round(eager_s / max(lane_s, 1e-9), 1),
        "level_sweep_s_no_retrace": sweep_s,
        "pallas_backend_s": round(interp_s, 3),
        "errors": int(stats.errors),
    }
    if dev.platform == "tpu":
        bw = hw.device_peaks(dev).hbm_bw
        out["hbm_floor_us_fused"] = bytes_fused / bw * 1e6
        out["hbm_floor_us_unfused"] = bytes_unfused / bw * 1e6
    return out


def bench_metrics(out) -> dict:
    """Flat latency/traffic metrics for the machine-readable BENCH_<n>.json
    emitted by benchmarks/run.py."""
    m = {
        "tensor_mib": out["tensor_mib"],
        "fusion_traffic_reduction_x": out["fusion_traffic_reduction_x"],
        "lane_path_s": out["lane_path_s"],
        "eager_oracle_s": out["eager_oracle_s"],
        "lane_vs_eager_speedup_x": out["lane_vs_eager_speedup_x"],
        "pallas_backend_s": out["pallas_backend_s"],
    }
    if "hbm_floor_us_fused" in out:
        m["hbm_floor_us_fused"] = out["hbm_floor_us_fused"]
    for level, secs in out["level_sweep_s_no_retrace"].items():
        m[f"level_sweep_{level.lower()}_s"] = secs
    return m


def main():
    import json
    print(json.dumps(run(), indent=1))


if __name__ == "__main__":
    main()
