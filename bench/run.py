"""Run one benchmark cell once on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/mixes/<traffic>.json``). The run builds the served path as
``repro.launch.serve`` does — one ``ServingEngine`` and one
``ContinuousScheduler`` — with weights drawn on the device from ``--seed``,
warms up every shape the mix uses, then serves the mix's stream
(``harness.traffic``) through one ``ContinuousScheduler.run`` call. The
stream stops after ``--seconds``; the window ends when the scheduler has
drained the pool and returned. Then the run checks what was served against
the plain reference (``harness.check``, limits in ``bench/limits``) and
prints one JSON line: the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics (from a profiler trace of the window) with ``--trace 1``.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result. ``--control 1`` puts the float8 control in the program's
place in that comparison (``check.compare``), which must then print
``correct`` false; the benchmark's own runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Mapping, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from harness import check, counts, registry, traffic  # noqa: E402
from harness import trace as tr  # noqa: E402

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def phase(name: str, since: float) -> float:
    """Print a set-up phase's seconds on stderr; returns the time now."""
    now = time.perf_counter()
    print(f"setup {name} {now - since:.3f}", file=sys.stderr)
    return now


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 1


def cell_of(bench: Mapping[str, Any], name: str) -> Dict[str, Any]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    cell["config_entry"] = next(c for c in bench["configs"]
                                if c["name"] == cell["config"])
    return cell


def metric_names(bench: Mapping[str, Any], cell: Mapping[str, Any],
                 traced: bool) -> List[str]:
    group = bench["per_layer" if traced else "end_to_end"]
    return [m["name"] for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


def model_config(sizes: Mapping[str, Any]):
    """The program's ``ModelConfig`` of a configuration file's ``model``:
    every list a tuple, as the frozen dataclass holds them."""
    from repro.configs.base import ModelConfig
    return ModelConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in sizes.items()})


def wrapping_rings(api, mix: traffic.Mix) -> List[str]:
    """Cache leaves (by path) whose ring of positions (the ``kv_seq`` axis)
    holds fewer than the mix's longest request. Such a ring wraps, and its
    decode writes land on words the final store no longer holds, which
    the comparison prices as writes over zeroed columns."""
    import jax
    longest = max(mix.prompt_lengths) + mix.decode_max
    shapes = jax.eval_shape(lambda: api.init_cache(1, mix.max_seq))
    axes = jax.tree_util.tree_leaves(
        api.cache_axes(), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for (path, leaf), names in zip(
            jax.tree_util.tree_flatten_with_path(shapes)[0], axes):
        if "kv_seq" in names and leaf.shape[names.index("kv_seq")] < longest:
            out.append(jax.tree_util.keystr(path))
    return out


def warm_up(sch, mix: traffic.Mix, vocab: int, make_request
            ) -> Dict[str, Any]:
    """Serve each shape the window uses once through the same scheduler:
    a request of every prompt length, each arriving alone and decoding one
    burst. Every program of the window's path is then compiled (or loaded
    from the cache) and run once. Returns the ledger's streams as they
    stand after the warm-up."""
    sh = traffic.shapes(mix)
    reqs = [make_request(-1 - i, traffic.prompt_tokens(0, i, p, vocab),
                         sh["burst_steps"] + 1, i * sh["burst_steps"])
            for i, p in enumerate(sh["admission"])]
    return copy.deepcopy({"streams": sch.run(reqs)["streams"]})


class CompileCounter:
    """Counts lowerings (``jax.monitoring``) while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == LOWERING:
            self.n += 1


def main(argv: Optional[List[str]] = None, rehearse: Any = None) -> int:
    """One run. ``rehearse`` (tests, and the fault readings of
    ``bench/tests/chip_readings.py``) supplies ``bench`` (the
    BENCHMARK.json document), ``model``/``mix`` overrides, ``peaks`` and
    ``limits`` (None: the files') and an optional ``patch(engine)``; it
    skips the look for a chip, and its stream ends after the mix's
    ``max_requests`` instead of after ``--seconds``, so a test serves the
    same requests on any host."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_path = ROOT / "BENCHMARK.json"
    if rehearse is None and not bench_path.is_file():
        return fail(f"{bench_path} not found")
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the program under test is not in {ROOT / 'src'}")
    bench = (rehearse.bench if rehearse is not None
             else json.loads(bench_path.read_text()))
    cell = cell_of(bench, args.workload)
    conf = json.loads((ROOT / cell["config_entry"]["file"]).read_text())
    mix = traffic.Mix.load(BENCH / "mixes" / f"{cell['traffic']}.json")
    sizes = dict(conf["model"])
    if rehearse is not None:
        sizes.update(rehearse.model)
        mix = dataclasses.replace(mix, **rehearse.mix)
        mix.validate()

    # the TPU runtime writes its logs under /tmp unless told otherwise; a
    # run writes only in its checkout and its own temporary directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    devs = jax.devices()
    dev = devs[0]
    t = phase("runtime", T_START)
    if rehearse is None:
        if dev.platform != "tpu":
            return fail(f"needs a TPU; JAX found {dev.platform!r}")
        if len(devs) < cell["chips"]:
            return fail(f"needs {cell['chips']} chips, JAX found {len(devs)}")
    peaks = getattr(rehearse, "peaks", None)
    if peaks is None:
        kinds = json.loads((BENCH / "peaks.json").read_text())["kinds"]
        if dev.device_kind not in kinds:
            return fail(f"no peaks for device kind {dev.device_kind!r}")
        peaks = kinds[dev.device_kind]
    limits = getattr(rehearse, "limits", None)
    if limits is None:
        limits_path = BENCH / "limits" / f"{cell['name']}.json"
        if not limits_path.is_file():
            return fail(f"no limits for {cell['name']} at {limits_path}")
        limits = json.loads(limits_path.read_text())
    unknown = sorted(set(limits) - set(check.NUMBERS))
    if not limits or unknown:
        return fail(f"limits must name some of {check.NUMBERS}; "
                    f"unknown: {unknown}")

    from repro.models import get_model
    from repro.serve import ContinuousScheduler, ServeConfig, ServingEngine
    from repro.serve.scheduler import Request
    from harness.weights import make_params

    ref = importlib.import_module(f"references.{conf['reference']}")
    cfg = model_config(sizes)
    api = get_model(cfg)
    wraps = wrapping_rings(api, mix)
    if wraps:
        return fail(f"the mix's longest request wraps the ring of {wraps}; "
                    "the comparison cannot price a wrapped ring")
    params = make_params(api, sizes, args.seed, ref)
    t = phase("weights", t)
    eng = ServingEngine(cfg, ServeConfig(
        max_seq=mix.max_seq, max_new_tokens=mix.max_new_tokens,
        **mix.serve), params=params)
    if rehearse is not None and rehearse.patch is not None:
        rehearse.patch(eng)
    scrub = None
    if mix.scrub:
        from repro.reliability import make_scrub_policy
        scrub = make_scrub_policy(**mix.scrub)
    sch = ContinuousScheduler(eng, capacity=mix.capacity,
                              max_burst=mix.max_burst, scrub_policy=scrub)

    def make_request(rid, tokens, new_tokens, arrival):
        return Request(rid=rid, prompt={"tokens": jnp.asarray(tokens)},
                       new_tokens=new_tokens, arrival=arrival)

    t = phase("engine", t)
    before = warm_up(sch, mix, cfg.vocab_size, make_request)
    t = phase("warm_up", t)
    stream = traffic.Stream(mix, args.seed, cfg.vocab_size, make_request)
    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    if trace_dir:
        # HLO protos are not read and would make the trace many MB larger
        opts = jax.profiler.ProfileOptions()
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    stream.close_at = None if rehearse is not None else t0 + args.seconds
    counter.on = True
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        report = sch.run(stream)
    counter.on = False
    window_s = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()
    if stream.exhausted and rehearse is None:
        return fail(f"the stream ran out of requests ({mix.max_requests}); "
                    "raise max_requests in the mix")
    # a scheduler's report keeps the requests of its earlier runs: the
    # warm-up's (negative ids) are not the window's
    report = dict(report, requests={
        rid: r for rid, r in report["requests"].items() if rid >= 0})
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    # -- correctness: copy the sampled rows out, free the program, replay
    served = {rid: {"prompt_len": stream.lengths[rid][0],
                    "n_tokens": r["n_tokens"]}
              for rid, r in report["requests"].items()}
    asked = {rid: stream.lengths[rid][1] + 1 for rid in range(stream.next)}
    bad = check.bad_requests(report, asked, cfg.vocab_size)
    rids = check.pick(report, mix.check_requests, args.seed)
    prompts = {rid: traffic.prompt_tokens(args.seed, rid,
                                          stream.lengths[rid][0],
                                          cfg.vocab_size) for rid in rids}
    samples = check.extract(sch.pool.cache, report, rids, prompts)
    ledger = check.decode_ledger(report, before)
    capacity, decode_steps = mix.capacity, report["decode_steps"]
    del sch, eng, stream
    gc.collect()
    t_check = time.perf_counter()
    found = check.compare(params, sizes, ref, samples, mix.max_seq, ledger,
                          control=bool(args.control))
    for n in found:
        n["bad_requests"] = bad
    nums = found[-1]  # the control's, where it takes the program's place
    check_s = time.perf_counter() - t_check

    work = counts.window_work(ref, sizes, served.values(), decode_steps,
                              capacity)
    reduced = None
    if trace_dir:
        reduced = tr.reduce(tr.load(tr.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = registry.RunData(
        cfg=sizes, mix=mix, report=report, setup_s=setup_s,
        window_s=window_s, work=work, peaks=peaks, memory_peak_bytes=peak,
        compiles=counter.n, trace=reduced)
    metrics = registry.read(metric_names(bench, cell, bool(args.trace)), run)

    checks = {name: {"value": nums[name], "limit": lim}
              for name, lim in limits.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out: Dict[str, Any] = {
        "correct": ok, "attempted": len(asked), "failed": bad,
        "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = tr.breakdown(reduced)
    out["info"] = {"decode_steps": decode_steps, "bursts": report["bursts"],
                   "requests": len(report["requests"]),
                   "window_s": window_s, "check_s": check_s,
                   "compiles_in_window": counter.n,
                   "pool": report["pool"], "total": report.get("total"),
                   "decode_ledger": ledger, "numbers": found[0],
                   "control_numbers": found[1] if args.control else None}
    if reduced is not None:
        out["info"]["programs"] = reduced["programs"]
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
