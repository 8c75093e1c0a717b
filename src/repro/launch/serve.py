"""Production serving launcher.

Continuous batching over an arrival stream (the default):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
      --requests 6 --capacity 3 --arrival-every 2 --new-tokens 16 \
      --quality chat=high [--no-extent] [--no-reduced] \
      [--backend oracle|lanes_ref|pallas|exact] [--soft-error-ber 1e-6] \
      [--ambient-k 350 --retention-scale 1000 --scrub-policy periodic \
       --scrub-interval 8 --scrub-cols 0] \
      [--wear-policy rotate --endurance-budget 100 --remap-group-cols 8] \
      [--prefix-cache --prefix-chunk 8 --prefix-table-size 256 \
       --shared-prefix 8] \
      [--shards 2 --die-ambient 1=400] \
      [--metrics-out metrics.prom --trace-timeline timeline.json]

Trace-driven workloads (repro.workload):

  PYTHONPATH=src python -m repro.launch.serve \
      --workload bursty --requests 12 --workload-seed 7   # generate
  PYTHONPATH=src python -m repro.launch.serve \
      --trace-record /tmp/stream.jsonl                    # record
  PYTHONPATH=src python -m repro.launch.serve \
      --trace /tmp/stream.jsonl                           # replay (bit-exact)

Monolithic one-batch mode (the pre-slot-pool engine path):

  PYTHONPATH=src python -m repro.launch.serve --monolithic --batch 4

``--reduced`` (on by default, ``--no-reduced`` to disable) shrinks the
config for CPU hosts; on a pod the same engine runs under the production
mesh with the serve_tp_only or serve_moe_2d residency strategies (see
sharding/rules.py). ``--quality app=level`` tags an application block in
the EXTENT table; requests cycling through that app inherit the level via
the quality-controller handshake. ``--backend`` selects the write-path
implementation from the ``repro.memory`` registry; ``--soft-error-ber``
turns on the post-write retention-upset hook (hardened driver by default),
surfaced as ``soft_strikes`` in the report. ``--retention-scale`` /
``--ambient-k`` enable the ``repro.reliability`` time-axis model (stored
bits decay at the Δ(T) rate of their priority level) and
``--scrub-policy`` schedules background corrective re-writes whose energy
lands in the report's lifetime ledger. ``--wear-policy rotate`` turns on
the physical addressing layer (``repro.memory.address``): hot-row wear is
tracked per physical row group and the logical→physical column remap
rotates when it concentrates, with the migration energy booked as the
ledger's remap component; ``--endurance-budget`` adds the stuck-at
failure model (worn row groups stop accepting writes — lost bits land in
the error counters and the wear report). ``--prefix-cache`` enables the
content-addressable prefix cache (``repro.serve.prefix``): admission
matches each request's leading prompt chunks against a CAM-style table
and links hits to already-resident KV columns instead of re-writing them;
``--shared-prefix N`` makes the synthetic arrival stream share its first
N prompt tokens so the cache has something to hit. ``--metrics-out`` /
``--trace-timeline`` enable ``repro.telemetry``: end-of-run metrics
(Prometheus text or annotated JSON) and a per-request span timeline as
Chrome trace-event JSON that opens directly in Perfetto — telemetry off
(the default) runs bit-identically and writes no files.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.priority import Priority
from repro.launch.compile_cache import use_compile_cache
from repro.memory import available_backends
from repro.serve import (ContinuousScheduler, ServeConfig, ServingEngine,
                         synthetic_requests)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--batch", type=int, default=4,
                    help="monolithic-mode batch size")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--no-extent", action="store_true")
    ap.add_argument("--backend", default="lanes_ref",
                    choices=available_backends(),
                    help="repro.memory write-path backend")
    ap.add_argument("--soft-error-ber", type=float, default=0.0,
                    help="post-write retention-upset BER (0 = off)")
    ap.add_argument("--soft-error-unhardened", action="store_true",
                    help="disable the hardened driver's exponent/sign "
                         "protection for the soft-error hook")
    # repro.reliability: retention decay + background scrubbing
    ap.add_argument("--ambient-k", type=float, default=300.0,
                    help="die ambient temperature (kelvin) for the "
                         "retention model")
    ap.add_argument("--retention-scale", type=float, default=0.0,
                    help="modeled device dwell (seconds) per decode step; "
                         "0 disables the retention model. Values >> real "
                         "step times accelerate aging for studies")
    ap.add_argument("--scrub-policy", default="none",
                    choices=("none", "periodic", "wear_aware",
                             "quality_floor"),
                    help="background scrub scheduling policy (continuous "
                         "mode; implies --retention-scale 1000 when that "
                         "flag is left at 0)")
    ap.add_argument("--scrub-interval", type=int, default=8,
                    help="base scrub interval in decode steps")
    ap.add_argument("--scrub-cols", type=int, default=0,
                    help="columns per scrub pass (0 = whole leaves)")
    # physical addressing: wear-leveling remap + endurance failure model
    ap.add_argument("--wear-policy", default="none",
                    choices=("none", "rotate"),
                    help="wear-leveling policy over the logical→physical "
                         "column remap (continuous mode): 'rotate' "
                         "rotates the permutation when hot-row wear "
                         "concentrates, paying a migration write booked "
                         "as the lifetime ledger's remap component")
    ap.add_argument("--endurance-budget", type=int, default=0,
                    help="writes+scrubs a physical row group survives "
                         "before going stuck-at (0 = unbounded)")
    ap.add_argument("--remap-group-cols", type=int, default=8,
                    help="ring columns per physical row group (the wear/"
                         "failure granularity)")
    ap.add_argument("--wear-check-interval", type=int, default=8,
                    help="decode steps between device wear reads")
    ap.add_argument("--hot-row-wear", type=int, default=16,
                    help="max-group wear since the last rotation that "
                         "arms the next one")
    # content-addressable prefix cache: cross-request KV write reuse
    ap.add_argument("--prefix-cache", action="store_true",
                    help="link matched prompt prefixes to resident KV "
                         "columns at admission instead of re-writing "
                         "them (continuous mode)")
    ap.add_argument("--prefix-chunk", type=int, default=8,
                    help="prompt tokens per CAM digest chunk (the match "
                         "granularity)")
    ap.add_argument("--prefix-table-size", type=int, default=256,
                    help="CAM match-table entries (LRU under pressure)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="leading prompt tokens shared across the "
                         "synthetic arrival stream (0 = fully unique "
                         "prompts, nothing for the prefix cache to hit)")
    # sharded serving (repro.sharding.DieMesh): one logical STT-RAM pool
    # across N independently aging dies
    ap.add_argument("--shards", type=int, default=1,
                    help="number of STT-RAM dies the slot pool is "
                         "sharded across (capacity must divide evenly; "
                         "any value is bit-identical to 1 until per-die "
                         "state diverges)")
    ap.add_argument("--die-ambient", action="append", default=[],
                    metavar="DIE=KELVIN",
                    help="override one die's ambient temperature "
                         "(repeats), e.g. --die-ambient 1=400; diverging "
                         "dies get per-slot decay operands, extra scrub "
                         "cadence, and HIGH-quality admission steering")
    ap.add_argument("--monolithic", action="store_true",
                    help="single fixed batch, no arrival stream")
    # trace-driven workloads (repro.workload): replay, generate, record
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="replay a recorded workload trace (JSONL) as the "
                         "arrival stream instead of the synthetic default")
    ap.add_argument("--workload", default=None, metavar="PRESET",
                    help="generate the arrival stream from a workload "
                         "preset (steady, diurnal, bursty, heavy_tail, "
                         "chat_batch, shared_system_prompt)")
    ap.add_argument("--workload-seed", type=int, default=0,
                    help="root seed for --workload generation (a (preset, "
                         "seed) pair IS the trace — fully deterministic)")
    ap.add_argument("--trace-record", default=None, metavar="PATH",
                    help="record the served arrival stream as a "
                         "replayable trace file")
    # observability (repro.telemetry): either flag turns telemetry on;
    # off (the default) is bit-identical and writes NO files
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write end-of-run metrics (Prometheus text, or "
                         "the annotated JSON document when PATH ends in "
                         ".json); enables telemetry")
    ap.add_argument("--trace-timeline", default=None, metavar="PATH",
                    help="write the per-request span timeline as Chrome "
                         "trace-event JSON (open in Perfetto / "
                         "chrome://tracing); enables telemetry")
    # arrival-stream simulation
    ap.add_argument("--requests", type=int, default=6,
                    help="number of requests in the arrival stream")
    ap.add_argument("--capacity", type=int, default=3,
                    help="slot-pool capacity (concurrent requests)")
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="decode steps between arrivals (0 = all at once)")
    ap.add_argument("--apps", default="chat,summarize",
                    help="comma-separated app ids cycled over requests "
                         "('' = anonymous requests, no table traffic)")
    ap.add_argument("--quality", action="append", default=[],
                    metavar="APP=LEVEL",
                    help="tag an app block (low/mid/high/exact); repeats")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    telemetry = None
    if args.metrics_out or args.trace_timeline:
        from repro.telemetry import Telemetry
        telemetry = Telemetry()

    def export_telemetry(snapshot) -> None:
        from repro.telemetry import write_metrics, write_timeline
        if args.metrics_out:
            p = write_metrics(snapshot, args.metrics_out)
            print(f"metrics -> {p}")
        if args.trace_timeline:
            p = write_timeline(snapshot, args.trace_timeline)
            print(f"timeline -> {p} (open in https://ui.perfetto.dev "
                  f"or chrome://tracing)")

    retention_scale = args.retention_scale
    if args.scrub_policy != "none" and retention_scale == 0.0:
        retention_scale = 1000.0  # scrubbing without decay is a no-op

    def serve_cfg(max_seq: int, new_tokens: int = None) -> ServeConfig:
        return ServeConfig(
            max_seq=max_seq,
            max_new_tokens=(new_tokens if new_tokens is not None
                            else args.new_tokens),
            extent_enabled=not args.no_extent, backend=args.backend,
            soft_error_ber=args.soft_error_ber,
            soft_error_hardened=not args.soft_error_unhardened,
            ambient_k=args.ambient_k, retention_scale=retention_scale,
            wear_policy=args.wear_policy,
            endurance_budget=args.endurance_budget,
            remap_group_cols=args.remap_group_cols,
            prefix_cache=args.prefix_cache,
            prefix_chunk=args.prefix_chunk,
            prefix_table_size=args.prefix_table_size,
            shards=args.shards)

    if args.monolithic:
        prompt = {"tokens": jax.random.randint(
            jax.random.PRNGKey(0), (args.batch, args.prompt_len), 0,
            cfg.vocab_size)}
        if cfg.family == "vlm":
            prompt["image_embeds"] = jax.random.normal(
                jax.random.PRNGKey(1),
                (args.batch, cfg.num_image_tokens, cfg.vision_dim),
                jnp.float32)
        if cfg.family == "audio":
            prompt["frames"] = jax.random.normal(
                jax.random.PRNGKey(1), (args.batch, 24, cfg.d_model),
                jnp.float32)
        max_seq = args.prompt_len + args.new_tokens + (
            cfg.num_image_tokens if cfg.family == "vlm" else 0)
        eng = ServingEngine(cfg, serve_cfg(max_seq))
        toks, report = eng.generate(prompt, telemetry=telemetry)
        print(f"generated {toks.shape} tokens; first row: "
              f"{[int(t) for t in toks[0][:8]]}...")
        if not args.no_extent:
            tot = report["total"]
            print(f"KV write energy {tot['energy_pj']/1e6:.3f} uJ "
                  f"(backend={args.backend}), "
                  f"skip-rate {tot['write_skip_rate']:.3f}, "
                  f"BER {tot['ber_realized']:.2e}")
            if args.soft_error_ber > 0:
                print(f"soft errors: {tot['soft_strikes']} strikes at "
                      f"BER {args.soft_error_ber:.1e} "
                      f"({'hardened' if not args.soft_error_unhardened else 'unhardened'} driver)")
        if telemetry is not None:
            export_telemetry(telemetry.snapshot())
        return

    # ----- continuous batching over an arrival stream: a replayed trace,
    # a generated workload preset, or the synthetic default
    from repro.workload import (TraceSource, load_trace, make_workload,
                                pressure_score, record_requests,
                                save_trace)
    if args.trace and args.workload:
        ap.error("--trace and --workload are mutually exclusive")
    trace = None
    if args.trace:
        trace = load_trace(args.trace)
        stream_desc = f"trace {args.trace}"
    elif args.workload:
        trace = make_workload(args.workload, cfg, args.requests,
                              seed=args.workload_seed)
        stream_desc = (f"workload {args.workload} "
                       f"(seed {args.workload_seed})")
    else:
        stream_desc = "synthetic"

    vlm_extra = cfg.num_image_tokens if cfg.family == "vlm" else 0
    if trace is not None:
        max_seq = trace.max_seq() + vlm_extra
        eng = ServingEngine(cfg, serve_cfg(max_seq,
                                           trace.max_new_tokens()))
    else:
        max_seq = args.prompt_len + args.new_tokens + vlm_extra
        eng = ServingEngine(cfg, serve_cfg(max_seq))
    apps = [a for a in args.apps.split(",") if a] or [None]
    for spec in args.quality:
        app, _, level = spec.partition("=")
        eng.controller.tag("kv_request", app, Priority.coerce(level))
    if trace is not None:
        reqs = TraceSource(trace, cfg)
    else:
        reqs = synthetic_requests(
            cfg, args.requests, prompt_len=args.prompt_len,
            new_tokens=args.new_tokens, arrival_every=args.arrival_every,
            app_ids=apps)
    if args.shared_prefix > 0 and trace is None:
        # overwrite each prompt's head with one common system prefix —
        # the cross-request overlap the prefix cache exists to exploit
        shared = jax.random.randint(
            jax.random.PRNGKey(1234), (1, args.shared_prefix), 0,
            cfg.vocab_size)
        for r in reqs:
            r.prompt["tokens"] = jnp.concatenate(
                [shared, r.prompt["tokens"][:, args.shared_prefix:]],
                axis=1)
    scrub_policy = None
    if args.scrub_policy != "none":
        from repro.reliability import make_scrub_policy
        scrub_policy = make_scrub_policy(args.scrub_policy,
                                         interval=args.scrub_interval,
                                         cols_per_pass=args.scrub_cols)
    wear_policy = None
    if args.wear_policy != "none":
        from repro.reliability import make_wear_policy
        # rotate by a whole row group per rotation: the hot columns hop to
        # fresh physical rows instead of shuffling inside the same group
        wear_policy = make_wear_policy(
            args.wear_policy, check_interval=args.wear_check_interval,
            rotate_step=args.remap_group_cols,
            hot_row_wear=args.hot_row_wear)
    die_ambients = {}
    for spec in args.die_ambient:
        die, _, kelvin = spec.partition("=")
        die_ambients[int(die)] = float(kelvin)
    sch = ContinuousScheduler(eng, capacity=args.capacity,
                              scrub_policy=scrub_policy,
                              wear_policy=wear_policy,
                              telemetry=telemetry,
                              die_ambients=die_ambients)
    # every stream is recordable/scorable: the synthetic default is read
    # back into a trace (one host read per request, pre-serve), trace and
    # workload modes already have one
    rec = trace if trace is not None else record_requests(
        reqs, cfg, meta={"source": "synthetic",
                         "arrival_every": args.arrival_every})
    if args.trace_record:
        save_trace(rec, args.trace_record)
        print(f"recorded trace -> {args.trace_record} "
              f"({len(rec.events)} events)")
    print(f"workload: {stream_desc}, {len(rec.events)} events, "
          f"pressure={pressure_score(rec):.4f}")
    report = sch.run(reqs)

    # ONE rendering path (repro.telemetry.report): every summary section
    # the scheduler emits surfaces here — known sections keep their
    # established line formats, unknown ones print through the fallback
    # instead of being silently dropped
    from repro.telemetry import render_report
    for line in render_report(
            report, backend=args.backend,
            show_extent=not args.no_extent,
            soft_error_ber=args.soft_error_ber,
            soft_error_hardened=not args.soft_error_unhardened):
        print(line)
    if telemetry is not None:
        export_telemetry(report["telemetry"])


if __name__ == "__main__":
    main()
