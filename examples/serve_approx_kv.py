"""Serving example: batched generation with EXTENT-approximate KV writes.

  PYTHONPATH=src python examples/serve_approx_kv.py [--arch qwen2.5-3b]

Serves a reduced-config model with the production engine, comparing exact
vs. approximate KV storage: token agreement, realized write-energy savings
vs. the basic (non-approximate) STT-RAM cell, and the CMP skip rate —
then replays the same traffic as a staggered arrival stream through the
continuous-batching slot pool, with one request negotiating a HIGH quality
floor through the EXTENT-table handshake (per-request energy/BER
attribution in the serve report).

The approximate write is fused into the jitted decode burst (one compiled
``lax.scan`` call per decode span, stats accumulated on device, synced
once per generate/scheduler event). ``--backend`` selects the write-path
implementation from the ``repro.memory`` registry — "lanes_ref" (default)
is the pure-jnp lane path, "pallas" the kernel (the Pallas interpreter
off a TPU: slow, correctness-mode; native on a TPU, where chip_smoke.py
runs it bit-identical to "lanes_ref"), "oracle" the eager bit-unpacked
reference.
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.energy_model import exact_baseline_energy_pj
from repro.core.priority import Priority
from repro.serve import (ContinuousScheduler, ServeConfig, ServingEngine,
                         synthetic_requests)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--backend", default="lanes_ref",
                    help="repro.memory write-path backend name")
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    prompt = {"tokens": jax.random.randint(
        jax.random.PRNGKey(0), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)}
    if cfg.family == "vlm":
        prompt["image_embeds"] = jax.random.normal(
            jax.random.PRNGKey(1),
            (args.batch, cfg.num_image_tokens, cfg.vision_dim), jnp.float32)
    if cfg.family == "audio":
        prompt["frames"] = jax.random.normal(
            jax.random.PRNGKey(1), (args.batch, 24, cfg.d_model), jnp.float32)

    max_seq = args.prompt_len + args.new_tokens + (
        cfg.num_image_tokens if cfg.family == "vlm" else 0)

    eng_x = ServingEngine(cfg, ServeConfig(max_seq=max_seq,
                                           max_new_tokens=args.new_tokens,
                                           extent_enabled=False))
    toks_x, _ = eng_x.generate(prompt)

    eng_a = ServingEngine(cfg, ServeConfig(max_seq=max_seq,
                                           max_new_tokens=args.new_tokens,
                                           extent_enabled=True,
                                           backend=args.backend))
    toks_a, report = eng_a.generate(prompt)

    agree = float(jnp.mean((toks_x == toks_a).astype(jnp.float32)))
    tot = report["total"]
    baseline = exact_baseline_energy_pj(tot["bits_total"])
    print(f"arch={cfg.name}  batch={args.batch}  "
          f"new_tokens={args.new_tokens}")
    print(f"token agreement (extent vs exact): {agree:.3f}")
    print(f"KV write energy: {tot['energy_pj']/1e6:.3f} uJ "
          f"(basic cell would pay {baseline/1e6:.3f} uJ -> "
          f"{100*(1-tot['energy_pj']/max(baseline,1e-9)):.1f}% saved)")
    print(f"CMP write-skip rate: {tot['write_skip_rate']:.3f}")
    print(f"realized KV bit-error rate: {tot['ber_realized']:.2e}")
    for stream, s in report["streams"].items():
        print(f"  {stream:12s} E={s['energy_pj']/1e6:.3f} uJ "
              f"errors={s['bit_errors']}")

    # ----- continuous batching: staggered arrivals through the slot pool,
    # one application negotiating HIGH quality via the EXTENT table
    print("\n-- continuous batching (slot pool, staggered arrivals) --")
    eng_c = ServingEngine(cfg, ServeConfig(max_seq=max_seq,
                                           max_new_tokens=args.new_tokens,
                                           extent_enabled=True,
                                           backend=args.backend))
    reqs = synthetic_requests(
        cfg, args.batch + 2, prompt_len=args.prompt_len,
        new_tokens=args.new_tokens, arrival_every=max(2, args.new_tokens // 4),
        app_ids=["chat", "legal", "chat"],
        qualities=[None, Priority.HIGH, None])
    sched = ContinuousScheduler(eng_c, capacity=args.batch)
    rep = sched.run(reqs)
    print(f"{len(rep['requests'])} requests, {rep['clock_steps']} steps, "
          f"{rep['bursts']} compiled bursts, peak occupancy "
          f"{rep['pool']['peak_occupancy']}/{rep['pool']['capacity']}")
    for rid in sorted(rep["requests"]):
        r = rep["requests"][rid]
        print(f"  req {rid} app={str(r['app_id']):6s} q={r['quality']:5s} "
              f"queued {r['queue_steps']:2d} latency {r['latency_steps']:3d} "
              f"E={r['energy_pj']/1e3:7.1f} nJ BER={r['ber']:.2e}")
    tbl = rep["extent_table"]
    print(f"EXTENT table: {tbl['hits']} hits / {tbl['misses']} misses "
          f"(hit rate {tbl['hit_rate']:.2f})")


if __name__ == "__main__":
    main()
