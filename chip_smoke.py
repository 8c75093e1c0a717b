"""Smoke run of the served path on a TPU, at full qwen2.5-3b size.

    python chip_smoke.py              # one chip: phases a, b, c
    python chip_smoke.py --chips 4    # four chips: the die-sharded pool only

Serves a short request stream through ``ServingEngine`` and
``ContinuousScheduler`` (the objects ``repro.launch.serve`` builds) with
qwen2.5-3b at its published width and depth (36 layers, d_model 2048,
vocab 151936) and random bf16 weights drawn from ``--seed``. The write
path is reached only through the backend registry (``ServeConfig.backend``).

One chip:

  (a) backend ``pallas``: the EXTENT write kernel runs natively (its
      resolved ``interpret`` must be False). The stream is served twice, on
      fresh slot pools, and must repeat bit-for-bit;
  (b) the same stream on ``lanes_ref``, the pure-jnp lane path: tokens and
      the report's ``total`` ledger must equal (a) exactly;
  (c) retention on (a hot die) with the ``periodic`` scrub policy, so the
      scrub kernel runs natively; ``pallas`` and ``lanes_ref`` must agree
      exactly here too, and the scrub must have re-written bits.

``--chips 4`` runs only the die-sharded path and what it is compared
with: the stream with ``shards=4`` over four devices and with
``shards=1``, in this one process, then the retention + scrub stream with
``shards=4`` (the scrub kernel over the die mesh). It prints whether
tokens, flips and errors are equal and the energy difference, the devices
the cache's shards sit on, per-device memory, and the collectives in the
compiled decode burst. The cache must span four devices. Then it feeds
the one-die run's first burst inputs to the decode step, the EXTENT
column write and a scrub of the cache, on one device and on the die mesh:
the write and the scrub must be bit-identical (the layout invariance the
repo promises); the decode step's logits difference is reported. On four
v5e chips the served streams differ end to end (see CHANGES.md): the
partitioned model step computes other logits on equal inputs (as a change
of batch shape does on one chip: at 36 random-weight layers rounding
differences decorrelate the logits), while the write stays bit-identical.

Lines starting with ``smoke:`` carry smoke numbers (set-up and
compile-inclusive wall seconds), not benchmark metrics. The last line is
the result, ``{"ok": true, "device": {...}}``. Any failed check raises,
so the script exits nonzero. Without a TPU it exits nonzero and prints no
result: it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

ARCH = "qwen2.5-3b"
CAPACITY = 4
N_REQUESTS = 4
PROMPT_LEN = 128
NEW_TOKENS = 25
ARRIVAL_EVERY = 8
MAX_SEQ = 256
# phase (c): a die hot enough that stored bits decay within the run
RETENTION = dict(retention_scale=50.0, ambient_k=420.0)
SCRUB_INTERVAL = 4
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def check(ok: bool, *what) -> None:
    """A failed check ends the run with a nonzero exit (asserts can be
    compiled away)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what!r}")


def say(tag: str, **fields) -> None:
    print(f"smoke: {tag} " + json.dumps(fields, default=str), flush=True)


def build(cfg, params, backend: str, **serve_kw):
    from repro.serve import ServeConfig, ServingEngine
    return ServingEngine(cfg, ServeConfig(
        max_seq=MAX_SEQ, max_new_tokens=NEW_TOKENS, backend=backend,
        **serve_kw), params=params)


def stream(cfg, seed: int):
    from repro.serve import synthetic_requests
    return synthetic_requests(cfg, N_REQUESTS, prompt_len=PROMPT_LEN,
                              new_tokens=NEW_TOKENS,
                              arrival_every=ARRIVAL_EVERY, seed=seed)


def serve(eng, seed: int, scrub: bool = False):
    """One run of the stream on a fresh pool; (report, wall seconds)."""
    from repro.reliability import make_scrub_policy
    from repro.serve import ContinuousScheduler
    policy = (make_scrub_policy("periodic", interval=SCRUB_INTERVAL)
              if scrub else None)
    sch = ContinuousScheduler(eng, capacity=CAPACITY, scrub_policy=policy)
    t0 = time.perf_counter()
    rep = sch.run(stream(eng.cfg, seed))
    return rep, time.perf_counter() - t0, sch


def tokens(rep):
    return {rid: list(r["tokens"]) for rid, r in rep["requests"].items()}


def check_served(cfg, rep, label: str) -> None:
    toks = tokens(rep)
    check(sorted(toks) == list(range(N_REQUESTS)), (label, sorted(toks)))
    for rid, t in toks.items():
        check(len(t) == NEW_TOKENS, (label, rid, len(t)))
        check(all(0 <= x < cfg.vocab_size for x in t), (label, rid, t))
    check(rep["total"]["energy_pj"] > 0.0, (label, rep["total"]))
    check(rep["total"]["bits_written"] > 0, (label, rep["total"]))


def same(a, b, label: str) -> None:
    check(tokens(a) == tokens(b), f"{label}: tokens differ")
    check(a["total"] == b["total"], (
        f"{label}: ledgers differ", a["total"], b["total"]))


def one_chip(cfg, params, seed: int) -> None:
    from repro.memory import get_backend

    # (a) pallas, natively
    eng = build(cfg, params, "pallas")
    interp = get_backend(eng.scfg.backend).interpret
    check(interp is False, f"pallas resolved interpret={interp!r}")
    rep_a, cold, _ = serve(eng, seed)
    check_served(cfg, rep_a, "a")
    rep_a2, warm, _ = serve(eng, seed)
    same(rep_a, rep_a2, "a: repeat on a fresh pool")
    say("phase_a", backend="pallas", interpret=interp, ok=True,
        wall_s_first=cold, wall_s_repeat=warm,
        bursts=rep_a["bursts"], decode_steps=rep_a["decode_steps"],
        burst_executables=eng._burst._cache_size(), total=rep_a["total"])

    # (b) lanes_ref must equal (a) exactly
    eng_b = build(cfg, params, "lanes_ref")
    rep_b, cold, _ = serve(eng_b, seed)
    same(rep_a, rep_b, "b: pallas vs lanes_ref")
    say("phase_b", backend="lanes_ref", ok=True, wall_s_first=cold,
        tokens_equal=True, total_equal=True)
    del eng_b

    # (c) retention + periodic scrub: the scrub kernel, natively
    reps = {}
    for backend in ("pallas", "lanes_ref"):
        eng_c = build(cfg, params, backend, **RETENTION)
        reps[backend], cold, _ = serve(eng_c, seed, scrub=True)
        check_served(cfg, reps[backend], f"c/{backend}")
        say("phase_c_run", backend=backend, wall_s_first=cold)
        del eng_c
    same(reps["pallas"], reps["lanes_ref"], "c: pallas vs lanes_ref")
    life = reps["pallas"]["lifetime"]
    check(life["scrub_passes"] > 0, life)
    check(life["scrub_energy_pj"] > 0.0, life)
    check(life["retention_flips"] > 0, life)
    say("phase_c", backend="pallas", ok=True, lifetime=life,
        equal_to_lanes_ref=True)


def collectives_in_burst(sch, calls) -> dict:
    """Count the collectives XLA put into the compiled decode burst, for
    the first burst call the scheduler made."""
    args, kw = calls[0]
    hlo = sch.eng._burst.lower(*args, **kw).compile().as_text()
    return {c: len(re.findall(rf"\b{c}(-start)?\(", hlo)) for c in COLLECTIVES}


def same_inputs(eng, eng4, mesh, call) -> None:
    """One device vs the die mesh on the SAME inputs (the one-die run's
    first burst): the model's decode step is reported as it is; the EXTENT
    column write and a scrub of the cache (``eng4``'s plan maps the kernels
    over the die mesh) — the layout-invariance the repo promises — must be
    bit-identical."""
    import jax
    import numpy as np
    from repro.core.priority import uint_type
    from repro.serve.engine import BATCH_AXIS

    (params, tok, cache, pos, key, _, _, _, vectors), _ = call

    def put(tree, axis):
        return jax.tree.map(lambda a: jax.device_put(
            a, mesh.sharding_for(a.ndim, axis)), tree)

    def bits(tree):
        return [np.asarray(a).view(np.uint8) for a in jax.tree.leaves(tree)]

    def equal(out1, out4):
        *d1, st1 = out1
        *d4, st4 = out4
        return (all(np.array_equal(a, b) for a, b in zip(bits(d1), bits(d4)))
                and st1.host_dict() == st4.host_dict())

    def scrub(plan):
        """Every approximate leaf of the cache, about 1/8 of its bits
        decayed, through the plan's scrub call."""
        def run(key, cache):
            out, acc = [], None
            for i, a in enumerate(jax.tree.leaves(cache)):
                if plan.leaf_levels[i] is None:
                    continue
                ut = uint_type(a.dtype)
                k = jax.random.split(jax.random.fold_in(key, i), 3)
                m = (jax.random.bits(k[0], a.shape, ut)
                     & jax.random.bits(k[1], a.shape, ut)
                     & jax.random.bits(k[2], a.shape, ut))
                s, r, st = plan.leaf_call("leaf_scrub", key, a, m, vectors[i])
                out += [s, r]
                acc = st if acc is None else acc + st
            return (*out, acc)
        return jax.jit(run)

    step = jax.jit(lambda p, t, c, q: eng.api.decode_step(p, t, c, q,
                                                          MAX_SEQ))
    logits1, new1 = step(params, tok, cache, pos)
    written1 = jax.jit(eng.plan.write_columns)(key, cache, new1, pos,
                                               vectors)
    scrubbed1 = scrub(eng.plan)(key, new1)
    cache4, tok4, pos4 = put(cache, BATCH_AXIS), put(tok, 0), put(pos, 0)
    logits4, new4 = step(params, tok4, cache4, pos4)
    new1_4 = put(new1, BATCH_AXIS)
    written4 = jax.jit(eng4.plan.write_columns)(key, cache4, new1_4, pos4,
                                                vectors)
    scrubbed4 = scrub(eng4.plan)(key, new1_4)
    l1 = np.asarray(logits1, np.float32).reshape(tok.shape[0], -1)
    l4 = np.asarray(logits4, np.float32).reshape(tok.shape[0], -1)
    top2 = np.sort(l1, axis=-1)[:, -2:]
    write_equal = equal(written1, written4)
    scrub_equal = equal(scrubbed1, scrubbed4)
    say("same_inputs", logits_max_abs_diff=float(np.max(np.abs(l1 - l4))),
        argmax_equal=(l1.argmax(-1) == l4.argmax(-1)).tolist(),
        top2_gap=(top2[:, 1] - top2[:, 0]).tolist(),
        decode_cache_equal=all(np.array_equal(a, b) for a, b in
                               zip(bits(new1), bits(new4))),
        write_equal=write_equal, scrub_equal=scrub_equal,
        scrub=scrubbed4[-1].host_dict())
    check(write_equal, "the sharded extent write differs on equal inputs")
    check(scrub_equal, "the sharded scrub differs on equal inputs")


def four_chips(cfg, params, seed: int) -> None:
    import jax

    check(len(jax.devices()) >= 4, jax.devices())
    reps, info, first, meshes, engines = {}, {}, {}, {}, {}
    for shards in (4, 1):
        eng = build(cfg, params, "pallas", shards=shards)
        calls = []
        burst = eng._burst

        class Recorder:
            """Keeps the first burst call's arguments for re-lowering."""
            def __call__(self, *a, **k):
                if not calls:
                    calls.append((a, k))
                return burst(*a, **k)

            def __getattr__(self, name):
                return getattr(burst, name)

        eng._burst = Recorder()
        rep, wall, sch = serve(eng, seed)
        check_served(cfg, rep, f"shards={shards}")
        eng._burst = burst
        cache_devs = sorted({d.id for leaf in jax.tree.leaves(sch.pool.cache)
                             for d in leaf.devices()})
        reps[shards], first[shards] = rep, calls[0]
        meshes[shards], engines[shards] = sch.mesh, eng
        info[shards] = dict(
            wall_s_first=wall, cache_devices=cache_devs,
            collectives=collectives_in_burst(sch, calls),
            bytes_in_use={d.id: (d.memory_stats() or {}).get("bytes_in_use")
                          for d in jax.devices()})
        say(f"shards_{shards}", **info[shards])
    t4, t1 = tokens(reps[4]), tokens(reps[1])
    equal = {"tokens": t4 == t1}
    for name, k in (("flips", "bits_written"), ("errors", "bit_errors"),
                    ("bits_total", "bits_total")):
        equal[name] = reps[4]["total"][k] == reps[1]["total"][k]
    e4, e1 = reps[4]["total"]["energy_pj"], reps[1]["total"]["energy_pj"]
    say("sharded", equal=equal, energy_pj_4=e4, energy_pj_1=e1,
        energy_rel_diff=(e4 - e1) / e1,
        tokens_differing={rid: sum(a != b for a, b in zip(t4[rid], t1[rid]))
                          for rid in t1},
        streams_4=reps[4]["streams"], streams_1=reps[1]["streams"],
        burst_collective_free=not any(info[4]["collectives"].values()))
    check(info[4]["cache_devices"] == [d.id for d in jax.devices()[:4]], (
        "the sharded cache does not span four devices", info[4]))
    check(equal["bits_total"], equal)
    same_inputs(engines[1], engines[4], meshes[4], first[1])

    # retention + periodic scrub over the die mesh: the scrub kernel,
    # mapped per die, inside the scheduler
    eng = build(cfg, params, "pallas", shards=4, **RETENTION)
    rep, wall, _ = serve(eng, seed, scrub=True)
    check_served(cfg, rep, "shards=4 scrub")
    life = rep["lifetime"]
    check(life["scrub_passes"] > 0 and life["scrub_energy_pj"] > 0.0, life)
    say("shards_4_scrub", wall_s_first=wall, lifetime=life)


def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.models import get_model

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    # one compiled program draws every weight (eager init pays a compile
    # per leaf shape and holds f32 draws of the largest leaves at once)
    params = jax.block_until_ready(
        jax.jit(get_model(cfg).init)(jax.random.PRNGKey(args.seed)))
    say("setup", device_kind=dev.device_kind, devices=len(jax.devices()),
        arch=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
        vocab=cfg.vocab_size,
        param_bytes=sum(x.nbytes for x in jax.tree.leaves(params)),
        init_s=time.perf_counter() - t0,
        peak_bytes_after_init=(dev.memory_stats() or {}).get(
            "peak_bytes_in_use"))

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(cfg, params, args.seed)
    else:
        one_chip(cfg, params, args.seed)
    say("done", wall_s=time.perf_counter() - t0,
        peak_bytes_in_use={d.id: (d.memory_stats() or {}).get(
            "peak_bytes_in_use") for d in jax.devices()[:args.chips]})
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
