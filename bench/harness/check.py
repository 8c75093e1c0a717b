"""The comparison that decides ``correct``.

After the window, a sample of the finished requests, drawn from the seed,
is replayed through the plain reference of the configuration
(``bench/references``). Only a request whose slot no later request took
still has its K/V in the pool, so the sample is drawn from the last
request of each slot, and the longest of those is always in it. The mixes
take all of them (``check_requests`` = the pool's capacity): a thousand or
more served tokens, so that a step that computes in lower precision
changes some served token on every seed.

``compare`` returns every number in ``NUMBERS``; a cell compares those
that its limits file, ``bench/limits/<cell>.json``, lists:

* ``logit_gap``: the widest gap, over every served token of the sample, by
  which the served token's reference logit lies below the reference's best
  (greedy serving picks the best). Covers the model step: the prefill, and
  each decode step over the K/V the store held.
* ``kv_exp_mismatch``: the share of stored cache elements of the sample
  (every leaf of every slot class) whose sign and exponent bits differ
  from the reference's own values rounded to bf16. The EXTENT write keeps
  sign and exponent exact and approximates the mantissa, so a store that
  holds the new values differs only where rounding crosses a power of
  two; a write that keeps old bits, or a step that leaves the cache as it
  was, differs almost everywhere.
* ``mantissa_loss_gap``: at the sample's decoded positions, the stored
  mantissa bits that the write lost against the reference's values of the
  leaf its module names (``LOSS_LEAF``; ``references.extent``), to the
  losses its driver levels predict:
  ``|lost / predicted - 1|``. An exact store loses none (1), a store of
  fewer mantissa bits loses many.
* ``write_energy_gap``, ``write_error_gap``: the decode writes' energy and
  failed bits in the program's ledger, per written column of the window,
  against what the driver levels price for a stored column of the sample:
  ``|ledger / reference - 1|``.
* ``bad_requests``: requests of the window that did not return exactly the
  tokens they asked for, all inside the vocabulary (limit 0).

With ``control``, the float8 reference takes the program's place: its
first-ranked tokens for the served ones and its own cache for the stored
ones. The ledger and ``bad_requests`` stay the program's.

Nothing here knows an architecture: the cache is the program's tree, each
leaf ``(layer stack, slot, position, ...)`` in the pool, and what a leaf's
bits mean is the reference module's (``references/__init__.py``).
"""
from __future__ import annotations

from types import ModuleType
from typing import Any, Dict, List, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness.traffic import _rng


def bad_requests(report: Mapping[str, Any], asked: Mapping[int, int],
                 vocab: int) -> int:
    """Requests whose served tokens are not ``asked[rid]`` ids in
    [0, vocab)."""
    bad = 0
    for rid, want in asked.items():
        r = report["requests"].get(rid)
        if (r is None or len(r["tokens"]) != want
                or not all(0 <= t < vocab for t in r["tokens"])):
            bad += 1
    return bad


def pick(report: Mapping[str, Any], n: int, seed: int) -> List[int]:
    """Request ids to replay: the last request of each slot, ``n`` of them
    drawn from the seed, the longest always among them."""
    last: Dict[int, Mapping[str, Any]] = {}
    for r in report["requests"].values():
        s = r["slot"]
        if s not in last or r["admitted_step"] > last[s]["admitted_step"]:
            last[s] = r
    cands = sorted(r["rid"] for r in last.values())
    longest = max(cands, key=lambda i: (report["requests"][i]["n_tokens"], i))
    rest = [c for c in cands if c != longest]
    k = min(n - 1, len(rest))
    chosen = _rng(seed, 3).choice(len(rest), size=k, replace=False)
    return [longest] + sorted(rest[i] for i in chosen)


def extract(cache: Mapping[str, Any], report: Mapping[str, Any],
            rids: Sequence[int], prompts: Mapping[int, np.ndarray]
            ) -> List[Dict[str, Any]]:
    """Copy each sampled request's row of every cache leaf out of the pool
    (``stored``, in the pool's tree), with its tokens, before the program
    is freed. Positions past the request's own are left in the copy and
    never read."""
    out = []
    for rid in rids:
        r = report["requests"][rid]
        p = prompts[rid]
        served = np.asarray(r["tokens"], np.int32)
        s = r["slot"]
        out.append({
            "rid": rid, "prompt_len": int(p.shape[-1]), "served": served,
            "tokens": np.concatenate([p.reshape(-1), served[:-1]]),
            "stored": jax.tree.map(lambda a: jnp.array(a[:, s]), cache)})
    return out


def _top9(x: jax.Array) -> jax.Array:
    """Sign and exponent bits of bf16 words."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16),
                                        jnp.uint16) >> 7


NUMBERS = ("logit_gap", "kv_exp_mismatch", "mantissa_loss_gap",
           "write_energy_gap", "write_error_gap", "bad_requests")


def _gap(got: float, want: float) -> float:
    """``|got / want - 1|``; 1 where the reference expects nothing."""
    return abs(got / want - 1.0) if want > 0 else 1.0


def decode_ledger(report: Mapping[str, Any],
                  before: Mapping[str, Any]) -> Dict[str, float]:
    """The window's decode writes from the serve report: its ``kv_decode``
    stream less the same stream in ``before`` (the report of the last
    warm-up run; a scheduler's ledger adds up over its runs), and the
    columns written, one per decode step of each request."""
    now = report["streams"]["kv_decode"]
    old = before.get("streams", {}).get("kv_decode", {})
    return {"energy_pj": now["energy_pj"] - old.get("energy_pj", 0.0),
            "errors": now["bit_errors"] - old.get("bit_errors", 0),
            "columns": sum(r["n_tokens"] - 1
                           for r in report["requests"].values())}


def _keys(path) -> List[str]:
    return [str(getattr(k, "key", k)) for k in path]


def _rows(leaf: jax.Array, keep: jax.Array) -> jax.Array:
    """``keep`` over a leaf's positions (axis 1), broadcast to its rank."""
    return keep[:leaf.shape[1]].reshape((1, -1) + (1,) * (leaf.ndim - 2))


def compare(params: Any, cfg: Mapping[str, Any], ref: ModuleType,
            samples: Sequence[Mapping[str, Any]], max_seq: int,
            ledger: Mapping[str, float], control: bool = False
            ) -> List[Dict[str, float]]:
    """Replay every sample through the reference module ``ref``, over its
    whole row of ``max_seq`` positions so one compiled program serves every
    request (rows past the request's own are computed and not read).
    Returns the program's numbers (every one of ``NUMBERS`` but
    ``bad_requests``) and, with ``control``, the float8 control's after
    them."""
    from references import extent
    frozen = dict(cfg)

    def tally(out, target, stored, P, T):
        rows = jnp.arange(target.shape[0])
        valid = (rows >= P - 1) & (rows < T)
        held, decoded = rows < T, (rows >= P) & (rows < T)
        lg = out["logits"]
        got = jnp.take_along_axis(lg, target[:, None], -1)[:, 0]
        res = {"gap": jnp.max(jnp.where(valid, jnp.max(lg, -1) - got, 0.0)),
               "mism": 0, "elems": 0, "columns": jnp.sum(decoded)}
        flat, tree = jax.tree_util.tree_flatten_with_path(stored)
        for (path, st), ow in zip(flat, tree.flatten_up_to(out["own"])):
            keys = _keys(path)
            name, key = keys[-1], "/".join(keys)
            h, d = _rows(st, held), _rows(st, decoded)
            res["mism"] += jnp.sum((_top9(ow) != _top9(st)) & h)
            res["elems"] += jnp.sum(h) * st[:, 0].size
            planes = ref.PLANES[name]
            for k, x in extent.column_expectation(planes, st, d).items():
                res[f"{k}@{key}"] = x
            if name == ref.LOSS_LEAF:
                for k, x in extent.mantissa_losses(
                        ow, st, d, planes, ref.LOSS_PLANES).items():
                    res[f"{k}@{key}"] = x
        return res

    @jax.jit
    def replay(params, toks, P, T, target, stored):
        out = ref.served_logits(params, frozen, toks, P, stored, "f32")
        res = [tally(out, target, stored, P, T)]
        if control:
            c = ref.served_logits(params, frozen, toks, P, stored, "fp8")
            res.append(tally(out, jnp.argmax(c["logits"], -1), c["own"],
                             P, T))
        return res

    sums: List[Dict[str, float]] = [{} for _ in range(1 + control)]
    for s in samples:
        P, served = s["prompt_len"], s["served"]
        T = P + served.shape[0] - 1
        target = np.zeros((max_seq,), np.int32)
        target[P - 1:T] = served
        toks = np.zeros((max_seq,), np.int32)
        toks[:T] = s["tokens"]
        rs = jax.device_get(replay(
            params, jnp.asarray(toks), jnp.int32(P), jnp.int32(T),
            jnp.asarray(target), s["stored"]))
        for acc, r in zip(sums, rs):
            acc["gap"] = max(acc.get("gap", 0.0), float(r.pop("gap")))
            for name, x in r.items():
                acc[name] = acc.get(name, 0.0) + float(x)
    return [_numbers(acc, ledger) for acc in sums]


def _numbers(sums: Mapping[str, float], ledger: Mapping[str, float]
             ) -> Dict[str, float]:
    def leaves(name):  # summed over the leaves, in the tree's order
        return sum(sums[k] for k in sorted(sums) if k.startswith(f"{name}@"))
    cols = max(sums["columns"], 1.0)
    per_col = lambda x, n: x / n if n else 0.0
    return {
        "logit_gap": sums["gap"],
        "kv_exp_mismatch": sums["mism"] / max(sums["elems"], 1.0),
        "mantissa_loss_gap": _gap(leaves("net"), leaves("expected")),
        "write_energy_gap": _gap(
            per_col(ledger["energy_pj"], ledger["columns"]),
            leaves("energy_pj") / cols),
        "write_error_gap": _gap(
            per_col(ledger["errors"], ledger["columns"]),
            leaves("errors") / cols),
    }
