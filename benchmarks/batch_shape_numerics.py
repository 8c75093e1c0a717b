"""Does a change of batch shape alone move the decode-step logits?

    python benchmarks/batch_shape_numerics.py                # qwen2.5-3b, depths 1 4 36
    python benchmarks/batch_shape_numerics.py --reduced --depths 1 2

One row's decode-step logits are computed twice on one device, on equal
inputs: alone (batch 1) and as row 0 of a batch of 4 copies. Weights are
random (``--seed``), the row's cache comes from a 128-token prefill. For
each depth and each compute mode (bf16; f32 at the default matmul
precision; f32 at ``highest``) it reports the largest absolute logit
difference, the correlation of the two logit vectors and the logit
standard deviation.

This is the check behind the die-sharded serving result: if rounding
differences from another program shape grow through depth until the
logits decorrelate, an N-die run can serve other tokens than one die
without any fault in the partitioning. A difference already large at one
layer would point to a fault instead. The last line is a JSON object.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

MODES = ("bf16", "f32", "f32_highest")
PROMPT, MAX_SEQ, BATCH = 128, 256, 4


def row_vs_batch(cfg, params, tokens, mode: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import get_model

    api = get_model(dataclasses.replace(
        cfg, compute_dtype="bfloat16" if mode == "bf16" else "float32"))
    ctx = (jax.default_matmul_precision("highest") if mode == "f32_highest"
           else contextlib.nullcontext())
    with ctx:
        prefill = jax.jit(lambda p, b: api.prefill(p, b, MAX_SEQ))
        step = jax.jit(lambda p, t, c, q: api.decode_step(p, t, c, q,
                                                          MAX_SEQ))
        logits, cache = prefill(params, {"tokens": tokens})
        tok = jnp.argmax(logits.reshape(1, -1), -1).astype(jnp.int32)
        pos = jnp.full((1,), PROMPT, jnp.int32)
        one, _ = step(params, tok, cache, pos)
        copies = jax.tree.map(lambda x: jnp.concatenate([x] * BATCH, axis=1),
                              cache)
        many, _ = step(params, jnp.concatenate([tok] * BATCH), copies,
                       jnp.concatenate([pos] * BATCH))
    one = np.asarray(one, np.float32).reshape(-1)
    row = np.asarray(many, np.float32).reshape(BATCH, -1)[0]
    return {"max_abs_diff": float(np.max(np.abs(one - row))),
            "corr": float(np.corrcoef(one, row)[0, 1]),
            "std": float(one.std())}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--depths", type=int, nargs="+", default=[1, 4, 36])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.configs import get_config
    from repro.models import get_model

    base = get_config(args.arch)
    if args.reduced:
        base = base.reduced()
    tokens = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                (1, PROMPT), 0, base.vocab_size)
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "arch": base.name}
    for depth in args.depths:
        cfg = dataclasses.replace(base, num_layers=depth)
        params = jax.jit(get_model(cfg).init)(jax.random.PRNGKey(args.seed))
        for mode in MODES:
            out[f"L{depth}_{mode}"] = row_vs_batch(cfg, params, tokens, mode)
        del params
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
