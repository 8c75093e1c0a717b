"""Operations and bytes the served algorithm needs, from configuration shapes.

The counts of one token are the architecture's, and live in the
configuration's reference module (``references.<reference>``:
``prefill_flops``, ``decode_flops``, ``decode_kv_bytes``,
``decode_weight_bytes``); they are the algorithm's needed operations and
bytes, not what the program moves, so no share of a peak built on them can
pass 100%. This module adds them up over the requests and steps of a
serving window.
"""
from __future__ import annotations

from types import ModuleType
from typing import Any, Dict, Iterable, Mapping


def request_work(ref: ModuleType, cfg: Mapping[str, Any], prompt_len: int,
                 n_tokens: int) -> Dict[str, int]:
    """FLOPs and decode K/V bytes of one served request: its prefill (which
    yields the first token) and ``n_tokens - 1`` decode steps, the step
    that yields token ``i`` at context ``prompt_len + i``."""
    dec_flops = dec_bytes = 0
    for i in range(1, n_tokens):
        ctx = prompt_len + i
        dec_flops += ref.decode_flops(cfg, ctx)
        dec_bytes += ref.decode_kv_bytes(cfg, ctx)
    return {"prefill_flops": ref.prefill_flops(cfg, prompt_len),
            "decode_flops": dec_flops, "decode_kv_bytes": dec_bytes}


def window_work(ref: ModuleType, cfg: Mapping[str, Any],
                requests: Iterable[Mapping[str, int]], decode_steps: int,
                capacity: int) -> Dict[str, int]:
    """Work of a serving window: the requests it served (``prompt_len`` and
    ``n_tokens`` each) and its ``decode_steps`` steps, each reading the
    weights once for a pool of ``capacity`` slots."""
    out = {"prefill_flops": 0, "decode_flops": 0, "decode_kv_bytes": 0,
           "prompt_tokens": 0, "output_tokens": 0}
    for r in requests:
        w = request_work(ref, cfg, r["prompt_len"], r["n_tokens"])
        for k, v in w.items():
            out[k] += v
        out["prompt_tokens"] += r["prompt_len"]
        out["output_tokens"] += r["n_tokens"]
    out["decode_weight_bytes"] = decode_steps * ref.decode_weight_bytes(
        cfg, capacity)
    out["decode_bytes"] = out["decode_weight_bytes"] + out["decode_kv_bytes"]
    out["flops"] = out["prefill_flops"] + out["decode_flops"]
    return out
