"""Operations and bytes the served algorithm needs, from configuration shapes.

These are the benchmark's own count functions: they describe the work of a
dense decoder-only transformer with grouped-query attention (the
configurations in ``bench/configs``), not what the program happens to move.
A decode step reads every weight once, reads the live K/V positions of each
active request, and writes one new K/V column per active request. Attention
counts only the keys a query may see (the causal prefix, cut to the sliding
window), so a program that computes over the whole cache row still reads at
most 100% of these rooflines.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Mapping

BF16_BYTES = 2


def _window(cfg: Mapping[str, Any]) -> int:
    """Largest number of keys a query sees; 0 means no window."""
    pattern = cfg["window_pattern"]
    if len(set(pattern)) != 1:
        raise ValueError(f"mixed window patterns are not counted: {pattern}")
    return int(pattern[0])


def _seen(cfg: Mapping[str, Any], ctx: int) -> int:
    w = _window(cfg)
    return min(ctx, w) if w > 0 else ctx


def layer_matmul_params(cfg: Mapping[str, Any]) -> int:
    """Weights of one layer that multiply every token: Q, K, V, O and the
    gated MLP."""
    D, H, K, h, F = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                     cfg["head_dim"], cfg["d_ff"])
    return D * H * h + 2 * D * K * h + H * h * D + 3 * D * F


def param_count(cfg: Mapping[str, Any]) -> int:
    """Every parameter: layers (with biases and the two norms), the
    embedding, the untied output head and the final norm."""
    D, H, K, h = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                  cfg["head_dim"])
    per_layer = layer_matmul_params(cfg) + 2 * D
    if cfg.get("qkv_bias"):
        per_layer += H * h + 2 * K * h
    embed = cfg["vocab_size"] * D
    head = 0 if cfg.get("tie_embeddings", True) else embed
    return cfg["num_layers"] * per_layer + embed + head + D


def kv_bytes_per_position(cfg: Mapping[str, Any]) -> int:
    """K and V of one token position over all layers, in bf16."""
    return (cfg["num_layers"] * 2 * cfg["num_kv_heads"] * cfg["head_dim"]
            * BF16_BYTES)


def _attn_flops(cfg: Mapping[str, Any], keys: int) -> int:
    """QK^T and PV of one query over ``keys`` keys, all layers."""
    return cfg["num_layers"] * 4 * cfg["num_heads"] * cfg["head_dim"] * keys


def _logits_flops(cfg: Mapping[str, Any]) -> int:
    return 2 * cfg["d_model"] * cfg["vocab_size"]


def prefill_flops(cfg: Mapping[str, Any], prompt_len: int) -> int:
    """One prompt: every position through every layer, causal attention,
    and logits for the last position only."""
    mm = 2 * cfg["num_layers"] * layer_matmul_params(cfg) * prompt_len
    attn = sum(_attn_flops(cfg, _seen(cfg, t))
               for t in range(1, prompt_len + 1))
    return mm + attn + _logits_flops(cfg)


def decode_flops(cfg: Mapping[str, Any], ctx: int) -> int:
    """One decoded token whose query sees ``ctx`` positions (itself
    included)."""
    return (2 * cfg["num_layers"] * layer_matmul_params(cfg)
            + _attn_flops(cfg, _seen(cfg, ctx)) + _logits_flops(cfg))


def decode_weight_bytes(cfg: Mapping[str, Any], batch: int) -> int:
    """Weights one decode step reads: every layer, the final norm and the
    output head in full; of an untied embedding only the ``batch`` rows
    looked up."""
    D, V = cfg["d_model"], cfg["vocab_size"]
    total = param_count(cfg)
    if not cfg.get("tie_embeddings", True):
        total -= V * D - batch * D
    return total * BF16_BYTES


def request_work(cfg: Mapping[str, Any], prompt_len: int,
                 n_tokens: int) -> Dict[str, int]:
    """FLOPs and decode K/V bytes of one served request: its prefill (which
    yields the first token) and ``n_tokens - 1`` decode steps, the step
    that yields token ``i`` reading the ``prompt_len + i`` positions before
    it and writing one."""
    kvb = kv_bytes_per_position(cfg)
    dec_flops = dec_bytes = 0
    for i in range(1, n_tokens):
        ctx = prompt_len + i
        dec_flops += decode_flops(cfg, ctx)
        dec_bytes += (_seen(cfg, ctx - 1) + 1) * kvb
    return {"prefill_flops": prefill_flops(cfg, prompt_len),
            "decode_flops": dec_flops, "decode_kv_bytes": dec_bytes}


def window_work(cfg: Mapping[str, Any], requests: Iterable[Mapping[str, int]],
                decode_steps: int, capacity: int) -> Dict[str, int]:
    """Work of a serving window: the requests it served (``prompt_len`` and
    ``n_tokens`` each) and its ``decode_steps`` steps, each reading the
    weights once for a pool of ``capacity`` slots."""
    out = {"prefill_flops": 0, "decode_flops": 0, "decode_kv_bytes": 0,
           "prompt_tokens": 0, "output_tokens": 0}
    for r in requests:
        w = request_work(cfg, r["prompt_len"], r["n_tokens"])
        for k, v in w.items():
            out[k] += v
        out["prompt_tokens"] += r["prompt_len"]
        out["output_tokens"] += r["n_tokens"]
    out["decode_weight_bytes"] = decode_steps * decode_weight_bytes(
        cfg, capacity)
    out["decode_bytes"] = out["decode_weight_bytes"] + out["decode_kv_bytes"]
    out["flops"] = out["prefill_flops"] + out["decode_flops"]
    return out
