"""Plain float32 reference of a dense decoder-only transformer with
grouped-query attention (qwen2.5-3b, h2o-danube-1.8b), and everything else
the harness knows of this family: the initialisation rule of its weights,
the work its served algorithm needs, and the bit planes of its K/V cache.

Written from the layer equations, in ``jax.numpy`` at float32 with every
matrix product at ``HIGHEST`` precision, one layer at a time. The
equations are those of the program's serving path, which departs from
the published models in two conventions (recorded in PERF.md): RMS norms
scale by ``1 + w``, and a tied embedding is multiplied by sqrt(d_model)
where it is looked up.

``served_logits`` replays one served request. The program keeps K and V
in an approximate store, so its decode steps attend over the K/V that the
store holds, not over exact values. The reference does the same: the
prompt is run as a prefill, attending over its own exact K/V, and each
decoded position attends over the stored K/V of the positions before it
and its own K/V. So the reference's logits at every position are what an
exact model step computes from the cache the program really had.

The window pattern is tiled over depth, as ``ModelConfig.layer_windows``
tiles it: layer ``l`` has the window of pattern position ``l % plen``.
The program keeps one slot class of cache per pattern position
(``slot<s>``), each stacked over the ``L / plen`` groups, so layer ``l``
is group ``l // plen`` of slot class ``l % plen``.

``precision="fp8"`` computes every matrix product from operands rounded to
float8 (e4m3, one scale per tensor): the control that a comparison must
fail.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
BF16_BYTES = 2

# -- initialisation ----------------------------------------------------------
#
# Every matrix is drawn with the standard deviation 1/sqrt(fan-in) of the
# dimensions it contracts, norm scales and biases are small and nonzero,
# and the embedding's deviation is ``EMBED / d_model``. With the fan-in
# counted right, attention stays soft and a rounding difference does not
# grow from layer to layer, so the logits of a bf16 program can be compared
# with a float32 reference at full depth. A tied head scores each token by
# its embedding's product with the final hidden state, in which the input
# token's own embedding survives; at this scale it stands about 1.4
# deviations above the rest at d_model 2048 (at unit scale, 14), so the top
# tokens are close and a less precise step picks others, as a trained
# model's would.

BIAS_STD = 0.5
NORM_STD = 0.1
#: the embedding's standard deviation, times 1/d_model
EMBED = 4.5


def init_std(path: str, shape, cfg: Mapping[str, Any]) -> float:
    """Standard deviation of one leaf, by its name in the program's tree
    and its shape."""
    D = cfg["d_model"]
    name = path.rsplit("/", 1)[-1]
    parent = path.rsplit("/", 2)[-2] if path.count("/") >= 1 else ""
    if name.startswith("ln_") or name == "final_norm":
        return NORM_STD
    if name in ("bq", "bk", "bv"):
        return BIAS_STD
    if name == "embedding":
        return EMBED / D
    if name == "unembedding":
        return 1.0 / math.sqrt(D)
    if name in ("wq", "wk", "wv", "wi_gate", "wi_up"):
        return 1.0 / math.sqrt(D)
    if name == "wo" and parent == "attn":      # (L, H, h, D)
        return 1.0 / math.sqrt(shape[1] * shape[2])
    if name == "wo" and parent == "mlp":       # (L, F, D)
        return 1.0 / math.sqrt(shape[1])
    raise KeyError(f"no initialisation rule for parameter {path!r}")


# -- work counts --------------------------------------------------------------
#
# The operations and bytes the served algorithm needs, from the
# configuration's shapes, not what the program happens to move: a decode
# step reads every weight once, reads the live K/V positions of each active
# request, and writes one new K/V column per active request. Attention
# counts only the keys a query may see (the causal prefix, cut to each
# layer's window), so a program that computes over the whole cache row
# still reads at most 100% of these rooflines.


def _window_layers(cfg: Mapping[str, Any]) -> List[Tuple[int, int]]:
    """(window, number of layers) of each position of the window pattern,
    tiled over depth (the program holds that it divides the depth); window
    0 means none."""
    pat, L = cfg["window_pattern"], cfg["num_layers"]
    return [(int(w), L // len(pat)) for w in pat]


def _seen(window: int, ctx: int) -> int:
    return min(ctx, window) if window > 0 else ctx


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


def _kv_bytes_per_layer(cfg: Mapping[str, Any]) -> int:
    return 2 * cfg["num_kv_heads"] * cfg["head_dim"] * BF16_BYTES


def kv_bytes_per_position(cfg: Mapping[str, Any]) -> int:
    """K and V of one token position over all layers, in bf16."""
    return cfg["num_layers"] * _kv_bytes_per_layer(cfg)


def cache_bytes(cfg: Mapping[str, Any], capacity: int, max_seq: int) -> int:
    """K and V of a pool of ``capacity`` slots: in each layer, a ring of
    ``max_seq`` positions cut to the layer's window."""
    return capacity * _kv_bytes_per_layer(cfg) * sum(
        n * _seen(w, max_seq) for w, n in _window_layers(cfg))


def _attn_flops(cfg: Mapping[str, Any], ctx: int) -> int:
    """QK^T and PV of one query at context ``ctx`` (itself included), all
    layers, each over the keys its window lets it see."""
    per_key = 4 * cfg["num_heads"] * cfg["head_dim"]
    return sum(n * per_key * _seen(w, ctx) for w, n in _window_layers(cfg))


def _logits_flops(cfg: Mapping[str, Any]) -> int:
    return 2 * cfg["d_model"] * cfg["vocab_size"]


def prefill_flops(cfg: Mapping[str, Any], prompt_len: int) -> int:
    """One prompt: every position through every layer, causal attention,
    and logits for the last position only."""
    mm = 2 * cfg["num_layers"] * layer_matmul_params(cfg) * prompt_len
    attn = sum(_attn_flops(cfg, t) for t in range(1, prompt_len + 1))
    return mm + attn + _logits_flops(cfg)


def decode_flops(cfg: Mapping[str, Any], ctx: int) -> int:
    """One decoded token whose query sees ``ctx`` positions (itself
    included)."""
    return (2 * cfg["num_layers"] * layer_matmul_params(cfg)
            + _attn_flops(cfg, ctx) + _logits_flops(cfg))


def decode_kv_bytes(cfg: Mapping[str, Any], ctx: int) -> int:
    """K/V bytes of the decode step at context ``ctx``: in each layer, the
    ``ctx - 1`` positions before it cut to the window, read, and its own
    column, written."""
    cols = sum(n * (_seen(w, ctx - 1) + 1) for w, n in _window_layers(cfg))
    return cols * _kv_bytes_per_layer(cfg)


def decode_weight_bytes(cfg: Mapping[str, Any], batch: int) -> int:
    """Weights one decode step reads: every layer, the final norm and the
    output head in full; of an untied embedding only the ``batch`` rows
    looked up."""
    D, V = cfg["d_model"], cfg["vocab_size"]
    total = param_count(cfg)
    if not cfg.get("tie_embeddings", True):
        total -= V * D - batch * D
    return total * BF16_BYTES


# -- the cache's bit planes ---------------------------------------------------
#
# The priority code (``references.extent``: 0 LOW .. 3 EXACT) of each bit
# of a bf16 word, least significant first, as the program's K/V policy
# drives them: sign and exponent exact, K's mantissa at MID in its upper
# half and LOW in its lower half, V's mantissa at LOW. The mantissa losses
# are read in V's upper mantissa, whose failures (at LOW) stand out from
# the program's rounding. K's upper mantissa fails at MID, too rarely to
# read there, and the lowest planes of both are set by the program's bf16
# rounding (a sum of two bf16 words is often a tie, which rounds to an even
# last bit).

PLANES = {"k": (0, 0, 0, 1, 1, 1, 1) + (3,) * 9,
          "v": (0,) * 7 + (3,) * 9}
LOSS_LEAF = "v"
LOSS_PLANES = (3, 4, 5, 6)


# -- the reference step -------------------------------------------------------


def _q8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, fp8: bool):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def _rope(x, pos, theta):
    """x (T, n, h); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _slot_classes(cfg: Mapping[str, Any]) -> List[str]:
    return [f"slot{s}" for s in range(len(cfg["window_pattern"]))]


def _layer_order(stored: Mapping[str, Any], cfg: Mapping[str, Any],
                 leaf: str, rows: int) -> jax.Array:
    """One leaf of every slot class, each (groups, capacity, K, h), as
    (L, rows, K, h) in layer order. Rows past a ring's capacity are zero
    and never read: a run refuses a ring that its longest request would
    wrap."""
    xs = [stored[c][leaf] for c in _slot_classes(cfg)]
    xs = [jnp.pad(x, ((0, 0), (0, rows - x.shape[1]), (0, 0), (0, 0)))
          for x in xs]
    x = jnp.stack(xs, 1)
    return x.reshape((-1,) + x.shape[2:])


def _slot_order(x: jax.Array, stored: Mapping[str, Any],
                cfg: Mapping[str, Any], leaf: str) -> Dict[str, jax.Array]:
    """``_layer_order`` undone: (L, rows, K, h) back to each slot class's
    (groups, capacity, K, h)."""
    classes = _slot_classes(cfg)
    x = x.reshape((-1, len(classes)) + x.shape[1:])
    return {c: x[:, s, :stored[c][leaf].shape[1]]
            for s, c in enumerate(classes)}


def served_logits(params: Any, cfg: Mapping[str, Any], tokens: jax.Array,
                  prompt_len: int, stored: Mapping[str, Any],
                  precision: str = "f32") -> Dict[str, Any]:
    """One request: ``tokens`` (T,) are the prompt and every served token
    but the last (any padding after them is ignored by the positions that
    matter); ``stored`` the store's cache at those positions, in the
    program's tree of slot classes, each ``{"k", "v"}`` of (groups,
    capacity, K, h). ``prompt_len`` may be traced. Returns ``logits``
    (T, V), row ``t`` predicting the token at ``t + 1``, and ``own``, the
    reference's own K/V in the tree and shapes of ``stored``."""
    fp8 = precision == "fp8"
    f32 = jnp.float32
    T = tokens.shape[0]
    P = prompt_len
    H, K, h = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    G = H // K
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    pat = cfg["window_pattern"]
    windows = jnp.asarray([pat[l % len(pat)] or T
                           for l in range(cfg["num_layers"])], jnp.int32)
    pos = jnp.arange(T)
    qi, kj = pos[:, None], pos[None, :]

    emb = params["embed"]["embedding"].astype(f32)
    x = emb[tokens]
    if cfg["tie_embeddings"]:
        x = x * math.sqrt(cfg["d_model"])

    def body(x, xs):
        lp, sk, sv, window = xs
        near = (qi - kj) < window
        # queries in the prompt see their own causal prefix; decoded
        # positions see the stored K/V before them and their own K/V
        m_own = jnp.where(qi < P, kj <= qi, kj == qi) & near
        m_st = (qi >= P) & (kj < qi) & near
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        sk, sv = sk.astype(f32), sv.astype(f32)
        a_in = _rms(x, lp["ln_attn"], eps)
        at = lp["attn"]
        q = _mm("td,dnh->tnh", a_in, at["wq"], fp8)
        k = _mm("td,dnh->tnh", a_in, at["wk"], fp8)
        v = _mm("td,dnh->tnh", a_in, at["wv"], fp8)
        if cfg["qkv_bias"]:
            q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        qg = q.reshape(T, K, G, h) / math.sqrt(h)
        s_own = _mm("tkgh,skh->kgts", qg, k, fp8)
        s_st = _mm("tkgh,skh->kgts", qg, sk, fp8)
        s = jnp.concatenate([jnp.where(m_own, s_own, NEG),
                             jnp.where(m_st, s_st, NEG)], -1)
        p = jax.nn.softmax(s, -1)
        o = (_mm("kgts,skh->tkgh", p[..., :T], v, fp8)
             + _mm("kgts,skh->tkgh", p[..., T:], sv, fp8))
        o = o.reshape(T, H, h)
        x = x + _mm("tnh,nhd->td", o, lp["attn"]["wo"], fp8)
        m_in = _rms(x, lp["ln_mlp"], eps)
        mlp = lp["mlp"]
        g = _mm("td,df->tf", m_in, mlp["wi_gate"], fp8)
        u = _mm("td,df->tf", m_in, mlp["wi_up"], fp8)
        x = x + _mm("tf,fd->td", jax.nn.silu(g) * u, mlp["wo"], fp8)
        return x, (k, v)

    x, (own_k, own_v) = jax.lax.scan(body, x, (
        params["layers"], _layer_order(stored, cfg, "k", T),
        _layer_order(stored, cfg, "v", T), windows))
    x = _rms(x, params["final_norm"].astype(f32), eps)
    head = (emb.T if cfg["tie_embeddings"]
            else params["embed"]["unembedding"].astype(f32))
    logits = _mm("td,dv->tv", x, head, fp8)
    own_k = _slot_order(own_k, stored, cfg, "k")
    own_v = _slot_order(own_v, stored, cfg, "v")
    own = {c: {"k": own_k[c], "v": own_v[c]} for c in _slot_classes(cfg)}
    return {"logits": logits, "own": own}
