"""Plain float32 reference of a dense decoder-only transformer with
grouped-query attention (qwen2.5-3b, h2o-danube-1.8b).

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

``precision="fp8"`` computes every matrix product from operands rounded to
float8 (e4m3, one scale per tensor): the control that a comparison must
fail.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


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


def served_logits(params: Any, cfg: Mapping[str, Any], tokens: jax.Array,
                  prompt_len: int, stored_k: jax.Array, stored_v: jax.Array,
                  precision: str = "f32") -> Dict[str, jax.Array]:
    """One request: ``tokens`` (T,) are the prompt and every served token
    but the last (any padding after them is ignored by the positions that
    matter); ``stored_k``/``stored_v`` (L, T, K, h) the store's K/V at
    those positions. ``prompt_len`` may be traced. Returns ``logits``
    (T, V), row ``t`` predicting the token at ``t + 1``, and the
    reference's own ``k``/``v`` (L, T, K, h)."""
    fp8 = precision == "fp8"
    f32 = jnp.float32
    T = tokens.shape[0]
    P = prompt_len
    H, K, h = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    G = H // K
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    window = cfg["window_pattern"][0] or T
    pos = jnp.arange(T)
    qi, kj = pos[:, None], pos[None, :]
    near = (qi - kj) < window
    # queries in the prompt see their own causal prefix; decoded positions
    # see the stored K/V before them and their own K/V
    m_own = jnp.where(qi < P, kj <= qi, kj == qi) & near
    m_st = (qi >= P) & (kj < qi) & near

    emb = params["embed"]["embedding"].astype(f32)
    x = emb[tokens]
    if cfg["tie_embeddings"]:
        x = x * math.sqrt(cfg["d_model"])

    def body(x, xs):
        lp, sk, sv = xs
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

    x, (own_k, own_v) = jax.lax.scan(body, x, (params["layers"], stored_k,
                                                stored_v))
    x = _rms(x, params["final_norm"].astype(f32), eps)
    head = (emb.T if cfg["tie_embeddings"]
            else params["embed"]["unembedding"].astype(f32))
    logits = _mm("td,dv->tv", x, head, fp8)
    return {"logits": logits, "k": own_k, "v": own_v}
