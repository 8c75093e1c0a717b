"""Pallas-TPU flash attention for causal sliding-window (SWA) layers.

Why a kernel: half of gemma2's layers (and all of h2o-danube's /
recurrentgemma's attention) are windowed — only a W-deep band of the score
matrix is live. The jnp path (models/attention.py) slices the key range per
q-chunk but still materializes (bq x W+bq) logits through HBM at long S.
This kernel keeps the whole online-softmax state in VMEM scratch and
streams K/V tiles, touching HBM O(S·h) instead of O(S·(W+bq)).

Mapping (TPU-idiomatic, not a CUDA port):
  grid = (B*H, nq, nk) — the last axis is the sequential K-tile walk, so
  scratch (m, l, acc) persists across it (TPU grids execute minor-most
  sequentially; interpret mode preserves the same semantics).
  For q-tile qi, K tiles cover positions [qi*bq - W_eff, qi*bq + bq):
  block index start_true may be negative at the left edge — the data index
  is clamped to 0 and a position-validity mask kills phantom contributions
  (tiles are aligned so a tile is either fully valid or fully phantom).
  GQA: the kv row for flat head index bh = b*H + head is
  b*K + head // (H//K), computed in the BlockSpec index_map — no K/V
  expansion through HBM.

Supports gemma2's attention logit softcap (tanh) inside the tile loop.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bq: int, bk: int, w_eff: int, window: int, nk: int,
            scale: float, softcap: float):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # true (unclamped) start position of this K tile
    start_true = qi * bq - w_eff + ki * bk
    pos_q = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    pos_k = start_true + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    d = pos_q - pos_k
    mask = (pos_k >= 0) & (d >= 0) & (d < window)

    q = q_ref[0].astype(jnp.float32)          # (bq, h)
    k = k_ref[0].astype(jnp.float32)          # (bk, h)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                        # (bq,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])            # (bq, bk)
    p = jnp.where(mask, p, 0.0)

    v = v_ref[0].astype(jnp.float32)           # (bk, h)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    m_ref[...] = m_cur

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def local_attention_kernel(
    q: jax.Array,   # (BH, S, h) — heads flattened into the batch dim
    k: jax.Array,   # (BK, S, h)
    v: jax.Array,
    *,
    num_q_heads: int,
    num_kv_heads: int,
    window: int,
    softcap: float = 0.0,
    bq: int = 256,
    bk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    BH, S, h = q.shape
    G = num_q_heads // num_kv_heads
    assert S % bq == 0 and bq % bk == 0, (S, bq, bk)
    w_eff = int(np.ceil(window / bk)) * bk     # tile-aligned window reach
    nq = S // bq
    nk = (w_eff + bq) // bk
    scale = h ** -0.5

    def q_index(bh, qi, ki):
        return (bh, qi, 0)

    def kv_index(bh, qi, ki):
        b = bh // num_q_heads
        head = bh % num_q_heads
        row = b * num_kv_heads + head // G
        start_blk = (qi * bq - w_eff) // bk + ki
        return (row, jnp.maximum(start_blk, 0), 0)

    grid = (BH, nq, nk)
    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, w_eff=w_eff, window=window,
                          nk=nk, scale=scale, softcap=softcap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, h), q_index),
            pl.BlockSpec((1, bk, h), kv_index),
            pl.BlockSpec((1, bk, h), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, h), q_index),
        out_shape=jax.ShapeDtypeStruct((BH, S, h), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # m: running max
            pltpu.VMEM((bq,), jnp.float32),      # l: running denom
            pltpu.VMEM((bq, h), jnp.float32),    # acc: running numerator
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
