"""jit'd wrapper: (B, S, H, h) GQA tensors -> flash SWA attention."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.local_attention import kernel as K


@functools.partial(jax.jit, static_argnames=("window", "softcap", "bq", "bk",
                                             "interpret"))
def local_attention(
    q: jax.Array,   # (B, S, H, h)
    k: jax.Array,   # (B, S, Kh, h)
    v: jax.Array,
    *,
    window: int,
    softcap: float = 0.0,
    bq: int = 256,
    bk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, S, H, h = q.shape
    Kh = k.shape[2]
    bq = min(bq, S)
    bk = min(bk, bq)
    if S % bq:
        bq = S  # smoke-scale fallback: single q tile
        bk = min(bk, bq)
    if bq % bk:
        bk = bq
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, h)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Kh, S, h)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Kh, S, h)
    of = K.local_attention_kernel(
        qf, kf, vf, num_q_heads=H, num_kv_heads=Kh,
        window=min(window, S), softcap=softcap, bq=bq, bk=bk,
        interpret=interpret)
    return of.reshape(B, H, S, h).transpose(0, 2, 1, 3)
