"""Pure-jnp oracle for flash attention prefill.  The decode kernel's
oracle is the path it replaces, ``models.layers._sdpa_chunked``."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True,
                  window: Optional[int] = None,
                  sm_scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B, H, Sq, D); k/v: (B, Hkv, Skv, D).  GQA via head grouping.
    Returns (B, H, Sq, D) in q.dtype, accumulating in fp32."""
    B, H, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    rep = H // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, Hkv, rep, Sq, D).astype(jnp.float32) * scale
    logits = jnp.einsum("bhrqd,bhkd->bhrqk", qg,
                        k.astype(jnp.float32))
    qpos = jnp.arange(Sq) + (Skv - Sq if causal else 0)
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhrqk,bhkd->bhrqd", w, v.astype(jnp.float32))
    return o.reshape(B, H, Sq, D).astype(q.dtype)

