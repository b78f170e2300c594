"""Jit'd public wrappers for the flash attention kernels.

The compiled Mosaic kernels run by default; ``interpret=True`` runs the
Pallas interpreter instead, and only a caller that asks for it gets it
(the CPU tests do).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.flash_attention import kernel as K


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "sm_scale", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """Prefill/training attention.  q: (B, H, S, D); k/v: (B, Hkv, S, D)."""
    return K.flash_attention_prefill(
        q, k, v, causal=causal, window=window, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_k", "interpret"))
def flash_decode(q, k, v, lengths, layer=0, *,
                 sm_scale: Optional[float] = None, block_k: int = 256,
                 interpret: bool = False):
    """Decode attention over live rows.  q: (B, H, D); k/v: the stacked
    cache (L, B, T, Hkv, D); lengths (B,); layer: the layer read."""
    return K.flash_attention_decode(
        q, k, v, lengths, layer, sm_scale=sm_scale, block_k=block_k,
        interpret=interpret)
