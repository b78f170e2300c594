"""Jit'd wrapper for the RWKV6 WKV kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.rwkv6 import kernel as K


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv(r, k, v, w, u, state, *, chunk: int = 64, interpret: bool = False):
    return K.wkv(r, k, v, w, u, state, chunk=chunk, interpret=interpret)
