"""Jit'd wrapper for the Mamba2 SSD chunk-scan kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.mamba2_ssd import kernel as K


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(xh, B_, C_, a_log, *, chunk: int = 128, interpret: bool = False):
    return K.ssd_chunk_scan(xh, B_, C_, a_log, chunk=chunk,
                            interpret=interpret)
