"""Jit'd wrapper for the grouped-matmul kernel (static tile map)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.moe_gmm import kernel as K

pad_groups = K.pad_groups


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def gmm(x, w, tile_gid, *, block_m: int = 128, block_n: int = 128,
        interpret: bool = False):
    return K.gmm(x, w, tile_gid, block_m=block_m, block_n=block_n,
                 interpret=interpret)
