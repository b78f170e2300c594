"""RWKV6 WKV Pallas TPU kernel.

The WKV recurrence has *per-channel* data-dependent decay (diag(w_t)), so
unlike Mamba2's scalar-decay SSD it does not close into plain GEMMs
without per-channel decay matrices.  The TPU-native choice: keep the
(P x P) state resident in VMEM scratch across a (batch, head, chunk)
grid and run the token recurrence on the VPU inside the chunk — the
state never round-trips HBM (the whole point of the kernel), and chunk
blocks stream r/k/v/w tiles HBM->VMEM.

This mirrors how the official CUDA kernel works (sequential inner loop,
state in shared memory), adapted to Pallas refs + grid carry.  The
wrapper lays tokens out head-major, (B, H, S, P), so every token block
ends in (chunk, P); each token is read as a (1, P) row, and the rows
needed as columns are turned by a masked lane reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                y_ref, sT_ref, state_ref, *, chunk, n_chunks):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[...].astype(jnp.float32)

    u = u_ref[...].astype(jnp.float32)               # (P, 1)
    P = u.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (P, P), 1))

    def column(row):
        # (1, P) -> (P, 1) by a masked lane reduction (no transpose)
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def step(t, _):
        tok = pl.ds(t, 1)
        rt = column(r_ref[tok, :].astype(jnp.float32))   # (P, 1)
        kt = column(k_ref[tok, :].astype(jnp.float32))   # (P, 1)
        wt = column(w_ref[tok, :].astype(jnp.float32))   # (P, 1)
        vt = v_ref[tok, :].astype(jnp.float32)           # (1, P)
        s = state_ref[...]                           # (P, P)
        kv = kt * vt                                 # (P, P)
        y = jnp.sum((s + u * kv) * rt, axis=0, keepdims=True)
        y_ref[tok, :] = y.astype(y_ref.dtype)
        state_ref[...] = wt * s + kv
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(ci == n_chunks - 1)
    def _emit():
        sT_ref[...] = state_ref[...]


def wkv(r, k, v, w, u, state, *, chunk=64, interpret=False):
    """r,k,v,w: (B, S, H, P); u: (H, P); state: (B, H, P, P).
    Returns (y (B, S, H, P) fp32, final state (B, H, P, P) fp32)."""
    B, S, H, P = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nC = S // chunk

    def heads_major(x):
        # (B, S, H, P) -> (B, H, S, P): blocks end in (chunk, P)
        return jnp.moveaxis(x, 2, 1).astype(jnp.float32)

    tok_spec = pl.BlockSpec((None, None, chunk, P),
                            lambda b, h, c: (b, h, c, 0))
    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=nC)
    y, sT = pl.pallas_call(
        kernel,
        grid=(B, H, nC),
        in_specs=[
            tok_spec, tok_spec, tok_spec, tok_spec,
            pl.BlockSpec((None, P, 1), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((None, None, P, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            tok_spec,
            pl.BlockSpec((None, None, P, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, P), jnp.float32)],
        interpret=interpret,
    )(heads_major(r), heads_major(k), heads_major(v), heads_major(w),
      u[..., None], state)
    return jnp.moveaxis(y, 1, 2), sT
