"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation of the SSD algorithm: the sequence is processed in chunks
of Q tokens; within a chunk the recurrence closes into three MXU matmuls
(CB^T Gram matrix, masked-decay weighting, PV product) — turning a
latency-bound scan into systolic-friendly GEMMs — while the (N, P)
running state of one head is carried across the chunk grid dimension in
VMEM scratch.  Grid: (batch, head, n_chunks), chunks innermost
(sequential on TPU, which legalizes the scratch carry).

The wrapper lays tokens out head-major, (B, H, S, P), so every block's
last two dimensions are (chunk, P).  The in-chunk cumulative decay is a
masked reduction over iota-built triangles (the TPU lowering has no
cumsum): the log decays arrive both as a column (chunk, 1) and as a row
(1, chunk), giving cum_t as a column and cum_s as a row without a
transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xh_ref, b_ref, c_ref, acol_ref, arow_ref, y_ref,
                hout_ref, state_ref, *, chunk, n_chunks):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    Q = chunk
    bmat = b_ref[...].astype(jnp.float32)            # (Q, N)
    cmat = c_ref[...].astype(jnp.float32)            # (Q, N)
    xh = xh_ref[...].astype(jnp.float32)             # (Q, P)
    a_col = acol_ref[...]                            # (Q, 1)
    a_row = arow_ref[...]                            # (1, Q)
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tril = row >= col
    # cum_t = sum_{s <= t} a_s, as a column and as a row
    cum_col = jnp.sum(jnp.where(tril, a_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(row <= col, a_col, 0.0), axis=0,
                      keepdims=True)
    total = cum_col[Q - 1:, :]                       # (1, 1)

    cb = jax.lax.dot_general(                        # (Q, Q) Gram
        cmat, bmat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    # L[t, s] = exp(cum_t - cum_s) for s <= t
    L = jnp.where(tril, jnp.exp(cum_col - cum_row), 0.0)
    y_intra = jax.lax.dot_general(
        cb * L, xh, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (Q, P)
    # incoming-state contribution: y_state[t] = (C_t * exp(cum_t)) h
    h_in = state_ref[...]                            # (N, P)
    y_state = jax.lax.dot_general(
        cmat * jnp.exp(cum_col), h_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (Q, P)
    y_ref[...] = (y_intra + y_state).astype(y_ref.dtype)
    # state update: h' = exp(cum_Q) h + sum_s exp(cum_Q - cum_s) B_s xh_s
    b_dec = bmat * jnp.exp(total - cum_col)          # (Q, N)
    st = jax.lax.dot_general(
        b_dec, xh, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (N, P)
    state_ref[...] = jnp.exp(total) * h_in + st

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        hout_ref[...] = state_ref[...]


def ssd_chunk_scan(xh, B_, C_, a_log, *, chunk=128, interpret=False):
    """xh: (B, S, H, P); B_/C_: (B, S, N); a_log: (B, S, H).
    Returns (y (B, S, H, P) fp32, final_state (B, H, N, P) fp32)."""
    Bb, S, H, P = xh.shape
    N = B_.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nC = S // chunk

    xt = jnp.moveaxis(xh, 2, 1)                      # (B, H, S, P)
    a = jnp.moveaxis(a_log, 2, 1).astype(jnp.float32)  # (B, H, S)
    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nC)
    y, hT = pl.pallas_call(
        kernel,
        grid=(Bb, H, nC),
        in_specs=[
            pl.BlockSpec((None, None, chunk, P),
                         lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((None, None, chunk, 1),
                         lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, 1, chunk),
                         lambda b, h, c: (b, h, 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, P),
                         lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((Bb, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xt, B_, C_, a[..., None], a[:, :, None, :])
    return jnp.moveaxis(y, 1, 2), hT
