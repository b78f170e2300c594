"""Flash attention Pallas TPU kernels (prefill + decode).

TPU-native adaptation of the FlashAttention tiling the paper profiles as
its compute-bound exemplar (§II-C): Q/K/V tiles stream HBM->VMEM under
explicit BlockSpecs, the online-softmax accumulators (m, l, acc) live in
VMEM scratch across the KV grid dimension, and tile shapes are MXU-
aligned (block_q x block_k x head_dim multiples of 128 where dtypes
allow).  GQA is expressed in the K/V index_map (query head h reads KV
head h // rep) — no KV replication in HBM.

Causal + sliding-window masking skips fully-masked KV blocks via
``pl.when`` so SWA runs O(S * window).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# --------------------------------------------------------------------- #
# Prefill kernel: grid (BH, nQ, nK), KV innermost (sequential on TPU).
# --------------------------------------------------------------------- #
def _prefill_kernel(q_ref, k_ref, v_ref, o_ref,
                    m_ref, l_ref, acc_ref, *,
                    sm_scale, causal, window, block_q, block_k,
                    kv_len, n_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # Skip KV blocks with no unmasked element.
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(
            run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[...].astype(jnp.float32) * sm_scale    # (bq, d)
        k = k_ref[...].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                              # (bq,)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        v = v_ref[...].astype(jnp.float32)               # (bk, d)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_cur

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_prefill(q, k, v, *, causal=True, window=None,
                            sm_scale=None, block_q=128, block_k=128,
                            interpret=False):
    """q: (B, H, Sq, D); k/v: (B, Hkv, Skv, D) -> (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    rep = H // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    n_q, n_k = Sq // block_q, Skv // block_k

    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * Hkv, Skv, D)
    vf = v.reshape(B * Hkv, Skv, D)

    def kv_index(bh, qi, ki):
        b = bh // H
        h = bh % H
        return (b * Hkv + h // rep, ki, 0)

    kernel = functools.partial(
        _prefill_kernel, sm_scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_len=Skv, n_k=n_k)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((None, block_q, D),
                         lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, block_k, D), kv_index),
            pl.BlockSpec((None, block_k, D), kv_index),
        ],
        out_specs=pl.BlockSpec((None, block_q, D),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D)


# --------------------------------------------------------------------- #
# Decode kernel: one query token, grid (B, H, nK); per-request lengths.
# --------------------------------------------------------------------- #
def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   sm_scale, block_k, n_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0)]
    k_start = ki * block_k

    @pl.when(k_start < length)
    def _body():
        q = q_ref[...].astype(jnp.float32) * sm_scale    # (1, d)
        k = k_ref[...].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (1, bk)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        mask = kpos < length
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        v = v_ref[...].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_cur

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_decode(q, k, v, lengths, *, sm_scale=None,
                           block_k=128, interpret=False):
    """q: (B, H, D); k/v: (B, Hkv, T, D); lengths: (B,) -> (B, H, D)."""
    B, H, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    rep = H // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    block_k = min(block_k, T)
    assert T % block_k == 0
    n_k = T // block_k

    qf = q.reshape(B, H, 1, D)

    kernel = functools.partial(_decode_kernel, sm_scale=scale,
                               block_k=block_k, n_k=n_k)
    # lengths ride in SMEM as a scalar-prefetch operand: the kernel reads
    # its row's length without a (1,)-shaped VMEM block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, n_k),
        in_specs=[
            pl.BlockSpec((None, None, 1, D),
                         lambda b, h, ki, lens: (b, h, 0, 0)),
            pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, ki, lens: (b, h // rep, ki, 0)),
            pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, ki, lens: (b, h // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, 1, D),
                               lambda b, h, ki, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qf, k, v)
    return out.reshape(B, H, D)
