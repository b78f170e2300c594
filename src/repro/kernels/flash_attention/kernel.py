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

The decode kernel is the served decode step's attention on a TPU
(``models.layers.attention``): it reads the stacked (L, B, T, Hkv, D)
cache where it lies and fetches only each slot's live rows.
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
# Decode kernel: one query token per slot, read in place from the stacked
# (L, B, T, Hkv, D) cache.  The grid walks only the live tiles, slot by
# slot: its length is the number of live tiles, known on the device.
# --------------------------------------------------------------------- #
def _decode_kernel(layer_ref, len_ref, slot_ref, tile_ref,
                   q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   block_k, rep):
    i = pl.program_id(0)
    tile = tile_ref[i]
    rows = len_ref[slot_ref[i]] - tile * block_k   # live rows from here
    kv_heads, D = k_ref.shape[-2:]
    width = block_k * kv_heads

    @pl.when(tile == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(masked):
        # All K/V heads of the tile at once, as (row, head) pairs: every
        # query head scores every pair and keeps its own head's, so each
        # row is read once, with no per-head gather.  bf16 operands into
        # the MXU, f32 accumulation and softmax.
        q = q_ref[...]                                    # (H, D)
        k = k_ref[...].reshape(width, D)
        v = v_ref[...].reshape(width, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (H, width)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) % kv_heads
        keep = (row >= head * rep) & (row < head * rep + rep)
        if masked:      # the slot's last tile: pairs past its length
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep &= col < rows * kv_heads
            # a row never written may hold anything, NaN included
            live = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) \
                < rows * kv_heads
            v = jnp.where(live, v, jnp.zeros_like(v))
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[...]                               # (H, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (H, D)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_cur

    @pl.when(rows >= block_k)
    def _full():
        attend(masked=False)

    @pl.when(jnp.logical_and(rows > 0, rows < block_k))
    def _last():
        attend(masked=True)

    @pl.when(rows <= block_k)       # the slot's last tile
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_decode(q, k, v, lengths, layer=0, *, sm_scale=None,
                           block_k=256, interpret=False):
    """q: (B, H, D); k/v: the stacked cache (L, B, T, Hkv, D); lengths:
    (B,) live rows per slot, 0 for an idle slot; layer: the cache layer
    read -> (B, H, D) in the value dtype, zeros for an idle slot.

    The grid has one step per live ``block_k`` tile, the slots' tiles in
    order (a scalar-prefetched schedule), so only live tiles are fetched
    and no step is spent on a dead one.  Query head h reads K/V head
    h // rep (the padded layout of ``layers.head_slots``)."""
    B, H, D = q.shape
    T, Hkv = k.shape[2], k.shape[3]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    block_k = math.gcd(block_k, T)

    lengths = jnp.clip(lengths.astype(jnp.int32), 0, T)
    tiles = pl.cdiv(lengths, block_k)
    ends = jnp.cumsum(tiles)
    steps = jnp.arange(B * (T // block_k), dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, steps, side="right"),
                       B - 1).astype(jnp.int32)
    tile = steps - (ends - tiles)[slot]
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def kv_index(i, layer_ref, len_ref, slot_ref, tile_ref):
        return (layer_ref[0], slot_ref[i], tile_ref[i], 0, 0)

    def q_index(i, layer_ref, len_ref, slot_ref, tile_ref):
        return (slot_ref[i], 0, 0)

    kv_spec = pl.BlockSpec((None, None, block_k, Hkv, D), kv_index)
    q_spec = pl.BlockSpec((None, H, D), q_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        # no live tile at all: one step that reads tile 0 and attends
        # to nothing
        grid=(jnp.maximum(ends[-1], 1),),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               rep=H // Hkv)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer, lengths, slot, tile, (q * scale).astype(q.dtype), k, v)
    # an idle slot's output block is never visited
    return jnp.where((lengths > 0)[:, None, None], out, 0)
