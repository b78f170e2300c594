"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis.

All kernels run in interpret mode on CPU (the TPU-target BlockSpecs are
exercised; Mosaic compilation happens on real TPUs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# The suite must collect (and the non-property tests must run) without
# hypothesis installed.  A bare ``pytest.importorskip("hypothesis")``
# would skip this whole module — including the parametrized shape sweeps
# — so absent hypothesis we fall back to a deterministic example sweep
# instead (see tests/_hypothesis_stub.py; CI installs the real thing via
# requirements-dev.txt).
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_stub import given, settings, strategies as st

from repro.kernels.flash_attention.kernel import (
    flash_attention_decode, flash_attention_prefill)
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba2_ssd.kernel import ssd_chunk_scan
from repro.kernels.mamba2_ssd.ref import ssd_ref
from repro.kernels.moe_gmm.kernel import gmm, pad_groups
from repro.kernels.moe_gmm.ref import gmm_ref
from repro.kernels.rwkv6.kernel import wkv
from repro.kernels.rwkv6.ref import wkv_ref
from repro.models.layers import _sdpa_chunked

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ===================================================================== #
# Flash attention
# ===================================================================== #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,S,D,bq,bk", [
    (1, 4, 4, 128, 64, 64, 64),      # MHA
    (2, 8, 2, 256, 64, 128, 64),     # GQA
    (1, 4, 1, 128, 128, 32, 32),     # MQA, head_dim 128
    (2, 2, 2, 64, 32, 64, 64),       # single q block
])
def test_flash_prefill_shapes(dtype, B, H, Hkv, S, D, bq, bk):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    out = flash_attention_prefill(q, k, v, causal=True, block_q=bq,
                                  block_k=bk, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("window", [32, 96, 200])
def test_flash_prefill_sliding_window(window):
    ks = jax.random.split(KEY, 3)
    B, H, S, D = 1, 2, 256, 64
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    out = flash_attention_prefill(q, k, v, causal=True, window=window,
                                  block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_prefill_noncausal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32))
    k = jax.random.normal(ks[1], (1, 2, 128, 32))
    v = jax.random.normal(ks[2], (1, 2, 128, 32))
    out = flash_attention_prefill(q, k, v, causal=False, block_q=64,
                                  block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _decode_oracle(q, k, v, lengths, layer):
    """The jnp path the kernel replaces on the stacked cache; an idle
    slot (length 0) returns zeros."""
    out = _sdpa_chunked(q[:, None], k[layer], v[layer], causal=True,
                        q_offset=lengths - 1, window=None, chunk=512)[:, 0]
    return jnp.where((lengths > 0)[:, None, None], out, 0)


def _unread_as_nan(x, lengths, layer):
    """x (L, B, T, Hkv, D) with NaN in every row the kernel must not
    read: other layers' rows and rows at or past each slot's length."""
    L_, _, T = x.shape[:3]
    live = jnp.arange(T)[None, :] < lengths[:, None]             # (B, T)
    keep = (jnp.arange(L_) == layer)[:, None, None] & live[None]
    return jnp.where(keep[..., None, None], x, jnp.nan)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_varlen(dtype):
    ks = jax.random.split(KEY, 3)
    L_, B, H, Hkv, T, D = 2, 4, 8, 2, 256, 64
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (L_, B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (L_, B, T, Hkv, D), dtype)
    lengths = jnp.array([1, 77, 128, 256], jnp.int32)
    out = flash_attention_decode(q, k, v, lengths, 1, block_k=64,
                                 interpret=True)
    assert out.dtype == dtype
    ref = _decode_oracle(q, k, v, lengths, 1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("rep", [2, 5])
def test_flash_decode_reads_only_live_rows(rep, layer):
    """Lengths 0, 1, block_k - 1, block_k, block_k + 1 and the whole
    capacity in one batch, on the first and last layer of a stacked
    cache: NaN in every row the kernel must not read leaves the output
    equal to the jnp path's on the clean cache, and an idle slot reads
    zeros."""
    L_, Hkv, T, D, bk = 3, 2, 256, 128, 64
    lengths = jnp.array([0, 1, bk - 1, bk, bk + 1, T, 0, 150], jnp.int32)
    B = lengths.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(rep), 3)
    q = jax.random.normal(ks[0], (B, Hkv * rep, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (L_, B, T, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (L_, B, T, Hkv, D), jnp.bfloat16)
    out = flash_attention_decode(
        q, _unread_as_nan(k, lengths, layer),
        _unread_as_nan(v, lengths, layer), lengths, layer, block_k=bk,
        interpret=True)
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    assert not out[np.asarray(lengths) == 0].any()
    np.testing.assert_allclose(
        out, np.asarray(_decode_oracle(q, k, v, lengths, layer), np.float32),
        **_tol(jnp.bfloat16))


@settings(max_examples=10, deadline=None)
@given(
    s_blocks=st.integers(1, 4), d=st.sampled_from([32, 64]),
    h=st.sampled_from([1, 2, 4]), seed=st.integers(0, 2**16),
    causal=st.booleans())
def test_property_flash_matches_ref(s_blocks, d, h, seed, causal):
    S = 64 * s_blocks
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, h, S, d))
    k = jax.random.normal(ks[1], (1, h, S, d))
    v = jax.random.normal(ks[2], (1, h, S, d))
    out = flash_attention_prefill(q, k, v, causal=causal, block_q=64,
                                  block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


# ===================================================================== #
# Mamba2 SSD
# ===================================================================== #
@pytest.mark.parametrize("S,chunk", [(32, 8), (64, 16), (128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunked_vs_sequential(S, chunk, dtype):
    ks = jax.random.split(KEY, 4)
    B, H, P, N = 2, 3, 16, 8
    xh = (jax.random.normal(ks[0], (B, S, H, P)) * 0.5).astype(dtype)
    B_ = (jax.random.normal(ks[1], (B, S, N)) * 0.5).astype(dtype)
    C_ = (jax.random.normal(ks[2], (B, S, N)) * 0.5).astype(dtype)
    a_log = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    y, hT = ssd_chunk_scan(xh, B_, C_, a_log, chunk=chunk,
                           interpret=True)
    yr, hr = ssd_ref(xh, B_, C_, a_log)
    tol = dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 \
        else dict(atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), **tol)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hr), **tol)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**16), chunks=st.integers(1, 4),
       h=st.integers(1, 4))
def test_property_ssd_state_carry(seed, chunks, h):
    """Chunked state must equal the sequential recurrence exactly."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    B, P, N = 1, 8, 4
    S = 16 * chunks
    xh = jax.random.normal(ks[0], (B, S, h, P)) * 0.3
    B_ = jax.random.normal(ks[1], (B, S, N)) * 0.3
    C_ = jax.random.normal(ks[2], (B, S, N)) * 0.3
    a_log = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, h)))
    y, hT = ssd_chunk_scan(xh, B_, C_, a_log, chunk=16, interpret=True)
    yr, hr = ssd_ref(xh, B_, C_, a_log)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hr),
                               atol=1e-4, rtol=1e-4)


# ===================================================================== #
# RWKV6 WKV
# ===================================================================== #
@pytest.mark.parametrize("S,chunk", [(16, 4), (64, 16), (32, 32)])
def test_wkv_chunked_vs_sequential(S, chunk):
    ks = jax.random.split(KEY, 6)
    B, H, P = 2, 2, 8
    r = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, P)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, P)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, P)))
    u = jax.random.normal(ks[4], (H, P)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, P, P)) * 0.1
    y, sT = wkv(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    yr, sr = wkv_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sr),
                               atol=1e-5, rtol=1e-5)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_property_wkv_decay_bounds(seed):
    """With decay w == 1 and u == 0, the state accumulates sum(k v^T)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    B, S, H, P = 1, 8, 1, 4
    r = jax.random.normal(ks[0], (B, S, H, P))
    k = jax.random.normal(ks[1], (B, S, H, P))
    v = jax.random.normal(ks[2], (B, S, H, P))
    w = jnp.ones((B, S, H, P))
    u = jnp.zeros((H, P))
    s0 = jnp.zeros((B, H, P, P))
    _, sT = wkv(r, k, v, w, u, s0, chunk=8, interpret=True)
    expect = jnp.einsum("bshp,bshq->bhpq", k, v)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


# ===================================================================== #
# MoE grouped matmul
# ===================================================================== #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sizes", [
    [13, 0, 25, 12], [8, 8, 8, 8], [0, 0, 50, 0], [1, 2, 3, 4]])
def test_gmm_group_sweep(sizes, dtype):
    sizes = np.array(sizes)
    T, d, E, f, bm = int(sizes.sum()), 32, 4, 64, 8
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (T, d), dtype)
    w = (jax.random.normal(ks[1], (E, d, f)) * 0.1).astype(dtype)
    xp, tile_gid, scatter = pad_groups(x, sizes, bm)
    out = gmm(xp, w, tile_gid, block_m=bm, block_n=32,
              interpret=True)[scatter]
    ref = gmm_ref(x, w, jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_gmm_matches_ragged_dot():
    """The kernel must agree with jax.lax.ragged_dot (the model path)."""
    sizes = np.array([10, 22, 0, 16])
    T, d, E, f = int(sizes.sum()), 16, 4, 32
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (T, d))
    w = jax.random.normal(ks[1], (E, d, f)) * 0.1
    ragged = jax.lax.ragged_dot(x, w, jnp.asarray(sizes, jnp.int32))
    xp, tile_gid, scatter = pad_groups(x, sizes, 8)
    out = gmm(xp, w, tile_gid, block_m=8, block_n=16,
              interpret=True)[scatter]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ragged),
                               atol=1e-5, rtol=1e-5)
