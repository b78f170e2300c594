"""Neural net layers shared by every architecture family.

Functional style: ``init_*`` build parameter pytrees, ``apply``-style
functions are pure.  Attention uses *query chunking* with windowed KV
slicing so that 32k-token prefill and 500k-token SWA never materialize an
O(S^2) logits tensor — this is what makes the big dry-run cells fit in
HBM without depending on the Pallas kernels (which target real TPUs and
are validated separately in interpret mode).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.ops import flash_decode
from repro.models.config import ModelConfig

Params = Dict[str, Any]


# --------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------- #
def init_rmsnorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rms_norm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------- #
# RoPE (+ M-RoPE for qwen2-vl)
# --------------------------------------------------------------------- #
def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) int32."""
    freqs = rope_frequencies(x.shape[-1], theta)           # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions3: jnp.ndarray,
                theta: float, sections: Tuple[int, ...]) -> jnp.ndarray:
    """Multimodal RoPE: positions3 (3, B, S) = (t, h, w) position ids;
    frequency channels are split across the three axes (qwen2-vl)."""
    D = x.shape[-1]
    freqs = rope_frequencies(D, theta)                      # (D/2,)
    # build per-channel position by section
    sec = jnp.concatenate([
        jnp.full((s,), i, dtype=jnp.int32)
        for i, s in enumerate(sections)])                   # (D/2,)
    # positions3: (3, B, S) -> (B, S, D/2) selecting axis sec[c] per channel
    p3 = jnp.moveaxis(positions3, 0, -1)                    # (B, S, 3)
    chan_pos = jnp.take(p3, sec, axis=-1).astype(jnp.float32)  # (B,S,D/2)
    ang = chan_pos * freqs                                   # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# Attention (GQA / MQA / SWA / cross) with query chunking
# --------------------------------------------------------------------- #
def head_slots(cfg: ModelConfig, padded: int) -> np.ndarray:
    """Where each published query head sits among ``padded`` slots.
    Slot h reads K/V head h // (padded / K/V heads), so head j, of group
    j // (heads / K/V heads), takes the same place in its group's run
    of slots: each group's real heads first."""
    rep = cfg.num_heads // cfg.num_kv_heads
    j = np.arange(cfg.num_heads)
    return (j // rep) * (padded // cfg.num_kv_heads) + j % rep


def init_attention(cfg: ModelConfig, d_model: Optional[int] = None,
                   key=None) -> Params:
    d = d_model or cfg.d_model
    hd, H, Hkv = cfg.head_dim, cfg.padded_heads, cfg.num_kv_heads
    dt = cfg.jnp_dtype
    if key is None:
        key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    wq = jax.random.normal(k1, (d, H, hd)) * s
    wo = jax.random.normal(k4, (H, hd, d)) * s
    if H != cfg.num_heads:
        # Megatron-style head padding: zero q/o slices for TP
        # divisibility — output is exactly the unpadded model's.
        mask = np.zeros(H, bool)
        mask[head_slots(cfg, H)] = True
        wq = wq * mask[None, :, None]
        wo = wo * mask[:, None, None]
    p = {
        "wq": wq.astype(dt),
        "wk": (jax.random.normal(k2, (d, Hkv, hd)) * s).astype(dt),
        "wv": (jax.random.normal(k3, (d, Hkv, hd)) * s).astype(dt),
        "wo": wo.astype(dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype=dt)
        p["bk"] = jnp.zeros((Hkv, hd), dtype=dt)
        p["bv"] = jnp.zeros((Hkv, hd), dtype=dt)
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dt)
        p["k_norm"] = init_rmsnorm(hd, dt)
    return p


def _sdpa_chunked(q, k, v, *, causal: bool, q_offset,
                  window: Optional[int], chunk: int,
                  softcap: float = 0.0, kv_valid_len=None) -> jnp.ndarray:
    """Scaled dot-product attention, chunked over the query axis.

    q: (B, Sq, H, D);  k/v: (B, Skv, Hkv, D).  ``q_offset`` is the
    absolute position of q[0] relative to k[0] (decode offset).  With a
    sliding ``window`` only the last ``window + chunk`` keys are sliced
    per chunk, keeping FLOPs O(Sq * window).
    """
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    scale = 1.0 / math.sqrt(D)
    q = q * scale

    # per-batch query offset (continuous batching: each request sits at a
    # different absolute position)
    q_off = jnp.asarray(q_offset)
    if q_off.ndim == 0:
        q_off = jnp.broadcast_to(q_off, (B,))

    def attend(qc, kc, vc, qpos, kpos):
        # qc: (B, C, H, D); kc/vc: (B, Kc, Hkv, D); qpos: (B, C);
        # kpos: (Kc,) or (B, Kc)
        qg = qc.reshape(B, qc.shape[1], Hkv, rep, D)
        # bf16 x bf16 -> f32 on the MXU: accumulate in fp32 WITHOUT
        # materializing fp32 copies of Q/K in HBM (hillclimb §Perf).
        logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, kc,
                            preferred_element_type=jnp.float32)
        if softcap > 0.0:
            logits = jnp.tanh(logits / softcap) * softcap
        kp = kpos if kpos.ndim == 2 else kpos[None, :]       # (B|1, Kc)
        mask = jnp.ones((B, qc.shape[1], kc.shape[1]), dtype=bool)
        if causal:
            mask &= kp[:, None, :] <= qpos[:, :, None]
        if window is not None:
            mask &= kp[:, None, :] > qpos[:, :, None] - window
        mask &= kp[:, None, :] >= 0                 # padded window slots
        if kv_valid_len is not None:                # ring-buffer warmup
            vl = jnp.asarray(kv_valid_len)
            vl = vl[:, None, None] if vl.ndim else vl
            mask &= kp[:, None, :] < vl
        logits = jnp.where(mask[:, None, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        # PV product in the value dtype with fp32 accumulation (flash
        # kernels do exactly this); avoids an fp32 copy of V.
        o = jnp.einsum("bhrqk,bkhd->bqhrd", w.astype(v.dtype), vc,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, qc.shape[1], H, D).astype(v.dtype)

    if Sq <= chunk:
        qpos = q_off[:, None] + jnp.arange(Sq)[None, :]
        kpos = jnp.arange(Skv)
        return attend(q, k, v, qpos, kpos)

    assert Sq % chunk == 0, (Sq, chunk)
    n_chunks = Sq // chunk
    qs = q.reshape(B, n_chunks, chunk, H, D)

    if window is not None and Skv > window + chunk:
        kv_span = window + chunk

        def body(i):
            qc = qs[:, i]
            qpos = q_off[:, None] + i * chunk + jnp.arange(chunk)[None]
            start = i * chunk + chunk - kv_span     # may be negative
            start_c = jnp.clip(start, 0, Skv - kv_span)
            kc = jax.lax.dynamic_slice_in_dim(k, start_c, kv_span, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start_c, kv_span, axis=1)
            kpos = start_c + jnp.arange(kv_span)
            return attend(qc, kc, vc, qpos, kpos)
    else:
        kpos = jnp.arange(Skv)

        def body(i):
            qc = qs[:, i]
            qpos = q_off[:, None] + i * chunk + jnp.arange(chunk)[None]
            return attend(qc, k, v, qpos, kpos)

    out = jax.lax.map(body, jnp.arange(n_chunks))   # (n, B, C, H, D)
    return jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, D)


def attention(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
              positions: jnp.ndarray,
              kv_cache: Optional[Dict[str, jnp.ndarray]] = None,
              cache_pos: Optional[jnp.ndarray] = None,
              cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              causal: bool = True,
              q_chunk: int = 512,
              positions3: Optional[jnp.ndarray] = None,
              cache_layer=None,
              kv_lengths: Optional[jnp.ndarray] = None,
              ) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """GQA attention.

    Modes:
      * kv_cache=None, cross_kv=None: full self-attention (train/encoder).
      * kv_cache given + x.shape[1] == cache capacity write: prefill fill.
      * kv_cache given + multi-token x + scalar ``cache_pos``: CHUNKED
        prefill — this chunk's K/V are written at offset ``cache_pos``
        and queries attend over the whole cache (the already-filled
        prefix plus this chunk; unfilled higher slots are excluded by
        the causal mask, so the result equals whole-prompt prefill).
      * kv_cache given + single-token x: decode step, in-place cache
        update at ``cache_pos`` (ring-buffer position for SWA).  With
        ``cache_layer`` the cache is the whole stacked (L, B, T, Hkv, D)
        buffer: the token's K/V land at ``[cache_layer, b, cache_pos]``
        and the step attends over ``cache[cache_layer]``, so the layer
        loop can carry the buffer and update it in place.  With
        ``kv_lengths`` too, (B,) live rows per slot, a program lowered
        for a TPU reads only those rows, in place, through the Pallas
        decode kernel (``_live_rows_apply``).
      * cross_kv given: cross-attention over precomputed encoder K/V.
    Returns (output, updated kv_cache or None).
    """
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    if cross_kv is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if "bk" in p:
            k = k + p["bk"]
            v = v + p["bv"]
    else:
        k, v = cross_kv
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        if cross_kv is None:
            k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    if cross_kv is None:        # no RoPE on cross-attention
        if cfg.mrope and positions3 is not None:
            q = apply_mrope(q, positions3, cfg.rope_theta,
                            cfg.mrope_sections)
            k = apply_mrope(k, positions3, cfg.rope_theta,
                            cfg.mrope_sections)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    q_offset = 0
    window = cfg.sliding_window
    if kv_cache is not None and cross_kv is None:
        if S == 1:
            # decode: write this token's K/V at cache_pos, read whole cache
            new_cache = {
                "k": _write_token(kv_cache["k"], k, cache_pos, cache_layer),
                "v": _write_token(kv_cache["v"], v, cache_pos, cache_layer)}
            k, v = new_cache["k"], new_cache["v"]
            if kv_lengths is not None and _live_rows_apply(cfg,
                                                           cache_layer):
                def whole(q, k, v, q_offset, lengths):
                    return _sdpa_chunked(
                        q, k[cache_layer], v[cache_layer], causal=True,
                        q_offset=q_offset, window=None, chunk=q_chunk)

                def live_rows(q, k, v, q_offset, lengths):
                    return flash_decode(q[:, 0], k, v, lengths, cache_layer,
                                        block_k=DECODE_BLOCK_K)[:, None]

                out = jax.lax.platform_dependent(
                    q, k, v, positions[:, 0], kv_lengths, tpu=live_rows,
                    default=whole)
                y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
                return y, new_cache
            if cache_layer is not None:
                k, v = k[cache_layer], v[cache_layer]
            cap = k.shape[1]
            if window is not None and cap <= window:
                # Ring buffer: every written slot is within the window;
                # slot order is irrelevant (RoPE applied before caching).
                # Mask only unwritten slots during warmup (per batch).
                valid = jnp.minimum(positions[:, 0] + 1, cap)   # (B,)
                out = _sdpa_chunked(
                    q, k, v, causal=False, q_offset=0, window=None,
                    chunk=q_chunk, softcap=cfg.attn_logit_softcap,
                    kv_valid_len=valid)
                y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
                return y, new_cache
            q_offset = positions[:, 0]                          # (B,)
        elif cache_pos is not None:
            # chunked prefill: write this chunk's K/V at the scalar
            # offset and attend over the full cache.  Slots below the
            # offset hold earlier chunks; slots at or above the chunk
            # end are zero-filled but carry kpos > qpos, so the causal
            # mask excludes them — exactness needs no valid-length
            # bookkeeping.  (Ring-buffer SWA caches never take this
            # path: their slot layout wraps at the window.)
            assert window is None, \
                "chunked prefill is undefined for ring-buffer SWA caches"
            off = jnp.asarray(cache_pos, jnp.int32)
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(
                    kv_cache["k"], k.astype(kv_cache["k"].dtype), off,
                    axis=1),
                "v": jax.lax.dynamic_update_slice_in_dim(
                    kv_cache["v"], v.astype(kv_cache["v"].dtype), off,
                    axis=1),
            }
            k, v = new_cache["k"], new_cache["v"]
            q_offset = off
        else:
            # prefill: fill cache[0:S]
            new_cache = {
                "k": _fill(kv_cache["k"], k),
                "v": _fill(kv_cache["v"], v),
            }
    out = _sdpa_chunked(q, k, v, causal=causal, q_offset=q_offset,
                        window=window, chunk=q_chunk,
                        softcap=cfg.attn_logit_softcap)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


# K/V rows the decode kernel fetches per tile (PERF.md, section 6)
DECODE_BLOCK_K = 256


def _live_rows_apply(cfg: ModelConfig, cache_layer) -> bool:
    """Whether decode may read the stacked cache through the Pallas
    kernel: a layer of the stacked cache, no sliding window (ring
    buffer), no logit softcap, and one device (GSPMD cannot partition
    the kernel across a mesh)."""
    from repro.distributed.context import current_mesh
    mesh = current_mesh()
    return (cache_layer is not None and cfg.sliding_window is None
            and cfg.attn_logit_softcap == 0.0
            and (mesh is None or mesh.size == 1))


def _write_token(cache, val, pos, layer=None):
    """cache (B, T, H, D) <- val (B, 1, H, D) at per-batch positions, as
    one scatter of B rows; with ``layer`` the cache is (L, B, T, H, D)
    and the rows land in that layer.

    ``pos`` may be a python int, a scalar array, or a (B,) vector (each
    request in a continuous batch sits at its own position).  Positions
    clamp to the capacity, as ``dynamic_update_slice`` clamps them."""
    B = val.shape[0]
    rows = (jnp.arange(B), jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                                            (B,)))
    if layer is not None:
        rows = (layer,) + rows
    return cache.at[rows].set(val[:, 0].astype(cache.dtype), mode="clip")


def _fill(cache, val):
    cap, S = cache.shape[1], val.shape[1]
    if cap == S:
        return val.astype(cache.dtype)
    if S < cap:
        return jax.lax.dynamic_update_slice_in_dim(
            cache, val.astype(cache.dtype), 0, axis=1)
    # SWA ring buffer smaller than the prefill: scatter the last ``cap``
    # tokens at their ring slots (abs_pos % cap) so subsequent decode
    # writes at (pos % cap) line up.
    tail = val[:, S - cap:].astype(cache.dtype)
    slots = (jnp.arange(S - cap, S) % cap)
    return cache.at[:, slots].set(tail)


def make_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  layers: Optional[int] = None) -> Dict[str, Any]:
    """Zero-initialized stacked KV cache (L, B, T, Hkv, D).  SWA models
    allocate only the window (ring buffer)."""
    L = layers if layers is not None else cfg.num_layers
    T = max_len
    if cfg.sliding_window is not None:
        T = min(T, cfg.sliding_window)
    shape = (L, batch, T, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.jnp_dtype),
            "v": jnp.zeros(shape, cfg.jnp_dtype)}


def make_kv_block_pool(cfg: ModelConfig, pool_blocks: int,
                       block_tokens: int,
                       layers: Optional[int] = None) -> Dict[str, Any]:
    """Zero-initialized paged KV pool (L, P, bt, Hkv, D).

    The pool replaces the per-slot batch axis with a flat block axis: a
    session's KV lives in ``ceil(tokens / block_tokens)`` pool blocks
    named by its block table, so resident capacity is bounded by tokens
    actually held rather than by ``slots * max_len``.
    """
    L = layers if layers is not None else cfg.num_layers
    shape = (L, pool_blocks, block_tokens, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.jnp_dtype),
            "v": jnp.zeros(shape, cfg.jnp_dtype)}


# --------------------------------------------------------------------- #
# MLP: SwiGLU / GeGLU
# --------------------------------------------------------------------- #
def init_mlp(cfg: ModelConfig, d_model: Optional[int] = None,
             d_ff: Optional[int] = None, key=None) -> Params:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    dt = cfg.jnp_dtype
    if key is None:
        key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    s = 1.0 / math.sqrt(d)
    return {
        "w_gate": (jax.random.normal(k1, (d, f)) * s).astype(dt),
        "w_up": (jax.random.normal(k2, (d, f)) * s).astype(dt),
        "w_down": (jax.random.normal(k3, (f, d)) / math.sqrt(f)).astype(dt),
    }


def mlp(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    g = x @ p["w_gate"]
    u = x @ p["w_up"]
    act = jax.nn.gelu(g) if cfg.activation == "geglu" else jax.nn.silu(g)
    return (act * u) @ p["w_down"]


# --------------------------------------------------------------------- #
# Mixture of Experts: top-k router + sort-based ragged dispatch
# --------------------------------------------------------------------- #
def init_moe(cfg: ModelConfig, key=None) -> Params:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = cfg.jnp_dtype
    if key is None:
        key = jax.random.PRNGKey(2)
    k0, k1, k2, k3 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    return {
        "router": (jax.random.normal(k0, (d, E)) * s).astype(jnp.float32),
        "w_gate": (jax.random.normal(k1, (E, d, f)) * s).astype(dt),
        "w_up": (jax.random.normal(k2, (E, d, f)) * s).astype(dt),
        "w_down": (jax.random.normal(k3, (E, f, d)) / math.sqrt(f)).astype(dt),
    }


def moe(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Dropless MoE.  ``ragged``: sort tokens by expert and use
    jax.lax.ragged_dot (exact active FLOPs — 2·k·T·d·f per matmul).
    ``dense_einsum``: every expert on every token, masked combine —
    simple and GSPMD-friendly, used as sharded fallback.
    ``ep``: expert-local shard_map path for production meshes — tokens
    never leave their data shard, expert FFN width shards over the model
    axis, one psum after combine (see ``_moe_ep``)."""
    if cfg.moe_impl == "ep":
        return _moe_ep(p, x, cfg)
    B, S, d = x.shape
    T = B * S
    k = cfg.experts_per_token
    E = cfg.num_experts
    xt = x.reshape(T, d)
    logits = xt.astype(jnp.float32) @ p["router"]
    gate_vals, expert_ids = jax.lax.top_k(logits, k)          # (T, k)
    gates = jax.nn.softmax(gate_vals, axis=-1)                 # (T, k)

    if cfg.moe_impl == "dense_einsum":
        # combine weights (T, E): sum of gate over chosen slots
        combine = jnp.zeros((T, E), jnp.float32)
        onehot = jax.nn.one_hot(expert_ids, E, dtype=jnp.float32)  # (T,k,E)
        combine = (onehot * gates[..., None]).sum(axis=1)           # (T, E)
        g = jnp.einsum("td,edf->tef", xt, p["w_gate"])
        u = jnp.einsum("td,edf->tef", xt, p["w_up"])
        h = jax.nn.silu(g) * u
        y = jnp.einsum("tef,efd->ted", h, p["w_down"])
        out = jnp.einsum("ted,te->td", y, combine.astype(y.dtype))
        return out.reshape(B, S, d)

    # ragged (sort-based, dropless)
    flat_expert = expert_ids.reshape(-1)                       # (T*k,)
    sort_idx = jnp.argsort(flat_expert)                        # (T*k,)
    token_idx = sort_idx // k
    xs = xt[token_idx]                                         # (T*k, d)
    group_sizes = jnp.bincount(flat_expert, length=E).astype(jnp.int32)
    g = jax.lax.ragged_dot(xs, p["w_gate"], group_sizes)
    u = jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    h = (jax.nn.silu(g.astype(jnp.float32)) *
         u.astype(jnp.float32)).astype(xs.dtype)
    y = jax.lax.ragged_dot(h, p["w_down"], group_sizes)        # (T*k, d)
    # unsort and combine with gates
    inv = jnp.argsort(sort_idx)
    y = y[inv].reshape(T, k, d)
    out = (y.astype(jnp.float32) * gates[..., None]).sum(axis=1)
    return out.astype(x.dtype).reshape(B, S, d)


def _moe_ep(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Capacity-based expert-parallel MoE under shard_map.

    Why (hillclimb log, EXPERIMENTS.md §Perf): the sort-based ragged path
    does a GLOBAL argsort over all tokens, which GSPMD resolves by
    gathering every token to every chip and all-reducing the (T·k, d_ff)
    expert activations at fp32 — 180 GB/chip/layer on dbrx train_4k.
    Here tokens stay inside their (pod, data) shard:

      local top-k -> slot position by masked cumsum -> scatter into a
      fixed (E, C, d) dispatch buffer -> batched expert GEMMs with the
      FFN width sharded over ``model`` -> gather+gate combine -> one
      psum('model') of the (T_loc, d) output.

    Per-chip FLOPs are exactly the active-expert FLOPs / chips; the only
    collective is the same-sized all-reduce a dense TP MLP needs.
    Capacity C = ceil(T_loc*k/E * capacity_factor); overflow tokens are
    dropped (standard GShard semantics), with factor >= E/k the path is
    exactly dropless (tests compare it against the ragged oracle).
    """
    from repro.distributed.context import current_mesh
    mesh = current_mesh()
    assert mesh is not None, "moe_impl='ep' requires mesh_context(mesh)"
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    model_axis = "model" if "model" in mesh.shape else None

    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    cap_factor = getattr(cfg, "moe_capacity_factor", 1.25)
    # shard tokens over the largest data-axis prefix dividing the batch
    # (long-context decode has batch 1: tokens replicate across data)
    chosen = ()
    n_data = 1
    for a in data_axes:
        if B % (n_data * mesh.shape[a]) == 0:
            chosen += (a,)
            n_data *= mesh.shape[a]
    data_axes = chosen

    def local_fn(xl, router, wg, wu, wd):
        Bl, Sl, _ = xl.shape
        Tl = Bl * Sl
        xt = xl.reshape(Tl, d)
        logits = xt.astype(jnp.float32) @ router
        gate_vals, eid = jax.lax.top_k(logits, k)            # (Tl, k)
        gates = jax.nn.softmax(gate_vals, axis=-1)
        flat_e = eid.reshape(-1)                             # (Tl*k,)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - onehot)
        pos = (pos * onehot).sum(-1)                         # slot in expert
        C = max(int(-(-Tl * k // E) * cap_factor), 1)
        keep = pos < C
        pos_c = jnp.minimum(pos, C - 1)
        token_idx = jnp.arange(Tl * k) // k
        xrep = xt[token_idx]                                 # (Tl*k, d)
        upd = jnp.where(keep[:, None], xrep, 0)
        disp = jnp.zeros((E, C, d), xl.dtype).at[
            flat_e, pos_c].add(upd)                          # unique slots
        g = jnp.einsum("ecd,edf->ecf", disp, wg,
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("ecd,edf->ecf", disp, wu,
                       preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(xl.dtype)
        y = jnp.einsum("ecf,efd->ecd", h, wd,
                       preferred_element_type=jnp.float32)   # f-partial
        rows = y[flat_e, pos_c] * keep[:, None]
        out = (rows.reshape(Tl, k, d).astype(jnp.float32)
               * gates[..., None]).sum(axis=1)
        if model_axis is not None:
            out = jax.lax.psum(out, model_axis)
        return out.astype(xl.dtype).reshape(Bl, Sl, d)

    from jax.sharding import PartitionSpec as P
    dspec = data_axes if data_axes else None
    w_in = P(None, None, model_axis)     # (E, d, f/n): FFN width sharded
    w_out = P(None, model_axis, None)    # (E, f/n, d)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dspec, None, None), P(None, None), w_in, w_in,
                  w_out),
        out_specs=P(dspec, None, None),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


# --------------------------------------------------------------------- #
# Embedding / unembedding
# --------------------------------------------------------------------- #
def init_embed(cfg: ModelConfig, key=None) -> Params:
    if key is None:
        key = jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(key)
    V = cfg.padded_vocab
    p = {"tok": (jax.random.normal(k1, (V, cfg.d_model))
                 * 0.02).astype(cfg.jnp_dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(
            k2, (cfg.d_model, V)) * 0.02).astype(cfg.jnp_dtype)
    return p


def embed(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Project to (padded) vocab logits.  Padding columns are masked to
    -inf, which keeps loss (logsumexp) and argmax EXACTLY equal to the
    unpadded computation — the padding exists purely so the vocab
    dimension shards over the model axis (no TB-scale logit gathers)."""
    if "unembed" in p:
        logits = x @ p["unembed"]
    else:
        logits = x @ p["tok"].T.astype(x.dtype)
    V = cfg.padded_vocab
    if V != cfg.vocab_size:
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, V), 2)
        logits = jnp.where(col < cfg.vocab_size, logits,
                           jnp.asarray(-1e30, logits.dtype))
    return logits
