"""Model assembly: one functional decoder stack covering every family.

Layer stacks are ``jax.lax.scan``-based over *stacked* per-layer parameter
leaves (leading dim = num_layers) so XLA compiles a single layer body —
essential for 40-cell dry-run compile times.  ``scan_layers=False``
unrolls the stack with per-layer region markers, the form the Tessera
analyzer consumes (one DDG node per kernel per layer).

Entry points (all pure):
  init_params / init_cache
  forward_logits(params, cfg, tokens, ...)      full-sequence logits
  loss_fn(params, cfg, tokens, targets)         train loss
  prefill(params, cfg, tokens, cache)           fill caches, last logits
  decode_step(params, cfg, tokens, cache, pos)  one token, (B,) positions
  export_kv / import_kv / kv_state_bytes        per-request state handoff
                                                (prefill/decode split)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import marker
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.config import ModelConfig

Params = Dict[str, Any]


# ===================================================================== #
# Parameter construction
# ===================================================================== #
def _stack_init(fn, num: int, key):
    keys = jax.random.split(key, num)
    return jax.vmap(fn)(keys)


def init_params(cfg: ModelConfig, key=None) -> Params:
    if key is None:
        key = jax.random.PRNGKey(0)
    k_emb, k_layers, k_extra = jax.random.split(key, 3)
    p: Params = {"embed": L.init_embed(cfg, k_emb),
                 "final_norm": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype)}

    if cfg.family in ("dense", "moe", "vlm"):
        def one(k):
            k1, k2 = jax.random.split(k)
            lp = {"ln1": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype),
                  "attn": L.init_attention(cfg, key=k1),
                  "ln2": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype)}
            if cfg.family == "moe":
                lp["moe"] = L.init_moe(cfg, k2)
            else:
                lp["mlp"] = L.init_mlp(cfg, key=k2)
            return lp
        p["layers"] = _stack_init(one, cfg.num_layers, k_layers)

    elif cfg.family == "ssm":           # rwkv6
        def one(k):
            return {"ln1": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype),
                    "tm": S.init_rwkv6(cfg, k),
                    "ln2": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype)}
        p["layers"] = _stack_init(one, cfg.num_layers, k_layers)

    elif cfg.family == "hybrid":        # zamba2
        def one(k):
            return {"ln": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype),
                    "mamba": S.init_mamba2(cfg, k)}
        p["layers"] = _stack_init(one, cfg.num_layers, k_layers)
        k1, k2 = jax.random.split(k_extra)
        p["shared_attn"] = {
            "ln1": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype),
            "attn": L.init_attention(cfg, key=k1),
            "ln2": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype),
            "mlp": L.init_mlp(cfg, key=k2),
        }

    elif cfg.family == "encdec":        # seamless backbone
        def enc_one(k):
            k1, k2 = jax.random.split(k)
            return {"ln1": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype),
                    "attn": L.init_attention(cfg, key=k1),
                    "ln2": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype),
                    "mlp": L.init_mlp(cfg, key=k2)}

        def dec_one(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {"ln1": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype),
                    "attn": L.init_attention(cfg, key=k1),
                    "ln_x": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype),
                    "xattn": L.init_attention(cfg, key=k2),
                    "ln2": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype),
                    "mlp": L.init_mlp(cfg, key=k3)}
        ke, kd = jax.random.split(k_layers)
        p["encoder"] = _stack_init(enc_one, cfg.encoder_layers, ke)
        p["layers"] = _stack_init(dec_one, cfg.num_layers, kd)
        p["enc_norm"] = L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype)
    else:
        raise ValueError(cfg.family)
    return p


# query-head axis of an attention block's leaves that have one
_HEAD_AXIS = {"wq": -2, "bq": -2, "wo": -3}


def published_heads(params: Params, cfg: ModelConfig) -> Params:
    """``params`` with every attention block holding the ``num_heads``
    published query heads, wherever a mesh's padding laid them out wider
    (their slots: ``layers.head_slots``).  Other leaves pass through."""
    def cut(path, x):
        keys = [getattr(k, "key", None) for k in path[-2:]]
        axis = _HEAD_AXIS.get(keys[-1])
        if keys[0] not in ("attn", "xattn") or axis is None \
                or x.shape[axis] == cfg.num_heads:
            return x
        return jnp.take(x, L.head_slots(cfg, x.shape[axis]), axis=axis)
    return jax.tree_util.tree_map_with_path(cut, params)


# ===================================================================== #
# Layer bodies (shared by scan and unrolled paths)
# ===================================================================== #
def _dense_block(lp, x, cfg, *, positions, cache=None, cache_pos=None,
                 cache_layer=None, kv_lengths=None, positions3=None,
                 layer_idx=-1, tagged=False):
    # Region markers open BEFORE the computation so the analyzer tags
    # every kernel traced inside the block (begin ... end brackets the
    # equation stream).
    xin = L.rms_norm(lp["ln1"], x, cfg.norm_eps)
    if tagged:
        xin, close = marker.tag(xin, block="attention", layer=layer_idx)
    h, new_cache = L.attention(
        lp["attn"], xin, cfg, positions=positions, kv_cache=cache,
        cache_pos=cache_pos, positions3=positions3, cache_layer=cache_layer,
        kv_lengths=kv_lengths)
    if tagged:
        h = close(h)
    x = x + h
    y_in = L.rms_norm(lp["ln2"], x, cfg.norm_eps)
    blk = "moe" if cfg.family == "moe" else "ffn"
    if tagged:
        y_in, close2 = marker.tag(y_in, block=blk, layer=layer_idx)
    if cfg.family == "moe":
        y = L.moe(lp["moe"], y_in, cfg)
    else:
        y = L.mlp(lp["mlp"], y_in, cfg)
    if tagged:
        y = close2(y)
    return x + y, new_cache


def _rwkv_block(lp, x, cfg, *, state=None, layer_idx=-1, tagged=False):
    st_tm = None if state is None else \
        {"tm_x": state["tm_x"], "wkv": state["wkv"]}
    xin = L.rms_norm(lp["ln1"], x, cfg.norm_eps)
    if tagged:
        xin, close = marker.tag(xin, block="ssm", layer=layer_idx)
    h, new_tm = S.rwkv6_time_mix(lp["tm"], xin, cfg, state=st_tm)
    if tagged:
        h = close(h)
    x = x + h
    st_cm = None if state is None else state["cm_x"]
    # channel-mix params live inside the "tm" dict (see ssm.init_rwkv6)
    yin = L.rms_norm(lp["ln2"], x, cfg.norm_eps)
    if tagged:
        yin, close2 = marker.tag(yin, block="ffn", layer=layer_idx)
    y, new_cm = S.rwkv6_channel_mix(lp["tm"], yin, cfg, state=st_cm)
    if tagged:
        y = close2(y)
    new_state = None
    if state is not None:
        new_state = {"tm_x": new_tm["tm_x"], "wkv": new_tm["wkv"],
                     "cm_x": new_cm}
    return x + y, new_state


def _mamba_block(lp, x, cfg, *, state=None, layer_idx=-1, tagged=False):
    st = None if state is None else \
        {"ssm": state["ssm"], "conv": state["conv"]}
    xin = L.rms_norm(lp["ln"], x, cfg.norm_eps)
    if tagged:
        xin, close = marker.tag(xin, block="ssm", layer=layer_idx)
    h, new_st = S.mamba2(lp["mamba"], xin, cfg, state=st)
    if tagged:
        h = close(h)
    return x + h, new_st


def _shared_attn_block(sp, x, cfg, *, positions, cache=None,
                       cache_pos=None, tagged=False, layer_idx=-1):
    xin = L.rms_norm(sp["ln1"], x, cfg.norm_eps)
    if tagged:
        xin, close = marker.tag(xin, block="attention", layer=layer_idx)
    h, new_cache = L.attention(sp["attn"], xin, cfg, positions=positions,
                               kv_cache=cache, cache_pos=cache_pos)
    if tagged:
        h = close(h)
    x = x + h
    yin = L.rms_norm(sp["ln2"], x, cfg.norm_eps)
    if tagged:
        yin, close2 = marker.tag(yin, block="ffn", layer=layer_idx)
    y = L.mlp(sp["mlp"], yin, cfg)
    if tagged:
        y = close2(y)
    return x + y, new_cache


# ===================================================================== #
# Layer-stack driver: scan (compile-once) or unrolled (analysis/roofline)
# ===================================================================== #
def _run_stack(body, x, xs_tree, scan: bool):
    """Exactly jax.lax.scan(body, x, xs_tree) semantics; ``scan=False``
    unrolls the loop in Python (used by the Tessera analyzer and by the
    roofline L1/L2 extrapolation compiles)."""
    if scan:
        return jax.lax.scan(body, x, xs_tree)
    leaves = jax.tree_util.tree_leaves(xs_tree)
    L = leaves[0].shape[0]
    ys = []
    for i in range(L):
        x, y = body(x, jax.tree_util.tree_map(lambda a: a[i], xs_tree))
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return x, ys


# ===================================================================== #
# Caches
# ===================================================================== #
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: Optional[int] = None) -> Params:
    if cfg.family in ("dense", "moe", "vlm"):
        return {"kv": L.make_kv_cache(cfg, batch, max_len)}
    if cfg.family == "ssm":
        return {"rwkv": S.make_rwkv6_state(cfg, batch)}
    if cfg.family == "hybrid":
        n_attn = (cfg.num_layers + cfg.hybrid_attn_every - 1) \
            // cfg.hybrid_attn_every
        return {"mamba": S.make_mamba2_state(cfg, batch),
                "kv": L.make_kv_cache(cfg, batch, max_len, layers=n_attn)}
    if cfg.family == "encdec":
        enc_len = enc_len or max_len
        return {"kv": L.make_kv_cache(cfg, batch, max_len),
                "cross_k": jnp.zeros(
                    (cfg.num_layers, batch, enc_len, cfg.num_kv_heads,
                     cfg.head_dim), cfg.jnp_dtype),
                "cross_v": jnp.zeros(
                    (cfg.num_layers, batch, enc_len, cfg.num_kv_heads,
                     cfg.head_dim), cfg.jnp_dtype)}
    raise ValueError(cfg.family)


# ===================================================================== #
# Forward paths
# ===================================================================== #
def _maybe_remat(fn, remat: bool):
    return jax.checkpoint(fn) if remat else fn


def _embed_inputs(params, cfg, tokens, patch_embeds):
    x = L.embed(params["embed"], tokens).astype(cfg.jnp_dtype)
    if cfg.family == "vlm" and patch_embeds is not None:
        npat = patch_embeds.shape[1]
        x = jax.lax.dynamic_update_slice_in_dim(
            x, patch_embeds.astype(x.dtype), 0, axis=1)
    return x


def _encoder_forward(params, cfg, enc_embeds, *, scan_layers=True,
                     remat=False):
    """Bidirectional encoder over precomputed frame embeddings (B,S,d)."""
    x = enc_embeds.astype(cfg.jnp_dtype)
    B, Se, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(Se)[None], (B, Se))

    def body(x, lp):
        h, _ = L.attention(lp["attn"],
                           L.rms_norm(lp["ln1"], x, cfg.norm_eps), cfg,
                           positions=positions, causal=False)
        x = x + h
        y = L.mlp(lp["mlp"], L.rms_norm(lp["ln2"], x, cfg.norm_eps), cfg)
        return x + y, None

    if scan_layers:
        x, _ = jax.lax.scan(_maybe_remat(body, remat), x,
                            params["encoder"])
    else:
        for i in range(cfg.encoder_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["encoder"])
            x, _ = body(x, lp)
    return L.rms_norm(params["enc_norm"], x, cfg.norm_eps)


def forward_logits(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                   *, patch_embeds=None, positions3=None, enc_embeds=None,
                   scan_layers: bool = True, remat: bool = False,
                   q_chunk: int = 512) -> jnp.ndarray:
    """Full-sequence logits (teacher forcing / training / prefill-style)."""
    B, Sq = tokens.shape
    x = _embed_inputs(params, cfg, tokens, patch_embeds)
    positions = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))

    enc_out = None
    if cfg.family == "encdec":
        assert enc_embeds is not None, "encdec requires encoder inputs"
        enc_out = _encoder_forward(params, cfg, enc_embeds,
                                   scan_layers=scan_layers, remat=remat)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(x, lp):
            y, _ = _dense_block(lp, x, cfg, positions=positions,
                                positions3=positions3)
            return y, None
        if scan_layers:
            x, _ = jax.lax.scan(_maybe_remat(body, remat), x,
                                params["layers"])
        else:
            for i in range(cfg.num_layers):
                lp = jax.tree_util.tree_map(lambda a: a[i],
                                            params["layers"])
                x, _ = _dense_block(lp, x, cfg, positions=positions,
                                    positions3=positions3, layer_idx=i,
                                    tagged=True)

    elif cfg.family == "ssm":
        def body(x, lp):
            y, _ = _rwkv_block(lp, x, cfg)
            return y, None
        if scan_layers:
            x, _ = jax.lax.scan(_maybe_remat(body, remat), x,
                                params["layers"])
        else:
            for i in range(cfg.num_layers):
                lp = jax.tree_util.tree_map(lambda a: a[i],
                                            params["layers"])
                x, _ = _rwkv_block(lp, x, cfg, layer_idx=i, tagged=True)

    elif cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        nSB = cfg.num_layers // k
        sp = params["shared_attn"]
        stacked = jax.tree_util.tree_map(
            lambda a: a.reshape((nSB, k) + a.shape[1:]), params["layers"])

        def body(x, lp_group):
            x, _ = _shared_attn_block(sp, x, cfg, positions=positions)
            for j in range(k):
                lp = jax.tree_util.tree_map(lambda a: a[j], lp_group)
                x, _ = _mamba_block(lp, x, cfg)
            return x, None
        if scan_layers:
            x, _ = jax.lax.scan(_maybe_remat(body, remat), x, stacked)
        else:
            for i in range(nSB):
                lp_group = jax.tree_util.tree_map(lambda a: a[i], stacked)
                x, _ = body(x, lp_group)

    elif cfg.family == "encdec":
        def body(x, lp):
            h, _ = L.attention(lp["attn"],
                               L.rms_norm(lp["ln1"], x, cfg.norm_eps),
                               cfg, positions=positions)
            x = x + h
            ck = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wk"])
            cv = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wv"])
            h, _ = L.attention(lp["xattn"],
                               L.rms_norm(lp["ln_x"], x, cfg.norm_eps),
                               cfg, positions=positions,
                               cross_kv=(ck, cv), causal=False)
            x = x + h
            y = L.mlp(lp["mlp"], L.rms_norm(lp["ln2"], x, cfg.norm_eps),
                      cfg)
            return x + y, None
        if scan_layers:
            x, _ = jax.lax.scan(_maybe_remat(body, remat), x,
                                params["layers"])
        else:
            for i in range(cfg.num_layers):
                lp = jax.tree_util.tree_map(lambda a: a[i],
                                            params["layers"])
                x, _ = body(x, lp)
    else:
        raise ValueError(cfg.family)

    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    return L.unembed(params["embed"], x, cfg)


def loss_fn(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            targets: jnp.ndarray, **kw) -> jnp.ndarray:
    logits = forward_logits(params, cfg, tokens, **kw)
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None],
                               axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# --------------------------------------------------------------------- #
# Serving paths
# --------------------------------------------------------------------- #
def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            cache: Params, *, patch_embeds=None, positions3=None,
            enc_embeds=None, scan_layers: bool = True,
            q_chunk: int = 512,
            last_pos: Optional[jnp.ndarray] = None,
            offset: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Params]:
    """Process the prompt, fill caches, return last-position logits.

    ``last_pos`` (B,) selects a per-row "last" position instead of the
    literal final column — used by continuous-batching engines that
    right-pad a multi-request admission batch to a common length (each
    row's true prompt ends at its own index).

    ``offset`` switches to CHUNK mode: ``tokens`` are the prompt slice
    at absolute positions [offset, offset+Sq) and the cache already
    holds the state of the preceding chunks.  Attention K/V are written
    at the offset and queries attend over the filled prefix + this
    chunk (exact under causal masking); recurrent state threads through
    the cache by construction.  Driving successive chunks through this
    path is exactly :func:`prefill_chunked`.  ``last_pos`` stays
    chunk-relative in this mode.  Ring-buffer (sliding-window) caches
    and encdec are not chunkable (wrap-around slot layout / encoder
    coupling).
    """
    B, Sq = tokens.shape
    x = _embed_inputs(params, cfg, tokens, patch_embeds)
    positions = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
    if offset is not None:
        assert cfg.family in ("dense", "moe", "ssm", "hybrid"), \
            "chunked prefill serves decoder-only families"
        assert cfg.sliding_window is None, \
            "chunked prefill is undefined for ring-buffer SWA caches"
        positions = positions + jnp.asarray(offset, jnp.int32)
    new_cache = dict(cache)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(x, xs):
            lp, ck, cv = xs
            y, nc = _dense_block(lp, x, cfg, positions=positions,
                                 cache={"k": ck, "v": cv},
                                 cache_pos=offset,
                                 positions3=positions3,
                                 tagged=not scan_layers)
            return y, (nc["k"], nc["v"])
        x, (nk, nv) = _run_stack(
            body, x, (params["layers"], cache["kv"]["k"],
                      cache["kv"]["v"]), scan_layers)
        new_cache["kv"] = {"k": nk, "v": nv}

    elif cfg.family == "ssm":
        st = cache["rwkv"]

        def body(x, xs):
            lp, tm_x, wkv, cm_x = xs
            y, ns = _rwkv_block(lp, x, cfg,
                                state={"tm_x": tm_x, "wkv": wkv,
                                       "cm_x": cm_x},
                                tagged=not scan_layers)
            return y, (ns["tm_x"], ns["wkv"], ns["cm_x"])
        x, (tm, wkv, cm) = _run_stack(
            body, x, (params["layers"], st["tm_x"], st["wkv"],
                      st["cm_x"]), scan_layers)
        new_cache["rwkv"] = {"tm_x": tm, "wkv": wkv, "cm_x": cm}

    elif cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        nSB = cfg.num_layers // k
        sp = params["shared_attn"]
        stacked = jax.tree_util.tree_map(
            lambda a: a.reshape((nSB, k) + a.shape[1:]), params["layers"])
        mst = jax.tree_util.tree_map(
            lambda a: a.reshape((nSB, k) + a.shape[1:]), cache["mamba"])

        def body(x, xs):
            lp_group, mamba_g, ck, cv = xs
            x, nc = _shared_attn_block(sp, x, cfg, positions=positions,
                                       cache={"k": ck, "v": cv},
                                       cache_pos=offset,
                                       tagged=not scan_layers)
            new_m = []
            for j in range(k):
                lp = jax.tree_util.tree_map(lambda a: a[j], lp_group)
                stj = jax.tree_util.tree_map(lambda a: a[j], mamba_g)
                x, ns = _mamba_block(lp, x, cfg, state=stj,
                                     tagged=not scan_layers)
                new_m.append(ns)
            new_m = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                           *new_m)
            return x, (new_m, nc["k"], nc["v"])
        x, (nm, nk, nv) = _run_stack(
            body, x, (stacked, mst, cache["kv"]["k"], cache["kv"]["v"]),
            scan_layers)
        new_cache["mamba"] = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), nm)
        new_cache["kv"] = {"k": nk, "v": nv}

    elif cfg.family == "encdec":
        assert enc_embeds is not None
        enc_out = _encoder_forward(params, cfg, enc_embeds,
                                   scan_layers=scan_layers)

        def body(x, xs):
            lp, ck, cv = xs
            h, nc = L.attention(lp["attn"],
                                L.rms_norm(lp["ln1"], x, cfg.norm_eps),
                                cfg, positions=positions,
                                kv_cache={"k": ck, "v": cv})
            x = x + h
            xk = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wk"])
            xv = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wv"])
            h, _ = L.attention(lp["xattn"],
                               L.rms_norm(lp["ln_x"], x, cfg.norm_eps),
                               cfg, positions=positions,
                               cross_kv=(xk, xv), causal=False)
            x = x + h
            y = L.mlp(lp["mlp"], L.rms_norm(lp["ln2"], x, cfg.norm_eps),
                      cfg)
            return x + y, (nc["k"], nc["v"], xk, xv)
        x, (nk, nv, xk, xv) = _run_stack(
            body, x, (params["layers"], cache["kv"]["k"],
                      cache["kv"]["v"]), scan_layers)
        new_cache["kv"] = {"k": nk, "v": nv}
        assert xk.shape[2] == cache["cross_k"].shape[2], \
            "cross-KV cache must be allocated with enc_len"
        new_cache["cross_k"] = xk
        new_cache["cross_v"] = xv
    else:
        raise ValueError(cfg.family)

    if last_pos is None:
        xl = x[:, -1:]
    else:
        idx = jnp.asarray(last_pos, jnp.int32)[:, None, None]
        xl = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[2])), axis=1)
    xl = L.rms_norm(params["final_norm"], xl, cfg.norm_eps)
    return L.unembed(params["embed"], xl, cfg)[:, 0], new_cache


def iter_prefill_chunks(params: Params, cfg: ModelConfig,
                        tokens, cache: Params, *, chunk_size: int,
                        last_pos: Optional[jnp.ndarray] = None,
                        scan_layers: bool = True, prefill_call=None):
    """Drive ``prefill(offset=)`` over fixed-size prompt chunks,
    yielding ``(t0, t1, logits, cache)`` after each — ``logits`` is
    the per-row last-position selection over all chunks SO FAR, so the
    final yield carries exactly :func:`prefill`'s result.

    The single source of the chunk-walk logic (range/clip/row-select):
    :func:`prefill_chunked` drains it, the serving engine interleaves
    decode steps between its yields, and the streamed handoff exports
    (layer, chunk) KV shards at each yield.

    ``prefill_call(cache, tokens_chunk, offset, rel_last) -> (logits,
    cache)`` lets callers inject a jitted chunk step; defaults to the
    plain :func:`prefill`.
    """
    B, S = tokens.shape
    assert chunk_size >= 1
    assert cfg.sliding_window is None, \
        "chunked prefill is undefined for ring-buffer SWA caches"
    if prefill_call is None:
        def prefill_call(c, t, off, lp):
            return prefill(params, cfg, jnp.asarray(t, jnp.int32), c,
                           offset=off, last_pos=lp,
                           scan_layers=scan_layers)
    last = (jnp.full((B,), S - 1, jnp.int32) if last_pos is None
            else jnp.asarray(last_pos, jnp.int32))
    logits = None
    for t0 in range(0, S, chunk_size):
        t1 = min(t0 + chunk_size, S)
        rel = jnp.clip(last - t0, 0, t1 - t0 - 1)
        lg, cache = prefill_call(cache, tokens[:, t0:t1],
                                 jnp.asarray(t0, jnp.int32), rel)
        # keep each row's logits from the chunk containing its last
        # position (rows whose prompt ended earlier ignore later chunks)
        sel = (last >= t0) & (last < t1)
        logits = lg if logits is None else \
            jnp.where(sel[:, None], lg, logits)
        yield t0, t1, logits, cache


def prefill_chunked(params: Params, cfg: ModelConfig,
                    tokens: jnp.ndarray, cache: Params, *,
                    chunk_size: int,
                    last_pos: Optional[jnp.ndarray] = None,
                    scan_layers: bool = True,
                    prefill_call=None) -> Tuple[jnp.ndarray, Params]:
    """Whole-prompt prefill as a sequence of fixed-size chunks.

    Equivalent to :func:`prefill` (same final logits and cache): each
    chunk updates the cache incrementally through ``prefill(offset=)``,
    which is exact for recurrent families by construction and
    causal-mask-safe for attention families.  This is what lets a
    serving engine (a) interleave decode steps of live slots between
    the chunks of a long admitted prompt and (b) stream completed
    (layer, chunk) KV shards to a decode engine while later chunks
    still compute.  Ring-buffer SWA caches fall back to one
    whole-prompt prefill (wrap-around slot layout is not chunkable).
    """
    S = tokens.shape[1]
    if cfg.sliding_window is not None or chunk_size >= S:
        return prefill(params, cfg, tokens, cache, last_pos=last_pos,
                       scan_layers=scan_layers)
    logits = None
    for _, _, logits, cache in iter_prefill_chunks(
            params, cfg, tokens, cache, chunk_size=chunk_size,
            last_pos=last_pos, scan_layers=scan_layers,
            prefill_call=prefill_call):
        pass
    return logits, cache


# --------------------------------------------------------------------- #
# Per-request state handoff (prefill/decode disaggregation)
# --------------------------------------------------------------------- #
def export_kv(cfg: ModelConfig, cache: Params, slot: int,
              length: Optional[int] = None) -> Params:
    """Extract one sequence's KV / recurrent state from a batched cache.

    Returns a batch-1 pytree mirroring the cache structure — the payload
    a prefill engine ships to a decode-only engine.  Every cache leaf is
    (L, B, ...); the batch axis is sliced at ``slot``.  For attention KV
    the time axis is additionally trimmed to ``length`` (only the filled
    prefix transfers — the size the cost model charges the interconnect
    for); recurrent state (ssm / hybrid mamba) is fixed-size and ships
    whole.  Ring-buffer (sliding-window) KV is never trimmed: slot
    layout depends on absolute positions.
    """
    out: Params = {}
    for key, val in cache.items():
        sub = jax.tree_util.tree_map(lambda a: a[:, slot:slot + 1], val)
        if key == "kv" and length is not None \
                and cfg.sliding_window is None:
            sub = {"k": sub["k"][:, :, :length],
                   "v": sub["v"][:, :, :length]}
        out[key] = sub
    return out


def import_kv(cfg: ModelConfig, cache: Params, slot: int,
              state: Params) -> Params:
    """Write an exported per-request state into ``slot`` of a batched
    cache (the decode_only admission path).  Inverse of
    :func:`export_kv`: a round trip through export/import must leave
    decode bit-identical to never having left the original engine.
    """
    new = dict(cache)
    for key, val in state.items():
        if key == "kv":
            T = val["k"].shape[2]
            new["kv"] = {
                "k": cache["kv"]["k"].at[:, slot:slot + 1, :T].set(
                    val["k"].astype(cache["kv"]["k"].dtype)),
                "v": cache["kv"]["v"].at[:, slot:slot + 1, :T].set(
                    val["v"].astype(cache["kv"]["v"].dtype)),
            }
        else:
            new[key] = jax.tree_util.tree_map(
                lambda full, s: full.at[:, slot:slot + 1].set(
                    s.astype(full.dtype)), cache[key], val)
    return new


def kv_state_bytes(state: Params) -> int:
    """Wire size of an exported state (what the interconnect carries)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(state))


# --------------------------------------------------------------------- #
# Layer-granular shards: the streaming unit of a pipelined handoff.
# export_kv emits one monolithic payload only after the whole prompt
# finishes; these shards let a prefill engine ship each (component,
# layer[, token-range]) slice as soon as it is computed, overlapping
# the fabric transfer with the remaining prefill compute.  Installing
# every shard of a request == import_kv of its whole export.
# --------------------------------------------------------------------- #
def cache_layer_counts(cache: Params) -> Dict[str, int]:
    """Leading (layer) dimension per cache component — components can
    disagree (a hybrid's shared-attn KV has fewer layers than its
    mamba state)."""
    return {key: jax.tree_util.tree_leaves(val)[0].shape[0]
            for key, val in cache.items()}


def export_kv_shard(cfg: ModelConfig, cache: Params, slot: int,
                    key: str, layer: int,
                    t0: Optional[int] = None,
                    t1: Optional[int] = None) -> Params:
    """One layer's slice of one sequence's state.

    For attention KV (``key == "kv"``) an optional token range
    ``[t0, t1)`` selects a chunk of the time axis — the (layer, chunk)
    granularity of a streamed handoff.  Ring-buffer (sliding-window) KV
    and recurrent state ignore the range and ship the whole layer
    (ring slot layout depends on absolute positions; recurrent state is
    fixed-size and only its final value matters).
    """
    sub = jax.tree_util.tree_map(
        lambda a: a[layer:layer + 1, slot:slot + 1], cache[key])
    if key == "kv" and t0 is not None and cfg.sliding_window is None:
        sub = {"k": sub["k"][:, :, t0:t1], "v": sub["v"][:, :, t0:t1]}
    return sub


def import_kv_window(cfg: ModelConfig, cache: Params, slot: int,
                     layer0: int, shards, t0: int = 0) -> Params:
    """Install a contiguous ascending run of attention-KV layer shards
    (layers ``layer0, layer0+1, ...``, all covering the same token
    window starting at ``t0``) in ONE cache update.

    A streamed admission receives one shard per layer per chunk;
    installing each individually rebuilds the whole batched cache
    O(layers x chunks) times, so the consumer buffers a window's run
    and flushes it here — one functional update per chunk instead of
    one per (layer, chunk).
    """
    ks = jnp.concatenate([s["k"] for s in shards], axis=0)
    vs = jnp.concatenate([s["v"] for s in shards], axis=0)
    L, T = ks.shape[0], ks.shape[2]
    new = dict(cache)
    new["kv"] = {
        "k": cache["kv"]["k"].at[
            layer0:layer0 + L, slot:slot + 1, t0:t0 + T].set(
            ks.astype(cache["kv"]["k"].dtype)),
        "v": cache["kv"]["v"].at[
            layer0:layer0 + L, slot:slot + 1, t0:t0 + T].set(
            vs.astype(cache["kv"]["v"].dtype)),
    }
    return new


def import_kv_shard(cfg: ModelConfig, cache: Params, slot: int,
                    key: str, layer: int, shard: Params,
                    t0: int = 0) -> Params:
    """Install one exported layer shard into ``slot`` of a batched
    cache.  Inverse of :func:`export_kv_shard`; installing all shards
    of a request reproduces :func:`import_kv` of its whole export."""
    new = dict(cache)
    if key == "kv" and cfg.sliding_window is None:
        T = shard["k"].shape[2]
        new["kv"] = {
            "k": cache["kv"]["k"].at[
                layer:layer + 1, slot:slot + 1, t0:t0 + T].set(
                shard["k"].astype(cache["kv"]["k"].dtype)),
            "v": cache["kv"]["v"].at[
                layer:layer + 1, slot:slot + 1, t0:t0 + T].set(
                shard["v"].astype(cache["kv"]["v"].dtype)),
        }
    else:
        new[key] = jax.tree_util.tree_map(
            lambda full, s: full.at[layer:layer + 1, slot:slot + 1].set(
                s.astype(full.dtype)), cache[key], shard)
    return new


# --------------------------------------------------------------------- #
# Paged KV blocks: block-granular views over a shared (layer, block)
# pool.  A session's attention KV is stored as ceil(T / block_tokens)
# pool blocks named by its block table; one block id spans all layers.
# pack/gather round-trip through the pool is exact (same dtype, no
# arithmetic), so parking a session and re-activating it later leaves
# greedy decode bit-identical to never having left the dense cache.
# --------------------------------------------------------------------- #
def kv_block_bytes(cfg: ModelConfig, block_tokens: int,
                   layers: Optional[int] = None) -> int:
    """Bytes one pool block holds across all layers (K and V)."""
    L = layers if layers is not None else cfg.num_layers
    itemsize = jnp.zeros((), cfg.jnp_dtype).dtype.itemsize
    return 2 * L * block_tokens * cfg.num_kv_heads * cfg.head_dim \
        * itemsize


def pack_kv_blocks(pool: Params, state: Params, block_ids) -> Params:
    """Scatter a batch-1 exported attention-KV state (L, 1, T, Hkv, D)
    into pool blocks ``block_ids`` — one functional update per
    component.  T is zero-padded up to ``len(block_ids) * block_tokens``
    (the tail of the last block is unused capacity)."""
    ids = jnp.asarray(block_ids, jnp.int32)
    bt = pool["k"].shape[2]
    new = {}
    for c in ("k", "v"):
        val = state[c][:, 0]                       # (L, T, Hkv, D)
        Lc, T = val.shape[0], val.shape[1]
        need = int(ids.shape[0]) * bt
        if T < need:
            val = jnp.pad(val, ((0, 0), (0, need - T), (0, 0), (0, 0)))
        blocks = val[:, :need].reshape(
            Lc, int(ids.shape[0]), bt, *val.shape[2:])
        new[c] = pool[c].at[:, ids].set(blocks.astype(pool[c].dtype))
    return new


def gather_kv_blocks(pool: Params, block_ids, length: int) -> Params:
    """Inverse of :func:`pack_kv_blocks`: gather ``block_ids`` from the
    pool and return a batch-1 state (L, 1, length, Hkv, D) — the exact
    payload :func:`import_kv` installs into a dense cache slot."""
    ids = jnp.asarray(block_ids, jnp.int32)
    out = {}
    for c in ("k", "v"):
        blocks = pool[c][:, ids]                   # (L, nb, bt, Hkv, D)
        Lc, nb, bt = blocks.shape[:3]
        flat = blocks.reshape(Lc, nb * bt, *blocks.shape[3:])
        out[c] = flat[:, :length][:, None]         # (L, 1, T, Hkv, D)
    return out


def decode_step(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: Params, pos: jnp.ndarray, *, positions3=None,
                scan_layers: bool = True,
                lengths: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Params]:
    """One decode step.  tokens: (B, 1) int32; pos: (B,) absolute
    positions; lengths: (B,) K/V rows each slot attends, 0 for an idle
    slot (default ``pos + 1``).  Returns (logits (B, V), updated
    cache)."""
    B = tokens.shape[0]
    x = L.embed(params["embed"], tokens).astype(cfg.jnp_dtype)
    positions = pos[:, None]                          # (B, 1)
    new_cache = dict(cache)

    if cfg.family in ("dense", "moe", "vlm"):
        cap = cache["kv"]["k"].shape[2]
        slot = pos % cap if cfg.sliding_window is not None else pos

        # The stacked cache rides in the carry: each layer scatters its
        # B new rows at [layer, b, slot] and reads its own slice, so a
        # donated cache is updated in place instead of rebuilt as the
        # scan's output.  The layer index is a numpy int when unrolled.
        # Only the scanned loop passes the live lengths on: the unrolled
        # one is the analyzer's trace, whose cost model would count both
        # of attention's platform branches.
        live = None
        if scan_layers:
            live = pos + 1 if lengths is None else lengths

        def body(carry, xs):
            x, kv = carry
            lp, i = xs
            return _dense_block(lp, x, cfg, positions=positions, cache=kv,
                                cache_pos=slot, cache_layer=i,
                                kv_lengths=live, positions3=positions3,
                                tagged=not scan_layers), None
        (x, new_cache["kv"]), _ = _run_stack(
            body, (x, cache["kv"]),
            (params["layers"], np.arange(cfg.num_layers)), scan_layers)

    elif cfg.family == "ssm":
        st = cache["rwkv"]

        def body(x, xs):
            lp, tm_x, wkv, cm_x = xs
            y, ns = _rwkv_block(lp, x, cfg,
                                state={"tm_x": tm_x, "wkv": wkv,
                                       "cm_x": cm_x},
                                tagged=not scan_layers)
            return y, (ns["tm_x"], ns["wkv"], ns["cm_x"])
        x, (tm, wkv, cm) = _run_stack(
            body, x, (params["layers"], st["tm_x"], st["wkv"],
                      st["cm_x"]), scan_layers)
        new_cache["rwkv"] = {"tm_x": tm, "wkv": wkv, "cm_x": cm}

    elif cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        nSB = cfg.num_layers // k
        sp = params["shared_attn"]
        stacked = jax.tree_util.tree_map(
            lambda a: a.reshape((nSB, k) + a.shape[1:]), params["layers"])
        mst = jax.tree_util.tree_map(
            lambda a: a.reshape((nSB, k) + a.shape[1:]), cache["mamba"])

        def body(x, xs):
            lp_group, mamba_g, ck, cv = xs
            x, nc = _shared_attn_block(sp, x, cfg, positions=positions,
                                       cache={"k": ck, "v": cv},
                                       cache_pos=pos,
                                       tagged=not scan_layers)
            new_m = []
            for j in range(k):
                lp = jax.tree_util.tree_map(lambda a: a[j], lp_group)
                stj = jax.tree_util.tree_map(lambda a: a[j], mamba_g)
                x, ns = _mamba_block(lp, x, cfg, state=stj,
                                     tagged=not scan_layers)
                new_m.append(ns)
            new_m = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                           *new_m)
            return x, (new_m, nc["k"], nc["v"])
        x, (nm, nk, nv) = _run_stack(
            body, x, (stacked, mst, cache["kv"]["k"], cache["kv"]["v"]),
            scan_layers)
        new_cache["mamba"] = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), nm)
        new_cache["kv"] = {"k": nk, "v": nv}

    elif cfg.family == "encdec":
        def body(x, xs):
            lp, ck, cv, xk, xv = xs
            h, nc = L.attention(lp["attn"],
                                L.rms_norm(lp["ln1"], x, cfg.norm_eps),
                                cfg, positions=positions,
                                kv_cache={"k": ck, "v": cv},
                                cache_pos=pos)
            x = x + h
            h, _ = L.attention(lp["xattn"],
                               L.rms_norm(lp["ln_x"], x, cfg.norm_eps),
                               cfg, positions=positions,
                               cross_kv=(xk, xv), causal=False)
            x = x + h
            y = L.mlp(lp["mlp"], L.rms_norm(lp["ln2"], x, cfg.norm_eps),
                      cfg)
            return x + y, (nc["k"], nc["v"])
        x, (nk, nv) = _run_stack(
            body, x, (params["layers"], cache["kv"]["k"],
                      cache["kv"]["v"], cache["cross_k"],
                      cache["cross_v"]), scan_layers)
        new_cache["kv"] = {"k": nk, "v": nv}
    else:
        raise ValueError(cfg.family)

    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    return L.unembed(params["embed"], x, cfg)[:, 0], new_cache
