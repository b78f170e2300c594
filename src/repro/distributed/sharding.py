"""Logical-axis sharding rules with divisibility fallback.

Every parameter / cache / activation dimension is assigned a *logical*
axis by pattern-matching its tree path and rank; rule tables map logical
axes onto mesh axes.  A mapping is dropped (replicated) whenever the
dimension size is not divisible by the mesh-axis product — e.g.
gemma-2b's 8 query heads cannot shard over a 16-way ``model`` axis, so
heads replicate while its 16384-wide d_ff and 256000 vocab shard.

Rule tables:
  TRAIN_RULES  — FSDP over ``data`` (embed dim) x TP over ``model``
                 (heads / mlp / vocab / experts); batch over (pod, data).
  SERVE_RULES  — pure TP for weights; batch over (pod, data); decode KV
                 sequence over ``model`` (flash-decode style partial
                 attention, reduced by GSPMD).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LogicalAxes = Tuple[Optional[str], ...]

# --------------------------------------------------------------------- #
# Logical axis assignment by leaf path
# --------------------------------------------------------------------- #
_PARAM_PATTERNS = [
    # (path substring, rank -> logical axes); first match wins.
    # The embedding table's d_model dim is deliberately NOT FSDP-sharded
    # ("emb_d" -> None): sharding the unembed contraction dim over the
    # same axis as the batch forces GSPMD to all-gather full-batch logits
    # (67 GB/chip on seamless) — replicating the small table is free.
    ("embed/tok",      {2: ("vocab", "emb_d")}),
    ("embed/unembed",  {2: ("emb_d", "vocab")}),
    ("wq",             {3: ("embed", "heads", None),
                        4: ("layers", "embed", "heads", None)}),
    ("wk",             {3: ("embed", "kv_heads", None),
                        4: ("layers", "embed", "kv_heads", None)}),
    ("wv",             {3: ("embed", "kv_heads", None),
                        4: ("layers", "embed", "kv_heads", None)}),
    ("wo",             {3: ("heads", None, "embed"),
                        4: ("layers", "heads", None, "embed")}),
    ("bq",             {2: ("heads", None), 3: ("layers", "heads", None)}),
    ("bk",             {2: ("kv_heads", None),
                        3: ("layers", "kv_heads", None)}),
    ("bv",             {2: ("kv_heads", None),
                        3: ("layers", "kv_heads", None)}),
    ("router",         {2: ("embed", "expert"),
                        3: ("layers", "embed", "expert")}),
    ("w_gate",         {2: ("embed", "mlp"),
                        3: ("layers", "embed", "mlp"),
                        4: ("layers", "expert", "embed", "mlp")}),
    ("w_up",           {2: ("embed", "mlp"),
                        3: ("layers", "embed", "mlp"),
                        4: ("layers", "expert", "embed", "mlp")}),
    ("w_down",         {2: ("mlp", "embed"),
                        3: ("layers", "mlp", "embed"),
                        4: ("layers", "expert", "mlp", "embed")}),
    # mamba2
    ("in_proj",        {2: ("embed", "mamba_proj"),
                        3: ("layers", "embed", "mamba_proj")}),
    ("out_proj",       {2: ("mamba_inner", "embed"),
                        3: ("layers", "mamba_inner", "embed")}),
    ("conv_w",         {2: (None, "mamba_proj"),
                        3: ("layers", None, "mamba_proj")}),
    # rwkv6
    ("w_lora_a",       {2: ("embed", None), 3: ("layers", "embed", None)}),
    ("w_lora_b",       {2: (None, "embed"), 3: ("layers", None, "embed")}),
    ("w_r",            {2: ("embed", "rwkv_inner"),
                        3: ("layers", "embed", "rwkv_inner")}),
    ("w_k",            {2: ("embed", "rwkv_inner"),
                        3: ("layers", "embed", "rwkv_inner")}),
    ("w_v",            {2: ("embed", "rwkv_inner"),
                        3: ("layers", "embed", "rwkv_inner")}),
    ("w_g",            {2: ("embed", "rwkv_inner"),
                        3: ("layers", "embed", "rwkv_inner")}),
    ("w_o",            {2: ("rwkv_inner", "embed"),
                        3: ("layers", "rwkv_inner", "embed")}),
    ("ck",             {2: ("embed", "mlp"),
                        3: ("layers", "embed", "mlp")}),
    ("cv",             {2: ("mlp", "embed"),
                        3: ("layers", "mlp", "embed")}),
    ("cr",             {2: ("embed", "rwkv_inner"),
                        3: ("layers", "embed", "rwkv_inner")}),
]


def _leaf_axes(path: str, ndim: int) -> LogicalAxes:
    for pat, by_rank in _PARAM_PATTERNS:
        if pat in path and ndim in by_rank:
            return by_rank[ndim]
    return (None,) * ndim       # norms, biases, scalars: replicate


def param_logical_axes(params: Any) -> Any:
    """Tree of logical-axes tuples matching the parameter tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        spath = "/".join(str(getattr(k, "key", k)) for k in path)
        out.append(_leaf_axes(spath, np.ndim(leaf) if not
                              hasattr(leaf, "ndim") else leaf.ndim))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------- #
# Rule tables: logical axis -> mesh axis (or tuple of mesh axes)
# --------------------------------------------------------------------- #
TRAIN_RULES: Dict[str, Any] = {
    "embed": "data",            # FSDP shard of the contraction dim
    "emb_d": None,              # embed table d_model: replicate (see above)
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "mamba_proj": "model",
    "mamba_inner": "model",
    "rwkv_inner": "model",
    "layers": None,
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,
}

SERVE_RULES: Dict[str, Any] = {
    "embed": None,              # weights replicated across data (TP only)
    "emb_d": None,
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "mamba_proj": "model",
    "mamba_inner": "model",
    "rwkv_inner": "model",
    "layers": None,
    "batch": ("pod", "data"),
    "seq": ("pod", "data"),     # long-context prefill: sequence parallel
    # decode: flash-decode style KV split over whatever batch left free
    "kv_seq": ("data", "model"),
}


def _mesh_axes_for(mesh: Mesh, rule) -> Tuple[Tuple[str, ...], int]:
    if rule is None:
        return (), 1
    axes = (rule,) if isinstance(rule, str) else tuple(rule)
    axes = tuple(a for a in axes if a in mesh.shape)
    size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    return axes, size


def head_shards(mesh: Mesh, rules: Dict[str, Any]) -> int:
    """Ways ``rules`` split query heads over ``mesh``; 1 where no axis of
    the mesh does.  A model's ``head_shards`` for that mesh: its query
    heads pad to a multiple of it where it does not divide them."""
    return _mesh_axes_for(mesh, rules.get("heads"))[1]


def spec_for(shape: Sequence[int], logical: LogicalAxes, mesh: Mesh,
             rules: Dict[str, Any]) -> P:
    """PartitionSpec with divisibility fallback to replication.

    Mesh axes already claimed by an earlier dimension of the same tensor
    are dropped from later rules (e.g. decode KV: batch takes (pod, data),
    kv_seq then maps onto the remaining model axis).  Rules whose full
    remaining product does not divide the dimension fall back to the
    largest dividing prefix, else replication.
    """
    used: set = set()
    parts = []
    for dim, name in zip(shape, logical):
        if name is None:
            parts.append(None)
            continue
        rule = rules.get(name)
        axes, _ = _mesh_axes_for(mesh, rule)
        axes = tuple(a for a in axes if a not in used)
        # largest prefix of axes whose product divides dim
        chosen: Tuple[str, ...] = ()
        size = 1
        for a in axes:
            nxt = size * int(mesh.shape[a])
            if dim % nxt == 0:
                chosen = chosen + (a,)
                size = nxt
        if not chosen or size <= 1:
            parts.append(None)
            continue
        used.update(chosen)
        parts.append(chosen[0] if len(chosen) == 1 else tuple(chosen))
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_shardings(tree_avals: Any, logical_tree: Any, mesh: Mesh,
                   rules: Dict[str, Any]) -> Any:
    """NamedSharding tree for an aval tree + logical-axes tree."""
    def one(aval, logical):
        return NamedSharding(mesh, spec_for(aval.shape, logical, mesh,
                                            rules))
    return jax.tree_util.tree_map(one, tree_avals, logical_tree)


def param_shardings(params_avals: Any, mesh: Mesh,
                    rules: Dict[str, Any]) -> Any:
    return tree_shardings(params_avals, param_logical_axes(params_avals),
                          mesh, rules)


# --------------------------------------------------------------------- #
# Cache / batch logical axes
# --------------------------------------------------------------------- #
def cache_logical_axes(cache: Any) -> Any:
    """KV caches: (L, B, T, H, D) -> (layers, batch, kv_seq, kv_heads, _);
    SSM states: (L, B, ...) -> batch-sharded only."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    out = []
    for path, leaf in flat:
        spath = "/".join(str(getattr(k, "key", k)) for k in path)
        nd = leaf.ndim
        if ("kv" in spath or "cross" in spath) and nd == 5:
            out.append(("layers", "batch", "kv_seq", "kv_heads", None))
        elif nd >= 2:
            out.append(("layers", "batch") + (None,) * (nd - 2))
        else:
            out.append((None,) * nd)
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_logical_axes(batch_tree: Any, seq_axis: bool = True) -> Any:
    def one(leaf):
        nd = leaf.ndim
        if nd == 0:
            return ()
        if nd == 1:
            return ("batch",)
        return ("batch", "seq" if seq_axis else None) + \
            (None,) * (nd - 2)
    return jax.tree_util.tree_map(one, batch_tree)
