"""Kernel analyzer: jaxpr -> KernelGraph with exact RAW dependencies.

This is the TPU-native replacement for the paper's PTX-instrumentation
analyzer (§III-A).  A jaxpr is pure SSA, so buffer read/write sets are
exact by construction: every equation's operands and results carry
``ShapedArray`` avals, giving precise per-edge transfer sizes without any
instrumentation, speculation, or min/max interval aggregation.

The analyzer also:
  * estimates per-kernel FLOPs and HBM bytes (recursing into call-like
    primitives: scan / while / cond / pjit / custom_* / remat / pallas_call),
  * recovers phase/block/layer tags from region markers (marker.py),
  * detects cross-iteration state (the paper's KV-cache RAW pattern) from a
    ``(state, inputs) -> (state', outputs)`` step signature and reports the
    node sets that read/write it so the planner can pin them.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import numpy as np
import jax.extend.core as jex_core

from repro.core.graph import KernelGraph, KernelNode
from repro.core.marker import MARKER_NAME

Var = Any       # jex_core.Var
Atom = Any      # Var | Literal

# Writes into a buffer: their result is the operand (invars[0]) updated,
# so where the operand is cross-iteration state the result is too.
_UPDATE_PRIMS = frozenset({"scatter", "dynamic_update_slice"})


# --------------------------------------------------------------------- #
# Per-primitive FLOP / byte estimation
# --------------------------------------------------------------------- #
def _aval_bytes(aval) -> float:
    try:
        return float(aval.size) * np.dtype(aval.dtype).itemsize
    except Exception:                                    # tokens, etc.
        return 0.0


def _out_size(eqn) -> float:
    return float(sum(getattr(v.aval, "size", 0) for v in eqn.outvars))


# Elementwise transcendental cost multipliers (flops per element).
_EW_COST = {
    "exp": 4.0, "log": 4.0, "tanh": 6.0, "logistic": 5.0, "erf": 6.0,
    "pow": 8.0, "rsqrt": 2.0, "sqrt": 2.0, "sin": 4.0, "cos": 4.0,
    "integer_pow": 2.0, "div": 2.0, "rem": 2.0,
}
_ZERO_FLOP = frozenset({
    "reshape", "broadcast_in_dim", "transpose", "squeeze", "slice",
    "concatenate", "convert_element_type", "stop_gradient", "copy",
    "expand_dims", "rev", "iota", "pad", "select_n", "gather",
    "dynamic_slice", "device_put", "split", "bitcast_convert_type",
    "real", "imag", "sharding_constraint", "optimization_barrier",
})
_CALL_PRIMS = frozenset({
    "pjit", "closed_call", "core_call", "xla_call", "remat", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_jvp_call_jaxpr",
})


def _dot_general_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    batch = math.prod(lhs.shape[d] for d in lb) if lb else 1
    contract = math.prod(lhs.shape[d] for d in lc) if lc else 1
    m = math.prod(s for d, s in enumerate(lhs.shape)
                  if d not in lc and d not in lb)
    n = math.prod(s for d, s in enumerate(rhs.shape)
                  if d not in rc and d not in rb)
    return 2.0 * batch * m * n * contract


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval            # kernel: spatial... x in_ch x out_ch
    k_elems = math.prod(rhs.shape[:-1])
    return 2.0 * out.size * k_elems


def _inner_jaxprs(eqn) -> List[Tuple[Any, float]]:
    """(closed_jaxpr, multiplier) pairs for call-like primitives."""
    name, p = eqn.primitive.name, eqn.params
    if name == "scan":
        return [(p["jaxpr"], float(p["length"]))]
    if name == "while":
        # Trip count is dynamic; 1 is the conservative static estimate and
        # callers that know better can multiply (decode loops use scan).
        return [(p["body_jaxpr"], 1.0), (p["cond_jaxpr"], 1.0)]
    if name == "cond":
        return [(b, 1.0 / max(len(p["branches"]), 1))
                for b in p["branches"]]
    if name in _CALL_PRIMS:
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            if key in p:
                return [(p[key], 1.0)]
        return []
    if name == "pallas_call":
        grid = ()
        gm = p.get("grid_mapping")
        if gm is not None:
            grid = tuple(d for d in getattr(gm, "grid", ())
                         if isinstance(d, int))
        mult = float(math.prod(grid)) if grid else 1.0
        j = p.get("jaxpr")
        return [(j, mult)] if j is not None else []
    return []


def _jaxpr_cost(closed_jaxpr) -> Tuple[float, float]:
    """(flops, bytes) aggregate of a (Closed)Jaxpr."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    fl = by = 0.0
    for eqn in jaxpr.eqns:
        f, b = _eqn_cost(eqn)
        fl += f
        by += b
    return fl, by


def _eqn_cost(eqn) -> Tuple[float, float]:
    """(flops, hbm_bytes) for one equation."""
    name = eqn.primitive.name
    in_bytes = sum(_aval_bytes(v.aval) for v in eqn.invars
                   if hasattr(v, "aval"))
    out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)

    inner = _inner_jaxprs(eqn)
    if inner:
        fl = by = 0.0
        for cj, mult in inner:
            f, b = _jaxpr_cost(cj)
            fl += f * mult
            by += b * mult
        return fl, by

    if name == "dot_general":
        return _dot_general_flops(eqn), in_bytes + out_bytes
    if name == "ragged_dot":
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        return 2.0 * lhs.size * rhs.shape[-1], in_bytes + out_bytes
    if name == "conv_general_dilated":
        return _conv_flops(eqn), in_bytes + out_bytes
    if name in ("dynamic_update_slice", "scatter", "scatter-add",
                "scatter_add"):
        # In-place update: traffic ~ update size, not full operand
        # (critical for KV-cache decode writes).
        upd = _aval_bytes(eqn.invars[1].aval) if len(eqn.invars) > 1 else 0.0
        return 0.0, 2.0 * upd + 64.0
    if name in _ZERO_FLOP:
        return 0.0, in_bytes + out_bytes
    if name.startswith("reduce_") or name in ("argmax", "argmin"):
        return float(sum(getattr(v.aval, "size", 0) for v in eqn.invars
                         if hasattr(v, "aval"))), in_bytes + out_bytes
    if name in ("cumsum", "cumprod", "cumlogsumexp", "cummax", "cummin",
                "sort", "top_k"):
        n = _out_size(eqn)
        mult = math.log2(max(n, 2.0)) if name in ("sort", "top_k") else 1.0
        return n * mult, in_bytes + out_bytes
    if name == MARKER_NAME:
        return 0.0, 0.0
    mult = _EW_COST.get(name, 1.0)
    return _out_size(eqn) * mult, in_bytes + out_bytes


# --------------------------------------------------------------------- #
# Analysis result
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class TracedGraph:
    """KernelGraph plus everything the executor needs to rebuild stages."""

    graph: KernelGraph
    closed_jaxpr: Any                       # the traced ClosedJaxpr
    eqn_of_node: Dict[int, Tuple[int, ...]]  # node idx -> raw eqn indices
    in_tree: Any                            # pytree def of fn args
    out_tree: Any
    state_readers: Set[int] = dataclasses.field(default_factory=set)
    state_writers: Set[int] = dataclasses.field(default_factory=set)

    @property
    def jaxpr(self):
        return self.closed_jaxpr.jaxpr

    def with_graph(self, graph: KernelGraph) -> "TracedGraph":
        return dataclasses.replace(
            self, graph=graph,
            eqn_of_node={n.idx: n.eqn_ids for n in graph.nodes})


# --------------------------------------------------------------------- #
# Main entry point
# --------------------------------------------------------------------- #
def analyze(fn: Callable, *example_args, name: str = "ddg",
            state_argnums: Sequence[int] = (),
            fuse: bool = True, **example_kwargs) -> TracedGraph:
    """Trace ``fn`` and build its kernel graph.

    ``example_args`` may be concrete arrays or ``jax.ShapeDtypeStruct``s.
    ``state_argnums``: positional args holding cross-iteration state (e.g.
    KV caches); kernels reading them and kernels producing the matching
    outputs are reported in ``state_readers`` / ``state_writers`` so the
    planner can pin them (DESIGN.md §2, KV pinning).  A scatter or
    dynamic_update_slice into state yields state, so a buffer updated
    layer after layer keeps every reader and writer along the chain.
    """
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(
        *example_args, **example_kwargs)
    flat_args, in_tree = jax.tree_util.tree_flatten(
        (example_args, example_kwargs))
    out_tree = jax.tree_util.tree_structure(out_shape)
    jaxpr = closed.jaxpr

    # -- var plumbing -------------------------------------------------- #
    # alias map routes dataflow through zero-cost markers
    alias: Dict[Var, Var] = {}

    def resolve(v: Atom) -> Atom:
        while isinstance(v, jex_core.Var) and v in alias:
            v = alias[v]
        return v

    producer: Dict[Var, int] = {}        # var -> producing eqn idx
    nodes: List[KernelNode] = []
    edges: Dict[Tuple[int, int], float] = {}
    eqn_node: Dict[int, int] = {}        # raw eqn idx -> node idx

    tag_stack: List[Tuple[str, str, int]] = []   # (phase, block, layer)
    cur = ("", "", -1)

    state_in_vars: Set[Var] = set()
    if state_argnums:
        # map flattened invars back to positional args
        arg_leaf_counts = [len(jax.tree_util.tree_leaves(a))
                           for a in example_args]
        offset = 0
        spans = []
        for c in arg_leaf_counts:
            spans.append((offset, offset + c))
            offset += c
        for an in state_argnums:
            lo, hi = spans[an]
            state_in_vars.update(jaxpr.invars[lo:hi])

    state_readers: Set[int] = set()
    for raw_idx, eqn in enumerate(jaxpr.eqns):
        pname = eqn.primitive.name
        if pname == MARKER_NAME:
            # identity: alias out -> in, push/pop tag scope
            alias[eqn.outvars[0]] = resolve(eqn.invars[0])
            p = eqn.params
            if p["kind"] == "begin":
                tag_stack.append(cur)
                cur = (p["phase"] or cur[0], p["block"] or cur[1],
                       p["layer"] if p["layer"] >= 0 else cur[2])
            else:
                cur = tag_stack.pop() if tag_stack else ("", "", -1)
            continue

        node_idx = len(nodes)
        eqn_node[raw_idx] = node_idx
        flops, nbytes = _eqn_cost(eqn)
        out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars)
        nodes.append(KernelNode(
            idx=node_idx, name=pname, flops=flops, bytes_accessed=nbytes,
            out_bytes=out_b, phase=cur[0], block=cur[1], layer=cur[2],
            eqn_ids=(raw_idx,)))

        for v in eqn.invars:
            v = resolve(v)
            if not isinstance(v, jex_core.Var):
                continue
            if v in state_in_vars:
                state_readers.add(node_idx)
                if pname in _UPDATE_PRIMS and v is resolve(eqn.invars[0]):
                    state_in_vars.add(eqn.outvars[0])
            src = producer.get(v)
            if src is not None and src != node_idx:
                key = (src, node_idx)
                edges[key] = edges.get(key, 0.0) + _aval_bytes(v.aval)
        for v in eqn.outvars:
            producer[v] = node_idx

    # state writers: producers of outputs that correspond to carried state.
    state_writers: Set[int] = set()
    if state_argnums:
        # Convention: fn returns (state', ...) with state' matching the
        # state args' structure; the first len(state leaves) outvars.
        n_state_leaves = sum(
            len(jax.tree_util.tree_leaves(example_args[an]))
            for an in state_argnums)
        for v in jaxpr.outvars[:n_state_leaves]:
            v = resolve(v)
            if isinstance(v, jex_core.Var) and v in producer:
                state_writers.add(producer[v])

    graph = KernelGraph(nodes, edges, name=name)
    graph.validate()
    traced = TracedGraph(
        graph=graph, closed_jaxpr=closed,
        eqn_of_node={n.idx: n.eqn_ids for n in nodes},
        in_tree=in_tree, out_tree=out_tree,
        state_readers=state_readers, state_writers=state_writers)
    if fuse:
        fused = graph.fuse_elementwise()
        # remap state reader/writer sets through fusion
        old_to_new: Dict[int, int] = {}
        for n in fused.nodes:
            for e in n.eqn_ids:
                old_to_new[eqn_node[e]] = n.idx
        traced = dataclasses.replace(
            traced.with_graph(fused),
            state_readers={old_to_new[i] for i in state_readers},
            state_writers={old_to_new[i] for i in state_writers})
        # eqn_of_node must map to raw eqn ids (it already does via eqn_ids)
    return traced


def pin_nodes(graph: KernelGraph, node_ids: Set[int],
              device: int) -> KernelGraph:
    """Return a copy of the graph with the given nodes pinned to a device."""
    nodes = [dataclasses.replace(n, pinned=device) if n.idx in node_ids
             else n for n in graph.nodes]
    return KernelGraph(nodes, dict(graph.edges), name=graph.name)
