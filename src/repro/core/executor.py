"""Disaggregated executor: run a jaxpr split across devices per a Plan.

This is the TPU/JAX analogue of the paper's GPU workers (§III-C).  The
plan's *stages* (maximal topological same-device kernel runs) are compiled
as independent jitted callables; values crossing a stage boundary onto a
different device are transferred explicitly (``jax.device_put``), which is
the runtime's ICI/DCN send-recv.  JAX's async dispatch overlaps those
transfers with compute on other stages/requests (pipeline.py).

Hot path (the *dispatch program*, built once per executable):

  * The per-request value environment is a flat Python **list** indexed by
    integer slots — no Var hashing on the critical path.  Slot indices for
    every stage's inputs/outputs are resolved at build time.
  * Plan stages that land on the same **physical** device are *fused* into
    one jitted callable (logical plan devices often alias one physical
    device — e.g. validation runs, or a 2-way plan on a 1-GPU host), which
    cuts per-request dispatch count to the number of physical-device
    alternations.
  * Cross-device transfers are issued **eagerly by the producer**: the
    moment a fused stage is dispatched, its exported values are
    ``device_put`` onto every consuming device.  With JAX async dispatch
    the send overlaps downstream compute — the consumer finds its inputs
    already resident (transfer prefetch).
  * Constants are placed onto every consuming device once at **build**
    time; weights (graph inputs consumed by a stage) are placed on first
    call and cached under a stable ``(arg slot, device index)`` key — the
    paper's selective weight replication: each device holds only the
    parameters its kernels touch, placed exactly once.

The legacy dict-environment walk (``init_env`` / ``run_stage`` /
``collect_outputs``) is retained as the *reference path*: parity tests and
straggler re-execution use it, and ``call_reference`` runs a whole request
through it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.extend.core as jex_core

from repro.core.analyzer import TracedGraph
from repro.core.marker import MARKER_NAME
from repro.core.planner import Plan, Stage

Var = Any


def _resolve_through_markers(jaxpr):
    """Alias map routing values through (dropped) marker equations."""
    alias: Dict[Var, Var] = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == MARKER_NAME:
            src = eqn.invars[0]
            while isinstance(src, jex_core.Var) and src in alias:
                src = alias[src]
            alias[eqn.outvars[0]] = src

    def resolve(v):
        while isinstance(v, jex_core.Var) and v in alias:
            v = alias[v]
        return v
    return resolve


def _jit_eqns(eqns: Sequence[Any], invars: Sequence[Var],
              outvars: Sequence[Var]):
    """Jit a callable executing ``eqns`` with the given signature.

    debug_info must be dropped: it describes the ORIGINAL jaxpr's arity,
    and jax asserts len(arg_names) == invars / len(result_paths) ==
    outvars on construction.
    """
    effects = frozenset().union(
        *[eqn.effects for eqn in eqns]) if eqns else frozenset()
    sub = jex_core.Jaxpr(
        constvars=[], invars=list(invars), outvars=list(outvars),
        eqns=list(eqns), effects=effects)
    return jax.jit(jex_core.jaxpr_as_fun(jex_core.ClosedJaxpr(sub, [])))


@dataclasses.dataclass
class CompiledStage:
    stage: Stage
    fn: Any                        # jitted callable
    invars: Tuple[Var, ...]        # external inputs, in call order
    outvars: Tuple[Var, ...]       # values this stage exports
    device: Any                    # jax.Device


@dataclasses.dataclass
class FusedStage:
    """One dispatch unit: a run of plan stages on one physical device."""
    idx: int
    stage_idxs: Tuple[int, ...]     # plan-stage indices folded in
    fn: Any                         # jitted callable over all member eqns
    device: Any                     # physical jax.Device (or None)
    in_slots: Tuple[int, ...]
    in_weight: Tuple[bool, ...]     # True -> graph input: cached placement
    out_slots: Tuple[int, ...]
    # (output position, destination device, destination slot): issued
    # eagerly right after dispatch — the transfer prefetch.
    transfers: Tuple[Tuple[int, Any, int], ...]


@dataclasses.dataclass
class DispatchProgram:
    """Indexed execution recipe: everything the hot loop needs, resolved
    to integer slots at build time."""
    num_slots: int
    arg_slots: Tuple[int, ...]              # slot per flattened invar
    const_template: Tuple[Tuple[int, Any], ...]   # (slot, placed value)
    fused: List[FusedStage]
    out_slots: Tuple[Optional[int], ...]    # per graph output; None=literal
    out_literals: Tuple[Any, ...]           # literal values (None-padded)


class StagedExecutable:
    """Callable that reproduces ``fn(*args)`` with disaggregated stages.

    ``device_map``: logical plan device id -> jax.Device.  On a real
    heterogeneous cluster these are devices of different types; in tests
    they are distinct host-platform devices, which exercises the same
    transfer paths.
    """

    def __init__(self, traced: TracedGraph, plan: Plan,
                 device_map: Sequence[Any]):
        self.traced = traced
        self.plan = plan
        self.device_map = list(device_map)
        # weight-placement cache keyed on STABLE (arg slot, device index)
        # pairs from the dispatch program — an id()-based key can alias
        # after GC reuses an address; slot indices never do.
        self._weight_cache: Dict[Tuple[int, int], Tuple[Any, Any]] = {}
        self.weight_places = 0          # device_puts of graph inputs
        self._build()
        self._build_program()

    # ------------------------------------------------------------------ #
    def _build(self) -> None:
        closed = self.traced.closed_jaxpr
        jaxpr = closed.jaxpr
        resolve = _resolve_through_markers(jaxpr)
        self._resolve = resolve

        # const vars behave like extra graph inputs
        self._const_env = dict(zip(jaxpr.constvars, closed.consts))

        graph_outs = [resolve(v) for v in jaxpr.outvars]
        graph_out_vars: Set[Var] = {
            v for v in graph_outs if isinstance(v, jex_core.Var)}

        # var -> producing stage (graph inputs/consts -> -1)
        producing_stage: Dict[Var, int] = {}
        stage_eqns: List[List[Any]] = []
        for st in self.plan.stages:
            eqns = []
            for e in st.eqn_ids:
                eqn = jaxpr.eqns[e]
                if eqn.primitive.name == MARKER_NAME:
                    continue
                new_invars = [resolve(v) for v in eqn.invars]
                eqns.append(eqn.replace(invars=new_invars))
            stage_eqns.append(eqns)
            for eqn in eqns:
                for v in eqn.outvars:
                    producing_stage[v] = st.idx

        # which stages consume each var
        consumers: Dict[Var, Set[int]] = {}
        for st, eqns in zip(self.plan.stages, stage_eqns):
            for eqn in eqns:
                for v in eqn.invars:
                    if isinstance(v, jex_core.Var):
                        consumers.setdefault(v, set()).add(st.idx)

        self.stages: List[CompiledStage] = []
        for st, eqns in zip(self.plan.stages, stage_eqns):
            defined: Set[Var] = set()
            ext: List[Var] = []
            seen_ext: Set[Var] = set()
            for eqn in eqns:
                for v in eqn.invars:
                    if (isinstance(v, jex_core.Var) and v not in defined
                            and v not in seen_ext):
                        ext.append(v)
                        seen_ext.add(v)
                for v in eqn.outvars:
                    defined.add(v)
            outs = [v for eqn in eqns for v in eqn.outvars
                    if (consumers.get(v, set()) - {st.idx})
                    or v in graph_out_vars]
            fn = _jit_eqns(eqns, ext, outs)
            dev = self.device_map[st.device] if self.device_map else None
            self.stages.append(CompiledStage(
                stage=st, fn=fn, invars=tuple(ext), outvars=tuple(outs),
                device=dev))

        self._graph_outs = graph_outs
        self._graph_out_vars = graph_out_vars
        self._invars = list(jaxpr.invars)
        self._stage_eqns = stage_eqns

    # ------------------------------------------------------------------ #
    def _build_program(self) -> None:
        """Compile the indexed dispatch program (see module docstring)."""
        # --- physical-device fusion groups ---------------------------- #
        groups: List[List[int]] = []
        for i, cs in enumerate(self.stages):
            if groups and self.stages[groups[-1][-1]].device is cs.device:
                groups[-1].append(i)
            else:
                groups.append([i])

        # interned physical devices -> stable small integer ids
        # (_dev_index is the single intern point: _place_arg cache keys
        # must match the program's const/transfer slot keys)
        self._devices: List[Any] = []
        dev_id = self._dev_index

        # --- slot allocation ------------------------------------------ #
        slot_of: Dict[Var, int] = {}
        n_slots = 0

        def alloc(v: Var) -> int:
            nonlocal n_slots
            if v not in slot_of:
                slot_of[v] = n_slots
                n_slots += 1
            return slot_of[v]

        arg_slots = tuple(alloc(v) for v in self._invars)
        const_slot = {v: alloc(v) for v in self._const_env}
        self._const_slot = const_slot

        # group-level signatures
        group_dev = [self.stages[g[0]].device for g in groups]
        group_of_stage = {s: gi for gi, g in enumerate(groups) for s in g}
        g_eqns = [[e for s in g for e in self._stage_eqns[s]]
                  for g in groups]
        g_defined = [set(v for eqn in eqns for v in eqn.outvars)
                     for eqns in g_eqns]
        producer_group: Dict[Var, int] = {}
        for gi, dset in enumerate(g_defined):
            for v in dset:
                producer_group[v] = gi
        # consumers at group granularity
        g_consumers: Dict[Var, Set[int]] = {}
        for gi, eqns in enumerate(g_eqns):
            for eqn in eqns:
                for v in eqn.invars:
                    if isinstance(v, jex_core.Var):
                        g_consumers.setdefault(v, set()).add(gi)

        # exported values + their slots (allocated in group order)
        g_ext: List[List[Var]] = []
        g_outs: List[List[Var]] = []
        for gi, eqns in enumerate(g_eqns):
            defined: Set[Var] = set()
            ext: List[Var] = []
            seen: Set[Var] = set()
            for eqn in eqns:
                for v in eqn.invars:
                    if (isinstance(v, jex_core.Var) and v not in defined
                            and v not in seen):
                        ext.append(v)
                        seen.add(v)
                for v in eqn.outvars:
                    defined.add(v)
            outs = [v for eqn in eqns for v in eqn.outvars
                    if (g_consumers.get(v, set()) - {gi})
                    or v in self._graph_out_vars]
            g_ext.append(ext)
            g_outs.append(outs)
            for v in outs:
                alloc(v)

        # transfer slots: one per (exported var, consuming device) pair
        # when the consumer group sits on a different physical device.
        xfer_slot: Dict[Tuple[Var, int], int] = {}
        for v, cons in g_consumers.items():
            pg = producer_group.get(v)
            if pg is None:
                continue            # graph input / const: placed, not sent
            for gi in cons:
                if gi == pg or group_dev[gi] is group_dev[pg]:
                    continue
                key = (v, dev_id(group_dev[gi]))
                if key not in xfer_slot:
                    slot_of_key = n_slots
                    n_slots += 1
                    xfer_slot[key] = slot_of_key

        # consts: place each onto every consuming group's device at build
        const_template: List[Tuple[int, Any]] = []
        const_dev_slot: Dict[Tuple[Var, int], int] = {}
        for v, val in self._const_env.items():
            const_template.append((const_slot[v], val))
            for gi in g_consumers.get(v, ()):
                dev = group_dev[gi]
                if dev is None:
                    continue
                key = (v, dev_id(dev))
                if key not in const_dev_slot:
                    const_dev_slot[key] = n_slots
                    const_template.append(
                        (n_slots, jax.device_put(val, dev)))
                    n_slots += 1

        # --- fused stage records -------------------------------------- #
        fused: List[FusedStage] = []
        for gi, g in enumerate(groups):
            dev = group_dev[gi]
            in_slots: List[int] = []
            in_weight: List[bool] = []
            for v in g_ext[gi]:
                if v in self._const_env:
                    if dev is not None:
                        in_slots.append(const_dev_slot[(v, dev_id(dev))])
                        in_weight.append(False)     # pre-placed at build
                    else:
                        in_slots.append(const_slot[v])
                        in_weight.append(False)
                elif v not in producer_group:
                    # graph input (weight / activation argument)
                    in_slots.append(slot_of[v])
                    in_weight.append(True)
                else:
                    pg = producer_group[v]
                    if group_dev[pg] is dev:
                        in_slots.append(slot_of[v])
                    else:
                        in_slots.append(xfer_slot[(v, dev_id(dev))])
                    in_weight.append(False)
            out_slots = tuple(slot_of[v] for v in g_outs[gi])
            transfers: List[Tuple[int, Any, int]] = []
            for pos, v in enumerate(g_outs[gi]):
                dests: Set[int] = set()
                for ci in g_consumers.get(v, ()):
                    if ci != gi and group_dev[ci] is not dev:
                        dests.add(dev_id(group_dev[ci]))
                for di in sorted(dests):
                    transfers.append(
                        (pos, self._devices[di], xfer_slot[(v, di)]))
            fused.append(FusedStage(
                idx=gi, stage_idxs=tuple(g), device=dev,
                fn=_jit_eqns(g_eqns[gi], g_ext[gi], g_outs[gi]),
                in_slots=tuple(in_slots), in_weight=tuple(in_weight),
                out_slots=tuple(out_slots), transfers=tuple(transfers)))

        out_slots: List[Optional[int]] = []
        out_literals: List[Any] = []
        for v in self._graph_outs:
            if isinstance(v, jex_core.Var):
                out_slots.append(alloc(v))
                out_literals.append(None)
            else:
                out_slots.append(None)
                out_literals.append(v.val)

        self.program = DispatchProgram(
            num_slots=n_slots, arg_slots=arg_slots,
            const_template=tuple(const_template), fused=fused,
            out_slots=tuple(out_slots), out_literals=tuple(out_literals))

    # ------------------------------------------------------------------ #
    # Indexed fast path
    # ------------------------------------------------------------------ #
    def init_slots(self, *args, **kwargs) -> List[Any]:
        """Seed the flat slot environment for one request."""
        flat, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        if in_tree != self.traced.in_tree:
            raise TypeError(
                f"argument structure {in_tree} != traced "
                f"{self.traced.in_tree}")
        slots: List[Any] = [None] * self.program.num_slots
        for sl, val in self.program.const_template:
            slots[sl] = val
        for sl, val in zip(self.program.arg_slots, flat):
            slots[sl] = val
        return slots

    def _place_arg(self, slot: int, val: Any, dev) -> Any:
        """Place a graph input on ``dev``, cached per (slot, device)."""
        key = (slot, self._dev_index(dev))
        cached = self._weight_cache.get(key)
        if cached is not None and cached[0] is val:
            return cached[1]
        placed = jax.device_put(val, dev)
        self.weight_places += 1
        self._weight_cache[key] = (val, placed)
        return placed

    def _dev_index(self, dev) -> int:
        for i, d in enumerate(self._devices):
            if d is dev:
                return i
        self._devices.append(dev)
        return len(self._devices) - 1

    def run_unit(self, slots: List[Any], unit_idx: int,
                 device_override: Any = None) -> int:
        """Dispatch one fused unit (async); returns #transfers issued.

        ``device_override`` reruns the unit on a different device — used
        by straggler mitigation (stage functions are pure, so duplicate
        execution is always safe).
        """
        fs = self.program.fused[unit_idx]
        dev = device_override if device_override is not None else fs.device
        if device_override is None:
            ins = []
            for sl, w in zip(fs.in_slots, fs.in_weight):
                v = slots[sl]
                ins.append(self._place_arg(sl, v, dev)
                           if (w and dev is not None) else v)
        else:
            # override device: everything must move; weights go through
            # the cache (the fallback device keeps its own copies).
            ins = []
            for sl, w in zip(fs.in_slots, fs.in_weight):
                v = slots[sl]
                ins.append(self._place_arg(sl, v, dev) if w
                           else jax.device_put(v, dev))
        outs = fs.fn(*ins)
        if device_override is not None and fs.device is not None \
                and device_override is not fs.device:
            # restore the slot invariant "exports live on the producing
            # unit's device": later same-device consumers read these
            # slots directly (no transfer slot), and a fused fn with
            # inputs committed to two devices is a jit error.
            outs = [jax.device_put(o, fs.device) for o in outs]
        for sl, val in zip(fs.out_slots, outs):
            slots[sl] = val
        # transfer prefetch: push exports toward their consumers NOW so
        # the send overlaps downstream dispatch/compute.
        for pos, ddev, dsl in fs.transfers:
            slots[dsl] = jax.device_put(outs[pos], ddev)
        return len(fs.transfers)

    def collect_slots(self, slots: List[Any]):
        results = []
        for sl, lit in zip(self.program.out_slots,
                           self.program.out_literals):
            results.append(slots[sl] if sl is not None else lit)
        return jax.tree_util.tree_unflatten(self.traced.out_tree, results)

    def unit_outputs(self, slots: List[Any], unit_idx: int) -> List[Any]:
        fs = self.program.fused[unit_idx]
        return [slots[sl] for sl in fs.out_slots]

    @property
    def num_units(self) -> int:
        return len(self.program.fused)

    def __call__(self, *args, **kwargs):
        slots = self.init_slots(*args, **kwargs)
        for i in range(len(self.program.fused)):
            self.run_unit(slots, i)
        return self.collect_slots(slots)

    # ------------------------------------------------------------------ #
    # Reference path (legacy dict environment; per-plan-stage dispatch)
    # ------------------------------------------------------------------ #
    def _place(self, var: Var, val: Any, dev, weight: bool) -> Any:
        if dev is None:
            return val
        if weight:
            return self._place_arg(self._ref_slot(var), val, dev)
        return jax.device_put(val, dev)

    def _ref_slot(self, var: Var) -> int:
        s = getattr(self, "_ref_slot_map", None)
        if s is None:
            s = {v: sl for v, sl in zip(self._invars,
                                        self.program.arg_slots)}
            s.update(self._const_slot)
            self._ref_slot_map = s
        return s[var]

    def init_env(self, *args, **kwargs) -> Dict[Var, Any]:
        """Seed the (reference-path) value environment for one request."""
        flat, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        if in_tree != self.traced.in_tree:
            raise TypeError(
                f"argument structure {in_tree} != traced "
                f"{self.traced.in_tree}")
        env: Dict[Var, Any] = dict(self._const_env)
        for var, val in zip(self._invars, flat):
            env[var] = val
        return env

    def run_stage(self, env: Dict[Var, Any], stage_idx: int,
                  device_override: Any = None) -> None:
        """Execute one plan stage (async dispatch); mutates env in place."""
        cs = self.stages[stage_idx]
        dev = device_override if device_override is not None else cs.device
        graph_inputs = self._graph_input_set
        ins = []
        for v in cs.invars:
            ins.append(self._place(v, env[v], dev,
                                   weight=v in graph_inputs))
        outs = cs.fn(*ins)
        for v, val in zip(cs.outvars, outs):
            env[v] = val

    def collect_outputs(self, env: Dict[Var, Any]):
        results = []
        for v in self._graph_outs:
            if isinstance(v, jex_core.Var):
                results.append(env[v])
            else:                                   # Literal
                results.append(v.val)
        return jax.tree_util.tree_unflatten(self.traced.out_tree, results)

    @property
    def _graph_input_set(self) -> Set[Var]:
        s = getattr(self, "_gi_cache", None)
        if s is None:
            s = set(self._invars) | set(self._const_env)
            self._gi_cache = s
        return s

    def call_reference(self, *args, **kwargs):
        """Run a request through the legacy per-stage dict walk."""
        env = self.init_env(*args, **kwargs)
        for i in range(len(self.stages)):
            self.run_stage(env, i)
        return self.collect_outputs(env)

    # ------------------------------------------------------------------ #
    def run_async(self, *args, **kwargs):
        """Same as __call__ — JAX dispatch is already asynchronous; the
        returned arrays are futures until blocked on."""
        return self(*args, **kwargs)

    def stage_summary(self) -> str:
        lines = []
        for cs in self.stages:
            st = cs.stage
            lines.append(
                f"  stage {st.idx:3d} dev={self.plan.devices[st.device]:<10}"
                f" kernels={len(st.node_ids):4d}"
                f" t={st.compute_time * 1e6:9.1f}us"
                f" recv={st.recv_bytes / 1e6:8.3f}MB"
                f" send={st.send_bytes / 1e6:8.3f}MB")
        lines.append(
            f"  fused: {len(self.stages)} stages -> "
            f"{len(self.program.fused)} dispatch units")
        return "\n".join(lines)


def host_device_map(plan: Plan) -> Optional[List[Any]]:
    """Logical plan device *i* -> ``jax.devices()[i]``: the map a
    serving caller hands :func:`build_executable`.  None where the host
    has fewer devices than the plan (single-device validation mode)."""
    devs = jax.devices()
    n = len(plan.devices)
    return list(devs[:n]) if len(devs) >= n else None


def build_executable(traced: TracedGraph, plan: Plan,
                     device_map: Optional[Sequence[Any]] = None
                     ) -> StagedExecutable:
    """Compile a traced graph + plan into a disaggregated executable.

    When ``device_map`` is None all stages run on the default device —
    useful for validating the stage decomposition itself.  Serving
    callers pass :func:`host_device_map`, which puts logical device *i*
    on the host's device *i*.
    """
    if device_map is None:
        d = jax.devices()[0]
        device_map = [d] * (max(plan.labels) + 1 if plan.labels else 1)
    return StagedExecutable(traced, plan, device_map)
