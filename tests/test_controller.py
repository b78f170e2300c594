"""Closed-loop autoscaling (serving/controller.py) + control-path
edges: the controller's DES decision loop (scale up under pressure,
down in troughs, hysteresis/cooldown, time-weighted billing), the
live-engine scale path (loss-free drain, bit-identical migrated
sessions, warm-up gating), sizing-history exactness, and failure
edges (t=0 apocalypse, draining the last group).
"""
import dataclasses

import numpy as np
import pytest

from conftest import random_dag
from repro.core.costmodel import CATALOG
from repro.serving.controller import (AutoscaleConfig, AutoscalePolicy,
                                      goodput_per_dollar)
from repro.serving.sizing import search_composition
from repro.serving.spec import DeploymentSpec
from repro.serving.workload import diurnal_trace, poisson_trace

SLOS = {"base": 2.0, "per_output_token": 0.02, "ttft": 0.5}
ANNEAL = 200


@pytest.fixture(scope="module")
def graph():
    return random_dag(24, seed=1)


def _ctl():
    return AutoscalePolicy(
        AutoscaleConfig(interval=0.05, window=0.2, cooldown=0.1,
                        warmup=0.05, queue_hi=0.5, queue_lo=0.15,
                        util_lo=0.6),
        inventory=[["a100", "l40s"], ["l40s"]])


@pytest.fixture(scope="module")
def elastic(graph):
    """(deployment, diurnal trace, static baseline result) shared by
    the controller tests — the spec is the static same-shape anchor."""
    spec = DeploymentSpec(groups=[["a100", "l40s"]],
                          router="jsed",
                          router_kwargs={"slo_shed": True},
                          slos=SLOS, budget=20.0, anneal_iters=ANNEAL)
    dep = spec.compile(graph)
    # peak demand ~3.4x the founding group's capacity; one full
    # diurnal cycle so the trough exercises scale-down
    rate = 2.0 * dep.cluster().capacity
    n = 3000
    trace = diurnal_trace(rate, n, seed=7, amplitude=0.7,
                          period=n / rate)
    return dep, trace, dep.simulate(trace)


# ===================================================================== #
# Controller configuration / binding
# ===================================================================== #
def test_autoscale_config_validation():
    with pytest.raises(ValueError, match="interval"):
        AutoscaleConfig(interval=0.0)
    with pytest.raises(ValueError, match="window"):
        AutoscaleConfig(interval=1.0, window=0.5)


def test_controller_requires_bind():
    ctl = _ctl()
    with pytest.raises(ValueError, match="bind"):
        ctl.begin(0.0)


def test_controller_rejects_second_deployment(graph):
    ctl = _ctl()
    d1 = DeploymentSpec(groups=[["l40s"]],
                        anneal_iters=ANNEAL).compile(graph)
    d2 = DeploymentSpec(groups=[["l40s"]],
                        anneal_iters=ANNEAL).compile(graph)
    ctl.bind(d1)
    ctl.bind(d1)                               # idempotent
    with pytest.raises(ValueError, match="already bound"):
        ctl.bind(d2)


# ===================================================================== #
# Closed loop on the DES backend
# ===================================================================== #
def test_controller_scales_up_under_pressure_and_down_in_trough(elastic):
    dep, trace, static = elastic
    ctl = _ctl()
    res = dep.simulate(trace, controller=ctl)
    kinds = {d.action for d in ctl.decisions}
    assert "up" in kinds, "no scale-up despite shed at the peak"
    assert "down" in kinds, "no scale-down despite the trough"
    # activating reserves under load must convert sheds into goodput
    assert res.shed < static.shed
    assert res.goodput > static.goodput
    # decisions respect the cooldown
    times = [d.time for d in ctl.decisions]
    assert all(b - a >= ctl.cfg.cooldown - 1e-9
               for a, b in zip(times, times[1:]))
    # reserves stay within the spec budget while active
    assert all(d.price_rate <= dep.spec.budget + 1e-9
               for d in ctl.decisions)


def test_controller_run_is_deterministic_and_replayable(elastic):
    dep, trace, _ = elastic
    ctl = _ctl()
    a = dep.simulate(trace, controller=ctl)
    first = list(ctl.decisions)
    # same policy object replayed on the same deployment: state resets
    b = dep.simulate(trace, controller=ctl)
    assert a.events == b.events and a.latencies == b.latencies
    assert first == ctl.decisions


def test_controller_billing_is_time_weighted(elastic):
    dep, trace, _ = elastic
    ctl = _ctl()
    res = dep.simulate(trace, controller=ctl)
    billed = ctl.billed_dollars()
    assert billed > 0.0
    # upper bound: everything (founders + the whole reserve pool)
    # provisioned for the whole run
    full_rate = dep.spec.price_rate + sum(
        CATALOG[n].price for g in ctl.inventory for n in g)
    assert billed <= full_rate * res.makespan / 3600.0 + 1e-9
    # lower bound: the founders alone for the whole run
    assert billed >= dep.spec.price_rate * res.makespan / 3600.0 - 1e-9
    # reserves that were never activated accrue nothing
    ctl2 = AutoscalePolicy(
        AutoscaleConfig(interval=0.05, window=0.2, shed_hi=10.0,
                        queue_hi=1e9, util_lo=-1.0),   # decide nothing
        inventory=[["l40s"]])
    res2 = dep.simulate(poisson_trace(1.0, 5, seed=0), controller=ctl2)
    assert not ctl2.decisions
    assert ctl2.billed_dollars() == pytest.approx(
        dep.spec.price_rate * res2.makespan / 3600.0)


def test_goodput_per_dollar_static_reduces_to_sizing_objective(elastic):
    _, _, static = elastic
    gpd = goodput_per_dollar(static)
    assert gpd == pytest.approx(
        static.slo_ok / (static.price_rate * static.makespan / 3600.0))


# ===================================================================== #
# Control-path edges
# ===================================================================== #
def test_failure_at_time_zero(graph):
    """A group that is dead before the first arrival: survivors take
    everything, nothing routes to the corpse, nothing crashes."""
    dep = DeploymentSpec(groups=[["a100", "l40s"], ["a100", "l40s"]],
                         anneal_iters=ANNEAL).compile(graph)
    trace = poisson_trace(rate=dep.cluster().capacity,
                          num_requests=60, seed=5)
    res = dep.simulate(trace, failures=[(0.0, 0)])
    assert res.completed == len(trace) and res.dropped == 0
    assert 0 not in res.assignments


def test_every_group_down_simultaneously(graph):
    """All groups dead at t=0: every request is shed (or dropped),
    none complete, and the DES terminates cleanly."""
    dep = DeploymentSpec(groups=[["a100", "l40s"], ["l40s"]],
                         anneal_iters=ANNEAL).compile(graph)
    trace = poisson_trace(rate=10.0, num_requests=30, seed=2)
    res = dep.simulate(trace, failures=[(0.0, 0), (0.0, 1)])
    assert res.completed == 0
    assert res.shed + res.dropped == len(trace)


def test_scale_remove_last_eligible_group_rejected(graph):
    dep = DeploymentSpec(groups=[["l40s"]],
                         anneal_iters=ANNEAL).compile(graph)
    with pytest.raises(ValueError, match="no eligible"):
        dep.scale(remove=[0], at=1.0)
    # scheduling the replacement FIRST makes the same drain legal —
    # provided its warm-up completes by the drain instant
    dep.scale(add=[["a100"]], at=0.0, warmup=0.5)
    dep.scale(remove=[0], at=1.0)
    trace = poisson_trace(rate=5.0, num_requests=40, seed=3)
    res = dep.simulate(trace)
    assert res.completed + res.shed + res.dropped == len(trace)
    # and a drain scheduled before the replacement is warm still raises
    dep2 = DeploymentSpec(groups=[["l40s"]],
                          anneal_iters=ANNEAL).compile(graph)
    dep2.scale(add=[["a100"]], at=0.0, warmup=2.0)
    with pytest.raises(ValueError, match="no eligible"):
        dep2.scale(remove=[0], at=1.0)


# ===================================================================== #
# Sizing history exactness (regression)
# ===================================================================== #
def test_sizing_history_counts_infeasible_iterations(graph):
    """Regression: infeasible mutations (budget/inventory reject the
    candidate) used to skip their history row, so plots and
    convergence checks silently mis-indexed.  With a one-template
    inventory, add/drop moves are always infeasible, yet history must
    still hold exactly iters + 1 rows."""
    inventory = {"l40s": 1}
    budget = CATALOG["l40s"].price + 0.01
    trace = poisson_trace(rate=20.0, num_requests=30, seed=1)
    sr = search_composition(inventory, budget, trace, graph,
                            iters=12, seed=0,
                            spec_kwargs={"slos": SLOS,
                                         "anneal_iters": 150})
    assert len(sr.history) == 13
    assert [row[0] for row in sr.history] == list(range(13))
    # best column never regresses
    bests = [row[2] for row in sr.history]
    assert bests == sorted(bests) or all(
        b2 >= b1 for b1, b2 in zip(bests, bests[1:]))


# ===================================================================== #
# Live-engine elasticity (real ServingEngines)
# ===================================================================== #
def _smoke_cfg():
    import repro.configs as configs
    return dataclasses.replace(configs.get_smoke("llama3_8b"),
                               dtype="float32")


def test_live_scale_drains_loss_free_bit_identical():
    """Draining a live engine mid-decode migrates every resident
    session (export_kv/import_kv) into survivors: zero drops, greedy
    tokens bit-identical to never having scaled, and the scaled-in
    engine is jit-primed before it becomes routable."""
    from repro.models import model as M
    from repro.serving.engine import Request, ServingEngine
    cfg = _smoke_cfg()
    params = M.init_params(cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 3, 9, 5)]

    def mk():
        return [Request(rid=i, prompt=p.copy(), max_new_tokens=8,
                        arrival=0.0) for i, p in enumerate(prompts)]

    singles = mk()
    ServingEngine(cfg, params, slots=4, max_len=32, sync_every=2) \
        .run(singles)
    want = [r.output for r in singles]

    ekw = {"slots": 2, "max_len": 32, "sync_every": 2}
    ld = DeploymentSpec(groups=[["h100"], ["l40s"]], arch="llama3_8b",
                        engine=ekw).compile().launch(cfg, params)
    ld.scale(add=[["a100"]], at=0.0)   # replacement first
    ld.scale(remove=[0], at=0.0)       # drain with sessions in flight
    split = mk()
    out = ld.run(split)
    assert [r.output for r in split] == want
    assert out["migrations"] >= 1 and out["wire_bytes"] > 0
    assert out["engine"]["completed"] == len(split)
    assert out["routable"] == [False, True, True]
    assert all(r.finished >= 0 for r in split), "dropped request"
    # migration must not restamp TTFT: the first token's stamp from
    # the source engine survives the move
    assert all(0 <= r.ttft <= r.finished for r in split)


def test_live_export_import_direct_bit_identical():
    """The migration primitives themselves: sessions.checkpoint
    mid-decode, sessions.restore on a fresh engine, finish — outputs
    match an unmigrated run."""
    from repro.models import model as M
    from repro.serving.engine import Request, ServingEngine
    cfg = _smoke_cfg()
    params = M.init_params(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (4, 7)]

    def mk():
        return [Request(rid=i, prompt=p.copy(), max_new_tokens=6,
                        arrival=0.0) for i, p in enumerate(prompts)]

    ref = mk()
    ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2) \
        .run(ref)
    want = [r.output for r in ref]

    src = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    dst = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    moved = mk()
    src.admit_batch(moved, 0.0)
    for _ in range(3):
        src.step(0.0)
    handoffs = src.sessions.checkpoint(0.0)
    assert not src._any_active(), "export left residents behind"
    for req, st in handoffs:
        assert st.nbytes > 0 and not st.done
        assert dst.sessions.restore(req, st, 0.0)
    while dst._any_active():
        dst.step(0.0)
    dst.sync(0.0)
    assert [r.output for r in moved] == want


def test_live_scale_validation():
    from repro.models import model as M
    cfg = _smoke_cfg()
    params = M.init_params(cfg)
    ekw = {"slots": 2, "max_len": 32, "sync_every": 2}
    ld = DeploymentSpec(groups=[["h100"]], arch="llama3_8b",
                        engine=ekw).compile().launch(cfg, params)
    with pytest.raises(ValueError, match="last routable"):
        ld.scale(remove=[0])
    with pytest.raises(ValueError, match="cannot remove"):
        ld.scale(remove=[3])
    pd = DeploymentSpec(groups=[["h100"], ["l40s"]], pd=True,
                        arch="llama3_8b", engine=ekw) \
        .compile().launch(cfg, params)
    with pytest.raises(ValueError, match="pd"):
        pd.scale(add=[["a100"]])
