"""Declarative deployment API: spec validation + round trip, shim
parity (legacy entry points == Deployment, bit-identical event logs),
replica-level fault injection, autoscaling drain/warm-up, cost-model
calibration, composition search, and the real-engine launch backend.
"""
import dataclasses
import json

import numpy as np
import pytest

from conftest import random_dag
import repro.configs as configs
from repro.core.costmodel import (CATALOG, Calibration, ScaledDevice,
                                  calibrate)
from repro.core.simulator import ControlEvent, Interconnect
from repro.serving.cluster import TesseraCluster
from repro.serving.router import (ROUTERS, JSEDRouter, LeastLoadedRouter,
                                  PDRouter, RoundRobinRouter, Router,
                                  make_router, register_router)
from repro.serving.sizing import (group_templates, search_composition,
                                  uniform_composition)
from repro.serving.spec import Deployment, DeploymentSpec
from repro.serving.workload import poisson_trace

GROUPS = [["h100", "rtxpro6000"], ["a100", "l40s"], ["a100", "l40s"]]
ANNEAL = 200


def pd_graph(n: int = 24, seed: int = 2):
    """Random DAG, first half prefill / second half decode (the shape
    request_graph produces from real models)."""
    g = random_dag(n, seed=seed)
    nodes = [dataclasses.replace(
        node, phase="prefill" if node.idx < n // 2 else "decode")
        for node in g.nodes]
    g2 = type(g)(nodes, dict(g.edges), name=g.name + ".dep")
    g2.validate()
    return g2


@pytest.fixture(scope="module")
def graph():
    return pd_graph()


@pytest.fixture(scope="module")
def legacy_cluster(graph):
    return TesseraCluster(graph, GROUPS, anneal_iters=ANNEAL)


@pytest.fixture(scope="module")
def deployment(graph):
    return DeploymentSpec(groups=GROUPS,
                          anneal_iters=ANNEAL).compile(graph)


def loaded_trace(deployment, n=150, load=1.5, seed=5):
    return poisson_trace(rate=load * deployment.cluster().capacity,
                         num_requests=n, seed=seed)


def loaded_trace_from(cluster, n=150, load=1.5, seed=5):
    return poisson_trace(rate=load * cluster.capacity,
                         num_requests=n, seed=seed)


# ===================================================================== #
# Spec validation + serialization
# ===================================================================== #
def test_spec_validation_errors():
    with pytest.raises(ValueError, match="unknown device"):
        DeploymentSpec(groups=[["v100"]])
    with pytest.raises(ValueError, match="non-empty"):
        DeploymentSpec(groups=[])
    with pytest.raises(ValueError, match="non-empty"):
        DeploymentSpec(groups=[[]])
    with pytest.raises(ValueError, match="unknown router"):
        DeploymentSpec(groups=[["h100"]], router="oracle")
    with pytest.raises(ValueError, match="requires pd"):
        DeploymentSpec(groups=[["h100"]], kv_chunks=4)
    with pytest.raises(ValueError, match="unknown slo keys"):
        DeploymentSpec(groups=[["h100"]], slos={"p99": 1.0})
    with pytest.raises(ValueError, match="positive deadline"):
        DeploymentSpec(groups=[["h100"]], slos={"base": 0.0})
    with pytest.raises(ValueError, match="unknown interconnect keys"):
        DeploymentSpec(groups=[["h100"]], interconnect={"bw_gbps": 1})
    with pytest.raises(ValueError, match="src-dst"):
        DeploymentSpec(groups=[["h100"]],
                       interconnect={"bw": {"a-b": 1e9}})
    with pytest.raises(ValueError, match="initial_policy"):
        DeploymentSpec(groups=[["h100"]], initial_policy="balanced")
    with pytest.raises(ValueError, match="over the"):
        DeploymentSpec(groups=[["b200", "b200"]], budget=5.0)
    with pytest.raises(ValueError, match="unknown engine keys"):
        DeploymentSpec(groups=[["h100"]], engine={"max_length": 256})
    # within budget is fine
    DeploymentSpec(groups=[["a100", "l40s"]], budget=5.0)


def test_spec_json_round_trip(tmp_path):
    spec = DeploymentSpec(
        groups=GROUPS, arch="llama3_8b", base_prompt=512, base_output=64,
        router="pd_split",
        router_kwargs={"prefill_pool": [0], "decode_pool": [1, 2],
                       "session_affinity": True, "affinity_break": 0.1},
        pd=True, kv_chunks=8,
        interconnect={"default_bw": 50e9, "bw": {"0-1": 200e9}},
        slos={"base": 2.0, "per_output_token": 0.02, "ttft": 0.3},
        budget=20.0,
        calibration={"ttft_wall_over_model": 3.0,
                     "tpot_wall_over_model": 2.0},
        monitor={"window": 0.05}, anneal_iters=300,
        engine={"slots": 2, "max_len": 32})
    assert DeploymentSpec.from_json(spec.to_json()) == spec
    path = tmp_path / "spec.json"
    spec.save(path)
    assert DeploymentSpec.load(path) == spec
    with pytest.raises(ValueError, match="unknown DeploymentSpec"):
        DeploymentSpec.from_json(json.dumps(
            {"groups": [["h100"]], "routr": "jsed"}))
    ic = spec.make_interconnect()
    assert ic.bandwidth(0, 1) == 200e9 and ic.bandwidth(1, 2) == 50e9


def test_spec_price_rate():
    spec = DeploymentSpec(groups=[["h100", "rtxpro6000"], ["l40s"]])
    assert spec.price_rate == pytest.approx(
        CATALOG["h100"].price + CATALOG["rtxpro6000"].price
        + CATALOG["l40s"].price)


# ===================================================================== #
# Shim parity: legacy entry points == Deployment, bit-identical
# ===================================================================== #
@pytest.mark.parametrize("router_name", ["round_robin", "least_loaded",
                                         "jsed"])
def test_colocated_parity_all_routers(graph, legacy_cluster, deployment,
                                      router_name):
    trace = loaded_trace(deployment)
    old = legacy_cluster.simulate(trace, make_router(router_name))
    spec = DeploymentSpec(groups=GROUPS, router=router_name,
                          anneal_iters=ANNEAL)
    new = spec.compile(graph).simulate(trace)
    assert old.events == new.events
    assert old.latencies == new.latencies
    assert old.ttfts == new.ttfts
    assert old.assignments == new.assignments
    assert old.makespan == new.makespan


@pytest.mark.parametrize("kv_chunks", [1, 4])
def test_pd_parity_split_router(graph, legacy_cluster, kv_chunks):
    trace = loaded_trace_from(legacy_cluster)
    kw = dict(prefill_frac=0.34, max_kv_lag=1.0)
    old = legacy_cluster.simulate_pd(
        trace, PDRouter(interconnect=Interconnect(),
                        kv_chunks=kv_chunks, **kw),
        kv_chunks=kv_chunks)
    spec = DeploymentSpec(groups=GROUPS, router="pd_split",
                          router_kwargs=kw, pd=True,
                          kv_chunks=kv_chunks, anneal_iters=ANNEAL)
    new = spec.compile(graph).simulate(trace)
    assert old.events == new.events
    assert old.ttfts == new.ttfts
    assert old.transfers == new.transfers
    assert old.peak_kv_bytes == new.peak_kv_bytes
    assert old.transfer_seconds == new.transfer_seconds


def test_pd_parity_colocated_router_through_pd_path(graph,
                                                    legacy_cluster):
    """simulate_cluster_pd with an int-returning router must equal the
    plain colocated path (the two legacy loops collapsed into one)."""
    trace = loaded_trace_from(legacy_cluster)
    a = legacy_cluster.simulate(trace, JSEDRouter())
    b = legacy_cluster.simulate_pd(trace, JSEDRouter())
    assert a.events == b.events
    assert a.latencies == b.latencies


# ===================================================================== #
# Fault injection
# ===================================================================== #
def test_failure_reroutes_in_flight(deployment):
    trace = loaded_trace(deployment)
    t_fail = trace[70].arrival
    res = deployment.simulate(trace, failures=[(t_fail, 0)])
    assert res.completed == len(trace)
    assert res.rerouted > 0
    assert res.dropped == 0
    ordered = sorted(trace, key=lambda r: (r.arrival, r.rid))
    after = [a for r, a in zip(ordered, res.assignments)
             if r.arrival > t_fail]
    assert after and 0 not in after, \
        "router must stop sending work to a dead group"
    # recovery is visible but bounded: everything still completes on
    # the survivors, at higher latency than the no-failure run
    base = deployment.simulate(trace)
    assert res.mean_latency >= base.mean_latency


def test_failure_of_every_group_drops_and_sheds(deployment):
    trace = loaded_trace(deployment, n=60)
    t_fail = trace[30].arrival
    res = deployment.simulate(
        trace, failures=[(t_fail, g) for g in range(3)])
    # arrivals after the apocalypse are shed; in-flight victims with
    # no survivor to re-route to are dropped
    assert res.shed > 0
    assert res.completed + res.shed + res.dropped == len(trace)
    assert res.completed == len([r for r in res.assignments if r >= 0])


def test_failure_event_log_deterministic(deployment):
    trace = loaded_trace(deployment)
    t_fail = trace[50].arrival
    a = deployment.simulate(trace, failures=[(t_fail, 1)])
    b = deployment.simulate(trace, failures=[(t_fail, 1)])
    assert a.events == b.events and a.latencies == b.latencies
    assert a.rerouted == b.rerouted


def test_failure_in_pd_deployment_recovers(graph):
    """Killing a decode-pool group mid-trace under phase-split routing:
    victims re-route (their resident-KV intervals end at the failure,
    not at their phantom finish), the pool collapses onto survivors,
    and everything completes."""
    spec = DeploymentSpec(groups=GROUPS, router="pd_split",
                          router_kwargs={"prefill_pool": [0],
                                         "decode_pool": [1, 2],
                                         "max_kv_lag": 1.0},
                          pd=True, anneal_iters=ANNEAL)
    dep = spec.compile(graph)
    trace = loaded_trace(dep)
    base = dep.simulate(trace)
    t_fail = trace[70].arrival
    res = dep.simulate(trace, failures=[(t_fail, 1)])
    assert res.completed == len(trace) and res.dropped == 0
    assert res.rerouted > 0
    ordered = sorted(trace, key=lambda r: (r.arrival, r.rid))
    after = [a for r, a in zip(ordered, res.assignments)
             if r.arrival > t_fail]
    assert 1 not in after
    # dead-group residency is truncated at the failure instant, so the
    # failure cannot inflate peak resident KV past base + one re-routed
    # handoff's worth of double-booking window
    assert res.peak_kv_bytes <= base.peak_kv_bytes * 3 + 1e-9


def test_control_event_validation(deployment):
    with pytest.raises(ValueError, match="unknown control-event"):
        ControlEvent(0.0, "explode", 0)
    with pytest.raises(ValueError, match="cannot fail group"):
        deployment.simulate(loaded_trace(deployment, n=10),
                            failures=[(0.0, 9)])


# ===================================================================== #
# Autoscaling: drain + warm-up
# ===================================================================== #
def test_drain_is_loss_free(graph):
    spec = DeploymentSpec(groups=GROUPS, anneal_iters=ANNEAL)
    dep = spec.compile(graph)
    trace = loaded_trace(dep)
    t_mid = trace[70].arrival
    dep.scale(remove=[2], at=t_mid)
    res = dep.simulate(trace)
    assert res.completed == len(trace), "drain dropped accepted requests"
    assert res.dropped == 0 and res.shed == 0
    ordered = sorted(trace, key=lambda r: (r.arrival, r.rid))
    after = [a for r, a in zip(ordered, res.assignments)
             if r.arrival > t_mid]
    assert 2 not in after, "drained group took new requests"
    # requests routed to group 2 before the drain still completed there
    before = [a for r, a in zip(ordered, res.assignments)
              if r.arrival <= t_mid]
    assert 2 in before


def test_warmup_gates_added_group(graph):
    spec = DeploymentSpec(groups=GROUPS[:2], anneal_iters=ANNEAL)
    dep = spec.compile(graph)
    trace = loaded_trace(dep, n=200)
    t_mid = trace[60].arrival
    warm = 0.5 * (trace[-1].arrival - t_mid)
    dep.scale(add=[["h100", "rtxpro6000"]], at=t_mid, warmup=warm)
    res = dep.simulate(trace)
    ordered = sorted(trace, key=lambda r: (r.arrival, r.rid))
    pre_warm = [a for r, a in zip(ordered, res.assignments)
                if r.arrival <= t_mid + warm]
    post_warm = [a for r, a in zip(ordered, res.assignments)
                 if r.arrival > t_mid + warm]
    assert 2 not in pre_warm, "group served before its warm-up finished"
    assert 2 in post_warm, "warmed-up group never became eligible"
    assert res.dropped == 0
    assert dep.price_rate > spec.price_rate


def test_scale_validation(graph):
    dep = DeploymentSpec(groups=GROUPS, anneal_iters=ANNEAL).compile(graph)
    with pytest.raises(ValueError, match="cannot remove"):
        dep.scale(remove=[7])
    with pytest.raises(ValueError, match="unknown device"):
        dep.scale(add=[["v100"]])


def test_pd_pool_collapses_onto_survivors(graph):
    """Draining the whole prefill pool must not strand the decode pool:
    survivors serve both phases colocated, loss-free."""
    spec = DeploymentSpec(groups=GROUPS, router="pd_split",
                          router_kwargs={"prefill_pool": [0],
                                         "decode_pool": [1, 2],
                                         "max_kv_lag": 1.0},
                          pd=True, anneal_iters=ANNEAL)
    dep = spec.compile(graph)
    trace = loaded_trace(dep)
    t_mid = trace[70].arrival
    dep.scale(remove=[0], at=t_mid)
    res = dep.simulate(trace)
    assert res.completed == len(trace) and res.dropped == 0
    ordered = sorted(trace, key=lambda r: (r.arrival, r.rid))
    after = [a for r, a in zip(ordered, res.assignments)
             if r.arrival > t_mid]
    assert after and 0 not in after


# ===================================================================== #
# Cost-model calibration
# ===================================================================== #
def test_calibrate_parses_line_dict_and_aliases():
    line = ('CALIBRATION {"modeled_ttft_s": 1e-4, "wall_ttft_s": 3e-4, '
            '"ttft_wall_over_model": 3.0, "tpot_wall_over_model": 2.0}')
    for payload in (line, json.loads(line[len("CALIBRATION"):]),
                    {"prefill_scale": 3.0, "decode_scale": 2.0}):
        cal = calibrate(payload)
        assert cal.prefill_scale == 3.0 and cal.decode_scale == 2.0
        assert cal.scale == pytest.approx((3.0 * 2.0) ** 0.5)
    with pytest.raises(ValueError, match="neither"):
        calibrate({"foo": 1.0})
    with pytest.raises(ValueError, match="positive"):
        Calibration(prefill_scale=-1.0)


def test_scaled_device_is_phase_aware(graph):
    cal = Calibration(prefill_scale=3.0, decode_scale=2.0)
    dev = ScaledDevice(CATALOG["h100"], cal)
    pre = next(n for n in graph.nodes if n.phase == "prefill")
    dec = next(n for n in graph.nodes if n.phase == "decode")
    base = CATALOG["h100"]
    assert dev.kernel_time(pre) == pytest.approx(base.kernel_time(pre) * 3)
    assert dev.kernel_time(dec) == pytest.approx(base.kernel_time(dec) * 2)
    assert dev.transfer_time(1e6, CATALOG["a100"]) == \
        base.transfer_time(1e6, CATALOG["a100"])
    assert dev.name != base.name        # distinct plan-cache identity
    assert dev.price == base.price


def test_spec_calibration_slows_des(graph):
    trace = poisson_trace(rate=500.0, num_requests=60, seed=3)
    plain = DeploymentSpec(groups=GROUPS[:1], anneal_iters=ANNEAL)
    cal = DeploymentSpec(groups=GROUPS[:1], anneal_iters=ANNEAL,
                         calibration={"ttft_wall_over_model": 4.0,
                                      "tpot_wall_over_model": 4.0})
    r_plain = plain.compile(graph).simulate(trace)
    r_cal = cal.compile(graph).simulate(trace)
    assert r_cal.mean_latency > 2.0 * r_plain.mean_latency


# ===================================================================== #
# Router registry
# ===================================================================== #
def test_register_router_roundtrip(graph):
    class FirstRouter(Router):
        name = "always_first"

        def route(self, req, replicas, now):
            return 0

    try:
        register_router(FirstRouter)
        assert isinstance(make_router("always_first"), FirstRouter)
        spec = DeploymentSpec(groups=GROUPS[:2], router="always_first",
                              anneal_iters=ANNEAL)
        res = spec.compile(graph).simulate(
            poisson_trace(rate=100.0, num_requests=20, seed=1))
        assert set(res.assignments) == {0}
    finally:
        ROUTERS.pop("always_first", None)
    with pytest.raises(ValueError, match="distinct class-level"):
        register_router(Router)


def test_routers_skip_ineligible():
    class Stub:
        def __init__(self, eligible=True):
            self.eligible = eligible

        def backlog(self, now):
            return 0.0

        def predicted_service(self, req):
            return 1.0

    reps = [Stub(False), Stub(True), Stub(False)]
    req = None
    rr = RoundRobinRouter()
    assert [rr.route(req, reps, 0.0) for _ in range(3)] == [1, 1, 1]
    assert LeastLoadedRouter().route(req, reps, 0.0) == 1
    none = [Stub(False)]
    assert RoundRobinRouter().route(req, none, 0.0) == -1
    assert LeastLoadedRouter().route(req, none, 0.0) == -1


# ===================================================================== #
# Composition search (sizing)
# ===================================================================== #
INVENTORY = {"h100": 2, "rtxpro6000": 2, "a100": 3, "l40s": 4}
BUDGET = 9.0


def test_group_templates_respect_inventory():
    ts = group_templates({"h100": 1, "l40s": 2})
    assert ("h100",) in ts and ("l40s", "l40s") in ts
    assert ("h100", "h100") not in ts   # only one in stock
    with pytest.raises(ValueError, match="unknown device"):
        group_templates({"v100": 1})


def test_uniform_composition_fits_budget(graph):
    comp = uniform_composition(INVENTORY, BUDGET, graph,
                               anneal_iters=150)
    assert comp and len({tuple(g) for g in comp}) == 1
    price = sum(CATALOG[n].price for g in comp for n in g)
    assert price <= BUDGET + 1e-9


def test_search_composition_beats_or_matches_seed(graph):
    trace = poisson_trace(rate=2000.0, num_requests=80, seed=9)
    slos = {"base": 2.0, "per_output_token": 0.02, "ttft": 0.3}
    sr = search_composition(
        INVENTORY, BUDGET, trace, graph, iters=10, seed=0,
        spec_kwargs={"slos": slos, "anneal_iters": 150})
    assert sr.score >= sr.seed_score
    assert sr.spec.price_rate <= BUDGET + 1e-9
    from collections import Counter
    used = Counter(n for g in sr.spec.groups for n in g)
    assert all(used[n] <= INVENTORY[n] for n in used)
    assert sr.evals >= 1 and len(sr.history) >= 1
    # deterministic in (inventory, budget, trace, seed)
    sr2 = search_composition(
        INVENTORY, BUDGET, trace, graph, iters=10, seed=0,
        spec_kwargs={"slos": slos, "anneal_iters": 150})
    assert sr.spec == sr2.spec and sr.score == sr2.score
    assert sr.history == sr2.history


# ===================================================================== #
# Launch backend: real engines from the spec
# ===================================================================== #
def _smoke(arch):
    return dataclasses.replace(configs.get_smoke(arch), dtype="float32")


def test_launch_matches_single_engine_all_backends():
    """The spec's three launch shapes — single engine, serial PD
    handoff, streamed PD handoff — must produce bit-identical greedy
    tokens (the acceptance criterion for subsuming the example flow)."""
    from repro.serving.engine import Request, ServingEngine
    cfg = _smoke("llama3_8b")
    from repro.models import model as M
    params = M.init_params(cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 3, 9)]

    def mk():
        return [Request(rid=i, prompt=p.copy(), max_new_tokens=6,
                        arrival=0.0) for i, p in enumerate(prompts)]

    singles = mk()
    ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2) \
        .run(singles)
    want = [r.output for r in singles]
    ekw = {"slots": 2, "max_len": 32, "sync_every": 2}

    solo = mk()
    out = DeploymentSpec(groups=[["h100"]], arch="llama3_8b",
                         engine=ekw).compile() \
        .launch(cfg, params).run(solo)
    assert [r.output for r in solo] == want
    assert out["wire_bytes"] == 0

    serial = mk()
    out = DeploymentSpec(groups=[["h100"], ["l40s"]], pd=True,
                         arch="llama3_8b", engine=ekw).compile() \
        .launch(cfg, params).run(serial)
    assert [r.output for r in serial] == want
    assert out["wire_bytes"] > 0 and out["shards"] == 0
    assert out["engine"]["prefill_batches"] == 0    # decode-only side

    streamed = mk()
    out = DeploymentSpec(groups=[["h100"], ["l40s"]], pd=True,
                         kv_chunks=8, arch="llama3_8b",
                         engine=ekw).compile() \
        .launch(cfg, params).run(streamed)
    assert [r.output for r in streamed] == want
    assert out["shards"] > 0


# two islands joined by one crossing: the prefill -> decode handoff
# rides a live fabric channel
PD_FABRIC = {"islands": [{"name": "pre", "groups": [0]},
                         {"name": "dec", "groups": [1]}],
             "crossings": [{"src": "pre", "dst": "dec", "bw": 2e8,
                            "latency": 50e-6, "duplex": "half"}],
             "host_island": "pre"}


@pytest.mark.parametrize("fabric", [None, PD_FABRIC],
                         ids=["plain", "fabric"])
def test_launch_streamed_pd_counts_every_shard(fabric):
    """Streamed P/D through ``launch().run``, with and without a fabric
    topology: greedy tokens equal one engine's, and ``wire_bytes`` and
    ``shards`` count exactly the shards a prefill engine streams for
    the same requests (the fabric charges the same bytes as URGENT)."""
    from repro.models import model as M
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.kvpool import KvSlice
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    rng = np.random.default_rng(11)
    # with kv_chunks=8 over max_len=32 the prefill chunk is 4: two
    # prompts stream chunk by chunk, the 3-token one in one piece
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 3, 9)]

    def mk():
        return [Request(rid=i, prompt=p.copy(), max_new_tokens=5,
                        arrival=0.0) for i, p in enumerate(prompts)]

    singles = mk()
    ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2) \
        .run(singles)
    producer = ServingEngine(cfg, params, slots=2, max_len=32,
                             prefill_chunk=4)
    items = [it for r in mk() for it in producer.sessions.stream(r, 0.0)]
    slices = [it for it in items if isinstance(it, KvSlice)]

    reqs = mk()
    out = DeploymentSpec(groups=[["h100"], ["l40s"]], pd=True,
                         kv_chunks=8, arch="llama3_8b", fabric=fabric,
                         engine={"slots": 2, "max_len": 32,
                                 "sync_every": 2}).compile() \
        .launch(cfg, params).run(reqs)
    assert [r.output for r in reqs] == [r.output for r in singles]
    assert out["shards"] == len(slices) > len(prompts)
    assert out["wire_bytes"] == sum(s.nbytes for s in slices) > 0
    if fabric is None:
        assert "fabric" not in out
    else:
        assert out["fabric"]["urgent_bytes"] == out["wire_bytes"]
        assert out["fabric"]["bulk_bytes"] == 0


def test_launch_without_graph_simulate_raises():
    dep = DeploymentSpec(groups=[["h100"]], arch="llama3_8b").compile()
    with pytest.raises(ValueError, match="compile\\(graph\\)"):
        dep.simulate(poisson_trace(rate=10.0, num_requests=5, seed=0))
