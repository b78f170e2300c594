"""Contended-fabric suite: topology lowering, channel invariants,
scheduler behavior, DES integration, straggle detection.

Covers the fabric subsystem (serving/fabric.py) at three levels:

* **Channel unit invariants** — byte conservation, non-overlapping
  committed spans, within-class completion-order monotonicity, and the
  priority-vs-FIFO head semantics, under hypothesis-driven random
  interleavings of urgent commits and bulk enqueues.
* **Topology** — validation (duplicate groups, undeclared hosts,
  unreachable islands, duplex conflicts), JSON round-trip through
  ``DeploymentSpec.fabric`` including unknown-key rejection, and the
  planner-facing contended-bandwidth lowering.
* **DES integration** — determinism of reference-vs-fast walks under
  contention, an uncontended mirror topology matching the point-to-
  point math, checkpoint shipping riding the fabric (with
  ``recovered``-parity when uncontended), and the straggle detector
  tripping breakers with no injected-fault declaration.
"""
import dataclasses
import math

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # collect without hypothesis (tier-1 guard)
    from _hypothesis_stub import given, settings, strategies as st

from conftest import random_dag
from repro.core.planner import contended_bw
from repro.core.simulator import FABRIC_BULK
from repro.serving.fabric import (BULK, HOST, URGENT, Crossing,
                                  FabricState, Island, LiveChannel,
                                  LiveFabric, Topology, TransferScheduler)
from repro.serving.faults import (FaultPlan, GroupHealth, RecoveryConfig,
                                  StraggleDetector)
from repro.serving.router import PDRouter
from repro.serving.spec import DeploymentSpec
from repro.serving.workload import diurnal_trace, poisson_trace

GROUPS = [["a100", "l40s"], ["h100", "h100"], ["a100", "l40s"]]
SLOS = {"base": 2.0, "per_output_token": 0.05, "ttft": 1.5}
ANNEAL = 150
EPS = 1e-9


def _phased(g):
    nodes = [dataclasses.replace(
        node, phase="prefill" if node.idx < len(g.nodes) // 2 else "decode")
        for node in g.nodes]
    g2 = type(g)(nodes, dict(g.edges), name=g.name + ".des")
    g2.validate()
    return g2


@pytest.fixture(scope="module")
def graph():
    return _phased(random_dag(24, seed=2))


def two_island_topology(scheduler="priority", bw=2e8, host="pre"):
    return Topology(
        islands=(Island("pre", groups=(0,), bw=600e9),
                 Island("dec", groups=(1, 2), bw=600e9)),
        crossings=(Crossing("pre", "dec", bw=bw, latency=50e-6,
                            duplex="half"),),
        host_island=host, scheduler=scheduler)


def fabric_dict(scheduler="priority", bw=2e8):
    return two_island_topology(scheduler, bw).to_dict()


def mirror_dict(n_groups):
    """Per-group islands with fat point-to-point crossings at the
    legacy Interconnect defaults (100 GB/s, 20 us)."""
    return {
        "islands": [{"name": f"g{i}", "groups": [i]}
                    for i in range(n_groups)],
        "crossings": [{"src": f"g{i}", "dst": f"g{j}",
                       "bw": 100e9, "latency": 20e-6}
                      for i in range(n_groups) for j in range(n_groups)
                      if i != j],
        "host_island": "g0", "scheduler": "priority",
    }


# ===================================================================== #
# Topology: validation + JSON round-trip
# ===================================================================== #
def test_topology_lowering_and_roundtrip():
    t = two_island_topology()
    assert t.channel_key(1, 2) == ("isl", "dec")      # same island
    assert t.channel_key(0, 0) is None                # same group
    assert t.channel_key(0, 1) == ("x", "pre", "dec")
    # half-duplex: the reverse direction shares the SAME channel key
    assert t.channel_key(1, 0) == ("x", "pre", "dec")
    assert t.channel_key(1, HOST) == ("x", "pre", "dec")
    assert t.channel_params(("x", "pre", "dec")) == (2e8, 50e-6)
    t2 = Topology.from_dict(t.to_dict())
    assert t2 == t


@pytest.mark.parametrize("mutate,err", [
    (lambda d: d["islands"][0].update(groups=[0, 1]), "both"),
    (lambda d: d.update(host_island="nope"), "not a declared"),
    (lambda d: d["crossings"].clear(), "no crossing"),
    (lambda d: d["crossings"][0].update(duplex="simplex"), "duplex"),
    (lambda d: d["crossings"][0].update(bogus=1), "unknown"),
    (lambda d: d["islands"][0].update(bogus=1), "unknown"),
    (lambda d: d.update(bogus=1), "unknown"),
    (lambda d: d.update(scheduler="lifo"), "scheduler"),
])
def test_topology_validation_rejects(mutate, err):
    d = fabric_dict()
    mutate(d)
    with pytest.raises((ValueError, TypeError), match=err):
        Topology.from_dict(d)


def test_spec_validates_fabric_eagerly(graph):
    # every group must sit on an island
    bad = fabric_dict()
    bad["islands"][1]["groups"] = [1]           # group 2 unmapped
    with pytest.raises(ValueError, match="not on any island"):
        DeploymentSpec(groups=GROUPS, fabric=bad)
    ok = DeploymentSpec(groups=GROUPS, fabric=fabric_dict())
    assert ok.make_topology() is not None
    assert DeploymentSpec(groups=GROUPS).make_topology() is None


# ===================================================================== #
# Channel invariants (hypothesis)
# ===================================================================== #
def _drive(policy, ops, bw=1e6, latency=1e-4):
    """Replay (kind, gap, nbytes) ops at non-decreasing watermarks on
    one channel; returns (channel, urgent spans, bulk slices, enqueued
    bulk bytes).  Mirrors the DES contract: urgent ready values and
    bulk enqueue times never move backwards, and the channel is
    materialized at each watermark before new work books."""
    ch = TransferScheduler(policy).make_channel(("x", "a", "b"),
                                               bw, latency)
    slices = []
    ch_sink = lambda s, d, r, t0, t1: slices.append((t0, t1))
    urgent, bulk_bytes, now = [], 0.0, 0.0
    for i, (kind, gap, nbytes) in enumerate(ops):
        now += gap
        ch.materialize(now, ch_sink)
        if kind == "u":
            s = max(now, ch.head())
            e = s + ch.duration(nbytes)
            ch.commit_urgent([(s, e)], now, nbytes)
            urgent.append((s, e))
        else:
            ch.enqueue_bulk(now, nbytes, ("b", i), 1, 0, i, ch_sink)
            bulk_bytes += nbytes
    ch.materialize(math.inf, ch_sink)
    return ch, urgent, slices, bulk_bytes


@settings(max_examples=60, deadline=None)
@given(seedlist=st.lists(st.tuples(st.booleans(),
                                   st.floats(min_value=0.0,
                                             max_value=2.0),
                                   st.integers(min_value=0,
                                               max_value=500_000)),
                         min_size=1, max_size=24),
       policy=st.sampled_from(["priority", "fifo"]))
def test_channel_conservation_and_no_overlap(seedlist, policy):
    ops = [("u" if u else "b", gap, nb) for u, gap, nb in seedlist]
    bw, latency = 1e6, 1e-4
    ch, urgent, slices, bulk_bytes = _drive(policy, ops, bw, latency)
    # byte conservation: every enqueued bulk byte is on the wire
    # (zero-byte transfers complete instantly, no wire time)
    n_bulk = sum(1 for k, _, nb in ops if k == "b" and nb > 0)
    wire = sum(e - s for s, e in slices)
    expect = bulk_bytes / bw + n_bulk * latency
    assert wire == pytest.approx(expect, rel=1e-9, abs=1e-9)
    # committed spans never overlap (one wire, one transfer at a time)
    spans = sorted([s for s in urgent if s[1] > s[0]]
                   + [s for s in slices if s[1] > s[0]])
    for (s0, e0), (s1, e1) in zip(spans, spans[1:]):
        assert e0 <= s1 + EPS, (policy, spans)
    # every bulk transfer completed; within the class, completion never
    # inverts enqueue order (priority backfill serves strictly in
    # (ready, seq) order, one segment at a time)
    done = [ch.done_at(("b", i)) for i, (k, _, _) in enumerate(ops)
            if k == "b"]
    assert all(d is not None for d in done)
    wired = [ch.done_at(("b", i)) for i, (k, _, nb) in enumerate(ops)
             if k == "b" and nb > 0]
    assert wired == sorted(wired)


def test_priority_head_unaffected_by_bulk():
    """A queued bulk transfer delays urgent work under FIFO but not
    under the priority scheduler — the core of the benchmark claim."""
    heads = {}
    for policy in ("fifo", "priority"):
        ch = TransferScheduler(policy).make_channel(("x", "a", "b"),
                                                    1e6, 0.0)
        ch.enqueue_bulk(0.0, 1_000_000, ("b", 0), 1, 0, 0, None)
        ch.materialize(0.5)
        heads[policy] = ch.head()
    assert heads["fifo"] == pytest.approx(1.0)    # behind the 1s bulk
    assert heads["priority"] == 0.0               # urgent head clear


def test_priority_backfills_urgent_gaps():
    ch = TransferScheduler("priority").make_channel(("x", "a", "b"),
                                                    1e6, 0.0)
    # urgent occupies [2, 3): the idle [0, 2) becomes settled gap
    ch.commit_urgent([(2.0, 3.0)], 0.0, 1_000_000)
    slices = []
    ch.enqueue_bulk(0.0, 500_000, ("b", 0), 1, 0, 0,
                    lambda s, d, r, t0, t1: slices.append((t0, t1)))
    ch.materialize(10.0, lambda s, d, r, t0, t1: slices.append((t0, t1)))
    assert slices == [(0.0, 0.5)]                 # inside the gap
    assert ch.done_at(("b", 0)) == pytest.approx(0.5)


def test_cancel_src_drops_pending_bulk():
    fs = FabricState(two_island_topology(), 3)
    fs.enqueue_bulk(1, HOST, 7, 1e6, 0.0, ("ckpt", 0, 1))
    fs.enqueue_bulk(2, HOST, 8, 1e6, 0.0, ("ckpt", 1, 1))
    assert fs.cancel_src(1, 0.0) == 1             # group 1's ship dies
    fs.flush()
    ch = fs.channel(1, HOST)
    assert ch.done_at(("ckpt", 0, 1)) is None
    assert ch.done_at(("ckpt", 1, 1)) is not None
    assert fs.ckpt_completed() == 1


# ===================================================================== #
# Router: queued transfer tail
# ===================================================================== #
def test_pd_router_charges_queued_tail():
    req = type("R", (), {"kv_bytes": 1e6})()
    r = PDRouter(kv_chunks=1)
    fs = FabricState(two_island_topology(bw=1e6), 3)
    r.bind_fabric(fs)
    unloaded = r._transfer_tail(req, 0, 1, now=0.0)
    assert unloaded == pytest.approx(50e-6 + 1.0)
    # urgent traffic already booked to t=3 on the shared crossing:
    # the estimate must charge the wait behind it
    ch = fs.channel(0, 1)
    ch.commit_urgent([(0.0, 3.0)], 0.0, 3e6)
    assert r._transfer_tail(req, 0, 1, now=0.0) \
        == pytest.approx(3.0 + unloaded)
    # same group -> no fabric hop, no tail
    assert r._transfer_tail(req, 1, 1, now=0.0) == 0.0
    # chunked: only the last chunk's tail rides on the queue
    rc = PDRouter(kv_chunks=4)
    rc.bind_fabric(fs)
    assert rc._transfer_tail(req, 0, 1, now=0.0) \
        == pytest.approx(3.0 + 50e-6 + 0.25)


# ===================================================================== #
# Planner: contended bandwidth lowering
# ===================================================================== #
def test_contended_bw_and_planner_lowering(graph):
    assert contended_bw(100e9, 2) == 50e9
    assert contended_bw(100e9, 0) == 100e9        # degenerate: no split
    t = two_island_topology()
    assert t.planner_bw(0) == 600e9               # alone on its island
    assert t.planner_bw(1) == 300e9               # shares with group 2
    # the deployment threads per-group contended bw into the planner
    dep = DeploymentSpec(groups=GROUPS, fabric=fabric_dict(),
                         anneal_iters=ANNEAL).compile(graph)
    assert dep.cluster().bw_overrides == [600e9, 300e9, 300e9]


# ===================================================================== #
# DES integration
# ===================================================================== #
def _simulate(graph, fabric=None, reference=False, sim_kw=None,
              **spec_kw):
    kw = dict(groups=GROUPS, router="pd_split", slos=SLOS, pd=True,
              kv_chunks=4, anneal_iters=ANNEAL, **spec_kw)
    dep = DeploymentSpec(**kw, fabric=fabric).compile(graph)
    trace = diurnal_trace(40.0, 300, seed=0)
    return dep.simulate(trace, reference=reference, **(sim_kw or {}))


@pytest.mark.parametrize("router,pd,kv_chunks", [
    ("jsed", False, 1), ("round_robin", False, 1),
    ("least_loaded", False, 1),
    ("pd_split", True, 1), ("pd_split", True, 4),
])
def test_ref_vs_fast_parity_under_contention(graph, router, pd,
                                             kv_chunks):
    """The reference and fast DES walks must stay bit-identical when
    transfers queue on contended shared channels."""
    kw = dict(groups=GROUPS, router=router, slos=SLOS, pd=pd,
              kv_chunks=kv_chunks, anneal_iters=ANNEAL,
              fabric=fabric_dict(bw=2e7))
    trace = diurnal_trace(40.0, 300, seed=0)
    ref = DeploymentSpec(**kw).compile(graph).simulate(
        trace, reference=True)
    fast = DeploymentSpec(**kw).compile(graph).simulate(trace)
    assert ref.events == fast.events
    assert ref.latencies == fast.latencies
    assert ref.ttfts == fast.ttfts
    assert ref.assignments == fast.assignments
    assert ref.fabric_wait_seconds == fast.fabric_wait_seconds


def test_contended_fabric_determinism(graph):
    a = _simulate(graph, fabric=fabric_dict(bw=2e7))
    b = _simulate(graph, fabric=fabric_dict(bw=2e7))
    assert a.events == b.events
    assert a.latencies == b.latencies
    assert a.fabric_wait_seconds == b.fabric_wait_seconds
    # contention is real on this thin crossing
    assert a.fabric_wait_seconds > 0.0


def test_mirror_topology_matches_point_to_point(graph):
    """An uncontended per-group-island topology at the Interconnect's
    default rates reproduces the point-to-point latencies on a trace
    sparse enough that transfers never queue."""
    kw = dict(groups=GROUPS, router="pd_split", slos=SLOS, pd=True,
              kv_chunks=4, anneal_iters=ANNEAL)
    trace = poisson_trace(rate=2.0, num_requests=60, seed=3)
    plain = DeploymentSpec(**kw).compile(graph).simulate(trace)
    mirror = DeploymentSpec(**kw, fabric=mirror_dict(len(GROUPS))
                            ).compile(graph).simulate(trace)
    assert mirror.completed == plain.completed
    assert mirror.shed == plain.shed
    for a, b in zip(plain.latencies, mirror.latencies):
        assert a == pytest.approx(b, rel=1e-9, abs=1e-12)
    for a, b in zip(plain.ttfts, mirror.ttfts):
        assert a == pytest.approx(b, rel=1e-9, abs=1e-12)


def test_fabric_bulk_events_recorded(graph):
    res = _simulate(graph, fabric=fabric_dict(bw=2e7),
                    sim_kw=dict(faults=FaultPlan(seed=3),
                                recovery=RecoveryConfig(interval=2e-3),
                                health=GroupHealth(len(GROUPS))))
    bulk = [e for e in res.events if e[2] == FABRIC_BULK]
    assert res.ckpt_shipped > 0
    assert bulk, "checkpoint ships must emit FABRIC_BULK events"
    assert res.fabric_bulk_bytes > 0.0
    for e in bulk:
        assert e[5] >= e[4]                     # well-formed [t0, t1)


def test_uncontended_fabric_recovery_parity(graph):
    """Satellite: checkpoint shipping through an UNCONTENDED fabric
    must not change what crash recovery restores."""
    # the full-outage blip from test_faults.py: every group crashes at
    # mid-trace under 1.5x overload, so victims hold checkpointed
    # in-flight decode state when the lights go out
    groups = [["h100", "rtxpro6000"], ["a100", "l40s"], ["a100", "l40s"]]
    kw = dict(groups=groups, anneal_iters=200)
    dep = DeploymentSpec(**kw).compile(graph)
    trace = poisson_trace(rate=1.5 * dep.cluster().capacity,
                          num_requests=150, seed=5)
    mid = trace[len(trace) // 2].arrival
    plan = FaultPlan(seed=1)
    for g in range(len(groups)):
        plan.crash(mid, group=g, recover_at=mid + 0.01)
    sim = dict(faults=plan, recovery=RecoveryConfig(interval=1e-5),
               health=GroupHealth(len(groups)))
    plain = dep.simulate(trace, **sim)
    fab = DeploymentSpec(**kw, fabric=mirror_dict(len(groups))
                         ).compile(graph).simulate(trace, **sim)
    assert plain.recovered > 0
    assert fab.recovered == plain.recovered
    assert fab.dropped == plain.dropped
    assert fab.completed == plain.completed
    assert fab.ckpt_shipped > 0                 # ships really ran
    # restore points now come from actual wire completions (ships in
    # flight at crash time don't count), so per-request latencies can
    # shift by the channel's microsecond setup cost — but only there:
    # the schedule itself must stay put
    assert plain.mean_latency == pytest.approx(fab.mean_latency,
                                               rel=1e-2)
    assert plain.makespan == pytest.approx(fab.makespan, rel=1e-2)


# ===================================================================== #
# Straggle detection (no declared fault)
# ===================================================================== #
def test_straggle_detector_catches_undeclared_straggle(graph):
    dep = DeploymentSpec(groups=GROUPS, router="jsed", slos=SLOS,
                         anneal_iters=ANNEAL).compile(graph)
    trace = diurnal_trace(40.0, 400, seed=0)
    plan = FaultPlan(seed=0).straggle(2.0, 6.0, group=0, factor=5.0)
    h = GroupHealth(len(GROUPS))
    det = StraggleDetector(h, interval=0.5)
    res = dep.simulate(trace, faults=plan, health=h, controller=det)
    # caught: the right group, inside (or shortly after) the window
    assert det.detections
    t, g, ratio = det.detections[0]
    assert g == 0
    assert 2.0 <= t <= 7.0
    assert ratio > det.threshold
    # routed around: the straggler takes less load than it does when
    # nobody watches the signals
    blind = dep.simulate(trace, faults=plan)
    load = res.assignments.count(0)
    blind_load = blind.assignments.count(0)
    assert load < blind_load
    assert res.completed >= blind.completed


def test_straggle_detector_clean_run_no_false_positive(graph):
    dep = DeploymentSpec(groups=GROUPS, router="jsed", slos=SLOS,
                         anneal_iters=ANNEAL).compile(graph)
    trace = diurnal_trace(40.0, 400, seed=0)
    h = GroupHealth(len(GROUPS))
    det = StraggleDetector(h, interval=0.5)
    watched = dep.simulate(trace, health=h, controller=det)
    assert det.detections == []
    # watching healthy signals must not perturb the schedule
    plain = dep.simulate(trace)
    assert watched.events == plain.events
    assert watched.latencies == plain.latencies


def test_group_health_suspect_semantics():
    h = GroupHealth(2)
    assert h.allow(0, 0.0)
    h.suspect(0, 1.0)
    assert h.state(0, 1.0) == "half_open"
    assert h.penalty(0, 1.0) > 0.0              # routers steer away
    h.record_ok(0, 1.5)
    assert h.state(0, 1.5) == "closed"
    # suspect never downgrades an OPEN breaker
    h.trip(1, 1.0)
    h.trip(1, 1.0)
    state = h.state(1, 1.0)
    h.suspect(1, 1.0)
    assert h.state(1, 1.0) == state


# ===================================================================== #
# Live accounting twin
# ===================================================================== #
def test_live_channel_wrap_counts_stamped_shards():
    from repro.serving.kvpool import KvSlice
    ch = LiveChannel(("x", "a", "b"), 1e9, 1e-5)
    shards = [KvSlice(rid=1, component="kv", layer=0, nbytes=100),
              KvSlice(rid=1, component="kv", layer=1, nbytes=200,
                      klass=BULK)]
    out = list(ch.wrap(iter(shards + ["cursor"])))
    assert out == shards + ["cursor"]           # pass-through
    assert ch.bytes_by_class[URGENT] == 100
    assert ch.bytes_by_class[BULK] == 200
    assert ch.modeled_seconds(URGENT) == pytest.approx(1e-5 + 100 / 1e9)


def test_live_fabric_ckpt_accounting():
    fab = LiveFabric(two_island_topology(), 3)
    fab.account_ckpt(1, 1000)                   # dec -> host crossing
    fab.account_ckpt(2, 500)
    st_ = fab.stats()
    assert st_["bulk_bytes"] == 1500
    assert st_["urgent_bytes"] == 0


def test_kvslice_klass_default():
    from repro.serving.kvpool import KvSlice
    sl = KvSlice(rid=1, component="kv", layer=0, nbytes=10)
    assert sl.klass == URGENT           # a fabric-unaware producer
