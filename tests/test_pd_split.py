"""Phase-split (prefill/decode) serving across replica groups.

Covers the PD-disaggregation acceptance criteria:
  * the KV-transfer edge is a first-class DES event whose time lands in
    TTFT, and phase-split replays are bit-deterministic,
  * ``export_kv``/``import_kv`` round-trip a request between two real
    engines with decode bit-identical to a single-engine run, for all
    four kernel families,
  * rate matching bounds the decode pool's resident-KV queue (decode
    saturation throttles prefill admission),
  * SLO admission control sheds doomed requests and goodput is
    reported next to throughput,
  * phase-split routing beats colocated JSED on a heterogeneous mix
    (the benchmark gate, at test scale).
"""
import dataclasses

import numpy as np
import pytest

from conftest import random_dag
import repro.configs as configs
from repro.core.monitor import MonitorConfig
from repro.core.simulator import (KV_TRANSFER, ClusterRequest,
                                  Interconnect)
from repro.models import model as M
from repro.serving.cluster import TesseraCluster
from repro.serving.engine import Request, ServingEngine
from repro.serving.router import JSEDRouter, PDRouter, make_router
from repro.serving.workload import assign_slos, poisson_trace

HET_GROUPS = [["h100", "rtxpro6000"], ["a100", "l40s"],
              ["a100", "l40s"], ["a100", "l40s"]]


def pd_dag(n: int = 24, seed: int = 2, decode_weight: float = 8.0):
    """Random DAG whose first half is the prefill phase and second half
    the (heavier, repeated) decode phase — the shape request_graph
    produces from real models."""
    g = random_dag(n, seed=seed)
    nodes = []
    for node in g.nodes:
        if node.idx < n // 2:
            nodes.append(dataclasses.replace(node, phase="prefill"))
        else:
            nodes.append(dataclasses.replace(
                node, phase="decode",
                flops=node.flops * decode_weight,
                bytes_accessed=node.bytes_accessed * decode_weight))
    g2 = type(g)(nodes, dict(g.edges), name=g.name + ".pd")
    g2.validate()
    return g2


@pytest.fixture(scope="module")
def pd_cluster():
    return TesseraCluster(pd_dag(), HET_GROUPS,
                          base_prompt=1024, base_output=128,
                          anneal_iters=300,
                          monitor_cfg=MonitorConfig(window=0.010),
                          model_cfg=configs.get("llama3_8b"))


def stable_rate(cluster) -> float:
    """A rate the colocated router can serve without divergence."""
    sat = cluster.simulate(poisson_trace(10 * cluster.capacity, 60,
                                         seed=3),
                           JSEDRouter()).throughput
    return 0.7 * sat


# ===================================================================== #
# Phase decomposition + decode_only admission (DES level)
# ===================================================================== #
def test_phase_service_decomposes(pd_cluster):
    """prefill-phase + decode-phase service == colocated service: the
    unit split loses no work."""
    probe = ClusterRequest(rid=0, arrival=0.0, scale_prompt=1.3,
                           scale_output=0.7)
    for rep in pd_cluster.build_replicas():
        tp = rep.predicted_phase_service(probe, "prefill")
        td = rep.predicted_phase_service(probe, "decode")
        assert tp > 0 and td > 0
        assert tp + td == pytest.approx(rep.predicted_service(probe))


def test_decode_only_admission_starts_after_kv(pd_cluster):
    """A decode_only submission must not start before the imported KV
    arrives (not_before), and must schedule no prefill-only units."""
    rep = pd_cluster.build_replicas()[0]
    req = ClusterRequest(rid=7, arrival=0.0)
    events = []
    finish = rep.submit(req, events, phase="decode", not_before=0.5)
    assert finish > 0.5
    assert events and all(t0 >= 0.5 for (_, _, _, _, t0, _) in events)
    # decode phase runs strictly less work than the full request
    rep2 = pd_cluster.build_replicas()[0]
    full = rep2.submit(ClusterRequest(rid=8, arrival=0.0))
    assert finish - 0.5 < full


def test_ttft_includes_kv_transfer_time(pd_cluster):
    """The transfer edge is in the event log and its duration (bytes /
    fabric bw + latency) is part of TTFT."""
    tr = [dataclasses.replace(r, session=None)
          for r in poisson_trace(5.0, 1, seed=0)]
    creq = pd_cluster.to_cluster_request(tr[0])
    assert creq.kv_bytes > 0
    router = PDRouter(prefill_pool=[0], decode_pool=[1])

    def run(bw):
        ic = Interconnect(default_bw=bw, base_latency=1e-5)
        pd_cluster.interconnect = ic
        return pd_cluster.simulate_pd(tr, router), ic

    try:
        res_fast, ic_fast = run(100e9)
        res_slow, ic_slow = run(1e9)
    finally:
        pd_cluster.interconnect = Interconnect()
    for res, ic in ((res_fast, ic_fast), (res_slow, ic_slow)):
        xfer = [e for e in res.events if e[2] == KV_TRANSFER]
        assert len(xfer) == 1
        dst, rid, kind, src, t0, t1 = xfer[0]
        assert (dst, src) == (1, 0)
        assert t1 - t0 == pytest.approx(
            ic.transfer_time(creq.kv_bytes, 0, 1))
        # first token cannot precede KV arrival: TTFT includes transfer
        assert res.ttfts[0] == pytest.approx(t1 - creq.arrival)
    slow_delta = ic_slow.transfer_time(creq.kv_bytes, 0, 1) \
        - ic_fast.transfer_time(creq.kv_bytes, 0, 1)
    assert res_slow.ttfts[0] - res_fast.ttfts[0] == \
        pytest.approx(slow_delta)
    assert res_slow.transfer_seconds > res_fast.transfer_seconds


def test_pd_event_log_deterministic(pd_cluster):
    tr = assign_slos(poisson_trace(stable_rate(pd_cluster), 80, seed=11),
                     base=5.0, ttft=0.5)
    r1 = pd_cluster.simulate_pd(tr, PDRouter())
    r2 = pd_cluster.simulate_pd(tr, PDRouter())
    assert r1.events == r2.events
    assert r1.latencies == r2.latencies
    assert r1.ttfts == r2.ttfts
    assert r1.makespan == r2.makespan
    assert any(e[2] == KV_TRANSFER for e in r1.events)


def test_pd_completes_all_and_counts_once(pd_cluster):
    """A split request completes exactly once (on its decode group)."""
    tr = poisson_trace(stable_rate(pd_cluster), 60, seed=5)
    res = pd_cluster.simulate_pd(
        tr, PDRouter(prefill_pool=[0], decode_pool=[1, 2, 3]))
    assert res.completed == 60
    assert sum(res.per_replica_completed) == 60
    assert res.per_replica_completed[0] == 0      # prefill-only group
    assert res.transfers == 60
    assert all(a in (1, 2, 3) for a in res.assignments)


# ===================================================================== #
# PDRouter: classification + rate matching
# ===================================================================== #
def test_pd_router_classifies_disjoint_pools(pd_cluster):
    router = PDRouter(prefill_frac=0.25)
    pre, dec = router.pools(pd_cluster.build_replicas())
    assert pre and dec
    assert not set(pre) & set(dec)
    assert sorted(pre + dec) == [0, 1, 2, 3]
    # single-replica cluster degenerates to colocated routing
    single = PDRouter().pools(pd_cluster.build_replicas()[:1])
    assert single == ([0], [0])


def test_pd_router_registry():
    assert isinstance(make_router("pd_split"), PDRouter)


def test_rate_matching_bounds_kv_queue(pd_cluster):
    """Decode-pool saturation must throttle prefill admission: the
    throttled router's resident-KV peak stays bounded as the trace
    grows, the unthrottled one's grows without bound.  The decode pool
    is a single group so decode (not prefill) is the saturated side —
    exactly the case rate matching exists for.  Monitors are disabled
    to isolate admission control from policy adaptation (the replica
    plans come from the planner's cache, so this cluster is cheap)."""
    cluster = TesseraCluster(pd_dag(), HET_GROUPS,
                             base_prompt=1024, base_output=128,
                             anneal_iters=300, monitor_cfg=None,
                             model_cfg=configs.get("llama3_8b"))
    rate = 2.0 * stable_rate(cluster)

    def peak(max_kv_lag, n):
        tr = poisson_trace(rate, n, seed=13)
        router = PDRouter(prefill_pool=[0], decode_pool=[1],
                          max_kv_lag=max_kv_lag)
        return cluster.simulate_pd(tr, router).peak_kv_bytes

    unthrottled_1x, unthrottled_4x = (peak(float("inf"), n)
                                      for n in (100, 400))
    throttled_1x, throttled_4x = (peak(0.2, n) for n in (100, 400))
    assert throttled_1x < unthrottled_1x
    # unbounded: peak keeps growing with the trace
    assert unthrottled_4x > 2.0 * unthrottled_1x
    # bounded: 4x the trace leaves the peak unchanged (the admission
    # governor reached its steady state)
    assert throttled_4x == pytest.approx(throttled_1x, rel=0.1)


def test_rate_matching_delays_admission(pd_cluster):
    """The rate-matched decision carries admit_at > now when the decode
    group is backlogged."""
    router = PDRouter(prefill_pool=[0], decode_pool=[1],
                      max_kv_lag=0.05)
    replicas = pd_cluster.build_replicas()
    req = ClusterRequest(rid=0, arrival=0.0)
    for _ in range(10):                        # saturate decode group
        replicas[1].submit(ClusterRequest(rid=99, arrival=0.0))
    p, d, admit_at = router.route(req, replicas, 0.0)
    assert (p, d) == (0, 1)
    assert admit_at == pytest.approx(
        replicas[1].backlog(0.0) - 0.05)
    assert admit_at > 0.0


# ===================================================================== #
# Admission control + goodput
# ===================================================================== #
def test_slo_shedding_under_overload(pd_cluster):
    rate = 10.0 * stable_rate(pd_cluster)
    tr = assign_slos(poisson_trace(rate, 200, seed=7), base=0.1)
    keep = pd_cluster.simulate(tr, JSEDRouter())
    shed = pd_cluster.simulate(tr, JSEDRouter(slo_shed=True))
    assert keep.shed == 0
    assert shed.shed > 0
    assert shed.completed + shed.shed == 200
    assert shed.assignments.count(-1) == shed.shed
    # shedding doomed requests must not reduce goodput
    assert shed.goodput >= keep.goodput
    assert len(shed.latencies) == shed.completed


def test_goodput_counts_both_slo_components(pd_cluster):
    tr = poisson_trace(stable_rate(pd_cluster), 40, seed=9)
    loose = assign_slos(tr, base=1e9, ttft=1e9)
    res = pd_cluster.simulate(loose, JSEDRouter())
    assert res.slo_ok == res.completed
    assert res.goodput == pytest.approx(res.throughput)
    tight = assign_slos(tr, base=1e9, ttft=1e-9)   # impossible TTFT
    res2 = pd_cluster.simulate(tight, JSEDRouter())
    assert res2.slo_ok == 0
    assert res2.goodput == 0.0


def test_phase_split_beats_colocated_on_hetero_mix(pd_cluster):
    """The acceptance-criterion comparison at test scale: at stable
    load with interactivity SLOs, phase-split must win goodput and
    TTFT while keeping throughput."""
    pd_cluster.interconnect = Interconnect(default_bw=100e9)
    tr = assign_slos(poisson_trace(stable_rate(pd_cluster), 150, seed=17),
                     base=8.0, per_output_token=0.02, ttft=0.5)
    co = pd_cluster.simulate(tr, JSEDRouter())
    pd = pd_cluster.simulate_pd(
        tr, PDRouter(prefill_pool=[0], decode_pool=[1, 2, 3],
                     max_kv_lag=1.0))
    assert pd.mean_ttft < co.mean_ttft
    assert pd.goodput >= co.goodput
    assert pd.throughput > 0.9 * co.throughput


# ===================================================================== #
# Real-engine state handoff: export_kv / import_kv
# ===================================================================== #
ARCHS = ("llama3_8b", "gpt_oss_20b", "rwkv6_3b", "zamba2_7b")


def _smoke(arch):
    return dataclasses.replace(configs.get_smoke(arch), dtype="float32")


@pytest.mark.parametrize("arch", ARCHS)
def test_handoff_bit_identical_to_single_engine(arch):
    """prefill on engine P -> export -> import -> decode on engine D
    must produce the same greedy tokens as one engine doing both, for
    every kernel family (dense / moe / ssm / hybrid)."""
    cfg = _smoke(arch)
    params = M.init_params(cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 3)]

    singles = [Request(rid=i, prompt=p.copy(), max_new_tokens=6,
                       arrival=0.0) for i, p in enumerate(prompts)]
    ref = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    ref.run(singles)

    splits = [Request(rid=i, prompt=p.copy(), max_new_tokens=6,
                      arrival=0.0) for i, p in enumerate(prompts)]
    pre = ServingEngine(cfg, params, slots=2, max_len=32)
    dec = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    for req in splits:
        st = pre.sessions.prefill(req, 0.0)
        assert st.nbytes > 0
        assert dec.sessions.restore(req, st, 0.0)
    while dec._any_active():
        dec.step(0.0)
    dec.sync(0.0)
    assert dec.stats.prefill_batches == 0          # decode_only engine
    assert dec.stats.completed == len(splits)
    assert [r.output for r in splits] == [r.output for r in singles]


def test_export_import_round_trips_cache_slot():
    """Model-level inverse property on a freshly prefixed cache."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    cache = M.init_cache(cfg, 3, 16)
    import jax.numpy as jnp
    toks = jnp.arange(12, dtype=jnp.int32).reshape(3, 4) % cfg.vocab_size
    _, cache = M.prefill(params, cfg, toks, cache)
    state = M.export_kv(cfg, cache, 1, 4)
    assert M.kv_state_bytes(state) > 0
    blank = M.init_cache(cfg, 2, 16)
    filled = M.import_kv(cfg, blank, 0, state)
    np.testing.assert_array_equal(
        np.asarray(filled["kv"]["k"][:, 0, :4]),
        np.asarray(cache["kv"]["k"][:, 1, :4]))
    np.testing.assert_array_equal(
        np.asarray(filled["kv"]["v"][:, 0, :4]),
        np.asarray(cache["kv"]["v"][:, 1, :4]))


def test_sessions_prefill_finishes_one_token_requests():
    """max_new_tokens=1 completes at prefill; the handoff is marked
    done (nothing to ship) and a decode engine rejects it loudly —
    a caller retrying it until admission would livelock."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    rng = np.random.default_rng(3)
    req = Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=4).astype(np.int32), max_new_tokens=1)
    pre = ServingEngine(cfg, params, slots=1, max_len=16)
    st = pre.sessions.prefill(req, 0.0)
    assert st.done
    assert st.nbytes == 0 and st.state is None
    assert pre.stats.completed == 1
    assert len(req.output) == 1
    dec = ServingEngine(cfg, params, slots=1, max_len=16)
    with pytest.raises(ValueError, match="finished at prefill"):
        dec.sessions.restore(req, st, 0.0)


def test_handoff_ttft_stamped_at_decode_admission():
    """TTFT accounting matches the simulator's KV-transfer edge: the
    first token streams only once the state lands on the decode
    engine, so sessions.restore (not sessions.prefill) stamps it."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    rng = np.random.default_rng(5)
    req = Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=5).astype(np.int32), max_new_tokens=4)
    pre = ServingEngine(cfg, params, slots=1, max_len=16)
    st = pre.sessions.prefill(req, now=1.0)
    assert req.ttft == -1.0                  # not stamped yet
    dec = ServingEngine(cfg, params, slots=1, max_len=16)
    assert dec.sessions.restore(req, st, now=3.5)
    assert req.ttft == 3.5


# ===================================================================== #
# Workload SLO helper + kv size model
# ===================================================================== #
def test_assign_slos_sizes_with_output():
    tr = poisson_trace(10.0, 20, seed=0)
    slos = assign_slos(tr, base=1.0, per_output_token=0.01, ttft=0.25)
    for orig, req in zip(tr, slos):
        assert req.slo == pytest.approx(1.0 + 0.01 * orig.output_tokens)
        assert req.slo_ttft == 0.25
        assert (req.rid, req.arrival) == (orig.rid, orig.arrival)


def test_kv_bytes_matches_config(pd_cluster):
    cfg = configs.get("llama3_8b")
    want = (2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim
            * cfg.jnp_dtype.itemsize * 1000)
    assert pd_cluster.kv_bytes(1000) == pytest.approx(want)


@pytest.mark.parametrize("arch", ("llama3_8b", "rwkv6_3b", "zamba2_7b"))
def test_kv_bytes_matches_real_export(arch):
    """The DES charges the interconnect exactly what export_kv ships:
    the cost-model formula and the real cache layout must not drift
    apart (dense per-token KV, ssm fixed-size state, hybrid both)."""
    cfg = _smoke(arch)
    plen = 6
    cluster = TesseraCluster.__new__(TesseraCluster)   # formula only
    cluster.model_cfg = cfg
    want = cluster.kv_bytes(plen)
    cache = M.init_cache(cfg, 2, 16)
    state = M.export_kv(cfg, cache, 0, plen)
    assert M.kv_state_bytes(state) == want
