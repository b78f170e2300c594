"""Paged KV block pool + priority preemption + the sessions facade.

Covers the paged-KV acceptance criteria:
  * BlockPool invariants (no double allocation, ``free + allocated ==
    pool``) hold across arbitrary admit/preempt/drain interleavings —
    property-tested (hypothesis, deterministic stub fallback),
  * a paged engine admits more concurrent sessions than it has slots,
    with preempt-and-resume greedy tokens bit-identical to a roomy
    fixed-slot engine, for all four kernel families,
  * spill-to-host / prefetch round-trips equal the in-HBM decode,
  * priority preemption: a high-priority arrival displaces the
    lowest-priority resident; ``preempt_priority=False`` disables it,
  * the ``engine.sessions`` facade (prefill / restore on a paged
    engine, checkpoint / restore, migrate, stream / receive) decodes
    bit-identically to a single engine that never moved a session,
  * DES mirror: ``KvPoolModel`` occupancy (delayed admission, LRU
    eviction, prefix/session cache hits), session-affinity ON strictly
    beating OFF on a multi-turn chat trace, kv_util reaching the
    autoscaler, and spec knob validation + JSON round-trip.
"""
import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_stub import given, settings, strategies as st

from conftest import random_dag
import repro.configs as configs
from repro.core.simulator import ClusterRequest, KvPoolModel
from repro.models import model as M
from repro.serving.controller import AutoscaleConfig, AutoscalePolicy
from repro.serving.engine import Request, ServingEngine
from repro.serving.kvpool import BlockPool, KvSlice, SessionState
from repro.serving.router import JSEDRouter
from repro.serving.spec import DeploymentSpec
from repro.serving.workload import make_trace

ARCHS = ("llama3_8b", "gpt_oss_20b", "rwkv6_3b", "zamba2_7b")


def _smoke(arch):
    return dataclasses.replace(configs.get_smoke(arch), dtype="float32")


def _prompts(cfg, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in sizes]


def _reqs(prompts, max_new=6, priority=None):
    return [Request(rid=i, prompt=p.copy(), max_new_tokens=max_new,
                    arrival=0.0,
                    priority=0 if priority is None else priority[i])
            for i, p in enumerate(prompts)]


def _drain(eng, t=0.0):
    while eng._any_active():
        eng.step(t)
        eng.sync(t)


# ===================================================================== #
# BlockPool property tests
# ===================================================================== #
@settings(max_examples=60, deadline=None)
@given(n_blocks=st.integers(min_value=1, max_value=24),
       seed=st.integers(min_value=0, max_value=10_000))
def test_blockpool_invariants_any_interleaving(n_blocks, seed):
    """free + allocated == pool and no double allocation after ANY
    interleaving of alloc (admit), partial release (preempt) and full
    release (drain)."""
    import random
    rng = random.Random(seed)
    pool = BlockPool(n_blocks)
    held = {}                            # rid -> block ids
    rid = 0
    for _ in range(80):
        op = rng.random()
        if op < 0.5:                     # admit
            want = rng.randint(1, max(1, n_blocks // 2))
            if want <= pool.free:
                ids = pool.alloc(rid, want)
                assert len(ids) == len(set(ids)) == want
                for other in held.values():
                    assert not set(ids) & set(other), "double allocation"
                held[rid] = ids
                rid += 1
            else:
                with pytest.raises(MemoryError):
                    pool.alloc(rid, want)
        elif op < 0.8 and held:          # preempt: release one session
            victim = rng.choice(sorted(held))
            pool.release(held.pop(victim))
        elif held:                       # drain: release everything
            for ids in held.values():
                pool.release(ids)
            held.clear()
        assert pool.check()
        assert pool.free + pool.allocated == n_blocks
        assert pool.allocated == sum(len(v) for v in held.values())
    for ids in held.values():
        pool.release(ids)
    assert pool.free == n_blocks and pool.check()


def test_blockpool_rejects_foreign_release():
    pool = BlockPool(4)
    pool.alloc(0, 2)
    with pytest.raises(AssertionError):
        pool.release([3])                # block 3 was never allocated


# ===================================================================== #
# Preempt-and-resume bit-parity (all four families)
# ===================================================================== #
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_preempt_resume_bit_identical(arch):
    """Six sessions on a two-slot paged engine (forced park/activate
    cycling) produce exactly the greedy tokens of a six-slot engine."""
    cfg = _smoke(arch)
    params = M.init_params(cfg)
    prompts = _prompts(cfg, (6, 3, 5, 4, 7, 2), seed=1)

    singles = _reqs(prompts)
    ref = ServingEngine(cfg, params, slots=6, max_len=32, sync_every=2)
    ref.run(singles)

    paged = _reqs(prompts)
    eng = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2,
                        kv_block_tokens=8, kv_pool_blocks=64)
    eng.run(paged)
    assert eng._paged.preemptions + len(eng._paged.parked()) >= 0
    for a, b in zip(singles, paged):
        assert a.output == b.output, f"{arch}: rid {a.rid} diverged"
    # the pool drains clean: every block back, bookkeeping intact
    assert eng._paged.pool.free == eng._paged.pool.n_blocks
    assert eng._paged.pool.check()


def test_paged_admits_beyond_slots():
    """Admission is gated by BLOCKS, not slots: 8 sessions enter a
    2-slot engine at once and all complete."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    prompts = _prompts(cfg, (4, 5, 3, 6, 4, 5, 3, 4), seed=2)
    reqs = _reqs(prompts, max_new=4)
    eng = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2,
                        kv_block_tokens=8, kv_pool_blocks=64)
    n = eng.admit_batch(reqs, 0.0)
    assert n == 8                        # all resident (2 active, 6 parked)
    assert len(eng._paged.parked()) >= 6
    _drain(eng)
    assert eng.stats.completed == 8
    assert all(len(r.output) == 4 for r in reqs)


# ===================================================================== #
# Spill / prefetch
# ===================================================================== #
@pytest.mark.parametrize("arch", ("llama3_8b", "zamba2_7b"))
def test_spill_prefetch_roundtrip_bit_identical(arch):
    """Host-spilling a parked session and letting the scheduler
    prefetch it back must not change a single sampled token."""
    cfg = _smoke(arch)
    params = M.init_params(cfg)
    prompts = _prompts(cfg, (5, 3, 4, 6), seed=3)

    singles = _reqs(prompts)
    ref = ServingEngine(cfg, params, slots=4, max_len=32, sync_every=2)
    ref.run(singles)

    spilled = _reqs(prompts)
    eng = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2,
                        kv_block_tokens=8, kv_pool_blocks=64)
    assert eng.admit_batch(spilled, 0.0) == 4
    parked = eng._paged.parked()
    assert parked
    eng._paged.spill(parked[0])          # HBM -> host
    assert eng._paged.spills == 1
    _drain(eng)
    assert eng._paged.prefetches == 1    # came back on activation
    for a, b in zip(singles, spilled):
        assert a.output == b.output
    assert eng._paged.pool.free == eng._paged.pool.n_blocks


# ===================================================================== #
# Priority preemption
# ===================================================================== #
def test_priority_preempts_lowest_resident():
    """Under block pressure a high-priority arrival parks + spills the
    lowest-priority resident instead of waiting; with
    ``preempt_priority=False`` nothing is displaced for priority."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    prompts = _prompts(cfg, (8, 8, 8), seed=4)
    # pool fits ~2 sessions (each needs ceil(min(8+6,16)/8)=2 blocks
    # of 8 tokens + fixed state); priorities 0, 0, then 5
    reqs = _reqs(prompts, priority=[0, 0, 5])
    eng = ServingEngine(cfg, params, slots=2, max_len=16, sync_every=2,
                        kv_block_tokens=8, kv_pool_blocks=4)
    n0 = eng.admit_batch(reqs[:2], 0.0)
    assert n0 == 2
    n1 = eng.admit_batch(reqs[2:], 0.0)
    assert n1 == 1
    assert eng._paged.preemptions >= 1
    _drain(eng)
    assert eng.stats.completed == 3      # preempted sessions resume
    assert eng._paged.pool.check()

    # same shape, preemption off: the high-priority request cannot
    # displace anyone, so the full batch refuses (engine.run would
    # retry it at the next wave instead)
    eng2 = ServingEngine(cfg, params, slots=2, max_len=16, sync_every=2,
                         kv_block_tokens=8, kv_pool_blocks=4,
                         preempt_priority=False, spill=False)
    reqs2 = _reqs(prompts, priority=[0, 0, 5])
    assert eng2.admit_batch(reqs2[:2], 0.0) == 2
    assert eng2.admit_batch(reqs2[2:], 0.0) == 0
    assert eng2._paged.preemptions == 0
    _drain(eng2)


# ===================================================================== #
# The sessions facade against a single engine that never moved
# ===================================================================== #
def test_facade_matches_legacy_handoff_bits():
    """sessions.prefill -> restore between two PAGED engines decodes
    bit-identically to one engine doing both; the restored sessions
    reserve pool blocks on the decode side."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    prompts = _prompts(cfg, (6, 4), seed=5)

    ref = _reqs(prompts)
    ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2) \
        .run(ref)

    moved = _reqs(prompts)
    paged = dict(slots=2, max_len=32, kv_block_tokens=8)
    pre = ServingEngine(cfg, params, **paged)
    dec = ServingEngine(cfg, params, sync_every=2, **paged)
    for r in moved:
        st_ = pre.sessions.prefill(r, 0.0)
        assert isinstance(st_, SessionState)
        assert st_.first_token_pending and not st_.done
        assert st_.nbytes == M.kv_state_bytes(st_.state) > 0
        assert dec.sessions.restore(r, st_, 0.0)
        assert dec._paged.holds(r.rid)
    _drain(dec)
    assert dec.stats.prefill_batches == 0
    assert dec._paged.pool.allocated == 0 and dec._paged.pool.check()
    for a, b in zip(ref, moved):
        assert a.output == b.output


def test_facade_checkpoint_restore_matches_export_import():
    """sessions.checkpoint() mid-decode, then sessions.restore() on a
    fresh engine, decodes bit-identically to an engine that never
    moved its sessions."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    prompts = _prompts(cfg, (5, 3, 4), seed=6)

    ref = _reqs(prompts)
    e_ref = ServingEngine(cfg, params, slots=3, max_len=32, sync_every=2)
    e_ref.run(ref)

    moved = _reqs(prompts)
    src = ServingEngine(cfg, params, slots=3, max_len=32, sync_every=2)
    assert src.admit_batch(moved, 0.0) == 3
    src.step(0.0)
    src.step(0.0)
    exported = src.sessions.checkpoint(0.0)
    assert len(exported) == 3
    assert not src._any_active()
    dst = ServingEngine(cfg, params, slots=3, max_len=32, sync_every=2)
    for r, st_ in exported:
        assert st_.rid == r.rid and not st_.first_token_pending
        assert dst.sessions.restore(r, st_, 0.0)
    _drain(dst)
    for a, b in zip(ref, moved):
        assert a.output == b.output


def test_sessions_migrate_between_engines():
    """sessions.migrate() moves every resident session to a peer and
    decode finishes there bit-identically."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    prompts = _prompts(cfg, (4, 6), seed=7)

    ref = _reqs(prompts)
    e_ref = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    e_ref.run(ref)

    mig = _reqs(prompts)
    a = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    b = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    assert a.admit_batch(mig, 0.0) == 2
    a.step(0.0)
    a.step(0.0)
    assert a.sessions.migrate(b, 0.0) == 2
    assert not a._any_active()
    _drain(b)
    for x, y in zip(ref, mig):
        assert x.output == y.output


def test_stream_receive_kvslice_and_legacy_dicts():
    """sessions.stream() yields KvSlice shards then the SessionState
    cursor; sessions.receive() installs them and decodes
    bit-identically to a single engine."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    prompts = _prompts(cfg, (7, 5), seed=8)

    ref = _reqs(prompts)
    e_ref = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    e_ref.run(ref)

    typed = _reqs(prompts)
    pre = ServingEngine(cfg, params, slots=2, max_len=32,
                        prefill_chunk=4)
    dec = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    for r in typed:
        items = list(pre.sessions.stream(r, 0.0))
        assert isinstance(items[-1], SessionState)
        assert all(isinstance(i, KvSlice) for i in items[:-1])
        assert items[-1].nbytes == sum(i.nbytes for i in items[:-1])
        assert dec.sessions.receive(r, iter(items), 0.0)
    dec.run([])
    for a, b in zip(ref, typed):
        assert a.output == b.output


def test_peer_prefetch_pulls_session():
    """sessions.prefetch(rid, peer) pulls one resident session from a
    peer engine (the peer-tier of the HBM -> host -> peer hierarchy)."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    prompts = _prompts(cfg, (5, 4), seed=9)

    ref = _reqs(prompts)
    e_ref = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    e_ref.run(ref)

    far = _reqs(prompts)
    peer = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    local = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    assert peer.admit_batch(far, 0.0) == 2
    peer.step(0.0)
    peer.step(0.0)
    assert local.sessions.prefetch(far[0].rid, peer, 0.0)
    _drain(local)
    _drain(peer)
    for a, b in zip(ref, far):
        assert a.output == b.output


# ===================================================================== #
# DES mirror: KvPoolModel
# ===================================================================== #
def test_kvpool_model_occupancy_and_hits():
    kvm = KvPoolModel(block_tokens=64, pool_blocks=8,
                      base_prompt=1024, base_output=256).bind(1)
    r0 = ClusterRequest(rid=0, arrival=0.0, scale_prompt=0.25,
                        scale_output=0.25, session=7)
    assert kvm.admit(0, r0, 0.0) == 0.0          # 5 of 8 blocks
    kvm.release(0, r0, 10.0)
    assert kvm.cached(0, 7, 11.0) == 320         # resident after finish
    # same session re-admits: accumulated context rolls the blocks over
    r1 = ClusterRequest(rid=1, arrival=11.0, scale_prompt=352 / 1024,
                        scale_output=0.25, session=7)
    assert kvm.admit(0, r1, 11.0) == 11.0
    kvm.release(0, r1, 20.0)
    # pressure: a stranger needing 6 blocks waits for the active finish
    r2 = ClusterRequest(rid=2, arrival=12.0, scale_prompt=0.25,
                        scale_output=0.4, session=None)
    assert kvm.admit(0, r2, 12.0) == 20.0
    assert kvm.delayed == 1
    assert kvm.peaks()[0] >= 7
    assert 0.0 < kvm.util_at(0, 21.0) <= 1.0


def test_kvpool_model_lru_eviction():
    kvm = KvPoolModel(block_tokens=64, pool_blocks=6,
                      base_prompt=1024, base_output=256).bind(1)
    for sid in (1, 2):
        r = ClusterRequest(rid=sid, arrival=0.0, scale_prompt=0.125,
                           scale_output=0.125, session=sid)   # 3 blocks
        kvm.admit(0, r, float(sid))
        kvm.release(0, r, float(sid) + 0.5)
    # both resident; session 1 is LRU.  A 3-block stranger evicts it
    # (and only it: the freed 3 blocks cover the need).
    r = ClusterRequest(rid=9, arrival=5.0, scale_prompt=0.125,
                       scale_output=0.125, session=None)
    assert kvm.admit(0, r, 5.0) == 5.0           # no wait: eviction
    assert kvm.evictions == 1
    assert kvm.cached(0, 1, 5.0) == 0            # evicted
    assert kvm.cached(0, 2, 5.0) > 0             # survivor


def _chat_deployment(slo_ttft=0.005):
    g0 = random_dag(24, seed=2)
    nodes = [dataclasses.replace(
        n, phase="prefill" if n.idx < 12 else "decode")
        for n in g0.nodes]
    g = type(g0)(nodes, dict(g0.edges), name=g0.name + ".kv")
    g.validate()
    spec = DeploymentSpec(
        groups=[["a100", "l40s"]] * 4, anneal_iters=200,
        slos={"base": 0.05, "per_output_token": 0.0005,
              "ttft": slo_ttft},
        engine={"kv_block_tokens": 16, "max_len": 64, "slots": 4,
                "kv_pool_blocks": 8192})
    return spec, spec.compile(g)


def test_affinity_on_strictly_beats_off_on_chat_trace():
    """The tentpole's measured claim: with per-group KV occupancy and
    prefix-cache hits modeled, decode-session affinity ON yields
    strictly higher goodput than OFF on a multi-turn chat trace."""
    spec, dep = _chat_deployment()
    cap = dep.cluster().capacity
    tr = make_trace("chat", 8.0 * cap, 800, seed=7, think_mean=0.05,
                    first_prompt_mean=1024, new_tokens_mean=512,
                    output_mean=16)
    on = dep.simulate(tr, router=JSEDRouter(session_affinity=True),
                      events=None)
    off = dep.simulate(tr, router=JSEDRouter(session_affinity=False),
                       events=None)
    assert on.kv_hits > off.kv_hits
    assert on.slo_ok > off.slo_ok, (on.slo_ok, off.slo_ok)
    assert on.kv_hit_tokens > 0 and on.peak_kv_blocks


def test_kv_util_reaches_autoscaler():
    """ControlSignals.kv_util is populated and a kv_hi breach scales
    up from the reserve pool."""
    spec, dep = _chat_deployment()
    cap = dep.cluster().capacity
    tr = make_trace("chat", 8.0 * cap, 300, seed=7, think_mean=0.05,
                    first_prompt_mean=1024, new_tokens_mean=512,
                    output_mean=16)
    seen = []

    class Probe(AutoscalePolicy):
        def decide(self, sig):
            seen.append(sig.kv_util)
            return super().decide(sig)

    ctl = Probe(AutoscaleConfig(interval=0.005, window=0.02,
                                cooldown=0.0, warmup=0.0,
                                kv_hi=0.0001, queue_hi=1e9),
                inventory=[["a100"]])
    dep.simulate(tr, controller=ctl, events=None)
    assert seen and any(ku for ku in seen)       # kv_util populated
    assert any(d.action == "up" and "kv_util" in d.reason
               for d in ctl.decisions)


def test_spec_kv_knobs_validation_and_roundtrip():
    s = DeploymentSpec(groups=[["a100"]],
                       engine={"slots": 4, "max_len": 64,
                               "kv_block_tokens": 16})
    # slots= shim: pool defaults to the fixed-slot footprint
    assert s.kv_config() == {"kv_block_tokens": 16,
                             "kv_pool_blocks": 16,
                             "spill": True, "preempt_priority": True}
    assert DeploymentSpec.from_json(s.to_json()) == s
    assert s.kv_model() is not None
    assert DeploymentSpec(groups=[["a100"]]).kv_model() is None
    with pytest.raises(ValueError, match="requires kv_block_tokens"):
        DeploymentSpec(groups=[["a100"]], engine={"kv_pool_blocks": 8})
    with pytest.raises(ValueError, match="must divide"):
        DeploymentSpec(groups=[["a100"]],
                       engine={"kv_block_tokens": 48, "max_len": 64})
    with pytest.raises(ValueError, match="kv_block_tokens must be"):
        DeploymentSpec(groups=[["a100"]], engine={"kv_block_tokens": 0})


def test_chat_trace_accumulates_context():
    tr = make_trace("chat", 8.0, 300, seed=3)
    assert all(r.session is not None for r in tr)
    last = {}
    follow = 0
    for r in tr:
        if r.session in last:
            follow += 1
            assert r.prompt_tokens > last[r.session]
        last[r.session] = r.prompt_tokens
    assert follow > 30                   # genuinely multi-turn
    # deterministic
    assert tr == make_trace("chat", 8.0, 300, seed=3)
