"""Overlapped KV streaming: chunked prefill + layer-streamed handoff.

Covers the pipelined-handoff acceptance criteria:
  * chunked prefill == whole-prompt prefill (logits parity) for every
    decoder family, at several chunk sizes,
  * streamed (layer, chunk) handoff decodes bit-identically to the
    serial handoff AND to a single engine that never split, for all
    four families,
  * the DES's overlapped KV arrival is NEVER later than the serial
    transfer edge (the sender's serial fallback), while an interior
    chunk count strictly beats both extremes when transfers are
    latency-amortizable,
  * chunked colocated admission interleaves decode steps between
    prefill chunks (the long-prompt head-of-line fix) without changing
    any output token,
  * PDRouter decode-session affinity reuses the decode group's
    resident state for follow-up turns (transfers_avoided accounting),
  * sessions.restore stamps wall-clock-mode times through the engine
    clock (regression: a literal 0.0 fallback).
"""
import dataclasses

import numpy as np
import pytest

from conftest import random_dag
import repro.configs as configs
from repro.core.monitor import MonitorConfig
from repro.core.simulator import (KV_TRANSFER, Interconnect,
                                  _stream_kv)
from repro.models import model as M
from repro.serving.cluster import TesseraCluster
from repro.serving.engine import Request, ServingEngine
from repro.serving.kvpool import KvSlice, SessionState
from repro.serving.router import PDRouter
from repro.serving.workload import poisson_trace

ARCHS = ("llama3_8b", "gpt_oss_20b", "rwkv6_3b", "zamba2_7b")

HET_GROUPS = [["h100", "rtxpro6000"], ["a100", "l40s"],
              ["a100", "l40s"], ["a100", "l40s"]]


def _smoke(arch):
    return dataclasses.replace(configs.get_smoke(arch), dtype="float32")


def pd_dag(n: int = 24, seed: int = 2, decode_weight: float = 8.0):
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


# ===================================================================== #
# Model level: chunked prefill == whole prefill
# ===================================================================== #
@pytest.mark.parametrize("arch", ARCHS)
def test_chunked_prefill_matches_whole(arch):
    """prefill_chunked must reproduce the whole-prompt prefill's
    last-position logits for every family and chunk size (including
    per-row last_pos selection across chunk boundaries)."""
    import jax.numpy as jnp
    cfg = _smoke(arch)
    params = M.init_params(cfg)
    rng = np.random.default_rng(0)
    B, S, T = 2, 7, 16
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, S)),
                       jnp.int32)
    last = jnp.asarray([S - 1, 4], jnp.int32)
    lg_w, cache_w = M.prefill(params, cfg, toks,
                              M.init_cache(cfg, B, T), last_pos=last)
    for cs in (1, 2, 3, 5):
        lg_c, cache_c = M.prefill_chunked(
            params, cfg, toks, M.init_cache(cfg, B, T),
            chunk_size=cs, last_pos=last)
        np.testing.assert_allclose(np.asarray(lg_c), np.asarray(lg_w),
                                   rtol=2e-5, atol=2e-5)
        # the filled cache must match too (exported handoffs come from
        # it); attention KV compared over the filled prefix only
        for key in cache_w:
            a = M.export_kv(cfg, cache_w, 0, S)[key]
            b = M.export_kv(cfg, cache_c, 0, S)[key]
            for la, lb in zip(*(map(
                    lambda t: __import__("jax").tree_util.tree_leaves(t),
                    (a, b)))):
                np.testing.assert_allclose(np.asarray(lb), np.asarray(la),
                                           rtol=2e-5, atol=2e-5)


def test_layer_shards_reassemble_whole_export():
    """Installing every (layer, chunk) shard == import_kv of the whole
    export, and the summed shard bytes match the monolithic payload."""
    import jax
    cfg = _smoke("zamba2_7b")        # hybrid: kv AND mamba components
    params = M.init_params(cfg)
    rng = np.random.default_rng(1)
    S, T = 6, 16
    toks = __import__("jax").numpy.asarray(
        rng.integers(0, cfg.vocab_size, size=(2, S)), dtype="int32")
    _, cache = M.prefill(params, cfg, toks, M.init_cache(cfg, 2, T))
    whole = M.import_kv(cfg, M.init_cache(cfg, 1, T), 0,
                        M.export_kv(cfg, cache, 1, S))
    sharded = M.init_cache(cfg, 1, T)
    total = 0
    for key, L in M.cache_layer_counts(cache).items():
        for layer in range(L):
            if key == "kv" and cfg.sliding_window is None:
                for t0 in range(0, S, 2):
                    sh = M.export_kv_shard(cfg, cache, 1, key, layer,
                                           t0, min(t0 + 2, S))
                    total += M.kv_state_bytes(sh)
                    sharded = M.import_kv_shard(cfg, sharded, 0, key,
                                                layer, sh, t0)
            else:
                sh = M.export_kv_shard(cfg, cache, 1, key, layer)
                total += M.kv_state_bytes(sh)
                sharded = M.import_kv_shard(cfg, sharded, 0, key, layer,
                                            sh)
    assert total == M.kv_state_bytes(M.export_kv(cfg, cache, 1, S))
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(sharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mamba2_state_carries_across_chunks():
    """Regression for the latent SSD bug chunking exposed: mamba2 with
    an incoming state and S > 1 must CONTINUE that state, not restart
    from zeros."""
    import jax, jax.numpy as jnp
    from repro.models import ssm as S
    cfg = _smoke("zamba2_7b")
    p = S.init_mamba2(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.float32)
    fresh = lambda: {k: v[0] for k, v in          # noqa: E731
                     S.make_mamba2_state(cfg, 2).items()}
    y_whole, st_w = S.mamba2(p, x, cfg, state=fresh())
    st = fresh()
    y1, st = S.mamba2(p, x[:, :3], cfg, state=st)
    y2, st = S.mamba2(p, x[:, 3:], cfg, state=st)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], axis=1)),
        np.asarray(y_whole), rtol=2e-5, atol=2e-5)
    for k in st_w:
        np.testing.assert_allclose(np.asarray(st[k]), np.asarray(st_w[k]),
                                   rtol=2e-5, atol=2e-5)


# ===================================================================== #
# Engine level: streamed handoff + chunked colocated admission
# ===================================================================== #
@pytest.mark.parametrize("arch", ARCHS)
def test_streamed_handoff_bit_identical(arch):
    """sessions.stream -> sessions.receive must produce the
    same greedy tokens as a single engine that never split, for every
    family (ring-buffer SWA falls back to per-layer streaming)."""
    cfg = _smoke(arch)
    params = M.init_params(cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (9, 5)]
    singles = [Request(rid=i, prompt=p.copy(), max_new_tokens=6)
               for i, p in enumerate(prompts)]
    ref = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    ref.run(singles)

    splits = [Request(rid=i, prompt=p.copy(), max_new_tokens=6)
              for i, p in enumerate(prompts)]
    pre = ServingEngine(cfg, params, slots=2, max_len=32)
    dec = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    for req in splits:
        shards = []
        def spy(gen):
            for item in gen:
                shards.append(item)
                yield item
        assert dec.sessions.receive(
            req, spy(pre.sessions.stream(req, 0.0, chunk_size=3)), 0.0)
        header = shards[-1]
        assert isinstance(header, SessionState) and not header.done
        # every pre-header item is a shard with a payload; chunked
        # families carry (layer, chunk) token ranges on their kv shards
        body = shards[:-1]
        assert body and all(isinstance(it, KvSlice)
                            and it.state is not None and it.nbytes > 0
                            for it in body)
        kv = [it for it in body if it.component == "kv"]
        if kv and cfg.sliding_window is None:
            expect = {(t0, min(t0 + 3, len(req.prompt)))
                      for t0 in range(0, len(req.prompt), 3)}
            assert {(it.t0, it.t1) for it in kv} == expect
        assert header.nbytes == sum(it.nbytes for it in body)
    assert dec.stats.prefill_batches == 0
    while dec._any_active():
        dec.step(0.0)
    dec.sync(0.0)
    assert [r.output for r in splits] == [r.output for r in singles]


def test_streamed_handoff_done_at_prefill_releases_slot():
    """A 1-token request finishes at prefill AFTER its shards already
    streamed: the done header must release the reserved decode slot
    and the producer finalizes the request (no retry livelock)."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    rng = np.random.default_rng(3)
    req = Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=6).astype(np.int32), max_new_tokens=1)
    pre = ServingEngine(cfg, params, slots=1, max_len=16)
    dec = ServingEngine(cfg, params, slots=1, max_len=16)
    assert dec.sessions.receive(
        req, pre.sessions.stream(req, 0.0, chunk_size=2), 0.0)
    assert dec.active == [None]              # slot released
    assert not dec._any_active()
    assert pre.stats.completed == 1 and len(req.output) == 1


def test_streamed_handoff_full_engine_rejects_without_consuming():
    """No free slot -> False, and the producer generator must NOT have
    been advanced (nothing prefilled, nothing lost)."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    rng = np.random.default_rng(4)
    mk = lambda rid: Request(rid=rid, prompt=rng.integers(  # noqa: E731
        0, cfg.vocab_size, size=5).astype(np.int32), max_new_tokens=4)
    pre = ServingEngine(cfg, params, slots=2, max_len=16)
    dec = ServingEngine(cfg, params, slots=1, max_len=16)
    first = mk(0)
    assert dec.sessions.receive(
        first, pre.sessions.stream(first, 0.0, chunk_size=2), 0.0)
    blocked = mk(1)
    gen = pre.sessions.stream(blocked, 0.0, chunk_size=2)
    before = pre.stats.prefill_batches
    assert not dec.sessions.receive(blocked, gen, 0.0)
    assert pre.stats.prefill_batches == before   # generator untouched
    assert blocked.output == []


def test_streamed_handoff_oversized_releases_slot():
    """An oversized handoff must fail WITHOUT leaking the reserved
    slot: the engine keeps serving afterwards."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    rng = np.random.default_rng(6)
    big = Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=20).astype(np.int32), max_new_tokens=4)
    pre = ServingEngine(cfg, params, slots=1, max_len=32)
    dec = ServingEngine(cfg, params, slots=1, max_len=16)   # too small
    with pytest.raises(AssertionError, match="max_len"):
        dec.sessions.receive(
            big, pre.sessions.stream(big, 0.0, chunk_size=4), 0.0)
    assert dec.active == [None]              # slot not leaked
    ok = Request(rid=1, prompt=rng.integers(
        0, cfg.vocab_size, size=5).astype(np.int32), max_new_tokens=3)
    assert dec.sessions.receive(
        ok, pre.sessions.stream(ok, 0.0, chunk_size=2), 0.0)
    while dec._any_active():
        dec.step(0.0)
    dec.sync(0.0)
    assert dec.stats.completed == 1


@pytest.mark.parametrize("arch", ("llama3_8b", "rwkv6_3b"))
def test_chunked_admission_interleaves_decode(arch):
    """With prefill_chunk set, a long admitted prompt must let live
    decode slots step between chunks — and change no output token."""
    cfg = _smoke(arch)
    params = M.init_params(cfg)
    rng = np.random.default_rng(11)
    short = Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=4).astype(np.int32), max_new_tokens=12)
    long_p = rng.integers(0, cfg.vocab_size, size=20).astype(np.int32)

    def run(chunk):
        eng = ServingEngine(cfg, params, slots=2, max_len=40,
                            sync_every=1, prefill_chunk=chunk)
        a = dataclasses.replace(short, output=[])
        eng.admit(a, 0.0)
        b = Request(rid=1, prompt=long_p.copy(), max_new_tokens=4)
        steps_before = eng.stats.decode_steps
        eng.admit(b, 0.0)
        interleaved = eng.stats.decode_steps - steps_before
        while eng._any_active():
            eng.step(0.0)
        eng.sync(0.0)
        return a.output, b.output, interleaved

    out_a0, out_b0, il0 = run(None)
    out_a1, out_b1, il1 = run(4)
    assert il0 == 0                  # serial prefill: decode frozen
    assert il1 > 0                   # chunked: decode streamed between
    assert (out_a1, out_b1) == (out_a0, out_b0)


def test_sessions_restore_uses_engine_clock_when_now_is_none():
    """Regression: sessions.restore(now=None) must stamp wall-clock-mode
    times through the engine clock, not a literal 0.0."""
    cfg = _smoke("llama3_8b")
    params = M.init_params(cfg)
    rng = np.random.default_rng(5)
    req = Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=5).astype(np.int32), max_new_tokens=3)
    pre = ServingEngine(cfg, params, slots=1, max_len=16)
    st = pre.sessions.prefill(req, 0.0)
    dec = ServingEngine(cfg, params, slots=1, max_len=16, sync_every=1)
    dec._clock = lambda: 7.5                 # wall-clock mode
    try:
        assert dec.sessions.restore(req, st, now=None)
        assert req.ttft == 7.5
        while dec._any_active():
            dec.step(None)
        dec.sync(None)
    finally:
        dec._clock = None
    assert req.finished == 7.5               # not stamped at t=0


# ===================================================================== #
# DES level: overlapped transfer model
# ===================================================================== #
def test_stream_kv_never_later_than_serial():
    """Property: for ANY (bytes, bandwidth, latency, prefill span,
    chunk count), the streamed KV arrival <= the serial edge — the
    sender falls back to one deferred transfer when chunking loses."""
    for bw in (1e8, 1e9, 100e9):
        for base in (0.0, 1e-5, 5e-3):
            for nbytes in (1e3, 1e6, 1e9):
                for span in (1e-4, 0.05, 2.0):
                    ic = Interconnect(default_bw=bw, base_latency=base)
                    serial = span + ic.transfer_time(nbytes, 0, 1)
                    for n in (1, 2, 4, 8, 32, 128):
                        kv_at, evs, busy = _stream_kv(
                            ic, nbytes, 0, 1, 0.0, span, n)
                        assert kv_at <= serial + 1e-12
                        assert evs[-1][1] == pytest.approx(kv_at)
                        assert all(e1 >= e0 for e0, e1 in evs)
                        assert busy >= 0.0


def test_stream_kv_interior_optimum_exists():
    """With per-transfer base latency, a moderate chunk count beats
    BOTH extremes (1 chunk defers all bytes past prefill-end; huge n
    drowns in base latency and falls back to serial)."""
    ic = Interconnect(default_bw=1e9, base_latency=2e-4)
    nbytes, span = 8e6, 0.02
    kv = {n: _stream_kv(ic, nbytes, 0, 1, 0.0, span, n)[0]
          for n in (1, 8, 4096)}
    assert kv[8] < kv[1]                       # overlap hides transfer
    serial = span + ic.transfer_time(nbytes, 0, 1)
    assert kv[1] == pytest.approx(serial)
    assert kv[4096] == pytest.approx(serial)   # fallback kicked in
    # compute-bound best case: only the last chunk's tail remains
    assert kv[8] >= span + ic.base_latency + (nbytes / 8) / 1e9 - 1e-12


def test_overlap_ttft_never_later_end_to_end(pd_cluster):
    """Every request's TTFT under kv_chunks=n must be <= the serial
    split's TTFT, and per-chunk KV_TRANSFER events appear in the log."""
    pd_cluster.interconnect = Interconnect(default_bw=2e9,
                                           base_latency=1e-5)
    try:
        tr = poisson_trace(3.0, 40, seed=21)
        mk = lambda: PDRouter(prefill_pool=[0],       # noqa: E731
                              decode_pool=[1, 2, 3], max_kv_lag=1.0)
        serial = pd_cluster.simulate_pd(tr, mk())
        for n in (2, 8, 32):
            ov = pd_cluster.simulate_pd(tr, mk(), kv_chunks=n)
            assert len(ov.ttfts) == len(serial.ttfts)
            for a, b in zip(ov.ttfts, serial.ttfts):
                assert a <= b + 1e-9
            assert ov.transfers == serial.transfers
            xfer = [e for e in ov.events if e[2] == KV_TRANSFER]
            # at least one request streamed in >1 chunk
            assert len(xfer) > serial.transfers
        # determinism of the overlapped replay
        r1 = pd_cluster.simulate_pd(tr, mk(), kv_chunks=8)
        r2 = pd_cluster.simulate_pd(tr, mk(), kv_chunks=8)
        assert r1.events == r2.events and r1.ttfts == r2.ttfts
    finally:
        pd_cluster.interconnect = Interconnect()


def test_pd_session_affinity_avoids_transfers(pd_cluster):
    """Follow-up turns of a session run on the decode group holding
    their resident state: no new KV transfer, counted per run."""
    tr = poisson_trace(3.0, 80, seed=9, session_follow=0.5)
    splits = sum(1 for r in tr)
    mk = lambda **kw: PDRouter(prefill_pool=[0],      # noqa: E731
                               decode_pool=[1, 2, 3],
                               max_kv_lag=1.0, **kw)
    base = pd_cluster.simulate_pd(tr, mk())
    assert base.transfers == splits and base.transfers_avoided == 0
    router = mk(session_affinity=True)
    aff = pd_cluster.simulate_pd(tr, router)
    assert aff.transfers_avoided > 0
    assert aff.transfers + aff.transfers_avoided == splits
    assert aff.completed == len(tr)
    # the counter reports the PER-RUN delta even when a router is
    # reused (its session map persists, so the replay finds every
    # session already resident — more avoided, never double-counted)
    aff2 = pd_cluster.simulate_pd(tr, router)
    assert aff.transfers_avoided <= aff2.transfers_avoided <= len(tr)
    fresh = pd_cluster.simulate_pd(tr, mk(session_affinity=True))
    assert fresh.transfers_avoided == aff.transfers_avoided
    # affinity_break=0 migrates instead of joining a backlogged home:
    # strictly fewer avoided transfers than always-stay
    strict = pd_cluster.simulate_pd(
        tr, mk(session_affinity=True, affinity_break=0.0))
    assert strict.transfers_avoided <= aff.transfers_avoided


def test_session_affinity_does_not_bypass_slo_shed(pd_cluster):
    """A follow-up turn whose home group cannot meet its SLO must be
    shed like any other request — affinity is not an admission-control
    bypass (and a shed follow-up is not counted as avoided)."""
    from repro.core.simulator import ClusterRequest
    router = PDRouter(prefill_pool=[0], decode_pool=[1, 2, 3],
                      max_kv_lag=1.0, session_affinity=True,
                      slo_shed=True)
    replicas = pd_cluster.build_replicas()
    first = ClusterRequest(rid=0, arrival=0.0, session=42)
    decision = router.route(first, replicas, 0.0)
    assert isinstance(decision, tuple)
    home = decision[1]
    doomed = ClusterRequest(rid=1, arrival=0.0, session=42,
                            slo_ttft=1e-12)
    assert router.route(doomed, replicas, 0.0) == -1
    assert router.transfers_avoided == 0
    fine = ClusterRequest(rid=2, arrival=0.0, session=42, slo_ttft=1e9)
    assert router.route(fine, replicas, 0.0) == home
    assert router.transfers_avoided == 1


def test_pd_router_shed_accounts_transfer_tail(pd_cluster):
    """With an interconnect, the shed estimate includes the KV-transfer
    tail, and overlapped streaming (kv_chunks>1) projects the EARLIER
    effective arrival — a request doomed by the serial transfer clears
    admission under streaming."""
    from repro.core.simulator import ClusterRequest
    ic = Interconnect(default_bw=1e8, base_latency=1e-4)
    replicas = pd_cluster.build_replicas()
    n = 16
    tail_serial = ic.transfer_time(8e6, 0, 1)
    tail_overlap = ic.base_latency + (8e6 / n) / 1e8
    assert tail_overlap < tail_serial
    tp = replicas[0].predicted_phase_service(
        ClusterRequest(rid=0, arrival=0.0), "prefill")
    slo = tp + (tail_serial + tail_overlap) / 2.0
    req = ClusterRequest(rid=0, arrival=0.0, kv_bytes=8e6, slo_ttft=slo)
    mk = lambda **kw: PDRouter(prefill_pool=[0],      # noqa: E731
                               decode_pool=[1, 2, 3], max_kv_lag=1.0,
                               slo_shed=True, interconnect=ic, **kw)
    assert mk().route(req, replicas, 0.0) == -1              # serial
    decision = mk(kv_chunks=n).route(req, replicas, 0.0)     # streamed
    assert isinstance(decision, tuple) and decision[0] == 0
    # without an interconnect the tail is unknown -> not charged
    no_ic = PDRouter(prefill_pool=[0], decode_pool=[1, 2, 3],
                     max_kv_lag=1.0, slo_shed=True)
    assert isinstance(no_ic.route(req, replicas, 0.0), tuple)
