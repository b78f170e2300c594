"""Live-engine chaos: flaky KV transfers and crash recovery must be
invisible in the tokens.

For every kernel family (dense / MoE / recurrent / hybrid):
  * a transiently flaky streamed PD handoff (per-shard failures fully
    absorbed by the link's retransmit budget) serves greedy tokens
    bit-identical to the fault-free run,
  * a persistently corrupting link (retry budget exhausted -> shard
    delivered corrupted) trips the receiver's checksum, and the
    re-prefill fallback on the decode engine is bit-identical,
  * an engine crash mid-decode with checkpoint-based recovery restores
    every accepted session on the survivor — zero lost, tokens
    bit-identical to the crash-free run.
"""
import dataclasses

import numpy as np
import pytest

import repro.configs as configs
from repro.models import model as M
from repro.serving.engine import Request
from repro.serving.faults import FaultPlan, RecoveryConfig
from repro.serving.kvpool import ShardChecksumError
from repro.serving.spec import DeploymentSpec

ARCHS = ("llama3_8b", "gpt_oss_20b", "rwkv6_3b", "zamba2_7b")


@pytest.fixture(scope="module", params=ARCHS)
def arch_setup(request):
    cfg = dataclasses.replace(configs.get_smoke(request.param),
                              dtype="float32")
    return request.param, cfg, M.init_params(cfg)


def _prompts(cfg, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in sizes]


def _reqs(cfg, max_new=6):
    return [Request(rid=i, prompt=p.copy(), max_new_tokens=max_new,
                    arrival=0.0)
            for i, p in enumerate(_prompts(cfg, (12, 9, 17)))]


def _pd_spec(arch):
    return DeploymentSpec(groups=[["h100"], ["a100"]], arch=arch,
                          pd=True, kv_chunks=4,
                          engine={"slots": 2, "max_len": 64})


def _pool_spec(arch):
    return DeploymentSpec(groups=[["h100"], ["a100"]], arch=arch,
                          engine={"slots": 4, "max_len": 64})


# ===================================================================== #
# Flaky streamed handoff
# ===================================================================== #
def test_transient_flaky_stream_bit_identical(arch_setup):
    arch, cfg, params = arch_setup
    spec = _pd_spec(arch)

    ref = _reqs(cfg)
    spec.compile().launch(cfg, params).run(ref)
    ref_out = [list(r.output) for r in ref]
    assert all(len(o) == 6 for o in ref_out)

    flaky = _reqs(cfg)
    dep = spec.compile().launch(cfg, params)
    dep.inject(FaultPlan(seed=1).flaky_link(0, 1, p=0.3,
                                            max_retries=50))
    stats = dep.run(flaky)
    assert [list(r.output) for r in flaky] == ref_out
    assert stats["kv_retries"] > 0          # the link really failed
    assert stats["kv_corrupted"] == 0       # ... and retries absorbed it
    assert stats["reprefills"] == 0


def test_corrupted_stream_reprefills_bit_identical(arch_setup):
    arch, cfg, params = arch_setup
    spec = _pd_spec(arch)

    ref = _reqs(cfg)
    spec.compile().launch(cfg, params).run(ref)
    ref_out = [list(r.output) for r in ref]

    bad = _reqs(cfg)
    dep = spec.compile().launch(cfg, params)
    # p=1, zero retries: every handoff delivers a corrupted first shard
    dep.inject(FaultPlan(seed=2).flaky_link(0, 1, p=1.0, max_retries=0))
    stats = dep.run(bad)
    assert [list(r.output) for r in bad] == ref_out
    assert stats["kv_corrupted"] == len(bad)
    assert stats["reprefills"] == len(bad)  # all fell back to decode


# ===================================================================== #
# Crash + checkpoint recovery on the colocated pool
# ===================================================================== #
def test_crash_recovery_zero_lost_bit_identical(arch_setup):
    arch, cfg, params = arch_setup
    spec = _pool_spec(arch)

    ref = _reqs(cfg, max_new=12)
    spec.compile().launch(cfg, params).run(ref)
    ref_out = [list(r.output) for r in ref]
    assert all(len(o) == 12 for o in ref_out)

    chaos = _reqs(cfg, max_new=12)
    dep = spec.compile().launch(cfg, params)
    # group 0 straggles until the crash, so its sessions are still
    # mid-decode at t=0.25 however fast the (already compiled) steps run
    dep.inject(FaultPlan(seed=4).crash(0.25, group=0, recover_at=0.6)
               .straggle(0.0, 0.25, group=0, factor=8.0),
               recovery=RecoveryConfig(interval=0.02,
                                       min_dirty_tokens=1))
    stats = dep.run(chaos)
    # recovered == 0 means the crash landed before any admission (or
    # after the drain) — the run proves nothing about recovery then
    assert stats["lost_sessions"] > 0
    assert stats["recovered_sessions"] == stats["lost_sessions"]
    assert stats["checkpoints"] > 0
    assert [list(r.output) for r in chaos] == ref_out   # dropped == 0,
    #                                         replay is bit-identical


# ===================================================================== #
# Cheap mechanism units (one family is enough)
# ===================================================================== #
def test_checksum_detects_corruption():
    cfg = dataclasses.replace(configs.get_smoke("llama3_8b"),
                              dtype="float32")
    params = M.init_params(cfg)
    from repro.serving.engine import ServingEngine
    from repro.serving.faults import corrupt_slice
    from repro.serving.kvpool import KvSlice

    pre = ServingEngine(cfg, params, slots=2, max_len=32,
                        prefill_chunk=4)
    req = Request(rid=0, prompt=_prompts(cfg, (9,))[0],
                  max_new_tokens=4, arrival=0.0)
    items = list(pre.sessions.stream(req, 0.0, checksum=True))
    shard = items[0]
    assert isinstance(shard, KvSlice) and shard.checksum is not None
    assert shard.verify()
    bad = corrupt_slice(shard)
    assert bad.checksum == shard.checksum   # checksum kept, data bad
    assert not bad.verify()

    dec = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    req2 = Request(rid=1, prompt=req.prompt.copy(), max_new_tokens=4,
                   arrival=0.0)
    with pytest.raises(ShardChecksumError):
        dec.sessions.receive(req2, iter([bad] + items[1:]), 0.0)
    # rollback freed the reserved slot; a clean retry succeeds
    assert dec.active.count(None) == dec.slots
    assert req2.output == []
    items2 = list(pre.sessions.stream(req2, 0.0, checksum=True))
    assert dec.sessions.receive(req2, iter(items2), 0.0)


def test_inject_rejects_crash_on_pd_pair():
    spec = _pd_spec("llama3_8b")
    cfg = dataclasses.replace(configs.get_smoke("llama3_8b"),
                              dtype="float32")
    dep = spec.compile().launch(cfg, M.init_params(cfg))
    with pytest.raises(ValueError, match="fixed topology"):
        dep.inject(FaultPlan().crash(1.0, group=0))
