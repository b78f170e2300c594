"""Fast-path parity: the indexed/fused dispatch program must be
bit-identical to the reference execution, across all four kernel
families, and must place weights exactly once per device."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.core import analyzer, planner
from repro.core.costmodel import GPU_A100, GPU_L40S
from repro.core.executor import build_executable
from repro.core.pipeline import PipelinedRunner
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba2_ssd.ref import ssd_ref
from repro.kernels.moe_gmm.ref import gmm_ref
from repro.kernels.rwkv6.ref import wkv_ref
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine

DEVS = [GPU_A100, GPU_L40S]
KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------- #
# One representative computation per kernel family.  Each mixes the
# family's core op with surrounding elementwise/matmul work so the
# planner produces a multi-stage decomposition worth fusing.
# --------------------------------------------------------------------- #
def _flash_attention_case():
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (1, 2, 64, 32))
    k = jax.random.normal(ks[1], (1, 2, 64, 32))
    v = jax.random.normal(ks[2], (1, 2, 64, 32))
    w = jax.random.normal(ks[3], (32, 32)) * 0.2

    def fn(q, k, v, w):
        o = attention_ref(q, k, v, causal=True)
        return jnp.tanh(o @ w).sum(axis=1), o.mean()
    return fn, (q, k, v, w)


def _moe_gmm_case():
    sizes = jnp.asarray([10, 22, 0, 16], jnp.int32)
    T, d, E, f = 48, 16, 4, 32
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (T, d))
    w = jax.random.normal(ks[1], (E, d, f)) * 0.1

    def fn(x, w):
        h = gmm_ref(x, w, sizes)
        return jax.nn.gelu(h).sum(axis=-1), h.max()
    return fn, (x, w)


def _rwkv6_case():
    ks = jax.random.split(KEY, 6)
    B, S, H, P = 1, 16, 2, 8
    r = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, P)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, P)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, P)))
    u = jax.random.normal(ks[4], (H, P)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, P, P)) * 0.1

    def fn(r, k, v, w, u, s0):
        y, sT = wkv_ref(r, k, v, w, u, s0)
        return jnp.tanh(y), sT
    return fn, (r, k, v, w, u, s0)


def _mamba2_ssd_case():
    ks = jax.random.split(KEY, 4)
    B, S, H, P, N = 1, 32, 2, 8, 4
    xh = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    B_ = jax.random.normal(ks[1], (B, S, N)) * 0.5
    C_ = jax.random.normal(ks[2], (B, S, N)) * 0.5
    a_log = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))

    def fn(xh, B_, C_, a_log):
        y, hT = ssd_ref(xh, B_, C_, a_log)
        return y.sum(axis=-1), hT
    return fn, (xh, B_, C_, a_log)


CASES = {
    "flash_attention": _flash_attention_case,
    "moe_gmm": _moe_gmm_case,
    "rwkv6": _rwkv6_case,
    "mamba2_ssd": _mamba2_ssd_case,
}


def _assert_bit_identical(got, want):
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), got, want)


@pytest.mark.parametrize("family", sorted(CASES))
@pytest.mark.parametrize("policy", ["throughput", "latency"])
def test_fast_path_bit_identical(family, policy):
    """Indexed-env (fused) executor == reference per-stage walk ==
    plain fn(*args), bitwise."""
    fn, args = CASES[family]()
    tg = analyzer.analyze(fn, *args)
    p = planner.plan(tg.graph, DEVS, policy=policy, cache=False)
    exe = build_executable(tg, p)
    fast = exe(*args)
    ref = exe.call_reference(*args)
    want = jax.jit(fn)(*args)
    _assert_bit_identical(fast, ref)
    _assert_bit_identical(fast, want)


@pytest.mark.parametrize("family", sorted(CASES))
def test_pipelined_runner_fast_path_parity(family):
    fn, args = CASES[family]()
    tg = analyzer.analyze(fn, *args)
    p = planner.plan(tg.graph, DEVS, cache=False)
    exe = build_executable(tg, p)
    runner = PipelinedRunner(exe, max_inflight=3)
    outs, stats = runner.run([(args, {})] * 3)
    assert stats.completed == 3
    # fusion must not dispatch more units than plan stages
    assert stats.stage_dispatches == 3 * exe.num_units
    assert exe.num_units <= len(exe.stages)
    want = jax.jit(fn)(*args)
    for o in outs:
        _assert_bit_identical(o, want)


def test_weights_placed_exactly_once_across_calls():
    """Repeated calls with identical params must not re-place weights;
    the cache key is (arg slot, device index) — stable across GC."""
    fn, args = CASES["flash_attention"]()
    tg = analyzer.analyze(fn, *args)
    p = planner.plan(tg.graph, DEVS, cache=False)
    exe = build_executable(tg, p)
    exe(*args)
    placed_after_first = exe.weight_places
    for _ in range(3):
        exe(*args)
    assert exe.weight_places == placed_after_first
    # per (slot, device) pair at most one cache entry
    assert len(exe._weight_cache) <= len(exe.program.arg_slots) * max(
        1, len(exe._devices))
    # changed weights must be re-placed (identity check, not id())
    new_args = tuple(a + 0 for a in args)
    exe(*new_args)
    assert exe.weight_places >= placed_after_first


def test_fusion_reduces_dispatch_on_single_device():
    """On one physical device every stage fuses into a single unit."""
    fn, args = CASES["moe_gmm"]()
    tg = analyzer.analyze(fn, *args)
    p = planner.plan(tg.graph, DEVS, cache=False)
    exe = build_executable(tg, p)     # default map: one physical device
    if len(exe.stages) > 1:
        assert exe.num_units == 1


# --------------------------------------------------------------------- #
# Sync-free engine semantics
# --------------------------------------------------------------------- #
def _engine_cfg():
    return dataclasses.replace(configs.get_smoke("qwen3_1_7b"),
                               dtype="float32")


def test_engine_sync_every_invariant():
    """Token streams must not depend on the sync cadence."""
    cfg = _engine_cfg()
    params = M.init_params(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (4, 7, 5, 6, 4)]

    def run(sync_every):
        reqs = [Request(rid=i, prompt=p, max_new_tokens=6, arrival=0.0)
                for i, p in enumerate(prompts)]
        eng = ServingEngine(cfg, params, slots=3, max_len=32,
                            sync_every=sync_every)
        stats = eng.run(reqs)
        assert stats.completed == len(reqs)
        return [r.output for r in reqs]

    base = run(1)
    for k in (2, 4, 16):
        assert run(k) == base


def test_engine_batched_prefill_matches_sequential():
    """Padded multi-request prefill must reproduce batch-1 prefills."""
    cfg = _engine_cfg()
    params = M.init_params(cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (3, 9, 6)]         # deliberately ragged

    def naive(prompt, n):
        cache = M.init_cache(cfg, 1, 64)
        logits, cache = M.prefill(params, cfg,
                                  jnp.asarray(prompt)[None], cache)
        toks = [int(jnp.argmax(logits, -1)[0])]
        pos = len(prompt)
        for _ in range(n - 1):
            lg, cache = M.decode_step(
                params, cfg, jnp.asarray([[toks[-1]]], jnp.int32),
                cache, jnp.asarray([pos], jnp.int32))
            toks.append(int(jnp.argmax(lg, -1)[0]))
            pos += 1
        return toks

    want = [naive(p, 5) for p in prompts]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=5, arrival=0.0)
            for i, p in enumerate(prompts)]
    eng = ServingEngine(cfg, params, slots=3, max_len=64, sync_every=4)
    stats = eng.run(reqs)
    assert stats.prefill_batches == 1      # ONE padded admission batch
    assert [r.output for r in reqs] == want


def test_engine_ssm_family_matches_sequential():
    """Recurrent families must NOT be length-padded at prefill: the
    state integrates every input token, so engine output must equal the
    sequential reference for prompts of awkward (non-multiple-of-8)
    lengths."""
    cfg = dataclasses.replace(configs.get_smoke("rwkv6_3b"),
                              dtype="float32")
    params = M.init_params(cfg)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 3, 6)]

    def naive(prompt, n):
        cache = M.init_cache(cfg, 1, 32)
        logits, cache = M.prefill(params, cfg,
                                  jnp.asarray(prompt)[None], cache)
        toks = [int(jnp.argmax(logits, -1)[0])]
        pos = len(prompt)
        for _ in range(n - 1):
            lg, cache = M.decode_step(
                params, cfg, jnp.asarray([[toks[-1]]], jnp.int32),
                cache, jnp.asarray([pos], jnp.int32))
            toks.append(int(jnp.argmax(lg, -1)[0]))
            pos += 1
        return toks

    want = [naive(p, 5) for p in prompts]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=5, arrival=0.0)
            for i, p in enumerate(prompts)]
    eng = ServingEngine(cfg, params, slots=3, max_len=32, sync_every=4)
    stats = eng.run(reqs)
    assert stats.completed == 3
    # equal-length prompts batch together; the odd one gets its own
    assert stats.prefill_batches == 2
    assert [r.output for r in reqs] == want


def test_engine_eos_at_prefill_frees_slot():
    """A request whose FIRST (prefill-sampled) token is EOS must be
    finalized AND its device slot deactivated — no ghost decoding."""
    cfg = _engine_cfg()
    params = M.init_params(cfg)
    rng = np.random.default_rng(21)
    prompt = rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
    # discover what greedy sampling emits at prefill, then make it EOS
    cache = M.init_cache(cfg, 1, 32)
    logits, _ = M.prefill(params, cfg, jnp.asarray(prompt)[None], cache)
    first_tok = int(jnp.argmax(logits, -1)[0])

    req = Request(rid=0, prompt=prompt, max_new_tokens=8, arrival=0.0)
    eng = ServingEngine(cfg, params, slots=2, max_len=32,
                        eos_id=first_tok, sync_every=4)
    stats = eng.run([req])
    assert stats.completed == 1
    assert req.output == [first_tok]
    assert not np.asarray(eng.active_mask).any()
    assert eng.active == [None, None]


def test_engine_midwindow_admission_keeps_tokens():
    """admit()/admit_batch() mid-window must flush the buffered sync
    window first — otherwise the new slot's tokens hide behind the old
    idle markers and are dropped at the next sync."""
    cfg = _engine_cfg()
    params = M.init_params(cfg)
    rng = np.random.default_rng(17)
    p1, p2 = (rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
              for _ in range(2))
    eng = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=8)
    r1 = Request(rid=0, prompt=p1, max_new_tokens=8, arrival=0.0)
    r2 = Request(rid=1, prompt=p2, max_new_tokens=4, arrival=0.0)
    assert eng.admit(r1, 0.0)
    for _ in range(3):
        eng.step(0.0)                     # slot 1 idle: 3 buffered -1s
    assert eng.admit(r2, 0.0)             # must flush the window
    while eng._any_active():
        eng.step(0.0)
    eng.sync(0.0)
    assert len(r1.output) == 8
    assert len(r2.output) == 4            # tokens not lost to -1 prefix


def test_engine_ttft_is_stamped_after_prefill():
    """TTFT must be >= the arrival->prefill-materialization gap (never
    the dispatch-time stamp the old engine recorded)."""
    cfg = _engine_cfg()
    params = M.init_params(cfg)
    rng = np.random.default_rng(11)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=6)
                    .astype(np.int32),
                    max_new_tokens=3, arrival=0.0) for i in range(2)]
    eng = ServingEngine(cfg, params, slots=2, max_len=32)
    stats = eng.run(reqs)
    assert stats.completed == 2
    for r in reqs:
        assert r.ttft > 0.0                 # real elapsed time, not 0
        assert r.finished >= r.ttft


def test_engine_counts_prefill_padding():
    """``prefill_tokens`` counts the real prompt tokens admitted,
    ``prefill_padded_tokens`` the rows x length the prefill computed;
    they agree for a lone prompt whose length is a multiple of 8."""
    cfg = _engine_cfg()
    params = M.init_params(cfg)
    rng = np.random.default_rng(13)

    def served(lens):
        eng = ServingEngine(cfg, params, slots=4, max_len=32)
        eng.run([Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                                    size=n).astype(np.int32),
                         max_new_tokens=2) for i, n in enumerate(lens)])
        return eng.stats.prefill_tokens, eng.stats.prefill_padded_tokens

    assert served([16]) == (16, 16)
    # three ragged prompts: one batch of 4 rows x 16 positions
    assert served([5, 9, 16]) == (30, 64)


def test_engine_stamps_admission_between_arrival_and_first_token():
    """``admitted`` falls between the request's arrival and its first
    token, for requests admitted at once and for ones that queue."""
    cfg = _engine_cfg()
    params = M.init_params(cfg)
    rng = np.random.default_rng(19)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=6)
                    .astype(np.int32), max_new_tokens=4, arrival=0.01 * i)
            for i in range(5)]
    eng = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=2)
    assert eng.run(reqs).completed == 5
    for r in reqs:
        assert r.arrival <= r.admitted <= r.ttft <= r.finished


def test_engine_trace_driven():
    """serving.workload traces drive the real engine end to end."""
    from repro.serving.engine import requests_from_trace
    from repro.serving.workload import poisson_trace

    cfg = _engine_cfg()
    params = M.init_params(cfg)
    trace = poisson_trace(rate=50.0, num_requests=6, seed=2)
    reqs = requests_from_trace(trace, cfg.vocab_size, max_prompt=8,
                               max_new=4, time_scale=0.1)
    eng = ServingEngine(cfg, params, slots=2, max_len=32, sync_every=4)
    stats = eng.run(reqs)
    assert stats.completed == 6
    assert all(len(r.output) >= 1 for r in reqs)
    assert stats.summary()["mean_tpot"] >= 0.0


def _constant_payload_bytes(hlo_text: str):
    """Bytes of every dense constant literal in a StableHLO module."""
    import re
    return [len(m) // 2 - 1 for m in
            re.findall(r'stablehlo\.constant dense<"(0x[0-9A-Fa-f]*)">',
                       hlo_text)]


@pytest.mark.parametrize("step", ["decode", "prefill", "prefill_chunk"])
def test_engine_weights_enter_as_arguments(step):
    """The engine's compiled programs take the weights as arguments: a
    closed-over weight would be baked into every executable (one copy per
    prefill bucket on the device)."""
    import re
    from repro.serving.engine import (decode_fused, donating,
                                      prefill_batch, prefill_chunk)
    cfg = configs.get_smoke("qwen3_1_7b")
    params = M.init_params(cfg)
    cache = M.init_cache(cfg, 2, 32)
    if step == "decode":
        z = jnp.zeros(2, jnp.int32)
        lowered = donating(decode_fused).lower(
            params, cache, z, z, z, jnp.ones(2, bool),
            jax.random.PRNGKey(0), cfg=cfg, eos=-1, temp=0.0, max_len=32)
    elif step == "prefill":
        lowered = prefill_batch.lower(
            params, cache, jnp.zeros((2, 8), jnp.int32),
            jnp.zeros(2, jnp.int32), cfg=cfg)
    else:
        lowered = prefill_chunk.lower(
            params, cache, jnp.zeros((2, 8), jnp.int32),
            jnp.asarray(8, jnp.int32), jnp.zeros(2, jnp.int32), cfg=cfg)
    text = lowered.as_text()
    main = re.search(r"func\.func public @main\((.*?)\) ->", text,
                     re.S).group(1)
    leaves = jax.tree_util.tree_leaves(params)
    assert len(re.findall(r"%arg\d+:", main)) >= len(leaves)
    biggest = max(leaf.nbytes for leaf in leaves)
    assert max(_constant_payload_bytes(text), default=0) < biggest
