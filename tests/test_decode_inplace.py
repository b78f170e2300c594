"""The decode step updates its KV cache in place.

``M.decode_step`` carries the stacked (L, B, T, Hkv, D) cache through
the layer loop and writes each slot's new K/V row at
``[layer, slot, pos]``; the engine's ``decode_fused`` donates the cache.
These tests hold the in-place step to what the rebuilt cache gave:

  * logits equal teacher forcing at every decoded position, with slots
    at different positions, for a dense model, an MoE model whose
    sliding-window ring buffer is smaller than ``max_len`` (so decode
    wraps), and a VLM decoding with M-RoPE ``positions3``;
  * the rows a decode writes equal a teacher-forced prefill's;
  * a step changes no row but ``[layer, slot, pos]``, bit for bit, on
    the scanned and on the unrolled layer loop;
  * on the unrolled loop the analyzer pins every layer's cache write
    and read, so the planner cannot cut the whole-cache buffer that
    passes from layer to layer;
  * the engine's step donates the cache, warm-up keeps the cache it
    donates, and a park -> activate round trip resumes bit-identically.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.core import analyzer
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine

KEY = jax.random.PRNGKey(0)
ARCHS = ("qwen3_1_7b", "mixtral_8x7b", "qwen2_vl_7b")
PROMPTS = (5, 8)            # per-slot prompt lengths: slots sit apart
STEPS = 12                  # decode steps after the prompt
MAX_LEN = 32                # mixtral's smoke window is 16: a ring of 16


def _cfg(arch):
    return dataclasses.replace(C.get_smoke(arch), dtype="float32")


def _positions3(cfg, pos):
    """(3, B, S) M-RoPE ids with distinct t/h/w axes, or None."""
    if cfg.family != "vlm":
        return None
    return jnp.stack([pos, pos // 2, pos // 3]).astype(jnp.int32)


def _slot(cache, pos):
    cap = cache["kv"]["k"].shape[2]
    return pos % cap


def _prefilled(cfg, params, toks):
    """Right-padded admission of PROMPTS (padding is token 0), then the
    decode positions each slot starts from."""
    B, S = len(PROMPTS), max(PROMPTS)
    lens = np.asarray(PROMPTS)
    prompt = np.where(np.arange(S)[None] < lens[:, None],
                      np.asarray(toks[:, :S]), 0)
    cache = M.init_cache(cfg, B, MAX_LEN)
    p3 = _positions3(cfg, jnp.broadcast_to(jnp.arange(S), (B, S)))
    lg, cache = M.prefill(params, cfg, jnp.asarray(prompt), cache,
                          last_pos=jnp.asarray(lens - 1), positions3=p3)
    return lg, cache, jnp.asarray(lens, jnp.int32)


def _decode(cfg, params, toks, cache, pos, **kw):
    tok = jnp.take_along_axis(toks, pos[:, None], axis=1)
    return M.decode_step(params, cfg, tok, cache, pos,
                         positions3=_positions3(cfg, pos[:, None]), **kw)


def _tokens(cfg):
    return jax.random.randint(KEY, (len(PROMPTS), max(PROMPTS) + STEPS),
                              0, cfg.vocab_size)


@pytest.mark.parametrize("arch", ARCHS)
def test_inplace_decode_matches_teacher_forcing(arch):
    cfg = _cfg(arch)
    params = M.init_params(cfg, KEY)
    toks = _tokens(cfg)
    T = toks.shape[1]
    full = M.forward_logits(
        params, cfg, toks,
        positions3=_positions3(cfg, jnp.broadcast_to(jnp.arange(T),
                                                     toks.shape)))
    lg, cache, pos = _prefilled(cfg, params, toks)
    rows = np.arange(len(PROMPTS))
    np.testing.assert_allclose(np.asarray(lg),
                               np.asarray(full[rows, pos - 1]),
                               atol=2e-4, rtol=2e-4)
    for _ in range(STEPS):
        lg, cache = _decode(cfg, params, toks, cache, pos)
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(full[rows, pos]),
                                   atol=2e-4, rtol=2e-4)
        pos = pos + 1


@pytest.mark.parametrize("arch", ARCHS)
def test_inplace_decode_writes_prefill_rows(arch):
    """Each slot's decoded rows equal a prefill of its whole sequence
    (ring caches: the rows still inside the window)."""
    cfg = _cfg(arch)
    params = M.init_params(cfg, KEY)
    toks = _tokens(cfg)
    _, cache, pos = _prefilled(cfg, params, toks)
    start = np.asarray(pos)
    for _ in range(STEPS):
        _, cache = _decode(cfg, params, toks, cache, pos)
        pos = pos + 1
    cap = cache["kv"]["k"].shape[2]
    for b, (s0, s1) in enumerate(zip(start, np.asarray(pos))):
        ref = M.init_cache(cfg, 1, MAX_LEN)
        seq = toks[b:b + 1, :s1]
        p3 = _positions3(cfg, jnp.arange(s1)[None])
        _, ref = M.prefill(params, cfg, seq, ref, positions3=p3)
        written = np.arange(max(s0, s1 - cap), s1) % cap
        for c in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(cache["kv"][c][:, b, written]),
                np.asarray(ref["kv"][c][:, 0, written]),
                atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scan", "unrolled"])
@pytest.mark.parametrize("arch", ARCHS)
def test_inplace_decode_leaves_other_rows(arch, scan_layers):
    """One step over a cache of random rows rewrites exactly the rows at
    ``[layer, slot, pos % cap]`` and leaves every other row's bits."""
    cfg = _cfg(arch)
    params = M.init_params(cfg, KEY)
    toks = _tokens(cfg)
    _, cache, _ = _prefilled(cfg, params, toks)
    kk, kv = jax.random.split(jax.random.PRNGKey(1))
    cache = {"kv": {
        "k": jax.random.normal(kk, cache["kv"]["k"].shape),
        "v": jax.random.normal(kv, cache["kv"]["v"].shape)}}
    before = jax.tree_util.tree_map(np.asarray, cache)
    # slot 1 is past the ring's capacity on the MoE model: it wraps
    pos = jnp.asarray([3, 21], jnp.int32)
    _, after = _decode(cfg, params, toks, cache, pos,
                       scan_layers=scan_layers)
    hit = np.zeros(before["kv"]["k"].shape[1:3], bool)
    hit[np.arange(len(PROMPTS)), np.asarray(_slot(cache, pos))] = True
    for c in ("k", "v"):
        new = np.asarray(after["kv"][c])
        np.testing.assert_array_equal(new[:, ~hit], before["kv"][c][:, ~hit])
        assert not np.any(new[:, hit] == before["kv"][c][:, hit])


@pytest.mark.parametrize("fuse", [False, True], ids=["raw", "fused"])
@pytest.mark.parametrize("arch", ARCHS)
def test_unrolled_decode_pins_every_layers_cache(arch, fuse):
    cfg = _cfg(arch)
    B = len(PROMPTS)
    cache = jax.eval_shape(lambda: M.init_cache(cfg, B, MAX_LEN))
    tg = analyzer.analyze(
        lambda p, c, t, q: M.decode_step(
            p, cfg, t, c, q, positions3=_positions3(cfg, q[:, None]),
            scan_layers=False),
        jax.eval_shape(lambda: M.init_params(cfg, KEY)), cache,
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        state_argnums=(1,), fuse=fuse)
    pinned = tg.state_readers | tg.state_writers
    shape = cache["kv"]["k"].shape
    node_of = {e: n for n, es in tg.eqn_of_node.items() for e in es}

    def whole(vs):
        return any(getattr(v.aval, "shape", None) == shape for v in vs)

    # every kernel that takes or makes a whole stacked cache is pinned,
    # so no hand-off of it from layer to layer can be cut
    touching = {node_of[i] for i, e in enumerate(tg.jaxpr.eqns)
                if i in node_of and (whole(e.invars) or whole(e.outvars))}
    assert touching and touching <= pinned, touching - pinned
    if not fuse:
        # each layer's K and V write is one of them, and each layer's
        # read of its K and V slice is pinned too
        writes = [e for e in tg.jaxpr.eqns
                  if e.primitive.name == "scatter" and whole(e.outvars)]
        assert len(writes) == 2 * cfg.num_layers
        reads = [n for n in tg.state_readers
                 if tg.graph.nodes[n].out_bytes * cfg.num_layers
                 == cache["kv"]["k"].size * cache["kv"]["k"].dtype.itemsize]
        assert len(reads) >= 2 * cfg.num_layers

def _engine_reqs(cfg, sizes=(6, 3, 5, 4), max_new=6, seed=1):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=max_new, arrival=0.0)
            for i, n in enumerate(sizes)]


def test_engine_step_donates_cache():
    cfg = _cfg("qwen3_1_7b")
    eng = ServingEngine(cfg, M.init_params(cfg), slots=4, max_len=32,
                        sync_every=2)
    assert eng.admit_batch(_engine_reqs(cfg), 0.0) == 4
    old = jax.tree_util.tree_leaves(eng.cache)
    eng.step(0.0)
    assert all(a.is_deleted() for a in old)
    assert not any(a.is_deleted()
                   for a in jax.tree_util.tree_leaves(eng.cache))


def test_warmup_keeps_engine_serving():
    """Warm-up's decode probe donates the cache and keeps what it
    returns: the engine then serves exactly the tokens of a cold one."""
    cfg = _cfg("qwen3_1_7b")
    params = M.init_params(cfg)
    cold = _engine_reqs(cfg)
    ServingEngine(cfg, params, slots=4, max_len=32, sync_every=2).run(cold)
    warm = _engine_reqs(cfg)
    eng = ServingEngine(cfg, params, slots=4, max_len=32, sync_every=2)
    eng.warmup()
    eng.run(warm)
    assert [r.output for r in warm] == [r.output for r in cold]
    assert all(len(r.output) == 6 for r in warm)


@pytest.mark.parametrize("arch", ["llama3_8b", "gpt_oss_20b"])
def test_park_activate_resumes_donated_decode(arch):
    """A slot parked into the block pool mid-decode and activated again
    resumes greedy decode bit-identically to never having left."""
    cfg = _cfg(arch)
    params = M.init_params(cfg)
    ref = _engine_reqs(cfg)
    ServingEngine(cfg, params, slots=4, max_len=32, sync_every=2).run(ref)

    reqs = _engine_reqs(cfg)
    eng = ServingEngine(cfg, params, slots=4, max_len=32, sync_every=2,
                        kv_block_tokens=8, kv_pool_blocks=64)
    assert eng.admit_batch(reqs, 0.0) == 4
    eng.step(0.0)
    eng.step(0.0)                       # sync_every=2: the window settles
    assert not eng._cols
    eng._park_slot(1, 0.0)
    eng.step(0.0)                       # the others decode on without it
    assert eng.active[1] is None
    assert eng._paged.parked() == [reqs[1].rid]
    while eng._any_active():            # the next sync activates it
        eng.step(0.0)
        eng.sync(0.0)
    assert not eng._paged.parked()
    assert [r.output for r in reqs] == [r.output for r in ref]
