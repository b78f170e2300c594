"""Query heads as published wherever no mesh axis splits them, and as
Qwen2.5 groups them wherever one does.

A config is laid out for the production mesh's 16-way ``model`` axis,
which pads heads that it does not divide; ``DeploymentSpec.launch``
holds the published heads, since its engines hold whole layers (one
chip, or a pipeline stage).  Padded heads are zero and sit at the end of
each K/V group's run of slots, so the padded model computes the
published one.  At a Qwen2 test size with 5:1 GQA, prefill and decoding
through the cache of a one-chip ``launch().run`` give the logits of the
benchmark's plain reference.
"""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from chipbench import costs, harness, weights
from chipbench.references import dense_gqa
from repro.distributed import sharding as SH
from repro.models import model as M
from repro.serving import engine as E
from repro.serving.engine import Request
from repro.serving.spec import DeploymentSpec

ROOT = Path(__file__).resolve().parents[1]
STAGE = json.loads(
    (ROOT / "chipbench/configs/qwen2_5_14b-12l.json").read_text())
# Qwen2 layers at a test size: 10 query heads in 2 groups of 5
QWEN2_TEST = dict(STAGE, name="qwen2_test", hidden_size=128,
                  intermediate_size=256, num_attention_heads=10,
                  num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
                  vocab_size=512)


def _spec(slots, max_len):
    return DeploymentSpec(groups=[["tpu-v5e"]], arch="qwen2_5_14b",
                          engine={"slots": slots, "max_len": max_len,
                                  "sync_every": 2, "temperature": 0.0,
                                  "seed": 0, "smoke": False})


def test_one_chip_stage_holds_published_heads():
    """The served one-chip program of the benchmark's Qwen2.5-14B stage
    holds 40 query heads at published widths, whether it draws its own
    weights or is handed ones laid out for the production mesh (shapes
    only)."""
    cfg = harness.program_config(STAGE)
    assert cfg.padded_heads == 48
    for given in (False, True):
        p = jax.eval_shape(lambda: _spec(16, 1024).compile().launch(
            cfg=cfg, params=M.init_params(cfg) if given else None).params)
        at = p["layers"]["attn"]
        assert at["wq"].shape == (12, 5120, 40, 128)
        assert at["wo"].shape == (12, 40, 128, 5120)
        assert at["bq"].shape == (12, 40, 128)
        assert at["wk"].shape == (12, 5120, 8, 128)


def test_head_shards_follow_the_mesh():
    """Heads pad only where the mesh axis that shards them does not
    divide them: 40 heads to 48 over the production mesh's 16-way
    ``model`` axis, and never where no axis shards heads."""
    cfg = harness.program_config(STAGE)
    for shape, axes, ways in [((16, 16), ("data", "model"), 16),
                              ((2, 16, 16), ("pod", "data", "model"), 16),
                              ((4,), ("data",), 1)]:
        mesh = AbstractMesh(shape, axes)
        for rules in (SH.SERVE_RULES, SH.TRAIN_RULES):
            assert SH.head_shards(mesh, rules) == ways
    for ways, padded in [(1, 40), (8, 40), (16, 48)]:
        assert dataclasses.replace(cfg, head_shards=ways).padded_heads \
            == padded
    # gemma-2b's 8 heads of one K/V group over 16 ways
    g = dataclasses.replace(cfg, num_heads=8, num_kv_heads=1, head_shards=16)
    assert g.padded_heads == 16


def test_padded_init_puts_real_heads_first_in_their_group():
    """With 16-way padding asked for, real head j sits in group j // 5,
    at the slot ``weights.head_slots`` gives it; the rest are zero."""
    cfg = dataclasses.replace(harness.program_config(QWEN2_TEST),
                              head_shards=16)
    assert cfg.padded_heads == 16
    at = M.init_params(cfg, jax.random.PRNGKey(1))["layers"]["attn"]
    live = np.flatnonzero(np.abs(np.asarray(at["wq"], np.float32))
                          .sum(axis=(0, 1, 3)))
    wo_live = np.flatnonzero(np.abs(np.asarray(at["wo"], np.float32))
                             .sum(axis=(0, 2, 3)))
    m = costs.Dense.from_config(QWEN2_TEST)
    assert live.tolist() == wo_live.tolist() == \
        weights.head_slots(m, 16).tolist()
    rep_p = cfg.padded_heads // cfg.num_kv_heads
    assert (live // rep_p).tolist() == (np.arange(10) // 5).tolist()


def test_padded_forward_equals_unpadded():
    """The 16-way padded program's own init computes the model that its
    real heads, held unpadded, compute; ``published_heads`` cuts them
    out at the benchmark's slots."""
    cfg = harness.program_config(dict(QWEN2_TEST, torch_dtype="float32"))
    padded = dataclasses.replace(cfg, head_shards=16)
    plain_cfg = dataclasses.replace(cfg, head_shards=1)
    params = M.init_params(padded, jax.random.PRNGKey(2))
    slots = jnp.asarray(weights.head_slots(
        costs.Dense.from_config(QWEN2_TEST), 16))
    at = params["layers"]["attn"]
    plain = dict(params, layers=dict(params["layers"], attn=dict(
        at, wq=at["wq"][:, :, slots], wo=at["wo"][:, slots],
        bq=at["bq"][:, slots])))
    cut = M.published_heads(params, plain_cfg)
    same = jax.tree_util.tree_map(lambda a, b: a.shape == b.shape
                                  and bool((a == b).all()), cut, plain)
    assert all(jax.tree_util.tree_leaves(same))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 24)),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        a = M.forward_logits(params, padded, toks)
        b = M.forward_logits(plain, plain_cfg, toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def _served_logits(config, prompts, max_new, monkeypatch):
    """Launch one chip with weights laid out for the production mesh
    (10 heads in 16 slots), serve ``prompts`` (admitted together,
    request i in slot i) and return (params, requests, logits), where
    logits[i] holds, for each served token of request i, the row of
    logits it was taken from: the prefill's, then one decode step's
    each."""
    m = costs.Dense.from_config(config)
    cfg = harness.program_config(config)
    params = weights.make_params(m, jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))), 2**33 + 5)
    prefill_rows, step_rows = [], []
    real_prefill, real_decode = E.prefill_batch, M.decode_step

    def prefill(*a, **k):
        logits, cache = real_prefill(*a, **k)
        prefill_rows.append(np.asarray(logits, np.float32))
        return logits, cache

    def decode(*a, **k):
        logits, cache = real_decode(*a, **k)
        jax.debug.callback(
            lambda x: step_rows.append(np.asarray(x, np.float32)), logits,
            ordered=True)
        return logits, cache

    monkeypatch.setattr(E, "prefill_batch", prefill)
    monkeypatch.setattr(M, "decode_step", decode)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    launched = _spec(4, 64).compile().launch(cfg=cfg, params=params)
    assert params["layers"]["attn"]["wq"].shape[2] == 16
    assert launched.params["layers"]["attn"]["wq"].shape[2] == 10
    launched.run(reqs)
    jax.effects_barrier()
    assert len(prefill_rows) == 1 and len(step_rows) >= max_new - 1
    logits = [np.stack([prefill_rows[0][i]]
                       + [s[i] for s in step_rows[:len(r.output) - 1]])
              for i, r in enumerate(reqs)]
    return params, reqs, logits


def _reference_logits(config, params, req):
    m = costs.Dense.from_config(config)
    seq = np.concatenate([req.prompt, req.output[:-1]]).astype(np.int32)
    rows = len(req.prompt) - 1 + np.arange(len(req.output))
    return np.asarray(dense_gqa.logits_at(
        params, jnp.asarray(seq), jnp.asarray(rows, jnp.int32), m=m,
        eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"])))


# In float32 the served path and the reference are the same function
# written apart, so they differ by summation order alone: 6e-7 to 7e-7
# of the largest logit at this size.  Served in the configured bfloat16
# they differ by 0.8% to 0.9%.  A tenth of a percent leaves the first
# over a thousand times its reading and fails the second eightfold.
TOL = 1e-3


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_launch_serves_reference_logits_at_qwen2_test_size(dtype,
                                                           monkeypatch):
    """Prefill, then decode through the cache, of a one-chip
    ``launch().run`` at 5:1 GQA: every served token's logits against
    ``dense_gqa.logits_at`` over the same sequence.  Served in float32
    they agree within ``TOL`` of the largest logit; served in bfloat16,
    the precision the configuration states, they do not, which is what
    shows ``TOL`` tight enough to catch a lower precision."""
    config = dict(QWEN2_TEST, torch_dtype=dtype)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (5, 13, 9)]
    params, reqs, served = _served_logits(config, prompts, 8, monkeypatch)
    worst = 0.0
    for r, got in zip(reqs, served):
        assert len(r.output) == 8
        ref = _reference_logits(config, params, r)
        assert got.shape == ref.shape
        worst = max(worst, float(np.abs(got - ref).max()
                                 / np.abs(ref).max()))
        if dtype == "float32":
            assert (got.argmax(-1) == np.asarray(r.output)).all()
    assert (worst < TOL) == (dtype == "float32"), worst
