"""Decode attention reads only the live K/V rows.

  * the scanned decode step carries the Pallas decode kernel as its TPU
    branch (``lax.platform_dependent``) wherever the kernel applies, and
    only the jnp path where the config rules it out (a sliding-window
    ring buffer, a logit softcap) or the layer loop is unrolled (the
    analyzer's trace); the CPU runs the jnp branch;
  * the engine counts the K/V rows its decode steps attend and the rows
    a whole-capacity read would take, from what the host already holds.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine


def _decode_jaxpr(cfg, scan_layers):
    params = jax.eval_shape(lambda: M.init_params(cfg))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 2, 32))
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((2,), jnp.int32)
    p3 = jax.ShapeDtypeStruct((3, 2, 1), jnp.int32) \
        if cfg.family == "vlm" else None
    return str(jax.make_jaxpr(
        lambda p, c, t, q, p3: M.decode_step(
            p, cfg, t, c, q, positions3=p3, scan_layers=scan_layers))(
        params, cache, tok, pos, p3))


@pytest.mark.parametrize("arch,change,kernel", [
    ("qwen3_1_7b", {}, True),
    ("llama3_8b", {}, True),
    ("qwen2_vl_7b", {}, True),                  # M-RoPE decode
    ("mixtral_8x7b", {}, False),                # sliding-window ring
    ("qwen3_1_7b", {"attn_logit_softcap": 30.0}, False),
])
def test_decode_takes_kernel_where_it_applies(arch, change, kernel):
    cfg = dataclasses.replace(C.get_smoke(arch), **change)
    assert ("pallas_call" in _decode_jaxpr(cfg, True)) == kernel
    assert "pallas_call" not in _decode_jaxpr(cfg, False)


def test_engine_counts_attended_kv_rows():
    """Three requests through two slots (one waits for a free slot):
    each decode step of a request with a P-token prompt and n tokens
    out so far attends P + n rows; every step counts slots x max_len
    rows of capacity."""
    cfg = dataclasses.replace(C.get_smoke("qwen3_1_7b"), dtype="float32")
    prompts, new = (5, 9, 3), (6, 4, 7)
    slots, max_len = 2, 32
    reqs = [Request(rid=i, prompt=np.arange(p, dtype=np.int32) + 1,
                    max_new_tokens=n, arrival=0.0)
            for i, (p, n) in enumerate(zip(prompts, new))]
    eng = ServingEngine(cfg, M.init_params(cfg), slots=slots,
                        max_len=max_len, sync_every=3)
    stats = eng.run(reqs)
    assert [len(r.output) for r in reqs] == list(new)
    assert stats.decode_kv_rows == sum(
        p + i for p, n in zip(prompts, new) for i in range(1, n))
    assert stats.decode_kv_capacity_rows == \
        stats.decode_steps * slots * max_len
    summary = stats.summary()
    assert summary["decode_kv_rows"] == stats.decode_kv_rows
    assert summary["decode_kv_capacity_rows"] == \
        stats.decode_kv_capacity_rows
