"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler refuses what interpret mode accepts (block shapes off
the (8, 128) tiling, primitives Mosaic cannot lower) and programs that
do not fit the chip's memory.  These tests compile, at real widths:

  * the serving engine's decode step and one prefill bucket for
    full-width qwen3-1.7B, from ``jax.eval_shape`` shapes, and check
    that arguments + outputs + temporaries fit one chip's 16 GiB;
  * each of the five Pallas kernel entry points, and check that the
    kernel is in the program (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.  Keep these compiles in this one file.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import repro.configs as configs
from repro.kernels.flash_attention.kernel import (
    flash_attention_decode, flash_attention_prefill)
from repro.kernels.mamba2_ssd.kernel import ssd_chunk_scan
from repro.kernels.moe_gmm.kernel import gmm
from repro.kernels.rwkv6.kernel import wkv
from repro.models import model as M
from repro.serving.engine import decode_fused, prefill_batch

HBM_BYTES = 16 * 2**30                  # one v5e chip
SLOTS, MAX_LEN, PROMPT = 8, 2048, 1024  # chip_smoke.py's engine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise log outside the checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.compilation_cache.reset_cache()
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:          # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits_one_chip(compiled, params):
    mem = compiled.memory_analysis()
    param_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree_util.tree_leaves(params))
    # the weights are arguments, not constants baked into the program
    assert mem.argument_size_in_bytes >= param_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_engine_step_compiles_for_v5e(one_chip, step):
    cfg = configs.get("qwen3_1_7b")
    params = _on(one_chip, jax.eval_shape(lambda: M.init_params(cfg)))

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = _on(one_chip, jax.eval_shape(
        lambda: M.init_cache(cfg, SLOTS, MAX_LEN)))
    if step == "decode":
        i32 = S((SLOTS,), jnp.int32)
        lowered = decode_fused.lower(
            params, cache, i32, i32, i32, S((SLOTS,), jnp.bool_),
            S((2,), jnp.uint32), cfg=cfg, eos=-1, temp=0.0,
            max_len=MAX_LEN)
    else:
        lowered = prefill_batch.lower(
            params, cache, S((SLOTS, PROMPT), jnp.int32),
            S((SLOTS,), jnp.int32), cfg=cfg)
    _fits_one_chip(lowered.compile(), params)


def _kernel_cases(S):
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    return {
        # qwen3-1.7B attention: 16 query / 8 KV heads of 128
        "flash_attention_prefill": (
            flash_attention_prefill,
            [S((1, 16, 2048, 128), bf), S((1, 8, 2048, 128), bf),
             S((1, 8, 2048, 128), bf)]),
        "flash_attention_decode": (
            flash_attention_decode,
            [S((8, 16, 128), bf), S((8, 8, 2048, 128), bf),
             S((8, 8, 2048, 128), bf), S((8,), i32)]),
        # mixtral-8x7B experts: d 4096, d_ff 14336
        "gmm": (gmm, [S((2048, 4096), bf), S((8, 4096, 14336), bf),
                      S((16,), i32)]),
        # zamba2-7B mamba2: 112 heads of 64, state 64
        "ssd_chunk_scan": (
            ssd_chunk_scan,
            [S((1, 2048, 112, 64), bf), S((1, 2048, 64), bf),
             S((1, 2048, 64), bf), S((1, 2048, 112), f32)]),
        # rwkv6-3B: 40 heads of 64
        "wkv": (wkv, [S((1, 2048, 40, 64), bf)] * 4
                + [S((40, 64), f32), S((1, 40, 64, 64), f32)]),
    }


@pytest.mark.parametrize("name", ["flash_attention_prefill",
                                  "flash_attention_decode", "gmm",
                                  "ssd_chunk_scan", "wkv"])
def test_kernel_compiles_for_v5e(one_chip, name):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_cases(S)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
