#!/usr/bin/env python3
"""Decode attention on a TPU: the Pallas decode kernel against the
whole-capacity jnp path it replaces.

    python3 benchmarks/decode_attention.py sweep [--steps 40]
    python3 benchmarks/decode_attention.py live-share --workload <cell> \\
        --seed <n> --seconds <s>

``sweep`` checks the kernel against ``layers._sdpa_chunked`` at served
shapes (16 slots x 1024 rows, 2 and 5 query heads per K/V head, rows
past each slot's length and other layers' rows filled with NaN), then
times the donated decode step of qwen3-1.7B (``chipbench/configs``) at
16 x 1024 against live context, for each kernel ``block_k`` and for the
jnp path: every slot live at a context, and a chat-like batch of 7 live
slots beside 9 idle ones.  Step time is host clock over back-to-back
dispatches ended by ``block_until_ready``.  The last line of stdout is
the whole result as one JSON object.

``live-share`` serves one window of a benchmark cell as
``chipbench/run.py`` does (no output check) and prints the engine's
``decode_kv_rows`` over ``decode_kv_capacity_rows``: the share of the
cache the kernel reads.

Without a TPU either command exits 2.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import harness, traffic  # noqa: E402
from repro.kernels.flash_attention.ops import flash_decode  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serving.engine import decode_fused, donating  # noqa: E402

CONFIG = "qwen3_1_7b"                   # the qwen3-chat cell's
SLOTS, MAX_LEN = 16, 1024               # both chat cells' engines
BLOCKS = (128, 256, 512)
CONTEXTS = (128, 256, 512, 1024)
# live rows of a chat-like batch: 7 of 16 slots (the cells' mean)
CHAT = (96, 150, 210, 260, 330, 430, 580)


def parity() -> list:
    """Kernel against the jnp path on one stacked cache, NaN where the
    kernel must not read."""
    out = []
    T, Hkv, D, layers = MAX_LEN, 8, 128, 4
    lengths = jnp.array([0, 1, 127, 128, 129, 255, 256, 257, 511, 512,
                         513, 700, 1023, 1024, 0, 300], jnp.int32)
    for rep in (2, 5):
        for bk in BLOCKS:
            ks = jax.random.split(jax.random.PRNGKey(rep * 1000 + bk), 3)
            q = jax.random.normal(ks[0], (SLOTS, Hkv * rep, D), jnp.bfloat16)
            shape = (layers, SLOTS, T, Hkv, D)
            k = jax.random.normal(ks[1], shape, jnp.bfloat16)
            v = jax.random.normal(ks[2], shape, jnp.bfloat16)
            for layer in (0, layers - 1):
                rows = jnp.arange(T)[None, :] < lengths[:, None]
                keep = (jnp.arange(layers) == layer)[:, None, None] \
                    & rows[None]
                keep = keep[..., None, None]
                got = flash_decode(q, jnp.where(keep, k, jnp.nan),
                                   jnp.where(keep, v, jnp.nan), lengths,
                                   layer, block_k=bk)
                want = L._sdpa_chunked(
                    q[:, None], k[layer], v[layer], causal=True,
                    q_offset=lengths - 1, window=None, chunk=512)[:, 0]
                want = jnp.where((lengths > 0)[:, None, None], want, 0)
                got = np.asarray(got, np.float32)
                err = float(np.abs(got - np.asarray(want, np.float32)).max())
                out.append({"rep": rep, "block_k": bk, "layer": layer,
                            "finite": bool(np.isfinite(got).all()),
                            "max_abs_err": err})
                print(json.dumps(out[-1]), flush=True)
    return out


def served_config(name: str):
    """A benchmark configuration as ``DeploymentSpec.launch`` holds it:
    published query heads."""
    conf = harness.load_json(ROOT / "chipbench" / "configs" / f"{name}.json")
    return dataclasses.replace(harness.program_config(conf), head_shards=1)


def scenarios():
    for c in CONTEXTS:
        yield f"all16@{c}", np.full(SLOTS, c - 1), np.ones(SLOTS, bool)
    pos = np.zeros(SLOTS, np.int64)
    live = np.zeros(SLOTS, bool)
    idx = np.arange(0, SLOTS, 2)[:len(CHAT)]      # live slots interleaved
    pos[idx], live[idx] = np.asarray(CHAT) - 1, True
    yield "chat7", pos, live


def time_step(cfg, params, steps: int) -> dict:
    """Step time (ms) of the donated decode program per scenario."""
    cache = M.init_cache(cfg, SLOTS, MAX_LEN)
    step = donating(decode_fused)
    kw = dict(cfg=cfg, eos=-1, temp=0.0, max_len=MAX_LEN)
    tok = jnp.ones(SLOTS, jnp.int32)
    budget = jnp.full(SLOTS, 1 << 20, jnp.int32)
    key = jax.random.PRNGKey(0)
    res = {}
    for name, pos, live in scenarios():
        pos, live = jnp.asarray(pos, jnp.int32), jnp.asarray(live)
        for _ in range(3):
            cache = step(params, cache, tok, pos, budget, live, key, **kw)[0]
        jax.block_until_ready(cache)
        t = time.perf_counter()
        for _ in range(steps):
            cache = step(params, cache, tok, pos, budget, live, key, **kw)[0]
        jax.block_until_ready(cache)
        res[name] = (time.perf_counter() - t) / steps * 1e3
    del cache
    return res


def sweep(steps: int) -> dict:
    result = {"device": jax.devices()[0].device_kind, "config": CONFIG,
              "parity": parity(), "step_ms": {}}
    live_rows = L._live_rows_apply
    cfg = served_config(CONFIG)
    params = jax.jit(M.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(1))
    for bk in BLOCKS + (None,):
        jax.clear_caches()
        if bk is None:          # the whole-capacity jnp path
            L._live_rows_apply = lambda cfg, layer: False
        else:
            L._live_rows_apply = live_rows
            L.DECODE_BLOCK_K = bk
        label = "jnp" if bk is None else f"block_k={bk}"
        result["step_ms"][label] = time_step(cfg, params, steps)
        print(json.dumps({label: result["step_ms"][label]}), flush=True)
    L._live_rows_apply = live_rows
    return result


def live_share(workload: str, seed: int, seconds: float) -> dict:
    cell = harness.load_cell(workload)
    harness.use_persistent_cache()
    dep = harness.setup(cell, seed)
    reqs = harness.requests(traffic.schedule(
        cell.mix, seed, seconds, dep.model.vocab,
        float(cell.settings["rate_rps"])))
    stats = dep.launched.engine.stats
    before = dataclasses.replace(stats)
    dep.launched.run(reqs)
    rows = stats.decode_kv_rows - before.decode_kv_rows
    cap = stats.decode_kv_capacity_rows - before.decode_kv_capacity_rows
    return {"workload": workload, "seed": seed, "seconds": seconds,
            "decode_steps": stats.decode_steps - before.decode_steps,
            "decode_kv_rows": rows, "decode_kv_capacity_rows": cap,
            "live_share": rows / cap if cap else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    sw = sub.add_parser("sweep")
    sw.add_argument("--steps", type=int, default=40)
    ls = sub.add_parser("live-share")
    ls.add_argument("--workload", required=True)
    ls.add_argument("--seed", type=int, required=True)
    ls.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        harness.accelerator(1)
    except harness.NoChip as e:
        print(f"decode_attention: {e}", file=sys.stderr)
        return 2
    if args.cmd == "sweep":
        result = sweep(args.steps)
    else:
        result = live_share(args.workload, args.seed, args.seconds)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
