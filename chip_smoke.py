#!/usr/bin/env python3
"""Chip smoke test: serve qwen3-1.7B at its published widths on a TPU.

    python3 chip_smoke.py               # one chip: colocated + P/D phases
    python3 chip_smoke.py --four-chips  # a four-chip host: the decode step
                                        # placed across the chips by the
                                        # planner, against one chip

The colocated and P/D phases go through the user's entry point,
``DeploymentSpec(...).compile().launch()``; the four-chip phase plans the
decode step as ``launch/serve.py --disaggregate`` does and maps logical
device i to ``jax.devices()[i]``.  Weights are random, made from a seed.
Every served token is checked teacher-forced against
``models.model.forward_logits`` with the same params: its reference logit
must lie within ``GAP_TOL`` of the reference row's maximum.

Everything runs in this one process (a chip belongs to one process).
There is no CPU fallback: without a TPU the script exits non-zero.  The
last line of stdout is one JSON object naming the device, printed only
when every phase passed.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import repro.configs as configs  # noqa: E402
from repro.core import analyzer, planner  # noqa: E402
from repro.core.costmodel import TPU_V5E  # noqa: E402
from repro.core.executor import build_executable, host_device_map  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402
from repro.serving.spec import DeploymentSpec  # noqa: E402

ARCH = "qwen3_1_7b"
SEED = 0
N_REQUESTS = 16
PROMPT_LENS = (64, 256, 1024)       # three padded-length buckets
NEW_TOKENS = (32, 64)               # inclusive range per request
SLOTS, MAX_LEN, SYNC_EVERY = 8, 2048, 4
PD_SLOTS, KV_CHUNKS = 4, 4          # two engines beside one set of params
# bf16 tolerance on (reference row max - reference logit of the served
# token).  With these random weights a row's logits spread with std ~0.9
# over 151,936 entries, so a wrong token sits ~4 below the maximum, while
# bf16 rounding differences between the served path (padded prefill,
# decode against the cache) and one full forward stay near 0.1.
GAP_TOL = 0.25


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------- #
# Requests and the teacher-forced reference
# --------------------------------------------------------------------- #
def make_requests(vocab: int, n: int = N_REQUESTS,
                  prompt_lens=PROMPT_LENS, new_tokens=NEW_TOKENS,
                  seed: int = SEED):
    """Seeded burst: every request arrives at t=0, so admission batches
    (and hence the compiled shapes) are the same on every run."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(rng.choice(prompt_lens))
        out.append(Request(
            rid=i,
            prompt=rng.integers(0, vocab, size=plen).astype(np.int32),
            max_new_tokens=int(rng.integers(new_tokens[0],
                                            new_tokens[1] + 1)),
            arrival=0.0))
    return out


def fresh(reqs):
    return [dataclasses.replace(r, output=[], ttft=-1.0, finished=-1.0)
            for r in reqs]


@functools.partial(jax.jit, static_argnames=("cfg",))
def _reference_gaps(params, tokens, positions, served, *, cfg):
    """(row max - logit of the served token, served token is the row's
    argmax) at each checked position of one teacher-forced sequence."""
    logits = M.forward_logits(params, cfg, tokens)[0]      # (L, V)
    rows = logits[positions].astype(jnp.float32)          # (n, V)
    picked = jnp.take_along_axis(rows, served[:, None], axis=1)[:, 0]
    return rows.max(axis=-1) - picked, rows.argmax(axis=-1) == served


def check_outputs(cfg, params, reqs, label: str) -> float:
    """Every request complete, every token within GAP_TOL of the
    reference maximum.  Returns the largest gap."""
    bad = [r.rid for r in reqs if len(r.output) != r.max_new_tokens]
    if bad:
        raise SmokeFailure(f"{label}: requests {bad} did not complete")
    q = 512                                   # forward_logits' q_chunk
    span = max(len(r.prompt) + len(r.output) - 1 for r in reqs)
    L = -(-span // q) * q
    n_max = max(len(r.output) for r in reqs)
    worst, agree, total = 0.0, 0, 0
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.output[:-1])])
        toks = np.zeros((1, L), np.int32)
        toks[0, :len(seq)] = seq
        n = len(r.output)
        pos = np.zeros(n_max, np.int32)
        pos[:n] = len(r.prompt) - 1 + np.arange(n)
        served = np.zeros(n_max, np.int32)
        served[:n] = r.output
        gaps, hits = _reference_gaps(params, toks, pos, served, cfg=cfg)
        gaps, hits = np.asarray(gaps)[:n], np.asarray(hits)[:n]
        if not np.all(np.isfinite(gaps)):
            raise SmokeFailure(f"{label}: non-finite reference logits "
                               f"for request {r.rid}")
        worst = max(worst, float(gaps.max()))
        agree += int(hits.sum())
        total += n
    log(f"{label}: reference check tokens={total} "
        f"argmax_agree={agree / total:.4f} max_gap={worst!r} "
        f"tol={GAP_TOL}")
    if worst > GAP_TOL:
        raise SmokeFailure(f"{label}: largest reference gap {worst!r} "
                           f"exceeds {GAP_TOL}")
    return worst


# --------------------------------------------------------------------- #
# Phases
# --------------------------------------------------------------------- #
def _counts(out) -> dict:
    c = {k: out["engine"][k] for k in ("completed", "decode_steps",
                                       "host_syncs")}
    c.update(wire_bytes=out["wire_bytes"], shards=out["shards"])
    return c


def serve(launched, reqs, label: str) -> None:
    """Warm up on a copy of the requests (compiles every shape the run
    uses), then serve them; prints compile+warm-up and serving seconds
    and the run's counters (which accumulate over a deployment's life,
    hence the differences)."""
    t0 = time.perf_counter()
    before = _counts(launched.run(fresh(reqs)))
    log(f"{label}: compile+warmup seconds={time.perf_counter() - t0!r}")
    t0 = time.perf_counter()
    after = _counts(launched.run(reqs))
    secs = time.perf_counter() - t0
    d = {k: after[k] - before[k] for k in after}
    log(f"{label}: requests sent={len(reqs)} completed={d['completed']} "
        f"failed={len(reqs) - d['completed']} "
        f"decode_steps={d['decode_steps']} host_syncs={d['host_syncs']} "
        f"wire_bytes={d['wire_bytes']} shards={d['shards']} "
        f"seconds={secs!r}")
    if d["completed"] != len(reqs):
        raise SmokeFailure(f"{label}: {len(reqs) - d['completed']} "
                           f"requests failed")


def peak_bytes() -> int:
    return int((jax.devices()[0].memory_stats() or {})
               .get("peak_bytes_in_use", -1))


def launch_phase(arch: str, engine: dict, reqs, label: str, *,
                 params=None, pd: bool = False, kv_chunks: int = 1):
    """Serve ``reqs`` through ``DeploymentSpec.launch()``; returns the
    params the deployment ran with."""
    groups = [["tpu-v5e"], ["tpu-v5e"]] if pd else [["tpu-v5e"]]
    spec = DeploymentSpec(groups=groups, arch=arch, pd=pd,
                          kv_chunks=kv_chunks, engine=engine)
    t0 = time.perf_counter()
    launched = spec.compile().launch(params=params)
    params = launched.params
    log(f"{label}: launch seconds={time.perf_counter() - t0!r} "
        f"engines={len(launched.engines)} "
        f"peak_bytes_in_use={peak_bytes()}")
    serve(launched, reqs, label)
    check_outputs(launched.cfg, params, reqs, label)
    del launched
    gc.collect()
    log(f"{label}: peak_bytes_in_use={peak_bytes()}")
    return params


def four_chip_phase(cfg, params, reqs, devices) -> int:
    """The planner splits the decode step across ``devices`` (logical
    device i on devices[i]); the same requests are served by a one-chip
    engine for comparison.  Returns the number of chips that ran a
    stage."""
    class Solo:
        """``LaunchedDeployment.run``'s result shape over one engine."""

        def __init__(self, engine):
            self.engine = engine

        def run(self, rs):
            return {"engine": self.engine.run(list(rs)).summary(),
                    "wire_bytes": 0, "shards": 0}

    one = fresh(reqs)
    serve(Solo(ServingEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                             sync_every=SYNC_EVERY)), one, "one-chip")
    check_outputs(cfg, params, one, "one-chip")
    gc.collect()

    t0 = time.perf_counter()
    shapes = jax.eval_shape(lambda: M.init_cache(cfg, SLOTS, MAX_LEN))
    traced = analyzer.analyze(
        lambda p, c, t, q: M.decode_step(p, cfg, t, c, q,
                                         scan_layers=False),
        jax.eval_shape(lambda: params), shapes,
        jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32), state_argnums=(1,))
    g = analyzer.pin_nodes(traced.graph,
                           traced.state_readers | traced.state_writers, 0)
    traced = traced.with_graph(g)
    plan = planner.plan(g, [TPU_V5E] * len(devices), policy="throughput")
    device_map = host_device_map(plan)
    if device_map is None or list(device_map) != list(devices):
        raise SmokeFailure(f"device map {device_map} != {devices}")
    exe = build_executable(traced, plan, device_map)
    log(f"four-chip: plan seconds={time.perf_counter() - t0!r} "
        f"{plan.summary()}")
    chip = {d: i for i, d in enumerate(devices)}
    stages = collections.Counter(chip[cs.device] for cs in exe.stages)
    kernels = collections.Counter(plan.labels)
    units = collections.Counter(chip[fs.device]
                                for fs in exe.program.fused)
    for c in range(len(devices)):
        log(f"four-chip: chip {c} stages={stages[c]} "
            f"kernels={kernels[c]} dispatch_units={units[c]}")

    ran = collections.Counter()
    moved = {"calls": 0, "transfers": 0, "bytes": 0}

    def decode_fn(p, c, t, q):
        # the executor's __call__, counting dispatches per chip and the
        # cross-chip sends each step issues
        slots = exe.init_slots(p, c, t, q)
        for i, fs in enumerate(exe.program.fused):
            moved["transfers"] += exe.run_unit(slots, i)
            for _, _, dst in fs.transfers:
                moved["bytes"] += slots[dst].nbytes
            out = slots[fs.out_slots[0]]
            ran[chip[next(iter(out.devices()))]] += 1
        moved["calls"] += 1
        return exe.collect_slots(slots)

    split = fresh(reqs)
    serve(Solo(ServingEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                             sync_every=SYNC_EVERY, decode_fn=decode_fn)),
          split, "four-chip")
    steps = max(moved["calls"], 1)
    log(f"four-chip: decode calls={moved['calls']} "
        f"transfers_per_step={moved['transfers'] / steps!r} "
        f"bytes_per_step={moved['bytes'] / steps!r} "
        f"unit_outputs_per_chip={dict(sorted(ran.items()))}")
    check_outputs(cfg, params, split, "four-chip")
    same = sum(a.output == b.output for a, b in zip(one, split))
    log(f"four-chip: requests with outputs identical to one chip="
        f"{same}/{len(reqs)}")
    if not any(c != 0 for c in ran):
        raise SmokeFailure("no stage ran on a chip other than chip 0")
    log(f"four-chip: peak_bytes_in_use(chip 0)={peak_bytes()}")
    return len(ran)


# --------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip placed decode step and "
                         "its one-chip comparison")
    args = ap.parse_args(argv)
    use_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"this check runs on the chip only", file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    log(f"device_kind={kind} platform={devices[0].platform} "
        f"devices={len(devices)}")

    cfg = configs.get(ARCH)
    reqs = make_requests(cfg.vocab_size)
    log(f"arch={ARCH} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} requests={len(reqs)} "
        f"prompt_tokens={sum(len(r.prompt) for r in reqs)}")

    if args.four_chips:
        if len(devices) < 4:
            raise SmokeFailure(f"--four-chips needs 4 devices, found "
                               f"{len(devices)}")
        t0 = time.perf_counter()
        # one compiled program, not the op-by-op init launch() runs
        params = jax.block_until_ready(jax.jit(
            M.init_params, static_argnums=0)(cfg, jax.random.PRNGKey(SEED)))
        log(f"params seconds={time.perf_counter() - t0!r} "
            f"peak_bytes_in_use={peak_bytes()}")
        used = four_chip_phase(cfg, params, reqs, list(devices[:4]))
    else:
        engine = {"smoke": False, "slots": SLOTS, "max_len": MAX_LEN,
                  "sync_every": SYNC_EVERY, "seed": SEED}
        params = launch_phase(ARCH, engine, reqs, "colocated")
        launch_phase(ARCH, dict(engine, slots=PD_SLOTS), fresh(reqs),
                     "pd", params=params, pd=True, kv_chunks=KV_CHUNKS)
        used = 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind, "count": used}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
