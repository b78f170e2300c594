"""Serving launcher: continuous-batching engine, optionally with Tessera
kernel disaggregation for the decode step.

  PYTHONPATH=src python -m repro.launch.serve --arch gpt_oss_20b --smoke \
      --requests 8 --disaggregate

Or launch from a serialized deployment spec (the declarative API —
single engine, or the prefill/decode handoff pair when the spec says
``pd``; engine knobs come from ``spec.engine``):

  PYTHONPATH=src python -m repro.launch.serve --deployment spec.json \
      --smoke --requests 8
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

import repro.configs as configs
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine


def _build_requests(args, cfg, max_len: int, rng_seed: int = 0):
    if args.trace:
        from repro.serving.engine import requests_from_trace
        from repro.serving.workload import make_trace
        trace = make_trace(args.trace, args.rate, args.requests, seed=0)
        return requests_from_trace(
            trace, cfg.vocab_size, max_prompt=max_len // 2,
            max_new=args.max_new)
    rng = np.random.default_rng(rng_seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=8)
                    .astype(np.int32),
                    max_new_tokens=args.max_new,
                    arrival=0.01 * i)
            for i in range(args.requests)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt_oss_20b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--disaggregate", action="store_true")
    ap.add_argument("--policy", default="throughput",
                    choices=["throughput", "latency"])
    ap.add_argument("--sync-every", type=int, default=8,
                    help="decode steps between host syncs (1 = legacy "
                         "per-token accounting)")
    ap.add_argument("--trace", default=None,
                    choices=["poisson", "bursty", "diurnal"],
                    help="drive the engine from an open-loop workload "
                         "trace instead of fixed arrivals")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="trace arrival rate (req/s)")
    ap.add_argument("--deployment", default=None, metavar="SPEC_JSON",
                    help="load a serialized DeploymentSpec and launch "
                         "its engine topology instead of the ad-hoc "
                         "flags (--arch/--slots/... are ignored except "
                         "--smoke/--requests/--max-new/--trace/--rate)")
    args = ap.parse_args()
    use_compile_cache()

    if args.deployment:
        from repro.serving.spec import DeploymentSpec
        spec = DeploymentSpec.load(args.deployment)
        arch = spec.arch or args.arch
        cfg = (configs.get_smoke(arch) if args.smoke
               else configs.get(arch))
        launched = spec.compile().launch(cfg)
        max_len = int(spec.engine.get("max_len", 64))
        reqs = _build_requests(args, cfg, max_len)
        out = launched.run(reqs)
        print(f"deployment: pd={spec.pd} kv_chunks={spec.kv_chunks} "
              f"engines={len(launched.engines)} "
              f"wire_bytes={out['wire_bytes']} shards={out['shards']}")
        print(out["engine"])
        return

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    params = M.init_params(cfg)

    decode_fn = None
    if args.disaggregate:
        from repro.core import analyzer, planner
        from repro.core.costmodel import TPU_V5E, TPU_V5P
        from repro.core.executor import build_executable, host_device_map
        import jax.numpy as jnp
        cache = M.init_cache(cfg, args.slots, args.max_len)
        toks = jnp.zeros((args.slots, 1), jnp.int32)
        pos = jnp.zeros((args.slots,), jnp.int32)
        step = lambda p, c, t, q: M.decode_step(p, cfg, t, c, q,
                                                scan_layers=False)
        traced = analyzer.analyze(step, params, cache, toks, pos,
                                  state_argnums=(1,))
        g = analyzer.pin_nodes(traced.graph,
                               traced.state_readers |
                               traced.state_writers, 0)
        traced = traced.with_graph(g)
        plan = planner.plan(g, [TPU_V5P, TPU_V5E], policy=args.policy)
        print(plan.summary())
        # logical device i runs on the host's device i; a host with
        # fewer devices than the plan validates it on one device
        device_map = host_device_map(plan)
        if device_map is None:
            print(f"{len(jax.devices())} device(s) for a "
                  f"{len(plan.devices)}-device plan: every stage runs "
                  f"on {jax.devices()[0]}")
        exe = build_executable(traced, plan, device_map)
        decode_fn = lambda p, c, t, q: exe(p, c, t, q)

    reqs = _build_requests(args, cfg, args.max_len)
    engine = ServingEngine(cfg, params, slots=args.slots,
                           max_len=args.max_len, decode_fn=decode_fn,
                           sync_every=args.sync_every)
    stats = engine.run(reqs)
    print(stats.summary())


if __name__ == "__main__":
    main()
