"""End-to-end serving driver (the paper's kind of workload).

  PYTHONPATH=src python examples/serve_pipeline.py

A Poisson open-loop trace (serving/workload.py) drives the REAL
continuous-batching engine; the decode step runs DISAGGREGATED across a
heterogeneous pair via Tessera, and the online monitor switches between
latency- and throughput-oriented plans as queueing pressure changes.

The cost model's predictions for the same plan are printed next to the
engine's wall-clock SLO stats: modeled TPOT is the decode plan's
pipelined bottleneck, modeled TTFT the serial prefill time on the
fastest device.  (Modeled numbers are for the TPU pair the plan was
solved for; wall clock is whatever host runs this script — the point is
the side-by-side harness, which later PRs tighten.)
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.core import analyzer, planner
from repro.core.costmodel import TPU_V5E, TPU_V5P, graph_time_on
from repro.core.executor import build_executable
from repro.core.monitor import MonitorConfig, OnlineMonitor
from repro.models import model as M
from repro.serving.engine import ServingEngine, requests_from_trace
from repro.serving.workload import poisson_trace, trace_stats

SLOTS, MAX_LEN = 4, 48
cfg = dataclasses.replace(configs.get_smoke("gpt_oss_20b"),
                          dtype="float32")
params = M.init_params(cfg)

# --- Tessera: plan the decode step for both policies ----------------- #
cache0 = M.init_cache(cfg, SLOTS, MAX_LEN)
toks0 = jnp.zeros((SLOTS, 1), jnp.int32)
pos0 = jnp.zeros((SLOTS,), jnp.int32)

def step(p, c, t, q):
    return M.decode_step(p, cfg, t, c, q, scan_layers=False)

traced = analyzer.analyze(step, params, cache0, toks0, pos0,
                          state_argnums=(1,))
g = analyzer.pin_nodes(traced.graph,
                       traced.state_readers | traced.state_writers, 0)
traced = traced.with_graph(g)
devs = [TPU_V5P, TPU_V5E]
plans = {pol: planner.plan(g, devs, policy=pol) for pol in
         ("latency", "throughput")}
for pol, p in plans.items():
    print(f"{pol:>10}: {p.summary()}")
executables = {pol: build_executable(traced, p)
               for pol, p in plans.items()}

monitor = OnlineMonitor(MonitorConfig(window=0.5, beta=1.5))

def decode_fn(p, c, t, q):
    return executables[monitor.policy](p, c, t, q)

# --- workload: an open-loop Poisson trace ----------------------------- #
PROMPT_CAP, NEW_CAP = 8, 6
trace = poisson_trace(rate=40.0, num_requests=12, seed=0)
print("trace:", {k: round(v, 3) for k, v in trace_stats(trace).items()})
reqs = requests_from_trace(trace, cfg.vocab_size, max_prompt=PROMPT_CAP,
                           max_new=NEW_CAP, time_scale=0.5)
engine = ServingEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                       decode_fn=decode_fn, sync_every=4)
t0 = time.perf_counter()
stats = engine.run(reqs)
for r in reqs:
    lat = r.finished - r.arrival
    monitor.record_request(r.finished, lat, lat * 0.5)
monitor.tick(time.perf_counter() - t0 + 1.0)

# --- modeled vs wall-clock SLOs --------------------------------------- #
# modeled TTFT: serial prefill on the faster device (no queueing term);
# modeled TPOT: pipelined steady-state bottleneck of the decode plan.
prefill_toks = jax.ShapeDtypeStruct((1, PROMPT_CAP), jnp.int32)
cache1 = jax.eval_shape(lambda: M.init_cache(cfg, 1, MAX_LEN))
tg_pre = analyzer.analyze(
    lambda p, c, t: M.prefill(p, cfg, t, c, scan_layers=False),
    jax.eval_shape(lambda: params), cache1, prefill_toks,
    state_argnums=(1,))
modeled_ttft = min(graph_time_on(tg_pre.graph, d) for d in devs)
s = stats.summary()
print("engine:", s)
print(f"{'':14}{'modeled':>12}{'wall-clock':>12}")
for name, model_v, wall_v in (
        ("TTFT", modeled_ttft, s["mean_ttft"]),
        ("TPOT", plans[monitor.policy].bottleneck, s["mean_tpot"])):
    ratio = wall_v / max(model_v, 1e-12)
    print(f"  {name:<12}{model_v * 1e3:>10.3f}ms{wall_v * 1e3:>10.3f}ms"
          f"   (wall/model {ratio:,.0f}x)")
# machine-readable modeled-vs-wall gap: the tracked baseline for the
# cost-model calibration follow-on (one JSON object per line, greppable
# by CALIBRATION)
import json                                            # noqa: E402
modeled_tpot = plans[monitor.policy].bottleneck
print("CALIBRATION " + json.dumps({
    "modeled_ttft_s": modeled_ttft, "wall_ttft_s": s["mean_ttft"],
    "ttft_wall_over_model": s["mean_ttft"] / max(modeled_ttft, 1e-12),
    "modeled_tpot_s": modeled_tpot, "wall_tpot_s": s["mean_tpot"],
    "tpot_wall_over_model": s["mean_tpot"] / max(modeled_tpot, 1e-12),
}))
print(f"monitor: policy={monitor.policy} switches={monitor.switches}")
print("sample output tokens:", reqs[0].output)

# --- phase-split: two-engine prefill→transfer→decode handoff ---------- #
# The real-engine analogue of the cluster simulator's KV-transfer edge,
# launched from the DECLARATIVE deployment spec: engine P runs ONLY
# prefills (the compute-rich pool's job), exports each request's
# KV/recurrent state, and engine D starts decode_only sessions from the
# imported state.  Greedy decode must be bit-identical to a single
# engine that never split the request.  The same spec object could be
# .simulate()d on the cluster DES instead — one description, two
# backends.
print("\n--- phase-split handoff (prefill engine -> decode engine) ---")
from repro.core.simulator import Interconnect          # noqa: E402
from repro.serving.spec import DeploymentSpec          # noqa: E402

ic = Interconnect(default_bw=100e9)
pd_trace = poisson_trace(rate=40.0, num_requests=6, seed=3)
single = requests_from_trace(pd_trace, cfg.vocab_size,
                             max_prompt=PROMPT_CAP, max_new=NEW_CAP,
                             time_scale=0.0)
split = requests_from_trace(pd_trace, cfg.vocab_size,
                            max_prompt=PROMPT_CAP, max_new=NEW_CAP,
                            time_scale=0.0)
ref_engine = ServingEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN)
ref_engine.run(single)

ENGINE_KW = {"slots": SLOTS, "max_len": MAX_LEN, "sync_every": 4}
pd_spec = DeploymentSpec(groups=[["tpu-v5p"], ["tpu-v5e"]], pd=True,
                         arch="gpt_oss_20b", engine=ENGINE_KW)
t0 = time.perf_counter()
out = pd_spec.compile().launch(cfg, params).run(split)
wall = time.perf_counter() - t0

match = all(a.output == b.output for a, b in zip(single, split))
wire_bytes = out["wire_bytes"]
print(f"requests={len(split)}  KV wire bytes={wire_bytes}  "
      f"modeled transfer={ic.transfer_time(wire_bytes, 0, 1) * 1e6:.1f}us"
      f"  wall={wall * 1e3:.1f}ms")
print(f"decode-only engine: {out['engine']}")
print("bit-identical to single engine:", match)
assert match, "phase-split decode diverged from the single-engine run"

# --- overlapped handoff: (layer, chunk) shards stream during prefill -- #
# kv_chunks > 1 in the spec launches the STREAMED pairing:
# the prefill engine's sessions.stream yields each layer's KV for a
# chunk the moment the chunk's prefill completes; the decode engine's
# sessions.receive installs the shards eagerly and starts decoding when
# the last one lands.  On real hardware the shard transfers ride the
# fabric concurrently with the remaining prefill compute, so only the
# transfer tail lands in TTFT (the engine analogue of
# simulate(kv_chunks=n) on the DES backend).
print("\n--- overlapped handoff (streamed (layer, chunk) shards) ---")
streamed = requests_from_trace(pd_trace, cfg.vocab_size,
                               max_prompt=PROMPT_CAP, max_new=NEW_CAP,
                               time_scale=0.0)
ov_spec = DeploymentSpec(groups=[["tpu-v5p"], ["tpu-v5e"]], pd=True,
                         kv_chunks=MAX_LEN // 4,   # 4-token chunks
                         arch="gpt_oss_20b", engine=ENGINE_KW)
out_s = ov_spec.compile().launch(cfg, params).run(streamed)
match_s = all(a.output == b.output for a, b in zip(single, streamed))
n_shards, shard_bytes = out_s["shards"], out_s["wire_bytes"]
per_chunk = ic.transfer_time(shard_bytes / max(n_shards, 1), 0, 1)
print(f"requests={len(streamed)}  shards={n_shards}  "
      f"bytes={shard_bytes}  modeled tail/shard={per_chunk * 1e6:.1f}us")
print("streamed decode bit-identical to single engine:", match_s)
assert match_s, "streamed handoff diverged from the single-engine run"
