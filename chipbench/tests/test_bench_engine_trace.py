"""The engine's spans and counters as the benchmark reads them: the
spans a served burst leaves in a CPU profile, the split of the device's
idle time by host activity on a hand-made trace, the recorded v5e slice
reduced as before, and the engine metrics on synthetic records."""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest
from jax._src.lib import _profiler

import repro.configs as configs
from chipbench import engine_trace, harness, record, trace_reduce
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine

FIXTURE = Path(__file__).resolve().parent / "data" / "v5e_trace_slice.json"


# --------------------------------------------------------------------- #
# The spans a served burst leaves in a profile
# --------------------------------------------------------------------- #
def _burst(cfg, rng):
    """Two requests due at once, then one a second later (the engine
    waits for it)."""
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=4, arrival=t)
            for i, (n, t) in enumerate([(5, 0.0), (8, 0.0), (8, 1.0)])]


@pytest.fixture(scope="module")
def profiled():
    """A tiny dense engine, warmed, serves a burst under a CPU profiler
    session; an empty sync follows inside the session."""
    cfg = dataclasses.replace(configs.get_smoke("qwen3_1_7b"),
                              dtype="float32")
    eng = ServingEngine(cfg, M.init_params(cfg), slots=2, max_len=32,
                        sync_every=2)
    rng = np.random.default_rng(4)
    eng.run(_burst(cfg, rng))               # compiles every shape
    syncs = eng.stats.host_syncs
    opts = _profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    session = _profiler.ProfilerSession(opts)
    eng.run(_burst(cfg, rng))
    eng.sync(0.0)                           # nothing buffered
    spans = engine_trace.extract(session.stop_and_get_profile_data())
    return engine_trace.nest(spans), eng.stats.host_syncs - syncs


def test_burst_emits_every_engine_span(profiled):
    spans, _ = profiled
    assert {n for n, *_ in spans} == {
        "engine.admit", "engine.admit.prepare", "engine.admit.prefill",
        "engine.admit.write", "engine.admit.wait", "engine.admit.sample",
        "engine.admit.slots", "engine.sync", "engine.sync.fetch",
        "engine.sync.settle", "engine.decode_dispatch", "engine.wait"}
    # two admissions: the burst's pair, and the late request
    assert [n for n, *_ in spans].count("engine.admit") == 2


def test_children_sit_inside_their_parent(profiled):
    spans, _ = profiled
    for n, a, b, parent in spans:
        if n.startswith(("engine.admit.", "engine.sync.")):
            pn, pa, pb, _ = spans[parent]
            assert pn == n.rsplit(".", 1)[0]
            assert pa <= a and b <= pb
        elif n != "engine.sync":
            assert parent == -1, n      # dispatch and wait: top level
        # a sync is top level, or settles the window an admission starts
        # with
        elif parent >= 0:
            assert spans[parent][0] == "engine.admit"


def test_only_a_sync_that_fetches_emits_a_span(profiled):
    spans, syncs = profiled
    assert syncs > 0
    assert [n for n, *_ in spans].count("engine.sync") == syncs


# --------------------------------------------------------------------- #
# The idle split on a hand-made trace (times in ns)
# --------------------------------------------------------------------- #
# traced part 0-100; device busy 10-20 (prefill_batch) and 40-50, 70-80
# (decode_fused); idle 0-10, 20-40, 50-70, 80-100.  Engine spans:
#   admit 2-25 holding sync 3-6 and its fetch 3-5, prepare 6-9 and
#   write 20-24; dispatch 30-38; sync 52-60 holding settle 54-58;
#   wait 85-95.  Idle 0-2, 25-30, 38-40, 50-52, 60-70, 80-85, 95-100 is
#   under no span.
HAND = {
    "devices": {0: {
        "modules": [("jit_prefill_batch(1)", 10, 20),
                    ("jit_decode_fused(2)", 40, 50),
                    ("jit_decode_fused(2)", 70, 80)],
        "ops": [("%fusion.1 bf16[8]", 10, 20), ("%fusion.2 bf16[8]", 40, 50),
                ("%fusion.2 bf16[8]", 70, 80)]}},
    "spans": [("trace_open", 0, 0), ("trace_close", 100, 100)]}
ENGINE = [("engine.admit", 2, 25), ("engine.sync", 3, 6),
          ("engine.sync.fetch", 3, 5), ("engine.admit.prepare", 6, 9),
          ("engine.admit.write", 20, 24), ("engine.decode_dispatch", 30, 38),
          ("engine.sync", 52, 60), ("engine.sync.settle", 54, 58),
          ("engine.wait", 85, 95)]


def _hand(spans=ENGINE, top=20):
    return engine_trace.reduce(HAND, spans, opened_at=1.0, top=top)


def test_idle_shares_sum_to_idle_share():
    et = _hand()
    run = record.Run(cell="t", seconds=1.0, requests=[], setup_s=0.0,
                     launch_s=0.0, counters={}, slots=1, sync_every=1,
                     model=None, peaks={}, trace=trace_reduce.reduce(HAND))
    total = harness.load_reader("idle_share")(run)
    assert total == pytest.approx(70.0)
    shares = [et.idle_share(a) for a in engine_trace.ACTIVITIES]
    assert abs(sum(shares) - total) < 1e-9


def test_idle_split_by_outermost_span():
    et = _hand()
    s = {a: v * 1e9 for a, v in et.idle_s.items()}
    # admit 2-10 (the sync inside it counts as admit) and 20-25
    assert s["admit"] == pytest.approx(13)
    assert s["dispatch"] == pytest.approx(8)        # 30-38
    assert s["sync"] == pytest.approx(8)            # 52-60
    # 0-2, 25-30, 38-40, 50-52, 60-70, 80-100 (the wait included)
    assert s["other"] == pytest.approx(41)


def test_gap_parts_carry_the_innermost_span():
    gaps = {n: s * 1e9 for n, s in _hand().idle_gaps}
    assert gaps["engine.sync.fetch"] == pytest.approx(2)      # 3-5
    assert gaps["engine.sync"] == pytest.approx(1 + 2 + 2)    # 5-6, 52-54, 58-60
    assert gaps["engine.admit"] == pytest.approx(1 + 1 + 1)   # 2-3, 9-10, 24-25
    assert gaps["engine.admit.prepare"] == pytest.approx(3)   # 6-9
    assert gaps["engine.admit.write"] == pytest.approx(4)     # 20-24
    assert gaps["engine.sync.settle"] == pytest.approx(4)
    assert gaps["engine.decode_dispatch"] == pytest.approx(8)
    assert gaps["engine.wait"] == pytest.approx(10)
    # the parts no span covers keep the programs on either side
    assert gaps["open -> prefill_batch"] == pytest.approx(2)
    assert gaps["prefill_batch -> decode_fused"] == pytest.approx(5 + 2)
    assert gaps["decode_fused -> decode_fused"] == pytest.approx(2 + 10)
    assert gaps["decode_fused -> close"] == pytest.approx(5 + 5)
    assert sum(gaps.values()) == pytest.approx(70)
    # the breakdown keeps the ten longest
    assert [n for n, _ in _hand(top=10).idle_gaps] == \
        [n for n, _ in _hand().idle_gaps][:10]


def test_spans_go_on_the_engine_clock():
    spans = _hand().spans
    assert spans[0][:2] == ("engine.admit",
                            pytest.approx(1.0 + 2e-9, abs=1e-12))
    names = [n for n, *_ in spans]
    assert spans[names.index("engine.sync.fetch")][3] == \
        names.index("engine.sync")
    assert spans[names.index("engine.sync")][3] == 0


def test_without_engine_spans_gaps_are_as_before():
    red = trace_reduce.reduce(HAND)
    et = _hand([], top=10)
    assert et.idle_gaps == red.idle_gaps
    assert et.idle_s["other"] == pytest.approx(red.window_s - red.busy_s)


def test_recorded_v5e_slice_reduces_as_before():
    raw = json.loads(FIXTURE.read_text())
    names = raw["op_names"]
    events = {"spans": raw["spans"], "devices": {
        int(k): {"modules": v["modules"],
                 "ops": [(names[i], a, b) for i, a, b in v["ops"]]}
        for k, v in raw["devices"].items()}}
    red = trace_reduce.reduce(events)
    et = engine_trace.reduce(events, [])
    assert et.idle_gaps == red.idle_gaps
    assert et.window_s == red.window_s
    assert sum(et.idle_s.values()) == pytest.approx(red.window_s - red.busy_s,
                                                    abs=1e-12)


def test_no_device_activity_reduces_to_nothing():
    assert engine_trace.reduce({"devices": {}, "spans": []}, ENGINE) is None


# --------------------------------------------------------------------- #
# The engine metrics on synthetic records
# --------------------------------------------------------------------- #
def _req(arrival, admitted):
    return Request(rid=0, prompt=np.zeros(4, np.int32), arrival=arrival,
                   admitted=admitted)


def test_queue_wait_and_prefill_real_share():
    reqs = [_req(i, i + 0.001 * i) for i in range(20)] + [_req(30, -1.0)]
    assert engine_trace.queue_wait_p95_ms(reqs) == pytest.approx(
        np.percentile([float(i) for i in range(20)], 95))
    assert engine_trace.prefill_real_share(
        {"prefill_tokens": 300, "prefill_padded_tokens": 400}) == 75.0


def test_metrics_without_a_trace_or_work_are_left_out():
    none = engine_trace.engine_metrics(
        [_req(0, -1.0)], {"prefill_tokens": 0, "prefill_padded_tokens": 0},
        None)
    assert none == {}
    some = engine_trace.engine_metrics(
        [_req(0, 0.002)], {"prefill_tokens": 8, "prefill_padded_tokens": 8},
        None)
    assert set(some) == {"queue_wait_p95_ms", "prefill_real_share"}
    traced = engine_trace.engine_metrics([], {"prefill_padded_tokens": 0},
                                         _hand())
    assert set(traced) == {f"idle_share.{a}" for a in engine_trace.ACTIVITIES}
    assert all(m["unit"] == "%" for m in traced.values())


def test_no_tpu_no_result():
    assert engine_trace.main(["--workload", "qwen3-chat", "--seed", "1",
                              "--seconds", "1"]) == 2
