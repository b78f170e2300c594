#!/usr/bin/env python3
"""What the serving engine's own spans and counters say about a window:
which host step held the chip idle, how long requests queued, and how
much of the prefill work was padding.

    python3 chipbench/engine_trace.py --workload <cell> --seed <n> \\
        --seconds <window> --trace <0|1>

The engine marks its control-plane steps with host spans named
``engine.*`` (``jax.profiler.TraceAnnotation``, ``serving/engine.py``).
A profiler trace records them on the timebase of the device's programs,
so each idle interval of device 0 in the traced part is laid at the host
step that covered it:

* its *activity* is that of the outermost covering span:
  ``engine.admit`` is admit, ``engine.sync`` sync,
  ``engine.decode_dispatch`` dispatch, and everything else (the wait for
  an arrival, the loop between spans, the traced part's edges) other.
  A sync inside an admission counts as admit;
* its label in ``idle_gaps`` is the innermost covering span's name.  A
  part no span covers keeps ``trace_reduce``'s ``<program> -> <program>``
  label, so a trace without engine spans gives the gaps
  ``trace_reduce.reduce`` gives.

The benchmark's own command does not report these yet.  This command
serves one window of a cell as ``chipbench/run.py`` does (no output
check) and prints one JSON line: the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, and the engine's: ``idle_share.<
activity>`` (%), ``queue_wait_p95_ms`` (95th percentile of ``admitted -
arrival``) and ``prefill_real_share`` (real prompt tokens over the
tokens the prefill programs computed, %).  Without a TPU it exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import sys  # noqa: E402
from bisect import bisect_left, bisect_right  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import record, trace_reduce  # noqa: E402
from chipbench.trace_reduce import program_name  # noqa: E402

PREFIX = "engine."
ACTIVITY = {"engine.admit": "admit", "engine.sync": "sync",
            "engine.decode_dispatch": "dispatch"}
ACTIVITIES = ("admit", "sync", "dispatch", "other")
UNITS = {**{f"idle_share.{a}": "%" for a in ACTIVITIES},
         "queue_wait_p95_ms": "ms", "prefill_real_share": "%"}
COUNTERS = ("completed", "decode_steps", "host_syncs", "prefill_batches",
            "prefill_tokens", "prefill_padded_tokens")

Span = Tuple[str, float, float, int]    # name, start, end, parent (-1: none)


def extract(profile) -> List[trace_reduce.Interval]:
    """The ``engine.*`` host events of the engine's thread (the host
    line that holds the most of them), times in ns."""
    threads = [[(e.name, e.start_ns, e.end_ns) for e in line.events
                if e.name.startswith(PREFIX)]
               for plane in profile.planes if plane.name.startswith("/host:")
               for line in plane.lines]
    return max(threads, key=len, default=[])


def nest(spans: Sequence[trace_reduce.Interval]) -> List[Span]:
    """Spans of one thread in start order, each with the index of the
    span that holds it (the span that caused it)."""
    out: List[Span] = []
    stack: List[int] = []
    for n, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        out.append((n, a, b, stack[-1] if stack else -1))
        stack.append(len(out) - 1)
    return out


def segments(spans: Sequence[Span]) -> List[Tuple[float, float, str, str]]:
    """The time the spans cover as disjoint pieces in time order:
    (start, end, innermost span's name, outermost span's name)."""
    top = []
    for n, _, _, parent in spans:
        top.append(top[parent] if parent >= 0 else n)
    out = []
    stack: List[int] = []
    cur = 0.0

    def emit(upto: float, i: int) -> None:
        if upto > cur:
            out.append((cur, upto, spans[i][0], top[i]))

    for i, (_, a, _, _) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= a:
            j = stack.pop()
            emit(spans[j][2], j)
            cur = max(cur, spans[j][2])
        if stack:
            emit(a, stack[-1])
        stack.append(i)
        cur = a
    while stack:
        j = stack.pop()
        emit(spans[j][2], j)
        cur = max(cur, spans[j][2])
    return out


@dataclasses.dataclass
class EngineTrace:
    window_s: float
    spans: List[Span]               # engine clock, cut to the traced part
    idle_s: Dict[str, float]        # device 0's idle seconds per activity
    idle_gaps: List[List]           # [label, seconds], top 10

    def idle_share(self, activity: str) -> Optional[float]:
        """Device 0's idle time in ``activity`` over the traced part, %."""
        if self.window_s <= 0:
            return None
        return 100.0 * self.idle_s[activity] / self.window_s


def reduce(events: Dict, spans: Sequence[trace_reduce.Interval],
           opened_at: float = 0.0, top: int = 10) -> Optional[EngineTrace]:
    """``events`` from ``trace_reduce.extract``, ``spans`` from
    ``extract``; None where the trace holds no device activity, as in
    ``trace_reduce.reduce``, whose traced part and idle gaps this
    uses."""
    devices = {d: v for d, v in events["devices"].items()
               if v["modules"] or v["ops"]}
    if not devices:
        return None
    every = [t for d in devices.values() for k in ("modules", "ops")
             for _, a, b in d[k] for t in (a, b)]
    marks = {n: a for n, a, _ in events["spans"]}
    lo = marks.get(trace_reduce.OPEN, min(every))
    hi = marks.get(trace_reduce.CLOSE, max(every))

    dev0 = devices[min(devices)]
    mods = sorted((x for x in dev0["modules"] if lo <= x[1] and x[2] <= hi),
                  key=lambda x: x[1])
    start_t = [a for _, a, _ in mods]
    by_end = sorted((b, n) for n, _, b in mods)
    end_t = [b for b, _ in by_end]
    ivs = trace_reduce._union(
        [(a, b) for _, a, b in trace_reduce._clip(
            dev0["ops"] or dev0["modules"], lo, hi)])
    edges = [lo] + [t for iv in ivs for t in iv] + [hi]

    nested = nest(trace_reduce._clip(spans, lo, hi))
    segs = segments(nested)
    idle: Dict[str, float] = dict.fromkeys(ACTIVITIES, 0.0)
    gaps: Dict[str, float] = collections.Counter()
    j = 0
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        covered = 0.0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s = min(b, segs[k][1]) - max(a, segs[k][0])
            gaps[segs[k][2]] += s * 1e-9
            idle[ACTIVITY.get(segs[k][3], "other")] += s * 1e-9
            covered += s
            k += 1
        if b - a > covered:
            # as trace_reduce labels a gap: the program it falls in or
            # follows, and the one it falls in or precedes
            i = bisect_left(start_t, a) - 1
            e = bisect_right(end_t, b)
            label = (f"{program_name(mods[i][0]) if i >= 0 else 'open'} -> "
                     f"{program_name(by_end[e][1]) if e < len(by_end) else 'close'}")
            gaps[label] += (b - a - covered) * 1e-9
            idle["other"] += (b - a - covered) * 1e-9

    def at(ns: float) -> float:
        return opened_at + (ns - lo) * 1e-9

    return EngineTrace(
        window_s=(hi - lo) * 1e-9,
        spans=[(n, at(a), at(b), p) for n, a, b, p in nested],
        idle_s=idle,
        idle_gaps=[[n, s] for n, s in
                   sorted(gaps.items(), key=lambda x: -x[1])[:top]])


def queue_wait_p95_ms(requests) -> Optional[float]:
    """95th percentile over the admitted requests of ``admitted -
    arrival``, in ms."""
    return record.percentile([(r.admitted - r.arrival) * 1e3
                              for r in requests if r.admitted >= 0], 95)


def prefill_real_share(counters: Dict[str, int]) -> Optional[float]:
    """Real prompt tokens over the tokens the prefill programs computed,
    in %."""
    padded = counters["prefill_padded_tokens"]
    return 100.0 * counters["prefill_tokens"] / padded if padded else None


def engine_metrics(requests, counters: Dict[str, int],
                   traced: Optional[EngineTrace]) -> Dict[str, Dict]:
    """The engine's metrics that have something to read, each with its
    unit."""
    values = {"queue_wait_p95_ms": queue_wait_p95_ms(requests),
              "prefill_real_share": prefill_real_share(counters)}
    if traced is not None:
        values.update((f"idle_share.{a}", traced.idle_share(a))
                      for a in ACTIVITIES)
    return {n: {"value": float(v), "unit": UNITS[n]}
            for n, v in values.items() if v is not None}


def span_seconds(spans: Sequence[Span]) -> Dict[str, List]:
    """Per span name: [count, host seconds]."""
    out: Dict[str, List] = {}
    for n, a, b, _ in spans:
        c = out.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += b - a
    return out


def span_cost_us(n: int = 100_000) -> float:
    """Host time of one empty ``engine.*`` span, in us."""
    from jax.profiler import TraceAnnotation
    t = time.perf_counter()
    for _ in range(n):
        with TraceAnnotation("engine.probe"):
            pass
    return (time.perf_counter() - t) / n * 1e6


def main(argv=None) -> int:
    import argparse
    import json

    from chipbench import harness, traffic
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.accelerator(cell.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.use_persistent_cache()
    counter = harness.CompileCounter()
    dep = harness.setup(cell, args.seed)
    reqs = harness.requests(traffic.schedule(
        cell.mix, args.seed, args.seconds, dep.model.vocab,
        float(cell.settings["rate_rps"])))
    off_us = span_cost_us()

    stats = dep.launched.engine.stats
    before = dataclasses.replace(stats)
    gc.collect()
    counter.count, counter.on = 0, True
    cpu = time.thread_time()
    t_open = time.perf_counter()
    tracer = harness.SubWindowTrace(t_open, args.seconds) \
        if args.trace else None
    if tracer is not None:
        tracer.start()
    dep.launched.run(reqs)
    t_close = time.perf_counter()
    cpu = time.thread_time() - cpu
    counter.on = False
    reduced = traced = None
    if tracer is not None:
        reduced = tracer.reduced()
        traced = reduce(trace_reduce.extract(tracer.data),
                        extract(tracer.data), opened_at=tracer.opened_at)
    counters = {k: getattr(stats, k) - getattr(before, k) for k in COUNTERS}
    counters["compiles_in_window"] = counter.count
    win = harness.Window(reqs=reqs, counters=counters, t_open=t_open,
                         t_close=t_close, trace=reduced)
    run = harness.make_run(cell, dep, win, args.seconds, T_START, devices)
    metrics = harness.read_metrics(run,
                                   harness.metrics_for(cell, "end_to_end"))
    if args.trace:
        metrics.update(harness.read_metrics(
            run, harness.metrics_for(cell, "per_layer")))
    metrics.update(engine_metrics(reqs, counters, traced))
    result = {
        "metrics": metrics,
        "counters": counters,
        "host": {"span_off_us": off_us,
                 "cpu_us_per_step": cpu / max(counters["decode_steps"], 1)
                 * 1e6},
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
    }
    if traced is not None:
        result["device"].update(busy_s=reduced.busy_s,
                                window_s=reduced.window_s)
        result["breakdown"] = {"idle_s": traced.idle_s,
                               "idle_gaps": traced.idle_gaps,
                               "spans": span_seconds(traced.spans)}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
