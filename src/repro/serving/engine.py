"""Serving engine: sync-free continuous batching over slot-based KV caches.

vLLM-shaped control plane on a JAX data plane:
  * fixed ``slots`` decode batch; idle slots are masked, arriving
    requests are admitted into free slots (continuous batching),
  * admissions are **batched**: all due arrivals that fit free slots go
    through ONE multi-request prefill — right-padded to a common length
    for attention families (exact under causal masking + per-row
    ``last_pos`` logit selection), grouped by exact prompt length for
    recurrent families (padding would pollute SSM state),
  * the decode hot loop is **sync-free**: ``last_tok``/``pos``/``budget``
    and the active mask live on device, sampling and termination logic
    are folded into the jitted decode step, and sampled tokens/done
    flags accumulate in device buffers that are fetched to the host only
    every ``sync_every`` steps — no per-token host round trip,
  * greedy / temperature sampling, per-slot positions, EOS/max-token
    termination, SLO accounting (TTFT / TPOT / normalized latency).
    TTFT is stamped only after the prefill logits are materialized
    (``block_until_ready``) — dispatch alone is not time-to-first-token,
  * optional Tessera integration: the decode step can be executed by a
    disaggregated StagedExecutable, with the OnlineMonitor switching
    between latency- and throughput-oriented plans (examples/
    serve_pipeline.py wires this up end to end),
  * prefill/decode disaggregation through ``engine.sessions``
    (kvpool.SessionManager): ``sessions.prefill`` runs a prompt and
    packages the per-request KV/recurrent state, ``sessions.restore``
    on a second engine starts decoding from it (greedy decode is
    bit-identical to a single-engine run) — the real-engine analogue
    of the cluster simulator's KV-transfer edge.  The PIPELINED pair
    ``sessions.stream`` / ``sessions.receive`` ships (layer, chunk)
    shards as soon as each prefill chunk completes, so the transfer
    overlaps the remaining prefill compute,
  * chunked COLOCATED admission: with ``prefill_chunk`` set, a long
    admitted prompt no longer freezes the live decode slots for its
    whole prefill — decode steps are interleaved between prefill
    chunks.

Accounting note: completion times are observed at sync boundaries, so a
request's ``finished`` stamp can be up to ``sync_every - 1`` decode steps
late.  That is the deliberate trade of the sync-free loop; run with
``sync_every=1`` to recover per-token accounting (and per-token host
syncs).  ``admitted`` is stamped when ``admit_batch`` takes a request,
before the sync that admission starts with, so ``admitted - arrival`` is
the request's queue wait: the time it was due while the engine was busy
elsewhere (a blocked sync, another admission, no free slot).

Host spans: the control plane marks its steps with
``jax.profiler.TraceAnnotation`` spans named ``engine.*`` (admission and
its parts, the sync and its parts, the decode dispatch, the wait for the
next arrival).  They cost about a microsecond each when no profiler
runs; a profiler trace puts them on the device's clock, so each idle gap
of the chip can be laid at the host step that held it.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serving.kvpool import PagedKvCache, SessionManager

# Families whose prefill is exact under right-padding (causal attention
# never reads positions past the query).  Recurrent state (ssm/hybrid)
# integrates every input token, so padded rows would corrupt it.  (vlm
# is deliberately absent: the engine does not serve it, and patch-embed
# placement under padding is unvalidated.)
_PAD_SAFE_FAMILIES = ("dense", "moe")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int = 16
    arrival: float = 0.0
    priority: int = 0                   # preemption rank (higher wins)
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    admitted: float = -1.0              # admit_batch took it (queue wait ends)
    ttft: float = -1.0
    finished: float = -1.0


@dataclasses.dataclass
class EngineStats:
    """Counters over the engine's life (take differences for a window),
    and the finished requests' TTFT, TPOT and latency per token.

    * ``completed``: requests finalized (budget, EOS or length).
    * ``decode_steps``: decode programs dispatched.
    * ``host_syncs``: non-empty syncs (one device-to-host fetch each).
    * ``prefill_batches``: admission groups prefilled.
    * ``prefill_tokens``: real prompt tokens admitted through them.
    * ``prefill_padded_tokens``: tokens the prefill programs computed
      (rows x length of the padded batch, pads included).
    * ``decode_kv_rows``: K/V rows the decode steps attended, summed
      over steps and live slots (a slot at position p attends p + 1).
    * ``decode_kv_capacity_rows``: steps x slots x ``max_len``, the rows
      a step that read every slot's whole cache would read.
    """
    completed: int = 0
    decode_steps: int = 0
    host_syncs: int = 0
    prefill_batches: int = 0
    prefill_tokens: int = 0
    prefill_padded_tokens: int = 0
    decode_kv_rows: int = 0
    decode_kv_capacity_rows: int = 0
    ttft: List[float] = dataclasses.field(default_factory=list)
    tpot: List[float] = dataclasses.field(default_factory=list)
    latency_per_token: List[float] = dataclasses.field(
        default_factory=list)

    def summary(self) -> Dict[str, float]:
        return {
            "completed": self.completed,
            "decode_steps": self.decode_steps,
            "host_syncs": self.host_syncs,
            "prefill_batches": self.prefill_batches,
            "decode_kv_rows": self.decode_kv_rows,
            "decode_kv_capacity_rows": self.decode_kv_capacity_rows,
            "mean_ttft": float(np.mean(self.ttft)) if self.ttft else 0.0,
            "mean_tpot": float(np.mean(self.tpot)) if self.tpot else 0.0,
            "mean_norm_latency": float(np.mean(self.latency_per_token))
            if self.latency_per_token else 0.0,
        }


# --------------------------------------------------------------------- #
# The engine's device programs.  Pure functions of (params, cache, ...)
# with the config and sampling constants static: the weights enter every
# compiled program as arguments, never as baked-in constants.
# --------------------------------------------------------------------- #
def _post(logits, last_tok, pos, budget, active, key, *, eos: int,
          temp: float, max_len: int):
    """Sampling + termination, fused with the decode dispatch.

    ``packed`` is one (2, slots) int32 array — [emitted token or -1;
    done flag] — so each step leaves exactly one buffer for the sync to
    fetch (no eager stacking on the hot path).
    """
    if temp <= 0.0:
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(sub, logits / temp,
                                     axis=-1).astype(jnp.int32)
    new_pos = jnp.where(active, pos + 1, pos)
    new_budget = jnp.where(active, budget - 1, budget)
    done = active & ((new_budget <= 0) | (tok == eos)
                     | (new_pos >= max_len - 1))
    new_active = active & ~done
    new_last = jnp.where(new_active, tok, last_tok)
    emit = jnp.where(active, tok, -1)     # -1 = idle slot
    packed = jnp.stack([emit, done.astype(jnp.int32)])
    return new_last, new_pos, new_budget, new_active, packed, key


post_step = jax.jit(_post, static_argnames=("eos", "temp", "max_len"))


_DECODE_STATIC = ("cfg", "eos", "temp", "max_len")


@functools.partial(jax.jit, static_argnames=_DECODE_STATIC)
def decode_fused(params, cache, last_tok, pos, budget, active, key, *,
                 cfg: ModelConfig, eos: int, temp: float, max_len: int):
    """One decode step over every slot, with sampling and termination."""
    logits, cache = M.decode_step(params, cfg, last_tok[:, None], cache,
                                  pos, lengths=jnp.where(active, pos + 1, 0))
    return (cache,) + _post(logits, last_tok, pos, budget, active, key,
                            eos=eos, temp=temp, max_len=max_len)


@functools.cache
def donating(program: Callable) -> Callable:
    """``program`` (a decode program of ``decode_fused``'s signature)
    compiled with its cache donated, under the program's own name.

    The engine owns its cache, so it runs the decode step this way: the
    step writes each slot's new K/V rows into the buffer it was given
    (``M.decode_step`` carries the cache through the layer loop) instead
    of producing a second whole cache, and the array passed in is
    deleted.  ``decode_fused`` itself stays pure, so a caller that keeps
    its arrays can call or lower it, and a program substituted for it
    runs inside the donating one and may return its own input."""
    return jax.jit(program, static_argnames=_DECODE_STATIC,
                   donate_argnames=("cache",))


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill_batch(params, cache, tokens, last_pos, *, cfg: ModelConfig):
    """Whole-prompt prefill of a right-padded admission batch."""
    return M.prefill(params, cfg, tokens, cache, last_pos=last_pos)


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill_chunk(params, cache, tokens, offset, last_pos, *,
                  cfg: ModelConfig):
    """One chunk of an incremental prefill (``offset`` is traced, so
    every full-size chunk shares one compile)."""
    return M.prefill(params, cfg, tokens, cache, offset=offset,
                     last_pos=last_pos)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params: Any, *, slots: int = 4,
                 max_len: int = 256, eos_id: Optional[int] = None,
                 temperature: float = 0.0, seed: int = 0,
                 decode_fn: Optional[Callable] = None,
                 prefill_fn: Optional[Callable] = None,
                 sync_every: int = 8,
                 prefill_chunk: Optional[int] = None,
                 kv_block_tokens: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 spill: bool = True,
                 preempt_priority: bool = True):
        assert cfg.family in ("dense", "moe", "ssm", "hybrid"), \
            "engine serves decoder-only families"
        assert sync_every >= 1
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.temperature = temperature
        self.sync_every = sync_every
        assert prefill_chunk is None or prefill_chunk >= 1
        self.prefill_chunk = prefill_chunk
        self.key = jax.random.PRNGKey(seed)
        self.stats = EngineStats()

        self.cache = M.init_cache(cfg, slots, max_len)
        self.active: List[Optional[Request]] = [None] * slots
        # Decode state is DEVICE-resident; the host only sees it at sync
        # boundaries.
        self.pos = jnp.zeros(slots, jnp.int32)        # next position
        self.budget = jnp.zeros(slots, jnp.int32)     # tokens remaining
        self.last_tok = jnp.zeros(slots, jnp.int32)
        self.active_mask = jnp.zeros(slots, bool)
        self._cols: List[jnp.ndarray] = []    # (2, slots) packed per step
        # upper bound on decode steps any live slot can still take
        # (recomputed whenever the host view is fresh)
        self._max_remaining = sync_every
        self._clock: Optional[Callable[[], float]] = None
        # Paged KV residency: sessions beyond the dense decode batch
        # park their state in a shared block pool (kvpool.PagedKvCache)
        # and time-slice through the slots at sync boundaries.  With
        # kv_block_tokens unset the engine is exactly the legacy
        # fixed-slot machine (self._paged is None everywhere).
        self.spill = spill
        self.preempt_priority = preempt_priority
        self._ran = [0] * slots         # decode steps since activation
        if kv_block_tokens is not None:
            pool_blocks = kv_pool_blocks if kv_pool_blocks is not None \
                else slots * (max_len // kv_block_tokens)
            self._paged: Optional[PagedKvCache] = PagedKvCache(
                cfg, pool_blocks, kv_block_tokens, max_len)
        else:
            assert kv_pool_blocks is None, \
                "kv_pool_blocks requires kv_block_tokens"
            self._paged = None
        self.sessions = SessionManager(self)
        self._decode_custom = decode_fn
        self._prefill_custom = prefill_fn
        self._sampling = dict(eos=-1 if eos_id is None else int(eos_id),
                              temp=float(temperature), max_len=max_len)

    # ------------------------------------------------------------------ #
    # Jitted steps with this engine's params bound (the programs take
    # params as an argument, so engines sharing params share one copy)
    # ------------------------------------------------------------------ #
    def _post(self, logits, last_tok, pos, budget, active, key):
        return post_step(logits, last_tok, pos, budget, active, key,
                         **self._sampling)

    def _step_fused(self, cache, last_tok, pos, budget, active, key):
        return donating(decode_fused)(self.params, cache, last_tok, pos,
                                      budget, active, key, cfg=self.cfg,
                                      **self._sampling)

    def _prefill(self, cache, toks, last_pos):
        return prefill_batch(self.params, cache, toks, last_pos,
                             cfg=self.cfg)

    def _prefill_at(self, cache, toks, offset, last_pos):
        return prefill_chunk(self.params, cache, toks, offset, last_pos,
                             cfg=self.cfg)

    # ------------------------------------------------------------------ #
    def _now(self, now: Optional[float]) -> float:
        if self._clock is not None:
            return self._clock()
        return now if now is not None else 0.0

    def _any_active(self) -> bool:
        if any(r is not None for r in self.active):
            return True
        return self._paged is not None and bool(self._paged.parked())

    def _write_slots(self, slots_: List[int], batch_cache: Any,
                     rows: int) -> None:
        """Scatter rows 0..rows of a prefill cache into engine slots —
        one scatter per cache leaf for the whole admission group."""
        idx = jnp.asarray(slots_, jnp.int32)

        def upd(full, grp):
            # full: (L, slots, ...); grp: (L, G_padded, ...)
            return full.at[:, idx].set(
                grp[:, :rows].astype(full.dtype))
        self.cache = jax.tree_util.tree_map(upd, self.cache, batch_cache)

    def _sample_host(self, logits: jnp.ndarray) -> np.ndarray:
        if self.temperature <= 0.0:
            return np.asarray(jnp.argmax(logits, axis=-1))
        self.key, sub = jax.random.split(self.key)
        return np.asarray(jax.random.categorical(
            sub, logits / self.temperature, axis=-1))

    # ------------------------------------------------------------------ #
    # Slot bookkeeping shared by admission, paged scheduling and the
    # session movers (kvpool.SessionManager)
    # ------------------------------------------------------------------ #
    def _prefill_len(self, n: int) -> int:
        """Prefill length for an ``n``-token prompt: pad-safe families
        round up to a multiple of 8 (capped at ``max_len - 1``), so
        admissions share compiled shapes; recurrent families keep the
        exact length."""
        if self.cfg.family in _PAD_SAFE_FAMILIES:
            return min(-(-n // 8) * 8, self.max_len - 1)
        return n

    def _prefill_one(self, req: Request):
        """Prefill ``req``'s prompt in a private batch-1 cache (no slot
        consumed), through the injected prefill when there is one.
        Returns (last-position logits, filled cache)."""
        plen = len(req.prompt)
        toks = np.zeros((1, self._prefill_len(plen)), np.int32)
        toks[0, :plen] = req.prompt
        cache1 = M.init_cache(self.cfg, 1, self.max_len)
        if self._prefill_custom is not None:
            return self._prefill_custom(
                self.params, cache1, jnp.asarray(toks[:, :plen], jnp.int32))
        return self._prefill(cache1, jnp.asarray(toks, jnp.int32),
                             jnp.asarray([plen - 1], jnp.int32))

    def _first_token(self, req: Request, logits,
                     now: Optional[float]) -> Tuple[int, bool]:
        """Sample ``req``'s first token once its prefill logits are
        real and append it.  Returns (token, whether the session lives
        on); a session that ends here (budget spent or EOS) is stamped
        and finalized at the ready time."""
        jax.block_until_ready(logits)
        t_ready = self._now(now)
        first = int(self._sample_host(logits)[0])
        self.stats.prefill_batches += 1
        req.output.append(first)
        live = req.max_new_tokens > 1 and not (
            self.eos_id is not None and first == self.eos_id)
        if not live:
            req.ttft = t_ready
            self._finalize(req, t_ready)
        return first, live

    def _install(self, slot: int, req: Request, pos: int, last_tok: int,
                 budget: int) -> None:
        """Make ``req`` live in ``slot`` at the given decode cursor (its
        state must already be in the slot's cache rows)."""
        self.pos = self.pos.at[slot].set(pos)
        self.last_tok = self.last_tok.at[slot].set(last_tok)
        self.budget = self.budget.at[slot].set(budget)
        self.active_mask = self.active_mask.at[slot].set(True)
        self.active[slot] = req
        self._ran[slot] = 0

    # ------------------------------------------------------------------ #
    # Admission: batched multi-request prefill
    # ------------------------------------------------------------------ #
    def admit(self, req: Request, now: float) -> bool:
        """Single-request admission (compat wrapper over admit_batch)."""
        return self.admit_batch([req], now) == 1

    def admit_batch(self, reqs: Sequence[Request], now: float) -> int:
        """Admit up to len(free slots) requests through batched prefills.

        Returns the number admitted.  Attention families take ONE padded
        prefill for the whole batch; recurrent families are grouped by
        exact prompt length (right-padding would pollute SSM state).
        Each request taken is stamped ``admitted`` with the time of this
        call, before its leading sync.
        """
        with TraceAnnotation("engine.admit"):
            t_take = self._now(now)
            # settle any buffered window first: admission must see fresh
            # slot state, and a slot re-filled mid-window would otherwise
            # have its new tokens hidden behind the old -1 idle markers
            self.sync(now)
            if self._paged is not None:
                # Paged admission runs in WAVES of up to ``slots``
                # requests: each wave prefills into the dense batch, then
                # parks into the pool to free slots for the next wave —
                # so concurrent residency is bounded by free BLOCKS, not
                # free slots.
                left = list(reqs)
                admitted = 0
                while left:
                    pairs = self._paged_admit(left, now)
                    if not pairs:
                        break
                    for _, r in pairs:
                        r.admitted = t_take
                    for group in self._admission_groups(pairs):
                        self._admit_group(group, now)
                    admitted += len(pairs)
                    left = left[len(pairs):]
                    if left:
                        self.sync(now)      # settle before parking
                        wave = {id(r) for _, r in pairs}
                        for s in range(self.slots):
                            if self.active[s] is not None \
                                    and id(self.active[s]) in wave:
                                self._park_slot(s, self._now(now))
                self._recompute_remaining()
                return admitted

            free = [s for s in range(self.slots) if self.active[s] is None]
            take = list(reqs[:len(free)])
            for r in take:
                assert len(r.prompt) < self.max_len, \
                    "prompt exceeds engine max_len"
                r.admitted = t_take
            pairs = list(zip(free, take))
            if not pairs:
                return 0
            for group in self._admission_groups(pairs):
                self._admit_group(group, now)
            self._recompute_remaining()
            return len(pairs)

    def _admission_groups(self, pairs: List[Tuple[int, "Request"]]
                          ) -> List[List]:
        """Partition admitted (slot, req) pairs into prefill groups:
        one padded batch for attention families, exact-length groups
        for recurrent families, batch-1 for injected prefill."""
        if self._prefill_custom is not None:
            return [[p] for p in pairs]
        if self.cfg.family in _PAD_SAFE_FAMILIES:
            return [pairs]
        by_len: Dict[int, List] = {}
        for s, r in pairs:
            by_len.setdefault(len(r.prompt), []).append((s, r))
        return list(by_len.values())

    def _paged_admit(self, reqs: List[Request],
                     now: float) -> List[Tuple[int, Request]]:
        """Paged admission: gated by free-BLOCK pressure, not free
        slots.  Each request reserves blocks for its worst-case token
        capacity; under pressure idle parked sessions spill to host
        (LRU), and with ``preempt_priority`` a strictly lower-priority
        active session is parked (freeing its slot) and spilled
        (freeing its blocks).  Returns the admitted (slot, req) pairs.
        """
        t = self._now(now)
        taken: set = set()
        pairs: List[Tuple[int, Request]] = []

        def free_slot() -> Optional[int]:
            for s in range(self.slots):
                if self.active[s] is None and s not in taken:
                    return s
            return None

        for r in reqs:
            assert len(r.prompt) < self.max_len, \
                "prompt exceeds engine max_len"
            cap = min(len(r.prompt) + r.max_new_tokens, self.max_len)
            # blocks FIRST: a request that cannot reserve memory must
            # not disturb any resident's slot
            reserved = self._paged.reserve(r, cap, spill=self.spill)
            while not reserved and self.preempt_priority and self.spill:
                # block pressure: evict (park + spill) a strictly
                # lower-priority active — its blocks go to host
                victim = self._preempt_victim(r.priority, taken)
                if victim is None:
                    break
                vrid = self.active[victim].rid
                self._park_slot(victim, t)
                self._paged.spill(vrid)
                self._paged.preemptions += 1
                reserved = self._paged.reserve(r, cap,
                                               spill=self.spill)
            if not reserved:
                break
            slot = free_slot()
            if slot is None:
                # no free slot: park a resident of <= priority (equal
                # priority time-slices fairly; a strictly higher one
                # is never displaced by admission)
                victim = self._preempt_victim(r.priority, taken,
                                              allow_equal=True)
                if victim is None:
                    self._paged.release(r.rid)   # roll the blocks back
                    break
                self._park_slot(victim, t)
                self._paged.preemptions += 1
                slot = victim
            taken.add(slot)
            pairs.append((slot, r))
        return pairs

    def _preempt_victim(self, incoming_prio: int, taken=(),
                        allow_equal: bool = False) -> Optional[int]:
        """Slot of the lowest-priority active session below (or, with
        ``allow_equal``, at) ``incoming_prio`` — ties broken toward
        the longest-running (round-robin LRU).  Slots in ``taken``
        (assigned this admission, prefill still pending) are never
        victims.  None when nothing is preemptible."""
        if not self.preempt_priority and not allow_equal:
            return None
        cands = []
        for s in range(self.slots):
            req = self.active[s]
            if req is None or s in taken:
                continue
            if req.priority < incoming_prio or \
                    (allow_equal and req.priority <= incoming_prio):
                cands.append((req.priority, -self._ran[s], s))
        return min(cands)[2] if cands else None

    # ------------------------------------------------------------------ #
    # Paged scheduling: park / activate through the block pool
    # ------------------------------------------------------------------ #
    def _park_slot(self, slot: int, t: float) -> None:
        """Preempt an active slot into the pool: export its state at
        the current decode cursor and pack it into the session's
        reserved blocks.  Requires a settled window (call at sync
        boundaries only); the park -> activate round trip is exact, so
        resumed greedy decode is bit-identical."""
        assert not self._cols, "parking requires a settled window"
        req = self.active[slot]
        p = int(self.pos[slot])
        state = M.export_kv(self.cfg, self.cache, slot, p)
        self._paged.park(req.rid, state, int(self.last_tok[slot]), p,
                         int(self.budget[slot]), t)
        self.active[slot] = None
        self.active_mask = self.active_mask.at[slot].set(False)
        self._ran[slot] = 0

    def _activate_parked(self, rid: int, slot: int, t: float) -> None:
        """Resume a parked session into a free slot (prefetching from
        host spill if needed) and restore its decode cursor."""
        req = self._paged.resident[rid].req
        state, last_tok, pos, budget = self._paged.activate(rid, t)
        self.cache = M.import_kv(self.cfg, self.cache, slot, state)
        self._install(slot, req, pos, last_tok, budget)

    def _schedule(self, now: Optional[float]) -> None:
        """Round-robin time slicing at sync boundaries: parked
        sessions activate into free slots FIFO; when none are free,
        actives that have used up their quantum (``sync_every`` decode
        steps) rotate out so every resident session makes progress."""
        runnable = self._paged.parked()
        if not runnable:
            return
        t = self._now(now)
        changed = False
        for s in range(self.slots):
            if not runnable:
                break
            if self.active[s] is None:
                self._activate_parked(runnable.pop(0), s, t)
                changed = True
        if runnable:
            expired = sorted(
                (s for s in range(self.slots)
                 if self.active[s] is not None
                 and self._ran[s] >= self.sync_every),
                key=lambda s: -self._ran[s])
            for s in expired[:len(runnable)]:
                self._park_slot(s, t)
                self._activate_parked(runnable.pop(0), s, t)
                changed = True
        if changed:
            self._recompute_remaining()

    def _admit_group(self, group: List, now: float) -> None:
        slots_ = [s for s, _ in group]
        reqs = [r for _, r in group]
        G = len(reqs)
        lens = [len(r.prompt) for r in reqs]
        # Pad sequence length to a multiple of 8 and batch to the next
        # power of two (padding rows are dummies): admission shapes are
        # bucketed, so the prefill jit compiles O(log slots) variants
        # instead of one per (batch, length) pair.  Length padding is
        # ONLY sound for causal-attention families — recurrent state
        # integrates every input token, pads included — so ssm/hybrid
        # groups (already exact-length) keep their exact length.
        S = self._prefill_len(max(lens))
        Gp = min(1 << (G - 1).bit_length(), self.slots)
        chunked = self._prefill_custom is None and self._can_chunk(S)
        with TraceAnnotation("engine.admit.prepare"):
            toks = np.zeros((Gp, S), np.int32)
            for i, r in enumerate(reqs):
                toks[i, :lens[i]] = r.prompt
            cache_g = M.init_cache(self.cfg, Gp, self.max_len)
            if self._prefill_custom is not None:
                args = (jnp.asarray(toks[:G, :max(lens)], jnp.int32),)
            else:
                last = np.zeros(Gp, np.int32)
                last[:G] = np.asarray(lens) - 1
                if not chunked:
                    args = (jnp.asarray(toks, jnp.int32),
                            jnp.asarray(last, jnp.int32))
        if chunked:
            # chunked prefill: live decode slots keep streaming between
            # chunks instead of stalling for the whole prompt (the
            # colocated head-of-line fix)
            logits, cache_g = self._prefill_chunks(
                cache_g, toks, last, now, interleave=True)
        else:
            with TraceAnnotation("engine.admit.prefill"):
                if self._prefill_custom is not None:
                    logits, cache_g = self._prefill_custom(
                        self.params, cache_g, *args)
                else:
                    logits, cache_g = self._prefill(cache_g, *args)
        self.stats.prefill_tokens += sum(lens)
        self.stats.prefill_padded_tokens += (
            G * max(lens) if self._prefill_custom is not None else Gp * S)
        with TraceAnnotation("engine.admit.write"):
            self._write_slots(slots_, cache_g, G)
        # honest TTFT: the first token exists only once logits are real
        with TraceAnnotation("engine.admit.wait"):
            jax.block_until_ready(logits)
        t_ready = self._now(now)
        with TraceAnnotation("engine.admit.sample"):
            first = self._sample_host(logits)[:G]
        self.stats.prefill_batches += 1

        with TraceAnnotation("engine.admit.slots"):
            upd_slots = jnp.asarray(slots_, jnp.int32)
            self.pos = self.pos.at[upd_slots].set(
                jnp.asarray(lens, jnp.int32))
            self.last_tok = self.last_tok.at[upd_slots].set(
                jnp.asarray(first, jnp.int32))
            budgets = [r.max_new_tokens - 1 for r in reqs]
            self.budget = self.budget.at[upd_slots].set(
                jnp.asarray(budgets, jnp.int32))
            # a slot only becomes live if it still has budget AND the
            # prefill token was not EOS — otherwise the device mask
            # would keep a ghost slot decoding after the host finalized
            # it
            live = [b > 0 and not (self.eos_id is not None
                                   and int(t) == self.eos_id)
                    for b, t in zip(budgets, first)]
            self.active_mask = self.active_mask.at[upd_slots].set(
                jnp.asarray(live))

            for i, (slot, req) in enumerate(group):
                tok = int(first[i])
                req.ttft = t_ready
                req.output.append(tok)
                if live[i]:
                    self.active[slot] = req
                else:
                    # completes at prefill (budget spent or EOS sampled)
                    self._finalize(req, t_ready)

    # ------------------------------------------------------------------ #
    # Chunked prefill: incremental cache fill with decode interleaving
    # ------------------------------------------------------------------ #
    def _can_chunk(self, S: int) -> bool:
        """Chunked prefill needs the built-in prefill path and a
        non-ring cache (SWA slot layout wraps at the window), and only
        pays off when the prompt spans more than one chunk."""
        return (self.prefill_chunk is not None
                and self._prefill_custom is None
                and self.cfg.sliding_window is None
                and S > self.prefill_chunk)

    def _prefill_chunks(self, cache_g, toks: np.ndarray,
                        last: np.ndarray, now: Optional[float] = None,
                        interleave: bool = False):
        """Drive ``prefill(offset=...)`` over prefill_chunk-sized
        slices of the padded admission batch.  With ``interleave`` one
        decode step runs between chunks, so a long admitted prompt no
        longer freezes the live decode slots for its whole prefill.
        Returns (last-position logits, filled cache) — identical to
        one whole-prompt prefill."""
        S = toks.shape[1]
        logits = None
        for _, t1, logits, cache_g in M.iter_prefill_chunks(
                self.params, self.cfg, toks, cache_g,
                chunk_size=self.prefill_chunk, last_pos=last,
                prefill_call=self._chunk_call):
            if interleave and t1 < S and self._any_active():
                self.step(self._now(now))
        return logits, cache_g

    def _chunk_call(self, cache, toks, off, rel):
        return self._prefill_at(cache, jnp.asarray(toks, jnp.int32),
                                off, jnp.asarray(rel, jnp.int32))

    def warmup(self) -> None:
        """Prime the jitted prefill and fused decode step (the common
        shape buckets) so a freshly scaled-in engine pays its compiles
        BEFORE it is marked routable, not on the first real request.
        Outputs are discarded except the cache, which the decode probe
        donates; engine state is otherwise untouched (the probe runs
        fully masked, and the rows it writes at each slot's position
        are overwritten by any admission, import or real step)."""
        if self._prefill_custom is None:
            cache1 = M.init_cache(self.cfg, 1, self.max_len)
            logits, _ = self._prefill(
                cache1, jnp.zeros((1, 8), jnp.int32),
                jnp.asarray([7], jnp.int32))
            jax.block_until_ready(logits)
        if self._decode_custom is None:
            out = self._step_fused(self.cache, self.last_tok, self.pos,
                                   self.budget, self.active_mask,
                                   self.key)
            self.cache = out[0]
            jax.block_until_ready(out[1])

    # ------------------------------------------------------------------ #
    # Sync-free decode loop
    # ------------------------------------------------------------------ #
    def step(self, now: float) -> None:
        """One decode step over all active slots (idle slots masked).

        Dispatch only — sampled tokens and done flags accumulate on
        device and reach the host every ``sync_every`` steps.
        """
        if not any(r is not None for r in self.active):
            if self._paged is not None:
                # no slot decoding but sessions may be parked: settle
                # and let the scheduler rotate them in
                self.sync(now)
            if not any(r is not None for r in self.active):
                return
        with TraceAnnotation("engine.decode_dispatch"):
            if self._decode_custom is not None:
                logits, self.cache = self._decode_custom(
                    self.params, self.cache, self.last_tok[:, None],
                    self.pos)
                (self.last_tok, self.pos, self.budget, self.active_mask,
                 packed, self.key) = self._post(
                    logits, self.last_tok, self.pos, self.budget,
                    self.active_mask, self.key)
            else:
                (self.cache, self.last_tok, self.pos, self.budget,
                 self.active_mask, packed, self.key) = self._step_fused(
                    self.cache, self.last_tok, self.pos,
                    self.budget, self.active_mask, self.key)
        self._cols.append(packed)
        self.stats.decode_steps += 1
        if self._paged is not None:
            for s in range(self.slots):
                if self.active[s] is not None:
                    self._ran[s] += 1
        # sync at the cadence, or as soon as every live slot must have
        # exhausted its budget (avoids masked tail steps at drain)
        if len(self._cols) >= min(self.sync_every, self._max_remaining):
            self.sync(now)

    def sync(self, now: float) -> None:
        """Fetch buffered tokens/flags; settle completions on the host.
        On paged engines the settled boundary is also the scheduling
        point: parked sessions rotate into freed slots here."""
        if self._cols:
            with TraceAnnotation("engine.sync"):
                self._settle_window(now)
        if self._paged is not None:
            self._schedule(now)

    def _settle_window(self, now: float) -> None:
        """Fetch the buffered steps' tokens and flags; settle them."""
        # one stacked D2H fetch for the whole window, not one per step
        with TraceAnnotation("engine.sync.fetch"):
            cols = self._cols[0] if len(self._cols) == 1 else \
                jnp.stack(self._cols, axis=2)
            window = np.asarray(cols).reshape(2, self.slots, -1)
        toks, dones = window[0], window[1]                 # (slots, k)
        self._cols = []
        self.stats.host_syncs += 1
        self.stats.decode_kv_capacity_rows += \
            toks.shape[1] * self.slots * self.max_len
        t_set = self._now(now)
        with TraceAnnotation("engine.sync.settle"):
            for s in range(self.slots):
                req = self.active[s]
                if req is None:
                    continue
                for k in range(toks.shape[1]):
                    t = int(toks[s, k])
                    if t < 0:       # slot went idle earlier in window
                        break
                    # the step ran at position prompt + output - 1
                    self.stats.decode_kv_rows += \
                        len(req.prompt) + len(req.output)
                    req.output.append(t)
                    if dones[s, k]:
                        self._finalize(req, t_set)
                        self.active[s] = None
                        break
            self._recompute_remaining()

    def _recompute_remaining(self) -> None:
        rem = [r.max_new_tokens - len(r.output)
               for r in self.active if r is not None]
        self._max_remaining = max(rem) if rem else self.sync_every

    def _finalize(self, req: Request, now: float) -> None:
        req.finished = now
        if self._paged is not None:
            self._paged.release(req.rid)    # no-op if never reserved
        self.stats.completed += 1
        self.stats.ttft.append(req.ttft - req.arrival)
        self.stats.tpot.append(
            (now - req.ttft) / max(len(req.output) - 1, 1))
        self.stats.latency_per_token.append(
            (now - req.arrival) / max(len(req.output), 1))

    # ------------------------------------------------------------------ #
    def run(self, requests: List[Request]) -> EngineStats:
        """Process a workload to completion (arrival times honored via
        a virtual clock driven by wall time)."""
        t0 = time.perf_counter()
        self._clock = lambda: time.perf_counter() - t0
        try:
            pending = sorted(requests, key=lambda r: r.arrival)
            while pending or self._any_active():
                now = self._clock()
                if pending and pending[0].arrival <= now \
                        and (None in self.active
                             or self._paged is not None):
                    # admit every due arrival that fits (admit_batch
                    # settles the buffered window itself); a paged
                    # engine may also preempt for a due arrival
                    batch = []
                    nfree = self.active.count(None)
                    if self._paged is not None:
                        nfree = max(nfree, 1)
                    while (pending and len(batch) < nfree
                           and pending[0].arrival <= self._clock()):
                        batch.append(pending.pop(0))
                    if batch:
                        n = self.admit_batch(batch, self._clock())
                        if n < len(batch):
                            # paged pressure refused the tail: requeue
                            # (batch holds the earliest arrivals, so
                            # prepending preserves sort order)
                            pending = batch[n:] + pending
                if not self._any_active():
                    if pending:
                        # idle until the next arrival: sleep, don't spin
                        delay = pending[0].arrival - self._clock()
                        if delay > 0:
                            with TraceAnnotation("engine.wait"):
                                time.sleep(delay)
                    continue
                self.step(self._clock())
            self.sync(self._clock())
        finally:
            self._clock = None
        return self.stats


# --------------------------------------------------------------------- #
def requests_from_trace(trace, vocab_size: int, *,
                        max_prompt: Optional[int] = None,
                        max_new: Optional[int] = None,
                        time_scale: float = 1.0,
                        seed: int = 0) -> List[Request]:
    """Materialize ``serving.workload`` trace entries as engine Requests.

    Workload traces carry token *counts*; this synthesizes concrete
    prompts (uniform random ids) at those lengths, optionally clipped to
    engine-sized ``max_prompt``/``max_new`` and with arrivals compressed
    by ``time_scale`` (CPU smoke runs serve far fewer tok/s than the
    modeled accelerators).
    """
    rng = np.random.default_rng(seed)
    out = []
    for w in trace:
        p = w.prompt_tokens if max_prompt is None \
            else min(w.prompt_tokens, max_prompt)
        n = w.output_tokens if max_new is None \
            else min(w.output_tokens, max_new)
        out.append(Request(
            rid=w.rid,
            prompt=rng.integers(0, vocab_size, size=max(1, p))
            .astype(np.int32),
            max_new_tokens=max(1, n),
            arrival=w.arrival * time_scale))
    return out
