"""Paged KV residency and the unified session-state API.

Two pieces, one file:

* **Paged residency** (`BlockPool`, `PagedKvCache`): a shared
  (layer, block) pool replaces "one session == one max_len slot" as the
  engine's memory architecture.  Every admitted session reserves
  ``ceil(capacity_tokens / block_tokens)`` pool blocks (plus a fixed
  block cost for recurrent / ring-buffer state that cannot be
  token-paged), so admission is gated by free-*block* pressure, not
  free slots.  Sessions time-slice through the small dense decode batch
  ("park" packs a slot's exported state into its pool blocks;
  "activate" gathers it back), priority preemption parks the
  lowest-priority active session, and a hierarchical tier spills idle
  parked sessions HBM -> host with LRU eviction (peer prefetch pulls a
  session straight off another engine).  Park -> activate round-trips
  through ``export_kv``/``import_kv`` with no arithmetic, so resumed
  greedy decode is bit-identical to never having been preempted.

* **Session API** (`KvSlice`, `SessionState`, `SessionManager`): one
  surface, ``engine.sessions``, for every move of a session's state:
  ``prefill`` / ``stream`` / ``restore`` / ``receive`` / ``checkpoint``
  / ``migrate`` / ``prefetch``, with typed values as the only wire
  format.  The engine owns the slot bookkeeping these verbs share
  (prefill length bucket, batch-1 prefill, first-token tail, slot
  install); this module never imports the engine.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models import layers as LY

__all__ = ["KvSlice", "SessionState", "BlockPool", "PagedKvCache",
           "SessionManager", "ShardChecksumError", "kv_checksum"]


class ShardChecksumError(RuntimeError):
    """A streamed shard arrived corrupted (its ``checksum`` does not
    match its state bytes).  :meth:`SessionManager.receive` raises this
    AFTER rolling the reserved slot/blocks back, so the caller can fall
    back to re-prefilling on the receiving engine."""


def kv_checksum(state: Any) -> int:
    """crc32 over the raw bytes of every leaf of a state pytree (leaf
    order is the pytree's canonical flatten order, so producer and
    consumer agree)."""
    crc = 0
    for leaf in jax.tree_util.tree_leaves(state):
        crc = zlib.crc32(
            np.ascontiguousarray(jax.device_get(leaf)).tobytes(), crc)
    return crc


# ===================================================================== #
# Payload dataclasses: the session-state wire format
# ===================================================================== #
@dataclasses.dataclass(frozen=True)
class KvSlice:
    """One streamed shard of a session's state: a (component, layer,
    token-range) slice of the cache pytree plus its wire size.  The
    unit yielded by :meth:`SessionManager.stream` and consumed by
    :meth:`SessionManager.receive`.  ``checksum`` (opt-in via
    ``stream(checksum=True)``) carries the producer-side
    :func:`kv_checksum`; :meth:`SessionManager.receive` verifies it on
    arrival and rejects the stream on mismatch."""
    rid: int
    component: str                      # "kv" / "rwkv" / "mamba" / ...
    layer: int
    t0: Optional[int] = None            # token window [t0, t1) for "kv"
    t1: Optional[int] = None
    state: Any = None                   # batch-1 layer-1 pytree
    nbytes: int = 0
    checksum: Optional[int] = None      # producer-side kv_checksum
    # fabric priority class (serving.fabric.URGENT / .BULK): decode-
    # blocking handoffs travel URGENT; checkpoint/migration shards
    # travel BULK.  Defaults URGENT, so a producer that knows no
    # fabric streams decode-blocking shards.
    klass: int = 0

    def verify(self) -> bool:
        """True when no checksum travelled or it matches the state."""
        return (self.checksum is None
                or self.checksum == kv_checksum(self.state))


@dataclasses.dataclass
class SessionState:
    """A session's portable decode state: the exported KV / recurrent
    pytree plus the decode cursor.  ``first_token_pending`` True means
    the first token has not streamed to the client yet (a prefill
    handoff), so :meth:`SessionManager.restore` stamps TTFT on
    arrival; False means the session already streamed tokens elsewhere
    (a checkpoint) and moving it must not touch the client's clock."""
    rid: int
    state: Any                          # cache pytree; None when done
    last_tok: int
    pos: int
    budget: int                         # decode tokens remaining
    nbytes: int                         # wire size of ``state``
    done: bool = False
    first_token_pending: bool = True
    priority: int = 0


# ===================================================================== #
# Block pool allocator
# ===================================================================== #
class BlockPool:
    """Free-list allocator over a fixed set of pool block ids.

    Invariants (property-tested): a block is never handed out twice,
    and ``free + allocated == n_blocks`` after any interleaving of
    alloc / release."""

    def __init__(self, n_blocks: int):
        assert n_blocks >= 1, "pool needs at least one block"
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(n_blocks - 1, -1, -1))
        self._owner: Dict[int, int] = {}        # block id -> rid

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def allocated(self) -> int:
        return len(self._owner)

    def alloc(self, rid: int, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(
                f"kv pool exhausted: need {n} blocks, {len(self._free)}"
                f" free of {self.n_blocks}")
        ids = [self._free.pop() for _ in range(n)]
        for b in ids:
            assert b not in self._owner, "double allocation"
            self._owner[b] = rid
        return ids

    def release(self, ids: List[int]) -> None:
        for b in ids:
            assert b in self._owner, "freeing an unowned block"
            del self._owner[b]
            self._free.append(b)

    def check(self) -> bool:
        assert self.free + self.allocated == self.n_blocks, \
            "block accounting broken"
        assert len(set(self._free)) == self.free, "free-list duplicate"
        assert not (set(self._free) & set(self._owner)), \
            "block both free and owned"
        return True


# ===================================================================== #
# Paged residency: block tables, tiers, park/activate
# ===================================================================== #
@dataclasses.dataclass
class _Resident:
    """One session's residency record (active in a slot, parked in
    HBM pool blocks, or spilled to host)."""
    req: Any
    block_ids: List[int]
    capacity: int                       # reserved token capacity
    priority: int = 0
    tier: str = "active"                # "active" | "hbm" | "host"
    payload: Any = None                 # non-token-paged components
    host: Any = None                    # host copy when tier == "host"
    last_tok: int = 0
    pos: int = 0
    budget: int = 0
    seq: int = 0                        # FIFO order for scheduling
    last_use: float = 0.0               # LRU key for spill


class PagedKvCache:
    """The residency layer of a paged engine: a shared (layer, block)
    attention-KV pool plus per-session block tables, with a host spill
    tier below it.

    The dense per-slot cache stays the engine's active-decode working
    set (the jitted hot loop is untouched); this class owns where
    *resident-but-not-decoding* state lives and how many blocks every
    session — active or parked — has reserved.
    """

    def __init__(self, cfg, pool_blocks: int, block_tokens: int,
                 max_len: int):
        assert pool_blocks >= 1 and block_tokens >= 1
        assert max_len % block_tokens == 0, \
            "max_len must be a multiple of kv_block_tokens"
        self.cfg = cfg
        self.block_tokens = block_tokens
        self.max_len = max_len
        self.pool = BlockPool(pool_blocks)
        self.resident: Dict[int, _Resident] = {}
        self._seq = 0
        self.spills = 0
        self.prefetches = 0
        self.preemptions = 0

        probe = M.init_cache(cfg, 1, max_len)
        counts = M.cache_layer_counts(probe)
        # attention KV is token-paged only when the time axis is a real
        # prefix (ring-buffer SWA slot layout depends on absolute
        # positions, so the whole ring travels as fixed payload)
        self.token_paged = ("kv" in counts
                            and cfg.sliding_window is None)
        n_kv = counts.get("kv", cfg.num_layers)
        self.block_bytes = M.kv_block_bytes(cfg, block_tokens,
                                            layers=n_kv)
        if self.token_paged:
            self.arrays = LY.make_kv_block_pool(
                cfg, pool_blocks, block_tokens, layers=n_kv)
            fixed = sum(
                leaf.size * leaf.dtype.itemsize
                for key, val in probe.items() if key != "kv"
                for leaf in jax.tree_util.tree_leaves(val))
        else:
            self.arrays = None
            fixed = sum(leaf.size * leaf.dtype.itemsize
                        for leaf in jax.tree_util.tree_leaves(probe))
        if self.block_bytes == 0:
            # pure-recurrent family (no attention KV anywhere): one
            # session's fixed-size state is the natural block unit
            self.block_bytes = max(fixed, 1)
        # fixed per-session block cost for state that cannot be paged
        self.fixed_blocks = -(-fixed // self.block_bytes) if fixed else 0
        del probe

    # ---------------------------------------------------------------- #
    # Accounting
    # ---------------------------------------------------------------- #
    def blocks_for(self, tokens: int) -> int:
        """Reserved blocks for a session of ``tokens`` capacity."""
        paged = -(-tokens // self.block_tokens) if self.token_paged \
            else 0
        return max(paged + self.fixed_blocks, 1)

    def util(self) -> float:
        return self.pool.allocated / self.pool.n_blocks

    def holds(self, rid: int) -> bool:
        return rid in self.resident

    def parked(self) -> List[int]:
        """Parked session rids in FIFO (park-order) sequence."""
        return sorted((r for r in self.resident
                       if self.resident[r].tier != "active"),
                      key=lambda r: self.resident[r].seq)

    # ---------------------------------------------------------------- #
    # Admission / release
    # ---------------------------------------------------------------- #
    def reserve(self, req: Any, capacity: int, *,
                spill: bool = True) -> bool:
        """Reserve blocks for a session of ``capacity`` tokens,
        spilling idle parked sessions (LRU) to host under pressure.
        Returns False when the pool cannot fit it even after spilling.
        """
        capacity = min(capacity, self.max_len)
        need = self.blocks_for(capacity)
        while self.pool.free < need and spill and self.spill_lru():
            pass
        if self.pool.free < need:
            return False
        ids = self.pool.alloc(req.rid, need)
        self._seq += 1
        self.resident[req.rid] = _Resident(
            req=req, block_ids=ids, capacity=capacity,
            priority=getattr(req, "priority", 0), seq=self._seq)
        return True

    def release(self, rid: int) -> None:
        ent = self.resident.pop(rid, None)
        if ent is not None and ent.block_ids:
            self.pool.release(ent.block_ids)

    # ---------------------------------------------------------------- #
    # Park / activate: the slot <-> pool data path
    # ---------------------------------------------------------------- #
    def park(self, rid: int, state: Any, last_tok: int, pos: int,
             budget: int, now: float = 0.0) -> None:
        """Pack an active session's exported state into its reserved
        pool blocks (one scatter for the paged KV; recurrent / ring
        components ride along as fixed payload)."""
        ent = self.resident[rid]
        assert ent.tier == "active", "parking a non-active session"
        if self.token_paged and "kv" in state:
            nb = -(-max(pos, 1) // self.block_tokens)
            assert nb * self.block_tokens <= \
                len(ent.block_ids) * self.block_tokens
            self.arrays = M.pack_kv_blocks(
                self.arrays, state["kv"], ent.block_ids[:nb])
            ent.payload = {k: v for k, v in state.items() if k != "kv"}
        else:
            ent.payload = state
        ent.last_tok, ent.pos, ent.budget = last_tok, pos, budget
        ent.tier = "hbm"
        ent.last_use = now
        self._seq += 1
        ent.seq = self._seq

    def activate(self, rid: int,
                 now: float = 0.0) -> Tuple[Any, int, int, int]:
        """Reassemble a parked session's state (prefetching from host
        if it was spilled) and mark it active.  Returns
        ``(state, last_tok, pos, budget)`` — exactly the payload
        :func:`repro.models.model.import_kv` installs."""
        ent = self.resident[rid]
        assert ent.tier != "active", "session already active"
        if ent.tier == "host":
            self._prefetch(ent)
        if self.token_paged and self.arrays is not None \
                and ent.payload is not None and ent.pos > 0 \
                and "kv" not in ent.payload:
            nb = -(-ent.pos // self.block_tokens)
            kv = M.gather_kv_blocks(self.arrays, ent.block_ids[:nb],
                                    ent.pos)
            state = dict(ent.payload)
            state["kv"] = kv
        else:
            state = ent.payload
        ent.payload = None
        ent.tier = "active"
        ent.last_use = now
        return state, ent.last_tok, ent.pos, ent.budget

    # ---------------------------------------------------------------- #
    # Hierarchical tier: HBM -> host spill, host -> HBM prefetch
    # ---------------------------------------------------------------- #
    def spill(self, rid: int) -> None:
        """Evict a parked session's blocks to host memory (the full
        assembled state moves; the HBM blocks are freed)."""
        ent = self.resident[rid]
        assert ent.tier == "hbm", "can only spill a parked session"
        if self.token_paged and ent.pos > 0 and ent.payload is not None:
            nb = -(-ent.pos // self.block_tokens)
            state = dict(ent.payload)
            state["kv"] = M.gather_kv_blocks(
                self.arrays, ent.block_ids[:nb], ent.pos)
        else:
            state = ent.payload
        ent.host = jax.device_get(state)
        ent.payload = None
        self.pool.release(ent.block_ids)
        ent.block_ids = []
        ent.tier = "host"
        self.spills += 1

    def _prefetch(self, ent: _Resident) -> None:
        """Bring a host-spilled session back into HBM pool blocks."""
        need = self.blocks_for(ent.capacity)
        while self.pool.free < need and self.spill_lru(
                exclude=ent.req.rid):
            pass
        ent.block_ids = self.pool.alloc(ent.req.rid, need)
        state = jax.tree_util.tree_map(jnp.asarray, ent.host)
        ent.host = None
        if self.token_paged and "kv" in state and ent.pos > 0:
            nb = -(-ent.pos // self.block_tokens)
            self.arrays = M.pack_kv_blocks(
                self.arrays, state["kv"], ent.block_ids[:nb])
            ent.payload = {k: v for k, v in state.items() if k != "kv"}
        else:
            ent.payload = state
        ent.tier = "hbm"
        self.prefetches += 1

    def spill_lru(self, exclude: Optional[int] = None) -> bool:
        """Spill the least-recently-used HBM-parked session.  Returns
        False when nothing is spillable (all sessions active or
        already on host)."""
        cands = [(ent.last_use, ent.seq, rid)
                 for rid, ent in self.resident.items()
                 if ent.tier == "hbm" and rid != exclude]
        if not cands:
            return False
        self.spill(min(cands)[2])
        return True

    # ---------------------------------------------------------------- #
    def assemble(self, rid: int) -> Tuple[Any, int, int, int]:
        """:meth:`peek`, then release the session's blocks (the
        checkpoint/drain path)."""
        out = self.peek(rid)
        self.release(rid)
        return out

    def peek(self, rid: int) -> Tuple[Any, int, int, int]:
        """Reassemble a parked session's state WITHOUT releasing its
        blocks or changing its tier — the non-destructive read the
        periodic checkpoint store polls (see
        :meth:`SessionManager.snapshot`)."""
        ent = self.resident[rid]
        assert ent.tier != "active", "active sessions export via slots"
        if ent.tier == "host":
            state = jax.tree_util.tree_map(jnp.asarray, ent.host)
        elif self.token_paged and ent.payload is not None \
                and ent.pos > 0 and "kv" not in ent.payload:
            nb = -(-ent.pos // self.block_tokens)
            state = dict(ent.payload)
            state["kv"] = M.gather_kv_blocks(
                self.arrays, ent.block_ids[:nb], ent.pos)
        else:
            state = ent.payload
        return state, ent.last_tok, ent.pos, ent.budget


# ===================================================================== #
# SessionManager: the one session-state API
# ===================================================================== #
def _prefilled(req, first: int, live: bool, state: Any,
               nbytes: int) -> SessionState:
    """The cursor a producer hands on after prefilling ``req``: a live
    session waits for its first token to stream from the consumer; a
    finished one (``done``) has nothing left to decode."""
    return SessionState(
        rid=req.rid, state=state, last_tok=first, pos=len(req.prompt),
        budget=req.max_new_tokens - 1 if live else 0, nbytes=nbytes,
        done=not live, first_token_pending=live,
        priority=getattr(req, "priority", 0))


def _checkpointed(req, state: Any, last_tok: int, pos: int,
                  budget: int) -> SessionState:
    """A resident session mid-decode, packaged to move: it keeps its
    clock (``first_token_pending=False``)."""
    return SessionState(
        rid=req.rid, state=state, last_tok=last_tok, pos=pos,
        budget=budget, nbytes=M.kv_state_bytes(state), done=False,
        first_token_pending=False, priority=getattr(req, "priority", 0))


class SessionManager:
    """``engine.sessions`` — the one surface over prefill handoff,
    streamed shard handoff, live migration, and peer prefetch:

    * ``prefill(req)`` -> :class:`SessionState` and ``stream(req)`` ->
      :class:`KvSlice` shards then the cursor, on a producer;
    * ``restore(req, st)`` and ``receive(req, slices)`` on a consumer;
    * ``checkpoint()`` drains every resident session,
      ``migrate(peer)`` (checkpoint -> peer.restore, loss-free) and
      ``prefetch(rid, peer)`` (pull ONE session off a peer engine — the
      top of the HBM -> host -> peer cache hierarchy);
    * the fault-tolerance pair ``snapshot()`` (non-destructive periodic
      checkpoint read) / ``crash()`` (lose everything, state
      unexported).
    """

    def __init__(self, engine):
        self.eng = engine

    # ---------------------------------------------------------------- #
    # Producer side: prefill on this engine, state leaves it
    # ---------------------------------------------------------------- #
    def prefill(self, req, now: Optional[float] = None) -> SessionState:
        """Run ``req``'s prompt in a private batch-1 cache (no decode
        slot consumed) and package the resulting state + cursor.  A
        request that finishes AT prefill is finalized here and returns
        ``done=True`` with no state.  TTFT is NOT stamped for live
        sessions — it belongs to the engine that streams the first
        token (``restore`` with ``first_token_pending=True``)."""
        eng = self.eng
        assert len(req.prompt) < eng.max_len, "prompt exceeds max_len"
        logits, cache1 = eng._prefill_one(req)
        first, live = eng._first_token(req, logits, now)
        if not live:        # done at prefill: nothing to hand off
            return _prefilled(req, first, False, None, 0)
        state = M.export_kv(eng.cfg, cache1, 0, len(req.prompt))
        return _prefilled(req, first, True, state,
                          M.kv_state_bytes(state))

    def stream(self, req, now: Optional[float] = None,
               chunk_size: Optional[int] = None,
               checksum: bool = False,
               klass: int = 0) -> Iterator[Any]:
        """Pipelined :meth:`prefill`: yield :class:`KvSlice` shards
        the moment each (layer, chunk) is computed, then the
        :class:`SessionState` cursor as the FINAL item (its ``nbytes``
        is the total shard bytes already streamed; ``state`` is None).
        Consuming the generator drives the producer's prefill chunks,
        so a :meth:`receive` on a peer overlaps transfer with the
        remaining prefill compute.  ``checksum=True`` stamps each
        shard with :func:`kv_checksum` (a host-side read per shard —
        off by default; the chaos-injection path turns it on) so the
        receiver can detect in-flight corruption.  ``klass`` stamps
        every shard with a fabric priority class (0 = URGENT decode-
        blocking, 1 = BULK background; see serving.fabric) so a
        transfer scheduler between producer and consumer can order
        competing streams."""
        eng = self.eng
        assert len(req.prompt) < eng.max_len, "prompt exceeds max_len"
        plen = len(req.prompt)
        C = chunk_size or eng.prefill_chunk or plen
        sent = 0

        def shard_item(key, layer, t0=None, t1=None):
            shard = M.export_kv_shard(eng.cfg, cache1, 0, key, layer,
                                      t0, t1)
            return KvSlice(rid=req.rid, component=key, layer=layer,
                           t0=t0, t1=t1, state=shard,
                           nbytes=M.kv_state_bytes(shard),
                           checksum=(kv_checksum(shard) if checksum
                                     else None),
                           klass=klass)

        if (eng._prefill_custom is None
                and eng.cfg.sliding_window is None and C < plen):
            cache1 = M.init_cache(eng.cfg, 1, eng.max_len)
            toks = np.asarray(req.prompt, np.int32).reshape(1, plen)
            n_kv = M.cache_layer_counts(cache1).get("kv", 0)
            logits = None
            for t0, t1, logits, cache1 in M.iter_prefill_chunks(
                    eng.params, eng.cfg, toks, cache1, chunk_size=C,
                    prefill_call=eng._chunk_call):
                # this chunk's K/V planes are final for every layer
                # the moment the chunk completes: stream them now
                for layer in range(n_kv):
                    item = shard_item("kv", layer, t0, t1)
                    sent += item.nbytes
                    yield item
            stream_kv_tail = False
        else:
            # serial fallback (ring-buffer SWA / injected prefill /
            # single-chunk prompt): the batch-1 prefill of prefill()
            logits, cache1 = eng._prefill_one(req)
            stream_kv_tail = True

        for key, n_layers in M.cache_layer_counts(cache1).items():
            if key == "kv" and not stream_kv_tail:
                continue        # already streamed per chunk above
            for layer in range(n_layers):
                if key == "kv" and eng.cfg.sliding_window is None:
                    item = shard_item(key, layer, 0, plen)
                else:           # recurrent state / whole SWA ring
                    item = shard_item(key, layer)
                sent += item.nbytes
                yield item

        # a session done at prefill is finalized here, on the producer
        first, live = eng._first_token(req, logits, now)
        yield _prefilled(req, first, live, None, sent)

    # ---------------------------------------------------------------- #
    # Consumer side: state lands on this engine, decode continues
    # ---------------------------------------------------------------- #
    def restore(self, req, st: SessionState,
                now: Optional[float] = None) -> bool:
        """Install a session's state and continue decoding here.
        Stamps TTFT iff ``st.first_token_pending`` (handoff admission:
        the first token streams only once the state lands); a migrated
        session keeps its original clock.  Returns False when no slot
        is free or (paged engines) the pool cannot fit the session
        even after spilling — retry after draining.  A session that
        finished at prefill has nothing to decode and is refused."""
        eng = self.eng
        if st.done:
            raise ValueError(f"request {st.rid} finished at prefill; "
                             "there is no decode to admit")
        assert st.pos < eng.max_len, \
            "imported state exceeds this engine's max_len"
        eng.sync(now)
        free = [s for s in range(eng.slots) if eng.active[s] is None]
        if not free:
            return False
        slot = free[0]
        if eng._paged is not None and not eng._paged.holds(st.rid):
            cap = min(st.pos + st.budget + 1, eng.max_len)
            if not eng._paged.reserve(req, cap, spill=eng.spill):
                return False
        eng.cache = M.import_kv(eng.cfg, eng.cache, slot, st.state)
        if st.first_token_pending:
            req.ttft = eng._now(now)
        eng._install(slot, req, st.pos, st.last_tok, st.budget)
        eng._recompute_remaining()
        return True

    def receive(self, req, slices,
                now: Optional[float] = None) -> bool:
        """Consume a :meth:`stream`: reserve a slot, install every
        :class:`KvSlice` eagerly as it arrives, and start decoding the
        moment the final :class:`SessionState` lands.  TTFT is stamped
        at that moment.  Returns False — without consuming anything —
        when no slot (or, paged, no pool room) is free.  A checksummed
        shard that fails :meth:`KvSlice.verify` raises
        :class:`ShardChecksumError` AFTER the reserved slot/blocks are
        rolled back, so the caller can re-prefill locally instead."""
        eng = self.eng
        assert len(req.prompt) < eng.max_len, \
            "handoff prompt exceeds this engine's max_len"
        eng.sync(now)
        free = [s for s in range(eng.slots) if eng.active[s] is None]
        if not free:
            return False
        slot = free[0]
        reserved = False
        if eng._paged is not None and not eng._paged.holds(req.rid):
            cap = min(len(req.prompt) + req.max_new_tokens,
                      eng.max_len)
            if not eng._paged.reserve(req, cap, spill=eng.spill):
                return False
            reserved = True
        # host-side reservation only: active_mask stays False, so the
        # decode loop masks the slot until the cursor activates it
        eng.active[slot] = req
        header: Optional[SessionState] = None
        # same-window attention-KV shards coalesce into ONE cache
        # update per chunk; stale leftovers in a released slot are
        # harmless — causal masking hides them and the next admission
        # overwrites them
        pend: List = []
        pend_win = None

        def flush():
            nonlocal pend, pend_win
            if pend:
                eng.cache = M.import_kv_window(
                    eng.cfg, eng.cache, slot, pend[0][0],
                    [s for _, s in pend], pend_win[0])
                pend, pend_win = [], None

        try:
            for item in slices:
                if isinstance(item, SessionState):
                    header = item
                    break
                if not item.verify():
                    raise ShardChecksumError(
                        f"rid {item.rid}: shard {item.component}/"
                        f"{item.layer} arrived corrupted")
                win = (item.t0 or 0, item.t1)
                if (item.component == "kv"
                        and eng.cfg.sliding_window is None):
                    if pend and (pend_win != win or
                                 item.layer != pend[0][0] + len(pend)):
                        flush()
                    pend.append((item.layer, item.state))
                    pend_win = pend_win or win
                    continue
                flush()
                eng.cache = M.import_kv_shard(
                    eng.cfg, eng.cache, slot, item.component,
                    item.layer, item.state, win[0])
            flush()
            assert header is not None, \
                "handoff stream ended without header"
        except BaseException:
            eng.active[slot] = None    # release the reserved slot
            if reserved:
                eng._paged.release(req.rid)
            raise
        if header.done:             # finished at prefill: free the slot
            eng.active[slot] = None
            if reserved:
                eng._paged.release(req.rid)
            return True
        assert header.pos < eng.max_len, \
            "imported state exceeds this engine's max_len"
        req.ttft = eng._now(now)
        eng._install(slot, req, header.pos, header.last_tok,
                     header.budget)
        eng._recompute_remaining()
        return True

    # ---------------------------------------------------------------- #
    # Whole-engine drain / migration / peer prefetch
    # ---------------------------------------------------------------- #
    def _slot_cursors(self) -> Tuple[np.ndarray, ...]:
        """Host copies of every slot's (pos, last_tok, budget)."""
        eng = self.eng
        return tuple(np.asarray(a) for a in
                     (eng.pos, eng.last_tok, eng.budget))

    def _from_slot(self, slot: int, cursors) -> SessionState:
        """The session live in ``slot``, exported at its cursor."""
        eng = self.eng
        pos, last, budget = (int(a[slot]) for a in cursors)
        state = M.export_kv(eng.cfg, eng.cache, slot, pos)
        return _checkpointed(eng.active[slot], state, last, pos, budget)

    def _free_slot(self, slot: int) -> None:
        """Empty ``slot`` and release its session's pool blocks."""
        eng = self.eng
        rid = eng.active[slot].rid
        eng.active[slot] = None
        eng.active_mask = eng.active_mask.at[slot].set(False)
        if eng._paged is not None:
            eng._paged.release(rid)

    def _residents(self, drain: bool) -> List[Tuple[Any, SessionState]]:
        """Every resident session as (request, SessionState): live
        slots first, then parked / spilled pool residents.  ``drain``
        frees each slot and its blocks once read (:meth:`checkpoint`);
        without it nothing changes (:meth:`snapshot`)."""
        eng = self.eng
        out: List[Tuple[Any, SessionState]] = []
        if any(r is not None for r in eng.active):
            cursors = self._slot_cursors()
            for slot, req in enumerate(eng.active):
                if req is not None:
                    out.append((req, self._from_slot(slot, cursors)))
                    if drain:
                        self._free_slot(slot)
        if eng._paged is not None:
            read = eng._paged.assemble if drain else eng._paged.peek
            for rid in eng._paged.parked():
                preq = eng._paged.resident[rid].req
                out.append((preq, _checkpointed(preq, *read(rid))))
        return out

    def checkpoint(self, now: Optional[float] = None
                   ) -> List[Tuple[Any, SessionState]]:
        """Drain this engine loss-free: settle the buffered window,
        package every resident session — active slots AND parked /
        spilled pool residents — as (request, SessionState) with the
        decode cursor, and free all slots and blocks.  Sessions keep
        their clocks (``first_token_pending=False``)."""
        self.eng.sync(now)
        out = self._residents(drain=True)
        self.eng._recompute_remaining()
        return out

    def snapshot(self, now: Optional[float] = None
                 ) -> List[Tuple[Any, SessionState]]:
        """Non-destructive :meth:`checkpoint`: settle the buffered
        window and package every resident session — active slots AND
        parked / spilled pool residents — WITHOUT freeing anything;
        decode continues untouched.  The periodic host-side
        ``CheckpointStore`` (serving/faults.py) polls this.  Exported
        states are copies (``export_kv`` / :meth:`PagedKvCache.peek`
        slice fresh arrays), so later decode steps do not mutate a
        taken snapshot."""
        self.eng.sync(now)
        return self._residents(drain=False)

    def crash(self, now: Optional[float] = None) -> List[Any]:
        """Hard-kill this engine's resident sessions WITHOUT exporting
        state (the fault-injection path): every active slot and every
        pool resident is lost; slots and blocks are freed.  Returns
        the lost requests — the caller decides which can come back
        from a checkpoint store (see ``LaunchedDeployment.inject``)."""
        eng = self.eng
        eng.sync(now)
        lost: List[Any] = []
        for slot in range(eng.slots):
            req = eng.active[slot]
            if req is None:
                continue
            lost.append(req)
            eng.active[slot] = None
            eng.active_mask = eng.active_mask.at[slot].set(False)
        if eng._paged is not None:
            for rid in eng._paged.parked():
                lost.append(eng._paged.resident[rid].req)
            for rid in list(eng._paged.resident):
                eng._paged.release(rid)
        eng._recompute_remaining()
        return lost

    def checkpoint_one(self, rid: int, now: Optional[float] = None
                       ) -> Optional[Tuple[Any, SessionState]]:
        """Checkpoint ONE resident session by rid (active or parked),
        freeing its slot/blocks.  Returns None when this engine does
        not hold it — the probe a peer prefetch uses."""
        eng = self.eng
        eng.sync(now)
        for slot, req in enumerate(eng.active):
            if req is not None and req.rid == rid:
                st = self._from_slot(slot, self._slot_cursors())
                self._free_slot(slot)
                eng._recompute_remaining()
                return req, st
        if eng._paged is not None and eng._paged.holds(rid):
            preq = eng._paged.resident[rid].req
            return preq, _checkpointed(preq, *eng._paged.assemble(rid))
        return None

    def migrate(self, peer, now: Optional[float] = None) -> int:
        """Move every resident session to ``peer`` loss-free
        (checkpoint -> peer restore, clocks preserved).  Sessions the
        peer cannot take are re-imported locally; returns the number
        actually moved."""
        dst = peer.sessions if hasattr(peer, "sessions") else peer
        moved = 0
        for req, st in self.checkpoint(now):
            if dst.restore(req, st, now):
                moved += 1
            else:
                ok = self.restore(req, st, now)
                assert ok, "failed to re-import unmigrated session"
        return moved

    def prefetch(self, rid: int, peer,
                 now: Optional[float] = None) -> bool:
        """Pull ONE session off ``peer`` into this engine — the peer
        tier of the HBM -> host -> peer cache hierarchy.  Returns
        False when the peer does not hold it or this engine cannot
        fit it (the session is returned to the peer)."""
        src = peer.sessions if hasattr(peer, "sessions") else peer
        item = src.checkpoint_one(rid, now)
        if item is None:
            return False
        req, st = item
        if self.restore(req, st, now):
            return True
        back = src.restore(req, st, now)
        assert back, "failed to return prefetched session to peer"
        return False
