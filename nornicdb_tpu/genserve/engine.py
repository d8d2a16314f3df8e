"""Continuous-batching generation engine over a paged KV cache.

The one way a decoder is served: every chat, QC and GraphRAG generation
is a submit into this engine, so concurrent requests decode in ONE running
batch and no prompt length compiles a cache shape of its own.  It owns the
generation path end to end:

* **A decoder-family seam.**  The engine serves any decoder whose module
  owns the config class and exposes ``init_pages(cfg, num_pages,
  page_size)``, ``num_pages(pool)`` and ``fused_step(params, cfg, meta,
  pages, *, lmax, w, tq, prev)`` (``models/qwen2.py``: K/V pages;
  ``models/deepseek_v2.py``: latent pages, routed experts), resolved once
  from ``type(cfg)``.  What a page holds is the family's; which pages a
  sequence holds, the row layout of a step (``nornicdb_tpu/ragged.py``) and the
  prefix cache are the scheduler's.
* **Page kinds.**  A family whose layers do not all keep a lane's whole
  history (``models/cohere2_moe.py``: window layers beside full ones) says
  so in ``page_kinds(cfg)``, one ``(name, horizon)`` a kind; its
  ``init_pages`` / ``num_pages`` / ``fused_step`` then take and give a
  tuple, an entry a kind (pools, page counts, table widths).  The scheduler
  keeps a pool, a free list, reference counts, prefix registrations and a
  page table a lane FOR EACH KIND (:class:`_Kind`).  A kind with a horizon
  holds, for a lane, the pages from the one its window still reaches
  onward: when a step moves the window past a page the lane drops its
  reference, and the page returns to that kind's free list unless the
  prefix cache (or another lane) still holds it; a prefix hit hands the
  new lane, a kind, the pages its first query can still see.  Such a
  kind's pool is sized by ``max_seqs x (horizon + prefill_chunk + a page)
  + one cached context``, not ``max_seqs x max_seq_tokens``.  A family
  without ``page_kinds`` has the one kind ``full``, and nothing of its
  step's layout, programs or counters differs from a scheduler that knew
  no kinds.
* **A state kind.**  Layers that keep one fixed block a lane instead of
  rows a token (``models/nemotron_h.py``: a Mamba-2 layer's convolution
  inputs and SSM state) are a kind whose horizon is ``ragged.STATE``: a
  pool of ``state_slots`` SLOTS, the same free list, reference counts and
  prefix registrations, no table.  A lane holds one slot; ``meta`` names,
  a lane, the slot its step reads and the slot it writes, and the step
  copies on write.  A lane seated for a new or re-queued sequence reads
  the null slot (zeros) or a SNAPSHOT: when the chunk lane's step ends on
  a page boundary it writes a fresh slot, registered under that boundary's
  chain key, and goes on from it (every such chunk end, the oldest idle
  snapshot reclaimed first, none taken when no slot is free).  A prefix
  hit is then as long as every page kind can serve AND ends where a
  snapshot stands; with no snapshot there is no hit.
* **Paged KV cache** (Ragged Paged Attention, PAPERS.md).  One pooled
  buffer of fixed-size pages shared by every sequence, with per-sequence
  page tables.  Attention block-gathers each sequence's pages; sequences
  join and leave the running batch at step boundaries by
  allocating/freeing pages — no cache reallocation, no cross-request
  shape coupling.  What a family's step computes is held to a plain
  float32 forward under ``models/reference/`` within a tolerance.
* **One fused ragged step per iteration** (genserve v2).  Each
  scheduler iteration submits a SINGLE device program (the family's
  ``fused_step``) serving every decode lane plus at most one
  prompt-prefill chunk as ragged per-lane metadata —
  no per-phase prefill/decode program split, half the dispatch overhead
  per generated token.  The flat token batch and the chunk width are
  power-of-two bucketed (the ``round_up_pow2`` discipline), so the
  program-class ledger stays bounded at one entry per (F, Tq) bucket
  pair, not one per (prefill, decode) shape combination.
* **One step in flight.**  Step N+1 is planned, packed and dispatched
  while step N runs, and N's ids are read after N+1 is queued on the
  device: a decode row whose token is still in flight names the entry of
  N's id vector that holds it and the step reads it there
  (``nornicdb_tpu/ragged.py``).  The plan needs counts only; ``</s>`` is
  found one step late, and that lane's row in N+1 is an OVERRUN row: its
  token is dropped and its write lands past the sequence's end on a page
  the sequence still owns (pages go back to the pool, and prefix pages
  are published, only once every step with a row of theirs was read).
  What needs a sequence's tokens on the host (eviction with re-prefill,
  a pool shed) reads the step in flight first.
* **Shared-prefix KV caching.**  Full prompt pages are content-hashed
  (a chained digest, so a page's key commits to everything before it)
  and kept resident after their sequence finishes; a new prompt whose
  leading pages hit the cache skips prefilling them entirely and
  attends to the shared physical pages through its own page table.
  Pages are refcounted: eviction and release only free a page when its
  last holder drops it, and cache-resident idle pages are reclaimed LRU
  under pool pressure — a shared page is never freed out from under a
  second sequence.  GraphRAG/HeimdallQC prompts share long
  system/context preambles, so this attacks ttft directly.
* **Admission / eviction on page-pool pressure.**  A bounded queue sheds
  at submit with :class:`ResourceExhausted` (HTTP 429 / gRPC
  RESOURCE_EXHAUSTED / Bolt transient at the edges); a sequence that
  needs a page when the pool is empty evicts the youngest other running
  sequence, which is requeued and re-prefilled from its prompt plus the
  tokens it already produced (greedy decode makes the continuation
  identical — tolerance-tested).
* **Deadline shedding.**  Requests carry a deadline: queued work expired
  before admission is shed, running work is shed at step boundaries,
  and waiting callers give up at deadline + grace — no caller blocks
  indefinitely, even with a hung accelerator.
* **Backend gating** (PR 6).  Every device dispatch is gated through the
  :mod:`nornicdb_tpu.backend` lifecycle manager BEFORE any lock: while
  the backend is degraded the engine re-prefills and decodes on CPU from
  a host parameter mirror (``fallback="cpu"``), or sheds cleanly with
  :class:`DeviceUnavailable` (``fallback="fail"``) — never a wedge.
* **Per-request streaming.**  ``submit`` returns a :class:`GenHandle`
  whose token/text streams deliver each token as the scheduler produces
  it (the Heimdall SSE path rides this).

Thread model: caller threads do admission and block on their handle; the
single scheduler thread owns the page pool, page tables and running set
exclusively, so no lock is ever held across a device op (NL-DEV01) or a
blocking decode (NL-LK02).  The engine lock guards only the queue and
gauges.
"""

from __future__ import annotations

import hashlib
import importlib
import logging
import queue as queue_mod
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Iterator, Optional, Sequence

import numpy as np

from nornicdb_tpu.errors import (
    ClosedError,
    DeviceUnavailable,
    ResourceExhausted,
)
from nornicdb_tpu.genserve import stats as _stats
from nornicdb_tpu.ragged import (
    STATE,
    KindTables,
    first_page,
    pack_ragged_meta,
    pages_for,
    round_up_pow2,
)
from nornicdb_tpu.telemetry import budget as _budget
from nornicdb_tpu.telemetry import costmodel as _costmodel
from nornicdb_tpu.telemetry import deviceprof as _deviceprof
from nornicdb_tpu.telemetry.tracing import tracer as _tracer

logger = logging.getLogger(__name__)

# sequence states (scheduler-owned)
_QUEUED, _PREFILL, _DECODE = "queued", "prefill", "decode"


@dataclass
class GenStats:
    requests: int = 0
    completed: int = 0
    generated_tokens: int = 0
    prefill_chunks: int = 0
    decode_steps: int = 0
    decode_lane_tokens: int = 0  # real (non-padding) lanes stepped
    # prefill-token accounting by pass: first-pass prompt tokens vs
    # tokens RE-prefilled after an eviction/re-platform readmission —
    # kept separate so bench prefill throughput is honest (a thrashing
    # pool re-prefilling the same prompt is not extra useful work)
    prefill_tokens_first: int = 0
    prefill_tokens_re: int = 0
    # shared-prefix cache: pages reused at admission + the prompt
    # tokens those pages made prefill skip
    prefix_hits: int = 0
    prefix_reused_tokens: int = 0
    admissions: int = 0
    readmissions: int = 0
    evictions: int = 0
    sheds_queue_full: int = 0
    sheds_deadline: int = 0
    sheds_pool: int = 0
    sheds_device: int = 0
    sheds_predicted: int = 0
    cancelled: int = 0
    errors: int = 0
    pool_resets: int = 0
    cpu_steps: int = 0
    # one step in flight: steps dispatched while the one before was
    # unread; rows whose token was dropped because their sequence had
    # ended (</s>, a deadline, a cancel) by the time they were read;
    # times the step in flight had to be read before planning (pool
    # pressure); seconds blocked in the device-to-host read of a step's
    # ids (large = the device sets the pace, ~0 = the host does)
    overlapped_steps: int = 0
    overrun_rows: int = 0
    drains: int = 0
    read_wait_seconds: float = 0.0
    # routed experts (a family that has them appends these to its step's
    # one int vector and names them in its STEP_COUNTERS, nornicdb_tpu/
    # ragged.py; others leave 0): top-k assignments that fell on experts
    # held here, rows routed (one per row per expert layer), per expert
    # layer the fullest held expert's rows and the held experts that got
    # any row, summed; and, for a family with zero-compute experts, the
    # top-k choices that fell on those
    expert_assignments: int = 0
    expert_rows_max: int = 0
    experts_hit: int = 0
    routed_rows: int = 0
    zero_assignments: int = 0
    # attention over live lengths (a family whose step walks its lanes'
    # page tables in blocks as far as the longest live lane: models/mla.py,
    # and Qwen's over models/kv_walk.py):
    # cache slots the steps' attention blocks gathered and scored, and the
    # slots the same lanes' whole tables hold
    attn_slots_walked: int = 0
    attn_slots_table: int = 0
    # pages of the run that every live decode lane's table begins with,
    # gathered ONCE a walk for all of them (models/kv_walk.py; ONE layer of
    # the kind without a horizon, summed over steps): over ``decode_steps``
    # it is how much of a step's lanes the prefix cache made one
    shared_run_pages: int = 0
    # page kinds (a family with window layers beside full ones:
    # models/cohere2_moe.py).  From the step's int vector, a pair a kind:
    # the pages its attention blocks gathered and scored, summed over
    # lanes and the kind's layers, and the pages that hold a slot those
    # lanes' live queries may see.  On the host: holds on pages of a kind
    # with a horizon that lanes let go as their windows moved, and those of
    # them that went back to the free list (the rest stay with the prefix
    # cache or another lane)
    full_pages_walked: int = 0
    full_pages_held: int = 0
    window_pages_walked: int = 0
    window_pages_held: int = 0
    window_pages_dropped: int = 0
    window_pages_freed: int = 0
    # the step's pairs summed over the kinds
    attn_pages_walked: int = 0
    attn_pages_held: int = 0
    # a state kind (a family with state-space layers: models/nemotron_h.py).
    # From the step's int vector: rows that advanced a live lane's state,
    # summed over the state layers.  On the host: snapshots of a prefilling
    # lane's state written at chunk ends on a page boundary, admissions
    # that began from one, snapshots reclaimed for a newer one or a lane,
    # and seatings whose step read one slot and wrote another (a copy on
    # write: a lane's first step, and the steps at and behind a snapshot)
    ssm_rows: int = 0
    state_snapshots_taken: int = 0
    state_snapshot_hits: int = 0
    state_snapshots_dropped: int = 0
    state_slots_copied: int = 0
    # the scheduler thread's cycle, one pass of ``_step`` a turn, each the
    # seconds of the ``tracer.stage`` of that name (docs/observability.md
    # "The scheduler's turn"): genserve.turn, and inside it .admit, .plan
    # (up to the dispatch; a drain inside it counts as read and deliver),
    # .dispatch (``jnp.asarray(meta)`` and the step's call until it
    # returns), .read (``read_wait_seconds``, above) and .deliver (what
    # ``_read`` does once the ids are on the host, their device buffer
    # given back included).  What is left of a turn
    # (queued deadlines, the gate) is ``turn_seconds`` less their sum, and
    # busy turns follow each other with nothing between
    turns: int = 0
    turn_seconds: float = 0.0
    admit_seconds: float = 0.0
    plan_seconds: float = 0.0
    dispatch_seconds: float = 0.0
    deliver_seconds: float = 0.0
    # a turn less its blocked read: the host's own work; the scheduler
    # thread's CPU seconds over the turns (``time.thread_time``); and the
    # first less the second: seconds the thread was runnable and did not
    # run (the GIL behind the HTTP threads, or the machine)
    host_turn_seconds: float = 0.0
    turn_cpu_seconds: float = 0.0
    host_offcpu_seconds: float = 0.0
    # dispatches that found the step in flight already finished (the chip
    # had been waiting for the host), and for those the host's seconds
    # since the dispatch before, less any blocked read between
    late_dispatches: int = 0
    late_host_seconds: float = 0.0
    # a token's way out, on the consumer's side of a streamed handle: from
    # ``_deliver`` to the consumer coming back for the next token (the
    # event decoded, serialised and written), folded in when a stream ends
    stream_lag_seconds: float = 0.0
    streamed_tokens: int = 0
    # a request's waits, an interval an admission each: submit (or requeue)
    # to seat, and seat to the admission's first token
    queue_wait_seconds: float = 0.0
    prefill_wait_seconds: float = 0.0

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__dataclass_fields__}


class GenHandle:
    """Caller-side surface of one generation request.

    Tokens accumulate on the handle as the scheduler produces them;
    callers either stream (:meth:`stream_tokens` / :meth:`stream_text`)
    or wait for the full result (:meth:`result` / :meth:`text`).  The
    per-token stream queue (and its thread wakeups) exists only once a
    consumer actually streams — batch consumers (QC, GraphRAG, the
    bench's throughput pass) wait on one completion event and cost the
    scheduler a list append per token, not a wakeup per token.  Every
    wait is bounded by the request deadline plus a grace window — a
    caller never blocks indefinitely on a wedged pipeline.
    """

    _GRACE = 1.0

    def __init__(self, engine: "GenerationEngine", deadline: float):
        self._engine = engine
        self._mu = threading.Lock()
        self._tokens: list[int] = []
        self._stream_q: Optional[queue_mod.Queue] = None
        self._done = threading.Event()
        self.deadline = deadline  # monotonic; 0 = none
        self.error: Optional[Exception] = None
        self.shed = False  # terminal: scheduler must drop this sequence
        # prompt tokens the shared-prefix cache let prefill skip (set at
        # admission; GraphRAG surfaces it in the answer payload)
        self.prefix_reused_tokens = 0

    # -- scheduler side ----------------------------------------------------
    def _deliver(self, tok: int) -> None:
        with self._mu:
            self._tokens.append(tok)
            q = self._stream_q
        if q is not None:
            q.put((tok, time.perf_counter()))

    def _finish(self, error: Optional[Exception] = None) -> None:
        with self._mu:
            if self._done.is_set():
                return
            self.error = error
            self._done.set()
            q = self._stream_q
        if q is not None:
            q.put(None)

    # -- caller side -------------------------------------------------------
    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def tokens(self) -> list[int]:
        with self._mu:
            return list(self._tokens)

    def _time_left(self) -> float:
        if not self.deadline:
            return 1.0
        return min(1.0, max(0.01,
                            self.deadline + self._GRACE - time.monotonic()))

    def _mark_shed(self) -> bool:
        """Atomically transition to shed; True only for the ONE thread
        (caller or scheduler) that made the transition — the shed
        counters increment exactly once per request."""
        with self._mu:
            if self.shed:
                return False
            self.shed = True
            return True

    def _give_up(self) -> Exception:
        """Caller-side deadline expiry: the scheduler sees .shed and
        frees the sequence's pages at the next step boundary."""
        if self._mark_shed():
            self._engine.stats.sheds_deadline += 1
            _stats.SHEDS.labels("deadline").inc()
        self.error = ResourceExhausted(
            "generation deadline exceeded", reason="deadline")
        return self.error

    def stream_tokens(self) -> Iterator[int]:
        """Yield token ids as the scheduler produces them (tokens already
        generated are replayed first).  Raises the request's terminal
        error (shed/closed) when generation failed."""
        with self._mu:
            if self._stream_q is None:
                self._stream_q = queue_mod.Queue()
                now = time.perf_counter()
                for tok in self._tokens:
                    self._stream_q.put((tok, now))
                if self._done.is_set():
                    self._stream_q.put(None)
            q = self._stream_q
        lag, back = 0.0, 0
        try:
            while True:
                try:
                    item = q.get(timeout=self._time_left())
                except queue_mod.Empty:
                    if self._done.is_set():
                        continue  # race: sentinel arriving; loop re-polls
                    if self.deadline and time.monotonic() > (
                            self.deadline + self._GRACE):
                        raise self._give_up()
                    continue
                if item is None:
                    if self.error is not None:
                        raise self.error
                    return
                yield item[0]
                # the consumer is back for its next token: the one before
                # is out (decoded, serialised, written to the socket)
                lag += time.perf_counter() - item[1]
                back += 1
        finally:
            if back:  # once a stream, so sixteen of them lose no update
                stats = self._engine.stats
                with self._engine._lock:
                    stats.stream_lag_seconds += lag
                    stats.streamed_tokens += back

    def stream_text(self) -> Iterator[str]:
        """Decoded text deltas (diffs of the running decode, so any
        tokenizer's spacing rules hold)."""
        tokenizer = self._engine.tokenizer
        if tokenizer is None:
            raise ValueError("engine has no tokenizer; stream tokens instead")
        prev = ""
        out: list[int] = []
        for tok in self.stream_tokens():
            out.append(tok)
            text = tokenizer.decode(out)
            if text != prev:
                yield text[len(prev):]
                prev = text

    def result(self, partial_ok: bool = False) -> list[int]:
        """All generated token ids (bounded wait on the completion event
        — no per-token stream consumption).  With ``partial_ok`` a
        shed/failed request returns what it produced instead of
        raising."""
        while not self._done.wait(timeout=self._time_left()):
            if self.deadline and time.monotonic() > (
                    self.deadline + self._GRACE):
                err = self._give_up()
                if not partial_ok:
                    raise err
                break
        if self._done.is_set() and self.error is not None and not partial_ok:
            raise self.error
        return self.tokens

    def text(self, partial_ok: bool = False) -> str:
        tokenizer = self._engine.tokenizer
        if tokenizer is None:
            raise ValueError("engine has no tokenizer")
        return tokenizer.decode(self.result(partial_ok=partial_ok))


class _Seq:
    """Scheduler-internal state of one admitted-or-queued request."""

    __slots__ = (
        "handle", "prompt", "out", "max_new", "eos_id", "state",
        "prefill_tokens", "prefill_pos", "tables", "bases", "held",
        "cache_len", "admit_no", "src", "row_step", "counted",
        "trace_ctx", "submitted_perf", "since", "prefix_keys", "re_prefill",
        "borrowed",
    )

    def __init__(self, handle: GenHandle, prompt: list[int], max_new: int,
                 eos_id: int):
        self.handle = handle
        self.prompt = prompt
        self.out: list[int] = []
        self.max_new = max_new
        self.eos_id = eos_id
        self.state = _QUEUED
        self.prefill_tokens: list[int] = []
        self.prefill_pos = 0
        # a page kind each: the lane's table, the logical page its column 0
        # stands for, and how many of its columns hold a page.  Of a state
        # kind: the lane's own slot (a table of one), and in ``bases`` the
        # slot its next step READS (the null slot, a snapshot, then what
        # its last step wrote)
        self.tables: Optional[list] = None
        self.bases: list[int] = []
        self.held: list[int] = []
        # (kind, slot): snapshots it holds a reference on until the step
        # that reads them is packed (a hit it begins from; one it just left)
        self.borrowed: list = []
        self.cache_len = 0
        self.admit_no = -1
        # state, prefill_pos and cache_len are the PLAN's: they advance
        # when a step is dispatched, not when it is read.  src: where the
        # next input token is, -1 = out[-1] on the host, else the entry
        # of the unread step's ids; row_step: the last dispatched step
        # that holds a row of this sequence
        self.src = -1
        self.row_step = 0
        self.counted = False
        # the submitting request's trace context: scheduler spans attach
        # to it (prefill/decode, queue-wait, eviction) so a GraphRAG
        # answer shows its full generation path in /admin/traces
        self.trace_ctx = None
        self.submitted_perf = 0.0
        # perf_counter reading its current wait began at: queued (submit,
        # eviction, a re-platform), then seated; 0 once the admission's
        # first token is out
        self.since = 0.0
        # chained page-content keys over this admission's prefill tokens
        # (full pages only); registered into the prefix cache when the
        # final chunk lands
        self.prefix_keys: Optional[list[bytes]] = None
        self.re_prefill = False  # this admission re-prefills prior work

    @property
    def trace_id(self) -> Optional[str]:
        ctx = self.trace_ctx
        return None if ctx is None else ctx.trace_id

    # the FIRST kind's table and the pages in it (a family without page
    # kinds has the one): what a scheduler that knew no kinds kept a lane
    @property
    def page_table(self) -> Optional[np.ndarray]:
        return self.tables[0] if self.tables else None

    @page_table.setter
    def page_table(self, table) -> None:
        self.tables, self.bases = [np.asarray(table, np.int32)], [0]
        self.held = self.held or [len(self.tables[0])]

    @property
    def page_ids(self) -> list[int]:
        return self.tables[0][:self.held[0]].tolist() if self.tables else []

    @page_ids.setter
    def page_ids(self, pids) -> None:
        self.held = [len(pids)]
        if self.tables is None:
            self.page_table = pids


class _Kind:
    """One page kind's allocator state (scheduler-owned, like the pool it
    indexes): the free list, who holds what, and the shared-prefix cache.

      refs    pid -> live holders (sequences sharing it)
      cache   chain-key -> pid, LRU order (oldest first); a cached page
              with refcount 0 stays RESIDENT and reclaimable, it is not
              on the free list
      hash    pid -> chain-key (reverse index for reclaim)
    """

    __slots__ = ("name", "horizon", "state", "width", "usable", "free",
                 "refs", "cache", "hash")

    def __init__(self, name: str, horizon, width: int, usable: int):
        self.name, self.horizon = name, horizon
        # a state kind: slots for pages, a lane holds ONE, a cached slot is
        # a snapshot under the chain key of the boundary it stands at
        self.state = horizon == STATE
        self.width = width      # pages of a lane's table
        self.usable = usable    # pages of the pool, the null page apart
        self.cache: "OrderedDict[bytes, int]" = OrderedDict()
        self.hash: dict[int, bytes] = {}
        self.refs: dict[int, int] = {}
        self.free: list[int] = []
        self.reset()

    def reset(self) -> None:
        """Pool content invalidated (re-platform / failed donated step):
        every cached key now describes bytes that no longer exist, and no
        sequence holds a page of it."""
        self.cache.clear()
        self.hash.clear()
        self.refs.clear()
        self.free = list(range(1, self.usable + 1))

    def alloc(self) -> Optional[int]:
        """One physical page for a new holder: the free list first, then
        the least-recently-used IDLE prefix-cached page (evicting it
        from the cache — a page some sequence still holds is never
        reclaimed).  None means genuine pool pressure."""
        if self.free:
            return self.free.pop()
        victim_key = None
        for key, pid in self.cache.items():  # oldest first
            if self.refs.get(pid, 0) == 0:
                victim_key = key
                break
        if victim_key is None:
            return None
        pid = self.cache.pop(victim_key)
        self.hash.pop(pid, None)
        return pid

    def available(self) -> int:
        """Pages an admission could claim: free + idle prefix-cached."""
        idle = sum(1 for pid in self.cache.values()
                   if self.refs.get(pid, 0) == 0)
        return len(self.free) + idle

    def take(self, pid: int) -> None:
        """One more holder of a page."""
        self.refs[pid] = self.refs.get(pid, 0) + 1

    def let_go(self, pid: int) -> bool:
        """One holder fewer; True where the page went back to the free
        list.  A page still shared with another live sequence stays theirs
        (eviction/finish NEVER frees a page out from under its co-holder);
        a prefix-cached page goes idle-resident (refcount 0), reclaimable
        LRU by :meth:`alloc` under pool pressure."""
        refs = self.refs.get(pid, 1) - 1
        if refs > 0:
            self.refs[pid] = refs
            return False
        self.refs.pop(pid, None)
        if pid in self.hash:
            return False
        self.free.append(pid)
        return True

    def publish(self, key: bytes, pid: int) -> None:
        """Offer a page to the prefix cache under its content key.  Pages
        already cached (the hits an admission reused, or a concurrent
        same-prompt registration) are skipped — first writer wins, the
        loser's page simply stays private."""
        if key in self.cache:
            self.cache.move_to_end(key)
        elif pid not in self.hash:
            self.cache[key] = pid
            self.hash[pid] = key


@dataclass(slots=True)
class _Flight:
    """One dispatched fused step whose ids the host has not read yet."""

    no: int
    ids: object                 # device: Lmax greedy ids [+ routing]; None
    #                             once read (the deliver stage lets it go)
    t0: float                   # perf_counter at dispatch
    waited: float               # stats.read_wait_seconds at dispatch
    late: bool                  # the step before had finished by then
    shape: str
    tq: int
    active: list                # decode rows' sequences, in lane order
    chunk_seq: Optional[_Seq]
    n_valid: int
    final: bool                 # the chunk's last piece: picks a token


class GenerationEngine:
    """Paged continuous-batching decode engine for one decoder, of any
    family (see the module note)."""

    def __init__(self, params, cfg, tokenizer=None, config=None,
                 manager=None):
        if config is None:
            from nornicdb_tpu.genserve import current_config

            config = current_config()
        self.params = params
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.config = config
        self.stats = GenStats()
        # compiled-program ledger: (kind, static shape) per jit entry the
        # engine has dispatched — the bench asserts this stays bounded and
        # that a warmed engine compiles nothing new in its timed pass
        self.programs: set = set()
        self._manager = manager
        # the decoder family: the module that owns the config class
        self._family = importlib.import_module(type(cfg).__module__)
        # the counts its step appends to the greedy ids (GenStats fields)
        self._step_counters: tuple = tuple(
            getattr(self._family, "STEP_COUNTERS", ()))
        self._page_size = max(1, int(config.page_size))
        self._table_width = pages_for(int(config.max_seq_tokens),
                                      self._page_size)
        self._usable_pages = int(config.pool_pages) - 1  # page 0 = null
        if self._usable_pages < self._table_width:
            raise ValueError(
                f"genserve pool_pages={config.pool_pages} cannot hold one "
                f"max_seq_tokens={config.max_seq_tokens} sequence "
                f"({self._table_width} pages needed + the null page)")
        self._prefill_chunk = round_up_pow2(
            max(16, int(config.prefill_chunk)), 16)
        self._max_seqs = max(1, int(config.max_seqs))
        # the family's page kinds (module note); without ``page_kinds`` the
        # one kind ``full`` and the seam's plain form: one pool, one width
        declared = getattr(self._family, "page_kinds", None)
        self._by_kind = declared is not None
        self._kinds: list[_Kind] = []
        for name, horizon in (declared(cfg) if declared else
                              (("full", None),)):
            width, usable = self._table_width, self._usable_pages
            if horizon == STATE:
                # a slot a lane (one more: a sequence that ended keeps its
                # own until its last step is read) and the snapshots
                width = 1
                usable = int(config.state_slots) - 1
                if usable < self._max_seqs + 1:
                    raise ValueError(
                        f"genserve state_slots={usable + 1} cannot seat "
                        f"max_seqs={self._max_seqs} sequences of a decoder "
                        f"with a state kind ({self._max_seqs + 1} slots + "
                        "the null slot, and what the prefix cache is to "
                        "keep as snapshots)")
            elif horizon is not None:
                # what a lane's window, a chunk's queries and the page the
                # window starts in can span; the pool: that for every lane
                # and one cached context
                width = min(width, pages_for(
                    int(horizon) + self._prefill_chunk, self._page_size) + 1)
                usable = min(usable,
                             self._max_seqs * width + self._table_width)
            self._kinds.append(_Kind(name, horizon, width, usable))
        # ``w`` and the pool's page counts as the family's seam takes them
        self._w = tuple(k.width for k in self._kinds) if self._by_kind \
            else self._table_width
        # attention-lane count of the fused ragged step: decode lanes
        # 0..max_seqs-1, the chunk lane, and a reserved dump lane for
        # padding rows — ONE constant per engine, never a program-shape
        # degree of freedom, so no bucketing: every extra lane is real
        # attention work on every step
        self._lmax = self._max_seqs + 2
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: deque[_Seq] = deque()
        self._stop = threading.Event()
        self._started = False
        self._thread: Optional[threading.Thread] = None
        # scheduler-owned (no lock: single owner thread)
        self._running: list[_Seq] = []
        self._pages = None
        self._admit_counter = 0
        # one step in flight: the dispatched, unread step; how many steps
        # were dispatched and which was read last; ended sequences that
        # keep their pages until a step with a row of theirs is read
        self._inflight: Optional[_Flight] = None
        self._step_no = 0
        self._read_no = 0
        self._zombies: list[_Seq] = []
        self._read_at = 0.0  # perf_counter when the last step's ids landed
        self._no_ids: dict = {}  # platform -> the first step's ``prev``
        self._device_kind: Optional[str] = None  # "default" | "cpu"
        self._cpu_params = None
        self._host_params = None
        self._cpu_device = None
        # fleet telemetry: the KV page pool's HBM residency (weakref'd
        # provider, summed at /metrics render — telemetry/deviceprof.py)
        _deviceprof.register_hbm(self, GenerationEngine._hbm_bytes)
        # and what /metrics derives from this engine at scrape time: the
        # turn's phase seconds off ``stats``, the three pool gauges
        _stats.track(self)

    @staticmethod
    def _hbm_bytes(self) -> dict:
        if self._pages is None:
            return {"kv_pages": 0, "kv_prefix": 0}
        # kv_prefix is the prefix-cache-resident SUBSET of the pools (not
        # additive residency): how much of them is pinned shareable.  The
        # first kind's pool is ``kv_pages``, a further kind's
        # ``kv_pages_<kind>``, a state kind's ``state_slots``: the
        # components add up to what is resident
        import jax

        out = {"kv_prefix": 0}
        for kind, pool, pages in zip(self._kinds, self._pools(),
                                     self._page_counts(self._pages)):
            total = sum(int(a.size) * a.dtype.itemsize
                        for a in jax.tree.leaves(pool))
            out["kv_prefix"] += len(kind.cache) * (total // max(1, pages))
            out["state_slots" if kind.state else "kv_pages"
                if kind is self._kinds[0] else f"kv_pages_{kind.name}"] = total
        return out

    # the FIRST kind's allocator under the names it had before there were
    # kinds (a family without ``page_kinds`` has no other)
    _free_pages = property(lambda self: self._kinds[0].free)
    _page_refs = property(lambda self: self._kinds[0].refs)
    _prefix_cache = property(lambda self: self._kinds[0].cache)
    _page_hash = property(lambda self: self._kinds[0].hash)

    def _alloc_page(self) -> Optional[int]:
        return self._kinds[0].alloc()

    def _available_pages(self) -> int:
        return self._kinds[0].available()

    def _pools(self) -> tuple:
        """The pool(s) as a tuple, an entry a kind."""
        return self._pages if self._by_kind else (self._pages,)

    def _page_counts(self, pages) -> tuple:
        counts = self._family.num_pages(pages)
        return counts if self._by_kind else (counts,)

    def _init_pages(self):
        """A fresh pool in the family's form: one array, or one a kind."""
        counts = tuple(k.usable + 1 for k in self._kinds)
        return self._family.init_pages(
            self.cfg, counts if self._by_kind else counts[0],
            self._page_size)

    def _blank_meta(self, f: int):
        """One step's packed rows, every row a padding row and every table
        null: (meta, the five row views, a :class:`KindTables` a kind; a
        family without kinds has no ``base``)."""
        meta, (tokens, lane_id, lane_pos, positions, logit_rows,
               lane_tables) = pack_ragged_meta(self._lmax, self._w, f)
        tokens[:] = 0
        lane_id[:] = self._lmax - 1                  # dump lane default
        lane_pos[:] = 0
        positions[:] = -1                            # -1 = padding row
        logit_rows[:] = 0
        parts = lane_tables if self._by_kind \
            else (KindTables(None, lane_tables),)
        for base, pages in parts:
            pages[:] = 0
            if base is not None:
                base[:] = 0
        return meta, (tokens, lane_id, lane_pos, positions, logit_rows), parts

    def _shape(self, f: int, tq: int) -> str:
        w = "+".join(str(k.width) for k in self._kinds)
        return f"f{f}q{tq}x{w}"

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        with self._lock:
            if self._started:
                return
            self._started = True
        t = threading.Thread(target=self._loop, name="nornicdb-genserve",
                             daemon=True)
        t.start()
        self._thread = t

    def stop(self) -> None:
        """Stop the scheduler; queued and running requests fail fast with
        ClosedError rather than stranding their callers."""
        self._stop.set()
        with self._cond:
            queued = list(self._queue)
            self._queue.clear()
            # the gauge is process-global: a replaced engine must not
            # leave its drained queue's depth behind as phantom backlog
            _stats.QUEUE_DEPTH.set(0)
            self._cond.notify_all()
        for seq in queued:
            self._finish_seq(seq, error=ClosedError("generation engine "
                                                    "stopped"), drop=False)
        if self._thread is not None:
            # the scheduler fails its own running set on exit (it owns
            # those structures); a join timeout means a hung device call —
            # callers stay bounded by their handle deadline + grace
            self._thread.join(timeout=5)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _ragged_classes(self) -> list[tuple[int, int]]:
        """Every (F, Tq) shape class the fused scheduler can dispatch.

        Decode-only steps collapse Tq to 1 with F = pow2(ndec), ndec in
        1..max_seqs.  A step carrying a chunk of bucket Tq=c has
        n_valid in [c/2+1, c] (or [1, 16] for the first bucket) plus
        0..max_seqs-1 decode rows, so the reachable F buckets for that c
        are the CONTIGUOUS pow2 range between those bounds — the warmup
        ladder walks all of them, not just the endpoints, or a mid-range
        step would pay a steady-state compile."""
        classes: list[tuple[int, int]] = []
        f = 8
        while True:
            classes.append((f, 1))
            if f >= round_up_pow2(self._max_seqs, 8):
                break
            f *= 2
        c = 16
        while True:
            # the bucket-edge clamp in _launch can shrink a Tq=c
            # chunk down to exactly c//2 flat rows, so lo starts there
            lo = 1 if c == 16 else c // 2
            hi = c + max(0, self._max_seqs - 1)
            f = round_up_pow2(lo, 8)
            top = round_up_pow2(hi, 8)
            while True:
                classes.append((f, c))
                if f >= top:
                    break
                f *= 2
            if c >= self._prefill_chunk:
                break
            c *= 2
        return classes

    def warmup(self, timeout: float = 60.0) -> None:
        """Compile EVERY program class the configured engine can dispatch
        — each (F, Tq) fused ragged-step bucket pair from
        :meth:`_ragged_classes` — before taking traffic, so no live
        request pays an XLA compile inside its deadline (the soak
        harness and ``cli serve`` call this at boot; benches call it
        before their timed passes and then assert the steady-state
        program set never grows).

        It compiles directly against a THROWAWAY pool on the
        caller thread (the jit cache is shared; the scheduler's pool and
        state are never touched, so warmup is safe while serving), GATED
        through the backend manager first — a wedged accelerator at boot
        degrades warmup to the CPU programs (or skips it under
        ``fallback="fail"``) instead of hanging startup in a raw
        dispatch.  ``timeout`` bounds both the gate and the compile loop
        (checked between compiles; one compile itself is uninterruptible,
        like any jit dispatch)."""
        deadline = time.monotonic() + timeout
        ready = self._mgr().await_ready(timeout)
        if not ready and (self.config.fallback or "cpu") != "cpu":
            return  # degraded + fail policy: requests will shed anyway
        kind = "default" if ready else "cpu"
        import contextlib
        import jax
        import jax.numpy as jnp

        params = self._params_for(kind)
        ctx = (jax.default_device(self._cpu_dev()) if kind == "cpu"
               else contextlib.nullcontext())
        w = self._w
        lmax = self._lmax
        with ctx:
            pool = self._init_pages()
            for f, tq in self._ragged_classes():
                if time.monotonic() >= deadline:
                    break
                meta, (_, lane_id, _, positions, _), parts = \
                    self._blank_meta(f)
                # one real row (writes throwaway page 1) so the compiled
                # program exercises the full scatter/attend path
                lane_id[0] = 0
                positions[0] = 0
                for _, pages in parts:
                    pages[0, 0] = 1
                self.programs.add(("ragged", f, tq, w))
                _deviceprof.record_compile("genserve", "ragged",
                                           self._shape(f, tq))
                # the served variant: ``prev`` is always an array
                ids, _lg, pool = self._family.fused_step(
                    params, self.cfg, jnp.asarray(meta), pool,
                    lmax=lmax, w=w, tq=tq,
                    prev=self._blank_ids(kind))
                np.asarray(ids)  # force execution before serving

    # -- submission --------------------------------------------------------
    def submit(self, prompt_ids: Sequence[int], max_new_tokens: int = 64,
               deadline_ms: Optional[float] = None) -> GenHandle:
        """Enqueue one generation request; returns its streaming handle.

        Sheds with :class:`ResourceExhausted` when the queue is full (an
        empty queue always admits) or the engine is stopped."""
        if self._stop.is_set():
            raise ClosedError("generation engine stopped")
        self.start()
        prompt = [int(t) for t in prompt_ids] or [1]
        # bound to the page table: keep the prompt TAIL (the recency rule
        # heimdall's generators apply to their trained window) and leave
        # room for at least one generated token
        limit = int(self.config.max_seq_tokens)
        if len(prompt) > limit - 1:
            prompt = prompt[-(limit - 1):]
        max_new = max(1, min(int(max_new_tokens), limit - len(prompt)))
        if deadline_ms is None:
            deadline_ms = float(self.config.deadline_ms)
        deadline = (time.monotonic() + deadline_ms / 1000.0
                    if deadline_ms and deadline_ms > 0 else 0.0)
        handle = GenHandle(self, deadline)
        eos = getattr(self.tokenizer, "eos_id", -1) if self.tokenizer else -1
        seq = _Seq(handle, prompt, max_new, eos)
        # the submitting request's trace rides the sequence: scheduler
        # spans (prefill/decode/queue-wait/eviction) attach to it, and
        # the admission decision itself records in the CALLER's trace
        seq.trace_ctx = _tracer.capture()
        seq.submitted_perf = seq.since = time.perf_counter()
        with _tracer.span("genserve.admit",
                          {"prompt_tokens": len(prompt),
                           "max_new": max_new}) as admit_span:
            with self._cond:
                # re-check under the lock stop() drains the queue with: a
                # seq appended after the drain would never be processed
                if self._stop.is_set():
                    raise ClosedError("generation engine stopped")
                if self._queue and len(self._queue) + 1 > int(
                        self.config.max_queue):
                    self.stats.sheds_queue_full += 1
                    _stats.SHEDS.labels("queue_full").inc()
                    _stats.REQUESTS.labels("shed").inc()
                    admit_span.set_attr("outcome", "shed")
                    raise ResourceExhausted(
                        f"generation queue full ({len(self._queue)} "
                        "queued); retry with backoff", reason="queue_full")
                if deadline:
                    # predictive admission: prefill chunks + first decode
                    # step for THIS request, behind every queued request's
                    # same cost (the queue is bounded by max_queue, so
                    # this walk is O(64) worst case under the lock)
                    chunk = self._prefill_chunk
                    own_steps = (len(prompt) + chunk - 1) // chunk + 1
                    backlog = sum(
                        (len(s.prompt) + chunk - 1) // chunk + 1
                        for s in self._queue)
                    # units=None on purpose: a decode step (1 token) costs
                    # roughly a full prefill chunk (both are one forward
                    # pass), so the kind's per-token slope is meaningless
                    # for ragged programs — per-dispatch EWMA x dispatch
                    # count is the honest estimator
                    decision = _costmodel.COST_MODEL.decide(
                        "generate", "genserve", "ragged",
                        units=None,
                        slack_s=deadline_ms / 1000.0,
                        dispatches_ahead=own_steps - 1 + backlog)
                    if not decision.admit:
                        self.stats.sheds_predicted += 1
                        _stats.SHEDS.labels("predicted_deadline").inc()
                        _stats.REQUESTS.labels("shed").inc()
                        admit_span.set_attr("outcome", "shed")
                        raise ResourceExhausted(
                            "predicted time-to-first-token "
                            f"{decision.predicted_s * 1e3:.0f}ms exceeds "
                            f"the {deadline_ms:.0f}ms deadline budget; "
                            "retry with backoff",
                            reason="predicted_deadline")
                    per_step, _conf = _costmodel.predict(
                        "genserve", "ragged")
                    _budget.open_budget(
                        _tracer.current_trace_id(), "generate",
                        deadline_ms / 1000.0,
                        {"prefill": per_step * (own_steps - 1),
                         "decode": per_step})
                self.stats.requests += 1
                self._queue.append(seq)
                admit_span.set_attr("queue_depth", len(self._queue))
                _stats.QUEUE_DEPTH.set(len(self._queue))
                self._cond.notify_all()
        return handle

    def generate(self, prompt_ids: Sequence[int], max_new_tokens: int = 64,
                 deadline_ms: Optional[float] = None) -> list[int]:
        """Synchronous convenience: submit + wait for the full result."""
        return self.submit(prompt_ids, max_new_tokens, deadline_ms).result()

    def generate_text(self, prompt: str, max_new_tokens: int = 64,
                      deadline_ms: Optional[float] = None) -> str:
        if self.tokenizer is None:
            raise ValueError("engine has no tokenizer")
        ids = self.tokenizer.encode(prompt, add_special=False)
        return self.submit(ids, max_new_tokens, deadline_ms).text()

    # -- scheduler ---------------------------------------------------------
    # nornlint: thread-role=scheduler
    def _loop(self) -> None:
        while not self._stop.is_set():
            if not (self._queue or self._running
                    or self._inflight is not None):
                # nothing to do: no turn.  The queue is looked at again
                # under the lock submit() appends under, so no wake-up is
                # lost; a busy loop takes no lock here, and whatever it
                # waits for lies inside the turn, where it is timed
                with self._cond:
                    if not self._queue and not self._stop.is_set():
                        self._cond.wait(0.25)
                continue
            try:
                self._step()
            except Exception as e:  # a broken step must not strand callers:
                # fail everything resident (running AND queued) — new
                # submits retry against a possibly-recovered backend, and
                # nobody waits out a full deadline on a dead step
                if isinstance(e, DeviceUnavailable):
                    logger.warning("genserve step shed: %s", e)
                    self.stats.sheds_device += 1
                    _stats.SHEDS.labels("device").inc()
                else:
                    logger.exception("genserve scheduler step failed")
                # the step in flight chains through the same donated
                # pool: whichever of the two failed, both are lost
                self._drop_inflight()
                for seq in list(self._running):
                    self._finish_seq(seq, error=e)
                # the failing call may have CONSUMED the donated pool
                # (donate_argnums): a poisoned buffer must not survive
                # into the next step, so rebuild from scratch — and the
                # prefix cache indexes CONTENT of the dropped pool, so
                # it must go with it
                self._pages = None
                self._reset_prefix_cache()
                with self._cond:
                    queued = list(self._queue)
                    self._queue.clear()
                    _stats.QUEUE_DEPTH.set(0)
                for seq in queued:
                    self._finish_seq(seq, error=e, drop=False)
        # scheduler exit: fail whatever is still resident so no caller
        # waits out its full deadline on a stopped engine
        for seq in list(self._running):
            self._finish_seq(seq, error=ClosedError(
                "generation engine stopped"))

    def _shed_expired_queued(self) -> None:
        """Drop queued requests whose deadline already passed (under the
        lock; no device work here)."""
        if not self._queue:
            return
        now = time.monotonic()
        keep: deque[_Seq] = deque()
        for seq in self._queue:
            h = seq.handle
            if h.shed:
                self._count_outcome(seq, "shed")
                h._finish(h.error or ResourceExhausted(
                    "generation request cancelled", reason="deadline"))
            elif h.deadline and now > h.deadline:
                if h._mark_shed():
                    self.stats.sheds_deadline += 1
                    _stats.SHEDS.labels("deadline").inc()
                self._count_outcome(seq, "shed")
                h._finish(ResourceExhausted(
                    "generation deadline exceeded before admission",
                    reason="deadline"))
            else:
                keep.append(seq)
        self._queue = keep
        _stats.QUEUE_DEPTH.set(len(self._queue))

    def _count_outcome(self, seq: _Seq, outcome: str) -> None:
        if seq.counted:
            return
        seq.counted = True
        if outcome == "ok":
            self.stats.completed += 1
            if seq.submitted_perf:
                _costmodel.record_latency(
                    "generate", time.perf_counter() - seq.submitted_perf)
        elif outcome == "error":
            self.stats.errors += 1
        _stats.REQUESTS.labels(outcome).inc()

    def _finish_seq(self, seq: _Seq, error: Optional[Exception] = None,
                    drop: bool = True) -> None:
        """Terminal bookkeeping for one sequence (scheduler thread, or
        stop()): free pages, count the outcome, wake the caller."""
        if drop and seq in self._running:
            self._running.remove(seq)
        if seq.row_step > self._read_no:
            # a row of it is in flight (an overrun row, if it ended at
            # </s>): the pages it writes stay its own until that step
            # has been read (_read releases them)
            self._zombies.append(seq)
        else:
            self._release_pages(seq)
        if error is None:
            self._count_outcome(seq, "ok")
        elif isinstance(error, ResourceExhausted):
            self._count_outcome(seq, "shed")
        else:
            self._count_outcome(seq, "error")
        seq.handle._finish(error)

    def _release_pages(self, seq: _Seq) -> None:
        for kind, table, held in zip(self._kinds, seq.tables or (),
                                     seq.held):
            for pid in table[:held].tolist():
                kind.let_go(pid)
        for kind, slot in seq.borrowed:
            kind.let_go(slot)
        seq.tables = None
        seq.bases, seq.held, seq.borrowed = [], [], []
        seq.cache_len = 0
        seq.prefill_pos = 0

    def _reset_prefix_cache(self) -> None:
        """Pool content invalidated (re-platform / failed donated step):
        no sequence holds a page of it any more, so every kind's allocator
        starts over, its free list whole."""
        for kind in self._kinds:
            kind.reset()

    def _prefix_page_keys(self, toks: list[int]) -> list[bytes]:
        """Chained content keys, one per FULL page of ``toks``: key i
        commits to every token in pages 0..i, so matching key i implies
        the whole prefix matches — page-granular prefix matching with
        one dict probe per page."""
        ps = self._page_size
        h = hashlib.sha1(b"nornic-prefix")
        keys: list[bytes] = []
        for i in range(len(toks) // ps):
            h.update(np.asarray(toks[i * ps:(i + 1) * ps],
                                np.int64).tobytes())
            keys.append(h.digest())
        return keys

    def _register_prefix(self, seq: _Seq) -> None:
        """Final prefill chunk landed: publish this sequence's full
        prompt pages into the prefix cache, a kind each (:meth:`_Kind.
        publish`).  Of a kind with a horizon the lane still holds only what
        its window reaches: the pages before were published as the lane
        let them go (:meth:`_slide`)."""
        if seq.prefix_keys is None or seq.tables is None:
            return
        n_full = min(len(seq.prefix_keys),
                     len(seq.prefill_tokens) // self._page_size)
        for kind, table, base, held in zip(self._kinds, seq.tables,
                                           seq.bases, seq.held):
            if kind.state:
                continue  # its snapshots were registered as they were taken
            for idx in range(base, min(n_full, base + held)):
                kind.publish(seq.prefix_keys[idx], int(table[idx - base]))

    def _prefix_hits(self, keys: list[bytes], cap: int) -> int:
        """How many leading pages of a prompt the prefix cache can hand a
        new lane: the longest run ``n <= cap`` of which EVERY kind holds
        what the lane's first query (at ``n x page_size``) can still see: a
        kind without a horizon pages ``0 .. n-1``, one with a horizon the
        pages from the one its window reaches, and a state kind a SNAPSHOT
        of the state at that boundary (under the key of page ``n - 1``):
        where none stands there is no hit."""
        n = min(len(keys), cap)
        for kind in self._kinds:
            if kind.horizon is None:
                n = next((i for i in range(n) if keys[i] not in kind.cache),
                         n)
        shrunk = True
        while n > 0 and shrunk:
            shrunk = False
            for kind in self._kinds:
                if kind.horizon is None:
                    continue
                if kind.state:
                    at = next((i for i in range(n, 0, -1)
                               if keys[i - 1] in kind.cache), 0)
                    n, shrunk = at, shrunk or at != n
                    continue
                lo = first_page(n * self._page_size, kind.horizon,
                                self._page_size)
                gap = next((i for i in range(n - 1, lo - 1, -1)
                            if keys[i] not in kind.cache), None)
                if gap is not None:
                    n, shrunk = gap, True  # no run through a missing page
        return n

    # -- device gating -----------------------------------------------------
    def _mgr(self):
        if self._manager is None:
            from nornicdb_tpu import backend

            self._manager = backend.manager()
        return self._manager

    def _gate(self) -> str:
        """Bounded backend gate BEFORE any device dispatch (no locks held:
        the scheduler thread owns everything it touches here).  Returns
        the platform to serve this step from."""
        mgr = self._mgr()
        if mgr.await_ready():
            return "default"
        if (self.config.fallback or "cpu") != "cpu":
            raise DeviceUnavailable(
                f"backend {mgr.state}; genserve fallback policy is "
                f"{self.config.fallback!r}")
        mgr.note_fallback("generate")
        return "cpu"

    def _active_params(self):
        return self._params_for(self._device_kind)

    def _params_for(self, kind):
        if kind != "cpu":
            return self.params
        if self._cpu_params is None:
            import jax

            if self._host_params is None:
                # host mirror: params committed to a dead accelerator
                # cannot be relocated by jax.default_device (the
                # TPUEmbedder lesson, PR 6)
                self._host_params = jax.tree.map(np.asarray, self.params)
            self._cpu_params = jax.tree.map(
                lambda a: jax.device_put(a, self._cpu_dev()),
                self._host_params)
        return self._cpu_params

    def _cpu_dev(self):
        if self._cpu_device is None:
            import jax

            self._cpu_device = jax.local_devices(backend="cpu")[0]
        return self._cpu_device

    def _platform_ctx(self):
        import contextlib

        if self._device_kind == "cpu":
            import jax

            return jax.default_device(self._cpu_dev())
        return contextlib.nullcontext()

    def _apply_platform(self, kind: str) -> None:
        """Handle a READY<->DEGRADED transition: the pool on the old
        platform is unreachable (or stale), so rebuild it and requeue
        every running sequence for re-prefill from prompt + emitted
        tokens (greedy continuation is identical)."""
        if kind == self._device_kind:
            return
        if self._device_kind is not None:
            self.stats.pool_resets += 1
            logger.warning("genserve: backend platform %s -> %s; "
                           "re-prefilling %d running sequences",
                           self._device_kind, kind, len(self._running))
        self._device_kind = kind
        self._pages = None
        # cached prefix pages lived in the dropped pool: forget them
        self._reset_prefix_cache()
        # the step in flight ran on the old platform (which may never
        # answer): its tokens are not read, the re-prefill below picks
        # the same ones again
        self._drop_inflight()
        requeue = list(self._running)
        self._running = []
        now = time.perf_counter()
        with self._cond:
            for seq in reversed(requeue):
                seq.since = now  # queued again
                seq.tables = None
                seq.bases, seq.held, seq.borrowed = [], [], []
                seq.cache_len = 0
                seq.prefill_pos = 0
                seq.src = -1
                seq.state = _QUEUED
                self._queue.appendleft(seq)
            _stats.QUEUE_DEPTH.set(len(self._queue))

    def _drop_inflight(self) -> None:
        """Forget the step in flight unread (its pool is gone): nothing
        holds pages for it any more."""
        self._inflight = None
        self._read_no = self._step_no
        self._zombies.clear()

    def _blank_ids(self, kind):
        """``prev`` for a step with none before it (call under the
        platform's context): zeros in the shape of the family's int
        vector, so the first step runs the one variant every step runs."""
        blank = self._no_ids.get(kind)
        if blank is None:
            import jax.numpy as jnp

            blank = self._no_ids[kind] = jnp.zeros(
                (self._lmax + len(self._step_counters),), jnp.int32)
        return blank

    def _ensure_pool(self):
        if self._pages is None:
            with self._platform_ctx():
                self._pages = self._init_pages()
        return self._pages

    # -- one scheduler iteration -------------------------------------------
    def _step(self) -> None:
        """One turn of the scheduler thread, timed where it happens: the
        turn and its parts are stages (``GenStats`` says which), so the
        parts tile the turn in the counters, and under a profiler capture
        on the device trace's own clock."""
        stats = self.stats
        waited0 = stats.read_wait_seconds
        seated0, done0 = stats.admissions, stats.completed
        with _tracer.stage("genserve.turn", stats, "turn_seconds") as turn:
            cpu0 = time.thread_time()
            if self._queue:
                with self._cond:
                    self._shed_expired_queued()
            kind = self._gate()
            self._apply_platform(kind)
            if kind == "cpu":
                stats.cpu_steps += 1
            self._ensure_pool()
            with _tracer.stage("genserve.turn.admit", stats, "admit_seconds"):
                self._admit()
            # dispatch N+1, THEN read N: the host's turn (deliver, admit,
            # plan, pack) runs while the device does
            nxt = self._launch()  # reads N itself first where it must
            flight, self._inflight = self._inflight, nxt
            if flight is not None:
                self._read(flight)
            # WHICH turns are the long ones (a capture's annotation)
            turn.set_attr("admitted", stats.admissions - seated0)
            turn.set_attr("finished", stats.completed - done0)
            turn.set_attr("chunk", int(nxt is not None
                                       and nxt.chunk_seq is not None))
            turn.set_attr("lanes", len(nxt.active) if nxt else 0)
            turn.set_attr("late", int(nxt is not None and nxt.late))
            cpu = time.thread_time() - cpu0
        host = turn.seconds - (stats.read_wait_seconds - waited0)
        stats.turns += 1
        stats.host_turn_seconds += host
        stats.turn_cpu_seconds += cpu
        stats.host_offcpu_seconds += host - cpu

    def _drain(self) -> None:
        """Read the step in flight NOW, before the plan goes on: every
        token it picked is on the host when this returns.  Called from
        inside the plan stage, whose counter gives these seconds back:
        they are read and deliver, never both."""
        flight, self._inflight = self._inflight, None
        if flight is not None:
            self.stats.drains += 1
            self.stats.plan_seconds -= self._read(flight)

    def _pool_gauges(self) -> tuple[int, int, int, int]:
        """(resident sequences, pages allocated, pages usable, pages the
        prefix cache indexes), read by ``genserve/stats.py`` at scrape
        time: nothing sets a gauge a step."""
        usable = sum(k.usable for k in self._kinds)
        return (len(self._running),
                usable - sum(len(k.free) for k in self._kinds), usable,
                sum(len(k.cache) for k in self._kinds))

    def _admit(self) -> None:
        ps = self._page_size
        while len(self._running) < self._max_seqs:
            with self._cond:
                if not self._queue:
                    return
                seq = self._queue[0]
                toks = seq.prompt + seq.out
                need = pages_for(len(toks) + 1, ps)
                keys = self._prefix_page_keys(toks)
                # cap reuse below the full prompt: the final chunk must
                # prefill at least one token to produce the first-token
                # logits
                n_hit = self._prefix_hits(keys, (len(toks) - 1) // ps)
                # a kind each: the lane's first logical page, the cached
                # pages it adopts from there, the fresh ones it takes now
                # (as far as its table reaches; :meth:`_reach` goes on)
                plan = []
                for kind in self._kinds:
                    lo = first_page(n_hit * ps, kind.horizon, ps)
                    if kind.state:
                        # the snapshot at the hit's end, and the lane's own
                        hits = [kind.cache[keys[n_hit - 1]]] if n_hit else []
                        fresh = 1
                    else:
                        hits = [kind.cache[keys[i]] for i in range(lo, n_hit)]
                        fresh = max(0, min(need, lo + kind.width) - n_hit)
                    # idle cached hits count as "available" but adopting
                    # them consumes that availability — exclude them
                    # before comparing against the fresh-page requirement
                    idle_hits = sum(1 for pid in hits
                                    if kind.refs.get(pid, 0) == 0)
                    if fresh > kind.available() - idle_hits:
                        return  # pool pressure: wait for a finisher/evictor
                    plan.append((lo, hits, fresh))
                self._queue.popleft()
                _stats.QUEUE_DEPTH.set(len(self._queue))
            if seq.handle.shed:
                self._finish_seq(seq, error=seq.handle.error or
                                 ResourceExhausted("cancelled",
                                                   reason="deadline"),
                                 drop=False)
                continue
            seq.prefill_tokens = toks
            seq.prefill_pos = 0
            seq.cache_len = 0
            seq.state = _PREFILL
            seq.admit_no = self._admit_counter
            self._admit_counter += 1
            seq.prefix_keys = keys
            seq.tables, seq.bases, seq.held = [], [], []
            for kind, (lo, hits, fresh) in zip(self._kinds, plan):
                for pid in hits:
                    # shared pages: take a reference, refresh LRU
                    kind.take(pid)
                    kind.cache.move_to_end(kind.hash[pid])
                pids = hits + [self._alloc(kind)  # availability: above
                               for _ in range(fresh)]
                for pid in pids[len(hits):]:
                    kind.take(pid)
                if kind.state:
                    # its first step reads the snapshot (or the null slot:
                    # zeros, never what the slot's last holder left) and
                    # writes its own slot
                    seq.borrowed += [(kind, pid) for pid in hits]
                    lo, pids = (hits or [0])[0], pids[len(hits):]
                    self.stats.state_snapshot_hits += len(hits)
                table = np.zeros((kind.width,), np.int32)
                table[:len(pids)] = pids
                seq.tables.append(table)
                seq.bases.append(lo)
                seq.held.append(len(pids))
            if n_hit:
                reused = n_hit * ps
                # cached pages already hold these tokens' KV:
                # prefill starts at the novel suffix
                seq.prefill_pos = reused
                seq.cache_len = reused
                seq.handle.prefix_reused_tokens = reused
                self.stats.prefix_hits += n_hit
                self.stats.prefix_reused_tokens += reused
                _stats.PREFIX_HITS.inc(n_hit)
            seq.re_prefill = bool(seq.out)
            if seq.out:
                self.stats.readmissions += 1
            self.stats.admissions += 1
            self._running.append(seq)
            # queue wait (submit, or the requeue, to this seat) lands
            # retroactively in the SUBMITTER's trace (the QueryBatcher
            # pattern — per-caller attribution); from here the sequence
            # waits for its first token
            seated = time.perf_counter()
            _tracer.add_stage(
                "genserve.queue_wait", seq.since, seated, self.stats,
                "queue_wait_seconds", parent=seq.trace_ctx,
                attrs={"readmission": bool(seq.out)})
            seq.since = seated

    def _alloc(self, kind: _Kind) -> Optional[int]:
        """:meth:`_Kind.alloc`, a snapshot it reclaimed counted."""
        cached = len(kind.cache)
        pid = kind.alloc()
        if kind.state:
            self.stats.state_snapshots_dropped += cached - len(kind.cache)
        return pid

    def _snapshot(self, seq: _Seq, end: int) -> dict:
        """The chunk lane's step ends at ``end`` tokens.  On a page boundary
        the state there is worth keeping: a state kind's step then writes a
        FRESH slot, registered at once under that boundary's chain key (the
        device runs the steps in the order they are dispatched, so whoever
        is seated behind it later reads what this step wrote), and the
        lane's next step goes on from it.  Every such chunk end, the oldest
        idle snapshot reclaimed first; none where no slot is free.  Returns
        {kind's index: the slot}."""
        ps, keep = self._page_size, {}
        if end % ps or seq.prefix_keys is None:
            return keep
        key = seq.prefix_keys[end // ps - 1]
        for k, kind in enumerate(self._kinds):
            if not kind.state or key in kind.cache:
                continue
            dropped = self.stats.state_snapshots_dropped
            slot = self._alloc(kind)
            if slot is None:
                continue
            kind.take(slot)  # the lane's, until its next step has read it
            kind.publish(key, slot)
            keep[k] = slot
            self.stats.state_snapshots_taken += 1
            if seq.trace_ctx is not None:
                now = time.perf_counter()
                _tracer.add_span(
                    "genserve.state_snapshot", now, now, parent=seq.trace_ctx,
                    attrs={"tokens": end, "slot": slot, "evicted":
                           self.stats.state_snapshots_dropped > dropped})
        return keep

    def _slide(self, seq: _Seq, first: int) -> None:
        """The lane's next queries stand at ``first`` and after: of each
        kind with a horizon, let go the pages wholly behind the window.  A
        page goes back to the kind's free list unless the prefix cache or
        another lane still holds it (:meth:`_Kind.let_go`); a prompt page
        that a lane still PREFILLING lets go is offered to the prefix
        cache first (its final chunk, which publishes the rest, comes
        after the window has left it)."""
        for k, kind in enumerate(self._kinds):
            if kind.horizon is None or kind.state:
                continue
            gone = first_page(first, kind.horizon, self._page_size) \
                - seq.bases[k]
            if gone <= 0:
                continue
            table, held = seq.tables[k], seq.held[k]
            drop = min(gone, held)
            n_full = 0
            if seq.state == _PREFILL and seq.prefix_keys is not None:
                n_full = min(len(seq.prefix_keys),
                             len(seq.prefill_tokens) // self._page_size)
            freed = 0
            for col, pid in enumerate(table[:drop].tolist()):
                if seq.bases[k] + col < n_full:
                    kind.publish(seq.prefix_keys[seq.bases[k] + col], pid)
                freed += kind.let_go(pid)
            table[:held - drop] = table[drop:held]
            table[held - drop:held] = 0
            seq.bases[k] += gone
            seq.held[k] = held - drop
            self.stats.window_pages_dropped += drop
            self.stats.window_pages_freed += freed
            _stats.PAGES_RELEASED.labels(kind.name).inc(drop)
            if drop and seq.trace_ctx is not None:
                now = time.perf_counter()
                _tracer.add_span(
                    "genserve.pages_released", now, now,
                    parent=seq.trace_ctx,
                    attrs={"kind": kind.name, "pages": drop, "freed": freed})

    def _reach(self, seq: _Seq, first: int, last: int) -> bool:
        """Before a step in which the sequence's rows stand at cache slots
        ``first .. last``: let go what the window of each kind has passed
        (:meth:`_slide`), then ensure a page of every kind for every slot
        up to ``last``.  On an empty free list, evict the youngest OTHER
        running sequence (requeued at the queue head for readmission).
        Returns False only when the sequence had to be shed (cannot happen
        for a lone sequence: its own bound fits the pool by construction)
        or ended at a token read meanwhile."""
        self._slide(seq, first)
        for k, kind in enumerate(self._kinds):
            if kind.state:
                continue  # one slot, taken at admission
            while seq.bases[k] + seq.held[k] <= last // self._page_size:
                pid = kind.alloc()
                if pid is None:
                    if self._inflight is not None:
                        # eviction re-prefills prompt + out and a shed
                        # ends a stream: both need every token on the
                        # host first
                        self._drain()
                        if seq not in self._running:
                            return False  # ended at the token just read
                        continue
                    # an eviction may free ZERO pages (every victim page
                    # shared or cache-resident), so alloc-then-evict
                    # loops: each round removes one victim, so it
                    # terminates
                    victims = [s for s in self._running
                               if s is not seq and s.tables is not None]
                    if not victims:
                        self.stats.sheds_pool += 1
                        _stats.SHEDS.labels("pool_exhausted").inc()
                        self._finish_seq(seq, error=ResourceExhausted(
                            "page pool exhausted", reason="pool_exhausted"))
                        return False
                    victim = max(victims, key=lambda s: s.admit_no)
                    self._evict(victim)
                    continue
                kind.take(pid)
                seq.tables[k][seq.held[k]] = pid
                seq.held[k] += 1
        return True

    def _evict(self, victim: _Seq) -> None:
        self.stats.evictions += 1
        _stats.EVICTIONS.inc()
        # the eviction is an event in the VICTIM's request trace: its
        # caller is still blocked waiting, so the span explains why the
        # answer took a re-prefill
        if victim.trace_ctx is not None:
            now = time.perf_counter()
            _tracer.add_span(
                "genserve.evicted", now, now, parent=victim.trace_ctx,
                attrs={"generated_tokens": len(victim.out)},
            )
        self._running.remove(victim)
        self._release_pages(victim)
        victim.state = _QUEUED
        victim.since = time.perf_counter()  # queued again
        with self._cond:
            self._queue.appendleft(victim)
            _stats.QUEUE_DEPTH.set(len(self._queue))

    # -- the fused ragged step ---------------------------------------------
    def _launch(self) -> Optional[_Flight]:
        """Plan, pack and dispatch ONE device program: every running
        decode lane plus at most one prompt-prefill chunk (the oldest
        admitted sequence still prefilling), as ragged per-lane metadata
        into the family's ``fused_step``.  Long prompts never stall the
        running batch — they ride the same program — and decode lanes
        never pay a separate dispatch while any prompt is prefilling.
        Two stages of the turn: the plan up to the packed rows, then the
        dispatch."""
        import jax.numpy as jnp

        stats = self.stats
        with _tracer.stage("genserve.turn.plan", stats, "plan_seconds"):
            planned = self._plan()
        if planned is None:
            return None
        meta, f, tq, active, chunk_seq, n_valid, final = planned
        ndec, lmax, w = len(active), self._lmax, self._w
        before = self._inflight
        # had the chip already finished the step in flight, it has been
        # waiting for this dispatch
        late = before is not None and before.ids.is_ready()
        with _tracer.stage("genserve.turn.dispatch", stats,
                           "dispatch_seconds") as sent:
            params = self._active_params()
            shape = self._shape(f, tq)
            if ("ragged", f, tq, w) not in self.programs:
                self.programs.add(("ragged", f, tq, w))
                _deviceprof.record_compile("genserve", "ragged", shape)
            with self._platform_ctx():
                try:
                    # greedy argmax runs inside the program and its (Lmax,)
                    # ints feed the next step on the device (``prev``); a
                    # family with routed experts appends its routing counts
                    # to the same vector, so they cost no second read
                    ids, _logits, self._pages = self._family.fused_step(
                        params, self.cfg, jnp.asarray(meta), self._pages,
                        lmax=lmax, w=w, tq=tq,
                        prev=(before.ids if before is not None else
                              self._blank_ids(self._device_kind)))
                except Exception:
                    # the failing dispatch may have CONSUMED the donated
                    # pool (donate_argnums): drop it at the dispatch site
                    # so _ensure_pool rebuilds from scratch, whatever the
                    # caller does (NL-JAX04) — and the prefix cache indexes
                    # the dropped pool's content, so it goes too, with the
                    # step in flight
                    self._pages = None
                    self._reset_prefix_cache()
                    self._drop_inflight()
                    raise
        # the plan moves on at dispatch: counts, never tokens
        self._step_no += 1
        if before is not None:
            stats.overlapped_steps += 1
        if late:
            stats.late_dispatches += 1
            stats.late_host_seconds += (sent.start - before.t0) - (
                stats.read_wait_seconds - before.waited)
        for i, seq in enumerate(active):
            seq.cache_len += 1
            seq.src = i
            seq.row_step = self._step_no
        if chunk_seq is not None:
            chunk_seq.prefill_pos += n_valid
            chunk_seq.cache_len = chunk_seq.prefill_pos
            chunk_seq.row_step = self._step_no
            if final:
                # its first token is entry ndec of this step's ids
                chunk_seq.state = _DECODE
                chunk_seq.src = ndec
        return _Flight(self._step_no, ids, sent.start,
                       stats.read_wait_seconds, late, shape, tq, active,
                       chunk_seq, n_valid, final)

    def _plan(self) -> Optional[tuple]:
        """The next step's rows: (meta, F, Tq, the decode rows' sequences
        in lane order, the chunk's sequence, its valid rows, whether it is
        the prompt's last piece), or None when no lane has work.

        The plan reads counts only, never a token of the step in flight:
        a lane whose last token is unread gets a row that names where the
        device will find it.  A sequence whose tokens, the unread one
        counted, already number ``max_new`` gets no row."""
        active = [s for s in self._running if s.state == _DECODE
                  and len(s.out) + (s.src >= 0) < s.max_new]
        active = [s for s in active if not self._expired(s)]
        # page growth first, for side effects only: a shed or evicted
        # sequence leaves self._running and the re-filter below drops it
        for seq in list(active):
            if seq in self._running:
                self._reach(seq, seq.cache_len, seq.cache_len)
        pre = [s for s in self._running if s.state == _PREFILL]
        chunk_seq = min(pre, key=lambda s: s.admit_no) if pre else None
        if chunk_seq is not None and self._expired(chunk_seq):
            chunk_seq = None
        if chunk_seq is not None:
            # as far as this step's chunk can reach (a kind without a
            # horizon got every page at admission: nothing to do there)
            at = chunk_seq.prefill_pos
            most = min(len(chunk_seq.prefill_tokens) - at,
                       self._prefill_chunk)
            if not self._reach(chunk_seq, at, at + most - 1) \
                    or chunk_seq.state != _PREFILL:
                chunk_seq = None  # shed, or evicted by a lane's growth
        active = [s for s in active if s in self._running
                  and s.state == _DECODE]
        if not active and chunk_seq is None:
            return None
        ndec = len(active)
        if chunk_seq is not None:
            remaining = (len(chunk_seq.prefill_tokens)
                         - chunk_seq.prefill_pos)
            tq = min(self._prefill_chunk,
                     round_up_pow2(remaining, 16))
            n_valid = min(remaining, tq)
            f = round_up_pow2(ndec + n_valid, 8)
            half = f // 2
            if (ndec + n_valid < f and half >= 8
                    and half - ndec >= (n_valid + 1) // 2):
                # decode rows pushed the flat bucket over a pow2 edge:
                # fill the LOWER bucket exactly and leave the chunk tail
                # for the next step — half the GEMM rows for one extra
                # dispatch.  Only when the clamp keeps at least half the
                # chunk: a thinner clamp fragments the tail into
                # near-empty steps, which costs far more than padding.
                n_valid = half - ndec
                f = half
            piece = chunk_seq.prefill_tokens[
                chunk_seq.prefill_pos:chunk_seq.prefill_pos + n_valid]
            final = (chunk_seq.prefill_pos + n_valid
                     >= len(chunk_seq.prefill_tokens))
        else:
            tq, piece, n_valid, final = 1, [], 0, False
            # flat token rows: decode lanes first, then the chunk, then
            # padding up to the pow2 bucket — F scales with REAL tokens
            f = round_up_pow2(ndec, 8)
        keep = self._snapshot(chunk_seq, chunk_seq.prefill_pos + n_valid) \
            if chunk_seq is not None else {}
        lmax = self._lmax
        # ONE packed int32 host array per step (one H2D transfer); the
        # names below are writable views into it.  Logits are projected
        # only for rows that pick a token: the decode rows and the chunk's
        # last valid row (Lmax rows, not F — at real vocabs that is the
        # difference between a (Lmax, V) and an (F, V) vocab GEMM every
        # step)
        meta, (tokens, lane_id, lane_pos, positions, logit_rows), parts = \
            self._blank_meta(f)

        def seat(lane: int, seq: _Seq, keep=()) -> None:
            for k, ((base, pages), table, at) in enumerate(zip(
                    parts, seq.tables, seq.bases)):
                pages[lane] = table
                if base is not None:
                    base[lane] = at
                if self._kinds[k].state:
                    # reads ``at``, writes its own slot or the snapshot's,
                    # and its next step reads what this one writes
                    if k in keep:
                        pages[lane, 0] = keep[k]
                    seq.bases[k] = int(pages[lane, 0])
                    self.stats.state_slots_copied += at != seq.bases[k]
            # what it read is the step's now; the snapshot it leaves is its
            # own until the next step has read it
            for kind, slot in seq.borrowed:
                kind.let_go(slot)
            seq.borrowed = [(self._kinds[k], keep[k]) for k in keep]

        for i, seq in enumerate(active):
            tokens[i] = seq.out[-1] if seq.src < 0 else -(seq.src + 1)
            lane_id[i] = i
            positions[i] = seq.cache_len
            seat(i, seq)
            logit_rows[i] = i
        chunk_lane = lmax - 2  # THE chunk lane, fixed by convention
        for j in range(n_valid):
            fi = ndec + j
            tokens[fi] = piece[j]
            lane_id[fi] = chunk_lane
            lane_pos[fi] = j
            positions[fi] = chunk_seq.prefill_pos + j
        if chunk_seq is not None:
            seat(chunk_lane, chunk_seq, keep)
            logit_rows[ndec] = ndec + n_valid - 1
        return meta, f, tq, active, chunk_seq, n_valid, final

    def _read(self, flight: _Flight) -> float:
        """Bring one dispatched step's ids to the host (the turn's read
        stage: blocked while the device runs), then act on them (its
        deliver stage).  Returns the two stages' seconds."""
        stats = self.stats
        with _tracer.stage("genserve.turn.read", stats,
                           "read_wait_seconds") as wait:
            # (Lmax,) ints cross to host, not the (Lmax, V) logits
            # (~MBs/step at real vocabs) — a bounded 4B-per-row sync, the
            # step's output
            # nornlint: disable=NL-JAX06
            picked = np.asarray(flight.ids).tolist()
        with _tracer.stage("genserve.turn.deliver", stats,
                           "deliver_seconds") as deliver:
            self._deliver_step(flight, picked, wait.start + wait.seconds)
            # the device buffer goes back here, under the stage's clock,
            # not whenever the caller's frame lets go of the flight: giving
            # it back is a call into the runtime (on the chip's host about
            # a millisecond: PERF.md section 5), and it is the host's work
            flight.ids = None
        return wait.seconds + deliver.seconds

    def _deliver_step(self, flight: _Flight, picked: list,
                      t1: float) -> None:
        """One read step's ids, on the host since ``t1``, acted on:
        counters and spans of that step, its tokens delivered, the pages
        of sequences that ended released."""
        lmax = self._lmax
        active, chunk_seq = flight.active, flight.chunk_seq
        ndec, n_valid = len(active), flight.n_valid
        self._read_no = flight.no
        # the step's own interval: from its dispatch, or from when the
        # step before it was read (the device began it then, wherever the
        # device sets the pace), to its ids.  Dispatch to read, which with
        # one step in flight is about two steps less a turn, is what the
        # cost model still learns (record_execute: ROADMAP D7)
        t0 = max(flight.t0, self._read_at)
        self._read_at = t1
        dt = t1 - t0
        routing = dict(zip(self._step_counters, picked[lmax:]))
        for name, count in routing.items():
            setattr(self.stats, name, getattr(self.stats, name) + count)
        if routing:
            # a family's counts are its own: routed experts, a walk, both
            _stats.EXPERT_ASSIGNMENTS.inc(routing.get("expert_assignments", 0))
            if "expert_rows_max" in routing:
                _stats.EXPERT_ROWS_MAX.set(routing["expert_rows_max"])
            _stats.ZERO_EXPERT_ASSIGNMENTS.inc(
                routing.get("zero_assignments", 0))
            _stats.ATTN_SLOTS_WALKED.inc(routing.get("attn_slots_walked", 0))
            _stats.ATTN_SLOTS_TABLE.inc(routing.get("attn_slots_table", 0))
            _stats.SHARED_RUN_PAGES.inc(routing.get("shared_run_pages", 0))
            for kind in self._kinds if self._by_kind else ():
                walked = routing.get(f"{kind.name}_pages_walked", 0)
                held = routing.get(f"{kind.name}_pages_held", 0)
                self.stats.attn_pages_walked += walked
                self.stats.attn_pages_held += held
                _stats.ATTN_PAGES_WALKED.labels(kind.name).inc(walked)
                _stats.ATTN_PAGES_HELD.labels(kind.name).inc(held)
        _deviceprof.record_execute("genserve", "ragged", flight.shape,
                                   t1 - flight.t0)
        # the one dispatch served both phases: observability stays
        # per-phase (retroactive spans in each submitter's trace, the
        # QueryBatcher convention), so dashboards and the trace tests
        # keep their shape across the v1 -> v2 rewire
        if chunk_seq is not None:
            _stats.PREFILL_HIST.observe(dt)
            self.stats.prefill_chunks += 1
            if chunk_seq.re_prefill:
                self.stats.prefill_tokens_re += n_valid
                _stats.PREFILL_TOKENS.labels("re").inc(n_valid)
            else:
                self.stats.prefill_tokens_first += n_valid
                _stats.PREFILL_TOKENS.labels("first").inc(n_valid)
            if chunk_seq.trace_ctx is not None:
                _tracer.add_span(
                    "genserve.prefill", t0, t1,
                    parent=chunk_seq.trace_ctx,
                    attrs={"chunk": flight.tq, "valid": n_valid,
                           "fused_decode_lanes": ndec})
        if active:
            _stats.DECODE_HIST.observe(dt)
            self.stats.decode_steps += 1
            self.stats.decode_lane_tokens += ndec
            leader_ctx = next(
                (s.trace_ctx for s in active if s.trace_ctx is not None),
                None)
            links = sorted({tid for s in active
                            if (tid := s.trace_id) is not None})
            if leader_ctx is not None:
                _tracer.add_span(
                    "genserve.decode", t0, t1, parent=leader_ctx,
                    attrs={"batch": ndec, "links": links})
        takers = list(zip(active, picked))
        if chunk_seq is not None and flight.final:
            # full prompt resident, every slot written by a step that
            # was read: publish its pages for sharing, then the last
            # valid row's logits (logit_rows[ndec]) pick the first token
            self._register_prefix(chunk_seq)
            takers.append((chunk_seq, picked[ndec]))
        for seq, tok in takers:
            if seq.row_step == flight.no:
                seq.src = -1  # no later step holds a row: out[-1] is next
            if seq.handle.done:
                # an overrun row: the sequence ended (</s> found one step
                # late, a deadline, a cancel) after this row was dispatched
                self.stats.overrun_rows += 1
            else:
                self._emit(seq, tok)
        if self._zombies:
            waiting = []
            for seq in self._zombies:
                if seq.row_step > flight.no:
                    waiting.append(seq)
                else:
                    self._release_pages(seq)
            self._zombies = waiting

    def _emit(self, seq: _Seq, tok: int) -> None:
        """Deliver one generated token and end the sequence where it
        ends."""
        seq.out.append(tok)
        if seq.since:  # the admission's first token
            _tracer.add_stage(
                "genserve.prefill_wait", seq.since, time.perf_counter(),
                self.stats, "prefill_wait_seconds", parent=seq.trace_ctx)
            seq.since = 0.0
        self.stats.generated_tokens += 1
        _stats.TOKENS.inc()
        seq.handle._deliver(tok)
        if (tok == seq.eos_id and seq.eos_id >= 0) or \
                len(seq.out) >= seq.max_new:
            self._finish_seq(seq)

    def _expired(self, seq: _Seq) -> bool:
        h = seq.handle
        if h.shed:
            self.stats.cancelled += 1
            self._finish_seq(seq, error=h.error or ResourceExhausted(
                "generation request cancelled", reason="deadline"))
            return True
        if h.deadline and time.monotonic() > h.deadline:
            if h._mark_shed():
                self.stats.sheds_deadline += 1
                _stats.SHEDS.labels("deadline").inc()
            self._finish_seq(seq, error=ResourceExhausted(
                "generation deadline exceeded", reason="deadline"))
            return True
        return False

    # -- observability -----------------------------------------------------
    def stats_snapshot(self) -> dict:
        out = self.stats.as_dict()
        with self._lock:
            out["queue_depth"] = len(self._queue)
        out["running_seqs"] = len(self._running)
        # over every page kind (a family without kinds has the one), then
        # kind by kind
        out["free_pages"] = sum(len(k.free) for k in self._kinds)
        out["prefix_pages"] = sum(len(k.cache) for k in self._kinds)
        out["usable_pages"] = sum(k.usable for k in self._kinds)
        out["page_kinds"] = {
            k.name: {"horizon": k.horizon, "table_width": k.width,
                     "usable_pages": k.usable, "free_pages": len(k.free),
                     "prefix_pages": len(k.cache)} for k in self._kinds}
        out["page_size"] = self._page_size
        out["mode"] = "paged"  # API: the one way a decoder is served
        out["device_kind"] = self._device_kind or "unstarted"
        out["max_seqs"] = self._max_seqs
        # copy first: the scheduler thread adds to the ledger concurrently
        out["programs"] = sorted(str(p) for p in self.programs.copy())
        return out
