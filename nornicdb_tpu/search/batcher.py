"""Coalescing dispatch for vector search: concurrent queries share one
corpus scan.

SURVEY.md §7 hard part (f): "keeping p50 low while the embed worker streams
updates — separate compute streams / program instances for query vs ingest".
On the TPU a scan of the resident corpus costs the same for one query as for
a block of them (the kernel is bound by the corpus read), so the queries that
are waiting anyway go as one (N, D) block.

QueryBatcher is a leader/follower dispatcher with one program in flight and
no linger: a caller that finds no scan in flight dispatches at once, on its
own thread; callers that arrive while a scan is in flight queue, and all of
them (up to ``max_batch``) go together as the next scan.  The thread that
reads a scan back launches the next one before it publishes anything, so
the chip is busy again before a single row of the finished scan is
formatted; the read-back of that next scan is handed to one of its own
callers.  What decides a batch is what the dispatcher observes (a program
in flight, the depth of the queue), never a window.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import numpy as np

from nornicdb_tpu.errors import ResourceExhausted
from nornicdb_tpu.telemetry import budget as _budget
from nornicdb_tpu.telemetry import costmodel as _costmodel
from nornicdb_tpu.telemetry.metrics import REGISTRY as _REGISTRY
from nornicdb_tpu.telemetry.tracing import tracer as _tracer

# queue wait (submit -> the dispatch of its batch) vs device time (the
# batched scan itself): the two halves of a query's latency in the dispatcher
_QUEUE_WAIT_HIST = _REGISTRY.histogram(
    "nornicdb_search_queue_wait_seconds",
    "Time a search waited in the dispatcher for its scan to be launched",
)
_DEVICE_HIST = _REGISTRY.histogram(
    "nornicdb_search_device_seconds",
    "Host-observed dispatch-to-result seconds per search dispatch "
    "(the first call of a shape includes its compile)",
)
# observed coalesced batch sizes: the distribution (not just max/avg) says
# how many queries a scan serves under the arrival pattern
_BATCH_SIZE_HIST = _REGISTRY.histogram(
    "nornicdb_search_batch_size",
    "Queries coalesced per batched device dispatch",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
)
# admission-control sheds (same family the serving engine feeds for the
# embed path; idempotent by-name resolution)
_SHEDS = _REGISTRY.counter(
    "nornicdb_serving_sheds_total",
    "Requests shed by serving admission control",
    labels=("path", "reason"),
)


class _Pending:
    """One submitted query.  Every field but ``query`` / ``k`` /
    ``min_similarity`` is read and written under the batcher's lock."""

    __slots__ = ("query", "k", "min_similarity", "enqueued", "deadline",
                 "cond", "done", "waiting", "rows", "index", "error",
                 "dispatched", "flight")

    def __init__(self, query: np.ndarray, k: int, min_similarity: float,
                 lock: threading.Lock):
        self.query = query
        self.k = k
        self.min_similarity = min_similarity
        self.enqueued = time.perf_counter()
        self.deadline = 0.0  # monotonic; 0 = none
        self.cond = threading.Condition(lock)
        self.done = False
        self.waiting = False  # its owner is blocked in wait(): it can lead
        self.rows: Optional[Sequence[list]] = None  # the batch's answer
        self.index = 0  # this query's row of it
        self.error: Optional[BaseException] = None
        self.dispatched = 0.0  # perf_counter when its scan was launched
        self.flight: Optional[_Flight] = None  # a launched scan to read back


class _Flight:
    """One scan: its queries, and what its launch returned."""

    __slots__ = ("pending", "rows", "error", "launched")

    def __init__(self, pending: list[_Pending]):
        self.pending = pending
        self.rows: Optional[Sequence[list]] = None
        self.error: Optional[BaseException] = None
        self.launched = 0.0  # perf_counter; 0 = not launched yet


@dataclass
class BatcherStats:
    queries: int = 0
    batches: int = 0
    max_batch: int = 0
    # rows of padding the batches' shape classes added (the corpus pads a
    # block of B queries up to query_class(B): those rows are scanned,
    # never formatted or returned)
    padded_rows: int = 0
    queue_wait_seconds: float = 0.0
    sheds_queue_full: int = 0
    sheds_deadline: int = 0
    sheds_predicted: int = 0

    @property
    def avg_batch(self) -> float:
        return self.queries / self.batches if self.batches else 0.0

    def as_dict(self) -> dict:
        """For the server stats/metrics surface: how many queries a scan
        serves, what the shape classes cost in padding, and what a query
        waits for its scan."""
        return {
            "queries": self.queries,
            "batches": self.batches,
            "max_batch": self.max_batch,
            "avg_batch": self.avg_batch,
            "padded_rows": self.padded_rows,
            "queue_wait_seconds": self.queue_wait_seconds,
            "sheds_queue_full": self.sheds_queue_full,
            "sheds_deadline": self.sheds_deadline,
            "sheds_predicted": self.sheds_predicted,
        }


class QueryBatcher:
    """Coalesce concurrent search calls into one device dispatch.

    ``search_batch_fn(queries (N, D), k, min_similarity)`` returns one row of
    ``[(id, score)]`` per query: a list, or the lazy sequence of
    ``DeviceCorpus.search(..., defer=True)``, which returns once the program
    is launched, reads the result back on ``fetch()`` and resolves a row
    when it is indexed.  With the lazy kind each caller formats its own row
    on its own thread, and the next scan is launched before the previous
    one's rows are published.

    One program is in flight at a time.  The caller that finds none in
    flight leads: it takes everything queued (its own query included, up to
    ``max_batch``), launches and reads back on its own thread.  Whoever
    reads a scan back then takes what queued meanwhile, launches it, and
    only then publishes the finished scan and hands the new one's read-back
    to one of its callers.  A lone query so never waits for company and
    never changes thread; under load the batch size follows (dispatch time
    x arrival rate) and the chip does not wait for the host's per-query
    work.

    A batch runs at the ``k`` of its oldest query and takes along the queued
    queries whose ``k`` is no larger; a larger ``k`` waits for a scan of its
    own.  An answer may say how many padding rows its shape class added
    (``padded_rows``, accounting only)."""

    def __init__(
        self,
        search_batch_fn: Callable[[np.ndarray, int, float], Sequence[list]],
        max_batch: int = 256,
        max_queue: int = 0,
        deadline: float = 0.0,
        cost_kind: str = "dense",
    ):
        self.search_batch_fn = search_batch_fn
        self.max_batch = max(1, max_batch)
        # deviceprof kind the predictive-admission check prices a batch
        # dispatch against ("dense" covers the single-device corpus; a
        # sharded deployment can pass its own kind)
        self.cost_kind = cost_kind
        # admission control (ROADMAP item 3): pending queries beyond
        # max_queue shed at submit instead of growing an unbounded list
        # (0 = unbounded); queries older than `deadline` seconds at dispatch
        # are shed rather than served stale (0 disables)
        self.max_queue = max_queue
        self.deadline = deadline
        self.stats = BatcherStats()
        self._lock = threading.Lock()
        self._pending: list[_Pending] = []
        self._in_flight = False

    def submit(
        self, query: np.ndarray, k: int, min_similarity: float = -1.0
    ) -> _Pending:
        """Enqueue one query without blocking — the cross-process device
        broker (server/broker.py) submits a whole worker batch this way,
        then waits on every ticket, so queries from ALL workers coalesce
        into the same fused device dispatch. Raises ResourceExhausted at
        admission when the queue is full."""
        p = _Pending(np.asarray(query, np.float32).reshape(-1), k,
                     min_similarity, self._lock)
        if self.deadline > 0:
            p.deadline = time.monotonic() + self.deadline
        with self._lock:
            if self.max_queue > 0 and len(self._pending) >= self.max_queue:
                self.stats.sheds_queue_full += 1
                _SHEDS.labels("search", "queue_full").inc()
                raise ResourceExhausted(
                    f"search batch queue full ({len(self._pending)} "
                    "pending); retry with backoff", reason="queue_full",
                )
            if p.deadline:
                # predictive admission: queries ahead mostly coalesce into
                # the same dispatch, so the wait is the batches that must
                # run before ours plus our own fused dispatch
                batches_ahead = len(self._pending) // self.max_batch
                decision = _costmodel.COST_MODEL.decide(
                    "search", "search", self.cost_kind, units=None,
                    slack_s=self.deadline,
                    dispatches_ahead=float(batches_ahead),
                )
                if not decision.admit:
                    self.stats.sheds_predicted += 1
                    _SHEDS.labels("search", "predicted_deadline").inc()
                    raise ResourceExhausted(
                        "predicted search completion "
                        f"{decision.predicted_s * 1e3:.0f}ms exceeds the "
                        f"{self.deadline * 1e3:.0f}ms deadline budget; "
                        "retry with backoff", reason="predicted_deadline",
                    )
                _budget.open_budget(
                    _tracer.current_trace_id(), "search", self.deadline,
                    {"device_sync": decision.predicted_s},
                )
            self._pending.append(p)
        return p

    def wait(self, p: _Pending) -> list:
        """The other half of search(): dispatch the queue if no scan is in
        flight, else block until a leader has served this ticket (or handed
        this caller the lead).  Deadline-carrying tickets give up at
        deadline+grace: the dispatch path is time-bounded (the backend
        manager degrades a hung device within its acquire timeout), so a
        search can never wedge its caller indefinitely."""
        while True:
            with self._lock:
                if p.done:
                    break
                flight = p.flight  # a launched scan handed to this caller
                p.flight = None
                if flight is None and not self._in_flight and self._pending:
                    flight = _Flight(self._take_batch())
                    self._in_flight = True
                if flight is None:
                    p.waiting = True
                    timeout = None
                    if p.deadline:
                        timeout = max(
                            0.05, p.deadline - time.monotonic()) + 1.0
                    woken = p.cond.wait(timeout)
                    p.waiting = False
                    if not woken and not p.done and p.flight is None:
                        # withdrawn: it is not scanned for nobody, and a
                        # hand-off that raced this timeout is passed on
                        self._withdraw(p)
                        self.stats.sheds_deadline += 1
                        _SHEDS.labels("search", "deadline").inc()
                        raise ResourceExhausted(
                            "search deadline exceeded", reason="deadline"
                        )
                    continue
            self._drive(flight)
        if p.error is not None:
            raise p.error
        # one timing: counter, histogram, the caller's own trace (this is
        # the caller's thread, so no context crosses a hop)
        _tracer.add_stage("search.queue_wait", p.enqueued, p.dispatched,
                          self.stats, "queue_wait_seconds")
        _QUEUE_WAIT_HIST.observe(p.dispatched - p.enqueued)
        # this caller's row, resolved here (a deferred answer formats it on
        # this thread), with its own k / min_similarity on the shared batch
        row = [
            (i, s) for i, s in p.rows[p.index] if s >= p.min_similarity
        ][: p.k]
        _costmodel.record_latency(
            "search", time.perf_counter() - p.enqueued)
        return row

    def search(
        self, query: np.ndarray, k: int, min_similarity: float = -1.0
    ) -> list:
        return self.wait(self.submit(query, k, min_similarity))

    def withdraw(self, tickets: list[_Pending]) -> None:
        """Take back tickets whose owner will not wait() for them (a
        submitter that failed part-way): one still queued would be scanned
        for nobody and hold a place in a bounded queue until then."""
        with self._lock:
            for p in tickets:
                self._withdraw(p)

    def _withdraw(self, p: _Pending) -> None:
        if p in self._pending:
            self._pending.remove(p)
        if not self._in_flight:
            self._hand_off()

    def _take_batch(self) -> list[_Pending]:
        """The next scan's queries (lock held): the oldest one and, in
        order, every queued query whose k is no larger than its k, up to
        max_batch.  A larger k stays queued: it leads a scan at its own k,
        so a mix never asks the corpus for a program no query warmed."""
        k = self._pending[0].k
        batch, rest = [], []
        for p in self._pending:
            if p.k <= k and len(batch) < self.max_batch:
                batch.append(p)
            else:
                rest.append(p)
        self._pending = rest
        return batch

    def _publish(self, batch: list[_Pending], rows, error) -> None:
        """A batch's outcome to its tickets (lock held)."""
        for i, p in enumerate(batch):
            p.rows, p.index, p.error = rows, i, error
            p.done = True
            if p.waiting:
                p.cond.notify()

    def _hand_off(self) -> None:
        """Wake one caller that is blocked in wait() to lead the next scan
        (lock held, nothing in flight).  A queued ticket whose owner has
        not reached wait() yet needs no hand-off: it leads when it does."""
        for p in self._pending:
            if p.waiting:
                p.cond.notify()
                return

    def _launch(self, flight: _Flight) -> None:
        """Start ``flight``'s scan (no lock held).  With a deferring corpus
        this returns when the program is enqueued on the device."""
        # deadline shedding at dispatch: work that already expired is
        # answered with ResourceExhausted instead of occupying the batch
        if self.deadline > 0:
            now = time.monotonic()
            shed = [p for p in flight.pending
                    if p.deadline and now > p.deadline]
            if shed:
                flight.pending = [p for p in flight.pending if p not in shed]
                with self._lock:
                    self.stats.sheds_deadline += len(shed)
                    _SHEDS.labels("search", "deadline").inc(len(shed))
                    self._publish(shed, None, ResourceExhausted(
                        "search deadline exceeded before dispatch",
                        reason="deadline"))
        live = flight.pending
        if not live:
            return
        flight.launched = time.perf_counter()
        for p in live:
            p.dispatched = flight.launched
        try:
            flight.rows = self.search_batch_fn(
                np.stack([p.query for p in live]), live[0].k,
                min(p.min_similarity for p in live))
        except Exception as e:  # fanned out when the flight is published
            flight.error = e

    def _read_back(self, flight: _Flight) -> None:
        """Block until ``flight``'s scan is on the host (no lock held)."""
        if not flight.pending:
            return
        try:
            fetch = getattr(flight.rows, "fetch", None)
            if fetch is not None and flight.error is None:
                fetch()
        except Exception as e:
            flight.error = e
        # one timing, launch to result, in the trace of the caller that
        # read the scan back
        _tracer.add_stage("search.vector", flight.launched,
                          time.perf_counter(), _DEVICE_HIST,
                          attrs={"batch_size": len(flight.pending)})
        _BATCH_SIZE_HIST.observe(len(flight.pending))

    def _drive(self, flight: _Flight) -> None:
        """Lead from ``flight`` on: launch it if its predecessor's reader
        has not, read it back, launch what queued meanwhile BEFORE
        publishing, publish, and pass the new scan's read-back to one of
        its callers that is blocked in wait() (or keep it, when none is)."""
        nxt: Optional[_Flight] = None
        try:
            while flight is not None:
                if not flight.launched:
                    self._launch(flight)
                self._read_back(flight)
                with self._lock:
                    if self._pending:
                        nxt = _Flight(self._take_batch())
                if nxt is not None:
                    self._launch(nxt)
                with self._lock:
                    self._settle(flight)
                    flight, nxt = nxt, None
                    if flight is None:
                        self._in_flight = False
                        # tickets that queued during the last lines
                        self._hand_off()
                        return
                    heir = next(
                        (p for p in flight.pending if p.waiting), None)
                    if heir is not None:
                        heir.flight = flight
                        heir.cond.notify()
                        return
        except BaseException:
            # the leader's thread is being torn down: nobody may be left
            # waiting, and the next caller must find the dispatcher idle
            with self._lock:
                for f in (flight, nxt):
                    if f is not None:
                        f.error = f.error or RuntimeError(
                            "search dispatch abandoned")
                        self._settle(f)
                self._in_flight = False
                self._hand_off()
            raise

    def _settle(self, flight: _Flight) -> None:
        """Count and publish a scan that has been read back (lock held)."""
        live = flight.pending
        if not live:
            return
        self.stats.queries += len(live)
        self.stats.batches += 1
        self.stats.max_batch = max(self.stats.max_batch, len(live))
        self.stats.padded_rows += getattr(flight.rows, "padded_rows", 0)
        self._publish(live, flight.rows, flight.error)
