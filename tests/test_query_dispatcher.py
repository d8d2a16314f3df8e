"""The coalescing search dispatcher (search/batcher.py) and the query-class
grid the corpora pad to (ops/similarity.py): no linger, bounded programs,
answers equal to the unbatched scan's, nothing lost on a failure.

Steadiness: the tests assert on the order of events, on counts of waits and
dispatches and on JAX's own compile events — never on wall time.
"""

from __future__ import annotations

import math
import sys
import threading
import time

import numpy as np
import pytest

from nornicdb_tpu.ops import similarity
from nornicdb_tpu.ops.similarity import (
    QUERY_CLASS_MIN,
    DeviceCorpus,
    LazyRows,
    pad_query_block,
    query_class,
    query_classes,
)
from nornicdb_tpu.search.batcher import QueryBatcher
from nornicdb_tpu.search.service import SearchConfig, SearchService
from nornicdb_tpu.storage import MemoryEngine, Node

DIMS = 32


def _wait_until(cond, seconds: float = 10.0) -> bool:
    until = time.monotonic() + seconds
    while not cond():
        if time.monotonic() > until:
            return False
        time.sleep(0.002)
    return True


def _echo(queries, k, min_sim):
    """A fake corpus: every query's answer names the query (its first
    component), so a mixed-up fan-out shows."""
    return [[(f"id{int(q[0])}", 1.0)] for q in queries]


class _Gated:
    """A slow fake: the first scan stays in flight until ``release``."""

    def __init__(self, fn=_echo):
        self.fn = fn
        self.sizes: list[int] = []
        self.ks: list[int] = []
        self.release = threading.Event()

    def __call__(self, queries, k, min_sim):
        self.sizes.append(len(queries))
        self.ks.append(k)
        if len(self.sizes) == 1:
            assert self.release.wait(30)
        return self.fn(queries, k, min_sim)


def _run_threads(target, n: int) -> list[threading.Thread]:
    threads = [threading.Thread(target=target, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    return threads


def _join(threads) -> None:
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)


# ----------------------------------------------------------------- no linger
class TestLoneQuery:
    def test_dispatched_at_once_on_its_own_thread_without_a_wait(
            self, monkeypatch):
        events: list = []
        waits: list = []
        real_wait = threading.Condition.wait

        def counting_wait(self, timeout=None):
            waits.append(timeout)
            return real_wait(self, timeout)

        def batch_fn(queries, k, min_sim):
            events.append(("dispatch", threading.get_ident(), len(queries)))
            return _echo(queries, k, min_sim)

        b = QueryBatcher(batch_fn, deadline=0.5)  # a deadline: waits timed
        monkeypatch.setattr(threading.Condition, "wait", counting_wait)
        events.append(("submit", threading.get_ident()))
        out = b.search(np.full(4, 7.0, np.float32), k=1)
        events.append(("answered", threading.get_ident()))
        monkeypatch.undo()
        me = threading.get_ident()
        assert events == [("submit", me), ("dispatch", me, 1),
                          ("answered", me)]
        assert waits == []  # no wait at all, timed or not
        assert out == [("id7", 1.0)]
        assert (b.stats.queries, b.stats.batches) == (1, 1)

    def test_idle_again_after_each_query(self):
        sizes = []
        b = QueryBatcher(lambda q, k, m: sizes.append(len(q)) or _echo(q, k, m))
        for i in range(5):
            assert b.search(np.full(4, float(i), np.float32), 1) == [
                (f"id{i}", 1.0)]
        assert sizes == [1] * 5
        assert not b._in_flight and not b._pending


# ---------------------------------------------------------------- coalescing
class TestCoalescing:
    @pytest.mark.parametrize("n,max_batch", [
        (16, 256), (16, 4), (9, 8), (33, 16), (2, 1)])
    def test_released_together_share_scans(self, n, max_batch):
        fake = _Gated()
        b = QueryBatcher(fake, max_batch=max_batch)
        results: dict = {}

        def one(i):
            results[i] = b.search(np.full(4, float(i), np.float32), k=1)

        threads = _run_threads(one, n)
        # one query is being scanned, the others have all queued behind it
        assert _wait_until(lambda: len(b._pending) == n - 1)
        fake.release.set()
        _join(threads)
        assert fake.sizes[0] == 1 and sum(fake.sizes) == n
        assert len(fake.sizes) <= 1 + math.ceil((n - 1) / max_batch)
        assert max(fake.sizes) <= max_batch
        assert results == {i: [(f"id{i}", 1.0)] for i in range(n)}
        assert b.stats.queries == n
        assert b.stats.batches == len(fake.sizes)
        assert b.stats.max_batch == max(fake.sizes)

    def test_larger_k_waits_for_a_scan_of_its_own(self):
        """A batch runs at its oldest query's k and takes along only
        queries whose k is no larger: a mix never asks for a k that no
        query of the batch brought (and so warmed)."""
        fake = _Gated(lambda q, k, m: [[("a", 0.9), ("b", 0.5), ("c", 0.1)][:k]
                                       for _ in q])
        b = QueryBatcher(fake)
        leader = threading.Thread(
            target=lambda: b.search(np.zeros(4, np.float32), 1))
        leader.start()
        assert _wait_until(lambda: b._in_flight)
        tickets = [b.submit(np.zeros(4, np.float32), k) for k in (2, 1, 3, 2)]
        fake.release.set()
        got = [b.wait(t) for t in tickets]
        _join([leader])
        assert fake.sizes == [1, 3, 1] and fake.ks == [1, 2, 3]
        assert [len(r) for r in got] == [2, 1, 3, 2]

    def test_per_caller_threshold_on_a_shared_batch(self):
        seen = []

        def fn(q, k, min_sim):
            seen.append(min_sim)
            return [[("a", 0.9), ("b", 0.5), ("c", 0.1)][:k] for _ in q]

        fake = _Gated(fn)
        b = QueryBatcher(fake)
        leader = threading.Thread(
            target=lambda: b.search(np.zeros(4, np.float32), 1))
        leader.start()
        assert _wait_until(lambda: b._in_flight)
        t1 = b.submit(np.zeros(4, np.float32), 3, 0.4)
        t2 = b.submit(np.zeros(4, np.float32), 3, 0.05)
        fake.release.set()
        assert b.wait(t1) == [("a", 0.9), ("b", 0.5)]
        assert b.wait(t2) == [("a", 0.9), ("b", 0.5), ("c", 0.1)]
        _join([leader])
        assert seen[1] == 0.05  # the batch's floor is its lowest

    def test_next_scan_is_launched_before_the_last_one_is_published(self):
        """With a deferring corpus the chip does not wait for the host's
        per-query work: whoever reads a scan back launches what queued
        meanwhile before any row of the finished scan is resolved, and the
        new scan is read back on a thread of one of its own callers."""
        events: list = []
        gate = threading.Event()

        def fn(queries, k, min_sim):
            batch = len(events)
            tags = [int(q[0]) for q in queries]
            events.append(("launch", tags))

            def fetch():
                if tags == [0]:
                    assert gate.wait(30)
                events.append(("fetch", tags, threading.get_ident()))
                return lambda i: (events.append(("row", tags[i]))
                                  or [(f"id{tags[i]}", 1.0)])

            return LazyRows(fetch, len(tags), padded_rows=8 - len(tags))

        b = QueryBatcher(fn)
        idents: dict = {}
        results: dict = {}

        def one(i):
            idents[i] = threading.get_ident()
            results[i] = b.search(np.full(4, float(i), np.float32), k=1)

        first = _run_threads(one, 1)
        assert _wait_until(lambda: b._in_flight)
        rest = [threading.Thread(target=one, args=(i,)) for i in (1, 2, 3)]
        for t in rest:
            t.start()
        assert _wait_until(lambda: len(b._pending) == 3)
        gate.set()
        _join(first + rest)
        assert results == {i: [(f"id{i}", 1.0)] for i in range(4)}
        names = [(e[0], e[1]) for e in events]
        second = sorted(names[2][1])
        assert names[:2] == [("launch", [0]), ("fetch", [0])]
        assert names[2][0] == "launch" and second == [1, 2, 3]
        assert names.index(("row", 0)) > 2  # formatted after the launch
        fetch2 = next(e for e in events if e[0] == "fetch" and e[1] != [0])
        assert fetch2[2] in {idents[i] for i in (1, 2, 3)}
        assert b.stats.batches == 2 and b.stats.padded_rows == 7 + 5
        assert not b._in_flight and not b._pending

    def test_stress_every_caller_gets_its_own_answer(self):
        """More threads than cores, a short switch interval: a lost wake-up
        would hang a join, a mixed-up fan-out would show in an answer."""
        b = QueryBatcher(_echo, max_batch=8)
        n_threads, per_thread = 24, 40
        bad: list = []

        def worker(t):
            for j in range(per_thread):
                tag = t * 1000 + j
                out = b.search(np.full(4, float(tag), np.float32), k=1)
                if out != [(f"id{tag}", 1.0)]:
                    bad.append((tag, out))

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            _join(_run_threads(worker, n_threads))
        finally:
            sys.setswitchinterval(old)
        assert bad == []
        assert b.stats.queries == n_threads * per_thread
        assert b.stats.max_batch <= 8
        assert not b._in_flight and not b._pending


# ------------------------------------------------------------------ failures
class TestFailures:
    def test_error_reaches_every_waiter_and_the_next_batch_is_served(self):
        calls = []
        release = threading.Event()

        def fn(queries, k, min_sim):
            calls.append(len(queries))
            if len(calls) == 1:
                assert release.wait(30)
                return _echo(queries, k, min_sim)
            if len(calls) == 2:
                raise RuntimeError("device fell over")
            return _echo(queries, k, min_sim)

        b = QueryBatcher(fn)
        outcomes: dict = {}

        def one(i):
            try:
                outcomes[i] = b.search(np.full(4, float(i), np.float32), 1)
            except RuntimeError as e:
                outcomes[i] = str(e)

        first = _run_threads(one, 1)
        assert _wait_until(lambda: b._in_flight)
        rest = [threading.Thread(target=one, args=(i,)) for i in range(1, 6)]
        for t in rest:
            t.start()
        assert _wait_until(lambda: len(b._pending) == 5)
        release.set()
        _join(first + rest)
        assert outcomes[0] == [("id0", 1.0)]
        assert [outcomes[i] for i in range(1, 6)] == ["device fell over"] * 5
        assert calls == [1, 5]
        # nothing is left in flight: the next query is served
        assert b.search(np.full(4, 9.0, np.float32), 1) == [("id9", 1.0)]
        assert calls == [1, 5, 1]

    def test_withdrawn_tickets_free_a_bounded_queue(self):
        from nornicdb_tpu.errors import ResourceExhausted

        b = QueryBatcher(_echo, max_queue=2)
        tickets = [b.submit(np.zeros(4, np.float32), 1) for _ in range(2)]
        with pytest.raises(ResourceExhausted):
            b.submit(np.zeros(4, np.float32), 1)
        b.withdraw(tickets)
        assert not b._pending
        assert b.search(np.full(4, 3.0, np.float32), 1) == [("id3", 1.0)]


# ------------------------------------------------------------ the class grid
class TestQueryClasses:
    def test_every_batch_size_maps_to_a_class_of_the_grid(self):
        for max_batch in (1, 8, 100, 256):
            grid = query_classes(max_batch)
            assert grid[0] == QUERY_CLASS_MIN
            assert list(grid) == sorted(set(grid))
            for b in range(1, max_batch + 1):
                cls = query_class(b)
                assert cls in grid and cls >= b
                assert cls == QUERY_CLASS_MIN or cls < 2 * b
        assert query_classes(256) == (8, 16, 32, 64, 128, 256)

    def test_padding_appends_zero_rows_only(self):
        q = np.arange(5 * 4, dtype=np.float32).reshape(5, 4)
        out = pad_query_block(q)
        assert out.shape == (8, 4)
        assert (out[:5] == q).all() and not out[5:].any()
        full = np.ones((16, 4), np.float32)
        assert pad_query_block(full) is full

    def test_lazy_rows_resolve_on_index(self):
        asked, fetched = [], []

        def fetch():
            fetched.append(threading.get_ident())
            return lambda i: asked.append(i) or [("x", float(i))]

        rows = LazyRows(fetch, 3, padded_rows=5)
        assert len(rows) == 3 and asked == [] and fetched == []
        assert rows[1] == [("x", 1.0)] and rows[-1] == [("x", 2.0)]
        assert asked == [1, 2] and len(fetched) == 1  # read back once
        assert rows.fetch() is rows and len(fetched) == 1
        assert list(rows) == [[("x", 0.0)], [("x", 1.0)], [("x", 2.0)]]
        with pytest.raises(IndexError):
            rows[3]


# ------------------------------------------------- a small real corpus (CPU)
def _service(n=300, batch_max=32, seed=0):
    eng = MemoryEngine()
    svc = SearchService(eng, dims=DIMS,
                        config=SearchConfig(batch_max=batch_max))
    rng = np.random.default_rng(seed)
    for i in range(n):
        v = rng.normal(size=DIMS).astype(np.float32)
        svc.index_node(Node(id=f"n{i}", labels=["Doc"], embedding=v))
    return svc, rng


class _Compiles:
    """JAX's own compile events, as bench/run.py counts them."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.on = True
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, secs, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class TestRealCorpus:
    def test_no_batch_size_compiles_after_the_first_query_of_a_k(self):
        svc, rng = _service(batch_max=32)
        q = rng.normal(size=(32, DIMS)).astype(np.float32)
        assert len(svc.vector_candidates(q[0], k=7)) == 7  # warms k=7
        compiles = _Compiles()
        try:
            for _ in range(2):
                for b in range(1, 33):
                    rows = svc._batched_corpus_search(q[:b], 7, -1.0)
                    assert len(rows) == b and len(rows[b - 1]) == 7
            assert compiles.count == 0
            # a k not seen before does compile: its whole grid, once
            svc.vector_candidates(q[0], k=9)
            first = compiles.count
            assert first >= 1
            for b in (1, 9, 17, 32):
                svc._batched_corpus_search(q[:b], 9, -1.0)
            assert compiles.count == first
        finally:
            compiles.on = False

    def test_batched_answers_equal_the_unbatched_scan(self):
        svc, rng = _service()
        corpus = svc.corpus()
        n = 12
        q = rng.normal(size=(n, DIMS)).astype(np.float32)
        ks = [3, 5, 10, 5, 3, 10, 1, 5, 10, 3, 5, 10]
        floors = [-1.0, 0.1, -1.0, 0.2, -1.0, 0.1, -1.0, -1.0, 0.3, 0.1,
                  -1.0, 0.2]
        batcher = svc.ensure_batcher()
        gated = _Gated(batcher.search_batch_fn)
        batcher.search_batch_fn = gated
        got: dict = {}

        def one(i):
            got[i] = svc.vector_candidates(q[i], k=ks[i],
                                           min_similarity=floors[i])

        threads = _run_threads(one, n)
        assert _wait_until(lambda: len(batcher._pending) == n - 1)
        gated.release.set()
        _join(threads)
        assert max(gated.sizes) > 1  # some queries did share a scan
        for i in range(n):
            want = corpus.search(q[i], k=ks[i], min_similarity=floors[i])[0]
            assert [id_ for id_, _ in got[i]] == [id_ for id_, _ in want]
            # a GEMM may block a batch differently from a single row
            assert np.allclose([s for _, s in got[i]],
                               [s for _, s in want], atol=1e-6)
            assert len(got[i]) <= ks[i]
            assert all(s >= floors[i] for _, s in got[i])

    def test_padding_rows_are_never_formatted(self, monkeypatch):
        svc, rng = _service()
        q = rng.normal(size=(3, DIMS)).astype(np.float32)
        svc.vector_candidates(q[0], k=5)  # warm: scans of zero queries
        formatted = []
        real = similarity.format_topk_results

        def recording(vals, idx, n_queries, k, min_similarity, ids):
            formatted.append((vals.shape[0], n_queries))
            return real(vals, idx, n_queries, k, min_similarity, ids)

        monkeypatch.setattr(similarity, "format_topk_results", recording)
        batcher = svc.ensure_batcher()
        before = batcher.stats.padded_rows
        rows = svc._batched_corpus_search(q, 5, -1.0)
        assert formatted == []  # deferred: the dispatching thread formats none
        assert len(rows) == 3 and rows.padded_rows == 5
        assert [len(rows[i]) for i in range(3)] == [5, 5, 5]
        assert formatted == [(1, 1)] * 3  # one real row each, no padding
        # through the dispatcher: a block of 3 is scanned as 8 rows
        formatted.clear()
        tickets = [batcher.submit(q[i], 5) for i in range(3)]
        assert [len(batcher.wait(t)) for t in tickets] == [5, 5, 5]
        assert formatted == [(1, 1)] * 3
        assert batcher.stats.padded_rows - before == 5
        # a new k's warm-up scans zero queries and formats none of them
        formatted.clear()
        svc.vector_candidates(q[0], k=6)
        assert formatted == [(1, 1)]

    def test_acknowledged_write_is_found_by_a_query_submitted_after_it(self):
        svc, rng = _service()
        batcher = svc.ensure_batcher()
        probe = rng.normal(size=DIMS).astype(np.float32)
        svc.vector_candidates(probe, k=1)  # warm
        gated = _Gated(batcher.search_batch_fn)
        batcher.search_batch_fn = gated
        leader = threading.Thread(
            target=lambda: svc.vector_candidates(probe, k=1))
        leader.start()
        assert _wait_until(lambda: batcher._in_flight)
        # a scan is in flight; the write is acknowledged, then the query for
        # it is submitted: it is scanned by the NEXT program, whose borrow
        # syncs the write
        fresh = rng.normal(size=DIMS).astype(np.float32)
        svc.index_node(Node(id="fresh", labels=["Doc"], embedding=fresh))
        answer: list = []
        asker = threading.Thread(
            target=lambda: answer.extend(svc.vector_candidates(fresh, k=1)))
        asker.start()
        assert _wait_until(lambda: len(batcher._pending) == 1)
        gated.release.set()
        _join([leader, asker])
        assert answer[0][0] == "fresh" and answer[0][1] > 0.999

    def test_counters_one_per_query_and_one_per_program(self):
        svc, rng = _service()
        q = rng.normal(size=(4, DIMS)).astype(np.float32)
        svc.vector_candidates(q[0], k=4)  # warm
        corpus, batcher = svc.corpus(), svc.ensure_batcher()
        s0 = svc.stats_snapshot()
        d0 = corpus.sync_stats.device_dispatches
        tickets = [batcher.submit(q[i], 4) for i in range(4)]
        for t in tickets:
            batcher.wait(t)
        for i in range(3):
            svc.vector_candidates(q[i], k=4)
        s1 = svc.stats_snapshot()
        assert corpus.sync_stats.device_dispatches - d0 == 4  # 1 + 3
        assert s1["vector_candidates"] - s0["vector_candidates"] == 3
        b0, b1 = s0["batcher"], s1["batcher"]
        assert b1["queries"] - b0["queries"] == 7
        assert b1["batches"] - b0["batches"] == 4
        assert b1["padded_rows"] - b0["padded_rows"] == 4 + 3 * 7
        assert b1["max_batch"] >= 4
        assert b1["queue_wait_seconds"] >= b0["queue_wait_seconds"]

    def test_direct_corpus_search_shares_the_grid(self):
        """Every caller of corpus.search pads to the same classes (the
        embed queue's auto-TLP scan among them): eager answers are those of
        the deferred ones, row for row."""
        corpus = DeviceCorpus(dims=DIMS)
        rng = np.random.default_rng(5)
        vecs = rng.normal(size=(200, DIMS)).astype(np.float32)
        corpus.add_batch([f"v{i}" for i in range(200)], vecs)
        q = rng.normal(size=(11, DIMS)).astype(np.float32)
        eager = corpus.search(q, k=6, min_similarity=0.05)
        lazy = corpus.search(q, k=6, min_similarity=0.05, defer=True)
        assert isinstance(eager, list) and len(eager) == len(lazy) == 11
        assert lazy.padded_rows == 5
        assert [lazy[i] for i in range(11)] == eager
        single = [corpus.search(q[i], k=6, min_similarity=0.05)[0]
                  for i in range(11)]
        for a, b in zip(eager, single):
            assert [i for i, _ in a] == [i for i, _ in b]
            assert np.allclose([s for _, s in a], [s for _, s in b],
                               atol=1e-6)


class TestShardedCorpus:
    def test_deferred_rows_equal_eager_rows_on_a_mesh(self):
        import jax.numpy as jnp

        from nornicdb_tpu.parallel import ShardedCorpus, make_mesh

        sc = ShardedCorpus(dims=16, mesh=make_mesh(), dtype=jnp.float32)
        rng = np.random.default_rng(2)
        vecs = rng.normal(size=(400, 16)).astype(np.float32)
        sc.add_batch([f"s{i}" for i in range(400)], vecs)
        q = rng.normal(size=(5, 16)).astype(np.float32)
        eager = sc.search(q, k=4)
        lazy = sc.search(q, k=4, defer=True)
        assert len(lazy) == 5 and lazy.padded_rows == 3
        assert [lazy[i] for i in range(5)] == eager
        sc.warm_query_classes(4, 16)
        d0 = sc.shard_stats.dispatches
        sc.warm_query_classes(4, 16)  # a set lookup the second time
        assert sc.shard_stats.dispatches == d0
