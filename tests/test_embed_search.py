"""Embed pipeline + search service tests (modeled on reference
pkg/embed tests, pkg/nornicdb/embed_queue tests, pkg/search tests)."""

import time

import numpy as np
import pytest

from nornicdb_tpu.embed import (
    CachedEmbedder,
    EmbedWorker,
    EmbedWorkerConfig,
    HashEmbedder,
    average_embeddings,
    build_embedding_text,
    chunk_text,
)
from nornicdb_tpu.search import BM25Index, HNSWIndex, SearchService, fuse_rrf
from nornicdb_tpu.search.fusion import apply_mmr
from nornicdb_tpu.storage import MemoryEngine, Node


class TestHashEmbedder:
    def test_deterministic(self):
        e = HashEmbedder(64)
        np.testing.assert_array_equal(e.embed("hello world"), e.embed("hello world"))

    def test_similarity_structure(self):
        e = HashEmbedder(256)
        a = e.embed("graph database storage engine")
        b = e.embed("graph database storage layer")
        c = e.embed("banana smoothie recipe")
        assert np.dot(a, b) > np.dot(a, c)

    def test_empty(self):
        e = HashEmbedder(16)
        assert np.linalg.norm(e.embed("")) == 0


class TestCachedEmbedder:
    def test_hits(self):
        inner = HashEmbedder(32)
        ce = CachedEmbedder(inner, capacity=10)
        v1 = ce.embed("abc")
        v2 = ce.embed("abc")
        np.testing.assert_array_equal(v1, v2)
        assert ce.hits == 1 and ce.misses == 1

    def test_eviction(self):
        ce = CachedEmbedder(HashEmbedder(8), capacity=2)
        for t in ["a", "b", "c"]:
            ce.embed(t)
        ce.embed("a")  # evicted -> miss
        assert ce.misses == 4


class TestChunking:
    def test_short_text_single_chunk(self):
        assert chunk_text("one two three", 512, 50) == ["one two three"]

    def test_chunking_with_overlap(self):
        words = " ".join(f"w{i}" for i in range(1000))
        chunks = chunk_text(words, 100, 10)
        assert all(len(c.split()) <= 100 for c in chunks)
        # overlap: chunk i+1 starts 90 words after chunk i
        assert chunks[0].split()[90] == chunks[1].split()[0]
        # every word covered
        covered = set(w for c in chunks for w in c.split())
        assert len(covered) == 1000

    def test_empty(self):
        assert chunk_text("   ", 10, 2) == []

    def test_average_normalized(self):
        v = average_embeddings([np.array([1, 0], np.float32), np.array([0, 1], np.float32)])
        assert np.linalg.norm(v) == pytest.approx(1.0)

    def test_build_embedding_text_priority(self):
        n = Node(properties={"name": "X", "content": "main text", "other": "ignored"})
        text = build_embedding_text(n)
        assert "main text" in text and "X" in text and "ignored" not in text


class TestEmbedWorker:
    def _setup(self, **cfg):
        eng = MemoryEngine()
        emb = HashEmbedder(32)
        w = EmbedWorker(eng, emb, EmbedWorkerConfig(**cfg))
        return eng, w

    def test_drain_embeds_pending(self):
        eng, w = self._setup()
        for i in range(5):
            eng.create_node(Node(id=f"n{i}", properties={"content": f"text number {i}"}))
            eng.mark_pending_embed(f"n{i}")
        n = w.drain()
        assert n == 5
        assert eng.pending_embed_ids() == []
        assert eng.get_node("n0").embedding is not None
        assert w.stats.processed == 5

    def test_chunked_long_document(self):
        eng, w = self._setup(chunk_tokens=20, chunk_overlap=5)
        long_text = " ".join(f"word{i}" for i in range(100))
        eng.create_node(Node(id="doc", properties={"content": long_text}))
        eng.mark_pending_embed("doc")
        w.drain()
        node = eng.get_node("doc")
        assert node.embedding is not None
        assert len(node.chunk_embeddings) > 1
        assert w.stats.chunked_nodes == 1

    def test_no_text_node_unmarked(self):
        eng, w = self._setup()
        eng.create_node(Node(id="empty", properties={"num": 42}))
        eng.mark_pending_embed("empty")
        assert w.drain() == 1  # handled (unmarked), not embedded
        assert w.stats.processed == 0
        assert eng.pending_embed_ids() == []

    def test_deleted_node_skipped(self):
        eng, w = self._setup()
        eng.create_node(Node(id="gone", properties={"content": "x"}))
        eng.mark_pending_embed("gone")
        eng.delete_node("gone")
        assert w.drain() == 0  # delete_node already unmarked it
        assert eng.pending_embed_ids() == []

    def test_drain_continues_past_textless_batch(self):
        """Regression: a full batch of textless nodes must not stop drain()
        before embeddable nodes behind them are processed."""
        eng, w = self._setup(batch_size=4)
        for i in range(4):
            eng.create_node(Node(id=f"e{i}", properties={"num": i}))
            eng.mark_pending_embed(f"e{i}")
        eng.create_node(Node(id="real", properties={"content": "actual text"}))
        eng.mark_pending_embed("real")
        w.drain()
        assert eng.pending_embed_ids() == []
        assert eng.get_node("real").embedding is not None

    def test_retry_then_success(self):
        eng = MemoryEngine()

        class FlakyEmbedder(HashEmbedder):
            def __init__(self):
                super().__init__(16)
                self.calls = 0

            def embed_batch(self, texts):
                self.calls += 1
                if self.calls == 1:
                    raise RuntimeError("device hiccup")
                return super().embed_batch(texts)

        emb = FlakyEmbedder()
        w = EmbedWorker(eng, emb, EmbedWorkerConfig(retry_backoff=0.01))
        eng.create_node(Node(id="a", properties={"content": "hi"}))
        eng.mark_pending_embed("a")
        assert w.drain() == 1
        assert w.stats.retries == 1

    def test_background_worker(self):
        eng, w = self._setup(poll_interval=0.01)
        w.start()
        try:
            eng.create_node(Node(id="bg", properties={"content": "background"}))
            eng.mark_pending_embed("bg")
            deadline = time.time() + 5
            while time.time() < deadline and eng.pending_embed_ids():
                time.sleep(0.02)
            assert eng.get_node("bg").embedding is not None
        finally:
            w.stop()
        assert not w.running


class TestBM25:
    def test_basic_ranking(self):
        idx = BM25Index()
        idx.index("d1", "the quick brown fox jumps")
        idx.index("d2", "quick quick quick repeated")
        idx.index("d3", "unrelated text about databases")
        res = idx.search("quick")
        assert res[0][0] == "d2"
        assert {r[0] for r in res} == {"d1", "d2"}

    def test_remove(self):
        idx = BM25Index()
        idx.index("d1", "hello world")
        idx.remove("d1")
        assert idx.search("hello") == []
        assert len(idx) == 0

    def test_update_replaces(self):
        idx = BM25Index()
        idx.index("d1", "cats")
        idx.index("d1", "dogs")
        assert idx.search("cats") == []
        assert idx.search("dogs")[0][0] == "d1"


class TestHNSW:
    def test_recall_on_small_corpus(self):
        rng = np.random.default_rng(0)
        data = rng.standard_normal((200, 32)).astype(np.float32)
        idx = HNSWIndex(dims=32, seed=1)
        for i, v in enumerate(data):
            idx.add(f"n{i}", v)
        hits = 0
        for qi in range(20):
            res = idx.search(data[qi], k=1)
            if res and res[0][0] == f"n{qi}":
                hits += 1
        assert hits >= 18  # >=90% self-recall

    def test_remove_and_rebuild(self):
        rng = np.random.default_rng(1)
        data = rng.standard_normal((50, 16)).astype(np.float32)
        idx = HNSWIndex(dims=16, rebuild_tombstone_ratio=0.1)
        for i, v in enumerate(data):
            idx.add(f"n{i}", v)
        for i in range(10):
            idx.remove(f"n{i}")
        assert len(idx) == 40
        res = idx.search(data[15], k=5)
        ids = [r[0] for r in res]
        # removed ids n0..n9 must never surface
        assert not any(i in ids for i in [f"n{j}" for j in range(10)])
        assert "n15" in ids  # live self-match survives the rebuild


class TestFusion:
    def test_rrf_prefers_agreement(self):
        fused = fuse_rrf({"a": ["x", "y", "z"], "b": ["y", "x", "w"]})
        ids = [i for i, _ in fused]
        assert ids[0] in ("x", "y")
        # single third-place appearance ranks below double appearances
        assert ids.index("w") > ids.index("x")
        assert ids.index("w") > ids.index("y")
        assert set(ids) == {"x", "y", "z", "w"}

    def test_rrf_weights(self):
        fused = fuse_rrf(
            {"a": ["x"], "b": ["y"]}, weights={"a": 2.0, "b": 0.5}
        )
        assert fused[0][0] == "x"

    def test_mmr_diversifies(self):
        # two near-duplicates + one distinct; limit 2 should take one dup + distinct
        v = {
            "dup1": np.array([1.0, 0.0], np.float32),
            "dup2": np.array([0.999, 0.04], np.float32),
            "other": np.array([0.0, 1.0], np.float32),
        }
        rel = {"dup1": 1.0, "dup2": 0.99, "other": 0.5}
        out = apply_mmr(["dup1", "dup2", "other"], rel, v, limit=2, lambda_=0.5)
        assert out == ["dup1", "other"]


class TestSearchService:
    def _db(self):
        eng = MemoryEngine()
        emb = HashEmbedder(64)
        svc = SearchService(eng, embedder=emb)
        svc.attach(eng)
        return eng, emb, svc

    def test_event_driven_indexing_and_hybrid_search(self):
        eng, emb, svc = self._db()
        texts = [
            "the graph database stores nodes and edges",
            "vector similarity search on TPU accelerators",
            "memory decay keeps the knowledge graph fresh",
        ]
        for i, t in enumerate(texts):
            n = Node(id=f"n{i}", properties={"content": t})
            n.embedding = emb.embed(t)
            eng.create_node(n)
        res = svc.search("vector similarity TPU", limit=2)
        assert res[0]["id"] == "n1"
        assert res[0]["score"] > 0

    def test_fulltext_only_when_no_embedding(self):
        eng = MemoryEngine()
        svc = SearchService(eng)  # no embedder
        svc.attach(eng)
        eng.create_node(Node(id="a", properties={"content": "pure text match"}))
        res = svc.search("text match")
        assert res and res[0]["id"] == "a"
        assert res[0]["vector_score"] is None

    def test_delete_removes_from_indexes(self):
        eng, emb, svc = self._db()
        n = Node(id="x", properties={"content": "to be deleted"})
        n.embedding = emb.embed("to be deleted")
        eng.create_node(n)
        eng.delete_node("x")
        assert svc.search("deleted") == []

    def test_min_similarity_filters_vector_results(self):
        eng, emb, svc = self._db()
        n = Node(id="a", properties={"content": "alpha beta"})
        n.embedding = emb.embed("alpha beta")
        eng.create_node(n)
        res = svc.vector_candidates(emb.embed("totally different words qqq"), 5, 0.9)
        assert res == []

    def test_build_indexes_from_existing(self):
        eng = MemoryEngine()
        emb = HashEmbedder(64)
        n = Node(id="pre", properties={"content": "preexisting node"})
        n.embedding = emb.embed("preexisting node")
        eng.create_node(n)
        svc = SearchService(eng, embedder=emb)
        assert svc.build_indexes() == 1
        assert svc.search("preexisting")[0]["id"] == "pre"


class TestQueryBatcher:
    """(SURVEY §7 hard part f — micro-batched device dispatch)"""

    def test_concurrent_queries_batch_into_one_dispatch(self):
        import threading

        from nornicdb_tpu.search.batcher import QueryBatcher

        calls = []
        gate = threading.Event()

        def batch_fn(queries, k, min_sim):
            calls.append(queries.shape[0])
            if len(calls) == 1:
                gate.wait(10)  # the first scan is in flight: the rest queue
            return [
                [(f"id{int(q[0])}", float(q[0]))] * min(k, 1) for q in queries
            ]

        b = QueryBatcher(batch_fn)
        results = {}

        def one(i):
            results[i] = b.search(np.full(4, float(i), np.float32), k=1)

        threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 10
        while len(b._pending) < 7 and time.monotonic() < deadline:
            time.sleep(0.005)
        gate.set()
        for t in threads:
            t.join(10)
        assert sum(calls) == 8
        assert len(calls) <= 2  # coalesced, not 8 dispatches
        assert results[3] == [("id3", 3.0)]
        assert b.stats.max_batch >= 4

    def test_per_caller_k_and_threshold(self):
        from nornicdb_tpu.search.batcher import QueryBatcher

        def batch_fn(queries, k, min_sim):
            return [[("a", 0.9), ("b", 0.5), ("c", 0.1)][:k] for _ in queries]

        b = QueryBatcher(batch_fn)
        out = b.search(np.zeros(4, np.float32), k=2, min_similarity=0.4)
        assert out == [("a", 0.9), ("b", 0.5)]

    def test_error_fans_out(self):
        from nornicdb_tpu.search.batcher import QueryBatcher

        def batch_fn(queries, k, min_sim):
            raise RuntimeError("device fell over")

        b = QueryBatcher(batch_fn)
        with pytest.raises(RuntimeError):
            b.search(np.zeros(4, np.float32), k=1)

    def test_service_integration(self):
        import threading

        from nornicdb_tpu.search.service import SearchConfig, SearchService
        from nornicdb_tpu.storage import MemoryEngine, Node

        eng = MemoryEngine()
        emb = HashEmbedder(32)
        svc = SearchService(eng, embedder=emb, config=SearchConfig())
        svc.attach(eng)
        for i in range(20):
            n = Node(id=f"n{i}", properties={"content": f"text number {i}"})
            n.embedding = emb.embed(n.properties["content"])
            eng.create_node(n)
        outs = {}

        def q(i):
            outs[i] = svc.vector_candidates(emb.embed(f"text number {i}"), k=1)

        threads = [threading.Thread(target=q, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(6):
            assert outs[i][0][0] == f"n{i}"
        stats = svc._batcher.stats
        assert stats.queries == 6 and 1 <= stats.batches <= 6


class TestRankCache:
    """Generation-invalidated ranked-result cache (ref: the reference's query
    cache pkg/cache + cached embedder, system-design.md:39)."""

    def _svc(self):
        from nornicdb_tpu.search.service import SearchService
        from nornicdb_tpu.storage import MemoryEngine, Node
        from nornicdb_tpu.embed import HashEmbedder

        eng = MemoryEngine()
        svc = SearchService(eng, embedder=HashEmbedder(32))
        for i in range(20):
            n = Node(id=f"n{i}", properties={"content": f"text topic {i % 3}"})
            eng.create_node(n)
            n.embedding = svc.embedder.embed(n.properties["content"])
            svc.index_node(n)
        return eng, svc

    def test_hit_serves_fresh_node_data(self):
        eng, svc = self._svc()
        r1 = svc.search("text topic 1", limit=3)
        assert r1
        top = r1[0]["id"]
        # mutate node properties WITHOUT reindexing (like an access-count
        # touch): a cached ranking must still serve the fresh node
        n = eng.get_node(top)
        n.properties["content"] = "updated content"
        eng.update_node(n)
        r2 = svc.search("text topic 1", limit=3)
        assert r2[0]["id"] == top
        assert r2[0]["content"] == "updated content"

    def test_index_mutation_invalidates(self):
        eng, svc = self._svc()
        svc.search("text topic 2", limit=3)
        gen0 = svc._generation
        from nornicdb_tpu.storage import Node
        nn = Node(id="fresh", properties={"content": "text topic 2 fresh"})
        eng.create_node(nn)
        nn.embedding = svc.embedder.embed(nn.properties["content"])
        svc.index_node(nn)
        assert svc._generation > gen0
        r = svc.search("text topic 2 fresh", limit=5)
        assert any(x["id"] == "fresh" for x in r)

    def test_deleted_id_drops_out_on_hit(self):
        eng, svc = self._svc()
        r1 = svc.search("text topic 0", limit=3)
        top = r1[0]["id"]
        # delete from storage only (index removal would bump the generation;
        # the stale cached ranking must cope with a missing node)
        eng.delete_node(top)
        r2 = svc.search("text topic 0", limit=3)
        assert all(x["id"] != top for x in r2)


class TestNamespacedCounts:
    def test_event_maintained_counts(self):
        from nornicdb_tpu.storage import MemoryEngine, NamespacedEngine, Node, Edge

        base = MemoryEngine()
        a = NamespacedEngine(base, "a")
        b = NamespacedEngine(base, "b")
        for i in range(5):
            a.create_node(Node(id=f"x{i}"))
        b.create_node(Node(id="y"))
        assert a.node_count() == 5
        assert b.node_count() == 1
        a.create_edge(Edge(id="e", start_node="x0", end_node="x1"))
        assert a.edge_count() == 1
        assert b.edge_count() == 0
        a.delete_node("x0")  # cascades the edge
        assert a.node_count() == 4
        assert a.edge_count() == 0
        assert b.node_count() == 1


class TestBucketedBatching:
    """Round-2 measured batching policy (PROGRESS table): length buckets +
    batch classes, bounded jit cache, order-stable output."""

    def test_mixed_lengths_order_stable(self):
        import numpy as np

        from nornicdb_tpu.embed import TPUEmbedder

        e = TPUEmbedder()
        texts = ["short", "medium one two three four five six",
                 " ".join(["w"] * 100), "tiny", " ".join(["x"] * 400)]
        out = e.embed_batch(texts)
        assert len(out) == len(texts)
        assert all(o.shape == (e.cfg.dims,) for o in out)
        # same text -> same vector regardless of batch composition
        solo = e.embed_batch([texts[2]])[0]
        assert np.allclose(out[2], solo, atol=1e-5)

    def test_batch_classes_bound_compile_shapes(self):
        from nornicdb_tpu.embed import TPUEmbedder

        e = TPUEmbedder(opt_batch=8)
        assert e._batch_class(1) == 1
        assert e._batch_class(3) == 4
        assert e._batch_class(8) == 8
        assert e._batch_class(100) == 8  # capped at opt_batch
        assert e._bucket_len(5) == 32
        assert e._bucket_len(33) == 64
        assert e._bucket_len(513) == e.max_len

    def test_data_parallel_embedder_on_mesh(self):
        import numpy as np

        from nornicdb_tpu.embed import TPUEmbedder
        from nornicdb_tpu.parallel import DataParallelEmbedder

        inner = TPUEmbedder()
        dp = DataParallelEmbedder(inner, n_devices=4)
        assert dp.n_devices == 4
        texts = [f"document number {i} " + "w " * (i * 7 % 40)
                 for i in range(10)]  # 10 rows pad to 12 over 4 devices
        out = dp.embed_batch(texts)
        assert len(out) == 10
        ref = inner.embed_batch(texts)
        for a, b in zip(out, ref):
            assert np.allclose(a, b, atol=1e-4)
