"""Per-component search unit depth (ref: pkg/search/search_test.go 1,564
LoC + hnsw_index_test.go 528 LoC — the reference's largest search suites).

Behavioral ports, reimplemented against this package's architecture:
BM25 index/tokenize/remove/replace semantics, RRF fusion + adaptive
weights at their word-count boundaries, MMR diversification, service-level
index/remove/enrich/empty-query/special-character behavior, and HNSW
add/remove/search/concurrency. Service tests pin the hnsw backend so they
run without a device corpus; the TPU corpus path is covered by
test_embed_search.py.
"""

import threading

import numpy as np
import pytest

from nornicdb_tpu.search.bm25 import BM25Index, tokenize
from nornicdb_tpu.search.fusion import adaptive_rrf_weights, apply_mmr, fuse_rrf
from nornicdb_tpu.search.hnsw import HNSWIndex
from nornicdb_tpu.search.service import SearchConfig, SearchService
from nornicdb_tpu.storage import MemoryEngine
from nornicdb_tpu.storage.types import Node


# ------------------------------------------------------------------ BM25
class TestTokenize:
    def test_lowercases_and_strips_punctuation(self):
        """ref: TestFulltextIndex_Tokenization"""
        assert tokenize("Hello, World! Foo-bar?") == \
            ["hello", "world", "foo", "bar"]

    def test_numbers_survive(self):
        assert "42" in tokenize("answer is 42.")

    def test_empty_and_whitespace(self):
        assert tokenize("") == []
        assert tokenize("   \t\n ") == []


class TestBM25Index:
    def test_rare_term_outranks_common(self):
        """ref: TestFulltextIndex_BM25 — IDF: a term present in one doc
        must rank that doc above docs matching only ubiquitous terms."""
        idx = BM25Index()
        idx.index("d1", "the quick brown fox jumps")
        idx.index("d2", "the lazy dog sleeps")
        idx.index("d3", "the quick cat runs")
        hits = idx.search("lazy dog")
        assert hits[0][0] == "d2"

    def test_term_frequency_matters(self):
        idx = BM25Index()
        idx.index("once", "jax compiles functions")
        idx.index("many", "jax jax jax everywhere jax")
        assert idx.search("jax")[0][0] == "many"

    def test_remove_deletes_doc(self):
        """ref: TestFulltextIndex_Remove"""
        idx = BM25Index()
        idx.index("d1", "alpha beta")
        idx.index("d2", "alpha gamma")
        assert len(idx) == 2
        idx.remove("d1")
        assert len(idx) == 1
        assert all(i != "d1" for i, _ in idx.search("alpha"))
        idx.remove("d1")  # idempotent
        assert len(idx) == 1

    def test_reindex_replaces_not_duplicates(self):
        idx = BM25Index()
        idx.index("d1", "original text about norway")
        idx.index("d1", "replacement text about iceland")
        assert len(idx) == 1
        assert idx.search("norway") == []
        assert idx.search("iceland")[0][0] == "d1"

    def test_empty_query_returns_nothing(self):
        """ref: TestSearchService_EmptyQuery"""
        idx = BM25Index()
        idx.index("d1", "content")
        assert idx.search("") == []

    def test_special_characters_query(self):
        """ref: TestSearchService_SpecialCharacters"""
        idx = BM25Index()
        idx.index("d1", "c plus plus and rust")
        for q in ("c++", "@#$%", "'; DROP TABLE--", "日本語"):
            idx.search(q)  # must not raise

    def test_limit_respected(self):
        idx = BM25Index()
        for i in range(20):
            idx.index(f"d{i}", "shared term corpus")
        assert len(idx.search("shared", limit=5)) == 5


# ------------------------------------------------------------ RRF fusion
class TestRRFFusion:
    def test_agreement_beats_single_list_rank(self):
        """ref: TestRRFFusion — an id ranked mid-list in BOTH lists beats
        an id topping only one."""
        fused = fuse_rrf({
            "vector": ["both", "v_only", "v2"],
            "fulltext": ["ft_only", "both", "ft2"],
        })
        assert fused[0][0] == "both"

    def test_weights_shift_winner(self):
        lists = {"vector": ["v"], "fulltext": ["f"]}
        assert fuse_rrf(lists, {"vector": 2.0, "fulltext": 0.5})[0][0] == "v"
        assert fuse_rrf(lists, {"vector": 0.5, "fulltext": 2.0})[0][0] == "f"

    def test_deterministic_tiebreak_by_id(self):
        fused = fuse_rrf({"vector": ["b"], "fulltext": ["a"]})
        assert [i for i, _ in fused] == ["a", "b"]

    def test_adaptive_weights_word_count_boundaries(self):
        """ref: TestGetAdaptiveRRFConfig — 2 words keyword-ish, 8+ natural
        language, 3-7 balanced."""
        short = adaptive_rrf_weights("error handling")
        assert short["fulltext"] > short["vector"]
        mid = adaptive_rrf_weights("how to handle errors fast")
        assert mid["fulltext"] == mid["vector"]
        long = adaptive_rrf_weights(
            "what is the best way to handle transient network errors")
        assert long["vector"] > long["fulltext"]


class TestMMR:
    def test_diversifies_near_duplicates(self):
        """ref: TestMMRDiversification — two near-identical top hits: MMR
        must pull in the diverse third instead of the duplicate."""
        vectors = {
            "a": np.array([1.0, 0.0], np.float32),
            "a_dup": np.array([0.999, 0.01], np.float32),
            "b": np.array([0.0, 1.0], np.float32),
        }
        rel = {"a": 1.0, "a_dup": 0.99, "b": 0.5}
        out = apply_mmr(["a", "a_dup", "b"], rel, vectors, limit=2,
                        lambda_=0.5)
        assert out == ["a", "b"]

    def test_limit_at_or_above_candidates_is_identity(self):
        out = apply_mmr(["x", "y"], {"x": 1.0, "y": 0.5}, {}, limit=5)
        assert out == ["x", "y"]


# ---------------------------------------------------------- SearchService
def _hnsw_service(engine=None):
    return SearchService(
        engine or MemoryEngine(),
        config=SearchConfig(backend="hnsw", mmr_enabled=False),
    )


def _vec(*xs):
    v = np.asarray(xs, np.float32)
    return v / np.linalg.norm(v)


class TestServiceIndexing:
    def test_fulltext_only_node_searchable(self):
        """ref: TestSearchService_FullTextOnly"""
        svc = _hnsw_service()
        svc.storage.create_node(Node(id="n1",
                                     properties={"content": "norse myths"}))
        svc.index_node(svc.storage.get_node("n1"))
        hits = svc.search("norse")
        assert [h["id"] for h in hits] == ["n1"]
        assert hits[0]["vector_score"] is None
        assert hits[0]["fulltext_score"] is not None

    def test_remove_node_clears_both_indexes(self):
        """ref: TestSearchService_RemoveNode(+OnlyRemovesTargetNode)"""
        svc = _hnsw_service()
        for i, vec in enumerate(([1, 0], [0, 1])):
            svc.storage.create_node(Node(
                id=f"n{i}", embedding=_vec(*vec),
                properties={"content": f"doc number {i}"}))
            svc.index_node(svc.storage.get_node(f"n{i}"))
        svc.remove_node("n0")
        assert all(h["id"] != "n0"
                   for h in svc.search("doc", query_embedding=_vec(1, 0)))
        # the OTHER node still searchable both ways
        assert any(h["id"] == "n1"
                   for h in svc.search("number", query_embedding=_vec(0, 1)))
        assert svc.stats.removed == 1

    def test_update_dropping_embedding_leaves_fulltext(self):
        svc = _hnsw_service()
        svc.storage.create_node(Node(id="n1", embedding=_vec(1, 0),
                                     properties={"content": "keep text"}))
        svc.index_node(svc.storage.get_node("n1"))
        updated = svc.storage.get_node("n1")
        updated.embedding = None
        svc.storage.update_node(updated)
        svc.index_node(svc.storage.get_node("n1"))
        assert svc.vector_candidates(_vec(1, 0), k=5) == []
        assert [h["id"] for h in svc.search("keep")] == ["n1"]

    def test_build_indexes_from_storage(self):
        """ref: TestSearchService_BuildIndexesFromStorage"""
        eng = MemoryEngine()
        for i in range(7):
            eng.create_node(Node(id=f"n{i}",
                                 properties={"content": f"stored doc {i}"}))
        svc = _hnsw_service(eng)
        assert svc.build_indexes() == 7
        assert len(svc.search("stored", limit=10)) == 7

    def test_enrich_serves_node_fields_and_drops_deleted(self):
        """ref: TestSearchService_EnrichResults"""
        svc = _hnsw_service()
        svc.storage.create_node(Node(
            id="n1", labels=["Doc"],
            properties={"content": "enriched body", "title": "T"}))
        svc.index_node(svc.storage.get_node("n1"))
        h = svc.search("enriched")[0]
        assert h["content"] == "enriched body"
        assert h["labels"] == ["Doc"]
        assert h["node"].properties["title"] == "T"
        # deleted after ranking: drops out instead of erroring
        svc.storage.delete_node("n1")
        assert svc.search("enriched body text") == []

    def test_empty_query_no_embedding_returns_empty(self):
        svc = _hnsw_service()
        svc.storage.create_node(Node(id="n1",
                                     properties={"content": "anything"}))
        svc.index_node(svc.storage.get_node("n1"))
        assert svc.search("") == []

    def test_min_similarity_threshold(self):
        svc = _hnsw_service()
        for i, vec in enumerate(([1, 0], [0.71, 0.71])):
            svc.storage.create_node(Node(id=f"n{i}", embedding=_vec(*vec),
                                         properties={"content": "x"}))
            svc.index_node(svc.storage.get_node(f"n{i}"))
        close = svc.vector_candidates(_vec(1, 0), k=5, min_similarity=0.9)
        assert [i for i, _ in close] == ["n0"]


# ------------------------------------------------------------------ HNSW
class TestHNSWIndex:
    def test_add_and_len(self):
        idx = HNSWIndex(dims=4)
        for i in range(10):
            idx.add(f"v{i}", _vec(*np.random.default_rng(i).normal(size=4)))
        assert len(idx) == 10

    def test_search_returns_nearest_first(self):
        """ref: TestHNSWIndex_Search — clustered data, the query's own
        cluster fills the head."""
        idx = HNSWIndex(dims=3)
        idx.add("x", _vec(1, 0, 0))
        idx.add("y", _vec(0, 1, 0))
        idx.add("z", _vec(0, 0, 1))
        idx.add("near_x", _vec(0.95, 0.05, 0))
        hits = idx.search(_vec(1, 0, 0), k=2)
        assert [i for i, _ in hits] == ["x", "near_x"]
        assert hits[0][1] >= hits[1][1]

    def test_remove_tombstones_and_ratio(self):
        """ref: TestHNSWIndex_Remove — below the rebuild threshold removals
        tombstone (ratio grows); crossing it compacts back to zero."""
        rng = np.random.default_rng(3)
        idx = HNSWIndex(dims=4)
        for i in range(40):
            v = rng.normal(size=4).astype(np.float32)
            idx.add(f"v{i}", v / np.linalg.norm(v))
        assert idx.remove("v0") is True
        assert idx.remove("ghost") is False
        assert idx.remove("v0") is False  # already tombstoned
        assert len(idx) == 39
        assert idx.tombstone_ratio() > 0.0
        assert all(i != "v0" for i, _ in idx.search(_vec(1, 0, 0, 0), k=40))
        # removing most of the index repeatedly crosses the threshold;
        # compactions keep the live ratio bounded below it
        for i in range(1, 35):
            idx.remove(f"v{i}")
        assert idx.tombstone_ratio() <= idx.rebuild_tombstone_ratio
        assert len(idx) == 5

    def test_concurrent_add_and_search(self):
        """ref: TestHNSWIndex_Concurrency"""
        idx = HNSWIndex(dims=8)
        rng = np.random.default_rng(0)
        seed_vecs = rng.normal(size=(20, 8)).astype(np.float32)
        for i, v in enumerate(seed_vecs):
            idx.add(f"seed{i}", v / np.linalg.norm(v))
        errs = []
        stop = threading.Event()

        def adder(base):
            try:
                r = np.random.default_rng(base)
                for i in range(30):
                    v = r.normal(size=8).astype(np.float32)
                    idx.add(f"t{base}-{i}", v / np.linalg.norm(v))
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        def searcher():
            r = np.random.default_rng(99)
            while not stop.is_set():
                try:
                    q = r.normal(size=8).astype(np.float32)
                    idx.search(q / np.linalg.norm(q), k=5)
                except Exception as e:  # noqa: BLE001
                    errs.append(e)
                    return

        s = threading.Thread(target=searcher)
        threads = [threading.Thread(target=adder, args=(t,)) for t in range(4)]
        s.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        s.join()
        assert not errs
        assert len(idx) == 20 + 4 * 30

    def test_recall_against_exact_on_random_corpus(self):
        """ref: TestHNSWIndex_RecallQuality — recall@10 >= 0.9 vs brute
        force on 300 random vectors."""
        rng = np.random.default_rng(7)
        vecs = rng.normal(size=(300, 16)).astype(np.float32)
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
        idx = HNSWIndex(dims=16)
        for i, v in enumerate(vecs):
            idx.add(f"v{i}", v)
        recalls = []
        for qi in range(10):
            q = vecs[qi * 17]
            exact = set(np.argsort(-(vecs @ q))[:10])
            got = {int(i[1:]) for i, _ in idx.search(q, k=10)}
            recalls.append(len(got & exact) / 10)
        assert float(np.mean(recalls)) >= 0.9, recalls
