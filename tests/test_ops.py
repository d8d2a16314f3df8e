"""Ops tests (modeled on reference pkg/simd/simd_test.go,
pkg/gpu/kmeans.go tests, pkg/gpu score_subset_race_test.go)."""

import numpy as np
import pytest

import jax.numpy as jnp

from nornicdb_tpu.ops import (
    DeviceCorpus,
    assign_clusters,
    cosine_scores,
    cosine_topk,
    euclidean_scores,
    fused_cosine_topk,
    kmeans_fit,
    l2_normalize,
    merge_topk,
    nearest_clusters,
    optimal_k,
    pad_to_multiple,
)


def _rand(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)).astype(np.float32)


class TestSimilarity:
    def test_l2_normalize(self):
        x = _rand(8, 16)
        n = np.asarray(l2_normalize(jnp.asarray(x)))
        np.testing.assert_allclose(np.linalg.norm(n, axis=1), 1.0, atol=1e-5)

    def test_l2_normalize_zero_row_safe(self):
        x = np.zeros((2, 4), np.float32)
        n = np.asarray(l2_normalize(jnp.asarray(x)))
        assert np.all(np.isfinite(n))

    def test_cosine_scores_match_numpy(self):
        q, c = _rand(4, 32, 1), _rand(10, 32, 2)
        got = np.asarray(cosine_scores(jnp.asarray(q), jnp.asarray(c), use_bf16=False))
        qn = q / np.linalg.norm(q, axis=1, keepdims=True)
        cn = c / np.linalg.norm(c, axis=1, keepdims=True)
        np.testing.assert_allclose(got, qn @ cn.T, atol=1e-4)

    def test_cosine_topk_identity(self):
        c = _rand(pad_to_multiple(64), 16, 3)
        q = c[:4]
        valid = jnp.ones(c.shape[0], bool)
        vals, idx = cosine_topk(
            l2_normalize(jnp.asarray(q)), l2_normalize(jnp.asarray(c)), valid, 1,
            use_bf16=False,
        )
        # each query's best match is itself
        assert list(np.asarray(idx[:, 0])) == [0, 1, 2, 3]
        np.testing.assert_allclose(np.asarray(vals[:, 0]), 1.0, atol=1e-3)

    def test_cosine_topk_masks_invalid(self):
        c = jnp.asarray(_rand(128, 8))
        q = l2_normalize(c[:1])
        valid = jnp.zeros(128, bool).at[5].set(True)
        vals, idx = cosine_topk(q, l2_normalize(c), valid, 3, use_bf16=False)
        assert int(idx[0, 0]) == 5
        assert not bool(jnp.isfinite(vals[0, 1]))  # only one valid row

    def test_euclidean(self):
        q, c = _rand(2, 8, 4), _rand(5, 8, 5)
        got = np.asarray(euclidean_scores(jnp.asarray(q), jnp.asarray(c)))
        want = ((q[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)

    def test_merge_topk(self):
        # two shards, one query, k=2
        vals = jnp.asarray([[[0.9, 0.1]], [[0.8, 0.7]]])  # (S=2, Q=1, k=2)
        idx = jnp.asarray([[[0, 1]], [[100, 101]]])
        v, i = merge_topk(vals, idx, 2)
        assert list(np.asarray(v[0])) == pytest.approx([0.9, 0.8])
        assert list(np.asarray(i[0])) == [0, 100]


class TestDeviceCorpus:
    def test_add_search(self):
        dc = DeviceCorpus(dims=16)
        data = _rand(50, 16, 7)
        for i, v in enumerate(data):
            dc.add(f"n{i}", v)
        res = dc.search(data[17], k=3)
        assert res[0][0][0] == "n17"
        assert res[0][0][1] == pytest.approx(1.0, abs=1e-2)

    def test_remove_then_search(self):
        dc = DeviceCorpus(dims=8, compact_ratio=0.9)
        data = _rand(10, 8, 8)
        for i, v in enumerate(data):
            dc.add(f"n{i}", v)
        dc.remove("n3")
        res = dc.search(data[3], k=10)
        ids = [r[0] for r in res[0]]
        assert "n3" not in ids
        assert len(dc) == 9

    def test_compaction(self):
        dc = DeviceCorpus(dims=8, compact_ratio=0.2)
        data = _rand(20, 8, 9)
        for i, v in enumerate(data):
            dc.add(f"n{i}", v)
        for i in range(10):
            dc.remove(f"n{i}")
        # compaction no longer runs on the remove() caller path: it is
        # deferred and coalesced into the next device sync
        assert dc._compact_pending
        assert dc._tombstones == 10
        res = dc.search(data[15], k=1)  # sync runs the pending compaction
        assert res[0][0][0] == "n15"
        assert dc._tombstones == 0  # one rewrite covered the whole burst
        assert len(dc._ids) == 10  # slots were reclaimed

    def test_update_in_place(self):
        dc = DeviceCorpus(dims=4)
        dc.add("a", np.array([1, 0, 0, 0], np.float32))
        dc.add("a", np.array([0, 1, 0, 0], np.float32))
        assert len(dc) == 1
        res = dc.search(np.array([0, 1, 0, 0], np.float32), k=1)
        assert res[0][0][1] == pytest.approx(1.0, abs=1e-3)

    def test_min_similarity_filter(self):
        dc = DeviceCorpus(dims=4)
        dc.add("same", np.array([1, 0, 0, 0], np.float32))
        dc.add("orth", np.array([0, 1, 0, 0], np.float32))
        res = dc.search(np.array([1, 0, 0, 0], np.float32), k=5, min_similarity=0.5)
        assert [r[0] for r in res[0]] == ["same"]

    def test_score_subset(self):
        dc = DeviceCorpus(dims=4)
        dc.add("a", np.array([1, 0, 0, 0], np.float32))
        dc.add("b", np.array([0, 1, 0, 0], np.float32))
        pairs = dc.score_subset(
            np.array([1, 0, 0, 0], np.float32), ["a", "missing", "b"]
        )
        assert [p[0] for p in pairs] == ["a", "b"]  # unknown id omitted, not shifted
        assert pairs[0][1] == pytest.approx(1.0, abs=1e-3)
        assert pairs[1][1] == pytest.approx(0.0, abs=1e-3)

    def test_growth(self):
        dc = DeviceCorpus(dims=4, capacity=8)
        for i in range(300):
            dc.add(f"n{i}", _rand(1, 4, i)[0])
        assert len(dc) == 300
        assert dc.capacity >= 300


class TestKMeans:
    def test_optimal_k(self):
        assert optimal_k(0) == 1
        assert optimal_k(200) == 10
        assert optimal_k(20000) == 100

    def test_clusters_separate_blobs(self):
        rng = np.random.default_rng(0)
        blob1 = rng.normal(0, 0.1, (50, 8)).astype(np.float32)
        blob2 = rng.normal(5, 0.1, (50, 8)).astype(np.float32)
        data = np.vstack([blob1, blob2])
        res = kmeans_fit(data, k=2, iters=8)
        a = res.assignments
        assert len(set(a[:50])) == 1
        assert len(set(a[50:])) == 1
        assert a[0] != a[50]

    def test_drift_decreases(self):
        data = _rand(200, 8, 11)
        res = kmeans_fit(data, k=5, iters=10)
        assert res.drift[-1] <= res.drift[0] + 1e-6

    def test_k_capped_at_n(self):
        data = _rand(3, 4, 12)
        res = kmeans_fit(data, k=10, iters=2)
        assert res.k == 3

    def test_assign_and_nearest_clusters(self):
        data = _rand(100, 8, 13)
        res = kmeans_fit(data, k=4, iters=5)
        a = np.asarray(assign_clusters(jnp.asarray(data), jnp.asarray(res.centroids)))
        np.testing.assert_array_equal(a, res.assignments)
        probe = nearest_clusters(jnp.asarray(data[0]), jnp.asarray(res.centroids), 2)
        assert int(probe[0]) == int(res.assignments[0])


class TestPallasKernels:
    def test_fused_matches_xla(self):
        q = l2_normalize(jnp.asarray(_rand(8, 128, 20)))
        c = jnp.asarray(_rand(512, 128, 21))
        valid = jnp.ones(512, bool)
        v1, i1 = fused_cosine_topk(q, c, valid, 5, tile_n=128, interpret=True)
        v2, i2 = cosine_topk(q, l2_normalize(c), valid, 5, use_bf16=False)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-4)


class TestKernelDispatch:
    """Where the streaming kernel runs is decided in one place: on a TPU
    by size (or force), off it never — the Pallas interpreter is something
    only a test asks for, by name."""

    @pytest.mark.parametrize("on_tpu,streaming,n,want", [
        (False, None, 1 << 20, (False, False)),
        (False, True, 1 << 20, (False, False)),
        (False, "interpret", 256, (True, True)),
        (True, None, 65_535, (False, False)),
        (True, None, 65_536, (True, False)),
        (True, True, 256, (True, False)),
        (True, False, 1 << 20, (False, False)),
        (True, "interpret", 256, (True, True)),
    ])
    def test_kernel_mode(self, monkeypatch, on_tpu, streaming, n, want):
        from nornicdb_tpu.ops import pallas_kernels, similarity

        monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: on_tpu)
        assert similarity._kernel_mode(streaming, n) == want

    def test_failed_backend_init_raises(self, monkeypatch):
        """A backend that cannot initialise must not read as "not a TPU"
        (and so as the XLA-on-CPU path): the error reaches the caller."""
        import jax

        from nornicdb_tpu.ops import pallas_kernels, similarity

        def boom():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "devices", boom)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            pallas_kernels._on_tpu()
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            similarity._kernel_mode(None, 1 << 20)


class TestClusterPrunedSearch:
    """(ref: ClusterIndex kmeans.go:144, SearchWithClusters :816,
    kmeans_candidate_gen.go)"""

    def _corpus(self):
        rng = np.random.default_rng(0)
        dc = DeviceCorpus(dims=16)
        # three well-separated blobs
        centers = np.eye(3, 16, dtype=np.float32) * 10
        data = np.concatenate(
            [centers[i] + rng.normal(0, 0.3, (40, 16)).astype(np.float32)
             for i in range(3)]
        )
        dc.add_batch([f"n{i}" for i in range(120)], data)
        return dc, data

    def test_cluster_and_pruned_search(self):
        dc, data = self._corpus()
        k = dc.cluster(k=3, iters=8)
        assert k == 3
        res = dc.search(data[5], k=3, n_probe=1)
        assert res[0][0][0] == "n5"  # self-match survives pruning
        assert res[0][0][1] > 0.9

    def test_pruned_matches_full_on_separated_data(self):
        dc, data = self._corpus()
        dc.cluster(k=3, iters=8)
        full = dc.search(data[50], k=5)[0]
        pruned = dc.search(data[50], k=5, n_probe=1)[0]
        assert [p[0] for p in pruned] == [f[0] for f in full]

    def test_no_clusters_falls_back_to_full(self):
        dc, data = self._corpus()
        res = dc.search(data[7], k=1, n_probe=4)  # no cluster() called
        assert res[0][0][0] == "n7"

    def test_clear_clusters(self):
        dc, data = self._corpus()
        dc.cluster(k=3)
        dc.clear_clusters()
        res = dc.search(data[7], k=1, n_probe=2)
        assert res[0][0][0] == "n7"

    def test_growth_invalidates_clusters(self):
        dc, data = self._corpus()
        dc.cluster(k=3)
        extra = np.random.default_rng(5).standard_normal((200, 16)).astype(np.float32)
        dc.add_batch([f"x{i}" for i in range(200)], extra)  # triggers _grow
        res = dc.search(data[5], k=1, n_probe=1)  # falls back to full scan
        assert res[0][0][0] == "n5"

    def test_set_clusters_external(self):
        dc, data = self._corpus()
        from nornicdb_tpu.ops import kmeans_fit
        res = kmeans_fit(data, k=3, iters=8)
        dc.set_clusters(res.centroids,
                        {f"n{i}": int(c) for i, c in enumerate(res.assignments)})
        out = dc.search(data[5], k=1, n_probe=1)
        assert out[0][0][0] == "n5"


class TestStreamingTopK:
    """Streaming Pallas top-k: one corpus read, running per-bin max in VMEM,
    no (Q, N) materialization (ref: fused CUDA scoring+topk
    cuda_kernels.cu:263,384). Interpret mode runs the identical kernel on CPU."""

    def _data(self, n=2048, d=128, q=4, seed=0):
        rng = np.random.default_rng(seed)
        c = rng.standard_normal((n, d)).astype(np.float32)
        c /= np.linalg.norm(c, axis=1, keepdims=True)
        qs = rng.standard_normal((q, d)).astype(np.float32)
        qs /= np.linalg.norm(qs, axis=1, keepdims=True)
        return qs, c

    def test_exact_when_bins_cover_corpus(self):
        from nornicdb_tpu.ops.pallas_kernels import streaming_cosine_topk

        qs, c = self._data(n=1024, d=128)
        valid = np.ones(1024, bool)
        v, i = streaming_cosine_topk(
            jnp.asarray(qs), jnp.asarray(c), jnp.asarray(valid), 16,
            tile_n=128, rows=8, interpret=True,  # 8*128 = full corpus: exact
        )
        scores = qs @ c.T
        gt = np.argsort(-scores, axis=1)[:, :16]
        assert (np.sort(np.asarray(i), axis=1) == np.sort(gt, axis=1)).all()

    def test_recall_and_masking(self):
        from nornicdb_tpu.ops.pallas_kernels import (
            pick_tile_n, streaming_cosine_topk, streaming_rows_for)

        qs, c = self._data(n=4096, d=128, q=8)
        valid = np.ones(4096, bool)
        valid[::7] = False  # tombstones
        k = 32
        tile = pick_tile_n(4096, preferred=512)
        rows = streaming_rows_for(k, tile)
        v, i = streaming_cosine_topk(
            jnp.asarray(qs), jnp.asarray(c), jnp.asarray(valid), k,
            tile_n=tile, rows=min(rows, 4096 // tile), interpret=True,
        )
        i = np.asarray(i)
        assert valid[i].all(), "masked rows leaked into results"
        scores = qs @ c.T
        scores[:, ~valid] = -np.inf
        gt = np.argsort(-scores, axis=1)[:, :k]
        recall = np.mean([len(set(i[r]) & set(gt[r])) / k for r in range(8)])
        assert recall >= 0.9, recall

    def test_device_corpus_streaming_path(self):
        from nornicdb_tpu.ops.similarity import DeviceCorpus

        rng = np.random.default_rng(3)
        corpus = DeviceCorpus(dims=64)
        vecs = rng.standard_normal((500, 64)).astype(np.float32)
        ids = [f"v{i}" for i in range(500)]
        corpus.add_batch(ids, vecs)
        for j in range(0, 500, 11):
            corpus.remove(f"v{j}")
        q = vecs[7]
        # streaming="interpret" forces the Pallas path (interpret off-TPU);
        # default path is the XLA approx_max_k — results must agree on top-1
        a = corpus.search(q, k=5, streaming="interpret")
        b = corpus.search(q, k=5, streaming=False)
        assert a[0][0][0] == b[0][0][0] == "v7"
        assert abs(a[0][0][1] - 1.0) < 1e-2
        removed = {f"v{j}" for j in range(0, 500, 11)}
        assert not ({id_ for id_, _ in a[0]} & removed)

    def test_epilogue_variants_agree(self):
        """sort and pallas epilogues are both exact over the bins (identical
        values); approx stays within its recall contract. (The epilogue is
        the serving kernel's measured hot spot: XLA's top_k is a full
        bitonic sort of the bin matrix.)"""
        from nornicdb_tpu.ops.pallas_kernels import (
            quantize_rows, streaming_cosine_topk, streaming_cosine_topk_int8)

        qs, c = self._data(n=4096, d=128, q=8)
        valid = np.ones(4096, bool)
        valid[::9] = False
        k = 32
        scores = qs @ c.T
        scores[:, ~valid] = -np.inf
        gt = np.argsort(-scores, axis=1)[:, :k]

        outs = {}
        for ep in ("sort", "approx", "pallas"):
            v, i = streaming_cosine_topk(
                jnp.asarray(qs), jnp.asarray(c), jnp.asarray(valid), k,
                tile_n=512, rows=4, interpret=True, epilogue=ep,
            )
            i = np.asarray(i)
            assert valid[i].all(), f"{ep}: masked rows leaked"
            rec = np.mean([len(set(i[r]) & set(gt[r])) / k for r in range(8)])
            assert rec >= 0.9, (ep, rec)
            outs[ep] = (np.asarray(v), i)
        # exact epilogues produce identical values (indices may differ
        # only on exact score ties)
        assert np.array_equal(outs["sort"][0], outs["pallas"][0])

        # int8 path: same contract
        q_i8, q_scale = quantize_rows(jnp.asarray(qs))
        c_i8, c_scale = quantize_rows(jnp.asarray(c))
        vals = {}
        for ep in ("sort", "pallas"):
            v, i = streaming_cosine_topk_int8(
                q_i8, q_scale, c_i8, c_scale, jnp.asarray(valid), k,
                tile_n=512, rows=4, interpret=True, epilogue=ep,
            )
            assert valid[np.asarray(i)].all()
            vals[ep] = np.asarray(v)
        assert np.array_equal(vals["sort"], vals["pallas"])

    def test_pick_tile_and_rows(self):
        from nornicdb_tpu.ops.pallas_kernels import (
            pick_tile_n, streaming_rows_for)

        assert pick_tile_n(1024 * 1024) == 1024
        assert pick_tile_n(128) == 128
        assert pick_tile_n(384) == 128  # 384 = 3*128: only 128 divides
        assert streaming_rows_for(100, 1024) * 1024 >= 2000
        assert streaming_rows_for(10, 1024) == 2

    def test_int8_kernel_recall_and_masking(self):
        from nornicdb_tpu.ops.pallas_kernels import (
            quantize_rows, streaming_cosine_topk_int8)

        qs, c = self._data(n=2048, d=128, q=8)
        valid = np.ones(2048, bool)
        valid[::5] = False
        k = 16
        q_i8, q_scale = quantize_rows(jnp.asarray(qs))
        c_i8, c_scale = quantize_rows(jnp.asarray(c))
        v, i = streaming_cosine_topk_int8(
            q_i8, q_scale, c_i8, c_scale, jnp.asarray(valid), k,
            tile_n=256, rows=8, interpret=True,  # full coverage: exact bins
        )
        i, v = np.asarray(i), np.asarray(v)
        assert valid[i].all(), "masked rows leaked into results"
        scores = qs @ c.T
        scores[:, ~valid] = -np.inf
        gt = np.argsort(-scores, axis=1)[:, :k]
        recall = np.mean([len(set(i[r]) & set(gt[r])) / k for r in range(8)])
        assert recall >= 0.9, recall
        # decoded values approximate true cosine within int8+packing noise
        top1_true = np.take_along_axis(scores, i[:, :1], axis=1)[:, 0]
        assert np.max(np.abs(v[:, 0] - top1_true)) < 0.02

    def test_device_corpus_quantized_path(self):
        from nornicdb_tpu.ops.similarity import DeviceCorpus

        rng = np.random.default_rng(5)
        corpus = DeviceCorpus(dims=64, quantize=True)
        vecs = rng.standard_normal((400, 64)).astype(np.float32)
        ids = [f"v{i}" for i in range(400)]
        corpus.add_batch(ids, vecs)
        corpus.remove("v8")
        a = corpus.search(vecs[7], k=5, streaming="interpret")
        assert a[0][0][0] == "v7"
        assert abs(a[0][0][1] - 1.0) < 0.02
        assert "v8" not in {id_ for id_, _ in a[0]}


class TestCorpusLifecycle:
    """ref: gpu_test.go:630-800 — EmbeddingIndex Has/Get/Clear/Stats/
    MemoryUsage/Serialize/Deserialize."""

    def _corpus(self):
        from nornicdb_tpu.ops.similarity import DeviceCorpus

        c = DeviceCorpus(dims=4)
        c.add("a", np.array([1, 0, 0, 0], np.float32))
        c.add("b", np.array([0, 1, 0, 0], np.float32))
        return c

    def test_has_and_get(self):
        c = self._corpus()
        assert c.has("a") and not c.has("zz")
        v = c.get("a")
        assert v is not None and abs(float(v[0]) - 1.0) < 1e-6
        assert c.get("zz") is None
        c.remove("a")
        assert not c.has("a") and c.get("a") is None

    def test_clear(self):
        c = self._corpus()
        c.clear()
        assert len(c) == 0 and not c.has("a")
        # usable after clear
        c.add("x", np.array([0, 0, 1, 0], np.float32))
        assert c.search(np.array([0, 0, 1, 0], np.float32), k=1)[0][0][0] == "x"

    def test_stats_and_memory(self):
        c = self._corpus()
        s = c.stats()
        assert s["count"] == 2 and s["dims"] == 4
        assert s["memory_bytes"] == c.memory_usage() > 0
        c.remove("a")
        assert c.stats()["count"] == 1

    def test_save_load_roundtrip(self, tmp_path):
        from nornicdb_tpu.ops.similarity import DeviceCorpus

        c = self._corpus()
        c.add("c", np.array([0, 0, 1, 0], np.float32))
        c.remove("b")  # tombstones must not round-trip
        path = str(tmp_path / "corpus.npz")
        c.save(path)
        loaded = DeviceCorpus.load(path)
        assert len(loaded) == 2
        assert loaded.has("a") and loaded.has("c") and not loaded.has("b")
        hits = loaded.search(np.array([0, 0, 1, 0], np.float32), k=1)[0]
        assert hits[0][0] == "c"

    def test_save_empty_and_bad_file(self, tmp_path):
        from nornicdb_tpu.ops.similarity import DeviceCorpus

        c = DeviceCorpus(dims=4)
        path = str(tmp_path / "empty.npz")
        c.save(path)
        assert len(DeviceCorpus.load(path)) == 0
        bad = tmp_path / "bad.npz"
        np.savez_compressed(str(bad), junk=np.zeros(3))
        with pytest.raises(ValueError):
            DeviceCorpus.load(str(bad))

    def test_clear_invalidates_clusters(self):
        """clear() remaps the slot space: stale cluster assignments would
        prune re-added vectors into the wrong buckets."""
        from nornicdb_tpu.ops.similarity import DeviceCorpus

        rng = np.random.default_rng(1)
        c = DeviceCorpus(dims=8)
        c.add_batch([f"o{i}" for i in range(40)],
                    rng.normal(size=(40, 8)).astype(np.float32))
        c.cluster(k=4)
        c.clear()
        assert c._centroids is None and c._assignments is None
        c.add("fresh", np.array([1, 0, 0, 0, 0, 0, 0, 0], np.float32))
        hits = c.search(np.array([1, 0, 0, 0, 0, 0, 0, 0], np.float32),
                        k=1, n_probe=2)[0]
        assert hits and hits[0][0] == "fresh"
