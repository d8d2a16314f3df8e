"""Mesh-sharded serving suite (ISSUE 7): twin-path equivalence between
ShardedCorpus (fused shard_map per-shard top-k + ICI all-gather merge) and
the single-device DeviceCorpus full scan, IVF composed with sharding,
shard lifecycle (rebalance on grow/compact, recovery re-upload), and the
serving-path invariants (one fused dispatch per batch, per-shard patching
after a single-row write).

Runs on the 8-device virtual CPU mesh (conftest forces
--xla_force_host_platform_device_count=8).  The suite is CHAOS-AWARE: under
NORNICDB_FAKE_BACKEND=hang (the CI chaos step / `make chaos`) both corpora
degrade to the exact host path, so the equivalence assertions still hold;
device-internal assertions (dispatch counters, patch-vs-full accounting)
skip — they describe a device that is deliberately unreachable.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nornicdb_tpu import backend as backend_mod
from nornicdb_tpu.backend import BackendManager, FakeHooks
from nornicdb_tpu.errors import DeviceUnavailable
from nornicdb_tpu.ops.similarity import DeviceCorpus, merge_topk
from nornicdb_tpu.parallel import ShardedCorpus, make_mesh

DIMS = 32

# the CI chaos step (`make chaos`) runs this suite with the accelerator
# backend forced to hang — the process-default manager degrades and every
# search serves from host arrays
CHAOS = os.environ.get("NORNICDB_FAKE_BACKEND", "").split(":")[0] in (
    "hang", "fail",
)
needs_device = pytest.mark.skipif(
    CHAOS, reason="device-internal assertion; backend deliberately down"
)

_LIVE_MANAGERS: list[BackendManager] = []


@pytest.fixture(autouse=True)
def _stop_managers():
    yield
    while _LIVE_MANAGERS:
        _LIVE_MANAGERS.pop().stop()


def _mgr(hooks, **kw):
    kw.setdefault("acquire_timeout", 0.5)
    kw.setdefault("probe_interval", 0.03)
    kw.setdefault("probe_timeout", 0.25)
    kw.setdefault("degrade_after", 3)
    kw.setdefault("recover_after", 2)
    mgr = BackendManager(hooks=hooks, **kw)
    _LIVE_MANAGERS.append(mgr)
    return mgr


def _join_reinstall_threads(timeout=10.0):
    """Join any in-flight cluster-reinstall threads: they are daemon
    threads doing device work, and one still inside XLA at interpreter
    exit can abort the process (terminate without an active exception) —
    polling _sivf alone leaves that window open."""
    for t in threading.enumerate():
        if t.name.startswith("nornicdb-") and (
            "reinstall" in t.name or "promote" in t.name
        ):
            t.join(timeout)


def _wait_state(mgr, state, timeout=10.0):
    deadline = time.monotonic() + timeout
    while mgr.state != state and time.monotonic() < deadline:
        time.sleep(0.01)
    assert mgr.state == state, f"never reached {state}, stuck at {mgr.state}"


def _sharded(dims=DIMS, **kw):
    """ShardedCorpus that still constructs under chaos: a degraded default
    manager cannot enumerate mesh devices, so fall back to an explicit
    device list (searches still gate through the manager and serve host)."""
    kw.setdefault("dtype", jnp.float32)
    try:
        return ShardedCorpus(dims=dims, **kw)
    except DeviceUnavailable:
        mesh = make_mesh(devices=jax.devices())
        return ShardedCorpus(dims=dims, mesh=mesh, **kw)


def _rand(n, d=DIMS, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)).astype(np.float32)


def _ids_scores(rows):
    return [i for i, _ in rows], [s for _, s in rows]


def assert_same_results(got, want, atol=1e-5):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        gi, gs = _ids_scores(g)
        wi, ws = _ids_scores(w)
        assert gi == wi, (gi[:5], wi[:5])
        np.testing.assert_allclose(gs, ws, atol=atol)


def _recall(got, want):
    ws = {i for i, _ in want}
    if not ws:
        return 1.0
    return len({i for i, _ in got} & ws) / len(ws)


# --------------------------------------------------------------- equivalence
class TestExactEquivalence:
    """Sharded exact mode must be IDENTICAL (ids, scores within float
    tolerance, stable tie order) to the single-device full scan."""

    # shard-boundary sizes on the 8-shard mesh: local_n = capacity/8 = 128
    # at the first alignment bucket (capacity 1024). One row, one short of
    # a full shard-row block, exactly at it, one over; a near-full and an
    # over-capacity corpus (forces a grow to capacity 2048).
    @pytest.mark.parametrize("n", [1, 127, 128, 129, 1023, 1025])
    @pytest.mark.parametrize("k", [1, 10, 100])
    def test_matches_single_device(self, n, k):
        data = _rand(n, seed=n)
        ids = [f"n{i}" for i in range(n)]
        sc = _sharded()
        dc = DeviceCorpus(dims=DIMS, dtype=jnp.float32)
        sc.add_batch(ids, data)
        dc.add_batch(ids, data)
        queries = _rand(4, seed=n + 1)
        got = sc.search(queries, k=k, exact=True)
        want = dc.search(queries, k=k, exact=True)
        assert_same_results(got, want)

    def test_stable_ties(self):
        """Duplicate vectors across different shards: the merge must order
        tied ids exactly like the single-device lax.top_k (ascending slot
        on equal score)."""
        base = _rand(8, seed=3)
        # 300 rows cycling 8 distinct vectors -> ~37 exact ties per vector,
        # spread across all shards
        data = np.stack([base[i % 8] for i in range(300)])
        ids = [f"t{i:03d}" for i in range(300)]
        sc = _sharded()
        dc = DeviceCorpus(dims=DIMS, dtype=jnp.float32)
        sc.add_batch(ids, data)
        dc.add_batch(ids, data)
        got = sc.search(base[2], k=40, exact=True)
        want = dc.search(base[2], k=40, exact=True)
        assert_same_results(got, want)

    def test_k_exceeds_live_rows_returns_all(self):
        """k far beyond the live rows: every live row comes back once,
        no sentinel/padding ids, scores equal to the single-device path."""
        data = _rand(7, seed=9)
        ids = [f"v{i}" for i in range(7)]
        sc = _sharded()
        dc = DeviceCorpus(dims=DIMS, dtype=jnp.float32)
        sc.add_batch(ids, data)
        dc.add_batch(ids, data)
        got = sc.search(data[0], k=100, exact=True)
        want = dc.search(data[0], k=100, exact=True)
        assert len(got[0]) == 7
        assert sorted(i for i, _ in got[0]) == sorted(ids)
        assert_same_results(got, want)

    def test_interleaved_mutations_stay_equivalent(self):
        """add/remove/overwrite/grow/compact interleaved with searches:
        the twin paths must agree after every step."""
        sc = _sharded(compact_ratio=0.2)
        dc = DeviceCorpus(dims=DIMS, dtype=jnp.float32, compact_ratio=0.2)
        rng = np.random.default_rng(17)
        live = {}
        step = 0
        for round_ in range(6):
            n_new = 220  # crosses the 1024 capacity on round 5 -> grow
            vecs = rng.standard_normal((n_new, DIMS)).astype(np.float32)
            ids = [f"r{round_}_{i}" for i in range(n_new)]
            sc.add_batch(ids, vecs)
            dc.add_batch(ids, vecs)
            live.update(zip(ids, vecs))
            # remove a slice of the previous round (tombstones; on some
            # rounds enough to trip the deferred compaction)
            if round_ > 0:
                victims = [f"r{round_ - 1}_{i}" for i in range(0, 120, 2)]
                for v in victims:
                    sc.remove(v)
                    dc.remove(v)
                    live.pop(v, None)
            # overwrite a surviving id in place
            ow = f"r{round_}_3"
            nv = rng.standard_normal(DIMS).astype(np.float32)
            sc.add(ow, nv)
            dc.add(ow, nv)
            live[ow] = nv
            q = rng.standard_normal((2, DIMS)).astype(np.float32)
            for k in (1, 10, 100):
                got = sc.search(q, k=k, exact=True)
                want = dc.search(q, k=k, exact=True)
                assert_same_results(got, want)
            step += 1
        assert len(sc) == len(dc) == len(live)
        # growth happened and stayed aligned to the shard granularity
        assert sc.capacity % (128 * sc.n_shards) == 0
        assert sc.capacity > 1024


class TestApproxAndIVFRecall:
    def test_approx_recall(self):
        n = 2048
        data = _rand(n, seed=21)
        ids = [f"a{i}" for i in range(n)]
        sc = _sharded()
        dc = DeviceCorpus(dims=DIMS, dtype=jnp.float32)
        sc.add_batch(ids, data)
        dc.add_batch(ids, data)
        queries = _rand(8, seed=22)
        want = dc.search(queries, k=20, exact=True)
        got = sc.search(queries, k=20)  # approx membership
        r = np.mean([_recall(g, w) for g, w in zip(got, want)])
        assert r >= 0.95, r

    def test_sharded_ivf_recall_and_scores(self):
        n = 2048
        data = _rand(n, seed=23)
        ids = [f"c{i}" for i in range(n)]
        sc = _sharded()
        dc = DeviceCorpus(dims=DIMS, dtype=jnp.float32)
        sc.add_batch(ids, data)
        dc.add_batch(ids, data)
        queries = _rand(8, seed=24)
        want = dc.search(queries, k=10, exact=True)
        fitted = sc.cluster(k=16, iters=5)
        if CHAOS:
            assert fitted == 0  # degraded: pruning is device-only
        got = sc.search(queries, k=10, n_probe=12)
        r = np.mean([_recall(g, w) for g, w in zip(got, want)])
        assert r >= 0.95, r
        # returned scores are exact-kind (bf16-GEMM of the TRUE rows, not
        # bin approximations): each returned score matches the f32 cosine
        # of that exact row to well within bf16 GEMM noise
        dn = data / np.linalg.norm(data, axis=1, keepdims=True)
        for qi, row in enumerate(got):
            qn = queries[qi] / np.linalg.norm(queries[qi])
            for i, s in row:
                slot = int(i[1:])
                assert s == pytest.approx(float(dn[slot] @ qn), abs=1e-2)

    @needs_device
    def test_ivf_layout_epoch_invalidation(self):
        """PR 2's layout contract under sharding: plain adds keep the
        fitted layout serving (new rows invisible until recluster);
        overwriting a covered row or compacting drops it."""
        n = 1000  # under the 1024 capacity: a plain add must NOT grow
        data = _rand(n, seed=25)
        ids = [f"e{i}" for i in range(n)]
        sc = _sharded()
        sc.add_batch(ids, data)
        sc.search(data[0], k=1)  # sync
        assert sc.cluster(k=8, iters=3) > 0
        assert sc._sivf is not None
        epoch = sc._layout_epoch
        # plain add: layout still valid (epoch unchanged)
        sc.add("fresh", _rand(1, seed=26)[0])
        assert sc._layout_epoch == epoch
        assert sc._sivf.epoch == sc._layout_epoch
        # pruned search serves (new row merely invisible to pruning)
        assert sc.search(data[3], k=5, n_probe=8)[0][0][0] == "e3"
        # overwrite of a covered row: epoch bumps, layout stops serving
        sc.add("e3", _rand(1, seed=27)[0])
        assert sc._layout_epoch != epoch
        assert sc._sivf.epoch != sc._layout_epoch
        # search still answers (falls back to the full sharded scan)
        res = sc.search(data[5], k=5, n_probe=8)
        assert res[0][0][0] == "e5"


# ----------------------------------------------------- merge sentinel edges
class TestMergeSentinels:
    def test_merge_topk_masks_padding_indices(self):
        """Regression (ISSUE 7 satellite): -inf padding entries from a
        near-empty shard must never surface an index — merge_topk returns
        idx -1 for every non-finite merged value."""
        # shard 0 has 2 real candidates, shard 1 is empty (all -inf) but
        # carries arbitrary garbage indices, as a real shard's top-k does
        vals = np.array([
            [[0.9, 0.5, -np.inf]],          # shard 0, query 0
            [[-np.inf, -np.inf, -np.inf]],  # shard 1 (near-empty)
        ], np.float32)
        idx = np.array([
            [[7, 3, 1]],
            [[128, 129, 130]],              # garbage pointing at live range
        ], np.int32)
        v, i = merge_topk(jnp.asarray(vals), jnp.asarray(idx), 6)
        v, i = np.asarray(v), np.asarray(i)
        assert list(i[0][:2]) == [7, 3]
        assert np.all(i[0][2:] == -1), i
        assert np.all(np.isneginf(v[0][2:]))

    def test_near_empty_shard_never_yields_padding_ids(self):
        """End-to-end at a shard boundary: 129 rows put exactly 1 live row
        on the second shard; k=100 forces every shard to pad.  No id may
        appear twice and no unknown id may appear."""
        n = 129
        data = _rand(n, seed=31)
        ids = [f"p{i}" for i in range(n)]
        sc = _sharded()
        sc.add_batch(ids, data)
        for exact in (True, False):
            res = sc.search(_rand(3, seed=32), k=100, exact=exact)
            for row in res:
                got_ids = [i for i, _ in row]
                assert len(got_ids) == len(set(got_ids))
                assert set(got_ids) <= set(ids)
                assert all(np.isfinite(s) for _, s in row)

    def test_min_similarity_filter_applies(self):
        data = _rand(64, seed=33)
        sc = _sharded()
        sc.add_batch([f"m{i}" for i in range(64)], data)
        res = sc.search(data[7], k=64, min_similarity=0.99)
        assert [i for i, _ in res[0]] == ["m7"]

    def test_host_topk_nan_query_matches_nothing(self):
        """Regression: a NaN query component (NaN survives the
        divide-by-norm normalization) made every boundary comparison in
        host_topk False, crashing the fixed-shape candidate write with a
        broadcast ValueError during DEGRADED_CPU serving.  NaN scores must
        degrade to filterable -inf instead."""
        from nornicdb_tpu.ops.host_search import host_topk

        corpus = _rand(16, seed=34)
        valid = np.ones(16, bool)
        v, i = host_topk(np.full((1, DIMS), np.nan, np.float32), corpus, valid, k=10)
        assert v.shape == (1, 10) and i.shape == (1, 10)
        assert np.all(np.isneginf(v))
        # mixed batch: the finite query is unaffected
        q = np.stack([np.full(DIMS, np.nan, np.float32), corpus[3]])
        v, i = host_topk(q, corpus, valid, k=5)
        assert np.all(np.isneginf(v[0]))
        assert i[1][0] == 3 and np.isfinite(v[1]).all()

    def test_host_topk_sparse_valid_avoids_full_sort_and_stays_exact(self):
        """Regression: with fewer than k finite scores the kth boundary is
        -inf, `s >= -inf` matched EVERY row, and the tie widening
        stable-sorted the entire capacity per query under _sync_lock (10M
        rows for a handful of live ones). Results must still be the live
        rows first, -inf padding after, fixed shape."""
        from nornicdb_tpu.ops.host_search import host_topk

        corpus = _rand(4096, seed=35)
        valid = np.zeros(4096, bool)
        valid[[17, 901, 3000]] = True  # 3 live rows, k=10
        v, i = host_topk(corpus[901][None], corpus, valid, k=10)
        assert v.shape == (1, 10) and i.shape == (1, 10)
        assert i[0][0] == 901  # exact: the query's own row wins
        assert set(i[0][:3]) == {17, 901, 3000}
        assert np.isfinite(v[0][:3]).all()
        assert np.all(np.isneginf(v[0][3:]))  # padding is filterable


# ------------------------------------------------------------ serving paths
class TestServingIntegration:
    @needs_device
    def test_batched_queries_one_dispatch(self):
        """QueryBatcher -> sharded corpus: N queued searches collapse
        into ONE fused device dispatch (the batch rides the (B, D) GEMM)."""
        from nornicdb_tpu.search.batcher import QueryBatcher

        data = _rand(512, seed=41)
        ids = [f"b{i}" for i in range(512)]
        sc = _sharded()
        sc.add_batch(ids, data)
        sc.search(data[0], k=5)  # warm: sync + compile outside the window

        def batch_fn(queries, k, min_sim):
            return sc.search(queries, k=k, min_similarity=min_sim)

        batcher = QueryBatcher(batch_fn, max_batch=64)
        before = sc.shard_stats.dispatches
        # queued while no scan is in flight (the broker's submit-then-wait):
        # the first waiter leads one scan for all twelve
        tickets = [batcher.submit(data[i], k=3) for i in range(12)]
        results = {i: batcher.wait(t) for i, t in enumerate(tickets)}
        assert len(results) == 12
        for i, rows in results.items():
            assert rows[0][0] == f"b{i}"
        assert sc.shard_stats.dispatches - before == 1
        assert batcher.stats.batches == 1
        assert batcher.stats.queries == 12

    @needs_device
    def test_single_write_patches_not_full_upload(self):
        """PR 2's incremental-sync guarantee under sharding: after the
        first sync, overwriting one row patches only its block run — no
        whole-corpus re-upload, and bytes shipped stay bounded."""
        data = _rand(1024, seed=42)
        ids = [f"w{i}" for i in range(1024)]
        sc = _sharded()
        sc.add_batch(ids, data)
        sc.search(data[0], k=5)  # first sync: the one full upload
        assert sc.sync_stats.full_uploads == 1
        patches_before = sc.sync_stats.patches
        bytes_before = sc.sync_stats.bytes_uploaded
        sc.add(ids[7], _rand(1, seed=43)[0])  # one-row overwrite
        res = sc.search(data[3], k=5)
        assert res[0][0][0] == "w3"
        assert sc.sync_stats.full_uploads == 1  # STILL one
        assert sc.sync_stats.patches == patches_before + 1
        patched = sc.sync_stats.bytes_uploaded - bytes_before
        assert patched < data.nbytes / 2, (
            f"patch shipped {patched}B of a {data.nbytes}B corpus"
        )
        # the patched buffer kept its mesh layout
        from jax.sharding import NamedSharding, PartitionSpec as P

        assert sc._dev.sharding == NamedSharding(sc.mesh, P("data", None))

    @needs_device
    def test_rebalance_counted_on_grow_and_compact(self):
        sc = _sharded(compact_ratio=0.05)
        data = _rand(1024, seed=44)
        sc.add_batch([f"g{i}" for i in range(1024)], data)
        sc.search(data[0], k=1)
        assert sc.shard_stats.rebalances == 0
        sc.add("overflow", _rand(1, seed=45)[0])  # capacity full -> grow
        assert sc.shard_stats.rebalances == 1
        for i in range(200):  # trip deferred compaction
            sc.remove(f"g{i}")
        sc.search(data[500], k=1)  # sync runs the pending compaction
        assert sc.shard_stats.rebalances == 2
        st = sc.stats()["shard"]
        assert st["rebalances"] == 2
        assert sum(st["rows_per_shard"]) == len(sc)

    @needs_device
    def test_local_k_oversampling_and_overflow_counter(self):
        """local_k widens each shard's candidate list; a merge where one
        shard saturates its list bumps the overflow counter."""
        # adversarial layout: the best 64 rows all live on shard 0
        # (slots 0..63), so its local top-k saturates any k<=64 merge
        q = _rand(1, seed=46)[0]
        q /= np.linalg.norm(q)
        close = q[None, :] + 0.01 * _rand(64, seed=47)
        far = _rand(960, seed=48) * 0.1 - q[None, :]
        sc = _sharded()
        sc.add_batch([f"c{i}" for i in range(64)], close)
        sc.add_batch([f"f{i}" for i in range(960)], far)
        before = sc.shard_stats.local_k_overflows
        res = sc.search(q, k=32)  # approx, local_k defaults to k
        assert sc.shard_stats.local_k_overflows > before
        assert all(i.startswith("c") for i, _ in res[0])
        # oversampling returns at least as many of the true top-32
        res_over = sc.search(q, k=32, local_k=64)
        assert len(res_over[0]) >= len(res[0])

    def test_local_k_overflow_detectable_beyond_merged_width(self):
        """Regression: with local_k oversampled past the merged width
        (k_prog columns) no shard could ever contribute >= lk entries, so
        the counter read 0 forever — exactly when the operator, following
        the metric's remediation, had raised local_k and still needed the
        saturation signal. One shard filling the whole merged output must
        count."""
        sc = _sharded()
        before = sc.shard_stats.local_k_overflows
        # merged width 16, every winner from shard 0, lk=32 > width
        idx = np.arange(16, dtype=np.int64)[None, :]
        sc._note_local_k_overflows(idx, lk=32, local_n=128)
        assert sc.shard_stats.local_k_overflows == before + 1
        # spread across shards: no saturation, no count
        idx2 = (np.arange(16, dtype=np.int64) * 128)[None, :] % (128 * sc.n_shards)
        sc._note_local_k_overflows(idx2, lk=32, local_n=128)
        assert sc.shard_stats.local_k_overflows == before + 1

    def test_concurrent_dispatches_do_not_deadlock(self):
        """Regression: two host threads launching the collective program
        simultaneously used to interleave their per-device enqueue order
        and deadlock at the all_gather rendezvous (found driving recall()
        against the embed worker).  Dispatches serialize on the process
        dispatch lock; correctness per thread is unaffected."""
        import threading

        data = _rand(512, seed=70)
        ids = [f"d{i}" for i in range(512)]
        sc = _sharded()
        sc.add_batch(ids, data)
        sc.search(data[0], k=4)  # warm + first sync
        errs: list = []

        def worker(base):
            try:
                for j in range(6):
                    q = data[(base + j * 31) % 512]
                    res = sc.search(q, k=4, exact=(base % 2 == 0))
                    assert res[0][0][0] == f"d{(base + j * 31) % 512}"
            except Exception as e:  # surfaced on the main thread
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(4)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 60
        for t in threads:
            t.join(timeout=max(0.1, deadline - time.monotonic()))
        stuck = [t for t in threads if t.is_alive()]
        assert not stuck, "sharded dispatches deadlocked"
        assert not errs, errs

    def test_service_auto_promotes_to_sharded(self):
        from nornicdb_tpu.search.service import SearchConfig, SearchService
        from nornicdb_tpu.storage import MemoryEngine
        from nornicdb_tpu.storage.types import Node

        svc = SearchService(
            MemoryEngine(),
            config=SearchConfig(backend="auto", sharded_min_rows=64),
        )
        rng = np.random.default_rng(49)
        vecs = rng.standard_normal((96, DIMS)).astype(np.float32)
        for i in range(96):
            svc.index_node(Node(
                id=f"n{i}", labels=["D"], properties={"content": f"d{i}"},
                embedding=vecs[i],
            ))
        deadline = time.monotonic() + 20
        state = None
        while time.monotonic() < deadline:
            with svc._lock:
                state = svc._promo_state
            if state in ("done", "unavailable"):
                break
            time.sleep(0.05)
        if CHAOS:
            # degraded backend: promotion defers (or marks unavailable);
            # serving must continue either way
            assert svc.vector_candidates(vecs[5], k=3)[0][0] == "n5"
            return
        assert state == "done", state
        with svc._lock:
            corpus = svc._corpus
        assert hasattr(corpus, "n_shards")
        assert len(corpus) == 96
        # results flow through the promoted corpus
        got = svc.vector_candidates(vecs[5], k=3)
        assert got[0][0] == "n5"
        snap = svc.stats_snapshot()
        assert snap["sharded_promotion"] == "done"
        assert snap["corpus"]["shard"]["promotions"] == 1
        svc.shutdown()

    def test_promotion_carries_cluster_fit(self):
        """An installed IVF fit must survive the promotion swap: without
        the carry-over the sharded corpus has no inverted lists and every
        n_probe search silently full-scans until the next embed-triggered
        recluster (on a read-heavy workload: indefinitely)."""
        from nornicdb_tpu.search.service import SearchConfig, SearchService
        from nornicdb_tpu.storage import MemoryEngine
        from nornicdb_tpu.storage.types import Node

        svc = SearchService(
            MemoryEngine(),
            config=SearchConfig(backend="auto", sharded_min_rows=96),
        )
        rng = np.random.default_rng(62)
        vecs = rng.standard_normal((128, DIMS)).astype(np.float32)

        def _index(lo, hi):
            for i in range(lo, hi):
                svc.index_node(Node(
                    id=f"n{i}", labels=["D"],
                    properties={"content": f"d{i}"}, embedding=vecs[i],
                ))

        _index(0, 64)
        assert svc.recluster(k=4) is not None  # fit lands pre-promotion
        _index(64, 128)  # crosses sharded_min_rows -> promotes
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            with svc._lock:
                if svc._promo_state in ("done", "unavailable"):
                    break
            time.sleep(0.05)
        if CHAOS:
            assert svc.vector_candidates(vecs[5], k=3)[0][0] == "n5"
            return
        with svc._lock:
            corpus, state = svc._corpus, svc._promo_state
        assert state == "done", state
        assert hasattr(corpus, "n_shards")
        deadline = time.monotonic() + 10
        while corpus._sivf is None and time.monotonic() < deadline:
            time.sleep(0.05)  # carry-over runs on the promotion thread
        assert corpus._sivf is not None  # fit survived the swap
        _join_reinstall_threads()
        svc.shutdown()

    def test_service_sharded_backend_stats_surface(self):
        from nornicdb_tpu.search.service import SearchConfig, SearchService
        from nornicdb_tpu.storage import MemoryEngine
        from nornicdb_tpu.storage.types import Node

        svc = SearchService(
            MemoryEngine(), config=SearchConfig(backend="sharded"),
        )
        rng = np.random.default_rng(50)
        for i in range(32):
            svc.index_node(Node(
                id=f"s{i}", labels=["D"], properties={"content": f"s{i}"},
                embedding=rng.standard_normal(DIMS).astype(np.float32),
            ))
        q = rng.standard_normal(DIMS).astype(np.float32)
        assert len(svc.vector_candidates(q, k=5)) <= 5
        snap = svc.stats_snapshot()
        assert "corpus" in snap
        if not CHAOS:
            assert "shard" in snap["corpus"]
            assert snap["corpus"]["shard"]["n_shards"] == 8
        svc.shutdown()

    def test_service_sharded_exact_matches_single_device_unpinned(self):
        """Regression: the SERVICE must honor the exact-mode contract with
        its own corpus construction (no test-pinned dtype).  ShardedCorpus
        defaults to bf16 storage; the serving path must override it to f32
        or exact results silently diverge from the single-device scan."""
        from nornicdb_tpu.search.service import SearchConfig, SearchService
        from nornicdb_tpu.storage import MemoryEngine
        from nornicdb_tpu.storage.types import Node

        sh = SearchService(
            MemoryEngine(), config=SearchConfig(backend="sharded", exact=True),
        )
        sd = SearchService(
            MemoryEngine(), config=SearchConfig(backend="tpu", exact=True),
        )
        rng = np.random.default_rng(53)
        vecs = rng.standard_normal((300, DIMS)).astype(np.float32)
        for i in range(300):
            node = Node(
                id=f"n{i}", labels=["D"], properties={"content": f"d{i}"},
                embedding=vecs[i],
            )
            sh.index_node(node)
            sd.index_node(node)
        if not CHAOS:
            assert jnp.dtype(sh._corpus.dtype) == jnp.float32
        q = rng.standard_normal(DIMS).astype(np.float32)
        for k in (1, 10, 100):
            got = sh.vector_candidates(q, k=k)
            want = sd.vector_candidates(q, k=k)
            assert [i for i, _ in got] == [i for i, _ in want], k
            np.testing.assert_allclose(
                [s for _, s in got], [s for _, s in want], atol=1e-5,
            )
        sh.shutdown()
        sd.shutdown()

    def test_promotion_carries_corpus_dtype(self):
        """Auto-promotion swaps DeviceCorpus -> ShardedCorpus mid-serve; the
        swap must keep the storage dtype (f32) so exact-mode results are
        identical before and after the promotion."""
        from nornicdb_tpu.search.service import SearchConfig, SearchService
        from nornicdb_tpu.storage import MemoryEngine
        from nornicdb_tpu.storage.types import Node

        svc = SearchService(
            MemoryEngine(),
            config=SearchConfig(backend="auto", sharded_min_rows=64,
                                exact=True),
        )
        rng = np.random.default_rng(54)
        vecs = rng.standard_normal((96, DIMS)).astype(np.float32)
        for i in range(96):
            svc.index_node(Node(
                id=f"p{i}", labels=["D"], properties={"content": f"p{i}"},
                embedding=vecs[i],
            ))
        q = rng.standard_normal(DIMS).astype(np.float32)
        before = svc.vector_candidates(q, k=10)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            with svc._lock:
                if svc._promo_state in ("done", "unavailable"):
                    break
            time.sleep(0.05)
        if not CHAOS:
            with svc._lock:
                corpus = svc._corpus
            assert hasattr(corpus, "n_shards")
            assert jnp.dtype(corpus.dtype) == jnp.float32
        after = svc.vector_candidates(q, k=10)
        assert [i for i, _ in after] == [i for i, _ in before]
        np.testing.assert_allclose(
            [s for _, s in after], [s for _, s in before], atol=1e-5,
        )
        svc.shutdown()


# --------------------------------------------------------- chaos / recovery
class TestLifecycle:
    def test_hang_backend_serves_exact_from_host(self):
        """The round-5 deadlock shape, sharded edition: with acquisition
        hung, search must answer exact results from host arrays within the
        acquire budget instead of wedging."""
        hooks = FakeHooks("hang")
        mgr = _mgr(hooks, acquire_timeout=0.3)
        mesh = make_mesh(devices=jax.devices())
        sc = ShardedCorpus(dims=DIMS, mesh=mesh, dtype=jnp.float32,
                           backend=mgr)
        data = _rand(200, seed=51)
        sc.add_batch([f"h{i}" for i in range(200)], data)
        t0 = time.monotonic()
        res = sc.search(data[9], k=5, exact=True)
        assert time.monotonic() - t0 < 5.0
        assert res[0][0][0] == "h9"
        assert mgr.counters.fallbacks >= 1

    def test_recovery_reuploads_shards_and_reinstalls_clusters(self):
        """Degrade -> write while degraded -> recover: the recovery
        registry must re-upload the mesh corpus per shard (full re-shard,
        counted as a rebalance) and re-install the degraded-era cluster
        fit; results match a from-scratch rebuild exactly."""
        hooks = FakeHooks("ok")
        mgr = _mgr(hooks)
        mesh = make_mesh(devices=jax.devices())
        sc = ShardedCorpus(dims=DIMS, mesh=mesh, dtype=jnp.float32,
                           backend=mgr)
        data = _rand(256, seed=52)
        sc.add_batch([f"n{i}" for i in range(256)], data)
        assert sc.search(data[0], k=3)[0][0][0] == "n0"  # device-served

        hooks.set_mode("fail")
        _wait_state(mgr, backend_mod.DEGRADED_CPU)
        extra = _rand(32, seed=53)
        sc.add_batch([f"x{i}" for i in range(32)], extra)  # degraded writes
        sc.remove("n5")
        # a cluster fit delivered while degraded is stashed, not dropped
        centroids = _rand(4, seed=54)
        assigns = {f"n{i}": i % 4 for i in range(256) if i != 5}
        sc.set_clusters(centroids, assigns)
        assert sc._pending_clusters is not None
        assert sc.search(extra[3], k=3)[0][0][0] == "x3"  # host path

        rebal_before = sc.shard_stats.rebalances
        hooks.set_mode("ok")
        _wait_state(mgr, backend_mod.READY)
        deadline = time.monotonic() + 10
        while sc._sivf is None and time.monotonic() < deadline:
            time.sleep(0.05)  # cluster re-install runs on its own thread

        fresh = ShardedCorpus(dims=DIMS, mesh=mesh, dtype=jnp.float32,
                              backend=_mgr(FakeHooks("ok")))
        fresh.add_batch([f"n{i}" for i in range(256)], data)
        fresh.add_batch([f"x{i}" for i in range(32)], extra)
        fresh.remove("n5")
        for q in (data[2], extra[4]):
            got = sc.search(q, k=8, exact=True)
            want = fresh.search(q, k=8, exact=True)
            assert_same_results(got, want)
        assert sc.shard_stats.rebalances > rebal_before
        assert sc._sivf is not None  # stashed fit installed on recovery
        _join_reinstall_threads()
        # probing every cluster makes pruned search exact over the
        # assigned rows (the fit's assignments were arbitrary, so fewer
        # probes could legitimately miss)
        assert sc.search(data[7], k=3, n_probe=4)[0][0][0] == "n7"

    def test_dirty_recovery_reinstalls_fit_after_degraded_compact(self):
        """A degraded-era compaction runs clear_clusters(), dropping the
        stashed fit along with the layout — but capacity is unchanged and
        the mesh buffers survive, so a "dirty" recovery skips the restash
        branch. The id-based host copy of the fit must still be
        reinstalled on READY (regression: it was silently lost and every
        pruned search fell back to the full scan until the next periodic
        recluster)."""
        hooks = FakeHooks("ok")
        mgr = _mgr(hooks, recovery_reupload="dirty")
        mesh = make_mesh(devices=jax.devices())
        sc = ShardedCorpus(dims=DIMS, mesh=mesh, dtype=jnp.float32,
                           backend=mgr)
        data = _rand(256, seed=60)
        sc.add_batch([f"n{i}" for i in range(256)], data)
        assert sc.search(data[0], k=3)[0][0][0] == "n0"  # buffers resident

        hooks.set_mode("fail")
        _wait_state(mgr, backend_mod.DEGRADED_CPU)
        centroids = _rand(4, seed=61)
        sc.set_clusters(centroids, {f"n{i}": i % 4 for i in range(256)})
        assert sc._pending_clusters is not None  # stashed, not installed
        for i in range(100):  # cross compact_ratio while degraded
            sc.remove(f"n{i}")
        assert sc._compact_pending
        sc.search(data[200], k=1)  # host path runs the pending compaction
        assert sc._pending_clusters is None  # stash dropped with the layout

        hooks.set_mode("ok")
        _wait_state(mgr, backend_mod.READY)
        deadline = time.monotonic() + 10
        while sc._sivf is None and time.monotonic() < deadline:
            time.sleep(0.05)  # reinstall runs on its own thread
        assert sc._sivf is not None  # fit recovered from _last_fit_host
        _join_reinstall_threads()
        assert sc.search(data[200], k=3, n_probe=4)[0][0][0] == "n200"


# ----------------------------------------------------------------- metrics
class TestShardTelemetry:
    def test_shard_metric_families_registered(self):
        from nornicdb_tpu.telemetry.metrics import REGISTRY

        text = REGISTRY.render_prometheus()
        for fam in (
            "nornicdb_sharded_search_seconds",
            "nornicdb_sharded_merge_seconds",
            "nornicdb_shard_rebalances_total",
            "nornicdb_shard_local_k_overflows_total",
            "nornicdb_shard_rows",
        ):
            assert f"# TYPE {fam} " in text, fam

    @needs_device
    def test_shard_rows_gauge_tracks_live_rows(self):
        from nornicdb_tpu.telemetry.metrics import REGISTRY

        sc = _sharded()
        data = _rand(300, seed=55)
        sc.add_batch([f"z{i}" for i in range(300)], data)
        sc.search(data[0], k=1)
        st = sc.stats()["shard"]
        assert sum(st["rows_per_shard"]) == 300
        assert len(st["rows_per_shard"]) == sc.n_shards
        text = REGISTRY.render_prometheus()
        assert 'nornicdb_shard_rows{shard="0"}' in text


# ------------------------------------------------------------- slow bench
@pytest.mark.slow
class TestShardedMicrobench:
    @needs_device
    def test_batched_dispatch_amortizes(self):
        """-m slow acceptance: one fused dispatch serves a 64-query batch
        in far less than 64 single-query dispatches, and the single-write
        patch path stays incremental at scale."""
        n, d = 16384, 64
        rng = np.random.default_rng(60)
        data = rng.standard_normal((n, d)).astype(np.float32)
        sc = _sharded(dims=d)
        sc.add_batch([f"v{i}" for i in range(n)], data)
        queries = rng.standard_normal((64, d)).astype(np.float32)
        sc.search(queries[:1], k=100)   # warm single
        sc.search(queries, k=100)       # warm batched shape
        t0 = time.perf_counter()
        for i in range(8):
            sc.search(queries[i:i + 1], k=100)
        t_single = (time.perf_counter() - t0) / 8
        before = sc.shard_stats.dispatches
        t0 = time.perf_counter()
        sc.search(queries, k=100)
        t_batch = time.perf_counter() - t0
        assert sc.shard_stats.dispatches - before == 1
        # 64 queries in one dispatch must beat 64 serial dispatches by a
        # wide margin (amortized launch + merge)
        assert t_batch < 64 * t_single * 0.5, (t_batch, t_single)
        # single-row write after first sync: per-shard patch, no full
        # re-upload, bytes bounded well under the corpus size
        full_before = sc.sync_stats.full_uploads
        bytes_before = sc.sync_stats.bytes_uploaded
        sc.add("v7", data[8])
        sc.search(queries[0], k=10)
        assert sc.sync_stats.full_uploads == full_before
        assert sc.sync_stats.bytes_uploaded - bytes_before < data.nbytes / 8
