"""Incremental device sync: dirty-block H2D patching.

Covers the write→serve spine the full-re-upload path used to serialize:
block-granular dirty tracking, patch-vs-full policy, write-behind uploader,
deferred compaction, block-aware IVF layout invalidation, the sharded mesh
patch path, and equivalence of incremental patching with a from-scratch
full upload across mutation interleavings.
"""

import time

import numpy as np
import pytest

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from nornicdb_tpu.ops.similarity import (
    BLOCK_ROWS,
    DeviceCorpus,
    LANE,
    _coalesce_runs,
)


def _rand(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)).astype(np.float32)


def _rebuild(corpus, **kwargs):
    """From-scratch corpus holding the same logical content: the incremental
    patch path must be indistinguishable from this."""
    fresh = type(corpus)(dims=corpus.dims, **kwargs)
    ids = [i for i in corpus._slot_of]
    if ids:
        fresh.add_batch(ids, np.stack([corpus.get(i) for i in ids]))
    return fresh


def _assert_same_results(a, b, queries, k=5):
    ra = a.search(queries, k=k, exact=True)
    rb = b.search(queries, k=k, exact=True)
    for qa, qb in zip(ra, rb):
        assert [i for i, _ in qa] == [i for i, _ in qb]
        np.testing.assert_allclose(
            [s for _, s in qa], [s for _, s in qb], atol=1e-3
        )


class TestCoalesceRuns:
    def test_single_block(self):
        assert _coalesce_runs([3], 16) == [(3, 1)]

    def test_adjacent_blocks_merge(self):
        [(start, n)] = _coalesce_runs([4, 5, 6], 16)
        assert start <= 4 and start + n >= 7

    def test_small_gaps_merge_large_gaps_split(self):
        assert len(_coalesce_runs([0, 2, 3], 16)) == 1
        assert len(_coalesce_runs([0, 12], 16)) == 2

    def test_padding_never_overruns_capacity(self):
        for blocks in ([15], [13, 14, 15], [0, 15]):
            for start, n in _coalesce_runs(blocks, 16):
                assert 0 <= start and start + n <= 16
                assert n & (n - 1) == 0  # power of two: bounded jit cache

    def test_all_dirty_blocks_covered(self):
        blocks = [1, 2, 9, 30, 31]
        runs = _coalesce_runs(blocks, 32)
        covered = set()
        for start, n in runs:
            covered.update(range(start, start + n))
        assert set(blocks) <= covered


class TestIncrementalPatch:
    def test_writes_patch_instead_of_full_upload(self):
        """Acceptance: after N single adds on a synced corpus, the next
        search uploads O(N * BLOCK_ROWS * dims) bytes, not O(capacity)."""
        dims = 32
        dc = DeviceCorpus(dims=dims, capacity=1024)
        data = _rand(512, dims, 1)
        dc.add_batch([f"n{i}" for i in range(512)], data)
        dc.search(data[0], k=4)
        s = dc.sync_stats
        assert s.full_uploads == 1 and s.patches == 0
        base = s.bytes_uploaded

        for i in range(3):
            dc.add(f"x{i}", _rand(1, dims, 100 + i)[0])
        res = dc.search(dc.get("x1"), k=1)
        assert res[0][0][0] == "x1"
        assert s.full_uploads == 1  # no whole-corpus re-upload
        assert s.patches == 1
        delta = s.bytes_uploaded - base
        row_bytes = dims * 4 + 1  # f32 row + valid byte
        # 3 adds land in at most 2 blocks; padded runs stay block-scale
        assert 0 < delta <= 2 * BLOCK_ROWS * row_bytes
        assert delta < dc.capacity * row_bytes // 4

    def test_patched_results_match_rebuild(self):
        dims = 16
        dc = DeviceCorpus(dims=dims, capacity=512)
        data = _rand(300, dims, 2)
        dc.add_batch([f"n{i}" for i in range(300)], data)
        dc.search(data[0], k=1)  # full sync
        dc.add("late", _rand(1, dims, 50)[0])
        dc.remove("n7")
        dc.add("n12", _rand(1, dims, 51)[0])  # in-place overwrite
        _assert_same_results(dc, _rebuild(dc), _rand(4, dims, 3))
        assert dc.sync_stats.full_uploads == 1

    def test_remove_patch_hides_row(self):
        dims = 8
        dc = DeviceCorpus(dims=dims, capacity=256)
        data = _rand(100, dims, 4)
        dc.add_batch([f"n{i}" for i in range(100)], data)
        dc.search(data[0], k=1)
        dc.remove("n42")
        res = dc.search(data[42], k=10)
        assert all(i != "n42" for i, _ in res[0])
        assert dc.sync_stats.full_uploads == 1

    def test_grow_forces_full_upload(self):
        dims = 8
        dc = DeviceCorpus(dims=dims, capacity=LANE)
        dc.add_batch([f"n{i}" for i in range(LANE)], _rand(LANE, dims, 5))
        dc.search(_rand(1, dims, 6)[0], k=1)
        dc.add("overflow", _rand(1, dims, 7)[0])  # triggers _grow
        res = dc.search(dc.get("overflow"), k=1)
        assert res[0][0][0] == "overflow"
        assert dc.sync_stats.full_uploads == 2

    def test_majority_dirty_falls_back_to_full(self):
        dims = 8
        dc = DeviceCorpus(dims=dims, capacity=512)
        dc.add_batch([f"n{i}" for i in range(512)], _rand(512, dims, 8))
        dc.search(_rand(1, dims, 9)[0], k=1)
        # rewrite most rows: patching >50% of blocks costs more than one
        # contiguous transfer, so the driver must choose a full upload
        dc.add_batch(
            [f"n{i}" for i in range(400)], _rand(400, dims, 10)
        )
        dc.search(_rand(1, dims, 11)[0], k=1)
        assert dc.sync_stats.full_uploads == 2
        assert dc.sync_stats.patches == 0

    def test_quantized_mirror_patches_with_corpus(self):
        dims = 64
        dc = DeviceCorpus(dims=dims, capacity=1024, quantize=True)
        data = _rand(512, dims, 12)
        dc.add_batch([f"v{i}" for i in range(512)], data)
        dc.search(data[0], k=1, streaming="interpret")
        assert dc.sync_stats.full_uploads == 1
        nv = _rand(1, dims, 13)[0]
        dc.add("fresh", nv)
        res = dc.search(nv, k=1, streaming="interpret")
        assert res[0][0][0] == "fresh"
        assert abs(res[0][0][1] - 1.0) < 0.02
        assert dc.sync_stats.full_uploads == 1 and dc.sync_stats.patches == 1
        # per-row quantization means block-local requantization matches a
        # full requantize: int8 codes exactly; scales to within one float
        # ulp (XLA lowers the division differently per program shape)
        ref = _rebuild(dc, capacity=1024, quantize=True)
        ref.search(nv, k=1, streaming="interpret")  # forces ref's full sync
        np.testing.assert_array_equal(
            np.asarray(dc._dev_i8[0]), np.asarray(ref._dev_i8[0])
        )
        np.testing.assert_allclose(
            np.asarray(dc._dev_i8[1]), np.asarray(ref._dev_i8[1]), rtol=1e-6
        )


class TestEquivalenceInterleavings:
    """Incremental patching across add/remove/grow/compact/quantize/cluster
    interleavings must be indistinguishable from a from-scratch upload."""

    @pytest.mark.parametrize("quantize", [False, True])
    def test_random_interleaving(self, quantize):
        dims = 16
        rng = np.random.default_rng(20)
        dc = DeviceCorpus(dims=dims, capacity=256, compact_ratio=0.4,
                          quantize=quantize)
        live = set()
        counter = 0

        def _vec(seed):
            return _rand(1, dims, seed)[0]

        for step in range(120):
            op = rng.integers(0, 10)
            if op <= 4 or not live:  # add new
                dc.add(f"id{counter}", _vec(counter))
                live.add(f"id{counter}")
                counter += 1
            elif op <= 6:  # remove (may set compaction pending)
                victim = sorted(live)[int(rng.integers(0, len(live)))]
                dc.remove(victim)
                live.discard(victim)
            elif op == 7:  # overwrite in place
                victim = sorted(live)[int(rng.integers(0, len(live)))]
                dc.add(victim, _vec(1000 + step))
            elif op == 8:  # batch ingest (can trigger grow)
                ids = [f"id{counter + j}" for j in range(17)]
                dc.add_batch(ids, _rand(17, dims, 2000 + step))
                live.update(ids)
                counter += 17
            else:  # interleave a search so syncs happen mid-stream
                dc.search(_vec(3000 + step), k=3)
            if step in (40, 80) and len(live) > 10:
                dc.cluster(k=4)
        _assert_same_results(
            dc, _rebuild(dc, quantize=quantize), _rand(5, dims, 21)
        )
        # the interleaved syncs actually exercised the patch path
        assert dc.sync_stats.patches >= 1

    def test_clear_then_reuse(self):
        dims = 8
        dc = DeviceCorpus(dims=dims, capacity=256)
        dc.add_batch([f"a{i}" for i in range(64)], _rand(64, dims, 22))
        dc.search(_rand(1, dims, 23)[0], k=1)
        dc.clear()
        dc.add("solo", _rand(1, dims, 24)[0])
        res = dc.search(dc.get("solo"), k=1)
        assert res[0][0][0] == "solo"
        _assert_same_results(dc, _rebuild(dc), _rand(2, dims, 25))


class TestLayoutEpoch:
    """Block-aware IVF invalidation: plain add/remove keep the fitted
    layout; only covered-row overwrites and slot remaps invalidate it."""

    def _clustered(self, dims=16):
        rng = np.random.default_rng(30)
        dc = DeviceCorpus(dims=dims, capacity=512)
        centers = np.eye(3, dims, dtype=np.float32) * 10
        data = np.concatenate([
            centers[i] + rng.normal(0, 0.3, (40, dims)).astype(np.float32)
            for i in range(3)
        ])
        dc.add_batch([f"n{i}" for i in range(120)], data)
        assert dc.cluster(k=3, iters=8) == 3
        return dc, data

    def test_single_add_keeps_layout(self):
        dc, data = self._clustered()
        layout = dc._ivf
        dc.add("new", _rand(1, 16, 31)[0])
        assert dc._ivf is layout
        assert layout.epoch == dc._layout_epoch  # still served
        res = dc.search(data[5], k=3, n_probe=1)
        assert res[0][0][0] == "n5"

    def test_single_remove_keeps_layout_and_hides_row(self):
        dc, data = self._clustered()
        layout = dc._ivf
        dc.remove("n17")
        assert layout.epoch == dc._layout_epoch
        res = dc.search(data[17], k=5, n_probe=2)
        assert all(i != "n17" for i, _ in res[0])

    def test_overwrite_of_clustered_row_invalidates(self):
        dc, data = self._clustered()
        layout = dc._ivf
        dc.add("n5", _rand(1, 16, 32)[0])
        assert layout.epoch != dc._layout_epoch  # stale copy must not serve

    def test_compact_and_grow_invalidate(self):
        dc, data = self._clustered()
        for i in range(60):
            dc.remove(f"n{i}")
        dc.search(data[70], k=1)  # deferred compaction runs here
        assert dc._ivf is None  # slot remap dropped the layout
        dc2, _ = self._clustered()
        dc2.add_batch([f"g{i}" for i in range(600)], _rand(600, 16, 33))
        assert dc2._ivf is None  # grow dropped it


class TestDeferredCompaction:
    def test_remove_defers_compaction_to_sync(self):
        dc = DeviceCorpus(dims=8, capacity=256, compact_ratio=0.2)
        data = _rand(40, 8, 40)
        dc.add_batch([f"n{i}" for i in range(40)], data)
        for i in range(20):
            dc.remove(f"n{i}")
        assert dc._compact_pending and dc._tombstones == 20
        res = dc.search(data[30], k=1)
        assert res[0][0][0] == "n30"
        assert dc._tombstones == 0 and not dc._compact_pending
        assert len(dc._ids) == 20

    def test_churn_without_searches_stays_bounded(self):
        """Write-only remove+add churn (no searches to trigger the deferred
        compaction) must reclaim tombstones before growing capacity."""
        dc = DeviceCorpus(dims=8, capacity=LANE, compact_ratio=0.2)
        for i in range(LANE):
            dc.add(f"n{i}", _rand(1, 8, i)[0])
        for round_ in range(6):
            for i in range(LANE // 2):
                dc.remove(f"n{round_}x{i}" if round_ else f"n{i}")
            for i in range(LANE // 2):
                dc.add(f"n{round_ + 1}x{i}", _rand(1, 8, 500 + i)[0])
        # live count never exceeds LANE, so compact-before-grow keeps
        # capacity at no more than one doubling
        assert dc.capacity <= 2 * LANE

    def test_uploader_runs_pending_compaction(self):
        dc = DeviceCorpus(dims=8, capacity=256, compact_ratio=0.2)
        dc.add_batch([f"n{i}" for i in range(40)], _rand(40, 8, 41))
        dc.start_uploader(interval=0.001)
        try:
            for i in range(20):
                dc.remove(f"n{i}")
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and dc._compact_pending:
                time.sleep(0.01)
            assert not dc._compact_pending
            assert dc._tombstones == 0
        finally:
            dc.stop_uploader()


class TestWriteBehindUploader:
    def test_uploader_drains_dirty_blocks(self):
        dims = 8
        dc = DeviceCorpus(dims=dims, capacity=512)
        dc.add_batch([f"n{i}" for i in range(256)], _rand(256, dims, 50))
        dc.search(_rand(1, dims, 51)[0], k=1)
        dc.start_uploader(interval=0.001)
        try:
            for i in range(5):
                dc.add(f"w{i}", _rand(1, dims, 60 + i)[0])
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                with dc._sync_lock:
                    if not dc._dirty_blocks and not dc._full_dirty:
                        break
                time.sleep(0.01)
            with dc._sync_lock:
                assert not dc._dirty_blocks and not dc._full_dirty
            assert dc.sync_stats.uploader_runs >= 1
            # a query now finds a clean buffer: bounded (zero) extra staging
            stall_before = dc.sync_stats.query_stall_s
            res = dc.search(dc.get("w4"), k=1)
            assert res[0][0][0] == "w4"
            assert dc.sync_stats.full_uploads == 1
        finally:
            dc.stop_uploader()

    def test_search_during_write_burst_is_consistent(self):
        """Searches racing the uploader must always see a coherent corpus
        (old or new snapshot, never a half-patched one)."""
        dims = 8
        dc = DeviceCorpus(dims=dims, capacity=1024)
        base = _rand(256, dims, 70)
        dc.add_batch([f"n{i}" for i in range(256)], base)
        dc.search(base[0], k=1)
        dc.start_uploader(interval=0.0)
        try:
            for i in range(40):
                dc.add(f"burst{i}", _rand(1, dims, 80 + i)[0])
                res = dc.search(base[3], k=1)
                assert res[0][0][0] == "n3"  # stable row always findable
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                res = dc.search(dc.get("burst39"), k=1)
                if res[0] and res[0][0][0] == "burst39":
                    break
                time.sleep(0.01)
            assert res[0][0][0] == "burst39"
        finally:
            dc.stop_uploader()

    def test_device_arrays_disables_donation(self):
        """Legacy device_arrays() hands out unscoped buffer refs; donation
        must stay off afterwards or a patch would free what callers hold."""
        dc = DeviceCorpus(dims=8, capacity=512)
        dc.add_batch([f"n{i}" for i in range(256)], _rand(256, 8, 95))
        leaked, _ = dc.device_arrays()
        assert not dc._donation_ok
        dc.add("late", _rand(1, 8, 96)[0])
        dc.search(dc.get("late"), k=1)  # patches without donating
        assert dc.sync_stats.patches == 1
        # the leaked reference must still be alive and readable
        assert np.isfinite(np.asarray(leaked)).all()

    def test_service_write_behind_config(self):
        from nornicdb_tpu.search.service import SearchConfig, SearchService
        from nornicdb_tpu.storage.types import Node

        svc = SearchService(
            storage=None, dims=8,
            config=SearchConfig(write_behind=True),
        )
        svc.index_node(Node(id="a", embedding=_rand(1, 8, 90)[0]))
        try:
            assert svc._corpus._uploader is not None
            snap = svc.stats_snapshot()
            assert snap["indexed"] == 1
            assert "sync" in snap["corpus"]
            assert snap["corpus"]["sync"]["full_uploads"] == 0
        finally:
            svc._corpus.stop_uploader()


class TestShardedPatchPath:
    """Per-shard patching on the multi-device CPU mesh."""

    def test_patch_after_full_sync(self):
        from nornicdb_tpu.parallel import ShardedCorpus, make_mesh

        mesh = make_mesh()
        sc = ShardedCorpus(dims=16, mesh=mesh, dtype=jnp.float32)
        data = _rand(2000, 16, 100)  # capacity 2048 = 2 * align(1024)
        sc.add_batch([f"n{i}" for i in range(2000)], data)
        sc.search(data[0], k=3)
        assert sc.sync_stats.full_uploads == 1
        nv = _rand(1, 16, 101)[0]
        sc.add("fresh", nv)
        res = sc.search(nv, k=1)
        assert res[0][0][0] == "fresh"
        assert sc.sync_stats.full_uploads == 1
        assert sc.sync_stats.patches == 1
        # the patched buffer kept its mesh layout
        assert sc._dev.sharding == NamedSharding(mesh, P("data", None))
        assert sc._dev_valid.sharding == NamedSharding(mesh, P("data"))

    def test_sharded_matches_single_device_after_patches(self):
        from nornicdb_tpu.ops import DeviceCorpus as DC
        from nornicdb_tpu.parallel import ShardedCorpus, make_mesh

        sc = ShardedCorpus(dims=16, mesh=make_mesh(), dtype=jnp.float32)
        dc = DC(dims=16, capacity=2048)
        data = _rand(1500, 16, 102)
        ids = [f"n{i}" for i in range(1500)]
        sc.add_batch(ids, data)
        dc.add_batch(ids, data)
        sc.search(data[0], k=1)
        dc.search(data[0], k=1)
        for i in range(4):  # patched on both paths
            v = _rand(1, 16, 110 + i)[0]
            sc.add(f"p{i}", v)
            dc.add(f"p{i}", v)
        sc.remove("n9")
        dc.remove("n9")
        q = data[123]
        got = sc.search(q, k=10, exact=True)[0]
        want = dc.search(q, k=10, exact=True)[0]
        assert [g[0] for g in got] == [w[0] for w in want]
        assert sc.sync_stats.patches >= 1


@pytest.mark.slow
class TestSyncMicrobench:
    def test_patched_vs_full_sync_latency(self, capsys):
        """Records patched-sync vs full-sync latency at >=100k rows. The
        whole point of the tentpole: a single-row write must not cost a
        whole-corpus re-upload on the next query."""
        import json
        import time as _t

        n, dims = 131_072, 64
        dc = DeviceCorpus(dims=dims, capacity=n)
        dc.add_batch([f"n{i}" for i in range(n - LANE)], _rand(n - LANE, dims, 120))
        dc._sync()
        # warm both programs so we time steady-state, not compilation
        dc.add("warm", _rand(1, dims, 121)[0])
        dc._sync()
        with dc._sync_lock:
            dc._mark_all_dirty()
        dc._sync()
        dc._dev.block_until_ready()  # timers must not absorb prior staging

        t0 = _t.perf_counter()
        dc.add("probe", _rand(1, dims, 122)[0])
        dc._sync()
        dc._dev.block_until_ready()
        patched_s = _t.perf_counter() - t0

        with dc._sync_lock:
            dc._mark_all_dirty()
        t0 = _t.perf_counter()
        dc._sync()
        dc._dev.block_until_ready()
        full_s = _t.perf_counter() - t0

        record = {
            "bench": "device_sync_patch_vs_full",
            "rows": n,
            "dims": dims,
            "patched_sync_s": round(patched_s, 6),
            "full_sync_s": round(full_s, 6),
            "speedup": round(full_s / max(patched_s, 1e-9), 1),
        }
        with capsys.disabled():
            print(json.dumps(record))
        assert patched_s < full_s


class TestDonationExceptionPaths:
    """NL-JAX04 regression: a failing donated patch must not leave the
    consumed buffer referenced.  _apply_patch drops the resident buffers
    on ANY exception so _device_ready() reports false and the next sync
    rebuilds via _upload_full instead of patching a poisoned buffer.

    Red without the try/except in _apply_patch: the assertion that the
    buffers were dropped fails (self._dev still points at the donated
    input)."""

    def _boom(self, *a, **k):
        raise RuntimeError("injected patch failure")

    def test_device_corpus_failed_patch_drops_and_recovers(
            self, monkeypatch):
        from nornicdb_tpu.ops import similarity as sim

        dims = 16
        dc = DeviceCorpus(dims=dims, capacity=512)
        data = _rand(300, dims, 20)
        dc.add_batch([f"n{i}" for i in range(300)], data)
        dc.search(data[0], k=1)  # full sync: resident buffers exist
        assert dc._dev is not None

        monkeypatch.setattr(sim, "_patch_rows_donated", self._boom)
        monkeypatch.setattr(sim, "_patch_rows", self._boom)
        with pytest.raises(RuntimeError, match="injected"):
            dc._apply_patch(
                0, data[:1], np.ones(1, bool), donate=True)
        # the donated inputs may be CONSUMED: no reference survives
        assert dc._dev is None
        assert dc._dev_valid is None
        assert dc._dev_i8 is None

        # recovery: with the failure gone, the next search rebuilds via
        # _upload_full and serves the same results
        monkeypatch.undo()
        dc.add("late", _rand(1, dims, 21)[0])
        res = dc.search(dc.get("late"), k=1)
        assert res[0][0][0] == "late"
        assert dc.sync_stats.full_uploads >= 2

    def test_sharded_corpus_failed_patch_drops_and_recovers(
            self, monkeypatch):
        from nornicdb_tpu.parallel import ShardedCorpus, make_mesh
        from nornicdb_tpu.parallel import sharded_index as si

        sc = ShardedCorpus(dims=16, mesh=make_mesh(), dtype=jnp.float32)
        data = _rand(1200, 16, 22)
        sc.add_batch([f"n{i}" for i in range(1200)], data)
        sc.search(data[0], k=1)
        assert sc._dev is not None

        monkeypatch.setattr(si, "_patch_rows_donated", self._boom)
        monkeypatch.setattr(si, "_patch_rows", self._boom)
        with pytest.raises(RuntimeError, match="injected"):
            sc._apply_patch(
                0, data[:1], np.ones(1, bool), donate=True)
        assert sc._dev is None
        assert sc._dev_valid is None
        assert sc._dev_i8 is None

        monkeypatch.undo()
        nv = _rand(1, 16, 23)[0]
        sc.add("fresh", nv)
        res = sc.search(nv, k=1)
        assert res[0][0][0] == "fresh"
        assert sc.sync_stats.full_uploads >= 2
