"""Continuous ragged batching engine tests (ISSUE 8 tentpole).

Covers the acceptance criteria:

* ragged-packed embedding is numerically equivalent to the per-request
  path (tolerance-bounded, incl. segment-boundary neighbors and
  max-length texts, f32 tight + bf16 loose);
* admission control saturation: a full queue sheds with
  :class:`ResourceExhausted` (HTTP 429 at the edge), never a wedge;
* the distilled student is only selectable when its eval MRR clears the
  configured threshold (red-green both sides of the gate);
* under a hung accelerator backend the engine sheds or serves from CPU
  within the deadline — no request blocks indefinitely.  The whole file
  is chaos-aware: it passes under ``NORNICDB_FAKE_BACKEND=hang`` (CI
  chaos step / ``make chaos``) because every TPUEmbedder here gets an
  injected manager with a short acquire timeout.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from nornicdb_tpu.backend import BackendManager, FakeHooks
from nornicdb_tpu.embed.base import HashEmbedder, TPUEmbedder
from nornicdb_tpu.errors import (
    ClosedError,
    ResourceExhausted,
    StudentGateError,
)
from nornicdb_tpu.models import bge_m3
from nornicdb_tpu.serving import (
    RaggedPacker,
    ServingEngine,
    builtin_eval_suite,
    evaluate_embedder,
    gate_student,
    unpack_results,
)

DIMS = 64

F32_CFG = bge_m3.BgeConfig(
    vocab_size=512, hidden=DIMS, layers=2, heads=4, intermediate=128,
    max_positions=512, dims=DIMS, dtype="float32",
)

_LIVE_MANAGERS: list[BackendManager] = []
_LIVE_ENGINES: list[ServingEngine] = []


@pytest.fixture(autouse=True)
def _cleanup():
    yield
    while _LIVE_ENGINES:
        _LIVE_ENGINES.pop().stop()
    while _LIVE_MANAGERS:
        _LIVE_MANAGERS.pop().stop()


def _mgr(hooks=None, **kw):
    kw.setdefault("acquire_timeout", 0.5)
    kw.setdefault("probe_interval", 0.05)
    kw.setdefault("probe_timeout", 0.4)
    mgr = BackendManager(hooks=hooks or FakeHooks("ok"), **kw)
    _LIVE_MANAGERS.append(mgr)
    return mgr


def _embedder(cfg=F32_CFG, **kw):
    kw.setdefault("backend", _mgr())
    return TPUEmbedder(cfg=cfg, **kw)


class _Cfg:
    """ServingConfig stand-in with test-friendly defaults (the real
    dataclass works too; this keeps knobs explicit per test)."""

    enabled = True
    embedder = "full"
    student_model_dir = ""
    student_min_mrr = 0.6
    student_eval_suite = ""
    max_queue = 4096
    max_queue_tokens = 262144
    deadline_ms = 10_000.0
    batch_wait_ms = 1.0
    max_batch_tokens = 2048
    max_rows = 8
    staging_depth = 2

    def __init__(self, **kw):
        for k, v in kw.items():
            assert hasattr(self, k), k
            setattr(self, k, v)


def _engine(inner=None, **cfg_kw) -> ServingEngine:
    eng = ServingEngine(inner or _embedder(), _Cfg(**cfg_kw))
    _LIVE_ENGINES.append(eng)
    return eng


MIXED_TEXTS = [
    "x",
    "short one",
    "two neighbors packed tight",
    "a slightly longer sentence with a dozen or so words inside it",
    " ".join(f"w{i}" for i in range(60)),
    " ".join(f"mid{i}" for i in range(120)),
    " ".join(f"long{i}" for i in range(505)),  # max-length row
    "tail text after the long one",
]


# ---------------------------------------------------------------- packer
class TestRaggedPacker:
    def _packer(self, **kw):
        kw.setdefault("pad_id", 1)
        kw.setdefault("pad_token_id", 1)
        return RaggedPacker(**kw)

    def test_pack_shapes_are_classes(self):
        p = self._packer(max_len=512, max_rows=16)
        seqs = [[5] * n for n in (3, 10, 30, 64, 100, 3, 7)]
        pack = p.pack(seqs)
        r, c = pack.ids.shape
        assert r & (r - 1) == 0  # power of two rows
        assert c in p.capacities
        assert len(pack.cls_rows) & (len(pack.cls_rows) - 1) == 0

    def test_every_token_lands_once(self):
        p = self._packer(max_len=128)
        seqs = [[i + 2] * (i + 1) for i in range(9)]
        pack = p.pack(seqs)
        assert pack.tokens == sum(len(s) for s in seqs)
        # segment s+1 occupies exactly len(seqs[order[s]]) cells
        for slot, idx in enumerate(pack.order):
            assert int((pack.seg == slot + 1).sum()) == len(seqs[idx])

    def test_positions_restart_per_segment(self):
        p = self._packer(pad_token_id=1, max_len=64)
        pack = p.pack([[9, 9, 9], [8, 8]])
        for slot in (1, 2):
            pos = pack.positions[pack.seg == slot]
            assert list(pos) == [i + 2 for i in range(len(pos))]

    def test_plan_respects_budget_and_fifo(self):
        p = self._packer(max_len=128, max_rows=4)
        lengths = [100, 100, 100, 100, 100, 100]
        take, r, c = p.plan(lengths, budget_tokens=250)
        assert take < len(lengths)  # budget trimmed the FIFO prefix
        assert c == 128 and r >= 1

    def test_plan_row_cap_defers_overflow(self):
        p = self._packer(max_len=128, max_rows=4)
        # 6 full rows of work against a 4-row cap: 4 now, 2 later
        take, r, c = p.plan([120] * 6)
        assert take == 4 and r == 4

    def test_plan_row_class_stays_tight(self):
        p = self._packer(max_len=128, max_rows=16)
        take, r, c = p.plan([120] * 5)
        assert take == 5
        assert 5 <= r <= 6  # nearest row class above the used rows

    def test_oversized_foreign_seq_truncates(self):
        p = self._packer(max_len=64)
        pack = p.pack([[7] * 500])
        assert pack.ids.shape[1] == 64
        assert pack.tokens == 64

    def test_off_grid_max_len_gets_own_class(self):
        """Trained/student checkpoints use max_len = max_positions - 8
        (e.g. 506): texts longer than the largest standard class must
        NOT be truncated — max_len itself becomes the final class."""
        p = self._packer(max_len=506)
        assert p.capacities[-1] == 506
        pack = p.pack([[7] * 300])
        assert pack.tokens == 300
        assert pack.ids.shape[1] == 506


# ------------------------------------------------------- equivalence
class TestRaggedEquivalence:
    def _pack_for(self, e, texts):
        seqs = [
            e.tokenizer.encode(t, max_len=e.max_len) or [e.tokenizer.pad_id]
            for t in texts
        ]
        packer = RaggedPacker(
            pad_id=e.tokenizer.pad_id,
            pad_token_id=e.cfg.pad_token_id,
            max_len=e.max_len,
        )
        return packer.pack(seqs)

    def test_f32_packed_matches_per_request_tight(self):
        e = _embedder()
        pack = self._pack_for(e, MIXED_TEXTS)
        ragged = unpack_results(
            pack, e.embed_packed(pack), n_inputs=len(MIXED_TEXTS)
        )
        for i, text in enumerate(MIXED_TEXTS):
            ref = e.embed(text)
            cos = float(np.dot(ragged[i], ref))
            assert cos > 1.0 - 1e-5, (i, cos)
            np.testing.assert_allclose(ragged[i], ref, atol=1e-4)

    def test_bf16_default_config_loose_bound(self):
        e = _embedder(cfg=bge_m3.BGE_SMALL)
        texts = MIXED_TEXTS[:6]
        pack = self._pack_for(e, texts)
        ragged = unpack_results(pack, e.embed_packed(pack), n_inputs=len(texts))
        for i, text in enumerate(texts):
            cos = float(np.dot(ragged[i], e.embed(text)))
            assert cos > 0.99, (i, cos)

    def test_segment_boundary_no_leak(self):
        """Adjacent segments in one row must not bleed into each other:
        the same text embeds identically regardless of its neighbors."""
        e = _embedder()
        probe = "the probe text under test"
        alone = e.embed(probe)
        for neighbors in (
            ["aaaa bbbb cccc"], ["x"], [" ".join(f"n{i}" for i in range(25))],
        ):
            pack = self._pack_for(e, [neighbors[0], probe, neighbors[0]])
            emb = unpack_results(pack, e.embed_packed(pack), n_inputs=3)
            np.testing.assert_allclose(emb[1], alone, atol=1e-4)

    def test_single_program_per_pack(self):
        e = _embedder()
        before = e.stats["packed_dispatches"]
        pack = self._pack_for(e, MIXED_TEXTS)
        e.embed_packed(pack)
        assert e.stats["packed_dispatches"] == before + 1
        # repeated same-shape packs add no new program classes
        shapes_before = set(e.packed_shapes)
        e.embed_packed(self._pack_for(e, MIXED_TEXTS))
        assert set(e.packed_shapes) == shapes_before


# ------------------------------------------------------------ engine
class TestServingEngine:
    def test_engine_matches_inner(self):
        inner = _embedder()
        eng = _engine(inner)
        out = eng.embed_batch(MIXED_TEXTS)
        ref = inner.embed_batch(MIXED_TEXTS)
        for a, b in zip(out, ref):
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_concurrent_callers_coalesce(self):
        inner = _embedder()
        eng = _engine(inner, batch_wait_ms=20.0)
        n = 12
        res: list = [None] * n
        errs: list = []

        def call(i):
            try:
                res[i] = eng.embed_batch([f"text number {i} here"])[0]
            except Exception as exc:  # pragma: no cover - fail loudly
                errs.append(exc)

        ts = [threading.Thread(target=call, args=(i,)) for i in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert not errs
        assert all(r is not None for r in res)
        # continuous batching: far fewer device batches than callers
        assert eng.stats.batches < n
        # results are per-caller correct, not leader-only
        for i in range(n):
            np.testing.assert_allclose(
                res[i], inner.embed(f"text number {i} here"), atol=1e-4
            )

    def test_hash_embedder_fallback_path(self):
        inner = HashEmbedder(32)
        eng = _engine(inner)
        out = eng.embed_batch(["a b c", "d e"])
        ref = inner.embed_batch(["a b c", "d e"])
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(a, b)
        assert eng.stats.packed_batches == 0  # no packed path for hash

    def test_queue_full_sheds_never_wedges(self):
        class SlowEmbedder(HashEmbedder):
            def embed_batch(self, texts):
                time.sleep(0.15)
                return super().embed_batch(texts)

        eng = _engine(
            SlowEmbedder(16), max_queue=4, max_queue_tokens=100_000,
            batch_wait_ms=0.0, deadline_ms=30_000.0,
        )
        held: list = []
        shed = 0

        def caller():
            try:
                held.append(eng.embed_batch([f"t {len(held)} word"] * 2))
            except ResourceExhausted:
                pass

        ts = [threading.Thread(target=caller) for _ in range(12)]
        for t in ts:
            t.start()
        # saturate from this thread too: at least one submit must shed
        for _ in range(20):
            try:
                eng.embed_batch(["x y z"] * 3)
            except ResourceExhausted as e:
                assert e.reason == "queue_full"
                shed += 1
        for t in ts:
            t.join(timeout=30)
        assert shed > 0
        assert eng.stats.sheds_queue_full > 0
        # never a wedge: the engine still serves after saturation
        out = eng.embed_batch(["post saturation text"])
        assert out[0].shape == (16,)

    def test_off_grid_max_len_engine_equivalence(self):
        """A 300-token text through an engine whose embedder has
        max_len=506 must match the per-request path (no truncation)."""
        inner = _embedder(max_len=506)
        eng = _engine(inner)
        text = " ".join(f"w{i}" for i in range(298))
        out = eng.embed_batch([text])[0]
        np.testing.assert_allclose(out, inner.embed(text), atol=1e-4)

    def test_queue_gauges_reset_after_shed_drain(self):
        from nornicdb_tpu.telemetry.metrics import REGISTRY

        class StuckEmbedder(HashEmbedder):
            def embed_batch(self, texts):
                time.sleep(5.0)
                return super().embed_batch(texts)

        eng = _engine(
            StuckEmbedder(8), deadline_ms=300.0, batch_wait_ms=0.0,
            staging_depth=1,
        )
        # several concurrent requests: the first occupies compute (stuck
        # 5s), the next fills the depth-1 staging buffer, the rest age
        # out IN THE QUEUE — the _shed_expired path must both fail them
        # and reset the depth gauges
        def caller():
            with pytest.raises(ResourceExhausted):
                eng.embed_batch(["doomed text"] * 2)

        ts = [threading.Thread(target=caller) for _ in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        # wait for the staging loop to shed the expired queue remainder
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with eng._lock:
                if eng._queued_texts == 0:
                    break
            time.sleep(0.05)
        assert eng.stats.sheds_deadline > 0
        text = REGISTRY.render_prometheus()
        depth = [
            l for l in text.splitlines()
            if l.startswith("nornicdb_serving_queue_depth ")
        ]
        assert depth and float(depth[0].split()[-1]) == 0.0, depth

    def test_deadline_sheds_bounded_time(self):
        class StuckEmbedder(HashEmbedder):
            def embed_batch(self, texts):
                time.sleep(5.0)
                return super().embed_batch(texts)

        # this test exercises the POST-dispatch deadline shed; a model
        # warmed on earlier slow-embedder tests would shed at submit
        # (predicted_deadline) before the path under test is reached
        from nornicdb_tpu.telemetry.costmodel import COST_MODEL
        COST_MODEL.reset()
        eng = _engine(StuckEmbedder(8), deadline_ms=300.0, batch_wait_ms=0.0)
        t0 = time.monotonic()
        with pytest.raises(ResourceExhausted) as ei:
            eng.embed_batch(["will expire"])
        assert ei.value.reason == "deadline"
        # deadline + 1s grace + wait granularity, not the 5s embed
        assert time.monotonic() - t0 < 4.0

    def test_stop_fails_pending_fast(self):
        class NeverEmbedder(HashEmbedder):
            def embed_batch(self, texts):
                time.sleep(30)
                return super().embed_batch(texts)

        eng = _engine(NeverEmbedder(8), deadline_ms=0.0, batch_wait_ms=0.0)
        errs: list = []

        def caller():
            try:
                eng.embed_batch(["stuck"])
            except Exception as exc:
                errs.append(exc)

        t = threading.Thread(target=caller)
        t.start()
        time.sleep(0.2)
        eng.stop()
        t.join(timeout=10)
        assert not t.is_alive()
        assert errs and isinstance(
            errs[0], (ClosedError, ResourceExhausted)
        )

    def test_stats_snapshot_shape(self):
        eng = _engine(_embedder())
        eng.embed_batch(MIXED_TEXTS[:4])
        snap = eng.stats_snapshot()
        assert snap["ragged"] is True
        assert snap["texts"] >= 4
        assert 0.0 < snap["pack_efficiency"] <= 1.0
        assert "packed_programs" in snap


# ----------------------------------------------------- hang-backend chaos
class TestHungBackendServing:
    """The acceptance scenario: accelerator hung, engine keeps serving
    (CPU fallback via the PR 6 lifecycle manager) or sheds — bounded."""

    def test_serves_from_cpu_within_deadline(self):
        mgr = _mgr(FakeHooks("hang"), acquire_timeout=0.3)
        inner = TPUEmbedder(cfg=F32_CFG, backend=mgr)
        eng = _engine(inner, deadline_ms=20_000.0)
        t0 = time.monotonic()
        out = eng.embed_batch(["served from host arrays", "second text"])
        took = time.monotonic() - t0
        assert out[0].shape == (DIMS,)
        assert np.isfinite(out[0]).all()
        # bounded by acquire timeout + compute, far under the deadline
        assert took < 15.0
        assert inner.stats["cpu_fallback_batches"] >= 1

    def test_fail_policy_surfaces_not_wedges(self):
        mgr = _mgr(FakeHooks("hang"), acquire_timeout=0.3, fallback="fail")
        with pytest.raises(Exception) as ei:
            inner = TPUEmbedder(cfg=F32_CFG, backend=mgr)
            eng = _engine(inner, deadline_ms=2_000.0)
            eng.embed_batch(["must not hang"])
        assert "DeviceUnavailable" in type(ei.value).__name__ or isinstance(
            ei.value, (ResourceExhausted, ClosedError)
        )


# -------------------------------------------------------- student gate
class _CollapsedEmbedder(HashEmbedder):
    """Every text maps to (nearly) the same vector: retrieval MRR ~ 1/n —
    the shape of a broken/undertrained student checkpoint."""

    def embed_batch(self, texts):
        rng = np.random.default_rng(0)
        base = rng.standard_normal(self._dims).astype(np.float32)
        base /= np.linalg.norm(base)
        out = []
        for i, _ in enumerate(texts):
            v = base.copy()
            v[0] += 1e-6 * i  # deterministic, meaningless tie-break
            out.append(v / np.linalg.norm(v))
        return out


class TestStudentGate:
    def test_green_semantic_embedder_admitted(self):
        report = gate_student(HashEmbedder(128), min_mrr=0.5)
        assert report.metrics.mrr >= 0.5

    def test_red_collapsed_student_rejected(self):
        with pytest.raises(StudentGateError) as ei:
            gate_student(_CollapsedEmbedder(128), min_mrr=0.5)
        msg = str(ei.value)
        assert "rejected" in msg and "MRR" in msg
        # the error must carry the remediation knobs
        assert "student_min_mrr" in msg

    def test_threshold_is_the_gate(self):
        """Same embedder passes a low bar and fails a high one."""
        emb = HashEmbedder(128)
        report = evaluate_embedder(emb, *_suite())
        low = max(0.0, report.metrics.mrr - 0.1)
        high = min(1.0, report.metrics.mrr + 0.01)
        gate_student(emb, min_mrr=low)  # passes
        if high > report.metrics.mrr:
            with pytest.raises(StudentGateError):
                gate_student(emb, min_mrr=high)

    def test_custom_suite_loading(self, tmp_path):
        docs, cases = _suite()
        p = tmp_path / "suite.json"
        p.write_text(json.dumps({
            "docs": docs,
            "cases": [
                {"query": c.query, "relevant": c.relevant} for c in cases
            ],
        }))
        report = gate_student(HashEmbedder(128), 0.4, str(p))
        assert report.metrics.mrr >= 0.4


def _suite():
    docs, cases = builtin_eval_suite()
    return docs, cases


# ------------------------------------------------- batcher admission
class TestQueryBatcherAdmission:
    def test_queue_full_sheds(self):
        from nornicdb_tpu.search.batcher import QueryBatcher

        release = threading.Event()

        def slow_search(queries, k, min_sim):
            release.wait(5.0)
            return [[("id", 1.0)] for _ in range(len(queries))]

        b = QueryBatcher(slow_search, max_batch=64, max_queue=2)
        results = []

        def caller():
            try:
                results.append(b.search(np.ones(4, np.float32), 1))
            except ResourceExhausted:
                results.append("shed")

        ts = [threading.Thread(target=caller) for _ in range(5)]
        for t in ts:
            t.start()
        time.sleep(0.3)
        assert "shed" in results  # beyond max_queue=2 shed immediately
        release.set()
        for t in ts:
            t.join(timeout=10)
        assert len(results) == 5
        assert b.stats.sheds_queue_full >= 1

    def test_deadline_sheds_and_never_wedges(self):
        from nornicdb_tpu.search.batcher import QueryBatcher

        release = threading.Event()

        def stuck_search(queries, k, min_sim):
            release.wait(5.0)
            return [[("id", 1.0)] for _ in range(len(queries))]

        b = QueryBatcher(stuck_search, deadline=0.2)
        # the caller that finds the dispatcher idle leads the stuck scan on
        # its own thread (bounded by the device path, as any dispatch is);
        # a caller queued behind it gives up at deadline + grace
        leader = threading.Thread(
            target=lambda: b.search(np.ones(4, np.float32), 1))
        leader.start()
        while not b._in_flight:
            time.sleep(0.005)
        t0 = time.monotonic()
        try:
            with pytest.raises(ResourceExhausted):
                b.search(np.ones(4, np.float32), 1)
            assert time.monotonic() - t0 < 4.0
            assert not b._pending  # withdrawn, not scanned for nobody
        finally:
            release.set()
            leader.join(timeout=10)
        assert not leader.is_alive()

    def test_dispatch_time_shedding(self):
        from nornicdb_tpu.search.batcher import QueryBatcher
        from nornicdb_tpu.telemetry.costmodel import COST_MODEL

        # cold model -> predictive admission fails open, so the
        # POST-dispatch deadline path under test is actually reached
        COST_MODEL.reset()
        calls = []

        release = threading.Event()

        def search_fn(queries, k, min_sim):
            calls.append(len(queries))
            if len(calls) == 1:
                release.wait(5.0)
            return [[("id", 1.0)] for _ in range(len(queries))]

        b = QueryBatcher(search_fn, deadline=0.05)
        leader = threading.Thread(
            target=lambda: b.search(np.ones(4, np.float32), 1))
        leader.start()
        while not b._in_flight:
            time.sleep(0.005)
        # queued behind the scan in flight, then the deadline lapses before
        # the next scan is launched: shed at dispatch, never scanned
        ticket = b.submit(np.ones(4, np.float32), 1)
        time.sleep(0.1)
        release.set()
        with pytest.raises(ResourceExhausted):
            b.wait(ticket)
        leader.join(timeout=10)
        assert not leader.is_alive()
        assert b.stats.sheds_deadline >= 1
        assert calls == [1]


# ----------------------------------------------------------- HTTP edge
class TestHttpSheddingEdge:
    def test_shed_maps_to_429(self):
        import nornicdb_tpu
        from nornicdb_tpu.server import HttpServer

        db = nornicdb_tpu.open_db("")
        db.set_embedder(HashEmbedder(16))
        srv = HttpServer(db, port=0)
        srv.start()
        try:
            svc = db.search  # force construction

            def shedding_search(*a, **kw):
                raise ResourceExhausted("queue full", reason="queue_full")

            svc.search = shedding_search
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/nornicdb/search",
                data=json.dumps({"query": "hello"}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            assert ei.value.code == 429
            assert ei.value.headers.get("Retry-After") == "1"
            body = json.loads(ei.value.read())
            assert body["reason"] == "queue_full"
        finally:
            srv.stop()
            db.close()

    def test_serving_metrics_in_exposition(self):
        import nornicdb_tpu
        from nornicdb_tpu.server import HttpServer

        db = nornicdb_tpu.open_db("")
        srv = HttpServer(db, port=0)
        srv.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=30
            ) as resp:
                text = resp.read().decode()
            for name in (
                "nornicdb_serving_packed_tokens",
                "nornicdb_serving_pack_efficiency",
                "nornicdb_serving_sheds_total",
                "nornicdb_serving_staging_overlap_ratio",
                "nornicdb_serving_embedder",
                "nornicdb_embed_retries_total",
            ):
                assert name in text, name
        finally:
            srv.stop()
            db.close()


# ------------------------------------------------- embed worker satellite
class TestEmbedWorkerRetryVisibility:
    def test_terminal_failure_logs_node_batch(self, caplog):
        import logging

        import nornicdb_tpu
        from nornicdb_tpu.embed.queue import EmbedWorker, EmbedWorkerConfig
        from nornicdb_tpu.storage import MemoryEngine, Node

        class FailingEmbedder(HashEmbedder):
            def embed_batch(self, texts):
                raise RuntimeError("backend exploded")

        eng = MemoryEngine()
        node = Node(id="n1", properties={"content": "some text"})
        eng.create_node(node)
        eng.mark_pending_embed("n1")
        w = EmbedWorker(
            eng, FailingEmbedder(8),
            EmbedWorkerConfig(max_retries=2, retry_backoff=0.01),
        )
        with caplog.at_level(logging.ERROR, logger="nornicdb_tpu.embed.queue"):
            w.process_batch()
        assert w.stats.failed == 1
        assert w.stats.retries == 2
        terminal = [
            r for r in caplog.records if "terminally" in r.getMessage()
        ]
        assert terminal and "n1" in terminal[0].getMessage()

    def test_shed_then_served_through_engine(self):
        """EmbedWorker retrying through a momentarily-full engine queue
        eventually embeds (backpressure is retryable, not fatal)."""
        from nornicdb_tpu.embed.queue import EmbedWorker, EmbedWorkerConfig
        from nornicdb_tpu.storage import MemoryEngine, Node

        class FlakyShedder(HashEmbedder):
            def __init__(self, dims):
                super().__init__(dims)
                self.calls = 0

            def embed_batch(self, texts):
                self.calls += 1
                if self.calls == 1:
                    raise ResourceExhausted("queue full")
                return super().embed_batch(texts)

        eng = MemoryEngine()
        eng.create_node(Node(id="n1", properties={"content": "hello world"}))
        eng.mark_pending_embed("n1")
        w = EmbedWorker(
            eng, FlakyShedder(8),
            EmbedWorkerConfig(max_retries=3, retry_backoff=0.01),
        )
        assert w.process_batch() == 1
        assert eng.get_node("n1").embedding is not None
        assert w.stats.retries == 1
