"""Device-broker tests: framing, fused cross-connection dispatch, the
admission/deadline taxonomy over the socket, DEGRADED redirection, and the
twin-path equivalence contract (broker results == in-process results)."""

import threading
import types

import numpy as np
import pytest

from nornicdb_tpu.backend import BackendManager, FakeHooks
from nornicdb_tpu.embed.base import HashEmbedder
from nornicdb_tpu.errors import ResourceExhausted
from nornicdb_tpu.search.service import SearchConfig, SearchService
from nornicdb_tpu.server import broker as broker_mod
from nornicdb_tpu.server.broker import (
    BrokerClient,
    BrokerDegraded,
    BrokerUnavailable,
    DeviceBroker,
    decode_embed_request,
    decode_search_request,
    decode_search_response,
    encode_embed_request,
    encode_search_request,
    encode_search_response,
)
from nornicdb_tpu.storage import MemoryEngine
from nornicdb_tpu.storage.types import Node


# ---------------------------------------------------------------- framing
class TestFraming:
    def test_search_request_roundtrip_f32(self):
        q = np.arange(12, dtype=np.float32).reshape(3, 4)
        buf = encode_search_request(q, k=7, min_similarity=0.25,
                                    with_content=True)
        got_q, k, min_sim, with_content = decode_search_request(buf)
        np.testing.assert_array_equal(got_q, q)
        assert (k, with_content) == (7, True)
        assert min_sim == pytest.approx(0.25)

    def test_search_request_roundtrip_int8(self):
        rng = np.random.default_rng(0)
        rows = rng.normal(size=(4, 8)).astype(np.float32)
        scales = (127.0 / np.maximum(np.max(np.abs(rows), axis=1), 1e-9))
        codes = np.round(rows * scales[:, None]).astype(np.int8)
        buf = encode_search_request(codes, k=3, min_similarity=-1.0,
                                    scales=scales.astype(np.float32))
        got_q, k, _min_sim, _wc = decode_search_request(buf)
        # dequantized queries approximate the originals
        np.testing.assert_allclose(got_q, rows, atol=0.02)

    def test_search_response_roundtrip(self):
        rows = [[("a", 0.5, "hello"), ("b", -0.25, "")], []]
        buf = encode_search_response(rows, with_content=True)
        got = decode_search_response(buf[1:])  # strip status byte
        assert got[0][0] == ("a", pytest.approx(0.5), "hello")
        assert got[0][1][0] == "b"
        assert got[1] == []

    def test_embed_request_roundtrip(self):
        texts = ["", "héllo wörld", "x" * 500]
        assert decode_embed_request(encode_embed_request(texts)) == texts


# ---------------------------------------------------------------- fixtures
def _build_stack(n=300, dims=32, config=None, backend=None):
    eng = MemoryEngine()
    emb = HashEmbedder(dims)
    svc = SearchService(eng, embedder=emb,
                        config=config or SearchConfig())
    rng = np.random.default_rng(0)
    for i in range(n):
        v = rng.normal(size=dims).astype(np.float32)
        v /= np.linalg.norm(v)
        node = Node(id=f"n{i}", labels=["Doc"],
                    properties={"content": f"doc {i}"}, embedding=v)
        eng.create_node(node)
        svc.index_node(node)
    if backend is None:
        # a private healthy manager: the suite's broker semantics must not
        # depend on the PROCESS-default manager, which the CI chaos step
        # forces to hang (NORNICDB_FAKE_BACKEND=hang) — degraded-path
        # behavior is tested explicitly with an injected failing manager
        backend = BackendManager(hooks=FakeHooks(mode="ok"))
        backend.ensure_started()
    svc.corpus()._backend = backend
    db = types.SimpleNamespace(search=svc, storage=eng, embedder=emb)
    return db, rng


@pytest.fixture()
def stack(tmp_path):
    db, rng = _build_stack()
    broker = DeviceBroker(db, str(tmp_path / "broker.sock"))
    client = BrokerClient(broker.path)
    yield db, broker, client, rng
    broker.stop()


# ---------------------------------------------------------------- serving
class TestBrokerServing:
    def test_search_twin_path_bit_identical(self, stack):
        """The broker's fused 5-query batch answers what five in-process
        single-query calls answer: ids exactly, scores to one f32 ulp — the
        socket loses nothing, but a (5, D) and a (1, D) GEMM are different
        XLA programs and may round the last bit differently."""
        db, _broker, client, rng = stack
        q = rng.normal(size=(5, 32)).astype(np.float32)
        got = client.search(q, k=10)
        for i in range(5):
            want = db.search.vector_candidates(q[i], 10, -1.0)
            assert [h[0] for h in got[i]] == [id_ for id_, _ in want]
            a = np.asarray([h[1] for h in got[i]], np.float32)
            b = np.asarray([s for _, s in want], np.float32)
            assert (np.abs(a - b) <= np.spacing(np.maximum(
                np.abs(a), np.abs(b)))).all(), (a, b)

    def test_with_content_enriches_from_storage(self, stack):
        _db, _broker, client, rng = stack
        q = rng.normal(size=(1, 32)).astype(np.float32)
        rows = client.search(q, k=3, with_content=True)
        assert all(c.startswith("doc ") for _i, _s, c in rows[0])

    def test_empty_corpus_returns_empty_rows(self, tmp_path):
        eng = MemoryEngine()
        emb = HashEmbedder(16)
        svc = SearchService(eng, embedder=emb)
        db = types.SimpleNamespace(search=svc, storage=eng, embedder=emb)
        broker = DeviceBroker(db, str(tmp_path / "b.sock"))
        try:
            client = BrokerClient(broker.path)
            assert client.search(np.zeros((2, 16), np.float32), k=5) == \
                [[], []]
        finally:
            broker.stop()

    def test_cross_connection_queries_fuse_into_batches(self, stack):
        """Queries arriving on DIFFERENT connections while a scan is in
        flight must coalesce: device programs (batches) << queries, and
        the one-program-per-fused-batch invariant holds."""
        db, _broker, _client, rng = stack
        batcher = db.search.ensure_batcher()
        corpus = db.search.corpus()
        q = rng.normal(size=(2, 32)).astype(np.float32)
        # the first query of a k compiles that k's class grid (one scan of
        # zero queries a class): before the counters are read
        _client.search(q[:1], k=5)
        b0 = batcher.stats.batches
        d0 = corpus.sync_stats.device_dispatches
        clients = [BrokerClient(_broker.path) for _ in range(6)]
        threads = []
        for c in clients:
            t = threading.Thread(target=lambda c=c: c.search(q, k=5))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(30)
        queries = 12
        batches = batcher.stats.batches - b0
        dispatches = corpus.sync_stats.device_dispatches - d0
        assert batches < queries, "no cross-connection fusing happened"
        # one device program per fused batch
        assert dispatches == batches

    def test_embed_matches_in_process(self, stack):
        db, _broker, client, _rng = stack
        out = client.embed(["hello", "world"])
        assert out.shape == (2, 32)
        np.testing.assert_array_equal(out[0], db.embedder.embed("hello"))

    def test_status_snapshot(self, stack):
        _db, _broker, client, _rng = stack
        s = client.status()
        assert s["backend_state"] == "READY"
        assert s["corpus_rows"] == 300
        assert "counters" in s


# ---------------------------------------------------------------- taxonomy
class TestBrokerTaxonomy:
    def test_queue_full_surfaces_resource_exhausted(self, tmp_path):
        db, rng = _build_stack(
            config=SearchConfig(batch_max=512, batch_max_queue=1),
        )
        broker = DeviceBroker(db, str(tmp_path / "b.sock"))
        try:
            client = BrokerClient(broker.path)
            q = rng.normal(size=(8, 32)).astype(np.float32)
            with pytest.raises(ResourceExhausted):
                # 8 tickets into a queue of 1: admission sheds
                client.search(q, k=5)
            assert broker.counters["search_shed"] == 1
            # the ticket admitted before the shed was withdrawn: the queue
            # of 1 is free again and the next search is served
            assert len(client.search(q[:1], k=5)[0]) == 5
        finally:
            broker.stop()

    def test_degraded_backend_redirects_to_fallback(self, tmp_path):
        mgr = BackendManager(hooks=FakeHooks(mode="fail"),
                             acquire_timeout=1.0)
        mgr.ensure_started()
        db, rng = _build_stack(backend=mgr)
        import time

        deadline = time.time() + 10
        while mgr.state != "DEGRADED_CPU" and time.time() < deadline:
            time.sleep(0.05)
        assert mgr.state == "DEGRADED_CPU"
        broker = DeviceBroker(db, str(tmp_path / "b.sock"))
        try:
            client = BrokerClient(broker.path)
            q = rng.normal(size=(1, 32)).astype(np.float32)
            with pytest.raises(BrokerDegraded):
                client.search(q, k=3)
            assert broker.counters["search_degraded"] == 1
        finally:
            broker.stop()
            mgr.stop()

    def test_stopped_broker_raises_unavailable(self, stack):
        _db, broker, client, rng = stack
        q = rng.normal(size=(1, 32)).astype(np.float32)
        client.search(q, k=1)  # healthy first
        broker.stop()
        with pytest.raises(BrokerUnavailable):
            client.search(q, k=1)

    def test_client_reconnects_after_conn_drop(self, stack):
        """One dead keep-alive connection must cost one retry, not an
        error: the client reconnects transparently."""
        _db, _broker, client, rng = stack
        q = rng.normal(size=(1, 32)).astype(np.float32)
        client.search(q, k=1)
        client._local.sock.close()  # simulate a dropped keep-alive
        assert client.search(q, k=1)  # reconnected

    def test_embedder_missing_is_error_not_hang(self, tmp_path):
        eng = MemoryEngine()
        svc = SearchService(eng, embedder=None, dims=8)
        db = types.SimpleNamespace(search=svc, storage=eng, embedder=None)
        broker = DeviceBroker(db, str(tmp_path / "b.sock"))
        try:
            client = BrokerClient(broker.path)
            with pytest.raises(broker_mod.BrokerError):
                client.embed(["x"])
        finally:
            broker.stop()

    def test_wrong_dims_rejected_before_fusing(self, stack):
        """A wrong-dimension query must be refused at the frame — fused
        into the shared batch it would error EVERY worker's queries in
        the same window."""
        _db, _broker, client, rng = stack
        with pytest.raises(broker_mod.BrokerError):
            client.search(rng.normal(size=(1, 16)).astype(np.float32), k=3)
        # the shared path still serves valid queries afterwards
        assert client.search(
            rng.normal(size=(1, 32)).astype(np.float32), k=3)[0]

    def test_garbage_frame_gets_error_reply(self, stack):
        _db, broker, _client, _rng = stack
        import socket as socket_mod
        import struct

        s = socket_mod.socket(socket_mod.AF_UNIX, socket_mod.SOCK_STREAM)
        s.connect(broker.path)
        payload = b"\xff" * 4  # undecodable SEARCH body
        # frame: u32 len | u8 type | u64 req_id | u8 tp_len | payload
        s.sendall(struct.pack("<IBQB", 10 + len(payload),
                              broker_mod.MSG_SEARCH, 1, 0) + payload)
        head = s.recv(4)
        (ln,) = struct.unpack("<I", head)
        body = b""
        while len(body) < ln:
            body += s.recv(ln - len(body))
        assert body[10] == broker_mod.STATUS_ERROR
        s.close()

    def test_traced_search_continues_worker_trace(self, stack):
        """A traceparent in the frame header makes the broker handler's
        spans land under the caller's trace id (the cross-process hop)."""
        from nornicdb_tpu.telemetry.tracing import tracer

        _db, _broker, client, rng = stack
        q = rng.normal(size=(1, 32)).astype(np.float32)
        with tracer.start_trace("worker.search") as root:
            client.search(q, k=3)
            tid = root.trace_id
        import time

        deadline = time.monotonic() + 5
        names: set = set()
        while time.monotonic() < deadline:
            entry = tracer.trace(tid)
            names = ({s["name"] for s in entry["spans"]}
                     if entry else set())
            if "broker.search" in names and "search.vector" in names:
                break
            time.sleep(0.02)
        assert "broker.search" in names, names
        assert "search.vector" in names, names

    def test_ship_spans_merges_remote_tree(self, stack):
        """MSG_SPANS: a worker-shipped finished trace merges into the
        primary ring tagged with its proc."""
        from nornicdb_tpu.telemetry.tracing import tracer

        _db, _broker, client, _rng = stack
        entry = {
            "trace_id": "fe" * 16,
            "root": "worker.search",
            "started": 1000.0,
            "duration_ms": 4.2,
            "spans": [{
                "name": "worker.search", "span_id": "ab" * 8,
                "parent_id": None, "start": 1000.0, "duration_ms": 4.2,
            }],
        }
        client.ship_spans(entry, proc="http-worker-0")
        merged = tracer.trace("fe" * 16)
        assert merged is not None
        rec = next(s for s in merged["spans"]
                   if s["name"] == "worker.search")
        assert rec["proc"] == "http-worker-0"

    def test_active_broker_stats_registry(self, stack):
        _db, broker, client, rng = stack
        client.search(rng.normal(size=(1, 32)).astype(np.float32), k=1)
        stats = broker_mod.active_broker_stats()
        assert any(s["path"] == broker.path for s in stats)
