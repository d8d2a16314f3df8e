"""Unified telemetry tests: metrics registry exposition (strict Prometheus
parse + docs catalog), request tracing (contextvar propagation, W3C
traceparent in/out, worker hops, replication RPCs), slow-query capture,
and the always-on-cheap overhead bound (`-m slow`).
"""

from __future__ import annotations

import json
import re
import threading
import time
import urllib.request

import pytest

import nornicdb_tpu
from nornicdb_tpu.db import Config
from nornicdb_tpu.embed.base import HashEmbedder
from nornicdb_tpu.server.http import HttpServer
from nornicdb_tpu.telemetry import metrics as tmetrics
from nornicdb_tpu.telemetry import slowlog as tslowlog
from nornicdb_tpu.telemetry.slowlog import slow_log
from nornicdb_tpu.telemetry.tracing import (
    format_traceparent,
    parse_traceparent,
    tracer,
)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """The tracer/slow-log singletons are process-global; every test starts
    from an empty ring and the default thresholds."""
    tracer.clear()
    slow_log.clear()
    slow_log.recorded = 0
    old_threshold = slow_log.threshold_s
    old_enabled, old_rate = tracer.enabled, tracer.sample_rate
    yield
    tracer.clear()
    slow_log.clear()
    slow_log.configure(threshold_s=old_threshold)
    tracer.configure(enabled=old_enabled, sample_rate=old_rate)


# ---------------------------------------------------------------- registry
class TestRegistry:
    def test_counter_gauge_labels_and_render(self):
        r = tmetrics.Registry()
        c = r.counter("t_total", "helptext", labels=("kind",))
        c.labels("a").inc()
        c.labels("a").inc(2)
        c.labels("b").inc()
        g = r.gauge("t_gauge", "g")
        g.set(2.5)
        text = r.render_prometheus()
        assert "# HELP t_total helptext" in text
        assert "# TYPE t_total counter" in text
        assert 't_total{kind="a"} 3' in text
        assert 't_total{kind="b"} 1' in text
        assert "t_gauge 2.5" in text

    def test_integral_values_render_without_decimal(self):
        r = tmetrics.Registry()
        c = r.counter("big_total")
        c.inc(12345678)  # {:g} would render 1.23457e+07
        assert "big_total 12345678" in r.render_prometheus()

    def test_histogram_triples_cumulative(self):
        r = tmetrics.Registry()
        h = r.histogram("lat_seconds", "lat", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 5.0, 0.01):  # 0.01 == bound: le includes it
            h.observe(v)
        text = r.render_prometheus()
        assert 'lat_seconds_bucket{le="0.01"} 2' in text
        assert 'lat_seconds_bucket{le="0.1"} 3' in text
        assert 'lat_seconds_bucket{le="1"} 3' in text
        assert 'lat_seconds_bucket{le="+Inf"} 4' in text
        assert "lat_seconds_count 4" in text
        assert "lat_seconds_sum" in text

    def test_label_escaping(self):
        r = tmetrics.Registry()
        c = r.counter("esc_total", labels=("q",))
        c.labels('say "hi"\nback\\slash').inc()
        text = r.render_prometheus()
        assert r'q="say \"hi\"\nback\\slash"' in text

    def test_idempotent_registration_and_kind_conflict(self):
        r = tmetrics.Registry()
        a = r.counter("same_total", labels=("x",))
        b = r.counter("same_total", labels=("x",))
        assert a is b
        with pytest.raises(ValueError):
            r.gauge("same_total")

    def test_stats_adapter_flatten_rename_counters(self):
        r = tmetrics.Registry()
        r.stats_callback(
            "app", lambda: {"sub": {"hits": 3, "ratio": 0.5, "skip": "str"}},
            rename={"app_sub_hits": "app_sub_hits_total"},
            counters={"app_sub_hits"},
        )
        text = r.render_prometheus()
        assert "# TYPE app_sub_hits_total counter" in text
        assert "app_sub_hits_total 3" in text
        assert "# TYPE app_sub_ratio gauge" in text
        assert "skip" not in text

    def test_dead_callback_does_not_break_render(self):
        r = tmetrics.Registry()
        r.gauge_callback("boom", "", lambda: 1 / 0)
        r.gauge("ok").set(1)
        text = r.render_prometheus()
        assert "ok 1" in text and "boom" not in text

    def test_parent_chain_renders_parent_families(self):
        parent = tmetrics.Registry()
        parent.counter("p_total").inc()
        child = tmetrics.Registry(parent=parent)
        child.gauge("c_gauge").set(1)
        text = child.render_prometheus()
        assert "p_total 1" in text and "c_gauge 1" in text


# ---------------------------------------------------------------- tracing
class TestTracing:
    def test_span_nesting_and_ring(self):
        with tracer.start_trace("root") as root:
            with tracer.span("child") as c1:
                with tracer.span("grandchild"):
                    pass
            assert c1.parent_id == root.span_id
        entry = tracer.trace(root.trace_id)
        assert entry is not None
        tree = entry["tree"]
        assert tree[0]["name"] == "root"
        assert tree[0]["children"][0]["name"] == "child"
        assert tree[0]["children"][0]["children"][0]["name"] == "grandchild"

    def test_span_without_trace_is_shared_noop(self):
        s1 = tracer.span("a")
        s2 = tracer.span("b")
        assert s1 is s2  # the shared no-op handle: no allocation
        with s1 as s:
            s.set_attr("k", "v")  # must not blow up
        assert tracer.count() == 0
        # a stage on the same untraced context still moves its layer's
        # counter, and builds no span (no id, nothing recorded)
        stats = {"seconds": 0.0}
        with tracer.stage("c", stats, "seconds") as st:
            st.set_attr("k", "v")  # must not blow up either
        assert stats["seconds"] == st.seconds > 0.0
        assert st.span_id is None
        assert tracer.count() == 0

    def test_traceparent_roundtrip(self):
        tp = format_traceparent("ab" * 16, "cd" * 8)
        parsed = parse_traceparent(tp)
        assert parsed == ("ab" * 16, "cd" * 8, True)
        assert parse_traceparent("junk") is None
        assert parse_traceparent("00-" + "0" * 32 + "-" + "cd" * 8 + "-01") is None

    def test_incoming_traceparent_continues_trace(self):
        tp = format_traceparent("12" * 16, "34" * 8)
        with tracer.start_trace("server", traceparent=tp) as root:
            assert root.trace_id == "12" * 16
            assert root.parent_id == "34" * 8
        entry = tracer.trace("12" * 16)
        assert entry["remote_parent"] == "34" * 8

    def test_unsampled_and_disabled_paths_record_nothing(self):
        tracer.configure(sample_rate=0.0)
        assert tracer.start_trace("x") is tracer.span("y")
        tracer.configure(sample_rate=1.0, enabled=False)
        assert tracer.start_trace("x") is tracer.span("y")
        assert tracer.count() == 0

    def test_sampled_flag_zero_suppresses(self):
        tp = format_traceparent("ab" * 16, "cd" * 8, sampled=False)
        assert tracer.start_trace("x", traceparent=tp) is tracer.span("y")

    def test_ring_is_bounded(self):
        tracer.configure(capacity=8)
        try:
            for i in range(20):
                with tracer.start_trace(f"t{i}"):
                    pass
            assert tracer.count() == 8
            assert tracer.traces()[0]["root"] == "t19"  # newest first
        finally:
            tracer.configure(capacity=256)

    def test_cross_thread_attach(self):
        seen = {}

        def worker(ctx):
            with tracer.attach(ctx):
                with tracer.span("worker.step"):
                    seen["trace"] = tracer.current_trace_id()

        with tracer.start_trace("root") as root:
            ctx = tracer.capture()
            t = threading.Thread(target=worker, args=(ctx,))
            t.start()
            t.join()
        assert seen["trace"] == root.trace_id
        names = {s["name"] for s in tracer.trace(root.trace_id)["spans"]}
        assert "worker.step" in names

    def test_add_span_retroactive(self):
        t0 = time.perf_counter()
        t1 = t0 + 0.25
        with tracer.start_trace("root") as root:
            tracer.add_span("queued", t0, t1)
        spans = tracer.trace(root.trace_id)["spans"]
        rec = next(s for s in spans if s["name"] == "queued")
        assert rec["parent_id"] == root.span_id
        assert 240 < rec["duration_ms"] < 260

    def test_error_recorded_on_exception(self):
        with pytest.raises(ValueError):
            with tracer.start_trace("root") as root:
                raise ValueError("boom")
        spans = tracer.trace(root.trace_id)["spans"]
        assert "ValueError: boom" in spans[0]["error"]


# ---------------------------------------------------------------- slow log
class TestSlowLog:
    def test_redact_query_strips_string_literals(self):
        q = "MATCH (n {name: 'secret', note: \"two words\"}) RETURN n"
        red = tslowlog.redact_query(q)
        assert "secret" not in red and "two words" not in red
        assert red.count("'?'") == 2

    def test_redact_params_keeps_shapes_only(self):
        red = tslowlog.redact_params(
            {"s": "classified", "n": 42, "lst": [1, 2, 3], "d": {"a": 1}}
        )
        assert red == {"s": "<str[10]>", "n": "<int>",
                       "lst": "<list[3]>", "d": "<dict[1]>"}
        assert "classified" not in json.dumps(red)

    def test_executor_records_over_threshold(self):
        db = nornicdb_tpu.open_db("")
        try:
            slow_log.configure(threshold_s=1e-9)
            db.cypher("CREATE (:SL {v: 'sensitive-value'})")
            assert slow_log.recorded >= 1
            entry = slow_log.snapshot()[0]
            assert "sensitive-value" not in entry["query"]
            assert entry["duration_ms"] > 0
            assert entry["plan"] is not None
        finally:
            db.close()

    def test_threshold_zero_disables(self):
        slow_log.configure(threshold_s=0.0)
        db = nornicdb_tpu.open_db("")
        try:
            db.cypher("RETURN 1")
            assert slow_log.recorded == 0 and not slow_log.snapshot()
        finally:
            db.close()

    def test_ring_bounded(self):
        slow_log.configure(threshold_s=1e-9, capacity=4)
        try:
            for i in range(10):
                slow_log.maybe_record(f"RETURN {i}", {}, 1.0)
            assert len(slow_log.snapshot()) == 4
            assert slow_log.recorded == 10
        finally:
            slow_log.configure(capacity=128)


# ---------------------------------------------------------------- HTTP e2e
def _span_index(entry):
    return {s["span_id"]: s for s in entry["spans"]}


def _is_ancestor(entry, ancestor_name: str, descendant_name: str) -> bool:
    """True if some span named ancestor_name is an ancestor of some span
    named descendant_name in the recorded trace."""
    by_id = _span_index(entry)
    for s in entry["spans"]:
        if s["name"] != descendant_name:
            continue
        cur = s
        while cur is not None:
            if cur["name"] == ancestor_name:
                return True
            cur = by_id.get(cur["parent_id"] or "")
    return False


@pytest.fixture
def traced_server(tmp_path):
    """Durable (WAL) engine with synchronous writes so storage spans land
    on the request thread, plus an embedder for the search stack."""
    # inference off: auto-TLP would run a similarity search right after
    # embedding and pay the first device sync OUTSIDE the traced request
    db = nornicdb_tpu.open_db(
        str(tmp_path / "db"),
        Config(async_writes=False, inference_enabled=False),
    )
    db.set_embedder(HashEmbedder(32))
    server = HttpServer(db, port=0)
    server.start()
    yield db, server
    server.stop()
    db.close()


def _post(port, path, body, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    return urllib.request.urlopen(req, timeout=30)


def _wait_trace(trace_id: str, timeout: float = 5.0):
    """The root span closes (and the trace rings) a hair AFTER the response
    bytes reach the client — poll instead of racing the handler thread."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        entry = tracer.trace(trace_id)
        if entry is not None:
            return entry
        time.sleep(0.01)
    return None


def _get_json(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30
    ) as resp:
        return json.loads(resp.read())


class TestHttpTelemetry:
    def test_traceparent_ingested_and_echoed(self, traced_server):
        db, srv = traced_server
        want = "ab" * 16
        resp = _post(
            srv.port, "/db/neo4j/tx/commit",
            {"statements": [{"statement": "RETURN 1"}]},
            headers={"traceparent": format_traceparent(want, "cd" * 8)},
        )
        echoed = resp.headers.get("traceparent")
        assert echoed is not None and parse_traceparent(echoed)[0] == want
        # the incoming trace id keys the recorded trace: every span below
        # (ingress, executor) was recorded under it
        entry = _wait_trace(want)
        assert entry is not None and entry["spans"]
        assert {"http.POST", "cypher.execute"} <= {
            s["name"] for s in entry["spans"]
        }

    def test_http_root_is_ancestor_of_executor_and_storage(self, traced_server):
        db, srv = traced_server
        want = "cd" * 16
        _post(
            srv.port, "/db/neo4j/tx/commit",
            {"statements": [
                {"statement": "CREATE (:Traced {k: 1}) RETURN 1"}]},
            headers={"traceparent": format_traceparent(want, "ab" * 8)},
        )
        entry = _wait_trace(want)
        assert entry is not None
        # end-to-end causality: HTTP ingress -> executor -> WAL append
        assert _is_ancestor(entry, "http.POST", "cypher.execute")
        assert _is_ancestor(entry, "cypher.execute", "wal.append")
        assert _is_ancestor(entry, "http.POST", "wal.append")

    def test_device_sync_span_under_search_request(self, traced_server):
        db, srv = traced_server
        db.store("telemetry document for device sync")
        db.process_pending_embeddings()
        want = "ef" * 16
        _post(
            srv.port, "/nornicdb/search",
            {"query": "telemetry document", "limit": 3},
            headers={"traceparent": format_traceparent(want, "ab" * 8)},
        )
        entry = _wait_trace(want)
        assert entry is not None
        names = {s["name"] for s in entry["spans"]}
        assert "search.rank" in names
        assert "device.sync" in names
        assert _is_ancestor(entry, "http.POST", "device.sync")

    def test_admin_traces_endpoints(self, traced_server):
        db, srv = traced_server
        _post(srv.port, "/db/neo4j/tx/commit",
              {"statements": [{"statement": "RETURN 1"}]})
        # the root span rings a hair after the response bytes reach the
        # client (see _wait_trace) — poll the listing instead of racing
        # the handler thread
        deadline = time.monotonic() + 5.0
        while True:
            listing = _get_json(srv.port, "/admin/traces")
            if listing["traces"] or time.monotonic() >= deadline:
                break
            time.sleep(0.01)
        assert listing["traces"], "no traces recorded"
        tid = listing["traces"][0]["trace_id"]
        tree = _get_json(srv.port, f"/admin/traces/{tid}")
        assert tree["trace_id"] == tid and tree["tree"]
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get_json(srv.port, "/admin/traces/ffffffffffffffff")
        assert exc.value.code == 404

    def test_admin_slow_queries_endpoint(self, traced_server):
        db, srv = traced_server
        slow_log.configure(threshold_s=1e-9)
        _post(srv.port, "/db/neo4j/tx/commit",
              {"statements": [{"statement": "CREATE (:Slow {s: 'val'})"}]})
        body = _get_json(srv.port, "/admin/slow-queries")
        assert body["recorded"] >= 1
        assert body["slow_queries"][0]["trace_id"] is not None
        assert "val" not in json.dumps(body["slow_queries"])

    def test_metrics_histograms_present(self, traced_server):
        db, srv = traced_server
        _post(srv.port, "/db/neo4j/tx/commit",
              {"statements": [{"statement": "RETURN 1"}]})
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=30
        ) as resp:
            text = resp.read().decode()
        for name in (
            "nornicdb_http_request_seconds",
            "nornicdb_cypher_stage_seconds",
            "nornicdb_wal_append_seconds",
            "nornicdb_device_sync_seconds",
            "nornicdb_search_queue_wait_seconds",
            "nornicdb_search_device_seconds",
        ):
            assert f"# TYPE {name} histogram" in text, name
        assert 'nornicdb_cypher_stage_seconds_bucket{stage="parse"' in text


# ------------------------------------------------------- golden exposition
# The strict parser is now library code (telemetry/promparse.py) shared
# with the fleet federation merge and the CI smoke script — this suite
# remains its golden consumer.
from nornicdb_tpu.telemetry.promparse import (  # noqa: E402
    parse_prometheus_strict,
)


class TestPrometheusGolden:
    @pytest.fixture
    def full_stack_server(self, tmp_path):
        """Force every documented subsystem live so the whole metric
        catalog renders: WAL engine, embed worker, device corpus + batcher,
        adjacency snapshot, traced HTTP request, slow query, heimdall."""
        from nornicdb_tpu.search.service import SearchConfig

        # register the bolt/grpc ingress families even if no such server
        # runs in this process
        import nornicdb_tpu.server.bolt  # noqa: F401
        import nornicdb_tpu.server.grpc_search  # noqa: F401

        db = nornicdb_tpu.open_db(
            str(tmp_path / "db"), Config(async_writes=True)
        )
        db.set_embedder(HashEmbedder(32))
        db.search.config = SearchConfig()
        server = HttpServer(db, port=0)
        server.start()
        slow_log.configure(threshold_s=1e-9)
        db.store("golden exposition corpus doc")
        db.process_pending_embeddings()
        _post(server.port, "/db/neo4j/tx/commit", {"statements": [
            {"statement":
             "CREATE (:G {k: 1})-[:R]->(:G {k: 2}) RETURN 1"}]})
        _post(server.port, "/db/neo4j/tx/commit", {"statements": [
            {"statement": "MATCH (a:G)-[*1..2]->(b) RETURN count(*)"}]})
        _post(server.port, "/nornicdb/search",
              {"query": "golden exposition", "limit": 3})
        db.heimdall.chat([{"role": "user", "content": "hello"}])
        db.flush()
        yield db, server
        server.stop()
        db.close()

    def test_exposition_parses_strict(self, full_stack_server):
        db, srv = full_stack_server
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=30
        ) as resp:
            assert "text/plain" in resp.headers.get("Content-Type", "")
            text = resp.read().decode()
        types, samples = parse_prometheus_strict(text)
        assert types and samples

    def test_every_documented_metric_exists(self, full_stack_server):
        """docs/observability.md's catalog IS the contract: every
        `nornicdb_*`/`heimdall_*` name in the doc must exist in a live
        exposition (and the doc must not be empty of names)."""
        import os

        doc = open(os.path.join(os.path.dirname(__file__), "..",
                                "docs", "observability.md")).read()
        documented = set(re.findall(
            r"`((?:nornicdb|heimdall)_[a-z0-9_]+)`", doc
        ))
        assert len(documented) >= 20, "metric catalog looks truncated"
        db, srv = full_stack_server
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=30
        ) as resp:
            text = resp.read().decode()
        types, _ = parse_prometheus_strict(text)
        missing = documented - set(types)
        assert not missing, f"documented but not exposed: {sorted(missing)}"

    def test_legacy_names_still_served(self, full_stack_server):
        db, srv = full_stack_server
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=30
        ) as resp:
            text = resp.read().decode()
        for name in (
            "nornicdb_uptime_seconds", "nornicdb_requests_total",
            "nornicdb_errors_total", "nornicdb_nodes", "nornicdb_edges",
            "nornicdb_pending_embeddings", "nornicdb_slow_queries_total",
            "nornicdb_embeddings_processed_total",
            "nornicdb_device_sync_bytes_total",
            "nornicdb_device_sync_patches_total",
            "nornicdb_adjacency_builds_total", "nornicdb_adjacency_bytes",
            "heimdall_chat_requests",
        ):
            assert re.search(rf"^{name}(\{{| )", text, re.M), name


# ---------------------------------------------------------------- batcher
class TestBatcherTelemetry:
    def test_queue_wait_span_lands_in_caller_trace(self):
        from nornicdb_tpu.search.batcher import QueryBatcher
        import numpy as np

        def batch_fn(queries, k, min_sim):
            return [[("id", 0.9)] for _ in range(queries.shape[0])]

        b = QueryBatcher(batch_fn, max_batch=8)
        with tracer.start_trace("caller") as root:
            res = b.search(np.ones(4, np.float32), k=1)
        assert res == [("id", 0.9)]
        entry = tracer.trace(root.trace_id)
        names = {s["name"] for s in entry["spans"]}
        assert "search.queue_wait" in names
        assert "search.vector" in names  # the dispatching caller's stage
        assert b.stats.batches == 1
        assert b.stats.queue_wait_seconds >= 0.0


# ------------------------------------------------------------ async flush
class TestAsyncFlushTrace:
    def test_background_flush_adopts_leader_trace(self):
        from nornicdb_tpu.storage import MemoryEngine, Node
        from nornicdb_tpu.storage.async_engine import AsyncEngine

        eng = AsyncEngine(MemoryEngine(), flush_interval=0.01)
        try:
            with tracer.start_trace("write.request") as root:
                eng.create_node(Node(id="af1", labels=["T"]))
            # the BACKGROUND loop drains the overlay; the leader's context
            # was captured at write time, so storage.flush lands in this
            # trace even though the root already closed. The span is
            # recorded AFTER the overlay empties — poll for the span
            # itself, not for drain.
            deadline = time.monotonic() + 5.0
            names: set = set()
            while time.monotonic() < deadline:
                entry = tracer.trace(root.trace_id)
                names = {s["name"] for s in entry["spans"]} if entry else set()
                if "storage.flush" in names:
                    break
                time.sleep(0.01)
            assert "storage.flush" in names
        finally:
            eng.close()


# ------------------------------------------------------------- replication
class TestReplicationTrace:
    def test_transport_carries_trace_id(self):
        from nornicdb_tpu.replication.transport import (
            InProcNetwork, InProcTransport, Message, MSG_REQUEST,
        )

        net = InProcNetwork()
        a = InProcTransport("a", net)
        b = InProcTransport("b", net)
        seen = {}

        def handler(msg):
            seen["trace"] = tracer.current_trace_id()
            return Message(0, {"ok": True})

        b.set_handler(handler)
        with tracer.start_trace("client.op") as root:
            reply = a.request("b", Message(MSG_REQUEST, {"x": 1}),
                              timeout=5.0)
        assert reply.payload == {"ok": True}
        assert seen["trace"] == root.trace_id
        # the receiver recorded its handler trace under the SAME trace id
        deadline = time.monotonic() + 2
        while tracer.trace(root.trace_id) is None and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        entry = tracer.trace(root.trace_id)
        assert entry is not None

    def test_message_codec_roundtrips_traceparent(self):
        from nornicdb_tpu.replication.transport import Message

        msg = Message(7, {"a": 1}, "rid", "node-1",
                      format_traceparent("ab" * 16, "cd" * 8))
        decoded = Message.decode(msg.encode())
        assert decoded.traceparent == msg.traceparent
        bare = Message.decode(Message(7, {"a": 1}).encode())
        assert bare.traceparent == ""

    def test_raft_append_rpc_carries_trace(self):
        from nornicdb_tpu.replication.raft import RaftCluster
        from nornicdb_tpu.replication.transport import InProcNetwork
        from nornicdb_tpu.storage import MemoryEngine

        net = InProcNetwork()
        cluster = RaftCluster(3, net,
                              storages=[MemoryEngine() for _ in range(3)])
        cluster.start()
        try:
            leader = cluster.leader(timeout=5.0)
            assert leader is not None
            with tracer.start_trace("write.request") as root:
                leader.propose("create_node", {"id": "n1", "labels": []})
            # the followers' transport hops continue the SAME trace id;
            # their handler traces land in the ring asynchronously
            deadline = time.monotonic() + 5.0
            found = False
            while time.monotonic() < deadline and not found:
                found = any(
                    e["trace_id"] == root.trace_id
                    and e["root"].startswith("replication.handle")
                    for e in tracer.traces(limit=500)
                )
                if not found:
                    time.sleep(0.02)
            assert found, "no replication.handle trace with the write's id"
            # the proposer's own entry (same trace id as the follower
            # handler entries) recorded the propose span
            proposer_entries = [
                t for t in tracer._ring
                if t["trace_id"] == root.trace_id
                and t["root"] == "write.request"
            ]
            assert proposer_entries
            names = {s["name"] for s in proposer_entries[0]["spans"]}
            assert "replication.propose" in names
        finally:
            cluster.stop()


# ---------------------------------------------------------------- bolt
class TestBoltTrace:
    def test_run_starts_trace_with_tx_metadata_traceparent(self):
        from nornicdb_tpu.server.bolt import BoltSession, MSG_RUN, MSG_SUCCESS

        db = nornicdb_tpu.open_db("")
        try:
            class FakeServer:
                auth_required = False
                authenticator = None
                session_executor_factory = None

                @staticmethod
                def executor_fn(q, p, d):
                    return db.executor.execute(q, p)

            session = BoltSession(FakeServer())
            want = "aa" * 16
            out = session.handle(MSG_RUN, [
                "RETURN 1", {},
                {"tx_metadata":
                 {"traceparent": format_traceparent(want, "bb" * 8)}},
            ])
            assert out[0][0] == MSG_SUCCESS
            entry = tracer.trace(want)
            assert entry is not None
            assert _is_ancestor(entry, "bolt.run", "cypher.execute")
        finally:
            db.close()


# ------------------------------------------------------------ microbench
@pytest.mark.slow
class TestOverheadMicrobench:
    """The always-on-cheap acceptance bound: with no active trace, the
    instrumented hot path must run within a small constant factor of the
    un-instrumented baseline (one contextvar read, no allocation)."""

    N = 50_000

    @staticmethod
    def _work(state: dict, i: int) -> None:
        state["k"] = i
        state["acc"] = state.get("acc", 0) + (i & 7)

    def _bench(self, fn) -> float:
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    def test_untraced_span_overhead_bounded(self):
        state: dict = {}
        work = self._work

        def baseline():
            for i in range(self.N):
                work(state, i)

        def instrumented():
            for i in range(self.N):
                with tracer.span("bench.op"):
                    work(state, i)

        assert tracer.capture() is None  # no active trace on this context
        base = self._bench(baseline)
        instr = self._bench(instrumented)
        ratio = instr / base
        print(f"untraced span overhead: {ratio:.2f}x "
              f"({base * 1e9 / self.N:.0f}ns -> {instr * 1e9 / self.N:.0f}ns/op)")
        assert ratio < 8.0, f"no-trace span path too slow: {ratio:.2f}x"

    def test_untraced_stage_overhead_bounded(self):
        """A stage always times and feeds its counter, so it costs more
        than the no-op span: two perf_counter calls, one handle, one
        ``is_enabled`` call, one add.  Stated bound: under 2.5 us a stage
        and under 25x the bare work (measured here: ~1.1 us, ~9x)."""
        state: dict = {}
        stats = {"seconds": 0.0}
        work = self._work

        def baseline():
            for i in range(self.N):
                work(state, i)

        def instrumented():
            for i in range(self.N):
                with tracer.stage("bench.op", stats, "seconds"):
                    work(state, i)

        assert tracer.capture() is None  # no active trace on this context
        base = self._bench(baseline)
        instr = self._bench(instrumented)
        per_stage_us = (instr - base) / self.N * 1e6
        print(f"untraced stage overhead: {instr / base:.2f}x "
              f"(+{per_stage_us:.2f} us a stage)")
        assert stats["seconds"] > 0.0
        assert per_stage_us < 2.5, f"untraced stage: {per_stage_us:.2f} us"
        assert instr / base < 25.0

    def test_disabled_tracer_overhead_bounded(self):
        state: dict = {}
        work = self._work
        tracer.configure(enabled=False)

        def baseline():
            for i in range(self.N):
                work(state, i)

        def instrumented():
            for i in range(self.N):
                with tracer.start_trace("bench.request"):
                    work(state, i)

        base = self._bench(baseline)
        instr = self._bench(instrumented)
        ratio = instr / base
        print(f"disabled start_trace overhead: {ratio:.2f}x")
        assert ratio < 8.0, f"disabled ingress path too slow: {ratio:.2f}x"
