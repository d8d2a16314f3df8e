"""Fleet telemetry plane tests (ISSUE 15): cross-process metrics
federation (worker expositions merged into /metrics under a proc label,
staleness drop for dead segments), trace propagation across the broker
hop (one span tree spanning two processes), the device-time & HBM
profiler (program ledger, residency gauges, /admin/profile capture), and
worker-side slow-query capture with served-path attribution.
"""

from __future__ import annotations

import http.client
import io
import json
import tarfile
import time

import numpy as np
import pytest

import nornicdb_tpu
from nornicdb_tpu.embed import HashEmbedder
from nornicdb_tpu.server import HttpServer, WorkerPool
from nornicdb_tpu.telemetry import deviceprof
from nornicdb_tpu.telemetry.federation import (
    FleetCollector,
    MetricsPublisher,
    merge_expositions,
)
from nornicdb_tpu.telemetry.metrics import REGISTRY, Registry
from nornicdb_tpu.telemetry.promparse import (
    parse_exposition,
    parse_prometheus_strict,
)
from nornicdb_tpu.telemetry.tracing import format_traceparent, tracer


# ------------------------------------------------------------- promparse
class TestPromparse:
    def test_structural_roundtrip(self):
        text = REGISTRY.render_prometheus()
        fams = parse_exposition(text)
        out: list[str] = []
        for fam in fams.values():
            fam.render(out)
        rendered = "\n".join(out) + "\n"
        # the re-render must still parse strictly and keep every family
        types, _ = parse_prometheus_strict(rendered)
        orig_types, _ = parse_prometheus_strict(text)
        assert set(types) == set(orig_types)

    def test_strict_raises_on_duplicate_type(self):
        bad = "# TYPE a counter\na 1\n# TYPE a counter\na 2\n"
        with pytest.raises(ValueError):
            parse_prometheus_strict(bad)
        with pytest.raises(ValueError):
            parse_exposition(bad)

    def test_strict_raises_on_undeclared_sample(self):
        with pytest.raises(ValueError):
            parse_prometheus_strict("orphan 1\n")

    def test_label_injection_replaces_existing_proc(self):
        text = '# TYPE x counter\nx{proc="stale",a="1"} 2\n'
        fams = parse_exposition(text)
        out: list[str] = []
        fams["x"].render(out, 'proc="fresh"')
        assert 'x{a="1",proc="fresh"} 2' in out


# ------------------------------------------------------------ federation
class TestFederationMerge:
    def _worker_registry(self) -> Registry:
        r = Registry()
        c = r.counter("nornicdb_worker_requests_total", "w",
                      labels=("served",))
        c.labels("broker").inc(3)
        c.labels("shm").inc(1)
        r.histogram("nornicdb_worker_broker_roundtrip_seconds",
                    "h").observe(0.004)
        r.counter("w_only_total", "worker-only family").inc(7)
        return r

    def test_merge_relabels_and_parses_strict(self, tmp_path):
        pub = MetricsPublisher(str(tmp_path / "w0.seg"), "http-worker-0",
                               registry=self._worker_registry())
        pub.publish_now()
        col = FleetCollector()
        col.register("http-worker-0", str(tmp_path / "w0.seg"))
        try:
            merged = col.merged_exposition(REGISTRY.render_prometheus())
            types, samples = parse_prometheus_strict(merged)
            got = {
                (n, l.get("served")): v for n, l, v in samples
                if n == "nornicdb_worker_requests_total"
                and l.get("proc") == "http-worker-0"
            }
            assert got[("nornicdb_worker_requests_total", "broker")] == 3
            # worker-only families splice in with TYPE declared once
            assert types["w_only_total"] == "counter"
            assert any(n == "w_only_total"
                       and l.get("proc") == "http-worker-0"
                       for n, l, _ in samples)
            # worker histogram buckets stay strict under the proc label
            assert any(
                n == "nornicdb_worker_broker_roundtrip_seconds_count"
                and l.get("proc") == "http-worker-0" and v == 1
                for n, l, v in samples)
        finally:
            col.unregister("http-worker-0")
            pub.stop()

    def test_unpublished_member_is_skipped(self, tmp_path):
        col = FleetCollector()
        col.register("http-worker-9", str(tmp_path / "never.seg"))
        try:
            primary = REGISTRY.render_prometheus()
            assert 'proc="http-worker-9"' not in \
                col.merged_exposition(primary)
            assert col.stats()["members"]["http-worker-9"] == \
                {"fresh": False}
        finally:
            col.unregister("http-worker-9")

    def test_stale_segment_dropped(self, tmp_path):
        pub = MetricsPublisher(str(tmp_path / "w.seg"), "http-worker-0",
                               registry=self._worker_registry())
        pub.publish_now()
        col = FleetCollector(staleness_s=3600.0)
        col.register("http-worker-0", str(tmp_path / "w.seg"))
        # a worker-ONLY sample proves splice-in; the primary's own fleet
        # age/member gauges carry proc labels regardless
        marker = 'w_only_total{proc="http-worker-0"}'
        try:
            primary = REGISTRY.render_prometheus()
            assert marker in col.merged_exposition(primary)
            drops0 = col.stale_drops
            col.configure(staleness_s=0.0)
            time.sleep(0.02)
            assert marker not in col.merged_exposition(primary)
            assert col.stale_drops > drops0
        finally:
            col.unregister("http-worker-0")
            pub.stop()

    def test_broken_worker_exposition_skipped_not_spliced(self):
        class W:
            proc = "http-worker-0"
            text = "# TYPE a counter\na 1\n# TYPE a counter\na 2\n"

        merged = merge_expositions(REGISTRY.render_prometheus(), [W()])
        parse_prometheus_strict(merged)  # still strict
        # the broken worker family never spliced in
        assert "# TYPE a counter" not in merged

    def test_conflicting_help_primary_wins(self):
        # the primary and a worker can disagree on HELP text (e.g. a
        # rolling deploy with an old worker binary); the merge must
        # render the primary's HELP once, never the worker's variant,
        # and for worker-only families the first worker's HELP wins
        primary = ("# HELP shared_total primary wording\n"
                   "# TYPE shared_total counter\n"
                   "shared_total 1\n")

        class W:
            def __init__(self, proc, text):
                self.proc = proc
                self.text = text

        w0 = W("http-worker-0",
               "# HELP shared_total old worker wording\n"
               "# TYPE shared_total counter\n"
               "shared_total 2\n"
               "# HELP w_only_total first wording\n"
               "# TYPE w_only_total counter\n"
               "w_only_total 7\n")
        w1 = W("http-worker-1",
               "# HELP w_only_total second wording\n"
               "# TYPE w_only_total counter\n"
               "w_only_total 9\n")
        merged = merge_expositions(primary, [w0, w1])
        parse_prometheus_strict(merged)
        assert merged.count("# HELP shared_total") == 1
        assert "# HELP shared_total primary wording" in merged
        assert "old worker wording" not in merged
        # both workers' cells spliced under one family declaration
        assert 'shared_total{proc="http-worker-0"} 2' in merged
        assert merged.count("# TYPE w_only_total") == 1
        assert "# HELP w_only_total first wording" in merged
        assert "second wording" not in merged
        assert 'w_only_total{proc="http-worker-1"} 9' in merged

    def test_conflicting_kind_skipped_and_counted(self):
        # same family name, different TYPE kind: the worker's cells must
        # NOT splice in (they'd corrupt the family) and the skip must be
        # visible in the merge-error counter
        from nornicdb_tpu.telemetry.federation import FLEET_MERGE_ERRORS

        primary = ("# TYPE shared_total counter\n"
                   "shared_total 1\n")

        class W:
            proc = "http-worker-0"
            text = ("# TYPE shared_total gauge\n"
                    "shared_total 5\n")

        errs0 = FLEET_MERGE_ERRORS.labels().get()
        merged = merge_expositions(primary, [W()])
        parse_prometheus_strict(merged)
        assert 'proc="http-worker-0"' not in merged
        assert "shared_total 1" in merged
        assert FLEET_MERGE_ERRORS.labels().get() == errs0 + 1

    def test_stale_ageout_rejoins_on_fresh_publish(self, tmp_path):
        # ageout race: a worker whose publisher stalls ages out of the
        # merge (counted once per dropped scrape), then REJOINS as soon
        # as a fresh publish lands — staleness is a per-scrape decision,
        # not a permanent eviction
        from nornicdb_tpu.telemetry.federation import FLEET_MEMBERS

        pub = MetricsPublisher(str(tmp_path / "w.seg"), "http-worker-0",
                               registry=self._worker_registry())
        pub.publish_now()
        col = FleetCollector(staleness_s=0.05)
        col.register("http-worker-0", str(tmp_path / "w.seg"))
        marker = 'w_only_total{proc="http-worker-0"}'
        try:
            primary = REGISTRY.render_prometheus()
            assert marker in col.merged_exposition(primary)
            assert FLEET_MEMBERS.labels("http-worker-0").get() == 1.0
            time.sleep(0.1)  # let the published stamp age past 0.05s
            drops0 = col.stale_drops
            assert marker not in col.merged_exposition(primary)
            assert col.stale_drops == drops0 + 1
            assert FLEET_MEMBERS.labels("http-worker-0").get() == 0.0
            # the structured read paths poll while stale WITHOUT bumping
            # the drop counter: it means "dropped from a /metrics merge"
            assert not col.stats()["members"]["http-worker-0"]["fresh"]
            assert col.slow_queries() == []
            assert col.stale_drops == drops0 + 1
            # fresh publish -> the very next scrape carries the worker
            pub.publish_now()
            assert marker in col.merged_exposition(primary)
            assert col.stale_drops == drops0 + 1
            assert FLEET_MEMBERS.labels("http-worker-0").get() == 1.0
        finally:
            col.unregister("http-worker-0")
            pub.stop()

    def test_slow_queries_tagged_with_proc(self, tmp_path):
        from nornicdb_tpu.telemetry.slowlog import slow_log

        slow_log.configure(threshold_s=1e-9)
        try:
            slow_log.maybe_record("VECTOR SEARCH k=5 dims=64", None,
                                  0.5, served="broker")
            pub = MetricsPublisher(str(tmp_path / "w.seg"),
                                   "http-worker-1")
            pub.publish_now()
            col = FleetCollector()
            col.register("http-worker-1", str(tmp_path / "w.seg"))
            try:
                entries = col.slow_queries()
                mine = [e for e in entries
                        if e.get("served") == "broker"
                        and e["proc"] == "http-worker-1"]
                assert mine and mine[0]["query"].startswith(
                    "VECTOR SEARCH")
            finally:
                col.unregister("http-worker-1")
                pub.stop()
        finally:
            slow_log.configure(threshold_s=1000.0)
            slow_log.clear()


# ------------------------------------------------------------ deviceprof
class TestDeviceProf:
    def test_execute_counts_compile_once_per_shape(self):
        p = deviceprof.DeviceProfiler()
        p.record_execute("t", "kernel", "b8", 0.001)
        p.record_execute("t", "kernel", "b8", 0.002)
        p.record_execute("t", "kernel", "b16", 0.003)
        snap = p.snapshot()
        by_shape = {e["shape"]: e for e in snap["programs"]
                    if e["subsystem"] == "t"}
        assert by_shape["b8"]["compiles"] == 1
        assert by_shape["b8"]["executes"] == 2
        assert by_shape["b16"]["compiles"] == 1
        assert snap["program_count"] == 2

    def test_record_compile_is_idempotent_ledger(self):
        p = deviceprof.DeviceProfiler()
        p.record_compile("t", "warm", "c16")
        p.record_compile("t", "warm", "c16")
        entry = p.snapshot()["programs"][0]
        assert entry["compiles"] == 1 and entry["executes"] == 0

    def test_hbm_provider_weakref_gc(self):
        p = deviceprof.DeviceProfiler()

        class Owner:
            nbytes = 1024

        owner = Owner()
        p.register_hbm(owner, lambda o: {"corpus_f32": o.nbytes})
        p.refresh_hbm()
        # providers are weakref'd: once the owner is GC'd its bytes
        # disappear from the sum without unregistration ceremony
        assert len(p._hbm_providers) == 1
        del owner
        import gc

        gc.collect()
        p.refresh_hbm()
        assert len(p._hbm_providers) == 0

    def test_corpus_registers_hbm_bytes(self):
        from nornicdb_tpu.ops.similarity import DeviceCorpus

        c = DeviceCorpus(dims=16, capacity=128)
        rng = np.random.default_rng(0)
        for i in range(4):
            c.add(f"v{i}", rng.normal(size=16).astype(np.float32))
        c.search(rng.normal(size=16).astype(np.float32), k=2)
        got = DeviceCorpus._hbm_bytes(c)
        assert got["corpus_f32"] > 0
        deviceprof.PROFILER.refresh_hbm()
        # the process-global gauge sums every live corpus: at least ours
        from nornicdb_tpu.telemetry.deviceprof import _HBM

        assert _HBM.get("corpus_f32") >= got["corpus_f32"]

    def test_search_dispatch_lands_in_program_ledger(self):
        from nornicdb_tpu.ops.similarity import DeviceCorpus

        c = DeviceCorpus(dims=16, capacity=128)
        rng = np.random.default_rng(1)
        for i in range(8):
            c.add(f"p{i}", rng.normal(size=16).astype(np.float32))
        c.search(rng.normal(size=16).astype(np.float32), k=2)
        snap = deviceprof.snapshot()
        assert any(e["subsystem"] == "search" and e["kind"] == "dense"
                   and e["executes"] >= 1 for e in snap["programs"])

    def test_capture_profile_nonempty_and_single_flight(self):
        p = deviceprof.DeviceProfiler()
        artifact = p.capture_profile(0.1)
        assert artifact[:2] == b"\x1f\x8b"  # gzip magic
        with tarfile.open(fileobj=io.BytesIO(artifact), mode="r:gz") as t:
            names = t.getnames()
        assert names, "profile artifact is empty"
        # single-flight: a concurrent capture is refused, not serialized
        assert p._capture_lock.acquire(blocking=False)
        try:
            with pytest.raises(deviceprof.ProfileBusy):
                p.capture_profile(0.1)
        finally:
            p._capture_lock.release()


# --------------------------------------------------------- remote traces
class TestRemoteTraceMerge:
    def test_merge_into_existing_entry_builds_one_tree(self):
        tracer.clear()
        tp = format_traceparent("ad" * 16, "cd" * 8)
        with tracer.start_trace("broker.search", traceparent=tp):
            with tracer.span("search.vector", {"batch_size": 3}):
                pass
        assert tracer.merge_remote("ad" * 16, [
            {"name": "worker.search", "span_id": "ab" * 8,
             "parent_id": None, "start": 1.0, "duration_ms": 9.0},
            {"name": "worker.broker_call", "span_id": "cd" * 8,
             "parent_id": "ab" * 8, "start": 1.0, "duration_ms": 8.0},
        ], proc="http-worker-0")
        entry = tracer.trace("ad" * 16)
        # ONE tree: worker.search roots it, broker.search nests under
        # the worker span that carried the traceparent
        assert len(entry["tree"]) == 1
        root = entry["tree"][0]
        assert root["name"] == "worker.search"
        assert root["proc"] == "http-worker-0"
        child = root["children"][0]
        assert child["name"] == "worker.broker_call"
        assert {c["name"] for c in child["children"]} == {"broker.search"}

    def test_merge_without_local_entry_creates_one(self):
        tracer.clear()
        assert tracer.merge_remote("be" * 16, [
            {"name": "worker.search", "span_id": "11" * 8,
             "parent_id": None, "start": 5.0, "duration_ms": 2.0},
        ], root="worker.search", started=5.0, duration_ms=2.0,
            proc="http-worker-1")
        entry = tracer.trace("be" * 16)
        assert entry["root"] == "worker.search"
        assert entry["spans"][0]["proc"] == "http-worker-1"

    def test_merge_rejects_junk(self):
        assert not tracer.merge_remote("", [])
        assert not tracer.merge_remote("aa" * 16, [{"no_span_id": 1}])


# ------------------------------------------------------------ twin-process
def _req(port, method, path, body=None, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        hdrs = {"Content-Type": "application/json", **(headers or {})}
        conn.request(
            method, path,
            json.dumps(body).encode() if body is not None else None,
            hdrs,
        )
        r = conn.getresponse()
        data = r.read()
        return r.status, dict(r.getheaders()), data
    finally:
        conn.close()


@pytest.fixture(scope="module")
def fleet_setup():
    """Primary + 2 prefork workers with the fleet plane live; the
    primary's slow-query threshold is configured tiny BEFORE the pool
    spawns — workers adopt the primary's applied telemetry policy via
    the worker config (not just env), which is itself under test."""
    from nornicdb_tpu.telemetry.slowlog import slow_log

    old_threshold = slow_log.threshold_s
    slow_log.configure(threshold_s=1e-6)
    db = nornicdb_tpu.open_db("")
    db.set_embedder(HashEmbedder(32))
    rng = np.random.default_rng(7)
    for i in range(32):
        db.store(f"fleet telemetry document {i}")
    db.process_pending_embeddings()
    primary = HttpServer(db, port=0)
    primary.start()
    pool = WorkerPool(db, primary.port, n_workers=2,
                      metrics_interval=0.2).start()
    deadline = time.time() + 60
    up = False
    while time.time() < deadline:
        try:
            _req(pool.port, "GET", "/health")
            up = True
            break
        except OSError:
            time.sleep(0.25)
    assert up, "workers never started listening"
    yield db, primary, pool, rng
    pool.stop()
    primary.stop()
    db.close()
    slow_log.configure(threshold_s=old_threshold)
    slow_log.clear()


def _broker_search(pool, rng, tp=None, tries=40):
    """Drive a vector search through the pool until the device plane
    (broker) serves it; returns the response headers."""
    last = None
    for i in range(tries):
        vec = [float(x) for x in rng.normal(size=32)]
        status, headers, data = _req(
            pool.port, "POST", "/nornicdb/search",
            {"vector": vec, "limit": 3},
            headers={"traceparent": tp} if tp else None,
        )
        assert status == 200, data
        last = headers
        if headers.get("X-Nornic-Served") == "broker":
            return headers
        time.sleep(0.1)
    pytest.fail(f"broker never served a vector search: {last}")


@pytest.mark.usefixtures("fleet_setup")
class TestFleetE2E:
    def test_merged_metrics_carries_worker_proc_labels(self, fleet_setup):
        _db, primary, pool, rng = fleet_setup
        _broker_search(pool, rng)

        def _served_counter_live(samples):
            return any(n == "nornicdb_worker_requests_total"
                       and l.get("proc", "").startswith("http-worker-")
                       and l.get("served") in ("broker", "shm", "cache",
                                               "proxy") and v > 0
                       for n, l, v in samples)

        deadline = time.time() + 30
        text, samples = "", []
        while time.time() < deadline:
            _status, _h, data = _req(primary.port, "GET", "/metrics")
            text = data.decode()
            # every scrape of the federated exposition must parse strict
            _types, samples = parse_prometheus_strict(text)
            if ('proc="http-worker-0"' in text
                    and 'proc="http-worker-1"' in text
                    and _served_counter_live(samples)):
                break
            time.sleep(0.25)
        assert 'proc="http-worker-0"' in text, "worker 0 never federated"
        assert 'proc="http-worker-1"' in text, "worker 1 never federated"
        # worker serving-ladder counters visible with proc labels
        assert _served_counter_live(samples), \
            "no worker served-request counter moved in the merge"
        # HBM residency: the acceptance families render with components
        hbm = {l["component"]: v for n, l, v in samples
               if n == "nornicdb_hbm_bytes" and "proc" not in l}
        assert hbm.get("corpus_f32", 0) > 0
        assert "kv_pages" in hbm
        # fleet membership one-hot for the primary + both workers
        members = {l.get("proc"): v for n, l, v in samples
                   if n == "nornicdb_fleet_members"}
        assert members.get("primary") == 1.0
        assert members.get("http-worker-0") == 1.0
        assert members.get("http-worker-1") == 1.0

    def test_broker_trace_renders_one_cross_process_tree(
            self, fleet_setup):
        _db, primary, pool, rng = fleet_setup
        want = "1f" * 16
        tp = format_traceparent(want, "2e" * 8)
        _broker_search(pool, rng, tp=tp)
        deadline = time.time() + 20
        entry = None
        while time.time() < deadline:
            status, _h, data = _req(primary.port, "GET",
                                    f"/admin/traces/{want}")
            if status == 200:
                entry = json.loads(data)
                names = {s["name"] for s in entry["spans"]}
                if "worker.search" in names and "broker.search" in names:
                    break
            time.sleep(0.2)
        assert entry is not None, "trace never reached the primary"
        names = {s["name"] for s in entry["spans"]}
        assert "worker.search" in names, names  # shipped worker span
        assert "broker.search" in names, names  # primary handler span
        # spans from TWO processes in one tree: worker spans carry their
        # proc tag, primary spans don't
        procs = {s.get("proc") for s in entry["spans"]}
        assert any(p and p.startswith("http-worker-") for p in procs)
        assert None in procs
        # one tree, rooted at the worker ingress, with the primary's
        # handler nested through the broker-call span
        by_id = {s["span_id"]: s for s in entry["spans"]}
        broker_span = next(s for s in entry["spans"]
                           if s["name"] == "broker.search")
        cur, seen = broker_span, set()
        while cur is not None and cur["span_id"] not in seen:
            seen.add(cur["span_id"])
            if cur["name"] == "worker.search":
                break
            cur = by_id.get(cur.get("parent_id") or "")
        assert cur is not None and cur["name"] == "worker.search", (
            "broker.search is not a descendant of the worker ingress")
        # queue-wait attributed per caller inside the same trace
        assert "search.queue_wait" in names

    def test_worker_slow_queries_federated_with_attribution(
            self, fleet_setup):
        _db, primary, pool, rng = fleet_setup
        _broker_search(pool, rng)
        deadline = time.time() + 20
        mine = []
        while time.time() < deadline:
            _s, _h, data = _req(primary.port, "GET",
                                "/admin/slow-queries")
            entries = json.loads(data)["slow_queries"]
            mine = [e for e in entries
                    if e.get("proc", "").startswith("http-worker-")
                    and e.get("served") in ("broker", "shm", "proxy")]
            if mine:
                break
            time.sleep(0.25)
        assert mine, "no worker slow-query entry federated"
        assert mine[0]["query"].startswith("VECTOR SEARCH")

    def test_admin_stats_fleet_section(self, fleet_setup):
        _db, primary, pool, _rng = fleet_setup
        _s, _h, data = _req(primary.port, "GET", "/admin/stats")
        stats = json.loads(data)
        fleet = stats["fleet"]
        assert set(fleet["members"]) >= {"http-worker-0", "http-worker-1"}
        pool_half = fleet["pools"][0]
        assert pool_half["n_workers"] == 2
        procs = {w["proc"]: w for w in pool_half["workers"]}
        assert procs["http-worker-0"]["alive"]
        assert procs["http-worker-1"]["alive"]
        # deviceprof section rides along
        assert "deviceprof" in stats
        assert "hbm_bytes" in stats["deviceprof"]

    def test_admin_profile_returns_artifact(self, fleet_setup):
        _db, primary, _pool, _rng = fleet_setup
        status, headers, data = _req(
            primary.port, "POST", "/admin/profile?seconds=0.2")
        assert status == 200, data
        assert headers.get("Content-Type") == "application/gzip"
        assert data[:2] == b"\x1f\x8b"
        with tarfile.open(fileobj=io.BytesIO(data), mode="r:gz") as t:
            assert t.getnames(), "empty profiler artifact"

    def test_respawned_worker_rejoins_fleet(self, fleet_setup):
        _db, primary, pool, rng = fleet_setup
        killed = pool.kill_worker(0)
        assert killed is not None
        deadline = time.time() + 30
        while time.time() < deadline and pool.alive() < 2:
            time.sleep(0.2)
        assert pool.alive() == 2, "worker never respawned"
        # the respawned worker republishes into the SAME segment and
        # shows back up in the merge (fresh generation)
        deadline = time.time() + 30
        ok = False
        while time.time() < deadline:
            _s, _h, data = _req(primary.port, "GET", "/metrics")
            text = data.decode()
            if 'nornicdb_fleet_members{proc="http-worker-0"} 1' in text:
                ok = True
                break
            time.sleep(0.25)
        assert ok, "respawned worker never rejoined the merge"
