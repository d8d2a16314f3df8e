"""Tests for cache, config, encryption, audit, retention, eval, heimdall
(ref: pkg/cache, pkg/config, pkg/encryption, pkg/audit, pkg/retention,
pkg/eval, pkg/heimdall tests)."""

import json
import time

import pytest

import nornicdb_tpu
from nornicdb_tpu.audit import AuditLog
from nornicdb_tpu.cache import QueryCache
from nornicdb_tpu.config import AppConfig, FeatureFlags, load_from_env, load_from_file
from nornicdb_tpu.eval import EvalCase, Harness, mrr, ndcg_at_k, precision_at_k
from nornicdb_tpu.heimdall import HeimdallManager, TemplateGenerator
from nornicdb_tpu.retention import (
    ERASURE_COMPLETED,
    Policy,
    RetentionManager,
)
from nornicdb_tpu.storage import MemoryEngine, Node


class TestQueryCache:
    def test_hit_miss_ttl(self):
        c = QueryCache(capacity=10, ttl=0.05)
        assert c.get("q") is None
        c.put("q", None, "result", {"A"})
        assert c.get("q") == "result"
        assert c.stats.hits == 1
        time.sleep(0.06)
        assert c.get("q") is None  # expired

    def test_params_key(self):
        c = QueryCache()
        c.put("q", {"x": 1}, "r1")
        c.put("q", {"x": 2}, "r2")
        assert c.get("q", {"x": 1}) == "r1"
        assert c.get("q", {"x": 2}) == "r2"

    def test_lru_eviction(self):
        c = QueryCache(capacity=2, ttl=60)
        c.put("a", None, 1)
        c.put("b", None, 2)
        c.get("a")
        c.put("c", None, 3)  # evicts b (LRU)
        assert c.get("b") is None
        assert c.get("a") == 1

    def test_label_invalidation(self):
        c = QueryCache()
        c.put("qa", None, 1, {"Person"})
        c.put("qb", None, 2, {"Movie"})
        c.put("qc", None, 3, set())  # label-agnostic
        c.invalidate_labels({"Person"})
        assert c.get("qa") is None
        assert c.get("qb") == 2
        assert c.get("qc") is None  # agnostic entries always dropped

    def test_served_copies_isolate_every_mutable_kind(self):
        """A caller mutating a served value — including ndarrays, tuples'
        contents, sets, and nested entity properties — must never reach the
        cached object (cache poisoning)."""
        import numpy as np

        from nornicdb_tpu.cypher.executor import _copy_result
        from nornicdb_tpu.cypher.executor import Result, Stats

        node = Node(id="n1", properties={"tags": ["a"], "m": {"k": [1]}})
        row = [
            node,
            [np.asarray([1.0, 2.0], np.float32)],
            (np.asarray([3.0], np.float32), "x"),
            {"inner": {1, 2}},
        ]
        cached = Result(["n", "l", "t", "s"], [row], Stats(), None)
        served = _copy_result(cached)
        s_node, s_list, s_tup, s_set = served.rows[0]
        # mutate everything the caller can reach
        s_node.properties["tags"].append("EVIL")
        s_node.properties["m"]["k"].append(99)
        s_list[0][0] = -1.0
        s_tup[0][0] = -1.0
        s_set["inner"].add(3)
        # the cached source is untouched
        assert node.properties["tags"] == ["a"]
        assert node.properties["m"]["k"] == [1]
        assert float(row[1][0][0]) == 1.0
        assert float(row[2][0][0]) == 3.0
        assert row[3]["inner"] == {1, 2}

    def test_executor_integration(self):
        db = nornicdb_tpu.open_db("")
        db.cypher("CREATE (:C {v: 1})")
        r1 = db.cypher("MATCH (c:C) RETURN c.v")
        assert db.query_cache.stats.misses >= 1
        r2 = db.cypher("MATCH (c:C) RETURN c.v")
        assert db.query_cache.stats.hits >= 1
        assert r2.rows == r1.rows
        # write invalidates
        db.cypher("CREATE (:C {v: 2})")
        r3 = db.cypher("MATCH (c:C) RETURN count(c)")
        assert r3.rows == [[2]]  # not stale
        db.close()


class TestConfig:
    def test_yaml_and_env(self, tmp_path, monkeypatch):
        p = tmp_path / "nornicdb.yaml"
        p.write_text("server:\n  http_port: 9999\ndatabase:\n  async_writes: false\n")
        cfg = load_from_file(str(p))
        assert cfg.server.http_port == 9999
        assert cfg.database.async_writes is False
        monkeypatch.setenv("NORNICDB_SERVER_HTTP_PORT", "1234")
        cfg = load_from_env(cfg)
        assert cfg.server.http_port == 1234

    def test_feature_flags(self):
        f = FeatureFlags()
        assert f.is_kalman_enabled()
        f.set("kalman", False)
        assert not f.is_enabled("kalman")
        with f.with_enabled("kalman", True):
            assert f.is_enabled("kalman")
        assert not f.is_enabled("kalman")


@pytest.fixture
def encryption_mod():
    """nornicdb_tpu.encryption needs the optional `cryptography` package;
    a bare-deps tier-1 run must skip, not error (module-level import would
    take the whole file's collection down with it)."""
    pytest.importorskip("cryptography")
    from nornicdb_tpu import encryption

    return encryption


class TestEncryption:
    def test_roundtrip(self, encryption_mod):
        Encryptor, new_salt = encryption_mod.Encryptor, encryption_mod.new_salt
        salt = new_salt()
        enc = Encryptor.from_passphrase("hunter2", salt, iterations=1000)
        blob = enc.encrypt(b"secret payload")
        assert blob != b"secret payload"
        assert enc.decrypt(blob) == b"secret payload"

    def test_wrong_key_fails(self, encryption_mod):
        Encryptor, new_salt = encryption_mod.Encryptor, encryption_mod.new_salt
        salt = new_salt()
        enc1 = Encryptor.from_passphrase("right", salt, iterations=1000)
        enc2 = Encryptor.from_passphrase("wrong", salt, iterations=1000)
        blob = enc1.encrypt(b"data")
        with pytest.raises(Exception):
            enc2.decrypt(blob)

    def test_derive_deterministic(self, encryption_mod):
        derive_key = encryption_mod.derive_key
        salt = b"x" * 16
        assert derive_key("pw", salt, 1000) == derive_key("pw", salt, 1000)


class TestAudit:
    def test_chain_and_verify(self, tmp_path):
        log = AuditLog(str(tmp_path / "audit.jsonl"))
        log.record("login_ok", "alice", {"ip": "10.0.0.1"})
        log.record("node_deleted", "bob")
        assert log.verify_chain()
        assert len(log.events("login_ok")) == 1
        # reload from disk preserves the chain
        log2 = AuditLog(str(tmp_path / "audit.jsonl"))
        assert log2.verify_chain()
        assert len(log2.events()) == 2

    def test_tamper_detected(self, tmp_path):
        log = AuditLog()
        log.record("a", "x")
        log.record("b", "y")
        log._events[0].detail["injected"] = True
        assert not log.verify_chain()

    def test_auth_hook_integration(self):
        from nornicdb_tpu.auth import Authenticator, ROLE_VIEWER

        log = AuditLog()
        auth = Authenticator(MemoryEngine(), audit_hook=log.auth_hook())
        auth.create_user("u", "pw", ROLE_VIEWER)
        auth.authenticate("u", "pw")
        assert [e.event for e in log.events()] == ["user_created", "login_ok"]


class TestRetention:
    def _mgr(self, now):
        eng = MemoryEngine()
        mgr = RetentionManager(eng, now_fn=lambda: now[0])
        return eng, mgr

    def test_policy_enforcement(self):
        now = [1000.0]
        eng, mgr = self._mgr(now)
        n = Node(id="old", properties={"category": "logs"})
        n.created_at = 0.0
        eng.create_node(n)
        fresh = Node(id="fresh", properties={"category": "logs"})
        fresh.created_at = 999.0
        eng.create_node(fresh)
        mgr.set_policy(Policy("logs", max_age=500.0))
        out = mgr.enforce()
        assert out["deleted"] == 1
        assert eng.node_count() == 1

    def test_legal_hold_blocks(self):
        now = [1000.0]
        eng, mgr = self._mgr(now)
        n = Node(id="held", properties={"category": "logs"})
        n.created_at = 0.0
        eng.create_node(n)
        mgr.set_policy(Policy("logs", max_age=100.0))
        hold = mgr.create_hold("litigation", node_ids={"held"})
        out = mgr.enforce()
        assert out == {"deleted": 0, "archived": 0, "held": 1}
        mgr.release_hold(hold.id)
        assert mgr.enforce()["deleted"] == 1

    def test_erasure_workflow(self):
        now = [1000.0]
        eng, mgr = self._mgr(now)
        eng.create_node(Node(id="d1", properties={"owner": "user-7"}))
        eng.create_node(Node(id="d2", properties={"owner": "user-7"}))
        eng.create_node(Node(id="other", properties={"owner": "someone"}))
        req = mgr.request_erasure("user-7")
        assert mgr.export_subject("user-7") and len(mgr.export_subject("user-7")) == 2
        with pytest.raises(Exception):
            mgr.execute_erasure(req.id)  # must approve first
        mgr.approve_erasure(req.id)
        done = mgr.execute_erasure(req.id)
        assert done.status == ERASURE_COMPLETED
        assert done.erased_count == 2
        assert eng.node_count() == 1


class TestEval:
    def test_metric_math(self):
        assert precision_at_k(["a", "b", "x"], {"a", "b"}, 3) == pytest.approx(2 / 3)
        assert mrr(["x", "a"], {"a"}) == 0.5
        assert ndcg_at_k(["a", "b"], ["a", "b"], 2) == pytest.approx(1.0)

    def test_harness_with_search_service(self):
        db = nornicdb_tpu.open_db("")
        from nornicdb_tpu.embed import HashEmbedder

        db.set_embedder(HashEmbedder(64))
        ids = {}
        for key, text in {
            "tpu": "TPU accelerators multiply matrices fast",
            "graph": "graph databases store nodes and relationships",
            "cook": "slow cooked stew with carrots",
        }.items():
            ids[key] = db.store(text).id
        db.process_pending_embeddings()
        harness = Harness(
            lambda q, k: [r["id"] for r in db.search.search(q, limit=k)],
            k=2, thresholds={"mrr": 0.5},
        )
        report = harness.run(
            [
                EvalCase("TPU matrices", [ids["tpu"]]),
                EvalCase("graph nodes relationships", [ids["graph"]]),
            ]
        )
        assert report.passed
        assert report.metrics.mrr == 1.0
        db.close()


class TestHeimdall:
    def test_template_chat_with_db_context(self):
        db = nornicdb_tpu.open_db("")
        db.cypher("CREATE (:M {content: 'x'}), (:M {content: 'y'})")
        resp = db.heimdall.chat([{"role": "user", "content": "How many nodes are there?"}])
        assert "2 nodes" in resp["choices"][0]["message"]["content"]
        db.close()

    def test_action_parsing_and_execution(self):
        mgr = HeimdallManager(TemplateGenerator(None))
        action = mgr.try_parse_action('blah {"action": "hello", "params": {}} blah')
        assert action == {"action": "hello", "params": {}}
        mgr.register_action("echo", lambda p: {"echoed": p.get("v")})
        resp = mgr.chat([{"role": "user", "content": "status please"}])
        # template generator answers status questions with an action JSON
        assert resp["choices"][0]["message"]["content"]

    def test_bifrost_broadcast(self):
        mgr = HeimdallManager(TemplateGenerator(None))
        q = mgr.bifrost.subscribe()
        mgr.chat([{"role": "user", "content": "hi"}])
        event = q.get(timeout=1)
        assert event["event"] == "chat"

    def test_streaming_chunks(self):
        mgr = HeimdallManager(TemplateGenerator(None))
        chunks = list(mgr.chat_stream([{"role": "user", "content": "hi"}]))
        assert chunks[-1]["choices"][0]["finish_reason"] == "stop"
        text = "".join(
            c["choices"][0]["delta"].get("content", "") for c in chunks
        )
        assert "Heimdall" in text

    def test_qwen_generator_runs(self):
        from nornicdb_tpu.config import GenServeConfig
        from nornicdb_tpu.heimdall import EngineGenerator, QwenGenerator

        gen = QwenGenerator()
        with pytest.raises(RuntimeError, match="genserve engine only"):
            gen.generate("hello world", max_tokens=4)
        served = EngineGenerator.serving(
            gen, config=GenServeConfig(deadline_ms=0))
        try:
            out = served.generate("hello world", max_tokens=4)
        finally:
            served.engine.stop()
        assert isinstance(out, str) and out

    def test_http_chat_endpoint(self):
        import urllib.request

        from nornicdb_tpu.server import HttpServer

        db = nornicdb_tpu.open_db("")
        server = HttpServer(db, port=0)
        server.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/api/bifrost/chat/completions",
                data=json.dumps(
                    {"messages": [{"role": "user", "content": "how many nodes?"}]}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req) as resp:
                out = json.loads(resp.read())
            assert out["object"] == "chat.completion"
        finally:
            server.stop()
            db.close()


class TestKmeansTestData:
    """ref: cmd/kmeans-test-data — deterministic corpora generators."""

    def test_clusters_mode_generates_and_imports(self, tmp_path):
        from nornicdb_tpu.cli import main as cli_main
        import numpy as np

        out = str(tmp_path / "gen")
        dbdir = str(tmp_path / "db")
        rc = cli_main([
            "kmeans-test-data", "--mode", "clusters", "--count", "200",
            "--dims", "16", "--clusters", "4", "--out", out,
            "--db", dbdir, "--seed", "7",
        ])
        assert rc == 0
        data = np.load(f"{out}/embeddings.npz")
        assert data["embeddings"].shape == (200, 16)
        assert set(np.unique(data["cluster"])) <= set(range(4))
        # unit-normalized rows (cosine-ready)
        norms = np.linalg.norm(data["embeddings"], axis=1)
        assert np.allclose(norms, 1.0, atol=1e-5)
        # imported nodes carry embeddings + cluster labels
        import nornicdb_tpu

        db = nornicdb_tpu.open_db(dbdir)
        try:
            nodes = db.storage.get_nodes_by_label("KMeansTest")
            assert len(nodes) == 200
            assert nodes[0].embedding is not None
        finally:
            db.close()

    def test_synthetic_mode(self, tmp_path):
        from nornicdb_tpu.cli import main as cli_main
        import numpy as np

        out = str(tmp_path / "gen2")
        rc = cli_main(["kmeans-test-data", "--mode", "synthetic",
                       "--count", "50", "--dims", "8", "--out", out])
        assert rc == 0
        data = np.load(f"{out}/embeddings.npz")
        assert data["embeddings"].shape == (50, 8)
        assert "cluster" not in data
