"""Core DB facade (ref: /root/reference/pkg/nornicdb/db.go).

`open()` assembles the storage chain, schema manager, search service, embed
queue, decay manager and inference engine, and exposes the memory-centric API:
Store / Recall / Remember / Link / Neighbors / Forget / Cypher
(ref: db.go:1365-1776).

Subsystems are attached progressively; the facade stays importable with only
the storage layer present.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

from nornicdb_tpu.errors import NotFoundError
from nornicdb_tpu.storage import (
    Edge,
    Engine,
    Node,
    SchemaManager,
    new_id,
    open_storage,
)
from nornicdb_tpu.telemetry.metrics import count_error

log = logging.getLogger(__name__)


@dataclass
class Config:
    """DB configuration (ref: pkg/config/config.go:82-420, subset)."""

    async_writes: bool = True
    flush_interval: float = 0.05
    wal_sync: bool = False
    # at-rest encryption (ref: db.go:781-809 — PBKDF2-derived key)
    encryption_passphrase: str = ""
    # durable engine: wal (memory + WAL replay) | segment (native C++ KV)
    storage_engine: str = "wal"
    auto_compact: bool = False
    auto_compact_interval: float = 300.0
    # embedding
    embed_enabled: bool = True
    embed_dimensions: int = 1024
    embed_chunk_tokens: int = 512
    embed_chunk_overlap: int = 50
    embed_workers: int = 1
    # decay
    decay_enabled: bool = False
    decay_interval: float = 3600.0
    archive_threshold: float = 0.05
    # inference (auto-TLP)
    inference_enabled: bool = True
    similarity_threshold: float = 0.85
    # integration adapters (ref: topology_integration.go, cluster_integration.go)
    topology_integration: bool = False
    cluster_integration: bool = False
    # search
    search_brute_force_max: int = 5000
    # query cache (ref: pkg/cache, ConfigureGlobalCache main.go:320)
    query_cache_enabled: bool = True
    query_cache_size: int = 1000
    query_cache_ttl: float = 60.0
    log_queries: bool = False  # (ref: --log-queries cmd/nornicdb/main.go:137)
    feature_flags: dict[str, bool] = field(default_factory=dict)


class DB:
    """The core database handle (ref: nornicdb.DB db.go:434)."""

    def __init__(self, data_dir: str = "", config: Optional[Config] = None):
        self.config = config or Config()
        self.data_dir = data_dir
        self._base_storage: Engine = open_storage(
            data_dir,
            async_writes=self.config.async_writes,
            flush_interval=self.config.flush_interval,
            wal_sync=self.config.wal_sync,
            auto_compact=self.config.auto_compact,
            auto_compact_interval=self.config.auto_compact_interval,
            encryption_passphrase=self.config.encryption_passphrase,
            engine=self.config.storage_engine,
        )
        # The default database is itself a namespace on the shared base
        # engine, exactly like the reference's "nornic" namespace
        # (ref: NamespacedEngine wrap, db.go:896) — so multi-database views
        # never leak into default-DB scans.
        from nornicdb_tpu.multidb import DEFAULT_DB
        from nornicdb_tpu.storage import NamespacedEngine

        self.default_database = DEFAULT_DB
        self._migrate_unprefixed(self._base_storage, DEFAULT_DB)
        self.storage: Engine = NamespacedEngine(self._base_storage, DEFAULT_DB)
        self.schema = SchemaManager()
        self.schema.attach(self.storage)
        self._lock = threading.RLock()
        self._closed = False
        self._decay_started = False
        # attached lazily by subsystem setters
        self._embedder = None
        self._embed_worker = None
        self._search = None
        self._decay = None
        self._inference = None
        self._temporal = None
        self._executor = None
        self._dbmanager = None
        self._db_executors: dict[str, Any] = {}
        self._query_cache = None
        self._heimdall = None
        self._genserve = None
        self._graphrag = None
        self._vectorspaces = None
        self._qdrant = None
        if self.config.decay_enabled:
            _ = self.decay  # starts the periodic recalculation ticker

    @staticmethod
    def _migrate_unprefixed(base: Engine, namespace: str) -> None:
        """Re-key data persisted before namespacing (bare uuid ids) into the
        default namespace so old data dirs keep working."""
        stale_nodes = [n for n in base.all_nodes() if ":" not in n.id]
        if not stale_nodes:
            return
        stale_edges = [e for e in base.all_edges() if ":" not in e.id]
        pending = set(base.pending_embed_ids())
        for e in stale_edges:
            base.delete_edge(e.id)
        for n in stale_nodes:
            base.delete_node(n.id)
        for n in stale_nodes:
            migrated = n.copy()
            migrated.id = f"{namespace}:{n.id}"
            base.create_node(migrated)
            if n.id in pending:
                base.mark_pending_embed(migrated.id)
        for e in stale_edges:
            migrated = e.copy()
            migrated.id = f"{namespace}:{e.id}"
            if ":" not in migrated.start_node:
                migrated.start_node = f"{namespace}:{migrated.start_node}"
            if ":" not in migrated.end_node:
                migrated.end_node = f"{namespace}:{migrated.end_node}"
            base.create_edge(migrated)

    def invalidate_database_cache(self, name: str) -> None:
        """Drop the cached per-DB executor after DROP DATABASE / limit changes."""
        with self._lock:
            self._db_executors.pop(name, None)

    # -- subsystem wiring --------------------------------------------------
    def set_embedder(self, embedder) -> None:
        """(ref: DB.SetEmbedder db.go:1074) — also starts the embed worker."""
        old_engine = self.serving_engine()
        self._embedder = embedder
        if old_engine is not None and old_engine is not self.serving_engine():
            # the replaced chain carried a continuous batching engine:
            # stop its pipeline threads instead of leaking them
            old_engine.stop()
        if self._search is not None:
            self._search.embedder = embedder
        if self._embed_worker is not None:
            self._embed_worker.stop()
            self._embed_worker = None
        if self.config.embed_enabled and embedder is not None:
            from nornicdb_tpu.embed.queue import EmbedWorker, EmbedWorkerConfig

            self._embed_worker = EmbedWorker(
                # the worker drains the BASE engine so pending nodes from
                # every database namespace get embedded, not just the default
                self._base_storage,
                embedder,
                EmbedWorkerConfig(
                    chunk_tokens=self.config.embed_chunk_tokens,
                    chunk_overlap=self.config.embed_chunk_overlap,
                    workers=self.config.embed_workers,
                ),
                # debounced k-means refit after bulk embedding
                # (ref: scheduleClusteringDebounced embed_queue.go:257)
                on_cluster_trigger=lambda: self.search.recluster(),
                # the learning loop: freshly-embedded nodes feed auto-TLP
                # (ref: SURVEY.md §3.3 embed -> inference.OnStore)
                on_embedded=self._on_embedded,
            )
            self._embed_worker.start()

    def _on_embedded(self, node) -> None:
        # node comes from the base engine with a namespaced id; auto-TLP
        # currently runs over the default database only
        prefix = f"{self.default_database}:"
        if self.config.inference_enabled and node.id.startswith(prefix):
            bare = node.copy()
            bare.id = node.id[len(prefix):]
            self.inference.on_store(bare)

    @property
    def embedder(self):
        return self._embedder

    def serving_engine(self):
        """The continuous batching ServingEngine in the embedder chain
        (CachedEmbedder(ServingEngine(inner)) is the `cli serve` stack),
        or None when serving isn't engine-fronted."""
        from nornicdb_tpu.serving import ServingEngine

        e = self._embedder
        seen = 0
        while e is not None and seen < 8:
            if isinstance(e, ServingEngine):
                return e
            e = getattr(e, "inner", None)
            seen += 1
        return None

    @property
    def search(self):
        with self._lock:
            svc = self._search
        if svc is not None:
            return svc
        from nornicdb_tpu.search.service import SearchService

        # construct + backfill OUTSIDE the db lock: the index build may
        # cold-acquire the device backend (bounded by the lifecycle
        # manager, but still seconds — NL-DEV01 bans it under any lock)
        # and can itself take seconds on a large corpus. Losers of the
        # creation race detach their event subscription and discard.
        svc = SearchService(
            self.storage,
            embedder=self._embedder,
            brute_force_max=self.config.search_brute_force_max,
            vectorspaces=self.vectorspaces,
        )
        # wire storage events + backfill existing nodes
        # (ref: db.go:1020-1033, EnsureSearchIndexesBuilt db.go:1044)
        svc.attach(self.storage)
        svc.build_indexes()
        with self._lock:
            if self._search is None:
                self._search = svc
                return svc
            winner = self._search
        svc.detach(self.storage)
        svc.shutdown()  # stop the loser's uploader thread; let it GC
        return winner

    @property
    def vectorspaces(self):
        """Canonical named vector spaces (ref: pkg/vectorspace registry)."""
        with self._lock:
            if self._vectorspaces is None:
                from nornicdb_tpu.vectorspace import VectorSpaceRegistry

                self._vectorspaces = VectorSpaceRegistry()
            return self._vectorspaces

    def qdrant_registry(self):
        """The ONE QdrantCollections registry for this db: the HTTP
        /collections/* surface, the Qdrant gRPC services, and the device
        broker's worker-side search path must share it — per-transport
        registries would each build their own per-collection device
        corpora (double residency) and drift on upserts (ref: the
        reference's "single unified vector index", pkg/qdrantgrpc
        server.go).

        Constructed OUTSIDE the db lock (the `search` property's
        pattern): the registry rebuild scans every persisted point and
        builds per-collection device corpora — seconds on a large point
        set, and every db-lock user would stall behind it. Losers of the
        creation race discard their registry before it serves anything."""
        with self._lock:
            if self._qdrant is not None:
                return self._qdrant
        from nornicdb_tpu.server.qdrant import QdrantCollections

        registry = QdrantCollections(
            self.storage, vectorspaces=self.vectorspaces
        )
        with self._lock:
            if self._qdrant is None:
                self._qdrant = registry
            return self._qdrant

    @property
    def query_cache(self):
        if self._query_cache is None:
            from nornicdb_tpu.cache import QueryCache
            from nornicdb_tpu.storage import Edge as _Edge, Node as _Node

            cache = QueryCache(
                capacity=self.config.query_cache_size,
                ttl=self.config.query_cache_ttl,
            )

            # Direct storage mutations (store/forget, decay, retention,
            # Qdrant upserts) must invalidate too — not just Cypher writes.
            def _on_event(kind: str, entity) -> None:
                if isinstance(entity, _Node):
                    if entity.labels:
                        cache.invalidate_labels(set(entity.labels))
                    else:
                        cache.clear()
                elif isinstance(entity, _Edge):
                    labels: set = set()
                    for nid in (entity.start_node, entity.end_node):
                        try:
                            labels.update(self.storage.get_node(nid).labels)
                        except Exception:
                            # endpoint vanished mid-event: we can't scope the
                            # invalidation, so drop everything (sound) — but
                            # leave a trace + counter so a hot loop of these
                            # (cache thrash) is visible to operators
                            log.debug("query-cache label scope lookup failed "
                                      "for %s; clearing cache", nid,
                                      exc_info=True)
                            count_error("db.query_cache_invalidate")
                            cache.clear()
                            return
                    if labels:
                        cache.invalidate_labels(labels)
                    else:
                        cache.clear()

            self.storage.on_event(_on_event)
            self._query_cache = cache
        return self._query_cache

    @property
    def executor(self):
        if self._executor is None:
            from nornicdb_tpu.cypher.executor import CypherExecutor

            cache = self.query_cache if self.config.query_cache_enabled else None
            self._executor = CypherExecutor(
                self.storage, schema=self.schema, db=self, cache=cache,
                log_queries=self.config.log_queries,
            )
        return self._executor

    @property
    def heimdall(self):
        """(ref: pkg/heimdall manager wiring). With a trained checkpoint
        mounted (NORNICDB_ASSISTANT_MODEL=<dir>, produced by
        `nornicdb train` / models.pretrain.train_assistant) the assistant
        runs the real prefill+KV-cache decode path; otherwise the
        deterministic template fallback (ref: llama_stub.go builds)."""
        if self._heimdall is None:
            from nornicdb_tpu.heimdall import HeimdallManager, TemplateGenerator

            generator = None
            model_dir = os.environ.get("NORNICDB_ASSISTANT_MODEL", "")
            if model_dir:
                try:
                    from nornicdb_tpu.models.pretrain import load_generator

                    generator = load_generator(model_dir)
                except Exception:  # bad checkpoint: fall back, loudly
                    log.warning(
                        "assistant checkpoint %r failed to load; using "
                        "template generator", model_dir, exc_info=True,
                    )
                    count_error("heimdall.checkpoint_load")
            if generator is None:
                generator = TemplateGenerator(self)
            self._heimdall = HeimdallManager(
                self._wire_genserve(generator), db=self)
        return self._heimdall

    def set_heimdall_generator(self, generator) -> None:
        from nornicdb_tpu.heimdall import HeimdallManager

        self._heimdall = HeimdallManager(
            self._wire_genserve(generator), db=self)

    def _wire_genserve(self, generator):
        """Front a weights-backed generator with the genserve
        continuous-batching engine (paged-KV decode, admission control,
        deadline shedding — docs/generation.md): the one way a decoder is
        served.  Template/stub generators pass through unchanged."""
        if self._genserve is not None:
            self._genserve.stop()
            self._genserve = None
        self._graphrag = None  # rebuilt against the new engine on demand
        if not all(hasattr(generator, a)
                   for a in ("params", "cfg", "tokenizer")):
            return generator
        from nornicdb_tpu.heimdall import EngineGenerator

        served = EngineGenerator.serving(generator)
        self._genserve = served.engine
        return served

    def genserve_engine(self):
        """The generation engine behind Heimdall, or None when generation
        is template-backed / disabled (observability surfaces must not
        force the assistant to build)."""
        return self._genserve

    def graphrag(self):
        """GraphRAG answer service over this DB's search + adjacency +
        generation engine (``POST /nornicdb/rag/answer``).  Cached: the
        service resolves its config once, not per request."""
        if self._graphrag is None:
            from nornicdb_tpu.genserve import GraphRAGService

            _ = self.heimdall  # builds the engine when weights exist
            self._graphrag = GraphRAGService(self, engine=self._genserve)
        return self._graphrag

    @property
    def decay(self):
        if self._decay is None:
            from nornicdb_tpu.decay.decay import DecayConfig, DecayManager

            self._decay = DecayManager(
                self.storage,
                config=DecayConfig(
                    archive_threshold=self.config.archive_threshold,
                    interval=self.config.decay_interval,
                ),
            )
            if self.config.decay_enabled and not self._decay_started:
                # periodic recalculation ticker (ref: decay.Start decay.go:643)
                self._decay.start()
                self._decay_started = True
        return self._decay

    @property
    def inference(self):
        if self._inference is None:
            from nornicdb_tpu.inference.engine import InferenceEngine

            engine = InferenceEngine(
                self.storage,
                similarity_fn=self._similarity_candidates,
                similarity_threshold=self.config.similarity_threshold,
            )
            if self.config.topology_integration:
                from nornicdb_tpu.inference.integrations import TopologyIntegration

                TopologyIntegration(self.storage).attach(engine)
            if self.config.cluster_integration:
                from nornicdb_tpu.inference.integrations import ClusterIntegration

                ClusterIntegration(
                    lambda: self.search.cluster_assignments
                ).attach(engine)
            self._inference = engine
        return self._inference

    @property
    def database_manager(self):
        """(ref: multidb.NewDatabaseManager cmd/nornicdb/main.go:501)"""
        with self._lock:
            if self._dbmanager is None:
                from nornicdb_tpu.multidb import DatabaseManager

                self._dbmanager = DatabaseManager(
                    self._base_storage,
                    on_invalidate=self.invalidate_database_cache,
                )
            return self._dbmanager

    def session_executor(self, database: Optional[str] = None):
        """A FRESH executor with its own explicit-transaction scope, for
        per-connection sessions (Bolt BEGIN/COMMIT isolation). Shares
        storage, schema, facade hooks and the query cache."""
        from nornicdb_tpu.cypher.executor import CypherExecutor

        if database and self.database_manager.resolve(database) != self.default_database:
            # share the database's CACHED schema (executor_for builds and
            # attaches it once): a fresh SchemaManager per session would
            # forget indexes/constraints created by earlier requests and
            # leak a permanent on_event subscription + full-store scan
            # per session
            base = self.executor_for(database)
            return CypherExecutor(base.storage, schema=base.schema, db=self,
                                  log_queries=self.config.log_queries)
        cache = self.query_cache if self.config.query_cache_enabled else None
        return CypherExecutor(self.storage, schema=self.schema, db=self,
                              cache=cache,
                              log_queries=self.config.log_queries)

    def executor_for(self, database: str):
        """Per-database Cypher executor over the namespaced engine
        (ref: :USE handling executor.go:500-541). Cached under the RESOLVED
        name so alias-routed executors die with their target database."""
        database = self.database_manager.resolve(database)
        if database == self.default_database:
            return self.executor
        with self._lock:
            ex = self._db_executors.get(database)
            if ex is None:
                from nornicdb_tpu.cypher.executor import CypherExecutor
                from nornicdb_tpu.storage import SchemaManager

                storage = self.database_manager.get_storage(database)
                schema = SchemaManager()
                schema.attach(storage)
                ex = CypherExecutor(storage, schema=schema, db=self,
                                    log_queries=self.config.log_queries)
                self._db_executors[database] = ex
            return ex

    @property
    def temporal(self):
        if self._temporal is None:
            from nornicdb_tpu.temporal.tracker import TemporalTracker

            self._temporal = TemporalTracker()
        return self._temporal

    def _similarity_candidates(self, embedding, k: int = 10):
        return self.search.vector_candidates(embedding, k=k)

    # -- memory-centric API (ref: db.go:1365-1776) --------------------------
    def store(
        self,
        content: str,
        *,
        labels: Optional[list[str]] = None,
        properties: Optional[dict[str, Any]] = None,
        memory_type: str = "semantic",
        node_id: Optional[str] = None,
    ) -> Node:
        """Store a memory node; queues it for auto-embedding (ref: Store db.go:1365)."""
        props = dict(properties or {})
        props.setdefault("content", content)
        node = Node(
            id=node_id or new_id(),
            labels=list(labels or ["Memory"]),
            properties=props,
            memory_type=memory_type,
        )
        created = self.storage.create_node(node)
        if self.config.embed_enabled:
            self.storage.mark_pending_embed(created.id)
        if self.config.inference_enabled and self._inference is not None:
            self._inference.on_store(created)
        return created

    def recall(self, query: str, limit: int = 10) -> list[dict[str, Any]]:
        """Hybrid search over stored memories (ref: Recall db.go)."""
        results = self.search.search(query, limit=limit)
        for r in results:
            self.touch(r["id"])
        return results

    def remember(self, node_id: str) -> Node:
        """Fetch + reinforce a memory (ref: Remember db.go)."""
        node = self.touch(node_id)
        if self.config.inference_enabled:
            self.inference.on_access(node_id)
        return node

    def touch(self, node_id: str) -> Node:
        """Record an access: bump access_count + last_accessed."""
        node = self.storage.get_node(node_id)
        node.access_count += 1
        node.last_accessed = time.time()
        if self._temporal is not None:
            self._temporal.record_access(node_id)
        return self.storage.update_node(node)

    def link(
        self,
        from_id: str,
        to_id: str,
        rel_type: str = "RELATED_TO",
        *,
        properties: Optional[dict[str, Any]] = None,
        confidence: float = 1.0,
        auto_generated: bool = False,
    ) -> Edge:
        """(ref: Link db.go)"""
        edge = Edge(
            start_node=from_id,
            end_node=to_id,
            type=rel_type,
            properties=dict(properties or {}),
            confidence=confidence,
            auto_generated=auto_generated,
        )
        return self.storage.create_edge(edge)

    def neighbors(self, node_id: str, depth: int = 1) -> list[Node]:
        """BFS neighborhood (ref: Neighbors db.go)."""
        seen = {node_id}
        frontier = [node_id]
        out: list[Node] = []
        for _ in range(depth):
            nxt: list[str] = []
            for nid in frontier:
                for e in self.storage.get_outgoing_edges(nid):
                    if e.end_node not in seen:
                        seen.add(e.end_node)
                        nxt.append(e.end_node)
                for e in self.storage.get_incoming_edges(nid):
                    if e.start_node not in seen:
                        seen.add(e.start_node)
                        nxt.append(e.start_node)
            out.extend(self.storage.batch_get_nodes(nxt))
            frontier = nxt
        return out

    def forget(self, node_id: str) -> None:
        """(ref: Forget db.go) — index removal rides the node_deleted event."""
        self.storage.delete_node(node_id)

    # -- Cypher ------------------------------------------------------------
    def cypher(self, query: str, params: Optional[dict[str, Any]] = None):
        """Execute a Cypher query (ref: ExecuteCypher db.go)."""
        return self.executor.execute(query, params or {})

    execute_cypher = cypher

    # -- maintenance -------------------------------------------------------
    def process_pending_embeddings(self, batch: int = 0) -> int:
        """Synchronously drain the pending-embed queue (test/CLI hook)."""
        if self._embed_worker is None:
            return 0
        return self._embed_worker.drain(batch)

    def flush(self) -> None:
        self.storage.flush()

    def wal_stats(self) -> Optional[dict[str, Any]]:
        """WAL health incl. degraded-mode flag (ref: wal_degraded.go), or
        None when the store has no WAL (in-memory / segment engine)."""
        eng = self._base_storage
        while eng is not None:
            wal = getattr(eng, "wal", None)
            if wal is not None:
                return dict(vars(wal.stats))
            eng = getattr(eng, "base", None)
        return None

    def adjacency_stats(self) -> Optional[dict[str, Any]]:
        """CSR adjacency snapshot counters (storage/adjacency.py), or None
        before the first traversal/GDS query attaches one."""
        snap = getattr(self.storage, "_adjacency_snapshot", None)
        return snap.stats_snapshot() if snap is not None else None

    def cypher_stats(self) -> Optional[dict[str, Any]]:
        """Columnar Cypher engine counters (plan-cache hit/miss/
        invalidations + per-outcome query counts), or None before the
        executor exists — stats must never force its lazy construction."""
        col = getattr(self._executor, "columnar", None)
        return col.stats_snapshot() if col is not None else None

    # -- backup / restore (ref: badger_backup.go + /admin/backup,
    # db_admin.go admin ops) -----------------------------------------------
    def backup(self, dest_path: Optional[str] = None) -> str:
        """Full-fidelity gzip backup of the BASE engine — every database
        namespace, with embeddings/decay/access state intact (export_json
        deliberately drops those; backup must not) — plus the default-db
        schema. Returns the archive path."""
        import gzip
        import json as _json
        import time as _time

        self.flush()
        if dest_path is None:
            bdir = os.path.join(self.data_dir or ".", "backups")
            os.makedirs(bdir, exist_ok=True)
            stamp = _time.strftime("%Y%m%d-%H%M%S")
            dest_path = os.path.join(bdir, f"backup-{stamp}.json.gz")
            seq = 1
            while os.path.exists(dest_path):  # two backups in one second
                dest_path = os.path.join(
                    bdir, f"backup-{stamp}-{seq}.json.gz")
                seq += 1
        nodes = [n.to_dict() for n in self._base_storage.all_nodes()]
        node_ids = {n["id"] for n in nodes}
        # the two passes are not one atomic snapshot: a concurrent writer
        # can add a node+edge between them. Keep the archive a consistent
        # prefix by dropping edges whose endpoints missed the node pass.
        edges = [
            e.to_dict() for e in self._base_storage.all_edges()
            if e.start_node in node_ids and e.end_node in node_ids
        ]
        payload = {
            "version": 1,
            "nodes": nodes,
            "edges": edges,
            "pending_embed": list(self._base_storage.pending_embed_ids()),
            "schema": {
                "indexes": [
                    {"name": i.name, "kind": i.kind, "label": i.label,
                     "properties": list(i.properties),
                     "options": dict(i.options)}
                    for i in self.schema.list_indexes()
                ],
                "constraints": [
                    {"name": c.name, "label": c.label,
                     "properties": list(c.properties), "kind": c.kind}
                    for c in self.schema.list_constraints()
                ],
            },
        }
        tmp = dest_path + ".tmp"
        with gzip.open(tmp, "wt") as f:
            _json.dump(payload, f)
        os.replace(tmp, dest_path)  # a torn backup must never look complete
        return dest_path

    def restore(self, src_path: str, skip_existing: bool = True) -> dict:
        """Load a backup archive into the base engine. Existing records are
        kept (skip_existing) or cause an error; returns counts."""
        import gzip
        import json as _json

        from nornicdb_tpu.errors import AlreadyExistsError
        from nornicdb_tpu.storage.types import Edge, Node

        with gzip.open(src_path, "rt") as f:
            payload = _json.load(f)
        # DDL first so the index value-maps exist while data loads
        sch = payload.get("schema", {})
        for i in sch.get("indexes", []):
            self.schema.create_index(i["name"], i["kind"], i["label"],
                                     i["properties"], i.get("options"),
                                     if_not_exists=True)
        for c in sch.get("constraints", []):
            self.schema.create_constraint(c["name"], c["label"],
                                          c["properties"], c.get("kind", "unique"),
                                          if_not_exists=True)
        n_nodes = n_edges = skipped_edges = 0
        for nd in payload.get("nodes", []):
            try:
                self._base_storage.create_node(Node.from_dict(nd))
                n_nodes += 1
            except AlreadyExistsError:
                if not skip_existing:
                    raise
        for ed in payload.get("edges", []):
            try:
                self._base_storage.create_edge(Edge.from_dict(ed))
                n_edges += 1
            except AlreadyExistsError:
                if not skip_existing:
                    raise
            except NotFoundError:
                skipped_edges += 1  # dangling edge in a foreign archive
        for nid in payload.get("pending_embed", []):
            self._base_storage.mark_pending_embed(nid)
        # schema value-maps only fill from storage events on the default-DB
        # view; restored records arrive via the base engine, so backfill the
        # index/constraint maps explicitly (idempotent)
        for n in self.storage.all_nodes():
            self.schema.index_node(n)
        # a live DatabaseManager caches the database list in memory; an
        # archive can introduce new databases (system-DB metadata nodes)
        if self._dbmanager is not None:
            self._dbmanager._load_metadata()
        out = {"nodes": n_nodes, "edges": n_edges}
        if skipped_edges:
            out["skipped_edges"] = skipped_edges
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._embed_worker is not None:
            self._embed_worker.stop()
        engine = self.serving_engine()
        if engine is not None:
            # stop the continuous batching pipeline; queued requests fail
            # fast with ClosedError instead of stranding callers
            engine.stop()
        if self._decay is not None:
            self._decay.stop()
        if self._genserve is not None:
            # generation engine: queued/running requests fail fast with
            # ClosedError instead of stranding callers
            self._genserve.stop()
        self._base_storage.close()

    def __enter__(self) -> "DB":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def open(data_dir: str = "", config: Optional[Config] = None) -> DB:  # noqa: A001
    """Open a database (ref: nornicdb.Open db.go:750)."""
    return DB(data_dir, config)
