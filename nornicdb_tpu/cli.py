"""Command-line interface.

Behavioral reference: /root/reference/cmd/nornicdb/main.go:71-208 — cobra
commands serve / init / import / shell / decay {recalculate,archive,stats};
runServe wiring (:210-649): config -> DB -> embedder -> auth -> HTTP + Bolt
servers -> signal handling.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time


def _open_db(args):
    import nornicdb_tpu
    from nornicdb_tpu.db import Config

    cfg = Config(log_queries=bool(getattr(args, "log_queries", False)))
    if cfg.log_queries:
        import logging

        logging.basicConfig(level=logging.INFO)
    return nornicdb_tpu.open_db(args.data_dir, cfg)


def cmd_serve(args) -> int:
    """(ref: runServe main.go:210)"""
    import nornicdb_tpu.telemetry as telemetry
    from nornicdb_tpu.auth import Authenticator, ROLE_ADMIN
    from nornicdb_tpu.config import load as load_app_config
    from nornicdb_tpu.embed import CachedEmbedder, HashEmbedder, TPUEmbedder
    from nornicdb_tpu.multidb import SYSTEM_DB
    from nornicdb_tpu.server import BoltServer, HttpServer

    # apply nornicdb.yaml/env telemetry + backend-lifecycle knobs to the
    # process-global tracer / slow-query log / device manager before any
    # server starts taking traffic
    app_cfg = load_app_config()
    telemetry.configure(**vars(app_cfg.telemetry))
    from nornicdb_tpu import backend as backend_mod

    backend_mod.configure(app_cfg.backend)
    # vector-serving knobs (backend selection, sharded promotion, recall
    # tuning) become the defaults for every SearchService this process
    # builds — docs/operations.md "Sharded serving tuning"
    from nornicdb_tpu.search import service as search_service

    search_service.configure_defaults(**vars(app_cfg.search))
    # generation-serving knobs (paged-KV geometry, concurrency, deadline,
    # degraded-backend policy) become the defaults for the genserve engine
    # this process builds behind Heimdall/GraphRAG — docs/generation.md
    from nornicdb_tpu import genserve as genserve_mod

    genserve_mod.configure(app_cfg.genserve)
    # kick off PJRT init + first-touch on the manager's worker thread NOW,
    # so the first search/embed finds a READY (or already-degraded) backend
    # instead of paying the acquire timeout inline
    backend_mod.manager().ensure_started()

    db = _open_db(args)
    # embedder: trained checkpoint > TPU bge-m3 preset > hash fallback
    if args.embedder == "trained" or (
        args.embedder == "tpu" and os.environ.get("NORNICDB_EMBEDDER_MODEL")
    ):
        from nornicdb_tpu.models.pretrain import load_embedder

        model_dir = os.environ.get("NORNICDB_EMBEDDER_MODEL", "")
        if not model_dir:
            raise SystemExit(
                "--embedder trained requires NORNICDB_EMBEDDER_MODEL=<dir>"
            )
        embedder = load_embedder(model_dir)
    elif args.embedder == "tpu":
        from nornicdb_tpu.models import bge_m3

        cfg_name = getattr(bge_m3, args.model_preset.upper().replace("-", "_"))
        embedder = TPUEmbedder(cfg=cfg_name)
    else:
        embedder = HashEmbedder(args.embed_dims)
    # distilled production embedder, behind the eval gate: the student
    # checkpoint only replaces the full encoder when its retrieval MRR
    # clears serving.student_min_mrr — otherwise the config is REJECTED
    # at startup with the measured number (docs/operations.md "Embed
    # serving tuning"; serving/student_gate.py)
    from nornicdb_tpu.errors import StudentGateError
    from nornicdb_tpu.serving import ServingEngine, gate_student
    from nornicdb_tpu.serving.stats import set_embedder_selection

    serving_cfg = app_cfg.serving
    if args.embedder == "student":
        # CLI shorthand for serving.embedder=student (config/env also work)
        serving_cfg.embedder = "student"
        if not serving_cfg.student_model_dir:
            serving_cfg.student_model_dir = os.environ.get(
                "NORNICDB_EMBEDDER_MODEL", ""
            )
    if serving_cfg.embedder == "student":
        from nornicdb_tpu.models.pretrain import load_embedder

        student_dir = serving_cfg.student_model_dir
        if not student_dir:
            raise SystemExit(
                "serving.embedder=student requires "
                "serving.student_model_dir (NORNICDB_STUDENT_MODEL)"
            )
        student = load_embedder(student_dir)
        try:
            report = gate_student(
                student,
                serving_cfg.student_min_mrr,
                serving_cfg.student_eval_suite,
            )
        except StudentGateError as e:
            raise SystemExit(f"serving config rejected: {e}")
        print(
            f"student embedder admitted: eval MRR "
            f"{report.metrics.mrr:.4f} >= {serving_cfg.student_min_mrr}"
        )
        embedder = student
        set_embedder_selection("student")
    else:
        set_embedder_selection("full")
    if serving_cfg.enabled:
        # continuous ragged batching engine fronts every embed path
        # (HTTP /nornicdb/embed, query embedding, EmbedWorker drains);
        # the cache sits outside so hits skip the queue entirely
        embedder = ServingEngine(embedder, serving_cfg)
    db.set_embedder(CachedEmbedder(embedder))
    # with an assistant checkpoint mounted, build + warm the generation
    # engine now: the paged prefill/decode programs compile before traffic
    # instead of inside the first request's deadline
    if os.environ.get("NORNICDB_ASSISTANT_MODEL"):
        _ = db.heimdall
        gen_engine = db.genserve_engine()
        if gen_engine is not None:
            gen_engine.warmup()

    authenticator = None
    if args.auth:
        from nornicdb_tpu.errors import AlreadyExistsError

        system = db.database_manager.get_storage(SYSTEM_DB)
        authenticator = Authenticator(system)
        try:
            authenticator.create_user(
                "admin", os.environ.get("NORNICDB_ADMIN_PASSWORD", "admin"),
                ROLE_ADMIN,
            )
        except AlreadyExistsError:
            pass  # exists from a previous run

    http_server = HttpServer(
        db, host=args.host, port=args.http_port,
        authenticator=authenticator, auth_required=args.auth,
        serve_ui=not args.headless,
    )
    http_server.start()
    bolt_server = BoltServer(
        lambda q, p, d: (db.executor_for(d) if d else db.executor).execute(q, p),
        host=args.host, port=args.bolt_port,
        authenticator=authenticator, auth_required=args.auth,
        session_executor_factory=db.session_executor,
    )
    bolt_server.start()
    # Qdrant gRPC on :6334, feature-flagged like the reference
    # (NORNICDB_QDRANT_GRPC_ENABLED, ref: server.go feature flag)
    qdrant_server = None
    if os.environ.get("NORNICDB_QDRANT_GRPC_ENABLED", "").lower() in (
        "1", "true", "yes",
    ):
        from nornicdb_tpu.server.qdrant_grpc import QdrantGrpcServer

        qdrant_server = QdrantGrpcServer(
            http_server.qdrant,  # shared registry: REST + gRPC, one index
            host=args.host,
            port=int(os.environ.get("NORNICDB_QDRANT_GRPC_PORT", "6334")),
            authenticator=authenticator,
            snapshot_dir=os.path.join(args.data_dir, "qdrant-snapshots")
            if args.data_dir else None,
        )
        qdrant_server.start()
    # native gRPC search on :50051, feature-flagged like the reference's
    # nornicgrpc service (ref: search_service.go)
    grpc_server = None
    if os.environ.get("NORNICDB_GRPC_ENABLED", "").lower() in (
        "1", "true", "yes",
    ):
        try:
            from nornicdb_tpu.server.grpc_search import GrpcSearchServer

            grpc_server = GrpcSearchServer(
                db, host=args.host,
                port=int(os.environ.get("NORNICDB_GRPC_PORT", "50051")),
            )
            grpc_server.start()
        except ImportError:
            print("NORNICDB_GRPC_ENABLED set but grpcio is not installed; "
                  "native gRPC disabled", file=sys.stderr)
    # prefork protocol workers: N subprocesses on a shared SO_REUSEPORT
    # public port, serving vector search through the device broker with a
    # shared-memory fallback (docs/operations.md "Multi-process serving")
    workers_cfg = app_cfg.workers
    n_http_workers = (args.workers if args.workers is not None
                      else workers_cfg.http)
    http_pool = grpc_pool = None
    rate = ((workers_cfg.rate_limit, workers_cfg.rate_burst)
            if workers_cfg.rate_limit > 0 else None)
    if n_http_workers > 0:
        from nornicdb_tpu.server.workers import WorkerPool

        http_pool = WorkerPool(
            db, http_server.port, n_workers=n_http_workers,
            host="127.0.0.1" if args.host == "0.0.0.0" else args.host,
            kind="http", public_port=workers_cfg.port,
            rate_limit=rate, broker=workers_cfg.broker,
            read_plane=workers_cfg.read_plane,
            respawn=workers_cfg.respawn,
            publish_interval=workers_cfg.publish_interval,
            auth_required=args.auth,
            metrics=workers_cfg.metrics,
            metrics_interval=workers_cfg.metrics_interval,
        ).start()
    if workers_cfg.grpc > 0 and grpc_server is not None:
        from nornicdb_tpu.server.workers import WorkerPool

        grpc_pool = WorkerPool(
            db, grpc_server.port, n_workers=workers_cfg.grpc,
            host="127.0.0.1" if args.host == "0.0.0.0" else args.host,
            kind="grpc", public_port=workers_cfg.grpc_port,
            rate_limit=rate,
            # share the HTTP pool's broker: one device owner per host
            broker=(http_pool.broker if http_pool is not None
                    and http_pool.broker is not None
                    else workers_cfg.broker),
            read_plane=workers_cfg.read_plane,
            respawn=workers_cfg.respawn,
            publish_interval=workers_cfg.publish_interval,
            auth_required=args.auth,
            metrics=workers_cfg.metrics,
            metrics_interval=workers_cfg.metrics_interval,
        ).start()
    print(f"NornicDB-TPU serving: bolt://{args.host}:{bolt_server.port} "
          f"http://{args.host}:{http_server.port}"
          + (f" qdrant-grpc://{args.host}:{qdrant_server.port}"
             if qdrant_server else "")
          + (f" grpc://{args.host}:{grpc_server.port}"
             if grpc_server else "")
          + (f" http-workers://{http_pool.host}:{http_pool.port}"
             f" x{http_pool.n_workers}" if http_pool else "")
          + (f" grpc-workers://{grpc_pool.host}:{grpc_pool.port}"
             f" x{grpc_pool.n_workers}" if grpc_pool else "")
          + f" (data: {args.data_dir or 'memory'})")

    stop = []
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.2)
    finally:
        print("shutting down...")
        if grpc_pool is not None:
            grpc_pool.stop()
        if http_pool is not None:
            http_pool.stop()
        if grpc_server is not None:
            grpc_server.stop()
        if qdrant_server is not None:
            qdrant_server.stop()
        bolt_server.stop()
        http_server.stop()
        db.close()
    return 0


def cmd_init(args) -> int:
    db = _open_db(args)
    db.close()
    print(f"initialized data directory {args.data_dir}")
    return 0


def cmd_shell(args) -> int:
    """(ref: nornicdb shell)"""
    db = _open_db(args)
    print("NornicDB-TPU shell. Cypher queries, or :quit")
    try:
        while True:
            try:
                line = input("cypher> ").strip()
            except EOFError:
                break
            if not line:
                continue
            if line in (":quit", ":exit", "quit", "exit"):
                break
            try:
                result = db.cypher(line)
                if result.columns:
                    print("\t".join(result.columns))
                    for row in result.rows:
                        print("\t".join(str(v) for v in row))
                stats = result.stats.as_dict()
                if stats:
                    print(f"-- {stats}")
            except Exception as e:
                print(f"error: {e}")
    finally:
        db.close()
    return 0


def cmd_import(args) -> int:
    """Neo4j-style JSON / Mimir JSONL import (ref: nornicdb import,
    storage loader.go + mimir_loader.go)."""
    from nornicdb_tpu.storage.io import import_json, load_mimir

    db = _open_db(args)
    try:
        if args.format == "mimir":
            n_nodes, n_edges = load_mimir(db.storage, args.file)
        else:
            with open(args.file) as f:
                data = json.load(f)
            n_nodes, n_edges = import_json(db.storage, data)
    finally:
        db.close()
    print(f"imported {n_nodes} nodes, {n_edges} relationships")
    return 0


def cmd_export(args) -> int:
    """Neo4j-style JSON export (ref: types.go:475-707)."""
    from nornicdb_tpu.storage.io import export_json

    db = _open_db(args)
    try:
        data = export_json(db.storage)
    finally:
        db.close()
    out = json.dumps(data, indent=2, default=str)
    if args.file == "-":
        print(out)
    else:
        with open(args.file, "w") as f:
            f.write(out)
        print(f"exported {len(data['nodes'])} nodes, "
              f"{len(data['relationships'])} relationships to {args.file}")
    return 0


def _require_data_dir(args) -> bool:
    """backup/restore against an empty data dir would silently operate on an
    ephemeral in-memory engine — success messages with nothing persisted."""
    if not args.data_dir:
        print("error: --data-dir (or NORNICDB_DATA_DIR) is required for "
              "this command", file=sys.stderr)
        return False
    return True


def cmd_backup(args) -> int:
    """Full-fidelity backup archive (ref: badger_backup.go role)."""
    if not _require_data_dir(args):
        return 2
    db = _open_db(args)
    try:
        path = db.backup(args.file if args.file != "-" else None)
    finally:
        db.close()
    print(f"backup written to {path}")
    return 0


def cmd_restore(args) -> int:
    if not _require_data_dir(args):
        return 2
    db = _open_db(args)
    try:
        counts = db.restore(args.file)
    finally:
        db.close()
    print(f"restored {counts['nodes']} nodes, {counts['edges']} edges")
    return 0


def cmd_eval(args) -> int:
    """Search-quality evaluation (ref: cmd/eval, pkg/eval harness)."""
    from nornicdb_tpu.embed import HashEmbedder
    from nornicdb_tpu.eval import Harness

    db = _open_db(args)
    try:
        if db.embedder is None:
            db.set_embedder(HashEmbedder(args.embed_dims))
            db.process_pending_embeddings()
        cases = Harness.load_suite(args.suite)
        thresholds = json.loads(args.thresholds) if args.thresholds else {}
        harness = Harness(
            lambda q, k: [r["id"] for r in db.search.search(q, limit=k)],
            k=args.k, thresholds=thresholds,
        )
        report = harness.run(cases)
        print(json.dumps({"metrics": report.metrics.as_dict(),
                          "passed": report.passed}, indent=2))
        return 0 if report.passed else 1
    finally:
        db.close()


def cmd_decay(args) -> int:
    """(ref: nornicdb decay {recalculate,archive,stats})"""
    db = _open_db(args)
    try:
        if args.action == "recalculate":
            scored, archived = db.decay.recalculate_all()
            print(f"scored {scored} nodes, archived {archived}")
        elif args.action == "stats":
            print(json.dumps(vars(db.decay.stats)))
        elif args.action == "archive":
            nodes = db.decay.archived_nodes()
            print(f"{len(nodes)} archived nodes")
    finally:
        db.close()
    return 0


def cmd_dataset(args) -> int:
    """(ref: neural/scripts dataset tooling)"""
    from itertools import chain

    from nornicdb_tpu.models import dataset

    if args.action == "validate":
        report = dataset.validate_jsonl(args.file)
        print(json.dumps(report, indent=2))
        return 0 if report["invalid"] == 0 else 1
    gens = []
    if args.kind in ("cypher", "all"):
        gens.append(dataset.generate_cypher_examples(
            args.count if args.kind == "cypher"
            else args.count - args.count // 2,  # odd counts stay exact
            seed=args.seed))
    if args.kind in ("heimdall", "all"):
        gens.append(dataset.generate_heimdall_examples(
            args.count if args.kind == "heimdall" else args.count // 2,
            seed=args.seed))
    n = dataset.write_jsonl(args.file, chain(*gens))
    print(f"wrote {n} examples to {args.file}")
    return 0


def cmd_train(args) -> int:
    """(replaces the reference's offline neural/train.py pipeline with
    first-class in-image training; see models/pretrain.py)"""
    from nornicdb_tpu.models import pretrain

    if args.model == "assistant":
        # facts + ACTION-MODE corpus: the served assistant must emit
        # machine-parseable query/status actions (measured held-out rates
        # in tests/test_heimdall_actions.py)
        corpus = (pretrain.synth_corpus(0, repeats=6)
                  + pretrain.synth_action_corpus(0, repeats=6))
        stats = pretrain.train_assistant(
            args.out, steps=args.steps or 1400, batch=24, seq_len=64,
            hidden=128, lr=2e-3, corpus=corpus,
        )
    else:
        stats = pretrain.train_encoder(args.out, steps=args.steps or 250)
    print(json.dumps({"model": args.model, "out": args.out, **stats}))
    return 0


def cmd_kmeans_test_data(args) -> int:
    """K-means test-data generator (ref: cmd/kmeans-test-data, 884 LoC —
    synthetic/clustered embedding corpora for clustering benchmarks; the
    download/movies modes need egress, so this build ships the two
    deterministic generators plus optional direct DB import)."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    if args.mode == "clusters":
        centers = rng.normal(0, 1.0, (args.clusters, args.dims))
        assign = rng.integers(0, args.clusters, args.count)
        emb = centers[assign] + rng.normal(0, 0.15, (args.count, args.dims))
    else:  # synthetic: isotropic Gaussian -> uniform directions on the sphere
        assign = None
        emb = rng.normal(0, 1.0, (args.count, args.dims))
    emb = emb / np.maximum(
        np.linalg.norm(emb, axis=1, keepdims=True), 1e-12)

    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "embeddings.npz")
    if assign is not None:
        np.savez_compressed(path, embeddings=emb.astype(np.float32),
                            cluster=assign.astype(np.int32))
    else:
        np.savez_compressed(path, embeddings=emb.astype(np.float32))
    print(json.dumps({"mode": args.mode, "count": args.count,
                      "dims": args.dims, "out": path}))

    # --db overrides, else the global --data-dir (the flag pattern every
    # other subcommand uses); neither set = generate files only
    target = args.db or args.data_dir
    if target:
        args = argparse.Namespace(**{**vars(args), "data_dir": target})
        db = _open_db(args)
        try:
            from nornicdb_tpu.storage import Node

            from nornicdb_tpu.errors import AlreadyExistsError

            imported = skipped = 0
            for i in range(args.count):
                props = {"kind": "kmeans-test"}
                if assign is not None:
                    props["cluster"] = int(assign[i])
                try:
                    db.storage.create_node(Node(
                        id=f"kmtest-{args.seed}-{i}",
                        labels=["KMeansTest"],
                        properties=props,
                        embedding=emb[i].astype(np.float32),
                    ))
                    imported += 1
                except AlreadyExistsError:
                    skipped += 1  # re-run with the same seed: idempotent
            db.flush()
            print(json.dumps({"imported": imported, "skipped": skipped,
                              "db": target}))
        finally:
            db.close()
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="nornicdb", description="NornicDB-TPU")
    p.add_argument("--data-dir", default=os.environ.get("NORNICDB_DATA_DIR", ""),
                   help="data directory (empty = in-memory)")
    sub = p.add_subparsers(dest="command", required=True)

    s = sub.add_parser("serve", help="run the database server")
    s.add_argument("--host", default="0.0.0.0")
    s.add_argument("--bolt-port", type=int, default=7687)
    s.add_argument("--http-port", type=int, default=7474)
    s.add_argument("--auth", action="store_true", help="require authentication")
    s.add_argument("--headless", action="store_true",
                   help="no browser UI (ref: -tags noui builds)")
    s.add_argument("--embedder", choices=["hash", "tpu", "trained", "student"],
                   default="tpu")
    s.add_argument("--embed-dims", type=int, default=1024)
    s.add_argument("--model-preset", default="bge_small")
    s.add_argument("--log-queries", action="store_true",
                   help="log every Cypher statement with wall time")
    s.add_argument("--workers", type=int, default=None,
                   help="prefork HTTP protocol workers (overrides the "
                        "workers.http config; 0 disables)")
    s.set_defaults(fn=cmd_serve)

    s = sub.add_parser("init", help="initialize a data directory")
    s.set_defaults(fn=cmd_init)

    s = sub.add_parser("shell", help="interactive Cypher shell")
    s.set_defaults(fn=cmd_shell)

    s = sub.add_parser("import", help="import Neo4j-style JSON or Mimir JSONL")
    s.add_argument("file")
    s.add_argument("--format", choices=["json", "mimir"], default="json")
    s.set_defaults(fn=cmd_import)

    s = sub.add_parser("export", help="export the graph as Neo4j-style JSON")
    s.add_argument("file", help="output path, or - for stdout")
    s.set_defaults(fn=cmd_export)

    s = sub.add_parser("backup", help="write a full-fidelity backup archive")
    s.add_argument("file", nargs="?", default="-",
                   help="output .json.gz path (default: <data-dir>/backups/)")
    s.set_defaults(fn=cmd_backup)

    s = sub.add_parser("restore", help="restore a backup archive")
    s.add_argument("file", help="backup .json.gz path")
    s.set_defaults(fn=cmd_restore)

    s = sub.add_parser("eval", help="run a search-quality evaluation suite")
    s.add_argument("suite", help="JSON suite: [{query, relevant: [ids]}]")
    s.add_argument("--k", type=int, default=10)
    s.add_argument("--embed-dims", type=int, default=256)
    s.add_argument("--thresholds", default="", help='JSON e.g. {"mrr": 0.8}')
    s.set_defaults(fn=cmd_eval)

    s = sub.add_parser("decay", help="memory decay operations")
    s.add_argument("action", choices=["recalculate", "archive", "stats"])
    s.set_defaults(fn=cmd_decay)

    s = sub.add_parser(
        "train",
        help="train in-image model checkpoints (assistant decoder via LM "
             "loss, embedding encoder via InfoNCE) on the synthetic domain "
             "corpus — the zero-egress replacement for mounting GGUF weights",
    )
    s.add_argument("model", choices=["assistant", "encoder"])
    s.add_argument("--out", required=True, help="checkpoint output directory")
    s.add_argument("--steps", type=int, default=0,
                   help="train steps (default: per-model preset)")
    s.set_defaults(fn=cmd_train)

    s = sub.add_parser(
        "kmeans-test-data",
        help="generate synthetic/clustered embedding corpora for k-means "
             "benchmarks (ref: cmd/kmeans-test-data)",
    )
    s.add_argument("--mode", choices=["synthetic", "clusters"],
                   default="clusters")
    s.add_argument("--count", type=int, default=5000)
    s.add_argument("--dims", type=int, default=1024)
    s.add_argument("--clusters", type=int, default=20)
    s.add_argument("--out", default="./data/kmeans-test")
    s.add_argument("--db", default="",
                   help="NornicDB data directory (if set, imports directly)")
    s.add_argument("--seed", type=int, default=42)
    s.set_defaults(fn=cmd_kmeans_test_data)

    s = sub.add_parser(
        "dataset",
        help="generate / validate instruction-tuning datasets "
             "(ref: neural/scripts/generate_*_dataset.py, "
             "validate_dataset.py)",
    )
    s.add_argument("action", choices=["generate", "validate"])
    s.add_argument("file", help="JSONL path")
    s.add_argument("--kind", choices=["cypher", "heimdall", "all"],
                   default="all")
    s.add_argument("--count", type=int, default=1000)
    s.add_argument("--seed", type=int, default=42)
    s.set_defaults(fn=cmd_dataset)

    s = sub.add_parser(
        "oauth-provider",
        help="run the standalone OAuth 2.0 test provider "
             "(ref: cmd/oauth-provider — local OAuth integration testing)",
    )
    s.add_argument("--port", type=int, default=8888)
    s.add_argument("--client-id", default="nornicdb-local-test")
    s.add_argument("--client-secret", default="local-test-secret-123")
    s.set_defaults(fn=lambda a: __import__(
        "nornicdb_tpu.server.oauth_provider", fromlist=["main"]
    ).main(a.port, a.client_id, a.client_secret))

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
