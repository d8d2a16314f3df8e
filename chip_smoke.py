#!/usr/bin/env python3
"""Store -> embed -> search -> Cypher answers on one TPU v5e chip.

Drives the database's main path once, in ONE process, through the entry
points a user calls — the stack ``nornicdb serve --embedder tpu
--model-preset bge_m3`` wires — and checks every answer against a plain
reference outside the served code:

1. bulk load: ``--rows`` nodes x 1024-d f32 unit vectors (from ``--seed``)
   through the embedded write path, indexed by storage events;
2. ingest text: 8 texts over ``POST /nornicdb/embed`` and 64 documents over
   ``db.store()`` -> embed queue -> ServingEngine -> ``forward_packed``;
3. answer: 16 x ``POST /nornicdb/search`` by vector (k=10 and k=100), 4 x
   ``db.recall()`` by text, 2 Cypher statements over the HTTP tx API;
4. compare with numpy / the per-request ``bge_m3.forward``;
5. device evidence from what the program already records.

It FAILS (non-zero exit, no result line) where JAX finds no TPU.  The last
stdout line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Every earlier line is a JSON object of set-up facts (phase wall seconds,
compile seconds, evidence) — none of them is a benchmark metric.

``--chips 4`` runs ONLY the mesh path (ShardedCorpus over four devices) and
what it is compared with.  ``--rehearse-cpu`` lets the same phases run on
the CPU backend at a size chosen on the command line; it never prints
``"ok": true``.
"""

from __future__ import annotations

import argparse
import collections
import http.client
import json
import os
import subprocess
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

DIMS = 1024
REFERENCE_ROWS = 1_000_000  # /root/reference docs/features/gpu-acceleration.md
RECALL_FLOOR = 0.95         # SearchConfig.recall_target
SCORE_TOL = 2e-2            # bf16 kernel scores vs exact f32
EMBED_COS_FLOOR = 0.999
N_HTTP_TEXTS = 8
N_DOCS = 64
VEC_LABEL, EMB_LABEL, DOC_LABEL = "Vec", "Emb", "Passage"
PHASE_BUDGET_S = 600.0      # any single wait gives up after this

_phase = "start"
_compile = {}               # phase -> [seconds, backend compiles, cache hits]
_shed_retries = collections.Counter()  # entry point -> sheds retried


def emit(**obj) -> None:
    print(json.dumps(obj, default=float), flush=True)


def require(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def hard_exit(rc: int) -> None:
    """Exit without interpreter teardown: the verdict is printed, and a
    daemon thread still inside XLA at teardown can abort the process."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)


class Phase:
    """Names the running phase (compile seconds and probe readings are
    attributed to it) and prints its wall seconds when it ends."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        global _phase
        _phase = self.name
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            secs, compiles, hits = _compile.get(self.name, (0.0, 0, 0))
            emit(phase=self.name,
                 wall_s=round(time.perf_counter() - self.t0, 3),
                 compile_s=round(secs, 3), backend_compiles=compiles,
                 compile_cache_hits=hits)


def watch_compiles() -> None:
    """Sum JAX's own compile events per phase: backend_compile_duration
    covers a persistent-cache hit's retrieval too, so a warm run shows
    fewer seconds and more hits."""
    import jax.monitoring

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            cell = _compile.setdefault(_phase, [0.0, 0, 0])
            cell[0] += secs
            cell[1] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _compile.setdefault(_phase, [0.0, 0, 0])[2] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


class ProbeWatch(threading.Thread):
    """Reads the backend manager's own probe counters while the phases
    run: one entry per health probe, tagged with the phase it fell in."""

    def __init__(self, mgr):
        super().__init__(name="smoke-probe-watch", daemon=True)
        self.mgr = mgr
        self.readings = []
        self._halt = threading.Event()

    def run(self):
        last = 0
        while not self._halt.wait(0.25):
            s = self.mgr.stats()
            if s["probes_total"] != last:
                last = s["probes_total"]
                self.readings.append({
                    "phase": _phase, "judged_s": s["probe_latency_s"],
                    "wall_s": s["probe_wall_s"],
                    "failures_total": s["probe_failures_total"],
                    "state": s["state"]})

    def stop(self):
        self._halt.set()
        self.join(timeout=5)


def build_native() -> dict:
    """The .so files are not in git: build them from native/*.cc as a fresh
    checkout must, and say which codec loaded."""
    subprocess.run(["make", "-C", os.path.join(ROOT, "native"), "clean", "all"],
                   check=True, capture_output=True, timeout=300)
    from nornicdb_tpu.storage import native, segment

    return {"built_from_source": True,
            "walcodec_loaded": native.load() is not None,
            "segstore_loaded": segment._load_lib() is not None,
            # open_db("") keeps the graph in memory: no WAL, so neither the
            # native nor the Python record codec is on this run's path
            "wal_codec_serving": "none (in-memory store)"}


def acquire_device(args):
    """backend.configure -> manager().ensure_started(), as `serve` does,
    under fallback="fail"; then refuse anything that is not the chip."""
    from nornicdb_tpu import backend
    from nornicdb_tpu.config import AppConfig

    app_cfg = AppConfig()
    app_cfg.backend.fallback = "fail"
    backend.configure(app_cfg.backend)
    mgr = backend.manager()
    t0 = time.perf_counter()
    mgr.ensure_started()
    ready = mgr.await_ready(timeout=PHASE_BUDGET_S)
    acquire_s = time.perf_counter() - t0
    stats = mgr.stats()
    if not ready:
        print(f"backend never reached READY: {json.dumps(stats)}",
              file=sys.stderr)
        hard_exit(2)
    dev = stats["device"]
    if dev.get("platform") != "tpu" and not args.rehearse_cpu:
        print(f"no TPU: JAX reports {dev}; this script measures nothing "
              "off the chip", file=sys.stderr)
        hard_exit(2)
    if dev.get("device_count", 0) < args.chips:
        print(f"--chips {args.chips} needs that many devices, JAX reports "
              f"{dev}", file=sys.stderr)
        hard_exit(2)
    import jax

    emit(acquire={"cold_seconds": round(acquire_s, 3),
                  "acquire_timeout_s": stats["acquire_timeout_s"],
                  "device": dev, "fallback_policy": stats["fallback_policy"],
                  "compile_cache_dir": jax.config.jax_compilation_cache_dir})
    return app_cfg, mgr


# ------------------------------------------------------------------ data
def unit_rows(rng, n: int, dims: int = DIMS):
    import numpy as np

    x = rng.standard_normal((n, dims), dtype=np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x


def make_texts(rng, n: int, lo: int, hi: int) -> list[str]:
    """Seeded documents of lo..hi words over a 4096-word vocabulary."""
    vocab = [f"w{j:04d}" for j in range(4096)]
    return [" ".join(vocab[j] for j in rng.integers(0, len(vocab), size=m))
            for m in rng.integers(lo, hi + 1, size=n)]


def recall_at_k(got_ids, exact_scores, k: int) -> float:
    import numpy as np

    truth = set(np.argsort(-exact_scores)[:k].tolist())
    return len(truth & set(got_ids)) / k


# ------------------------------------------------------------------ http
class Client:
    def __init__(self, port: int):
        self.port = port

    def call(self, method: str, path: str, body=None):
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)
        try:
            conn.request(method, path,
                         None if body is None else json.dumps(body).encode(),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, resp.getheader("Retry-After"), \
                json.loads(resp.read() or b"null")
        finally:
            conn.close()

    def ok(self, method: str, path: str, body=None):
        """A 200 answer.  429 is the serving engine's documented shed (a
        request that meets a cold compile misses its deadline): retry as
        its Retry-After says, and count it."""
        deadline = time.monotonic() + PHASE_BUDGET_S
        while True:
            status, retry_after, payload = self.call(method, path, body)
            if status == 429 and time.monotonic() < deadline:
                _shed_retries["http_429"] += 1
                time.sleep(float(retry_after or 1))
                continue
            require(status == 200, f"{method} {path} -> {status}: {payload}")
            return payload


def retry_shed(fn):
    """The in-process twin of Client.ok's 429 handling: the serving engine
    sheds with ResourceExhausted ("retry with backoff") while a cold
    compile has its cost model over-predicting."""
    from nornicdb_tpu.errors import ResourceExhausted

    deadline = time.monotonic() + PHASE_BUDGET_S
    while True:
        try:
            return fn()
        except ResourceExhausted:
            if time.monotonic() >= deadline:
                raise
            _shed_retries["in_process"] += 1
            time.sleep(1.0)


# ------------------------------------------------------------- one chip
def run_one_chip(args) -> None:
    import numpy as np

    app_cfg, mgr = acquire_device(args)
    emit(native=build_native())
    watch_compiles()
    probes = ProbeWatch(mgr)
    probes.start()

    import nornicdb_tpu
    import nornicdb_tpu.telemetry as telemetry
    from nornicdb_tpu.embed import CachedEmbedder, TPUEmbedder
    from nornicdb_tpu.models import bge_m3
    from nornicdb_tpu.search import service as search_service
    from nornicdb_tpu.server import HttpServer
    from nornicdb_tpu.serving import ServingEngine
    from nornicdb_tpu.storage import Node
    from nornicdb_tpu.telemetry import deviceprof

    rows = args.rows
    emit(reduced={"rows": [REFERENCE_ROWS, rows]},
         why="host load time of the embedded write path; widths unchanged")

    with Phase("serve_stack"):
        telemetry.configure(**vars(app_cfg.telemetry))
        search_service.configure_defaults(**vars(app_cfg.search))
        db = nornicdb_tpu.open_db("")
        cfg = getattr(bge_m3, args.model_preset.upper().replace("-", "_"))
        tpu_embedder = TPUEmbedder(cfg=cfg, seed=args.seed)
        db.set_embedder(CachedEmbedder(
            ServingEngine(tpu_embedder, app_cfg.serving)))
        http_server = HttpServer(db, port=0)
        http_server.start()
        client = Client(http_server.port)
        emit(model={"preset": args.model_preset, "layers": cfg.layers,
                    "hidden": cfg.hidden, "vocab": cfg.vocab_size,
                    "dtype": cfg.dtype, "dims": cfg.dims,
                    "param_bytes": TPUEmbedder._hbm_bytes(tpu_embedder)[
                        "embedder_params"]})
    dims = cfg.dims

    rng = np.random.default_rng(args.seed)
    with Phase("load"):
        corpus = unit_rows(rng, rows, dims)
        # the Cypher VectorTopK operator reads a node PROPERTY, so a
        # labelled subset carries its vector as one too
        n_emb = min(rows, max(8192, rows // 16))
        svc = db.search  # subscribes index_node to storage events
        for i in range(rows):
            sub = i < n_emb
            db.storage.create_node(Node(
                id=f"v{i:07d}",
                labels=[VEC_LABEL, EMB_LABEL] if sub else [VEC_LABEL],
                properties={"idx": i, "emb": corpus[i].tolist()} if sub
                else {"idx": i},
                embedding=corpus[i]))
        require(len(svc.corpus()) == rows, "indexed rows != written rows")

    with Phase("ingest"):
        # two length bands -> two pack shape classes for the documents
        # ((8,512,8): one long text a row; (16,64,32): two short ones a
        # row), a third for single short requests ((1,64,8)); each class is
        # one full-depth compile
        http_texts = make_texts(rng, N_HTTP_TEXTS, 20, 30)
        http_vecs = [client.ok("POST", "/nornicdb/embed", {"text": t})
                     for t in http_texts]
        docs = make_texts(rng, N_DOCS // 2, 257, 400) + \
            make_texts(rng, N_DOCS // 2, 20, 30)
        doc_nodes = [db.store(t, labels=[DOC_LABEL]) for t in docs]
        deadline = time.monotonic() + PHASE_BUDGET_S
        while True:
            doc_vecs = [db.storage.get_node(n.id).embedding
                        for n in doc_nodes]
            if all(v is not None for v in doc_vecs) \
                    or time.monotonic() >= deadline:
                break
            time.sleep(0.2)
        require(all(v is not None for v in doc_vecs),
                "embed queue did not embed every stored document")
        require(len(svc.corpus()) == rows + N_DOCS,
                "embedded documents were not indexed")

    with Phase("answer:search"):
        qrng = np.random.default_rng(args.seed + 1)
        queries = unit_rows(qrng, 16, dims)
        ks = [10] * 8 + [100] * 8
        search_answers = [
            client.ok("POST", "/nornicdb/search",
                      {"vector": q.tolist(), "limit": k, "min_score": -1.0,
                       "include_content": False})["results"]
            for q, k in zip(queries, ks)]

    with Phase("answer:recall"):
        # text queries nobody embedded before (a cache hit would skip the
        # chip): the opening words of four stored long documents
        recall_texts = [" ".join(docs[i].split()[:24]) for i in range(4)]
        embedded_before = tpu_embedder.stats["embedded"]
        recall_answers = [retry_shed(lambda t=t: db.recall(t, limit=10))
                          for t in recall_texts]
        require(tpu_embedder.stats["embedded"] == embedded_before + 4,
                "recall() queries were not embedded by the TPU embedder")
        # what the program recorded while it answered, read at once: 30 s
        # after the last document the embed queue's debounced recluster
        # starts k-means and IVF tuning in the background (not this run's
        # subject), and the ledger would show its searches too
        search_ledger = {(p["subsystem"], p["kind"]): p for p in
                         deviceprof.PROFILER.snapshot()["programs"]}

    with Phase("answer:cypher"):
        db_name = db.default_database
        count_rows = client.ok(
            "POST", f"/db/{db_name}/tx/commit",
            {"statements": [{"statement":
                             f"MATCH (n:{VEC_LABEL}) RETURN count(n)"}]})
        cq = unit_rows(qrng, 1, dims)[0]
        topk_rows = client.ok(
            "POST", f"/db/{db_name}/tx/commit",
            {"statements": [{
                "statement":
                    f"MATCH (n:{EMB_LABEL}) RETURN n.idx AS idx ORDER BY "
                    "vector.similarity.cosine(n.emb, $q) DESC LIMIT 10",
                "parameters": {"q": cq.tolist()}}]})
        status = client.ok("GET", "/admin/tpu/status")
        back = mgr.stats()
        snap = deviceprof.PROFILER.snapshot()
        engine = db.serving_engine().stats_snapshot()
        probes.stop()

    with Phase("compare"):
        all_vecs = np.concatenate([corpus, np.stack(doc_vecs)])
        all_ids = [f"v{i:07d}" for i in range(rows)] + \
            [n.id for n in doc_nodes]
        row_of = {id_: i for i, id_ in enumerate(all_ids)}
        exact = queries @ all_vecs.T  # plain numpy f32
        recalls = {10: [], 100: []}
        worst_err = 0.0
        for qi, (k, hits) in enumerate(zip(ks, search_answers)):
            require(len(hits) == k, f"search {qi}: {len(hits)} hits, want {k}")
            require(all(h["id"] in row_of for h in hits),
                    f"search {qi}: an id outside the corpus")
            got = [row_of[h["id"]] for h in hits]
            recalls[k].append(recall_at_k(got, exact[qi], k))
            worst_err = max(worst_err, max(
                abs(h["score"] - float(exact[qi][r]))
                for h, r in zip(hits, got)))
        for k, vals in recalls.items():
            require(np.mean(vals) >= RECALL_FLOOR,
                    f"recall@{k} {np.mean(vals):.4f} < {RECALL_FLOOR}")
        require(worst_err <= SCORE_TOL,
                f"search score off by {worst_err} > {SCORE_TOL}")
        emit(search={"requests": len(ks),
                     "recall_at_10_mean": float(np.mean(recalls[10])),
                     "recall_at_10_min": float(np.min(recalls[10])),
                     "recall_at_100_mean": float(np.mean(recalls[100])),
                     "recall_at_100_min": float(np.min(recalls[100])),
                     "max_abs_score_error": worst_err,
                     "reference": "numpy f32 argsort(-(Q @ C.T))"})

        # embeddings vs the per-request forward of the same tokens
        import jax
        import jax.numpy as jnp

        tok = tpu_embedder.tokenizer
        forward = jax.jit(lambda p, i, m: bge_m3.forward(p, cfg, i, m))

        def plain_forward(text: str):
            ids = tok.encode(text, max_len=tpu_embedder.max_len)
            width = 64 if len(ids) <= 64 else 512
            padded = np.full((1, width), tok.pad_id, np.int32)
            mask = np.zeros((1, width), np.int32)
            padded[0, :len(ids)] = ids
            mask[0, :len(ids)] = 1
            return np.asarray(forward(
                tpu_embedder.params, jnp.asarray(padded),
                jnp.asarray(mask)), np.float32)[0]

        def check_embedding(vec, text, what) -> float:
            vec = np.asarray(vec, np.float32)
            require(vec.shape == (dims,), f"{what}: shape {vec.shape}")
            require(np.isfinite(vec).all(), f"{what}: not finite")
            require(abs(float(np.linalg.norm(vec)) - 1.0) < 1e-3,
                    f"{what}: norm {np.linalg.norm(vec)}")
            cos = float(vec @ plain_forward(text))
            require(cos >= EMBED_COS_FLOOR,
                    f"{what}: cosine {cos} vs plain forward")
            return cos

        http_cos = [check_embedding(r["embedding"], t, f"/nornicdb/embed {i}")
                    for i, (r, t) in enumerate(zip(http_vecs, http_texts))]
        require(all(r["dimensions"] == dims for r in http_vecs),
                "/nornicdb/embed dimensions")
        doc_cos = [check_embedding(v, t, f"stored document {i}")
                   for i, (v, t) in enumerate(zip(doc_vecs, docs))]
        emit(embed={"http_texts": len(http_cos),
                    "http_min_cosine": min(http_cos),
                    "stored_documents": len(doc_cos),
                    "stored_min_cosine": min(doc_cos),
                    "reference": "per-request bge_m3.forward, same tokens"})

        # recall() fuses BM25 with the vector list: every hit that came off
        # the vector list is one of numpy's nearest to the query's embedding
        # (the service asks for limit x candidates_multiplier of them) and
        # carries the true cosine
        n_cand = 10 * svc.config.candidates_multiplier
        worst_recall_err, from_vectors, in_truth = 0.0, 0, 0
        for i, (text, hits) in enumerate(zip(recall_texts, recall_answers)):
            require(len(hits) == 10, f"recall {i}: {len(hits)} hits")
            qv = np.asarray(db.embedder.embed(text), np.float32)
            scores = all_vecs @ qv
            truth = set(np.argsort(-scores)[:n_cand].tolist())
            for h in hits:
                if h["vector_score"] is not None:
                    r = row_of[h["id"]]
                    from_vectors += 1
                    in_truth += r in truth
                    worst_recall_err = max(worst_recall_err, abs(
                        h["vector_score"] - float(scores[r])))
        require(from_vectors > 0 and in_truth >= RECALL_FLOOR * from_vectors,
                f"recall(): {in_truth}/{from_vectors} vector hits are true "
                "neighbours")
        require(worst_recall_err <= SCORE_TOL,
                f"recall() vector score off by {worst_recall_err}")
        emit(recall={"queries": len(recall_answers),
                     "vector_hits": from_vectors,
                     "vector_hits_in_numpy_top": in_truth,
                     "max_abs_vector_score_error": worst_recall_err})

        require(not count_rows["errors"] and not topk_rows["errors"],
                f"cypher errors: {count_rows['errors']} {topk_rows['errors']}")
        counted = count_rows["results"][0]["data"][0]["row"][0]
        require(counted == rows, f"count(n) = {counted}, wrote {rows}")
        got_idx = [d["row"][0] for d in topk_rows["results"][0]["data"]]
        sub = corpus[:n_emb].astype(np.float64)
        want_idx = np.argsort(-(sub @ cq.astype(np.float64)))[:10].tolist()
        require(got_idx == want_idx,
                f"VectorTopK rows {got_idx} != numpy {want_idx}")
        emit(cypher={"count": counted, "vector_topk_rows_scored": n_emb,
                     "vector_topk_top10_matches_numpy": True})

    with Phase("evidence"):
        from nornicdb_tpu.ops import pallas_kernels, similarity

        on_chip = jax.devices()[0].platform == "tpu"
        ledger = {(p["subsystem"], p["kind"]): p for p in snap["programs"]}
        emit(tpu_status={"platform": status["platform"],
                         "device_kind": status.get("device_kind"),
                         "lifecycle": status["lifecycle"]["state"]},
             backend={k: back[k] for k in (
                 "state", "fallbacks_total", "degrades_total",
                 "recoveries_total", "acquire_timeouts_total", "probes_total",
                 "probe_failures_total")},
             transitions=[(t["from"], t["to"], t["reason"])
                          for t in back["transitions"]])
        emit(probes={"threshold_s": mgr.probe_latency_threshold,
                     "degrade_after": mgr.degrade_after,
                     "max_judged_s": max(
                         (r["judged_s"] for r in probes.readings),
                         default=None),
                     "max_wall_s": max(
                         (r["wall_s"] for r in probes.readings),
                         default=None),
                     "readings": probes.readings})
        emit(embedder=tpu_embedder.stats,
             packed_programs=engine.get("packed_programs"),
             sheds={k: engine[k] for k in (
                 "sheds_queue_full", "sheds_deadline", "sheds_predicted")},
             shed_retries=dict(_shed_retries),
             embed_worker=vars(db._embed_worker.stats),
             ivf_tunes=svc.stats_snapshot()["ivf_tuner"]["tunes"])
        emit(hbm_bytes=snap["hbm_bytes"],
             resident_rows=len(svc.corpus()), dims=dims,
             programs=[[p["subsystem"], p["kind"], p["shape"], p["executes"]]
                       for p in snap["programs"]])
        require(status["lifecycle"]["state"] == "READY", "lifecycle not READY")
        require(back["fallbacks_total"] == 0, "fallbacks_total != 0")
        require(back["degrades_total"] == 0, "the backend degraded")
        require(tpu_embedder.stats["cpu_fallback_batches"] == 0,
                "embedder served a batch from the CPU")
        require(snap["hbm_bytes"]["corpus_f32"] >=
                (rows + N_DOCS) * dims * 4, "corpus is not device-resident")
        n_searches = len(ks) + len(recall_answers)
        require(search_ledger[("search", "dense")]["executes"] >= n_searches
                and ("search", "ivf") not in search_ledger,
                "vector searches did not all take the dense device path")
        if n_emb >= 8192:  # below it the operator sorts on the host by design
            require(ledger.get(("cypher", "vector_topk"), {}).get(
                "executes", 0) >= 1,
                "the VectorTopK operator's device program never ran")
        if on_chip:
            require(status["platform"] == "tpu", "/admin/tpu/status platform")
            # the callable that served: the streaming kernel's jit cache
            # filled, the XLA scorer's stayed empty, and the dispatcher's
            # program for the served shapes holds the kernel
            kernel = {
                "streaming_cosine_topk_programs":
                    pallas_kernels.streaming_cosine_topk._cache_size(),
                "xla_cosine_topk_programs":
                    similarity.cosine_topk._cache_size()}
            cap = svc.corpus().capacity
            kernel["tpu_custom_call_in_served_program"] = \
                "tpu_custom_call" in jax.jit(
                    lambda q, c, v: similarity.topk_backend(q, c, v, 10)
                ).lower(jax.ShapeDtypeStruct((1, dims), jnp.float32),
                        jax.ShapeDtypeStruct((cap, dims), jnp.float32),
                        jax.ShapeDtypeStruct((cap,), jnp.bool_)).as_text()
            emit(kernel=kernel)
            require(kernel["streaming_cosine_topk_programs"] >= 2
                    and kernel["xla_cosine_topk_programs"] == 0
                    and kernel["tpu_custom_call_in_served_program"],
                    f"the streaming kernel did not serve: {kernel}")

    http_server.stop()
    db.close()
    finish(args)


# ----------------------------------------------------------- four chips
def run_four_chips(args) -> None:
    """The mesh path and what it is compared with, nothing else:
    ShardedCorpus over four devices, built as SearchService builds it (f32
    rows, bf16 MXU scoring; then int8 residency), against numpy and a
    single-device DeviceCorpus over the same rows."""
    import numpy as np

    app_cfg, mgr = acquire_device(args)
    watch_compiles()

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nornicdb_tpu.ops.similarity import DeviceCorpus
    from nornicdb_tpu.parallel import ShardedCorpus, make_mesh, sharded_index

    n = args.rows * args.chips
    rng = np.random.default_rng(args.seed)
    with Phase("generate"):
        corpus = unit_rows(rng, n)
        ids = [f"v{i:07d}" for i in range(n)]
        queries = unit_rows(np.random.default_rng(args.seed + 1), 16)
        exact = queries @ corpus.T
    k = 100
    scfg = app_cfg.search
    mesh = make_mesh(devices=jax.devices()[:args.chips])

    def answer(corp, name):
        """16 queries in one stacked block, as
        SearchService._batched_corpus_search hands them over."""
        with Phase(f"{name}:load"):
            corp.add_batch(ids, corpus)
        with Phase(f"{name}:search"):
            hits = corp.search(queries, k=k, min_similarity=-1.0)
        rec, err = [], 0.0
        for qi, row in enumerate(hits):
            require(len(row) == k, f"{name} query {qi}: {len(row)} hits")
            got = [int(id_[1:]) for id_, _ in row]
            rec.append(recall_at_k(got, exact[qi], k))
            err = max(err, max(abs(s - float(exact[qi][r]))
                               for (_, s), r in zip(row, got)))
        require(np.mean(rec) >= RECALL_FLOOR,
                f"{name}: recall@{k} {np.mean(rec):.4f} < {RECALL_FLOOR}")
        require(err <= SCORE_TOL, f"{name}: score off by {err}")
        emit(corpus=name, recall_at_100_mean=float(np.mean(rec)),
             recall_at_100_min=float(np.min(rec)), max_abs_score_error=err)
        return [[id_ for id_, _ in row] for row in hits]

    def placement(arr, name):
        shards = arr.addressable_shards
        devices = {s.device for s in shards}
        per = [s.data.shape[0] for s in shards]
        emit(corpus=name, shard_devices=sorted(str(d) for d in devices),
             rows_per_shard=per)
        require(len(devices) == args.chips,
                f"{name}: {len(devices)} devices hold the corpus")
        require(per == [n // args.chips] * args.chips,
                f"{name}: rows per shard {per}")

    answers = {}
    for quantized, name in ((False, "sharded"), (True, "sharded_int8")):
        corp = ShardedCorpus(dims=DIMS, mesh=mesh, dtype=jnp.float32,
                             quantized=quantized,
                             rescore_factor=scfg.rescore_factor)
        answers[name] = answer(corp, name)
        placement(corp._dev_i8[0] if quantized else corp._dev, name)
        require(corp.shard_stats.dispatches == 1,
                f"{name}: {corp.shard_stats.dispatches} dispatches for one "
                "batch")
        del corp
    answers["single"] = answer(DeviceCorpus(dims=DIMS), "single_device")
    for name in ("sharded", "sharded_int8"):
        overlap = float(np.mean([
            len(set(a) & set(b)) / k
            for a, b in zip(answers[name], answers["single"])]))
        emit(corpus=name, overlap_with_single_device=overlap)
        require(overlap >= 0.9, f"{name} disagrees with the single-device "
                f"corpus: overlap@{k} {overlap}")

    if jax.devices()[0].platform == "tpu":
        text = sharded_index._sharded_search.lower(
            jax.ShapeDtypeStruct((16, DIMS), jnp.float32,
                                 sharding=NamedSharding(mesh, P())),
            jax.ShapeDtypeStruct((n, DIMS), jnp.float32,
                                 sharding=NamedSharding(mesh, P("data", None))),
            jax.ShapeDtypeStruct((n,), jnp.bool_,
                                 sharding=NamedSharding(mesh, P("data"))),
            128, 128, "data", mesh).compile().as_text()
        emit(program={"tpu_custom_call": "tpu_custom_call" in text,
                      "all_gather": "all-gather" in text})
        require("tpu_custom_call" in text and "all-gather" in text,
                "the served mesh program lacks the kernel or the all-gather")
    back = mgr.stats()
    emit(backend={key: back[key] for key in (
        "state", "fallbacks_total", "degrades_total")})
    require(back["fallbacks_total"] == 0 and back["degrades_total"] == 0,
            "the backend fell back or degraded")
    finish(args)


def finish(args) -> None:
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform == "tpu":
        print(json.dumps({"ok": True, "device": device}), flush=True)
    else:  # only --rehearse-cpu gets here: every phase passed, nothing earned
        print(json.dumps({"ok": False, "rehearsal_passed": True,
                          "device": device}), flush=True)
    hard_exit(0)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=262_144,
                    help="corpus rows per chip (lower only to rehearse)")
    ap.add_argument("--model-preset", default="bge_m3",
                    help="models.bge_m3 preset, as `serve --model-preset`")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the mesh-sharded corpus path")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="let the phases run on the CPU backend; never "
                         "prints an ok result")
    args = ap.parse_args()
    try:
        (run_four_chips if args.chips == 4 else run_one_chip)(args)
    except BaseException:
        # the one handler: report, and make the run fail
        traceback.print_exc()
        print(f"chip_smoke: FAILED in phase {_phase!r}", file=sys.stderr)
        hard_exit(1)


if __name__ == "__main__":
    main()
