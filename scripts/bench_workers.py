#!/usr/bin/env python
"""Multi-process worker-scaling sweep: does adding cores add throughput?

Boots the full prefork stack — primary DB + HttpServer, N SO_REUSEPORT
protocol workers, the device broker, the shared-memory read plane — and
drives a mixed load (raw-vector search + embed + Cypher) through the
WORKER port for N in the sweep (default 1/2/4/8). Every vector search
crosses worker → broker → QueryBatcher → one fused device program; embeds
and Cypher proxy to the primary, so the table shows exactly which classes
scale with workers and which stay pinned to the primary's GIL.

Writes the committed ``BENCH_multiproc.json`` artifact (ROADMAP item 1's
"published scaling table") and asserts two invariants at exit:

* **one-program-per-fused-batch** — device search programs launched ==
  QueryBatcher batches dispatched, per configuration and in total. The
  broker may never turn one worker batch into per-query programs.
* **scaling** (on runners with >= 4 cores) — aggregate search qps at
  4 workers >= 2x the 1-worker number.

stdout carries only the artifact JSON; progress goes to stderr (the
``make bench`` convention).
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _bench_common  # noqa: E402

if _REPO not in sys.path:  # runnable without an editable install
    sys.path.insert(0, _REPO)


def eprint(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


DIMS = 256
N_DOCS = 2000


def build_db(seed: int = 0):
    import numpy as np

    import nornicdb_tpu
    from nornicdb_tpu.db import Config
    from nornicdb_tpu.embed.base import HashEmbedder
    from nornicdb_tpu.storage.types import Node

    db = nornicdb_tpu.DB(None, Config(inference_enabled=False,
                                      auto_compact=False))
    db.set_embedder(HashEmbedder(DIMS))
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(N_DOCS, DIMS)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    for i in range(N_DOCS):
        # embedding attached at create: the search service indexes it off
        # the storage event — no embed-worker round trip for corpus setup
        db.storage.create_node(Node(
            id=f"doc{i}", labels=["Bench"],
            properties={"content": f"bench doc {i}"},
            embedding=vecs[i],
        ))
    return db


class LoadGen:
    """One traffic class: threads with keep-alive connections hammering
    one endpoint until the deadline; per-request latencies collected."""

    def __init__(self, name: str, port: int, n_threads: int, make_request):
        self.name = name
        self.port = port
        self.n_threads = n_threads
        self.make_request = make_request
        self.latencies: list[float] = []
        self.errors = 0
        self.sheds = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

    def _loop(self, idx: int) -> None:
        rng = random.Random(1000 + idx)
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=30)
        local_lat: list[float] = []
        errors = sheds = 0
        while not self._stop.is_set():
            path, body = self.make_request(rng)
            t0 = time.perf_counter()
            try:
                conn.request("POST", path, body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                if resp.status == 429:
                    sheds += 1
                elif resp.status != 200:
                    errors += 1
                else:
                    local_lat.append(time.perf_counter() - t0)
            except OSError:
                errors += 1
                try:
                    conn.close()
                except OSError:
                    pass
                conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=30)
        try:
            conn.close()
        except OSError:
            pass
        with self._lock:
            self.latencies.extend(local_lat)
            self.errors += errors
            self.sheds += sheds

    def start(self) -> "LoadGen":
        for i in range(self.n_threads):
            t = threading.Thread(target=self._loop, args=(i,), daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(30)

    def summary(self, wall_s: float) -> dict:
        lat = sorted(self.latencies)

        def pct(p: float) -> float:
            return round(lat[int(p * (len(lat) - 1))] * 1e3, 3) if lat \
                else 0.0

        return {
            "requests": len(lat),
            "qps": round(len(lat) / wall_s, 1),
            "p50_ms": pct(0.50),
            "p99_ms": pct(0.99),
            "errors": self.errors,
            "sheds_429": self.sheds,
        }


def run_config(n_workers: int, duration: float, seed: int) -> dict:
    import numpy as np

    from nornicdb_tpu.server.http import HttpServer
    from nornicdb_tpu.server.workers import WorkerPool

    eprint(f"[bench_workers] config: {n_workers} worker(s)")
    db = build_db(seed)
    http_srv = HttpServer(db, port=0, serve_ui=False)
    http_srv.start()
    pool = WorkerPool(db, http_srv.port, n_workers=n_workers).start()
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            c = http.client.HTTPConnection("127.0.0.1", pool.port,
                                           timeout=5)
            c.request("GET", "/health")
            c.getresponse().read()
            c.close()
            break
        except OSError:
            time.sleep(0.2)
    else:
        raise RuntimeError("workers never started listening")

    # force batcher creation now so counter deltas are clean
    batcher = db.search.ensure_batcher()
    corpus = db.search.corpus()
    rng0 = np.random.default_rng(seed + 1)
    base_vecs = rng0.normal(size=(512, DIMS)).astype(np.float32).tolist()

    # warmup: first dispatches pay device program compiles (seconds on a
    # cold process) — they must not land inside the measured window
    warm = http.client.HTTPConnection("127.0.0.1", pool.port, timeout=30)
    for i in range(5):
        warm.request("POST", "/nornicdb/search", json.dumps(
            {"vector": base_vecs[i], "limit": 10}).encode(),
            {"Content-Type": "application/json"})
        warm.getresponse().read()
    warm.close()

    def search_req(rng: random.Random):
        # unique-ish vectors: perturb a base row so the generation-stamped
        # worker caches can't serve the whole run from one entry
        row = list(base_vecs[rng.randrange(len(base_vecs))])
        row[rng.randrange(DIMS)] += rng.random()
        # ids/scores only: per-hit content enrichment would serialize the
        # sweep on the PRIMARY's GIL and mask the worker scaling under test
        return "/nornicdb/search", json.dumps(
            {"vector": row, "limit": 5,
             "include_content": False}).encode()

    def embed_req(rng: random.Random):
        return "/nornicdb/embed", json.dumps(
            {"text": f"bench embed {rng.randrange(10_000)}"}).encode()

    def cypher_req(rng: random.Random):
        if rng.random() < 0.3:
            stmt = {"statement": "CREATE (:BenchW {k: $k})",
                    "parameters": {"k": rng.randrange(10_000)}}
        else:
            stmt = {"statement":
                    "MATCH (n:Bench) RETURN count(n) AS c",
                    "parameters": {}}
        return "/db/neo4j/tx/commit", json.dumps(
            {"statements": [stmt]}).encode()

    q0 = batcher.stats.queries
    b0 = batcher.stats.batches
    d0 = corpus.sync_stats.device_dispatches
    # enough client concurrency that queue depth — and therefore fused
    # batch size — survives the kernel spreading connections across N
    # workers: the scaling story is protocol parse fanning out while the
    # device serves everyone from ONE program per batch window
    gens = [
        LoadGen("search", pool.port, 32, search_req).start(),
        LoadGen("embed", pool.port, 2, embed_req).start(),
        LoadGen("cypher", pool.port, 2, cypher_req).start(),
    ]
    t0 = time.perf_counter()
    time.sleep(duration)
    for g in gens:
        g.stop()
    wall = time.perf_counter() - t0
    queries = batcher.stats.queries - q0
    batches = batcher.stats.batches - b0
    dispatches = corpus.sync_stats.device_dispatches - d0
    out = {
        "workers": n_workers,
        "wall_s": round(wall, 2),
        "classes": {g.name: g.summary(wall) for g in gens},
        "broker": {
            "queries": queries,
            "fused_batches": batches,
            "device_dispatches": dispatches,
            "avg_fused_batch": round(queries / batches, 2) if batches
            else 0.0,
        },
        "pool": {"alive": pool.alive(), "respawns": pool.respawns},
    }
    pool.stop()
    http_srv.stop()
    db.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="short load windows (CI smoke)")
    ap.add_argument("--workers", default="1,2,4,8",
                    help="comma-separated worker counts to sweep")
    ap.add_argument("--duration", type=float, default=6.0,
                    help="seconds of load per configuration")
    ap.add_argument("--out", default="BENCH_multiproc.json")
    args = ap.parse_args(argv)

    counts = [int(x) for x in args.workers.split(",") if x.strip()]
    duration = 2.5 if args.quick else args.duration
    cores = os.cpu_count() or 1
    eprint(f"[bench_workers] sweep {counts} x {duration}s on {cores} cores")

    t_start = time.time()
    configs = [run_config(n, duration, seed=42) for n in counts]

    # -- invariants, asserted at exit ---------------------------------------
    failures: list[str] = []
    for cfg in configs:
        br = cfg["broker"]
        if br["fused_batches"] != br["device_dispatches"]:
            failures.append(
                f"{cfg['workers']}w: {br['fused_batches']} fused batches "
                f"but {br['device_dispatches']} device programs — the "
                "one-program-per-fused-batch invariant is broken")
        if br["queries"] == 0:
            failures.append(
                f"{cfg['workers']}w: no query ever reached the broker")
        if cfg["classes"]["search"]["errors"]:
            failures.append(
                f"{cfg['workers']}w: "
                f"{cfg['classes']['search']['errors']} search errors")
    by_n = {c["workers"]: c for c in configs}
    scaling = None
    if 1 in by_n and 4 in by_n:
        q1 = by_n[1]["classes"]["search"]["qps"]
        q4 = by_n[4]["classes"]["search"]["qps"]
        scaling = {"search_qps_1w": q1, "search_qps_4w": q4,
                   "speedup_4w": round(q4 / q1, 2) if q1 else 0.0}
        if cores >= 4 and q1 and q4 < 2.0 * q1:
            failures.append(
                f"4-worker search qps {q4} < 2x the 1-worker {q1} on a "
                f"{cores}-core runner")

    artifact = {
        "bench": "multiproc_workers",
        "generated_unix": int(t_start),
        "host": {"cores": cores, "quick": bool(args.quick),
                 "duration_s": duration},
        "corpus": {"docs": N_DOCS, "dims": DIMS},
        "configs": configs,
        "scaling": scaling,
        "invariants": {
            "one_program_per_fused_batch": not any(
                "invariant" in f for f in failures),
            "failures": failures,
        },
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(artifact["scaling"] or {}, sort_keys=True))
    for cfg in configs:
        s = cfg["classes"]["search"]
        eprint(f"[bench_workers] {cfg['workers']}w: search {s['qps']} qps "
               f"p50={s['p50_ms']}ms p99={s['p99_ms']}ms "
               f"fused_avg={cfg['broker']['avg_fused_batch']}")
    rc = _bench_common.finish("bench_workers", failures, log_fn=eprint)
    if rc == 0:
        eprint(f"[bench_workers] -> {args.out}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
