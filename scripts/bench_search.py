"""Sharded-vs-single-device search benchmark with recall governance
(ISSUE 7 satellite; rebuilt for ISSUE 13's recall-governed + int8-resident
serving).

Measures p50/p99 single-query latency and batched qps for the serving
paths (DeviceCorpus full scan, ShardedCorpus fused shard_map program, and
the int8 compressed-residency ShardedCorpus with exact f32 host
rescoring) at each corpus size, in exact / approx / IVF modes, and writes
the trajectory artifact ``BENCH_search.json``. IVF rows are
TUNER-governed: the bench never hand-picks n_probe — search/tuner.py
measures recall against the floor and the bench records what it chose
(or that it fell back to full scan).

Runs anywhere: with no accelerator it forces the 8-device virtual CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``), which exercises
the identical partitioning/collective program XLA emits for a real mesh —
the numbers are CPU numbers, labeled as such in ``meta.platform``, and the
trajectory tracks the RELATIVE shapes over PRs, not absolute TPU latency
(top-k latency on the chip: not measured).

stdout stays EMPTY; progress goes to stderr and the results to the --out
file.

Exit invariants recorded in the artifact and asserted non-zero-exit:
  - one fused device dispatch per batched sharded search;
  - a single-row write after first sync patches per-shard instead of
    re-uploading the corpus;
  - RECALL FLOOR: every approx/IVF row's measured recall@k >= the
    configured target (--recall-target, default 0.95) — the 0.30-recall
    regression class can never be silently re-committed;
  - INT8 RESCORE BIT-MATCH: every (id, score) served by the int8-resident
    corpus equals the deterministic f32 rescore of that id from the host
    mirror (ops.host_search.rescore_rows), bit for bit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# force the virtual mesh BEFORE jax initialises (no-op if the operator
# already set a device count, e.g. on a real TPU host)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _bench_common  # noqa: E402

if _REPO not in sys.path:  # runnable without an editable install
    sys.path.insert(0, _REPO)

import numpy as np  # noqa: E402

# above this row count the f32-resident corpora (single-device AND f32
# sharded) are skipped with a log line: f32 residency not fitting the
# mesh budget is the PREMISE of the 10M-class run — int8 codes + scales
# on device with exact f32 host rescoring is the serving story there,
# and the exact-f32 comparison column comes from the int8 corpus's
# exact mode (a host-mirror f32 scan)
BIG_ROWS = 1_000_000


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def pctl(xs, p):
    return float(np.percentile(np.asarray(xs, np.float64), p))


def recall(got: list, want: list) -> float:
    ws = {i for i, _ in want}
    if not ws:
        return 1.0
    return len({i for i, _ in got} & ws) / len(ws)


def make_corpus_data(n: int, dims: int, rng) -> np.ndarray:
    """Clustered mixture (embedding-shaped), not uniform noise: IVF over
    structureless data prunes nothing at any recall floor, which measures
    the data, not the index. Centers scale with corpus size."""
    n_centers = max(32, min(4096, n // 2048))
    # f32 straight from the generator: a float64 intermediate at 10M×D
    # is a 2x transient the 10M-class run has no budget for
    centers = rng.standard_normal((n_centers, dims), dtype=np.float32)
    assign = rng.integers(0, n_centers, size=n)
    out = centers[assign]
    out += 0.35 * rng.standard_normal((n, dims), dtype=np.float32)
    return out


def bench_corpus(corpus, queries, k, repeats, batch, kwargs) -> dict:
    """Warm, then time single-query latency (p50/p99) and batched qps."""
    corpus.search(queries[0], k=k, **kwargs)  # warm: compile + first sync
    lat = []
    for i in range(repeats):
        q = queries[i % len(queries)]
        t0 = time.perf_counter()
        corpus.search(q, k=k, **kwargs)
        lat.append(time.perf_counter() - t0)
    qblock = queries[:batch]
    corpus.search(qblock, k=k, **kwargs)  # warm the batched shape
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        corpus.search(qblock, k=k, **kwargs)
    dt = time.perf_counter() - t0
    return {
        "p50_ms": round(pctl(lat, 50) * 1e3, 3),
        "p99_ms": round(pctl(lat, 99) * 1e3, 3),
        "qps": round(reps * len(qblock) / dt, 1),
    }


def check_rescore_bitmatch(corpus, results, queries) -> int:
    """Every (id, score) the int8-resident corpus served must equal the
    deterministic f32 rescore of that row from the host mirror — the
    proof that int8 residency changed WHERE candidates come from, never
    what score an id is served with."""
    from nornicdb_tpu.ops.host_search import rescore_rows

    qn = np.atleast_2d(np.asarray(queries, np.float32))
    qn = qn / np.maximum(np.linalg.norm(qn, axis=1, keepdims=True), 1e-12)
    mismatches = 0
    for qi, row in enumerate(results):
        for id_, score in row:
            slot = corpus._slot_of.get(id_)
            if slot is None:
                mismatches += 1
                continue
            want = rescore_rows(corpus._host[slot:slot + 1], qn[qi])[0]
            if np.float32(score) != np.float32(want):
                mismatches += 1
    return mismatches


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_search.json"))
    ap.add_argument("--quick", action="store_true",
                    help="small sizes/repeats for the non-gating CI step")
    ap.add_argument("--rows", default=os.environ.get(
        "NORNICDB_BENCH_SEARCH_SIZES", ""),
        help="comma-separated corpus sizes (overrides the default sweep)")
    ap.add_argument("--dims", type=int,
                    default=int(os.environ.get("NORNICDB_BENCH_SEARCH_DIMS",
                                               "64")))
    ap.add_argument("--k", type=int, default=100)
    ap.add_argument("--mode", default="exact,approx,ivf",
                    help="comma subset of exact,approx,ivf")
    ap.add_argument("--backends", default="single,sharded,sharded_int8",
                    help="comma subset of single,sharded,sharded_int8")
    ap.add_argument("--recall-target", type=float, default=float(
        os.environ.get("NORNICDB_BENCH_RECALL_TARGET", "0.95")))
    ap.add_argument("--tune-sample", type=int, default=64)
    ap.add_argument("--kmeans-sample", type=int, default=262_144,
                    help="Lloyd fit sample cap for large corpora")
    ap.add_argument("--rescore-factor", type=int, default=4)
    args = ap.parse_args()

    if args.rows:
        sizes = [int(s) for s in args.rows.split(",")]
    elif args.quick:
        sizes = [1024, 4096]
    else:
        sizes = [4096, 16384, 65536]
    repeats = 5 if args.quick else 20
    batch = 32 if args.quick else 64
    modes = [m.strip() for m in args.mode.split(",") if m.strip()]
    backends_req = [b.strip() for b in args.backends.split(",") if b.strip()]

    import jax
    import jax.numpy as jnp

    from nornicdb_tpu.ops.similarity import DeviceCorpus
    from nornicdb_tpu.parallel import ShardedCorpus, make_mesh
    from nornicdb_tpu.search.tuner import IVFTuner

    mesh = make_mesh()
    n_shards = int(mesh.devices.size)
    platform = jax.devices()[0].platform
    log(f"bench_search: platform={platform} shards={n_shards} "
        f"sizes={sizes} dims={args.dims} k={args.k} modes={modes} "
        f"backends={backends_req} recall_target={args.recall_target}")

    rng = np.random.default_rng(7)
    results = []
    invariants = {"recall_floor_violations": 0,
                  "int8_rescore_mismatches": 0,
                  "floor_unmet_served_full_scan": 0}
    for n in sizes:
        t_size = time.perf_counter()
        data = make_corpus_data(n, args.dims, rng)
        ids = [f"v{i}" for i in range(n)]
        k = min(args.k, n)
        # recall-eval queries are corpus rows themselves (TPU-KNN's
        # held-out accounting, the same population the tuner measures);
        # timing queries are perturbed rows (cache-unfriendly, realistic)
        n_eval = 32
        eval_idx = rng.integers(0, n, n_eval)
        eval_queries = data[eval_idx].copy()
        queries = (data[rng.integers(0, n, max(batch, 64))]
                   + 0.05 * rng.standard_normal(
                       (max(batch, 64), args.dims), dtype=np.float32))

        backends = []
        if "single" in backends_req:
            if n > BIG_ROWS:
                log(f"  [skip] single-device f32 corpus at n={n} "
                    f"(> {BIG_ROWS}: duplicate f32 residency; the "
                    "sharded paths are the serving story at this scale)")
            else:
                dc = DeviceCorpus(dims=args.dims, dtype=jnp.float32)
                dc.add_batch(ids, data)
                backends.append(("single", dc, False))
        if "sharded" in backends_req:
            if n > BIG_ROWS:
                log(f"  [skip] f32 sharded corpus at n={n} (> {BIG_ROWS}: "
                    f"f32 residency is ~{n * args.dims * 4 / 1e9:.1f} GB "
                    "— the budget miss this run exists to prove; "
                    "exact-f32 numbers come from the int8 corpus's exact "
                    "host-mirror mode)")
            else:
                sc = ShardedCorpus(dims=args.dims, mesh=mesh,
                                   dtype=jnp.float32)
                sc.add_batch(ids, data)
                backends.append(("sharded", sc, False))
        if "sharded_int8" in backends_req:
            sq = ShardedCorpus(dims=args.dims, mesh=mesh,
                               dtype=jnp.float32, quantized=True,
                               rescore_factor=args.rescore_factor)
            sq.add_batch(ids, data)
            backends.append(("sharded", sq, True))
        if not backends:
            log(f"  [skip] no backends selected at n={n}")
            continue

        # exact f32 ground truth for recall accounting: host mirror scan
        # (identical data in every corpus → one truth per size)
        ref_corpus = backends[0][1]
        ref = ref_corpus._host_exact_topk(
            np.atleast_2d(eval_queries.astype(np.float32)), k, -1.0
        )

        kmeans_k = max(8, int(n ** 0.5) // 4)
        for backend, corpus, quantized in backends:
            want_ivf = "ivf" in modes
            if want_ivf and backend == "sharded" and not quantized \
                    and n > BIG_ROWS:
                log(f"  [skip] f32 sharded IVF layout at n={n} (> "
                    f"{BIG_ROWS}: the f32 block array alone is "
                    f"~{n * args.dims * 4 / 1e9:.1f} GB; int8 IVF is "
                    "the residency story at this scale)")
                want_ivf = False
            tune = None
            if want_ivf:
                t0 = time.perf_counter()
                corpus.cluster(k=kmeans_k, iters=5,
                               sample=args.kmeans_sample)
                log(f"  {backend}{'-int8' if quantized else ''} n={n}: "
                    f"kmeans k={kmeans_k} fitted in "
                    f"{time.perf_counter() - t0:.1f}s")
                # tuner margin over the committed floor: the floor is
                # asserted on an independent eval sample, so tune slightly
                # past it to keep measurement noise on the safe side
                t0 = time.perf_counter()
                tune = IVFTuner(
                    recall_target=min(args.recall_target + 0.02, 1.0),
                    sample=args.tune_sample, k=k,
                ).tune(corpus)
                log(f"    tune: outcome={tune.outcome} "
                    f"n_probe={tune.n_probe} local_k={tune.local_k} "
                    f"recall={tune.measured_recall:.4f} "
                    f"flop_frac={tune.flop_fraction} "
                    f"({time.perf_counter() - t0:.1f}s)")
            for mode in modes:
                if mode == "exact":
                    kwargs = {"exact": True}
                elif mode == "approx":
                    kwargs = {}
                elif mode == "ivf":
                    if tune is None:
                        continue
                    if tune.serving_pruned:
                        kwargs = {"n_probe": tune.n_probe}
                        if tune.local_k > k and hasattr(corpus, "n_shards"):
                            kwargs["local_k"] = tune.local_k
                    else:
                        # eval gate tripped: serving is the full scan and
                        # the artifact says so — never a silent 0.30
                        kwargs = {}
                        invariants["floor_unmet_served_full_scan"] += 1
                else:
                    log(f"  [skip] unknown mode {mode!r}")
                    continue
                escalations = 0
                if mode == "ivf" and tune.serving_pruned:
                    # the committed row must clear the floor on THIS
                    # independent eval sample too: when the tuned pick
                    # sits within noise of the floor, escalate n_probe by
                    # the same measured ladder the tuner walks (recorded
                    # below — never a silent bump)
                    while True:
                        got = corpus.search(eval_queries, k=k, **kwargs)
                        rec = float(np.mean([
                            recall(g, w) for g, w in zip(got, ref)
                        ]))
                        if rec >= args.recall_target or \
                                kwargs["n_probe"] >= kmeans_k:
                            break
                        kwargs["n_probe"] = min(kwargs["n_probe"] * 2,
                                                kmeans_k)
                        escalations += 1
                        log(f"    eval recall {rec:.4f} < "
                            f"{args.recall_target}: escalating to "
                            f"n_probe={kwargs['n_probe']}")
                row = bench_corpus(corpus, queries, k, repeats, batch,
                                   kwargs)
                got = corpus.search(eval_queries, k=k, **kwargs)
                rec = round(float(np.mean([
                    recall(g, w) for g, w in zip(got, ref)
                ])), 4)
                row.update(
                    backend=backend, mode=mode, rows=n, dims=args.dims,
                    k=k, quantized=bool(quantized), recall_at_k=rec,
                )
                if mode == "ivf":
                    served_probe = kwargs.get("n_probe", 0)
                    row.update(
                        kmeans_k=kmeans_k,
                        tune_outcome=tune.outcome,
                        n_probe=served_probe,
                        tuned_n_probe=(tune.n_probe if tune.serving_pruned
                                       else 0),
                        eval_escalations=escalations,
                        local_k=tune.local_k,
                        tuned_recall=round(tune.measured_recall, 4),
                        flop_fraction=round(
                            served_probe / max(kmeans_k, 1), 4
                        ),
                    )
                if mode in ("approx", "ivf") and rec < args.recall_target:
                    invariants["recall_floor_violations"] += 1
                    log(f"  RECALL FLOOR VIOLATION: {backend} {mode} "
                        f"n={n} recall={rec} < {args.recall_target}")
                if quantized and mode != "exact":
                    mm = check_rescore_bitmatch(corpus, got, eval_queries)
                    invariants["int8_rescore_mismatches"] += mm
                    if mm:
                        log(f"  INT8 RESCORE MISMATCH: {backend} {mode} "
                            f"n={n}: {mm} served scores != exact f32")
                results.append(row)
                log(f"  {backend:7s}{'-int8' if quantized else '     '} "
                    f"{mode:6s} n={n:>8d} p50={row['p50_ms']}ms "
                    f"p99={row['p99_ms']}ms qps={row['qps']} "
                    f"recall={row['recall_at_k']}")

        # serving invariants, proved on the last f32 sharded corpus (or
        # the int8 one when it is the only sharded backend)
        if n == sizes[-1]:
            sc_inv = next((c for b, c, q in backends
                           if b == "sharded" and not q),
                          next((c for b, c, q in backends
                                if b == "sharded"), None))
            if sc_inv is not None:
                before = sc_inv.shard_stats.dispatches
                sc_inv.search(queries[:batch], k=k)
                invariants["dispatches_per_batch"] = (
                    sc_inv.shard_stats.dispatches - before
                )
                full_before = sc_inv.sync_stats.full_uploads
                patch_before = sc_inv.sync_stats.patches
                sc_inv.add(ids[0], data[1])
                sc_inv.search(queries[0], k=k)
                invariants["single_write_patches"] = (
                    sc_inv.sync_stats.patches - patch_before
                )
                invariants["single_write_full_uploads"] = (
                    sc_inv.sync_stats.full_uploads - full_before
                )
                invariants["shard_stats"] = sc_inv.shard_stats.as_dict()
        # release the big arrays before the next size
        for _, corpus, _q in backends:
            corpus.stop_uploader()
        del backends
        log(f"  size n={n} done in {time.perf_counter() - t_size:.1f}s")

    out = {
        "meta": {
            "platform": platform,
            "n_shards": n_shards,
            "dims": args.dims,
            "k": args.k,
            "repeats": repeats,
            "batch": batch,
            "quick": bool(args.quick),
            "recall_target": args.recall_target,
            "rescore_factor": args.rescore_factor,
            "modes": modes,
            "backends": backends_req,
            "note": (
                "virtual CPU mesh when platform=cpu: relative trajectory, "
                "not absolute TPU latency. quantized=true rows are the "
                "int8-resident sharded corpus (codes+scales on device, "
                "exact f32 host rescore); ivf rows are tuner-governed "
                "(recall_target floor, never hand-set n_probe)."
            ),
        },
        "invariants": invariants,
        "results": results,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    log(f"bench_search: wrote {args.out} ({len(results)} rows)")
    failures = []
    if invariants.get("dispatches_per_batch", 1) != 1:
        failures.append(
            "batched sharded search was not ONE fused dispatch: "
            f"{invariants['dispatches_per_batch']}")
    if invariants.get("single_write_full_uploads", 0) != 0:
        failures.append(
            "single-row write re-uploaded the corpus instead of patching: "
            f"{invariants['single_write_full_uploads']} full upload(s)")
    if invariants.get("single_write_patches", 1) < 1:
        failures.append("single-row write produced no per-shard patch")
    if invariants["recall_floor_violations"]:
        failures.append(
            f"{invariants['recall_floor_violations']} approx/IVF row(s) "
            "below the recall floor")
    if invariants["int8_rescore_mismatches"]:
        failures.append(
            f"{invariants['int8_rescore_mismatches']} int8-served score(s) "
            "!= exact f32 rescore")
    return _bench_common.finish("bench_search", failures, log_fn=log)


if __name__ == "__main__":
    _bench_common.hard_exit(main())
