"""Hybrid search service: TPU vector search + BM25 + RRF fusion + MMR.

Behavioral reference: /root/reference/pkg/search/search.go —
Service :236, Search :851, rrfHybridSearch :890, VectorSearchCandidates
:1005, index maintenance :1187-1301; vector_pipeline.go (candidate
generation policy).

TPU-first departure from the reference's pipeline policy (vector_pipeline.go
:22-28 — brute force only when N<5000, else HNSW): here the device-resident
brute-force corpus is the PRIMARY path at every N (exact scores, batched
GEMM; approx_max_k membership), and HNSW is the no-accelerator fallback.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from nornicdb_tpu.embed.base import Embedder
from nornicdb_tpu.embed.queue import build_embedding_text
from nornicdb_tpu.errors import NotFoundError
from nornicdb_tpu.ops.similarity import DeviceCorpus
# imported with the service (not on first use) so that /metrics renders the
# dispatcher's families before the first vector search
from nornicdb_tpu.search.batcher import QueryBatcher
from nornicdb_tpu.search.bm25 import BM25Index
from nornicdb_tpu.search.fusion import adaptive_rrf_weights, apply_mmr, fuse_rrf
from nornicdb_tpu.search.hnsw import HNSWIndex
from nornicdb_tpu.search.tuner import TUNE_OUTCOMES, IVFTuner, TuneState
from nornicdb_tpu.storage.types import Engine, Node
from nornicdb_tpu.telemetry.tracing import tracer as _tracer

logger = logging.getLogger(__name__)


@dataclass
class SearchStats:
    indexed: int = 0
    removed: int = 0
    searches: int = 0
    vector_candidates: int = 0
    fulltext_candidates: int = 0


@dataclass
class SearchConfig:
    min_similarity: float = 0.0
    rrf_k: float = 60.0
    mmr_enabled: bool = False
    mmr_lambda: float = 0.7
    candidates_multiplier: int = 4  # fetch k*mult candidates per modality
    # auto | tpu | sharded | hnsw.  "sharded" pins the mesh path from the
    # start; "auto" starts single-device and promotes to the sharded path
    # once the corpus crosses sharded_min_rows on a >1-device mesh
    # (docs/operations.md "Sharded serving tuning")
    backend: str = "auto"
    # auto-promotion threshold: rows at which one chip's HBM stops being
    # the right home for the corpus.  0 disables promotion.
    sharded_min_rows: int = 100_000
    # exact=True full-sorts per shard/device (recall 1.0, slower);
    # the default approx membership honors the ~0.95 recall contract
    exact: bool = False
    # per-shard candidate count for the sharded merge (0 = k). Raising it
    # above k oversamples each shard's approx top-k — the recall knob the
    # shard_local_k_overflows metric tunes.
    local_k: int = 0
    # cross-encoder second stage (ref: applyCrossEncoderRerank search.go:1639,
    # feature-flag-gated like the reference)
    rerank_enabled: bool = False
    rerank_candidates: int = 20
    # IVF cluster pruning — EXPLICIT OVERRIDE ONLY (0 = tuner-governed).
    # The supported operator contract is recall_target below: the tuner
    # measures recall@tune_k of the fitted layout against exact ground
    # truth at recluster/promotion time and picks the smallest
    # (n_probe, local_k) meeting the floor. Setting n_probe here bypasses
    # the eval gate — a hand-tuned speed knob with an unmeasured recall
    # cost, the exact footgun the tuner exists to kill.
    n_probe: int = 0
    # recall-governed IVF autotuning (search/tuner.py, TPU-KNN's
    # recall-vs-FLOPs accounting): operators set the floor, never probe
    # counts. A layout that can't meet the floor serves full scan and
    # increments nornicdb_ivf_tunes_total{outcome="floor_unmet"}.
    recall_target: float = 0.95
    tune_enabled: bool = True
    tune_sample: int = 64        # held-out corpus rows per measurement
    tune_k: int = 100            # recall@k the floor is measured at
    tune_min_rows: int = 4096    # below this, full scan is the right plan
    # drift-triggered re-tune: fraction of the corpus mutated (adds +
    # removes) since the last tune that schedules a background
    # recluster + re-tune (0 disables)
    drift_threshold: float = 0.25
    # k-means fit sample cap for recluster (ops.kmeans.kmeans_fit): past
    # this many live rows the Lloyd fit runs on a uniform sample and the
    # full set chunk-assigns against the fitted centroids — at 10M×1024
    # a full fit is an O(10^13)-FLOP pass the drift re-tune would
    # otherwise pay in the background. 0 = always fit everything.
    cluster_fit_sample: int = 262_144
    # int8 compressed residency (sharded corpus only): device HBM holds
    # int8 codes + per-row scales (≈4x rows per byte); the merged
    # candidate set (rescore_factor × k oversample) is exact-rescored in
    # f32 from the host mirror, so served scores stay exact
    int8_residency: bool = False
    rescore_factor: int = 4
    # most queries one corpus scan serves: every vector search goes through
    # the coalescing dispatcher (search/batcher.py), which sends what is
    # queued when the chip comes free and never lingers for company
    # (SURVEY §7 hard part f)
    batch_max: int = 256
    # batched-search admission control (ROADMAP item 3): pending queries
    # beyond batch_max_queue shed with ResourceExhausted (0 = unbounded);
    # queries older than batch_deadline_ms at dispatch are shed too
    # (0 disables). Surfaced as 429/RESOURCE_EXHAUSTED at the edges.
    batch_max_queue: int = 1024
    batch_deadline_ms: float = 0.0
    # write-behind device sync: a background thread coalesces dirty corpus
    # blocks and patches them between queries, so a query after a write
    # burst waits for a bounded patch instead of staging the whole burst
    write_behind: bool = False
    write_behind_interval: float = 0.002


# -- default-config layering -------------------------------------------------
# `cli serve` installs the operator's AppConfig.search section here before
# any SearchService exists; embedded processes (tests, workers, notebooks)
# can set the same knobs via NORNICDB_SEARCH_<FIELD> env vars, read once per
# service construction. Precedence: explicit SearchService(config=...) >
# configure_defaults() > env > dataclass defaults.
_DEFAULTS_LOCK = threading.Lock()
_CONFIG_DEFAULTS: dict[str, Any] = {}


def configure_defaults(**kwargs) -> None:
    """Set process-wide SearchConfig defaults (unknown keys rejected)."""
    from dataclasses import fields as _fields

    known = {f.name for f in _fields(SearchConfig)}
    bad = set(kwargs) - known
    if bad:
        raise ValueError(f"unknown SearchConfig field(s): {sorted(bad)}")
    with _DEFAULTS_LOCK:
        _CONFIG_DEFAULTS.update(kwargs)


def default_search_config() -> SearchConfig:
    from dataclasses import fields as _fields
    import os

    from nornicdb_tpu.config import _coerce_env

    cfg = SearchConfig()
    for f in _fields(SearchConfig):
        raw = os.environ.get(f"NORNICDB_SEARCH_{f.name.upper()}")
        if raw is None:
            continue
        # same coercion rules as AppConfig's load_from_env, so the same
        # env value parses identically in served and embedded processes
        setattr(cfg, f.name, _coerce_env(getattr(cfg, f.name), raw))
    with _DEFAULTS_LOCK:
        overrides = dict(_CONFIG_DEFAULTS)
    for name, value in overrides.items():
        setattr(cfg, name, value)
    return cfg


# -- graph×vector fusion -----------------------------------------------------
def _pow2_row_bucket(n: int) -> int:
    """Row/k counts padded to power-of-two shape classes so the VectorTopK
    GEMM compiles once per bucket, never per exact corpus size (the
    nornjit recompile-sentinel contract)."""
    return 1 << (int(n) - 1).bit_length() if n > 1 else 1


def graph_masked_scores(
    qn: np.ndarray,
    corpus: np.ndarray,
    valid: np.ndarray,
    k: int,
    desc: bool,
    dev_ref: Optional[list] = None,
):
    """Device scoring for the Cypher ``VectorTopK`` operator: one masked
    GEMM over a row-normalized ``corpus`` (n, d) with the graph-predicate
    survivors as ``valid``, returning ``(scores, boundary)`` — per-row
    cosine scores (length n, original orientation) and the kth best
    masked score in that orientation.  ``desc=False`` (ORDER BY ... ASC)
    rides the same kernel on the negated query.  None when no device
    manager is serving (caller scores on host) — the gate never blocks,
    so a hung backend degrades to host scoring instead of wedging the
    query.  ``dev_ref`` is a one-slot list caching the padded
    device-resident corpus across queries of the same shape bucket.
    """
    from nornicdb_tpu import backend as _bk

    try:
        if _bk.manager_stats() is None or not _bk.manager().ready():
            return None
        import jax.numpy as jnp

        from nornicdb_tpu.ops.similarity import LANE, masked_dot_topk
        from nornicdb_tpu.telemetry import deviceprof as _deviceprof

        n = corpus.shape[0]
        rows_pad = max(_pow2_row_bucket(n), LANE)
        k_pad = min(_pow2_row_bucket(max(k, 1)), rows_pad)
        t0 = time.perf_counter()
        dev = None
        if dev_ref and dev_ref[0] is not None:
            cached_pad, cached = dev_ref[0]
            if cached_pad == rows_pad:
                dev = cached
        if dev is None:
            buf = np.zeros((rows_pad, corpus.shape[1]), np.float32)
            buf[:n] = corpus
            dev = jnp.asarray(buf)
            if dev_ref is not None:
                dev_ref[0] = (rows_pad, dev)
        vpad = np.zeros(rows_pad, bool)
        vpad[:n] = valid
        q = np.asarray(qn if desc else -qn, np.float32)
        scores, top = masked_dot_topk(
            jnp.asarray(q), dev, jnp.asarray(vpad), k_pad)
        scores = np.asarray(scores[:n], np.float64)
        boundary = float(np.asarray(top)[min(k, k_pad) - 1])
        _deviceprof.record_execute(
            "cypher", "vector_topk", _deviceprof.pow2_class(rows_pad, "n"),
            time.perf_counter() - t0)
        if not desc:
            # undo the ASC negation; masked rows become +inf, which can
            # never pass the caller's `score <= boundary + eps` cut
            scores = -scores
            boundary = -boundary
        return scores, boundary
    except Exception:
        logger.debug("graph-masked device scoring unavailable",
                     exc_info=True)
        return None


class SearchService:
    """(ref: search.Service pkg/search/search.go:236)"""

    def __init__(
        self,
        storage: Engine,
        embedder: Optional[Embedder] = None,
        dims: int = 0,
        config: Optional[SearchConfig] = None,
        brute_force_max: int = 0,  # kept for reference parity; unused on TPU
        vectorspaces=None,
    ):
        self.storage = storage
        self.embedder = embedder
        self.config = config or default_search_config()
        self.stats = SearchStats()
        self.vectorspaces = vectorspaces
        self._lock = threading.RLock()
        self._dims = dims or (embedder.dimensions() if embedder else 0)
        self._corpus: Optional[DeviceCorpus] = None
        self._hnsw: Optional[HNSWIndex] = None
        self._bm25 = BM25Index()
        self._vectors: dict[str, np.ndarray] = {}  # normalized, for MMR
        # id -> (text-digest, embedding-digest): lets no-op updates (e.g. the
        # access-count touch recall() performs per result) skip re-indexing,
        # which would otherwise dirty corpus blocks (and, for clustered
        # rows, invalidate the fitted IVF layout) on every search
        self._fingerprints: dict[str, tuple[bytes, bytes]] = {}
        self.cluster_result = None
        self.cluster_assignments: dict[str, int] = {}
        # ranked-result cache (ref: the reference's query cache pkg/cache +
        # embedding cache "450,000x speedup on hits", system-design.md:39).
        # Keyed by (query, limit, min_sim); stores only the ranked
        # (id, score, vec, ft) tuples — node data is re-fetched per hit so
        # property updates that don't reindex (access counts, decay scores)
        # never go stale. Invalidation is generation-based: any index
        # mutation bumps _generation, making every older entry dead on
        # lookup (O(1) invalidation, no sweeps).
        self._generation = 0
        self._rank_cache: "OrderedDict[tuple, tuple[int, float, list]]" = (
            OrderedDict()
        )
        self._rank_cache_max = 2048
        self._rank_cache_ttl = 30.0
        # backend="auto" shard promotion: None = not attempted, "running",
        # "done", "unavailable" (single device / promotion disabled)
        self._promo_state: Optional[str] = None
        self._promo_retry_at = 0.0
        # recall-governed IVF tuner state (search/tuner.py): the serving
        # plan (n_probe/local_k) + its measured-recall evidence, plus the
        # drift bookkeeping that schedules background re-tunes
        self._tune_state: Optional[TuneState] = None
        self.tune_counts: dict[str, int] = {o: 0 for o in TUNE_OUTCOMES}
        self._churn_since_tune = 0
        self._retuning = False

    # -- index plumbing ----------------------------------------------------
    def _ensure_vector_index(self, dims: int) -> None:
        """Create the vector index on first use.  MUST be called with no
        lock held: building a sharded corpus enumerates mesh devices — a
        cold backend acquisition that may block for the manager's acquire
        timeout (NL-DEV01).  Construction races resolve under the lock;
        the loser's corpus is discarded before it holds any resource."""
        with self._lock:
            if self._corpus is not None or self._hnsw is not None:
                return
        corpus = hnsw = None
        if self.config.backend == "sharded":
            # corpus rows sharded over the device mesh, per-shard top-k
            # merged via ICI all-gather (parallel.ShardedCorpus). A
            # degraded backend cannot enumerate mesh devices — serve on
            # a single-device corpus (itself host-backed while degraded)
            # instead of refusing to index; recovery re-uploads it.
            import jax.numpy as jnp

            from nornicdb_tpu.errors import DeviceUnavailable
            from nornicdb_tpu.parallel import ShardedCorpus

            try:
                # f32 storage, NOT ShardedCorpus's bf16 default: the
                # serving contract (docs/operations.md) is that exact
                # mode returns ids/scores identical to the single-device
                # DeviceCorpus full scan, and DeviceCorpus stores f32.
                # bf16 sharding stays an explicit opt-in for direct
                # constructor callers chasing peak MXU FLOP/s.
                corpus = ShardedCorpus(
                    dims=dims, dtype=jnp.float32,
                    quantized=self.config.int8_residency,
                    rescore_factor=self.config.rescore_factor,
                )
            except DeviceUnavailable:
                logger.warning(
                    "backend degraded: sharded corpus unavailable, "
                    "falling back to single-device corpus"
                )
                corpus = DeviceCorpus(dims=dims)
        elif self.config.backend in ("auto", "tpu"):
            corpus = DeviceCorpus(dims=dims)
        else:
            hnsw = HNSWIndex(dims=dims)
        with self._lock:
            if self._corpus is not None or self._hnsw is not None:
                return  # lost the creation race: drop ours, nothing started
            self._dims = dims
            if self.vectorspaces is not None:
                from nornicdb_tpu.vectorspace import VectorSpaceKey

                self.vectorspaces.register(VectorSpaceKey("default", dims))
            self._corpus, self._hnsw = corpus, hnsw
            if corpus is not None and self.config.write_behind:
                corpus.start_uploader(self.config.write_behind_interval)

    def index_node(self, node: Node) -> None:
        """(ref: IndexNode search.go:651; event wiring db.go:1020-1033)"""
        import hashlib

        text = build_embedding_text(node)
        emb = (
            np.asarray(node.embedding, np.float32)
            if node.embedding is not None else None
        )
        fp = (
            hashlib.blake2s(text.encode()).digest(),
            hashlib.blake2s(emb.tobytes()).digest() if emb is not None
            else b"",
        )
        if emb is not None and self._corpus is None and self._hnsw is None:
            # index creation happens OUTSIDE the service lock: a sharded
            # corpus enumerates mesh devices, and a cold/lost backend
            # would otherwise hang acquisition while every search and
            # index event waits on this lock (the round-5 deadlock shape,
            # NL-DEV01). The unlocked None-check is a benign race:
            # _ensure_vector_index is idempotent and double-checked.
            self._ensure_vector_index(emb.shape[0])
        with self._lock:
            if self._fingerprints.get(node.id) == fp:
                return  # unchanged: keep device corpus clean
            self._fingerprints[node.id] = fp
            self._generation += 1  # kills every cached ranking
            if text:
                self._bm25.index(node.id, text)
            else:
                self._bm25.remove(node.id)  # text dropped on update
            if emb is not None:
                v = emb
                n = np.linalg.norm(v)
                vn = v / n if n > 1e-12 else v
                self._vectors[node.id] = vn
                if self._corpus is not None:
                    self._corpus.add(node.id, vn)
                if self._hnsw is not None:
                    self._hnsw.add(node.id, vn)
            elif node.id in self._vectors:  # embedding dropped on update
                self._vectors.pop(node.id, None)
                if self._corpus is not None:
                    self._corpus.remove(node.id)
                if self._hnsw is not None:
                    self._hnsw.remove(node.id)
            self.stats.indexed += 1
        # OUTSIDE the lock (mesh enumeration is a cold backend
        # acquisition): promote to the sharded mesh path once the corpus
        # outgrows one chip (backend="auto", docs/operations.md)
        self._maybe_promote_sharded()
        self._note_churn()

    def remove_node(self, node_id: str) -> None:
        with self._lock:
            self._generation += 1
            self._fingerprints.pop(node_id, None)
            self._bm25.remove(node_id)
            self._vectors.pop(node_id, None)
            if self._corpus is not None:
                self._corpus.remove(node_id)
            if self._hnsw is not None:
                self._hnsw.remove(node_id)
            self.stats.removed += 1
        self._note_churn()

    def build_indexes(self) -> int:
        """Full rebuild from storage (ref: BuildIndexes / EnsureSearchIndexesBuilt
        db.go:1044-1062)."""
        n = 0
        for node in self.storage.all_nodes():
            self.index_node(node)
            n += 1
        return n

    # -- shard promotion ---------------------------------------------------
    def _maybe_promote_sharded(self) -> None:
        """backend="auto": once the corpus crosses sharded_min_rows, swap
        the single-device corpus for a mesh-sharded one on a background
        thread.  Must be called with NO lock held (the thread it spawns
        enumerates mesh devices — a cold backend acquisition)."""
        cfg = self.config
        if cfg.backend != "auto" or cfg.sharded_min_rows <= 0:
            return
        with self._lock:
            corpus = self._corpus
            if (
                corpus is None
                or hasattr(corpus, "n_shards")  # already sharded
                or self._promo_state in ("running", "done", "unavailable")
                or len(corpus) < cfg.sharded_min_rows
                or time.monotonic() < self._promo_retry_at
            ):
                return
            self._promo_state = "running"
        threading.Thread(
            target=self._promote_sharded, name="nornicdb-shard-promote",
            daemon=True,
        ).start()

    def _promote_sharded(self) -> None:
        from nornicdb_tpu.errors import DeviceUnavailable

        try:
            from nornicdb_tpu.parallel import ShardedCorpus, can_shard

            if not can_shard():
                with self._lock:
                    self._promo_state = "unavailable"
                logger.info(
                    "sharded promotion skipped: single-device backend"
                )
                return
            # carry the single-device corpus's storage dtype (f32 by
            # default) so the promotion swap never changes scoring:
            # exact-mode results must be identical before and after
            with self._lock:
                cur = self._corpus
                cur_dtype = getattr(cur, "dtype", None)
            if cur_dtype is None:
                import jax.numpy as jnp

                cur_dtype = jnp.float32
            sharded = ShardedCorpus(
                dims=self._dims, dtype=cur_dtype,
                quantized=self.config.int8_residency,
                rescore_factor=self.config.rescore_factor,
            )
        except DeviceUnavailable:
            # degraded backend: retry after a cooldown instead of pinning
            # the corpus to one chip forever
            with self._lock:
                self._promo_state = None
                self._promo_retry_at = time.monotonic() + 60.0
            logger.warning(
                "sharded promotion deferred: backend degraded"
            )
            return
        except Exception:
            with self._lock:
                self._promo_state = "unavailable"
            logger.exception("sharded promotion failed")
            return
        # bulk-load from a snapshot, then replay the (bounded) diff and
        # swap under the service lock — writers queue only for the diff.
        # Any failure here must reset _promo_state: leaving it "running"
        # would permanently block every future promotion attempt.
        try:
            with self._lock:
                snap = dict(self._vectors)
            if snap:
                sharded.add_batch(list(snap.keys()),
                                  np.stack(list(snap.values())))
            with self._lock:
                cur = self._vectors
                for id_, v in cur.items():
                    # index_node stores a NEW array object on every real
                    # change, so identity inequality == changed-since-snapshot
                    if snap.get(id_) is not v:
                        sharded.add(id_, v)
                for id_ in snap:
                    if id_ not in cur:
                        sharded.remove(id_)
                old, self._corpus = self._corpus, sharded
                self._generation += 1  # cached rankings die with the old corpus
                if self.config.write_behind:
                    sharded.start_uploader(self.config.write_behind_interval)
                sharded.shard_stats.promotions += 1
                self._promo_state = "done"
        except DeviceUnavailable:
            with self._lock:
                self._promo_state = None
                self._promo_retry_at = time.monotonic() + 60.0
            logger.warning("sharded promotion deferred: backend degraded")
            return
        except Exception:
            with self._lock:
                self._promo_state = "unavailable"
            logger.exception("sharded promotion failed")
            return
        if old is not None and hasattr(old, "stop_uploader"):
            old.stop_uploader()
        # carry the installed cluster fit across the swap: without it the
        # sharded corpus has no inverted lists and every n_probe search
        # silently full-scans until the next embed-triggered recluster —
        # on a read-heavy workload, indefinitely, exactly at the corpus
        # size where pruning matters. set_clusters runs OUTSIDE the
        # service lock (device transfers) and stashes itself if the
        # backend degraded mid-promotion.
        with self._lock:
            res = self.cluster_result
            assignments = dict(self.cluster_assignments)
        if res is not None and assignments:
            try:
                sharded.set_clusters(
                    np.asarray(res.centroids, np.float32), assignments
                )
                # re-tune against the SHARDED layout: per-shard inverted
                # lists + local_k change the recall-vs-FLOPs curve, so the
                # single-device plan does not carry over
                self.run_tune(sharded)
            except Exception:
                logger.exception(
                    "cluster fit carry-over failed after sharded promotion"
                )
        logger.info(
            "search corpus promoted to mesh-sharded serving "
            "(%d rows, %d shards)", len(sharded), sharded.n_shards,
        )

    # -- queries -----------------------------------------------------------
    def _corpus_search_kwargs(self, corpus) -> dict:
        """Per-dispatch knobs for this corpus type: exact full-sort,
        IVF pruning, per-shard local_k oversampling (sharded only).

        The pruning plan comes from the TUNER (recall-governed, measured
        against the floor) unless the operator explicitly set n_probe —
        a bypass of the eval gate kept for debugging, not a supported
        knob. A tune whose outcome isn't "ok" (floor_unmet / degraded /
        no_layout / ...) contributes nothing: the search full-scans, which
        is always recall-correct."""
        kwargs: dict = {}
        if self.config.exact:
            kwargs["exact"] = True
        clustered = hasattr(corpus, "cluster")
        if self.config.n_probe > 0 and clustered:
            kwargs["n_probe"] = self.config.n_probe
        elif clustered and not self.config.exact:
            # exact=True is the recall-1.0 contract and the corpora take
            # the pruned branch before honoring exact — the tuner must
            # never inject pruning under it
            tune = self._tune_state
            if tune is not None and tune.serving_pruned:
                # staleness is the corpus's problem, not ours: a layout
                # whose epoch moved makes _pruned_search return None and
                # the search full-scans regardless of what we pass here
                kwargs["n_probe"] = tune.n_probe
                if tune.local_k > 0 and hasattr(corpus, "n_shards"):
                    kwargs["local_k"] = tune.local_k
        if self.config.local_k > 0 and hasattr(corpus, "n_shards"):
            kwargs["local_k"] = self.config.local_k
        return kwargs

    def _batched_corpus_search(
        self, queries: np.ndarray, k: int, min_similarity: float
    ):
        """One device dispatch for the whole batch: the corpus search
        (single-device or mesh-sharded) takes the stacked (B, D) block and
        scans it at its query class.  The first batch of a ``k`` compiles
        that ``k``'s whole class grid, so no later batch size meets a new
        program; rows come back deferred, each caller resolving its own."""
        with self._lock:
            corpus = self._corpus  # promotion may swap it mid-flight
        kwargs = self._corpus_search_kwargs(corpus)
        corpus.warm_query_classes(k, self.config.batch_max, **kwargs)
        return corpus.search(
            queries, k=k, min_similarity=min_similarity, defer=True,
            **kwargs,
        )

    def corpus(self):
        """The live vector corpus (None before first indexed embedding).
        Promotion may swap it — hold the returned reference, don't re-read
        mid-operation."""
        with self._lock:
            return self._corpus

    def ensure_batcher(self):
        """The service's QueryBatcher, created on first use with the
        config's batching knobs: the one dispatcher that in-process callers
        (vector_candidates) and the device broker (server/broker.py) share,
        so cross-worker traffic coalesces with the primary's own."""
        batcher = getattr(self, "_batcher", None)
        if batcher is None:
            with self._lock:
                batcher = getattr(self, "_batcher", None)
                if batcher is None:
                    batcher = self._batcher = QueryBatcher(
                        self._batched_corpus_search,
                        max_batch=self.config.batch_max,
                        max_queue=self.config.batch_max_queue,
                        deadline=self.config.batch_deadline_ms / 1000.0,
                    )
        return batcher

    def vector_candidates(
        self, embedding: np.ndarray, k: int = 10, min_similarity: float = -1.0
    ) -> list[tuple[str, float]]:
        """(ref: VectorSearchCandidates search.go:1005)"""
        if self._promo_state is None:
            # a promotion deferred while the backend was degraded must be
            # retryable from the READ path too: on a read-only workload
            # index_node never runs again, and the corpus would stay
            # pinned to one chip after recovery. Unlocked read is a
            # benign race — _maybe_promote_sharded re-checks under _lock
            # and the cooldown gate keeps the retry cheap.
            self._maybe_promote_sharded()
        # snapshot index refs under the lock, dispatch OUTSIDE it: the
        # round-5 deadlock was exactly a device acquisition hanging while
        # this lock was held, wedging every later search/index call. The
        # corpus has its own consistency story (_borrow_device snapshots);
        # holding the service lock across the dispatch adds nothing but
        # the deadlock. Enforced by NL-DEV01 + the manager's NORNSAN guard.
        with self._lock:
            self.stats.vector_candidates += 1
            corpus, hnsw = self._corpus, self._hnsw
        if corpus is not None:
            # every query with a device corpus shares the one dispatcher:
            # alone it is dispatched at once on this thread, in company it
            # shares the next scan (the `search.vector` stage is the
            # dispatching caller's)
            return self.ensure_batcher().search(embedding, k, min_similarity)
        if hnsw is not None:
            return [
                (i, s)
                for i, s in hnsw.search(embedding, k)
                if s >= min_similarity
            ]
        return []

    def stats_snapshot(self) -> dict:
        """Search-stack observability bundle for the server stats/metrics
        surface: index/search counters, the corpus's device-sync accounting
        (patches vs full uploads, bytes, query stall), and the query
        dispatcher's counters (queries a scan, padding rows, queue wait)."""
        from dataclasses import asdict

        out: dict = asdict(self.stats)
        with self._lock:
            corpus, batcher = self._corpus, getattr(self, "_batcher", None)
            if self._promo_state is not None:
                out["sharded_promotion"] = self._promo_state
            # active recall-governed tuner state: the serving plan, its
            # measured-recall evidence, outcome counts, and how far the
            # corpus has drifted from it (docs/observability.md)
            tuner: dict = {
                "tunes": dict(self.tune_counts),
                "churn_since_tune": self._churn_since_tune,
                "drift_threshold": self.config.drift_threshold,
                "recall_target": self.config.recall_target,
                "retuning": self._retuning,
            }
            if self._tune_state is not None:
                tuner["active"] = self._tune_state.as_dict()
            out["ivf_tuner"] = tuner
        if corpus is not None:
            out["corpus"] = corpus.stats()
            mgr = getattr(corpus, "_backend", None)
            if mgr is not None:
                # lifecycle state + fallback/recovery counters for the
                # corpus's backend manager (the /admin/stats "backend"
                # section mirrors the process default; this one follows
                # an injected test manager too)
                out["backend"] = mgr.stats()
        if batcher is not None:
            out["batcher"] = batcher.stats.as_dict()
        return out

    def search(
        self,
        query: str,
        limit: int = 10,
        min_similarity: Optional[float] = None,
        query_embedding: Optional[np.ndarray] = None,
    ) -> list[dict[str, Any]]:
        """Hybrid RRF search (ref: Search :851 -> rrfHybridSearch :890)."""
        self.stats.searches += 1
        min_sim = self.config.min_similarity if min_similarity is None else min_similarity
        cache_key = None
        if query_embedding is None and query:
            cache_key = (query, limit, min_sim)
            with self._lock:
                hit = self._rank_cache.get(cache_key)
                if hit is not None:
                    gen, ts, rank = hit
                    if (
                        gen == self._generation
                        and time.monotonic() - ts < self._rank_cache_ttl
                    ):
                        self._rank_cache.move_to_end(cache_key)
                    else:
                        del self._rank_cache[cache_key]
                        hit = None
            if hit is not None:
                # enrich OUTSIDE the lock: node fetches must not serialize
                # concurrent hits or block index writers
                return self._enrich(hit[2], limit)
        # snapshot the generation BEFORE ranking: a mutation racing _rank()
        # must make this entry dead on arrival, not cached as current
        gen_before = self._generation
        rank = self._rank(query, limit, min_sim, query_embedding)
        if cache_key is not None:
            with self._lock:
                self._rank_cache[cache_key] = (
                    gen_before, time.monotonic(), rank,
                )
                self._rank_cache.move_to_end(cache_key)
                while len(self._rank_cache) > self._rank_cache_max:
                    self._rank_cache.popitem(last=False)
        return self._enrich(rank, limit)

    def _rank(
        self,
        query: str,
        limit: int,
        min_sim: float,
        query_embedding: Optional[np.ndarray],
    ) -> list[tuple[str, float, Optional[float], Optional[float]]]:
        """The expensive half of a search: embed + vector + BM25 + fusion
        (+ rerank/MMR). Returns ordered (id, score, vec_score, ft_score)."""
        with _tracer.span("search.rank"):
            return self._rank_inner(query, limit, min_sim, query_embedding)

    def _rank_inner(
        self,
        query: str,
        limit: int,
        min_sim: float,
        query_embedding: Optional[np.ndarray],
    ) -> list[tuple[str, float, Optional[float], Optional[float]]]:
        n_cand = max(limit * self.config.candidates_multiplier, limit)
        ranked: dict[str, list[str]] = {}
        vec_scores: dict[str, float] = {}
        if query_embedding is None and self.embedder is not None and query:
            with _tracer.span("search.embed"):
                query_embedding = self.embedder.embed(query)
        if query_embedding is not None:
            vec = self.vector_candidates(query_embedding, n_cand, min_sim)
            ranked["vector"] = [i for i, _ in vec]
            vec_scores = dict(vec)
        ft = self._bm25.search(query, n_cand) if query else []
        if ft:
            ranked["fulltext"] = [i for i, _ in ft]
        ft_scores = dict(ft)
        if not ranked:
            return []
        fused = fuse_rrf(ranked, adaptive_rrf_weights(query), self.config.rrf_k)
        ordered = [i for i, _ in fused]
        if self.config.rerank_enabled and query:
            ordered = self._apply_rerank(query, ordered)
        if self.config.mmr_enabled:
            rel = {i: s for i, s in fused}
            with self._lock:
                ordered = apply_mmr(
                    ordered, rel, self._vectors, limit, self.config.mmr_lambda
                )
        score_map = dict(fused)
        return [
            (id_, score_map[id_], vec_scores.get(id_), ft_scores.get(id_))
            for id_ in ordered[: max(limit, self.config.rerank_candidates)]
        ]

    def _enrich(
        self,
        rank: list[tuple[str, float, Optional[float], Optional[float]]],
        limit: int,
    ) -> list[dict[str, Any]]:
        """Fetch nodes for the ranked head (ref: enrichResults search.go:1932).
        Always reads storage, so cached rankings serve fresh node data; ids
        deleted since ranking simply drop out."""
        results = []
        for id_, score, vs, fs in rank:
            if len(results) >= limit:
                break
            try:
                node = self.storage.get_node(id_)
            except NotFoundError:
                continue
            results.append(
                {
                    "id": id_,
                    "node": node,
                    "score": score,
                    "vector_score": vs,
                    "fulltext_score": fs,
                    "content": node.properties.get("content", ""),
                    "labels": node.labels,
                }
            )
        return results

    # -- cross-encoder second stage (ref: rerank.go; search.go:1639) --------
    def set_reranker(self, reranker) -> None:
        self._reranker = reranker

    def _apply_rerank(self, query: str, ordered: list[str]) -> list[str]:
        reranker = getattr(self, "_reranker", None)
        if reranker is None:
            from nornicdb_tpu.search.rerank import CrossEncoderReranker

            reranker = self._reranker = CrossEncoderReranker()
        head = ordered[: self.config.rerank_candidates]
        candidates = []
        missing = []  # lookup failures keep their head position, not the tail
        for id_ in head:
            try:
                node = self.storage.get_node(id_)
            except NotFoundError:
                missing.append(id_)
                continue
            candidates.append((id_, build_embedding_text(node)[:1000]))
        if not candidates:
            return ordered
        reranked = [i for i, _ in reranker.rerank(query, candidates)]
        new_head = reranked + missing
        head_set = set(new_head)
        return new_head + [i for i in ordered if i not in head_set]

    # -- clustering (ref: gpu.ClusterIndex kmeans.go:144; debounced trigger
    # embed_queue.go:257) -----------------------------------------------------
    def recluster(self, k: int = 0, iters: int = 10) -> Optional[dict[str, int]]:
        """Re-fit k-means over the current vector set on TPU; stores
        id->cluster assignments for cluster-pruned candidate generation
        (DeviceCorpus IVF) and the inference engine's cluster integration."""
        with self._lock:
            ids = list(self._vectors.keys())
            if len(ids) < 2:
                return None
            mat = np.stack([self._vectors[i] for i in ids])
            # drift resets HERE, at the fit snapshot — not after the tune:
            # mutations landing while the fit/tune runs are invisible to
            # the new layout and must still count as churn against it
            # (the drift-retune loop's settle check reads this)
            self._churn_since_tune = 0
        from nornicdb_tpu.ops.kmeans import kmeans_fit

        res = kmeans_fit(mat, k=k, iters=iters,
                         sample=self.config.cluster_fit_sample)
        assignments = {id_: int(c) for id_, c in zip(ids, res.assignments)}
        with self._lock:
            self.cluster_result = res
            self.cluster_assignments = assignments
            corpus = self._corpus
        if corpus is not None and hasattr(corpus, "set_clusters"):
            # cold-gate BEFORE the install: on a never-acquired backend
            # set_clusters would stash the fit for the recovery thread and
            # the tune right after would measure a layout that isn't there
            # yet. The bounded acquisition is legal here — no lock held,
            # and recluster already runs on background threads. Degraded
            # stays degraded: the stash path below still applies.
            from nornicdb_tpu.errors import DeviceUnavailable

            try:
                corpus._device_gate()
            except DeviceUnavailable:
                pass  # fallback-policy "fail": stash + degraded tune
            # reuse the one fit: map assignments onto corpus slots (no second
            # k-means, and nothing heavy runs under the service lock)
            corpus.set_clusters(res.centroids, assignments)
            # eval-gate the fresh layout before it serves: measure recall
            # against the floor and pick (n_probe, local_k) — or record
            # that the floor is unreachable and keep full-scanning
            self.run_tune(corpus)
        return assignments

    def run_tune(self, corpus=None) -> Optional[TuneState]:
        """Measure the fitted IVF layout against the recall floor and
        install the resulting serving plan (search/tuner.py). Runs with
        no service lock held — the tuner dispatches real searches. Also
        the drift-retune entry point; callers may pass the corpus they
        already hold to dodge the promotion-swap race."""
        cfg = self.config
        if not cfg.tune_enabled:
            return None
        if corpus is None:
            with self._lock:
                corpus = self._corpus
        if corpus is None or not hasattr(corpus, "cluster"):
            return None
        if len(corpus) < cfg.tune_min_rows:
            # a corpus this small full-scans in the noise floor; recording
            # too_small (rather than silence) keeps /admin/stats honest
            # about WHY nothing is pruned
            from nornicdb_tpu.search.tuner import count_tune_outcome

            state = TuneState(outcome="too_small",
                              recall_target=cfg.recall_target,
                              corpus_rows=len(corpus))
            count_tune_outcome("too_small")
        else:
            tuner = IVFTuner(
                recall_target=cfg.recall_target,
                sample=cfg.tune_sample,
                k=cfg.tune_k,
            )
            state = tuner.tune(corpus)
        self._install_tune(state, corpus)
        return state

    def _install_tune(self, state: TuneState, corpus) -> None:
        """Install a tune verdict as the serving plan.

        Transient failures (a tune racing churn, a crashed tune, a
        degraded backend) must not evict a measured-good plan — but a
        kept plan must still describe the layout that is actually
        serving: it survives only while it was measured on THIS corpus
        and the corpus's layout epoch still matches (a post-churn or
        post-promotion layout is epoch-valid to the corpus's own guard,
        so an unmeasured old plan against it would be exactly the silent
        recall degradation the tuner exists to kill). Real verdicts (ok,
        floor_unmet, no_layout, too_small) always replace."""
        from nornicdb_tpu.search.tuner import publish_plan

        import weakref

        layout = IVFTuner._layout_of(corpus)[0] if corpus is not None \
            else None
        with self._lock:
            transient = state.outcome in ("stale", "error", "degraded")
            old = self._tune_state
            old_layout_ref = getattr(self, "_tuned_layout_ref", None)
            # the plan is pinned to the LAYOUT OBJECT it was measured on
            # (epochs alone don't discriminate: a re-fitted layout after
            # plain adds shares the old epoch, and a promoted corpus
            # starts a fresh epoch space)
            keep_old = (
                transient
                and old is not None
                and old.outcome == "ok"
                and layout is not None
                and old_layout_ref is not None
                and old_layout_ref() is layout
            )
            if not keep_old:
                self._tune_state = state
                self._tuned_layout_ref = (
                    weakref.ref(layout)
                    if state.outcome == "ok" and layout is not None
                    else None
                )
            self.tune_counts[state.outcome] = (
                self.tune_counts.get(state.outcome, 0) + 1
            )
            serving = self._tune_state
        # gauges reflect the plan the service actually SERVES (post
        # keep/replace), not whatever the last tune attempt measured
        publish_plan(serving)

    def _note_churn(self) -> None:
        """Drift tracking: every index mutation ages the tuned plan (new
        rows are invisible to the fitted layout; removals thin it). Past
        drift_threshold × corpus size, schedule a background recluster +
        re-tune so the measured recall floor is restored without an
        operator in the loop."""
        cfg = self.config
        if not cfg.tune_enabled or cfg.drift_threshold <= 0:
            return
        with self._lock:
            self._churn_since_tune += 1
            tune = self._tune_state
            corpus = self._corpus
            if (
                tune is None         # nothing tuned yet: recluster's job
                or self._retuning
                or corpus is None
            ):
                return
            # a too_small verdict does NOT pin full scan forever: once
            # the corpus grows past tune_min_rows, churn since that
            # verdict schedules the first real tune like any other drift
            n = len(corpus)
            if n < cfg.tune_min_rows:
                return
            if self._churn_since_tune < max(32, int(cfg.drift_threshold * n)):
                return
            self._retuning = True
        threading.Thread(
            target=self._drift_retune, name="nornicdb-ivf-retune",
            daemon=True,
        ).start()

    def _drift_retune(self) -> None:
        """Background drift response: refit k-means over the current
        vector set (recluster installs the layout and re-runs the tuner).
        Loops while the write burst is still landing — a layout fitted
        mid-burst is stale the moment it installs (measured: a re-tune
        racing the tail of a churn burst reports floor_unmet because the
        tune sampled rows the fit never saw) — and stops once churn
        settles. Failures leave the old plan serving; the corpus's
        layout-epoch guard already full-scans anything stale."""
        try:
            for _ in range(3):
                self.recluster()
                with self._lock:
                    churn = self._churn_since_tune
                    corpus = self._corpus
                # settle threshold scales WITH the trigger (a tenth of
                # it), not an absolute count: a steady write trickle on a
                # 10M corpus lands far more than 32 rows during one
                # recluster, and re-fitting three times over 0.001% drift
                # is pure background burn
                n = len(corpus) if corpus is not None else 0
                trigger = max(32, int(self.config.drift_threshold * n))
                if churn < max(32, trigger // 10):
                    break
        except Exception:
            logger.exception("drift-triggered IVF re-tune failed")
        finally:
            with self._lock:
                self._retuning = False

    # -- wiring ------------------------------------------------------------
    def attach(self, engine: Engine) -> None:
        """Subscribe to storage events (ref: db.go:1020-1033)."""

        def _on(kind: str, entity) -> None:
            if not isinstance(entity, Node):
                return
            if kind in ("node_created", "node_updated"):
                self.index_node(entity)
            elif kind == "node_deleted":
                self.remove_node(entity.id)

        self._event_cb = _on
        engine.on_event(_on)

    def detach(self, engine: Engine) -> None:
        """Unsubscribe (a service that lost the DB's creation race must
        not keep shadow-indexing every storage event forever)."""
        cb = getattr(self, "_event_cb", None)
        if cb is not None:
            engine.off_event(cb)
            self._event_cb = None

    def shutdown(self) -> None:
        """Stop background resources: the corpus's write-behind uploader
        thread (a discarded service that keeps one alive also keeps its
        corpus referenced, so the backend manager's weakref registry
        would re-upload the zombie corpus on every recovery)."""
        with self._lock:
            corpus = self._corpus
        if corpus is not None and hasattr(corpus, "stop_uploader"):
            corpus.stop_uploader()
