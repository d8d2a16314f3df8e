"""Sharded vector index: the corpus rows shard across a TPU mesh; each chip
computes a local top-k; partial results merge over ICI all-gather.

This realises the reference's *planned* sharded vector index
(/root/reference/docs/architecture/clustering-roadmap.md "Sharded ...
Planned") as the framework's primary ANN path — at TPU-pod scale, sharded
brute-force scoring beats HNSW for corpora ≤ tens of millions (SURVEY.md §7
step 4). Scores are always exact; candidate membership defaults to
approx_max_k / the streaming Pallas bin-reduce kernel (recall_target 0.95
per shard, the TPU-native top-k) with an exact=True full-sort opt-in for
recall 1.0.

Data plane: XLA collectives over ICI inside one jit'd program (shard_map).
No host-side shard coordinator exists — the "merge" is an all_gather + top_k
epilogue compiled into the same program as the scoring GEMM, so one search
(of any batch size) is ONE device dispatch.

local_k sizing contract
-----------------------
Each shard contributes ``local_k = clamp(max(k, requested_local_k),
1, local_n)`` candidates to the merge.  In exact mode this is provably
lossless for any live-row distribution: a shard can contribute at most k
rows to the global top-k, and a shard with fewer than local_k live rows
returns ALL of them (the remainder are -inf sentinels whose indices are
masked to -1 before the merge, so padding can never surface as a
candidate — see ops.similarity.merge_topk).  In approx mode local_k is a
recall knob: per-shard bin-reduce membership is ~0.95 at local_k == k, and
oversampling (SearchConfig.local_k > k) buys recall back at the cost of a
wider all-gather.  The shard_local_k_overflows metric counts merges where
one shard's list saturated — the signal to raise it.

IVF under sharding: centroids are replicated (every shard probes the same
n_probe clusters in-program), inverted lists are per-shard
(ops.ivf.build_sharded_ivf_layout), and the layout serves only while its
build-time epoch matches the corpus layout epoch (PR 2's invalidation
contract — covered-row overwrites and slot remaps kill it, plain
adds/removes don't).

int8 compressed residency (``quantized=True``): device HBM holds int8
codes + per-row scales instead of f32 rows (≈4x the rows per HBM byte;
the IVF block array quantizes too), candidate selection oversamples
``rescore_factor × k`` per query, and the merged candidate set is
exact-rescored in f32 from the host mirror — served (id, score) pairs
bit-match the deterministic f32 rescore (ops.host_search.rescore_rows).
The f32 truth never leaves the host; WindVE's CPU↔accelerator split as a
storage policy (PAPERS.md). docs/operations.md "Recall tuning" has the
memory math.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from collections.abc import Sequence
from dataclasses import asdict, dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nornicdb_tpu.errors import DeviceUnavailable
from nornicdb_tpu.ops.host_search import quantize_rows_np, rescore_rows
from nornicdb_tpu.telemetry import deviceprof as _deviceprof
from nornicdb_tpu.ops.ivf import _next_pow2
from nornicdb_tpu.ops.similarity import (
    _SHARD_LOCALK_OVERFLOWS,
    _SHARD_REBALANCES,
    _SHARD_ROWS_GAUGE,
    _SHARDED_MERGE_HIST,
    _SHARDED_SEARCH_HIST,
    HostCorpus,
    _patch_rows,
    _patch_rows_donated,
    _patch_valid,
    _patch_valid_donated,
    cosine_topk,
    dot_scores,
    l2_normalize,
    merge_topk,
    pad_query_block,
    query_class,
    topk_backend,
    topk_backend_int8,
)
from nornicdb_tpu.parallel.mesh import make_mesh

logger = logging.getLogger(__name__)

# Collective programs launched from two host threads can interleave their
# per-device enqueue order and deadlock at the all_gather rendezvous
# (reproduced live on the 8-device CPU mesh: a recall() on the main thread
# racing the embed worker's dispatch left every device waiting for a
# participant enqueued behind the OTHER program). The same out-of-order
# enqueue hazard exists on a real mesh, so every sharded serving dispatch
# in the process serializes through this leaf lock. It guards only WARM,
# already-gated dispatches (never backend acquisition — NL-DEV01-safe) and
# nothing else is ever acquired while holding it; result materialization
# happens inside so the program has fully retired before the next launch.
_COLLECTIVE_DISPATCH_LOCK = threading.Lock()


@functools.partial(
    jax.jit,
    static_argnames=("k", "local_k", "axis", "mesh_static", "use_bf16",
                     "exact", "streaming"),
)
def _sharded_search(
    queries: jax.Array,
    corpus: jax.Array,
    valid: jax.Array,
    k: int,
    local_k: int,
    axis: str,
    mesh_static: Mesh,
    use_bf16: bool = True,
    exact: bool = False,
    streaming=None,
):
    """One XLA program: per-shard GEMM + top-local_k, ICI all-gather of
    (vals, global_idx) only, global merge.  Per-shard scoring dispatches
    through topk_backend, so on TPU at scale each chip runs the streaming
    Pallas bin-reduce kernel over its corpus shard (TPU-KNN shape); the
    exact=True fallback full-sorts per shard instead."""

    def shard_fn(q, c, v):
        local_n = c.shape[0]
        n_shards = mesh_static.shape[axis]
        lk = max(1, min(local_k, local_n))
        vals, idx = topk_backend(
            q, c, v, lk, exact=exact, use_bf16=use_bf16,
            streaming=streaming,
        )
        shard = jax.lax.axis_index(axis)
        gidx = idx + shard * local_n
        # sentinel at the source: a near-empty shard pads its list with
        # -inf entries whose per-shard indices are arbitrary — mask them
        # to -1 BEFORE they cross the interconnect, so no consumer can
        # resolve a padding slot into an id
        gidx = jnp.where(jnp.isfinite(vals), gidx, -1)
        # (S, Q, local_k) partials on every chip, then merged identically
        vals_all = jax.lax.all_gather(vals, axis)
        idx_all = jax.lax.all_gather(gidx, axis)
        return merge_topk(vals_all, idx_all, min(k, lk * n_shards))

    return jax.shard_map(
        shard_fn,
        mesh=mesh_static,
        in_specs=(P(), P(axis, None), P(axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )(queries, corpus, valid)


@functools.partial(
    jax.jit,
    static_argnames=("k", "local_k", "axis", "mesh_static", "streaming"),
)
def _sharded_search_int8(
    queries: jax.Array,   # (B, D) f32 L2-normalized, replicated
    codes: jax.Array,     # (N, D) int8 corpus codes, sharded on N
    scales: jax.Array,    # (N,) f32 quantize_rows scales, sharded
    valid: jax.Array,     # (N,) bool, sharded
    k: int,
    local_k: int,
    axis: str,
    mesh_static: Mesh,
    streaming=None,
):
    """Compressed-residency sharded search: each shard scores its int8
    code slice (streaming int8 Pallas kernel on TPU, dequant-GEMM XLA
    fallback elsewhere) — no f32/bf16 corpus copy exists on device. Same
    all-gather merge and (vals, global_idx) wire format as the dense
    program; candidate scores carry int8 noise and the caller rescores
    the merged set exactly from the host f32 mirror."""

    def shard_fn(q, c8, sc, v):
        local_n = c8.shape[0]
        n_shards = mesh_static.shape[axis]
        lk = max(1, min(local_k, local_n))
        vals, idx = topk_backend_int8(q, c8, sc, v, lk, streaming=streaming)
        shard = jax.lax.axis_index(axis)
        gidx = idx + shard * local_n
        gidx = jnp.where(jnp.isfinite(vals), gidx, -1)
        vals_all = jax.lax.all_gather(vals, axis)
        idx_all = jax.lax.all_gather(gidx, axis)
        return merge_topk(vals_all, idx_all, min(k, lk * n_shards))

    return jax.shard_map(
        shard_fn,
        mesh=mesh_static,
        in_specs=(P(), P(axis, None), P(axis), P(axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )(queries, codes, scales, valid)


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probe", "axis", "mesh_static", "has_residual",
                     "quantized"),
)
def _sharded_ivf_topk(
    queries: jax.Array,        # (B, D) L2-normalized, replicated
    centroids: jax.Array,      # (K, D) replicated
    blocks: jax.Array,         # (S, K, Cmax, D) sharded on S (int8 when
                               # quantized)
    counts: jax.Array,         # (S, K) sharded
    slotmap: jax.Array,        # (S, K, Cmax) GLOBAL slots, sharded
    residual: jax.Array,       # (S, Rmax, D) sharded (dummy when absent)
    residual_slots: jax.Array,  # (S, Rmax) sharded (dummy when absent)
    block_scales: jax.Array,   # (S, K, Cmax) f32 dequant multipliers
                               # (dummy unless quantized)
    residual_scales: jax.Array,  # (S, Rmax) f32 (dummy unless quantized)
    k: int,
    n_probe: int,
    axis: str,
    mesh_static: Mesh,
    has_residual: bool,
    quantized: bool,
):
    """Fused sharded IVF: replicated centroid probe → per-shard block
    gather + bf16 scoring → per-shard residual scan → local top-k over
    GLOBAL slots → all_gather merge.  One device dispatch per batch, same
    wire format ((vals, global_slot) pairs) as the dense sharded path.

    ``quantized=True``: the blocks hold int8 codes (exactly representable
    in bf16, so the same einsum runs) and the per-row dequant multiplier
    rides the f32 epilogue — dead/pad rows carry multiplier 0 and are
    masked by the live-count test anyway."""

    def shard_fn(q, cent, blk, cnt, smap, res, rslots, bsc, rsc):
        blk, cnt, smap = blk[0], cnt[0], smap[0]
        cmax = blk.shape[1]
        cscores = dot_scores(q, cent)                 # (B, K), replicated
        _, probes = jax.lax.top_k(cscores, n_probe)    # (B, P) same on all
        gathered = blk[probes]                         # (B, P, Cmax, D)
        scores = jnp.einsum(
            "bd,bpcd->bpc",
            q.astype(jnp.bfloat16),
            gathered.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        if quantized:
            scores = scores * bsc[0][probes]           # (B, P, Cmax)
        live = jnp.arange(cmax)[None, None, :] < cnt[probes][:, :, None]
        scores = jnp.where(live, scores, -jnp.inf)
        cand = smap[probes]                            # (B, P, Cmax)
        b = scores.shape[0]
        flat_v = scores.reshape(b, -1)
        flat_s = cand.reshape(b, -1)
        if has_residual:
            r, rs = res[0], rslots[0]
            rscores = dot_scores(q, r)
            if quantized:
                rscores = rscores * rsc[0][None, :]
            rscores = jnp.where((rs >= 0)[None, :], rscores, -jnp.inf)
            flat_v = jnp.concatenate([flat_v, rscores], axis=1)
            flat_s = jnp.concatenate(
                [flat_s, jnp.broadcast_to(rs[None, :], rscores.shape)],
                axis=1,
            )
        kk = min(k, flat_v.shape[1])
        vals, pos = jax.lax.top_k(flat_v, kk)
        slots_top = jnp.take_along_axis(flat_s, pos, axis=1)
        vals_all = jax.lax.all_gather(vals, axis)
        slots_all = jax.lax.all_gather(slots_top, axis)
        n_shards = mesh_static.shape[axis]
        return merge_topk(vals_all, slots_all, min(k, kk * n_shards))

    rspec = P(axis) if has_residual else P()
    bspec = P(axis) if quantized else P()
    rsspec = P(axis) if (quantized and has_residual) else P()
    return jax.shard_map(
        shard_fn,
        mesh=mesh_static,
        in_specs=(P(), P(), P(axis), P(axis), P(axis), rspec, rspec,
                  bspec, rsspec),
        out_specs=(P(), P()),
        check_vma=False,
    )(queries, centroids, blocks, counts, slotmap, residual, residual_slots,
      block_scales, residual_scales)


@dataclass
class ShardStats:
    """Mesh-serving accounting for one ShardedCorpus (stats()["shard"],
    /admin/stats, and the nornicdb_shard_* metric families)."""

    dispatches: int = 0          # fused dense dispatches (1 per batch)
    ivf_dispatches: int = 0      # fused IVF dispatches (1 per batch)
    rebalances: int = 0          # grow/compact/recovery full re-shards
    local_k_overflows: int = 0   # approx merges saturated by one shard
    promotions: int = 0          # auto single-device -> sharded swaps
    rescored_queries: int = 0    # int8-residency queries exact-rescored
    last_dispatch_s: float = 0.0
    last_merge_s: float = 0.0
    last_rescore_s: float = 0.0
    rows_per_shard: list = field(default_factory=list)

    def as_dict(self) -> dict:
        return asdict(self)


class ShardedCorpus(HostCorpus):
    """Mesh-sharded, device-resident embedding corpus.

    Host keeps the (ids, vectors) truth (HostCorpus); the device copy is a
    padded (Np, D) matrix laid out P("data", None) across the mesh, with
    rows aligned to 128 * n_shards so every shard stays lane-aligned.

    Mirrors gpu.EmbeddingIndex semantics (Add/Remove/Search, dirty-tracking
    resync — /root/reference/pkg/gpu/gpu.go:1224-1619) but the buffer spans
    every chip on the mesh instead of one GPU.  Grow/compact remap the
    shard boundaries (every shard's slice changes), which the sync driver
    serves as one full re-shard upload — counted as a rebalance; steady-
    state writes keep PR 2's incremental per-shard patching.
    """

    def __init__(
        self,
        dims: int,
        mesh: Optional[Mesh] = None,
        axis: str = "data",
        dtype=jnp.bfloat16,
        compact_ratio: float = 0.3,
        backend=None,
        quantized: bool = False,
        rescore_factor: int = 4,
    ):
        # int8 compressed residency (WindVE's CPU↔accelerator split as a
        # storage policy): with quantized=True only int8 codes + per-row
        # scales live on device (≈4x the rows per HBM byte; the f32 truth
        # stays in the host mirror), candidate selection oversamples
        # rescore_factor × k on device, and the merged candidate set is
        # re-scored exactly in f32 from the host mirror — served scores
        # bit-match the f32 exact path for the same ids.
        self.quantized = bool(quantized)
        self.rescore_factor = max(1, int(rescore_factor))
        # building a mesh enumerates devices — a COLD backend acquisition.
        # make_mesh gates through the BackendManager (bounded wait on its
        # worker thread) and raises DeviceUnavailable when degraded; the
        # search service catches that and falls back to a single-device
        # corpus, which itself serves from host arrays until recovery.
        self.mesh = mesh if mesh is not None else make_mesh(backend=backend)
        self.axis = axis
        self.dtype = dtype
        self.n_shards = self.mesh.shape[axis]
        super().__init__(
            dims,
            # 128 * n_shards (not lcm): every PER-SHARD slice must itself be
            # a lane multiple, or the per-shard streaming kernel's tile
            # cannot divide the local row count
            align=128 * self.n_shards,
            compact_ratio=compact_ratio,
            backend=backend,
        )
        self._dev = None
        self._dev_valid = None
        self._dev_i8: Optional[tuple[jax.Array, jax.Array]] = None
        self._sharding = NamedSharding(self.mesh, P(self.axis, None))
        self._vsharding = NamedSharding(self.mesh, P(self.axis))
        self._repsharding = NamedSharding(self.mesh, P())
        self.shard_stats = ShardStats()
        # sharded IVF layout (ops.ivf.ShardedIVFLayout) + the recovery
        # contract fields HostCorpus._on_backend_recovered drives
        self._sivf = None
        self._pending_clusters: Optional[tuple] = None
        self._last_fit_host: Optional[tuple] = None
        # fleet telemetry: mesh-resident byte accounting per component
        # (summed with any other live corpora at /metrics render)
        _deviceprof.register_hbm(self, ShardedCorpus._hbm_bytes)

    @staticmethod
    def _hbm_bytes(self) -> dict:
        """Lock-free HBM accounting (scrape thread): f32 buffers, int8
        codes+scales, and the sharded IVF layout's device arrays."""
        out = {"corpus_f32": 0, "corpus_int8": 0, "ivf": 0}
        dev, valid, i8, sivf = (self._dev, self._dev_valid, self._dev_i8,
                                self._sivf)
        for arr in (dev, valid):
            if arr is not None:
                out["corpus_f32"] += int(arr.size) * arr.dtype.itemsize
        if i8 is not None:
            for arr in i8:
                out["corpus_int8"] += int(arr.size) * arr.dtype.itemsize
        if sivf is not None:
            for name in ("blocks", "counts", "slotmap", "centroids",
                         "residual", "residual_slots", "block_scales",
                         "residual_scales"):
                arr = getattr(sivf, name, None)
                if arr is not None and not isinstance(arr, np.ndarray):
                    out["ivf"] += int(arr.size) * arr.dtype.itemsize
        return out

    @property
    def local_n(self) -> int:
        """Rows resident per shard (capacity / n_shards; lane-aligned)."""
        return self.capacity // self.n_shards

    # -- device sync -------------------------------------------------------
    # The generic HostCorpus._sync driver (dirty-block coalescing, deferred
    # compaction, patch-vs-full policy, stats) drives these two hooks.
    def _device_ready(self) -> bool:
        if self.quantized:
            i8 = self._dev_i8
            return i8 is not None and int(i8[0].shape[0]) == self.capacity
        return super()._device_ready()

    def _upload_full(self) -> None:
        # NL-DEV01 suppressions: warm transfers under _sync_lock by design
        # (gated upstream by _sync's _device_ok_nowait; the mesh was
        # enumerated through the manager at construction) — same rationale
        # as DeviceCorpus._upload_full
        if self.quantized:
            # compressed residency: quantize on the HOST so the f32 corpus
            # never materializes in device memory — the transfer and the
            # resident footprint are both N*D bytes + 4N scales, 4x less
            # than the f32 layout this mode exists to avoid
            codes, scales = quantize_rows_np(self._host)
            self._dev_i8 = (
                jax.device_put(  # nornlint: disable=NL-DEV01
                    jnp.asarray(codes),  # nornlint: disable=NL-DEV01
                    self._sharding,
                ),
                jax.device_put(  # nornlint: disable=NL-DEV01
                    jnp.asarray(scales),  # nornlint: disable=NL-DEV01
                    self._vsharding,
                ),
            )
            self._dev = None
            self._dev_valid = jax.device_put(  # nornlint: disable=NL-DEV01
                jnp.asarray(self._valid),  # nornlint: disable=NL-DEV01
                self._vsharding,
            )
            self._update_shard_rows()
            return
        self._dev = jax.device_put(  # nornlint: disable=NL-DEV01
            jnp.asarray(self._host, dtype=self.dtype),  # nornlint: disable=NL-DEV01
            self._sharding,
        )
        self._dev_valid = jax.device_put(  # nornlint: disable=NL-DEV01
            jnp.asarray(self._valid),  # nornlint: disable=NL-DEV01
            self._vsharding,
        )
        self._update_shard_rows()

    def _apply_patch(
        self, start_row: int, rows: np.ndarray, valid_rows: np.ndarray,
        donate: bool,
    ) -> None:
        """Patch one dirty run into the mesh-sharded buffer. XLA partitions
        the dynamic_update_slice, so a run touches only the shards it
        overlaps; device_put re-pins the P(axis, None) layout (a no-op when
        GSPMD already kept it, which it does for update-slice)."""
        # NL-DEV01 suppressions: warm patches under _sync_lock by design
        # (same rationale as _upload_full above).
        # Dispatch lock: GSPMD lowers a dynamic_update_slice whose start
        # falls on the PARTITIONED dim to an all_gather + update + reslice,
        # so the patch is itself a collective program — it must not race a
        # search dispatch (observed pool-starvation deadlock on the CPU
        # mesh: the patch's rendezvous and a search's rendezvous each held
        # half the device threads). Order is always _sync_lock -> dispatch
        # lock; the dispatch lock is a leaf.
        start = np.int32(start_row)
        with _COLLECTIVE_DISPATCH_LOCK:
            try:
                patch = _patch_rows_donated if donate else _patch_rows
                vpatch = _patch_valid_donated if donate else _patch_valid
                if self.quantized:
                    # requantize ONLY the patched rows on the host
                    # (per-row symmetric quantization is block-local by
                    # construction — the _requantize_rows contract of the
                    # single-device int8 mirror) and patch codes + scales
                    # in place
                    codes, scales = quantize_rows_np(rows)
                    self._dev_i8 = (
                        jax.device_put(  # nornlint: disable=NL-DEV01
                            patch(self._dev_i8[0],
                                  jnp.asarray(codes),  # nornlint: disable=NL-DEV01
                                  start),
                            self._sharding,
                        ),
                        jax.device_put(  # nornlint: disable=NL-DEV01
                            vpatch(self._dev_i8[1],
                                   jnp.asarray(scales),  # nornlint: disable=NL-DEV01
                                   start),
                            self._vsharding,
                        ),
                    )
                else:
                    self._dev = jax.device_put(  # nornlint: disable=NL-DEV01
                        patch(self._dev,
                              jnp.asarray(rows, dtype=self.dtype),  # nornlint: disable=NL-DEV01
                              start),
                        self._sharding,
                    )
                self._dev_valid = jax.device_put(  # nornlint: disable=NL-DEV01
                    vpatch(self._dev_valid,
                           jnp.asarray(valid_rows),  # nornlint: disable=NL-DEV01
                           start),
                    self._vsharding,
                )
            except Exception:
                # a failing donated patch has CONSUMED an unknown subset
                # of the sharded buffers — drop them all so
                # _device_ready() reports false and the next _sync
                # rebuilds via _upload_full (NL-JAX04)
                self._dev = None
                self._dev_valid = None
                self._dev_i8 = None
                raise
            # retire EVERY patch before releasing: the valid-mask patch is
            # its own collective program enqueued after the row patch — an
            # async collective still enqueueing while a search launches
            # reintroduces the race
            if self.quantized:
                self._dev_i8[0].block_until_ready()  # nornlint: disable=NL-LK02
                self._dev_i8[1].block_until_ready()  # nornlint: disable=NL-LK02
            else:
                self._dev.block_until_ready()  # nornlint: disable=NL-LK02
            self._dev_valid.block_until_ready()  # nornlint: disable=NL-LK02

    # -- shard lifecycle ---------------------------------------------------
    def _note_rebalance(self, reason: str) -> None:
        self.shard_stats.rebalances += 1
        _SHARD_REBALANCES.inc()
        logger.info("sharded corpus rebalance (%s): capacity=%d shards=%d",
                    reason, self.capacity, self.n_shards)

    def _grow(self, min_capacity: int = 0) -> None:
        # capacity change moves every shard boundary: the next sync is a
        # full re-shard upload (re-pinned NamedSharding), and any fitted
        # per-shard inverted lists describe the old boundaries
        super()._grow(min_capacity)
        self.clear_clusters()
        self._note_rebalance("grow")

    def _compact(self) -> None:
        # compaction remaps slots across shard boundaries (live rows pack
        # to the front): full re-shard, stale layouts dropped
        super()._compact()
        self.clear_clusters()
        self._note_rebalance("compact")

    def _on_backend_recovered(self, mode: str) -> None:
        """Recovery re-upload goes through the same per-shard path: "full"
        drops the mesh-resident buffers and the next sync re-shards the
        whole corpus (counted as a rebalance); "dirty" trusts surviving
        shard buffers and patches only degraded-era blocks."""
        had_dev = self._dev is not None
        super()._on_backend_recovered(mode)
        if mode != "dirty" and had_dev:
            self._note_rebalance("recovery")

    def _on_backend_ready(self) -> None:
        """Post-recovery: wake the uploader (base) and re-install any
        cluster fit stashed while degraded — on a throwaway thread, never
        the manager's probe thread (same rationale as DeviceCorpus)."""
        super()._on_backend_ready()
        with self._sync_lock:
            pending, self._pending_clusters = self._pending_clusters, None
            if pending is None and self._sivf is None:
                # a degraded-era rebalance (grow/compact) ran
                # clear_clusters(), dropping the stash with the layout;
                # the id-based host copy survives slot remaps — reinstall
                # it rather than serving full sharded scans until the next
                # periodic recluster (the set_clusters stash contract)
                pending = self._last_fit_host
        if pending is None:
            return

        def _install() -> None:
            try:
                self.set_clusters(pending[0], pending[1])
            except Exception:
                logger.exception(
                    "post-recovery sharded cluster install failed"
                )

        threading.Thread(
            target=_install, name="nornicdb-shard-cluster-reinstall",
            daemon=True,
        ).start()

    def _update_shard_rows(self) -> list[int]:
        """Per-shard live-row counts -> stats + the shard gauge. Called
        under _sync_lock (full upload) and lock-free from stats(): the
        mask scan is O(capacity), and a /metrics scrape must not stall
        searches/writes queued on _sync_lock for it. The single ref read
        is atomic and in-place bit flips only skew counts by in-flight
        writes — stats-grade accuracy."""
        valid = self._valid
        per = valid.reshape(self.n_shards, -1).sum(axis=1)
        rows = [int(x) for x in per]
        self.shard_stats.rows_per_shard = rows
        for s, n in enumerate(rows):
            _SHARD_ROWS_GAUGE.labels(str(s)).set(float(n))
        return rows

    def _device_bytes(self) -> int:
        """Resident device bytes across the mesh (corpus + IVF layout):
        the number the int8 residency math in docs/operations.md is
        checked against."""
        n = 0
        for arr in (self._dev, self._dev_valid):
            if arr is not None:
                n += int(arr.size) * arr.dtype.itemsize
        if self._dev_i8 is not None:
            for arr in self._dev_i8:
                n += int(arr.size) * arr.dtype.itemsize
        sivf = self._sivf
        if sivf is not None:
            for arr in (sivf.blocks, sivf.counts, sivf.slotmap,
                        sivf.centroids, sivf.residual, sivf.residual_slots,
                        sivf.block_scales, sivf.residual_scales):
                if arr is not None:
                    n += int(arr.size) * arr.dtype.itemsize
        return n

    def stats(self) -> dict:
        out = super().stats()
        rows = self._update_shard_rows()
        shard = self.shard_stats.as_dict()
        shard.update(
            n_shards=self.n_shards,
            local_n=self.local_n,
            rows_per_shard=rows,
            ivf_fitted=self._sivf is not None,
            quantized=self.quantized,
            rescore_factor=self.rescore_factor,
            device_bytes=self._device_bytes(),
        )
        out["shard"] = shard
        return out

    # -- IVF under sharding ------------------------------------------------
    def clear_clusters(self) -> None:
        self._sivf = None
        self._layout_slots = None
        self._pending_clusters = None

    def cluster(self, k: int = 0, iters: int = 10, seed: int = 0,
                sample: int = 0) -> int:
        """Fit k-means over live rows and install the per-shard inverted
        lists.  Same optimistic-install dance as DeviceCorpus.cluster: the
        fit and the layout build (device transfers included) run OUTSIDE
        _sync_lock; a layout-epoch change during either voids the
        install.  ``sample`` caps the Lloyd fit (ops.kmeans.kmeans_fit)
        for 10M-row-class corpora."""
        from nornicdb_tpu.ops.kmeans import kmeans_fit

        if not self._device_gate():
            return 0  # degraded: pruning is a device-path optimization
        with self._sync_lock:
            live = [i for i, id_ in enumerate(self._ids) if id_ is not None]
            if len(live) < 2:
                return 0
            data = self._host[live]  # fancy indexing copies: snapshot
            epoch_at_read = self._layout_epoch
            mask = np.zeros(self.capacity, bool)
            mask[live] = True
            if (
                self._layout_slots is not None
                and self._layout_slots.size == self.capacity
            ):
                mask |= self._layout_slots
            self._layout_slots = mask
        res = kmeans_fit(data, k=k, iters=iters, seed=seed, sample=sample)
        with self._sync_lock:
            if self._layout_epoch != epoch_at_read:
                return 0  # slot space moved mid-fit: caller may recluster
            # id-based host copy: full-mode recovery re-installs from this
            self._last_fit_host = (
                np.asarray(res.centroids, np.float32),
                {
                    self._ids[slot]: int(res.assignments[row])
                    for row, slot in enumerate(live)
                    if slot < len(self._ids) and self._ids[slot] is not None
                },
            )
        self._install_sharded_layout(
            np.asarray(live), res.assignments,
            np.asarray(res.centroids, np.float32),
            expect_epoch=epoch_at_read,
        )
        return res.k

    def set_clusters(
        self, centroids: np.ndarray, assignments_by_id: dict[str, int]
    ) -> None:
        """Install externally computed clusters (the search service's fit)
        as per-shard inverted lists.  Degraded backends stash the fit and
        install it on recovery (_on_backend_ready) — full scan keeps
        serving meanwhile."""
        if not self._device_ok_nowait():
            with self._sync_lock:
                self._pending_clusters = (
                    np.asarray(centroids, np.float32),
                    dict(assignments_by_id),
                )
                self._last_fit_host = self._pending_clusters
            return
        fit_host = (np.asarray(centroids, np.float32),
                    dict(assignments_by_id))
        with self._sync_lock:
            self._last_fit_host = fit_host
            slot_assignments = np.full(self.capacity, -1, np.int32)
            for id_, c in assignments_by_id.items():
                slot = self._slot_of.get(id_)
                if slot is not None:
                    slot_assignments[slot] = c
            # the old layout describes the replaced clustering — drop it
            # even when no live rows match; a stashed degraded-era fit is
            # superseded too
            self._sivf = None
            self._layout_slots = None
            self._pending_clusters = None
            live = np.nonzero((slot_assignments >= 0) & self._valid)[0]
            epoch_at_read = self._layout_epoch
        if live.size:
            self._install_sharded_layout(
                live, slot_assignments[live],
                np.asarray(centroids, np.float32),
                expect_epoch=epoch_at_read,
            )

    def _install_sharded_layout(
        self,
        live_slots: np.ndarray,
        live_assignments: np.ndarray,
        centroids: np.ndarray,
        expect_epoch: Optional[int] = None,
    ) -> None:
        """Build + optimistically install the per-shard IVF layout.  The
        build (H2D transfers included) runs OUTSIDE the lock (NL-DEV01);
        the snapshot pins the layout epoch and the install is skipped if
        the epoch moved (the widened _layout_slots mask makes covered-row
        overwrites bump it, same contract as DeviceCorpus)."""
        from nornicdb_tpu.ops.ivf import build_sharded_ivf_layout

        with self._sync_lock:
            if expect_epoch is not None and self._layout_epoch != expect_epoch:
                return
            epoch_at_read = self._layout_epoch
            rows = self._host[live_slots]  # fancy indexing copies: snapshot
            mask = np.zeros(self.capacity, bool)
            mask[live_slots] = True
            self._layout_slots = mask
        layout = build_sharded_ivf_layout(
            rows, live_slots.astype(np.int32),
            np.asarray(live_assignments, np.int32), centroids,
            n_shards=self.n_shards, local_n=self.local_n,
            shard_sharding=self._vsharding,
            replicated_sharding=self._repsharding,
            dtype=self.dtype, epoch=epoch_at_read,
            quantize=self.quantized,
        )
        with self._sync_lock:
            if self._layout_epoch != epoch_at_read:
                return  # mutated mid-build: discard the stale layout
            self._sivf = layout

    def _rescore_host(
        self, q: np.ndarray, slots: np.ndarray, host: np.ndarray, k: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Exact f32 re-score of device-selected candidates from the host
        mirror: the epilogue that makes int8 residency serve EXACT scores.
        ``host`` must be the array captured with the buffers the slots came
        from (a racing compaction REBINDS self._host; the captured array
        keeps the slot space the device scored). The gather runs under
        _sync_lock because in-place overwrites mutate rows without
        rebinding — same torn-read rule as _search_host.

        Returns (vals (B, k), slots (B, k)) with -inf/-1 padding; ties
        break by ascending slot, the host_topk/lax.top_k rule. Scores come
        from ops.host_search.rescore_rows — the deterministic f32 kernel
        score_subset's host twin uses — so the same (id, query) pair
        rescored anywhere yields the same bits."""
        norms = np.linalg.norm(q, axis=1, keepdims=True)
        qn = (q / np.maximum(norms, 1e-12)).astype(np.float32)
        b = q.shape[0]
        out_v = np.full((b, k), -np.inf, np.float32)
        out_s = np.full((b, k), -1, np.int64)
        t0 = time.perf_counter()
        # only the GATHER needs the lock (fancy indexing copies, so the
        # torn-read hazard is the in-place overwrite during the copy);
        # scoring + sorting run on the copies with no lock, so a batch's
        # rescore epilogue never serializes writers or other searches
        with self._sync_lock:
            gathered = []
            for qi in range(b):
                sel = slots[qi][slots[qi] >= 0]
                gathered.append((sel, host[sel] if sel.size else None))
        for qi, (sel, rows_sel) in enumerate(gathered):
            if rows_sel is None:
                continue
            scores = rescore_rows(rows_sel, qn[qi])
            order = np.lexsort((sel, -scores))[:k]
            out_v[qi, :order.size] = scores[order]
            out_s[qi, :order.size] = sel[order]
        self.shard_stats.rescored_queries += b
        self.shard_stats.last_rescore_s = time.perf_counter() - t0
        return out_v, out_s

    def _pruned_search(
        self, q: np.ndarray, k: int, min_similarity: float, n_probe: int,
        local_k: int = 0,
    ) -> Optional[list[list[tuple[str, float]]]]:
        """Fused sharded IVF path; None when no valid layout is installed
        (caller falls back to the full sharded scan — recall unaffected).
        ``local_k`` oversamples each shard's pre-merge contribution (the
        per-shard top-k over its probed blocks + residual) past k — the
        same recall knob it is on the dense path, here recovering true
        neighbors a shard-local truncation at k would cut. With a
        quantized layout the device program additionally oversamples
        rescore_factor × k and the merged set is exact-rescored from the
        host mirror before formatting."""
        with self._sync_lock:
            # a pending compaction would remap slots out from under the
            # layout's epoch check — run the sync first, like the dense path
            self._sync()
            ids = self._ids
            host = self._host
            layout = self._sivf
            layout_ok = (
                layout is not None and layout.epoch == self._layout_epoch
            )
        if not layout_ok:
            return None
        b = q.shape[0]
        q2 = pad_query_block(q)
        quantized = layout.quantized
        k_dev = k * self.rescore_factor if quantized else k
        k_prog = _next_pow2(max(k_dev, local_k, 8))
        qdtype = jnp.float32 if quantized else self.dtype
        qn = l2_normalize(jnp.asarray(q2, dtype=qdtype))
        n_probe = max(1, min(n_probe, layout.k))
        has_res = layout.residual is not None
        dummy = jnp.zeros((1, 1), self.dtype)
        dummy_i = jnp.zeros((1, 1), jnp.int32)
        dummy_f = jnp.zeros((1, 1), jnp.float32)
        t0 = time.perf_counter()
        with _COLLECTIVE_DISPATCH_LOCK:
            vals, slots = _sharded_ivf_topk(
                qn, layout.centroids, layout.blocks, layout.counts,
                layout.slotmap,
                layout.residual if has_res else dummy,
                layout.residual_slots if has_res else dummy_i,
                layout.block_scales if quantized else dummy_f,
                (layout.residual_scales if (quantized and has_res)
                 else dummy_f),
                k=k_prog, n_probe=n_probe, axis=self.axis,
                mesh_static=self.mesh, has_residual=has_res,
                quantized=quantized,
            )
            keep = max(k_dev, local_k)
            vals_np = np.asarray(vals, np.float32)[:b, :keep]
            slots_np = np.asarray(slots)[:b, :keep]
        t1 = time.perf_counter()
        self.shard_stats.ivf_dispatches += 1
        self.shard_stats.last_dispatch_s = t1 - t0
        _SHARDED_SEARCH_HIST.observe(t1 - t0)
        _deviceprof.record_execute(
            "search", "sharded_ivf", _deviceprof.pow2_class(b, "b"),
            t1 - t0)
        if quantized:
            vals_np, slots_np = self._rescore_host(q, slots_np, host, k)
        out = self._format_results(
            vals_np[:, :k], slots_np[:, :k], b, k, min_similarity, ids=ids,
        )
        merge_s = time.perf_counter() - t1
        self.shard_stats.last_merge_s = merge_s
        _SHARDED_MERGE_HIST.observe(merge_s)
        return out

    def _quantized_search(
        self, q: np.ndarray, k: int, min_similarity: float,
        local_k: int, streaming,
    ) -> list[list[tuple[str, float]]]:
        """Compressed-residency full scan: the int8 sharded program
        selects rescore_factor × k candidates per query (one fused device
        dispatch), then the merged set is exact-rescored from the host f32
        mirror. Served (id, score) pairs bit-match the f32 exact path for
        every returned id; only candidate MEMBERSHIP carries int8 noise,
        which the oversample is sized to absorb."""
        b = q.shape[0]
        q2 = pad_query_block(q)
        # inline borrow (the _pruned_search idiom): the host mirror must be
        # captured ATOMICALLY with the int8 buffers — a background
        # compaction rebinds self._host, and slots of the old buffer
        # resolved through the new array would read other rows' vectors
        with self._sync_lock:
            self._sync()
            self._readers += 1
            i8 = self._dev_i8
            dev_valid = self._dev_valid
            ids = self._ids
            host = self._host
        try:
            if i8 is None or dev_valid is None:
                raise DeviceUnavailable(
                    "no resident int8 buffer (degraded)"
                )
            cap = int(i8[0].shape[0])
            local_n = cap // self.n_shards
            k_dev = min(_next_pow2(max(k * self.rescore_factor, 8)), cap)
            lk = max(1, min(_next_pow2(max(k_dev, local_k, 8)), local_n))
            qd = l2_normalize(jnp.asarray(q2, dtype=jnp.float32))
            t0 = time.perf_counter()
            with _COLLECTIVE_DISPATCH_LOCK:
                _vals, idx = _sharded_search_int8(
                    qd, i8[0], i8[1], dev_valid, k_dev, lk,
                    self.axis, self.mesh, streaming=streaming,
                )
                # materialize inside the borrow + dispatch lock, same
                # rationale as the dense path
                idx_np = np.asarray(idx)[:b]
            t1 = time.perf_counter()
            self.shard_stats.dispatches += 1
            self.shard_stats.last_dispatch_s = t1 - t0
            _SHARDED_SEARCH_HIST.observe(t1 - t0)
            _deviceprof.record_execute(
                "search", "sharded_int8", _deviceprof.pow2_class(b, "b"),
                t1 - t0)
            if lk < local_n:
                self._note_local_k_overflows(idx_np, lk, local_n)
            vals_np, slots_np = self._rescore_host(q, idx_np, host, k)
            out = self._format_results(
                vals_np, slots_np, b, k, min_similarity, ids=ids,
            )
            merge_s = time.perf_counter() - t1
            self.shard_stats.last_merge_s = merge_s
            _SHARDED_MERGE_HIST.observe(merge_s)
            return out
        finally:
            with self._sync_lock:
                self._readers -= 1

    # -- search ------------------------------------------------------------
    def search(
        self,
        queries: np.ndarray,
        k: int,
        min_similarity: float = -1.0,
        exact: bool = False,
        n_probe: int = 0,
        streaming=None,
        local_k: int = 0,
        defer: bool = False,
    ) -> Sequence[list[tuple[str, float]]]:
        """Sharded cosine top-k: per-shard GEMM + top-local_k, ICI
        all-gather merge — one device dispatch for the whole (possibly
        batched) query block.  Scores are exact; with the default
        exact=False per-shard candidate membership uses approx_max_k or
        the streaming Pallas kernel (recall ~0.95+, tunable via local_k
        oversampling); exact=True gives recall 1.0 with tie-breaking
        identical to the single-device full scan.  n_probe > 0 with a
        fitted cluster index routes through the fused sharded IVF
        program instead.  quantized=True corpora select candidates from
        the int8 codes and exact-rescore the merged set from the host
        f32 mirror (exact=True serves the host mirror directly).  ``defer``
        as in DeviceCorpus.search: the full scan's rows are resolved to ids
        by whoever indexes them."""
        q = np.atleast_2d(np.asarray(queries, np.float32))
        if len(self._slot_of) == 0:
            return [[] for _ in range(q.shape[0])]
        # same lifecycle gate as DeviceCorpus.search: cold acquisition on
        # the manager's worker thread, degraded -> exact host fallback
        if not self._device_gate():
            return self._search_host(q, k, min_similarity, defer)
        try:
            if n_probe > 0:
                pruned = self._pruned_search(
                    q, k, min_similarity, n_probe, local_k=local_k
                )
                if pruned is not None:
                    return pruned
            if self.quantized:
                if exact:
                    # quantized device membership cannot honor the
                    # recall-1.0 contract; the host f32 mirror can —
                    # identical ids/scores/tie order to a DeviceCorpus
                    # full sort, by construction
                    return self._host_exact_topk(q, k, min_similarity)
                return self._quantized_search(
                    q, k, min_similarity, local_k, streaming
                )
            b = q.shape[0]
            # shape classes for batch (the query classes every corpus
            # pads to), k and local_k (powers of two): the program is
            # shape-keyed jit over a collective, and the QueryBatcher
            # hands us every coalesced batch size from 1..batch_max —
            # without padding each one compiles a fresh XLA program on
            # the serving hot path (same rationale and scheme as
            # _pruned_search).  Padding lk upward only widens
            # each shard's contribution, so exact mode stays lossless
            # (lk >= min(k, local_n) still holds) and approx recall can
            # only improve; padded query rows are zeros, sliced off the
            # result before formatting.
            q2 = pad_query_block(q)
            with self._borrow_device() as (dev, dev_valid, _i8, ids, _):
                # shard geometry comes from the BORROWED buffer, not self:
                # _borrow_device's sync may have just grown/re-sharded the
                # corpus (and a concurrent grow can rebind self._dev
                # again mid-search) — lk sized off a stale local_n would
                # silently cut exact-mode candidates on the new shards,
                # and overflow attribution would divide by the wrong width
                cap = int(dev.shape[0])
                local_n = cap // self.n_shards
                k_prog = min(_next_pow2(max(k, 8)), cap)
                lk = max(1, min(_next_pow2(max(k, local_k, 8)), local_n))
                qd = l2_normalize(jnp.asarray(q2, dtype=self.dtype))
                t0 = time.perf_counter()
                with _COLLECTIVE_DISPATCH_LOCK:
                    vals, idx = _sharded_search(
                        qd, dev, dev_valid, k_prog, lk,
                        self.axis, self.mesh, exact=exact,
                        streaming=streaming,
                    )
                    # materialize inside the borrow so the patcher can't
                    # donate the buffers this program is still reading (and
                    # inside the dispatch lock so the collective retires
                    # before another program may enqueue)
                    vals_np = np.asarray(vals, np.float32)[:b]
                    idx_np = np.asarray(idx)[:b]
                t1 = time.perf_counter()
        except DeviceUnavailable:
            return self._search_host(q, k, min_similarity, defer)
        self.shard_stats.dispatches += 1
        self.shard_stats.last_dispatch_s = t1 - t0
        _SHARDED_SEARCH_HIST.observe(t1 - t0)
        _deviceprof.record_execute(
            "search", "sharded", f"b{query_class(b)}", t1 - t0)
        if not exact and lk < local_n:
            # detect saturation on the UNSLICED merged width: a shard
            # contributing all lk of its oversampled candidates is the
            # truncation signal, regardless of the caller's k
            self._note_local_k_overflows(idx_np, lk, local_n)
        out = self._format_results(
            vals_np[:, :k], idx_np[:, :k], b, k, min_similarity,
            ids=ids, defer=defer, padded_rows=query_class(b) - b,
        )
        merge_s = time.perf_counter() - t1
        self.shard_stats.last_merge_s = merge_s
        _SHARDED_MERGE_HIST.observe(merge_s)
        return out

    def _note_local_k_overflows(
        self, idx: np.ndarray, lk: int, local_n: int
    ) -> None:
        """Count merged results where a single shard saturated its
        local_k contribution: in approx mode that shard's bin-reduce list
        was truncated exactly where real candidates may have been cut, so
        the operator signal is "raise SearchConfig.local_k"."""
        # a shard can contribute at most the merged width idx.shape[1]
        # (k_prog) entries — with local_k oversampled past that, `>= lk`
        # would be unreachable and the counter would read 0 forever,
        # silencing the exact signal the knob is tuned by. Saturating the
        # whole merged output is the strongest observable truncation sign.
        sat = min(lk, idx.shape[1])
        hits = 0
        for qi in range(idx.shape[0]):
            live = idx[qi][idx[qi] >= 0]
            if live.size == 0:
                continue
            per_shard = np.bincount(live // local_n, minlength=self.n_shards)
            if int(per_shard.max()) >= sat:
                hits += 1
        if hits:
            self.shard_stats.local_k_overflows += hits
            _SHARD_LOCALK_OVERFLOWS.inc(hits)
