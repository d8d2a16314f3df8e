"""Fused one-program IVF search: cluster-contiguous layout + single-jit
probe→gather→score→top-k.

Behavioral reference: /root/reference/pkg/gpu/kmeans.go —
ClusterIndex.SearchWithClusters (:816) probes the n_probe nearest
centroids and scores only their member rows; kmeans_candidate_gen.go
feeds the same candidates to the search pipeline.

TPU-first design (replaces the round-1 per-query host loop, which paid
one device round-trip per query):
  - The corpus is re-laid out cluster-contiguous: one (K, Cmax, D) block
    array, each cluster's rows contiguous and zero-padded to a shared
    power-of-two Cmax. Block gathers are coarse contiguous HBM reads —
    the row-gather pattern the TPU punishes never appears.
  - Oversized clusters spill their overflow rows into a residual segment
    that every query scans (brute force), so a pathological k-means
    imbalance degrades speed, never recall, and the block array is at
    most ~2x the live corpus.
  - One jit per (B, n_probe, Cmax) shape class does everything: centroid
    GEMM probe, block gather, bf16 scoring with f32 accumulation,
    validity masking, residual concat, top-k. No host round-trips inside
    the batch.

FLOP math at N=1M, D=1024, K=~707: a full scan is B·N·D MACs; probing
P=8 of ~707 clusters scores ~P/K of the corpus (~1.1%) plus residual —
the HBM read per query batch drops by the same factor, which is what
matters at small B where the scan is bandwidth-bound.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.ops.similarity import (
    LANE,
    dot_scores,
    l2_normalize,
    pad_query_block,
)


@dataclass
class IVFLayout:
    """Cluster-contiguous device layout built by build_ivf_layout."""

    blocks: jax.Array        # (K, Cmax, D) zero-padded cluster blocks
    counts: jax.Array        # (K,) int32 live rows per block
    centroids: jax.Array     # (K, D)
    slotmap: np.ndarray      # (K, Cmax) int32 -> corpus slot, -1 = pad
    residual: Optional[jax.Array]   # (Rp, D) spilled rows (None if none)
    residual_slots: np.ndarray      # (Rp,) int32 -> corpus slot, -1 = pad
    residual_valid: Optional[jax.Array]  # (Rp,) device mask, built once
    cmax: int
    k: int
    # corpus LAYOUT epoch at build time: the layout serves while this
    # matches HostCorpus._layout_epoch, which bumps only when a covered row
    # is overwritten in place or the slot space remaps (grow/compact/clear)
    # — plain adds/removes leave a fitted layout valid
    epoch: int

    @property
    def n_rows(self) -> int:
        return int((self.slotmap >= 0).sum() + (self.residual_slots >= 0).sum())


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def build_ivf_layout(
    rows: np.ndarray,
    slots: np.ndarray,
    assignments: np.ndarray,
    centroids: np.ndarray,
    dtype=jnp.float32,
    epoch: int = 0,
    max_block_factor: float = 2.0,
) -> IVFLayout:
    """Builds the block layout from live rows.

    rows:        (N, D) float32, already L2-normalized (corpus invariant)
    slots:       (N,) original corpus slot per row
    assignments: (N,) cluster id per row
    centroids:   (K, D)
    max_block_factor: Cmax is capped at ~factor x mean cluster size;
        overflow rows spill to the residual segment.
    """
    n, d = rows.shape
    k = centroids.shape[0]
    mean = max(1, n // max(1, k))
    cmax = _next_pow2(min(max(int(mean * max_block_factor), 8), n))
    # fully vectorized scatter: sort by cluster, compute each row's rank
    # within its cluster, rows with rank < Cmax land in the block array,
    # the rest spill (an O(N) Python loop here cost tens of seconds per
    # recluster at N=1M)
    in_range = (assignments >= 0) & (assignments < k)
    rows_v, slots_v, assign_v = rows[in_range], slots[in_range], assignments[in_range]
    order = np.argsort(assign_v, kind="stable")
    sorted_assign = assign_v[order]
    counts_all = np.bincount(sorted_assign, minlength=k)
    starts = np.concatenate(([0], np.cumsum(counts_all)[:-1]))
    rank = np.arange(sorted_assign.size) - starts[sorted_assign]
    in_block = rank < cmax
    blocks = np.zeros((k, cmax, d), np.float32)
    slotmap = np.full((k, cmax), -1, np.int32)
    c_idx = sorted_assign[in_block]
    p_idx = rank[in_block]
    blocks[c_idx, p_idx] = rows_v[order][in_block]
    slotmap[c_idx, p_idx] = slots_v[order][in_block]
    counts = np.minimum(counts_all, cmax).astype(np.int32)
    spill_rows = rows_v[order][~in_block]
    spill_slot_arr = slots_v[order][~in_block]
    if spill_rows.shape[0]:
        rp = ((spill_rows.shape[0] + LANE - 1) // LANE) * LANE
        residual = np.zeros((rp, d), np.float32)
        residual[: spill_rows.shape[0]] = spill_rows
        residual_slots = np.full(rp, -1, np.int32)
        residual_slots[: spill_slot_arr.shape[0]] = spill_slot_arr
        residual_dev = jnp.asarray(residual, dtype=dtype)
        residual_valid = jnp.asarray(residual_slots >= 0)
    else:
        residual_dev = None
        residual_slots = np.empty(0, np.int32)
        residual_valid = None
    return IVFLayout(
        blocks=jnp.asarray(blocks, dtype=dtype),
        counts=jnp.asarray(counts),
        centroids=jnp.asarray(centroids, dtype=dtype),
        slotmap=slotmap,
        residual=residual_dev,
        residual_slots=residual_slots,
        residual_valid=residual_valid,
        cmax=cmax,
        k=k,
        epoch=epoch,
    )


@functools.partial(jax.jit, static_argnames=("n_probe", "k"))
def _ivf_topk_program(
    queries: jax.Array,      # (B, D) L2-normalized
    centroids: jax.Array,    # (K, D)
    blocks: jax.Array,       # (K, Cmax, D)
    counts: jax.Array,       # (K,)
    n_probe: int,
    k: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (vals (B,k), flat candidate idx (B,k), probes (B,P)).
    Flat idx encodes (probe position p, row c) as p * Cmax + c."""
    cmax = blocks.shape[1]
    cscores = dot_scores(queries, centroids)            # (B, K)
    _, probes = jax.lax.top_k(cscores, n_probe)          # (B, P)
    gathered = blocks[probes]                            # (B, P, Cmax, D)
    scores = jnp.einsum(
        "bd,bpcd->bpc",
        queries.astype(jnp.bfloat16),
        gathered.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    live = jnp.arange(cmax)[None, None, :] < counts[probes][:, :, None]
    scores = jnp.where(live, scores, -jnp.inf)
    flat = scores.reshape(scores.shape[0], -1)           # (B, P*Cmax)
    kk = min(k, flat.shape[1])
    vals, idx = jax.lax.top_k(flat, kk)
    return vals, idx, probes


@functools.partial(jax.jit, static_argnames=("k",))
def _residual_topk(
    queries: jax.Array, residual: jax.Array, valid: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    scores = dot_scores(queries, residual)
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    kk = min(k, scores.shape[1])
    return jax.lax.top_k(scores, kk)


def ivf_search(
    layout: IVFLayout,
    queries: np.ndarray,
    k: int,
    n_probe: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Fused IVF top-k. queries (B, D) need not be normalized.
    Returns (scores (B, k), corpus slots (B, k)); slot -1 = no candidate
    (short clusters). Scores of returned rows are exact bf16-GEMM scores,
    identical in kind to the full-scan path."""
    q2 = np.atleast_2d(np.asarray(queries, np.float32))
    b = q2.shape[0]
    # bucket B (the dense scan's query classes) and k (powers of two) so
    # the jit caches a handful of shape classes instead of recompiling per
    # client-supplied batch/limit (same rationale as the fallback path's
    # candidate buckets)
    q2 = pad_query_block(q2)
    k_prog = _next_pow2(max(k, 8))
    qn = l2_normalize(jnp.asarray(q2))
    n_probe = max(1, min(n_probe, layout.k))
    vals, idx, probes = _ivf_topk_program(
        qn, layout.centroids, layout.blocks, layout.counts, n_probe, k_prog
    )
    vals = np.asarray(vals, np.float32)[:b, :k]
    idx = np.asarray(idx)[:b, :k]
    probes_np = np.asarray(probes)[:b]
    # resolve flat (p, c) -> corpus slot through the host slotmap
    p_pos = idx // layout.cmax
    c_pos = idx % layout.cmax
    cluster_ids = np.take_along_axis(probes_np, p_pos, axis=1)
    slots = layout.slotmap[cluster_ids, c_pos]
    slots = np.where(np.isfinite(vals), slots, -1)
    if layout.residual is not None:
        rvals, ridx = _residual_topk(
            qn, layout.residual, layout.residual_valid, k_prog
        )
        rvals = np.asarray(rvals, np.float32)[:b]
        rslots = layout.residual_slots[np.asarray(ridx)[:b]]
        rslots = np.where(np.isfinite(rvals), rslots, -1)
        # merge the two k-lists per query (host merge of 2k items)
        merged_scores = np.concatenate([vals, rvals], axis=1)
        merged_slots = np.concatenate([slots, rslots], axis=1)
        order = np.argsort(-merged_scores, axis=1)[:, :k]
        vals = np.take_along_axis(merged_scores, order, axis=1)
        slots = np.take_along_axis(merged_slots, order, axis=1)
    if vals.shape[1] < k:
        pad = k - vals.shape[1]
        vals = np.pad(vals, ((0, 0), (0, pad)), constant_values=-np.inf)
        slots = np.pad(slots, ((0, 0), (0, pad)), constant_values=-1)
    return vals, slots


# ------------------------------------------------------------ sharded IVF
#
# IVF composed with mesh sharding (ROADMAP item 2): centroids are
# REPLICATED (every shard probes identically — the centroid GEMM is tiny),
# inverted lists are PER-SHARD (each shard owns the cluster members that
# live in its slot range), and n_probe pruning happens INSIDE the shard
# program, so the fused sharded search gets the same ~P/K FLOP/HBM cut per
# shard that the single-device layout gets. All shards share one static
# (K, Cmax, D) block shape (shard_map needs uniform shapes); skew between
# shards pads with dead rows that the count masks exclude, and per-shard
# overflow spills into a shared-width residual segment scanned brute-force.
#
# The slotmap rides the device this time (the single-device layout resolves
# slots host-side): each shard must translate its local (probe, col) hits
# into GLOBAL corpus slots BEFORE the all-gather merge, so the merge
# exchanges only (vals, global_slot) pairs — the same wire format as the
# dense sharded path.
#
# Invalidation contract == PR 2's layout epoch: the layout serves while its
# build-time epoch matches HostCorpus._layout_epoch (bumped by covered-row
# overwrites and slot remaps; plain adds/removes keep it serving — new rows
# are invisible to pruned search until recluster, removals filter at
# format time through the captured id map).


@dataclass
class ShardedIVFLayout:
    """Per-shard cluster-contiguous layout for the fused sharded IVF path.

    Built by build_sharded_ivf_layout; consumed by the shard_map program in
    parallel.sharded_index (kept there — this module stays mesh-agnostic;
    the device arrays arrive pre-placed via the shardings the caller
    passes in).

    ``quantized=True`` stores the blocks (and residual) as int8 codes with
    per-row dequant MULTIPLIERS (1/scale, 0 for pad rows) so the block
    array costs 1 byte/element instead of 4 — the compressed-residency
    twin of ShardedCorpus's int8 serving mode. Device scores then carry
    int8 rounding noise; the corpus rescores the merged candidate set
    exactly from its host f32 mirror.
    """

    blocks: jax.Array        # (S, K, Cmax, D) zero-padded, P(axis,...)
    counts: jax.Array        # (S, K) int32 live rows per shard-cluster
    slotmap: jax.Array       # (S, K, Cmax) int32 GLOBAL slot, -1 = pad
    centroids: jax.Array     # (K, D) replicated
    residual: Optional[jax.Array]      # (S, Rmax, D) per-shard spill
    residual_slots: Optional[jax.Array]  # (S, Rmax) int32 global slot, -1
    cmax: int
    rmax: int
    k: int                   # cluster count
    n_shards: int
    epoch: int               # corpus layout epoch at build time
    quantized: bool = False
    # int8 mode only: per-row dequant multipliers (0 = dead/pad row)
    block_scales: Optional[jax.Array] = None     # (S, K, Cmax) f32
    residual_scales: Optional[jax.Array] = None  # (S, Rmax) f32

    @property
    def n_rows(self) -> int:
        n = int(np.asarray(jnp.sum(self.slotmap >= 0)))
        if self.residual_slots is not None:
            n += int(np.asarray(jnp.sum(self.residual_slots >= 0)))
        return n


def build_sharded_ivf_layout(
    rows: np.ndarray,
    slots: np.ndarray,
    assignments: np.ndarray,
    centroids: np.ndarray,
    n_shards: int,
    local_n: int,
    shard_sharding,
    replicated_sharding,
    dtype=jnp.float32,
    epoch: int = 0,
    max_block_factor: float = 2.0,
    quantize: bool = False,
) -> ShardedIVFLayout:
    """Build the per-shard inverted lists.

    rows:        (N, D) float32, L2-normalized live rows
    slots:       (N,) GLOBAL corpus slot per row; shard = slot // local_n
    assignments: (N,) cluster id per row
    n_shards/local_n: the corpus's mesh layout (capacity = S * local_n)
    shard_sharding: NamedSharding partitioning the leading shard axis
        (trailing dims replicated) — placed on every (S, ...) array;
    replicated_sharding: NamedSharding for the replicated centroids.
    quantize: store blocks/residual as int8 codes + per-row dequant
        multipliers (compressed residency — see ShardedIVFLayout).
    """
    n, d = rows.shape
    k = centroids.shape[0]
    shard_of = slots // local_n
    in_range = (
        (assignments >= 0) & (assignments < k)
        & (shard_of >= 0) & (shard_of < n_shards)
    )
    rows_v = rows[in_range]
    slots_v = slots[in_range]
    assign_v = assignments[in_range]
    shard_v = shard_of[in_range]
    # shared Cmax across shards: ~factor x the mean shard-cluster size, so
    # one skewed shard pads instead of inflating every shard's block array
    mean = max(1, rows_v.shape[0] // max(1, n_shards * k))
    cmax = _next_pow2(min(max(int(mean * max_block_factor), 8),
                          max(local_n, 1)))
    # vectorized scatter, same trick as the single-device build but keyed
    # by (shard, cluster): sort, rank within the pair, rank < Cmax lands
    # in the block, the rest spills per shard
    pair = shard_v.astype(np.int64) * k + assign_v
    order = np.argsort(pair, kind="stable")
    sorted_pair = pair[order]
    counts_all = np.bincount(sorted_pair, minlength=n_shards * k)
    starts = np.concatenate(([0], np.cumsum(counts_all)[:-1]))
    rank = np.arange(sorted_pair.size) - starts[sorted_pair]
    in_block = rank < cmax
    if quantize:
        from nornicdb_tpu.ops.host_search import quantize_rows_np

        # one pass over the live rows; the scatter then moves 1-byte codes
        # plus a (row,) multiplier column instead of f32 row copies
        codes_v, scale_v = quantize_rows_np(rows_v)
        mult_v = (1.0 / np.maximum(scale_v, 1e-30)).astype(np.float32)
        store_v = codes_v
        blocks = np.zeros((n_shards, k, cmax, d), np.int8)
        block_scales = np.zeros((n_shards, k, cmax), np.float32)
    else:
        store_v = rows_v
        blocks = np.zeros((n_shards, k, cmax, d), np.float32)
        block_scales = None
    slotmap = np.full((n_shards, k, cmax), -1, np.int32)
    s_idx = (sorted_pair // k)[in_block]
    c_idx = (sorted_pair % k)[in_block]
    p_idx = rank[in_block]
    blocks[s_idx, c_idx, p_idx] = store_v[order][in_block]
    slotmap[s_idx, c_idx, p_idx] = slots_v[order][in_block]
    if quantize:
        block_scales[s_idx, c_idx, p_idx] = mult_v[order][in_block]
    counts = np.minimum(
        counts_all.reshape(n_shards, k), cmax
    ).astype(np.int32)
    # per-shard residual spill, padded to a shared LANE-multiple width
    spill_rows = store_v[order][~in_block]
    spill_slots = slots_v[order][~in_block]
    spill_shard = (sorted_pair // k)[~in_block]
    residual_dev = residual_slots_dev = residual_scales_dev = None
    rmax = 0
    if spill_rows.shape[0]:
        per_shard = np.bincount(spill_shard, minlength=n_shards)
        rmax = ((int(per_shard.max()) + LANE - 1) // LANE) * LANE
        residual = np.zeros((n_shards, rmax, d), spill_rows.dtype)
        residual_slots = np.full((n_shards, rmax), -1, np.int32)
        residual_scales = (np.zeros((n_shards, rmax), np.float32)
                           if quantize else None)
        spill_mult = mult_v[order][~in_block] if quantize else None
        # spill rows are already grouped by shard (sorted by pair)
        for s in range(n_shards):
            m = spill_shard == s
            cnt = int(m.sum())
            if cnt:
                residual[s, :cnt] = spill_rows[m]
                residual_slots[s, :cnt] = spill_slots[m]
                if quantize:
                    residual_scales[s, :cnt] = spill_mult[m]
        residual_dev = jax.device_put(
            jnp.asarray(residual) if quantize
            else jnp.asarray(residual, dtype=dtype),
            shard_sharding,
        )
        residual_slots_dev = jax.device_put(
            jnp.asarray(residual_slots), shard_sharding
        )
        if quantize:
            residual_scales_dev = jax.device_put(
                jnp.asarray(residual_scales), shard_sharding
            )
    return ShardedIVFLayout(
        blocks=jax.device_put(
            jnp.asarray(blocks) if quantize
            else jnp.asarray(blocks, dtype=dtype),
            shard_sharding,
        ),
        counts=jax.device_put(jnp.asarray(counts), shard_sharding),
        slotmap=jax.device_put(jnp.asarray(slotmap), shard_sharding),
        centroids=jax.device_put(jnp.asarray(centroids, dtype=dtype),
                                 replicated_sharding),
        residual=residual_dev,
        residual_slots=residual_slots_dev,
        cmax=cmax,
        rmax=rmax,
        k=k,
        n_shards=n_shards,
        epoch=epoch,
        quantized=quantize,
        block_scales=(jax.device_put(jnp.asarray(block_scales),
                                     shard_sharding)
                      if quantize else None),
        residual_scales=residual_scales_dev,
    )
