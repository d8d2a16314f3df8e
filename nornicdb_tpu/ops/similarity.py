"""Batch vector similarity + top-k on TPU via XLA.

Replaces the reference's CUDA/Metal kernels
(/root/reference/pkg/gpu/cuda/cuda_kernels.cu: kernel_compute_norms :185,
kernel_normalize_vectors :206, kernel_cosine_similarity :284,
kernel_topk_simple :384; pkg/simd/simd.go:38-240).

TPU-first design notes:
  - Cosine scoring IS a matmul: normalize once, then Q @ C^T rides the MXU.
    We keep corpora normalized at insert time so the query path is one GEMM.
  - Scores + top-k are computed under one jit so XLA fuses the epilogue and
    never round-trips the (Q, N) score matrix through HBM when chunked.
  - Static shapes: corpora are padded to lane multiples (128) and masked with
    -inf; jit caches per padded shape bucket, not per exact N.
  - bf16 matmul with f32 accumulation (preferred_element_type) matches MXU
    native precision.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import threading
import time
from dataclasses import asdict, dataclass
from collections.abc import Sequence
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu import backend as _backend
from nornicdb_tpu.errors import DeviceUnavailable
from nornicdb_tpu.ops.host_search import (
    format_topk_results,
    host_score_rows,
    host_topk,
)

logger = logging.getLogger(__name__)

LANE = 128  # TPU lane width; min tile second dim


def pad_to_multiple(n: int, m: int = LANE) -> int:
    return ((n + m - 1) // m) * m


# -- query-block shape classes ------------------------------------------------
# Every top-k program is jitted per (Q, k): a block of B queries is scanned
# as a block of query_class(B) rows, zero rows making up the difference, so
# that the batch sizes a coalescing dispatcher produces (1..batch_max) meet
# a bounded grid of programs.  The smallest class is 8: a (1, 1024) f32 block
# already occupies eight sublanes, so Q = 1..8 cost one scan alike.
QUERY_CLASS_MIN = 8


def query_class(b: int) -> int:
    """Rows of the block that ``b`` queries are scanned as."""
    return max(QUERY_CLASS_MIN, 1 << max(0, int(b) - 1).bit_length())


def query_classes(max_batch: int) -> tuple[int, ...]:
    """The grid: every class a block of 1..max_batch queries can map to."""
    classes = [QUERY_CLASS_MIN]
    while classes[-1] < max_batch:
        classes.append(2 * classes[-1])
    return tuple(classes)


def pad_query_block(q: np.ndarray) -> np.ndarray:
    """``q`` (B, D) with zero rows appended up to its class.  The padding
    rows are scanned with the rest; their top-k is sliced off before any
    row is resolved to ids."""
    b = q.shape[0]
    cls = query_class(b)
    if cls == b:
        return q
    out = np.zeros((cls, q.shape[1]), q.dtype)
    out[:b] = q
    return out


class LazyRows(Sequence):
    """``search(..., defer=True)``: the rows of an eager answer with the
    host's share of the work left undone.  ``fetch()`` blocks until the
    scan's top-k is on the host (once, whoever calls it first; the program
    was launched before this object was returned) and indexing resolves one
    row to ``[(id, score)]`` on the thread that indexes it.  A dispatcher
    that fans one scan out to many callers so can launch the next scan
    before anything of this one is read back or formatted, and a row nobody
    asks for is never formatted.  ``padded_rows`` says how many rows of the
    scanned block were padding."""

    def __init__(self, fetch, n: int, padded_rows: int = 0):
        self._fetch = fetch  # () -> (row index -> [(id, score)])
        self._row = None
        self._error: Optional[BaseException] = None
        self._lock = threading.Lock()
        self._n = n
        self.padded_rows = padded_rows

    def fetch(self) -> "LazyRows":
        if self._row is None:
            with self._lock:
                if self._error is not None:
                    raise self._error
                if self._row is None:
                    try:
                        self._row = self._fetch()
                    except BaseException as e:
                        self._error = e
                        raise
                    finally:
                        self._fetch = None  # drops what the closure pins
        return self

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(self._n))]
        if i < 0:
            i += self._n
        if not 0 <= i < self._n:
            raise IndexError(i)
        return self.fetch()._row(i)


@jax.jit
def l2_normalize(x: jax.Array, eps: float = 1e-12) -> jax.Array:
    """Row-wise L2 normalization (ref: kernel_normalize_vectors cuda_kernels.cu:206)."""
    with jax.named_scope("l2_normalize"):
        norm = jnp.sqrt(
            jnp.sum(x.astype(jnp.float32) ** 2, axis=-1, keepdims=True))
        return (x / jnp.maximum(norm, eps)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("use_bf16",))
def dot_scores(
    queries: jax.Array, corpus: jax.Array, use_bf16: bool = True
) -> jax.Array:
    """(Q, D) x (N, D) -> (Q, N) dot-product scores on the MXU."""
    if use_bf16:
        queries = queries.astype(jnp.bfloat16)
        corpus = corpus.astype(jnp.bfloat16)
    return jax.lax.dot_general(
        queries,
        corpus,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("use_bf16",))
def cosine_scores(
    queries: jax.Array, corpus: jax.Array, use_bf16: bool = True
) -> jax.Array:
    """Full cosine similarity: normalizes both sides then one GEMM
    (ref: kernel_cosine_similarity cuda_kernels.cu:284)."""
    return dot_scores(l2_normalize(queries), l2_normalize(corpus), use_bf16)


@functools.partial(
    jax.jit, static_argnames=("k", "normalized", "use_bf16", "exact", "recall_target")
)
def cosine_topk(
    queries: jax.Array,
    corpus: jax.Array,
    valid: jax.Array,
    k: int,
    normalized: bool = True,
    use_bf16: bool = True,
    exact: bool = False,
    recall_target: float = 0.95,
) -> tuple[jax.Array, jax.Array]:
    """Fused cosine scoring + top-k.

    queries: (Q, D); corpus: (Np, D) padded to a lane multiple;
    valid:   (Np,) bool mask — False rows (padding / tombstones) score -inf.
    Returns (values (Q, k), indices (Q, k)).

    By default top-k uses lax.approx_max_k, the TPU-native partial-reduction
    top-k (fuses into the GEMM epilogue; measured ~4x faster end-to-end at
    N=1M than exact lax.top_k, which adds a full-sort pass). Scores of the
    returned candidates are exact; only set membership is approximate
    (recall_target, default 0.95 — same contract as the reference's HNSW
    path, pkg/search/hnsw_index.go). exact=True restores full sort.
    """
    q = queries if normalized else l2_normalize(queries)
    c = corpus if normalized else l2_normalize(corpus)
    scores = dot_scores(q, c, use_bf16)
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    if exact:
        return jax.lax.top_k(scores, k)
    return jax.lax.approx_max_k(scores, k, recall_target=recall_target)


@functools.partial(jax.jit, static_argnames=("k",))
def masked_dot_topk(
    query: jax.Array, corpus: jax.Array, valid: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Graph-filtered top-k for the Cypher ``VectorTopK`` operator: one
    (1, D) x (Np, D) GEMM over a pre-normalized corpus with the surviving
    graph-predicate rows as a validity mask (False -> -inf, covering both
    pad rows and mask-rejected rows), plus the exact k largest masked
    scores for the rescore boundary.

    Returns ``(scores (Np,), top_vals (k,))``.  f32 end to end — the
    caller's widened-boundary rescore contract budgets for f32 GEMM
    rounding only, not bf16.
    """
    with jax.named_scope("masked_dot_topk"):
        s = dot_scores(query[None, :], corpus, use_bf16=False)[0]
        s = jnp.where(valid, s, -jnp.inf)
        return s, jax.lax.top_k(s, k)[0]


# streaming Pallas top-k engages above this corpus size; below it the (Q, N)
# score matrix is small enough that the XLA GEMM+approx_max_k path wins on
# dispatch overhead
STREAMING_MIN_ROWS = 65_536

# bin-reduction strategy for the streaming kernels ("sort" | "approx" |
# "pallas", see pallas_kernels._topk_bins). Overridable per-deployment while
# autotune data accumulates (benchmarks/kernel_autotune.py). Validated here
# so a config typo fails at import, not inside the first jitted query.
TOPK_EPILOGUE = os.environ.get("NORNICDB_TOPK_EPILOGUE", "sort")
if TOPK_EPILOGUE not in ("sort", "approx", "pallas"):
    raise ValueError(
        f"NORNICDB_TOPK_EPILOGUE={TOPK_EPILOGUE!r}: "
        "must be one of sort|approx|pallas"
    )


def _kernel_mode(streaming, n: int, auto_ok: bool = True) -> tuple[bool, bool]:
    """``(use_kernel, interpret)`` for one top-k dispatch over ``n`` rows.

    ``streaming=None`` auto-selects the Pallas streaming kernel on a TPU at
    ``STREAMING_MIN_ROWS`` and up; ``True`` forces it wherever a TPU is
    attached; ``False`` pins the XLA path.  Off-TPU all three take the XLA
    path — serving never runs an interpreted kernel.  ``"interpret"`` is the
    tests' explicit request for the kernel under the Pallas interpreter."""
    if streaming == "interpret":
        return True, True
    if streaming is False:
        return False, False
    from nornicdb_tpu.ops.pallas_kernels import _on_tpu

    if not _on_tpu():
        return False, False
    if streaming is None:
        return auto_ok and n >= STREAMING_MIN_ROWS, False
    return True, False


def topk_backend(
    queries: jax.Array,
    corpus: jax.Array,
    valid: jax.Array,
    k: int,
    exact: bool = False,
    use_bf16: bool = True,
    streaming=None,
    quantized: Optional[tuple[jax.Array, jax.Array]] = None,
) -> tuple[jax.Array, jax.Array]:
    """Top-k dispatch for normalized inputs: the streaming Pallas kernel
    (ops.pallas_kernels.streaming_cosine_topk — one corpus read, no (Q, N)
    materialization) on TPU for large corpora, else the XLA
    GEMM+approx_max_k path. ``streaming`` is read by :func:`_kernel_mode`.
    The kernel scores in bf16, so an explicit use_bf16=False keeps the XLA
    f32 path.
    `quantized=(c_i8, c_scale)` (quantize_rows of the same corpus) engages
    the int8 MXU kernel — 2x the bf16 MXU rate, half the corpus HBM read."""
    from nornicdb_tpu.ops.pallas_kernels import (
        pick_tile_n,
        quantize_rows,
        streaming_cosine_topk,
        streaming_cosine_topk_int8,
        streaming_rows_for,
    )

    n = int(corpus.shape[0])
    use_kernel, interpret = _kernel_mode(
        streaming, n, auto_ok=(not exact) and use_bf16)
    if use_kernel and not exact:
        tile = pick_tile_n(n)
        rows = min(streaming_rows_for(k, tile), max(n // tile, 1))
        # tile must divide n (corpus capacities are 128-multiples, but a
        # sharded slice need not be) and the bins must hold a full top-k;
        # otherwise fall through to the XLA path instead of crashing
        if n % tile == 0 and rows * tile >= k:
            if quantized is not None:
                q_i8, q_scale = quantize_rows(queries)
                return streaming_cosine_topk_int8(
                    q_i8, q_scale, quantized[0], quantized[1], valid,
                    min(k, n), tile_n=tile, rows=rows,
                    interpret=interpret, epilogue=TOPK_EPILOGUE,
                )
            return streaming_cosine_topk(
                queries, corpus, valid, min(k, n),
                tile_n=tile, rows=rows, interpret=interpret,
                epilogue=TOPK_EPILOGUE,
            )
    return cosine_topk(
        queries, corpus, valid, k, normalized=True, use_bf16=use_bf16,
        exact=exact,
    )


@functools.partial(jax.jit, static_argnames=("k",))
def cosine_topk_int8_xla(
    queries: jax.Array,
    c_i8: jax.Array,
    c_scale: jax.Array,
    valid: jax.Array,
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """XLA fallback scoring over an int8-resident corpus: dequantize the
    codes into the bf16 GEMM (int8 values are exactly representable in
    bf16), apply the per-row dequant multiplier in the f32 epilogue.

    Engages where the streaming int8 Pallas kernel doesn't (non-TPU
    backends, small corpora, tile-indivisible shard slices). Queries stay
    f32/bf16 — only the CORPUS is quantized, so candidate membership is at
    least as accurate as the both-sides-int8 kernel. Always approximate:
    there is deliberately NO exact int8 device mode — the recall-1.0
    contract is served from the host f32 mirror, and served scores come
    from the caller's exact f32 host rescore either way."""
    scores = jax.lax.dot_general(
        queries.astype(jnp.bfloat16),
        c_i8.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) / jnp.maximum(c_scale, 1e-9)[None, :]
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    return jax.lax.approx_max_k(scores, k, recall_target=0.95)


def topk_backend_int8(
    queries: jax.Array,
    c_i8: jax.Array,
    c_scale: jax.Array,
    valid: jax.Array,
    k: int,
    streaming=None,
) -> tuple[jax.Array, jax.Array]:
    """Top-k dispatch for an int8-RESIDENT corpus (no f32/bf16 device copy
    exists — compressed residency, 4x the rows per HBM byte). On TPU at
    scale the streaming int8 Pallas bin-reduce kernel runs the MXU at the
    int8 rate over the codes; elsewhere the XLA dequant-GEMM fallback
    (``streaming`` as in :func:`_kernel_mode`).
    ``c_scale`` follows the quantize_rows convention (x ~= int8 / scale).
    Candidate scores are approximate (int8 + bf16 noise); callers rescore
    the candidate set exactly from the host f32 mirror."""
    from nornicdb_tpu.ops.pallas_kernels import (
        pick_tile_n,
        quantize_rows,
        streaming_cosine_topk_int8,
        streaming_rows_for,
    )

    n = int(c_i8.shape[0])
    use_kernel, interpret = _kernel_mode(streaming, n)
    if use_kernel:
        tile = pick_tile_n(n)
        rows = min(streaming_rows_for(k, tile), max(n // tile, 1))
        if n % tile == 0 and rows * tile >= k:
            q_i8, q_scale = quantize_rows(queries)
            return streaming_cosine_topk_int8(
                q_i8, q_scale, c_i8, c_scale, valid,
                min(k, n), tile_n=tile, rows=rows,
                interpret=interpret, epilogue=TOPK_EPILOGUE,
            )
    return cosine_topk_int8_xla(queries, c_i8, c_scale, valid, min(k, n))


@functools.partial(jax.jit, static_argnames=("use_bf16",))
def score_subset(
    query: jax.Array, corpus: jax.Array, indices: jax.Array, use_bf16: bool = True
) -> jax.Array:
    """Exact re-score of candidate rows (ref: EmbeddingIndex.ScoreSubset
    pkg/gpu/gpu.go:1554): gather candidates then one small GEMV."""
    cand = corpus[indices]  # (C, D)
    q = query.reshape(1, -1)
    return dot_scores(q, cand, use_bf16)[0]


@jax.jit
def euclidean_scores(queries: jax.Array, corpus: jax.Array) -> jax.Array:
    """Squared euclidean distances via the |x|^2 - 2xy + |y|^2 expansion so the
    cross term rides the MXU (ref: euclidean_distance shaders_darwin.metal:333)."""
    qn = jnp.sum(queries.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    cn = jnp.sum(corpus.astype(jnp.float32) ** 2, axis=1)[None, :]
    cross = dot_scores(queries, corpus, use_bf16=False)
    return jnp.maximum(qn - 2.0 * cross + cn, 0.0)


def merge_topk(
    values: jax.Array, indices: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Merge per-shard/per-chunk top-k lists into a global top-k.

    values/indices: (S, Q, k) stacked partial results with GLOBAL indices.
    Returns (Q, k). Used for the ICI all-gather merge of sharded search.

    Sentinel contract: any merged entry whose value is not finite (the
    -inf padding a near-empty shard emits when ``k`` exceeds its live
    rows) gets index -1, so a padding slot's index can NEVER surface as
    a candidate — even through a caller that forgets to filter by score.
    (Before this guard a -inf entry kept whatever index the per-shard
    top-k happened to assign it, and ``ids[idx]`` on a negative or
    recycled index could attribute a live id to a sentinel score.)

    Tie-breaking is stable vs the single-device path: the flattened
    candidate axis is shard-major (shard s, rank j -> s*k + j), and
    lax.top_k breaks value ties by the lowest flattened position — i.e.
    lowest shard first, then best per-shard rank. Because row slots are
    laid out contiguously per shard, that is exactly ascending global
    row index, the same order lax.top_k yields on one device.
    """
    s, q, kk = values.shape
    flat_v = jnp.transpose(values, (1, 0, 2)).reshape(q, s * kk)
    flat_i = jnp.transpose(indices, (1, 0, 2)).reshape(q, s * kk)
    best_v, pos = jax.lax.top_k(flat_v, k)
    best_i = jnp.take_along_axis(flat_i, pos, axis=1)
    best_i = jnp.where(jnp.isfinite(best_v), best_i, -1)
    return best_v, best_i


# ------------------------------------------------------------- device sync
# dirty-tracking granularity: one block = one LANE-aligned row group. Writes
# mark only the blocks they touch; sync patches only dirty blocks.
BLOCK_ROWS = LANE

# H2D sync telemetry: duration + bytes histograms by mode (patch vs full
# upload), plus a `device.sync` span when a traced request pays the sync
from nornicdb_tpu.telemetry.metrics import (  # noqa: E402
    BYTE_BUCKETS as _BYTE_BUCKETS,
    REGISTRY as _REGISTRY,
)
from nornicdb_tpu.telemetry.tracing import tracer as _tracer  # noqa: E402

_SYNC_HIST = _REGISTRY.histogram(
    "nornicdb_device_sync_seconds",
    "Host-to-device corpus sync duration by mode",
    labels=("mode",),
)
_SYNC_PATCH_CELL = _SYNC_HIST.labels("patch")
_SYNC_FULL_CELL = _SYNC_HIST.labels("full")
_SYNC_BYTES_HIST = _REGISTRY.histogram(
    "nornicdb_device_sync_transfer_bytes",
    "Bytes shipped per host-to-device sync by mode",
    labels=("mode",),
    buckets=_BYTE_BUCKETS,
)
_SYNC_PATCH_BYTES_CELL = _SYNC_BYTES_HIST.labels("patch")
_SYNC_FULL_BYTES_CELL = _SYNC_BYTES_HIST.labels("full")

# mesh-sharded serving telemetry (parallel.ShardedCorpus): registered here —
# not in parallel/ — so the families render in the /metrics catalog of every
# process (the sharded module imports lazily, only when a mesh exists)
_SHARDED_SEARCH_HIST = _REGISTRY.histogram(
    "nornicdb_sharded_search_seconds",
    "Fused per-shard scoring + local top-k + ICI all-gather merge: one "
    "device dispatch per (possibly batched) sharded search",
)
_SHARDED_MERGE_HIST = _REGISTRY.histogram(
    "nornicdb_sharded_merge_seconds",
    "Host-side merge epilogue of a sharded search (sentinel filtering, "
    "id resolution, IVF block+residual candidate merge)",
)
_SHARD_REBALANCES = _REGISTRY.counter(
    "nornicdb_shard_rebalances_total",
    "Shard-boundary remaps (grow/compact/recovery) that forced a full "
    "re-shard re-upload of the mesh corpus",
)
_SHARD_LOCALK_OVERFLOWS = _REGISTRY.counter(
    "nornicdb_shard_local_k_overflows_total",
    "Approx sharded searches where one shard's local_k candidate list "
    "saturated the merged top-k (raise local_k to recover recall)",
)
_SHARD_ROWS_GAUGE = _REGISTRY.gauge(
    "nornicdb_shard_rows",
    "Live corpus rows resident on each mesh shard",
    labels=("shard",),
)

# above this fraction of dirty blocks, one contiguous full transfer beats
# many small patch dispatches (each patch pays launch + slice overhead and
# the runs re-upload their padding rows)
FULL_SYNC_DIRTY_FRACTION = 0.5


@dataclass
class SyncStats:
    """H2D sync accounting for one corpus (exposed via stats()["sync"] and
    the server's /admin/stats + /metrics)."""

    patches: int = 0          # incremental patch syncs (1 per sync pass)
    full_uploads: int = 0     # whole-corpus transfers (first sync/grow/…)
    bytes_uploaded: int = 0   # total host bytes shipped to the device
    patch_bytes: int = 0      # subset of bytes_uploaded moved by patching
    rows_patched: int = 0
    uploader_runs: int = 0    # write-behind background sync cycles
    query_stall_s: float = 0.0  # time the query path spent blocked in sync
    # device search programs launched (one per fused batch when queries go
    # through the QueryBatcher) — the counter the multi-process bench's
    # one-program-per-fused-batch invariant is asserted against
    device_dispatches: int = 0
    # host-observed seconds of the dense device search's three stages,
    # each fed by the tracer.stage of the same name: corpus.dispatch
    # (sync/borrow + normalize + the top-k program launched),
    # corpus.fetch (the blocking read-back: device execution + D2H, and
    # whatever is queued ahead on the chip), corpus.format (slot -> id)
    search_dispatch_seconds: float = 0.0
    search_fetch_seconds: float = 0.0
    search_format_seconds: float = 0.0

    def as_dict(self) -> dict:
        return asdict(self)


def _coalesce_runs(
    blocks: Sequence[int], cap_blocks: int
) -> list[tuple[int, int]]:
    """Coalesce sorted dirty block ids into (start_block, n_blocks) upload
    runs. Blocks separated by <= 2 clean blocks merge into one run (a couple
    of redundant blocks cost less than another dispatch), and run lengths
    round up to powers of two so the jitted patch program caches O(log N)
    shapes instead of one per burst size; the start shifts back when the
    padding would overrun capacity. Padding rows rewrite identical host
    bytes, so overlap between padded runs is harmless."""
    runs: list[tuple[int, int]] = []
    i = 0
    while i < len(blocks):
        j = i
        while j + 1 < len(blocks) and blocks[j + 1] - blocks[j] <= 3:
            j += 1
        start, n = blocks[i], blocks[j] - blocks[i] + 1
        n = min(1 << (n - 1).bit_length(), cap_blocks)
        runs.append((min(start, cap_blocks - n), n))
        i = j + 1
    return runs


def _patch_rows_impl(dev: jax.Array, rows: jax.Array, start) -> jax.Array:
    return jax.lax.dynamic_update_slice(dev, rows, (start, 0))


def _patch_valid_impl(dev_valid: jax.Array, rows: jax.Array, start) -> jax.Array:
    return jax.lax.dynamic_update_slice(dev_valid, rows, (start,))


def _patch_i8_impl(dev_i8, dev_scale, rows, start):
    """Requantize ONLY the patched rows: quantization is per-row symmetric
    (ops.pallas_kernels.quantize_rows), so block-local requantization
    matches requantizing the whole corpus (int8 codes exactly; scales to
    within a float ulp of XLA codegen variance)."""
    from nornicdb_tpu.ops.pallas_kernels import quantize_rows

    i8, s = quantize_rows(rows)
    return (
        jax.lax.dynamic_update_slice(dev_i8, i8, (start, 0)),
        jax.lax.dynamic_update_slice(dev_scale, s, (start,)),
    )


# donated variants update the resident buffer in place on TPU (no 2x HBM
# spike during the patch); the non-donated twins run while a search still
# borrows the buffer (HostCorpus._borrow_device reader guard)
_patch_rows = jax.jit(_patch_rows_impl)
_patch_rows_donated = jax.jit(_patch_rows_impl, donate_argnums=(0,))
_patch_valid = jax.jit(_patch_valid_impl)
_patch_valid_donated = jax.jit(_patch_valid_impl, donate_argnums=(0,))
_patch_i8 = jax.jit(_patch_i8_impl)
_patch_i8_donated = jax.jit(_patch_i8_impl, donate_argnums=(0, 1))


# ----------------------------------------------------------------- host API
class HostCorpus:
    """Host-side state machine shared by DeviceCorpus (single chip) and
    parallel.ShardedCorpus (mesh): id->slot map, padded row matrix, tombstone
    removal, deferred ratio-triggered compaction, capacity growth, plus the
    block-granular dirty tracking + incremental H2D sync driver (subclasses
    supply _upload_full/_apply_patch for their device layout) and the
    write-behind uploader thread.

    Mirrors gpu.EmbeddingIndex host bookkeeping (ref: pkg/gpu/gpu.go:1224,
    Add/Remove :1378-1460; the reference's HNSW uses the same
    tombstone-then-rebuild idea, search.go:1215). `align` keeps the row count
    a multiple of the hardware tile / shard granularity.
    """

    def __init__(
        self,
        dims: int,
        align: int = LANE,
        capacity: int = 0,
        compact_ratio: float = 0.3,
        backend=None,
    ):
        self.dims = dims
        self.align = align
        self.compact_ratio = compact_ratio
        # device lifecycle manager (nornicdb_tpu.backend): every device
        # path gates through it BEFORE taking any lock, and serves from
        # the host arrays while it reports DEGRADED_CPU. None -> the
        # process-default manager, resolved lazily on first device use.
        self._backend = backend
        self._backend_registered = False
        cap = max(capacity, align)
        cap = ((cap + align - 1) // align) * align
        self._ids: list[Optional[str]] = []
        self._slot_of: dict[str, int] = {}
        self._host = np.zeros((cap, dims), np.float32)
        self._valid = np.zeros(cap, bool)
        self._tombstones = 0
        # dirty tracking is block-granular: mutators mark only the
        # BLOCK_ROWS-row blocks they touch; _full_dirty forces a whole-corpus
        # upload (first sync, grow/compact/clear, dtype change)
        self._dirty_blocks: set[int] = set()
        self._full_dirty = True
        self._compact_pending = False
        # guards host arrays + dirty sets + device-buffer swaps against the
        # write-behind uploader thread and concurrent searchers
        self._sync_lock = threading.RLock()
        # searches borrowing the device buffer; while > 0 the patcher must
        # not donate (free) the buffer they hold. device_arrays() leaks an
        # unscoped reference and clears _donation_ok for good.
        self._readers = 0
        self._donation_ok = True
        self.sync_stats = SyncStats()
        # mutation epoch: bumps on every write (stats / cache invalidation)
        self._epoch = 0
        # layout epoch: bumps ONLY when a mutation invalidates derived
        # layouts (IVF blocks hold row copies) — i.e. in-place overwrite of
        # a covered slot, or any slot-space remap (grow/compact/clear). New
        # ids and removals leave fitted layouts valid: fresh slots are in no
        # block, and removed slots filter out host-side at result time.
        self._layout_epoch = 0
        self._layout_slots: Optional[np.ndarray] = None  # bool per slot
        # write-behind uploader (start_uploader): coalesces dirty blocks in
        # the background so the query path usually finds a clean buffer
        self._uploader: Optional[threading.Thread] = None
        self._uploader_stop = threading.Event()
        self._uploader_wake = threading.Event()
        self._uploader_interval = 0.002
        # (capacity, k, grid, options) whose query-class programs were
        # compiled (warm_query_classes); the lock makes concurrent first
        # sights of one key compile once
        self._warm_classes: set[tuple] = set()
        self._warm_lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._slot_of)

    @property
    def capacity(self) -> int:
        return self._host.shape[0]

    # -- dirty-block bookkeeping (all called under _sync_lock) -------------
    def _mark_rows_dirty(self, start: int, stop: int) -> None:
        self._dirty_blocks.update(
            range(start // BLOCK_ROWS, (stop - 1) // BLOCK_ROWS + 1)
        )

    def _mark_all_dirty(self) -> None:
        self._full_dirty = True
        self._dirty_blocks.clear()

    def _note_overwrite(self, slot: int) -> None:
        """In-place update of a slot covered by a derived layout: the IVF
        blocks hold a COPY of the row, so the layout would serve the stale
        vector — it must rebuild (layout epoch bump)."""
        ls = self._layout_slots
        if ls is not None and slot < ls.size and ls[slot]:
            self._layout_epoch += 1

    def add(self, id_: str, vector: np.ndarray) -> None:
        v = np.asarray(vector, np.float32)
        norm = float(np.linalg.norm(v))
        if norm > 1e-12:
            v = v / norm
        with self._sync_lock:
            slot = self._slot_of.get(id_)
            if slot is None:
                if len(self._ids) >= self.capacity and self._compact_pending:
                    # reclaim tombstoned slots before paying for a capacity
                    # doubling: a write-only churn workload (no searches to
                    # trigger the deferred compaction) must stay bounded
                    self._compact()
                slot = len(self._ids)
                if slot >= self.capacity:
                    self._grow()
                self._ids.append(id_)
                self._slot_of[id_] = slot
            else:
                self._note_overwrite(slot)
            self._host[slot] = v
            self._valid[slot] = True
            self._mark_rows_dirty(slot, slot + 1)
            self._epoch += 1
        self._wake_uploader()

    def add_batch(self, ids: list[str], vectors: np.ndarray) -> None:
        if not ids:
            return
        vectors = np.atleast_2d(np.asarray(vectors, np.float32))
        norms = np.linalg.norm(vectors, axis=1, keepdims=True)
        vectors = vectors / np.maximum(norms, 1e-12)
        with self._sync_lock:
            all_new = len(set(ids)) == len(ids) and not any(
                i in self._slot_of for i in ids
            )
            if all_new:
                # bulk-ingest fast path: one slice assignment into the slot
                # tail instead of a Python loop per row
                if (
                    len(self._ids) + len(ids) > self.capacity
                    and self._compact_pending
                ):
                    self._compact()  # reclaim tombstones before growing
                start = len(self._ids)
                end = start + len(ids)
                if end > self.capacity:
                    self._grow(min_capacity=end)
                self._host[start:end] = vectors
                self._valid[start:end] = True
                self._ids.extend(ids)
                self._slot_of.update(
                    (id_, start + i) for i, id_ in enumerate(ids)
                )
                self._mark_rows_dirty(start, end)
            else:
                for i, id_ in enumerate(ids):
                    slot = self._slot_of.get(id_)
                    if slot is None:
                        if (
                            len(self._ids) >= self.capacity
                            and self._compact_pending
                        ):
                            self._compact()
                        slot = len(self._ids)
                        if slot >= self.capacity:
                            self._grow(min_capacity=slot + len(ids) - i)
                        self._ids.append(id_)
                        self._slot_of[id_] = slot
                    else:
                        self._note_overwrite(slot)
                    self._host[slot] = vectors[i]
                    self._valid[slot] = True
                    self._mark_rows_dirty(slot, slot + 1)
            self._epoch += 1
        self._wake_uploader()

    def remove(self, id_: str) -> bool:
        with self._sync_lock:
            slot = self._slot_of.pop(id_, None)
            if slot is None:
                return False
            self._ids[slot] = None
            self._valid[slot] = False
            self._tombstones += 1
            self._mark_rows_dirty(slot, slot + 1)
            self._epoch += 1
            if (
                self._ids
                and self._tombstones / len(self._ids) > self.compact_ratio
            ):
                # deferred: the full rewrite + full re-upload runs coalesced
                # on the write-behind uploader (or the next sync), never on
                # the caller's write path
                self._compact_pending = True
        self._wake_uploader()
        return True

    # -- inspection / lifecycle (ref: EmbeddingIndex Has/Get/Clear/Stats/
    # MemoryUsage/Serialize, pkg/gpu/gpu.go + gpu_test.go:630-800) ---------
    def has(self, id_: str) -> bool:
        with self._sync_lock:
            return id_ in self._slot_of

    def get(self, id_: str) -> Optional[np.ndarray]:
        """The stored (normalized) vector, or None when absent."""
        # slot lookup and row read must be one atomic view: the write-behind
        # uploader thread's deferred _compact() rebinds _slot_of/_host with a
        # remapped slot space, and a stale slot indexed into the new _host
        # would silently return another id's vector
        with self._sync_lock:
            slot = self._slot_of.get(id_)
            if slot is None:
                return None
            return self._host[slot].copy()

    def clear(self) -> None:
        with self._sync_lock:
            cap = self.capacity
            self._ids = []
            self._slot_of = {}
            self._host = np.zeros((cap, self.dims), np.float32)
            self._valid = np.zeros(cap, bool)
            self._tombstones = 0
            self._compact_pending = False
            self._mark_all_dirty()
            self._epoch += 1
            self._layout_epoch += 1
            # slot space was remapped: derived cluster layouts (DeviceCorpus
            # _assignments/IVF blocks) would index the wrong rows — same
            # reason _grow/_compact invalidate them
            clear_clusters = getattr(self, "clear_clusters", None)
            if callable(clear_clusters):
                clear_clusters()

    def stats(self) -> dict:
        return {
            "count": len(self._slot_of),
            "capacity": self.capacity,
            "dims": self.dims,
            "tombstones": self._tombstones,
            "epoch": self._epoch,
            "layout_epoch": self._layout_epoch,
            "dirty_blocks": len(self._dirty_blocks),
            "memory_bytes": self.memory_usage(),
            "sync": self.sync_stats.as_dict(),
        }

    def memory_usage(self) -> int:
        return int(self._host.nbytes + self._valid.nbytes)

    def export_host_state(self) -> dict:
        """Consistent copies of the host arrays + slot map for the
        cross-process shared-memory read plane (server/readplane.py):
        ``{"rows", "valid", "ids", "epoch", "count", "dims"}``.  The copy
        runs under _sync_lock so a racing in-place row overwrite can never
        tear an exported vector; the slot layout is exported AS IS (no
        forced compaction) so exported indices mean the same thing they
        mean to the in-process host/device search paths."""
        with self._sync_lock:
            return {
                "rows": self._host.copy(),
                "valid": self._valid.copy(),
                "ids": list(self._ids),
                "epoch": self._epoch,
                "count": len(self._slot_of),
                "dims": self.dims,
            }

    def save(self, path: str) -> None:
        """Persist live ids + vectors (tombstones are not serialized —
        matches the reference's compact-on-serialize behavior)."""
        # same atomic-view contract as get(): the uploader thread's deferred
        # _compact() rebinds _ids/_host with remapped slots, and a snapshot
        # torn across that rebind would checkpoint ids against other rows
        with self._sync_lock:
            live = [(i, id_) for i, id_ in enumerate(self._ids)
                    if id_ is not None]
            ids = np.asarray([id_ for _, id_ in live])
            vecs = (self._host[[i for i, _ in live]].copy()
                    if live else np.zeros((0, self.dims), np.float32))
        np.savez_compressed(path, ids=ids, vectors=vecs,
                            dims=np.asarray(self.dims))

    @classmethod
    def load(cls, path: str, **kwargs) -> "HostCorpus":
        with np.load(path, allow_pickle=False) as data:
            if any(k not in data for k in ("vectors", "ids", "dims")):
                raise ValueError(f"{path} is not a corpus checkpoint")
            dims = int(data["dims"])
            out = cls(dims=dims, **kwargs)
            vecs = data["vectors"]
            ids = [str(i) for i in data["ids"]]
            if ids:
                out.add_batch(ids, vecs)
        return out

    def _grow(self, min_capacity: int = 0) -> None:
        need = max(self.capacity * 2, min_capacity, self.align)
        new_cap = ((need + self.align - 1) // self.align) * self.align
        host = np.zeros((new_cap, self.dims), np.float32)
        valid = np.zeros(new_cap, bool)
        host[: self._host.shape[0]] = self._host
        valid[: self._valid.shape[0]] = self._valid
        self._host, self._valid = host, valid
        # shape change: the resident device buffer cannot be patched in place
        self._mark_all_dirty()
        self._layout_epoch += 1

    def _compact(self) -> None:
        live = [(i, id_) for i, id_ in enumerate(self._ids) if id_ is not None]
        host = np.zeros_like(self._host)
        valid = np.zeros_like(self._valid)
        ids: list[Optional[str]] = []
        slot_of: dict[str, int] = {}
        for new_slot, (old_slot, id_) in enumerate(live):
            host[new_slot] = self._host[old_slot]
            valid[new_slot] = True
            ids.append(id_)
            slot_of[id_] = new_slot
        self._host, self._valid = host, valid
        self._ids, self._slot_of = ids, slot_of
        self._tombstones = 0
        self._compact_pending = False
        self._mark_all_dirty()
        self._epoch += 1
        self._layout_epoch += 1

    # -- backend lifecycle gate --------------------------------------------
    def _backend_mgr(self):
        """This corpus's BackendManager (process default unless injected),
        registered for recovery re-upload on first resolution."""
        mgr = self._backend
        if mgr is None:
            mgr = self._backend = _backend.manager()
        if not self._backend_registered:
            self._backend_registered = True
            mgr.register_corpus(self)
        return mgr

    def _device_ok_nowait(self) -> bool:
        """Non-blocking state read for code already inside a lock
        (``_sync``), where *waiting* on acquisition is exactly the bug
        NL-DEV01 bans."""
        return self._backend_mgr().ready()

    def _device_gate(self) -> bool:
        """Is the device serving?  The cold entry of a search: blocks —
        bounded by the manager's acquire timeout, on the manager's worker
        thread, with NO caller lock held — and honors the fallback policy
        (raises DeviceUnavailable under "fail")."""
        mgr = self._backend_mgr()
        mgr.require_ready()
        return mgr.ready()

    def _on_backend_recovered(self, mode: str) -> None:
        """The manager re-acquired the device: schedule the re-upload.
        ``mode="full"`` assumes device memory was lost — drop the resident
        buffers and mark everything dirty (next sync is a whole-corpus
        transfer).  ``mode="dirty"`` trusts a surviving resident buffer
        (transient hang) and only patches the blocks written while
        degraded, which the dirty tracking already holds."""
        with self._sync_lock:
            if not (mode == "dirty" and self._device_ready()):
                self._dev = None
                self._dev_valid = None
                if getattr(self, "_dev_i8", None) is not None:
                    self._dev_i8 = None
                self._mark_all_dirty()
                # device-resident cluster state (IVF blocks, centroids)
                # died with the device too — a post-recovery pruned search
                # must not dereference buffers of the lost incarnation.
                # Drop it and queue the last fit's HOST copy (id-based, so
                # it survives slot remaps) for re-install once READY.
                clear = getattr(self, "clear_clusters", None)
                if callable(clear):
                    last_fit = getattr(self, "_last_fit_host", None)
                    clear()
                    self._pending_clusters = last_fit
        self._wake_uploader()

    def _on_backend_ready(self) -> None:
        """Called by the manager AFTER the READY transition lands (the
        _on_backend_recovered wake can be consumed by an uploader that
        still observed RECOVERING): guarantees the re-upload runs in the
        background instead of inline on the first post-recovery query."""
        self._wake_uploader()

    def _search_host(
        self, q: np.ndarray, k: int, min_similarity: float,
        defer: bool = False,
    ) -> Sequence[list[tuple[str, float]]]:
        """DEGRADED_CPU serving: exact NumPy top-k over the host arrays.

        Scoring holds _sync_lock: writers mutate _host rows IN PLACE, and
        a scan racing an overwrite would read torn vectors (half-old,
        half-new — the atomic-view contract get()/save() keep for the
        same reason; the device path reads immutable buffers instead).
        Writers briefly queue behind a degraded-mode scan — correctness
        over throughput while the accelerator is down."""
        self._backend_mgr().note_fallback("search")
        return self._host_exact_topk(q, k, min_similarity, defer)

    def _host_exact_topk(
        self, q: np.ndarray, k: int, min_similarity: float,
        defer: bool = False,
    ) -> Sequence[list[tuple[str, float]]]:
        """Exact f32 top-k over the host arrays — the scoring core of
        ``_search_host``, reusable without the degraded-fallback accounting
        (the int8-resident corpus serves its ``exact=True`` contract here:
        quantized device membership can't be exact, the host mirror is)."""
        norms = np.linalg.norm(q, axis=1, keepdims=True)
        qn = q / np.maximum(norms, 1e-12)
        with self._sync_lock:
            if self._compact_pending:
                self._compact()
            vals, idx = host_topk(
                qn, self._host, self._valid, min(k, self.capacity)
            )
            ids = self._ids
        return self._format_results(
            vals, idx, q.shape[0], k, min_similarity, ids=ids, defer=defer,
        )

    # -- device sync engine ------------------------------------------------
    # Subclasses provide the actual device buffers through three hooks:
    # _device_ready (is there a patchable resident buffer), _upload_full
    # (whole-corpus transfer) and _apply_patch (jitted dynamic_update_slice
    # of one contiguous row run). The driver below owns the policy: deferred
    # compaction, patch-vs-full choice, run coalescing, stats.
    def _device_ready(self) -> bool:
        dev = getattr(self, "_dev", None)
        return dev is not None and int(dev.shape[0]) == self.capacity

    def _upload_full(self) -> None:
        raise NotImplementedError

    def _apply_patch(
        self, start_row: int, rows: np.ndarray, valid_rows: np.ndarray,
        donate: bool,
    ) -> None:
        raise NotImplementedError

    def _sync(self, _record_stall: bool = True) -> None:
        """Bring the resident device buffer up to date with the host.

        Incremental path: dirty BLOCK_ROWS-row blocks coalesce into
        contiguous runs patched into the resident buffer via jitted
        dynamic_update_slice — O(dirty rows) transferred, not O(capacity).
        Full upload only on first sync, grow/compact/clear, or when most of
        the corpus is dirty. In-flight searches always see either the
        pre-patch or post-patch buffer, never a half-patched one: a patch
        builds a new (immutable) array while borrowers hold the old one, and
        the old buffer is donated back to the allocator only when nobody
        borrows it (ref: shouldAutoSync gpu.go:1473 — which re-uploaded the
        whole corpus on any write)."""
        if not self._device_ok_nowait():
            # backend degraded: keep accumulating dirty state on the host;
            # the manager's recovery notification re-uploads when the
            # device comes back. NEVER wait here — this runs under
            # _sync_lock, the exact shape of the round-5 deadlock.
            return
        with self._sync_lock:
            if self._compact_pending:
                self._compact()  # coalesced: one rewrite for the whole burst
            needs_full = self._full_dirty or not self._device_ready()
            if not needs_full and not self._dirty_blocks:
                return
            t0 = time.perf_counter()
            s = self.sync_stats
            cap_blocks = max(1, self.capacity // BLOCK_ROWS)
            if (
                not needs_full
                and len(self._dirty_blocks)
                > cap_blocks * FULL_SYNC_DIRTY_FRACTION
            ):
                needs_full = True
            if needs_full:
                with _tracer.span("device.sync", {"mode": "full"}):
                    self._upload_full()
                s.full_uploads += 1
                nbytes = int(self._host.nbytes + self._valid.nbytes)
                s.bytes_uploaded += nbytes
                _SYNC_FULL_CELL.observe(time.perf_counter() - t0)
                _SYNC_FULL_BYTES_CELL.observe(nbytes)
            else:
                donate = self._readers == 0 and self._donation_ok
                patch_bytes = 0
                with _tracer.span("device.sync", {"mode": "patch"}) as sp:
                    for start_b, n_b in _coalesce_runs(
                        sorted(self._dirty_blocks), cap_blocks
                    ):
                        r0 = start_b * BLOCK_ROWS
                        r1 = min((start_b + n_b) * BLOCK_ROWS, self.capacity)
                        rows, vrows = self._host[r0:r1], self._valid[r0:r1]
                        self._apply_patch(r0, rows, vrows, donate)
                        nbytes = int(rows.nbytes + vrows.nbytes)
                        patch_bytes += nbytes
                        s.patch_bytes += nbytes
                        s.bytes_uploaded += nbytes
                        s.rows_patched += r1 - r0
                    sp.set_attr("bytes", patch_bytes)
                s.patches += 1
                _SYNC_PATCH_CELL.observe(time.perf_counter() - t0)
                _SYNC_PATCH_BYTES_CELL.observe(patch_bytes)
            self._full_dirty = False
            self._dirty_blocks.clear()
            if _record_stall:
                s.query_stall_s += time.perf_counter() - t0

    @contextlib.contextmanager
    def _borrow_device(self):
        """Sync, then pin the serving buffer for the duration of a search.
        While any borrower is active the patcher will not donate the buffer
        out from under it — this is what lets the write-behind uploader
        double-buffer: readers keep the old snapshot, the patch lands in a
        new one.

        Yields (dev, valid, i8, ids, slot_of). ids/slot_of are the host
        mappings captured under the lock: compaction/clear REBIND them (new
        list/dict), so a borrower resolving slots of the borrowed buffer
        through these references can never see a background compaction's
        remapped slot space mid-search. In-place mutations (remove's
        tombstone, add's append) remain visible, which only ever hides
        just-removed ids — never misattributes."""
        with self._sync_lock:
            self._sync()
            self._readers += 1
            dev, valid = self._dev, self._dev_valid
            i8 = getattr(self, "_dev_i8", None)
            ids, slot_of = self._ids, self._slot_of
        if dev is None:
            # the backend degraded between the caller's gate and the sync
            # (or was never acquired): there is no resident buffer to
            # borrow — callers catch this and serve the host path
            with self._sync_lock:
                self._readers -= 1
            raise DeviceUnavailable("no resident device buffer (degraded)")
        try:
            yield dev, valid, i8, ids, slot_of
        finally:
            with self._sync_lock:
                self._readers -= 1

    # -- write-behind uploader ---------------------------------------------
    def start_uploader(self, interval: float = 0.002) -> None:
        """Start the write-behind H2D sync thread: it coalesces dirty blocks
        and stages them between queries, so a query arriving after a write
        burst waits only for whatever the uploader has not staged yet (a
        bounded patch), never a full transfer. `interval` is the coalescing
        window after the first write of a burst."""
        with self._sync_lock:
            if self._uploader is not None:
                return
            self._uploader_interval = interval
            self._uploader_stop = threading.Event()
            self._uploader_wake = threading.Event()
            self._uploader = threading.Thread(
                target=self._uploader_loop, name="nornicdb-uploader",
                daemon=True,
            )
            self._uploader.start()

    def stop_uploader(self) -> None:
        with self._sync_lock:
            t, self._uploader = self._uploader, None
            # capture THIS thread's events under the lock: a concurrent
            # start_uploader() swaps in fresh ones, and signalling those
            # would kill the new thread while the old one runs forever
            stop, wake = self._uploader_stop, self._uploader_wake
        if t is None:
            return
        stop.set()
        wake.set()
        t.join(timeout=5.0)

    def _wake_uploader(self) -> None:
        if self._uploader is not None:
            self._uploader_wake.set()

    def _uploader_loop(self) -> None:
        stop, wake = self._uploader_stop, self._uploader_wake
        while not stop.is_set():
            if not wake.wait(timeout=0.25):
                continue
            wake.clear()
            # coalescing window: let the write burst accumulate so one patch
            # covers it, instead of one dispatch per row
            if stop.wait(self._uploader_interval):
                break
            try:
                self._sync(_record_stall=False)
                self.sync_stats.uploader_runs += 1
            except Exception:
                logger.exception("write-behind device sync failed")

    def _format_results(
        self,
        vals: np.ndarray,
        idx: np.ndarray,
        n_queries: int,
        k: int,
        min_similarity: float,
        ids: Optional[list[Optional[str]]] = None,
        defer: bool = False,
        padded_rows: int = 0,
    ) -> Sequence[list[tuple[str, float]]]:
        """Resolve slot indices to ids. `ids` must be the slot map captured
        with the buffer the indices came from (_borrow_device) — resolving
        against live self._ids would misattribute results if a background
        compaction remapped the slot space mid-search. Delegates to the
        shared epilogue (ops.host_search.format_topk_results) so the
        cross-process read plane resolves identically by construction.
        ``defer`` answers a LazyRows over the same arrays and id snapshot:
        each row is resolved (one ``corpus.format`` stage) when it is
        asked for."""
        ids = self._ids if ids is None else ids
        if defer:
            row = self._row_resolver(vals, idx, k, min_similarity, ids)
            return LazyRows(lambda: row, n_queries, padded_rows)
        with _tracer.stage("corpus.format", self.sync_stats,
                           "search_format_seconds"):
            return format_topk_results(
                vals, idx, n_queries, k, min_similarity, ids
            )

    def _row_resolver(self, vals, idx, k, min_similarity, ids):
        """Row index -> that row's ``[(id, score)]``, formatted when asked
        for (one ``corpus.format`` stage a row): what a LazyRows indexes."""
        return lambda i: self._format_results(
            vals[i:i + 1], idx[i:i + 1], 1, k, min_similarity, ids,
        )[0]

    def warm_query_classes(self, k: int, max_batch: int, **options) -> None:
        """Compile this ``k``'s program for every query class up to
        ``max_batch``, the first time ``k`` is seen at this capacity and
        with these search ``options``; a set lookup after that.  The first
        query of a ``k`` pays a compile anyway: it pays for the grid
        (classes side by side, one scan of zero queries each), so that no
        later batch size meets a new program on a request's path.  While
        the backend serves from the host there is nothing to compile."""
        key = (self.capacity, k, max_batch, tuple(sorted(options.items())))
        if key in self._warm_classes or len(self._slot_of) == 0:
            return
        if not self._device_gate():
            return
        with self._warm_lock:
            if key in self._warm_classes:
                return

            ctx = _tracer.capture()  # the grid is this request's cost

            def scan(cls: int) -> None:
                try:
                    with _tracer.attach(ctx):
                        rows = self.search(
                            np.zeros((cls, self.dims), np.float32), k,
                            defer=True, **options)
                        if isinstance(rows, LazyRows):
                            rows.fetch()  # the program ran to its end
                except Exception:
                    # the class's own first batch will raise it to a caller
                    logger.warning("query class %d (k=%d) failed to warm",
                                   cls, k, exc_info=True)

            threads = [
                threading.Thread(target=scan, args=(cls,),
                                 name="nornicdb-class-warm", daemon=True)
                for cls in query_classes(max_batch)
            ]
            for t in threads:
                t.start()
            # NL-LK02 suppression: _warm_lock exists for this wait (a second
            # first sight of the key waits for the one compile); it is a
            # leaf, taken with no other lock held, and the scans take none
            # of their caller's
            for t in threads:
                t.join()  # nornlint: disable=NL-LK02
            self._warm_classes.add(key)


class DeviceCorpus(HostCorpus):
    """Single-device resident, padded, normalized embedding matrix with
    incremental dirty-block host sync: writes patch only the 128-row blocks
    they touched into the resident buffer (ref: gpu.EmbeddingIndex
    pkg/gpu/gpu.go:1224 — flat buffer, shouldAutoSync :1473 which re-uploads
    everything, Search :1519, ScoreSubset :1554).

    Optional IVF-style cluster pruning (ref: ClusterIndex kmeans.go:144,
    SearchWithClusters :816, search-side candidate gen
    kmeans_candidate_gen.go): after cluster() the search scores only the
    rows assigned to the n_probe nearest centroids, cutting FLOPs ~K/n_probe
    at a small recall cost. Stale assignments degrade recall, never
    correctness (scores stay exact); recluster on the embed queue's
    debounced trigger.
    """

    def __init__(
        self,
        dims: int,
        capacity: int = LANE,
        dtype=jnp.float32,
        compact_ratio: float = 0.3,
        quantize: bool = False,
        backend=None,
    ):
        super().__init__(dims, align=LANE, capacity=capacity,
                         compact_ratio=compact_ratio, backend=backend)
        self.dtype = dtype
        # int8 serving mirror (ref: the CUDA path's fp16 storage trade-off,
        # gpu-acceleration.md — here int8 runs the MXU at 2x the bf16 rate)
        self.quantize = quantize
        self._dev: Optional[jax.Array] = None
        self._dev_valid: Optional[jax.Array] = None
        self._dev_i8: Optional[tuple[jax.Array, jax.Array]] = None
        # IVF state: (K, D) centroids + per-slot assignment (-1 = unassigned)
        self._centroids: Optional[jax.Array] = None
        self._assignments: Optional[np.ndarray] = None
        # fused cluster-contiguous layout (ops/ivf.py); valid only while
        # its epoch matches the corpus mutation epoch
        self._ivf = None
        # cluster fit delivered while DEGRADED_CPU: the device install is
        # deferred, not dropped — applied by _on_backend_ready on recovery
        self._pending_clusters: Optional[tuple] = None
        # host copy (centroids ndarray, id->cluster map) of the last
        # installed fit: full-mode recovery re-installs from this after
        # dropping the device-resident cluster buffers
        self._last_fit_host: Optional[tuple] = None
        # fleet telemetry: HBM residency provider (weakref'd; summed per
        # component at /metrics render — telemetry/deviceprof.py)
        from nornicdb_tpu.telemetry import deviceprof as _deviceprof

        _deviceprof.register_hbm(self, DeviceCorpus._hbm_bytes)

    @staticmethod
    def _hbm_bytes(self) -> dict:
        """Lock-free device-resident byte accounting (scrape thread)."""
        out = {"corpus_f32": 0, "corpus_int8": 0, "ivf": 0}
        dev, valid, i8, ivf = (self._dev, self._dev_valid, self._dev_i8,
                               self._ivf)
        for arr in (dev, valid):
            if arr is not None:
                out["corpus_f32"] += int(arr.size) * arr.dtype.itemsize
        if i8 is not None:
            for arr in i8:
                out["corpus_int8"] += int(arr.size) * arr.dtype.itemsize
        if ivf is not None:
            for name in ("blocks", "counts", "slotmap", "centroids",
                         "residual", "residual_slots", "residual_valid",
                         "block_scales", "residual_scales"):
                arr = getattr(ivf, name, None)
                # host-side layout fields (np slotmaps) are not HBM
                if arr is not None and not isinstance(arr, np.ndarray):
                    out["ivf"] += int(arr.size) * arr.dtype.itemsize
        return out

    # -- cluster pruning ----------------------------------------------------
    def cluster(self, k: int = 0, iters: int = 10, seed: int = 0,
                sample: int = 0) -> int:
        """Fit k-means over live rows (ref: ClusterIndex.Cluster kmeans.go:232).
        Returns the cluster count; 0 when nothing was installed (too few
        rows, or the corpus mutated underneath the fit).  ``sample`` caps
        the Lloyd fit (ops.kmeans.kmeans_fit) for very large corpora.

        The fit itself runs outside the lock (it can take seconds at
        scale); install is optimistic: snapshot the rows + layout epoch
        under the lock, and install only if the epoch is unchanged — a
        background compaction (write-behind uploader) or an overwrite of a
        snapshot row during the fit would otherwise stamp a layout built
        from stale slots as current."""
        from nornicdb_tpu.ops.kmeans import kmeans_fit

        if not self._device_gate():
            return 0  # degraded: pruning is a device-path optimization
        with self._sync_lock:
            live = [i for i, id_ in enumerate(self._ids) if id_ is not None]
            if len(live) < 2:
                return 0
            data = self._host[live]  # fancy indexing copies: stable snapshot
            epoch_at_read = self._layout_epoch
            # widen the overwrite guard to the snapshot rows so an in-place
            # update during the fit bumps the epoch and voids the install
            mask = np.zeros(self.capacity, bool)
            mask[live] = True
            if (
                self._layout_slots is not None
                and self._layout_slots.size == self.capacity
            ):
                mask |= self._layout_slots
            self._layout_slots = mask
        res = kmeans_fit(data, k=k, iters=iters, seed=seed, sample=sample)
        # H2D transfer OUTSIDE the lock (NL-DEV01): only the pointer
        # install runs in the critical section
        centroids_dev = jnp.asarray(res.centroids, dtype=self.dtype)
        with self._sync_lock:
            if self._layout_epoch != epoch_at_read:
                return 0  # slot space moved mid-fit: caller may recluster
            assignments = np.full(self.capacity, -1, np.int32)
            for row, slot in enumerate(live):
                assignments[slot] = res.assignments[row]
            self._centroids = centroids_dev
            self._assignments = assignments
            # id-based host copy: full-mode recovery re-installs from this
            self._last_fit_host = (
                np.asarray(res.centroids, np.float32),
                {
                    self._ids[slot]: int(res.assignments[row])
                    for row, slot in enumerate(live)
                    if slot < len(self._ids) and self._ids[slot] is not None
                },
            )
        self._build_ivf_layout(np.asarray(live), res.assignments,
                               res.centroids, expect_epoch=epoch_at_read)
        return res.k

    def _build_ivf_layout(self, live_slots: np.ndarray,
                          live_assignments: np.ndarray,
                          centroids: np.ndarray,
                          expect_epoch: Optional[int] = None) -> None:
        """Cluster-contiguous block layout for the fused one-program IVF
        path (ops/ivf.py). Invalidated by any corpus mutation.

        The build (and its H2D transfers) runs OUTSIDE the lock
        (NL-DEV01); install is optimistic: the row snapshot pins the
        layout epoch, and the built layout installs only if the epoch is
        unchanged — an overwrite/compaction during the build voids it
        (the widened ``_layout_slots`` mask makes covered-row overwrites
        bump the epoch, same contract as ``cluster()``)."""
        from nornicdb_tpu.ops.ivf import build_ivf_layout

        with self._sync_lock:
            if expect_epoch is not None and self._layout_epoch != expect_epoch:
                return  # slot space moved since the caller resolved slots
            epoch_at_read = self._layout_epoch
            rows = self._host[live_slots]  # fancy indexing copies: snapshot
            # slots the layout copies rows from: an in-place overwrite of
            # any of these bumps _layout_epoch (invalidates the layout);
            # writes to OTHER slots leave it serving correct vectors
            mask = np.zeros(self.capacity, bool)
            mask[live_slots] = True
            self._layout_slots = mask
        layout = build_ivf_layout(
            rows, live_slots, live_assignments, centroids,
            dtype=self.dtype, epoch=epoch_at_read,
        )
        with self._sync_lock:
            if self._layout_epoch != epoch_at_read:
                return  # mutated mid-build: discard the stale layout
            self._ivf = layout

    def clear_clusters(self) -> None:
        self._centroids = None
        self._assignments = None
        self._ivf = None
        self._layout_slots = None
        self._pending_clusters = None

    def _on_backend_ready(self) -> None:
        """Post-recovery: wake the uploader (base) and install any cluster
        fit that arrived while degraded.  The install's device transfers
        run on a throwaway thread, NEVER on the manager's probe thread —
        if the flaky device hangs again mid-install, the watchdog that
        detects hangs must not be the thread that hung (the install
        thread strands harmlessly: set_clusters holds no lock across its
        device ops)."""
        super()._on_backend_ready()
        with self._sync_lock:
            pending, self._pending_clusters = self._pending_clusters, None
            if pending is None and self._ivf is None:
                # a degraded-era grow/compact ran clear_clusters(), which
                # drops the stash along with the layout — but the id-based
                # host copy survives slot remaps and still describes the
                # newest fit. Reinstall it instead of serving full scans
                # until the next periodic recluster (the set_clusters
                # contract: a degraded-era fit is NOT discarded).
                pending = self._last_fit_host
        if pending is None:
            return

        def _install() -> None:
            try:
                self.set_clusters(pending[0], pending[1])
            except Exception:
                logger.exception("post-recovery cluster install failed")

        threading.Thread(
            target=_install, name="nornicdb-cluster-reinstall", daemon=True,
        ).start()

    def set_clusters(
        self, centroids: np.ndarray, assignments_by_id: dict[str, int]
    ) -> None:
        """Install externally computed clusters (e.g. the search service's
        fit) without re-running k-means. The id->slot resolution sees one
        consistent slot space under the sync lock; the H2D transfer and
        layout build run OUTSIDE it (NL-DEV01) with an optimistic
        epoch-checked install (the write-behind uploader may compact
        concurrently — a remap voids the stale layout)."""
        if not self._device_ok_nowait():
            # degraded: the fit is NOT discarded — stash it host-side and
            # install on recovery (_on_backend_ready), so pruned search
            # comes back with the device instead of waiting for the next
            # periodic re-cluster. Full scan keeps serving meanwhile.
            with self._sync_lock:
                self._pending_clusters = (
                    np.asarray(centroids, np.float32),
                    dict(assignments_by_id),
                )
                self._last_fit_host = self._pending_clusters
            return
        fit_host = (np.asarray(centroids, np.float32), dict(assignments_by_id))
        centroids_dev = jnp.asarray(centroids, dtype=self.dtype)
        with self._sync_lock:
            self._last_fit_host = fit_host
            slot_assignments = np.full(self.capacity, -1, np.int32)
            for id_, c in assignments_by_id.items():
                slot = self._slot_of.get(id_)
                if slot is not None:
                    slot_assignments[slot] = c
            self._centroids = centroids_dev
            self._assignments = slot_assignments
            # the old layout describes the replaced clustering — drop it
            # even when no live rows match (else the epoch guard keeps
            # serving it); a stashed degraded-era fit is superseded too
            self._ivf = None
            self._layout_slots = None
            self._pending_clusters = None
            live = np.nonzero((slot_assignments >= 0) & self._valid)[0]
            epoch_at_read = self._layout_epoch
        if live.size:
            self._build_ivf_layout(live, slot_assignments[live],
                                   np.asarray(centroids, np.float32),
                                   expect_epoch=epoch_at_read)

    def _grow(self, min_capacity: int = 0) -> None:
        super()._grow(min_capacity)
        # slot space changed shape: stale cluster state would crash/corrupt
        # pruned search — drop it until the next recluster
        self.clear_clusters()

    def _compact(self) -> None:
        super()._compact()
        # compaction remaps slots: old assignments index the wrong rows
        self.clear_clusters()

    def _pruned_search(
        self, q: np.ndarray, k: int, min_similarity: float, n_probe: int,
        exact: bool,
    ) -> Optional[list[list[tuple[str, float]]]]:
        """Score only rows in the n_probe nearest clusters; None when no
        cluster index is fitted (caller falls back to the full scan).

        Buffer, id map, cluster state and the layout-epoch check are all
        captured under ONE lock hold (and the sync — including any pending
        compaction — runs first), so a background compaction racing this
        search can only ever rebind state we no longer read: everything
        below resolves against the captured snapshot."""
        with self._sync_lock:
            self._sync()
            self._readers += 1
            corpus = self._dev
            ids, valid_host = self._ids, self._valid
            centroids, assignments = self._centroids, self._assignments
            layout = self._ivf
            layout_ok = (
                layout is not None and layout.epoch == self._layout_epoch
            )
        try:
            if corpus is None or centroids is None or assignments is None:
                return None
            # fused one-program path: valid while the layout matches the
            # LAYOUT epoch, which bumps only when a covered row was
            # overwritten in place or the slot space remapped
            # (grow/compact/clear). Plain adds and removes keep the layout
            # serving: new rows are merely invisible to pruned search until
            # the next recluster (recall, not correctness) and removed rows
            # filter out through the captured id map below.
            if layout_ok:
                from nornicdb_tpu.ops.ivf import ivf_search

                vals, slots = ivf_search(layout, q, k, n_probe)
                out: list[list[tuple[str, float]]] = []
                for qi in range(vals.shape[0]):
                    row: list[tuple[str, float]] = []
                    for s, slot in zip(vals[qi], slots[qi]):
                        if (
                            slot < 0 or not np.isfinite(s)
                            or s < min_similarity
                        ):
                            continue
                        id_ = ids[slot] if slot < len(ids) else None
                        if id_ is not None:
                            row.append((id_, float(s)))
                    out.append(row[:k])
                return out
            n_probe = min(n_probe, int(centroids.shape[0]))
            return self._pruned_scan(
                q, k, min_similarity, n_probe, corpus, ids, valid_host,
                centroids, assignments,
            )
        finally:
            with self._sync_lock:
                self._readers -= 1

    def _pruned_scan(
        self, q: np.ndarray, k: int, min_similarity: float, n_probe: int,
        corpus: jax.Array, ids: list[Optional[str]], valid_host: np.ndarray,
        centroids: jax.Array, assignments: np.ndarray,
    ) -> list[list[tuple[str, float]]]:
        """Assignment-mask fallback pruning over the synced device corpus.
        All host state comes in as the snapshot captured with the buffer."""
        from nornicdb_tpu.ops.kmeans import nearest_clusters

        out: list[list[tuple[str, float]]] = []
        for qi in range(q.shape[0]):
            probes = np.asarray(
                nearest_clusters(
                    jnp.asarray(q[qi], dtype=self.dtype), centroids, n_probe
                )
            )
            mask = np.isin(assignments, probes) & valid_host
            slots = np.nonzero(mask)[0]
            if slots.size == 0:
                out.append([])
                continue
            # pad the candidate set to a power-of-two bucket so the jitted
            # score program caches a handful of shapes instead of recompiling
            # per query (dynamic shapes were 6x slower than the full scan)
            bucket = max(1024, 1 << (int(slots.size) - 1).bit_length())
            padded = np.zeros(bucket, np.int64)
            padded[: slots.size] = slots
            qd = l2_normalize(jnp.asarray(q[qi], dtype=self.dtype).reshape(-1))
            scores = np.asarray(
                score_subset(qd, corpus, jnp.asarray(padded)), np.float32
            )[: slots.size]
            order = np.argsort(-scores)[:k]
            row = []
            for j in order:
                s = float(scores[j])
                if s < min_similarity:
                    continue
                id_ = ids[slots[j]]
                if id_ is not None:
                    row.append((id_, s))
            out.append(row)
        return out

    def _upload_full(self) -> None:
        """Whole-corpus H2D transfer (first sync / grow / compact / clear).

        NL-DEV01 suppressions: these transfers run under _sync_lock by
        design — they must see the host arrays and dirty bookkeeping as
        one atomic view. They are WARM, never cold: _sync gates on
        _device_ok_nowait() first, so the backend was acquired by the
        manager's worker thread before any of these can execute."""
        self._dev = jnp.asarray(  # nornlint: disable=NL-DEV01
            self._host, dtype=self.dtype)
        self._dev_valid = jnp.asarray(self._valid)  # nornlint: disable=NL-DEV01
        if self.quantize:
            from nornicdb_tpu.ops.pallas_kernels import quantize_rows

            self._dev_i8 = quantize_rows(self._dev)

    def _apply_patch(
        self, start_row: int, rows: np.ndarray, valid_rows: np.ndarray,
        donate: bool,
    ) -> None:
        """Patch one contiguous dirty run into the resident buffers; the
        int8 serving mirror requantizes only the patched rows.

        NL-DEV01 suppressions: warm transfers under _sync_lock by design
        (same rationale as _upload_full — gated upstream, atomic view)."""
        start = np.int32(start_row)
        # one H2D conversion feeds both the f32/bf16 patch and the int8
        # requantization — the rows transfer once, not per consumer
        rows_dev = jnp.asarray(  # nornlint: disable=NL-DEV01
            rows, dtype=self.dtype)
        try:
            patch = _patch_rows_donated if donate else _patch_rows
            self._dev = patch(self._dev, rows_dev, start)
            vpatch = _patch_valid_donated if donate else _patch_valid
            self._dev_valid = vpatch(
                self._dev_valid,
                jnp.asarray(valid_rows),  # nornlint: disable=NL-DEV01
                start,
            )
            if self.quantize and self._dev_i8 is not None:
                qpatch = _patch_i8_donated if donate else _patch_i8
                self._dev_i8 = qpatch(
                    self._dev_i8[0], self._dev_i8[1], rows_dev, start,
                )
        except Exception:
            # a failing donated patch has CONSUMED an unknown subset of
            # the resident buffers — drop them all so _device_ready()
            # reports false and the next _sync rebuilds via _upload_full
            # instead of patching a poisoned buffer (NL-JAX04)
            self._dev = None
            self._dev_valid = None
            self._dev_i8 = None
            raise

    def device_arrays(self) -> tuple[jax.Array, jax.Array]:
        """Legacy unguarded access to the resident buffers. Callers may hold
        the returned arrays indefinitely, so donation is permanently
        disabled for this corpus the moment anyone uses this — otherwise a
        later patch would free a buffer the caller still reads. Prefer
        _borrow_device, which scopes the pin to the search."""
        self._device_gate()  # cold acquisition happens HERE, not under lock
        with self._sync_lock:
            self._donation_ok = False
            self._sync()
            if self._dev is None:
                raise DeviceUnavailable(
                    "backend degraded: no resident device buffer"
                )
            return self._dev, self._dev_valid

    def search(
        self,
        queries: np.ndarray,
        k: int,
        min_similarity: float = -1.0,
        exact: bool = False,
        n_probe: int = 0,
        streaming=None,
        defer: bool = False,
    ) -> Sequence[list[tuple[str, float]]]:
        """Brute-force cosine top-k. Returned scores are exact; with the
        default exact=False, candidate membership uses the TPU-native
        approx_max_k or (on TPU at scale, the default serving path) the
        streaming Pallas kernel — both honoring the ~0.95 recall contract of
        the reference's HNSW ANN path; exact=True gives recall 1.0 at the
        cost of a full sort. With n_probe > 0 and a fitted cluster index,
        only the n_probe nearest clusters are scored (IVF pruning,
        ref: SearchWithClusters kmeans.go:816). Returns per-query
        [(id, score)] filtered by min_similarity (ref: Search gpu.go:1519,
        MinSimilarity semantics search.go:157-205).  The block is scanned at
        its query class (pad_query_block); ``defer`` leaves each row's id
        resolution to whoever indexes it (LazyRows)."""
        if len(self._slot_of) == 0:
            return [[] for _ in range(np.atleast_2d(queries).shape[0])]
        q = np.atleast_2d(np.asarray(queries, np.float32))
        # lifecycle gate FIRST, before any lock: a cold backend acquires on
        # the manager's worker thread (bounded by the config timeout), a
        # degraded one routes this search to the exact host path
        if not self._device_gate():
            return self._search_host(q, k, min_similarity, defer)
        from nornicdb_tpu.telemetry import deviceprof as _deviceprof

        b = q.shape[0]
        try:
            if n_probe > 0:
                t0 = time.perf_counter()
                pruned = self._pruned_search(
                    q, k, min_similarity, n_probe, exact
                )
                if pruned is not None:
                    self.sync_stats.device_dispatches += 1
                    # unified program ledger (fleet telemetry plane):
                    # shape class = pow2 batch, bounded like the jit
                    # shape classes themselves
                    _deviceprof.record_execute(
                        "search", "ivf",
                        _deviceprof.pow2_class(q.shape[0], "b"),
                        time.perf_counter() - t0,
                    )
                    return pruned
            stats = self.sync_stats
            with contextlib.ExitStack() as borrowed:
                with _tracer.stage("corpus.dispatch", stats,
                                   "search_dispatch_seconds") as dispatch:
                    corpus, valid, dev_i8, ids, _ = borrowed.enter_context(
                        self._borrow_device())
                    kk = min(k, self.capacity)
                    block = pad_query_block(q)
                    if self.dtype != jnp.float32:
                        block = jnp.asarray(block, dtype=self.dtype)
                    # an f32 block goes to the jitted normalize as it is:
                    # the upload rides that call instead of one of its own
                    vals, idx = topk_backend(
                        l2_normalize(block),
                        corpus, valid, kk, exact=exact, streaming=streaming,
                        quantized=dev_i8 if self.quantize else None,
                    )
                # launched: the borrow now ends with the read-back, which a
                # deferred answer leaves to whoever fetches it
                held = borrowed.pop_all()
        except DeviceUnavailable:
            # degraded between the gate and the borrow
            return self._search_host(q, k, min_similarity, defer)

        def read_back() -> tuple[np.ndarray, np.ndarray]:
            # materialize INSIDE the borrow: the computation must finish
            # before the patcher may donate the buffer it reads
            with held, _tracer.stage("corpus.fetch", stats,
                                     "search_fetch_seconds") as fetched:
                vals_np = np.asarray(vals, np.float32)[:b]
                idx_np = np.asarray(idx)[:b]
            stats.device_dispatches += 1
            _deviceprof.record_execute(
                "search", "dense", f"b{query_class(b)}",
                dispatch.seconds + fetched.seconds,
            )
            return vals_np, idx_np

        if not defer:
            try:
                vals_np, idx_np = read_back()
            except DeviceUnavailable:
                return self._search_host(q, k, min_similarity)
            return self._format_results(
                vals_np, idx_np, b, k, min_similarity, ids=ids,
            )

        def fetch():
            try:
                vals_np, idx_np = read_back()
            except DeviceUnavailable:
                return self._search_host(q, k, min_similarity).__getitem__
            return self._row_resolver(vals_np, idx_np, k, min_similarity, ids)

        return LazyRows(fetch, b, query_class(b) - b)

    def score_subset(
        self, query: np.ndarray, ids: list[str]
    ) -> list[tuple[str, float]]:
        """Exact re-score of the given ids; unknown/removed ids are omitted
        from the returned (id, score) pairs so results stay attributable."""
        if not self._device_gate():
            return self._score_subset_host(query, ids)
        try:
            with self._borrow_device() as (corpus, _, _i8, _ids, slot_of):
                # slot_of is the snapshot consistent with the borrowed
                # buffer — a racing background compaction rebinds, never
                # mutates, it
                present = [(i, slot_of[i]) for i in ids if i in slot_of]
                if not present:
                    return []
                q = l2_normalize(
                    jnp.asarray(query, dtype=self.dtype).reshape(-1)
                )
                slots = jnp.asarray([s for _, s in present])
                scores = np.asarray(score_subset(q, corpus, slots), np.float32)
        except DeviceUnavailable:
            return self._score_subset_host(query, ids)
        return [(id_, float(s)) for (id_, _), s in zip(present, scores)]

    def _score_subset_host(
        self, query: np.ndarray, ids: list[str]
    ) -> list[tuple[str, float]]:
        """DEGRADED_CPU twin of score_subset over the host arrays."""
        self._backend_mgr().note_fallback("search")
        with self._sync_lock:
            present = [(i, self._slot_of[i]) for i in ids if i in self._slot_of]
            if not present:
                return []
            scores = host_score_rows(
                query, self._host, np.asarray([s for _, s in present])
            )
        return [(id_, float(s)) for (id_, _), s in zip(present, scores)]
