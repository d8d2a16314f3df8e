"""Qwen2-architecture decoder (Qwen2.5-0.5B-Instruct shape) in JAX.

Replaces the reference's llama.cpp generation model
(/root/reference/pkg/localllm/llama.go:748 GenerationModel, generate.go) that
powers the Heimdall assistant (pkg/heimdall/scheduler.go:178). Pre-norm
RMSNorm decoder, RoPE, grouped-query attention, SwiGLU MLP, tied embeddings.
``forward`` is the training and scoring path; generation is served by
``ragged_fused_step`` over a paged K/V pool (genserve/engine.py), and judged
against the plain float32 forward of ``models/reference/qwen2.py``.

Presets: QWEN25_05B (real shape), QWEN_SMALL (tests).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.models import kv_walk
from nornicdb_tpu.models.layers import (
    apply_rope,
    dense,
    grouped_attention,
    init_dense,
    init_rms_norm,
    normal_init,
    rms_norm,
    rope_freqs,
)
from nornicdb_tpu.ragged import NULL_PAGE, pack_ragged_meta


@dataclass(frozen=True)
class QwenConfig:
    vocab_size: int = 151936
    hidden: int = 896
    layers: int = 24
    heads: int = 14
    kv_heads: int = 2
    intermediate: int = 4864
    max_positions: int = 32768
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    tie_embeddings: bool = True
    dtype: str = "bfloat16"


QWEN25_05B = QwenConfig()
QWEN_SMALL = QwenConfig(
    vocab_size=512, hidden=64, layers=2, heads=4, kv_heads=2,
    intermediate=128, max_positions=256, rope_theta=10000.0,
)


def init_params(cfg: QwenConfig, key: jax.Array) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    head_dim = cfg.hidden // cfg.heads
    keys = jax.random.split(key, cfg.layers + 2)
    params = {
        "tok_emb": normal_init(keys[0], (cfg.vocab_size, cfg.hidden), dtype=dtype),
        "final_norm": init_rms_norm(cfg.hidden),
        "blocks": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(
            keys[1], cfg.hidden, cfg.vocab_size, bias=False, dtype=dtype
        )
    for i in range(cfg.layers):
        k = jax.random.split(keys[2 + i], 7)
        params["blocks"].append(
            {
                "q": init_dense(k[0], cfg.hidden, cfg.heads * head_dim, dtype=dtype),
                "k": init_dense(k[1], cfg.hidden, cfg.kv_heads * head_dim, dtype=dtype),
                "v": init_dense(k[2], cfg.hidden, cfg.kv_heads * head_dim, dtype=dtype),
                "o": init_dense(
                    k[3], cfg.heads * head_dim, cfg.hidden, bias=False, dtype=dtype
                ),
                "attn_norm": init_rms_norm(cfg.hidden),
                "gate": init_dense(
                    k[4], cfg.hidden, cfg.intermediate, bias=False, dtype=dtype
                ),
                "up": init_dense(
                    k[5], cfg.hidden, cfg.intermediate, bias=False, dtype=dtype
                ),
                "down": init_dense(
                    k[6], cfg.intermediate, cfg.hidden, bias=False, dtype=dtype
                ),
                "mlp_norm": init_rms_norm(cfg.hidden),
            }
        )
    return params


def _block(cfg: QwenConfig, blk: dict, h, angles, mask):
    b, t, _ = h.shape
    head_dim = cfg.hidden // cfg.heads
    x = rms_norm(blk["attn_norm"], h, cfg.rms_eps)
    q = dense(blk["q"], x).reshape(b, t, cfg.heads, head_dim)
    k = dense(blk["k"], x).reshape(b, t, cfg.kv_heads, head_dim)
    v = dense(blk["v"], x).reshape(b, t, cfg.kv_heads, head_dim)
    q = apply_rope(q, angles)
    k = apply_rope(k, angles)
    o = grouped_attention(q, k.reshape(b, t, -1), v.reshape(b, t, -1), mask)
    h = h + dense(blk["o"], o.reshape(b, t, cfg.heads * head_dim))
    x = rms_norm(blk["mlp_norm"], h, cfg.rms_eps)
    m = dense(blk["down"], jax.nn.silu(dense(blk["gate"], x)) * dense(blk["up"], x))
    return h + m


def _logits(params, cfg, h):
    if cfg.tie_embeddings:
        return jnp.einsum(
            "bth,vh->btv", h.astype(jnp.float32),
            params["tok_emb"].astype(jnp.float32),
        )
    return dense(params["lm_head"], h).astype(jnp.float32)


def forward(params: dict, cfg: QwenConfig, input_ids: jax.Array) -> jax.Array:
    """(B, T) -> (B, T, V) logits, causal, no cache (training/scoring path)."""
    b, t = input_ids.shape
    h = params["tok_emb"][input_ids]
    angles = rope_freqs(cfg.hidden // cfg.heads, t, cfg.rope_theta)
    causal = jnp.where(
        jnp.tril(jnp.ones((t, t), bool))[None, None], 0.0, -1e30
    )
    for blk in params["blocks"]:
        h = _block(cfg, blk, h, angles, causal)
    h = rms_norm(params["final_norm"], h, cfg.rms_eps)
    return _logits(params, cfg, h)


# -- paged KV cache (genserve continuous-batching decode) --------------------
#
# The paged layout (Ragged Paged Attention, PAPERS.md) keeps ONE pool of
# fixed-size pages shared by every sequence, plus a per-sequence page table
# mapping logical pages -> physical pool slots: a cache per request would be
# reallocated to a common length whenever a request joined the batch.
# Sequences join/leave the batch by allocating/freeing pages; attention
# walks each sequence's table a block of pages at a time and masks by true
# length.  Physical page 0 is RESERVED as the null/scratch page: padded
# lanes and padded chunk positions route their writes there, so a
# static-shape program never corrupts a live page (``NULL_PAGE``,
# ``pages_for``: nornicdb_tpu/ragged.py).
#
# A cache slot's row is its K (or V) heads side by side, kv_heads * head_dim
# wide: at Qwen2.5's widths 2 x 64 = ONE 128-lane tile.  With (kv_heads,
# head_dim) minor, the 64-wide minor dimension made the TPU compiler put
# another axis minor; the step's scatter and its gather each wanted their
# own layout and every step copied the whole pool there and back (9.4 + 9.0
# ms of the chip at 8,193 pages: PERF.md section 6, PR 31).  The rows reach
# the contraction as stored: no ``repeat_kv`` copy, which the chip made in
# f32 at 528 MB a layer, and no split of a row.
# What a step reads of the pool (PR 38; ``models/kv_walk.py``, the walk
# Command A+'s step runs too): each layer's two attention blocks gather,
# by FLAT page number straight from the pool, only the blocks of 128 pages
# that the step's longest live lane reaches (three of a 512-page table's
# four at 4.7k-5.7k tokens), fold them into a running float32 softmax and
# keep a turn's rows and scores on the chip.  No layer's K or V is sliced
# out of the pool first (48 copies of 33.6 MB a step before PR 38), and no
# lane's whole table is gathered.  What it still does: every lane of a
# block walks as far as the longest, empty lanes too, and the shared prefix
# pages are read once a lane (ROADMAP G1).


def init_kv_pages(cfg: QwenConfig, num_pages: int, page_size: int) -> jax.Array:
    """One pooled KV buffer: (layers, 2[k|v], num_pages, page_size,
    kv_heads * head_dim), a cache slot's K (or V) heads side by side in
    one row.  Page 0 is the null page (see module note)."""
    head_dim = cfg.hidden // cfg.heads
    return jnp.zeros(
        (cfg.layers, 2, num_pages, page_size, cfg.kv_heads * head_dim),
        jnp.dtype(cfg.dtype),
    )


def _apply_rope_rows(x: jax.Array, angles: jax.Array) -> jax.Array:
    """apply_rope with PER-SEQUENCE positions: x (B, T, H, Dh), angles
    (B, T, Dh/2) — the fused step rotates each row at its own cache
    position."""
    xf = x.astype(jnp.float32)
    d2 = x.shape[-1] // 2
    x1, x2 = xf[..., :d2], xf[..., d2:]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# -- ragged fused step (genserve v2) -----------------------------------------
#
# ONE device program per scheduler iteration serving mixed prefill + decode
# (Ragged Paged Attention, PAPERS.md): the one implementation of the
# decoder over the pool, and what the engine submits.
#
# Layout: everything row-independent (embeddings, norms, QKV/O/MLP GEMMs,
# rope) runs on a FLAT (F, 1, hidden) token batch — F is the pow2 bucket
# of (#decode lanes + prefill-chunk valid tokens), so the GEMM work
# scales with real tokens, not lanes x chunk. Only attention needs lane
# structure, and the two ragged shapes are served by two SMALL padded
# blocks inside the one program (one device dispatch) instead of one
# (Lmax, Tq) cross-product block whose Lmax*Tq padded query rows would
# dwarf the ~Lmax+Tq real ones:
#   decode block (Lmax-1, 1) single-token lanes, scattered by lane_id
#   chunk  block (1, Tq)     the prefill chunk, scattered by lane_pos
# Lane roles are FIXED by lane_id so the split needs no dynamic count:
# rows with lane_id < Lmax-2 are decode lanes, lane_id == Lmax-2 is THE
# chunk lane, lane_id == Lmax-1 is the dump lane for padding rows.
# Per-row metadata:
#   lane_id (F,)   attention lane for the row (see roles above)
#   lane_pos (F,)  query slot within the lane (decode rows 0, chunk rows
#                  their chunk offset)
#   positions (F,) cache slot the row writes+attends at; -1 = padding
#   logit_rows (Lmax,) flat row indices whose logits the caller wants
#                  (the decode rows + the chunk's last valid row) — the
#                  vocab projection runs on Lmax rows, not F
# All int32 metadata travels in ONE packed host array (one H2D per step
# instead of six — the scheduler dispatches this thousands of times a
# second), and the greedy argmax runs inside the program, so a steady
# step is exactly one dispatch and one (Lmax + 2,) device->host read.
# Padding rows route their page writes to NULL_PAGE and mask every key
# slot; their attention output is garbage never gathered. Masked slots
# score -1e30 before the f32 softmax, so exp underflows to exactly 0.0 and
# null/foreign page content contributes nothing.  What the step is held
# to is a tolerance against the plain float32 forward of
# ``models/reference/qwen2.py`` (tests/test_qwen2_step.py; the benchmark
# holds the chip to the same kind of comparison), not equality with
# another implementation.
# The two blocks are ``kv_walk``'s: the decode block holds the Lmax-2
# decode lanes and ONE dump lane (the chunk lane's slot: chunk and padding
# rows land there, masked everywhere), the chunk block the chunk lane.

# what the step appends to its Lmax greedy ids (``GenStats`` fields of the
# same names, ``mla.WALK_COUNTERS``' meaning): the cache slots its attention
# blocks gathered and scored (a block every live lane shares once), and the
# slots those lanes' whole tables hold, each summed over lanes and layers;
# and the pages of the run that the decode block gathered once for all its
# lanes, ONE layer (``kv_walk``: 0 where no two lanes share a first block)
STEP_COUNTERS = ("attn_slots_walked", "attn_slots_table", "shared_run_pages")


@functools.partial(
    jax.jit, static_argnames=("cfg", "lmax", "w", "tq"),
    donate_argnums=(3,),
)
def ragged_fused_step(params, cfg: QwenConfig, meta: jax.Array,
                      pages: jax.Array, *, lmax: int, w: int, tq: int,
                      prev=None):
    """One fused prefill+decode step over the paged pool.

    meta: the packed int32 array from :func:`pack_ragged_meta` —
    (F,) tokens/lane_id/lane_pos/positions flat rows (see module note),
    (Lmax,) logit_rows, and the (Lmax, P) per-lane page tables (row
    Lmax-2 is the chunk lane's table); ``tq`` is the static query width
    of the chunk attention block — ``tq == 1`` declares a decode-only
    step (no row may carry the chunk lane id); ``prev`` is the previous
    step's ints, where a row whose token is ``-(src + 1)`` finds it
    (``nornicdb_tpu/ragged.py``).  Attention is ``kv_walk``'s walk over
    live lengths (one kind, base 0, no horizon) on every platform.
    Returns (ints = the (Lmax,) greedy token ids followed by the step's
    counts in :data:`STEP_COUNTERS` order, so one device-to-host read
    carries both; (Lmax, V) f32 logits for ``logit_rows``; advanced
    pages); ``pages`` is DONATED.
    """
    rows = kv_walk.plan_step(meta, (pages,), (None,), lmax=lmax, w=w, tq=tq,
                             prev=prev)
    kind, = rows.kinds
    f = rows.tokens.shape[0]
    ps = pages.shape[3]
    head_dim = cfg.hidden // cfg.heads
    angles = rope_freqs(head_dim, w * ps, cfg.rope_theta)[rows.pos][:, None]
    h = params["tok_emb"][rows.tokens][:, None]      # (F, 1, hidden)
    for li, blk in enumerate(params["blocks"]):
        x = rms_norm(blk["attn_norm"], h, cfg.rms_eps)
        q = dense(blk["q"], x).reshape(f, 1, cfg.heads, head_dim)
        k = dense(blk["k"], x).reshape(f, 1, cfg.kv_heads, head_dim)
        v = dense(blk["v"], x)
        q = _apply_rope_rows(q, angles)
        k = _apply_rope_rows(k, angles)
        pages = pages.at[li, 0, kind.phys, rows.off].set(k.reshape(f, -1))
        pages = pages.at[li, 1, kind.phys, rows.off].set(v[:, 0])
        o = kv_walk.attend_blocks(cfg.kv_heads, rows, kind, q[:, 0], pages,
                                  li, None)          # (F, heads x Dh)
        h = h + dense(blk["o"], o[:, None])
        x = rms_norm(blk["mlp_norm"], h, cfg.rms_eps)
        h = h + dense(
            blk["down"], jax.nn.silu(dense(blk["gate"], x)) * dense(blk["up"], x)
        )
    h = rms_norm(params["final_norm"], h, cfg.rms_eps)
    h_sel = h[jnp.clip(rows.logit_rows, 0, f - 1)]   # (Lmax, 1, hidden)
    logits = _logits(params, cfg, h_sel)[:, 0, :]
    # slots walked (``kind.walk`` counts pages, one layer) and the slots of
    # the blocks' lanes' whole tables, padded to whole blocks of pages
    lanes = kind.dec_tables.shape[0] + (tq > 1)
    table = lanes * kind.dec_tables.shape[1]
    walk = jnp.stack([kind.walk[0], jnp.int32(table)]) * (ps * cfg.layers)
    ints = jnp.concatenate([jnp.argmax(logits, axis=-1).astype(jnp.int32),
                            walk, kind.shared_pages[None]])
    return ints, logits, pages


# -- the decoder-family seam (genserve/engine.py resolves this module from
# type(cfg) and calls these three) -------------------------------------------
# the seam's name for the pool maker; ``init_kv_pages`` stays because the
# benchmark's files call it (bench/tests/test_qwen2_reference.py)
init_pages = init_kv_pages


def fused_step(params, cfg: QwenConfig, meta, pages, **kw):
    """The family's step: :func:`ragged_fused_step`, looked up when called
    (fault injectors and tests replace the module attribute)."""
    return ragged_fused_step(params, cfg, meta, pages, **kw)


def num_pages(pool: jax.Array) -> int:
    """Pages of a pool made by :func:`init_pages` (null page included)."""
    return pool.shape[2]


# -- for bench/tests/test_qwen2_reference.py; goes with ROADMAP B0 (D11) -----
# The two per-phase primitives the fused step replaced, kept by signature
# and re-expressed over it: each packs one step's rows as the scheduler does
# and has no layer loop of its own.  The pool is donated, as it always was.


def paged_prefill_chunk(params, cfg: QwenConfig, chunk_ids, pages,
                        page_table, start, n_valid):
    """One chunk of one prompt: chunk_ids (C,) at positions start .. start
    + n_valid - 1 (rows past n_valid are padding), page_table (P,).
    Returns ((V,) logits at the last valid row, advanced pages)."""
    ids, n, at = np.asarray(chunk_ids), int(n_valid), int(start)
    c, w = ids.shape[0], page_table.shape[0]
    lmax = 2  # no decode lane: the chunk lane, the dump lane
    meta, (tokens, lane_id, lane_pos, positions, logit_rows,
           tables) = pack_ragged_meta(lmax, w, c)
    valid = np.arange(c) < n
    tokens[:], lane_pos[:] = ids, np.arange(c)
    lane_id[:] = np.where(valid, lmax - 2, lmax - 1)
    positions[:] = np.where(valid, at + np.arange(c), -1)
    logit_rows[:], tables[:] = 0, NULL_PAGE
    logit_rows[0], tables[lmax - 2] = n - 1, np.asarray(page_table)
    _, logits, pages = ragged_fused_step(
        params, cfg, jnp.asarray(meta), pages, lmax=lmax, w=w, tq=c)
    return logits[0], pages


def paged_decode_step(params, cfg: QwenConfig, tokens, pages, page_tables,
                      lengths):
    """One decode step of B sequences: tokens (B,) at positions lengths
    (B,), page_tables (B, P).  Returns ((B, V) logits, advanced pages)."""
    b, w = page_tables.shape
    lmax = b + 2
    meta, (toks, lane_id, lane_pos, positions, logit_rows,
           tables) = pack_ragged_meta(lmax, w, b)
    toks[:], positions[:] = np.asarray(tokens), np.asarray(lengths)
    lane_id[:], lane_pos[:] = np.arange(b), 0
    logit_rows[:], tables[:] = 0, NULL_PAGE
    logit_rows[:b], tables[:b] = np.arange(b), np.asarray(page_tables)
    _, logits, pages = ragged_fused_step(
        params, cfg, jnp.asarray(meta), pages, lmax=lmax, w=w, tq=1)
    return logits[:b], pages
