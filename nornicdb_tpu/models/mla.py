"""Multi-head latent attention (MLA) over a latent page pool: what every
latent family (``models/deepseek_v2.py``, ``models/longcat_flash.py``) runs,
written once.  (The masked matmul over the routed experts a process holds
is ``models/experts.py``'s, for a family without latent attention routes
too; the latent families call it by its names here, ``held_gates`` /
``held_experts``, which is where their fault injectors plant.)

One MLA block, whoever owns it: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb``
-> per head ``[q_nope | q_pe]``; ``[c_kv | k_pe] = x W_kva``, ``c_kv =
RMSNorm(c_kv)``; rotary embedding on ``q_pe`` and on the ONE shared
``k_pe``; per head ``[k_nope | v] = c_kv W_kvb``.  What a token leaves in
the cache is ``[c_kv | k_pe]``, ``latent_width`` values, stored as a pool
row of ``page_row_width`` (:func:`init_pages`).  Attention runs in the
ABSORBED form (``q~ = q_nope W_kvb,k^T``, ``s = q~.c_kv + q_pe.k_pe``, ``o
= (sum_u p c_kv(u)) W_kvb,v``): every head attends ONE cached row.

A family brings what differs: its rope tables (:func:`rope_tables` of its
own frequencies), its score scale, its scales of ``q`` and ``c_kv`` if it
has them, and its router.  A block's parameters are ``attn_norm``,
``q_a``, ``q_a_norm``, ``q_b``, ``kv_a``, ``kv_a_norm``, ``kv_b_k``,
``kv_b_v``, ``o``; a config is read for ``num_attention_heads``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``kv_lora_rank``,
``rms_norm_eps`` and ``dtype`` only.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.models.experts import held_experts, held_gates  # noqa: F401
from nornicdb_tpu.models.layers import dense, rms_norm
from nornicdb_tpu.ragged import NULL_PAGE, unpack_ragged_meta


def latent_width(cfg) -> int:
    """Values a token leaves in the cache, per attention block."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def page_row_width(cfg) -> int:
    """A pool row: ``latent_width`` padded with zeros to whole 128-lane
    tiles (576 -> 640).  The TPU's default layout of an array whose minor
    dimension is not a multiple of 128 puts ANOTHER dimension minor (here
    the pages), and a step that scatters rows and gathers pages then copies
    the whole pool to row-major and back, every step (PERF.md, PR 29 and PR
    30); with whole tiles the default IS row-major and scatter, gather and
    the donated buffer agree."""
    return -(-latent_width(cfg) // 128) * 128


def init_pages(cfg, blocks: int, num_pages: int, page_size: int) -> jax.Array:
    """One pooled latent cache: (attention blocks, num_pages, page_size,
    page_row_width): one row a token a block (``[c_kv | k_pe | zeros]``),
    no K/V axis, no head axis.  The leading axis is the family's own: a
    layer with two attention blocks has two.  Page 0 is the null page.
    The step scatters rows by (page, slot) and gathers whole pages by page
    id: both index the leading page axes and leave the row contiguous, so
    the pool keeps one layout (see :func:`page_row_width`)."""
    return jnp.zeros((blocks, num_pages, page_size, page_row_width(cfg)),
                     jnp.dtype(cfg.dtype))


def num_pages(pool: jax.Array) -> int:
    """Pages of a pool made by :func:`init_pages` (null page included)."""
    return pool.shape[1]


def rope_tables(inv_freq: np.ndarray, max_pos: int, scale: float = 1.0):
    """(max_pos, rope/2) cos and sin of the family's frequencies, angles
    in float64 then f32, each times ``scale``."""
    angles = np.outer(np.arange(max_pos, dtype=np.float64), inv_freq)
    return (jnp.asarray(np.cos(angles) * scale, jnp.float32),
            jnp.asarray(np.sin(angles) * scale, jnp.float32))


def rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate half-pairs of the last axis; cos/sin broadcast against
    ``x[..., :d/2]``."""
    xf = x.astype(jnp.float32)
    d2 = x.shape[-1] // 2
    x1, x2 = xf[..., :d2], xf[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def project(cfg, blk: dict, h: jax.Array, cos, sin, *, q_scale: float = 1.0,
            kv_scale: float = 1.0, rope=rope):
    """Rows h (N, hidden) at the positions of cos/sin (N, rope/2) ->
    q_nope (N, heads, nope), q_pe (N, heads, rope) rotated, and the cached
    row [c_kv after its norm | k_pe rotated] (N, latent_width).  A family
    that scales its low-rank projections says by what: ``q`` (both parts)
    times ``q_scale``, ``c_kv`` after its norm times ``kv_scale``; ``k_pe``
    is never scaled.  ``rope`` is the rotation (a family hands in its own
    name for it so that a planted fault on that name reaches here)."""
    heads, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    x = rms_norm(blk["attn_norm"], h, cfg.rms_norm_eps)
    c_q = rms_norm(blk["q_a_norm"], dense(blk["q_a"], x), cfg.rms_norm_eps)
    q = dense(blk["q_b"], c_q).reshape(
        -1, heads, nope + cfg.qk_rope_head_dim)
    kv = dense(blk["kv_a"], x)
    c_kv = rms_norm(blk["kv_a_norm"], kv[:, :cfg.kv_lora_rank],
                    cfg.rms_norm_eps)
    if q_scale != 1.0:
        q = (q.astype(jnp.float32) * q_scale).astype(q.dtype)
    if kv_scale != 1.0:
        c_kv = (c_kv.astype(jnp.float32) * kv_scale).astype(c_kv.dtype)
    k_pe = rope(kv[:, cfg.kv_lora_rank:], cos, sin)
    q_pe = rope(q[..., nope:], cos[:, None], sin[:, None])
    return q[..., :nope], q_pe, jnp.concatenate([c_kv, k_pe], axis=-1)


def absorb_query(blk: dict, q_nope: jax.Array, q_pe: jax.Array):
    """[q~ | q_pe] (..., heads, latent_width): q_nope through W_kvb,k, so a
    head's score against a cached row is one dot product."""
    q_lat = jnp.einsum("...hn,chn->...hc", q_nope, blk["kv_b_k"],
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat.astype(q_nope.dtype), q_pe], axis=-1)


def attend_absorbed(cfg, blk: dict, q_abs: jax.Array, rows: jax.Array,
                    mask: jax.Array, score_scale: float) -> jax.Array:
    """q_abs (L, T, heads, width) against the cached rows (L, S, width)
    under the additive mask (L, 1, T, S) -> (L, T, heads, v_head_dim);
    width = latent_width, or page_row_width with zeros behind on both
    sides; ``score_scale`` is the family's.  The values are the rows
    themselves (their c_kv part), expanded through W_kvb,v after the
    weighted sum."""
    s = jnp.einsum("lthd,lsd->lhts", q_abs, rows,
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(s * score_scale + mask, axis=-1)
    # over the whole row (the k_pe columns ride along and are dropped):
    # slicing the gathered rows first would copy them
    o_lat = jnp.einsum("lhts,lsd->lhtd", p.astype(rows.dtype), rows,
                       preferred_element_type=jnp.float32)
    o_lat = o_lat[..., :cfg.kv_lora_rank].astype(rows.dtype)
    return jnp.einsum("lhtc,chv->lthv", o_lat, blk["kv_b_v"],
                      preferred_element_type=jnp.float32).astype(rows.dtype)


def attend_sequences(cfg, blk: dict, h: jax.Array, b: int, cos, sin, mask,
                     project, score_scale: float) -> jax.Array:
    """h + the block's attention for ``b`` whole sequences of equal length
    laid end to end in h (b*t, hidden), no cache: the plain batched
    forward's attention, in the absorbed form.  ``project(cfg, blk, h,
    cos, sin)`` is the family's projection (:func:`project` with its
    scales)."""
    lanes = lambda a: a.reshape(b, -1, *a.shape[1:])  # noqa: E731
    q_nope, q_pe, rows = project(cfg, blk, h, cos, sin)
    o = attend_absorbed(cfg, blk, absorb_query(blk, lanes(q_nope),
                                               lanes(q_pe)),
                        lanes(rows), mask, score_scale)
    return h + dense(blk["o"], o.reshape(h.shape[0], -1))


# pages of a lane's table that one turn of a step's walk gathers, scores and
# sums (x page_size slots): the step's two attention blocks walk their
# lanes' tables in blocks of this many pages, only as far as the longest
# live lane reaches.  One constant for every latent family; 16 / 32 / 64
# were read on the chip (PERF.md section 6, PR 36).  A table narrower than
# a block is walked as one block.
BLOCK_PAGES = 32
# what a latent family's step appends, last, to its ``STEP_COUNTERS``
# (``GenStats`` fields of the same names; ``StepRows.walk`` times the
# family's attention blocks): the cache slots the step's two attention
# blocks gathered and scored, summed over their lanes, and the slots a walk
# of those lanes' whole tables would have (whole blocks; a step that
# gathered every page of every lane did that)
WALK_COUNTERS = ("attn_slots_walked", "attn_slots_table")


class StepRows(NamedTuple):
    """What one fused step's rows say, unpacked once for every attention
    block of the step (:func:`plan_step`)."""
    tokens: jax.Array       # (F,) input ids, ``prev`` resolved
    logit_rows: jax.Array   # (Lmax,)
    valid: jax.Array        # (F,) not a padding row
    cos: jax.Array          # (F, rope/2) at the rows' positions
    sin: jax.Array
    phys: jax.Array         # (F,) the page a row's latent is written to
    off: jax.Array          # (F,) and its slot there
    dec_lane: jax.Array     # (F,) lane of the decode block (dump lane last)
    dec_tables: jax.Array   # (Lmax-1, W') W' = W up to whole blocks of pages
    pos_dec: jax.Array      # (Lmax-1, 1) a lane's query position, -1 = none
    dec_blocks: jax.Array   # () blocks of pages the decode block walks
    is_chunk: jax.Array     # (F,)
    chunk_row: jax.Array | None   # (F,) 0 for a chunk row, else out of bounds
    slot_c: jax.Array       # (F,) a chunk row's place in the chunk block
    chunk_table: jax.Array | None  # (1, W')
    pos_chk: jax.Array | None      # (1, Tq) the chunk rows' positions
    chunk_blocks: jax.Array | None  # () blocks the chunk block walks
    walk: jax.Array         # (2,) WALK_COUNTERS of ONE attention block


def _block_pages(w: int) -> int:
    return min(BLOCK_PAGES, w)


def plan_step(meta: jax.Array, pages: jax.Array, tables, *, lmax: int,
              w: int, tq: int, prev=None) -> StepRows:
    """The engine's flat rows (``nornicdb_tpu/ragged.py``: ``meta`` holds F
    token rows, their lanes and positions, ``lmax`` logit rows and the
    ``(lmax, w)`` page tables; ``tq`` is the chunk block's static width, 1
    = decode only; ``prev`` is the previous step's ``ints``) as the two
    attention blocks see them: the decode block (one query a lane; the
    decode lanes and, last, a dump lane for every row that is not a decode
    row, masked everywhere, never gathered back) and the chunk block
    (``tq`` queries of the chunk lane).  Each block walks its lanes' tables
    in blocks of :data:`BLOCK_PAGES` pages as far as its longest live lane
    reaches: ``dec_blocks`` / ``chunk_blocks``, read off ``positions``, at
    least 1; ``walk`` counts what that is of the whole tables.  ``tables``
    = the family's :func:`rope_tables` over ``w * page_size`` positions."""
    tokens, lane_id, lane_pos, positions, logit_rows, lane_tables = \
        unpack_ragged_meta(meta, lmax, w, prev)
    ps = pages.shape[2]
    max_len = w * ps
    cos_t, sin_t = tables
    valid = positions >= 0
    pos_c = jnp.clip(positions, 0, max_len - 1)
    cos, sin = cos_t[pos_c], sin_t[pos_c]
    lane_c = jnp.clip(lane_id, 0, lmax - 1)
    slot_c = jnp.clip(lane_pos, 0, tq - 1)
    is_chunk = lane_id == lmax - 2
    phys = jnp.where(
        valid, lane_tables[lane_c, jnp.clip(pos_c // ps, 0, w - 1)],
        NULL_PAGE)
    off = pos_c % ps
    ldec = lmax - 1
    dec_lane = jnp.where(is_chunk | ~valid, ldec - 1,
                         jnp.minimum(lane_c, ldec - 1))
    pos_dec = jnp.full((ldec, 1), -1, jnp.int32).at[dec_lane, 0].set(
        jnp.where(valid & ~is_chunk, positions, -1))
    bp = _block_pages(w)
    n_blocks = -(-w // bp)
    # whole blocks: the columns behind a table's end are the null page's,
    # and no position reaches them
    lane_tables = jnp.pad(lane_tables, ((0, 0), (0, n_blocks * bp - w)))

    def blocks(pos):
        return jnp.clip(-(-(pos.max() + 1) // (bp * ps)), 1, n_blocks)

    dec_blocks = blocks(pos_dec)
    walked, lanes = dec_blocks * ldec, ldec
    chunk_row = chunk_table = pos_chk = chunk_blocks = None
    if tq > 1:
        # chunk rows scatter into the (1, tq) block; every other row's
        # index lands out of bounds on the lane axis and is dropped
        chunk_row = jnp.where(is_chunk & valid, 0, 1)
        pos_chk = jnp.full((1, tq), -1, jnp.int32).at[
            chunk_row, slot_c].set(positions, mode="drop")
        chunk_table = lane_tables[lmax - 2][None]
        chunk_blocks = blocks(pos_chk)
        walked, lanes = walked + chunk_blocks, lanes + 1
    # every lane of a block walks as far as its longest
    walk = jnp.stack([walked, jnp.int32(lanes * n_blocks)]) * (bp * ps)
    return StepRows(tokens, logit_rows, valid, cos, sin, phys, off, dec_lane,
                    lane_tables[:ldec], pos_dec, dec_blocks, is_chunk,
                    chunk_row, slot_c, chunk_table, pos_chk, chunk_blocks,
                    walk)


def attend_live(cfg, blk: dict, q_abs: jax.Array, pages: jax.Array, at: int,
                tables: jax.Array, pos: jax.Array, n_blocks: jax.Array,
                score_scale: float) -> jax.Array:
    """:func:`attend_absorbed` over what is live of the lanes' pages in
    pool layer ``at``: q_abs (L, T, heads, row width) against the first
    ``n_blocks`` blocks of :data:`BLOCK_PAGES` pages of ``tables`` (L, W'),
    a query at ``pos`` (L, T) seeing the slots up to its own (-1: none; its
    output is garbage and never read) -> (L, T, heads, v_head_dim).  One
    turn gathers a block of every lane's pages, scores it in f32 and folds
    it into a running softmax (m, l, acc: f32); nothing of a table behind
    ``n_blocks`` is gathered, scored or summed.  The pool is only read."""
    lanes, t, heads, width = q_abs.shape
    bp = _block_pages(tables.shape[1])
    bs = bp * pages.shape[2]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, bs), 3)
    last = pos[:, None, :, None]                     # (L, 1, T, 1)

    def turn(b, state):
        m, total, acc = state
        table = jax.lax.dynamic_slice_in_dim(tables, b * bp, bp, axis=1)
        rows = pages[at, table].reshape(lanes, bs, width)
        s = jnp.einsum("lthd,lsd->lhts", q_abs, rows,
                       preferred_element_type=jnp.float32)
        s = s * score_scale + jnp.where(slot + b * bs <= last, 0.0, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        keep = jnp.exp(m - m_new)
        # over the whole row (the k_pe columns ride along and are dropped):
        # slicing the gathered rows first would copy them
        acc = acc * keep[..., None] + jnp.einsum(
            "lhts,lsd->lhtd", p.astype(rows.dtype), rows,
            preferred_element_type=jnp.float32)
        return m_new, total * keep + p.sum(axis=-1), acc

    # a live query sees slot 0, so its m is finite after the first turn and
    # a block that is wholly masked for it adds exactly 0
    start = (jnp.full((lanes, heads, t), -1e30, jnp.float32),
             jnp.zeros((lanes, heads, t), jnp.float32),
             jnp.zeros((lanes, heads, t, width), jnp.float32))
    _, total, acc = jax.lax.fori_loop(0, n_blocks, turn, start)
    o_lat = (acc / total[..., None])[..., :cfg.kv_lora_rank].astype(
        q_abs.dtype)
    return jnp.einsum("lhtc,chv->lthv", o_lat, blk["kv_b_v"],
                      preferred_element_type=jnp.float32).astype(q_abs.dtype)


def attend_step(cfg, blk: dict, rows: StepRows, pages: jax.Array, at: int,
                h: jax.Array, project, score_scale: float):
    """One attention block of a fused step over pool layer ``at``: each
    row's ``[c_kv | k_pe]`` (the family's ``project(cfg, blk, h, cos, sin)``) is
    written once to its (page, slot), then the decode block and the chunk
    block attend what is live of their lanes' pages in the absorbed form
    (:func:`attend_live`).  h (F, hidden) -> (h + attention, pages)."""
    f = h.shape[0]
    ldec = rows.dec_tables.shape[0]
    pad = page_row_width(cfg) - latent_width(cfg)    # zeros: score nothing
    with jax.named_scope("mla.project"):
        q_nope, q_pe, row = project(cfg, blk, h, rows.cos, rows.sin)
        pages = pages.at[at, rows.phys, rows.off].set(
            jnp.pad(row, ((0, 0), (0, pad))))
    with jax.named_scope("mla.absorb"):
        q_abs = jnp.pad(absorb_query(blk, q_nope, q_pe),
                        ((0, 0), (0, 0), (0, pad)))  # (F, heads, row)
    with jax.named_scope("mla.attend"):
        q_dec = jnp.zeros((ldec, 1) + q_abs.shape[1:], q_abs.dtype)
        q_dec = q_dec.at[rows.dec_lane, 0].set(q_abs)
        o_dec = attend_live(cfg, blk, q_dec, pages, at, rows.dec_tables,
                            rows.pos_dec, rows.dec_blocks, score_scale)
        o = o_dec[rows.dec_lane, 0]                  # (F, heads, v)
        if rows.chunk_row is not None:
            tq = rows.pos_chk.shape[1]
            q_chk = jnp.zeros((1, tq) + q_abs.shape[1:], q_abs.dtype)
            q_chk = q_chk.at[rows.chunk_row, rows.slot_c].set(
                q_abs, mode="drop")
            o_chk = attend_live(cfg, blk, q_chk, pages, at, rows.chunk_table,
                                rows.pos_chk, rows.chunk_blocks, score_scale)
            o = jnp.where(rows.is_chunk[:, None, None],
                          o_chk[0, rows.slot_c], o)
    return h + dense(blk["o"], o.reshape(f, -1)), pages


# ------------------------------------------------ the dense feed-forward
def swiglu(mlp: dict, x: jax.Array) -> jax.Array:
    gate = dense({"w": mlp["gate"]}, x)
    return dense({"w": mlp["down"]},
                 jax.nn.silu(gate) * dense({"w": mlp["up"]}, x))

