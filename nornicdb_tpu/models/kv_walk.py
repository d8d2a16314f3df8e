"""The fused step's attention over a paged K/V pool, for every family that
keeps one (``models/qwen2.py``, ``models/cohere2_moe.py``): the step's rows
as its two attention blocks see them (:func:`plan_step`), and ONE walk of
the lanes' page tables (:func:`attend_pages`, :func:`attend_blocks`).

A pool is ``(layers, 2[k|v], pages, page_size, kv heads x head_dim)``: a
cache slot's K (or V) heads side by side in one row.  Each of a step's two
attention blocks (the decode block: one query a lane; the chunk block: the
prefill chunk's queries of one lane) gathers, scores and sums only the
BLOCKS OF PAGES its live queries can see, straight from the pool: a
``fori_loop`` whose trip count is read off the step's ``positions``, a
running float32 softmax (m, l, acc), nothing a layer wide or a table wide
ever copied.  Two things follow what the code can see of its shapes, never
a family:

* **Pages by flat page number.**  A turn indexes ``pool.reshape(layers * 2
  * pages, page_size, row)`` (a bitcast) at ``(layer * 2 + kv) * pages +
  table``: the TPU compiler makes of that the one-dimensional gather
  (``start_index_map={0}``) that ``pool[layer, kv][table]`` makes of a
  33.6 MB slice, without the slice; ``pool[layer, kv, table]`` lowers to a
  gather with three-wide index vectors, which the chip runs at a quarter of
  the rate (PERF.md section 6, PRs 31 and 38; held by
  ``tests/test_chip_compile.py``).
* **The contraction follows the head's width.**  A head as wide as a lane
  tile (``head_dim % 128 == 0``: Command A+'s 8 x 128) contracts a K/V
  group at a time.  A narrower one (Qwen2.5's 2 x 64 = ONE 128-lane row)
  may not split a row: each head's query sits in its group's lanes of a
  row-wide vector and its output is taken by the same select
  (``layers.heads_to_rows`` / ``rows_to_heads``, what
  ``layers.grouped_attention`` does and why: PR 31).
* **A block is sized by the page's bytes** (:func:`block_pages`).
* **A run every live lane shares is gathered once.**  Lanes seated behind
  one prefix hold the SAME physical pages in their tables' first columns
  (the prefix cache hands out page numbers, nothing is copied).
  :func:`plan_step` reads that run off the step's own tables, in whole
  blocks; the decode block's walk gathers those blocks ONE time, from the
  first live lane's row, and scores them for every lane's query at once,
  then walks each lane's private blocks from the running softmax the
  shared ones left.  No run (lanes that share nothing, a kind with a
  horizon, whose lanes' tables start at unlike pages) is zero turns of
  the first loop: the per-lane walk, to the letter.

A family with page kinds (``nornicdb_tpu/ragged.py``) has a pool, a table
and a horizon a kind; one without has the one kind ``full``: ``base`` 0,
``horizon`` None.  Nothing here knows a model's config class.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from nornicdb_tpu.models.layers import heads_to_rows, rows_to_heads
from nornicdb_tpu.ragged import NULL_PAGE, KindTables, unpack_ragged_meta

# one turn of a walk gathers, for every lane of its block, this many bytes
# of K (and as many of V), in whole pages: at least MIN_BLOCK_PAGES, at
# most MAX_BLOCK_SLOTS cache slots (a turn's f32 scores are heads x queries
# x slots).  A turn costs ~7 us beside its data, so small pages want many a
# turn; a block's last pages are dead for most lanes, so large pages want
# few.  Read on the chip: PERF.md section 6, PRs 36, 37 and 38
BLOCK_BYTES = 512 * 1024
MIN_BLOCK_PAGES = 32
MAX_BLOCK_SLOTS = 2048


def block_pages(pool: jax.Array, width: int) -> int:
    """Pages of a lane's table that one turn gathers, scores and sums, for
    tables ``width`` pages wide over ``pool``: Command A+'s 32 KB pages 32,
    Qwen2.5's 4 KB pages 128; a table narrower than a block is walked as
    one block."""
    ps, row = pool.shape[3], pool.shape[4]
    pages = BLOCK_BYTES // (ps * row * pool.dtype.itemsize)
    return min(width, max(MIN_BLOCK_PAGES, min(pages, MAX_BLOCK_SLOTS // ps)))


class KindRows(NamedTuple):
    """One kind's part of a step: where its layers write each row and what
    its two attention blocks walk."""
    phys: jax.Array           # (F,) the page of this kind a row is written to
    dec_tables: jax.Array     # (Lmax-1, W') W' = W up to whole blocks
    dec_base: jax.Array       # (Lmax-1,) the logical page of column 0
    dec_span: tuple           # (first block, behind the last) of the walk
    dec_shared: tuple | None  # ((W',) the first live lane's row, its leading
    #                           blocks that every live lane holds too); None
    #                           for a kind with a horizon
    chunk_table: jax.Array | None   # (1, W')
    chunk_base: jax.Array | None    # (1,)
    chunk_span: tuple | None
    walk: jax.Array           # (2,) pages gathered, pages held, ONE layer
    shared_pages: jax.Array   # () of them, the decode block's shared run


class StepRows(NamedTuple):
    tokens: jax.Array         # (F,) input ids, ``prev`` resolved
    logit_rows: jax.Array     # (Lmax,)
    valid: jax.Array          # (F,) not a padding row
    pos: jax.Array            # (F,) positions, padding rows at 0
    off: jax.Array            # (F,) a row's slot in its page
    dec_lane: jax.Array       # (F,) lane of the decode block (dump lane last)
    pos_dec: jax.Array        # (Lmax-1, 1) a lane's query position, -1 = none
    is_chunk: jax.Array       # (F,)
    chunk_row: jax.Array | None   # (F,) 0 for a chunk row, else out of bounds
    slot_c: jax.Array         # (F,) a chunk row's place in the chunk block
    pos_chk: jax.Array | None     # (1, Tq)
    kinds: tuple              # KindRows, a kind


def _span(pos, base, horizon, ps: int, bp: int, n_blocks: int):
    """The blocks of ``bp`` pages that the live queries at ``pos`` (L, T; -1
    = none) of lanes whose tables start at logical page ``base`` (L,) walk:
    from the first one a window still reaches to the last live one, at
    least one block; and the pages (walked a lane, held in all)."""
    live = pos >= 0
    rel = pos - base[:, None] * ps                    # slot in its table
    hi = jnp.clip(-(-(jnp.where(live, rel, -1).max() + 1) // (bp * ps)),
                  1, n_blocks)
    first = jnp.zeros_like(pos) if horizon is None else \
        jnp.maximum(pos - horizon + 1, 0)             # the oldest key seen
    lo = 0 if horizon is None else jnp.clip(
        jnp.where(live, first - base[:, None] * ps, n_blocks * bp * ps)
        .min() // (bp * ps), 0, hi - 1)
    # a lane's pages from its oldest query's first key to its newest's own
    lane_live = live.any(axis=1)
    held = jnp.where(
        lane_live, jnp.where(live, pos, -1).max(axis=1) // ps
        - jnp.where(live, first, 2 ** 30).min(axis=1) // ps + 1, 0).sum()
    return (lo, hi), (hi - lo) * bp, held


def _shared_run(tables, base, live, bp: int, hi):
    """The run of pages that every ``live`` (L,) lane of ``tables`` (L, W')
    begins with: (the first live lane's row (W',), its leading blocks of
    ``bp`` pages in which every live lane holds the same page numbers, at
    most ``hi``, the walk's end).  Lanes that are not live do not vote; with
    ONE live lane every column agrees and the run ends where the walk does;
    with none it is 0."""
    first = jnp.argmax(live)
    same = (tables == tables[first]) & (base == base[first])[:, None]
    common = jnp.cumprod((same | ~live[:, None]).all(axis=0)).sum()
    return tables[first], jnp.where(live.any(),
                                    jnp.minimum(common // bp, hi), 0)


def plan_step(meta: jax.Array, pools: tuple, horizons: tuple, *, lmax: int,
              w, tq: int, prev=None) -> StepRows:
    """The engine's flat rows (``nornicdb_tpu/ragged.py``) as the step's two
    attention blocks see them, kind by kind: the decode block (one query a
    lane; the decode lanes and, last, a dump lane for every row that is not
    a decode row) and the chunk block (``tq`` queries of the chunk lane; 1 =
    a decode-only step).  ``pools`` and ``horizons`` name a pool and a
    horizon a kind; ``w`` is a table width a kind, or the one width of a
    family without page kinds (its tables then start at logical page 0)."""
    tokens, lane_id, lane_pos, positions, logit_rows, lane_tables = \
        unpack_ragged_meta(meta, lmax, w, prev)
    if not isinstance(w, tuple):
        w = (w,)
        lane_tables = (KindTables(jnp.zeros((lmax,), jnp.int32),
                                  lane_tables),)
    ps = pools[0].shape[3]
    valid = positions >= 0
    pos = jnp.maximum(positions, 0)
    lane_c = jnp.clip(lane_id, 0, lmax - 1)
    slot_c = jnp.clip(lane_pos, 0, tq - 1)
    is_chunk = lane_id == lmax - 2
    ldec = lmax - 1
    dec_lane = jnp.where(is_chunk | ~valid, ldec - 1,
                         jnp.minimum(lane_c, ldec - 1))
    pos_dec = jnp.full((ldec, 1), -1, jnp.int32).at[dec_lane, 0].set(
        jnp.where(valid & ~is_chunk, positions, -1))
    chunk_row = pos_chk = None
    if tq > 1:
        # chunk rows scatter into the (1, tq) block; every other row's
        # index lands out of bounds on the lane axis and is dropped
        chunk_row = jnp.where(is_chunk & valid, 0, 1)
        pos_chk = jnp.full((1, tq), -1, jnp.int32).at[
            chunk_row, slot_c].set(positions, mode="drop")
    kinds = []
    for pool, horizon, wk, (base, table) in zip(pools, horizons, w,
                                                lane_tables, strict=True):
        col = pos // ps - base[lane_c]
        phys = jnp.where(valid & (col >= 0) & (col < wk),
                         table[lane_c, jnp.clip(col, 0, wk - 1)], NULL_PAGE)
        bp = block_pages(pool, wk)
        n_blocks = -(-wk // bp)
        # whole blocks: the columns behind a table's end are the null
        # page's, and no position reaches them
        table = jnp.pad(table, ((0, 0), (0, n_blocks * bp - wk)))
        dec_span, walked, held = _span(pos_dec, base[:ldec], horizon, ps, bp,
                                       n_blocks)
        walked, dec_shared, run = walked * ldec, None, jnp.int32(0)
        if horizon is None:
            # a shared block is gathered once, not once a lane
            dec_shared = _shared_run(table[:ldec], base[:ldec],
                                     pos_dec[:, 0] >= 0, bp, dec_span[1])
            run = (dec_shared[1] * bp).astype(jnp.int32)
            walked = walked - run * (ldec - 1)
        chunk_table = chunk_base = chunk_span = None
        if tq > 1:
            chunk_table, chunk_base = table[lmax - 2][None], \
                base[lmax - 2][None]
            chunk_span, more, held_c = _span(pos_chk, chunk_base, horizon,
                                             ps, bp, n_blocks)
            walked, held = walked + more, held + held_c
        kinds.append(KindRows(
            phys, table[:ldec], base[:ldec], dec_span, dec_shared, chunk_table,
            chunk_base, chunk_span,
            jnp.stack([walked, held]).astype(jnp.int32), run))
    return StepRows(tokens, logit_rows, valid, pos, pos % ps, dec_lane,
                    pos_dec, is_chunk, chunk_row, slot_c, pos_chk,
                    tuple(kinds))


def attend_pages(kv_heads: int, q: jax.Array, pool: jax.Array, at,
                 tables: jax.Array, base: jax.Array, pos: jax.Array,
                 span: tuple, horizon, shared=None) -> jax.Array:
    """Grouped-query attention over what is live, and inside the horizon, of
    the lanes' pages in pool layer ``at`` (an int or a traced scalar): q
    (L, T, heads, d) against blocks ``span`` = (first, behind the last) of
    :func:`block_pages` pages of
    ``tables`` (L, W'), whose column 0 is logical page ``base`` (L,); a
    query at ``pos`` (L, T) sees the slots ``pos - horizon < slot <= pos``
    (-1: none; its output is garbage and never read) -> (L, T, heads x d).
    One turn gathers a block of every lane's K and V pages by flat page
    number, scores it in f32 and folds it into a running softmax (m, l,
    acc: f32); nothing outside ``span`` is gathered.  ``shared`` = (row
    (W',), blocks): the lanes' tables all begin with ``blocks`` blocks of
    ``row`` (:func:`_shared_run`; their bases agree), and those are
    gathered ONCE, without a lane axis, and scored for all the lanes'
    queries in the same turn and the same state; the per-lane turns start
    behind them.  bf16 operands stay bf16, ``p`` is cast to V's dtype.  The
    pool is only read."""
    lanes, t, heads, d = q.shape
    g = kv_heads
    layers, _, num_pages, ps, row = pool.shape
    bp = block_pages(pool, tables.shape[1])
    bs = bp * ps
    view = pool.reshape(layers * 2 * num_pages, ps, row)   # no copy

    def by(kv):
        """A turn's K or V is (L, bs, row), a block a lane, or (bs, row),
        ONE block for all the lanes."""
        return "l" if kv.ndim == 3 else ""

    if d % 128 == 0:
        # a head fills whole lane tiles: a K/V group at a time
        qx = q.reshape(lanes, t, g, heads // g, d)
        lead, width = (lanes, g, heads // g), d

        def scores(k):
            return jnp.einsum(f"ltgrd,{by(k)}sgd->lgrts", qx,
                              k.reshape(*k.shape[:-1], g, d),
                              preferred_element_type=jnp.float32)

        def summed(p, v):
            return jnp.einsum(f"lgrts,{by(v)}sgd->lgrtd", p,
                              v.reshape(*v.shape[:-1], g, d),
                              preferred_element_type=jnp.float32)

        def by_query(o):                              # (L, g, r, T, d)
            return jnp.transpose(o.astype(q.dtype), (0, 3, 1, 2, 4))
    else:
        # a row is never split: q in its group's lanes of a row-wide vector
        qx = heads_to_rows(q, g)                      # (L, heads, T, row)
        lead, width = (lanes, heads), row

        def scores(k):
            return jnp.einsum(f"lhtc,{by(k)}sc->lhts", qx, k,
                              preferred_element_type=jnp.float32)

        def summed(p, v):
            return jnp.einsum(f"lhts,{by(v)}sc->lhtc", p, v,
                              preferred_element_type=jnp.float32)

        def by_query(o):                              # (L, heads, T, row)
            return jnp.transpose(rows_to_heads(o, g),
                                 (0, 2, 1, 3)).astype(q.dtype)

    ones = (1,) * (len(lead) - 1)
    slot = base[:, None] * ps + jax.lax.broadcasted_iota(
        jnp.int32, (lanes, bs), 1)                    # (L, bs) at block 0
    last = pos[:, :, None]                            # (L, T, 1)

    def turn(b, state, tables=tables):
        m, total, acc = state
        table = jax.lax.dynamic_slice_in_dim(tables, b * bp, bp, axis=-1)
        block = table.shape[:-1] + (bs, row)          # (L, bs, row) / (bs, row)
        k = view[(at * 2) * num_pages + table].reshape(block)
        v = view[(at * 2 + 1) * num_pages + table].reshape(block)
        here = (slot + b * bs)[:, None, :]            # (L, 1, bs)
        seen = here <= last
        if horizon is not None:
            seen &= here > last - horizon
        s = jnp.where(seen.reshape(lanes, *ones, t, bs),
                      scores(k) * d ** -0.5, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        keep = jnp.exp(m - m_new)
        acc = acc * keep[..., None] + summed(p.astype(v.dtype), v)
        return m_new, total * keep + p.sum(axis=-1), acc

    # a block that is wholly masked for a query leaves m at -1e30 and sums
    # garbage with weight 1; the first block that holds a key it sees (its
    # own, at the latest) scales that by exp(-1e30 - m) = 0; a masked block
    # AFTER that adds exp(-1e30 - m) = 0
    state = (jnp.full(lead + (t,), -1e30, jnp.float32),
             jnp.zeros(lead + (t,), jnp.float32),
             jnp.zeros(lead + (t, width), jnp.float32))
    if shared is not None:
        one, blocks = shared
        state = jax.lax.fori_loop(
            0, blocks, functools.partial(turn, tables=one), state)
        span = (jnp.maximum(span[0], blocks), span[1])
    _, total, acc = jax.lax.fori_loop(span[0], span[1], turn, state)
    return by_query(acc / total[..., None]).reshape(lanes, t, heads * d)


@functools.partial(jax.jit, static_argnames=("kv_heads", "horizon"))
def attend_blocks(kv_heads: int, rows: StepRows, kind: KindRows,
                  q: jax.Array, pool: jax.Array, at, horizon) -> jax.Array:
    """A step's two attention blocks over pool layer ``at`` of one kind,
    AFTER the step's rows were written there: q (F, heads, d), one row a
    token -> (F, heads x d).  The decode rows go to their lanes of the
    decode block, the chunk's rows to the chunk block, each block attends
    (:func:`attend_pages`), and every row takes its own back.  Jitted with
    the layer's index a VALUE, so a step traces and lowers its walks once
    for all the layers of a kind, not once a layer (24 layers of two
    ``while``s each cost a Qwen step class a second of every warm start;
    the compiler inlines the calls: the compiled step is the same)."""
    with jax.named_scope("attn.attend"):
        ldec = kind.dec_tables.shape[0]
        q_dec = jnp.zeros((ldec, 1) + q.shape[1:], q.dtype)
        q_dec = q_dec.at[rows.dec_lane, 0].set(q)
        o_dec = attend_pages(kv_heads, q_dec, pool, at, kind.dec_tables,
                             kind.dec_base, rows.pos_dec, kind.dec_span,
                             horizon, kind.dec_shared)
        o = o_dec[rows.dec_lane, 0]                   # (F, heads x d)
        if rows.chunk_row is not None:
            tq = rows.pos_chk.shape[1]
            q_chk = jnp.zeros((1, tq) + q.shape[1:], q.dtype)
            q_chk = q_chk.at[rows.chunk_row, rows.slot_c].set(q, mode="drop")
            o_chk = attend_pages(kv_heads, q_chk, pool, at, kind.chunk_table,
                                 kind.chunk_base, rows.pos_chk,
                                 kind.chunk_span, horizon)
            o = jnp.where(rows.is_chunk[:, None], o_chk[0, rows.slot_c], o)
    return o
