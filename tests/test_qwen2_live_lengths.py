"""Qwen's fused step attends what is live, straight from the pool
(``models/kv_walk.py``: a step's two attention blocks walk their lanes' page
tables in blocks of ``block_pages`` pages, gathered by flat page number,
only as far as the longest live lane reaches, with a running softmax):
``models/qwen2.py`` through ``decoder_harness.Pool`` against the plain
float32 reference, at tables wider than a block; and the walk itself,
in both of its contractions, against attention written out.

A run of pages that every live lane of the decode block holds in its
table's first columns (lanes seated behind one cached prefix) is gathered
ONCE and scored for all lanes, in whole blocks; ``attn_slots_walked`` counts
what was GATHERED (a shared block once, a private one once a lane) and
``shared_run_pages`` the run.

The step's cases are in float32 and held to ``F32_TOL`` 2e-4, the tolerance
of ``tests/test_qwen2_step.py``: the walk computes the reference's
mathematics in another order (the softmax's sum taken a block at a time).
``EDGE`` is a block's extent in slots; a lane of ``n`` slots walks
``ceil(n / EDGE)`` blocks.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_harness import LMAX, PAGE, Pool, table_of, tokens, with_norm_scales
from nornicdb_tpu.models import kv_walk, qwen2
from nornicdb_tpu.models.reference import qwen2 as ref

F32 = dataclasses.replace(qwen2.QWEN_SMALL, dtype="float32")
F32_TOL = 2e-4
BLOCK = kv_walk.block_pages(
    jax.eval_shape(lambda: qwen2.init_pages(F32, 2, PAGE)), 1 << 30)
EDGE = BLOCK * PAGE                # slots of one block
WIDE = BLOCK + 3                   # pages of a table: a block and a part
TABLE = 2 * EDGE                   # slots a walk of a whole table gathers
LDEC = LMAX - 1                    # lanes of the decode block


@functools.cache
def served():
    params = with_norm_scales(
        qwen2.init_params(F32, jax.random.PRNGKey(11)), 1011)
    params["tok_emb"] = params["tok_emb"] * 6.0   # logits of spread 1
    return params


def pool(width: int = WIDE, lmax: int = LMAX) -> Pool:
    return Pool(qwen2, F32, served(), pages=3 * WIDE + 8, width=width,
                lmax=lmax)


def want(ids):
    """The reference's logits at every position of ``ids``."""
    return np.asarray(ref.forward(served(), F32, list(ids)))


def walked(p: Pool) -> tuple[int, int]:
    """(slots walked, slots of the tables) of the pool's LAST step, a
    layer."""
    got = dict(zip(p.counters, p.ints[p.lmax:].tolist()))
    return (got["attn_slots_walked"] // F32.layers,
            got["attn_slots_table"] // F32.layers)


def run(p: Pool) -> int:
    """Pages of the run the LAST step's decode block gathered once."""
    return dict(zip(p.counters, p.ints[p.lmax:].tolist()))["shared_run_pages"]


def gathered(shared: int, private: int, ldec: int = LDEC) -> int:
    """Slots a decode block of ``ldec`` lanes gathers, a layer: ``shared``
    blocks once and ``private`` blocks once a lane."""
    return (shared + private * ldec) * EDGE


def draw(seed: int, n: int) -> list[int]:
    return tokens(seed, n, F32.vocab_size)


def lane(first: int, width: int = WIDE):
    """A lane's table: ``width`` consecutive pages from ``first``."""
    return table_of(*range(first, first + width), width=width)


def test_the_tables_here_are_wider_than_a_block():
    assert EDGE < WIDE * PAGE <= TABLE
    assert qwen2.STEP_COUNTERS == ("attn_slots_walked", "attn_slots_table",
                                   "shared_run_pages")


@pytest.mark.parametrize("page_size,row,itemsize,pages", [
    (16, 128, 2, 128),     # Qwen2.5-0.5B: 4 KB pages
    (16, 1024, 2, 32),     # Command A+: 32 KB pages, its measured 32
    (16, 32, 4, 128),      # this file's: never more than 2,048 slots
    (64, 128, 2, 32),      # 16 KB pages
    (16, 4096, 2, 32),     # pages over the block's bytes: the floor
])
def test_a_block_follows_the_pages_bytes(page_size, row, itemsize, pages):
    """``block_pages`` reads a pool's SHAPE: pages of like bytes a block
    whatever family keeps them, and a table narrower than a block is one
    block."""
    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    pool = jax.ShapeDtypeStruct((3, 2, 99, page_size, row), dtype)
    assert kv_walk.block_pages(pool, 512) == pages
    assert kv_walk.block_pages(pool, 8) == 8


@pytest.mark.parametrize("slots", [EDGE - 1, EDGE, EDGE + 1],
                         ids=["edge-1", "edge", "edge+1"])
def test_a_lane_whose_length_straddles_a_block_edge(slots):
    """A prompt prefilled in chunks and decoded so that its first decode
    row attends ``slots`` slots (the last of block 0, exactly block 0, one
    into block 1) and the next two cross the edge: the reference's logits
    at every produced position."""
    ids = draw(slots, slots - 1)
    p = pool()
    out, got = p.serve(ids, lane(1), steps=4, chunk=256)
    ref_rows = want(ids + out[:-1])[len(ids) - 1:]
    assert np.abs(got - ref_rows).max() < F32_TOL
    # the last decode row stood at position slots + 1: slots + 2 slots; ONE
    # live lane "shares" its whole walk: gathered once, no lane axis
    blocks = -(-(slots + 2) // EDGE)
    assert walked(p)[0] == gathered(blocks, 0) and run(p) == blocks * BLOCK


def test_a_one_token_lane_beside_one_that_fills_the_table():
    """One decode step carries a lane at position 0 (nothing cached: it
    attends its own slot) and a lane whose row takes the table's LAST slot;
    each reads the reference's logits, and the short lane the logits it
    reads in a step of its own, where the step walks one block."""
    full = WIDE * PAGE
    ids = draw(3, full)
    p = pool()
    _, first = p.serve(ids[:-1], lane(1), steps=1, chunk=256)
    alone = p.step(decode=[(9, 0, lane(1 + WIDE))])[0]
    assert walked(p) == (gathered(1, 0), TABLE * LDEC)
    both = p.step(decode=[(ids[-1], full - 1, lane(1)),
                          (9, 0, lane(1 + 2 * WIDE))])
    # unlike tables from column 0: no run, every lane walks both blocks
    assert walked(p) == (TABLE * LDEC,) * 2 and run(p) == 0
    ref_rows = want(ids)
    assert np.abs(first[0] - ref_rows[-2]).max() < F32_TOL
    assert np.abs(both[0] - ref_rows[-1]).max() < F32_TOL
    assert np.abs(both[1] - want([9])[0]).max() < F32_TOL
    assert np.abs(both[1] - alone).max() < F32_TOL


def test_a_chunk_that_crosses_a_block_edge():
    """A 16-token chunk whose rows stand 8 before and 8 behind the edge,
    beside a decode lane that ends in block 0; then the lane decodes over
    what the chunk wrote."""
    ids = draw(5, EDGE + 8)
    short = draw(6, 20)
    p = pool()
    p.serve(ids[:EDGE - 8], lane(1), steps=1, chunk=256)
    p.serve(short, lane(1 + WIDE), steps=1)
    got = p.step(decode=[(7, len(short), lane(1 + WIDE))],
                 chunk=(ids[EDGE - 8:], EDGE - 8, lane(1)))
    # the decode block ends in block 0 (one live lane: gathered once), the
    # chunk block walks two
    assert walked(p) == (gathered(1, 0) + 2 * EDGE, TABLE * (LDEC + 1))
    assert run(p) == BLOCK
    assert np.abs(got[1] - want(ids)[-1]).max() < F32_TOL
    assert np.abs(got[0] - want(short + [7])[-1]).max() < F32_TOL
    nxt = p.step(decode=[(11, len(ids), lane(1))])[0]
    assert np.abs(nxt - want(ids + [11])[-1]).max() < F32_TOL


def test_a_decode_only_step_over_lanes_of_unlike_lengths():
    """Two decode lanes, 5 slots and ``EDGE + 3`` slots, no chunk: the
    step walks two blocks for both, the short lane's second wholly masked."""
    a, b = draw(7, 4), draw(8, EDGE + 2)
    p = pool()
    p.serve(a, lane(1), steps=1)
    p.serve(b, lane(1 + WIDE), steps=1, chunk=256)
    got = p.step(decode=[(5, len(a), lane(1)), (6, len(b), lane(1 + WIDE))])
    assert walked(p)[0] == gathered(0, 2) and run(p) == 0
    assert np.abs(got[0] - want(a + [5])[-1]).max() < F32_TOL
    assert np.abs(got[1] - want(b + [6])[-1]).max() < F32_TOL


def test_a_table_narrower_than_a_block_is_walked_as_one_block():
    """The tests' usual 8-page table: every step walks all of it."""
    ids = draw(9, 37)
    p = pool(width=8)
    out, got = p.serve(ids, lane(1, 8), steps=3)
    assert walked(p) == (8 * PAGE, 8 * PAGE * LDEC) and run(p) == 8
    ref_rows = want(ids + out[:-1])[len(ids) - 1:]
    assert np.abs(got - ref_rows).max() < F32_TOL


@pytest.mark.parametrize("slots,mate,shared,private", [
    (1, None, 1, 0), (EDGE, None, 1, 0), (EDGE + 1, None, 2, 0),
    (WIDE * PAGE, None, 2, 0),
    # a second live lane whose table begins with the first one's pages
    (EDGE + 1, BLOCK, 1, 1), (EDGE + 1, BLOCK - 1, 0, 2),
    (WIDE * PAGE, WIDE, 2, 0), (EDGE, BLOCK, 1, 0), (1, 0, 0, 1)],
    ids=["one", "edge", "edge+1", "full", "mate-shares-a-block",
         "mate-shares-less", "mate-shares-all", "mate-ends-in-the-run",
         "mate-shares-nothing"])
def test_slots_walked_follow_positions(slots, mate, shared, private):
    """``attn_slots_walked`` of a decode-only step over one lane at
    position ``slots - 1`` (nothing else of the step is read: the pool is
    blank) counts what the decode block GATHERED, a layer: its walk's
    blocks once where the lane is alone (one live lane shares its whole
    walk with itself), and beside a ``mate`` at the same position whose
    table begins with ``mate`` of the lane's pages, the ``shared`` whole
    blocks both hold once and the ``private`` ones behind them once for
    each of the block's lanes, of what a walk of their whole tables gathers
    (the part of a block at a table's end counts as a block)."""
    p = pool()
    rows = [(4, slots - 1, lane(1))]
    if mate is not None:
        other = lane(1 + WIDE)
        other[:mate] = lane(1)[:mate]
        rows.append((5, slots - 1, other))
    p.step(decode=rows)
    got = dict(zip(p.counters, p.ints[LMAX:].tolist()))
    assert got["attn_slots_walked"] == gathered(shared, private) * F32.layers
    assert got["attn_slots_table"] == TABLE * LDEC * F32.layers
    assert got["shared_run_pages"] == shared * BLOCK   # ONE layer


# -------------------- lanes seated behind one prefix, as the cache seats them

def behind_one_prefix(common: int, tails, lmax: int = LMAX):
    """A pool in which ``len(tails)`` lanes were prefilled behind one prefix
    of ``common`` pages, which lane 0 wrote and the others found (their
    tables begin with its page NUMBERS, as a prefix hit's do; their prefill
    starts behind it); lane i then has ``tails[i]`` tokens and the rest of
    its table in pages of its own: (pool, [ids], [table])."""
    p = pool(lmax=lmax)
    prefix = draw(40 + common, common * PAGE)
    seqs, tables = [], []
    for i, n in enumerate(tails):
        own = WIDE - common
        first = 1 + common + own * i
        table = table_of(*range(1, 1 + common), *range(first, first + own),
                         width=WIDE)
        ids = prefix + draw(50 + i, n)
        p.serve(ids, table, start=common * PAGE if i else 0, steps=1,
                chunk=256)
        seqs.append(ids)
        tables.append(table)
    return p, seqs, tables


def decode_all(p, seqs, tables, lanes=None):
    """One decode step with a row of every lane; each row's logits against
    the reference's at that position."""
    got = p.step(decode=[(7 + i, len(ids), table) for i, (ids, table)
                         in enumerate(zip(seqs, tables))], lanes=lanes)
    for i, ids in enumerate(seqs):
        assert np.abs(got[i] - want(ids + [7 + i])[-1]).max() < F32_TOL, i


@pytest.mark.parametrize("common,shared", [
    (0, 0), (5, 0), (BLOCK - 1, 0), (BLOCK, 1), (BLOCK + 2, 1)],
    ids=["nothing", "five-pages", "a-page-short", "a-block", "a-block-and-two"])
def test_a_run_both_lanes_share_is_gathered_once(common, shared):
    """Two lanes behind one prefix of ``common`` pages, each with a tail of
    its own that ends in block 1: the run is the WHOLE blocks of the prefix
    (rounded down, so the private walk starts on a block's edge), the step
    gathers those once and the rest once a lane, and both rows read the
    reference's logits.  With nothing in common, or less than a block, the
    count is the per-lane walk's: two blocks for every lane."""
    far = max(EDGE - common * PAGE, 0) + 3     # tails that end in block 1
    p, seqs, tables = behind_one_prefix(common, [far, far + 6])
    decode_all(p, seqs, tables)
    assert run(p) == shared * BLOCK
    assert walked(p) == (gathered(shared, 2 - shared), TABLE * LDEC)


def test_a_run_longer_than_the_shortest_lane():
    """A lane that stands INSIDE the run the tables share (its table holds
    the prefix's pages beyond its own length: it decodes the prefix's own
    token 20, which rewrites what is there) beside one that ends in block
    1: the run is block 0 for both, and the short lane's mask, not the
    run, ends what it sees."""
    p, (ids, _), (table, short) = behind_one_prefix(BLOCK, [40, 1])
    got = p.step(decode=[(ids[20], 20, short), (9, len(ids), table)])
    assert run(p) == BLOCK and walked(p)[0] == gathered(1, 1)
    assert np.abs(got[0] - want(ids[:21])[-1]).max() < F32_TOL
    assert np.abs(got[1] - want(ids + [9])[-1]).max() < F32_TOL


def test_one_lane_that_shares_nothing_ends_the_run():
    """Three live lanes: two behind one prefix of a block, one seated on
    pages of its own.  No column is common to ALL, so nothing is shared:
    the count is the per-lane walk's and every row the reference's."""
    p, seqs, tables = behind_one_prefix(BLOCK, [20, 35], lmax=5)
    loner = draw(77, 30)
    tables.append(lane(1 + 2 * WIDE))
    p.serve(loner, tables[-1], steps=1, chunk=256)
    decode_all(p, seqs + [loner], tables)
    assert run(p) == 0
    assert walked(p) == (gathered(0, 2, ldec=4), TABLE * 4)
    # without the loner's row the other two share their block again
    decode_all(p, seqs, tables[:2])
    assert run(p) == BLOCK and walked(p)[0] == gathered(1, 1, ldec=4)


def test_the_first_live_lane_is_not_lane_0():
    """Lanes 1 and 2 live behind one prefix, lane 0 empty (a null table,
    no row): the shared gather reads the first LIVE lane's row."""
    p, seqs, tables = behind_one_prefix(BLOCK, [10, 30], lmax=5)
    decode_all(p, seqs, tables, lanes=[1, 2])
    assert run(p) == BLOCK and walked(p)[0] == gathered(1, 1, ldec=4)


def test_a_chunk_step_without_a_decode_row_shares_nothing():
    """Only a chunk rides: no lane of the decode block is live, the run is
    0 and the decode block walks its one masked block a lane, as ever."""
    ids = draw(13, 40)
    p = pool()
    got = p.step(chunk=(ids, 0, lane(1)))
    assert run(p) == 0 and walked(p)[0] == gathered(0, 1) + EDGE
    assert np.abs(got[0] - want(ids)[-1]).max() < F32_TOL


# ----------------------------- the walk itself, in both its contractions
def _plain(q, k, v, pos, horizon):
    """q (T, heads, d), k / v (S, g, d) in float64: every head against its
    group's keys ``pos - horizon < j <= pos``, written out."""
    t, heads, d = q.shape
    g = k.shape[1]
    out = np.zeros((t, heads, d))
    for i in range(t):
        lo = 0 if horizon is None else max(0, pos[i] - horizon + 1)
        for h in range(heads):
            kk, vv = (x[lo:pos[i] + 1, h // (heads // g)] for x in (k, v))
            s = kk @ q[i, h] * d ** -0.5
            p = np.exp(s - s.max())
            out[i, h] = (p / p.sum()) @ vv
    return out.reshape(t, heads * d)


@pytest.mark.parametrize("horizon", [None, 40], ids=["full", "window"])
@pytest.mark.parametrize("d", [128, 64, 16],
                         ids=["a-tile-a-head", "half-a-tile", "narrow"])
def test_the_walk_is_attention_in_both_contractions(monkeypatch, d, horizon):
    """``attend_pages`` over a float32 pool, at a head as wide as a lane
    tile (contracted a K/V group at a time: Command A+'s form on the chip)
    and at narrower ones (a row is never split: Qwen's form), full and
    behind a horizon, two lanes of unlike lengths over scattered pages, in
    blocks of 2 pages of a 6-page table (the rule is stood aside, so that
    a walk has turns at this size): attention written out, to 1e-5."""
    g, heads, ps, width, layers, at = 2, 4, 8, 6, 2, 1
    rng = np.random.default_rng(d)
    pool = rng.normal(size=(layers, 2, 20, ps, g * d)).astype(np.float32)
    tables = np.array([[3, 9, 1, 17, 5, 12], [7, 2, 19, 0, 0, 0]], np.int32)
    pos = np.array([[44, 45, 47], [2, 17, -1]], np.int32)      # (L, T)
    q = rng.normal(size=(2, 3, heads, d)).astype(np.float32)
    bp = 2
    monkeypatch.setattr(kv_walk, "block_pages", lambda pool, w: min(w, bp))
    span, _, _ = kv_walk._span(jnp.asarray(pos), jnp.zeros(2, jnp.int32),
                               horizon, ps, bp, width // bp)
    got = np.asarray(kv_walk.attend_pages(
        g, jnp.asarray(q), jnp.asarray(pool), at, jnp.asarray(tables),
        jnp.zeros(2, jnp.int32), jnp.asarray(pos), span, horizon))
    for ln in range(2):
        rows = pool[at][:, tables[ln]].reshape(2, width * ps, g, d)
        live = pos[ln] >= 0
        ref_o = _plain(q[ln][live].astype(np.float64),
                       rows[0].astype(np.float64), rows[1].astype(np.float64),
                       pos[ln][live], horizon)
        assert np.abs(got[ln][live] - ref_o).max() < 1e-5


@pytest.mark.parametrize("d", [128, 64], ids=["a-tile-a-head", "half-a-tile"])
def test_a_shared_run_is_attention_in_both_contractions(monkeypatch, d):
    """Four lanes, the first and the last dead, the two live ones with the
    same page numbers in their tables' first 5 columns and pages of their
    own behind: ``_shared_run`` reads a run of 2 whole blocks of 2 pages off
    the first LIVE lane's row (no more than the walk's end: 3), the walk
    that gathers those once is attention written out for both, in both
    contractions; and a run of 0 blocks is the per-lane walk to the bit."""
    g, heads, ps, width, layers, at, bp = 2, 4, 8, 6, 2, 1, 2
    rng = np.random.default_rng(d)
    pool = rng.normal(size=(layers, 2, 20, ps, g * d)).astype(np.float32)
    tables = np.array([[0] * 6, [3, 9, 1, 17, 5, 12], [3, 9, 1, 17, 5, 7],
                       [4] * 6], np.int32)
    pos = np.array([[-1, -1], [44, 47], [41, -1], [-1, -1]], np.int32)
    q = rng.normal(size=(4, 2, heads, d)).astype(np.float32)
    monkeypatch.setattr(kv_walk, "block_pages", lambda pool, w: min(w, bp))
    base = jnp.zeros(4, jnp.int32)
    span, _, _ = kv_walk._span(jnp.asarray(pos), base, None, ps, bp,
                               width // bp)
    live = jnp.asarray((pos >= 0).any(axis=1))
    one, blocks = kv_walk._shared_run(jnp.asarray(tables), base, live, bp,
                                      span[1])
    assert (np.asarray(one) == tables[1]).all() and int(blocks) == 2
    assert int(kv_walk._shared_run(jnp.asarray(tables), base, live, bp,
                                   1)[1]) == 1        # the walk ends first
    assert int(kv_walk._shared_run(jnp.asarray(tables), base, live & False,
                                   bp, span[1])[1]) == 0   # nobody lives
    assert int(kv_walk._shared_run(
        jnp.asarray(tables), base.at[2].set(1), live, bp, span[1])[1]) == 0

    def walk(shared):
        return np.asarray(kv_walk.attend_pages(
            g, jnp.asarray(q), jnp.asarray(pool), at, jnp.asarray(tables),
            base, jnp.asarray(pos), span, None, shared))

    got = walk((one, blocks))
    for ln in (1, 2):
        rows = pool[at][:, tables[ln]].reshape(2, width * ps, g, d)
        seen = pos[ln] >= 0
        ref_o = _plain(q[ln][seen].astype(np.float64),
                       rows[0].astype(np.float64), rows[1].astype(np.float64),
                       pos[ln][seen], None)
        assert np.abs(got[ln][seen] - ref_o).max() < 1e-5
    per_lane = walk(None)
    assert np.abs(got[1:3] - per_lane[1:3])[pos[1:3] >= 0].max() < 1e-5
    assert (walk((one, jnp.int32(0))) == per_lane).all()
