"""Qwen's fused step attends what is live, straight from the pool
(``models/kv_walk.py``: a step's two attention blocks walk their lanes' page
tables in blocks of ``block_pages`` pages, gathered by flat page number,
only as far as the longest live lane reaches, with a running softmax):
``models/qwen2.py`` through ``decoder_harness.Pool`` against the plain
float32 reference, at tables wider than a block; and the walk itself,
in both of its contractions, against attention written out.

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


def pool(width: int = WIDE) -> Pool:
    return Pool(qwen2, F32, served(), pages=3 * WIDE + 8, width=width)


def want(ids):
    """The reference's logits at every position of ``ids``."""
    return np.asarray(ref.forward(served(), F32, list(ids)))


def walked(p: Pool) -> tuple[int, int]:
    """(slots walked, slots of the tables) of the pool's LAST step, a
    layer."""
    got = dict(zip(p.counters, p.ints[LMAX:].tolist()))
    return (got["attn_slots_walked"] // F32.layers,
            got["attn_slots_table"] // F32.layers)


def draw(seed: int, n: int) -> list[int]:
    return tokens(seed, n, F32.vocab_size)


def lane(first: int, width: int = WIDE):
    """A lane's table: ``width`` consecutive pages from ``first``."""
    return table_of(*range(first, first + width), width=width)


def test_the_tables_here_are_wider_than_a_block():
    assert EDGE < WIDE * PAGE <= TABLE
    assert qwen2.STEP_COUNTERS == ("attn_slots_walked", "attn_slots_table")


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
    # the last decode row stood at position slots + 1: slots + 2 slots
    assert walked(p)[0] == -(-(slots + 2) // EDGE) * EDGE * LDEC


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
    assert walked(p) == (EDGE * LDEC, TABLE * LDEC)
    both = p.step(decode=[(ids[-1], full - 1, lane(1)),
                          (9, 0, lane(1 + 2 * WIDE))])
    assert walked(p) == (TABLE * LDEC,) * 2
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
    # the decode block ends in block 0, the chunk block walks two
    assert walked(p) == (EDGE * LDEC + 2 * EDGE, TABLE * (LDEC + 1))
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
    assert walked(p)[0] == 2 * EDGE * LDEC
    assert np.abs(got[0] - want(a + [5])[-1]).max() < F32_TOL
    assert np.abs(got[1] - want(b + [6])[-1]).max() < F32_TOL


def test_a_table_narrower_than_a_block_is_walked_as_one_block():
    """The tests' usual 8-page table: every step walks all of it."""
    ids = draw(9, 37)
    p = pool(width=8)
    out, got = p.serve(ids, lane(1, 8), steps=3)
    assert walked(p) == (8 * PAGE * LDEC,) * 2
    ref_rows = want(ids + out[:-1])[len(ids) - 1:]
    assert np.abs(got - ref_rows).max() < F32_TOL


@pytest.mark.parametrize("slots,blocks", [
    (1, 1), (EDGE, 1), (EDGE + 1, 2), (WIDE * PAGE, 2)],
    ids=["one", "edge", "edge+1", "full"])
def test_slots_walked_follow_positions(slots, blocks):
    """``attn_slots_walked`` of a decode-only step over one lane at
    position ``slots - 1`` (nothing else of the step is read: the pool is
    blank): ``blocks`` blocks for each of the decode block's lanes and
    every layer, of what a walk of their whole tables gathers (the part of
    a block at a table's end counts as a block)."""
    p = pool()
    p.step(decode=[(4, slots - 1, lane(1))])
    got = dict(zip(p.counters, p.ints[LMAX:].tolist()))
    assert got["attn_slots_walked"] == blocks * EDGE * LDEC * F32.layers
    assert got["attn_slots_table"] == TABLE * LDEC * F32.layers


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
