"""The latent families' step attends what is live (``models/mla.py``: a
step's two attention blocks walk their lanes' page tables in blocks of
``BLOCK_PAGES`` pages, only as far as the longest live lane reaches, with a
running softmax): both families (``models/deepseek_v2.py``,
``models/longcat_flash.py``) through ``decoder_harness.Pool`` against their
plain float32 references, at tables of several blocks.

Every case is in float32 and held to ``F32_TOL`` 2e-4, the tolerance of the
families' own step tests: the walk computes the reference's mathematics in
another order (the softmax's sum taken a block at a time).  ``EDGE`` is a
block's extent in slots; a lane of ``n`` slots walks ``ceil(n / EDGE)``
blocks.
"""

import dataclasses
import functools

import jax
import numpy as np
import pytest

from decoder_harness import LMAX, PAGE, Pool, table_of, tokens, with_norm_scales
from nornicdb_tpu.models import deepseek_v2 as ds
from nornicdb_tpu.models import longcat_flash as lcf
from nornicdb_tpu.models import mla
from nornicdb_tpu.models.reference import deepseek_v2 as ds_ref
from nornicdb_tpu.models.reference import longcat_flash as lcf_ref

F32_TOL = 2e-4
EDGE = mla.BLOCK_PAGES * PAGE      # slots of one block
WIDE = 2 * mla.BLOCK_PAGES + 3     # pages of a table: two blocks and a part
TABLE = 3 * EDGE                   # slots a walk of a whole table gathers
FAMILIES = {
    "dsv2": (ds, ds_ref, dataclasses.replace(
        ds.DEEPSEEK_V2_SMALL, dtype="float32"),
        ds.DEEPSEEK_V2_SMALL.num_hidden_layers),
    "lcf": (lcf, lcf_ref, dataclasses.replace(
        lcf.LONGCAT_FLASH_SMALL, dtype="float32"),
        lcf.LONGCAT_FLASH_SMALL.attention_blocks),
}


class Served:
    """One family at its small float32 preset with seeded weights, and
    pools over tables of ``WIDE`` pages."""

    def __init__(self, name: str):
        self.family, self.ref, self.cfg, self.blocks = FAMILIES[name]
        self.params = with_norm_scales(
            self.family.init_params(self.cfg, jax.random.PRNGKey(11)), 1011)

    def pool(self, width: int = WIDE) -> Pool:
        return Pool(self.family, self.cfg, self.params,
                    pages=3 * WIDE + 8, width=width)

    def want(self, ids):
        """The reference's logits at every position of ``ids``."""
        return np.asarray(self.ref.forward(self.params, self.cfg, list(ids)))

    def walked(self, pool: Pool) -> tuple[int, int]:
        """(blocks' worth of slots walked a lane-block, slots of the
        tables) of the pool's LAST step, per attention block."""
        got = dict(zip(pool.counters, pool.ints[LMAX:].tolist()))
        return (got["attn_slots_walked"] // self.blocks,
                got["attn_slots_table"] // self.blocks)


_served = functools.cache(Served)  # a family's weights are made once


@pytest.fixture(params=list(FAMILIES))
def served(request) -> Served:
    return _served(request.param)


def lane(first: int, width: int = WIDE):
    """A lane's table: ``width`` consecutive pages from ``first``."""
    return table_of(*range(first, first + width), width=width)


def test_the_tables_here_are_wider_than_a_block():
    assert WIDE * PAGE > 2 * EDGE and mla.BLOCK_PAGES in (16, 32, 64)


@pytest.mark.parametrize("slots", [EDGE - 1, EDGE, EDGE + 1],
                         ids=["edge-1", "edge", "edge+1"])
def test_a_lane_whose_length_straddles_a_block_edge(served, slots):
    """A prompt prefilled in chunks and decoded so that its first decode
    row attends ``slots`` slots (the last of block 0, exactly block 0, one
    into block 1) and the next two cross the edge: the reference's logits
    at every produced position."""
    ids = tokens(slots, slots - 1, served.cfg.vocab_size)
    pool = served.pool()
    out, got = pool.serve(ids, lane(1), steps=4, chunk=128)
    want = served.want(ids + out[:-1])[len(ids) - 1:]
    assert np.abs(got - want).max() < F32_TOL
    # the last decode row stood at position slots + 1: slots + 2 slots
    assert served.walked(pool)[0] == -(-(slots + 2) // EDGE) * EDGE * (LMAX - 1)


def test_a_one_token_lane_beside_one_that_fills_the_table(served):
    """One decode step carries a lane at position 0 (nothing cached: it
    attends its own slot) and a lane whose row takes the table's LAST slot;
    each reads the reference's logits, and the short lane the logits it
    reads in a step of its own, where the step walks one block."""
    full = WIDE * PAGE
    ids = tokens(3, full, served.cfg.vocab_size)
    pool = served.pool()
    _, first = pool.serve(ids[:-1], lane(1), steps=1, chunk=256)
    alone = pool.step(decode=[(9, 0, lane(1 + WIDE))])[0]
    assert served.walked(pool) == (EDGE * (LMAX - 1), TABLE * (LMAX - 1))
    both = pool.step(decode=[(ids[-1], full - 1, lane(1)),
                             (9, 0, lane(1 + 2 * WIDE))])
    assert served.walked(pool) == (TABLE * (LMAX - 1),) * 2
    want = served.want(ids)
    assert np.abs(first[0] - want[-2]).max() < F32_TOL
    assert np.abs(both[0] - want[-1]).max() < F32_TOL
    assert np.abs(both[1] - served.want([9])[0]).max() < F32_TOL
    assert np.abs(both[1] - alone).max() < F32_TOL


def test_a_chunk_that_crosses_a_block_edge(served):
    """A 16-token chunk whose rows stand 8 before and 8 behind the edge,
    beside a decode lane that ends in block 0; then the lane decodes over
    what the chunk wrote."""
    ids = tokens(5, EDGE + 8, served.cfg.vocab_size)
    short = tokens(6, 20, served.cfg.vocab_size)
    pool = served.pool()
    pool.serve(ids[:EDGE - 8], lane(1), steps=1, chunk=128)
    pool.serve(short, lane(1 + WIDE), steps=1)
    got = pool.step(decode=[(7, len(short), lane(1 + WIDE))],
                    chunk=(ids[EDGE - 8:], EDGE - 8, lane(1)))
    # the decode block ends in block 0, the chunk block walks two
    assert served.walked(pool)[0] == EDGE * (LMAX - 1) + 2 * EDGE
    assert np.abs(got[1] - served.want(ids)[-1]).max() < F32_TOL
    assert np.abs(got[0] - served.want(short + [7])[-1]).max() < F32_TOL
    nxt = pool.step(decode=[(11, len(ids), lane(1))])[0]
    assert np.abs(nxt - served.want(ids + [11])[-1]).max() < F32_TOL


def test_a_decode_only_step_over_lanes_of_unlike_lengths(served):
    """Two decode lanes, 5 slots and ``EDGE + 3`` slots, no chunk: the
    step walks two blocks for both, the short lane's second wholly masked."""
    a = tokens(7, 4, served.cfg.vocab_size)
    b = tokens(8, EDGE + 2, served.cfg.vocab_size)
    pool = served.pool()
    pool.serve(a, lane(1), steps=1)
    pool.serve(b, lane(1 + WIDE), steps=1, chunk=128)
    got = pool.step(decode=[(5, len(a), lane(1)),
                            (6, len(b), lane(1 + WIDE))])
    assert served.walked(pool)[0] == 2 * EDGE * (LMAX - 1)
    assert np.abs(got[0] - served.want(a + [5])[-1]).max() < F32_TOL
    assert np.abs(got[1] - served.want(b + [6])[-1]).max() < F32_TOL


def test_a_table_narrower_than_a_block_is_walked_as_one_block(served):
    """The tests' usual 8-page table: every step walks all of it."""
    ids = tokens(9, 37, served.cfg.vocab_size)
    pool = served.pool(width=8)
    out, got = pool.serve(ids, lane(1, 8), steps=3)
    assert served.walked(pool) == (8 * PAGE * (LMAX - 1),) * 2
    want = served.want(ids + out[:-1])[len(ids) - 1:]
    assert np.abs(got - want).max() < F32_TOL


@pytest.mark.parametrize("slots,blocks", [
    (1, 1), (EDGE, 1), (EDGE + 1, 2), (2 * EDGE + 1, 3),
    (WIDE * PAGE, 3)], ids=["one", "edge", "edge+1", "third", "full"])
def test_slots_walked_follow_positions(served, slots, blocks):
    """``attn_slots_walked`` of a decode-only step over one lane at
    position ``slots - 1`` (nothing else of the step is read: the pool is
    blank): ``blocks`` blocks for each of the decode block's lanes and
    every attention block, of what a walk of their whole tables gathers
    (the part of a block at a table's end counts as a block)."""
    pool = served.pool()
    pool.step(decode=[(4, slots - 1, lane(1))])
    got = dict(zip(pool.counters, pool.ints[LMAX:].tolist()))
    assert got["attn_slots_walked"] == \
        blocks * EDGE * (LMAX - 1) * served.blocks
    assert got["attn_slots_table"] == TABLE * (LMAX - 1) * served.blocks
