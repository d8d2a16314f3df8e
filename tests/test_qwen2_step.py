"""Qwen2 behind genserve: the program (``models/qwen2.py``: ``forward`` for
training and scoring, ``ragged_fused_step`` over a pool of K/V pages for
serving) against the plain float32 reference (``models/reference/qwen2.py``),
at small sizes on the CPU.  DeepSeek-V2 has the same suite
(``tests/test_deepseek_v2.py``); both drive their step through
``decoder_harness.Pool``, which packs rows as the scheduler does.

Every comparison is on logits, never on sampled tokens: with random weights
the largest logit changes on rounding.  Tolerances, and why:

* ``F32_TOL`` 2e-4: the program in float32 computes the same mathematics as
  the reference in another order (batched rows, K/V contracted grouped,
  masked slots of a gathered table); readings are 4e-7 to 4e-6 on logits of
  spread 1.
* ``BF16_TOL`` 0.15, on the LARGEST logit error of any position: the program
  in bfloat16 (weights, activations and the pool's rows) against the
  float32 reference over the same weights.  Readings are 0.03-0.07.  A dense
  model has no routing edge to fall off, so the largest error can tell bf16
  from the precision below it: the fp8 control (the reference with both
  operands of every weight matmul rounded to e4m3) reads 0.40-0.79, over
  twice the tolerance, as a forward below the stated precision has to.

What the tolerance catches is planted at the end: a step whose decode rows
sit one slot too far, and a prefix table that points at another request's
pages, read 1.8-3.8.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_harness import (
    PAGE,
    Pool,
    fp8,
    greedy_gap,
    largest,
    reference_rows,
    table_of,
    tokens as draw,
    with_norm_scales,
)
from nornicdb_tpu.models import qwen2
from nornicdb_tpu.models.reference import qwen2 as ref

BF16 = qwen2.QWEN_SMALL
F32 = dataclasses.replace(BF16, dtype="float32")
F32_TOL = 2e-4
BF16_TOL = 0.15


def make_params(cfg, seed: int):
    """Seeded weights with the token table at six times its usual spread
    (logits of spread 1, so that a tolerance means what it means at real
    widths) and non-trivial norm scales, so that a norm left out shows."""
    params = with_norm_scales(
        qwen2.init_params(cfg, jax.random.PRNGKey(seed)), seed + 1000)
    params["tok_emb"] = (params["tok_emb"].astype(jnp.float32)
                         * 6.0).astype(params["tok_emb"].dtype)
    return params


def tokens(seed: int, n: int) -> list[int]:
    return draw(seed, n, BF16.vocab_size)


# ------------------------------------------------ (a) forward = reference
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_forward_is_the_reference_in_float32(seed):
    params = make_params(F32, seed)
    ids = tokens(seed, 40)
    got = np.asarray(qwen2.forward(params, F32, jnp.asarray([ids, ids[::-1]])))
    assert largest(got[0], ref.forward(params, F32, ids)) < F32_TOL
    assert largest(got[1], ref.forward(params, F32, ids[::-1])) < F32_TOL


def test_an_untied_head_is_read_where_the_tree_has_one():
    cfg = dataclasses.replace(F32, tie_embeddings=False)
    params = make_params(cfg, 4)
    assert "lm_head" in params
    ids = tokens(4, 24)
    got = np.asarray(qwen2.forward(params, cfg, jnp.asarray([ids])))[0]
    assert largest(got, ref.forward(params, cfg, ids)) < F32_TOL
    tied = np.asarray(ref.forward(params, F32, ids))  # the head ignored
    assert largest(got, tied) > 0.1


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bf16_forward_is_within_tolerance_and_fp8_is_not(seed):
    params = make_params(BF16, seed)
    ids = tokens(seed, 40)
    want = np.asarray(ref.forward(params, BF16, ids))
    got = np.asarray(qwen2.forward(params, BF16, jnp.asarray([ids])))[0]
    low = np.asarray(ref.forward(params, BF16, ids, rounded=fp8))
    assert largest(got, want) < BF16_TOL
    assert largest(low, want) > 2 * BF16_TOL


# ----------------------- (b) chunked prefill, decode, prefix pages
@pytest.mark.parametrize("cfg,tol,seed", [
    (F32, F32_TOL, 1), (F32, F32_TOL, 2), (BF16, BF16_TOL, 1),
    (BF16, BF16_TOL, 2), (BF16, BF16_TOL, 3)])
def test_pool_serving_is_the_reference_at_every_position(cfg, tol, seed):
    """A prompt prefilled in chunks of 16 and decoded through the pool; a
    second prompt that shares its first three pages and prefills only its
    own suffix (the prefix-cache hit), which reads the same logits as the
    same prompt served cold into pages of its own."""
    params = make_params(cfg, seed)
    prefix = tokens(seed, 3 * PAGE)
    a, b = prefix + tokens(seed + 1, 21), prefix + tokens(seed + 2, 30)
    pool = Pool(qwen2, cfg, params)
    out_a, got_a = pool.serve(a, table_of(1, 2, 3, 4, 5, 6), steps=12)
    hit = table_of(1, 2, 3, 9, 10, 11)        # a's first three pages
    out_b, got_b = pool.serve(b, hit, start=len(prefix), steps=12)
    out_c, got_c = pool.serve(b, table_of(20, 21, 22, 23, 24, 25), steps=12)
    for ids, out, got in ((a, out_a, got_a), (b, out_b, got_b)):
        want = reference_rows(ref.forward, params, cfg, ids, out)
        assert largest(got, want) < tol
    assert out_c == out_b
    assert largest(got_c, got_b) < (F32_TOL if cfg is F32 else 0.05)
    if cfg is BF16:  # a forward below the stated precision is over it
        want = reference_rows(ref.forward, params, cfg, a, out_a)
        low = reference_rows(ref.forward, params, cfg, a, out_a, rounded=fp8)
        assert largest(low, want) > 2 * tol
    # the null page took the padding rows' writes and nothing else moved
    assert pool.pool.shape == (cfg.layers, 2, 40, PAGE, 2 * 16)
    assert np.asarray(pool.pool[:, :, 0]).any()
    assert not np.asarray(pool.pool[:, :, 30:]).any()
    assert not np.asarray(pool.pool[:, :, 12:20]).any()


def test_decode_lanes_beside_a_chunk_read_what_they_read_alone():
    """One fused step carrying two decode lanes and another request's
    chunk gives each the logits it gets in a step of its own."""
    params = make_params(F32, 5)
    a, b, c = tokens(1, 20), tokens(2, 27), tokens(3, 13)
    ta, tb, tc = table_of(1, 2), table_of(3, 4), table_of(5)
    alone, mixed = Pool(qwen2, F32, params), Pool(qwen2, F32, params)
    for pool in (alone, mixed):
        pool.serve(a, ta, steps=1)
        pool.serve(b, tb, steps=1)
    la = alone.step(decode=[(7, len(a), ta)])[0]
    lb = alone.step(decode=[(9, len(b), tb)])[0]
    lc = alone.step(chunk=(c, 0, tc))[-1]
    got = mixed.step(decode=[(7, len(a), ta), (9, len(b), tb)],
                     chunk=(c, 0, tc))
    for want, row in zip((la, lb, lc), got):
        assert largest(want, row) < F32_TOL
    # and each is the reference's row for its own sequence
    for ids, tok, row in ((a, 7, got[0]), (b, 9, got[1])):
        want = np.asarray(ref.forward(params, F32, ids + [tok]))[-1]
        assert largest(row, want) < F32_TOL
    assert largest(got[2], np.asarray(ref.forward(params, F32, c))[-1]) \
        < F32_TOL
    # both pools hold the same rows on every real page
    assert largest(alone.pool[:, :, 1:], mixed.pool[:, :, 1:]) < F32_TOL


# ------------------------------- (c) the names bench/ keeps (ROADMAP D11)
def test_the_per_phase_names_answer_through_the_fused_step():
    """``paged_prefill_chunk`` / ``paged_decode_step`` (what
    ``bench/tests/test_qwen2_reference.py`` calls until ROADMAP B0 re-points
    it) keep their signatures and returns, and what they return is the
    reference's."""
    params = make_params(F32, 6)
    ids = tokens(6, 21)
    pages = qwen2.init_kv_pages(F32, 9, PAGE)
    table = jnp.asarray(table_of(1, 2))
    logits = None
    for at in (0, 16):
        n = min(16, len(ids) - at)
        chunk = np.zeros(16, np.int32)
        chunk[:n] = ids[at:at + n]
        donated = pages
        logits, pages = qwen2.paged_prefill_chunk(
            params, F32, jnp.asarray(chunk), donated, table,
            jnp.asarray(at), jnp.asarray(n))
        assert donated.is_deleted() and logits.shape == (F32.vocab_size,)
    want = np.asarray(ref.forward(params, F32, ids + [11, 12]))
    assert largest(logits, want[len(ids) - 1]) < F32_TOL
    tables = jnp.stack([table, jnp.asarray(table_of(3))])
    rows, pages = qwen2.paged_decode_step(
        params, F32, jnp.asarray([11, 5], jnp.int32), pages, tables,
        jnp.asarray([len(ids), 0], jnp.int32))
    assert rows.shape == (2, F32.vocab_size)
    assert largest(rows[0], want[len(ids)]) < F32_TOL
    assert largest(rows[1], np.asarray(ref.forward(params, F32, [5]))[0]) \
        < F32_TOL


# ------------------------------- (d) what the tolerance catches, planted
def shifted_decode_rows(plain):
    """Every decode row of the fused step writes and attends one cache slot
    too far (``bench/tests/faults.py::decode_position_off``)."""
    def off(params, cfg, meta, pages, *, lmax, w, tq):
        m = np.array(meta)
        f = (m.shape[0] - lmax - lmax * w) // 4
        lane, pos = m[f:2 * f], m[3 * f:4 * f]
        pos[(lane < lmax - 2) & (pos >= 0)] += 1
        return plain(params, cfg, jnp.asarray(m), pages, lmax=lmax, w=w,
                     tq=tq)
    return off


@pytest.mark.parametrize("cfg,tol", [(F32, F32_TOL), (BF16, BF16_TOL)])
def test_decode_rows_one_slot_too_far_read_outside_the_tolerance(
        monkeypatch, cfg, tol):
    params = make_params(cfg, 1)
    ids = tokens(1, 37)
    monkeypatch.setattr(qwen2, "ragged_fused_step",
                        shifted_decode_rows(qwen2.ragged_fused_step))
    out, got = Pool(qwen2, cfg, params).serve(ids, table_of(1, 2, 3, 4),
                                              steps=8)
    want = reference_rows(ref.forward, params, cfg, ids, out)
    assert largest(got[0], want[0]) < tol       # the prefill is where it was
    assert largest(got[1:], want[1:]) > 2 * tol  # the decode rows are not


@pytest.mark.parametrize("cfg,tol", [(F32, F32_TOL), (BF16, BF16_TOL)])
def test_a_prefix_table_on_another_requests_pages_reads_outside_the_tolerance(
        cfg, tol):
    params = make_params(cfg, 2)
    prefix = tokens(2, 3 * PAGE)
    a, other = prefix + tokens(3, 9), tokens(4, 3 * PAGE + 5)
    b = prefix + tokens(5, 14)
    pool = Pool(qwen2, cfg, params)
    pool.serve(a, table_of(1, 2, 3, 4), steps=1)
    pool.serve(other, table_of(5, 6, 7, 8), steps=1)
    right, got_r = pool.serve(b, table_of(1, 2, 3, 9, 11),
                              start=len(prefix), steps=6)
    wrong, got_w = pool.serve(b, table_of(5, 6, 7, 10, 12),
                              start=len(prefix), steps=6)
    assert largest(got_r, reference_rows(ref.forward, params, cfg, b,
                                         right)) < tol
    assert largest(got_w, reference_rows(ref.forward, params, cfg, b,
                                         wrong)) > 2 * tol
    assert greedy_gap(ref.forward, params, cfg, b, right) < tol
