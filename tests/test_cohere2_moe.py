"""Command A+'s language model behind genserve: the program
(``models/cohere2_moe.py``: window layers beside full ones over a K/V pool a
page kind, a parallel block, a sigmoid router, averaged shared experts, of
whose routed experts a share is held, the fused ragged step) against the
plain float32 reference (``models/reference/cohere2_moe.py``), and the
scheduler's page kinds (``genserve/engine.py``), at small sizes on the CPU:
a window of 32 tokens under contexts of 100 and more, so that pages ARE let
go mid-sequence and taken again by another lane before a comparison.

Every comparison is on logits (or on the greedy GAP read off the
reference's logits), never on sampled tokens.  Tolerances, and why:

* ``F32_TOL`` 2e-4: the program in float32 computes the reference's
  mathematics in another order (batched, blocks of pages under a running
  softmax, masked experts); readings are 3e-7 to 2e-6 on logits of spread
  0.16.
* ``BF16_TOL`` 0.04, on the MEDIAN over positions of a position's largest
  logit error (:func:`typical`): rounding reads 0.006-0.012; a routed model
  is discontinuous besides (a token whose 4th and 5th scores lie within
  rounding takes another expert, rightly), so the largest error cannot tell
  bfloat16 from fp8 and the median can: the fp8 control reads 0.09-0.14.
* ``WIRING_TOL`` 0.01: a float32 step wired wrongly (the window ignored,
  rope in a full layer, the shared experts summed, gates left unnormalised,
  a row in the other kind's pool, a page let go a page early) is off by
  0.02 and more; the sound float32 step reads under ``F32_TOL``.
* ``GAP_TOL`` 0.06 for bfloat16 through the engine: the served token's
  reference logit under the reference's best.  ``genserve_harness`` argues
  twice the largest logit error (0.012 here) for a dense model; a routed
  one takes another expert at a tie and is off by more at that position:
  readings over this file's prompts are 0.0 on most and 0.030 at most.  A
  token drawn at random lies 0.5 under the best at the median.
"""

import dataclasses
import http.client
import json
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_harness as harness
import genserve_harness as gs
from decoder_harness import (
    LMAX,
    PAGE,
    Lane,
    Pool,
    fp8,
    largest,
    tokens as draw,
    typical,
    with_norm_scales,
)
from nornicdb_tpu.models import cohere2_moe as cm
from nornicdb_tpu.models import experts
from nornicdb_tpu.models.reference import cohere2_moe as ref
from nornicdb_tpu.ragged import ROUTING_COUNTERS, first_page, pages_for

BF16 = cm.COHERE2_MOE_SMALL
F32 = dataclasses.replace(BF16, dtype="float32")
WINDOW = BF16.sliding_window
F32_TOL = 2e-4
BF16_TOL = 0.04
WIRING_TOL = 0.01
GAP_TOL = 0.06


def make_params(cfg, seed: int):
    """Seeded weights; the router's rows at four times the usual spread (a
    row's sixteen logits spread by 4, so its four gates are uneven, 0.1 to
    0.4, and what is wrong in the routed sum shows) and non-trivial norm
    scales, so that a norm left out shows."""
    params = with_norm_scales(
        cm.init_params(cfg, jax.random.PRNGKey(seed)), seed + 1000)
    for blk in params["blocks"]:
        blk["router"] = (blk["router"].astype(jnp.float32) * 4.0).astype(
            blk["router"].dtype)
    return params


def hold_experts(params, cfg, first: int, count: int):
    """One expert-parallel rank's share of a model whose tree holds every
    routed expert: experts ``first .. first + count - 1`` of each layer and
    everything else (the shared experts too) as it was."""
    lo = first - cfg.held_experts[0]
    blocks = [{**blk, "experts": {k: w[lo:lo + count]
                                  for k, w in blk["experts"].items()}}
              for blk in params["blocks"]]
    return ({**params, "blocks": blocks},
            dataclasses.replace(cfg, held_experts=(first, count)))


def tokens(seed: int, n: int, vocab: int = BF16.vocab_size) -> list[int]:
    return draw(seed, n, vocab)


# ------------------------------------------------ (a) forward = reference
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_forward_is_the_reference_in_float32(seed):
    params = make_params(F32, seed)
    ids = tokens(seed, 3 * WINDOW + 7)
    got = np.asarray(cm.forward(params, F32, jnp.asarray([ids])))[0]
    assert largest(got, ref.forward(params, F32, ids)) < F32_TOL


@pytest.mark.parametrize("seed", [1, 2])
def test_bf16_forward_is_within_tolerance_and_fp8_is_not(seed):
    params = make_params(BF16, seed)
    ids = tokens(seed, 3 * WINDOW)
    want = ref.forward(params, BF16, ids)
    got = np.asarray(cm.forward(params, BF16, jnp.asarray([ids])))[0]
    assert typical(got, want) < BF16_TOL
    assert typical(ref.forward(params, BF16, ids, rounded=fp8),
                   want) > 2 * BF16_TOL


def test_the_published_config_and_the_benchmarks_cut():
    full = cm.Cohere2MoeConfig()
    assert full.layer_types == (cm.SLIDING,) * 3 + (cm.FULL,) \
        + full.layer_types[4:] and len(full.layer_types) == 32
    assert full.layer_types.count(cm.FULL) == 8
    assert cm.page_kinds(full) == (("full", None), ("window", 4096))
    cut = cm.COMMAND_A_PLUS_EP16_4L
    assert cut.layer_types == full.layer_types[:4]  # one whole period
    for width in ("hidden_size", "intermediate_size", "head_dim",
                  "num_attention_heads", "num_key_value_heads",
                  "num_experts", "num_experts_per_tok", "num_shared_experts",
                  "sliding_window"):
        assert getattr(cut, width) == getattr(full, width), width
    assert cut.held_experts == (0, 8) and cut.vocab_size * 8 == \
        full.vocab_size
    with pytest.raises(ValueError):
        dataclasses.replace(cut, layer_types=cut.layer_types[:3])
    pools = jax.eval_shape(lambda: cm.init_pages(cut, (8193, 4689), 16))
    assert [p.shape for p in pools] == [(1, 2, 8193, 16, 1024),
                                        (3, 2, 4689, 16, 1024)]
    assert cm.num_pages(pools) == (8193, 4689)


def test_only_the_held_experts_are_made():
    cfg = dataclasses.replace(BF16, held_experts=(4, 2))
    params = jax.eval_shape(lambda: cm.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    blk = params["blocks"][0]
    assert blk["experts"]["gate"].shape == (2, 64, 64)
    assert blk["shared"]["down"].shape == (2, 64, 64)
    assert blk["router"].shape == (64, 16)  # every output, as published
    assert "lm_head" not in params  # tied


# ------------------------------------------- (b) the expert layer's parts
@pytest.mark.parametrize("seed", [1, 2])
def test_sigmoid_routing_against_a_numpy_loop(seed):
    """The 4 best of 16 sigmoid scores, each gate its score over the sum of
    the chosen four: against a loop, and against the reference's router."""
    params = make_params(F32, seed)
    router = np.asarray(params["blocks"][1]["router"], np.float64)
    x = np.random.default_rng(seed).standard_normal((24, 64)).astype(
        np.float32)
    ids, gates = (np.asarray(a) for a in cm.route(F32, router, jnp.asarray(x)))
    for r in range(len(x)):
        s = 1.0 / (1.0 + np.exp(-(x[r].astype(np.float64) @ router)))
        best = np.argsort(-s)[:4]
        assert sorted(ids[r]) == sorted(best)
        np.testing.assert_allclose(gates[r], s[ids[r]] / s[best].sum(),
                                   rtol=1e-5)
    assert np.allclose(gates.sum(-1), 1.0, atol=1e-6)
    rid, rg = ref.route(F32, params["blocks"][1]["router"], jnp.asarray(x))
    assert (np.asarray(rid) == ids).all()
    np.testing.assert_allclose(np.asarray(rg), gates, rtol=1e-5)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_sixteen_shares_add_up_to_the_uncut_expert_layer(seed):
    """The guide's share test at 128 / 8 = 16 shares (here 16 experts, one
    a share): the routed parts that the shares give, with the shared
    average (which every rank computes alike) counted ONCE, add up to the
    uncut reference's expert layer."""
    params = make_params(F32, seed)
    blk = params["blocks"][2]
    x = jnp.asarray(np.random.default_rng(seed).standard_normal((20, 64)),
                    jnp.float32)
    uncut = np.asarray(ref.expert_layer(F32, blk, x, F32.held_experts))
    shared = np.mean([
        (jax.nn.silu(x @ blk["shared"]["gate"][j])
         * (x @ blk["shared"]["up"][j])) @ blk["shared"]["down"][j]
        for j in range(F32.num_shared_experts)], axis=0)
    total, assignments = np.array(shared), 0
    for first in range(16):
        part, cfg = hold_experts(params, F32, first, 1)
        out, counts = cm.expert_layer(cfg, part["blocks"][2], x)
        total += np.asarray(out) - shared
        assignments += int(counts[0])
        assert np.abs(np.asarray(out) - np.asarray(ref.expert_layer(
            cfg, part["blocks"][2], x, cfg.held_experts))).max() < F32_TOL
    assert np.abs(total - uncut).max() < F32_TOL
    assert assignments == 20 * F32.num_experts_per_tok  # every choice, once


# ---------------------------------------------------- (c) no positions
def test_full_layers_ignore_positions_and_window_layers_do_not():
    """A stack of full layers only has no positions: standing its tokens at
    0, 2, 4, ... (or anywhere increasing) changes nothing, exactly.  One
    window layer among them and the same move changes every logit: the
    distances its rotation reads have doubled."""
    nope = dataclasses.replace(F32, num_hidden_layers=2,
                               layer_types=(cm.FULL, cm.FULL))
    params = make_params(nope, 5)
    ids = tokens(5, 40)
    plain = np.asarray(ref.forward(params, nope, ids))
    for at in (2 * np.arange(40), np.arange(40) + 1000):
        assert (np.asarray(ref.forward(params, nope, ids, positions=at))
                == plain).all()
    mixed = dataclasses.replace(nope, layer_types=(cm.SLIDING, cm.FULL))
    moved = np.asarray(ref.forward(params, mixed, ids,
                                   positions=2 * np.arange(40)))
    assert typical(moved, ref.forward(params, mixed, ids)) > WIRING_TOL
    # and the program's step over a stack without a window layer has the
    # one kind, and agrees with that reference
    assert cm.page_kinds(nope) == (("full", None),)
    pool = Pool(cm, nope, params)
    out, rows = pool.serve(ids, Lane(pool), steps=4)
    want = harness.reference_rows(ref.forward, params, nope, ids, out)
    assert largest(rows, want) < F32_TOL
    assert pool.counts[-3:-1].tolist() == [0, 0]  # no window layer walked


# ----------------------- (d) chunked prefill + decode through both pools
@pytest.mark.parametrize("cfg,tol,measure", [
    (F32, F32_TOL, largest), (BF16, BF16_TOL, typical)])
def test_both_pools_serve_the_reference_past_three_windows(cfg, tol, measure):
    """Two lanes of 105 and 110 tokens (over three windows of 32), prefilled
    in chunks of 16 and decoded, through the full pool and the window pool:
    the first lane lets go its window pages as it goes, the second TAKES
    them (the window pool has 8 pages and goes round) and overwrites them
    while the first still decodes beside it; every produced position of
    both is the reference's."""
    params = make_params(cfg, 7)
    pool = Pool(cm, cfg, params, pages=(24, 9))
    a, b = Lane(pool), Lane(pool)
    ids_a, ids_b = tokens(11, 105), tokens(12, 110)
    out_a, rows_a = pool.serve(ids_a, a, steps=3)
    assert pool.released == [0, 4]  # four window pages behind 105 tokens
    # lane b prefills beside lane a's decode rows
    at, rows_b = 0, None
    while at < len(ids_b):
        piece = ids_b[at:at + 16]
        n = len(ids_a) + len(out_a) - 1
        got = pool.step(decode=[(out_a[-1], n, a)], chunk=(piece, at, b))
        rows_a = np.concatenate([rows_a, got[:1]])
        out_a.append(int(got[0].argmax()))
        rows_b, at = got[-1], at + len(piece)
    assert a.ever[1] & b.ever[1], "no window page went from lane to lane"
    assert not set(a.pages[1]) & set(b.pages[1])
    assert len(a.pages[1]) <= 3 and len(b.pages[1]) <= 3  # a window's worth
    assert len(a.pages[0]) == pages_for(len(ids_a) + len(out_a), PAGE)
    out_b = [int(rows_b.argmax())]
    got = pool.step(decode=[(out_a[-1], len(ids_a) + len(out_a) - 1, a),
                            (out_b[-1], len(ids_b), b)])
    rows_a = np.concatenate([rows_a, got[:1]])
    rows_b = np.stack([rows_b, got[1]])
    out_a.append(int(got[0].argmax()))
    out_b.append(int(got[1].argmax()))
    for ids, out, rows in ((ids_a, out_a, rows_a), (ids_b, out_b, rows_b)):
        want = harness.reference_rows(ref.forward, params, cfg, ids, out)
        assert measure(rows, want) < tol
    if cfg is F32:  # greedy: the reference's own continuation
        assert harness.greedy_gap(ref.forward, params, cfg, ids_a,
                                  out_a) < F32_TOL


def test_the_walk_stays_inside_the_window_and_the_live_length():
    """What the step's attention walked, by kind (its int vector's last
    five): the window layers no more than a window's blocks whatever the
    context, the full layer as far as the longest lane, ONCE where one lane
    is live (the run it "shares" is its whole walk: ``shared_run_pages``,
    which counts the full kind only: lanes' window tables start at unlike
    pages and never share); and ``held`` is the pages a live query may
    see."""
    assert cm.STEP_COUNTERS == ROUTING_COUNTERS + (
        "full_pages_walked", "full_pages_held", "window_pages_walked",
        "window_pages_held", "shared_run_pages")
    params = make_params(F32, 3)
    pool = Pool(cm, F32, params, pages=24)
    lane = Lane(pool)
    ids = tokens(3, 96)
    for at in range(0, 96, 16):
        pool.step(chunk=(ids[at:at + 16], at, lane))
    pool.counts[:] = 0
    pool.step(decode=[(5, 96, lane)])
    counts = dict(zip(pool.counters, pool.counts.tolist()))
    ldec = LMAX - 1
    # the full kind's 8-page table is one block; its one layer; one live
    # lane: gathered once
    assert counts["full_pages_walked"] == counts["shared_run_pages"] == 8
    assert counts["full_pages_held"] == 96 // PAGE + 1
    # the window kind's table is 4 pages wide: one block, three layers
    assert counts["window_pages_walked"] == 4 * ldec * 3
    seen = 96 // PAGE - first_page(96, WINDOW, PAGE) + 1
    assert seen == 3 and counts["window_pages_held"] == seen * 3
    assert counts["routed_rows"] == 4  # one row, four expert layers


@pytest.mark.parametrize("common,shared", [(127, 0), (128, 1)],
                         ids=["a-page-short-of-a-block", "a-block"])
def test_lanes_behind_one_prefix_share_the_full_kinds_run(common, shared):
    """Two lanes seated behind one prefix of ``common`` pages (lane b finds
    lane a's page NUMBERS in both kinds, as a prefix hit does, and each then
    prefills a tail of its own), full tables of two blocks of 128 pages:
    the full layer's decode block gathers the prefix's whole blocks ONCE
    (``shared_run_pages``, and ``full_pages_walked`` counts them once) and
    walks the rest once a lane; the window layers' count is the per-lane
    walk's, whatever the tables hold (a kind with a horizon never shares);
    both rows read the reference's logits.  One page short of a block
    nothing is shared and the full kind's count is the per-lane walk's too."""
    block, ldec = 128, LMAX - 1
    params = make_params(F32, 9)
    pool = Pool(cm, F32, params, pages=(2 * block + 40, 120),
                width=block + 3, chunk=256)
    assert pool.width == (block + 3, pages_for(WINDOW + 256, PAGE) + 1)
    prefix = tokens(21, common * PAGE)
    a, b = Lane(pool), Lane(pool)
    pool.serve(prefix, a, steps=1, chunk=256)
    b.base, b.pages = list(a.base), [list(held) for held in a.pages]
    ids = [prefix + tokens(22, 2060 - len(prefix)),
           prefix + tokens(23, 2071 - len(prefix))]
    for lane, seq in zip((a, b), ids):
        pool.serve(seq, lane, start=len(prefix), steps=1, chunk=256)
    assert a.pages[0][:common] == b.pages[0][:common]
    assert not set(a.pages[0][common:]) & set(b.pages[0][common:])
    pool.counts[:] = 0
    got = pool.step(decode=[(5, len(ids[0]), a), (6, len(ids[1]), b)])
    for row, seq, tok in zip(got, ids, (5, 6)):
        assert largest(row, ref.forward(params, F32, seq + [tok])[-1]) \
            < F32_TOL
    counts = dict(zip(pool.counters, pool.counts.tolist()))
    assert counts["shared_run_pages"] == shared * block
    assert counts["full_pages_walked"] == \
        (shared + (2 - shared) * ldec) * block
    assert counts["window_pages_walked"] == pool.width[1] * ldec * 3


def _broken(monkeypatch, fault: str):
    """Plant one wiring fault in the module (the benchmark's planted faults,
    bench/tests/faults_cmda.py, at this file's size) and hand back a step
    that is traced anew."""
    if fault == "window_ignored":
        plain = cm.attend_step
        monkeypatch.setattr(cm, "attend_step", lambda *a: plain(*a[:-1], None))
    elif fault == "rope_in_full":
        plain = cm.project
        monkeypatch.setattr(
            cm, "project", lambda cfg, blk, x, rotary, at=[None]: plain(
                cfg, blk, x, at.__setitem__(0, rotary or at[0]) or at[0]))
    elif fault == "shared_summed":
        plain = experts.held_experts
        monkeypatch.setattr(
            experts, "held_experts", lambda tree, x, weight: plain(
                tree, x, jnp.ceil(weight) if tree["gate"].shape[0]
                == F32.num_shared_experts else weight))
    elif fault == "gates_unnormalised":
        def raw(cfg, router, x):
            s = jax.nn.sigmoid(x.astype(jnp.float32)
                               @ router.astype(jnp.float32))
            top, ids = jax.lax.top_k(s, cfg.num_experts_per_tok)
            return ids, top
        monkeypatch.setattr(cm, "route", raw)
    elif fault == "held_dropped":
        plain = experts.held_experts
        monkeypatch.setattr(
            experts, "held_experts", lambda tree, x, weight: plain(
                tree, x, weight * (tree["gate"].shape[0]
                                   == F32.num_shared_experts)))
    def fresh(params, cfg, meta, pages, **kw):  # its own function, so trace
        return cm.fused_step.__wrapped__(params, cfg, meta, pages, **kw)

    step = jax.jit(fresh, static_argnames=("cfg", "lmax", "w", "tq"),
                   donate_argnums=(3,))
    return types.SimpleNamespace(
        init_pages=cm.init_pages, page_kinds=cm.page_kinds, fused_step=step,
        STEP_COUNTERS=cm.STEP_COUNTERS)


@pytest.mark.parametrize("fault", [
    "window_ignored", "rope_in_full", "shared_summed", "gates_unnormalised",
    "held_dropped"])
def test_a_step_wired_wrongly_is_outside_the_tolerance(monkeypatch, fault):
    params = make_params(F32, 9)
    ids = tokens(9, 100)
    pool = Pool(_broken(monkeypatch, fault), F32, params, pages=24)
    out, rows = pool.serve(ids, Lane(pool), steps=4)
    want = harness.reference_rows(ref.forward, params, F32, ids, out)
    assert typical(rows, want) > WIRING_TOL, (fault, typical(rows, want))


def test_a_page_let_go_a_page_early_is_outside_the_tolerance():
    """The allocator's own fault: a lane that lets a window page go while
    its window still reaches it attends zeros where keys were."""
    params = make_params(F32, 9)
    ids = tokens(9, 100)
    pool = Pool(cm, F32, params, pages=24)
    lane = Lane(pool)
    reach = lane.reach

    def early(first, last):
        reach(first + PAGE, max(last, first + PAGE))  # a page too far
        lane.base[0] = 0  # (the full kind has no window: as it was)

    lane.reach = early
    out, rows = pool.serve(ids, lane, steps=4)
    want = harness.reference_rows(ref.forward, params, F32, ids, out)
    assert typical(rows, want) > WIRING_TOL


def test_the_step_carries_its_scopes_and_its_own_module_name():
    params = jax.eval_shape(lambda: cm.init_params(BF16,
                                                   jax.random.PRNGKey(0)))
    w = (8, 4)
    meta = jax.ShapeDtypeStruct(
        (4 * 16 + LMAX + sum(LMAX * (1 + wk) for wk in w),), jnp.int32)
    pages = jax.eval_shape(lambda: cm.init_pages(BF16, (9, 9), PAGE))
    lowered = cm.fused_step.lower(params, BF16, meta, pages, lmax=LMAX, w=w,
                                  tq=16)
    text = lowered.as_text(debug_info=True)
    for scope in ("attn.project", "attn.attend", "moe.route", "moe.experts",
                  "moe.shared"):
        assert scope in text, scope
    module = re.search(r"module @(\S+)", lowered.as_text()).group(1)
    assert module == "jit_parallel_moe_fused_step"


# ---------------------------------------- (e) the scheduler's page kinds
def small_engine(cfg=BF16, seed=21, **kw):
    params = make_params(cfg, seed)
    kw.setdefault("max_seq_tokens", 160)
    kw.setdefault("pool_pages", 41)
    eng = gs.engine(model=(params, cfg), tokenizer=None, **kw)
    return eng, params


def settled(eng):
    """Wait until nothing runs, then hold every kind to the allocator's
    invariants: no page both free and cached, free + cached = the pool, no
    holder left."""
    import time

    deadline = time.monotonic() + 60
    while eng._running or eng._inflight is not None or eng._zombies \
            or eng._queue:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    time.sleep(0.05)
    for kind in eng._kinds:
        assert len(set(kind.free)) == len(kind.free), kind.name
        assert not set(kind.free) & set(kind.hash), kind.name
        assert len(kind.free) + len(kind.hash) == kind.usable, kind.name
        assert not any(kind.refs.values()), kind.name
        assert set(kind.cache.values()) == set(kind.hash), kind.name


def held_now(eng):
    """While lanes run (scheduler thread paused by the caller's timing, so
    read defensively): kind -> pid -> the lanes that hold it."""
    out = []
    for k, kind in enumerate(eng._kinds):
        holders = {}
        for seq in list(eng._running):
            tables, held = seq.tables, seq.held
            if tables is None or len(held) <= k:
                continue
            for pid in tables[k][:held[k]].tolist():
                holders.setdefault(pid, []).append(seq)
        out.append(holders)
    return out


def test_the_engine_sizes_each_kind_from_the_models_config():
    eng, _ = small_engine(max_seqs=4, prefill_chunk=32)
    full, window = eng._kinds
    assert (full.name, full.horizon, full.width, full.usable) == \
        ("full", None, 10, 40)
    # a window, a chunk and the page the window starts in; the pool: that
    # for each of 4 lanes and one cached context
    assert window.horizon == WINDOW and window.width == \
        pages_for(WINDOW + 32, PAGE) + 1 == 5
    assert window.usable == min(40, 4 * 5 + 10) == 30
    assert eng._w == (10, 5) and eng._by_kind
    snap = eng.stats_snapshot()
    assert snap["page_kinds"]["window"] == {
        "horizon": 32, "table_width": 5, "usable_pages": 30,
        "free_pages": 30, "prefix_pages": 0}
    assert snap["usable_pages"] == 70 and snap["free_pages"] == 70
    # a family without kinds: the one kind, the seam's plain form
    plain = gs.engine()
    assert [k.name for k in plain._kinds] == ["full"]
    assert plain._w == plain._table_width and not plain._by_kind
    assert set(plain._hbm_bytes(plain)) == {"kv_pages", "kv_prefix"}


@pytest.mark.parametrize("cfg,tol", [(F32, F32_TOL), (BF16, GAP_TOL)])
def test_window_pages_return_to_the_free_list_while_the_lane_lives(cfg, tol):
    """Four lanes of 100-130 tokens (over three windows) decode 12 tokens
    each: every lane lets window pages go while it lives, they return to
    the free list and are taken again; what is generated is the
    reference's greedy continuation; afterwards every kind's allocator
    adds up."""
    eng, params = small_engine(cfg)
    prompts = [tokens(30 + i, 100 + 10 * i) for i in range(4)]
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    outs = [h.result() for h in handles]
    for ids, out in zip(prompts, outs):
        assert len(out) == 12
        assert harness.greedy_gap(ref.forward, params, cfg, ids, out) < tol
    settled(eng)
    snap = eng.stats_snapshot()
    # a lane of n tokens lets go every window page wholly behind its last
    # query's window; the prompt's go to the prefix cache (idle there, and
    # the allocator's to reclaim), the rest straight to the free list
    least = sum(first_page(len(p) + 11, WINDOW, PAGE) for p in prompts)
    assert snap["window_pages_dropped"] >= least - 4
    assert snap["window_pages_freed"] <= snap["window_pages_dropped"]
    window = eng._kinds[1]
    # the window pool never held a lane's whole history: 4 lanes of 7-9
    # pages each would be 32 pages of its 30 at once; a window's worth is 3
    assert window.usable == 30
    assert snap["window_pages_walked"] > 0 and snap["full_pages_walked"] > 0
    assert snap["attn_pages_walked"] == snap["window_pages_walked"] \
        + snap["full_pages_walked"]
    assert snap["window_pages_held"] < snap["full_pages_held"] * 3
    hbm = eng._hbm_bytes(eng)
    row = cfg.kv_row * jnp.dtype(cfg.dtype).itemsize * 2 * PAGE
    assert hbm["kv_pages"] == 41 * row * 1  # one full layer
    assert hbm["kv_pages_window"] == 31 * row * 3  # three window layers


def test_a_freed_window_page_is_written_by_another_lane_mid_sequence():
    """Step by step on the scheduler's own structures: a page that lane A
    let go is in lane B's table while A is still running, and no private
    page is ever in both."""
    eng, params = small_engine(F32, max_seqs=2)
    eng.start = lambda: None  # this test turns the scheduler by hand
    # a short prompt and a long answer: the pages of GENERATED tokens are
    # no prompt's, so the prefix cache never keeps them and they go
    # straight back to the free list as the window passes them
    ids_a, ids_b = tokens(40, 40), tokens(41, 60)
    a, b = eng.submit(ids_a, max_new_tokens=90), None
    ever_a, moved = set(), set()
    for _ in range(400):
        eng._step()
        holders = held_now(eng)[1]
        assert all(len(v) == 1 for v in holders.values()), \
            "a private window page in two tables"
        now = {h: {p for p, v in holders.items() if v[0].handle is h}
               for h in (a, b)}
        ever_a |= now[a]
        if b is None and eng.stats.window_pages_freed >= 2:
            b = eng.submit(ids_b, max_new_tokens=4)
        if not a.done:
            moved |= now.get(b, set()) & (ever_a - now[a])
        if a.done and b is not None and b.done and not eng._running \
                and eng._inflight is None:
            break
    assert a.done and b.done and moved, "no page went from lane to lane"
    for h, ids in ((a, ids_a), (b, ids_b)):
        assert harness.greedy_gap(ref.forward, params, F32, ids,
                                  h.tokens) < F32_TOL
    settled(eng)


@pytest.mark.parametrize("cfg,tol", [(F32, F32_TOL), (BF16, GAP_TOL)])
def test_a_prefix_hit_on_a_context_longer_than_the_window(cfg, tol):
    """A 96-token system prompt (three windows) shared by three requests:
    the first leaves every page of it in BOTH kinds' caches (the window
    kind's as its lane let them go), the others adopt all six pages of the
    full kind and only the pages their first query still sees of the window
    kind; the generated tokens are the reference's."""
    eng, params = small_engine(cfg)
    system = tokens(50, 96)
    prompts = [system + tokens(51 + i, 20 + 7 * i) for i in range(3)]
    first = eng.submit(prompts[0], max_new_tokens=6)
    outs = [first.result()]
    assert first.prefix_reused_tokens == 0
    full, window = eng._kinds
    # the whole prompt's full pages, in both kinds
    n_full = len(prompts[0]) // PAGE
    assert len(full.cache) == len(window.cache) == n_full
    rest = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
    outs += [h.result() for h in rest]
    for h in rest:
        assert h.prefix_reused_tokens == 96
    for ids, out in zip(prompts, outs):
        assert harness.greedy_gap(ref.forward, params, cfg, ids, out) < tol
    snap = eng.stats_snapshot()
    assert snap["prefix_hits"] == 12  # six pages, twice
    assert snap["prefill_tokens_first"] == sum(map(len, prompts)) - 2 * 96
    settled(eng)
    # what a hit hands over in the window kind: from the first page its
    # first query (at 96) still reads
    assert first_page(96, WINDOW, PAGE) == 4


def test_a_prefix_hit_hands_the_window_kind_only_what_is_still_seen():
    eng, _ = small_engine(F32, max_seqs=2)
    eng.start = lambda: None
    system = tokens(60, 96)
    warm = eng.submit(system + tokens(61, 9), max_new_tokens=2)
    while not warm.done:
        eng._step()
    eng._step()
    h = eng.submit(system + tokens(62, 30), max_new_tokens=2)
    eng._ensure_pool()
    eng._admit()
    seq = eng._running[0]
    assert h.prefix_reused_tokens == 96
    assert seq.bases == [0, 4] and seq.held[0] == pages_for(127, PAGE)
    cached = [set(k.cache.values()) for k in eng._kinds]
    assert set(seq.tables[0][:6].tolist()) <= cached[0]
    assert set(seq.tables[1][:2].tolist()) <= cached[1]  # pages 4 and 5
    assert not set(seq.tables[1][2:seq.held[1]].tolist()) & cached[1]
    # a cached window page that was reclaimed: the hit shrinks to below it
    gone = eng._prefix_page_keys(system)[4]
    pid = eng._kinds[1].cache.pop(gone)
    eng._kinds[1].hash.pop(pid)
    assert eng._prefix_hits(eng._prefix_page_keys(system), 6) == 4
    eng._kinds[1].cache[gone], eng._kinds[1].hash[pid] = pid, gone
    while not h.done:
        eng._step()
    settled(eng)


@pytest.mark.parametrize("cfg,tol", [(F32, F32_TOL), (BF16, GAP_TOL)])
def test_eviction_and_re_prefill_with_two_kinds(cfg, tol):
    """A full pool too small for four long lanes at once: the youngest is
    evicted, requeued and re-prefilled from its prompt and the tokens it
    had produced, through both kinds; every stream is the one its prompt
    gets alone."""
    eng, params = small_engine(cfg, pool_pages=25, max_seq_tokens=160)
    prompts = [tokens(70 + i, 100 + 5 * i) for i in range(4)]
    handles = [eng.submit(p, max_new_tokens=40) for p in prompts]
    outs = [h.result() for h in handles]
    snap = eng.stats_snapshot()
    assert snap["evictions"] > 0 and snap["readmissions"] > 0
    assert snap["prefill_tokens_re"] > 0
    for ids, out in zip(prompts, outs):
        assert len(out) == 40
        assert harness.greedy_gap(ref.forward, params, cfg, ids, out) < tol
    if cfg is F32:
        lone, _ = small_engine(cfg)
        assert [lone.generate(p, max_new_tokens=40) for p in prompts] == outs
    settled(eng)


def test_idle_cached_window_pages_are_reclaimed_under_pressure():
    """Eight distinct prompts leave 50 prompt pages with the window kind's
    prefix cache, whose pool has 30: the allocator reclaims idle cached
    pages, oldest first, and never one a lane holds; the streams are the
    reference's and every kind adds up afterwards."""
    eng, params = small_engine(F32)
    prompts = [tokens(80 + i, 100 + 3 * i) for i in range(8)]
    handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
    outs = [h.result() for h in handles]
    for ids, out in zip(prompts, outs):
        assert harness.greedy_gap(ref.forward, params, F32, ids,
                                  out) < F32_TOL
    settled(eng)
    window = eng._kinds[1]
    published = sum(len(p) // PAGE for p in prompts)
    assert published > window.usable >= len(window.cache)
    assert eng.stats_snapshot()["evictions"] == 0


def test_a_failed_step_resets_every_kind():
    eng, _ = small_engine(F32, max_seqs=2)
    eng.start = lambda: None
    h = eng.submit(tokens(90, 100), max_new_tokens=4)
    for _ in range(4):
        eng._step()
    assert any(k.refs for k in eng._kinds)
    plain = cm.parallel_moe_fused_step
    try:
        cm.fused_step = lambda *a, **kw: (_ for _ in ()).throw(
            RuntimeError("boom"))
        with pytest.raises(RuntimeError):
            eng._step()
    finally:
        cm.fused_step = plain
    assert eng._pages is None
    for kind in eng._kinds:
        assert not kind.cache and not kind.hash and not kind.refs
    assert h is not None


# ------------------------------------------------ (f) Heimdall, over SSE
def test_heimdall_streams_the_references_greedy_continuation_over_sse():
    """``db.set_heimdall_generator`` -> ``_wire_genserve`` ->
    GenerationEngine (the family resolved from the config's type, its page
    kinds from the config) -> ``POST /v1/chat/completions`` as server-sent
    events: the streamed ids read no gap against the reference's logits
    over the prompt that Heimdall assembled, which is longer than ten
    windows."""
    import nornicdb_tpu
    from nornicdb_tpu import genserve
    from nornicdb_tpu.config import GenServeConfig
    from nornicdb_tpu.heimdall import EngineGenerator, WeightsGenerator
    from nornicdb_tpu.models.tokenizer import HashTokenizer
    from nornicdb_tpu.server import HttpServer

    cfg = dataclasses.replace(F32, vocab_size=2048)
    params = make_params(cfg, 13)
    generator = WeightsGenerator(cfg, params, HashTokenizer(cfg.vocab_size),
                                 max_context=1024)
    with pytest.raises(RuntimeError, match="genserve engine only"):
        generator.generate("hello")
    genserve.configure(GenServeConfig(
        max_seqs=2, max_seq_tokens=1536, pool_pages=200, page_size=PAGE,
        prefill_chunk=64, deadline_ms=0))
    db = nornicdb_tpu.open_db("")
    http_server = None
    try:
        db.set_heimdall_generator(generator)
        engine = db.genserve_engine()
        assert isinstance(db.heimdall.generator, EngineGenerator)
        assert engine._family is cm
        assert [(k.name, k.horizon) for k in engine._kinds] == \
            [("full", None), ("window", WINDOW)]
        seen = []
        submit = engine.submit
        engine.submit = lambda ids, *a, **kw: (
            seen.append(list(ids)), submit(ids, *a, **kw))[1]
        http_server = HttpServer(db, port=0)
        http_server.start()
        conn = http.client.HTTPConnection("127.0.0.1", http_server.port,
                                          timeout=300)
        conn.request("POST", "/v1/chat/completions", json.dumps({
            "messages": [{"role": "user", "content": "how many nodes?"}],
            "max_tokens": 6, "stream": True}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type").startswith("text/event-stream")
        out = []
        for line in resp.read().decode().splitlines():
            if line.startswith("data: ") and line != "data: [DONE]":
                for choice in json.loads(line[6:]).get("choices", []):
                    text = (choice.get("delta") or {}).get("content") or ""
                    out += [int(i) for i in re.findall(r"<(\d+)>", text)]
        conn.close()
        stats = engine.stats_snapshot()
    finally:
        if http_server is not None:
            http_server.stop()
        genserve.configure(None)
        if db.genserve_engine() is not None:
            db.genserve_engine().stop()
        db.close()
    assert len(seen) == 1 and len(out) == 6
    assert len(seen[0]) > 10 * WINDOW
    assert harness.greedy_gap(ref.forward, params, cfg, seen[0],
                              out) < F32_TOL
    assert stats["window_pages_dropped"] >= \
        first_page(len(seen[0]), WINDOW, PAGE) - 1
    assert stats["expert_assignments"] > 0 and stats["routed_rows"] > 0
