"""Nemotron 3 Nano's language model behind genserve: the program
(``models/nemotron_h.py``: one mixer a layer, Mamba-2 layers whose state
lives in a pool of SLOTS beside the attention layers' K/V pages, ungated
relu^2 experts of which a share is held, the fused ragged step) against the
plain float32 reference (``models/reference/nemotron_h.py``: the sequential
recurrence from a zero state), and the scheduler's STATE kind
(``genserve/engine.py``: a slot a lane, snapshots at chunk ends, a prefix
hit that ends where a snapshot stands), at small sizes on the CPU.

Every comparison is on logits (or on the greedy GAP read off the
reference's logits), never on sampled tokens.  Tolerances, and why:

* ``F32_TOL`` 2e-4: the program in float32 computes the reference's
  mathematics in another order (batched, the chunked matmul form of the
  recurrence in place of one token after the other, blocks of pages under
  a running softmax, masked experts); readings are 2e-6 to 7e-6 on logits
  of spread ~1.
* ``BF16_TOL`` 0.25, on the MEDIAN over positions of a position's largest
  logit error (:func:`typical`): rounding reads 0.04-0.08 on logits of
  spread ~1 (the untied head is N(0, 1/hidden), not the 0.02 table); a
  routed model is discontinuous besides, so the largest error cannot tell
  bfloat16 from fp8 and the median can: the fp8 control reads 0.5-0.9.
* ``WIRING_TOL`` 0.01: a float32 step wired wrongly, or a state kept
  wrongly (listed at the test), is off by 0.02 and more; the sound float32
  step reads under ``F32_TOL``.
* ``GAP_TOL`` 0.3 for bfloat16 through the engine: the served token's
  reference logit under the reference's best; twice the largest logit
  error and a routed model's ties.  A token drawn at random lies ~3 under
  the best.
"""

import dataclasses
import functools
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
from jax.experimental import pallas as pl

from nornicdb_tpu.models import experts
from nornicdb_tpu.models import nemotron_h as nh
from nornicdb_tpu.models.reference import nemotron_h as ref
from nornicdb_tpu.ragged import ROUTING_COUNTERS, STATE

BF16 = nh.NEMOTRON_H_SMALL
F32 = dataclasses.replace(BF16, dtype="float32")
F32_TOL = 2e-4
BF16_TOL = 0.25
WIRING_TOL = 0.01
GAP_TOL = 0.3
N_MAMBA = len(F32.layers_of("M"))


def make_params(cfg, seed: int):
    """Seeded weights; the router's rows at four times the usual spread (so
    a row's four gates are uneven and what is wrong in the routed sum
    shows), a selection bias that reorders scores next to the edge, a
    convolution bias, and non-trivial norm scales, so that any of them left
    out shows."""
    params = with_norm_scales(
        nh.init_params(cfg, jax.random.PRNGKey(seed)), seed + 1000)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 2000), 64))
    for blk in params["blocks"]:
        if "router" in blk:
            blk["router"] = (blk["router"].astype(jnp.float32) * 4.0).astype(
                blk["router"].dtype)
            blk["router_bias"] = 0.05 * jax.random.normal(
                next(keys), blk["router_bias"].shape)
        if "conv" in blk:
            blk["conv"]["b"] = 0.1 * jax.random.normal(
                next(keys), blk["conv"]["b"].shape)
    return params


def hold_experts(params, cfg, first: int, count: int):
    """One expert-parallel rank's share of a model whose tree holds every
    routed expert: experts ``first .. first + count - 1`` of each expert
    layer and everything else (the shared expert too) as it was."""
    lo = first - cfg.held_experts[0]
    blocks = [{**blk, "experts": {k: w[lo:lo + count]
                                  for k, w in blk["experts"].items()}}
              if "experts" in blk else blk for blk in params["blocks"]]
    return ({**params, "blocks": blocks},
            dataclasses.replace(cfg, held_experts=(first, count)))


def tokens(seed: int, n: int, vocab: int = BF16.vocab_size) -> list[int]:
    return draw(seed, n, vocab)


def new_pool(params, cfg=F32, family=nh, pages=(40, 12)):
    return Pool(family, cfg, params, pages=pages)


def prefill(pool, lane, ids, start=0, snapshot_at=None, chunk=16):
    """``ids[start:]`` through the chunk lane; the chunk that ends at
    ``snapshot_at`` tokens leaves a snapshot.  Returns (the last chunk's
    last logits, the snapshot's slot)."""
    at, slot, logits = start, None, None
    while at < len(ids):
        piece = ids[at:at + chunk]
        if at + len(piece) == snapshot_at:
            slot = lane.snapshot()
        logits = pool.step(chunk=(piece, at, lane))[-1]
        at += len(piece)
    return logits, slot


# ------------------------------------- (a) the step against the reference
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chunked_prefill_then_decode_is_the_reference_in_float32(seed):
    """A prompt that ends inside a chunk (50 tokens in chunks of 16: the
    last chunk has padding rows), then eight decode steps, one lane: every
    produced position's logits are the reference's."""
    params = make_params(F32, seed)
    ids = tokens(seed, 50)
    pool = new_pool(params)
    out, rows = pool.serve(ids, Lane(pool), steps=8)
    want = harness.reference_rows(ref.forward, params, F32, ids, out)
    assert largest(rows, want) < F32_TOL, largest(rows, want)
    counts = dict(zip(pool.counters, pool.counts.tolist()))
    # every token advanced the lane's state once a Mamba layer, and was
    # routed once an expert layer; padding rows neither
    assert counts["ssm_rows"] == (50 + 7) * N_MAMBA
    assert counts["routed_rows"] == (50 + 7) * len(F32.layers_of("E"))
    assert nh.STEP_COUNTERS[:4] == ROUTING_COUNTERS


@pytest.mark.parametrize("seed", [3, 4])
def test_bf16_step_is_within_tolerance_and_fp8_is_not(seed):
    params = make_params(BF16, seed)
    ids = tokens(seed, 64)
    pool = new_pool(params, BF16)
    out, rows = pool.serve(ids, Lane(pool), steps=8)
    want = harness.reference_rows(ref.forward, params, BF16, ids, out)
    low = harness.reference_rows(ref.forward, params, BF16, ids, out,
                                 rounded=fp8)
    assert typical(rows, want) < BF16_TOL, typical(rows, want)
    assert typical(low, want) > BF16_TOL, typical(low, want)


def plain_step(x, dt, a, b, c, s0):
    """``ssm_step`` in ``ssd_block``'s shapes at ``T = 1``."""
    y, s_1 = nh.ssm_step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], s0)
    return y[:, None], s_1


def recurrence_inputs(seed, lanes, t, h, p, g, n):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(lanes, t, h, p))
    dt = rng.uniform(0.01, 0.5, size=(lanes, t, h))
    dt[1, t // 2:] = 0.0  # lane 1 ends half way (at t = 1: it has no token)
    a = -rng.uniform(0.5, 8.0, size=h)
    b, c = rng.normal(size=(2, lanes, t, g, n))
    s0 = rng.normal(size=(lanes, h, p, n))
    return x, dt, a, b, c, s0


@pytest.mark.parametrize("t,form", [
    (1, nh.ssd_block), (7, nh.ssd_block), (16, nh.ssd_block),
    (1, plain_step)], ids=["1", "7", "16", "1-plain"])
def test_the_chunked_recurrence_is_the_sequential_one(t, form):
    """``ssd_block`` (the matmul form over a block of rows, from a state),
    and at ``t = 1`` ``ssm_step`` (the decode block's plain step), against
    one token after the other in numpy float64; rows at dt = 0 (padding)
    neither decay nor feed the state: lane 1 at ``t = 1`` keeps its own."""
    lanes, h, p, g, n = 3, 8, 4, 2, 5
    x, dt, a, b, c, s0 = recurrence_inputs(t, lanes, t, h, p, g, n)
    y, s_t = form(*(jnp.asarray(v, jnp.float32)
                    for v in (x, dt, a, b, c, s0)))
    s, want = s0.copy(), np.zeros_like(x)
    for i in range(t):
        bh, ch = (np.repeat(v[:, i], h // g, axis=1) for v in (b, c))
        s = np.exp(dt[:, i] * a)[..., None, None] * s \
            + (dt[:, i, :, None] * x[:, i])[..., None] * bh[:, :, None, :]
        want[:, i] = np.einsum("lhpn,lhn->lhp", s, ch)
    live = dt[..., :1] > 0  # a row at dt = 0 reads the state, unused
    np.testing.assert_allclose(np.asarray(y) * live[..., None],
                               want * live[..., None], atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_t), s, atol=2e-5)
    if t == 1:
        assert (np.asarray(s_t)[1] == s0[1].astype(np.float32)).all()


@pytest.mark.parametrize("h,g", [(8, 1), (8, 2), (8, 8), (6, 3)])
def test_the_plain_step_is_the_chunked_form_at_one_row(h, g):
    """The two forms of one algorithm at the shape where they meet:
    ``ssm_step`` against ``ssd_block`` at ``T = 1``, in float32, for one
    group, several heads a group (``H / G`` 8, 4, 2) and a group a head; a
    lane at dt = 0 comes back bit for bit."""
    lanes, p, n = 4, 4, 16
    args = [jnp.asarray(v, jnp.float32)
            for v in recurrence_inputs(100 * h + g, lanes, 1, h, p, g, n)]
    (y, s_1), (want_y, want_s) = plain_step(*args), nh.ssd_block(*args)
    assert y.shape == want_y.shape and s_1.shape == want_s.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_1), np.asarray(want_s),
                               atol=2e-5)
    assert (np.asarray(s_1)[1] == np.asarray(args[5])[1]).all()


def interpreted(monkeypatch):
    """The TPU kernel on this backend: Pallas's interpreter in place of its
    compiler (the program takes no option for it)."""
    monkeypatch.setattr(nh.pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))


@pytest.mark.parametrize("h,g", [(8, 1), (8, 2), (8, 8), (32, 4)])
def test_the_one_pass_kernel_is_gather_step_scatter(monkeypatch, h, g):
    """``step_slots_in_place`` (the decode block on a TPU: the pool's own
    slots as the kernel's blocks) against ``step_slots`` (gather, plain
    step, scatter) over one pool of three layers: lanes that read and write
    their own slot, one that begins from the null slot, one that leaves a
    snapshot behind (reads one slot, writes another), one at dt = 0, and
    lanes that write nothing, whose layer's null slot stays zeros."""
    interpreted(monkeypatch)
    lanes, p, n, slots, layer = 7, 8, 16, 9, 1
    rng = np.random.default_rng(10 * h + g)
    x, dt, a, b, c, _ = (jnp.asarray(v, jnp.float32) for v in
                         recurrence_inputs(h + g, lanes, 1, h, p, g, n))
    ssm = rng.normal(size=(3, slots, h, p, n)).astype(np.float32)
    ssm[:, 0] = 0.0
    read = np.array([3, 1, 0, 5, 2, 0, 0])
    write = np.array([3, 1, 4, 6, 2, 0, 0])
    src, dst = (jnp.asarray(layer * slots + v, jnp.int32)
                for v in (read, write))
    args = (src, dst, jnp.asarray(write != 0), x[:, 0], dt[:, 0], a,
            b[:, 0], c[:, 0])
    flat = jnp.asarray(ssm.reshape((-1,) + ssm.shape[2:]))
    want_y, want = nh.step_slots(flat, *args)
    y, got = jax.jit(nh.step_slots_in_place, donate_argnums=0)(
        jnp.array(flat), *args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    got = np.asarray(got).reshape(ssm.shape)
    assert not got[:, 0].any()
    assert (got[[0, 2]] == ssm[[0, 2]]).all()             # other layers
    assert (got[layer, [5, 7, 8]] == ssm[layer, [5, 7, 8]]).all()
    assert (got[layer, 1] == ssm[layer, 1]).all()         # the lane at dt = 0


# ------------------------------------------- (b) the config and the cut
def test_the_published_config_and_the_benchmarks_cut():
    cfg = nh.NemotronHConfig()
    assert (cfg.d_inner, cfg.conv_dim, cfg.kv_row) == (4096, 6144, 256)
    assert cfg.hybrid_override_pattern.count("M") == 23
    assert cfg.hybrid_override_pattern.count("E") == 23
    assert cfg.hybrid_override_pattern.count("*") == 6
    cut = nh.NEMOTRON_3_NANO_EP8_27L
    assert cut.hybrid_override_pattern == cfg.hybrid_override_pattern[:27] \
        == "MEMEM*" + "EMEMEM*" * 3
    assert (len(cut.layers_of("M")), len(cut.layers_of("E")),
            len(cut.layers_of("*"))) == (12, 11, 4)
    shapes = jax.eval_shape(lambda: nh.init_params(cut,
                                                   jax.random.PRNGKey(0)))
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    blocks = shapes["blocks"]
    # ISSUE 41's counts, a layer with its norm
    assert size(blocks[0]) == 38_744_896
    assert blocks[0]["in_proj"]["w"].shape == (2688, 10304)
    assert size(blocks[5]) == 23_399_040
    assert blocks[1]["experts"]["up"].shape == (16, 2688, 1856)
    assert "gate" not in blocks[1]["experts"]
    assert blocks[1]["shared"]["down"].shape == (1, 3712, 2688)
    assert size(blocks[1]) - size(blocks[1]["experts"]) == 20_302_592
    assert size(shapes) == 2_626_049_152
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        dataclasses.replace(cut, num_hidden_layers=26)
    kv, state = jax.eval_shape(lambda: nh.init_pages(cut, (8193, 82), 16))
    assert kv.shape == (4, 2, 8193, 16, 256)
    assert state["ssm"].shape == (12, 82, 64, 64, 128)
    assert state["ssm"].dtype == jnp.float32
    assert state["conv"].shape == (12, 82, 3, 6144)
    assert nh.num_pages((kv, state)) == (8193, 82)
    assert nh.page_kinds(cut) == (("full", None), ("state", STATE))


@pytest.mark.parametrize("seed", [0, 1])
def test_sigmoid_routing_against_a_numpy_loop(seed):
    params = make_params(F32, seed)
    blk = params["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(seed), (24, F32.hidden_size))
    ids, gates = (np.asarray(a) for a in nh.route(F32, blk, x))
    w, bias = np.asarray(blk["router"], np.float64), np.asarray(
        blk["router_bias"], np.float64)
    for t in range(x.shape[0]):
        s = 1.0 / (1.0 + np.exp(-(np.asarray(x[t], np.float64) @ w)))
        top = np.argsort(-(s + bias), kind="stable")[:4]
        assert sorted(ids[t]) == sorted(top)
        # the bias chose; the gates are the bare scores, normalised, x 2.5
        want = s[ids[t]] / s[top].sum() * 2.5
        np.testing.assert_allclose(gates[t], want, rtol=1e-5)
    assert abs(gates.sum(-1) - 2.5).max() < 1e-5


@pytest.mark.parametrize("seed", [0, 1])
def test_the_eight_shares_add_up_to_the_uncut_expert_layer(seed):
    """The guide's test of the cut: the parts of an expert layer's result
    that all 8 ranks give (2 of the 16 experts each), with the shared
    expert, which every rank computes alike, counted once, add up to what
    the uncut reference gives for the whole layer."""
    params = make_params(F32, seed)
    blk = params["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(seed + 5), (24, F32.hidden_size))
    whole = np.asarray(ref.expert_layer(F32, blk, x, F32.held_experts))
    shared = np.asarray(experts.held_experts(
        blk["shared"], x, jnp.ones((24, 1), jnp.float32)))
    total, assigned = np.zeros_like(whole), 0
    for first in range(0, 16, 2):
        share, cfg = hold_experts(params, F32, first, 2)
        assert share["blocks"][1]["experts"]["up"].shape[0] == 2
        part, counts = nh.expert_layer(cfg, share["blocks"][1], x)
        total += np.asarray(part) - shared
        assigned += int(counts[0])
        # the reference, given the same share, leaves out the same
        alone = ref.expert_layer(cfg, share["blocks"][1], x, (first, 2))
        assert largest(part, alone) < F32_TOL
    assert largest(total + shared, whole) < F32_TOL
    assert assigned == 24 * F32.num_experts_per_tok


def test_ungated_experts_beside_gated_ones():
    """``experts.held_experts`` takes the form from the tree: a stack with
    a ``gate`` is SwiGLU, one without is ``down(relu(up x)^2)``."""
    rng = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(rng[0], (6, 8))
    up, gate = jax.random.normal(rng[1], (3, 8, 5)), \
        jax.random.normal(rng[2], (3, 8, 5))
    down = jax.random.normal(rng[3], (3, 5, 8))
    weight = jax.random.uniform(rng[4], (6, 3))
    plain = sum(weight[:, c, None] * (jnp.square(jax.nn.relu(x @ up[c]))
                                      @ down[c]) for c in range(3))
    assert largest(experts.held_experts({"up": up, "down": down}, x, weight),
                   plain) < 1e-4
    swiglu = sum(weight[:, c, None] * ((jax.nn.silu(x @ gate[c])
                                        * (x @ up[c])) @ down[c])
                 for c in range(3))
    assert largest(experts.held_experts(
        {"gate": gate, "up": up, "down": down}, x, weight), swiglu) < 1e-4


# --------------------------------- (c) the state kind, slot by slot
def test_a_prefix_hit_taken_from_a_snapshot_is_the_uncached_run():
    """Lane a leaves a snapshot where its second chunk ends (32 tokens) and
    goes on; lane b, behind the same 32 tokens, adopts a's two K/V pages,
    reads the snapshot and prefills only what follows: both read the
    reference's logits, and the snapshot is as a's step left it."""
    params = make_params(F32, 5)
    head = tokens(5, 32)
    ids_a, ids_b = head + tokens(6, 21), head + tokens(7, 30)
    pool = new_pool(params)
    a = Lane(pool)
    last, snap = prefill(pool, a, ids_a, snapshot_at=32)
    assert snap is not None and snap != a.slot[1]
    kept = np.asarray(pool.pool[1]["ssm"][:, snap])
    b = Lane(pool, begin=snap)
    b.pages[0] = a.pages[0][:2]  # the prefix cache hands out page numbers
    out_b, rows_b = pool.serve(ids_b, b, start=32, steps=6)
    want_b = harness.reference_rows(ref.forward, params, F32, ids_b, out_b)
    assert largest(rows_b, want_b) < F32_TOL
    # a goes on decoding beside it, from its own slot
    out_a = [int(last.argmax())]
    rows_a = [last]
    for n in range(len(ids_a), len(ids_a) + 5):
        rows_a.append(pool.step(decode=[(out_a[-1], n, a)])[0])
        out_a.append(int(rows_a[-1].argmax()))
    want_a = harness.reference_rows(ref.forward, params, F32, ids_a, out_a)
    assert largest(np.stack(rows_a), want_a) < F32_TOL
    assert (np.asarray(pool.pool[1]["ssm"][:, snap]) == kept).all()
    assert np.abs(kept).max() > 0


def test_a_reseated_lane_starts_from_zeros_not_from_what_was_left():
    """A slot goes back to the free list as its last holder left it; the
    next lane that takes it READS the null slot in its first step."""
    params = make_params(F32, 8)
    pool = new_pool(params, pages=(40, 3))  # the null slot and two
    first = Lane(pool)
    pool.serve(tokens(8, 40), first, steps=3)
    pool.free[1].insert(0, first.slot[1])
    ids = tokens(9, 37)
    again = Lane(pool)
    assert again.slot[1] == first.slot[1]
    assert np.abs(np.asarray(pool.pool[1]["ssm"][:, again.slot[1]])).max() > 0
    out, rows = pool.serve(ids, again, steps=4)
    want = harness.reference_rows(ref.forward, params, F32, ids, out)
    assert largest(rows, want) < F32_TOL


def test_padding_rows_and_empty_lanes_advance_nothing():
    """Two lanes decode in lanes 0 and 1 beside a third sequence's chunk
    that ends inside its bucket (padding rows) while the dump lane and the
    rows without a lane write the null slot: each lane's state after the
    step is what it is when the lane runs alone, and slot 0 stays zeros."""
    params = make_params(F32, 10)
    prompts = [tokens(10 + i, 20 + 3 * i) for i in range(3)]

    def run(together: bool):
        pool = new_pool(params)
        lanes = [Lane(pool) for _ in prompts]
        firsts = [int(prefill(pool, lane, ids)[0].argmax())
                  for lane, ids in zip(lanes[:2], prompts[:2])]
        decode = [(tok, len(ids), lane) for tok, ids, lane in
                  zip(firsts, prompts, lanes)]
        if together:
            logits = pool.step(decode=decode,
                               chunk=(prompts[2][:13], 0, lanes[2]))[:2]
        else:
            logits = [pool.step(decode=[row])[0] for row in decode]
            pool.step(chunk=(prompts[2][:13], 0, lanes[2]))
        state = pool.pool[1]
        assert not np.asarray(state["ssm"][:, 0]).any()
        assert not np.asarray(state["conv"][:, 0]).any()
        return np.stack(logits), [
            np.asarray(state["ssm"][:, lane.slot[1]]) for lane in lanes]

    (both, states), (alone, want) = run(True), run(False)
    assert largest(both, alone) < F32_TOL
    for got, exp in zip(states, want):
        assert np.abs(got - exp).max() < 1e-5


@pytest.mark.parametrize("seed", [12, 13])
def test_the_decode_block_and_the_chunk_block_agree_on_one_token(seed):
    """The same token behind the same prompt, once as a decode row (the
    plain step, ``T = 1``) and once as a one-token chunk (the chunked form
    over a bucket of 16 rows, 15 of them padding at dt = 0): the same
    logits, and the same convolution inputs and SSM state left in the
    lane's slot, in float32."""
    params = make_params(F32, seed)
    ids = tokens(seed, 21)

    def run(as_chunk: bool):
        pool = new_pool(params)
        lane = Lane(pool)
        tok = int(prefill(pool, lane, ids)[0].argmax())
        logits = pool.step(chunk=([tok], len(ids), lane))[-1] if as_chunk \
            else pool.step(decode=[(tok, len(ids), lane)])[0]
        return logits, {k: np.asarray(v[:, lane.slot[1]])
                        for k, v in pool.pool[1].items()}

    (dec, dec_state), (chk, chk_state) = run(False), run(True)
    assert largest(dec[None], chk[None]) < F32_TOL
    assert np.abs(dec_state["ssm"]).max() > 0.01
    for part in ("conv", "ssm"):
        assert np.abs(dec_state[part] - chk_state[part]).max() < 1e-5, part


def _rotated(plain):
    """``attend_step`` with q and k rotated by position (half pairs): what
    the config's unused ``rope_theta`` would do."""
    def step(cfg, blk, rows, pool, at, x):
        d, dense = cfg.head_dim, nh.dense
        angles = rows.pos[:, None] * (
            1.0 / 10000.0 ** (jnp.arange(0, d, 2) / d))
        cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]

        def rotating(p, rows_in):
            y = dense(p, rows_in)
            if p is not blk["q"] and p is not blk["k"]:
                return y
            heads = y.reshape(y.shape[0], -1, d)
            lo, hi = heads[..., :d // 2], heads[..., d // 2:]
            return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                                   axis=-1).reshape(y.shape).astype(y.dtype)

        nh.dense = rotating
        try:
            return plain(cfg, blk, rows, pool, at, x)
        finally:
            nh.dense = dense

    return step


def _broken(monkeypatch, fault: str):
    """Plant one fault in the module (the benchmark's planted faults,
    bench/tests/faults_nemo.py, at this file's size) and hand back a
    family whose step is traced anew."""
    served = F32
    if fault == "gates_unscaled":
        served = dataclasses.replace(F32, routed_scaling_factor=1.0)
    elif fault == "gates_unnormalised":
        plain = nh.route

        def raw(cfg, blk, x):
            ids, gates = plain(cfg, blk, x)
            s = jax.nn.sigmoid(x.astype(jnp.float32)
                               @ blk["router"].astype(jnp.float32))
            return ids, jnp.take_along_axis(s, ids, -1) \
                * cfg.routed_scaling_factor
        monkeypatch.setattr(nh, "route", raw)
    elif fault in ("shared_dropped", "held_dropped"):
        plain = experts.held_experts
        drop = 1 if fault == "shared_dropped" else F32.held_experts[1]
        monkeypatch.setattr(
            experts, "held_experts", lambda tree, x, weight: plain(
                tree, x, weight * (tree["up"].shape[0] != drop)))
    elif fault == "rope_in_attention":
        monkeypatch.setattr(nh, "attend_step", _rotated(nh.attend_step))
    elif fault == "padding_advances":
        plain = nh.state_rows

        def every_row(rows, read, write, lmax):
            out = plain(rows, read, write, lmax)
            return out._replace(chunk=out.chunk and (
                *out.chunk[:2], jnp.ones_like(out.chunk[2]), *out.chunk[3:]))
        monkeypatch.setattr(nh, "state_rows", every_row)
    def anew(fn):
        """A NEW function object around the layer's body: jit's trace cache
        is keyed by the function, and a trace made under a fault (or
        before it) must not be another test's."""
        return jax.jit(lambda cfg, *a: fn.__wrapped__(cfg, *a),
                       static_argnums=(0,))

    for name in ("expert_layer", "mamba_layer"):
        monkeypatch.setattr(nh, name, anew(getattr(nh, name)))

    def fresh(params, cfg, meta, pages, **kw):  # its own function, so trace
        return nh.fused_step.__wrapped__(params, served, meta, pages, **kw)

    step = jax.jit(fresh, static_argnames=("cfg", "lmax", "w", "tq"),
                   donate_argnums=(3,))
    return types.SimpleNamespace(
        init_pages=nh.init_pages, page_kinds=nh.page_kinds, fused_step=step,
        STEP_COUNTERS=nh.STEP_COUNTERS)


def test_the_step_on_the_one_pass_kernel_is_the_step(monkeypatch):
    """The whole fused step with the decode block on the TPU's kernel (here
    under Pallas's interpreter, as ``platform_dependent``'s default): the
    same logits, the same states and a null slot of zeros as the step this
    backend runs, for decode lanes beside a chunk with padding rows, empty
    lanes, and a lane that goes on from the snapshot its prefill left."""
    params = make_params(F32, 14)
    prompts = [tokens(14 + i, 20 + 3 * i) for i in range(3)]

    def run(family):
        pool = new_pool(params, family=family)
        lanes = [Lane(pool) for _ in prompts]
        # lane 1's last chunk leaves a snapshot: its first decode row reads
        # that slot and writes the lane's own
        firsts = [int(prefill(pool, lane, ids,
                              snapshot_at=len(ids) if i else None)[0].argmax())
                  for i, (lane, ids) in enumerate(zip(lanes[:2], prompts))]
        decode = [(tok, len(ids), lane) for tok, ids, lane in
                  zip(firsts, prompts, lanes)]
        logits = [pool.step(decode=decode,
                            chunk=(prompts[2][:13], 0, lanes[2]))[:2]]
        decode = [(int(row.argmax()), at + 1, lane)
                  for row, (_, at, lane) in zip(logits[0], decode)]
        logits.append(pool.step(decode=decode))
        state = pool.pool[1]
        assert not np.asarray(state["ssm"][:, 0]).any()
        return np.concatenate(logits), np.asarray(state["ssm"])

    want, want_states = run(nh)  # traced before the kernel is planted
    interpreted(monkeypatch)
    # a new function object: the branches' traces are cached by function
    monkeypatch.setattr(nh, "step_slots",
                        lambda *args: nh.step_slots_in_place(*args))
    rows, states = run(_broken(monkeypatch, "no fault"))
    assert largest(rows, want) < F32_TOL
    assert np.abs(states - want_states).max() < 1e-5


@pytest.mark.parametrize("fault", [
    "gates_unscaled", "gates_unnormalised", "shared_dropped", "held_dropped",
    "rope_in_attention", "padding_advances", "dt_bias_left_out"])
def test_a_step_wired_wrongly_is_outside_the_tolerance(monkeypatch, fault):
    params = make_params(F32, 9)
    ids = tokens(9, 53)  # ends inside a chunk: the last one has padding rows
    served = params
    if fault == "dt_bias_left_out":
        served = {**params, "blocks": [
            {**blk, "dt_bias": jnp.zeros_like(blk["dt_bias"])}
            if "dt_bias" in blk else blk for blk in params["blocks"]]}
    pool = new_pool(served, family=_broken(monkeypatch, fault))
    out, rows = pool.serve(ids, Lane(pool), steps=4)
    want = harness.reference_rows(ref.forward, params, F32, ids, out)
    assert typical(rows, want) > WIRING_TOL, (fault, typical(rows, want))


@pytest.mark.parametrize("fault", [
    "snapshot_a_chunk_early", "conv_state_dropped_at_the_hit",
    "ssm_state_dropped_at_the_hit", "state_not_reset_on_reseat"])
def test_a_state_kept_wrongly_is_outside_the_tolerance(fault):
    """The scheduler's own faults, on the sound step: a hit that begins
    from the snapshot of the chunk before, a snapshot of which one half was
    lost, a lane that reads what its slot's last holder left."""
    params = make_params(F32, 11)
    head = tokens(11, 48)
    ids_a, ids_b = head + tokens(12, 9), head + tokens(13, 22)
    pool = new_pool(params)
    a = Lane(pool)
    at = 32 if fault == "snapshot_a_chunk_early" else 48
    _, snap = prefill(pool, a, ids_a, snapshot_at=at)
    kv, state = pool.pool
    if fault == "conv_state_dropped_at_the_hit":
        state = {**state, "conv": state["conv"].at[:, snap].set(0)}
    elif fault == "ssm_state_dropped_at_the_hit":
        state = {**state, "ssm": state["ssm"].at[:, snap].set(0)}
    pool.pool = (kv, state)
    begin = a.slot[1] if fault == "state_not_reset_on_reseat" else snap
    b = Lane(pool, begin=begin)
    if fault == "state_not_reset_on_reseat":
        out, rows = pool.serve(ids_b, b, steps=4)
    else:
        b.pages[0] = a.pages[0][:3]
        out, rows = pool.serve(ids_b, b, start=48, steps=4)
    want = harness.reference_rows(ref.forward, params, F32, ids_b, out)
    assert typical(rows, want) > WIRING_TOL, (fault, typical(rows, want))


def lowered_step(f: int, tq: int):
    """The small config's step class (F flat rows, a chunk bucket of tq),
    lowered from shapes."""
    params = jax.eval_shape(lambda: nh.init_params(BF16,
                                                   jax.random.PRNGKey(0)))
    w = (8, 1)
    meta = jax.ShapeDtypeStruct(
        (4 * f + LMAX + sum(LMAX * (1 + wk) for wk in w),), jnp.int32)
    pages = jax.eval_shape(lambda: nh.init_pages(BF16, (9, 5), PAGE))
    return nh.fused_step.lower(params, BF16, meta, pages, lmax=LMAX, w=w,
                               tq=tq)


def test_the_step_carries_its_scopes_and_its_own_module_name():
    lowered = lowered_step(16, 16)
    text = lowered.as_text(debug_info=True)
    for scope in ("ssm.project", "ssm.conv", "ssm.scan", "ssm.gate",
                  "ssm.out", "attn.project", "attn.attend", "moe.route",
                  "moe.experts", "moe.shared"):
        assert scope in text, scope
    module = re.search(r"module @(\S+)", lowered.as_text()).group(1)
    assert module == "jit_hybrid_fused_step"
    assert nh.STEP_COUNTERS == ROUTING_COUNTERS + (
        "attn_slots_walked", "attn_slots_table", "shared_run_pages",
        "ssm_rows")


# ----------------------------- (c') what a step class costs a warm start
def scan_ops(text: str, part: str | None = None) -> list[str]:
    """Of a lowered module's text (``debug_info=True``), or of ``part`` of
    it, the location name of every exponential and every ``dot_general``
    inside scope ``ssm.scan``: ``ssm.scan/exp``, ``ssm.scan/dot_general``."""
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    found = [names.get(loc, "") for loc in re.findall(
        r"stablehlo\.(?:exponential|dot_general)\b.*loc\((#loc\d+)\)",
        text if part is None else part)]
    return [name for name in found if "ssm.scan" in name]


@pytest.mark.parametrize("f,tq", [(8, 1), (32, 16)], ids=["decode", "chunk"])
def test_a_step_class_lowers_the_mamba_layer_once_and_no_matmul_a_decode_row(
        f, tq):
    """What a step class costs a WARM start (PERF.md section 7 (nn)): the
    engine traces and lowers every one of its step classes before it can
    ask the compile cache, so whatever is traced once a LAYER counts
    twelve-fold in the cell's ``stack_s``, eleven classes over.  Held here
    on the lowered module of a decode-only and of a chunk class: (a) the
    Mamba layer's body is ONE private function, called once a Mamba layer
    (``mamba_layer`` is one ``jax.jit`` with the pool layer a value), and
    the recurrence's ops are in it alone; (b) the decode block's recurrence
    is the plain step: a decode-only class holds no ``dot_general`` inside
    ``ssm.scan`` (the chunked form's ``highest`` matmuls load a lane's whole
    state into the MXU for ONE row: 0.44 ms a layer where the state read
    and written once is 0.09), and a class with a chunk holds
    ``ssd_block``'s four, the chunk block's, and no more."""
    text = lowered_step(f, tq).as_text(debug_info=True)
    assert len(re.findall(r"func\.func private @mamba_layer\w*\(",
                          text)) == 1
    assert len(re.findall(r"call @mamba_layer\(", text)) == N_MAMBA
    body = text[text.index("func.func private @mamba_layer("):]
    body = body[:body.index("\n  } loc(")]
    scan = scan_ops(text)
    assert scan and scan == scan_ops(text, body)  # once, whatever the layers
    matmuls = [name for name in scan if name.endswith("dot_general")]
    assert len(matmuls) == (0 if tq == 1 else 4), matmuls


def test_the_engines_step_classes_and_programs_do_not_grow():
    """(c) of the guard above: a step class is 7-9 s of compile in a cold
    start and a trace, a lowering and an executable to load in a warm one
    (PERF.md section 7 (nn)), so the choice between the recurrence's two
    forms may not be a class of its own: at the cell's geometry (16 lanes, a
    64-token chunk) the engine lists eleven classes as it did, ``warmup()``
    compiles just those, and traffic (chunks of every bucket beside decode
    rows, decode-only steps) adds no program to them."""
    eng, params = small_engine(max_seqs=16, prefill_chunk=64,
                               state_slots=40, pool_pages=129)
    classes = eng._ragged_classes()
    assert len(classes) == 11
    assert [c for c in classes if c[1] == 1] == [(8, 1), (16, 1)]
    eng.warmup(timeout=600)
    warmed = set(eng.programs)
    assert len(warmed) == 11
    prompts = [tokens(90 + i, n) for i, n in enumerate((9, 40, 70, 100, 23))]
    handles = [eng.submit(ids, max_new_tokens=6) for ids in prompts]
    outs = [h.result() for h in handles]
    for ids, out in zip(prompts, outs):
        assert_reference(params, F32, ids, out)
    assert eng.stats_snapshot()["ssm_rows"] > 0
    assert set(eng.programs) == warmed
    settled(eng)


# ------------------------------------- (d) the scheduler's state kind
def small_engine(cfg=F32, seed=21, **kw):
    params = make_params(cfg, seed)
    kw.setdefault("max_seq_tokens", 256)
    kw.setdefault("pool_pages", 65)
    kw.setdefault("state_slots", 22)  # the null slot, 5 lanes', 16 snapshots
    eng = gs.engine(model=(params, cfg), tokenizer=None, **kw)
    return eng, params


def settled(eng):
    """Wait until nothing runs, then hold every kind to the allocator's
    invariants: no page (or slot) both free and cached, free + cached = the
    pool, no holder left."""
    gs.settle(eng, timeout=60)
    assert not eng._queue
    for kind in eng._kinds:
        assert len(set(kind.free)) == len(kind.free), kind.name
        assert not set(kind.free) & set(kind.hash), kind.name
        assert len(kind.free) + len(kind.hash) == kind.usable, kind.name
        assert not any(kind.refs.values()), kind.name
        assert set(kind.cache.values()) == set(kind.hash), kind.name


def assert_reference(params, cfg, ids, out, tol=F32_TOL):
    gap = harness.greedy_gap(ref.forward, params, cfg, ids, out)
    assert gap < tol, gap


def test_the_engine_sizes_the_state_kind_from_the_config():
    eng, _ = small_engine(max_seqs=4)
    full, state = eng._kinds
    assert (full.name, full.horizon, full.state) == ("full", None, False)
    assert (state.name, state.horizon, state.state) == ("state", STATE, True)
    assert (state.width, state.usable) == (1, 22 - 1)  # less the null slot
    assert eng._w == (16, 1)
    eng, _ = small_engine(max_seqs=4, state_slots=9)
    assert eng._kinds[1].usable == 8
    # no default: a pool that cannot seat the lanes is refused, unset too
    for too_few in (5, 0):
        with pytest.raises(ValueError, match="state_slots"):
            small_engine(max_seqs=4, state_slots=too_few)
    eng._ensure_pool()
    hbm = eng._hbm_bytes(eng)
    slot = N_MAMBA * (8 * 8 * 16 * 4 + 3 * (64 + 2 * 2 * 16) * 4)
    assert hbm["state_slots"] == 9 * slot
    assert set(hbm) == {"kv_prefix", "kv_pages", "state_slots"}
    snap = eng.stats_snapshot()
    assert snap["page_kinds"]["state"]["usable_pages"] == 8


@pytest.mark.parametrize("cfg,tol", [(F32, F32_TOL), (BF16, GAP_TOL)])
def test_a_prefix_hit_ends_where_a_snapshot_stands(cfg, tol):
    """Two prompts behind one 100-token head, chunks of 32: the first
    leaves snapshots at 32, 64, 96 and 128 tokens; the second's K/V pages
    match through page 5 (96 tokens; 100 is inside page 6) and a snapshot
    stands at 96, so it prefills from there, from the snapshot, and streams
    what it streams alone."""
    eng, params = small_engine(cfg)
    head = tokens(30, 100)
    first, second = head + tokens(31, 30), head + tokens(32, 41)
    out = eng.generate(first, max_new_tokens=8)
    assert_reference(params, cfg, first, out, tol)
    gs.settle(eng)
    stats = eng.stats
    assert stats.state_snapshots_taken == 4 and stats.state_snapshot_hits == 0
    handle = eng.submit(second, max_new_tokens=8)
    out = handle.result()
    assert handle.prefix_reused_tokens == 96
    assert_reference(params, cfg, second, out, tol)
    assert stats.state_snapshot_hits == 1
    # its own chunks: 96 -> 128 (a boundary that has a snapshot already:
    # another prompt's, other tokens, another key) and the last, at 141
    assert stats.state_snapshots_taken == 5
    if cfg is F32:
        lone, _ = small_engine(cfg)
        assert lone.generate(second, max_new_tokens=8) == out
    settled(eng)


def test_a_hit_is_refused_where_pages_stand_but_no_snapshot_does():
    eng, params = small_engine()
    head = tokens(33, 100)
    eng.generate(head + tokens(34, 20), max_new_tokens=4)
    gs.settle(eng)
    state = eng._kinds[1]
    # every snapshot reclaimed (as slot pressure leaves it), pages standing
    while state.cache:
        _, slot = state.cache.popitem()
        state.hash.pop(slot)
        state.free.append(slot)
    assert len(eng._kinds[0].cache) >= 6
    second = head + tokens(35, 25)
    handle = eng.submit(second, max_new_tokens=6)
    out = handle.result()
    assert handle.prefix_reused_tokens == 0
    assert eng.stats.state_snapshot_hits == 0
    assert_reference(params, F32, second, out)
    # and a snapshot at 64 with pages through 96: the hit ends at 64
    third = head + tokens(36, 25)
    keys = eng._prefix_page_keys(third)
    at_96 = state.cache.pop(keys[5])
    state.hash.pop(at_96)
    state.free.append(at_96)
    assert eng._prefix_hits(keys, (len(third) - 1) // PAGE) == 4
    handle = eng.submit(third, max_new_tokens=6)
    out = handle.result()
    assert handle.prefix_reused_tokens == 64
    assert_reference(params, F32, third, out)
    settled(eng)


def test_an_evicted_sequence_is_re_prefilled_to_the_same_tokens():
    """A K/V pool too small for four long lanes: the youngest is evicted
    (its slot goes back), requeued and re-prefilled from its prompt and the
    tokens it had produced, from zeros or from a snapshot that still
    stands; every stream is the one its prompt gets alone."""
    eng, params = small_engine(pool_pages=33, max_seq_tokens=192)
    prompts = [tokens(70 + i, 100 + 5 * i) for i in range(4)]
    handles = [eng.submit(p, max_new_tokens=40) for p in prompts]
    outs = [h.result() for h in handles]
    snap = eng.stats_snapshot()
    assert snap["evictions"] > 0 and snap["readmissions"] > 0
    for ids, out in zip(prompts, outs):
        assert len(out) == 40
        assert_reference(params, F32, ids, out)
    lone, _ = small_engine()
    assert [lone.generate(p, max_new_tokens=40) for p in prompts] == outs
    settled(eng)


def test_a_snapshot_dropped_under_slot_pressure_is_never_handed_out():
    """Seven slots beside the null one for four lanes: two at most are
    ever snapshots, and eight prompts behind one head leave dozens of chunk
    ends.  Snapshots are reclaimed, oldest idle first, for lanes and for
    newer ones; a reclaimed slot's key is gone with it, so no admission
    begins from bytes that another sequence has since written: every
    stream is the reference's."""
    eng, params = small_engine(state_slots=8)
    head = tokens(40, 70)
    prompts = [head + tokens(41 + i, 30 + 7 * i) for i in range(8)]
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    outs = [h.result() for h in handles]
    for ids, out in zip(prompts, outs):
        assert_reference(params, F32, ids, out)
    stats = eng.stats
    assert stats.state_snapshots_dropped > 0
    assert stats.state_snapshots_taken > stats.state_snapshots_dropped
    assert stats.state_slots_copied >= len(prompts)
    settled(eng)
    state = eng._kinds[1]
    assert len(state.cache) == stats.state_snapshots_taken \
        - stats.state_snapshots_dropped
    lone, _ = small_engine()
    assert [lone.generate(p, max_new_tokens=12) for p in prompts[:3]] \
        == outs[:3]


def test_overrun_rows_write_only_their_own_slot():
    """One step in flight: a stream that ends at ``max_new`` or </s> has a
    row in the step behind its last; that row advances the ended
    sequence's OWN slot, which stays its own until the step is read.  Four
    short streams end beside four long ones: the long ones read the
    reference."""
    eng, params = small_engine()
    prompts = [tokens(50 + i, 40 + i) for i in range(8)]
    handles = [eng.submit(p, max_new_tokens=3 if i % 2 else 24)
               for i, p in enumerate(prompts)]
    outs = [h.result() for h in handles]
    for ids, out in zip(prompts, outs):
        assert_reference(params, F32, ids, out)
    settled(eng)


def test_a_failed_step_resets_the_state_kind_with_the_others():
    eng, _ = small_engine(max_seqs=2)
    eng.start = lambda: None
    h = eng.submit(tokens(90, 100), max_new_tokens=4)
    for _ in range(4):
        eng._step()
    assert all(k.refs for k in eng._kinds) and eng._kinds[1].cache
    plain = nh.fused_step
    try:
        nh.fused_step = lambda *a, **kw: (_ for _ in ()).throw(
            RuntimeError("boom"))
        with pytest.raises(RuntimeError):
            eng._step()
    finally:
        nh.fused_step = plain
    assert eng._pages is None
    for kind in eng._kinds:
        assert not kind.cache and not kind.hash and not kind.refs
        assert len(kind.free) == kind.usable
    assert h is not None


def test_snapshots_in_the_requests_trace_and_on_metrics():
    from nornicdb_tpu.genserve import stats as gstats
    from nornicdb_tpu.telemetry.metrics import REGISTRY
    from nornicdb_tpu.telemetry.tracing import tracer

    eng, _ = small_engine()
    REGISTRY.render_prometheus()  # a scrape: what other engines moved is in
    before = {e: gstats.STATE_SNAPSHOTS.labels(e).value
              for e in ("taken", "hit", "dropped")}
    head = tokens(60, 70)
    with tracer.start_trace("test.request") as root:
        eng.generate(head + tokens(61, 10), max_new_tokens=2)
        eng.generate(head + tokens(62, 12), max_new_tokens=2)
        gs.settle(eng)
    spans = [s for s in tracer.trace(root.trace_id)["spans"]
             if s["name"] == "genserve.state_snapshot"]
    assert {s["attrs"]["tokens"] for s in spans} >= {32, 64}
    assert all(s["attrs"]["slot"] > 0 and s["attrs"]["evicted"] is False
               for s in spans)
    text = REGISTRY.render_prometheus()
    assert 'nornicdb_genserve_state_snapshots_total{event="taken"}' in text
    assert gstats.STATE_SNAPSHOTS.labels("taken").value - before["taken"] \
        == eng.stats.state_snapshots_taken >= 3
    assert gstats.STATE_SNAPSHOTS.labels("hit").value - before["hit"] == 1


# ------------------------------------------------ (e) Heimdall, over SSE
def test_heimdall_streams_the_references_greedy_continuation_over_sse():
    """``db.set_heimdall_generator`` -> ``_wire_genserve`` ->
    GenerationEngine (the family resolved from the config's type, its state
    kind from the config) -> ``POST /v1/chat/completions`` as server-sent
    events, twice: the second request's hit ends on a snapshot of the
    first's; both streams read no gap against the reference's logits over
    the prompt that Heimdall assembled."""
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
    genserve.configure(GenServeConfig(
        max_seqs=2, max_seq_tokens=1536, pool_pages=200, page_size=PAGE,
        prefill_chunk=64, deadline_ms=0, state_slots=12))
    db = nornicdb_tpu.open_db("")
    http_server = None
    try:
        db.set_heimdall_generator(generator)
        engine = db.genserve_engine()
        assert isinstance(db.heimdall.generator, EngineGenerator)
        assert engine._family is nh
        assert [(k.name, k.horizon) for k in engine._kinds] == \
            [("full", None), ("state", STATE)]
        seen = []
        submit = engine.submit
        engine.submit = lambda ids, *a, **kw: (
            seen.append(list(ids)), submit(ids, *a, **kw))[1]
        http_server = HttpServer(db, port=0)
        http_server.start()
        outs = []
        for question in ("how many nodes?", "which labels are there?"):
            conn = http.client.HTTPConnection("127.0.0.1", http_server.port,
                                              timeout=300)
            conn.request("POST", "/v1/chat/completions", json.dumps({
                "messages": [{"role": "user", "content": question}],
                "max_tokens": 6, "stream": True}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            out = []
            for line in resp.read().decode().splitlines():
                if line.startswith("data: ") and line != "data: [DONE]":
                    for choice in json.loads(line[6:]).get("choices", []):
                        text = (choice.get("delta") or {}).get("content") or ""
                        out += [int(i) for i in re.findall(r"<(\d+)>", text)]
            conn.close()
            outs.append(out)
        stats = engine.stats_snapshot()
    finally:
        if http_server is not None:
            http_server.stop()
        genserve.configure(None)
        if db.genserve_engine() is not None:
            db.genserve_engine().stop()
        db.close()
    assert len(seen) == 2 and all(len(out) == 6 for out in outs)
    for ids, out in zip(seen, outs):
        assert harness.greedy_gap(ref.forward, params, cfg, ids,
                                  out) < F32_TOL
    assert stats["state_snapshot_hits"] == 1
    assert stats["prefix_reused_tokens"] >= 64
    assert stats["ssm_rows"] > 0 and stats["expert_assignments"] > 0
