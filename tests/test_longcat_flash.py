"""LongCat-Flash's language model behind genserve: the program
(``models/longcat_flash.py``: a double layer of two MLA blocks over a latent
page pool with two rows a token a layer, a shortcut-connected expert branch
whose router also scores zero-compute experts, of whose routed experts a
share is held, the fused ragged step) against the plain float32 reference
(``models/reference/longcat_flash.py``), at small sizes on the CPU.

Every comparison is on logits (or on the greedy GAP read off the
reference's logits), never on sampled tokens.  Tolerances, and why:

* ``F32_TOL`` 2e-4: the program in float32 computes the same mathematics
  as the reference in another order (batched, absorbed, masked experts, the
  zero experts' gates summed); readings are 5e-6 to 2e-5 on logits of
  spread 1.
* ``BF16_TOL`` 0.2, on the MEDIAN over positions of a position's largest
  logit error (:func:`typical`): the program in bfloat16 against the
  float32 reference.  Rounding alone reads 0.04-0.11 at every position.
  A routed model is
  discontinuous besides: a token whose 4th and 5th choices lie within
  rounding takes ANOTHER expert than the reference does, and that
  position is off by more than the fp8 control's typical one.  So the
  largest error cannot tell bf16 from fp8 and the median can: the fp8
  control (the reference with both operands of every weight matmul
  rounded to e4m3) reads 0.51-0.75, over twice the tolerance, as a
  forward in a precision below the stated one has to.  Every position is
  held exactly by the float32 cases.
* ``WIRING_TOL`` 0.05: a float32 layer wired wrongly (the branch joined a
  sub-layer early, fed from the second norm, a block's row in the other
  block's pool layer, a LoRA scale left out, the bias in the gates) is
  off by 0.2 and more on logits of spread 1; the sound float32 program
  reads under ``F32_TOL``.
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
from decoder_harness import (
    LMAX,
    PAGE,
    WIDTH,
    Pool,
    fp8,
    table_of,
    tokens as draw,
    typical,
    with_norm_scales,
)
from nornicdb_tpu.models import longcat_flash as lcf
from nornicdb_tpu.models import mla
from nornicdb_tpu.models.reference import longcat_flash as ref

BF16 = lcf.LONGCAT_FLASH_SMALL
F32 = dataclasses.replace(BF16, dtype="float32")
F32_TOL = 2e-4
BF16_TOL = 0.2
WIRING_TOL = 0.05


def make_params(cfg, seed: int):
    """Seeded weights; the router's rows at twice the usual spread (a row's
    scores over the 16 outputs spread by 2, so its four gates sum to the
    order of 1 and the branch is a tenth of the stream: what is wrong in it
    shows), a bias of the scores' own scale (0.05 against 1/16: it changes
    the chosen four in a share of the rows and never a gate) and
    non-trivial norm scales, so that a norm left out shows."""
    params = with_norm_scales(
        lcf.init_params(cfg, jax.random.PRNGKey(seed)), seed + 1000)
    for i, layer in enumerate(params["blocks"]):
        layer["router"] = (layer["router"].astype(jnp.float32)
                           * 2.0).astype(layer["router"].dtype)
        layer["router_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(seed * 100 + i), (cfg.router_outputs,))
    return params


def hold_experts(params, cfg, first: int, count: int):
    """One expert-parallel rank's share of a model whose tree holds every
    routed expert: experts ``first .. first + count - 1`` of each layer and
    everything else (the zero experts too: they have no weights) as it
    was."""
    lo = first - cfg.held_experts[0]
    blocks = [{**layer, "experts": {k: w[lo:lo + count]
                                    for k, w in layer["experts"].items()}}
              for layer in params["blocks"]]
    return ({**params, "blocks": blocks},
            dataclasses.replace(cfg, held_experts=(first, count)))


def tokens(seed: int, n: int, vocab: int = BF16.vocab_size) -> list[int]:
    return draw(seed, n, vocab)


def unjitted(name: str):
    """A family like ``lcf`` whose ``forward`` / ``fused_step`` are traced
    anew (JAX caches a trace by the function traced and its shapes: a fault
    planted afterwards would not reach the module's own)."""
    def fresh(params, cfg, meta, pages, **kw):  # its own function, so trace
        return lcf.fused_step.__wrapped__(params, cfg, meta, pages, **kw)

    step = jax.jit(fresh, static_argnames=("cfg", "lmax", "w", "tq"),
                   donate_argnums=(3,))
    return types.SimpleNamespace(
        __name__=name, init_pages=lcf.init_pages, fused_step=step,
        forward=lcf.forward.__wrapped__, STEP_COUNTERS=lcf.STEP_COUNTERS)


# ------------------------------------------------ (a) forward = reference
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_forward_is_the_reference_in_float32(seed):
    params = make_params(F32, seed)
    ids = tokens(seed, 40)
    want = np.asarray(ref.forward(params, F32, ids))
    got = np.asarray(lcf.forward(params, F32, jnp.asarray([ids, ids[::-1]])))
    assert np.abs(got[0] - want).max() < F32_TOL
    back = np.asarray(ref.forward(params, F32, ids[::-1]))
    assert np.abs(got[1] - back).max() < F32_TOL


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bf16_forward_is_within_tolerance_and_fp8_is_not(seed):
    params = make_params(BF16, seed)
    ids = tokens(seed, 40)
    want = np.asarray(ref.forward(params, BF16, ids))
    got = np.asarray(lcf.forward(params, BF16, jnp.asarray([ids])))[0]
    low = np.asarray(ref.forward(params, BF16, ids, rounded=fp8))
    assert typical(got, want) < BF16_TOL
    assert typical(low, want) > 2 * BF16_TOL


# ------------------------------------------------------ (b) the share test
@pytest.mark.parametrize("seed", [1, 2])
def test_the_shares_add_up_to_the_uncut_double_layer(seed):
    """Three expert-parallel ranks of four routed experts each: the held
    experts' parts they compute, with what every rank computes alike (the
    residual, both attention blocks, both dense feed-forwards, the zero
    experts' part) counted once, are the uncut reference's layer."""
    params = make_params(F32, seed)
    layer = params["blocks"][1]
    t = 24
    hid = jax.random.normal(jax.random.PRNGKey(seed), (t, F32.hidden_size))
    cos, sin = ref.rotary(F32, t)
    whole = np.asarray(ref.double_layer(F32, layer, hid, cos, sin, (0, 12)))
    mask = jnp.where(jnp.tril(jnp.ones((t, t), bool)), 0.0, -1e30)[None, None]

    def attend(blk, _at, x, _pool):
        return mla.attend_sequences(
            F32, blk, x, 1, cos, sin, mask,
            lcf._project, F32.score_scale), None

    # the branch's input, as the layer makes it
    a0, _ = attend(layer["attn"][0], 0, hid, None)
    x0 = lcf.rms_norm(layer["mlp_norm"][0], a0, F32.rms_norm_eps)
    ids, gates = lcf.route(F32, layer["router"], layer["router_bias"], x0)
    zero = np.asarray(jnp.where(ids >= F32.n_routed_experts, gates, 0.0)
                      .sum(-1)[:, None] * x0)
    alike0, own, counts = None, 0.0, np.zeros(4, np.int64)
    for first in (0, 4, 8):
        share, cfg = hold_experts(params, F32, first, 4)
        assert share["blocks"][1]["experts"]["gate"].shape[0] == 4
        out, _, _ = lcf._layer(cfg, share["blocks"][1], hid, attend)
        m, c = lcf.expert_branch(cfg, share["blocks"][1], x0)
        counts += np.asarray(c)
        # the reference, given the same share, leaves out the same
        alone = ref.expert_branch(F32, share["blocks"][1], x0, (first, 4))
        assert np.abs(np.asarray(m) - np.asarray(alone)).max() < F32_TOL
        alike = np.asarray(out) - np.asarray(m) + zero  # what all compute
        alike0 = alike if alike0 is None else alike0
        assert np.abs(alike - alike0).max() < F32_TOL
        own = own + (np.asarray(m) - zero)              # this rank's own
    assert np.abs(alike0 + own - whole).max() < F32_TOL
    # every row's top-4 fell on exactly one share or on a zero expert
    assert counts[0] + counts[3] // 3 == t * F32.moe_topk
    assert counts[3] // 3 > 0 and counts[0] > 0


def test_only_the_held_experts_are_made():
    """The benchmark's cut holds 8 of the 512 routed experts a layer, the
    router keeps its published 768 outputs, every layer is a double one."""
    cfg = lcf.LONGCAT_FLASH_EP64_4L
    assert cfg.held_experts == (0, 8) and cfg.num_layers == 4
    assert cfg.router_outputs == 768 and cfg.moe_topk == 12
    shapes = jax.eval_shape(lambda: lcf.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    for layer in shapes["blocks"]:
        assert layer["experts"]["gate"].shape == (8, 6144, 2048)
        assert layer["router"].shape == (6144, 768)
        assert layer["router_bias"].shape == (768,)
        assert len(layer["attn"]) == len(layer["mlp"]) == 2
        assert layer["mlp"][1]["down"].shape == (12288, 6144)
        assert layer["attn"][1]["q_b"]["w"].shape == (1536, 64 * 192)
    assert shapes["lm_head"]["w"].shape == (6144, 16384)
    leaves = jax.tree_util.tree_leaves(shapes)
    matrices = sum(int(np.prod(x.shape)) for x in leaves if x.ndim > 1)
    # ISSUE 34's arithmetic, 3,964,786,688 parameters for 4 layers + 1/8
    # vocabulary at this share: its matrices, and the norm scales (28,672 a
    # layer and the final norm's 6,144) beside the router's bias (768 a
    # layer, which that count leaves out)
    vectors = sum(int(np.prod(x.shape)) for x in leaves if x.ndim == 1)
    assert matrices == 3_964_786_688 - 4 * 28_672 - 6_144
    assert vectors == 4 * (28_672 + 768) + 6_144
    pool = jax.eval_shape(lambda: lcf.init_pages(cfg, 8193, 16))
    assert pool.shape == (8, 8193, 16, 640) and pool.dtype == jnp.bfloat16


# ---------------------------------------------------------- (c) the router
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_router_against_a_numpy_loop(seed):
    """The bias moves the choice and never a gate; gates are scaling x
    softmax, not renormalised; and the bias does change some row's chosen
    four (else the case shows nothing)."""
    cfg = F32
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((64, cfg.hidden_size)).astype(np.float32)
    router = rng.standard_normal(
        (cfg.hidden_size, cfg.router_outputs)).astype(np.float32) * 0.15
    bias = (rng.standard_normal(cfg.router_outputs) * 0.05).astype(np.float32)
    ids, gates = (np.asarray(a) for a in lcf.route(
        cfg, jnp.asarray(router), jnp.asarray(bias), jnp.asarray(x)))
    moved = 0
    for t in range(x.shape[0]):
        logits = x[t].astype(np.float64) @ router.astype(np.float64)
        p = np.exp(logits - logits.max())
        p /= p.sum()
        best = np.argsort(-(p + bias), kind="stable")[:cfg.moe_topk]
        assert sorted(ids[t].tolist()) == sorted(best.tolist())
        moved += sorted(best) != sorted(np.argsort(-p)[:cfg.moe_topk])
        for e, g in zip(ids[t], gates[t]):
            assert abs(g - cfg.routed_scaling_factor * p[e]) < 1e-5
    assert moved > 0
    # and the reference's router is the same rule, written again
    rids, rgates = ref.route(cfg, jnp.asarray(router), jnp.asarray(bias),
                             jnp.asarray(x))
    assert (np.sort(np.asarray(rids), -1) == np.sort(ids, -1)).all()
    assert np.abs(np.sort(np.asarray(rgates), -1)
                  - np.sort(gates, -1)).max() < 1e-5


def test_a_zero_expert_adds_its_gate_times_the_input():
    """A router that puts all of a row's top-4 on zero experts gives ``m =
    (sum of the four gates) x0`` and counts four zero choices a row; one
    that puts them on held experts counts none."""
    params = make_params(F32, 4)
    layer = dict(params["blocks"][0])
    x = jax.random.normal(jax.random.PRNGKey(4), (8, F32.hidden_size))
    bias = jnp.zeros((F32.router_outputs,)).at[F32.n_routed_experts:].set(9.)
    layer["router_bias"] = bias
    m, counts = lcf.expert_branch(F32, layer, x)
    ids, gates = lcf.route(F32, layer["router"], bias, x)
    assert (np.asarray(ids) >= F32.n_routed_experts).all()
    assert np.abs(np.asarray(m) - np.asarray(
        gates.sum(-1)[:, None] * x)).max() < 1e-5
    assert np.asarray(counts).tolist() == [0, 0, 0, 8 * F32.moe_topk]
    layer["router_bias"] = -bias
    _, counts = lcf.expert_branch(F32, layer, x)
    assert np.asarray(counts)[[0, 3]].tolist() == [8 * F32.moe_topk, 0]


# --------------------------------------------- (d) the shortcut's wiring
def _joined_early(cfg, layer, h, attend, pool=None, at=0, valid=None):
    """PLANTED: ``m`` joins the stream after FFN[l,0]."""
    eps = cfg.rms_norm_eps
    a0, pool = attend(layer["attn"][0], at, h, pool)
    x0 = lcf.rms_norm(layer["mlp_norm"][0], a0, eps)
    m, counts = lcf.expert_branch(cfg, layer, x0, valid)
    b0 = a0 + mla.swiglu(layer["mlp"][0], x0) + m.astype(h.dtype)
    a1, pool = attend(layer["attn"][1], at + 1, b0, pool)
    b1 = a1 + mla.swiglu(layer["mlp"][1],
                         lcf.rms_norm(layer["mlp_norm"][1], a1, eps))
    return b1, counts, pool


def _fed_late(cfg, layer, h, attend, pool=None, at=0, valid=None):
    """PLANTED: ``m`` is computed from norm_post[l,1](a1)."""
    eps = cfg.rms_norm_eps
    a0, pool = attend(layer["attn"][0], at, h, pool)
    b0 = a0 + mla.swiglu(layer["mlp"][0],
                         lcf.rms_norm(layer["mlp_norm"][0], a0, eps))
    a1, pool = attend(layer["attn"][1], at + 1, b0, pool)
    x1 = lcf.rms_norm(layer["mlp_norm"][1], a1, eps)
    m, counts = lcf.expert_branch(cfg, layer, x1, valid)
    return a1 + mla.swiglu(layer["mlp"][1], x1) + m.astype(h.dtype), \
        counts, pool


@pytest.mark.parametrize("planted", [_joined_early, _fed_late],
                         ids=["joined-early", "fed-late"])
def test_the_branch_leaves_after_the_first_attention_and_rejoins_last(
        planted, monkeypatch):
    params = make_params(F32, 6)
    ids = tokens(6, 40)
    want = np.asarray(ref.forward(params, F32, ids))
    family = unjitted("sound")
    got = np.asarray(family.forward(params, F32, jnp.asarray([ids])))[0]
    assert np.abs(got - want).max() < F32_TOL
    monkeypatch.setattr(lcf, "_layer", planted)
    got = np.asarray(family.forward(params, F32, jnp.asarray([ids])))[0]
    assert typical(got, want) > WIRING_TOL


# ----------------------- (e) the LoRA scales, plain rope at theta 1e7
def test_lora_scales_and_rope_at_the_published_numbers():
    cfg = lcf.LongCatFlashConfig()  # the published language model
    assert cfg.q_scale == 2.0 and abs(cfg.kv_scale - 3.4641) < 1e-4
    assert np.isclose(cfg.kv_scale, (6144 / 512) ** 0.5, rtol=1e-12)
    assert np.isclose(cfg.score_scale, 192 ** -0.5, rtol=1e-12)
    assert np.allclose(lcf.inv_freq(cfg), 1e7 ** (-np.arange(32) / 32.0),
                       rtol=1e-12)
    cos, sin = ref.rotary(cfg, 9)
    assert np.allclose(np.asarray(cos)[8], np.cos(8 * lcf.inv_freq(cfg)),
                       atol=1e-6)
    assert mla.latent_width(cfg) == 576 and cfg.page_row_width == 640
    assert cfg.router_outputs == 768 and cfg.attention_blocks == 56
    off = dataclasses.replace(cfg, mla_scale_q_lora=False,
                              mla_scale_kv_lora=False)
    assert off.q_scale == off.kv_scale == 1.0


def test_the_cached_row_carries_the_kv_scale_and_an_unscaled_rotary_key():
    """``c_kv`` is cached after its norm AND its scale; ``k_r`` after rope,
    unscaled; ``q`` carries ``s_q`` on both parts."""
    params = make_params(F32, 3)
    blk = params["blocks"][0]["attn"][0]
    h = jax.random.normal(jax.random.PRNGKey(3), (5, F32.hidden_size))
    cos, sin = (a[:5] for a in ref.rotary(F32, 5))
    bare = dataclasses.replace(F32, mla_scale_q_lora=False,
                               mla_scale_kv_lora=False)
    q_nope, q_pe, row = lcf._project(F32, blk, h, cos, sin)
    q_nope1, q_pe1, row1 = lcf._project(bare, blk, h, cos, sin)
    kvl = F32.kv_lora_rank
    assert np.allclose(row[:, :kvl], row1[:, :kvl] * F32.kv_scale, rtol=1e-5)
    assert np.allclose(row[:, kvl:], row1[:, kvl:], rtol=1e-6)   # k_r
    assert np.allclose(q_nope, q_nope1 * F32.q_scale, rtol=1e-5)
    assert np.allclose(q_pe, q_pe1 * F32.q_scale, rtol=1e-5)
    assert abs(F32.kv_scale - 2.0) < 1e-12  # sqrt(128 / 32) at this size
    # a forward without them is outside the wiring tolerance
    ids = tokens(3, 40)
    want = np.asarray(ref.forward(params, F32, ids))
    got = np.asarray(lcf.forward(params, bare, jnp.asarray([ids])))[0]
    assert typical(got, want) > WIRING_TOL


# ----------------------- (f) chunked prefill, decode, prefix pages
def reference_rows(cfg, params, ids, out, **kw):
    return harness.reference_rows(ref.forward, params, cfg, ids, out, **kw)


@pytest.mark.parametrize("cfg,tol,seed", [
    (F32, F32_TOL, 1), (F32, F32_TOL, 2), (BF16, BF16_TOL, 1),
    (BF16, BF16_TOL, 2), (BF16, BF16_TOL, 3)])
def test_latent_pool_serving_is_the_reference_at_every_position(cfg, tol,
                                                                seed):
    """A prompt prefilled in chunks of 16 and decoded through the latent
    pool (two rows a token a layer); a second prompt that shares its first
    three pages and prefills only its own suffix (the prefix-cache hit),
    which reads the same logits as the same prompt served cold into pages
    of its own."""
    params = make_params(cfg, seed)
    prefix = tokens(seed, 3 * PAGE)
    a, b = prefix + tokens(seed + 1, 21), prefix + tokens(seed + 2, 30)
    pool = Pool(lcf, cfg, params)
    error = (lambda got, want: np.abs(got - want).max()) if cfg is F32 \
        else typical
    out_a, got_a = pool.serve(a, table_of(1, 2, 3, 4, 5, 6), steps=12)
    hit = table_of(1, 2, 3, 9, 10, 11)        # a's first three pages
    out_b, got_b = pool.serve(b, hit, start=len(prefix), steps=12)
    out_c, got_c = pool.serve(b, table_of(20, 21, 22, 23, 24, 25), steps=12)
    for ids, out, got in ((a, out_a, got_a), (b, out_b, got_b)):
        assert error(got, reference_rows(cfg, params, ids, out)) < tol
    assert out_c == out_b
    assert np.abs(got_c - got_b).max() < (F32_TOL if cfg is F32 else 0.05)
    if cfg is BF16:  # a forward below the stated precision is over it
        low = reference_rows(cfg, params, a, out_a, rounded=fp8)
        assert typical(low, reference_rows(cfg, params, a, out_a)) > 2 * tol
    # two pool layers a model layer; the null page took the padding rows'
    # writes and nothing else moved; BOTH blocks of a layer left rows
    assert pool.pool.shape == (2 * cfg.num_layers, 40, PAGE,
                               cfg.page_row_width)
    assert not np.asarray(pool.pool[:, 30:]).any()
    written = np.asarray(pool.pool[:, 1]).astype(np.float32)
    assert all(np.abs(written[i]).max() > 0 for i in range(len(written)))
    assert np.abs(written[0] - written[1]).max() > 0.1


def test_a_blocks_row_in_the_other_blocks_pool_layer_fails(monkeypatch):
    """PLANTED: block 1 of every layer writes and reads block 0's pool
    layer.  Prefill alone would not show it (a chunk reads back what it
    just wrote); decoding over the cache does."""
    params = make_params(F32, 8)
    ids = tokens(8, 37)
    table = table_of(1, 2, 3, 4)
    out, got = Pool(unjitted("sound"), F32, params).serve(ids, table, steps=8)
    assert np.abs(got - reference_rows(F32, params, ids, out)).max() < F32_TOL
    plain = mla.attend_step
    monkeypatch.setattr(
        mla, "attend_step", lambda cfg, blk, rows, pages, at, *a:
        plain(cfg, blk, rows, pages, at - at % 2, *a))
    out, got = Pool(unjitted("planted"), F32, params).serve(ids, table,
                                                           steps=8)
    assert typical(got, reference_rows(F32, params, ids, out)) > WIRING_TOL


def test_decode_lanes_beside_a_chunk_read_what_they_read_alone():
    """One fused step carrying two decode lanes and another request's
    chunk gives each the logits it gets in a step of its own."""
    params = make_params(F32, 5)
    a, b, c = tokens(1, 20), tokens(2, 27), tokens(3, 13)
    ta, tb, tc = table_of(1, 2), table_of(3, 4), table_of(5)
    alone, mixed = Pool(lcf, F32, params), Pool(lcf, F32, params)
    for pool in (alone, mixed):
        pool.serve(a, ta, steps=1)
        pool.serve(b, tb, steps=1)
    la = alone.step(decode=[(7, len(a), ta)])[0]
    lb = alone.step(decode=[(9, len(b), tb)])[0]
    lc = alone.step(chunk=(c, 0, tc))[-1]
    got = mixed.step(decode=[(7, len(a), ta), (9, len(b), tb)],
                     chunk=(c, 0, tc))
    for want, row in zip((la, lb, lc), got):
        assert np.abs(want - row).max() < F32_TOL


# --------------------------------------- (h) the step's routing counts
@pytest.mark.parametrize("held", [(0, 12), (4, 8), (8, 4)])
def test_routing_counts_equal_a_numpy_count(held):
    """One step prefills 13 tokens (3 padding rows ride along and must not
    count): the counts in its int vector against the reference's routing
    of the same rows, walked layer by layer."""
    full = make_params(F32, 7)
    params, cfg = hold_experts(full, F32, *held)
    ids = tokens(7, 13)
    pool = Pool(lcf, cfg, params)
    pool.step(chunk=(ids, 0, table_of(1)))
    assert pool.counters == lcf.STEP_COUNTERS and pool.counts.any()
    hid = params["tok_emb"][jnp.asarray(ids)].astype(jnp.float32)
    cos, sin = ref.rotary(cfg, 13)
    want = dict.fromkeys(lcf.STEP_COUNTERS, 0)
    for layer in params["blocks"]:
        a0 = ref.attention_block(cfg, layer["attn"][0], hid, cos, sin)
        picked, _ = ref.route(cfg, layer["router"], layer["router_bias"],
                              ref._rms(layer["mlp_norm"][0], a0,
                                       cfg.rms_norm_eps))
        rows = np.zeros(held[1], np.int64)
        for e in np.asarray(picked).ravel():
            if held[0] <= e < held[0] + held[1]:
                rows[e - held[0]] += 1
            want["zero_assignments"] += int(e >= cfg.n_routed_experts)
        want["expert_assignments"] += int(rows.sum())
        want["expert_rows_max"] += int(rows.max())
        want["experts_hit"] += int((rows > 0).sum())
        want["routed_rows"] += 13
        hid = ref.double_layer(cfg, layer, hid, cos, sin, held)
    # a table of 8 pages is narrower than a block: the decode block's three
    # lanes and the chunk lane each walk all of it, in every attention block
    want["attn_slots_walked"] = want["attn_slots_table"] = \
        LMAX * WIDTH * PAGE * cfg.attention_blocks
    assert dict(zip(lcf.STEP_COUNTERS, pool.counts.tolist())) == want
    assert want["zero_assignments"] > 0
    if held == (0, 12):
        assert want["expert_assignments"] + want["zero_assignments"] \
            == 13 * cfg.num_layers * cfg.moe_topk


def test_the_step_carries_its_scopes_and_its_own_module_name():
    params = jax.eval_shape(lambda: lcf.init_params(BF16,
                                                    jax.random.PRNGKey(0)))
    meta = jax.ShapeDtypeStruct((4 * 16 + LMAX + LMAX * WIDTH,), jnp.int32)
    pages = jax.eval_shape(lambda: lcf.init_pages(BF16, 9, PAGE))
    lowered = lcf.fused_step.lower(params, BF16, meta, pages, lmax=LMAX,
                                   w=WIDTH, tq=16)
    text = lowered.as_text(debug_info=True)
    for scope in ("mla.project", "mla.absorb", "mla.attend", "ffn.dense",
                  "moe.route", "moe.experts", "moe.zero"):
        assert scope in text, scope
    module = re.search(r"module @(\S+)", lowered.as_text()).group(1)
    assert module == "jit_scmoe_mla_fused_step"
    assert lcf.num_pages(pages) == 9 and pages.shape[0] == 2 * BF16.num_layers
    # the family's own count stands behind the four every routed family
    # has, and before the two every latent family has (PR 36): the fifth
    # of seven
    assert lcf.STEP_COUNTERS.index("zero_assignments") == 4
    assert lcf.STEP_COUNTERS[5:] == mla.WALK_COUNTERS
    assert len(lcf.STEP_COUNTERS) == 7


# -------------------------------------- (j), (g): the engine, Heimdall
def engine_config(**kw):
    from nornicdb_tpu.config import GenServeConfig

    return GenServeConfig(**{**dict(max_seqs=2, max_seq_tokens=128,
                                    pool_pages=33, page_size=PAGE,
                                    prefill_chunk=16, deadline_ms=0), **kw})


def test_the_family_plugs_into_the_seam_with_no_new_option():
    import importlib

    from nornicdb_tpu.config import GenServeConfig
    from nornicdb_tpu.genserve import GenerationEngine, GenStats

    # (the eleventh is PR 41's ``state_slots``, which no family without a
    # state kind reads)
    assert len(GenServeConfig.__dataclass_fields__) == 11
    for name in ("init_pages", "num_pages", "fused_step"):
        assert callable(getattr(lcf, name)), name
    for gone in ("prefill", "decode", "decode_step", "generate"):
        assert not hasattr(lcf, gone), gone
    plain = importlib.import_module(
        lcf.__name__.replace(".models.", ".models.reference."))
    assert callable(plain.forward)
    params = jax.eval_shape(lambda: lcf.init_params(BF16,
                                                    jax.random.PRNGKey(0)))
    engine = GenerationEngine(params, BF16, config=engine_config())
    assert engine._family is lcf
    assert engine._step_counters == lcf.STEP_COUNTERS
    assert set(lcf.STEP_COUNTERS) <= set(GenStats.__dataclass_fields__)
    assert engine.stats_snapshot()["mode"] == "paged"


def test_the_engine_serves_the_family_through_its_latent_pool():
    """Two prompts with a shared prefix through the GenerationEngine: what
    it generates reads a greedy gap of (float32) nothing against the
    reference, the second takes its prefix from the cache, the routing
    counters and ``zero_assignments`` move and the pool is the family's
    (two pool layers a layer: nothing in genserve counts layers)."""
    from nornicdb_tpu.genserve import GenerationEngine

    full = make_params(F32, 11)
    params, cfg = hold_experts(full, F32, 4, 8)
    engine = GenerationEngine(params, cfg, config=engine_config())
    prefix = tokens(11, 40)
    seqs = []
    try:
        for n in (9, 23):
            prompt = prefix + tokens(n, n)
            seqs.append((prompt, engine.generate(prompt, max_new_tokens=8)))
        stats = engine.stats_snapshot()
        hbm = GenerationEngine._hbm_bytes(engine)
    finally:
        engine.stop()
    assert stats["prefix_reused_tokens"] == 32
    for prompt, out in seqs:
        assert harness.greedy_gap(ref.forward, params, cfg, prompt,
                                  out) < F32_TOL
    steps_rows = stats["prefill_tokens_first"] + stats["decode_lane_tokens"]
    assert stats["routed_rows"] == steps_rows * cfg.num_layers
    assert 0 < stats["expert_assignments"] < stats["routed_rows"] * 4
    assert 0 < stats["zero_assignments"] < stats["routed_rows"] * 4
    assert stats["attn_slots_walked"] == stats["attn_slots_table"] > 0
    assert stats["expert_assignments"] + stats["zero_assignments"] \
        <= stats["routed_rows"] * cfg.moe_topk
    assert stats["experts_hit"] <= stats["expert_assignments"]
    row = cfg.page_row_width * 4 * 2 * cfg.num_layers * PAGE
    assert hbm["kv_pages"] == 33 * row
    assert hbm["kv_prefix"] == stats["prefix_pages"] * row


def test_heimdall_streams_the_references_greedy_continuation_over_sse():
    """``db.set_heimdall_generator`` -> ``_wire_genserve`` ->
    GenerationEngine -> ``POST /v1/chat/completions`` as server-sent
    events: the streamed ids read no gap against the reference's logits
    over the prompt that Heimdall assembled, and the metric family of the
    zero experts moved."""
    import nornicdb_tpu
    from nornicdb_tpu import genserve
    from nornicdb_tpu.genserve import stats as gen_stats
    from nornicdb_tpu.heimdall import EngineGenerator, WeightsGenerator
    from nornicdb_tpu.models.tokenizer import HashTokenizer
    from nornicdb_tpu.server import HttpServer

    cfg = dataclasses.replace(F32, vocab_size=2048)
    params = make_params(cfg, 13)
    generator = WeightsGenerator(cfg, params, HashTokenizer(cfg.vocab_size),
                                 max_context=1024)
    genserve.configure(engine_config(max_seq_tokens=1536, pool_pages=200,
                                     prefill_chunk=64))
    db = nornicdb_tpu.open_db("")
    http_server = None
    before = gen_stats.ZERO_EXPERT_ASSIGNMENTS.labels().get()
    try:
        db.set_heimdall_generator(generator)
        engine = db.genserve_engine()
        assert isinstance(db.heimdall.generator, EngineGenerator)
        assert engine._family is lcf
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
        zero = engine.stats_snapshot()["zero_assignments"]
    finally:
        if http_server is not None:
            http_server.stop()
        genserve.configure(None)
        if db.genserve_engine() is not None:
            db.genserve_engine().stop()
        db.close()
    assert len(seen) == 1 and len(out) == 6
    assert harness.greedy_gap(ref.forward, params, cfg, seen[0],
                              out) < F32_TOL
    assert zero > 0
    assert gen_stats.ZERO_EXPERT_ASSIGNMENTS.labels().get() - before >= zero
