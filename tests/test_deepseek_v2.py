"""DeepSeek-V2 behind genserve: the program (``models/deepseek_v2.py``: MLA
over a latent page pool, group-limited routed experts of which a share is
held, the fused ragged step) against the plain float32 reference
(``models/reference/deepseek_v2.py``), at small sizes on the CPU.

Every comparison is on logits (or on the greedy GAP read off the
reference's logits), never on sampled tokens: with random weights the
largest logit changes on rounding.  Tolerances, and why:

* ``F32_TOL`` 2e-4: the program in float32 computes the same mathematics
  as the reference in another order (batched, absorbed, masked experts);
  readings are 2e-6 to 3e-5 on logits of spread 1.
* ``BF16_TOL`` 0.25, on the MEDIAN over positions of a position's largest
  logit error (:func:`typical`): the program in bfloat16 against the
  float32 reference.  Rounding alone reads 0.05-0.16 at every position.
  A routed model is discontinuous besides: a token whose 4th and 5th
  expert scores lie within rounding takes ANOTHER expert than the
  reference does, and that position (a minority: one in ten here) is off
  by 0.4-1.9 at this size, more than the fp8 control's typical position.
  So the largest error cannot tell bf16 from fp8 and the median can: the
  fp8 control (the reference with both operands of every weight matmul
  rounded to e4m3) reads 0.78-1.2, over twice the tolerance, as a forward
  in a precision below the stated one has to.  Every position is held
  exactly by the float32 cases.
"""

import dataclasses
import hashlib
import http.client
import json
import re

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
from nornicdb_tpu.ragged import ROUTING_COUNTERS, pages_for
from nornicdb_tpu.models import deepseek_v2 as ds
from nornicdb_tpu.models import mla
from nornicdb_tpu.models import qwen2
from nornicdb_tpu.models.reference import deepseek_v2 as ref

BF16 = ds.DEEPSEEK_V2_SMALL
F32 = dataclasses.replace(BF16, dtype="float32")
F32_TOL = 2e-4
BF16_TOL = 0.25


def make_params(cfg, seed: int):
    """Seeded weights; the router's rows at half the usual spread (a
    row's scores spread by 0.5, so fewer tokens sit on a routing edge:
    PERF.md section 6) and non-trivial norm scales, so that a norm left
    out shows."""
    params = with_norm_scales(
        ds.init_params(cfg, jax.random.PRNGKey(seed)), seed + 1000)
    for blk in params["blocks"]:
        if "router" in blk:
            blk["router"] = (blk["router"].astype(jnp.float32)
                             * 0.5).astype(blk["router"].dtype)
    return params


def hold_experts(params, cfg, first: int, count: int):
    """One expert-parallel rank's share of a model whose tree holds every
    routed expert: ``(params, cfg)`` with experts ``first .. first + count
    - 1`` of each expert layer and everything else as it was."""
    lo = first - cfg.held_experts[0]
    blocks = [{**blk, "experts": {k: w[lo:lo + count]
                                  for k, w in blk["experts"].items()}}
              if "experts" in blk else blk for blk in params["blocks"]]
    return ({**params, "blocks": blocks},
            dataclasses.replace(cfg, held_experts=(first, count)))


def attend_expanded(cfg, blk, q_nope, q_pe, rows, mask):
    """The published form over ``attend_absorbed``'s arguments: per-head
    k_nope and v made from every row's c_kv.  q_nope / q_pe (L, T, heads,
    .), rows (L, S, latent_width)."""
    c_kv, k_pe = rows[..., :cfg.kv_lora_rank], rows[..., cfg.kv_lora_rank:]
    k_nope = jnp.einsum("lsc,chn->lshn", c_kv, blk["kv_b_k"], precision="highest")
    v = jnp.einsum("lsc,chv->lshv", c_kv, blk["kv_b_v"], precision="highest")
    s = jnp.einsum("lthn,lshn->lhts", q_nope, k_nope, precision="highest") \
        + jnp.einsum("lthr,lsr->lhts", q_pe, k_pe, precision="highest")
    p = jax.nn.softmax(s * ds.softmax_scale(cfg) + mask, axis=-1)
    return jnp.einsum("lhts,lshv->lthv", p, v, precision="highest")


def tokens(seed: int, n: int, vocab: int = BF16.vocab_size) -> list[int]:
    return draw(seed, n, vocab)


# ------------------------------------------------ (a) forward = reference
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_forward_is_the_reference_in_float32(seed):
    params = make_params(F32, seed)
    ids = tokens(seed, 40)
    want = np.asarray(ref.forward(params, F32, ids))
    got = np.asarray(ds.forward(params, F32, jnp.asarray([ids, ids[::-1]])))
    assert np.abs(got[0] - want).max() < F32_TOL
    back = np.asarray(ref.forward(params, F32, ids[::-1]))
    assert np.abs(got[1] - back).max() < F32_TOL


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bf16_forward_is_within_tolerance_and_fp8_is_not(seed):
    params = make_params(BF16, seed)
    ids = tokens(seed, 40)
    want = np.asarray(ref.forward(params, BF16, ids))
    got = np.asarray(ds.forward(params, BF16, jnp.asarray([ids])))[0]
    low = np.asarray(ref.forward(params, BF16, ids, rounded=fp8))
    assert typical(got, want) < BF16_TOL
    assert typical(low, want) > 2 * BF16_TOL


# ------------------------------------------------------ (b) the share test
@pytest.mark.parametrize("seed", [1, 2])
def test_the_shares_add_up_to_the_uncut_expert_layer(seed):
    """Four expert-parallel ranks of four experts each: the routed parts
    they compute, with the shared experts and the residual counted once,
    are the uncut reference's expert layer."""
    params = make_params(F32, seed)
    blk = params["blocks"][1]
    hid = jax.random.normal(jax.random.PRNGKey(seed), (24, F32.hidden_size))
    whole = np.asarray(ref.expert_layer(F32, blk, hid, (0, 16)))
    x = ds.rms_norm(blk["mlp_norm"], hid, F32.rms_norm_eps)
    total = np.asarray(hid + ds._swiglu(blk["shared"], x))
    counts = np.zeros(3, np.int64)
    for first in (0, 4, 8, 12):
        share, cfg = hold_experts(params, F32, first, 4)
        assert share["blocks"][1]["experts"]["gate"].shape[0] == 4
        part, c = ds.routed_experts(cfg, share["blocks"][1], x)
        total = total + np.asarray(part)
        counts += np.asarray(c)
        # and the reference, given the same share, leaves out the same
        alone = ref.routed_part(F32, share["blocks"][1], x, (first, 4))
        assert np.abs(np.asarray(part) - np.asarray(alone)).max() < F32_TOL
    assert np.abs(total - whole).max() < F32_TOL
    # every row's top-4 fell on exactly one of the shares
    assert counts[0] == 24 * F32.num_experts_per_tok


def test_only_the_held_experts_are_made():
    """The benchmark's cut holds 20 of the 160 experts a layer, the router
    keeps its published width, and the leading layer is dense."""
    cfg = ds.DEEPSEEK_V2_EP8_5L
    assert cfg.held_experts == (0, 20) and cfg.expert_layers == 4
    shapes = jax.eval_shape(lambda: ds.init_params(cfg, jax.random.PRNGKey(0)))
    assert "mlp" in shapes["blocks"][0] and "experts" not in shapes["blocks"][0]
    for blk in shapes["blocks"][1:]:
        assert blk["experts"]["gate"].shape == (20, 5120, 1536)
        assert blk["router"].shape == (5120, 160)
    assert shapes["lm_head"]["w"].shape == (5120, 12800)


# ------------------------------------------- (c) group-limited routing
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_group_limited_routing_against_a_numpy_loop(seed):
    cfg = F32
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((64, cfg.hidden_size)).astype(np.float32)
    router = rng.standard_normal(
        (cfg.hidden_size, cfg.n_routed_experts)).astype(np.float32) * 0.1
    ids, gates = (np.asarray(a) for a in ds.route(cfg, jnp.asarray(router),
                                                  jnp.asarray(x)))
    per = cfg.n_routed_experts // cfg.n_group
    for t in range(x.shape[0]):
        logits = x[t].astype(np.float64) @ router.astype(np.float64)
        p = np.exp(logits - logits.max())
        p /= p.sum()
        groups = np.argsort(-p.reshape(cfg.n_group, per).max(1),
                            kind="stable")[:cfg.topk_group]
        allowed = [e for e in range(cfg.n_routed_experts)
                   if e // per in groups]
        best = sorted(allowed, key=lambda e: -p[e])[:cfg.num_experts_per_tok]
        assert sorted(ids[t].tolist()) == sorted(best)
        assert len({e // per for e in ids[t]}) <= cfg.topk_group
        # unnormalised: p times the scaling factor, whatever they sum to
        want = {e: p[e] * cfg.routed_scaling_factor for e in best}
        for e, g in zip(ids[t], gates[t]):
            assert abs(g - want[int(e)]) < 1e-5


# --------------------------------- (d) YaRN at the published numbers
def test_yarn_frequencies_and_score_scale_at_the_published_numbers():
    cfg = ds.DeepSeekV2Config()  # the published model
    inv = ds.yarn_inv_freq(cfg)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    # correction range: 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47 ->
    # 10; 64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 -> 23
    assert np.allclose(inv[:11], plain[:11], rtol=1e-12)
    assert np.allclose(inv[23:], plain[23:] / 40.0, rtol=1e-12)
    for i in range(11, 23):
        ramp = (i - 10) / 13.0
        assert np.isclose(inv[i], plain[i] * (1 - ramp) + plain[i] / 40 * ramp,
                          rtol=1e-12)
    assert np.isclose(inv[16], plain[16] * (7 / 13 + 6 / 13 / 40), rtol=1e-12)
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    assert abs(m - 1.2608) < 1e-4
    assert np.isclose(ds.softmax_scale(cfg), 192 ** -0.5 * m * m, rtol=1e-12)
    assert abs(ds.softmax_scale(cfg) - 0.114721) < 1e-6
    assert ds.rope_scale(cfg) == 1.0  # mscale / mscale_all_dim
    assert np.allclose(ref.yarn_inv_freq(cfg), inv, rtol=1e-12)
    assert np.isclose(ref.softmax_scale(cfg), ds.softmax_scale(cfg))
    assert cfg.latent_width == 576 and cfg.page_row_width == 640


# ------------------------------------------- (e) absorbed = expanded
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_absorbed_attention_is_the_expanded_one(seed):
    params = make_params(F32, seed)
    ids = jnp.asarray([tokens(seed, 48), tokens(seed + 9, 48)])
    # the whole model: the program's forward is absorbed, the reference's
    # is expanded
    a = np.asarray(ds.forward(params, F32, ids))
    for row, seq in zip(a, np.asarray(ids)):
        b = np.asarray(ref.forward(params, F32, seq.tolist()))
        assert np.abs(row - b).max() < F32_TOL
    # and on one layer, arguments in hand
    blk = params["blocks"][0]
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    q_nope = jax.random.normal(k[0], (2, 5, 8, 16))
    q_pe = jax.random.normal(k[1], (2, 5, 8, 8))
    rows = jax.random.normal(k[2], (2, 24, 40))
    mask = jnp.where(jnp.arange(24)[None, :] <= 19 + jnp.arange(5)[:, None],
                     0.0, -1e30)[None, None]
    want = attend_expanded(F32, blk, q_nope, q_pe, rows, mask)
    got = mla.attend_absorbed(F32, blk, mla.absorb_query(blk, q_nope, q_pe),
                              rows, mask, ds.softmax_scale(F32))
    assert np.abs(np.asarray(got - want)).max() < 1e-5


# ----------------------- (f) chunked prefill, decode, prefix pages
# (``decoder_harness.Pool`` drives ``fused_step`` as the scheduler does)
def reference_rows(cfg, params, ids, out, **kw):
    return harness.reference_rows(ref.forward, params, cfg, ids, out, **kw)


@pytest.mark.parametrize("cfg,tol,seed", [
    (F32, F32_TOL, 1), (F32, F32_TOL, 2), (BF16, BF16_TOL, 1),
    (BF16, BF16_TOL, 2), (BF16, BF16_TOL, 3)])
def test_latent_pool_serving_is_the_reference_at_every_position(cfg, tol,
                                                                seed):
    """A prompt prefilled in chunks of 16 and decoded through the latent
    pool; a second prompt that shares its first three pages and prefills
    only its own suffix (the prefix-cache hit), which reads the same
    logits as the same prompt served cold into pages of its own."""
    params = make_params(cfg, seed)
    prefix = tokens(seed, 3 * PAGE)
    a, b = prefix + tokens(seed + 1, 21), prefix + tokens(seed + 2, 30)
    pool = Pool(ds, cfg, params)
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
    # the null page took the padding rows' writes and nothing else moved
    assert pool.pool.shape == (cfg.num_hidden_layers, 40, PAGE,
                               cfg.page_row_width)
    assert not np.asarray(pool.pool[:, 30:]).any()


def test_decode_lanes_beside_a_chunk_read_what_they_read_alone():
    """One fused step carrying two decode lanes and another request's
    chunk gives each the logits it gets in a step of its own."""
    params = make_params(F32, 5)
    a, b, c = tokens(1, 20), tokens(2, 27), tokens(3, 13)
    ta, tb, tc = table_of(1, 2), table_of(3, 4), table_of(5)
    alone, mixed = Pool(ds, F32, params), Pool(ds, F32, params)
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
@pytest.mark.parametrize("held", [(0, 16), (4, 8), (12, 4)])
def test_routing_counts_equal_a_numpy_count(held):
    """One step prefills 13 tokens (3 padding rows ride along and must not
    count): the counts in its int vector against the reference's routing
    of the same rows, walked layer by layer."""
    full = make_params(F32, 7)
    params, cfg = hold_experts(full, F32, *held)
    ids = tokens(7, 13)
    pool = Pool(ds, cfg, params)
    pool.step(chunk=(ids, 0, table_of(1)))
    assert pool.counts.any()  # the step's int vector carried its counts
    hid = params["tok_emb"][jnp.asarray(ids)].astype(jnp.float32)
    angles = np.outer(np.arange(13), ref.yarn_inv_freq(cfg))
    cos, sin = (jnp.asarray(f(angles), jnp.float32) for f in (np.cos, np.sin))
    want = dict.fromkeys(ROUTING_COUNTERS, 0)
    for blk in params["blocks"]:
        hid = ref.attention_layer(cfg, blk, hid, cos, sin)
        if "mlp" in blk:
            hid = hid + ref._swiglu(
                blk["mlp"], ref._rms(blk["mlp_norm"], hid, cfg.rms_norm_eps),
                None)
            continue
        picked, _ = ref.route(cfg, blk["router"], ref._rms(
            blk["mlp_norm"], hid, cfg.rms_norm_eps))
        rows = np.zeros(held[1], np.int64)
        for e in np.asarray(picked).ravel():
            if held[0] <= e < held[0] + held[1]:
                rows[e - held[0]] += 1
        want["expert_assignments"] += int(rows.sum())
        want["expert_rows_max"] += int(rows.max())
        want["experts_hit"] += int((rows > 0).sum())
        want["routed_rows"] += 13
        hid = ref.expert_layer(cfg, blk, hid, held)
    assert dict(zip(ROUTING_COUNTERS, pool.counts.tolist())) == want
    # behind them the walk's pair: a table of 8 pages is narrower than a
    # block, so the decode block's three lanes and the chunk lane each
    # walked all of it, in every layer
    assert ds.STEP_COUNTERS == ROUTING_COUNTERS + mla.WALK_COUNTERS
    assert pool.counts[4:].tolist() == \
        [LMAX * WIDTH * PAGE * cfg.num_hidden_layers] * 2
    if held == (0, 16):
        assert want["expert_assignments"] == 13 * 2 * cfg.num_experts_per_tok


def test_the_step_carries_its_scopes_and_its_own_module_name():
    params = jax.eval_shape(lambda: ds.init_params(BF16,
                                                   jax.random.PRNGKey(0)))
    meta = jax.ShapeDtypeStruct((4 * 16 + LMAX + LMAX * WIDTH,), jnp.int32)
    pages = jax.eval_shape(lambda: ds.init_pages(BF16, 9, PAGE))
    lowered = ds.fused_step.lower(params, BF16, meta, pages, lmax=LMAX,
                                  w=WIDTH, tq=16)
    text = lowered.as_text(debug_info=True)
    for scope in ("mla.project", "mla.absorb", "mla.attend", "moe.route",
                  "moe.experts", "moe.shared"):
        assert scope in text, scope
    module = re.search(r"module @(\S+)", lowered.as_text()).group(1)
    assert module == "jit_mla_moe_fused_step"
    assert ds.num_pages(pages) == 9 and qwen2.num_pages(
        jax.eval_shape(lambda: qwen2.init_pages(qwen2.QWEN_SMALL, 9, PAGE))
    ) == 9


# ------------------------------------ (i) Qwen's lowered step unchanged
@pytest.mark.parametrize("tq,sha", [
    (16, "64847afe19ad45be9aacac6b961e2fc9f9f1418809994efb224a9c3cc26117a6"),
    (1, "68e064b882241adfa6be0ed3a66a8ffff83323c99dd91247948bdaf8d64ffb5f")])
def test_qwens_lowered_step_is_what_it_was_before_the_family_seam(tq, sha):
    """The lowered text of ``ragged_fused_step`` for one step class is what
    it was when last measured: a PR that does not mean to change Qwen's
    step (PR 30's family seam moved the scheduler's helpers out of
    ``models/qwen2.py``) may not move the program.  A PR that MEANS to
    change it renews the two digests and measures ``mem-chat-sys4k``; PR 31
    did (128-wide pool rows, ``layers.grouped_attention``), and PR 33 (the
    served variant takes ``prev``, the previous step's ids, and reads the
    tokens still in flight there: one ``select`` over a gather before the
    embedding lookup), and PR 38: the step's two attention blocks are
    ``models/kv_walk.py``'s walk over live lengths (a ``while`` a block
    that gathers pages by FLAT page number straight from the pool, a
    running softmax, the row-wide contraction of ``grouped_attention``
    inside it) in place of a slice of each layer's K and V out of the pool
    and a gather of every page of every lane; the decode block has
    ``lmax - 1`` lanes, and the int vector ends with the walk's two counts
    (``qwen2.STEP_COUNTERS``), so ``prev`` is ``lmax + 2`` wide; the two
    blocks are one jitted function with the layer's index a value, lowered
    once and called a layer (``kv_walk.attend_blocks``); and PR 40: the
    decode block's walk is two ``while``s, the run of blocks that every
    live lane's table begins with gathered once from the first live lane's
    row and scored for all lanes, then the per-lane turns behind it, and
    the int vector ends with ``shared_run_pages`` (``prev`` is ``lmax + 3``
    wide).  At this
    case's 8-page tables a block is the whole table, so the digests do not
    move with ``kv_walk``'s block rule."""
    cfg = qwen2.QWEN_SMALL
    params = jax.eval_shape(lambda: qwen2.init_params(cfg,
                                                      jax.random.PRNGKey(0)))
    lmax, w, f = 6, 8, 16
    meta = jax.ShapeDtypeStruct((4 * f + lmax + lmax * w,), jnp.int32)
    pages = jax.eval_shape(lambda: qwen2.init_pages(cfg, 17, 16))
    prev = jax.ShapeDtypeStruct((lmax + len(qwen2.STEP_COUNTERS),), jnp.int32)
    text = qwen2.ragged_fused_step.lower(params, cfg, meta, pages, lmax=lmax,
                                         w=w, tq=tq, prev=prev).as_text()
    assert re.search(r"module @(\S+)", text).group(1) == \
        "jit_ragged_fused_step"
    assert hashlib.sha256(text.encode()).hexdigest() == sha


@pytest.mark.parametrize("tq,sha", [
    (16, "9f11b85a7ec7cef39c71b43cc2651c477f0e15e32e0b0bb9fee8744406b9aa4a"),
    (1, "f158c711bb7c5d1fb010ba30c92ec71daa3d992f0c1446c7c82e2b981450688b")])
def test_dsv2s_lowered_step_is_what_it_was_before_mla_was_shared(tq, sha):
    """As the case above, for ``mla_moe_fused_step``: the lowered text for
    one step class at the small preset is what it was before PR 34 moved
    the MLA projection, the absorbed attention, the step's two blocks, the
    latent pool and the masked matmul over held experts into
    ``models/mla.py`` (same digests before and after the move).  The other
    latent family (``models/longcat_flash.py``) runs that module too: a PR
    that changes it reaches BOTH families, renews the two digests and
    measures ``dsv2-chat-sys4k`` and ``lcf-chat-sys4k``; one that does not
    mean to may not move this program.  PR 36 MEANT to and renewed them:
    the step's two attention blocks walk their lanes' page tables in
    blocks of ``mla.BLOCK_PAGES`` pages up to the longest live length with
    a running softmax (a ``while`` a block in place of one gather of every
    page of every lane), and the int vector ends with the walk's two
    counts.  At this case's 8-page tables a block is the whole table, so
    the digests do not move with ``BLOCK_PAGES``."""
    cfg = ds.DEEPSEEK_V2_SMALL
    params = jax.eval_shape(lambda: ds.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    lmax, w, f = 6, 8, 16
    meta = jax.ShapeDtypeStruct((4 * f + lmax + lmax * w,), jnp.int32)
    pages = jax.eval_shape(lambda: ds.init_pages(cfg, 17, 16))
    prev = jax.ShapeDtypeStruct((lmax + len(ds.STEP_COUNTERS),), jnp.int32)
    text = ds.fused_step.lower(params, cfg, meta, pages, lmax=lmax, w=w,
                               tq=tq, prev=prev).as_text()
    assert re.search(r"module @(\S+)", text).group(1) == \
        "jit_mla_moe_fused_step"
    assert hashlib.sha256(text.encode()).hexdigest() == sha


# -------------------------------------- (j), (g): the engine, Heimdall
def engine_config(**kw):
    from nornicdb_tpu.config import GenServeConfig

    return GenServeConfig(**{**dict(max_seqs=2, max_seq_tokens=128,
                                    pool_pages=33, page_size=PAGE,
                                    prefill_chunk=16, deadline_ms=0), **kw})


def test_there_is_one_way_to_run_a_decoder():
    """No option selects a path: ``GenServeConfig`` has neither ``mode``
    nor ``enabled``, every family module exposes the seam's three names and
    a plain reference beside it, and no family has a step of another
    kind."""
    import importlib

    from nornicdb_tpu.config import GenServeConfig
    from nornicdb_tpu.genserve import GenerationEngine

    fields = GenServeConfig.__dataclass_fields__
    assert "mode" not in fields and "enabled" not in fields
    with pytest.raises(TypeError):
        engine_config(mode="dense")
    for family, small in ((ds, BF16), (qwen2, qwen2.QWEN_SMALL)):
        for name in ("init_pages", "num_pages", "fused_step"):
            assert callable(getattr(family, name)), (family.__name__, name)
        for gone in ("prefill", "decode", "decode_step", "generate"):
            assert not hasattr(family, gone), (family.__name__, gone)
        plain = importlib.import_module(
            family.__name__.replace(".models.", ".models.reference."))
        assert callable(plain.forward)
        params = jax.eval_shape(lambda: family.init_params(
            small, jax.random.PRNGKey(0)))
        engine = GenerationEngine(params, small, config=engine_config())
        assert engine._family is family
        assert engine.stats_snapshot()["mode"] == "paged"


def test_the_engine_serves_the_family_through_its_latent_pool():
    """Two prompts with a shared prefix through the GenerationEngine: what
    it generates reads a greedy gap of (float32) nothing against the
    reference, the second takes its prefix from the cache, the routing
    counters move and the pool is the family's."""
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
    assert stats["routed_rows"] == steps_rows * cfg.expert_layers
    assert 0 < stats["expert_assignments"] < stats["routed_rows"] * 4
    assert stats["experts_hit"] <= stats["expert_assignments"]
    assert stats["expert_rows_max"] <= stats["expert_assignments"]
    # the engine's 8-page tables are narrower than a block: every step
    # walked all of them, and GenStats read that off the same int vector
    assert stats["attn_slots_walked"] == stats["attn_slots_table"] > 0
    row = cfg.page_row_width * 4 * cfg.num_hidden_layers * PAGE
    assert hbm["kv_pages"] == 33 * row
    assert hbm["kv_prefix"] == stats["prefix_pages"] * row


def test_heimdall_streams_the_references_greedy_continuation_over_sse():
    """``db.set_heimdall_generator`` -> ``_wire_genserve`` ->
    GenerationEngine -> ``POST /v1/chat/completions`` as server-sent
    events: the streamed ids read no gap against the reference's logits
    over the prompt that Heimdall assembled."""
    import nornicdb_tpu
    from nornicdb_tpu import genserve
    from nornicdb_tpu.heimdall import EngineGenerator, WeightsGenerator
    from nornicdb_tpu.models.tokenizer import HashTokenizer
    from nornicdb_tpu.server import HttpServer

    cfg = dataclasses.replace(F32, vocab_size=2048)
    params = make_params(cfg, 13)
    generator = WeightsGenerator(cfg, params, HashTokenizer(cfg.vocab_size),
                                 max_context=1024)
    with pytest.raises(RuntimeError, match="genserve engine only"):
        generator.generate("hello")
    genserve.configure(engine_config(max_seq_tokens=1536, pool_pages=200,
                                     prefill_chunk=64))
    db = nornicdb_tpu.open_db("")
    http_server = None
    try:
        db.set_heimdall_generator(generator)
        engine = db.genserve_engine()
        assert isinstance(db.heimdall.generator, EngineGenerator)
        assert engine._family is ds
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
    assert pages_for(len(seen[0]) + 6, PAGE) <= 96
