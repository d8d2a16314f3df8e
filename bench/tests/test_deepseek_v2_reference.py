"""The plain DeepSeek-V2 reference of ``bench/models/deepseek_v2.py`` against
the program's served path, on seeded weights at a size a test run can hold.

* The generation engine itself (the fused step over the latent pool, the
  scheduler, the prefix cache): what it streams for two prompts with a
  shared prefix reads a greedy gap under ``GAP_TOLERANCE``; the same
  streams with every id shifted by one read far over it.
* The fp8 control (the reference with every weight matmul in e4m3) put in
  the program's place reads over the tolerance; the reference itself reads
  0.
* The family file's reference (from the configuration's numbers alone)
  gives the program's own plain float32 reference
  (``nornicdb_tpu/models/reference/deepseek_v2.py``) to rounding: two
  independent writings of the published layer.
* Routing edges: where the reference's own margin between the last kept
  and the first dropped expert (or group) is under ``ROUTE_TIE`` it reads
  the row under each routing and takes the least gap; a tie between
  experts held elsewhere adds nothing; a row's first reading is the plain
  reference's; a token served from the other side of an edge reads 0, the
  same token without an edge reads its whole gap.
* The work functions, on numbers small enough to check by hand.

``GAP_TOLERANCE`` 0.15, the rehearsal's limit: bf16 against the float32
reference at this size reads 0.013-0.052 on the rehearsal's own runs; the
fp8 control reads 0.54-1.20, a missing or misplaced routed part 0.46-0.70.

    python3 -m pytest bench/tests/test_deepseek_v2_reference.py -q
"""

import json
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import loadgen  # noqa: E402

family = loadgen.load_file("models/deepseek_v2.py")
GAP_TOLERANCE = 0.15


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "assistant-1m-deepseek-v2-ep8.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec(config):
    return {**config["generator"], **config["rehearsal"]["generator"]}


def test_the_configuration_is_the_published_model_cut_three_ways(config):
    """Every published width unchanged, ``reduced`` exactly depth, experts
    held and vocabulary, the reckoning adds up to 12.4-12.6 GB, and the
    file's sizes are the program's preset."""
    from nornicdb_tpu.models import deepseek_v2

    g = config["generator"]
    published = {"hidden_size": 5120, "num_attention_heads": 128,
                 "q_lora_rank": 1536, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "intermediate_size": 12288,
                 "moe_intermediate_size": 1536, "router_outputs": 160,
                 "num_experts_per_tok": 6, "n_group": 8, "topk_group": 3,
                 "routed_scaling_factor": 16.0, "n_shared_experts": 2,
                 "first_k_dense_replace": 1, "rope_theta": 10000}
    assert {k: g[k] for k in published} == published
    assert g["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (g["num_layers"], g["n_routed_experts"], g["vocab_size"]) == \
        (5, 20, 12800)
    assert g["published"]["num_hidden_layers"] == 60
    assert g["published"]["n_routed_experts"] == 160
    assert g["published"]["vocab_size"] == 102400
    assert family.program_config(g) == deepseek_v2.DEEPSEEK_V2_EP8_5L
    reck = config["hbm_reckoning"]
    assert reck["generator_params_bytes"] == family.param_bytes(g)
    assert family.matrix_params(g) + 66560 == 3_145_466_880
    assert reck["latent_pages_bytes"] == 8193 * 16 * 5 * 640 * 2
    assert 12.4e9 <= reck["total_bytes"] <= 12.6e9


def test_the_engine_streams_what_the_reference_would(spec):
    """Two prompts through the GenerationEngine, the second after the first
    so that it takes the shared latent pages from the prefix cache."""
    from nornicdb_tpu.config import GenServeConfig
    from nornicdb_tpu.genserve import GenerationEngine

    cfg = family.program_config(spec)
    params = family.make_params(spec, 5)
    engine = GenerationEngine(params, cfg, config=GenServeConfig(
        max_seqs=2, max_seq_tokens=256, pool_pages=65, deadline_ms=0))
    rng = np.random.default_rng(5)
    prefix = rng.integers(4, spec["vocab_size"], 80).tolist()
    seqs = []
    try:
        for n in (21, 40):
            prompt = prefix + rng.integers(4, spec["vocab_size"], n).tolist()
            seqs.append((prompt, engine.generate(prompt, max_new_tokens=12)))
        stats = engine.stats_snapshot()
    finally:
        engine.stop()
    assert stats["prefix_reused_tokens"] == 80
    assert stats["expert_assignments"] > 0
    assert all(len(out) == 12 for _, out in seqs)
    gaps, _ = family.greedy_gaps(spec, params, seqs, control=False)
    assert max(float(g.max()) for g in gaps) < GAP_TOLERANCE
    wrong = [(p, [(t + 1) % spec["vocab_size"] for t in out])
             for p, out in seqs]
    gaps, _ = family.greedy_gaps(spec, params, wrong, control=False)
    assert max(float(g.max()) for g in gaps) > GAP_TOLERANCE


@pytest.mark.parametrize("seed", [1, 2147483659, 3000000019])
def test_fp8_decoder_fails_the_tolerance_and_the_reference_reads_nought(
        spec, seed):
    params = family.make_params(spec, seed)
    rng = np.random.default_rng(seed)
    seqs = []
    for n in (40, 150):
        prompt = rng.integers(4, spec["vocab_size"], n).tolist()
        out = []
        for _ in range(24):
            row = [len(prompt) + len(out) - 1]
            out.append(int(family.reference_logits(
                spec, params, prompt + out, row, pad_to=256)[0].argmax()))
        seqs.append((prompt, out))
    gaps, low = family.greedy_gaps(spec, params, seqs, control=True)
    assert max(float(g.max()) for g in gaps) == 0.0
    assert max(float(g.max()) for g in low) > GAP_TOLERANCE, low


@pytest.mark.parametrize("seed", [3, 2147483659])
def test_the_two_references_agree(spec, seed):
    """The family file's blocked reference and the program's plain one:
    written apart, from the same published description."""
    from nornicdb_tpu.models.reference import deepseek_v2 as plain

    params = family.make_params(spec, seed)
    cfg = family.program_config(spec)
    ids = np.random.default_rng(seed).integers(
        4, spec["vocab_size"], 150).tolist()
    rows = list(range(len(ids)))
    mine = family.reference_logits(spec, params, ids, rows)
    theirs = np.asarray(plain.forward(params, cfg, ids))
    assert np.abs(mine - theirs).max() < 2e-4
    inv, scale, s_scale = family.yarn(spec)
    assert np.allclose(inv, plain.yarn_inv_freq(cfg), rtol=1e-12)
    assert scale == 1.0 and np.isclose(s_scale, plain.softmax_scale(cfg))


def _scores(spec, logits):
    p = np.exp(np.asarray(logits, np.float64))
    return (p / p.sum(-1, keepdims=True)).astype(np.float32)


def test_a_routing_edge_is_read_on_both_sides(spec):
    """16 experts in 4 groups of 4, the best 2 groups, the best 4 experts
    among them, experts 0-3 (group 0) held, gates 2 p."""
    e = spec["router_outputs"]
    clear = np.full(e, -4.0)
    clear[[0, 1, 4, 5, 6]] = [2.0, 1.5, 1.8, 1.0, 0.2]   # 5th far behind
    expert_edge = clear.copy()
    expert_edge[[1, 5, 6]] = [1.0, 1.2, 0.999]  # 4th (held) and 5th tie
    elsewhere = clear.copy()
    elsewhere[[5, 6]] = [1.0, 0.999]       # the tie is between 5 and 6
    group_edge = np.full(e, -4.0)
    group_edge[[0, 1, 4, 5, 8]] = [1.0, 0.5, 2.0, -1.0, 1.0005]  # groups 2, 0 tie
    p = _scores(spec, [clear, expert_edge, elsewhere, group_edge])
    plain, edge, other, groups = family.held_gate_choices(spec, p)
    assert len(plain) == 1 and len(other) == 1
    assert np.allclose(plain[0], [2 * p[0, 0], 2 * p[0, 1], 0, 0])
    assert len(edge) == 2
    assert np.allclose(edge[0], [2 * p[1, 0], 2 * p[1, 1], 0, 0])
    assert np.allclose(edge[1], [2 * p[1, 0], 0, 0, 0])
    # group 2 kept beside group 1 (nothing held is routed to), or group 0
    # in its place (experts 0 and 1 beside 4 and 5)
    assert len(groups) == 2
    assert np.allclose(groups[0], [0, 0, 0, 0])
    assert np.allclose(groups[1], [2 * p[3, 0], 2 * p[3, 1], 0, 0])


@pytest.mark.parametrize("seed", [4, 2147483659])
def test_a_token_from_the_other_side_of_an_edge_reads_nought(
        spec, seed, monkeypatch):
    params = family.make_params(spec, seed)
    ids = np.random.default_rng(seed).integers(
        4, spec["vocab_size"], 200).tolist()
    rows = np.arange(100, 200)
    latents = family._forward(spec, params, ids, "highest")[1]
    plain = family.reference_logits(spec, params, ids, rows)
    # every margin counts as a tie: each row is read under its other
    # routings too, and its first reading is still the plain reference's
    monkeypatch.setattr(family, "ROUTE_TIE", 1e9)
    logits, of = family._row_readings(spec, params, ids, latents, rows)
    first = np.array([np.flatnonzero(of == i)[0] for i in range(len(rows))])
    assert np.abs(logits[first] - plain).max() < 2e-4
    counts = np.bincount(of)
    assert counts.max() <= family.ROW_READINGS and counts.max() > 1
    # the reading whose first token the plain reference likes least
    tokens = logits.argmax(axis=1)
    far = int((plain[of].max(axis=1) - plain[of, tokens]).argmax())
    row, token = int(of[far]), int(tokens[far])
    assert token != int(plain[row].argmax())
    seq = [(ids[:rows[row] + 1], [token])]
    gaps, _ = family.greedy_gaps(spec, params, seq, control=False)
    assert float(gaps[0][0]) == 0.0
    # without the edge the same token reads its whole gap
    monkeypatch.setattr(family, "ROUTE_TIE", 0.0)
    gaps, _ = family.greedy_gaps(spec, params, seq, control=False)
    assert np.isclose(float(gaps[0][0]),
                      plain[row].max() - plain[row, token], atol=2e-4)
    assert float(gaps[0][0]) > 0.0


def test_work_functions_on_round_numbers():
    gen = {"generator": {
        "hidden_size": 4, "num_layers": 3, "first_k_dense_replace": 1,
        "num_attention_heads": 2, "q_lora_rank": 3, "kv_lora_rank": 2,
        "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2,
        "intermediate_size": 8, "moe_intermediate_size": 2,
        "router_outputs": 8, "held_experts": [0, 2], "n_routed_experts": 2,
        "n_shared_experts": 1, "num_experts_per_tok": 2, "vocab_size": 10,
        "dtype": "bfloat16"}}
    g = gen["generator"]
    # attention: 4x3 + 3x2x4 + 4x4 + 2x2x4 + 4x4 = 84; an expert 3x4x2 = 24
    attn, expert = 84, 24
    outside = 3 * attn + 96 + 2 * (32 + expert) + 80
    assert family._outside_experts(g) == outside
    assert family.matrix_params(g) == outside + 2 * 2 * expert
    assert family.param_bytes(g) == 2 * (outside + 96) + 4 * (
        3 * (8 + 3 + 2) + 4)
    # a token meets 2 x 2 / 8 = 0.5 held experts a layer
    per_tok = 3 * attn + 96 + 2 * (32 + 1.5 * expert)
    assert family.matmul_params_per_token(g) == per_tok
    toks = family.gen_tokens(gen, [[0.5, 2, 6]], [[6, 8]], 3)
    cached = 2.0 * 3 * 2 * (2 * 2 + 2)  # 2 x layers x heads x (2 kvl + rope)
    want = 0.5 * (2 * per_tok * 4 + cached * 18) + 2 * per_tok * 2 \
        + cached * 15 + 2.0 * 10 * 4 * 3
    assert toks == {"flops": want, "bytes": float(family.param_bytes(g))}
    steps = family.fused_steps(gen, 2, [[0.5, 2, 6]], [[6, 8]], 3)
    rows = (0.5 * 4 + 2) / 2
    hit = 2 * (1 - (1 - 2 / 8) ** rows)
    assert steps["flops"] == want
    assert np.isclose(steps["bytes"], 2 * 2 * (outside + 2 * hit * expert)
                      + 15 * 3 * 4 * 2)
    assert family.fused_steps(gen, 0, [], [], 0) == {"flops": 0.0,
                                                     "bytes": 0.0}
