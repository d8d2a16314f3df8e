"""The plain LongCat-Flash reference of ``bench/models/longcat_flash.py``
against the program's served path, on seeded weights at a size a test run
can hold.

* The configuration is the published language model cut three ways, and the
  file's top-level copy of the source's keys is what the family file runs.
* The generation engine itself (the fused step over the two-rows-a-layer
  latent pool, the scheduler, the prefix cache): what it streams for two
  prompts with a shared prefix reads a greedy gap under ``GAP_TOLERANCE``;
  the same streams with every id shifted by one read far over it.
* The fp8 control (the reference with every weight matmul in e4m3) put in
  the program's place reads over the tolerance; the reference itself reads
  0.
* The family file's reference (from the configuration's numbers alone)
  gives the program's own plain float32 reference
  (``nornicdb_tpu/models/reference/longcat_flash.py``) to rounding: two
  independent writings of the published layer.
* The routing edge, in score + bias: where the reference's own margin
  between the last kept and the first dropped output is under
  ``ROUTE_TIE`` it reads the row under each routing and takes the least
  gap; a swap between two zero experts or a held and an absent expert
  counts, one between two absent experts adds nothing; a row's first
  reading is the plain reference's.
* The work functions, on numbers small enough to check by hand.

``GAP_TOLERANCE`` 0.12, the rehearsal's limit (its readings are in the
configuration's ``rehearsal.limits_note``).

    python3 -m pytest bench/tests/test_longcat_flash_reference.py -q
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

family = loadgen.load_file("models/longcat_flash.py")
GAP_TOLERANCE = 0.12
# the source's config.json for the language model (the catalog's row)
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12}
CUT = {"num_layers": 4, "n_routed_experts": 8, "vocab_size": 16384}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "assistant-1m-longcat-flash-ep64.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec(config):
    return {**config["generator"], **config["rehearsal"]["generator"]}


def test_the_configuration_is_the_published_model_cut_three_ways(config):
    """Every key of the source under its own name, at the top of the file
    and in the generator block alike, unchanged but for ``reduced`` =
    depth, experts held and vocabulary; the reckoning adds up to 14.70 GB =
    85.6 %; the file's sizes are the program's preset."""
    from nornicdb_tpu.models import longcat_flash

    g = config["generator"]
    assert config["reduced"] == list(CUT)
    for key, value in PUBLISHED.items():
        want = CUT.get(key, value)
        assert config[key] == want and g[key] == want, key
    assert g["published"]["num_layers"] == 28
    assert g["published"]["n_routed_experts"] == 512
    assert g["published"]["vocab_size"] == 131072
    assert (g["router_outputs"], g["held_experts"]) == (768, [0, 8])
    assert family.program_config(g) == longcat_flash.LONGCAT_FLASH_EP64_4L
    assert len(config["source"]) <= 200
    reck = config["hbm_reckoning"]
    assert reck["generator_params_bytes"] == family.param_bytes(g)
    # ISSUE 34's arithmetic: a layer outside its experts 638,873,600 with
    # its 28,672 norm scales, an expert 37,748,736, 3,964,786,688 in all
    assert family._layer_outside_experts(g) + 28_672 == 638_873_600
    assert family._attention_params(g) == 90_570_752
    assert family._expert_params(g) == 37_748_736
    assert family.matrix_params(g) + 4 * 28_672 + 6_144 == 3_964_786_688
    assert reck["latent_pages_bytes"] == 8193 * 16 * 8 * 640 * 2
    assert reck["total_bytes"] == 14_701_795_328
    assert reck["share_percent"] == 85.6
    whole = 28 * (638_873_600 + 512 * 37_748_736) + 2 * 131072 * 6144
    assert round(whole / 1e9, 2) == 560.66
    assert family.lora_scales(g) == (2.0, (6144 / 512) ** 0.5)
    assert config["deployment"]["options"] == {
        "backend.fallback": "fail", "genserve.max_seqs": 16,
        "genserve.max_seq_tokens": 8192, "genserve.page_size": 16,
        "genserve.pool_pages": 8193, "genserve.fallback": "fail",
        "genserve.deadline_ms": 120000}


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
    assert stats["expert_assignments"] > 0 and stats["zero_assignments"] > 0
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
    from nornicdb_tpu.models.reference import longcat_flash as plain

    params = family.make_params(spec, seed)
    cfg = family.program_config(spec)
    ids = np.random.default_rng(seed).integers(
        4, spec["vocab_size"], 150).tolist()
    rows = list(range(len(ids)))
    mine = family.reference_logits(spec, params, ids, rows)
    theirs = np.asarray(plain.forward(params, cfg, ids))
    assert np.abs(mine - theirs).max() < 2e-4
    cos, sin = family._rotary(spec, np.arange(9))
    pcos, psin = plain.rotary(cfg, 9)
    assert np.allclose(cos, pcos, atol=1e-7) and np.allclose(sin, psin,
                                                              atol=1e-7)


def _scores(logits):
    p = np.exp(np.asarray(logits, np.float64))
    return (p / p.sum(-1, keepdims=True)).astype(np.float32)


def test_the_routing_edge_is_read_on_both_sides_after_the_bias(spec):
    """16 outputs: 12 routed (0-3 held) then 4 zero experts (12-15); the
    best 4 of score + bias; gates 2 p.  A choice is ``[held gates (4) |
    the zero experts' summed gate]``."""
    e = spec["router_outputs"]
    none = np.zeros(e)
    clear = np.full(e, -4.0)
    clear[[0, 1, 5, 12, 6]] = [2.0, 1.5, 1.8, 1.0, 0.2]    # 5th far behind
    held_edge = clear.copy()
    held_edge[[1, 12, 6]] = [1.0, 1.6, 0.999]   # 4th (held 1) and 5th tie
    elsewhere = clear.copy()
    elsewhere[[12, 5, 6, 7]] = [1.8, 1.0, 0.999, -4.0]  # absent 5 and 6 tie
    zero_edge = clear.copy()
    zero_edge[[12, 13]] = [1.0, 0.999]          # two zero experts tie
    p = _scores([clear, held_edge, elsewhere, zero_edge])
    plain, edge, other, zeros = family.gate_choices(spec, p, none)
    assert len(plain) == 1 and len(other) == 1
    assert np.allclose(plain[0], [2 * p[0, 0], 2 * p[0, 1], 0, 0,
                                  2 * p[0, 12]])
    assert len(edge) == 2
    assert np.allclose(edge[0], [2 * p[1, 0], 2 * p[1, 1], 0, 0,
                                 2 * p[1, 12]])
    assert np.allclose(edge[1], [2 * p[1, 0], 0, 0, 0, 2 * p[1, 12]])
    # the other zero expert's gate differs (a hair): a reading of its own
    assert len(zeros) == 2
    assert np.allclose(zeros[1], [2 * p[3, 0], 2 * p[3, 1], 0, 0,
                                  2 * p[3, 13]])
    # the bias decides the choice and never a gate: a bias that lifts
    # output 6 (absent) over all puts it in the place of the last kept, the
    # zero expert 12; the held gates are still the scores' own
    bias = none.copy()
    bias[6] = 1.0
    lifted, = family.gate_choices(spec, p[:1], bias)
    assert len(lifted) == 1
    assert np.allclose(lifted[0], [2 * p[0, 0], 2 * p[0, 1], 0, 0, 0])
    # and a bias that closes a clear margin opens the edge: _need is in
    # score + bias
    close = none.copy()
    close[6] = p[0, 12] - p[0, 6] - 1e-4
    assert len(family.gate_choices(spec, p[:1], close)[0]) == 2
    assert family._need(p[0].astype(np.float64), none, 12, 6) < 0
    assert np.isclose(family._need(p[0].astype(np.float64), none, 6, 12),
                      np.log(p[0, 12] / p[0, 6]), rtol=1e-4)


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
        "hidden_size": 4, "num_layers": 2, "num_attention_heads": 2,
        "q_lora_rank": 3, "kv_lora_rank": 2, "qk_nope_head_dim": 2,
        "qk_rope_head_dim": 2, "v_head_dim": 2, "ffn_hidden_size": 8,
        "expert_ffn_hidden_size": 2, "router_outputs": 8,
        "zero_expert_num": 2, "held_experts": [0, 2], "n_routed_experts": 2,
        "moe_topk": 2, "vocab_size": 10, "dtype": "bfloat16"}}
    g = gen["generator"]
    # a block: 4x3 + 3x2x4 + 4x4 + 2x2x4 + 4x4 = 84; a dense feed-forward
    # 3x4x8 = 96; the router 4x8 = 32; an expert 3x4x2 = 24
    attn, dense, router, expert = 84, 96, 32, 24
    layer = 2 * attn + 2 * dense + router
    assert family._layer_outside_experts(g) == layer
    assert family._outside_experts(g) == 2 * layer + 80
    assert family.matrix_params(g) == 2 * layer + 80 + 2 * 2 * expert
    assert family.param_bytes(g) == 2 * family.matrix_params(g) + 4 * (
        2 * (4 * 4 + 2 * (3 + 2) + 8) + 4)
    # a token meets 2 x 2 / 8 = 0.5 held experts a layer; a zero expert
    # costs nothing
    per_tok = 2 * (layer + 0.5 * expert)
    assert family.matmul_params_per_token(g) == per_tok
    assert family.latent_bytes_per_token(g) == 2 * 2 * 4 * 2
    toks = family.gen_tokens(gen, [[0.5, 2, 6]], [[6, 8]], 3)
    cached = 2.0 * 4 * 2 * (2 * 2 + 2)  # 2 x blocks x heads x (2 kvl + rope)
    want = 0.5 * (2 * per_tok * 4 + cached * 18) + 2 * per_tok * 2 \
        + cached * 15 + 2.0 * 10 * 4 * 3
    assert toks == {"flops": want, "bytes": float(family.param_bytes(g))}
    steps = family.fused_steps(gen, 2, [[0.5, 2, 6]], [[6, 8]], 3)
    rows = (0.5 * 4 + 2) / 2
    hit = 2 * (1 - (1 - 2 / 8) ** rows)
    assert steps["flops"] == want
    assert np.isclose(steps["bytes"], 2 * 2 * (2 * layer + 80
                                               + 2 * hit * expert)
                      + 15 * 2 * 2 * 4 * 2)
    assert family.fused_steps(gen, 0, [], [], 0) == {"flops": 0.0,
                                                     "bytes": 0.0}
    # the share of held experts a run of 75 rows reaches: ISSUE 34's 0.69
    assert round(1 - (1 - 12 / 768) ** 75, 2) == 0.69
