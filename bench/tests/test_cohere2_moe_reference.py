"""The plain Command A+ reference of ``bench/models/cohere2_moe.py`` against
the program's served path, on seeded weights at a size a test run can hold.

* The configuration is the published language model cut three ways; the
  file's top-level copy of the source's keys is the catalog's row, and the
  generator block is what the family file runs.
* The generation engine itself (the fused step over the two page kinds'
  pools, the scheduler, the prefix cache across kinds): what it streams for
  two prompts with a shared prefix LONGER than the window reads a greedy
  gap under ``GAP_TOLERANCE``; the same streams with every id shifted by
  one read far over it.
* The fp8 control (the reference with every weight matmul in e4m3) put in
  the program's place reads over the tolerance; the reference itself reads
  0.
* The family file's reference (from the configuration's numbers alone)
  gives the program's own plain float32 reference
  (``nornicdb_tpu/models/reference/cohere2_moe.py``) to rounding: two
  independent writings of the published layer.
* The routing edge, on the router's logits: where the reference's own
  margin between the 8th and the 9th is under ``ROUTE_TIE`` it reads the
  row under each routing and takes the least gap; the gates are normalised
  over the chosen, so a swap between two absent experts moves the held
  gates too; a row's first reading is the plain reference's.
* The work functions, on numbers small enough to check by hand.

``GAP_TOLERANCE`` is the rehearsal's limit (its readings are in the
configuration's ``rehearsal.limits_note``).

    python3 -m pytest bench/tests/test_cohere2_moe_reference.py -q
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

family = loadgen.load_file("models/cohere2_moe.py")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the source's config.json for the language model (the catalog's row)
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "layer_types": PERIOD * 8, "logit_scale": 1,
    "max_position_embeddings": 200000, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144}
CUT = {"num_layers": 4, "num_experts": 8, "vocab_size": 32768}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "assistant-1m-command-a-plus-ep16.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec(config):
    return {**config["generator"], **config["rehearsal"]["generator"]}


@pytest.fixture(scope="module")
def gap_tolerance(config):
    return config["rehearsal"]["limits"]["greedy_gap_max"]


def test_the_configuration_is_the_published_model_cut_three_ways(config):
    """Every key of the source under its own name at the top of the file,
    unchanged but for ``reduced`` = depth, experts held and vocabulary (the
    depth under ``num_layers``, beside the source's ``num_hidden_layers``
    at its published 32); the generator block is what is run: one period;
    the reckoning adds up to 13.13 GB = 76.4 %, its pools what the engine
    sizes from the model's config; the file's sizes are the program's
    preset."""
    from nornicdb_tpu.models import cohere2_moe

    g = config["generator"]
    assert config["reduced"] == list(CUT)
    for key, value in PUBLISHED.items():
        assert config[key] == CUT.get(key, value), key
    assert config["num_layers"] == g["num_layers"] == 4
    assert g["layer_types"] == PERIOD == config["layer_types"][:4]
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "num_shared_experts", "sliding_window",
                "rope_theta", "layer_norm_eps", "logit_scale", "num_experts",
                "vocab_size"):
        assert g[key] == config[key], key
    assert g["published"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144,
        "parameters": g["published"]["parameters"]}
    assert (g["router_outputs"], g["held_experts"]) == (128, [0, 8])
    assert family.program_config(g) == cohere2_moe.COMMAND_A_PLUS_EP16_4L
    assert len(config["source"]) <= 200
    reck = config["hbm_reckoning"]
    assert reck["generator_params_bytes"] == family.param_bytes(g)
    # ISSUE 37's arithmetic
    assert family._attention_params(g) == 142_606_336
    assert family._expert_params(g) == 50_331_648
    assert family._layer_outside_experts(g) == 344_457_216
    assert family.matrix_params(g) == 4 * 747_110_400 + 134_217_728 \
        == 3_122_659_328
    whole = 32 * (344_457_216 + 128 * 50_331_648) + 262144 * 4096
    assert round(whole / 1e9, 2) == 218.25
    active = 32 * (344_457_216 + 8 * 50_331_648) + 262144 * 4096
    assert round(active / 1e9, 1) == 25.0
    options = config["deployment"]["options"]
    assert options == {
        "backend.fallback": "fail", "genserve.max_seqs": 16,
        "genserve.max_seq_tokens": 8192, "genserve.page_size": 16,
        "genserve.pool_pages": 8193, "genserve.fallback": "fail",
        "genserve.deadline_ms": 120000}
    pages = family.kv_page_counts(g, options)
    assert pages == {"full": 8193, "window": 16 * 261 + 512 + 1}
    row = family.kv_bytes_per_token_layer(g) * 16  # a page, K and V
    assert reck["kv_pages_full_bytes"] == 1 * pages["full"] * row
    assert reck["kv_pages_window_bytes"] == 3 * pages["window"] * row
    assert reck["total_bytes"] == 13_133_858_816
    assert reck["share_percent"] == 76.4
    # and the engine sizes its kinds so, from the model's config alone
    import jax

    from nornicdb_tpu.config import GenServeConfig
    from nornicdb_tpu.genserve import GenerationEngine

    cfg = family.program_config(g)
    params = jax.eval_shape(lambda: cohere2_moe.init_params(
        cfg, jax.random.PRNGKey(0)))
    engine = GenerationEngine(params, cfg, config=GenServeConfig(
        max_seqs=16, max_seq_tokens=8192, page_size=16, pool_pages=8193))
    assert [(k.name, k.horizon, k.width, k.usable + 1)
            for k in engine._kinds] == [("full", None, 512, 8193),
                                        ("window", 4096, 261, 4689)]


def test_the_engine_streams_what_the_reference_would(spec, gap_tolerance):
    """Two prompts through the GenerationEngine, the second after the first
    so that it takes the shared pages from the prefix cache, of each kind
    what its first query still sees (the prefix is 400 tokens, the window
    128)."""
    from nornicdb_tpu.config import GenServeConfig
    from nornicdb_tpu.genserve import GenerationEngine

    cfg = family.program_config(spec)
    params = family.make_params(spec, 5)
    engine = GenerationEngine(params, cfg, config=GenServeConfig(
        max_seqs=2, max_seq_tokens=512, pool_pages=65, deadline_ms=0))
    rng = np.random.default_rng(5)
    prefix = rng.integers(4, spec["vocab_size"], 400).tolist()
    seqs = []
    try:
        for n in (21, 40):
            prompt = prefix + rng.integers(4, spec["vocab_size"], n).tolist()
            seqs.append((prompt, engine.generate(prompt, max_new_tokens=12)))
        stats = engine.stats_snapshot()
    finally:
        engine.stop()
    assert stats["prefix_reused_tokens"] == 400
    assert stats["expert_assignments"] > 0
    # the first lane lets go what its window passed of the prefix; the
    # second never took those pages: a hit hands it what its first query sees
    assert 2 * (400 - 128) // 16 > stats["window_pages_dropped"] \
        > (400 - 128) // 16
    assert all(len(out) == 12 for _, out in seqs)
    gaps, _ = family.greedy_gaps(spec, params, seqs, control=False)
    assert max(float(g.max()) for g in gaps) < gap_tolerance
    wrong = [(p, [(t + 1) % spec["vocab_size"] for t in out])
             for p, out in seqs]
    gaps, _ = family.greedy_gaps(spec, params, wrong, control=False)
    assert max(float(g.max()) for g in gaps) > gap_tolerance


@pytest.mark.parametrize("seed", [1, 2147483659, 3000000019])
def test_fp8_decoder_fails_the_tolerance_and_the_reference_reads_nought(
        spec, seed, gap_tolerance):
    params = family.make_params(spec, seed)
    rng = np.random.default_rng(seed)
    seqs = []
    for n in (40, 300):
        prompt = rng.integers(4, spec["vocab_size"], n).tolist()
        out = []
        for _ in range(24):
            row = [len(prompt) + len(out) - 1]
            out.append(int(family.reference_logits(
                spec, params, prompt + out, row, pad_to=384)[0].argmax()))
        seqs.append((prompt, out))
    gaps, low = family.greedy_gaps(spec, params, seqs, control=True)
    assert max(float(g.max()) for g in gaps) == 0.0
    assert max(float(g.max()) for g in low) > gap_tolerance, low


@pytest.mark.parametrize("seed", [3, 2147483659])
def test_the_two_references_agree(spec, seed):
    """The family file's blocked reference and the program's plain one:
    written apart, from the same published description."""
    from nornicdb_tpu.models.reference import cohere2_moe as plain

    params = family.make_params(spec, seed)
    cfg = family.program_config(spec)
    ids = np.random.default_rng(seed).integers(
        4, spec["vocab_size"], 300).tolist()  # over two windows
    rows = list(range(len(ids)))
    mine = family.reference_logits(spec, params, ids, rows)
    theirs = np.asarray(plain.forward(params, cfg, ids))
    assert np.abs(mine - theirs).max() < 2e-4


def test_the_routing_edge_is_read_on_the_logits(spec):
    """16 outputs, experts 0-3 held, the best 4 of the sigmoid scores, each
    gate its score over the sum of the chosen four."""
    e = spec["router_outputs"]
    s = lambda z: 1.0 / (1.0 + np.exp(-np.asarray(z, np.float64)))  # noqa: E731,E501
    clear = np.full(e, -4.0)
    clear[[0, 1, 5, 12, 6]] = [2.0, 1.5, 1.8, 1.0, 0.2]    # 5th far behind
    held_edge = clear.copy()
    held_edge[[1, 12, 6]] = [1.0, 1.6, 0.999]   # 4th (held 1) and 5th tie
    elsewhere = clear.copy()
    elsewhere[[12, 5, 6, 7]] = [1.8, 1.0, 0.999, -4.0]  # absent 5 and 6 tie
    z = np.stack([clear, held_edge, elsewhere]).astype(np.float32)
    plain, edge, other = family.held_gate_choices(spec, z)
    total = s(z[0, [0, 1, 5, 12]]).sum()
    assert len(plain) == 1
    assert np.allclose(plain[0], [s(2.0) / total, s(1.5) / total, 0, 0])
    # the held expert on the edge: in, or out for absent 6
    assert len(edge) == 2
    kept = s(z[1, [0, 1, 5, 12]]).sum()
    swapped = s(z[1, [0, 6, 5, 12]]).sum()
    assert np.allclose(edge[0], [s(2.0) / kept, s(1.0) / kept, 0, 0])
    assert np.allclose(edge[1], [s(2.0) / swapped, 0, 0, 0])
    # two ABSENT experts on the edge: the sum of the chosen moves, and the
    # held gates with it (a hair), so it is a reading of its own
    assert len(other) == 2
    assert other[0][0] != other[1][0]
    assert np.isclose(other[0][0], s(2.0) / s(z[2, [0, 1, 12, 5]]).sum())
    assert np.isclose(other[1][0], s(2.0) / s(z[2, [0, 1, 12, 6]]).sum())


@pytest.mark.parametrize("seed", [4, 2147483659])
def test_a_token_from_the_other_side_of_an_edge_reads_nought(
        spec, seed, monkeypatch):
    params = family.make_params(spec, seed)
    ids = np.random.default_rng(seed).integers(
        4, spec["vocab_size"], 300).tolist()
    rows = np.arange(200, 300)
    cached = family._forward(spec, params, ids, "highest")[1]
    plain = family.reference_logits(spec, params, ids, rows)
    # every margin counts as a tie: each row is read under its other
    # routings too, and its first reading is still the plain reference's
    monkeypatch.setattr(family, "ROUTE_TIE", 1e9)
    logits, of = family._row_readings(spec, params, ids, cached, rows)
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
        "hidden_size": 4, "intermediate_size": 2, "num_layers": 4,
        "layer_types": PERIOD, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 2, "num_experts": 2,
        "router_outputs": 8, "held_experts": [0, 2],
        "num_experts_per_tok": 2, "num_shared_experts": 2,
        "sliding_window": 4, "vocab_size": 10, "dtype": "bfloat16"}}
    g = gen["generator"]
    # attention 2 x 4x8 + 2 x 4x4 = 96; an expert 3x4x2 = 24; router 32
    attn, expert, router = 96, 24, 32
    layer = attn + 2 * expert + router
    assert family._attention_params(g) == attn
    assert family._layer_outside_experts(g) == layer
    assert family._outside_experts(g) == 4 * layer + 40  # the tied table
    assert family.matrix_params(g) == 4 * layer + 40 + 4 * 2 * expert
    assert family.param_bytes(g) == 2 * family.matrix_params(g) + 4 * 5 * 4
    # a token meets 2 x 2 / 8 = 0.5 held experts a layer
    per_tok = 4 * (layer + 0.5 * expert)
    assert family.matmul_params_per_token(g) == per_tok
    assert family.kv_bytes_per_token_layer(g) == 2 * 2 * 2 * 2
    # rows seen by positions 2..5: the full layer 3+4+5+6 = 18; each of the
    # three window layers min(p + 1, 4) = 3+4+4+4 = 15
    assert family._seen(g, 2, 6) == 18 + 3 * 15
    assert family._seen(g, 6, 8) == 7 + 8 + 3 * 8
    assert family._seen(g, 0, 2) == 3 + 3 * 3
    toks = family.gen_tokens(gen, [[0.5, 2, 6]], [[6, 8]], 3)
    cached = 4.0 * 4 * 2  # QK^T and PV: 4 x heads x head_dim a row
    want = 0.5 * (2 * per_tok * 4 + cached * 63) + 2 * per_tok * 2 \
        + cached * 39 + 2.0 * 10 * 4 * 3
    assert toks == {"flops": want, "bytes": float(family.param_bytes(g))}
    steps = family.fused_steps(gen, 2, [[0.5, 2, 6]], [[6, 8]], 3)
    rows = (0.5 * 4 + 2) / 2
    hit = 2 * (1 - (1 - 2 / 8) ** rows)
    assert steps["flops"] == want
    assert np.isclose(steps["bytes"], 2 * 2 * (4 * layer + 40
                                               + 4 * hit * expert)
                      + 39 * 16)
    assert family.fused_steps(gen, 0, [], [], 0) == {"flops": 0.0,
                                                     "bytes": 0.0}
    # the share of held experts a run of 75 rows reaches
    assert round(1 - (1 - 8 / 128) ** 75, 3) == 0.992
