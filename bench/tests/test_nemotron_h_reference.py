"""The plain Nemotron 3 Nano reference of ``bench/models/nemotron_h.py``
against the program's served path, on seeded weights at a size a test run
can hold.

* The configuration is the published language model cut three ways; the
  file's top-level copy of the source's keys is the catalog's row, and the
  generator block is what the family file runs.
* The generation engine itself (the fused step over the K/V pool and the
  state pool, the scheduler, the prefix cache with its snapshots): what it
  streams for two prompts with a shared prefix, the second's hit ending on
  a snapshot, reads a greedy gap under ``GAP_TOLERANCE``; the same streams
  with every id shifted by one read far over it.
* The fp8 control (the reference with every weight matmul in e4m3) put in
  the program's place reads over the tolerance; the reference itself reads
  0.
* The family file's reference (from the configuration's numbers alone, the
  Mamba layer one token after the other) gives the program's own plain
  float32 reference (``nornicdb_tpu/models/reference/nemotron_h.py``) to
  rounding: two independent writings of the published layer.
* The routing edge, on the choice scores (sigmoid + bias): where the
  reference's own margin between the 6th and the 7th is under
  ``ROUTE_TIE`` it reads the row under each routing and takes the least
  gap; a row's first reading is the plain reference's, through the Mamba
  layers too (a row goes on from the sequence's own inputs and state).
* The work functions, on numbers small enough to check by hand.

``GAP_TOLERANCE`` is the rehearsal's limit (its readings are in the
configuration's ``rehearsal.limits_note``).

    python3 -m pytest bench/tests/test_nemotron_h_reference.py -q
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

family = loadgen.load_file("models/nemotron_h.py")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# the source's config.json (the catalog's row)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
CUT = {"num_layers": 27, "n_routed_experts": 16, "vocab_size": 16384}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "assistant-1m-nemotron-3-nano-ep8.json")) as f:
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
    at its published 52); the generator block is what is run: the first 27
    layers; the reckoning adds up to 13.32 GB = 77.5 %, its pools what the
    engine sizes from the model's config and the one option; the file's
    sizes are the program's preset."""
    from nornicdb_tpu.models import nemotron_h

    g = config["generator"]
    assert config["reduced"] == list(CUT)
    for key, value in PUBLISHED.items():
        assert config[key] == CUT.get(key, value), key
    assert config["num_layers"] == g["num_layers"] == 27
    assert g["hybrid_override_pattern"] == PATTERN[:27] \
        == "MEMEM*" + 3 * "EMEMEM*"
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "mamba_num_heads", "mamba_head_dim",
                "n_groups", "ssm_state_size", "conv_kernel",
                "num_experts_per_tok", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "n_shared_experts",
                "routed_scaling_factor", "norm_eps", "time_step_min",
                "time_step_max", "time_step_floor", "n_routed_experts",
                "vocab_size"):
        assert g[key] == config[key], key
    assert g["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "parameters": g["published"]["parameters"]}
    assert (g["router_outputs"], g["held_experts"]) == (128, [0, 16])
    assert family.program_config(g) == nemotron_h.NEMOTRON_3_NANO_EP8_27L
    assert len(config["source"]) <= 200
    reck = config["hbm_reckoning"]
    assert reck["generator_params_bytes"] == family.param_bytes(g)
    # ISSUE 41's arithmetic (there with a layer's norm and small vectors)
    assert family._mamba_params(g) == 38_731_776
    assert family._attention_params(g) == 23_396_352
    assert family._expert_layer_outside(g) == 20_299_776
    assert family._expert_params(g) == 9_977_856
    assert family.matrix_params(g) == 2_625_847_296
    # ISSUE 41's 2,626,049,152 parameters = these and 201,856 scalars
    assert family.param_bytes(g) == 2 * 2_625_847_296 + 4 * 201_856 \
        == 5_252_502_016
    whole = 23 * 38_731_776 + 6 * 23_396_352 \
        + 23 * (20_299_776 + 128 * 9_977_856) + 2 * 131072 * 2688
    assert round(whole / 1e9, 2) == 31.58
    options = config["deployment"]["options"]
    assert options == {
        "backend.fallback": "fail", "genserve.max_seqs": 16,
        "genserve.max_seq_tokens": 8192, "genserve.page_size": 16,
        "genserve.pool_pages": 8193, "genserve.fallback": "fail",
        "genserve.deadline_ms": 120000, "genserve.state_slots": 82}
    assert family.state_slot_bytes(g) == 12 * (64 * 64 * 128 * 4
                                                + 3 * 6144 * 2) == 25_608_192
    pools = family.pool_bytes(g, options)
    assert reck["kv_pages_bytes"] == pools["full"] \
        == 4 * 2 * 8193 * 16 * 512 == 536_936_448
    assert reck["state_slots_bytes"] == pools["state"] \
        == 82 * 25_608_192 == 2_099_871_744
    assert reck["total_bytes"] == 13_318_937_088
    assert reck["share_percent"] == 77.5
    # and the engine sizes its kinds so, and the family's pools weigh that
    import jax

    from nornicdb_tpu.config import GenServeConfig
    from nornicdb_tpu.genserve import GenerationEngine

    cfg = family.program_config(g)
    params = jax.eval_shape(lambda: nemotron_h.init_params(
        cfg, jax.random.PRNGKey(0)))
    engine = GenerationEngine(params, cfg, config=GenServeConfig(
        max_seqs=16, max_seq_tokens=8192, page_size=16, pool_pages=8193,
        state_slots=82))
    assert [(k.name, k.horizon, k.width, k.usable + 1)
            for k in engine._kinds] == [("full", None, 512, 8193),
                                        ("state", "state", 1, 82)]
    # the option has no default: a decoder with a state kind is given it
    with pytest.raises(ValueError, match="state_slots"):
        GenerationEngine(params, cfg, config=GenServeConfig(
            max_seqs=16, max_seq_tokens=8192, page_size=16, pool_pages=8193))
    kv, state = jax.eval_shape(lambda: nemotron_h.init_pages(
        cfg, (8193, 82), 16))
    weigh = lambda tree: sum(int(np.prod(a.shape)) * a.dtype.itemsize  # noqa: E731,E501
                             for a in jax.tree.leaves(tree))
    assert (weigh(kv), weigh(state)) == (pools["full"], pools["state"])
    assert weigh(params) == family.param_bytes(g)


def test_the_engine_streams_what_the_reference_would(spec, gap_tolerance):
    """Two prompts through the GenerationEngine, the second after the first
    so that it takes the shared pages from the prefix cache and begins from
    the snapshot at the end of the hit (the prefix is 400 tokens: 384 on
    chunk ends)."""
    from nornicdb_tpu.config import GenServeConfig
    from nornicdb_tpu.genserve import GenerationEngine

    cfg = family.program_config(spec)
    params = family.make_params(spec, 5)
    engine = GenerationEngine(params, cfg, config=GenServeConfig(
        max_seqs=2, max_seq_tokens=512, pool_pages=65, deadline_ms=0,
        state_slots=12))
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
    assert stats["prefix_reused_tokens"] == 384
    assert stats["state_snapshot_hits"] == 1
    assert stats["state_snapshots_taken"] >= 6
    assert stats["expert_assignments"] > 0 and stats["ssm_rows"] > 0
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


def test_a_runs_gap_is_the_mean_over_every_compared_position(spec):
    """``greedy_gaps`` gives the harness ONE number for the run, once a
    sequence: the mean of ``position_gaps`` over all of them; and a forward
    that is handed the float32 forward's own experts is that forward."""
    params = family.make_params(spec, 7)
    rng = np.random.default_rng(7)
    seqs = [(rng.integers(4, spec["vocab_size"], n).tolist(),
             rng.integers(4, spec["vocab_size"], m).tolist())
            for n, m in ((40, 5), (150, 9))]
    each, low = family.position_gaps(
        spec, params, seqs, ("fp8", "bf16", "highest+routing"))
    assert [len(g) for g in each] == [5, 9]
    assert min(float(g.min()) for g in each) > 0.0   # random tokens
    run, ctl = family.greedy_gaps(spec, params, seqs, control=True)
    mean = np.concatenate(each).mean()
    assert [g.shape for g in run] == [(1,), (1,)]
    assert all(np.isclose(float(g[0]), mean, rtol=1e-6) for g in run)
    assert all(np.isclose(float(g[0]), np.concatenate(low["fp8"]).mean(),
                          rtol=1e-6) for g in ctl)
    assert max(float(g.max()) for g in low["highest+routing"]) == 0.0
    # the served precision reads no more than the one below it
    assert np.concatenate(low["bf16"]).mean() \
        <= np.concatenate(low["fp8"]).mean()


@pytest.mark.parametrize("seed", [3, 2147483659])
def test_the_two_references_agree(spec, seed):
    """The family file's blocked reference and the program's plain one:
    written apart, from the same published description."""
    from nornicdb_tpu.models.reference import nemotron_h as plain

    params = family.make_params(spec, seed)
    cfg = family.program_config(spec)
    ids = np.random.default_rng(seed).integers(
        4, spec["vocab_size"], 300).tolist()
    rows = list(range(len(ids)))
    mine = family.reference_logits(spec, params, ids, rows)
    theirs = np.asarray(plain.forward(params, cfg, ids))
    assert np.abs(mine - theirs).max() < 2e-4


def test_the_routing_edge_is_read_on_the_choice_scores(spec):
    """16 outputs, experts 0-3 held, the best 4 of sigmoid score + bias,
    each gate its bare score over the sum of the chosen four, x 2.5."""
    e = spec["router_outputs"]
    s = lambda z: 1.0 / (1.0 + np.exp(-np.asarray(z, np.float64)))  # noqa: E731,E501
    none = np.zeros(e)
    clear = np.full(e, -4.0)
    clear[[0, 1, 5, 12, 6]] = [2.0, 1.5, 1.8, 1.0, 0.2]    # 5th far behind
    held_edge = clear.copy()
    held_edge[[1, 12, 6]] = [1.0, 1.6, 0.999]   # 4th (held 1) and 5th tie
    z = np.stack([clear, held_edge]).astype(np.float32)
    plain, edge = family.held_gate_choices(spec, z, none)
    total = s(z[0, [0, 1, 5, 12]]).sum()
    assert len(plain) == 1
    assert np.allclose(plain[0], [2.5 * s(2.0) / total, 2.5 * s(1.5) / total,
                                  0, 0])
    # the held expert on the edge: in, or out for absent 6
    assert len(edge) == 2
    kept = s(z[1, [0, 1, 5, 12]]).sum()
    swapped = s(z[1, [0, 6, 5, 12]]).sum()
    assert np.allclose(edge[0], [2.5 * s(2.0) / kept, 2.5 * s(1.0) / kept,
                                 0, 0])
    assert np.allclose(edge[1], [2.5 * s(2.0) / swapped, 0, 0, 0])
    # the bias chooses and never weighs: lifted to the top, absent 6 is in
    # for absent 12, and the gates are the BARE scores' shares (6's is 0.55)
    lifted = none.copy()
    lifted[6] = 0.5
    (chosen,), = family.held_gate_choices(spec, z[:1], lifted)
    total = s(z[0, [0, 1, 5, 6]]).sum()
    assert np.allclose(chosen, [2.5 * s(2.0) / total, 2.5 * s(1.5) / total,
                                0, 0])


@pytest.mark.parametrize("seed", [4, 2147483659])
def test_a_token_from_the_other_side_of_an_edge_reads_nought(
        spec, seed, monkeypatch):
    params = family.make_params(spec, seed)
    ids = np.random.default_rng(seed).integers(
        4, spec["vocab_size"], 300).tolist()
    rows = np.arange(200, 300)
    plain = family.reference_logits(spec, params, ids, rows)
    # every margin counts as a tie: each row is read under its other
    # routings too, and its first reading is still the plain reference's
    # (through the Mamba layers from the sequence's own inputs and state)
    monkeypatch.setattr(family, "ROUTE_TIE", 1e9)
    logits, of = family._row_readings(spec, params, ids, rows, 0)
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
        "hidden_size": 4, "num_layers": 4, "hybrid_override_pattern": "ME*M",
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
        "mamba_num_heads": 2, "mamba_head_dim": 3, "n_groups": 1,
        "ssm_state_size": 5, "conv_kernel": 4, "moe_intermediate_size": 2,
        "moe_shared_expert_intermediate_size": 3, "n_routed_experts": 2,
        "router_outputs": 8, "held_experts": [0, 2],
        "num_experts_per_tok": 2, "vocab_size": 10, "dtype": "bfloat16"}}
    g = gen["generator"]
    # Mamba: d_inner 6, conv_dim 6 + 2 x 5 = 16: in 4 x (6 + 16 + 2) = 96,
    # taps 4 x 16 = 64, out 6 x 4 = 24; attention 2 x 4x8 + 2 x 4x4 = 96;
    # an expert 2 x 4 x 2 = 16; the shared one 2 x 4 x 3 = 24; router 32
    mamba, attn, expert, outside = 184, 96, 16, 24 + 32
    assert family._mamba_params(g) == mamba
    assert family._attention_params(g) == attn
    assert family._expert_params(g) == expert
    assert family._expert_layer_outside(g) == outside
    assert family._outside_experts(g) == 2 * mamba + attn + outside + 2 * 40
    assert family.matrix_params(g) == family._outside_experts(g) + 2 * expert
    small = 5 * 4 + 2 * (6 + 16 + 3 * 2) + 8
    assert family.param_bytes(g) == 2 * family.matrix_params(g) + 4 * small
    # a lane's state: 2 layers x (2 x 3 x 5 f32 + 3 x 16 bf16)
    assert family.state_slot_bytes(g) == 2 * (30 * 4 + 48 * 2) == 432
    # a token meets 2 x 2 / 8 = 0.5 held experts an expert layer
    per_tok = 2 * mamba + attn + outside + 0.5 * expert
    assert family.matmul_params_per_token(g) == per_tok
    assert family.scan_flops_per_token(g) == 6 * 2 * 3 * 5
    assert family.kv_bytes_per_token_layer(g) == 2 * 2 * 2 * 2
    # rows seen by positions 2..5 in the ONE attention layer: 3+4+5+6
    assert family._seen(2, 6) == 18 and family._seen(6, 8) == 15
    toks = family.gen_tokens(gen, [[0.5, 2, 6]], [[6, 8]], 3)
    cached = 4.0 * 4 * 2  # QK^T and PV: 4 x heads x head_dim a row
    tok = 2 * per_tok + 2 * 180
    want = 0.5 * (tok * 4 + cached * 18) + tok * 2 + cached * 15 \
        + 2.0 * 10 * 4 * 3
    assert toks == {"flops": want, "bytes": float(family.param_bytes(g))}
    steps = family.fused_steps(gen, 2, [[0.5, 2, 6]], [[6, 8]], 3)
    rows = (0.5 * 4 + 2) / 2
    hit = 2 * (1 - (1 - 2 / 8) ** rows)
    assert steps["flops"] == want
    # the weights a run, the K/V rows the two decoded tokens see, and a
    # lane's state read and written once a decoded token and half a time
    # for the half of a prefill that fell inside
    assert np.isclose(steps["bytes"], 2 * 2 * (family._outside_experts(g)
                                               + hit * expert)
                      + 15 * 16 + 2 * (2 + 0.5) * 432)
    assert family.fused_steps(gen, 0, [], [], 0) == {"flops": 0.0,
                                                     "bytes": 0.0}
    # the share of held experts a run of 75 rows reaches
    assert round(1 - (1 - 6 / 128) ** 75, 3) == 0.973
