"""Model family ``longcat_flash``: the language model of LongCat-Flash-Omni
(a double layer of two latent-attention blocks and two dense feed-forwards,
a shortcut-connected expert branch whose router also scores zero-compute
experts) behind the assistant, served by genserve as ONE of the ranks that
share each layer by expert parallelism: the configuration says which routed
experts are held here, how many layers and which slice of the vocabulary.

The same parts as every family file (see ``bge_m3.py``, ``qwen2.py``,
``deepseek_v2.py``).  Below ``install`` nothing imports the program or
takes anything it made.

The reference is the decoder as published (the release's ``config.json``
and modeling code): pre-norm RMSNorm, for a layer with stream ``h``::

    a0 = h  + MLA_0(norm_in_0(h));   x0 = norm_post_0(a0)
    m  = MoE(x0)                     # the shortcut branch: not yet added
    b0 = a0 + FFN_0(x0)
    a1 = b0 + MLA_1(norm_in_1(b0))
    b1 = a1 + FFN_1(norm_post_1(a1))
    h' = b1 + m

MLA: ``c_q = norm(x W_qa)``, ``q = c_q W_qb * sqrt(hidden / q_lora_rank)``
per head ``[nope | rope]``, ``[c | k_r] = x W_kva``, ``c_kv = norm(c) *
sqrt(hidden / kv_lora_rank)``, plain rotary embedding (``rope_theta``, no
scaling) on ``q``'s rope part and the one shared ``k_r``, per-head ``[k_nope
| v] = c_kv W_kvb`` (the EXPANDED form: no absorption), scores scaled by
``(nope + rope)^-0.5``, causal softmax.  MoE: ``s = softmax(x0 W_r)`` in
float32 over routed + zero experts, the ``moe_topk`` best of ``s + bias``,
gates ``routed_scaling_factor * s`` (no bias, unnormalised), a loop over
the HELD experts (what the absent ones would add is left out, as in the
program), a zero (identity) expert adds ``g x0``; untied head.  Float32 at
``highest`` matmul precision, one sequence at a time, one sub-layer at a
time, attention in groups of heads and blocks of queries, each feed-forward
in slabs of its width (one slab of each matrix cast at a time): no cache,
no batching.  Departures from the checkpoint: rotary half-pairs instead of
interleaved pairs (a column permutation of ``W_qb`` / ``W_kva``), ``W_kvb``
kept as its two column blocks, the experts' matrices stacked.
"""

from __future__ import annotations

import functools
import itertools
import math
import os
import sys

import numpy as np

from reference import fp8, hash_word_ids as tokenize, jax_key
from work import BYTES_OF

ROLE = "generator"
HERE = os.path.dirname(os.path.abspath(__file__))
EOS = 2
# the reference runs beside the deployment's 14.7 GB: a block's scores are
# (block, group, T) float32, 46 MB at T = 5,632; a group's q, k_nope, v and
# output 0.25 GB; a feed-forward slab's three casts 0.23 GB
QUERY_BLOCK = 128
HEAD_GROUP = 16
FF_SLAB = 3072    # columns of a dense feed-forward's width at a time
ROW_SLAB = 2048   # rows of the routed comparison's readings at a time


# ------------------------------------------------------- the program's side
def program_config(spec: dict):
    # the program's module FIRST: a commit that has none ends here, before
    # any weights are made
    from nornicdb_tpu.models import longcat_flash

    fields = longcat_flash.LongCatFlashConfig.__dataclass_fields__
    sizes = {k: v for k, v in spec.items() if k in fields}
    sizes.update(
        n_routed_experts=spec["router_outputs"] - spec["zero_expert_num"],
        held_experts=tuple(spec["held_experts"]))
    cfg = longcat_flash.LongCatFlashConfig(**sizes)
    if spec["n_routed_experts"] != spec["held_experts"][1]:
        sys.exit("n_routed_experts states the experts held here: "
                 f"{spec['n_routed_experts']} != {spec['held_experts'][1]}")
    if spec.get("preset"):
        preset = getattr(longcat_flash, spec["preset"])
        if cfg != preset:
            sys.exit(f"sizes differ from the serve preset {spec['preset']}: "
                     f"{cfg} != {preset}")
    return cfg


def install(db, app_cfg, spec: dict, params):
    """What ``db.heimdall`` wires for a weights-backed assistant of any
    decoder family (``bench/models/deepseek_v2.py`` ``install``): the
    generator handed to ``db.set_heimdall_generator``, so that
    ``_wire_genserve`` builds the GenerationEngine (which resolves the
    family from the config's type); then the engine's own warm-up of every
    program class, as ``cmd_serve`` calls it at boot."""
    from nornicdb_tpu.heimdall.manager import WeightsGenerator
    from nornicdb_tpu.models.tokenizer import HashTokenizer

    db.set_heimdall_generator(WeightsGenerator(
        cfg=program_config(spec), params=params,
        tokenizer=HashTokenizer(spec["vocab_size"]),
        max_context=spec["max_context"]))
    engine = db.genserve_engine()
    if engine is None:
        sys.exit("db.set_heimdall_generator built no generation engine "
                 "(genserve.enabled is off?)")
    engine.warmup(timeout=float(spec.get("warmup_timeout_s", 1100.0)))
    return engine


# -------------------------------------------------- tokenizer and prompt
@functools.lru_cache(maxsize=4)
def _head_ids(preamble_file: str, template: str, system: str,
              vocab_size: int) -> tuple:
    with open(os.path.join(os.path.dirname(HERE), preamble_file)) as f:
        preamble = f.read()
    return tuple(tokenize(template.format(preamble=preamble, system=system),
                          vocab_size))


def prompt_ids(spec: dict, request: dict) -> list[int]:
    """The token ids the engine is handed for one chat request: the
    deployment's prompt format (the assistant's own preamble, each message
    as ``role: content``, then ``assistant:``), tokenized word by word over
    the HELD slice of the vocabulary, the tail kept where it passes
    ``max_context``."""
    p = spec["prompt"]
    head = _head_ids(p["preamble_file"], p["head"], request["system"],
                     spec["vocab_size"])
    tail = tokenize(p["tail"].format(user=request["user"]),
                    spec["vocab_size"])
    return (list(head) + tail)[-spec["max_context"]:]


def shared_prefix_tokens(spec: dict, request: dict) -> int:
    """Tokens every request of the run shares (the preamble and the system
    message): any implementation has to prefill only what follows them."""
    p = spec["prompt"]
    return len(_head_ids(p["preamble_file"], p["head"], request["system"],
                         spec["vocab_size"]))


# --------------------------------------------------------------- weights
def make_params(spec: dict, seed: int) -> dict:
    """Seeded weights in the served dtype, made on the device a LAYER at a
    time.  Matrices are N(0, 1/fan_in), and the three behind a LoRA scale
    (``q_b`` behind ``s_q``; ``kv_b_k`` and ``kv_b_v`` behind ``s_kv``)
    N(0, 1/(fan_in s^2)) = N(0, 1/hidden), which is what the scales are
    for: a low-rank projection that behaves as a full-rank one drawn at
    ``hidden^-0.5``.  So queries, keys, values, scores and the residual
    stream are O(1) at every depth (at N(0, 1/fan_in) throughout the
    scores spread by 5.8 and every head attends one key: PERF.md section
    6); norm scales are 1 + 0.1 N(0,1), so leaving one out shows; the
    router's rows are N(0, router_logit_std^2 / hidden): over
    unit-RMS rows the ``router_outputs`` logits spread by
    ``router_logit_std``, so a row's ``moe_topk`` gates sum to the order of
    1 and the branch shows in the stream; ``e_score_correction_bias`` is
    N(0, router_bias_std^2), of the scores' own scale: it changes which
    experts a row takes and never a gate."""
    import jax
    import jax.numpy as jnp

    h, heads = spec["hidden_size"], spec["num_attention_heads"]
    nope, rope, vd = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                      spec["v_head_dim"])
    ql, kvl = spec["q_lora_rank"], spec["kv_lora_rank"]
    held, outputs = spec["held_experts"][1], spec["router_outputs"]
    s_q, s_kv = lora_scales(spec)
    dt = jnp.dtype(spec["dtype"])

    def mat(k, *shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def scale(k, n):
        return {"scale": 1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)}

    def mlp(k, width, lead=()):
        k = jax.random.split(k, 3)
        return {"gate": mat(k[0], *lead, h, width, std=h ** -0.5),
                "up": mat(k[1], *lead, h, width, std=h ** -0.5),
                "down": mat(k[2], *lead, width, h, std=width ** -0.5)}

    def attention(key):
        k = jax.random.split(key, 9)
        return {
            "attn_norm": scale(k[0], h),
            "q_a": {"w": mat(k[1], h, ql, std=h ** -0.5)},
            "q_a_norm": scale(k[2], ql),
            "q_b": {"w": mat(k[3], ql, heads * (nope + rope),
                             std=ql ** -0.5 / s_q)},
            "kv_a": {"w": mat(k[4], h, kvl + rope, std=h ** -0.5)},
            "kv_a_norm": scale(k[5], kvl),
            "kv_b_k": mat(k[6], kvl, heads, nope, std=kvl ** -0.5 / s_kv),
            "kv_b_v": mat(k[7], kvl, heads, vd, std=kvl ** -0.5 / s_kv),
            "o": {"w": mat(k[8], heads * vd, h, std=(heads * vd) ** -0.5)}}

    @jax.jit
    def layer(key):
        k = jax.random.split(key, 9)
        return {
            "attn": [attention(k[0]), attention(k[1])],
            "mlp_norm": [scale(k[2], h), scale(k[3], h)],
            "mlp": [mlp(k[4], spec["ffn_hidden_size"]),
                    mlp(k[5], spec["ffn_hidden_size"])],
            "router": mat(k[6], h, outputs,
                          std=spec["router_logit_std"] * h ** -0.5),
            "router_bias": jax.random.normal(k[7], (outputs,), jnp.float32)
            * spec["router_bias_std"],
            "experts": mlp(k[8], spec["expert_ffn_hidden_size"],
                           lead=(held,))}

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 3)
        return {"tok_emb": mat(k[0], spec["vocab_size"], h, std=0.02),
                "lm_head": {"w": mat(k[1], h, spec["vocab_size"],
                                     std=h ** -0.5)},
                "final_norm": scale(k[2], h)}

    keys = jax.random.split(jax_key(seed + 2), spec["num_layers"] + 1)
    params = ends(keys[0])
    params["blocks"] = [layer(keys[1 + li])
                        for li in range(spec["num_layers"])]
    return params


def _attention_params(spec: dict) -> int:
    """One MLA block's matrices."""
    h, heads = spec["hidden_size"], spec["num_attention_heads"]
    nope, rope, vd = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                      spec["v_head_dim"])
    ql, kvl = spec["q_lora_rank"], spec["kv_lora_rank"]
    return (h * ql + ql * heads * (nope + rope) + h * (kvl + rope)
            + kvl * heads * (nope + vd) + heads * vd * h)


def _expert_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["expert_ffn_hidden_size"]


def _layer_outside_experts(spec: dict) -> int:
    """A double layer's matrices outside its routed experts: two attention
    blocks, two dense feed-forwards, the router."""
    h = spec["hidden_size"]
    return (2 * _attention_params(spec) + 2 * 3 * h * spec["ffn_hidden_size"]
            + h * spec["router_outputs"])


def _outside_experts(spec: dict) -> int:
    """Matrix parameters held here outside the routed experts: the above
    for every layer, token table and head."""
    return spec["num_layers"] * _layer_outside_experts(spec) \
        + 2 * spec["vocab_size"] * spec["hidden_size"]


def matrix_params(spec: dict) -> int:
    return _outside_experts(spec) + spec["num_layers"] \
        * spec["held_experts"][1] * _expert_params(spec)


def param_bytes(spec: dict) -> int:
    """The matrices in the served dtype; in float32 the norm scales (four of
    ``hidden`` and two of each LoRA rank a layer, and the final one) and the
    router's bias."""
    vectors = spec["num_layers"] * (
        4 * spec["hidden_size"] + 2 * (spec["q_lora_rank"]
                                       + spec["kv_lora_rank"])
        + spec["router_outputs"]) + spec["hidden_size"]
    return matrix_params(spec) * BYTES_OF[spec["dtype"]] + vectors * 4


# ------------------------------------------------------------- reference
def lora_scales(spec: dict) -> tuple:
    """(s_q, s_kv): ``sqrt(hidden / rank)`` where the config's flag is set."""
    h = spec["hidden_size"]
    return (math.sqrt(h / spec["q_lora_rank"])
            if spec["mla_scale_q_lora"] else 1.0,
            math.sqrt(h / spec["kv_lora_rank"])
            if spec["mla_scale_kv_lora"] else 1.0)


@functools.lru_cache(maxsize=4)
def _programs(shape: tuple, mode: str):
    """``shape`` = (heads, nope, rope, kv_lora, eps, s_q, s_kv, routed
    outputs, experts a token, gate scale, first held expert)."""
    import jax
    import jax.numpy as jnp

    (heads, nope, rope_d, kvl, eps, s_q, s_kv, n_routed, top_k, g_scale,
     first) = shape
    s_scale = (nope + rope_d) ** -0.5
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def mm(x, w, spec="ti,io->to"):
        w = w.astype(f32)
        if mode == "fp8":
            x, w = fp8(x), fp8(w)
        return jnp.einsum(spec, x, w, precision=hi)

    def rms(p, x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"].astype(f32)

    def rope(x, cos, sin):  # x (..., d); cos, sin broadcast to (..., d/2)
        d2 = x.shape[-1] // 2
        x1, x2 = x[..., :d2], x[..., d2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def swiglu(p, x):
        return mm(jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])

    def latent(blk, hid, cos, sin):
        """(c_q, c_kv scaled, k_r rotated) of rows hid."""
        x = rms(blk["attn_norm"], hid)
        c_q = rms(blk["q_a_norm"], mm(x, blk["q_a"]["w"]))
        kv = mm(x, blk["kv_a"]["w"])
        return (c_q, rms(blk["kv_a_norm"], kv[:, :kvl]) * s_kv,
                rope(kv[:, kvl:], cos, sin))  # one head, never scaled

    def by_group(blk, hid):
        """The block's per-head matrices, HEAD_GROUP heads at a time."""
        n = max(1, heads // HEAD_GROUP)
        g = heads // n
        split = lambda w, axis: jnp.moveaxis(  # noqa: E731
            w.reshape(*w.shape[:axis], n, g, *w.shape[axis + 1:]), axis, 0)
        ql = blk["q_b"]["w"].shape[0]
        return g, (split(blk["q_b"]["w"].reshape(ql, heads, -1), 1),
                   split(blk["kv_b_k"], 1), split(blk["kv_b_v"], 1),
                   blk["o"]["w"].reshape(n, -1, hid.shape[1]))

    @jax.jit
    def attention(blk, hid, cos, sin):
        t = hid.shape[0]
        c_q, c_kv, k_pe = latent(blk, hid, cos, sin)
        keys = jnp.arange(t)
        g, weights = by_group(blk, hid)

        def group(w):
            wq, wk, wv, wo = w
            q = mm(c_q, wq, "tq,qhd->thd") * s_q
            q_pe = rope(q[..., nope:], cos[:, None], sin[:, None])
            k_nope = mm(c_kv, wk, "tc,chn->thn")
            v = mm(c_kv, wv, "tc,chv->thv")

            def block(args):  # one block of queries against every key
                qn, qp, at = args
                s = (jnp.einsum("qhn,khn->hqk", qn, k_nope, precision=hi)
                     + jnp.einsum("qhr,kr->hqk", qp, k_pe, precision=hi)) \
                    * s_scale
                seen = keys[None, :] <= (at + jnp.arange(QUERY_BLOCK))[:, None]
                p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), -1)
                return jnp.einsum("hqk,khv->qhv", p, v, precision=hi)

            o = jax.lax.map(block, (
                q[..., :nope].reshape(-1, QUERY_BLOCK, g, nope),
                q_pe.reshape(-1, QUERY_BLOCK, g, rope_d),
                jnp.arange(0, t, QUERY_BLOCK)))
            return mm(o.reshape(t, -1), wo)

        return hid + jax.lax.map(group, weights).sum(0), c_kv, k_pe

    @jax.jit
    def attention_rows(blk, hid, at, cos, sin, c_kv_all, k_pe_all):
        """:func:`attention` for SOME rows of the sequence, each perhaps
        changed upstream: row i stands at position ``at[i]`` and sees the
        sequence's own latent rows before it (``c_kv_all``, ``k_pe_all``,
        what :func:`attention` returned for this block) and itself."""
        c_q, c_kv, k_pe = latent(blk, hid, cos, sin)
        keys = jnp.arange(c_kv_all.shape[0])
        g, weights = by_group(blk, hid)

        def group(w):
            wq, wk, wv, wo = w
            q = mm(c_q, wq, "tq,qhd->thd") * s_q
            q_pe = rope(q[..., nope:], cos[:, None], sin[:, None])
            k_nope = mm(c_kv_all, wk, "tc,chn->thn")
            v = mm(c_kv_all, wv, "tc,chv->thv")
            k_own = mm(c_kv, wk, "tc,chn->thn")
            v_own = mm(c_kv, wv, "tc,chv->thv")

            def block(args):  # a block of rows: the keys before, and itself
                qn, qp, pos, kn, kp, vo = args
                s = (jnp.einsum("qhn,khn->hqk", qn, k_nope, precision=hi)
                     + jnp.einsum("qhr,kr->hqk", qp, k_pe_all, precision=hi)) \
                    * s_scale
                s = jnp.where((keys[None, :] < pos[:, None])[None], s, -1e30)
                own = (jnp.einsum("qhn,qhn->hq", qn, kn, precision=hi)
                       + jnp.einsum("qhr,qr->hq", qp, kp, precision=hi)) \
                    * s_scale
                p = jax.nn.softmax(
                    jnp.concatenate([s, own[..., None]], -1), -1)
                return jnp.einsum("hqk,khv->qhv", p[..., :-1], v,
                                  precision=hi) \
                    + jnp.moveaxis(p[..., -1], 0, 1)[..., None] * vo

            blocks = lambda a: a.reshape(-1, QUERY_BLOCK, *a.shape[1:])  # noqa: E731,E501
            o = jax.lax.map(block, (
                blocks(q[..., :nope]), blocks(q_pe), blocks(at),
                blocks(k_own), blocks(k_pe), blocks(v_own)))
            return mm(o.reshape(hid.shape[0], -1), wo)

        return hid + jax.lax.map(group, weights).sum(0)

    @jax.jit
    def dense_ff(norm, mlp, hid):
        """hid + the dense SwiGLU of its norm, a slab of the feed-forward's
        width at a time: a slab of gate, of up and of down is cast and
        used, then the next."""
        x = rms(norm, hid)
        width = mlp["gate"].shape[1]
        n = max(1, width // FF_SLAB)
        slabs = {"gate": jnp.moveaxis(mlp["gate"].reshape(-1, n, width // n),
                                      1, 0),
                 "up": jnp.moveaxis(mlp["up"].reshape(-1, n, width // n),
                                    1, 0),
                 "down": mlp["down"].reshape(n, width // n, -1)}

        def one(acc, slab):
            return acc + swiglu(slab, x), None

        return jax.lax.scan(one, hid, slabs)[0]

    def scores(layer, x):
        # the router, in float32 in every mode (its stated precision)
        return jax.nn.softmax(jnp.einsum(
            "th,he->te", x, layer["router"].astype(f32), precision=hi), -1)

    def branch(layer, x, held_gates, zero_gate):
        """``m`` = the held experts under ``held_gates`` (rows, held) + the
        zero experts' summed gate (rows,) times the input."""
        def one(acc, args):  # a held expert, for the rows routed to it
            g, w = args
            return acc + g[:, None] * swiglu(w, x), None

        routed, _ = jax.lax.scan(one, jnp.zeros_like(x),
                                 (held_gates.T, layer["experts"]))
        return routed + zero_gate[:, None] * x

    @jax.jit
    def expert_m(layer, hid):
        """``m`` of rows hid (= a0) under the reference's own routing."""
        x = rms(layer["mlp_norm"][0], hid)
        p = scores(layer, x)
        _, ids = jax.lax.top_k(p + layer["router_bias"].astype(f32), top_k)
        gates = jnp.take_along_axis(p, ids, -1) * g_scale
        held = layer["experts"]["gate"].shape[0]
        on_held = ids[..., None] == first + jnp.arange(held)
        return branch(
            layer, x, jnp.sum(jnp.where(on_held, gates[..., None], 0.0), 1),
            jnp.sum(jnp.where(ids >= n_routed, gates, 0.0), -1))

    @jax.jit
    def row_scores(layer, hid):
        return scores(layer, rms(layer["mlp_norm"][0], hid))

    @jax.jit
    def row_m(layer, hid, held_gates, zero_gate):
        return branch(layer, rms(layer["mlp_norm"][0], hid), held_gates,
                      zero_gate)

    @jax.jit
    def head(final_norm, w, hid_rows):
        return mm(rms(final_norm, hid_rows), w["w"])

    return (attention, dense_ff, expert_m, head, attention_rows, row_scores,
            row_m)


def _shape_of(spec: dict) -> tuple:
    return (spec["num_attention_heads"], spec["qk_nope_head_dim"],
            spec["qk_rope_head_dim"], spec["kv_lora_rank"],
            float(spec["rms_norm_eps"]), *lora_scales(spec),
            spec["router_outputs"] - spec["zero_expert_num"],
            spec["moe_topk"], float(spec["routed_scaling_factor"]),
            spec["held_experts"][0])


def _rotary(spec: dict, positions):
    d = spec["qk_rope_head_dim"]
    inv = 1.0 / float(spec["rope_theta"]) ** (
        np.arange(0, d, 2, dtype=np.float64) / d)
    angles = np.outer(np.asarray(positions, np.float64), inv)
    return (np.cos(angles).astype(np.float32),
            np.sin(angles).astype(np.float32))


def _forward(spec: dict, params: dict, ids: list[int], mode: str,
             pad_to: int = 0):
    """ONE sequence through every layer with no cache, padded on the right
    (causal, so the padding is never seen) to a multiple of the query block
    or to ``pad_to``.  Returns the last hidden rows and, for each layer, its
    two blocks' latent rows ``[(c_kv, k_pe), (c_kv, k_pe)]``."""
    import jax.numpy as jnp

    attention, dense_ff, expert_m = _programs(_shape_of(spec), mode)[:3]
    t = max(len(ids), pad_to)
    t += -t % QUERY_BLOCK
    padded = np.zeros((t,), np.int32)
    padded[:len(ids)] = ids
    cos, sin = _rotary(spec, np.arange(t))
    hid = params["tok_emb"][padded].astype(jnp.float32)
    latents = []
    for layer in params["blocks"]:
        a0, c0, k0 = attention(layer["attn"][0], hid, cos, sin)
        m = expert_m(layer, a0)
        b0 = dense_ff(layer["mlp_norm"][0], layer["mlp"][0], a0)
        a1, c1, k1 = attention(layer["attn"][1], b0, cos, sin)
        hid = dense_ff(layer["mlp_norm"][1], layer["mlp"][1], a1) + m
        latents.append([(c0, k0), (c1, k1)])
    return hid, latents


def reference_logits(spec: dict, params: dict, ids: list[int], rows,
                     mode: str = "highest", pad_to: int = 0) -> np.ndarray:
    """(len(rows), vocab) float32 logits at positions ``rows`` of ONE
    sequence (:func:`_forward`)."""
    head = _programs(_shape_of(spec), mode)[3]
    hid = _forward(spec, params, ids, mode, pad_to)[0]
    return np.asarray(head(params["final_norm"], params["lm_head"],
                           hid[np.asarray(rows, np.int32)]))


# A routed branch is discontinuous where scores tie: the served program reads
# the router's input in bfloat16 and may then keep another expert than the
# float32 reference, rightly.  So the reference answers for EVERY routing
# that its own scores allow once each log-score may move by half of
# ROUTE_TIE, in units of the row's spread of log-scores over all outputs
# (``bench/models/deepseek_v2.py`` has the argument and the measurement
# behind 2^-3).  This router has ONE edge, the ``moe_topk``-th against the
# next, and it lies in score + bias: output i kept over output j needs u >= 1
# with ``p_i u + b_i = p_j / u + b_j`` (both log-scores moved by ln u), which
# without a bias is DeepSeek-V2's ``ln p_j - ln p_i``.
ROUTE_TIE = 2.0 ** -3
# at most this many readings of one row, the likeliest first
ROW_READINGS = 16
_EDGE = 3   # outputs looked at on each side of the edge


def _need(p: np.ndarray, b: np.ndarray, kept: int, dropped: int) -> float:
    """How far apart two log-scores have to move (the kept one up, the
    dropped one down, by half each) before ``kept`` stands over ``dropped``
    in score + bias; negative where it does already."""
    d = b[kept] - b[dropped]
    u = (-d + math.sqrt(d * d + 4.0 * p[kept] * p[dropped])) / (2.0 * p[kept])
    return 2.0 * math.log(max(u, 1e-300))


def _tops(p: np.ndarray, b: np.ndarray, m: int, tie: float) -> list[tuple]:
    """(how far scores have to move, indices) of every set of ``m`` outputs
    that is the top ``m`` of ``p + b`` once each log-score may move by ``tie
    / 2``: the plain top ``m`` first, then by need.  Of the kept ones only
    the last ``_EDGE`` may go, of the dropped ones only the first ``_EDGE``
    may come."""
    order = np.argsort(-(p + b), kind="stable")
    top, rest = order[:m], order[m:]
    if not len(rest):
        return [(-np.inf, top)]
    out_able = [i for i in top[-_EDGE:] if _need(p, b, rest[0], i) <= tie]
    in_able = [j for j in rest[:_EDGE] if _need(p, b, j, top[-1]) <= tie]
    sure = [i for i in top if i not in out_able]
    edge = out_able + in_able
    sets = []
    for kept in itertools.combinations(edge, len(out_able)):
        dropped = [j for j in edge if j not in kept]
        need = max((_need(p, b, i, j) for i in kept for j in dropped),
                   default=-np.inf)
        if need <= tie:
            sets.append((need, np.asarray(sure + list(kept), np.int64)))
    return sorted(sets, key=lambda s: s[0])


def gate_choices(spec: dict, p: np.ndarray,
                 bias: np.ndarray) -> list[list[np.ndarray]]:
    """For each row of router scores ``p`` (rows, outputs): ``[the gates of
    the HELD experts (held,) | the zero experts' summed gate]`` under the
    reference's routing, then under every other routing within ROUTE_TIE
    (:func:`_tops`) that differs on a held or a zero expert, the likeliest
    first.  Plain numpy: the same top-k of score + bias as ``expert_m``,
    written again."""
    k, scale = spec["moe_topk"], spec["routed_scaling_factor"]
    first, held = spec["held_experts"]
    n_routed = spec["router_outputs"] - spec["zero_expert_num"]
    p64, b64 = p.astype(np.float64), np.asarray(bias, np.float64)
    tie = ROUTE_TIE * np.log(np.maximum(p64, 1e-300)).std(axis=1)
    out = []
    for r in range(p.shape[0]):
        choices = []
        for _, ids in _tops(p64[r], b64, k, tie[r]):
            gates = np.zeros(held + 1, np.float32)
            here = (ids >= first) & (ids < first + held)
            gates[ids[here] - first] = scale * p[r, ids[here]]
            gates[held] = scale * p[r, ids[ids >= n_routed]].sum()
            if not any(np.array_equal(gates, c) for c in choices):
                choices.append(gates)
        out.append(choices)
    return out


def _row_readings(spec: dict, params: dict, ids, latents, positions):
    """Logits of the rows at ``positions`` of the sequence ``ids``, whose
    layers left ``latents`` (:func:`_forward`), under every routing that
    :func:`gate_choices` allows them, layer after layer (a row that took
    another expert in one layer goes on from there, against the sequence's
    own latent rows: what one row's other routing does to LATER rows
    through their attention is left out, as for DeepSeek-V2).  The branch
    joins the stream at the END of its layer, so a row's readings part
    there: attention blocks and dense feed-forwards run once a reading that
    came in.  Returns (logits (readings, vocab), the row of ``positions``
    each reading is of); a row's first reading is the reference's own."""
    _, dense_ff, _, head, attention_rows, row_scores, row_m = \
        _programs(_shape_of(spec), "highest")
    held = spec["held_experts"][1]
    of = np.arange(len(positions))
    at = np.asarray(positions, np.int32)
    hid = np.asarray(params["tok_emb"][np.asarray(ids, np.int32)[at]],
                     np.float32)

    def slabs(fn, *rows):
        """``fn`` over ROW_SLAB rows at a time, the last slab padded on the
        host: one shape a function, small temporaries."""
        out = []
        for lo in range(0, len(rows[0]), ROW_SLAB):
            part = [a[lo:lo + ROW_SLAB] for a in rows]
            n = len(part[0])
            part = [np.concatenate([a, np.repeat(a[:1], ROW_SLAB - n, 0)])
                    for a in part]
            out.append(np.asarray(fn(*part))[:n])
        return np.concatenate(out)

    for layer, (lat0, lat1) in zip(params["blocks"], latents):
        cos, sin = _rotary(spec, at[of])
        a0 = slabs(lambda h, p, c, s: attention_rows(
            layer["attn"][0], h, p, c, s, *lat0), hid, at[of], cos, sin)
        scores = slabs(lambda h: row_scores(layer, h), a0)
        b0 = slabs(lambda h: dense_ff(layer["mlp_norm"][0], layer["mlp"][0],
                                      h), a0)
        a1 = slabs(lambda h, p, c, s: attention_rows(
            layer["attn"][1], h, p, c, s, *lat1), b0, at[of], cos, sin)
        b1 = slabs(lambda h: dense_ff(layer["mlp_norm"][1], layer["mlp"][1],
                                      h), a1)
        readings = np.bincount(of, minlength=len(at))
        parent, gates = [], []
        bias = np.asarray(layer["router_bias"])
        for i, options in enumerate(gate_choices(spec, scores, bias)):
            room = ROW_READINGS - readings[of[i]]
            options = options[:1 + max(0, min(len(options) - 1, room))]
            readings[of[i]] += len(options) - 1
            parent += [i] * len(options)
            gates += options
        gates = np.stack(gates)
        hid = b1[parent] + slabs(
            lambda h, g, z: row_m(layer, h, g, z[:, 0]), a0[parent],
            gates[:, :held], gates[:, held:])
        of = of[parent]
    logits = slabs(lambda h: head(params["final_norm"], params["lm_head"], h),
                   hid)
    return logits, of


def greedy_gaps(spec: dict, params: dict, sequences: list, control: bool):
    """For each ``(prompt ids, produced ids)``: at every produced position
    the reference's best logit minus the reference's logit of the token that
    was served (0 wherever the served token is the reference's argmax);
    where the reference's routing of that row stands on an edge
    (:data:`ROUTE_TIE`), the least such gap over the routings the edge
    allows, each computed by the reference alone.  With ``control`` also the
    same gap for the token that the fp8 forward of the same prompt and
    tokens puts first.  Returns (gaps, control gaps), one array a
    sequence."""
    pad_to = max(len(p) + len(o) for p, o in sequences)
    gaps, low = [], []

    def least(logits, of, tokens):
        gap = logits.max(axis=1) - logits[np.arange(len(of)), tokens[of]]
        out = np.full(len(tokens), np.inf, np.float32)
        np.minimum.at(out, of, gap)
        return out

    for prompt, out in sequences:
        ids = list(prompt) + list(out[:-1])
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        latents = _forward(spec, params, ids, "highest", pad_to)[1]
        logits, of = _row_readings(spec, params, ids, latents, rows)
        served = np.clip(np.asarray(out, np.int64), 0, logits.shape[1] - 1)
        gaps.append(least(logits, of, served))
        if control:
            first = reference_logits(spec, params, ids, rows, mode="fp8",
                                     pad_to=pad_to).argmax(axis=1)
            low.append(least(logits, of, first))
    return gaps, low


# ------------------------------------------------------------------ work
def _held_share(spec: dict) -> float:
    """Routed experts a token meets HERE, a layer: ``top-k x held / router
    outputs`` (0.125 of the 12 for 8 of 768), the expectation under even
    routing; what a run really routed here is ``routed_here_share``.  A
    zero expert costs nothing anywhere."""
    return spec["moe_topk"] * spec["held_experts"][1] / spec["router_outputs"]


def matmul_params_per_token(spec: dict) -> float:
    """Parameters a token multiplies against on this chip's share, outside
    the head: both attention blocks, both dense feed-forwards and the
    router of every layer, the routed experts at the expected number met
    here."""
    return spec["num_layers"] * (
        _layer_outside_experts(spec)
        + _held_share(spec) * _expert_params(spec))


def latent_bytes_per_token(spec: dict) -> int:
    """Two latent rows a layer (one an attention block)."""
    return 2 * spec["num_layers"] * (
        spec["kv_lora_rank"] + spec["qk_rope_head_dim"]) \
        * BYTES_OF[spec["dtype"]]


def _span_tokens(prefill_spans, decode_spans) -> float:
    return sum(share * (hi - lo) for share, lo, hi in prefill_spans) \
        + sum(hi - lo for lo, hi in decode_spans)


def _flops(spec: dict, prefill_spans, decode_spans, sampled: float) -> float:
    """2 FLOPs a parameter a token (above), attention over the context the
    token sees in the absorbed form (every head of each of a layer's two
    blocks scores one cached row of ``kv_lora + rope`` and sums its
    ``kv_lora``: 2 x heads x (576 + 512) a cached row a block), and one row
    of the head over the held vocabulary for each token that was sampled."""
    per_tok = 2.0 * matmul_params_per_token(spec)
    attn = 2.0 * 2 * spec["num_layers"] * spec["num_attention_heads"] * (
        2 * spec["kv_lora_rank"] + spec["qk_rope_head_dim"])
    flops = 0.0
    for share, lo, hi in prefill_spans:  # positions lo..hi-1, a share of it
        n = hi - lo
        flops += share * (per_tok * n + attn * (n * (lo + hi + 1) / 2.0))
    for lo, hi in decode_spans:          # positions lo..hi-1, one a step
        n = hi - lo
        flops += per_tok * n + attn * (n * (lo + hi + 1) / 2.0)
    return flops + 2.0 * spec["vocab_size"] * spec["hidden_size"] * sampled


def gen_tokens(config: dict, prefill_spans, decode_spans, sampled) -> dict:
    """The tokens prefilled and decoded, whatever implements them: their
    FLOPs on this chip's share, and as bytes the held weights once (any
    number of tokens can share one read).  For ``step_mfu``: it cannot pass
    100 %."""
    spec = config["generator"]
    flops = _flops(spec, prefill_spans, decode_spans, sampled)
    return {"flops": flops,
            "bytes": float(param_bytes(spec)) if flops else 0.0}


def fused_steps(config: dict, executions: int, prefill_spans, decode_spans,
                sampled) -> dict:
    """``executions`` runs of the fused step that carried these tokens
    between them.  Each run reads the weights outside the routed experts
    once, and of each layer's expert branch the held experts that at least
    one of its rows is routed to: with R rows a run (the tokens over the
    runs) and even routing, ``held x (1 - (1 - top-k / outputs)^R)`` of
    them; nothing for a zero expert.  Each decoded token reads the latent
    rows of its own context, two a layer.  The FLOPs are the tokens' own.
    For ``step_roofline``."""
    spec = config["generator"]
    flops = _flops(spec, prefill_spans, decode_spans, sampled)
    if not flops or not executions:
        return {"flops": 0.0, "bytes": 0.0}
    rows = _span_tokens(prefill_spans, decode_spans) / executions
    hit = spec["held_experts"][1] * (
        1.0 - (1.0 - spec["moe_topk"] / spec["router_outputs"]) ** rows)
    weights = (_outside_experts(spec) + spec["num_layers"] * hit
               * _expert_params(spec)) * BYTES_OF[spec["dtype"]]
    cached = sum((hi - lo) * (lo + hi + 1) / 2.0 for lo, hi in decode_spans)
    return {"flops": flops, "bytes": executions * weights
            + cached * latent_bytes_per_token(spec)}
