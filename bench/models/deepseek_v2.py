"""Model family ``deepseek_v2``: a DeepSeek-V2 decoder (multi-head latent
attention, group-limited routed experts beside shared ones) behind the
assistant, served by genserve as ONE of the ranks that share each layer by
expert parallelism: the configuration says which routed experts are held
here, how many layers and which slice of the vocabulary.

The same parts as every family file (see ``bge_m3.py``, ``qwen2.py``).
Below ``install`` nothing imports the program or takes anything it made.

The reference is the decoder as published (``modeling_deepseek.py``):
pre-norm RMSNorm, ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` per head ``[nope
| rope]``, ``[c_kv | k_pe] = x W_kva``, ``c_kv = norm(c_kv)``, YaRN rotary
embedding on ``q_pe`` and the one shared ``k_pe``, per-head ``[k_nope | v] =
c_kv W_kvb`` (the EXPANDED form: no absorption), scores scaled by ``(nope +
rope)^-0.5 m^2``, causal softmax; a dense SwiGLU in the leading layers,
then ``softmax`` routing in float32 over all the published experts, the best
``topk_group`` groups by their best expert, the best ``num_experts_per_tok``
experts among them, gates ``routed_scaling_factor * p`` unnormalised, a loop
over the HELD experts (what the absent ones would add is left out, as in the
program), the shared experts for every row; untied head.  Float32 at
``highest`` matmul precision, one sequence at a time, one layer at a time,
attention in blocks of queries: no cache, no batching.  Departures from the
checkpoint: rotary half-pairs instead of interleaved pairs (a column
permutation of ``W_qb`` / ``W_kva``), ``W_kvb`` kept as its two column
blocks, the experts' matrices stacked.
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
# reference attention: queries a block.  128, not the 512 of qwen2.py: a
# block's scores are (block, 128 heads, T) float32, 370 MB at T = 5,632,
# and the reference runs beside the deployment's 12.5 GB
QUERY_BLOCK = 128
HEAD_GROUP = 32   # and heads a group


# ------------------------------------------------------- the program's side
def program_config(spec: dict):
    # the program's module FIRST: a commit that has none ends here, before
    # any weights are made
    from nornicdb_tpu.models import deepseek_v2

    fields = deepseek_v2.DeepSeekV2Config.__dataclass_fields__
    sizes = {k: v for k, v in spec.items() if k in fields}
    sizes.update({"rope_" + k: v for k, v in spec["rope_scaling"].items()
                  if "rope_" + k in fields},
                 num_hidden_layers=spec["num_layers"],
                 n_routed_experts=spec["router_outputs"],
                 held_experts=tuple(spec["held_experts"]))
    cfg = deepseek_v2.DeepSeekV2Config(**sizes)
    if spec["n_routed_experts"] != spec["held_experts"][1]:
        sys.exit("n_routed_experts states the experts held here: "
                 f"{spec['n_routed_experts']} != {spec['held_experts'][1]}")
    if spec.get("preset"):
        preset = getattr(deepseek_v2, spec["preset"])
        if cfg != preset:
            sys.exit(f"sizes differ from the serve preset {spec['preset']}: "
                     f"{cfg} != {preset}")
    return cfg


def install(db, app_cfg, spec: dict, params):
    """What ``db.heimdall`` wires for a weights-backed assistant of any
    decoder family: a generator that carries ``cfg`` / ``params`` /
    ``tokenizer`` / ``max_context``, handed to ``db.set_heimdall_generator``
    so that ``_wire_genserve`` builds the GenerationEngine (which resolves
    the family from the config's type) from the configuration's
    ``genserve.*`` options; then the engine's own warm-up of every program
    class, as ``cmd_serve`` calls it at boot."""
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
    time (the float32 draw of one layer's held experts is 1.9 GB at the
    published widths: it never stands beside the whole result).  Matrices
    are N(0, 1/fan_in), so scores and the residual stream are O(1) at every
    depth; norm scales are 1 + 0.1 N(0,1), so leaving one out shows; the
    router's rows are N(0, router_logit_std^2 / hidden): over unit-RMS rows
    the ``router_outputs`` scores spread by ``router_logit_std``, so the
    gates differ and a wrong expert shows."""
    import jax
    import jax.numpy as jnp

    h, heads = spec["hidden_size"], spec["num_attention_heads"]
    nope, rope, vd = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                      spec["v_head_dim"])
    ql, kvl = spec["q_lora_rank"], spec["kv_lora_rank"]
    im, held = spec["moe_intermediate_size"], spec["held_experts"][1]
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

    @functools.partial(jax.jit, static_argnames="dense_ff")
    def block(key, dense_ff: bool):
        k = jax.random.split(key, 14)
        blk = {
            "attn_norm": scale(k[0], h), "mlp_norm": scale(k[1], h),
            "q_a": {"w": mat(k[2], h, ql, std=h ** -0.5)},
            "q_a_norm": scale(k[3], ql),
            "q_b": {"w": mat(k[4], ql, heads * (nope + rope),
                             std=ql ** -0.5)},
            "kv_a": {"w": mat(k[5], h, kvl + rope, std=h ** -0.5)},
            "kv_a_norm": scale(k[6], kvl),
            "kv_b_k": mat(k[7], kvl, heads, nope, std=kvl ** -0.5),
            "kv_b_v": mat(k[8], kvl, heads, vd, std=kvl ** -0.5),
            "o": {"w": mat(k[9], heads * vd, h, std=(heads * vd) ** -0.5)}}
        if dense_ff:
            blk["mlp"] = mlp(k[10], spec["intermediate_size"])
        else:
            blk["router"] = mat(k[11], h, spec["router_outputs"],
                                std=spec["router_logit_std"] * h ** -0.5)
            blk["experts"] = mlp(k[12], im, lead=(held,))
            blk["shared"] = mlp(k[13], spec["n_shared_experts"] * im)
        return blk

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 3)
        return {"tok_emb": mat(k[0], spec["vocab_size"], h, std=0.02),
                "lm_head": {"w": mat(k[1], h, spec["vocab_size"],
                                     std=h ** -0.5)},
                "final_norm": scale(k[2], h)}

    keys = jax.random.split(jax_key(seed + 2), spec["num_layers"] + 1)
    params = ends(keys[0])
    params["blocks"] = [
        block(keys[1 + li], dense_ff=li < spec["first_k_dense_replace"])
        for li in range(spec["num_layers"])]
    return params


def _attention_params(spec: dict) -> int:
    h, heads = spec["hidden_size"], spec["num_attention_heads"]
    nope, rope, vd = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                      spec["v_head_dim"])
    ql, kvl = spec["q_lora_rank"], spec["kv_lora_rank"]
    return (h * ql + ql * heads * (nope + rope) + h * (kvl + rope)
            + kvl * heads * (nope + vd) + heads * vd * h)


def _expert_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["moe_intermediate_size"]


def _outside_experts(spec: dict) -> int:
    """Matrix parameters held here outside the routed experts: attention of
    every layer, the leading dense feed-forwards, routers, shared experts,
    token table and head."""
    h = spec["hidden_size"]
    dense_n = spec["first_k_dense_replace"]
    moe_n = spec["num_layers"] - dense_n
    return (spec["num_layers"] * _attention_params(spec)
            + dense_n * 3 * h * spec["intermediate_size"]
            + moe_n * (h * spec["router_outputs"]
                       + spec["n_shared_experts"] * _expert_params(spec))
            + 2 * spec["vocab_size"] * h)


def matrix_params(spec: dict) -> int:
    moe_n = spec["num_layers"] - spec["first_k_dense_replace"]
    return _outside_experts(spec) \
        + moe_n * spec["held_experts"][1] * _expert_params(spec)


def param_bytes(spec: dict) -> int:
    norms = spec["num_layers"] * (
        2 * spec["hidden_size"] + spec["q_lora_rank"]
        + spec["kv_lora_rank"]) + spec["hidden_size"]
    return matrix_params(spec) * BYTES_OF[spec["dtype"]] + norms * 4


# ------------------------------------------------------------- reference
def yarn(spec: dict):
    """(inv_freq (rope/2,), cos/sin scale, softmax scale) as published:
    each frequency blended between ``f`` and ``f / factor`` by the linear
    ramp over the correction range ``[floor(dim(beta_fast)),
    ceil(dim(beta_slow))]``, ``dim(r) = d ln(original / (2 pi r)) / (2 ln
    theta)``; cos and sin times ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``; scores times ``(nope + rope)^-0.5 mscale(factor,
    mscale_all_dim)^2``, ``mscale(s, m) = 0.1 m ln s + 1``."""
    r, d = spec["rope_scaling"], spec["qk_rope_head_dim"]
    base, factor = float(spec["rope_theta"]), float(r["factor"])
    plain = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_of(rotations):
        return d * math.log(r["original_max_position_embeddings"]
                            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(r["beta_fast"])), 0)
    high = min(math.ceil(dim_of(r["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 0.001), 0, 1)
    mscale = lambda m: 0.1 * m * math.log(factor) + 1.0  # noqa: E731
    return (plain / factor * ramp + plain * (1 - ramp),
            mscale(r["mscale"]) / mscale(r["mscale_all_dim"]),
            (spec["qk_nope_head_dim"] + d) ** -0.5
            * mscale(r["mscale_all_dim"]) ** 2)


@functools.lru_cache(maxsize=4)
def _programs(shape: tuple, mode: str):
    """``shape`` = (heads, nope, rope, kv_lora, eps, score scale, groups,
    groups kept, experts a token, gate scale, first held expert)."""
    import jax
    import jax.numpy as jnp

    (heads, nope, rope_d, kvl, eps, s_scale, n_group, topk_group, top_k,
     g_scale, first) = shape
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

    @jax.jit
    def attention(blk, hid, cos, sin):
        t = hid.shape[0]
        x = rms(blk["attn_norm"], hid)
        c_q = rms(blk["q_a_norm"], mm(x, blk["q_a"]["w"]))
        kv = mm(x, blk["kv_a"]["w"])
        c_kv = rms(blk["kv_a_norm"], kv[:, :kvl])
        k_pe = rope(kv[:, kvl:], cos, sin)  # one head, shared by all
        keys = jnp.arange(t)
        # the heads in groups of HEAD_GROUP, one group at a time: all 128
        # heads' q, k_nope, v and output at once are 1.7 GB in float32
        n = max(1, heads // HEAD_GROUP)
        g = heads // n

        def group(w):
            wq, wk, wv, wo = w
            q = mm(c_q, wq, "tq,qhd->thd")
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

        by_group = lambda w, axis: jnp.moveaxis(  # noqa: E731
            w.reshape(*w.shape[:axis], n, g, *w.shape[axis + 1:]), axis, 0)
        ql = blk["q_b"]["w"].shape[0]
        out = jax.lax.map(group, (
            by_group(blk["q_b"]["w"].reshape(ql, heads, -1), 1),
            by_group(blk["kv_b_k"], 1), by_group(blk["kv_b_v"], 1),
            blk["o"]["w"].reshape(n, -1, hid.shape[1])))
        return hid + out.sum(0), c_kv, k_pe

    @jax.jit
    def attention_rows(blk, hid, at, cos, sin, c_kv_all, k_pe_all):
        """:func:`attention` for SOME rows of the sequence, each perhaps
        changed upstream: row i stands at position ``at[i]`` and sees the
        sequence's own latent rows before it (``c_kv_all``, ``k_pe_all``,
        what :func:`attention` returned for this layer) and itself."""
        x = rms(blk["attn_norm"], hid)
        c_q = rms(blk["q_a_norm"], mm(x, blk["q_a"]["w"]))
        kv = mm(x, blk["kv_a"]["w"])
        c_kv = rms(blk["kv_a_norm"], kv[:, :kvl])
        k_pe = rope(kv[:, kvl:], cos, sin)
        keys = jnp.arange(c_kv_all.shape[0])
        n = max(1, heads // HEAD_GROUP)
        g = heads // n

        def group(w):
            wq, wk, wv, wo = w
            q = mm(c_q, wq, "tq,qhd->thd")
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

        by_group = lambda w, axis: jnp.moveaxis(  # noqa: E731
            w.reshape(*w.shape[:axis], n, g, *w.shape[axis + 1:]), axis, 0)
        ql = blk["q_b"]["w"].shape[0]
        out = jax.lax.map(group, (
            by_group(blk["q_b"]["w"].reshape(ql, heads, -1), 1),
            by_group(blk["kv_b_k"], 1), by_group(blk["kv_b_v"], 1),
            blk["o"]["w"].reshape(n, -1, hid.shape[1])))
        return hid + out.sum(0)

    @jax.jit
    def dense_ff(blk, hid):
        return hid + swiglu(blk["mlp"], rms(blk["mlp_norm"], hid))

    def scores(blk, x):
        # the router, in float32 in every mode (its stated precision)
        return jax.nn.softmax(jnp.einsum(
            "th,he->te", x, blk["router"].astype(f32), precision=hi), -1)

    def expert_sum(blk, hid, x, held_gates):
        """hid + the held experts under ``held_gates`` (rows, held) + the
        shared experts."""
        def one(acc, args):  # a held expert, for the rows routed to it
            g, w = args
            return acc + g[:, None] * swiglu(w, x), None

        routed, _ = jax.lax.scan(one, jnp.zeros_like(hid),
                                 (held_gates.T, blk["experts"]))
        return hid + routed + swiglu(blk["shared"], x)

    @jax.jit
    def expert_ff(blk, hid):
        x = rms(blk["mlp_norm"], hid)
        p = scores(blk, x)
        e = p.shape[1]
        best = p.reshape(-1, n_group, e // n_group).max(-1)
        _, groups = jax.lax.top_k(best, topk_group)
        kept = jnp.zeros_like(best, bool).at[
            jnp.arange(best.shape[0])[:, None], groups].set(True)
        gates, ids = jax.lax.top_k(
            jnp.where(jnp.repeat(kept, e // n_group, 1), p, 0.0), top_k)
        held = blk["experts"]["gate"].shape[0]
        on_held = ids[..., None] == first + jnp.arange(held)
        return expert_sum(blk, hid, x, jnp.sum(
            jnp.where(on_held, gates[..., None] * g_scale, 0.0), 1))

    @jax.jit
    def row_scores(blk, hid):
        return scores(blk, rms(blk["mlp_norm"], hid))

    @jax.jit
    def row_experts(blk, hid, held_gates):
        return expert_sum(blk, hid, rms(blk["mlp_norm"], hid), held_gates)

    @jax.jit
    def head(final_norm, w, hid_rows):
        return mm(rms(final_norm, hid_rows), w["w"])

    return (attention, dense_ff, expert_ff, head, attention_rows,
            row_scores, row_experts)


def _shape_of(spec: dict) -> tuple:
    return (spec["num_attention_heads"], spec["qk_nope_head_dim"],
            spec["qk_rope_head_dim"], spec["kv_lora_rank"],
            float(spec["rms_norm_eps"]), yarn(spec)[2], spec["n_group"],
            spec["topk_group"], spec["num_experts_per_tok"],
            float(spec["routed_scaling_factor"]), spec["held_experts"][0])


def _rotary(spec: dict, positions):
    inv, scale, _ = yarn(spec)
    angles = np.outer(np.asarray(positions, np.float64), inv)
    return ((np.cos(angles) * scale).astype(np.float32),
            (np.sin(angles) * scale).astype(np.float32))


def _forward(spec: dict, params: dict, ids: list[int], mode: str,
             pad_to: int = 0):
    """ONE sequence through every layer with no cache, padded on the right
    (causal, so the padding is never seen) to a multiple of the query block
    or to ``pad_to``.  Returns the last hidden rows and each layer's latent
    rows ``(c_kv, k_pe)``."""
    import jax.numpy as jnp

    attention, dense_ff, expert_ff = _programs(_shape_of(spec), mode)[:3]
    t = max(len(ids), pad_to)
    t += -t % QUERY_BLOCK
    padded = np.zeros((t,), np.int32)
    padded[:len(ids)] = ids
    cos, sin = _rotary(spec, np.arange(t))
    hid = params["tok_emb"][padded].astype(jnp.float32)
    latents = []
    for blk in params["blocks"]:
        hid, c_kv, k_pe = attention(blk, hid, cos, sin)
        latents.append((c_kv, k_pe))
        hid = dense_ff(blk, hid) if "mlp" in blk else expert_ff(blk, hid)
    return hid, latents


def reference_logits(spec: dict, params: dict, ids: list[int], rows,
                     mode: str = "highest", pad_to: int = 0) -> np.ndarray:
    """(len(rows), vocab) float32 logits at positions ``rows`` of ONE
    sequence (:func:`_forward`)."""
    head = _programs(_shape_of(spec), mode)[3]
    hid = _forward(spec, params, ids, mode, pad_to)[0]
    return np.asarray(head(params["final_norm"], params["lm_head"],
                           hid[np.asarray(rows, np.int32)]))


# A routed layer is discontinuous where scores tie: the served program reads
# the router's input in bfloat16 and may then keep another expert (or
# another group) than the float32 reference, rightly.  So the reference
# answers for EVERY routing that its own scores allow once each log-score
# may move by half of ROUTE_TIE, in units of the row's spread of log-scores
# over all experts (the router is linear in its input, so a relative error
# of the input moves each of its logits in proportion to that spread,
# whatever the router's scale).  2^-3: in the first expert layer of a
# 512-wide model on the CPU the bfloat16 program kept another expert than
# this reference at margins up to 0.053 (median 0.01) in 1,024 rows; the
# stream has taken more roundings by the last layer (PERF.md section 6).
ROUTE_TIE = 2.0 ** -3
# at most this many readings of one row, the likeliest first
ROW_READINGS = 16
_EDGE = 3   # scores looked at on each side of an edge


def _tops(logs: np.ndarray, m: int, tie: float) -> list[tuple]:
    """(how far scores have to move, indices) of every set of ``m`` entries
    that is the top ``m`` of ``logs`` once each may move by ``tie / 2``: the
    plain top ``m`` first (it needs less than nothing), then by need.  Of
    the kept ones only the last ``_EDGE`` may go, of the dropped ones only
    the first ``_EDGE`` may come."""
    order = np.argsort(-logs, kind="stable")
    top, rest = order[:m], order[m:]
    if not len(rest):
        return [(-np.inf, top)]
    out_able = [i for i in top[-_EDGE:] if logs[i] - logs[rest[0]] <= tie]
    in_able = [j for j in rest[:_EDGE] if logs[top[-1]] - logs[j] <= tie]
    sure = [i for i in top if i not in out_able]
    edge = out_able + in_able
    sets = []
    for kept in itertools.combinations(edge, len(out_able)):
        dropped = [j for j in edge if j not in kept]
        need = max((logs[j] for j in dropped), default=-np.inf) \
            - min((logs[i] for i in kept), default=np.inf)
        if need <= tie:
            sets.append((need, np.asarray(sure + list(kept), np.int64)))
    return sorted(sets, key=lambda s: s[0])


def held_gate_choices(spec: dict, p: np.ndarray) -> list[list[np.ndarray]]:
    """For each row of router scores ``p`` (rows, experts): the gates of the
    HELD experts (held,) under the reference's routing, then under every
    other routing within ROUTE_TIE (:func:`_tops`, for the groups kept and
    for the experts kept among them) that differs on the held experts, the
    likeliest first.  Plain numpy: the same group-limited greedy top-k as
    ``expert_ff``, written again."""
    e, n_group = p.shape[1], spec["n_group"]
    kg, k = spec["topk_group"], spec["num_experts_per_tok"]
    first, held = spec["held_experts"]
    per = e // n_group
    logp = np.log(np.maximum(p.astype(np.float64), 1e-300))
    tie = ROUTE_TIE * logp.std(axis=1)
    best = logp.reshape(-1, n_group, per).max(-1)
    out = []
    for r in range(p.shape[0]):
        routings = []
        for need_g, groups in _tops(best[r], kg, tie[r]):
            allowed = np.isin(np.arange(e) // per, groups)
            for need_e, ids in _tops(np.where(allowed, logp[r], -np.inf),
                                     k, tie[r]):
                routings.append((max(need_g, need_e), ids))
        choices = []
        for _, ids in sorted(routings, key=lambda x: x[0]):
            gates = np.zeros(held, np.float32)
            here = (ids >= first) & (ids < first + held)
            gates[ids[here] - first] = \
                spec["routed_scaling_factor"] * p[r, ids[here]]
            if not any(np.array_equal(gates, c) for c in choices):
                choices.append(gates)
        out.append(choices)
    return out


def _row_readings(spec: dict, params: dict, ids, latents, positions):
    """Logits of the rows at ``positions`` of the sequence ``ids``, whose
    layers left ``latents`` (:func:`_forward`), under every
    routing that :func:`held_gate_choices` allows them, layer after layer (a
    row that took another expert in one layer goes on from there, against
    the sequence's own latent rows: what one row's other routing does to
    LATER rows through their attention is a five-thousandth of it and is
    left out).  Returns (logits (readings, vocab), the row of ``positions``
    each reading is of); a row's first reading is the reference's own."""
    _, dense_ff, _, head, attention_rows, row_scores, row_experts = \
        _programs(_shape_of(spec), "highest")
    of = np.arange(len(positions))
    at = np.asarray(positions, np.int32)
    hid = np.asarray(params["tok_emb"][np.asarray(ids, np.int32)[at]],
                     np.float32)

    def padded(a):  # on the host, to a power of two of rows: few shapes
        n = max(QUERY_BLOCK, 1 << (len(a) - 1).bit_length())
        return np.concatenate([a, np.repeat(a[:1], n - len(a), 0)])

    for blk, (c_kv, k_pe) in zip(params["blocks"], latents):
        cos, sin = _rotary(spec, at[of])
        hid = attention_rows(blk, padded(hid), padded(at[of]), padded(cos),
                             padded(sin), c_kv, k_pe)
        if "mlp" in blk:
            hid = np.asarray(dense_ff(blk, hid))[:len(of)]
            continue
        scores = np.asarray(row_scores(blk, hid))[:len(of)]
        readings = np.bincount(of, minlength=len(at))
        parent, gates = [], []
        for i, options in enumerate(held_gate_choices(spec, scores)):
            room = ROW_READINGS - readings[of[i]]
            options = options[:1 + max(0, min(len(options) - 1, room))]
            readings[of[i]] += len(options) - 1
            parent += [i] * len(options)
            gates += options
        hid = np.asarray(row_experts(
            blk, padded(np.asarray(hid)[parent]),
            padded(np.stack(gates))))[:len(parent)]
        of = of[parent]
    logits = head(params["final_norm"], params["lm_head"], padded(hid))
    return np.asarray(logits)[:len(of)], of


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
    """Routed experts a token meets HERE, a layer: ``top-k x held /
    published`` (0.75 of the 6 for 20 of 160), the expectation under even
    routing; what a run really routed here is ``routed_here_share``."""
    return spec["num_experts_per_tok"] * spec["held_experts"][1] \
        / spec["router_outputs"]


def matmul_params_per_token(spec: dict) -> float:
    """Parameters a token multiplies against on this chip's share, outside
    the head: attention and feed-forward of every layer, the routed experts
    at the expected number met here."""
    h = spec["hidden_size"]
    dense_n = spec["first_k_dense_replace"]
    moe_n = spec["num_layers"] - dense_n
    return (spec["num_layers"] * _attention_params(spec)
            + dense_n * 3 * h * spec["intermediate_size"]
            + moe_n * (h * spec["router_outputs"]
                       + (spec["n_shared_experts"] + _held_share(spec))
                       * _expert_params(spec)))


def latent_bytes_per_token(spec: dict) -> int:
    return spec["num_layers"] * (
        spec["kv_lora_rank"] + spec["qk_rope_head_dim"]) \
        * BYTES_OF[spec["dtype"]]


def _span_tokens(prefill_spans, decode_spans) -> float:
    return sum(share * (hi - lo) for share, lo, hi in prefill_spans) \
        + sum(hi - lo for lo, hi in decode_spans)


def _flops(spec: dict, prefill_spans, decode_spans, sampled: float) -> float:
    """2 FLOPs a parameter a token (above), attention over the context the
    token sees in the absorbed form (every head scores one cached row of
    ``kv_lora + rope`` and sums its ``kv_lora``: 2 x heads x (576 + 512) a
    cached row a layer), and one row of the head over the held vocabulary
    for each token that was sampled."""
    per_tok = 2.0 * matmul_params_per_token(spec)
    attn = 2.0 * spec["num_layers"] * spec["num_attention_heads"] * (
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
    once, and of each expert layer the held experts that at least one of
    its rows is routed to: with R rows a run (the tokens over the runs) and
    even routing, ``held x (1 - (1 - top-k / published)^R)`` of them.  Each
    decoded token reads the latent rows of its own context.  The FLOPs are
    the tokens' own.  For ``step_roofline``."""
    spec = config["generator"]
    flops = _flops(spec, prefill_spans, decode_spans, sampled)
    if not flops or not executions:
        return {"flops": 0.0, "bytes": 0.0}
    rows = _span_tokens(prefill_spans, decode_spans) / executions
    held = spec["held_experts"][1]
    hit = held * (1.0 - (1.0 - spec["num_experts_per_tok"]
                         / spec["router_outputs"]) ** rows)
    moe_n = spec["num_layers"] - spec["first_k_dense_replace"]
    weights = (_outside_experts(spec) + moe_n * hit * _expert_params(spec)) \
        * BYTES_OF[spec["dtype"]]
    cached = sum((hi - lo) * (lo + hi + 1) / 2.0 for lo, hi in decode_spans)
    return {"flops": flops, "bytes": executions * weights
            + cached * latent_bytes_per_token(spec)}
