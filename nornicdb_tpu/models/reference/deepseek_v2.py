"""The DeepSeek-V2 forward pass as published (``modeling_deepseek.py`` of
huggingface.co/deepseek-ai/DeepSeek-V2), plainly: float32, ``highest``
matmul precision, ONE sequence, no cache, no batching, the EXPANDED
attention (per-head ``k_nope`` and ``v`` made from ``c_kv``; nothing is
absorbed), a Python loop over the experts.

It reads the parameter tree of ``models/deepseek_v2.py`` and takes from the
config only numbers; it shares no code with that module (YaRN, the router
and the layers are written again here).  ``held`` = ``(first, count)`` says
which routed experts are present (the tree's ``experts`` stack holds exactly
those): the router still scores all ``n_routed_experts`` and keeps its
top-k, and what the absent experts would add is left out, as one
expert-parallel rank leaves it out.

Departures from the checkpoint, none of them mathematical: rotary embedding
over half-pairs ``(i, i + d/2)`` instead of the checkpoint's interleaved
pairs ``(2i, 2i + 1)`` (a column permutation of ``W_qb`` and ``W_kva``);
``W_kvb`` kept as its column blocks ``kv_b_k`` / ``kv_b_v``; an expert's
three matrices stacked over the held experts.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg) -> np.ndarray:
    d, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    freq_extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    freq_inter = freq_extra / cfg.rope_factor

    def correction_dim(rotations):
        return d * math.log(cfg.rope_original_max_position_embeddings
                            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def softmax_scale(cfg) -> float:
    m = _mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _mm(x, w, spec="ti,io->to", rounded=None):
    w = w.astype(jnp.float32)
    if rounded is not None:
        x, w = rounded(x), rounded(w)
    return jnp.einsum(spec, x, w, precision=_HI)


def _rms(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32)


def _rope(x, cos, sin):
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(p, x, rounded):
    return _mm(jax.nn.silu(_mm(x, p["gate"], rounded=rounded))
               * _mm(x, p["up"], rounded=rounded), p["down"],
               rounded=rounded)


def route(cfg, router, x):
    """x (T, hidden) f32 -> (expert ids (T, k), gates (T, k)): softmax over
    all experts, the ``topk_group`` groups with the best single expert, the
    ``num_experts_per_tok`` best experts inside them, gates ``p *
    routed_scaling_factor`` (``norm_topk_prob`` is false: not
    renormalised)."""
    p = jax.nn.softmax(jnp.einsum("th,he->te", x, router.astype(jnp.float32),
                                  precision=_HI), -1)
    t, e = p.shape
    per = e // cfg.n_group
    group_scores = p.reshape(t, cfg.n_group, per).max(-1)
    group_idx = jnp.argsort(-group_scores, axis=-1,
                            stable=True)[:, :cfg.topk_group]
    group_mask = jnp.zeros((t, cfg.n_group), bool).at[
        jnp.arange(t)[:, None], group_idx].set(True)
    masked = jnp.where(jnp.repeat(group_mask, per, axis=1), p, 0.0)
    ids = jnp.argsort(-masked, axis=-1,
                      stable=True)[:, :cfg.num_experts_per_tok]
    gates = jnp.take_along_axis(masked, ids, axis=-1)
    return ids, gates * cfg.routed_scaling_factor


def routed_part(cfg, blk, x, held, rounded=None):
    """What the experts ``held = (first, count)`` add for rows x."""
    ids, gates = route(cfg, blk["router"], x)
    first, count = held
    out = jnp.zeros_like(x)
    for j in range(count):  # a loop over the experts present
        expert = {k: w[j] for k, w in blk["experts"].items()}
        gate = jnp.sum(jnp.where(ids == first + j, gates, 0.0), -1)
        out = out + gate[:, None] * _swiglu(expert, x, rounded)
    return out


def expert_layer(cfg, blk, hid, held, rounded=None):
    """hid (T, hidden) -> hid + routed part of the held experts + shared."""
    x = _rms(blk["mlp_norm"], hid, cfg.rms_norm_eps)
    return hid + routed_part(cfg, blk, x, held, rounded) \
        + _swiglu(blk["shared"], x, rounded)


def attention_layer(cfg, blk, hid, cos, sin, rounded=None):
    t = hid.shape[0]
    heads, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    x = _rms(blk["attn_norm"], hid, cfg.rms_norm_eps)
    c_q = _rms(blk["q_a_norm"], _mm(x, blk["q_a"]["w"], rounded=rounded),
               cfg.rms_norm_eps)
    q = _mm(c_q, blk["q_b"]["w"], rounded=rounded).reshape(
        t, heads, nope + cfg.qk_rope_head_dim)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], cos[:, None],
                                        sin[:, None])
    kv = _mm(x, blk["kv_a"]["w"], rounded=rounded)
    c_kv = _rms(blk["kv_a_norm"], kv[:, :cfg.kv_lora_rank], cfg.rms_norm_eps)
    k_pe = _rope(kv[:, cfg.kv_lora_rank:], cos, sin)  # one head, shared
    k_nope = _mm(c_kv, blk["kv_b_k"], "tc,chn->thn", rounded)
    v = _mm(c_kv, blk["kv_b_v"], "tc,chv->thv", rounded)
    s = (jnp.einsum("qhn,khn->hqk", q_nope, k_nope, precision=_HI)
         + jnp.einsum("qhr,kr->hqk", q_pe, k_pe, precision=_HI)) \
        * softmax_scale(cfg)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khv->qhv", p, v, precision=_HI).reshape(t, -1)
    return hid + _mm(o, blk["o"]["w"], rounded=rounded)


def forward(params, cfg, ids, rounded=None) -> jax.Array:
    """ids (T,) -> (T, vocab) float32 logits.  ``rounded``, if given, is
    applied to both operands of every weight matmul outside the router: a
    control that computes in a lower precision than the model states."""
    ids = jnp.asarray(ids, jnp.int32)
    t = ids.shape[0]
    angles = np.outer(np.arange(t, dtype=np.float64), yarn_inv_freq(cfg))
    scale = _mscale(cfg.rope_factor, cfg.rope_mscale) \
        / _mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    cos = jnp.asarray(np.cos(angles) * scale, jnp.float32)
    sin = jnp.asarray(np.sin(angles) * scale, jnp.float32)
    hid = params["tok_emb"][ids].astype(jnp.float32)
    for blk in params["blocks"]:
        hid = attention_layer(cfg, blk, hid, cos, sin, rounded)
        if "mlp" in blk:
            hid = hid + _swiglu(blk["mlp"], _rms(blk["mlp_norm"], hid,
                                                 cfg.rms_norm_eps), rounded)
        else:
            hid = expert_layer(cfg, blk, hid, cfg.held_experts, rounded)
    return _mm(_rms(params["final_norm"], hid, cfg.rms_norm_eps),
               params["lm_head"]["w"], rounded=rounded)
