"""The forward pass of LongCat-Flash's language model as published
(huggingface.co/meituan-longcat/LongCat-Flash-Omni ``config.json`` and the
release's modeling code), plainly: float32, ``highest`` matmul precision,
ONE sequence, no cache, no batching, the EXPANDED attention (per-head
``k_nope`` and ``v`` made from ``c_kv``; nothing is absorbed), the experts
as a loop over a row's ``moe_topk`` choices.

A double layer, stream ``h``::

    a0 = h  + MLA_0(norm_in_0(h));   x0 = norm_post_0(a0)
    m  = MoE(x0)                     # the shortcut branch: not yet added
    b0 = a0 + FFN_0(x0)
    a1 = b0 + MLA_1(norm_in_1(b0))
    b1 = a1 + FFN_1(norm_post_1(a1))
    h' = b1 + m

MLA: ``c_q = norm(x W_qa)``, ``q = c_q W_qb * sqrt(hidden / q_lora_rank)``,
``[c | k_r] = x W_kva``, ``c_kv = norm(c) * sqrt(hidden / kv_lora_rank)``,
plain rotary embedding (theta from the config, no scaling) on ``q``'s rope
part and on the one shared ``k_r``, ``[k_nope | v] = c_kv W_kvb`` per
head, scores times ``(nope + rope)^-0.5``.  MoE: ``s = softmax(x0 W_r)`` over
routed + zero experts, the top-k of ``s + bias``, gates ``scaling * s``
(without the bias, not renormalised); a routed choice adds ``g E_i(x0)``, a
zero (identity) choice adds ``g x0``.

It reads the parameter tree of ``models/longcat_flash.py`` and takes from
the config only numbers; it shares no code with that module or with
``models/mla.py``.  ``held`` = ``(first, count)`` says which routed experts
are present (the tree's ``experts`` stack holds exactly those): the router
still scores every output and keeps its top-k, the zero experts are all
here, and what the absent routed experts would add is left out, as one
expert-parallel rank leaves it out.

Departures from the checkpoint, none of them mathematical: rotary embedding
over half-pairs ``(i, i + d/2)`` instead of interleaved pairs ``(2i, 2i +
1)`` (a column permutation of ``W_qb`` and ``W_kva``); ``W_kvb`` kept as its
column blocks; an expert's three matrices stacked over the held experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


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


def route(cfg, router, bias, x):
    """x (T, hidden) f32 -> (ids (T, k), gates (T, k)): softmax over every
    router output, the ``moe_topk`` best of score + bias, gates
    ``routed_scaling_factor`` times the scores themselves."""
    s = jax.nn.softmax(jnp.einsum("th,he->te", x, router.astype(jnp.float32),
                                  precision=_HI), -1)
    ids = jnp.argsort(-(s + bias.astype(jnp.float32)), axis=-1,
                      stable=True)[:, :cfg.moe_topk]
    return ids, jnp.take_along_axis(s, ids, axis=-1) \
        * cfg.routed_scaling_factor


def expert_branch(cfg, layer, x, held, rounded=None):
    """``m`` for rows x: a row's choices one at a time; a choice on a routed
    expert that is present adds ``g E_i(x)``, one on a zero expert ``g x``,
    one on an absent routed expert nothing."""
    ids, gates = route(cfg, layer["router"], layer["router_bias"], x)
    first, count = held
    mats = {k: w.astype(jnp.float32) for k, w in layer["experts"].items()}
    if rounded is not None:
        mats = {k: rounded(w) for k, w in mats.items()}
        xr = rounded(x)
    else:
        xr = x
    out = jnp.zeros_like(x)
    for k in range(cfg.moe_topk):
        i, g = ids[:, k], gates[:, k]
        here = (i >= first) & (i < first + count)
        at = jnp.clip(i - first, 0, count - 1)  # each row's own expert
        act = jax.nn.silu(jnp.einsum("th,thw->tw", xr, mats["gate"][at],
                                     precision=_HI)) \
            * jnp.einsum("th,thw->tw", xr, mats["up"][at], precision=_HI)
        if rounded is not None:
            act = rounded(act)
        y = jnp.einsum("tw,twh->th", act, mats["down"][at], precision=_HI)
        out = out + jnp.where(here, g, 0.0)[:, None] * y \
            + jnp.where(i >= cfg.n_routed_experts, g, 0.0)[:, None] * x
    return out


def attention_block(cfg, blk, hid, cos, sin, rounded=None):
    """hid + one MLA block, in the expanded form."""
    t = hid.shape[0]
    heads, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    eps = cfg.rms_norm_eps
    s_q = (cfg.hidden_size / cfg.q_lora_rank) ** 0.5 \
        if cfg.mla_scale_q_lora else 1.0
    s_kv = (cfg.hidden_size / cfg.kv_lora_rank) ** 0.5 \
        if cfg.mla_scale_kv_lora else 1.0
    x = _rms(blk["attn_norm"], hid, eps)
    c_q = _rms(blk["q_a_norm"], _mm(x, blk["q_a"]["w"], rounded=rounded), eps)
    q = _mm(c_q, blk["q_b"]["w"], rounded=rounded).reshape(
        t, heads, nope + cfg.qk_rope_head_dim) * s_q
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], cos[:, None],
                                        sin[:, None])
    kv = _mm(x, blk["kv_a"]["w"], rounded=rounded)
    c_kv = _rms(blk["kv_a_norm"], kv[:, :cfg.kv_lora_rank], eps) * s_kv
    k_pe = _rope(kv[:, cfg.kv_lora_rank:], cos, sin)  # one head, unscaled
    k_nope = _mm(c_kv, blk["kv_b_k"], "tc,chn->thn", rounded)
    v = _mm(c_kv, blk["kv_b_v"], "tc,chv->thv", rounded)
    s = (jnp.einsum("qhn,khn->hqk", q_nope, k_nope, precision=_HI)
         + jnp.einsum("qhr,kr->hqk", q_pe, k_pe, precision=_HI)) \
        * (nope + cfg.qk_rope_head_dim) ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khv->qhv", p, v, precision=_HI).reshape(t, -1)
    return hid + _mm(o, blk["o"]["w"], rounded=rounded)


def double_layer(cfg, layer, hid, cos, sin, held, rounded=None):
    eps = cfg.rms_norm_eps
    a0 = attention_block(cfg, layer["attn"][0], hid, cos, sin, rounded)
    x0 = _rms(layer["mlp_norm"][0], a0, eps)
    m = expert_branch(cfg, layer, x0, held, rounded)
    b0 = a0 + _swiglu(layer["mlp"][0], x0, rounded)
    a1 = attention_block(cfg, layer["attn"][1], b0, cos, sin, rounded)
    b1 = a1 + _swiglu(layer["mlp"][1], _rms(layer["mlp_norm"][1], a1, eps),
                      rounded)
    return b1 + m


def rotary(cfg, t: int):
    """(t, rope/2) cos and sin: plain inverse frequencies, unscaled."""
    d = cfg.qk_rope_head_dim
    inv = 1.0 / float(cfg.rope_theta) ** (
        np.arange(0, d, 2, dtype=np.float64) / d)
    angles = np.outer(np.arange(t, dtype=np.float64), inv)
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32))


def forward(params, cfg, ids, rounded=None) -> jax.Array:
    """ids (T,) -> (T, vocab) float32 logits.  ``rounded``, if given, is
    applied to both operands of every weight matmul outside the router: a
    control that computes in a lower precision than the model states."""
    ids = jnp.asarray(ids, jnp.int32)
    cos, sin = rotary(cfg, ids.shape[0])
    hid = params["tok_emb"][ids].astype(jnp.float32)
    for layer in params["blocks"]:
        hid = double_layer(cfg, layer, hid, cos, sin, cfg.held_experts,
                           rounded)
    return _mm(_rms(params["final_norm"], hid, cfg.rms_norm_eps),
               params["lm_head"]["w"], rounded=rounded)
