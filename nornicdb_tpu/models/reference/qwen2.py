"""The Qwen2 forward pass as published (``modeling_qwen2.py`` of
huggingface.co/Qwen/Qwen2.5-0.5B-Instruct), plainly: float32, ``highest``
matmul precision, ONE sequence, no cache, no batching, every K/V head
repeated for the query heads of its group (nothing is contracted grouped).

It reads the parameter tree of ``models/qwen2.py`` (``init_params``) and
takes from the config only numbers; it shares no code with that module or
with ``models/layers.py`` (RoPE, RMSNorm and the attention are written again
here).  The benchmark carries a reference of its own (``bench/models/
qwen2.py``): the two stay independent of each other.  Rotary embedding is
over half-pairs ``(i, i + d/2)``, the checkpoint's own convention.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def _mm(x, p, rounded=None):
    """x (T, in) f32 times a ``{"w": (in, out)[, "b": (out,)]}`` leaf."""
    w = p["w"].astype(jnp.float32)
    if rounded is not None:
        x, w = rounded(x), rounded(w)
    y = jnp.einsum("ti,io->to", x, w, precision=_HI)
    return y + p["b"].astype(jnp.float32) if "b" in p else y


def _rms(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32)


def _rope(x, cos, sin):
    """x (T, heads, d); cos, sin (T, 1, d/2)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_layer(cfg, blk, hid, cos, sin, rounded=None):
    t = hid.shape[0]
    d = cfg.hidden // cfg.heads
    x = _rms(blk["attn_norm"], hid, cfg.rms_eps)
    q = _rope(_mm(x, blk["q"], rounded).reshape(t, cfg.heads, d), cos, sin)
    k = _rope(_mm(x, blk["k"], rounded).reshape(t, cfg.kv_heads, d), cos, sin)
    v = _mm(x, blk["v"], rounded).reshape(t, cfg.kv_heads, d)
    group = cfg.heads // cfg.kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) * d ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=_HI).reshape(t, -1)
    return hid + _mm(o, blk["o"], rounded)


def mlp_layer(cfg, blk, hid, rounded=None):
    x = _rms(blk["mlp_norm"], hid, cfg.rms_eps)
    return hid + _mm(jax.nn.silu(_mm(x, blk["gate"], rounded))
                     * _mm(x, blk["up"], rounded), blk["down"], rounded)


def forward(params, cfg, ids, rounded=None) -> jax.Array:
    """ids (T,) -> (T, vocab) float32 logits.  ``rounded``, if given, is
    applied to both operands of every weight matmul: a control that computes
    in a lower precision than the model states."""
    ids = jnp.asarray(ids, jnp.int32)
    d = cfg.hidden // cfg.heads
    inv_freq = float(cfg.rope_theta) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d)
    angles = np.outer(np.arange(ids.shape[0], dtype=np.float64), inv_freq)
    cos = jnp.asarray(np.cos(angles), jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[:, None]
    hid = params["tok_emb"][ids].astype(jnp.float32)
    for blk in params["blocks"]:
        hid = attention_layer(cfg, blk, hid, cos, sin, rounded)
        hid = mlp_layer(cfg, blk, hid, rounded)
    hid = _rms(params["final_norm"], hid, cfg.rms_eps)
    head = {"w": params["tok_emb"].T} if cfg.tie_embeddings \
        else params["lm_head"]
    return _mm(hid, head, rounded)
