"""The forward pass of Command A+'s language model as published
(huggingface.co/CohereLabs/command-a-plus-05-2026 ``config.json``,
``model_type`` ``cohere2_moe``), plainly: float32, ``highest`` matmul
precision, ONE sequence, no cache, no batching, no kernels, every query
against every key under a mask, the experts as a loop over a row's
``num_experts_per_tok`` choices.

A layer, stream ``x`` (a parallel block; every layer is an expert layer)::

    h      = LN(x)                       (x - mean) / sqrt(var + eps) * g
    q,k,v  = h Wq, h Wk, h Wv            128 / 8 / 8 heads of 128
    sliding_attention: q, k rotated over interleaved pairs (2i, 2i + 1),
                       theta from the config, all of a head's dims; query i
                       sees keys j with i - sliding_window < j <= i
    full_attention:    no rotation; query i sees every j <= i
    a      = softmax(q k^T / sqrt(head_dim)) v Wo
    s      = sigmoid(h Wr); the top-k of s; g_i = s_i / sum of the chosen
    x'     = x + a + sum_i g_i E_i(h) + 1/n sum_j S_j(h)

``logits = LN_f(x) E^T * logit_scale`` over the tied table.

It reads the parameter tree of ``models/cohere2_moe.py`` and takes from the
config only numbers and ``layer_types``; it shares no code with that module
or with ``models/experts.py``.  ``cfg.held_experts = (first, count)`` says
which routed experts are present (the tree's ``experts`` stack holds exactly
those): the router still scores every output, keeps its top-k and
normalises the gates over all of the chosen; the shared experts are all
here; what the absent routed experts would add is left out, as one
expert-parallel rank leaves it out.

Read from the source where its config does not settle it: ``"average"`` =
the mean of the shared experts' outputs, added to the routed sum; no routed
scaling factor, no router bias; the window's edge ``i - j <
sliding_window``.  Departure from the checkpoint, not mathematical: an
expert's three matrices stacked over the held (or shared) experts.
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


def _ln(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)


def _rope(x, angles):
    """x (T, heads, d), angles (T, d/2): pair i is columns (2i, 2i + 1)."""
    x0, x1 = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def route(cfg, router, x):
    """x (T, hidden) f32 -> (ids (T, k), gates (T, k)): sigmoid over every
    router output, the ``num_experts_per_tok`` best, gates normalised over
    the chosen."""
    s = jax.nn.sigmoid(jnp.einsum("th,he->te", x, router.astype(jnp.float32),
                                  precision=_HI))
    ids = jnp.argsort(-s, axis=-1, stable=True)[:, :cfg.num_experts_per_tok]
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, chosen / chosen.sum(-1, keepdims=True)


def _expert(mats, at, x, rounded):
    """Each row through its own expert ``at`` (T,) of the stack ``mats``."""
    act = jax.nn.silu(jnp.einsum("th,thw->tw", x, mats["gate"][at],
                                 precision=_HI)) \
        * jnp.einsum("th,thw->tw", x, mats["up"][at], precision=_HI)
    if rounded is not None:
        act = rounded(act)
    return jnp.einsum("tw,twh->th", act, mats["down"][at], precision=_HI)


def expert_layer(cfg, blk, x, held, rounded=None):
    """``routed + shared`` for rows x: a row's choices one at a time (one on
    an absent expert adds nothing), then the shared experts one at a time,
    their mean."""
    ids, gates = route(cfg, blk["router"], x)
    first, count = held
    cast = lambda tree: {k: (w.astype(jnp.float32) if rounded is None  # noqa: E731,E501
                             else rounded(w.astype(jnp.float32)))
                         for k, w in tree.items()}
    mats, shared = cast(blk["experts"]), cast(blk["shared"])
    xr = x if rounded is None else rounded(x)
    out = jnp.zeros_like(x)
    for k in range(cfg.num_experts_per_tok):
        i, g = ids[:, k], gates[:, k]
        here = (i >= first) & (i < first + count)
        y = _expert(mats, jnp.clip(i - first, 0, count - 1), xr, rounded)
        out = out + jnp.where(here, g, 0.0)[:, None] * y
    every = jnp.zeros((x.shape[0],), jnp.int32)
    mean = sum(_expert(shared, every + j, xr, rounded)
               for j in range(cfg.num_shared_experts)) / cfg.num_shared_experts
    return out + mean


def attention(cfg, blk, h, kind, positions, rounded=None):
    """One layer's attention of normed rows h (T, hidden) -> (T, hidden)."""
    t = h.shape[0]
    heads, g, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    q = _mm(h, blk["q"]["w"], rounded=rounded).reshape(t, heads, d)
    k = _mm(h, blk["k"]["w"], rounded=rounded).reshape(t, g, d)
    v = _mm(h, blk["v"]["w"], rounded=rounded).reshape(t, g, d)
    i, j = positions[:, None], positions[None, :]
    seen = j <= i
    if kind == "sliding_attention":
        inv = 1.0 / float(cfg.rope_theta) ** (
            np.arange(0, d, 2, dtype=np.float64) / d)
        angles = jnp.asarray(np.outer(np.asarray(positions, np.float64), inv),
                             jnp.float32)
        q, k = _rope(q, angles), _rope(k, angles)
        seen = seen & (i - j < cfg.sliding_window)
    # head n attends K/V head n // (heads / kv heads)
    k, v = (jnp.repeat(a, heads // g, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=_HI).reshape(t, -1)
    return _mm(o, blk["o"]["w"], rounded=rounded)


def layer(cfg, blk, x, kind, positions, held, rounded=None):
    h = _ln(blk["norm"], x, cfg.layer_norm_eps)
    return x + attention(cfg, blk, h, kind, positions, rounded) \
        + expert_layer(cfg, blk, h, held, rounded)


def forward(params, cfg, ids, rounded=None, positions=None) -> jax.Array:
    """ids (T,) -> (T, vocab) float32 logits.  ``rounded``, if given, is
    applied to both operands of every weight matmul outside the router: a
    control that computes in a lower precision than the model states.
    ``positions`` (T,), increasing: where the tokens stand (0 .. T-1 unless
    given)."""
    ids = jnp.asarray(ids, jnp.int32)
    at = np.arange(ids.shape[0]) if positions is None \
        else np.asarray(positions)
    x = params["tok_emb"][ids].astype(jnp.float32)
    for blk, kind in zip(params["blocks"], cfg.layer_types, strict=True):
        x = layer(cfg, blk, x, kind, at, cfg.held_experts, rounded)
    return _mm(_ln(params["final_norm"], x, cfg.layer_norm_eps),
               params["tok_emb"], "th,vh->tv", rounded) * cfg.logit_scale
