"""The forward pass of Nemotron 3 Nano's language model as published
(huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``,
``model_type`` ``nemotron_h``), plainly: float32, ``highest`` matmul
precision, ONE sequence, no cache, no state kept between calls, no batching,
no kernels; the Mamba-2 layer as the SEQUENTIAL recurrence, one token after
the other from a zero state; every query against every key under a mask;
the experts as a loop over a row's ``num_experts_per_tok`` choices.

A layer, stream ``x``, is ONE mixer by ``hybrid_override_pattern``::

    x' = x + mixer(RMSNorm(x))           w * x / sqrt(mean(x^2) + eps)

    M  [z | xBC | dt] = x W_in
       xBC_t = silu(sum_{j<K} w_j xBC_{t-K+1+j} + b)    inputs before the
                                                        sequence are zero
       [x' | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
       S_h <- exp(dt_h A_h) S_h + dt_h x'_h (x) B_g;  y_h = S_h C_g + D_h x'_h
       out = RMSNorm_grouped(y * silu(z)) W_out         groups of d_inner / G
    E  s = sigmoid(x W_r); the top-k of s + bias; g_i = s_i / sum of the
       chosen * routed_scaling_factor; sum_i g_i E_i(x) + S(x),
       E(x) = W_down relu(W_up x)^2
    *  softmax(q k^T / sqrt(head_dim)) v W_o, causal, no positions

``logits = RMSNorm_f(x) W_head``.

It reads the parameter tree of ``models/nemotron_h.py`` and takes from the
config only numbers and the pattern; it shares no code with that module,
with ``models/kv_walk.py`` or with ``models/experts.py``.
``cfg.held_experts = (first, count)`` says which routed experts are present
(the tree's ``experts`` stack holds exactly those): the router still scores
every output, keeps its top-k and normalises the gates over all of the
chosen; the shared expert is here; what the absent routed experts would add
is left out, as one expert-parallel rank leaves it out.

Read from the source where its config does not settle it: no position
embedding in attention; ``dt`` not clamped; the router has no groups.
Departure from the checkpoint, not mathematical: an expert's two matrices
stacked over the held (or shared) experts.
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


def _rms(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def mamba(cfg, blk, h, rounded=None):
    """One Mamba-2 mixer over normed rows h (T, hidden) of one sequence from
    a zero state -> (T, hidden)."""
    t = h.shape[0]
    heads, p, n, g = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                      cfg.ssm_state_size, cfg.n_groups)
    d_inner, k = heads * p, cfg.conv_kernel
    proj = _mm(h, blk["in_proj"]["w"], rounded=rounded)
    z, xbc, dt = (proj[:, :d_inner], proj[:, d_inner:-heads],
                  proj[:, -heads:])
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    w = blk["conv"]["w"].astype(jnp.float32)
    xbc = jax.nn.silu(sum(w[j] * padded[j:j + t] for j in range(k))
                      + blk["conv"]["b"])
    x = xbc[:, :d_inner].reshape(t, heads, p)
    # head h reads the B and C of group h // (heads / groups)
    b, c = (jnp.repeat(part.reshape(t, g, n), heads // g, axis=1)
            for part in (xbc[:, d_inner:d_inner + g * n],
                         xbc[:, d_inner + g * n:]))
    dt = jax.nn.softplus(dt + blk["dt_bias"])
    a = -jnp.exp(blk["A_log"])

    def token(s, row):
        x_t, b_t, c_t, dt_t = row
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t, precision=_HI)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n)), (x, b, c, dt))
    y = (y + blk["D"][:, None] * x).reshape(t, d_inner) * jax.nn.silu(z)
    y = _rms(jnp.ones(()), y.reshape(t, g, -1), cfg.norm_eps).reshape(
        t, d_inner) * blk["gate_norm"]["scale"]
    return _mm(y, blk["out_proj"]["w"], rounded=rounded)


def route(cfg, blk, x):
    """x (T, hidden) f32 -> (ids (T, k), gates (T, k))."""
    s = jax.nn.sigmoid(jnp.einsum("th,he->te", x,
                                  blk["router"].astype(jnp.float32),
                                  precision=_HI))
    ids = jnp.argsort(-(s + blk["router_bias"]), axis=-1,
                      stable=True)[:, :cfg.num_experts_per_tok]
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, chosen / chosen.sum(-1, keepdims=True) \
        * cfg.routed_scaling_factor


def _expert(mats, at, x, rounded):
    """Each row through its own expert ``at`` (T,) of the stack ``mats``."""
    act = jnp.square(jax.nn.relu(jnp.einsum("th,thw->tw", x, mats["up"][at],
                                            precision=_HI)))
    if rounded is not None:
        act = rounded(act)
    return jnp.einsum("tw,twh->th", act, mats["down"][at], precision=_HI)


def expert_layer(cfg, blk, x, held, rounded=None):
    """``routed + shared`` for rows x: a row's choices one at a time (one on
    an absent expert adds nothing), then the shared expert."""
    ids, gates = route(cfg, blk, x)
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
    return out + _expert(shared, jnp.zeros((x.shape[0],), jnp.int32), xr,
                         rounded)


def attention(cfg, blk, h, rounded=None):
    """One layer's attention of normed rows h (T, hidden) -> (T, hidden)."""
    t = h.shape[0]
    heads, g, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    q = _mm(h, blk["q"]["w"], rounded=rounded).reshape(t, heads, d)
    k = _mm(h, blk["k"]["w"], rounded=rounded).reshape(t, g, d)
    v = _mm(h, blk["v"]["w"], rounded=rounded).reshape(t, g, d)
    at = np.arange(t)
    # head n attends K/V head n // (heads / kv heads)
    k, v = (jnp.repeat(a, heads // g, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(at[None, :] <= at[:, None], s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=_HI).reshape(t, -1)
    return _mm(o, blk["o"]["w"], rounded=rounded)


def layer(cfg, blk, mixer, x, rounded=None):
    h = _rms(blk["norm"]["scale"], x, cfg.norm_eps)
    if mixer == "M":
        return x + mamba(cfg, blk, h, rounded)
    if mixer == "E":
        return x + expert_layer(cfg, blk, h, cfg.held_experts, rounded)
    return x + attention(cfg, blk, h, rounded)


def forward(params, cfg, ids, rounded=None) -> jax.Array:
    """ids (T,) -> (T, vocab) float32 logits.  ``rounded``, if given, is
    applied to both operands of every weight matmul outside the router: a
    control that computes in a lower precision than the model states."""
    x = params["tok_emb"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    for blk, mixer in zip(params["blocks"], cfg.hybrid_override_pattern,
                          strict=True):
        x = layer(cfg, blk, mixer, x, rounded)
    return _mm(_rms(params["final_norm"]["scale"], x, cfg.norm_eps),
               params["lm_head"]["w"], "th,hv->tv", rounded)
