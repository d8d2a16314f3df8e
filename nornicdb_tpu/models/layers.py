"""Shared neural-net layers as pure functions over parameter pytrees.

Models are plain dict pytrees + pure apply functions (no framework Module
state) so pjit/shard_map sharding annotations stay first-class and the same
code serves single-chip jit and multi-chip meshes.

Replaces the reference's llama.cpp compute graph (lib/llama/*.h,
pkg/localllm/llama.go) with jit'd XLA graphs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dense(params: dict, x: jax.Array) -> jax.Array:
    """x @ W + b. W: (in, out)."""
    y = jnp.einsum("...i,io->...o", x, params["w"],
                   preferred_element_type=jnp.float32)
    if "b" in params:
        y = y + params["b"]
    return y.astype(x.dtype)


def layer_norm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(x.dtype)


def rms_norm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * params["scale"]).astype(x.dtype)


def rope_freqs(dim: int, max_pos: int, theta: float = 10000.0) -> jax.Array:
    """(max_pos, dim/2) rotation angles."""
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    pos = np.arange(max_pos, dtype=np.float32)
    return jnp.asarray(np.outer(pos, inv))  # (P, dim/2)


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x: (B, T, H, Dh); angles: (T, Dh/2) — rotate half-pairs."""
    xf = x.astype(jnp.float32)
    d2 = x.shape[-1] // 2
    x1, x2 = xf[..., :d2], xf[..., d2:]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None = None,
) -> jax.Array:
    """(B, T, H, Dh) attention; mask: broadcastable to (B, H, Tq, Tk), additive."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if mask is not None:
        s = s + mask
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def _own(h: int, hkv: int) -> np.ndarray:
    """own[h, g]: K/V head g is query head h's, shaped to broadcast over
    (B, H, T, Hkv, Dh)."""
    return (np.arange(h)[:, None] // (h // hkv)
            == np.arange(hkv))[None, :, None, :, None]


def heads_to_rows(q: jax.Array, hkv: int) -> jax.Array:
    """q (B, T, H, Dh) -> (B, H, T, Hkv * Dh): each head's Dh query values
    in its K/V group's lanes of a row-wide vector, zeros elsewhere."""
    b, t, h, dh = q.shape
    qh = jnp.transpose(q, (0, 2, 1, 3))[:, :, :, None, :]  # (B, H, T, 1, Dh)
    return jnp.where(_own(h, hkv), qh, 0).reshape(b, h, t, hkv * dh)


def rows_to_heads(o_rows: jax.Array, hkv: int) -> jax.Array:
    """(B, H, T, Hkv * Dh) -> (B, H, T, Dh): each head's own group's lanes
    of a row-wide result.  Both this and :func:`heads_to_rows` are selects
    under one mask: taking o as slices of lanes concatenated over heads
    reads the wrong lanes on a TPU v5e (this XLA; PERF.md section 6, PR
    31), and only there."""
    b, h, t, row = o_rows.shape
    return jnp.where(_own(h, hkv),
                     o_rows.reshape(b, h, t, hkv, row // hkv), 0.0).sum(axis=3)


def grouped_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None = None,
) -> jax.Array:
    """Grouped-query attention over K/V rows as a paged pool stores them.

    q: (B, T, H, Dh); k, v: (B, S, Hkv * Dh), one row a cache slot, the K/V
    heads side by side; mask as :func:`attention`'s.  Head ``h`` attends
    K/V head ``h // (H // Hkv)``.  The ``n_rep``-fold copy of K and V that
    ``attention(q, repeat_kv(k), repeat_kv(v))`` contracts over is never
    made, and a row is never split: each head's Dh query values sit in its
    group's lanes of a row-wide vector, zeros elsewhere
    (:func:`heads_to_rows`), so both contractions are plain batched matmuls
    over whole rows (at Qwen2.5's 2 x 64 one 128-lane tile; the zeros add
    exactly 0.0), and the group's lanes of ``p @ V`` are the head's output
    (:func:`rows_to_heads`).  Operands keep their dtype, accumulation and
    softmax are f32, ``p`` is cast to V's dtype: the arithmetic of
    :func:`attention`, in another order of reduction.  The zero lanes cost
    Hkv times the contractions' FLOPs, which a step bound by reading K and
    V does not notice at Hkv = 2.  The fused steps' walk over a paged pool
    (``models/kv_walk.py``) contracts the same way where a head is narrower
    than a lane tile.
    """
    dh = q.shape[-1]
    hkv = k.shape[-1] // dh
    s = jnp.einsum("bhqc,bkc->bhqk", heads_to_rows(q, hkv), k,
                   preferred_element_type=jnp.float32)
    s = s * dh ** -0.5
    if mask is not None:
        s = s + mask
    p = jax.nn.softmax(s, axis=-1)
    o_rows = jnp.einsum("bhqk,bkc->bhqc", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
    return jnp.transpose(rows_to_heads(o_rows, hkv),
                         (0, 2, 1, 3)).astype(q.dtype)


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """GQA: expand (B, T, Hkv, Dh) -> (B, T, Hkv*n_rep, Dh)."""
    if n_rep == 1:
        return x
    b, t, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, t, h, n_rep, d)).reshape(
        b, t, h * n_rep, d
    )


# -- initializers ---------------------------------------------------------
def glorot(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[0], shape[-1]
    lim = float(np.sqrt(6.0 / (fan_in + fan_out)))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def normal_init(key, shape, stddev=0.02, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * stddev


def init_dense(key, d_in, d_out, bias=True, dtype=jnp.float32):
    p = {"w": glorot(key, (d_in, d_out), dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def init_layer_norm(dim, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def init_rms_norm(dim, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def count_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
