"""Ring attention: exact attention over sequences sharded across chips.

The reference has no attention in its serving path (SURVEY.md §5
"long-context"), but this framework runs its embedding/assistant models on
TPU, and long-context is first-class: sequences shard over a "seq" mesh axis;
K/V blocks rotate around the ring via ppermute while each chip accumulates
flash-attention-style online softmax for its local Q block. Communication
overlaps with compute and total memory per chip is O(T/S).

Causal masking uses global position offsets so the sharded result matches
single-device attention exactly.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def _block_attn(q, k, v, mask):
    """One (Tq x Tk) attention block with stable online-softmax stats.

    q: (B, Tq, H, Dh); k/v: (B, Tk, H, Dh); mask: (Tq, Tk) additive.
    Returns (numerator (B, Tq, H, Dh), row_max (B, H, Tq), row_sum (B, H, Tq)).
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale + mask[None, None, :, :]
    m = jnp.max(s, axis=-1)  # (B, H, Tq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)  # noqa: E741
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def _ring_body(axis_name: str, n_blocks: int, causal: bool):
    def body(carry, step):
        k, v, o_acc, m_acc, l_acc, q, my_idx = carry
        # which shard's K/V block do we currently hold?
        src = (my_idx - step) % n_blocks
        tq = q.shape[1]
        tk = k.shape[1]
        if causal:
            q_pos = my_idx * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            k_pos = src * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            mask = jnp.where(k_pos <= q_pos, 0.0, NEG_INF).astype(jnp.float32)
        else:
            mask = jnp.zeros((tq, tk), jnp.float32)
        o, m, l = _block_attn(q, k, v, mask)  # noqa: E741
        # online-softmax merge of the new block into the accumulator
        m_new = jnp.maximum(m_acc, m)
        alpha = jnp.exp(m_acc - m_new)  # rescale old
        beta = jnp.exp(m - m_new)  # rescale new
        l_new = l_acc * alpha + l * beta
        o_new = (
            o_acc * jnp.moveaxis(alpha, 1, -1)[..., None]
            + o * jnp.moveaxis(beta, 1, -1)[..., None]
        )
        # rotate K/V to the next chip on the ICI ring
        perm = [(i, (i + 1) % n_blocks) for i in range(n_blocks)]
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        return (k, v, o_new, m_new, l_new, q, my_idx), None

    return body


def make_ring_attention(
    mesh: Mesh, axis_name: str = "seq", causal: bool = True
):
    """Build a jit'd ring-attention callable for (B, T, H, Dh) inputs with T
    sharded over `axis_name`."""
    n_blocks = mesh.shape[axis_name]

    def local_fn(q, k, v):
        my_idx = jax.lax.axis_index(axis_name)
        b, tq, h, dh = q.shape
        o0 = jnp.zeros((b, tq, h, dh), jnp.float32)
        m0 = jnp.full((b, h, tq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, tq), jnp.float32)
        carry, _ = jax.lax.scan(
            _ring_body(axis_name, n_blocks, causal),
            (k, v, o0, m0, l0, q, my_idx),
            jnp.arange(n_blocks),
        )
        _, _, o_acc, m_acc, l_acc, _, _ = carry
        denom = jnp.moveaxis(l_acc, 1, -1)[..., None]
        return (o_acc / jnp.maximum(denom, 1e-30)).astype(q.dtype)

    spec = P(None, axis_name, None, None)
    sharded = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(sharded)


def reference_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True
) -> jax.Array:
    """Single-device exact attention, for parity tests."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    ).astype(q.dtype)
