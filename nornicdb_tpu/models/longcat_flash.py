"""LongCat-Flash's language model in JAX (the decoder of LongCat-Flash-Omni;
huggingface.co/meituan-longcat/LongCat-Flash-Omni ``config.json``): a
DOUBLE layer of two multi-head latent attention blocks and two dense
feed-forwards, with a shortcut-connected expert branch whose router also
scores zero-compute (identity) experts, told which of the routed experts
this process holds.  The Omni release's audio and vision encoders and its
codec decoder are not here: the published config gives none of their
sizes, and the assistant is a text model.

Pre-norm RMSNorm (eps 1e-5), no biases, SwiGLU with SiLU, untied head.  For
layer ``l`` with stream ``h``::

    a0 = h  + MLA[l,0](norm_in[l,0](h))
    x0 = norm_post[l,0](a0)
    m  = MoE[l](x0)                  # the shortcut branch: from x0, added last
    b0 = a0 + FFN[l,0](x0)           # dense, ffn_hidden_size wide
    a1 = b0 + MLA[l,1](norm_in[l,1](b0))
    b1 = a1 + FFN[l,1](norm_post[l,1](a1))
    h' = b1 + m

* MLA, both blocks (``models/mla.py``, shared with ``models/deepseek_v2.py``:
  the projection, the absorbed attention, the step's decode and chunk
  blocks, the latent pool).  This family's own: ``q <- q * s_q`` on both
  parts, ``s_q = sqrt(hidden / q_lora_rank)`` (2.0), and ``c_kv <- c_kv *
  s_kv`` after its norm, ``s_kv = sqrt(hidden / kv_lora_rank)`` (3.4641)
  (``mla_scale_q_lora`` / ``mla_scale_kv_lora``; ``k_pe`` is not scaled);
  plain rotary embedding at ``rope_theta`` 1e7 (no ``rope_scaling``: plain
  inverse frequencies, cos and sin unscaled); score scale ``(nope +
  rope)^-0.5`` with no mscale.  A token leaves ``[c_kv after norm and scale
  | k_pe after rope]`` in the cache, once for EACH of a layer's two blocks:
  the pool has ``2 x num_layers`` on its leading axis (:func:`init_pages`),
  block ``j`` of layer ``l`` at ``2 l + j``.
* router and experts: ``s = softmax(x0 W_r)`` in f32 over ``n_routed_experts
  + zero_expert_num`` outputs (512 routed, then 256 zero); ``idx =
  top-k(s + e_score_correction_bias)`` (the bias selects only); ``g_i =
  routed_scaling_factor * s_i`` (the scores without the bias, not
  renormalised); ``m = sum_{i in idx, i < n_routed} g_i E_i(x0) + (sum_{i in
  idx, i >= n_routed} g_i) x0`` (``zero_expert_type`` identity), ``E_i`` a
  SwiGLU of ``expert_ffn_hidden_size``.

``held_experts = (first, count)`` says which routed experts this process
holds (expert parallelism).  The router keeps its published width and
top-k; the first sum runs over the held ``i`` only (the masked matmul of
``models/experts.py``), the zero part is computed whole for every row (a row's
home rank needs no exchange for it), and what the absent experts would add
is left out: the partial result goes to the next layer.  Nothing here
stands in for the other ranks or their exchange.

Departures from the checkpoint's layout, none from its mathematics: RoPE
rotates half-pairs ``(i, i + d/2)`` where the checkpoint interleaves ``(2i,
2i+1)`` (a fixed permutation of the rope columns of ``W_qb`` / ``W_kva``:
``models/mla.rope``, as in ``models/deepseek_v2.py``); ``W_kvb`` is kept as
its two column blocks ``kv_b_k`` / ``kv_b_v``; an expert's three matrices
are stacked over the held experts; a layer's two attention blocks, two
norms and two feed-forwards are lists of two.  The two LoRA scales are
written from the published modeling code; the config gives only the flags.

``LongCatFlashConfig()`` is the published language model.  Presets:
LONGCAT_FLASH_EP64_4L (one of 64 expert-parallel ranks, 4 layers, 1/8
vocabulary: the benchmark's cut), LONGCAT_FLASH_SMALL (tests).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.models import mla
from nornicdb_tpu.models.layers import rms_norm
from nornicdb_tpu.ragged import ROUTING_COUNTERS

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class LongCatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    num_layers: int = 28             # double layers
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    n_routed_experts: int = 512      # the router's routed outputs, as published
    held_experts: tuple = (0, 512)   # (first, count) of them held here
    zero_expert_num: int = 256       # identity experts, after the routed ones
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    max_position_embeddings: int = 131072
    dtype: str = "bfloat16"

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def attention_blocks(self) -> int:
        """The pool's leading axis: two latent rows a token a layer."""
        return 2 * self.num_layers

    @property
    def page_row_width(self) -> int:
        return mla.page_row_width(self)

    @property
    def q_scale(self) -> float:
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self) -> float:
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1.0

    @property
    def score_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


LONGCAT_FLASH_EP64_4L = LongCatFlashConfig(
    vocab_size=16384, num_layers=4, held_experts=(0, 8))
LONGCAT_FLASH_SMALL = LongCatFlashConfig(
    vocab_size=512, hidden_size=128, num_layers=3, num_attention_heads=8,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, ffn_hidden_size=256, expert_ffn_hidden_size=64,
    n_routed_experts=12, held_experts=(0, 12), zero_expert_num=4, moe_topk=4,
    routed_scaling_factor=2.0, max_position_embeddings=2560,
)


def inv_freq(cfg: LongCatFlashConfig) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` plain rotary frequencies."""
    d = cfg.qk_rope_head_dim
    return 1.0 / cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d)


def _rope_tables(cfg: LongCatFlashConfig, max_pos: int):
    return mla.rope_tables(inv_freq(cfg), max_pos)


def _project(cfg: LongCatFlashConfig, blk: dict, h: jax.Array, cos, sin):
    """:func:`mla.project` with this family's two LoRA scales."""
    return mla.project(cfg, blk, h, cos, sin, q_scale=cfg.q_scale,
                       kv_scale=cfg.kv_scale)


# --------------------------------------------------------------- weights
def init_params(cfg: LongCatFlashConfig, key: jax.Array) -> dict:
    """Seeded weights: N(0, 1/fan_in) matrices (the three behind a LoRA
    scale, ``q_b`` / ``kv_b_k`` / ``kv_b_v``, N(0, 1/(fan_in s^2)): the
    scales then give unit queries, keys and values, as they are meant
    to), unit norm scales, a zero ``e_score_correction_bias``.  Only the
    held experts are made."""
    dt = jnp.dtype(cfg.dtype)
    h, heads = cfg.hidden_size, cfg.num_attention_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    held = cfg.held_experts[1]

    def mat(k, *shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def mlp(k, width, lead=()):
        k = jax.random.split(k, 3)
        return {"gate": mat(k[0], *lead, h, width, fan_in=h),
                "up": mat(k[1], *lead, h, width, fan_in=h),
                "down": mat(k[2], *lead, width, h, fan_in=width)}

    ones = lambda n: {"scale": jnp.ones((n,), jnp.float32)}  # noqa: E731

    def attention(k):
        k = jax.random.split(k, 6)
        return {
            "attn_norm": ones(h),
            "q_a": {"w": mat(k[0], h, cfg.q_lora_rank, fan_in=h)},
            "q_a_norm": ones(cfg.q_lora_rank),
            "q_b": {"w": mat(k[1], cfg.q_lora_rank, heads * (nope + rope),
                             fan_in=cfg.q_lora_rank * cfg.q_scale ** 2)},
            "kv_a": {"w": mat(k[2], h, cfg.kv_lora_rank + rope, fan_in=h)},
            "kv_a_norm": ones(cfg.kv_lora_rank),
            "kv_b_k": mat(k[3], cfg.kv_lora_rank, heads, nope,
                          fan_in=cfg.kv_lora_rank * cfg.kv_scale ** 2),
            "kv_b_v": mat(k[4], cfg.kv_lora_rank, heads, vd,
                          fan_in=cfg.kv_lora_rank * cfg.kv_scale ** 2),
            "o": {"w": mat(k[5], heads * vd, h, fan_in=heads * vd)},
        }

    keys = jax.random.split(key, cfg.num_layers + 2)
    params = {"tok_emb": mat(keys[0], cfg.vocab_size, h, fan_in=h),
              "lm_head": {"w": mat(keys[1], h, cfg.vocab_size, fan_in=h)},
              "final_norm": ones(h), "blocks": []}
    for li in range(cfg.num_layers):
        k = jax.random.split(keys[2 + li], 6)
        params["blocks"].append({
            "attn": [attention(k[0]), attention(k[1])],
            "mlp_norm": [ones(h), ones(h)],
            "mlp": [mlp(k[2], cfg.ffn_hidden_size),
                    mlp(k[3], cfg.ffn_hidden_size)],
            "router": mat(k[4], h, cfg.router_outputs, fan_in=h),
            "router_bias": jnp.zeros((cfg.router_outputs,), jnp.float32),
            "experts": mlp(k[5], cfg.expert_ffn_hidden_size, lead=(held,)),
        })
    return params


# ----------------------------------------------------- the expert branch
def route(cfg: LongCatFlashConfig, router: jax.Array, bias: jax.Array,
          x: jax.Array):
    """Top-k over ALL router outputs (routed, then zero experts), in f32:
    the choice by score + bias, the gates by score alone.  x (N, hidden) ->
    (ids (N, k), gates (N, k) = scaling * softmax score)."""
    logits = jnp.einsum("nh,he->ne", x.astype(jnp.float32),
                        router.astype(jnp.float32), precision=_HI)
    p = jax.nn.softmax(logits, axis=-1)
    _, ids = jax.lax.top_k(p + bias.astype(jnp.float32), cfg.moe_topk)
    return ids, jnp.take_along_axis(p, ids, axis=-1) \
        * cfg.routed_scaling_factor


def expert_branch(cfg: LongCatFlashConfig, layer: dict, x: jax.Array,
                  valid: jax.Array | None = None):
    """``m`` for rows x (N, hidden) on this process's share: the HELD
    experts' part plus the zero experts' part, f32 (N, hidden); and the
    counts over the ``valid`` rows, int32 (4,) = (assignments on held
    experts, the fullest held expert's rows, held experts that got a row,
    choices that fell on zero experts)."""
    with jax.named_scope("moe.route"):
        ids, gates = route(cfg, layer["router"], layer["router_bias"], x)
        weight, counts = mla.held_gates(ids, gates, cfg.held_experts, valid)
        on_zero = ids >= cfg.n_routed_experts
        zeros = on_zero if valid is None else on_zero & valid[:, None]
        counts = jnp.concatenate(
            [counts, zeros.sum().astype(jnp.int32)[None]])
    with jax.named_scope("moe.experts"):
        out = mla.held_experts(layer["experts"], x, weight)
    with jax.named_scope("moe.zero"):
        # an identity expert returns its input: the gates on them, summed
        zero_gate = jnp.where(on_zero, gates, 0.0).sum(axis=-1)
        out = out + zero_gate[:, None] * x.astype(jnp.float32)
    return out, counts


def _layer(cfg: LongCatFlashConfig, layer: dict, h: jax.Array, attend,
           pool=None, at: int = 0, valid: jax.Array | None = None):
    """One double layer, whose two attention blocks keep their rows in pool
    layers ``at`` and ``at + 1``.  ``attend(blk, pool layer, h, pool)`` =
    (h + that block's attention, the pool with its rows written: None
    where there is no cache).  Returns (h', the expert branch's counts
    (4,), pool)."""
    eps = cfg.rms_norm_eps
    a0, pool = attend(layer["attn"][0], at, h, pool)
    x0 = rms_norm(layer["mlp_norm"][0], a0, eps)
    m, counts = expert_branch(cfg, layer, x0, valid)
    with jax.named_scope("ffn.dense"):
        b0 = a0 + mla.swiglu(layer["mlp"][0], x0)
    a1, pool = attend(layer["attn"][1], at + 1, b0, pool)
    with jax.named_scope("ffn.dense"):
        b1 = a1 + mla.swiglu(layer["mlp"][1],
                             rms_norm(layer["mlp_norm"][1], a1, eps))
    return (b1.astype(jnp.float32) + m).astype(h.dtype), counts, pool


def _logits(params: dict, cfg: LongCatFlashConfig, h: jax.Array) -> jax.Array:
    x = rms_norm(params["final_norm"], h, cfg.rms_norm_eps)
    return jnp.einsum("...h,hv->...v", x, params["lm_head"]["w"],
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg",))
def forward(params: dict, cfg: LongCatFlashConfig,
            input_ids: jax.Array) -> jax.Array:
    """(B, T) -> (B, T, V) f32 logits, causal, no cache: the plain batched
    forward in the configuration's dtype, attention in the absorbed form."""
    b, t = input_ids.shape
    cos, sin = (jnp.tile(a, (b, 1)) for a in _rope_tables(cfg, t))
    mask = jnp.where(jnp.tril(jnp.ones((t, t), bool)), 0.0, -1e30)[None, None]
    h = params["tok_emb"][input_ids].reshape(b * t, -1)

    def attend(blk, _at, x, _pool):
        return mla.attend_sequences(cfg, blk, x, b, cos, sin, mask, _project,
                                    cfg.score_scale), None

    for layer in params["blocks"]:
        h, _, _ = _layer(cfg, layer, h, attend)
    return _logits(params, cfg, h).reshape(b, t, -1)


# ------------------------------------------------ the latent page pool
def init_pages(cfg: LongCatFlashConfig, num_pages: int,
               page_size: int) -> jax.Array:
    """The latent pool (:func:`mla.init_pages`): ``2 x num_layers`` on its
    leading axis, a layer's block ``j`` at ``2 l + j``.  Page tables,
    prefix pages and eviction are per token and know nothing of it."""
    return mla.init_pages(cfg, cfg.attention_blocks, num_pages, page_size)


num_pages = mla.num_pages


@functools.partial(jax.jit, static_argnames=("cfg", "lmax", "w", "tq"),
                   donate_argnums=(3,))
def scmoe_mla_fused_step(params, cfg: LongCatFlashConfig, meta: jax.Array,
                         pages: jax.Array, *, lmax: int, w: int, tq: int,
                         prev=None):
    """One fused prefill+decode step over the latent pool, on the engine's
    flat rows (:func:`mla.plan_step`).  Each row's latent is written once
    to its (page, slot) in EACH of a layer's two pool layers; the shortcut
    branch rides across the layer's second attention block and rejoins the
    stream after its second feed-forward.  Returns ``(ints, logits,
    pages)``: ``ints`` = the ``lmax`` greedy ids followed by the step's
    counts in :data:`STEP_COUNTERS` order (assignments on held experts, the
    fullest held expert's rows and the held experts hit, each summed over
    the layers; rows routed = valid rows x layers; top-k choices that fell
    on zero experts; the slots the attention blocks walked and the slots
    their whole tables hold: :data:`mla.WALK_COUNTERS`), so one
    device-to-host read carries both; ``logits`` (lmax, V) f32 for
    ``logit_rows``; ``pages`` is DONATED."""
    rows = mla.plan_step(
        meta, pages, _rope_tables(cfg, w * pages.shape[2]), lmax=lmax, w=w,
        tq=tq, prev=prev)
    f = rows.tokens.shape[0]
    h = params["tok_emb"][rows.tokens]               # (F, hidden)
    counts = jnp.zeros((4,), jnp.int32)

    def attend(blk, at, x, pool):
        return mla.attend_step(cfg, blk, rows, pool, at, x, _project,
                               cfg.score_scale)

    for li, layer in enumerate(params["blocks"]):
        h, layer_counts, pages = _layer(cfg, layer, h, attend, pages, 2 * li,
                                        rows.valid)
        counts = counts + layer_counts
    logits = _logits(params, cfg, h[jnp.clip(rows.logit_rows, 0, f - 1)])
    routed = rows.valid.sum().astype(jnp.int32) * cfg.num_layers
    ints = jnp.concatenate([jnp.argmax(logits, axis=-1).astype(jnp.int32),
                            counts[:3], routed[None], counts[3:],
                            rows.walk * cfg.attention_blocks])
    return ints, logits, pages


# the decoder-family seam (genserve/engine.py)
fused_step = scmoe_mla_fused_step
# what ``ints`` carries after the ids (nornicdb_tpu/ragged.py)
STEP_COUNTERS = ROUTING_COUNTERS + ("zero_assignments",) + mla.WALK_COUNTERS
