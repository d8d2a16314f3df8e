"""DeepSeek-V2-architecture decoder in JAX: multi-head latent attention (MLA)
over a latent page pool, group-limited routed experts beside shared ones,
told which of the routed experts this process holds.

The layer (huggingface.co/deepseek-ai/DeepSeek-V2 ``config.json`` /
``modeling_deepseek.py``), pre-norm RMSNorm, no biases, untied head:

* attention: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> per head
  ``[q_nope | q_pe]``; ``[c_kv | k_pe] = x W_kva``, ``c_kv = RMSNorm(c_kv)``,
  ``k_pe = RoPE(k_pe)`` (ONE head, shared by all), ``q_pe = RoPE(q_pe)``;
  per head ``[k_nope | v] = c_kv W_kvb``; scores ``(q_nope.k_nope +
  q_pe.k_pe) * (nope + rope)^-0.5 * m^2`` with the YaRN ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``, causal softmax in f32.  RoPE is YaRN
  (:func:`yarn_inv_freq`).  What a token leaves in the cache, per layer,
  is ``[c_kv after its norm | k_pe after RoPE]``: ``kv_lora_rank +
  qk_rope_head_dim`` values (:func:`init_pages`).
* the ABSORBED form, the same mathematics with W_kvb folded into the query
  and the output: ``q~ = q_nope W_kvb,k^T`` (``kv_lora_rank`` wide), ``s =
  q~.c_kv + q_pe.k_pe``, ``o = (sum_u p c_kv(u)) W_kvb,v``: every head
  attends ONE cached row and per-head K/V over the cache never exists.
  The serving step (:func:`mla_moe_fused_step`) uses it for its decode
  block and its chunk block alike, and so does the plain :func:`forward`;
  the expanded form as published is the reference's
  (``models/reference/deepseek_v2.py``).  The projection, the absorbed
  attention, the step's two blocks and the latent pool are
  ``models/mla.py``'s, shared with every latent family; YaRN, the score
  scale and the router are this family's.
* feed-forward: the first ``first_k_dense_replace`` layers a dense SwiGLU;
  the others ``p = softmax(x W_g)`` in f32 over ALL ``n_routed_experts``,
  the experts in ``n_group`` groups, a group's score its best expert's,
  the ``topk_group`` best groups kept, the ``num_experts_per_tok`` best
  experts among them, gates ``routed_scaling_factor * p`` (not
  renormalised), plus the shared experts (one SwiGLU of ``n_shared_experts
  * moe_intermediate_size``) for every row.

``held_experts = (first, count)`` says which routed experts this process
holds (expert parallelism: the model's ``n_group`` is the number of
devices a layer's experts are spread over, a group is one device's).  The
router keeps its published width and top-k; only assignments that fall on
held experts are computed (a masked matmul over the held ones: no dropped
tokens, no capacity factor) and what the absent experts would add is left
out: the partial result goes to the next layer.  Nothing here stands in for
the other ranks or their exchange.

Departures from the checkpoint's layout, none from its mathematics: RoPE
rotates half-pairs ``(i, i + d/2)`` where the checkpoint interleaves ``(2i,
2i+1)`` (a column permutation of ``W_qb`` / ``W_kva``); ``W_kvb`` is kept
as its two column blocks ``kv_b_k`` / ``kv_b_v``; an expert's three
matrices are stacked over the held experts.

``DeepSeekV2Config()`` is the published model.  Presets:
DEEPSEEK_V2_EP8_5L (one of eight expert-parallel ranks, 1 dense + 4 expert
layers, 1/8 vocabulary: the benchmark's cut), DEEPSEEK_V2_SMALL (tests).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.models import mla
from nornicdb_tpu.models.layers import dense, rms_norm  # noqa: F401 (faults)
from nornicdb_tpu.ragged import ROUTING_COUNTERS

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class DeepSeekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    num_hidden_layers: int = 60
    first_k_dense_replace: int = 1
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 160      # the router's width, as published
    held_experts: tuple = (0, 160)   # (first, count) of them held here
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    max_position_embeddings: int = 163840
    dtype: str = "bfloat16"

    @property
    def latent_width(self) -> int:
        """Values a token leaves in the cache, per layer."""
        return mla.latent_width(self)

    @property
    def page_row_width(self) -> int:
        """A pool row: ``latent_width`` in whole 128-lane tiles."""
        return mla.page_row_width(self)

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


DEEPSEEK_V2_EP8_5L = DeepSeekV2Config(
    vocab_size=12800, num_hidden_layers=5, held_experts=(0, 20))
DEEPSEEK_V2_SMALL = DeepSeekV2Config(
    vocab_size=512, hidden_size=128, num_hidden_layers=3,
    num_attention_heads=8, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=256, moe_intermediate_size=64, n_routed_experts=16,
    held_experts=(0, 16), n_shared_experts=1, num_experts_per_tok=4,
    n_group=4, topk_group=2, routed_scaling_factor=4.0,
    rope_original_max_position_embeddings=64, max_position_embeddings=2560,
)


# ------------------------------------------------------------------ YaRN
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg: DeepSeekV2Config) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies: each blended between
    the plain ``f = theta^(-2i/d)`` and the interpolated ``f / factor`` by
    a linear ramp over the correction range (the dimensions that turn
    ``beta_fast`` .. ``beta_slow`` times within the original context keep
    ``f`` .. take ``f / factor``)."""
    d, base = cfg.qk_rope_head_dim, cfg.rope_theta
    plain = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def correction_dim(rotations: float) -> float:
        return d * math.log(cfg.rope_original_max_position_embeddings
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return plain / cfg.rope_factor * ramp + plain * (1.0 - ramp)


def rope_scale(cfg: DeepSeekV2Config) -> float:
    """What cos and sin are multiplied by: ``mscale / mscale_all_dim``."""
    return _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)


def softmax_scale(cfg: DeepSeekV2Config) -> float:
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _rope_tables(cfg: DeepSeekV2Config, max_pos: int):
    """(max_pos, rope/2) cos and sin of the YaRN frequencies."""
    return mla.rope_tables(yarn_inv_freq(cfg), max_pos, rope_scale(cfg))


# the shared MLA under this family's names (``bench/tests/faults_dsv2.py``
# plants its faults on ``_rope`` and ``_project``: both are looked up here
# at every call)
_rope = mla.rope


def _project(cfg: DeepSeekV2Config, blk: dict, h: jax.Array, cos, sin):
    """:func:`mla.project` with nothing scaled."""
    return mla.project(cfg, blk, h, cos, sin, rope=_rope)


# --------------------------------------------------------------- weights
def init_params(cfg: DeepSeekV2Config, key: jax.Array) -> dict:
    """Seeded weights: N(0, 1/fan_in) matrices, unit norm scales.  Only the
    held experts are made."""
    dt = jnp.dtype(cfg.dtype)
    h, heads = cfg.hidden_size, cfg.num_attention_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    held, im = cfg.held_experts[1], cfg.moe_intermediate_size

    def mat(k, *shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def mlp(k, width, lead=()):
        k = jax.random.split(k, 3)
        return {"gate": mat(k[0], *lead, h, width, fan_in=h),
                "up": mat(k[1], *lead, h, width, fan_in=h),
                "down": mat(k[2], *lead, width, h, fan_in=width)}

    ones = lambda n: {"scale": jnp.ones((n,), jnp.float32)}  # noqa: E731
    keys = jax.random.split(key, cfg.num_hidden_layers + 2)
    params = {"tok_emb": mat(keys[0], cfg.vocab_size, h, fan_in=h),
              "lm_head": {"w": mat(keys[1], h, cfg.vocab_size, fan_in=h)},
              "final_norm": ones(h), "blocks": []}
    for li in range(cfg.num_hidden_layers):
        k = jax.random.split(keys[2 + li], 10)
        blk = {
            "attn_norm": ones(h), "mlp_norm": ones(h),
            "q_a": {"w": mat(k[0], h, cfg.q_lora_rank, fan_in=h)},
            "q_a_norm": ones(cfg.q_lora_rank),
            "q_b": {"w": mat(k[1], cfg.q_lora_rank, heads * (nope + rope),
                             fan_in=cfg.q_lora_rank)},
            "kv_a": {"w": mat(k[2], h, cfg.kv_lora_rank + rope, fan_in=h)},
            "kv_a_norm": ones(cfg.kv_lora_rank),
            "kv_b_k": mat(k[3], cfg.kv_lora_rank, heads, nope,
                          fan_in=cfg.kv_lora_rank),
            "kv_b_v": mat(k[4], cfg.kv_lora_rank, heads, vd,
                          fan_in=cfg.kv_lora_rank),
            "o": {"w": mat(k[5], heads * vd, h, fan_in=heads * vd)},
        }
        if li < cfg.first_k_dense_replace:
            blk["mlp"] = mlp(k[6], cfg.intermediate_size)
        else:
            blk["router"] = mat(k[7], h, cfg.n_routed_experts, fan_in=h)
            blk["experts"] = mlp(k[8], im, lead=(held,))
            blk["shared"] = mlp(k[9], cfg.n_shared_experts * im)
        params["blocks"].append(blk)
    return params


# --------------------------------------------------------- feed-forward
_swiglu = mla.swiglu


def route(cfg: DeepSeekV2Config, router: jax.Array, x: jax.Array):
    """Group-limited greedy top-k over ALL routed experts, in f32.
    x (N, hidden) -> (expert ids (N, k), gates (N, k) = scaling * p)."""
    e, g = cfg.n_routed_experts, cfg.n_group
    logits = jnp.einsum("nh,he->ne", x.astype(jnp.float32),
                        router.astype(jnp.float32), precision=_HI)
    p = jax.nn.softmax(logits, axis=-1)
    _, groups = jax.lax.top_k(p.reshape(-1, g, e // g).max(axis=-1),
                              cfg.topk_group)
    kept = jax.nn.one_hot(groups, g, dtype=jnp.bool_).any(axis=1)
    allowed = jnp.repeat(kept, e // g, axis=1)
    gates, ids = jax.lax.top_k(jnp.where(allowed, p, 0.0),
                               cfg.num_experts_per_tok)
    return ids, gates * cfg.routed_scaling_factor


def routed_experts(cfg: DeepSeekV2Config, blk: dict, x: jax.Array,
                   valid: jax.Array | None = None):
    """What the HELD experts add for rows x (N, hidden), and the routing
    counts over the ``valid`` rows: f32 (N, hidden), int32 (3,) =
    (assignments on held experts, the fullest held expert's rows, held
    experts that got a row): this family's router, then the masked matmul
    every routed family shares (``models/experts.py``)."""
    with jax.named_scope("moe.route"):
        ids, gates = route(cfg, blk["router"], x)
        weight, counts = mla.held_gates(ids, gates, cfg.held_experts, valid)
    with jax.named_scope("moe.experts"):
        out = mla.held_experts(blk["experts"], x, weight)
    return out, counts


def _feed_forward(cfg: DeepSeekV2Config, blk: dict, h: jax.Array,
                  valid: jax.Array | None = None):
    """h (N, hidden) -> (h + its feed-forward, routing counts (3,))."""
    x = rms_norm(blk["mlp_norm"], h, cfg.rms_norm_eps)
    if "mlp" in blk:
        return h + _swiglu(blk["mlp"], x), jnp.zeros((3,), jnp.int32)
    routed, counts = routed_experts(cfg, blk, x, valid)
    with jax.named_scope("moe.shared"):
        shared = _swiglu(blk["shared"], x)
    return h + (routed + shared.astype(jnp.float32)).astype(h.dtype), counts


# ------------------------------------------------------------- the model
def _logits(params: dict, cfg: DeepSeekV2Config, h: jax.Array) -> jax.Array:
    x = rms_norm(params["final_norm"], h, cfg.rms_norm_eps)
    return jnp.einsum("...h,hv->...v", x, params["lm_head"]["w"],
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg",))
def forward(params: dict, cfg: DeepSeekV2Config,
            input_ids: jax.Array) -> jax.Array:
    """(B, T) -> (B, T, V) f32 logits, causal, no cache: the plain batched
    forward in the configuration's dtype, attention in the absorbed form."""
    b, t = input_ids.shape
    cos, sin = (jnp.tile(a, (b, 1)) for a in _rope_tables(cfg, t))
    mask = jnp.where(jnp.tril(jnp.ones((t, t), bool)), 0.0, -1e30)[None, None]
    h = params["tok_emb"][input_ids].reshape(b * t, -1)
    for blk in params["blocks"]:
        h = mla.attend_sequences(cfg, blk, h, b, cos, sin, mask, _project,
                                 softmax_scale(cfg))
        h, _ = _feed_forward(cfg, blk, h)
    return _logits(params, cfg, h).reshape(b, t, -1)


# ------------------------------------------------ the latent page pool
def init_pages(cfg: DeepSeekV2Config, num_pages: int,
               page_size: int) -> jax.Array:
    """The latent pool (:func:`mla.init_pages`), one attention block a
    layer."""
    return mla.init_pages(cfg, cfg.num_hidden_layers, num_pages, page_size)


num_pages = mla.num_pages


@functools.partial(jax.jit, static_argnames=("cfg", "lmax", "w", "tq"),
                   donate_argnums=(3,))
def mla_moe_fused_step(params, cfg: DeepSeekV2Config, meta: jax.Array,
                       pages: jax.Array, *, lmax: int, w: int, tq: int,
                       prev=None):
    """One fused prefill+decode step over the latent pool, on the engine's
    flat rows (``nornicdb_tpu/ragged.py``: ``meta`` holds F token rows, their
    lanes and positions, ``lmax`` logit rows and the ``(lmax, w)`` page
    tables; ``tq`` is the chunk block's static width, 1 = decode only;
    ``prev`` is the previous step's ``ints``, where a row whose token is
    ``-(src + 1)`` finds it).

    Each row's ``[c_kv | k_pe]`` is written once to its (page, slot); the
    decode block (one query a lane) and the chunk block (``tq`` queries of
    the chunk lane) then attend what is live of their lanes' pages in the
    absorbed form (:func:`mla.attend_live`).  Returns ``(ints, logits,
    pages)``: ``ints`` = the ``lmax`` greedy ids followed by the step's
    counts in :data:`STEP_COUNTERS` order (assignments on held experts, the
    fullest held expert's rows and the held experts hit, each summed over
    the expert layers; rows routed = valid rows x expert layers; the slots
    the attention blocks walked and the slots their whole tables hold:
    :data:`mla.WALK_COUNTERS`), so one device-to-host read carries both;
    ``logits`` (lmax, V) f32 for ``logit_rows``; ``pages`` is DONATED."""
    rows = mla.plan_step(
        meta, pages, _rope_tables(cfg, w * pages.shape[2]), lmax=lmax, w=w,
        tq=tq, prev=prev)
    valid, f = rows.valid, rows.tokens.shape[0]
    h = params["tok_emb"][rows.tokens]               # (F, hidden)
    counts = jnp.zeros((3,), jnp.int32)
    for li, blk in enumerate(params["blocks"]):
        h, pages = mla.attend_step(cfg, blk, rows, pages, li, h, _project,
                                   softmax_scale(cfg))
        h, layer_counts = _feed_forward(cfg, blk, h, valid)
        counts = counts + layer_counts
    logits = _logits(params, cfg, h[jnp.clip(rows.logit_rows, 0, f - 1)])
    routed = valid.sum().astype(jnp.int32) * cfg.expert_layers
    ints = jnp.concatenate([jnp.argmax(logits, axis=-1).astype(jnp.int32),
                            counts, routed[None],
                            rows.walk * cfg.num_hidden_layers])
    return ints, logits, pages


# the decoder-family seam (genserve/engine.py); no dense-mode pair
fused_step = mla_moe_fused_step
# what ``ints`` carries after the ids (nornicdb_tpu/ragged.py)
STEP_COUNTERS = ROUTING_COUNTERS + mla.WALK_COUNTERS
