"""Command A+'s language model in JAX (``model_type`` ``cohere2_moe``;
huggingface.co/CohereLabs/command-a-plus-05-2026 ``config.json``): window
layers beside full ones, a PARALLEL block (attention and the expert layer
read the same normed input and join the stream together), a sigmoid router
and averaged shared experts, told which of the routed experts this process
holds.  The release's vision tower is not here: the published config gives
none of its sizes, and the assistant is a text model.

For a layer with stream ``x`` (``use_parallel_block``; ``first_k_dense_replace``
0, so every layer is an expert layer)::

    h      = LN(x)                 # Cohere's LayerNorm: (x - mean) /
                                   # sqrt(var + eps) * g, no bias
    q,k,v  = h Wq, h Wk, h Wv      # 128 / 8 / 8 heads of 128, no bias, no
                                   # qk-norm, scores times head_dim^-0.5
    sliding_attention:  q, k rotated over INTERLEAVED pairs (2i, 2i + 1)
                        (``rope_gptj``), all 128 dims, theta 50000; query i
                        sees keys j with i - sliding_window < j <= i
    full_attention:     no rotation at all (no positions); query i sees
                        every j <= i
    a      = softmax(q k^T) v Wo
    s      = sigmoid(h Wr)         # f32; the 8 best of 128; g_i = s_i over
                                   # the sum of the chosen eight
    routed = sum_i g_i E_i(h)      # E(h) = W_down(silu(W_gate h) * W_up h)
    shared = 1/4 sum_j S_j(h)      # four shared experts, AVERAGED
    x'     = x + a + routed + shared

and ``logits = LN_f(x) E^T * logit_scale`` over the tied table.

Two kinds of cache state side by side (:func:`page_kinds`): a ``full``
layer keeps a lane's whole history, a ``window`` layer the last
``sliding_window`` tokens only, so the scheduler keeps a K/V pool, a page
table and a free list FOR EACH KIND (``nornicdb_tpu/ragged.py``,
``genserve/engine.py``) and a window lane hands back the pages its window
has passed while it lives.  A pool is Qwen's layout: ``(layers of the kind,
2[k|v], pages, page_size, 8 x 128)``, a slot's K (or V) heads side by side.
The step's two attention blocks walk a lane's table of the layer's kind in
blocks of pages with a running float32 softmax (``models/kv_walk.py``, the
walk Qwen's step runs too: 32 pages a block at this model's 32 KB pages),
from the first block a live query's window still reaches to the last live
one: nothing behind the window or past the live length is gathered.

``held_experts = (first, count)`` says which routed experts this process
holds (expert parallelism; ``models/experts.py``).  The router keeps its
published 128 outputs and top-8 and the gates are normalised over the chosen
eight wherever they live; the routed sum runs over the held ones, the shared
average is computed whole (every rank computes it alike), and what the
absent experts would add is left out: the partial result goes to the next
layer.  Nothing here stands in for the other ranks or their exchange.

Read from the source where its config does not settle it (the benchmark's
configuration lists these under ``assumed``): ``"average"`` is the mean of
the four shared experts' outputs, added to the routed sum; no routed scaling
factor and no router bias (no key for either); the window's edge as the
``transformers`` Cohere2 mask has it (``i - j < sliding_window``).
Departures from the checkpoint's layout, none from its mathematics: an
expert's three matrices are stacked over the held (or shared) experts.

``Cohere2MoeConfig()`` is the published language model.  Presets:
COMMAND_A_PLUS_EP16_4L (one of 16 expert-parallel ranks, one period of 4
layers, 1/8 vocabulary: the benchmark's cut), COHERE2_MOE_SMALL (tests).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.models import experts, kv_walk
from nornicdb_tpu.models.layers import dense
from nornicdb_tpu.ragged import ROUTING_COUNTERS

_HI = jax.lax.Precision.HIGHEST
SLIDING, FULL = "sliding_attention", "full_attention"
_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096    # one expert's width, routed or shared
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 128           # the router's outputs, as published
    held_experts: tuple = (0, 128)   # (first, count) of them held here
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    sliding_window: int = 4096
    layer_types: tuple = _PERIOD * 8  # as published, one entry a layer
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_position_embeddings: int = 200000
    dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers or not set(
                self.layer_types) <= {SLIDING, FULL}:
            raise ValueError("layer_types names each of num_hidden_layers "
                             f"layers {SLIDING!r} or {FULL!r}")

    @property
    def kv_row(self) -> int:
        """A cache slot's K (or V): the K/V heads side by side."""
        return self.num_key_value_heads * self.head_dim


COMMAND_A_PLUS_EP16_4L = Cohere2MoeConfig(
    vocab_size=32768, num_hidden_layers=4, layer_types=_PERIOD,
    held_experts=(0, 8))
COHERE2_MOE_SMALL = Cohere2MoeConfig(
    vocab_size=512, hidden_size=64, intermediate_size=64,
    num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, num_experts=16, held_experts=(0, 16), num_experts_per_tok=4,
    num_shared_experts=2, sliding_window=32, layer_types=_PERIOD,
    max_position_embeddings=2048,
)


# ------------------------------------------------------------ page kinds
def _kinds(cfg: Cohere2MoeConfig) -> tuple:
    """((name, horizon, the layers of the kind), ...): ``full`` first; a
    kind no layer is of is left out."""
    kinds = (("full", None, FULL), ("window", cfg.sliding_window, SLIDING))
    return tuple(
        (name, horizon, tuple(i for i, t in enumerate(cfg.layer_types)
                              if t == which))
        for name, horizon, which in kinds if which in cfg.layer_types)


def page_kinds(cfg: Cohere2MoeConfig) -> tuple:
    """The family's page kinds, ``(name, horizon)`` each
    (``nornicdb_tpu/ragged.py``): what the scheduler keeps a pool, a table a
    lane and a free list for."""
    return tuple((name, horizon) for name, horizon, _ in _kinds(cfg))


def _place(cfg: Cohere2MoeConfig) -> list:
    """layer -> (its kind's index, its layer in that kind's pool)."""
    place = [None] * cfg.num_hidden_layers
    for k, (_, _, layers) in enumerate(_kinds(cfg)):
        for at, li in enumerate(layers):
            place[li] = (k, at)
    return place


def init_pages(cfg: Cohere2MoeConfig, num_pages: tuple,
               page_size: int) -> tuple:
    """One K/V pool a kind, ``num_pages[k]`` pages each: ``(layers of the
    kind, 2[k|v], pages, page_size, kv heads x head_dim)``.  Page 0 of each
    is its null page."""
    return tuple(
        jnp.zeros((len(layers), 2, n, page_size, cfg.kv_row),
                  jnp.dtype(cfg.dtype))
        for (_, _, layers), n in zip(_kinds(cfg), num_pages, strict=True))


def num_pages(pools: tuple) -> tuple:
    """Pages of each kind's pool (null page included)."""
    return tuple(pool.shape[2] for pool in pools)


# --------------------------------------------------------------- weights
def init_params(cfg: Cohere2MoeConfig, key: jax.Array) -> dict:
    """Seeded weights: N(0, 1/fan_in) matrices, a 0.02 token table (tied
    head), unit norm scales.  Only the held experts are made."""
    dt = jnp.dtype(cfg.dtype)
    h, width = cfg.hidden_size, cfg.intermediate_size
    hq = cfg.num_attention_heads * cfg.head_dim

    def mat(k, *shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def mlp(k, count):
        k = jax.random.split(k, 3)
        return {"gate": mat(k[0], count, h, width, fan_in=h),
                "up": mat(k[1], count, h, width, fan_in=h),
                "down": mat(k[2], count, width, h, fan_in=width)}

    ones = lambda n: {"scale": jnp.ones((n,), jnp.float32)}  # noqa: E731
    keys = jax.random.split(key, cfg.num_hidden_layers + 1)
    params = {"tok_emb": (jax.random.normal(keys[0], (cfg.vocab_size, h),
                                            jnp.float32) * 0.02).astype(dt),
              "final_norm": ones(h), "blocks": []}
    for li in range(cfg.num_hidden_layers):
        k = jax.random.split(keys[1 + li], 7)
        params["blocks"].append({
            "norm": ones(h),
            "q": {"w": mat(k[0], h, hq, fan_in=h)},
            "k": {"w": mat(k[1], h, cfg.kv_row, fan_in=h)},
            "v": {"w": mat(k[2], h, cfg.kv_row, fan_in=h)},
            "o": {"w": mat(k[3], hq, h, fan_in=hq)},
            "router": mat(k[4], h, cfg.num_experts, fan_in=h),
            "experts": mlp(k[5], cfg.held_experts[1]),
            "shared": mlp(k[6], cfg.num_shared_experts),
        })
    return params


# ----------------------------------------------------------- the layer
def norm(p: dict, x: jax.Array, eps: float) -> jax.Array:
    """Cohere's LayerNorm: centred, scaled, NO bias; statistics in f32."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


def _pair_swap(d: int) -> np.ndarray:
    """(d, d) with ``x @ P = (-x1, x0, -x3, x2, ...)``: the partner of each
    interleaved pair, signed.  One entry a column, +-1: exact in any dtype."""
    p = np.zeros((d, d), np.float32)
    even = np.arange(0, d, 2)
    p[even + 1, even] = -1.0
    p[even, even + 1] = 1.0
    return p


def rope_tables(cfg: Cohere2MoeConfig, max_pos: int):
    """(max_pos, head_dim) cos and sin, each pair's angle on both of its
    columns (interleaved pairs, ``rotary_pct`` 1): angles in float64."""
    d = cfg.head_dim
    inv = 1.0 / cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angles = np.repeat(np.outer(np.arange(max_pos, dtype=np.float64), inv),
                       2, axis=1)
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32))


def rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the interleaved pairs ``(2i, 2i + 1)`` of x (N, heads, d) by
    cos/sin (N, d): ``x cos + swap(x) sin``, the swap one small matmul
    against a signed permutation (a strided split of the minor axis costs
    the TPU a relayout)."""
    swapped = jnp.einsum("nhd,de->nhe", x, _pair_swap(x.shape[-1]).astype(
        x.dtype), preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos[:, None] + swapped * sin[:, None]
            ).astype(x.dtype)


def project(cfg: Cohere2MoeConfig, blk: dict, x: jax.Array, rotary):
    """Normed rows x (N, hidden) -> q (N, heads, d), k (N, kv heads, d), v
    (N, kv heads x d); q and k rotated where ``rotary`` = (cos, sin) at the
    rows' positions is given (a window layer), left as they are where it is
    None (a full layer has no positions)."""
    n = x.shape[0]
    q = dense(blk["q"], x).reshape(n, cfg.num_attention_heads, cfg.head_dim)
    k = dense(blk["k"], x).reshape(n, cfg.num_key_value_heads, cfg.head_dim)
    if rotary is not None:
        q, k = rope(q, *rotary), rope(k, *rotary)
    return q, k, dense(blk["v"], x)


def route(cfg: Cohere2MoeConfig, router: jax.Array, x: jax.Array):
    """x (N, hidden) -> (ids (N, k), gates (N, k)): sigmoid scores in f32
    over ALL router outputs, the ``num_experts_per_tok`` best, each gate its
    score over the sum of the chosen ones' (wherever they are held)."""
    s = jax.nn.sigmoid(jnp.einsum("nh,he->ne", x.astype(jnp.float32),
                                  router.astype(jnp.float32), precision=_HI))
    top, ids = jax.lax.top_k(s, cfg.num_experts_per_tok)
    return ids, top / top.sum(axis=-1, keepdims=True)


def expert_layer(cfg: Cohere2MoeConfig, blk: dict, x: jax.Array,
                 valid: jax.Array | None = None):
    """``routed + shared`` for normed rows x (N, hidden) on this process's
    share, f32 (N, hidden): the HELD experts' part of the routed sum plus
    the shared experts' average; and the counts over the ``valid`` rows,
    int32 (3,) (``experts.held_gates``)."""
    with jax.named_scope("moe.route"):
        ids, gates = route(cfg, blk["router"], x)
        weight, counts = experts.held_gates(ids, gates, cfg.held_experts,
                                            valid)
    with jax.named_scope("moe.experts"):
        out = experts.held_experts(blk["experts"], x, weight)
    with jax.named_scope("moe.shared"):
        even = jnp.full((x.shape[0], cfg.num_shared_experts),
                        1.0 / cfg.num_shared_experts, jnp.float32)
        out = out + experts.held_experts(blk["shared"], x, even)
    return out, counts


def _logits(params: dict, cfg: Cohere2MoeConfig, h: jax.Array) -> jax.Array:
    x = norm(params["final_norm"], h, cfg.layer_norm_eps)
    return jnp.einsum("...h,vh->...v", x, params["tok_emb"],
                      preferred_element_type=jnp.float32) * cfg.logit_scale


# ------------------------------------------- the step's rows, kind by kind
# what the step appends after ROUTING_COUNTERS (``GenStats`` fields of the
# same names), a pair a kind: the pages its attention blocks gathered and
# scored, summed over their lanes and the kind's layers, and the pages that
# hold a slot those lanes' live queries may see (walked / held = 1.0 where
# every lane of a block is as long as its longest and ends on a block's edge)
WALK_COUNTERS = {"full": ("full_pages_walked", "full_pages_held"),
                 "window": ("window_pages_walked", "window_pages_held")}


def attend_step(cfg: Cohere2MoeConfig, blk: dict, rows: kv_walk.StepRows,
                kind: kv_walk.KindRows, pool: jax.Array, at: int,
                x: jax.Array, rotary, horizon):
    """One layer's attention inside a fused step, over its kind's pool layer
    ``at``: each row's K and V are written once to their (page, slot), then
    the decode block and the chunk block attend
    (``kv_walk.attend_blocks``).  Normed rows x (F, hidden) -> (attention
    through W_o (F, hidden), pool)."""
    f = x.shape[0]
    with jax.named_scope("attn.project"):
        q, k, v = project(cfg, blk, x, rotary)
        pool = pool.at[at, 0, kind.phys, rows.off].set(k.reshape(f, -1))
        pool = pool.at[at, 1, kind.phys, rows.off].set(v)
    o = kv_walk.attend_blocks(cfg.num_key_value_heads, rows, kind, q, pool,
                              at, horizon)
    return dense(blk["o"], o), pool


def _join(h: jax.Array, a: jax.Array, m: jax.Array) -> jax.Array:
    """``x + a + routed + shared``: the parallel block's one addition."""
    return (h.astype(jnp.float32) + a.astype(jnp.float32) + m).astype(h.dtype)


@functools.partial(jax.jit, static_argnames=("cfg",))
def forward(params: dict, cfg: Cohere2MoeConfig,
            input_ids: jax.Array) -> jax.Array:
    """(B, T) -> (B, T, V) f32 logits, causal, no cache: the plain batched
    forward in the configuration's dtype (scoring; generation is served by
    :func:`fused_step`)."""
    b, t = input_ids.shape
    g, d = cfg.num_key_value_heads, cfg.head_dim
    cos, sin = (jnp.tile(a, (b, 1)) for a in rope_tables(cfg, t))
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    h = params["tok_emb"][input_ids].reshape(b * t, -1)
    for blk, kind in zip(params["blocks"], cfg.layer_types, strict=True):
        x = norm(blk["norm"], h, cfg.layer_norm_eps)
        q, k, v = project(cfg, blk, x, (cos, sin) if kind == SLIDING else None)
        seen = j <= i
        if kind == SLIDING:
            seen &= j > i - cfg.sliding_window
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(b, t, g, -1, d),
                       k.reshape(b, t, g, d),
                       preferred_element_type=jnp.float32) * d ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v.dtype),
                       v.reshape(b, t, g, d),
                       preferred_element_type=jnp.float32).astype(h.dtype)
        h = _join(h, dense(blk["o"], o.reshape(b * t, -1)),
                  expert_layer(cfg, blk, x)[0])
    return _logits(params, cfg, h).reshape(b, t, -1)


@functools.partial(jax.jit, static_argnames=("cfg", "lmax", "w", "tq"),
                   donate_argnums=(3,))
def parallel_moe_fused_step(params, cfg: Cohere2MoeConfig, meta: jax.Array,
                            pages: tuple, *, lmax: int, w: tuple, tq: int,
                            prev=None):
    """One fused prefill+decode step over the pools, a kind each, on the
    engine's flat rows (``kv_walk.plan_step``, each kind with its horizon;
    ``w`` a table width a kind).  A
    layer writes each row's K and V to the row's page of the layer's KIND
    and attends that kind's tables; attention and the expert layer read the
    same normed rows and join the stream together.  Returns ``(ints, logits,
    pages)``: ``ints`` = the ``lmax`` greedy ids followed by the step's
    counts in :data:`STEP_COUNTERS` order (assignments on held experts, the
    fullest held expert's rows and the held experts hit, each summed over
    the layers; rows routed = valid rows x layers; pages walked and pages
    held, a pair a kind, zeros for a kind no layer is of), so one
    device-to-host read carries both;
    ``logits`` (lmax, V) f32 for ``logit_rows``; ``pages`` is DONATED."""
    kinds, place = _kinds(cfg), _place(cfg)
    rows = kv_walk.plan_step(
        meta, pages, tuple(horizon for _, horizon, _ in kinds), lmax=lmax,
        w=w, tq=tq, prev=prev)
    f = rows.tokens.shape[0]
    # positions stay inside the full kind's table (a lane's whole history);
    # a stack with no full layer may stand anywhere the model allows
    cos, sin = rope_tables(cfg, w[0] * pages[0].shape[3] if kinds[0][1] is None
                           else cfg.max_position_embeddings)
    rotary = (cos[rows.pos], sin[rows.pos])
    pages = list(pages)
    h = params["tok_emb"][rows.tokens]                # (F, hidden)
    counts = jnp.zeros((3,), jnp.int32)
    for li, blk in enumerate(params["blocks"]):
        k, at = place[li]
        horizon = kinds[k][1]
        x = norm(blk["norm"], h, cfg.layer_norm_eps)
        a, pages[k] = attend_step(
            cfg, blk, rows, rows.kinds[k], pages[k], at, x,
            rotary if cfg.layer_types[li] == SLIDING else None, horizon)
        m, layer_counts = expert_layer(cfg, blk, x, rows.valid)
        h = _join(h, a, m)
        counts = counts + layer_counts
    logits = _logits(params, cfg, h[jnp.clip(rows.logit_rows, 0, f - 1)])
    routed = rows.valid.sum().astype(jnp.int32) * cfg.num_hidden_layers
    walk = {name: kr.walk * len(layers)
            for kr, (name, _, layers) in zip(rows.kinds, kinds, strict=True)}
    none = jnp.zeros((2,), jnp.int32)                 # a kind no layer is of
    # the run the decode block gathered once for all its lanes: the kind
    # without a horizon's, ONE layer (a window kind never shares)
    run = sum(kr.shared_pages for kr in rows.kinds)
    ints = jnp.concatenate([jnp.argmax(logits, axis=-1).astype(jnp.int32),
                            counts, routed[None],
                            *(walk.get(name, none) for name in WALK_COUNTERS),
                            run[None]])
    return ints, logits, tuple(pages)


# the decoder-family seam (genserve/engine.py), with :func:`page_kinds`,
# :func:`init_pages` and :func:`num_pages`
fused_step = parallel_moe_fused_step
# what ``ints`` carries after the ids (nornicdb_tpu/ragged.py)
STEP_COUNTERS = ROUTING_COUNTERS + tuple(
    name for pair in WALK_COUNTERS.values() for name in pair) + (
    "shared_run_pages",)
