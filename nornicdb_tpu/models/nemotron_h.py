"""Nemotron 3 Nano's language model in JAX (``model_type`` ``nemotron_h``;
huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``):
a stack whose every layer is ONE mixer, by the config's pattern string, a
Mamba-2 layer (``M``), an expert layer (``E``) or attention (``*``), told
which of the routed experts this process holds.

For a layer ``l`` with stream ``x``::

    x'     = x + mixer_l(RMSNorm_l(x))   # w * x / sqrt(mean(x^2) + eps)

    M      [z | xBC | dt] = x W_in       # 4096 | 6144 | 64 at the published
                                         # sizes (d_inner = heads x head dim)
           xBC_t = silu(sum_{j<4} w_j * xBC_{t-3+j} + b)   # depthwise, causal
           [x' | B | C] = xBC            # 4096 | 8 x 128 | 8 x 128
           dt  = softplus(dt + dt_bias);  A = -exp(A_log), a head
           S_h = exp(dt_h A_h) S_h + dt_h x'_h (x) B_g     # g = h // (H / G);
           y_h = S_h C_g + D_h x'_h                        # S_h float32
           out = RMSNorm_grouped(y * silu(z)) W_out       # groups of d_inner/G
    E      s = sigmoid(x W_r)            # f32; the 6 best of s + bias; g_i =
                                         # s_i / sum of the chosen x 2.5
           out = sum_i g_i E_i(x) + S(x) # E(x) = W_down relu(W_up x)^2
    *      grouped-query attention, 32 heads over 2 K/V heads of 128, causal,
           NO positions (the Mamba layers carry order), scale head_dim^-0.5

and ``logits = RMSNorm_f(x) W_head`` over an untied head.

Two kinds of cache state side by side (:func:`page_kinds`,
``nornicdb_tpu/ragged.py``): the attention layers keep K/V rows a token in
PAGES (Qwen's pool layout, walked by ``models/kv_walk.py``); the Mamba
layers keep no row at all but one fixed block a lane, the convolution's
last ``conv_kernel - 1`` inputs and the SSM state, in a pool of SLOTS.  A
step reads each lane's state from the slot ``meta`` names and writes the
advanced state to the slot it names (they differ where a lane begins from a
snapshot, or from nothing, or leaves a snapshot behind); a write to the
null slot is dropped, so lanes without a sequence and padding rows advance
nothing and slot 0 stays zeros.  Decode rows are one plain step of the
recurrence a lane (:func:`ssm_step`; on a TPU in place over the pool's
slots, :func:`step_slots_in_place`); the chunk lane's rows run the chunked
(matmul) form of the same recurrence from the lane's state
(:func:`ssd_block`), rows that are no token at ``dt = 0``.

``held_experts = (first, count)`` says which routed experts this process
holds (expert parallelism; ``models/experts.py``): the router keeps its
published outputs and top-k and the gates are normalised over the chosen
wherever they live; the routed sum runs over the held ones, the shared
expert is computed whole, and what the absent experts would add is left
out.  Nothing here stands in for the other ranks or their exchange.

Read from the source where its config does not settle it (the benchmark's
configuration lists these under ``assumed``): no position embedding in the
attention layers (``rope_theta`` / ``partial_rotary_factor`` are unused by
the family's own code); ``dt`` is not clamped (no ``time_step_limit`` key);
``n_group`` 1 / ``topk_group`` 1: the router has no groups (``n_groups`` is
Mamba's); the state is kept in float32.  Departure from the checkpoint's
layout, none from its mathematics: an expert's two matrices are stacked
over the held (or shared) experts.

``NemotronHConfig()`` is the published language model.  Presets:
NEMOTRON_3_NANO_EP8_27L (one of 8 expert-parallel ranks, the first 27
layers, 1/8 vocabulary: the benchmark's cut), NEMOTRON_H_SMALL (tests).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nornicdb_tpu.models import experts, kv_walk
from nornicdb_tpu.models.layers import dense, rms_norm
from nornicdb_tpu.ragged import NULL_PAGE, ROUTING_COUNTERS, STATE, split_state

_HI = jax.lax.Precision.HIGHEST
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = _PATTERN   # one mixer a layer
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8                # Mamba's B/C groups (not the router's)
    ssm_state_size: int = 128
    conv_kernel: int = 4
    n_routed_experts: int = 128      # the router's outputs, as published
    held_experts: tuple = (0, 128)   # (first, count) of them held here
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.hybrid_override_pattern) != self.num_hidden_layers \
                or not set(self.hybrid_override_pattern) <= {
                    MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(
                "hybrid_override_pattern names each of num_hidden_layers "
                f"layers {MAMBA!r}, {EXPERTS!r} or {ATTENTION!r}")

    @property
    def kv_row(self) -> int:
        """A cache slot's K (or V): the K/V heads side by side."""
        return self.num_key_value_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        """Mamba's inner width: heads x head dim (NOT expand x hidden)."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """What the convolution runs over: x' | B | C."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def layers_of(self, mixer: str) -> tuple:
        return tuple(i for i, m in enumerate(self.hybrid_override_pattern)
                     if m == mixer)


NEMOTRON_3_NANO_EP8_27L = NemotronHConfig(
    vocab_size=16384, num_hidden_layers=27,
    hybrid_override_pattern=_PATTERN[:27], held_experts=(0, 16))
NEMOTRON_H_SMALL = NemotronHConfig(
    vocab_size=512, hidden_size=64, num_hidden_layers=7,
    hybrid_override_pattern="MEM*EM*", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, n_routed_experts=16,
    held_experts=(0, 16), num_experts_per_tok=4, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=64,
)


# ------------------------------------------------------------ cache state
def page_kinds(cfg: NemotronHConfig) -> tuple:
    """The family's kinds of cache state, ``(name, horizon)`` each
    (``nornicdb_tpu/ragged.py``): K/V pages with a table a lane for the
    attention layers, a STATE kind (one slot a lane) for the Mamba
    layers."""
    return (("full", None), ("state", STATE))


def init_pages(cfg: NemotronHConfig, num_pages: tuple,
               page_size: int) -> tuple:
    """``(K/V pool, state pool)``: the first ``(attention layers, 2[k|v],
    pages, page_size, kv heads x head_dim)``, Qwen's layout; the second a
    slot a lane, ``conv`` ``(Mamba layers, slots, conv_kernel - 1,
    conv_dim)`` in the served dtype (a layer's last inputs, as it computed
    them) and ``ssm`` ``(Mamba layers, slots, heads, head dim, state)`` in
    float32.  Page 0 and slot 0 are null."""
    n_kv, n_slots = num_pages
    n_m = len(cfg.layers_of(MAMBA))
    dt = jnp.dtype(cfg.dtype)
    return (jnp.zeros((len(cfg.layers_of(ATTENTION)), 2, n_kv, page_size,
                       cfg.kv_row), dt),
            {"conv": jnp.zeros((n_m, n_slots, cfg.conv_kernel - 1,
                                cfg.conv_dim), dt),
             "ssm": jnp.zeros((n_m, n_slots, cfg.mamba_num_heads,
                               cfg.mamba_head_dim, cfg.ssm_state_size),
                              jnp.float32)})


def num_pages(pools: tuple) -> tuple:
    """(K/V pages, state slots), the null ones included."""
    return pools[0].shape[2], pools[1]["ssm"].shape[1]


# --------------------------------------------------------------- weights
def dt_bias_of(cfg: NemotronHConfig, key: jax.Array) -> jax.Array:
    """The inverse softplus of a log-uniform draw in [time_step_min,
    time_step_max], floored at time_step_floor (Mamba-2's own
    initialisation, the config's keys): with ``A = -(1 .. heads)`` a head
    remembers from one to about a thousand tokens."""
    lo, hi = np.log(cfg.time_step_min), np.log(cfg.time_step_max)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        key, (cfg.mamba_num_heads,), jnp.float32) * (hi - lo) + lo),
        cfg.time_step_floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def init_params(cfg: NemotronHConfig, key: jax.Array) -> dict:
    """Seeded weights: N(0, 1/fan_in) matrices, a 0.02 token table, an
    untied head, unit norm scales, ``A_log = log(1 .. heads)``, ``D = 1``,
    :func:`dt_bias_of`, a zero ``router_bias``.  A block holds its own
    mixer's matrices only, and of the routed experts the held ones."""
    dt = jnp.dtype(cfg.dtype)
    h = cfg.hidden_size
    hq = cfg.num_attention_heads * cfg.head_dim
    heads = cfg.mamba_num_heads

    def mat(k, *shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def mlp(k, count, width):
        k = jax.random.split(k, 2)
        return {"up": mat(k[0], count, h, width, fan_in=h),
                "down": mat(k[1], count, width, h, fan_in=width)}

    ones = lambda n: {"scale": jnp.ones((n,), jnp.float32)}  # noqa: E731

    def block(mixer, key):
        k = jax.random.split(key, 4)
        if mixer == MAMBA:
            return {
                "in_proj": {"w": mat(k[0], h, cfg.d_inner + cfg.conv_dim
                                     + heads, fan_in=h)},
                "conv": {"w": mat(k[1], cfg.conv_kernel, cfg.conv_dim,
                                  fan_in=cfg.conv_kernel),
                         "b": jnp.zeros((cfg.conv_dim,), jnp.float32)},
                "dt_bias": dt_bias_of(cfg, k[2]),
                "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
                "D": jnp.ones((heads,), jnp.float32),
                "gate_norm": ones(cfg.d_inner),
                "out_proj": {"w": mat(k[3], cfg.d_inner, h,
                                      fan_in=cfg.d_inner)}}
        if mixer == EXPERTS:
            return {
                "router": mat(k[0], h, cfg.n_routed_experts, fan_in=h),
                "router_bias": jnp.zeros((cfg.n_routed_experts,),
                                         jnp.float32),
                "experts": mlp(k[1], cfg.held_experts[1],
                               cfg.moe_intermediate_size),
                "shared": mlp(k[2], 1,
                              cfg.moe_shared_expert_intermediate_size)}
        return {"q": {"w": mat(k[0], h, hq, fan_in=h)},
                "k": {"w": mat(k[1], h, cfg.kv_row, fan_in=h)},
                "v": {"w": mat(k[2], h, cfg.kv_row, fan_in=h)},
                "o": {"w": mat(k[3], hq, h, fan_in=hq)}}

    keys = jax.random.split(key, cfg.num_hidden_layers + 2)
    return {
        "tok_emb": (jax.random.normal(keys[0], (cfg.vocab_size, h),
                                      jnp.float32) * 0.02).astype(dt),
        "lm_head": {"w": mat(keys[1], h, cfg.vocab_size, fan_in=h)},
        "final_norm": ones(h),
        "blocks": [{"norm": ones(h), **block(mixer, keys[2 + li])}
                   for li, mixer in enumerate(cfg.hybrid_override_pattern)]}


# ------------------------------------------------------ the expert layer
def route(cfg: NemotronHConfig, blk: dict, x: jax.Array):
    """x (N, hidden) -> (ids (N, k), gates (N, k)): sigmoid scores in f32
    over ALL router outputs, the ``num_experts_per_tok`` best of score +
    ``router_bias`` (the bias selects only), each gate its score over the
    sum of the chosen ones' (wherever they are held), times
    ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(jnp.einsum(
        "nh,he->ne", x.astype(jnp.float32), blk["router"].astype(jnp.float32),
        precision=_HI))
    _, ids = jax.lax.top_k(s + blk["router_bias"], cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, chosen / chosen.sum(axis=-1, keepdims=True) \
        * cfg.routed_scaling_factor


@functools.partial(jax.jit, static_argnames=("cfg",))
def expert_layer(cfg: NemotronHConfig, blk: dict, x: jax.Array,
                 valid: jax.Array | None = None):
    """``routed + shared`` for normed rows x (N, hidden) on this process's
    share, f32 (N, hidden): the HELD experts' part of the routed sum plus
    the shared expert; and the counts over the ``valid`` rows, int32 (3,)
    (``experts.held_gates``).  Jitted, so a step lowers it once for all its
    expert layers."""
    with jax.named_scope("moe.route"):
        ids, gates = route(cfg, blk, x)
        weight, counts = experts.held_gates(ids, gates, cfg.held_experts,
                                            valid)
    with jax.named_scope("moe.experts"):
        out = experts.held_experts(blk["experts"], x, weight)
    with jax.named_scope("moe.shared"):
        out = out + experts.held_experts(
            blk["shared"], x, jnp.ones((x.shape[0], 1), jnp.float32))
    return out, counts


# -------------------------------------------------------- the Mamba layer
def ssd_block(x, dt, a, b, c, s0):
    """The Mamba-2 recurrence over blocks of rows, each from its lane's
    state, in the chunked (matmul) form: x (L, T, H, P), dt (L, T, H) (0
    for a row that is no token: it neither decays nor feeds the state), a
    (H,) = -exp(A_log), b and c (L, T, G, N), s0 (L, H, P, N), all f32 ->
    (y (L, T, H, P) without the D term, the state behind each lane's last
    row (L, H, P, N)).  With ``cum_t = sum_{u <= t} dt_u a``::

        y_t = exp(cum_t) C_t S0
              + sum_{s <= t} exp(cum_t - cum_s) dt_s (C_t B_s) x_s
        S_T = exp(cum_T) S0 + sum_s exp(cum_T - cum_s) dt_s x_s (x) B_s

    every exponent <= 0.  The form for a CHUNK's rows (``T = tq`` up to
    64): its matmuls do a block of rows at once from one read of the state.
    At ``T = 1`` it is one step of the recurrence a lane and the same
    numbers as :func:`ssm_step`, which the decode block takes instead: here
    a lane's whole state would be a matmul's operand for ONE row."""
    lanes, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    cum = jnp.cumsum(dt * a, axis=1).reshape(lanes, t, g, r)
    dt = dt.reshape(lanes, t, g, r)
    x = x.reshape(lanes, t, g, r, p)
    s0 = s0.reshape(lanes, g, r, p, n)
    y = jnp.einsum("ltgn,lgrpn->ltgrp", c, s0, precision=_HI) \
        * jnp.exp(cum)[..., None]
    cb = jnp.einsum("ltgn,lsgn->ltsg", c, b, precision=_HI)
    at = jnp.arange(t)
    seen = (at[:, None] >= at[None, :])[None, :, :, None, None]
    decay = jnp.exp(jnp.where(seen, cum[:, :, None] - cum[:, None], -jnp.inf))
    y = y + jnp.einsum("ltsgr,lsgrp->ltgrp",
                       cb[..., None] * decay * dt[:, None], x, precision=_HI)
    left = jnp.exp(cum[:, -1:] - cum) * dt              # (L, T, G, R)
    s_t = s0 * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
        "lsgrp,lsgn->lgrpn", x * left[..., None], b, precision=_HI)
    return y.reshape(lanes, t, h, p), s_t.reshape(lanes, h, p, n)


def ssm_step(x, dt, a, b, c, s0):
    """ONE plain step of the recurrence a lane, :func:`ssd_block`'s own
    formula at ``T = 1`` written out: x (L, H, P), dt (L, H) (0: the lane
    keeps its state), a (H,), b and c (L, G, N), s0 (L, H, P, N), all f32 ->
    (y (L, H, P) without the D term, the advanced state (L, H, P, N))::

        S_1 = exp(dt a) S0 + dt x (x) B;   y_1 = S_1 C

    elementwise in float32: a lane's state is read once, multiplied and
    added once, reduced once over N and written once, and none of it is a
    matmul's operand."""
    lanes, h, p = x.shape
    g, n = b.shape[1:]
    r = h // g
    s0 = s0.reshape(lanes, g, r, p, n)
    keep = jnp.exp(dt * a).reshape(lanes, g, r, 1, 1)
    feed = (dt[..., None] * x).reshape(lanes, g, r, p, 1)
    s_1 = keep * s0 + feed * b[:, :, None, None, :]
    y = (s_1 * c[:, :, None, None, :]).sum(axis=-1)
    return y.reshape(lanes, h, p), s_1.reshape(lanes, h, p, n)


def step_slots(ssm, src, dst, alive, x, dt, a, b, c):
    """:func:`ssm_step` over the flat pool ``ssm`` (slots, H, P, N): each
    lane's state gathered from slot ``src`` (L,), advanced, and scattered to
    slot ``dst`` (L,) where ``alive`` (a write that is not is dropped) ->
    (y (L, H, P), ssm).  Three passes over the lanes' states, as XLA has
    them; what a backend without :func:`step_slots_in_place` runs."""
    y, s_1 = ssm_step(x, dt, a, b, c, ssm[src])
    to = jnp.where(alive, dst, ssm.shape[0])
    return y, ssm.at[to].set(s_1, mode="drop")


def _step_kernel(src, dst, alive, keep, feed, b, c, s0, y, s_1, *, heads, r,
                 turn):
    """One lane's block of ``heads`` heads: refs keep (L, H) in SMEM, feed
    and y (1, 1, P, heads) (a head a lane of the tile, so that a head's
    ``dt x`` is a column over P and broadcasts over N), b and c (1, G, N), s0
    and s_1 (1, heads, P, N), the lane's slot of the pool read and written.
    A head at a time: 8 vector registers of state, a row of B and of C;
    ``turn`` heads a turn of the loop, so that one head's lane reductions
    run under the next one's loads and multiplies."""
    lane, block = pl.program_id(0), pl.program_id(1)
    written = alive[lane] != 0
    at = jax.lax.broadcasted_iota(jnp.int32, feed.shape[2:], 1)
    fed = feed[0, 0]

    def heads_of(k, out):
        for h in range(turn):
            h = k * turn + h
            g = (block * heads + h) // r
            dtx = jnp.sum(jnp.where(at == h, fed, 0.0), axis=1, keepdims=True)
            new = keep[lane, block * heads + h] * s0[0, h] \
                + dtx * b[0, pl.ds(g, 1), :]
            # a lane that writes no slot is pointed at the null one: zeros
            s_1[0, h] = jnp.where(written, new, 0.0)
            y_h = jnp.sum(new * c[0, pl.ds(g, 1), :], axis=1, keepdims=True)
            out = jnp.where(at == h, y_h, out)
        return out

    y[0, 0] = jax.lax.fori_loop(0, heads // turn, heads_of,
                                jnp.zeros(fed.shape, jnp.float32))


_HEADS_A_TURN = 4   # of the kernel's loop: 2 and 8 are slower on the chip


def step_slots_in_place(ssm, src, dst, alive, x, dt, a, b, c):
    """:func:`step_slots` as ONE pass on a TPU: a Pallas kernel whose blocks
    are slots of the pool itself (the slot numbers prefetched as scalars,
    the pool aliased in and out), so a lane's state comes from its read slot
    into VMEM, is advanced there and goes to its write slot: read once,
    written once, no gathered copy and no scatter.  ``dst`` of a lane that is
    not ``alive`` must be its layer's null slot, which gets zeros.  A step
    never reads a slot that another of its lanes writes (a snapshot is read
    by LATER steps: ``genserve/engine.py`` ``_snapshot``), so the order of
    the lanes does not show.  The heads' loop is a ``fori_loop`` of four
    heads a turn: it lowers in 0.07 s a step class where 16 heads unrolled
    take 0.18 and 32 take 0.33 (a warm start lowers every class:
    :func:`mamba_layer`), and runs at what the pool through VMEM and back
    costs with no arithmetic, where a head a turn takes twice that (PERF.md
    section 6, PR 43)."""
    lanes, h, p = x.shape
    g, n = b.shape[1:]
    # 2 MB of state a block: a lane's 64 heads at the published sizes
    heads = math.gcd(h, max(1, (2 << 20) // (4 * p * n)))
    keep = jnp.exp(dt * a)
    feed = (dt[..., None] * x).reshape(lanes, h // heads, heads, p)
    # an index map is handed (lane, block of heads, src, dst, alive)
    tile = pl.BlockSpec((1, 1, p, heads), lambda i, j, *_: (i, j, 0, 0))
    row = pl.BlockSpec((1, g, n), lambda i, j, *_: (i, 0, 0))
    y, ssm = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads, r=h // g,
                          turn=math.gcd(heads, _HEADS_A_TURN)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(lanes, h // heads),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM), tile, row, row,
                pl.BlockSpec((1, heads, p, n),
                             lambda i, j, src, *_: (src[i], j, 0, 0))],
            out_specs=[
                tile,
                pl.BlockSpec((1, heads, p, n),
                             lambda i, j, src, dst, _: (dst[i], j, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((lanes, h // heads, p, heads),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        input_output_aliases={7: 1}, name="ssm_step",
    )(src, dst, alive.astype(jnp.int32), keep, feed.swapaxes(2, 3), b, c,
      ssm)
    return y.swapaxes(2, 3).reshape(lanes, h, p), ssm


def _lane_block(cfg, blk, state, at, xbc, dt, lane, slot, live, read, write):
    """One block of lanes through a Mamba layer's convolution and
    recurrence: the rows' xBC (F, conv_dim) and dt (F, H) scattered to
    ``(lane, slot)`` of an (L, T) block (a row that is not the block's falls
    out of bounds and is dropped), ``live`` (L, T) its rows that are tokens,
    each lane's state read from slot ``read`` (L,) of layer ``at`` and the
    advanced state written to slot ``write`` (a write to the null slot is
    dropped).  The block's static ``T`` picks the recurrence's form: one
    row a lane (the decode block, ``(lmax - 2, 1)`` in every step class) is
    the plain step, elementwise over the state, where the step is lowered
    for a TPU one pass over the pool's own slots
    (:func:`step_slots_in_place`) and elsewhere gather, :func:`ssm_step`,
    scatter (:func:`step_slots`); a block of rows (the chunk block, ``(1,
    tq)``) is :func:`ssd_block` between a gather and a scatter of its one
    lane.  One algorithm at two shapes, no option and no step class of its
    own.  Returns (y + D x' (L, T, d_inner) f32, state)."""
    lanes, t = live.shape
    heads, p, n = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size
    g, keep = cfg.n_groups, cfg.conv_kernel - 1
    layers, slots = state["ssm"].shape[:2]
    conv = state["conv"].reshape((layers * slots,) + state["conv"].shape[2:])
    ssm = state["ssm"].reshape((layers * slots,) + state["ssm"].shape[2:])
    with jax.named_scope("ssm.conv"):
        new = jnp.zeros((lanes, t, cfg.conv_dim), xbc.dtype).at[
            lane, slot].set(xbc, mode="drop")
        seq = jnp.concatenate([conv[at * slots + read], new], axis=1)
        w = blk["conv"]["w"].astype(jnp.float32)
        out = sum(w[j] * seq[:, j:j + t].astype(jnp.float32)
                  for j in range(keep + 1)) + blk["conv"]["b"]
        out = jax.nn.silu(out)
        # the inputs behind the lane's last token (a lane without a token
        # keeps what it read)
        last = live.sum(axis=1)[:, None] + jnp.arange(keep)
        conv_new = jnp.take_along_axis(seq, last[:, :, None], axis=1)
    with jax.named_scope("ssm.scan"):
        x = out[..., :cfg.d_inner].reshape(lanes, t, heads, p)
        b = out[..., cfg.d_inner:cfg.d_inner + g * n].reshape(lanes, t, g, n)
        c = out[..., cfg.d_inner + g * n:].reshape(lanes, t, g, n)
        dt = jnp.zeros((lanes, t, heads), jnp.float32).at[lane, slot].set(
            dt, mode="drop") * live[..., None]
        a = -jnp.exp(blk["A_log"])
        src, dst, alive = at * slots + read, at * slots + write, \
            write != NULL_PAGE
        to = jnp.where(alive, dst, layers * slots)
        if t == 1:
            y, ssm = jax.lax.platform_dependent(
                ssm, src, dst, alive, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0],
                tpu=step_slots_in_place, default=step_slots)
            y = y[:, None]
        else:
            y, ssm_new = ssd_block(x, dt, a, b, c, ssm[src])
            ssm = ssm.at[to].set(ssm_new, mode="drop")
        y = y + blk["D"][:, None] * x
        state = {"conv": conv.at[to].set(conv_new, mode="drop")
                 .reshape(state["conv"].shape),
                 "ssm": ssm.reshape(state["ssm"].shape)}
    return y.reshape(lanes, t, cfg.d_inner), state


class StateRows(NamedTuple):
    """A step's rows as its Mamba layers see them, a block each: (lane (F,)
    and slot (F,) of every row in the block, out of bounds for a row of
    another block; live (L, T) the block's rows that are tokens; the slots
    its lanes read (L,) and write (L,))."""
    dec: tuple            # one row a lane, the ``lmax - 2`` decode lanes
    chunk: tuple | None   # the chunk lane's ``tq`` rows; None: decode only
    is_chunk: jax.Array   # (F,)
    advanced: jax.Array   # () live rows of lanes that write a slot


def state_rows(rows: kv_walk.StepRows, read, write, lmax: int) -> StateRows:
    n = lmax - 2
    # ``dec_lane`` sends every row that is no decode row to lane n: out of
    # this block's bounds
    dec = (rows.dec_lane, jnp.zeros_like(rows.dec_lane),
           rows.pos_dec[:n] >= 0, read[:n], write[:n])
    blocks = [dec]
    chunk = None
    if rows.chunk_row is not None:
        chunk = (rows.chunk_row, rows.slot_c, rows.pos_chk >= 0,
                 read[n:n + 1], write[n:n + 1])
        blocks.append(chunk)
    advanced = sum((live & (to != NULL_PAGE)[:, None]).sum()
                   for _, _, live, _, to in blocks)
    return StateRows(dec, chunk, rows.is_chunk, advanced.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("cfg",))
def mamba_layer(cfg: NemotronHConfig, blk: dict, lanes: StateRows,
                x: jax.Array, state: dict, at):
    """One Mamba-2 layer inside a fused step, over state pool layer ``at``
    (a value: a step lowers this once for all its Mamba layers, and a warm
    start traces and lowers every step class before it can ask the compile
    cache, so what is traced here counts once a class and what is traced a
    layer twelve-fold): normed rows x (F, hidden) -> (the mixer's output
    (F, hidden), state).  The decode block advances each lane by the plain
    step, the chunk block by the chunked form (:func:`_lane_block`)."""
    with jax.named_scope("ssm.project"):
        proj = dense(blk["in_proj"], x)
        z = proj[:, :cfg.d_inner]
        xbc = proj[:, cfg.d_inner:cfg.d_inner + cfg.conv_dim]
        dt = jax.nn.softplus(proj[:, cfg.d_inner + cfg.conv_dim:]
                             .astype(jnp.float32) + blk["dt_bias"])
    y, state = _lane_block(cfg, blk, state, at, xbc, dt, *lanes.dec)
    y = y[jnp.minimum(lanes.dec[0], y.shape[0] - 1), 0]
    if lanes.chunk is not None:
        y_chk, state = _lane_block(cfg, blk, state, at, xbc, dt, *lanes.chunk)
        y = jnp.where(lanes.is_chunk[:, None], y_chk[0, lanes.chunk[1]], y)
    with jax.named_scope("ssm.gate"):
        y = y * jax.nn.silu(z.astype(jnp.float32))
        grouped = y.reshape(y.shape[0], cfg.n_groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True) + cfg.norm_eps)
        y = (grouped.reshape(y.shape) * blk["gate_norm"]["scale"]).astype(
            x.dtype)
    with jax.named_scope("ssm.out"):
        return dense(blk["out_proj"], y), state


# ------------------------------------------------------- the attention layer
def attend_step(cfg: NemotronHConfig, blk: dict, rows: kv_walk.StepRows,
                pool: jax.Array, at: int, x: jax.Array):
    """One attention layer inside a fused step, over K/V pool layer ``at``:
    each row's K and V are written once to their (page, slot), then the
    decode block and the chunk block attend (``kv_walk.attend_blocks``).  No
    positions.  Normed rows x (F, hidden) -> (attention through W_o (F,
    hidden), pool)."""
    f, kind = x.shape[0], rows.kinds[0]
    with jax.named_scope("attn.project"):
        q = dense(blk["q"], x).reshape(f, cfg.num_attention_heads,
                                       cfg.head_dim)
        pool = pool.at[at, 0, kind.phys, rows.off].set(dense(blk["k"], x))
        pool = pool.at[at, 1, kind.phys, rows.off].set(dense(blk["v"], x))
    o = kv_walk.attend_blocks(cfg.num_key_value_heads, rows, kind, q, pool,
                              at, None)
    return dense(blk["o"], o), pool


# ------------------------------------------------------------- the step
@functools.partial(jax.jit, static_argnames=("cfg", "lmax", "w", "tq"),
                   donate_argnums=(3,))
def hybrid_fused_step(params, cfg: NemotronHConfig, meta: jax.Array,
                      pages: tuple, *, lmax: int, w: tuple, tq: int,
                      prev=None):
    """One fused prefill+decode step over the K/V pool and the state pool,
    on the engine's flat rows (``kv_walk.plan_step`` for the page kind; the
    state kind's read and write slots a lane, ``ragged.split_state``).  A
    layer runs its ONE mixer: an attention layer writes each row's K and V
    and attends the lanes' tables, a Mamba layer advances each lane's state
    from its read slot into its write slot, an expert layer routes.
    Returns ``(ints, logits, pages)``: ``ints`` = the ``lmax`` greedy ids
    followed by the step's counts in :data:`STEP_COUNTERS` order, so one
    device-to-host read carries both; ``logits`` (lmax, V) f32 for
    ``logit_rows``; ``pages`` is DONATED."""
    kv, state = pages
    paged, read, write = split_state(meta, lmax)
    rows = kv_walk.plan_step(paged, (kv,), (None,), lmax=lmax, w=w[:1], tq=tq,
                             prev=prev)
    lanes = state_rows(rows, read, write, lmax)
    f = rows.tokens.shape[0]
    place = {li: at for mixer in (MAMBA, ATTENTION)
             for at, li in enumerate(cfg.layers_of(mixer))}
    h = params["tok_emb"][rows.tokens]                # (F, hidden)
    counts = jnp.zeros((3,), jnp.int32)
    for li, (blk, mixer) in enumerate(zip(
            params["blocks"], cfg.hybrid_override_pattern, strict=True)):
        x = rms_norm(blk["norm"], h, cfg.norm_eps)
        if mixer == MAMBA:
            out, state = mamba_layer(cfg, blk, lanes, x, state,
                                     jnp.int32(place[li]))
        elif mixer == ATTENTION:
            out, kv = attend_step(cfg, blk, rows, kv, place[li], x)
        else:
            out, layer_counts = expert_layer(cfg, blk, x, rows.valid)
            counts = counts + layer_counts
        h = (h.astype(jnp.float32) + out.astype(jnp.float32)).astype(h.dtype)
    x = rms_norm(params["final_norm"], h[jnp.clip(rows.logit_rows, 0, f - 1)],
                 cfg.norm_eps)
    logits = jnp.einsum("nh,hv->nv", x, params["lm_head"]["w"],
                        preferred_element_type=jnp.float32)
    n_attn = len(cfg.layers_of(ATTENTION))
    kind, ps = rows.kinds[0], kv.shape[3]
    table = (kind.dec_tables.shape[0] + (tq > 1)) * kind.dec_tables.shape[1]
    ints = jnp.concatenate([
        jnp.argmax(logits, axis=-1).astype(jnp.int32), counts,
        (rows.valid.sum() * len(cfg.layers_of(EXPERTS)))[None].astype(
            jnp.int32),
        jnp.stack([kind.walk[0], jnp.int32(table)]) * (ps * n_attn),
        kind.shared_pages[None],
        (lanes.advanced * len(cfg.layers_of(MAMBA)))[None]])
    return ints, logits, (kv, state)


# the decoder-family seam (genserve/engine.py), with :func:`page_kinds`,
# :func:`init_pages` and :func:`num_pages`
fused_step = hybrid_fused_step
# what ``ints`` carries after the ids (nornicdb_tpu/ragged.py): the routing
# counts, the walk's (slots gathered and scored, the slots of the lanes'
# whole tables, the shared run's pages: models/kv_walk.py) and the rows that
# advanced a live state, summed over the Mamba layers
STEP_COUNTERS = ROUTING_COUNTERS + (
    "attn_slots_walked", "attn_slots_table", "shared_run_pages", "ssm_rows")
