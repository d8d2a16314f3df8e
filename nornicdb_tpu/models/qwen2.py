"""Qwen2-architecture decoder (Qwen2.5-0.5B-Instruct shape) in JAX.

Replaces the reference's llama.cpp generation model
(/root/reference/pkg/localllm/llama.go:748 GenerationModel, generate.go) that
powers the Heimdall assistant (pkg/heimdall/scheduler.go:178). Pre-norm
RMSNorm decoder, RoPE, grouped-query attention, SwiGLU MLP, tied embeddings;
greedy/temperature decode with a static-shape KV cache under lax.while_loop
so the whole decode loop is one XLA program.

Presets: QWEN25_05B (real shape), QWEN_SMALL (tests).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from nornicdb_tpu.models.layers import (
    apply_rope,
    dense,
    grouped_attention,
    init_dense,
    init_rms_norm,
    normal_init,
    rms_norm,
    rope_freqs,
)
from nornicdb_tpu.ragged import NULL_PAGE


@dataclass(frozen=True)
class QwenConfig:
    vocab_size: int = 151936
    hidden: int = 896
    layers: int = 24
    heads: int = 14
    kv_heads: int = 2
    intermediate: int = 4864
    max_positions: int = 32768
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    tie_embeddings: bool = True
    dtype: str = "bfloat16"


QWEN25_05B = QwenConfig()
QWEN_SMALL = QwenConfig(
    vocab_size=512, hidden=64, layers=2, heads=4, kv_heads=2,
    intermediate=128, max_positions=256, rope_theta=10000.0,
)


def init_params(cfg: QwenConfig, key: jax.Array) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    head_dim = cfg.hidden // cfg.heads
    keys = jax.random.split(key, cfg.layers + 2)
    params = {
        "tok_emb": normal_init(keys[0], (cfg.vocab_size, cfg.hidden), dtype=dtype),
        "final_norm": init_rms_norm(cfg.hidden),
        "blocks": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(
            keys[1], cfg.hidden, cfg.vocab_size, bias=False, dtype=dtype
        )
    for i in range(cfg.layers):
        k = jax.random.split(keys[2 + i], 7)
        params["blocks"].append(
            {
                "q": init_dense(k[0], cfg.hidden, cfg.heads * head_dim, dtype=dtype),
                "k": init_dense(k[1], cfg.hidden, cfg.kv_heads * head_dim, dtype=dtype),
                "v": init_dense(k[2], cfg.hidden, cfg.kv_heads * head_dim, dtype=dtype),
                "o": init_dense(
                    k[3], cfg.heads * head_dim, cfg.hidden, bias=False, dtype=dtype
                ),
                "attn_norm": init_rms_norm(cfg.hidden),
                "gate": init_dense(
                    k[4], cfg.hidden, cfg.intermediate, bias=False, dtype=dtype
                ),
                "up": init_dense(
                    k[5], cfg.hidden, cfg.intermediate, bias=False, dtype=dtype
                ),
                "down": init_dense(
                    k[6], cfg.intermediate, cfg.hidden, bias=False, dtype=dtype
                ),
                "mlp_norm": init_rms_norm(cfg.hidden),
            }
        )
    return params


def _block(cfg: QwenConfig, blk: dict, h, angles, mask, kv_cache=None, pos=None):
    b, t, _ = h.shape
    head_dim = cfg.hidden // cfg.heads
    x = rms_norm(blk["attn_norm"], h, cfg.rms_eps)
    q = dense(blk["q"], x).reshape(b, t, cfg.heads, head_dim)
    k = dense(blk["k"], x).reshape(b, t, cfg.kv_heads, head_dim)
    v = dense(blk["v"], x).reshape(b, t, cfg.kv_heads, head_dim)
    q = apply_rope(q, angles)
    k = apply_rope(k, angles)
    new_cache = None
    if kv_cache is not None:
        ck, cv = kv_cache  # (B, Tmax, Hkv, Dh)
        ck = jax.lax.dynamic_update_slice(ck, k, (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v, (0, pos, 0, 0))
        new_cache = (ck, cv)
        k, v = ck, cv
    kv_len = k.shape[1]
    o = grouped_attention(q, k.reshape(b, kv_len, -1),
                          v.reshape(b, kv_len, -1), mask)
    h = h + dense(blk["o"], o.reshape(b, t, cfg.heads * head_dim))
    x = rms_norm(blk["mlp_norm"], h, cfg.rms_eps)
    m = dense(blk["down"], jax.nn.silu(dense(blk["gate"], x)) * dense(blk["up"], x))
    return h + m, new_cache


def _logits(params, cfg, h):
    if cfg.tie_embeddings:
        return jnp.einsum(
            "bth,vh->btv", h.astype(jnp.float32),
            params["tok_emb"].astype(jnp.float32),
        )
    return dense(params["lm_head"], h).astype(jnp.float32)


def forward(params: dict, cfg: QwenConfig, input_ids: jax.Array) -> jax.Array:
    """(B, T) -> (B, T, V) logits, causal, no cache (training/scoring path)."""
    b, t = input_ids.shape
    h = params["tok_emb"][input_ids]
    angles = rope_freqs(cfg.hidden // cfg.heads, t, cfg.rope_theta)
    causal = jnp.where(
        jnp.tril(jnp.ones((t, t), bool))[None, None], 0.0, -1e30
    )
    for blk in params["blocks"]:
        h, _ = _block(cfg, blk, h, angles, causal)
    h = rms_norm(params["final_norm"], h, cfg.rms_eps)
    return _logits(params, cfg, h)


def init_kv_cache(cfg: QwenConfig, batch: int, max_len: int) -> list:
    head_dim = cfg.hidden // cfg.heads
    dtype = jnp.dtype(cfg.dtype)
    return [
        (
            jnp.zeros((batch, max_len, cfg.kv_heads, head_dim), dtype),
            jnp.zeros((batch, max_len, cfg.kv_heads, head_dim), dtype),
        )
        for _ in range(cfg.layers)
    ]


@functools.partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill(params, cfg: QwenConfig, input_ids, max_len: int):
    """Run the prompt through the model filling a (B, max_len) KV cache.
    Returns (last_logits (B, V), caches)."""
    b, t = input_ids.shape
    h = params["tok_emb"][input_ids]
    angles = rope_freqs(cfg.hidden // cfg.heads, max_len, cfg.rope_theta)[:t]
    # causal over the cache: query i attends cache slots <= i
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (t, max_len), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (t, max_len), 1)
    mask = jnp.where(k_pos <= q_pos, 0.0, -1e30)[None, None]
    caches = init_kv_cache(cfg, b, max_len)
    new_caches = []
    for blk, cache in zip(params["blocks"], caches):
        h, cache = _block(cfg, blk, h, angles, mask, kv_cache=cache, pos=0)
        new_caches.append(cache)
    h = rms_norm(params["final_norm"], h, cfg.rms_eps)
    return _logits(params, cfg, h)[:, -1, :], new_caches


@functools.partial(
    jax.jit, static_argnames=("cfg", "steps", "temperature", "eos_id")
)
def decode(
    params,
    cfg: QwenConfig,
    first_token: jax.Array,  # (B,)
    caches,
    start_pos: jax.Array,  # scalar: prompt length
    steps: int,
    temperature: float = 0.0,
    key: jax.Array | None = None,
    eos_id: int = -1,
):
    """Greedy/temperature decode `steps` tokens with the static KV cache.
    Returns (B, steps) tokens. The loop is a lax.scan — one XLA program."""
    b = first_token.shape[0]
    max_len = caches[0][0].shape[1]
    full_angles = rope_freqs(cfg.hidden // cfg.heads, max_len, cfg.rope_theta)
    if key is None:
        key = jax.random.PRNGKey(0)

    def step(carry, _):
        tok, caches, pos, key, done = carry
        logits, new_caches = _cached_step(
            params, cfg, tok, caches, pos, full_angles)
        key, sub = jax.random.split(key)
        if temperature > 0:
            nxt = jax.random.categorical(sub, logits / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        nxt = jnp.where(done, eos_id, nxt)
        done = jnp.logical_or(done, nxt == eos_id)
        return (nxt, new_caches, pos + 1, key, done), nxt

    init = (first_token, caches, start_pos, key, jnp.zeros((b,), bool))
    _, toks = jax.lax.scan(step, init, None, length=steps)
    return jnp.transpose(toks)  # (B, steps)


def _cached_step(params, cfg: QwenConfig, token: jax.Array, caches,
                 pos: jax.Array, full_angles: jax.Array):
    """Shared single-token cached decoder body — the ONE implementation
    behind both decode()'s scan and the streaming decode_step, so the
    mask/rope slicing can never diverge between the two paths."""
    max_len = caches[0][0].shape[1]
    h = params["tok_emb"][token[:, None]]
    angles = jax.lax.dynamic_slice(
        full_angles, (pos, 0), (1, full_angles.shape[1]))
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, max_len), 1)
    mask = jnp.where(k_pos <= pos, 0.0, -1e30)[None, None]
    new_caches = []
    for blk, cache in zip(params["blocks"], caches):
        h, cache = _block(cfg, blk, h, angles, mask, kv_cache=cache, pos=pos)
        new_caches.append(cache)
    h = rms_norm(params["final_norm"], h, cfg.rms_eps)
    return _logits(params, cfg, h)[:, 0, :], new_caches


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(3,))
def decode_step(params, cfg: QwenConfig, token: jax.Array, caches,
                pos: jax.Array):
    """ONE cached decode step: (B,) token at position `pos` -> ((B, V)
    logits, advanced caches). The streaming generation path
    (heimdall QwenGenerator.generate_stream) calls this per yielded token.
    Caches are DONATED: XLA aliases the input/output KV buffers, so each
    step updates in place instead of copying the whole cache (the caller
    must not reuse the passed-in caches)."""
    max_len = caches[0][0].shape[1]
    full_angles = rope_freqs(cfg.hidden // cfg.heads, max_len, cfg.rope_theta)
    return _cached_step(params, cfg, token, caches, pos, full_angles)


# -- paged KV cache (genserve continuous-batching decode) --------------------
#
# The dense cache above is per-request (B, Tmax): admitting a new request
# into a running batch means reallocating/copying every sequence's cache to
# a common Tmax.  The paged layout (Ragged Paged Attention, PAPERS.md)
# instead keeps ONE pool of fixed-size pages shared by every sequence, plus
# a per-sequence page table mapping logical pages -> physical pool slots.
# Sequences join/leave the batch by allocating/freeing pages; attention
# block-gathers each sequence's pages into contiguous (S = P*page_size)
# keys and masks by true length.  Physical page 0 is RESERVED as the null/
# scratch page: padded lanes and padded chunk positions route their writes
# there, so a static-shape program never corrupts a live page
# (``NULL_PAGE``, ``pages_for``: nornicdb_tpu/ragged.py).
#
# A cache slot's row is its K (or V) heads side by side, kv_heads * head_dim
# wide: at Qwen2.5's widths 2 x 64 = ONE 128-lane tile.  With (kv_heads,
# head_dim) minor, the 64-wide minor dimension made the TPU compiler put
# another axis minor; the step's scatter and its gather each wanted their
# own layout and every step copied the whole pool there and back (9.4 + 9.0
# ms of the chip at 8,193 pages: PERF.md section 6, PR 31).  The rows reach
# the contraction as stored (``layers.grouped_attention``): no ``repeat_kv``
# copy, which the chip made in f32 at 528 MB a layer, and no split of a row.
# What the block-gather still does: it reads EVERY page of EVERY lane's
# table, live or not (attention over real lengths: ROADMAP G1).


def init_kv_pages(cfg: QwenConfig, num_pages: int, page_size: int) -> jax.Array:
    """One pooled KV buffer: (layers, 2[k|v], num_pages, page_size,
    kv_heads * head_dim), a cache slot's K (or V) heads side by side in
    one row.  Page 0 is the null page (see module note)."""
    head_dim = cfg.hidden // cfg.heads
    return jnp.zeros(
        (cfg.layers, 2, num_pages, page_size, cfg.kv_heads * head_dim),
        jnp.dtype(cfg.dtype),
    )


def _apply_rope_rows(x: jax.Array, angles: jax.Array) -> jax.Array:
    """apply_rope with PER-SEQUENCE positions: x (B, T, H, Dh), angles
    (B, T, Dh/2) — the batched decode step rotates each lane at its own
    cache length, where the dense path's shared scalar pos cannot."""
    xf = x.astype(jnp.float32)
    d2 = x.shape[-1] // 2
    x1, x2 = xf[..., :d2], xf[..., d2:]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _paged_attention(pages, li, page_tables, q, mask):
    """Block-gather one layer's K/V pages for every sequence and attend.
    page_tables: (B, P) physical page ids; q: (B, T, H, Dh).  The rows go
    from the pool to the contraction as they are stored."""
    b, p = page_tables.shape
    _, _, _, ps, row = pages.shape
    # the layer's K (then V) sliced out of the pool first, then gathered:
    # one gather from the whole pool (``pages[li, 0, page_tables]``) moves
    # fewer bytes by the compiler's account and is slower on the chip (9.3
    # against 7.8 ms a decode-only step at 8,193 pages: PERF.md section 6)
    k_all = pages[li, 0][page_tables].reshape(b, p * ps, row)
    v_all = pages[li, 1][page_tables].reshape(b, p * ps, row)
    return grouped_attention(q, k_all, v_all, mask)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(3,))
def paged_decode_step(params, cfg: QwenConfig, tokens: jax.Array,
                      pages: jax.Array, page_tables: jax.Array,
                      lengths: jax.Array):
    """ONE decode step for a whole running batch over the paged pool.

    tokens: (B,) current token per sequence (position = lengths[b]);
    page_tables: (B, P) physical page per logical page (NULL_PAGE pads);
    lengths: (B,) cache slots already written per sequence (padding lanes
    carry length 0 and an all-null table; their logits are garbage the
    scheduler discards).  Returns ((B, V) logits, advanced pages).

    ``pages`` is DONATED: XLA aliases the pool in/out so each step writes
    the two (B, Hkv, Dh) cache lines in place instead of copying the whole
    pool (the caller must drop its reference to the passed-in pool).
    """
    b = tokens.shape[0]
    p = page_tables.shape[1]
    ps = pages.shape[3]
    max_len = p * ps
    head_dim = cfg.hidden // cfg.heads
    full_angles = rope_freqs(head_dim, max_len, cfg.rope_theta)
    angles = full_angles[lengths][:, None, :]  # (B, 1, Dh/2)
    page_idx = jnp.clip(lengths // ps, 0, p - 1)
    phys = jnp.take_along_axis(page_tables, page_idx[:, None], axis=1)[:, 0]
    off = lengths % ps
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, max_len), 1)
    mask = jnp.where(slot <= lengths[:, None], 0.0, -1e30)[:, None, None, :]
    h = params["tok_emb"][tokens[:, None]]
    for li, blk in enumerate(params["blocks"]):
        x = rms_norm(blk["attn_norm"], h, cfg.rms_eps)
        q = dense(blk["q"], x).reshape(b, 1, cfg.heads, head_dim)
        k = dense(blk["k"], x).reshape(b, 1, cfg.kv_heads, head_dim)
        v = dense(blk["v"], x)
        q = _apply_rope_rows(q, angles)
        k = _apply_rope_rows(k, angles)
        pages = pages.at[li, 0, phys, off].set(k.reshape(b, -1))
        pages = pages.at[li, 1, phys, off].set(v[:, 0])
        o = _paged_attention(pages, li, page_tables, q, mask)
        h = h + dense(blk["o"], o.reshape(b, 1, cfg.heads * head_dim))
        x = rms_norm(blk["mlp_norm"], h, cfg.rms_eps)
        h = h + dense(
            blk["down"], jax.nn.silu(dense(blk["gate"], x)) * dense(blk["up"], x)
        )
    h = rms_norm(params["final_norm"], h, cfg.rms_eps)
    return _logits(params, cfg, h)[:, 0, :], pages


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(3,))
def paged_prefill_chunk(params, cfg: QwenConfig, chunk_ids: jax.Array,
                        pages: jax.Array, page_table: jax.Array,
                        start: jax.Array, n_valid: jax.Array):
    """Prefill ONE chunk of one sequence's prompt into its pages.

    chunk_ids: (C,) tokens at positions start..start+C-1 (padded past
    n_valid; padded positions write to the null page); page_table: (P,)
    this sequence's table.  The chunk's queries attend every cache slot
    <= their own position, so a prompt split across chunks sees all
    earlier chunks through the pool — the scheduler interleaves these
    chunks with decode steps of the running batch.  Returns ((V,) logits
    at the last valid position, advanced pages); the logits pick the
    first generated token when this is the final chunk.
    """
    c = chunk_ids.shape[0]
    p = page_table.shape[0]
    ps = pages.shape[3]
    max_len = p * ps
    head_dim = cfg.hidden // cfg.heads
    full_angles = rope_freqs(head_dim, max_len, cfg.rope_theta)
    idx = jax.lax.iota(jnp.int32, c)
    pos = jnp.clip(start + idx, 0, max_len - 1)
    valid = idx < n_valid
    angles = full_angles[pos][None]  # (1, C, Dh/2)
    phys = jnp.where(valid, page_table[jnp.clip(pos // ps, 0, p - 1)],
                     NULL_PAGE)
    off = pos % ps
    slot = jax.lax.broadcasted_iota(jnp.int32, (c, max_len), 1)
    mask = jnp.where(slot <= pos[:, None], 0.0, -1e30)[None, None]
    h = params["tok_emb"][chunk_ids][None]  # (1, C, hidden)
    for li, blk in enumerate(params["blocks"]):
        x = rms_norm(blk["attn_norm"], h, cfg.rms_eps)
        q = dense(blk["q"], x).reshape(1, c, cfg.heads, head_dim)
        k = dense(blk["k"], x).reshape(1, c, cfg.kv_heads, head_dim)
        v = dense(blk["v"], x)
        q = _apply_rope_rows(q, angles)
        k = _apply_rope_rows(k, angles)
        pages = pages.at[li, 0, phys, off].set(k.reshape(c, -1))
        pages = pages.at[li, 1, phys, off].set(v[0])
        o = _paged_attention(pages, li, page_table[None], q, mask)
        h = h + dense(blk["o"], o.reshape(1, c, cfg.heads * head_dim))
        x = rms_norm(blk["mlp_norm"], h, cfg.rms_eps)
        h = h + dense(
            blk["down"], jax.nn.silu(dense(blk["gate"], x)) * dense(blk["up"], x)
        )
    h = rms_norm(params["final_norm"], h, cfg.rms_eps)
    logits = _logits(params, cfg, h)[0]  # (C, V)
    last = jnp.clip(n_valid - 1, 0, c - 1)
    return logits[last], pages


# -- ragged fused step (genserve v2) -----------------------------------------
#
# ONE device program per scheduler iteration serving mixed prefill + decode
# (Ragged Paged Attention, PAPERS.md): the per-phase paged_prefill_chunk /
# paged_decode_step pair above is kept as the primitive the equivalence
# suite drives directly, but the engine now submits a single fused step.
#
# Layout: everything row-independent (embeddings, norms, QKV/O/MLP GEMMs,
# rope) runs on a FLAT (F, 1, hidden) token batch — F is the pow2 bucket
# of (#decode lanes + prefill-chunk valid tokens), so the GEMM work
# scales with real tokens, not lanes x chunk. Only attention needs lane
# structure, and the two ragged shapes are served by two SMALL padded
# blocks inside the one program (one device dispatch) instead of one
# (Lmax, Tq) cross-product block whose Lmax*Tq padded query rows would
# dwarf the ~Lmax+Tq real ones:
#   decode block (Lmax, 1)  single-token lanes, scattered by lane_id
#   chunk  block (1, Tq)    the prefill chunk, scattered by lane_pos
# Lane roles are FIXED by lane_id so the split needs no dynamic count:
# rows with lane_id < Lmax-2 are decode lanes, lane_id == Lmax-2 is THE
# chunk lane, lane_id == Lmax-1 is the dump lane for padding rows.
# Per-row metadata:
#   lane_id (F,)   attention lane for the row (see roles above)
#   lane_pos (F,)  query slot within the lane (decode rows 0, chunk rows
#                  their chunk offset)
#   positions (F,) cache slot the row writes+attends at; -1 = padding
#   logit_rows (Lmax,) flat row indices whose logits the caller wants
#                  (the decode rows + the chunk's last valid row) — the
#                  vocab projection runs on Lmax rows, not F
# All int32 metadata travels in ONE packed host array (one H2D per step
# instead of six — the scheduler dispatches this thousands of times a
# second), and the greedy argmax runs inside the program, so a steady
# step is exactly one dispatch and one (Lmax,) device->host read.
# Padding rows route their page writes to NULL_PAGE and mask every key
# slot; their attention output is garbage never gathered. Masked slots
# add -1e30 before the f32 softmax, so exp underflows to exactly 0.0 and
# null/foreign page content contributes nothing — the fused logits stay
# bit-identical to the sequential chunk-then-decode programs, and to the
# dense path: all of them attend through ``layers.grouped_attention``.
# Both blocks gather whole tables: the decode block all Lmax lanes' W pages
# (chunk and dump lanes included), the chunk block its lane's W pages.


@functools.partial(
    jax.jit, static_argnames=("cfg", "lmax", "w", "tq", "attn_impl"),
    donate_argnums=(3,),
)
def ragged_fused_step(params, cfg: QwenConfig, meta: jax.Array,
                      pages: jax.Array, *, lmax: int, w: int, tq: int,
                      attn_impl: str = "xla"):
    """One fused prefill+decode step over the paged pool.

    meta: the packed int32 array from :func:`pack_ragged_meta` —
    (F,) tokens/lane_id/lane_pos/positions flat rows (see module note),
    (Lmax,) logit_rows, and the (Lmax, P) per-lane page tables (row
    Lmax-2 is the chunk lane's table); ``tq`` is the static query width
    of the chunk attention block — ``tq == 1`` declares a decode-only
    step (no row may carry the chunk lane id); ``attn_impl`` picks "xla"
    (the block-gather — the one serving path, on a TPU too: Mosaic refuses
    the ragged kernel at serving geometry, see ops/pallas_kernels.py),
    "pallas" (ragged TPU kernel) or "pallas_interpret" (kernel under the
    CPU interpreter, tests).
    Returns ((Lmax,) greedy token ids, (Lmax, V) f32 logits for
    ``logit_rows``, advanced pages); ``pages`` is DONATED.
    """
    f = (meta.shape[0] - lmax - lmax * w) // 4
    tokens = meta[:f]
    lane_id = meta[f:2 * f]
    lane_pos = meta[2 * f:3 * f]
    positions = meta[3 * f:4 * f]
    logit_rows = meta[4 * f:4 * f + lmax]
    lane_tables = meta[4 * f + lmax:].reshape(lmax, w)
    p = w
    ps = pages.shape[3]
    max_len = p * ps
    head_dim = cfg.hidden // cfg.heads
    full_angles = rope_freqs(head_dim, max_len, cfg.rope_theta)
    valid = positions >= 0
    pos_c = jnp.clip(positions, 0, max_len - 1)
    angles = full_angles[pos_c][:, None, :]          # (F, 1, Dh/2)
    lane_c = jnp.clip(lane_id, 0, lmax - 1)
    slot_c = jnp.clip(lane_pos, 0, tq - 1)
    is_chunk = lane_id == lmax - 2
    # non-decode rows scatter to the dump lane; chunk/pad collisions
    # there are harmless (masked, never gathered)
    dec_lane = jnp.where(is_chunk, lmax - 1, lane_c)
    phys = jnp.where(
        valid, lane_tables[lane_c, jnp.clip(pos_c // ps, 0, p - 1)],
        NULL_PAGE)
    off = pos_c % ps
    pos_dec = jnp.full((lmax, 1), -1, jnp.int32).at[dec_lane, 0].set(
        jnp.where(valid & ~is_chunk, positions, -1))
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, max_len), 1)
    mask_dec = jnp.where(slot[None] <= pos_dec[:, :, None],
                         0.0, -1e30)[:, None]
    if tq > 1:
        # chunk rows scatter into the (1, Tq) block; every other row's
        # index lands out of bounds on the lane axis and is dropped
        chunk_row = jnp.where(is_chunk & valid, 0, 1)
        pos_chk = jnp.full((1, tq), -1, jnp.int32).at[
            chunk_row, slot_c].set(positions, mode="drop")
        slot_q = jax.lax.broadcasted_iota(jnp.int32, (tq, max_len), 1)
        mask_chk = jnp.where(slot_q[None] <= pos_chk[:, :, None],
                             0.0, -1e30)[:, None]
        chunk_table = lane_tables[lmax - 2][None]
    h = params["tok_emb"][tokens][:, None]           # (F, 1, hidden)
    for li, blk in enumerate(params["blocks"]):
        x = rms_norm(blk["attn_norm"], h, cfg.rms_eps)
        q = dense(blk["q"], x).reshape(f, 1, cfg.heads, head_dim)
        k = dense(blk["k"], x).reshape(f, 1, cfg.kv_heads, head_dim)
        v = dense(blk["v"], x)
        q = _apply_rope_rows(q, angles)
        k = _apply_rope_rows(k, angles)
        pages = pages.at[li, 0, phys, off].set(k.reshape(f, -1))
        pages = pages.at[li, 1, phys, off].set(v[:, 0])
        q_dec = jnp.zeros((lmax, 1, cfg.heads, head_dim), q.dtype)
        q_dec = q_dec.at[dec_lane, 0].set(q[:, 0])
        if attn_impl == "xla":
            o_dec = _paged_attention(pages, li, lane_tables, q_dec,
                                     mask_dec)
        else:
            from nornicdb_tpu.ops import pallas_kernels as _pk

            # the kernel's view of a row: (Hkv, Dh)
            k_heads, v_heads = (
                pages[li, i].reshape(-1, ps, cfg.kv_heads, head_dim)
                for i in (0, 1))
            o_dec = _pk.ragged_paged_attention(
                q_dec, k_heads, v_heads, lane_tables, pos_dec,
                interpret=(attn_impl == "pallas_interpret"))
        o = o_dec[dec_lane, 0]                       # (F, H, Dh)
        if tq > 1:
            q_chk = jnp.zeros((1, tq, cfg.heads, head_dim), q.dtype)
            q_chk = q_chk.at[chunk_row, slot_c].set(q[:, 0], mode="drop")
            if attn_impl == "xla":
                o_chk = _paged_attention(pages, li, chunk_table,
                                         q_chk, mask_chk)
            else:
                o_chk = _pk.ragged_paged_attention(
                    q_chk, k_heads, v_heads, chunk_table,
                    pos_chk, interpret=(attn_impl == "pallas_interpret"))
            o = jnp.where(is_chunk[:, None, None], o_chk[0, slot_c], o)
        o = o[:, None]                               # (F, 1, H, Dh)
        h = h + dense(blk["o"], o.reshape(f, 1, cfg.heads * head_dim))
        x = rms_norm(blk["mlp_norm"], h, cfg.rms_eps)
        h = h + dense(
            blk["down"], jax.nn.silu(dense(blk["gate"], x)) * dense(blk["up"], x)
        )
    h = rms_norm(params["final_norm"], h, cfg.rms_eps)
    h_sel = h[jnp.clip(logit_rows, 0, f - 1)]        # (Lmax, 1, hidden)
    logits = _logits(params, cfg, h_sel)[:, 0, :]
    return jnp.argmax(logits, axis=-1), logits, pages


# -- the decoder-family seam (genserve/engine.py resolves this module from
# type(cfg) and calls these three; ``prefill`` / ``decode_step`` above are
# the optional dense-mode pair) ----------------------------------------------
# the seam's name for the pool maker; ``init_kv_pages`` stays because the
# benchmark's files call it (bench/tests/test_qwen2_reference.py)
init_pages = init_kv_pages


def fused_step(params, cfg: QwenConfig, meta, pages, *, lmax: int, w: int,
               tq: int):
    """The family's step: :func:`ragged_fused_step`, looked up when called
    (fault injectors and tests replace the module attribute)."""
    return ragged_fused_step(params, cfg, meta, pages, lmax=lmax, w=w, tq=tq)


def num_pages(pool: jax.Array) -> int:
    """Pages of a pool made by :func:`init_pages` (null page included)."""
    return pool.shape[2]


def generate(
    params,
    cfg: QwenConfig,
    prompt_ids: list[int],
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    eos_id: int = -1,
    seed: int = 0,
) -> list[int]:
    """Host convenience wrapper: prefill + decode, returns generated ids."""
    ids = jnp.asarray([prompt_ids], jnp.int32)
    max_len = ids.shape[1] + max_new_tokens
    logits, caches = prefill(params, cfg, ids, max_len)
    first = jnp.argmax(logits, axis=-1)
    toks = decode(
        params, cfg, first, caches, jnp.asarray(ids.shape[1] - 1 + 1),
        steps=max_new_tokens - 1, temperature=temperature,
        key=jax.random.PRNGKey(seed), eos_id=eos_id,
    )
    out = [int(first[0])] + [int(t) for t in toks[0]]
    if eos_id >= 0 and eos_id in out:
        out = out[: out.index(eos_id)]
    return out
