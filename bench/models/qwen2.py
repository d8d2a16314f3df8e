"""Model family ``qwen2``: the Qwen2 decoder behind the assistant (Heimdall
chat over ``POST /v1/chat/completions``), served by genserve.

The same five parts as every family file (see ``bge_m3.py``): the program's
config object, seeded weights, ``install`` (what ``cmd_serve`` and
``db.heimdall`` wire), the plain reference with its fp8 control, and the work
functions.  Below ``install`` nothing imports the program or takes anything
it made: the prompt is assembled and tokenized again from the configuration's
stated prompt format (``generator.prompt``), the weights come from the seed.

The reference is the decoder as published (``transformers``
``modeling_qwen2``): pre-norm RMSNorm, rotary embeddings over half-pairs with
``rope_theta``, grouped-query attention with q/k/v biases, SwiGLU, tied
output head; float32 at ``highest`` matmul precision, one sequence at a time,
one layer at a time, attention in blocks of queries: no cache, no batching.
"""

from __future__ import annotations

import functools
import os
import sys

import numpy as np

from reference import fp8, hash_word_ids as tokenize, jax_key
from work import BYTES_OF

ROLE = "generator"
HERE = os.path.dirname(os.path.abspath(__file__))
EOS = 2
QUERY_BLOCK = 512  # reference attention: queries a block (and the padding)


# ------------------------------------------------------- the program's side
def program_config(spec: dict):
    from nornicdb_tpu.models import qwen2

    fields = qwen2.QwenConfig.__dataclass_fields__
    cfg = qwen2.QwenConfig(**{k: v for k, v in spec.items() if k in fields})
    if spec.get("preset"):
        preset = getattr(qwen2, spec["preset"])
        if cfg != preset:
            sys.exit(f"sizes differ from the serve preset {spec['preset']}: "
                     f"{cfg} != {preset}")
    return cfg


def install(db, app_cfg, spec: dict, params):
    """What ``db.heimdall`` wires for a weights-backed assistant: a
    generator with ``params`` / ``cfg`` / ``tokenizer`` handed to
    ``db.set_heimdall_generator``, so that ``_wire_genserve`` builds the
    GenerationEngine from the configuration's ``genserve.*`` options; then
    the engine's own warm-up of every program class, as ``cmd_serve`` calls
    it at boot (its 60 s default would stop a cold compile half way and leave
    the rest to the first requests: the benchmark lets it finish)."""
    from nornicdb_tpu.heimdall.manager import QwenGenerator
    from nornicdb_tpu.models.tokenizer import HashTokenizer

    db.set_heimdall_generator(QwenGenerator(
        cfg=program_config(spec), params=params,
        tokenizer=HashTokenizer(spec["vocab_size"]),
        max_context=spec["max_context"]))
    engine = db.genserve_engine()
    if engine is None:
        sys.exit("db.set_heimdall_generator built no generation engine "
                 "(genserve.enabled is off?)")
    engine.warmup(timeout=float(spec.get("warmup_timeout_s", 1100.0)))
    return engine


# -------------------------------------------------- tokenizer and prompt
@functools.lru_cache(maxsize=4)
def _head_ids(preamble_file: str, template: str, system: str,
              vocab_size: int) -> tuple:
    with open(os.path.join(os.path.dirname(HERE), preamble_file)) as f:
        preamble = f.read()
    return tuple(tokenize(template.format(preamble=preamble, system=system),
                          vocab_size))


def prompt_ids(spec: dict, request: dict) -> list[int]:
    """The token ids the engine is handed for one chat request (the
    generation path encodes a prompt with no special tokens): the
    deployment's prompt format (the assistant's own preamble, then each
    message as ``role: content``, then ``assistant:``), tokenized word by
    word, the tail kept where it passes ``max_context``.  The pieces split
    at white space, so their ids concatenate."""
    p = spec["prompt"]
    head = _head_ids(p["preamble_file"], p["head"], request["system"],
                     spec["vocab_size"])
    tail = tokenize(p["tail"].format(user=request["user"]),
                    spec["vocab_size"])
    return (list(head) + tail)[-spec["max_context"]:]


def shared_prefix_tokens(spec: dict, request: dict) -> int:
    """Tokens every request of the run shares (the preamble and the system
    message): any implementation has to prefill only what follows them."""
    p = spec["prompt"]
    return len(_head_ids(p["preamble_file"], p["head"], request["system"],
                         spec["vocab_size"]))


# --------------------------------------------------------------- weights
def make_params(spec: dict, seed: int) -> dict:
    """Seeded weights in the served dtype, made on the device in ONE jitted
    call, in the tree the decoder's published layout names: the (tied) token
    table, per block q/k/v with biases, o, gate/up/down and two RMSNorm
    scales, the final norm.  Matrices are N(0, 1/fan_in), so attention
    scores and the residual stream are O(1) at every depth; norm scales are
    1 + 0.1 N(0,1), so leaving one out shows."""
    import jax
    import jax.numpy as jnp

    h, i, n = spec["hidden"], spec["intermediate"], spec["layers"]
    dh = h // spec["heads"]
    hq, hkv = spec["heads"] * dh, spec["kv_heads"] * dh
    dt = jnp.dtype(spec["dtype"])
    if not spec.get("tie_embeddings", True):
        sys.exit("bench/models/qwen2.py makes tied embeddings only")

    def make(key):
        ks = jax.random.split(key, 12)
        norm = lambda k, shape, std: (  # noqa: E731
            jax.random.normal(k, shape, jnp.float32) * std).astype(dt)
        scale = lambda k, shape: 1.0 + 0.1 * jax.random.normal(  # noqa: E731
            k, shape, jnp.float32)
        wq = norm(ks[0], (n, h, hq), h ** -0.5)
        wkv = norm(ks[1], (n, 2, h, hkv), h ** -0.5)
        wo = norm(ks[2], (n, hq, h), hq ** -0.5)
        wgu = norm(ks[3], (n, 2, h, i), h ** -0.5)
        wd = norm(ks[4], (n, i, h), i ** -0.5)
        bq = norm(ks[5], (n, hq), 0.02)
        bkv = norm(ks[6], (n, 2, hkv), 0.02)
        norms = scale(ks[7], (n, 2, h))
        blocks = [{
            "q": {"w": wq[l], "b": bq[l]},
            "k": {"w": wkv[l, 0], "b": bkv[l, 0]},
            "v": {"w": wkv[l, 1], "b": bkv[l, 1]},
            "o": {"w": wo[l]},
            "attn_norm": {"scale": norms[l, 0]},
            "gate": {"w": wgu[l, 0]}, "up": {"w": wgu[l, 1]},
            "down": {"w": wd[l]},
            "mlp_norm": {"scale": norms[l, 1]}} for l in range(n)]
        return {"tok_emb": norm(ks[8], (spec["vocab_size"], h), 0.02),
                "final_norm": {"scale": scale(ks[9], (h,))},
                "blocks": blocks}

    return jax.jit(make)(jax_key(seed + 2))


def param_bytes(spec: dict) -> int:
    h, i, n = spec["hidden"], spec["intermediate"], spec["layers"]
    dh = h // spec["heads"]
    hq, hkv = spec["heads"] * dh, spec["kv_heads"] * dh
    mats = spec["vocab_size"] * h + n * (
        h * hq + hq + 2 * (h * hkv + hkv) + hq * h + 3 * h * i)
    return mats * BYTES_OF[spec["dtype"]] + (2 * n + 1) * h * 4


# ------------------------------------------------------------- reference
@functools.lru_cache(maxsize=4)
def _programs(heads: int, kv_heads: int, eps: float, mode: str):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def dense(p, x):
        w = p["w"].astype(f32)
        if mode == "fp8":
            x, w = fp8(x), fp8(w)
        y = jnp.einsum("ti,io->to", x, w, precision=hi)
        return y + p["b"].astype(f32) if "b" in p else y

    def rms(p, x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"].astype(f32)

    def rope(x, cos, sin):  # x (T, H, Dh); cos, sin (T, Dh/2): half-pairs
        d2 = x.shape[-1] // 2
        x1, x2 = x[..., :d2], x[..., d2:]
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    @jax.jit
    def layer(blk, hid, cos, sin):
        t, width = hid.shape
        dh, rep = width // heads, heads // kv_heads
        x = rms(blk["attn_norm"], hid)
        q = rope(dense(blk["q"], x).reshape(t, heads, dh), cos, sin)
        k = rope(dense(blk["k"], x).reshape(t, kv_heads, dh), cos, sin)
        v = dense(blk["v"], x).reshape(t, kv_heads, dh)
        keys = jnp.arange(t)

        def block(args):  # one block of queries against every key
            qb, at = args
            s = jnp.einsum("qgrd,kgd->grqk",
                           qb.reshape(QUERY_BLOCK, kv_heads, rep, dh), k,
                           precision=hi) * dh ** -0.5
            seen = keys[None, :] <= (at + jnp.arange(QUERY_BLOCK))[:, None]
            s = jnp.where(seen[None, None], s, -1e30)
            o = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, -1), v,
                           precision=hi)
            return o.reshape(QUERY_BLOCK, width)

        o = jax.lax.map(block, (q.reshape(-1, QUERY_BLOCK, heads, dh),
                                jnp.arange(0, t, QUERY_BLOCK)))
        hid = hid + dense(blk["o"], o.reshape(t, width))
        x = rms(blk["mlp_norm"], hid)
        return hid + dense(blk["down"], jax.nn.silu(dense(blk["gate"], x))
                           * dense(blk["up"], x))

    @jax.jit
    def head(final_norm, table, hid_rows):
        x, w = rms(final_norm, hid_rows), table.astype(f32)
        if mode == "fp8":
            x, w = fp8(x), fp8(w)
        return jnp.einsum("th,vh->tv", x, w, precision=hi)

    return layer, head


def reference_logits(spec: dict, params: dict, ids: list[int], rows,
                     mode: str = "highest", pad_to: int = 0) -> np.ndarray:
    """(len(rows), vocab) float32 logits at positions ``rows`` of ONE
    sequence: the whole sequence through every layer with no cache, padded
    on the right (causal, so the padding is never seen) to a multiple of
    the query block or to ``pad_to``."""
    import jax.numpy as jnp

    layer, head = _programs(spec["heads"], spec["kv_heads"],
                            float(spec["rms_eps"]), mode)
    t = max(len(ids), pad_to)
    t += -t % QUERY_BLOCK
    padded = np.zeros((t,), np.int32)
    padded[:len(ids)] = ids
    dh = spec["hidden"] // spec["heads"]
    inv = 1.0 / (float(spec["rope_theta"])
                 ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    angles = np.outer(np.arange(t, dtype=np.float64), inv)
    cos = jnp.asarray(np.cos(angles), jnp.float32)
    sin = jnp.asarray(np.sin(angles), jnp.float32)
    hid = params["tok_emb"][padded].astype(jnp.float32)
    for blk in params["blocks"]:
        hid = layer(blk, hid, cos, sin)
    return np.asarray(head(params["final_norm"], params["tok_emb"],
                           hid[np.asarray(rows, np.int32)]))


def greedy_gaps(spec: dict, params: dict, sequences: list, control: bool):
    """For each ``(prompt ids, produced ids)``: at every produced position
    the reference's best logit minus the reference's logit of the token that
    was served (0 wherever the served token is the reference's argmax).
    With ``control`` also the same gap for the token that the fp8 forward of
    the same prompt and tokens puts first.  Returns (gaps, control gaps),
    one array a sequence."""
    pad_to = max(len(p) + len(o) for p, o in sequences)
    gaps, low = [], []
    for prompt, out in sequences:
        ids = list(prompt) + list(out[:-1])
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        ref = reference_logits(spec, params, ids, rows, pad_to=pad_to)
        best = ref.max(axis=1)
        at = np.arange(len(out))
        served = np.clip(np.asarray(out, np.int64), 0, ref.shape[1] - 1)
        gaps.append(best - ref[at, served])
        if control:
            first = reference_logits(spec, params, ids, rows, mode="fp8",
                                     pad_to=pad_to).argmax(axis=1)
            low.append(best - ref[at, first])
    return gaps, low


# ------------------------------------------------------------------ work
def matmul_params(spec: dict) -> int:
    """Parameters every token multiplies against in the blocks (q, k, v, o,
    gate, up, down); the token table is a gather going in."""
    h, i = spec["hidden"], spec["intermediate"]
    dh = h // spec["heads"]
    return spec["layers"] * (2 * h * spec["heads"] * dh
                             + 2 * h * spec["kv_heads"] * dh + 3 * h * i)


def kv_bytes_per_token(spec: dict) -> int:
    dh = spec["hidden"] // spec["heads"]
    return spec["layers"] * 2 * spec["kv_heads"] * dh * BYTES_OF[spec["dtype"]]


def _flops(spec: dict, prefill_spans, decode_spans, sampled: float) -> float:
    """2 FLOPs a block parameter a token, QK^T and PV over the context the
    token sees (4 * hidden * (position + 1) a layer), and one row of the
    vocabulary projection for each token that was sampled."""
    per_tok, attn = 2.0 * matmul_params(spec), \
        4.0 * spec["layers"] * spec["hidden"]
    flops = 0.0
    for share, lo, hi in prefill_spans:  # positions lo..hi-1, a share of it
        n = hi - lo
        flops += share * (per_tok * n + attn * (n * (lo + hi + 1) / 2.0))
    for lo, hi in decode_spans:          # positions lo..hi-1, one a step
        n = hi - lo
        flops += per_tok * n + attn * (n * (lo + hi + 1) / 2.0)
    return flops + 2.0 * spec["vocab_size"] * spec["hidden"] * sampled


def gen_tokens(config: dict, prefill_spans, decode_spans, sampled) -> dict:
    """The tokens prefilled and decoded, whatever implements them: their
    FLOPs, and as bytes the weights once (any number of tokens can share one
    read).  For ``step_mfu``: it cannot pass 100 %."""
    spec = config["generator"]
    flops = _flops(spec, prefill_spans, decode_spans, sampled)
    return {"flops": flops,
            "bytes": float(param_bytes(spec)) if flops else 0.0}
