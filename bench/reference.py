"""The plain references and the comparison that decides ``correct``.

Nothing here imports the program or takes anything the program made: the
corpus rows, the weights, the token ids and the expected answers all come
from ``--seed`` and the configuration file.  (``make_params`` is also what
``run.py`` hands the program as its weights: the benchmark makes them, the
program serves them.)

* top-k: plain numpy ``argsort(-(Q @ C.T))`` over the seeded rows, f32.
* bge-m3: the XLM-RoBERTa encoder as published (post-LN, GELU, CLS pooling,
  L2 normalisation) in float32 at ``highest`` matmul precision, one text a
  row, layer by layer so that it fits beside anything.

``mode="fp8"`` computes the same references one precision step below the
bf16 the configurations state (e4m3, per-tensor scale) and stands in the
program's place as the control that has to come out NOT correct
(``bench/tests/test_control.py``, ``run.py --control``).
"""

from __future__ import annotations

import hashlib
import re

import numpy as np

_WORD = re.compile(r"\w+|[^\w\s]", re.UNICODE)
CLS, PAD, EOS, RESERVED = 0, 1, 2, 4


# ------------------------------------------------------------- tokenizer
def tokenize(text: str, vocab_size: int, max_len: int) -> list[int]:
    """The configuration's tokenizer (``hash-word-blake2s``): id = 4 +
    blake2s(lowercased word)[:4] mod (vocab - 4), between <s> and </s>."""
    ids = [RESERVED + int.from_bytes(
        hashlib.blake2s(w.lower().encode()).digest()[:4], "little")
        % (vocab_size - RESERVED) for w in _WORD.findall(text)]
    return ([CLS] + ids + [EOS])[:max_len]


# --------------------------------------------------------------- weights
def jax_key(seed: int):
    import jax

    return jax.random.PRNGKey(
        int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1))


def make_params(model: dict, seed: int) -> dict:
    """Seeded weights in the served dtype, made on the device in ONE jitted
    call, in the tree the encoder's published layout names (token, position
    and type tables, embedding LayerNorm, per block q/k/v/o, up/down and two
    LayerNorms)."""
    import jax
    import jax.numpy as jnp

    h, i, n = model["hidden"], model["intermediate"], model["layers"]
    dt = jnp.dtype(model["dtype"])

    def make(key):
        ks = jax.random.split(key, 10)
        norm = lambda k, shape, std: (  # noqa: E731
            jax.random.normal(k, shape, jnp.float32) * std).astype(dt)
        sq = norm(ks[0], (n, 4, h, h), (1.0 / h) ** 0.5)
        up = norm(ks[1], (n, h, i), (2.0 / (h + i)) ** 0.5)
        down = norm(ks[2], (n, i, h), (2.0 / (h + i)) ** 0.5)
        b_sq = norm(ks[3], (n, 4, h), 0.02)
        b_up = norm(ks[4], (n, i), 0.02)
        b_down = norm(ks[5], (n, h), 0.02)
        ln = lambda: {"scale": jnp.ones((h,), jnp.float32),  # noqa: E731
                      "bias": jnp.zeros((h,), jnp.float32)}
        blocks = [{
            **{name: {"w": sq[l, j], "b": b_sq[l, j]}
               for j, name in enumerate("qkvo")},
            "attn_ln": ln(),
            "up": {"w": up[l], "b": b_up[l]},
            "down": {"w": down[l], "b": b_down[l]},
            "mlp_ln": ln()} for l in range(n)]
        return {"tok_emb": norm(ks[6], (model["vocab_size"], h), 0.02),
                "pos_emb": norm(ks[7], (model["max_positions"], h), 0.02),
                "type_emb": norm(ks[8], (model["type_vocab"], h), 0.02),
                "emb_ln": ln(), "blocks": blocks}

    return jax.jit(make)(jax_key(seed))


# ------------------------------------------------------- bge-m3 reference
def _fp8(x):
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _layer(model: dict, mode: str):
    import jax
    import jax.numpy as jnp

    heads = model["heads"]
    hi = jax.lax.Precision.HIGHEST

    def dense(p, x):
        w = p["w"].astype(jnp.float32)
        if mode == "fp8":
            x, w = _fp8(x), _fp8(w)
        return jnp.einsum("bti,io->bto", x, w, precision=hi) \
            + p["b"].astype(jnp.float32)

    def norm(p, x):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]

    def layer(blk, hid, mask):
        b, t, width = hid.shape
        split = lambda x: x.reshape(b, t, heads, width // heads)  # noqa: E731
        q, k, v = (split(dense(blk[n], hid)) for n in "qkv")
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) \
            * (width // heads) ** -0.5
        s = jnp.where(mask[:, None, None, :] > 0, s, -1e30)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                       precision=hi).reshape(b, t, width)
        hid = norm(blk["attn_ln"], hid + dense(blk["o"], o))
        m = dense(blk["down"], jax.nn.gelu(dense(blk["up"], hid)))
        return norm(blk["mlp_ln"], hid + m)

    return jax.jit(layer), norm


def embed_reference(model: dict, params: dict, texts: list[str],
                    mode: str = "highest", rows: int = 16) -> np.ndarray:
    """(len(texts), dims) float32 unit vectors: each text alone in its row,
    rows padded to 64 tokens or to the longest the model takes (two shapes,
    so two compiles of one layer) and masked."""
    import jax.numpy as jnp

    layer, norm = _layer(model, mode)
    seqs = [tokenize(t, model["vocab_size"], model["max_len"]) or [PAD]
            for t in texts]
    out = np.zeros((len(texts), model["hidden"]), np.float32)
    by_width: dict[int, list[int]] = {}
    for j, s in enumerate(seqs):
        width = 64 if len(s) <= 64 else model["max_len"]
        by_width.setdefault(width, []).append(j)
    for width, members in sorted(by_width.items()):
        for at in range(0, len(members), rows):
            chunk = members[at:at + rows]
            ids = np.full((rows, width), PAD, np.int32)
            mask = np.zeros((rows, width), np.int32)
            for r, j in enumerate(chunk):
                ids[r, :len(seqs[j])] = seqs[j]
                mask[r, :len(seqs[j])] = 1
            pos = np.cumsum(mask, 1) * mask + model["pad_token_id"]
            f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
            hid = f32(params["tok_emb"][ids]) + f32(params["pos_emb"][pos]) \
                + f32(params["type_emb"][np.zeros_like(ids)])
            hid = norm(params["emb_ln"], hid)
            for blk in params["blocks"]:
                hid = layer(blk, hid, jnp.asarray(mask))
            cls = np.asarray(hid[:len(chunk), 0, :], np.float32)
            out[chunk] = cls / np.maximum(
                np.linalg.norm(cls, axis=1, keepdims=True), 1e-12)
    return out


# --------------------------------------------------------- top-k reference
def _fp8_np(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    scale = max(float(np.abs(x).max()), 1e-30) / 448.0
    return (x / scale).astype(ml_dtypes.float8_e4m3fn).astype(
        np.float32) * scale


def exact_scores(queries: np.ndarray, rows: np.ndarray, mode: str = "highest",
                 block: int = 65536) -> np.ndarray:
    """(Q, N) cosine scores of unit queries against unit rows, plain numpy
    float32, in blocks of rows."""
    q = _fp8_np(queries) if mode == "fp8" else queries
    out = np.empty((len(queries), len(rows)), np.float32)
    for at in range(0, len(rows), block):
        c = rows[at:at + block]
        out[:, at:at + block] = q @ (_fp8_np(c) if mode == "fp8" else c).T
    return out


def topk_answers(scores: np.ndarray, k: int) -> list[list[tuple[int, float]]]:
    """What a top-k server would answer from these scores: (row, score)."""
    top = np.argpartition(-scores, k - 1, axis=1)[:, :k]
    return [[(int(r), float(scores[qi, r])) for r in
             top[qi][np.argsort(-scores[qi, top[qi]])]]
            for qi in range(len(scores))]


# ------------------------------------------------------------- comparisons
def number(name: str, value: float, limit: float, better: str) -> dict:
    ok = value <= limit if better == "lower" else value >= limit
    return {"name": name, "value": float(value), "limit": float(limit),
            "ok": bool(ok)}


def check_search(limits: dict, k: int, exact: np.ndarray,
                 answers: list[list[tuple[int, float]]]) -> list[dict]:
    """``answers[q]`` = the served (row, score) pairs (row -1: an id that is
    no live row); ``exact`` = the reference's scores for the same queries."""
    recalls, err, bad = [], 0.0, 0
    for qi, hits in enumerate(answers):
        bad += sum(1 for r, _ in hits if r < 0) + abs(len(hits) - k) \
            + len(hits) - len({r for r, _ in hits})
        truth = set(np.argpartition(-exact[qi], k - 1)[:k].tolist())
        recalls.append(len(truth & {r for r, _ in hits}) / k)
        err = max([err] + [abs(s - float(exact[qi, r]))
                           for r, s in hits if r >= 0])
    return [number("recall_at_k_mean", float(np.mean(recalls)),
                   limits["recall_at_k_mean_min"], "higher"),
            number("score_err_max", err, limits["score_err_max"], "lower"),
            number("bad_ids", bad, 0, "lower")]


def check_vectors(limits: dict, served: np.ndarray,
                  reference: np.ndarray) -> list[dict]:
    """Worst L2 distance between a served embedding and the reference's
    (both unit vectors: the distance also catches a wrong norm)."""
    served = np.asarray(served, np.float32).reshape(len(reference), -1)
    dist = float(np.max(np.linalg.norm(served - reference, axis=1))) \
        if np.isfinite(served).all() else float("inf")
    return [number("embed_dist_max", dist, limits["embed_dist_max"], "lower")]
