"""Model family ``bge_m3``: the XLM-RoBERTa encoder behind the embedder.

A family file is everything the harness knows about one kind of model, found
by the ``family`` a configuration names (``bench/run.py`` names none):

* ``program_config(spec)``: the program's config object from the file's sizes
  (a size that differs from the serve preset it names is refused);
* ``make_params(spec, seed)``: seeded weights in the served dtype;
* ``install(db, app_cfg, spec, params)``: wires the model as ``cmd_serve``
  does and returns the served object (the embedder's ``stats`` are a root
  of ``run.py``'s counters);
* the plain reference (float32, ``highest`` matmul precision, no cache, no
  batching, in blocks so that it fits) and its fp8 control;
* the work functions the ``mfu`` / ``roofline`` readers call.

Nothing below ``install`` imports the program or takes anything it made.
"""

from __future__ import annotations

import sys

import numpy as np

from reference import fp8, hash_word_ids, jax_key
from work import BYTES_OF

ROLE = "embedder"
CLS, PAD, EOS = 0, 1, 2


# ------------------------------------------------------- the program's side
def program_config(spec: dict):
    from nornicdb_tpu.models import bge_m3

    fields = bge_m3.BgeConfig.__dataclass_fields__
    cfg = bge_m3.BgeConfig(**{k: v for k, v in spec.items() if k in fields})
    if spec.get("preset"):
        preset = getattr(bge_m3, spec["preset"])
        if cfg != preset:
            sys.exit(f"sizes differ from the serve preset {spec['preset']}: "
                     f"{cfg} != {preset}")
    return cfg


def install(db, app_cfg, spec: dict, params):
    """cmd_serve's embedder chain (nornicdb_tpu/cli.py): TPUEmbedder ->
    ServingEngine -> CachedEmbedder, with the benchmark's seeded weights."""
    from nornicdb_tpu.embed import CachedEmbedder, TPUEmbedder
    from nornicdb_tpu.serving import ServingEngine

    embedder = TPUEmbedder(cfg=program_config(spec), params=params,
                           max_len=spec["max_len"])
    db.set_embedder(CachedEmbedder(ServingEngine(embedder, app_cfg.serving)))
    return embedder


# ------------------------------------------------------------- tokenizer
def tokenize(text: str, vocab_size: int, max_len: int) -> list[int]:
    """The configuration's tokenizer (``hash-word-blake2s``), between <s>
    and </s>."""
    return ([CLS] + hash_word_ids(text, vocab_size) + [EOS])[:max_len]


def token_length(spec: dict, text: str) -> int:
    return len(tokenize(text, spec["vocab_size"], spec["max_len"]))


# --------------------------------------------------------------- weights
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


# ------------------------------------------------------------- reference
def _layer(model: dict, mode: str):
    import jax
    import jax.numpy as jnp

    heads = model["heads"]
    hi = jax.lax.Precision.HIGHEST

    def dense(p, x):
        w = p["w"].astype(jnp.float32)
        if mode == "fp8":
            x, w = fp8(x), fp8(w)
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


# ------------------------------------------------------------------ work
def matmul_params(model: dict) -> int:
    """Parameters every token multiplies against (q, k, v, o, up, down of
    each layer); the embedding tables are gathers, not multiplies."""
    h, i = model["hidden"], model["intermediate"]
    return model["layers"] * (4 * h * h + 2 * h * i)


def embed_texts(config: dict, token_lengths) -> dict:
    """Forward passes over texts of the given token lengths: 2 FLOPs per
    matmul parameter per token, plus QK^T and PV (4 * hidden * len^2 per
    layer per text).  Bytes: the weights once (any number of texts can
    share one read)."""
    m = config["model"]
    tokens = float(sum(token_lengths))
    squares = float(sum(n * n for n in token_lengths))
    flops = 2.0 * matmul_params(m) * tokens \
        + 4.0 * m["layers"] * m["hidden"] * squares
    return {"flops": flops,
            "bytes": float(matmul_params(m) * BYTES_OF[m["dtype"]])
            if tokens else 0.0}
