"""The plain references that belong to no model family, and the comparison
that decides ``correct``.

Nothing here imports the program or takes anything the program made: the
corpus rows, the queries and the expected answers all come from ``--seed``
and the configuration file.  A model family's reference, weights, tokenizer
and work functions live in ``bench/models/<family>.py``.

* top-k: plain numpy ``argsort(-(Q @ C.T))`` over the seeded rows, f32.

``mode="fp8"`` computes the same reference one precision step below the
bf16 the configurations state (e4m3, per-tensor scale) and stands in the
program's place as the control that has to come out NOT correct
(``bench/tests/test_control.py``, ``run.py --control``).
"""

from __future__ import annotations

import functools
import hashlib
import re

import numpy as np

_WORD = re.compile(r"\w+|[^\w\s]", re.UNICODE)
RESERVED = 4  # ids 0..3: <s>, <pad>, </s>, <unk>


@functools.lru_cache(maxsize=None)
def _word_id(word: str, vocab_size: int) -> int:
    return RESERVED + int.from_bytes(
        hashlib.blake2s(word.encode()).digest()[:4], "little") \
        % (vocab_size - RESERVED)


def hash_word_ids(text: str, vocab_size: int) -> list[int]:
    """The configurations' tokenizer (``hash-word-blake2s``), without special
    tokens: a word or a punctuation mark is a token, id = 4 +
    blake2s(lowercased word)[:4] mod (vocab - 4)."""
    return [_word_id(w.lower(), vocab_size) for w in _WORD.findall(text)]


def jax_key(seed: int):
    import jax

    return jax.random.PRNGKey(
        int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1))


def fp8(x):
    """e4m3 with a per-tensor scale, back in float32 (jax arrays)."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


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
