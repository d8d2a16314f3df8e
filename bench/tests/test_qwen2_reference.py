"""The plain Qwen2 reference of ``bench/models/qwen2.py`` against the
program's served path, on seeded weights at a size a test run can hold.

* The paged primitives (``paged_prefill_chunk`` in chunks of 16, then
  ``paged_decode_step`` through the paged cache), for a prompt and for a
  second prompt that shares its first four pages and prefills only its own
  suffix: every row of logits within ``LOGIT_TOLERANCE`` of the cache-free
  float32 forward.  The tolerance is bf16's reading with room (0.011-0.012
  at this size on five seeds) and an fp8 forward fails it (0.18-0.21).
* The generation engine itself (fused ragged step, scheduler, prefix cache):
  what it streams for two prompts with a shared prefix reads a greedy gap
  under the tolerance.

    python3 -m pytest bench/tests/test_qwen2_reference.py -q
"""

import json
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import loadgen  # noqa: E402

family = loadgen.load_file("models/qwen2.py")
LOGIT_TOLERANCE = 0.04
PAGE, WIDTH = 16, 16


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(BENCH, "configs",
                           "assistant-1m-qwen2.5-0.5b.json")) as f:
        cfg = json.load(f)
    return {**cfg["generator"], **cfg["rehearsal"]["generator"], "layers": 4}


def serve(qwen2, cfg, params, pages, ids, table, start, steps=6):
    """Prefill ``ids[start:]`` in chunks of 16 into ``table``'s pages, then
    decode ``steps - 1`` tokens greedily: the logits of every step."""
    import jax.numpy as jnp

    pos, logits = start, None
    while pos < len(ids):
        n = min(16, len(ids) - pos)
        chunk = np.zeros(16, np.int32)
        chunk[:n] = ids[pos:pos + n]
        logits, pages = qwen2.paged_prefill_chunk(
            params, cfg, jnp.asarray(chunk), pages, jnp.asarray(table),
            jnp.asarray(pos), jnp.asarray(n))
        pos += n
    rows, out = [np.asarray(logits)], [int(np.argmax(logits))]
    for length in range(len(ids), len(ids) + steps - 1):
        row, pages = qwen2.paged_decode_step(
            params, cfg, jnp.asarray([out[-1]], jnp.int32), pages,
            jnp.asarray(table)[None], jnp.asarray([length], jnp.int32))
        rows.append(np.asarray(row[0]))
        out.append(int(np.argmax(rows[-1])))
    return out, np.stack(rows), pages


@pytest.mark.parametrize("seed", [1, 2147483659, 3000000019])
def test_chunked_prefill_and_paged_decode_agree_with_the_reference(spec, seed):
    from nornicdb_tpu.models import qwen2

    cfg = family.program_config(spec)
    params = family.make_params(spec, seed)
    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(4, spec["vocab_size"], n).tolist()  # noqa: E731
    prefix = draw(4 * PAGE)
    pages = qwen2.init_kv_pages(cfg, 64, PAGE)
    first = np.zeros(WIDTH, np.int32)
    first[:8] = np.arange(1, 9)
    second = np.zeros(WIDTH, np.int32)
    second[:4], second[4:9] = first[:4], np.arange(20, 25)  # the shared pages
    a = prefix + draw(23)
    b = prefix + draw(37)
    out_a, served_a, pages = serve(qwen2, cfg, params, pages, a, first, 0)
    out_b, served_b, pages = serve(qwen2, cfg, params, pages, b, second,
                                   len(prefix))
    for ids, out, served in ((a, out_a, served_a), (b, out_b, served_b)):
        rows = np.arange(len(ids) - 1, len(ids) - 1 + len(out))
        ref = family.reference_logits(spec, params, ids + out[:-1], rows)
        low = family.reference_logits(spec, params, ids + out[:-1], rows,
                                      mode="fp8")
        assert np.abs(served - ref).max() < LOGIT_TOLERANCE
        assert np.abs(low - ref).max() > 2 * LOGIT_TOLERANCE


def test_the_engine_streams_what_the_reference_would(spec):
    """Two prompts through the GenerationEngine, the second after the first
    so that it takes the shared pages from the prefix cache."""
    from nornicdb_tpu.config import GenServeConfig
    from nornicdb_tpu.genserve import GenerationEngine

    cfg = family.program_config(spec)
    params = family.make_params(spec, 5)
    engine = GenerationEngine(params, cfg, config=GenServeConfig(
        max_seqs=2, max_seq_tokens=256, pool_pages=65, deadline_ms=0))
    rng = np.random.default_rng(5)
    prefix = rng.integers(4, spec["vocab_size"], 80).tolist()
    seqs = []
    try:
        for n in (21, 40):
            prompt = prefix + rng.integers(4, spec["vocab_size"], n).tolist()
            seqs.append((prompt, engine.generate(prompt, max_new_tokens=12)))
        stats = engine.stats_snapshot()
    finally:
        engine.stop()
    assert stats["prefix_reused_tokens"] == 80
    assert all(len(out) == 12 for _, out in seqs)
    gaps, _ = family.greedy_gaps(spec, params, seqs, control=False)
    assert max(float(g.max()) for g in gaps) < LOGIT_TOLERANCE
    wrong = [(p, [(t + 1) % spec["vocab_size"] for t in out])
             for p, out in seqs]
    gaps, _ = family.greedy_gaps(spec, params, wrong, control=False)
    assert max(float(g.max()) for g in gaps) > LOGIT_TOLERANCE
