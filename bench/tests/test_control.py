"""The control — the plain reference computed one precision step below the
bf16 the configurations state (fp8 e4m3), put in the program's place — has
to come out NOT correct under each configuration's own limits, and the
reference itself has to pass them.  Sizes a test run can hold; the same
control at the cells' own sizes is ``run.py --control`` on the chip."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import loadgen  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402

bge_m3 = loadgen.load_file("models/bge_m3.py")
qwen2 = loadgen.load_file("models/qwen2.py")


# a size a test run can hold at which fp8 is as far off as at the cell's own
CONTROL_SIZE = {"layers": 8, "hidden": 256, "heads": 4, "kv_heads": 2,
                "intermediate": 1024, "vocab_size": 32768}


def config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [1, 2147483659, 3000000019])
def test_fp8_topk_fails_the_vecstore_limits(seed):
    cfg = config("vecstore-1m-1024")
    rows = traffic.unit_rows(traffic.rng_for(seed, 1), 16384, 1024)
    queries = traffic.unit_rows(traffic.rng_for(seed, 3), 16, 1024)
    exact = reference.exact_scores(queries, rows)
    sound = reference.check_search(cfg["limits"], 100, exact,
                                   reference.topk_answers(exact, 100))
    assert all(n["ok"] for n in sound), sound
    low = reference.topk_answers(
        reference.exact_scores(queries, rows, mode="fp8"), 100)
    control = reference.check_search(cfg["limits"], 100, exact, low)
    assert not all(n["ok"] for n in control), control


@pytest.mark.parametrize("seed", [1, 2147483659, 3000000019])
def test_fp8_forward_fails_the_memory_limits(seed):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cfg = config("memory-1m-bge-m3")
    model = {**cfg["model"], **cfg["rehearsal"]["model"], "layers": 4}
    params = bge_m3.make_params(model, seed)
    rng = traffic.rng_for(seed, 5)
    texts = [traffic.text_of(rng, n) for n in (8, 20, 60, 250, 400)]
    ref = bge_m3.embed_reference(model, params, texts)
    assert all(n["ok"] for n in
               reference.check_vectors(cfg["limits"], ref, ref))
    low = bge_m3.embed_reference(model, params, texts, mode="fp8")
    control = reference.check_vectors(cfg["limits"], low, ref)
    assert not all(n["ok"] for n in control), control


@pytest.mark.parametrize("seed", [1, 2147483659, 3000000019])
def test_fp8_decoder_fails_the_assistant_limits(seed):
    """The reference decodes greedily (each token from one cache-free
    forward); put in the program's place it reads a gap of 0, and the fp8
    forward of the same prompts and tokens puts first, somewhere, a token
    that lies further under the reference's best than the limit allows."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cfg = config("assistant-1m-qwen2.5-0.5b")
    spec = {**cfg["generator"], **cfg["rehearsal"]["generator"],
            **CONTROL_SIZE}
    params = qwen2.make_params(spec, seed)
    rng = traffic.rng_for(seed, 5)
    seqs = []
    for n in (40, 150):
        prompt = rng.integers(4, spec["vocab_size"], n).tolist()
        out = []
        for _ in range(24):
            row = [len(prompt) + len(out) - 1]
            out.append(int(qwen2.reference_logits(
                spec, params, prompt + out, row, pad_to=512)[0].argmax()))
        seqs.append((prompt, out))
    gaps, low = qwen2.greedy_gaps(spec, params, seqs, control=True)
    limit = cfg["limits"]["greedy_gap_max"]
    assert max(float(g.max()) for g in gaps) == 0.0
    assert max(float(g.max()) for g in low) > limit, low
