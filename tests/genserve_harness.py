"""What ``test_genserve.py`` and ``test_genserve_ragged.py`` share: a small
Qwen behind a GenerationEngine with an injected backend manager (so both
files pass under ``NORNICDB_FAKE_BACKEND=hang``), and the two comparisons a
served token list is held to.

* :func:`assert_reference`: at every produced position the served token's
  logit in the plain float32 forward (``models/reference/qwen2.py``) lies
  within ``GAP_TOL`` of that forward's best — the benchmark's
  ``greedy_gap``, and what the chip is held to.
* :func:`alone`: the token lists of a second engine of the same geometry
  that serves each prompt by itself.  Every row of a step is computed for
  itself, so where a test is about a SCHEDULING invariant (a shared batch,
  eviction and re-admission, a prefix hit, the CPU fallback, recovery
  mid-decode) the two lists are equal by construction.
"""

import time

import jax
import numpy as np
import pytest

from decoder_harness import greedy_gap
from nornicdb_tpu.backend import BackendManager, FakeHooks
from nornicdb_tpu.config import GenServeConfig
from nornicdb_tpu.genserve import GenerationEngine
from nornicdb_tpu.models import qwen2
from nornicdb_tpu.models.reference import qwen2 as ref
from nornicdb_tpu.models.tokenizer import HashTokenizer

CFG = qwen2.QWEN_SMALL
PARAMS = qwen2.init_params(CFG, jax.random.PRNGKey(0))
TOK = HashTokenizer(CFG.vocab_size)

# bfloat16 weights at their usual init: logits of spread 0.16, the program's
# largest logit error against the reference 0.006-0.012, so a served token
# that is not the reference's first lies at most twice that under it (the
# engine's readings over the two files' prompts: 0.0 on most, 0.0013 at
# most).  A token drawn at random lies 0.5 under the best at the median and
# 0.09 at the first percentile.
GAP_TOL = 0.03

LIVE: list = []


@pytest.fixture(autouse=True)
def stop_what_the_test_started():
    yield
    while LIVE:
        LIVE.pop().stop()


def mgr(hooks=None, **kw):
    kw.setdefault("acquire_timeout", 0.5)
    kw.setdefault("probe_interval", 0.05)
    kw.setdefault("probe_timeout", 0.4)
    kw.setdefault("degrade_after", 1)
    kw.setdefault("recover_after", 1)
    manager = BackendManager(hooks=hooks or FakeHooks("ok"), **kw)
    LIVE.append(manager)
    return manager


def engine(manager=None, tokenizer=TOK, model=(PARAMS, CFG), **cfg_kw):
    """An engine of the harness's geometry over ``model`` = (params, cfg),
    of any decoder family (the small Qwen unless given)."""
    cfg_kw.setdefault("page_size", 16)
    cfg_kw.setdefault("pool_pages", 33)
    cfg_kw.setdefault("max_seqs", 4)
    cfg_kw.setdefault("max_seq_tokens", 128)
    cfg_kw.setdefault("prefill_chunk", 32)
    cfg_kw.setdefault("deadline_ms", 60000)
    eng = GenerationEngine(
        *model, tokenizer=tokenizer,
        config=GenServeConfig(**cfg_kw),
        manager=manager or mgr())
    LIVE.append(eng)
    return eng


def settle(eng, timeout: float = 30.0) -> None:
    """Until nothing is resident and no step is unread: the step after a
    stream's last is read by the scheduler's next turn."""
    end = time.monotonic() + timeout
    while eng._running or eng._inflight is not None or eng._zombies:
        assert time.monotonic() < end, "the engine did not come to rest"
        time.sleep(0.005)
    # and the turn that read it has run to its end (its counters are all
    # in, its annotation is closed): no further turn begins while idle
    turns = -1
    while turns != eng.stats.turns:
        turns = eng.stats.turns
        time.sleep(0.02)


def prompt(n: int, seed: int = 0) -> list[int]:
    rng = np.random.default_rng(seed * 1000 + n)
    return [int(x) for x in rng.integers(4, CFG.vocab_size, n)]


def assert_reference(ids: list[int], out: list[int], max_new: int) -> None:
    """``out`` is the reference's greedy decoding of ``ids`` within
    ``GAP_TOL``: ``max_new`` tokens unless </s> came first."""
    assert out, "nothing was generated"
    assert len(out) == max_new or out[-1] == TOK.eos_id, (len(out), max_new)
    assert TOK.eos_id not in out[:-1]
    gap = greedy_gap(ref.forward, PARAMS, CFG, ids, out)
    assert gap < GAP_TOL, gap


def alone(prompts, max_new: int, **geometry) -> list[list[int]]:
    """Each prompt through an engine of its own with this geometry and the
    harness's roomy pool: no batch, no eviction, no prefix hit."""
    outs = []
    for ids in prompts:
        eng = engine(**geometry)
        outs.append(eng.generate(ids, max_new_tokens=max_new))
        eng.stop()
    return outs
