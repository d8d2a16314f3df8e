"""One fused step in flight (``genserve/engine.py``): step N+1 is planned,
packed and dispatched before step N's ids are read, its decode rows taking
their tokens from N's int vector on the device (``nornicdb_tpu/ragged.py``:
a token ``-(src + 1)`` and the step's ``prev``).

Every case runs for both decoder families through the harnesses that the
other genserve tests use.  What a served list is held to is what they are
held to: the plain float32 reference within a tolerance
(``decoder_harness.greedy_gap``), and, where the case is about scheduling,
the list an engine of the same geometry gives the prompt alone.
"""

import dataclasses
import functools
import types

import jax
import numpy as np
import pytest

from decoder_harness import Pool, greedy_gap, table_of, tokens as draw
from genserve_harness import (  # noqa: F401  (the fixture is autouse)
    GAP_TOL,
    PARAMS,
    alone as _alone,
    engine as _engine,
    settle,
    stop_what_the_test_started,
)
from nornicdb_tpu.config import GenServeConfig
from nornicdb_tpu.models import deepseek_v2 as ds
from nornicdb_tpu.models import qwen2
from nornicdb_tpu.models.reference import deepseek_v2 as ds_ref
from nornicdb_tpu.models.reference import qwen2 as qwen_ref


@dataclasses.dataclass(frozen=True)
class Kit:
    family: types.ModuleType
    cfg: object
    reference: types.ModuleType
    tol: float          # on greedy_gap against the float32 reference

    @functools.cached_property
    def params(self):
        if self.family is qwen2:
            return PARAMS
        return self.family.init_params(self.cfg, jax.random.PRNGKey(5))

    @property
    def model(self):
        return self.params, self.cfg


# Qwen in bfloat16 under the harness's GAP_TOL; DeepSeek-V2 in float32,
# where the program and the reference pick the same experts (in bfloat16 a
# row on a routing edge takes another expert: tests/test_deepseek_v2.py)
KITS = {
    "qwen2": Kit(qwen2, qwen2.QWEN_SMALL, qwen_ref, GAP_TOL),
    "deepseek_v2": Kit(ds, dataclasses.replace(ds.DEEPSEEK_V2_SMALL,
                                               dtype="float32"),
                       ds_ref, 2e-3),
}


@pytest.fixture(params=list(KITS))
def kit(request):
    return KITS[request.param]


def prompt(kit, n: int, seed: int) -> list[int]:
    return draw(seed * 1000 + n, n, kit.cfg.vocab_size)


def engine(kit, eos_id=None, **cfg_kw):
    tokenizer = None if eos_id is None else \
        types.SimpleNamespace(eos_id=eos_id)
    return _engine(tokenizer=tokenizer, model=kit.model, **cfg_kw)


def assert_pool_whole(eng) -> None:
    """Every page is free or resident in the prefix cache, each once."""
    free = eng._free_pages
    assert len(set(free)) == len(free), "a page was freed twice"
    assert not set(free) & set(eng._page_hash)
    assert len(free) + len(eng._page_hash) == eng._usable_pages
    assert not any(eng._page_refs.values())


def gap(kit, ids, out) -> float:
    return greedy_gap(kit.reference.forward, kit.params, kit.cfg, ids, out)


# ------------------------------------------------ the row contract itself
def test_a_row_reads_its_token_from_the_step_before(kit):
    """A decode row that names an entry of the previous step's ints
    computes what the same row computes with that token written out, and
    ``fused_step`` without ``prev`` is still the step it was."""
    ids = prompt(kit, 21, 1)
    table = table_of(1, 2, 3)
    rows = []
    for by_reference in (False, True):
        pool = Pool(kit.family, kit.cfg, kit.params)
        pool.step(chunk=(ids[:16], 0, table))
        first = pool.step(chunk=(ids[16:], 16, table))[-1]
        # the chunk's token is entry 0 of that step's ints (no decode row)
        assert pool.ints[0] == first.argmax()
        token = -(0 + 1) if by_reference else int(pool.ints[0])
        rows.append(pool.step(decode=[(token, len(ids), table)],
                              prev=pool.ints if by_reference else None)[0])
    np.testing.assert_array_equal(rows[0], rows[1])


# ----------------------------------------------- </s> is found a step late
@pytest.mark.parametrize("at", [0, 3], ids=["chunk-row", "decode-lane"])
def test_eos_is_found_one_step_late_and_its_overrun_row_is_dropped(kit, at):
    """The lane that samples </s> (as the chunk's first token, or on a
    decode lane) already has a row in the next step when the host finds
    out: the stream ends at </s>, that row's token is never delivered,
    ``overrun_rows`` counts it, its pages go back once, and a later request
    that hits its prefix pages reads the reference's continuation."""
    ids = prompt(kit, 40, 2)
    free_run = _alone_of(kit, [ids], 12)[0]
    # </s> = the first token from ``at`` on that has not come before
    at = next(k for k in range(at, 11) if free_run[k] not in free_run[:k])
    eos = free_run[at]
    eng = engine(kit, eos_id=eos)
    handle = eng.submit(ids, max_new_tokens=12)
    streamed = list(handle.stream_tokens())
    assert streamed == free_run[:at + 1] == handle.result()
    settle(eng)
    assert eng.stats.overrun_rows == 1
    assert eng.stats.generated_tokens == at + 1
    assert eng.stats.decode_lane_tokens == at + 1  # the overrun row ran
    assert_pool_whole(eng)
    assert len(eng._page_hash) == 2  # the prompt's two full pages
    # the next prompt shares those two pages (32 tokens) and nothing else
    eng.tokenizer = None
    later = ids[:32] + prompt(kit, 9, 3)
    handle = eng.submit(later, max_new_tokens=8)
    out = handle.result()
    assert handle.prefix_reused_tokens == 32
    assert len(out) == 8 and gap(kit, later, out) < kit.tol
    settle(eng)
    assert_pool_whole(eng)


def test_a_sequence_finishing_by_max_new_gets_no_overrun_row(kit):
    """``max_new`` is a count, so the plan knows: the last token's lane has
    no row in the step dispatched before that token is read."""
    eng = engine(kit)
    prompts = [prompt(kit, n, 4) for n in (7, 30)]
    handles = [eng.submit(p, max_new_tokens=m)
               for p, m in zip(prompts, (1, 9))]
    outs = [h.result() for h in handles]
    settle(eng)
    assert [len(o) for o in outs] == [1, 9]
    assert eng.stats.overrun_rows == 0
    # one decode row for every token but each stream's first
    assert eng.stats.decode_lane_tokens == 0 + 8
    assert eng.stats.generated_tokens == 10
    for p, out in zip(prompts, outs):
        assert gap(kit, p, out) < kit.tol
    assert_pool_whole(eng)


# -------------------------------------------------- what drains the flight
def test_an_eviction_reads_the_step_in_flight_first(kit):
    """Eviction re-prefills ``prompt + out``, so every token has to be on
    the host: the step in flight is read before the victim goes, and the
    re-admitted stream is the undisturbed one."""
    geometry = dict(page_size=8, max_seq_tokens=56, prefill_chunk=16)
    eng = engine(kit, pool_pages=8, **geometry)
    prompts = [prompt(kit, n, 5) for n in (6, 9, 13)]
    handles = [eng.submit(p, max_new_tokens=20) for p in prompts]
    outs = [h.result() for h in handles]
    settle(eng)
    assert eng.stats.evictions > 0, "pool was sized to force eviction"
    assert eng.stats.readmissions > 0
    assert eng.stats.drains > 0
    assert eng.stats.overrun_rows == 0
    assert outs == _alone_of(kit, prompts, 20, **geometry)
    for p, out in zip(prompts, outs):
        assert len(out) == 20 and gap(kit, p, out) < kit.tol
    assert_pool_whole(eng)


def test_a_failing_step_loses_both_steps_and_the_pool_is_rebuilt(
        kit, monkeypatch):
    """The dispatch that raises has the step before it still unread: both
    are lost, every resident request fails with the error, and the next
    request is served from a pool built anew."""
    plain, calls = kit.family.fused_step, []

    def third_call_raises(*a, **kw):
        calls.append(kw["prev"])
        if len(calls) == 3:
            raise RuntimeError("injected dispatch failure")
        return plain(*a, **kw)

    monkeypatch.setattr(kit.family, "fused_step", third_call_raises)
    eng = engine(kit)
    prompts = [prompt(kit, n, 6) for n in (20, 28)]
    handles = [eng.submit(p, max_new_tokens=16) for p in prompts]
    for h in handles:
        with pytest.raises(RuntimeError, match="injected"):
            h.result()
    settle(eng)
    assert eng.stats.errors == 2 and eng.stats.overlapped_steps >= 1
    assert eng._inflight is None and not eng._prefix_cache
    assert sorted(eng._free_pages) == list(range(1, eng._usable_pages + 1))
    out = eng.generate(prompts[0], max_new_tokens=8)
    assert len(out) == 8 and gap(kit, prompts[0], out) < kit.tol
    assert out == _alone_of(kit, prompts[:1], 8)[0]
    # every step got an array for ``prev``: one served variant
    assert all(p is not None for p in calls)


# ------------------------------------------------------- the steady state
def test_a_steady_batch_overlaps_nearly_every_step(kit):
    """Four lanes decoding: each step is dispatched while the one before
    is unread, except the first of a busy period."""
    eng = engine(kit)
    prompts = [prompt(kit, n, 7) for n in (5, 12, 19, 26)]
    handles = [eng.submit(p, max_new_tokens=48) for p in prompts]
    outs = [h.result() for h in handles]
    settle(eng)
    stats = eng.stats
    assert stats.overlapped_steps / stats.decode_steps > 0.9
    assert stats.drains == 0 and stats.overrun_rows == 0
    assert stats.read_wait_seconds > 0.0
    assert outs == _alone_of(kit, prompts, 48)
    for p, out in zip(prompts, outs):
        assert gap(kit, p, out) < kit.tol


def test_one_program_variant_a_step_class(kit):
    """The engine always hands the step an array for ``prev`` (zeros
    before the first step), so warm-up compiles the one variant that
    traffic runs: neither the ledger nor the jit's own cache grows."""
    step = qwen2.ragged_fused_step if kit.family is qwen2 else ds.fused_step
    eng = engine(kit)
    eng.warmup()
    ledger, compiled = set(eng.programs), step._cache_size()
    prompts = [prompt(kit, n, 8) for n in (3, 17, 33, 64)]
    handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
    for h in handles:
        assert len(h.result()) == 10
    assert set(eng.programs) == ledger
    assert step._cache_size() == compiled


def test_the_pipeline_added_no_option():
    # ten, and since PR 41 ``state_slots``: the one option the state kind
    # brought (the size of a pool that only a family with state-space
    # layers has, and has to be given)
    assert len(GenServeConfig.__dataclass_fields__) == 11


def _alone_of(kit, prompts, max_new, **geometry):
    return _alone(prompts, max_new, tokenizer=None, model=kit.model,
                  **geometry)
