"""Genserve v2: the fused ragged step behind the scheduler + shared-prefix
KV caching.

The step itself is held to the plain float32 reference in
``tests/test_qwen2_step.py`` (chunked prefill, decode, a prefix-page hit,
decode lanes beside a chunk, donation and the null page).  Here, the
engine around it:

- shared-prefix admission skips prefill and the served tokens stay the
  reference's greedy continuation (``genserve_harness.assert_reference``)
  and the tokens of an engine that never shared a page
  (``genserve_harness.alone``); eviction never frees a refcounted shared
  page; re-prefill after eviction re-hits the cache;
- warmup covers every steady-state shape class (the nornjit churn gate).
"""

from __future__ import annotations

import os

import numpy as np

from genserve_harness import (  # noqa: F401  (the fixture is autouse)
    alone as _alone,
    assert_reference as _assert_reference,
    engine as _engine,
    mgr as _mgr,
    prompt as _prompt,
    stop_what_the_test_started,
)
from nornicdb_tpu.backend import FakeHooks
from nornicdb_tpu.ragged import round_up_pow2


# ---------------------------------------------------------------------------
# engine: shared-prefix caching semantics
# ---------------------------------------------------------------------------
class TestPrefixCache:
    def test_prefix_hit_skips_prefill_and_matches_the_reference(self):
        """Second identical prompt adopts the cached prefix pages —
        fewer first-pass prefill tokens, same emitted tokens (adopted KV
        is the SAME bytes prefill wrote), and they are the reference's."""
        eng = _engine()
        shared = _prompt(50, seed=7)
        out1 = eng.generate(shared, max_new_tokens=4)
        first_after_1 = eng.stats.prefill_tokens_first
        h2 = eng.submit(shared, max_new_tokens=4)
        out2 = h2.result()
        _assert_reference(shared, out1, 4)
        assert out2 == out1 == _alone([shared], 4)[0]
        # 3 full 16-token pages adopted (the 4th would swallow the whole
        # prompt; the final chunk must still produce first-token logits)
        assert h2.prefix_reused_tokens == 48
        assert eng.stats.prefix_hits >= 3
        assert (eng.stats.prefill_tokens_first - first_after_1
                == len(shared) - 48)
        snap = eng.stats_snapshot()
        assert snap["prefix_pages"] >= 3
        assert snap["prefix_reused_tokens"] >= 48

    def test_shared_page_release_keeps_coholder(self):
        """Unit invariant: releasing one holder of a refcounted page
        decrements — the page never reaches the free list while a second
        sequence still holds it, and a cached page goes idle-resident
        instead of free."""
        eng = _engine()
        eng.submit([1], max_new_tokens=1).result()  # builds the pool
        a = eng._running  # settled
        assert a == []
        from nornicdb_tpu.genserve.engine import _Seq, GenHandle
        free0 = list(eng._free_pages)
        pid = free0[-1]
        seq1 = _Seq(GenHandle(eng, 0.0), [1], 1, -1)
        seq2 = _Seq(GenHandle(eng, 0.0), [1], 1, -1)
        eng._free_pages.pop()
        eng._page_refs[pid] = 2  # shared by both
        seq1.page_ids = [pid]
        seq1.page_table = np.asarray([pid], np.int32)
        seq2.page_ids = [pid]
        seq2.page_table = np.asarray([pid], np.int32)
        eng._release_pages(seq1)
        assert pid not in eng._free_pages, (
            "shared page freed out from under its co-holder")
        assert eng._page_refs[pid] == 1
        # also prefix-cached: the LAST holder's release keeps it resident
        eng._prefix_cache[b"k"] = pid
        eng._page_hash[pid] = b"k"
        eng._release_pages(seq2)
        assert pid not in eng._free_pages
        assert pid not in eng._page_refs
        assert eng._alloc_page() != pid or not eng._free_pages

    def test_eviction_with_shared_prefix_stays_exact_and_rehits(self):
        """Pool sized to thrash: sequences sharing a prompt prefix get
        evicted and re-prefilled.  Eviction must never corrupt the
        shared pages (outputs stay the reference's, and those of an
        engine that shared nothing) and the re-prefill pass re-hits the
        prefix cache instead of redoing the shared pages."""
        geometry = dict(page_size=8, max_seq_tokens=56, prefill_chunk=16)
        eng = _engine(pool_pages=8, **geometry)
        common = _prompt(16, seed=9)
        prompts = [common + _prompt(n, seed=10 + n) for n in (5, 9, 12)]
        handles = [eng.submit(p, max_new_tokens=20) for p in prompts]
        outs = [h.result() for h in handles]
        for ids, out in zip(prompts, outs):
            _assert_reference(ids, out, 20)
        assert outs == _alone(prompts, 20, **geometry)
        assert eng.stats.evictions > 0, "pool was sized to force eviction"
        assert eng.stats.prefix_hits > 0
        assert eng.stats.prefill_tokens_re > 0, (
            "re-prefill after eviction not accounted separately")
        assert eng.stats.prefill_tokens_first > 0

    def test_idle_cached_pages_reclaimed_lru_under_pressure(self):
        """Idle prefix-cached pages are capacity, not a leak: when the
        free list drains, admission reclaims them LRU and the engine
        keeps serving exactly."""
        geometry = dict(page_size=8, max_seq_tokens=64, max_seqs=2,
                        prefill_chunk=16)
        eng = _engine(pool_pages=12, **geometry)
        # populate the cache: distinct prompts, each registering pages
        for s in range(4):
            eng.generate(_prompt(17, seed=20 + s), max_new_tokens=2)
        assert len(eng._prefix_cache) > 0
        cached_before = len(eng._prefix_cache)
        # now a burst that needs more pages than the free list holds
        prompts = [_prompt(30, seed=40 + s) for s in range(3)]
        handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
        outs = [h.result() for h in handles]
        for ids, out in zip(prompts, outs):
            _assert_reference(ids, out, 8)
        assert outs == _alone(prompts, 8, **geometry)
        assert len(eng._prefix_cache) <= cached_before + 3 * 3

    def test_cpu_fallback_serves_prefix_hits_exactly(self):
        """Degraded backend (CPU-served steps): the prefix cache still
        hits and the outputs stay the reference's — re-platforming resets
        the cache rather than serving stale KV."""
        mgr = _mgr(FakeHooks("hang"), acquire_timeout=0.3)
        eng = _engine(manager=mgr, deadline_ms=30000)
        shared = _prompt(40, seed=13)
        out1 = eng.generate(shared, max_new_tokens=4)
        out2 = eng.generate(shared, max_new_tokens=4)
        _assert_reference(shared, out1, 4)
        assert out2 == out1 == _alone([shared], 4)[0]
        assert eng.stats.cpu_steps > 0
        assert eng.stats.prefix_hits > 0


# ---------------------------------------------------------------------------
# warmup ladder / nornjit churn gate
# ---------------------------------------------------------------------------
class TestWarmupCoverage:
    def test_ragged_classes_cover_contiguous_f_buckets(self):
        eng = _engine()
        classes = eng._ragged_classes()
        assert (8, 1) in classes  # decode-only floor
        # chunk bucket 32 with max_seqs 4 decode riders: n_valid up to
        # 32 + 3 -> F buckets {32, 48->64}; ALL contiguous pow2 stops
        assert (32, 32) in classes and (64, 32) in classes
        for fa, tqa in classes:
            assert fa == round_up_pow2(fa, 8)

    def test_warmup_then_steady_traffic_compiles_nothing(self):
        """One shape-class compile per (F, Tq) bucket at warmup; varied
        steady traffic — short/long prompts, full decode batches,
        prefix hits and misses — adds NO program.  Under NORNJIT=1 the
        conftest gate also fails this test on any fresh XLA compile
        after the declaration."""
        eng = _engine()
        eng.warmup()
        programs = set(eng.programs)
        assert programs, "warmup compiled nothing"
        if os.environ.get("NORNJIT") == "1":
            from nornicdb_tpu.tools import nornjit
            nornjit.declare_warmup_done("genserve ragged ladder")
        handles = [eng.submit(_prompt(n, seed=n), max_new_tokens=6)
                   for n in (3, 18, 40, 61, 27)]
        for h in handles:
            h.result()
        shared = _prompt(45, seed=99)
        eng.generate(shared, max_new_tokens=4)
        eng.generate(shared, max_new_tokens=4)  # prefix-hit path
        assert set(eng.programs) == programs, (
            "steady-state traffic dispatched an unwarmed shape class: "
            f"{sorted(set(eng.programs) - programs)}")
