"""Genserve v2: ragged fused step + shared-prefix KV caching.

Three layers of coverage, mirroring the acceptance bar:

- kernel: the ragged paged attention kernel (interpret mode on CPU) is
  BIT-identical to gathering each lane's pages and calling
  layers.attention — the dense-equivalence anchor.
- model: ``ragged_fused_step`` mixing decode lanes with a prefill chunk
  is BIT-identical to the sequential ``paged_prefill_chunk`` +
  ``paged_decode_step`` programs it replaced, logits AND pool content.
- engine: shared-prefix admission skips prefill without changing a
  single emitted token; eviction never frees a refcounted shared page;
  re-prefill after eviction re-hits the cache; warmup covers every
  steady-state shape class (the nornjit churn gate).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nornicdb_tpu.backend import BackendManager, FakeHooks
from nornicdb_tpu.config import GenServeConfig
from nornicdb_tpu.genserve import GenerationEngine
from nornicdb_tpu.models import layers, qwen2
from nornicdb_tpu.models.tokenizer import HashTokenizer
from nornicdb_tpu.ops import pallas_kernels as pk
from nornicdb_tpu.ragged import pack_ragged_meta, round_up_pow2

CFG = qwen2.QWEN_SMALL
PARAMS = qwen2.init_params(CFG, jax.random.PRNGKey(0))
TOK = HashTokenizer(CFG.vocab_size)

_LIVE: list = []


@pytest.fixture(autouse=True)
def _cleanup():
    yield
    while _LIVE:
        _LIVE.pop().stop()


def _mgr(hooks=None, **kw):
    kw.setdefault("acquire_timeout", 0.5)
    kw.setdefault("probe_interval", 0.05)
    kw.setdefault("probe_timeout", 0.4)
    kw.setdefault("degrade_after", 1)
    kw.setdefault("recover_after", 1)
    mgr = BackendManager(hooks=hooks or FakeHooks("ok"), **kw)
    _LIVE.append(mgr)
    return mgr


def _engine(manager=None, **cfg_kw):
    cfg_kw.setdefault("page_size", 16)
    cfg_kw.setdefault("pool_pages", 33)
    cfg_kw.setdefault("max_seqs", 4)
    cfg_kw.setdefault("max_seq_tokens", 128)
    cfg_kw.setdefault("prefill_chunk", 32)
    cfg_kw.setdefault("deadline_ms", 60000)
    eng = GenerationEngine(
        PARAMS, CFG, tokenizer=TOK,
        config=GenServeConfig(**cfg_kw),
        manager=manager or _mgr())
    _LIVE.append(eng)
    return eng


def _prompt(n: int, seed: int = 0) -> list[int]:
    rng = np.random.default_rng(seed * 1000 + n)
    return [int(x) for x in rng.integers(4, CFG.vocab_size, n)]


def _dense_ref(prompt: list[int], max_new: int,
               max_len: int = 128) -> list[int]:
    logits, caches = qwen2.prefill(
        PARAMS, CFG, jnp.asarray([prompt], jnp.int32), max_len)
    tok = int(np.asarray(logits)[0].argmax())
    out = [tok]
    pos = len(prompt)
    while len(out) < max_new and tok != TOK.eos_id:
        lg, caches = qwen2.decode_step(
            PARAMS, CFG, jnp.asarray([tok], jnp.int32), caches,
            jnp.asarray(pos))
        tok = int(np.asarray(lg)[0].argmax())
        out.append(tok)
        pos += 1
    return out


# ---------------------------------------------------------------------------
# kernel: ragged paged attention vs gather + layers.attention
# ---------------------------------------------------------------------------
class TestRaggedKernel:
    def test_kernel_bit_exact_vs_gather_reference(self):
        """Every lane — decode (Tq slots, 1 valid), mid-prefill chunk,
        all-padding — matches gathering that lane's pages and running
        the dense attention it abbreviates, bit for bit."""
        rng = np.random.default_rng(3)
        lmax, tq, p, ps = 4, 8, 6, 4
        hkv, dh = CFG.kv_heads, CFG.hidden // CFG.heads
        h = CFG.heads
        dt = np.float32
        k_pages = rng.standard_normal((p, ps, hkv, dh)).astype(dt)
        v_pages = rng.standard_normal((p, ps, hkv, dh)).astype(dt)
        q = rng.standard_normal((lmax, tq, h, dh)).astype(dt)
        tables = np.zeros((lmax, p), np.int32)
        positions = np.full((lmax, tq), -1, np.int32)
        # lane 0: decode at slot 9 (3 pages resident)
        tables[0, :3] = [1, 2, 3]
        positions[0, 0] = 9
        # lane 1: prefill chunk rows 0..tq-1 at slots 4..11
        tables[1, :3] = [4, 5, 2]
        positions[1] = np.arange(4, 4 + tq)
        # lane 2: all padding (null table, all -1) — output discarded
        out = pk.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(tables), jnp.asarray(positions), interpret=True)
        max_len = p * ps
        slot = np.arange(max_len)
        for lane in (0, 1):
            ks = k_pages[tables[lane]].reshape(max_len, hkv, dh)
            vs = v_pages[tables[lane]].reshape(max_len, hkv, dh)
            mask = np.where(
                slot[None, :] <= positions[lane][:, None], 0.0, -1e30)
            ref = layers.attention(
                jnp.asarray(q[lane])[None],
                layers.repeat_kv(jnp.asarray(ks)[None], h // hkv),
                layers.repeat_kv(jnp.asarray(vs)[None], h // hkv),
                jnp.asarray(mask)[None, None])[0]
            valid = positions[lane] >= 0
            np.testing.assert_array_equal(
                np.asarray(out[lane])[valid], np.asarray(ref)[valid])


# ---------------------------------------------------------------------------
# model: fused ragged step vs the sequential paged programs
# ---------------------------------------------------------------------------
class TestFusedStep:
    def test_fused_mixed_step_bit_exact_vs_sequential(self):
        """Two decode lanes + one mid-prompt prefill chunk in ONE fused
        dispatch == the legacy chunk program then the legacy batched
        decode program, logits and pool content bit-identical."""
        ps, pool_pages, w = 16, 12, 4
        lmax = 8
        prompts = [_prompt(7, seed=1), _prompt(19, seed=2)]
        chunk_prompt = _prompt(21, seed=3)
        # -- legacy path: prefill both decode seqs, one decode step for
        # both, then the chunk seq's first chunk
        pages_a = qwen2.init_kv_pages(CFG, pool_pages, ps)
        tables = np.zeros((3, w), np.int32)
        tables[0, :2] = [1, 2]
        tables[1, :2] = [3, 4]
        tables[2, :2] = [5, 6]
        toks = [None, None]
        for i, prompt in enumerate(prompts):
            chunk = prompt + [0] * (32 - len(prompt))
            lg, pages_a = qwen2.paged_prefill_chunk(
                PARAMS, CFG, jnp.asarray(chunk, jnp.int32), pages_a,
                jnp.asarray(tables[i]), jnp.asarray(0),
                jnp.asarray(len(prompt)))
            toks[i] = int(np.asarray(lg).argmax())
        dec_logits, pages_a = qwen2.paged_decode_step(
            PARAMS, CFG, jnp.asarray(toks, jnp.int32), pages_a,
            jnp.asarray(tables[:2]),
            jnp.asarray([len(p) for p in prompts], jnp.int32))
        chunk_pad = chunk_prompt + [0] * (32 - len(chunk_prompt))
        pre_logits, pages_a = qwen2.paged_prefill_chunk(
            PARAMS, CFG, jnp.asarray(chunk_pad, jnp.int32), pages_a,
            jnp.asarray(tables[2]), jnp.asarray(0),
            jnp.asarray(len(chunk_prompt)))
        # -- fused path: same initial prefills, then ONE ragged step
        pages_b = qwen2.init_kv_pages(CFG, pool_pages, ps)
        for i, prompt in enumerate(prompts):
            chunk = prompt + [0] * (32 - len(prompt))
            _, pages_b = qwen2.paged_prefill_chunk(
                PARAMS, CFG, jnp.asarray(chunk, jnp.int32), pages_b,
                jnp.asarray(tables[i]), jnp.asarray(0),
                jnp.asarray(len(prompt)))
        tq = 32
        n_valid = len(chunk_prompt)
        f = round_up_pow2(2 + n_valid, 16)
        meta, (tokens, lane_id, lane_pos, positions, logit_rows,
               lane_tables) = pack_ragged_meta(lmax, w, f)
        tokens[:] = 0
        lane_id[:] = lmax - 1
        lane_pos[:] = 0
        positions[:] = -1
        logit_rows[:] = 0
        lane_tables[:] = 0
        for i in range(2):
            tokens[i] = toks[i]
            lane_id[i] = i
            positions[i] = len(prompts[i])
            lane_tables[i] = tables[i]
        for j in range(n_valid):
            fi = 2 + j
            tokens[fi] = chunk_prompt[j]
            lane_id[fi] = lmax - 2  # THE chunk lane, by convention
            lane_pos[fi] = j
            positions[fi] = j
        lane_tables[lmax - 2] = tables[2]
        logit_rows[0], logit_rows[1] = 0, 1
        logit_rows[2] = 2 + n_valid - 1
        _ids, fused_logits, pages_b = qwen2.ragged_fused_step(
            PARAMS, CFG, jnp.asarray(meta), pages_b,
            lmax=lmax, w=w, tq=tq, attn_impl="xla")
        fused = np.asarray(fused_logits)
        np.testing.assert_array_equal(np.asarray(dec_logits), fused[:2])
        np.testing.assert_array_equal(np.asarray(pre_logits), fused[2])
        # pool content identical on every real page (page 0 = NULL dump)
        np.testing.assert_array_equal(
            np.asarray(pages_a)[:, :, 1:], np.asarray(pages_b)[:, :, 1:])

    def test_fused_pallas_interpret_matches_xla(self):
        """attn_impl="pallas_interpret" (the kernel, interpreted on CPU)
        and attn_impl="xla" (the block-gather fallback) agree bit-for-bit
        on real rows AND pool content — the fallback equivalence the
        serving path relies on when no TPU is attached."""
        # lmax sized so the (Lmax,) logit_rows can cover every valid
        # chunk row (direct callers pick their own lane geometry)
        ps, pool_pages, w, lmax = 16, 8, 4, 32
        prompt = _prompt(21, seed=5)
        tq = 32
        n_valid = len(prompt)
        f = round_up_pow2(n_valid, 16)
        meta, (tokens, lane_id, lane_pos, positions, logit_rows,
               lane_tables) = pack_ragged_meta(lmax, w, f)
        tokens[:] = 0
        lane_id[:] = lmax - 1
        lane_pos[:] = 0
        positions[:] = -1
        logit_rows[:] = 0
        lane_tables[:] = 0
        for j in range(n_valid):
            tokens[j] = prompt[j]
            lane_id[j] = lmax - 2  # THE chunk lane, by convention
            lane_pos[j] = j
            positions[j] = j
        lane_tables[lmax - 2, :2] = [1, 2]
        logit_rows[:n_valid] = np.arange(n_valid, dtype=np.int32)
        outs = {}
        for impl in ("xla", "pallas_interpret"):
            pages = qwen2.init_kv_pages(CFG, pool_pages, ps)
            _ids, lg, pages = qwen2.ragged_fused_step(
                PARAMS, CFG, jnp.asarray(np.array(meta)), pages,
                lmax=lmax, w=w, tq=tq, attn_impl=impl)
            outs[impl] = (np.asarray(lg)[:n_valid], np.asarray(pages))
        np.testing.assert_array_equal(outs["xla"][0],
                                      outs["pallas_interpret"][0])
        np.testing.assert_array_equal(outs["xla"][1][:, :, 1:],
                                      outs["pallas_interpret"][1][:, :, 1:])


# ---------------------------------------------------------------------------
# engine: shared-prefix caching semantics
# ---------------------------------------------------------------------------
class TestPrefixCache:
    def test_prefix_hit_skips_prefill_and_matches_dense(self):
        """Second identical prompt adopts the cached prefix pages —
        fewer first-pass prefill tokens, same emitted tokens as the
        dense reference (adopted KV is the SAME bytes prefill wrote)."""
        eng = _engine()
        shared = _prompt(50, seed=7)
        out1 = eng.generate(shared, max_new_tokens=4)
        first_after_1 = eng.stats.prefill_tokens_first
        h2 = eng.submit(shared, max_new_tokens=4)
        out2 = h2.result()
        ref = _dense_ref(shared, 4)
        assert out1 == ref and out2 == ref
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
        shared pages (outputs stay dense-exact) and the re-prefill pass
        re-hits the prefix cache instead of redoing the shared pages."""
        eng = _engine(page_size=8, pool_pages=8, max_seq_tokens=56,
                      prefill_chunk=16)
        common = _prompt(16, seed=9)
        prompts = [common + _prompt(n, seed=10 + n) for n in (5, 9, 12)]
        handles = [eng.submit(p, max_new_tokens=20) for p in prompts]
        outs = [h.result() for h in handles]
        assert outs == [_dense_ref(p, 20, max_len=56) for p in prompts]
        assert eng.stats.evictions > 0, "pool was sized to force eviction"
        assert eng.stats.prefix_hits > 0
        assert eng.stats.prefill_tokens_re > 0, (
            "re-prefill after eviction not accounted separately")
        assert eng.stats.prefill_tokens_first > 0

    def test_idle_cached_pages_reclaimed_lru_under_pressure(self):
        """Idle prefix-cached pages are capacity, not a leak: when the
        free list drains, admission reclaims them LRU and the engine
        keeps serving exactly."""
        eng = _engine(page_size=8, pool_pages=12, max_seq_tokens=64,
                      max_seqs=2, prefill_chunk=16)
        # populate the cache: distinct prompts, each registering pages
        for s in range(4):
            eng.generate(_prompt(17, seed=20 + s), max_new_tokens=2)
        assert len(eng._prefix_cache) > 0
        cached_before = len(eng._prefix_cache)
        # now a burst that needs more pages than the free list holds
        prompts = [_prompt(30, seed=40 + s) for s in range(3)]
        handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
        outs = [h.result() for h in handles]
        assert outs == [_dense_ref(p, 8, max_len=64) for p in prompts]
        assert len(eng._prefix_cache) <= cached_before + 3 * 3

    def test_cpu_fallback_serves_prefix_hits_exactly(self):
        """Degraded backend (CPU-served steps): the prefix cache still
        hits and the XLA fallback attention keeps outputs dense-exact —
        re-platforming resets the cache rather than serving stale KV."""
        mgr = _mgr(FakeHooks("hang"), acquire_timeout=0.3)
        eng = _engine(manager=mgr, deadline_ms=30000)
        shared = _prompt(40, seed=13)
        out1 = eng.generate(shared, max_new_tokens=4)
        out2 = eng.generate(shared, max_new_tokens=4)
        ref = _dense_ref(shared, 4)
        assert out1 == ref and out2 == ref
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
