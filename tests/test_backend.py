"""Backend lifecycle manager tests: probe → acquire → serve → degrade →
recover (ISSUE 6 tentpole).

A fault-injecting FakeHooks backend drives the scenarios a lost or hung
accelerator produces in production:

* hang-on-acquire — the caller's timeout fires, the service answers from
  CPU host arrays, and no caller ever blocks on PJRT init while holding a
  lock (the round-5 deadlock regression; the NORNSAN guard in
  ``BackendManager.await_ready`` raises on any held instrumented lock
  when the sanitizer is active, so the CI sanitize run asserts the
  invariant live).
* probe-flap — hysteresis (``degrade_after``/``recover_after``) prevents
  state thrash on an intermittently healthy device.
* recovery — the re-acquired device gets a corpus re-upload whose search
  results match a from-scratch rebuild.
"""

from __future__ import annotations

import importlib
import os
import threading
import time

import numpy as np
import pytest

from nornicdb_tpu import backend as backend_mod
from nornicdb_tpu.backend import BackendManager, FakeHooks, hooks_from_env
from nornicdb_tpu.errors import BackendLockHeldError, DeviceUnavailable
from nornicdb_tpu.ops.similarity import DeviceCorpus

# `backend.manager` is the accessor function; the module hides behind it
manager_mod = importlib.import_module("nornicdb_tpu.backend.manager")

DIMS = 16

_LIVE_MANAGERS: list[BackendManager] = []


@pytest.fixture(autouse=True)
def _stop_managers():
    """Stop every test-built manager's probe loop at test end, so dozens
    of 30ms probe threads don't keep spinning for the whole session."""
    yield
    while _LIVE_MANAGERS:
        _LIVE_MANAGERS.pop().stop()


def _mgr(hooks, **kw):
    kw.setdefault("acquire_timeout", 0.3)
    kw.setdefault("probe_interval", 0.03)
    kw.setdefault("probe_timeout", 0.25)
    kw.setdefault("degrade_after", 3)
    kw.setdefault("recover_after", 2)
    mgr = BackendManager(hooks=hooks, **kw)
    _LIVE_MANAGERS.append(mgr)
    return mgr


def _wait_state(mgr, state, timeout=10.0):
    deadline = time.monotonic() + timeout
    while mgr.state != state and time.monotonic() < deadline:
        time.sleep(0.01)
    assert mgr.state == state, f"never reached {state}, stuck at {mgr.state}"


def _corpus(mgr, n=64, seed=0):
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, DIMS)).astype(np.float32)
    c = DeviceCorpus(dims=DIMS, backend=mgr)
    c.add_batch([f"n{i}" for i in range(n)], vecs)
    return c, vecs


class TestStateMachine:
    def test_ok_acquire_reaches_ready(self):
        mgr = _mgr(FakeHooks("ok"))
        assert mgr.await_ready() is True
        assert mgr.state == backend_mod.READY
        assert mgr.stats()["device"]["platform"] == "fake"

    def test_hang_acquire_times_out_to_degraded(self):
        mgr = _mgr(FakeHooks("hang"), acquire_timeout=0.2)
        t0 = time.perf_counter()
        ok = mgr.await_ready()
        waited = time.perf_counter() - t0
        assert ok is False
        assert waited < 1.2, "await_ready must honor the acquire timeout"
        _wait_state(mgr, backend_mod.DEGRADED_CPU, timeout=2.0)
        assert mgr.counters.acquire_timeouts >= 1

    def test_failing_acquire_degrades(self):
        mgr = _mgr(FakeHooks("fail"))
        assert mgr.await_ready() is False
        _wait_state(mgr, backend_mod.DEGRADED_CPU, timeout=2.0)

    def test_degraded_await_fails_fast(self):
        mgr = _mgr(FakeHooks("hang"), acquire_timeout=0.2)
        mgr.await_ready()
        _wait_state(mgr, backend_mod.DEGRADED_CPU, timeout=2.0)
        t0 = time.perf_counter()
        assert mgr.await_ready() is False
        assert time.perf_counter() - t0 < 0.05, (
            "once degraded, callers must not re-pay the acquire timeout"
        )

    def test_probe_flap_hysteresis_no_thrash(self):
        """Fewer than degrade_after consecutive failures never degrade,
        alternation never recovers, and sustained streaks transition
        exactly once — driven deterministically through _probe_tick (the
        probe loop's body) with the background loop parked."""
        hooks = FakeHooks("ok")
        mgr = _mgr(hooks, degrade_after=3, recover_after=2,
                   probe_interval=60.0)
        assert mgr.await_ready()

        def tick(mode):
            hooks.set_mode(mode)
            mgr._probe_tick()

        # two failures, then green: hysteresis keeps READY
        tick("fail")
        tick("fail")
        assert mgr.state == backend_mod.READY
        tick("ok")  # streak resets
        tick("fail")
        tick("fail")
        assert mgr.state == backend_mod.READY
        assert mgr.counters.degrades == 0

        # third consecutive failure: degrade exactly once
        tick("fail")
        assert mgr.state == backend_mod.DEGRADED_CPU
        assert mgr.counters.degrades == 1

        # strict alternation can never assemble recover_after=2 greens:
        # the manager stays parked (no flap-thrash in either direction)
        for j in range(6):
            tick("ok" if j % 2 == 0 else "fail")
        assert mgr.state == backend_mod.DEGRADED_CPU
        assert mgr.counters.degrades == 1
        assert mgr.counters.recoveries == 0

        # two consecutive greens: recover exactly once
        tick("ok")
        tick("ok")
        assert mgr.state == backend_mod.READY
        assert mgr.counters.recoveries == 1

    def test_slow_probe_counts_as_failure(self):
        hooks = FakeHooks("ok")
        mgr = _mgr(hooks, probe_latency_threshold=0.02, probe_timeout=1.0)
        assert mgr.await_ready()
        hooks.set_mode("slow")
        hooks.delay = 0.05  # over the latency threshold, under the timeout
        _wait_state(mgr, backend_mod.DEGRADED_CPU)
        assert mgr.counters.probe_failures >= mgr.degrade_after

    @pytest.mark.parametrize("answer_s,wall_s,degrades", [
        (0.001, 0.05, False),  # healthy chip, probe queued behind work
        (0.05, 0.0, True),     # device itself slow to answer
    ])
    def test_latency_is_judged_on_the_answer_not_the_queue(
            self, answer_s, wall_s, degrades):
        """Hooks that can separate the device's answer time from the time
        spent queued behind serving work return it, and THAT is held to
        the threshold; the wall-clock round trip is only reported."""

        class Hooks(FakeHooks):
            def probe(self):
                super().probe()
                time.sleep(wall_s)
                return answer_s

        mgr = _mgr(Hooks("ok"), probe_latency_threshold=0.02,
                   probe_timeout=1.0)
        assert mgr.await_ready()
        if degrades:
            _wait_state(mgr, backend_mod.DEGRADED_CPU)
            return
        deadline = time.monotonic() + 5.0
        while mgr.counters.probes < 8 and time.monotonic() < deadline:
            time.sleep(0.02)
        s = mgr.stats()
        assert mgr.counters.probes >= 8
        assert (s["state"], s["probe_failures_total"]) == \
            (backend_mod.READY, 0)
        assert s["probe_latency_s"] == answer_s
        assert s["probe_wall_s"] >= wall_s

    def test_real_probe_reports_its_answer_time(self):
        answered = backend_mod.RealHooks().probe()
        assert isinstance(answered, float) and 0.0 <= answered < 1.0

    def test_stats_shape(self):
        mgr = _mgr(FakeHooks("ok"))
        mgr.await_ready()
        s = mgr.stats()
        for key in ("state", "fallbacks_total", "recoveries_total",
                    "degrades_total", "probe_failures_total", "transitions"):
            assert key in s, s
        assert s["transitions"][-1]["to"] == backend_mod.READY

    def test_fake_hooks_from_env(self, monkeypatch):
        monkeypatch.setenv("NORNICDB_FAKE_BACKEND", "hang")
        h = hooks_from_env()
        assert isinstance(h, FakeHooks) and h.mode == "hang"
        monkeypatch.setenv("NORNICDB_FAKE_BACKEND", "slow:0.2")
        h = hooks_from_env()
        assert h.mode == "slow" and h.delay == 0.2
        monkeypatch.setenv("NORNICDB_FAKE_BACKEND", "bogus")
        assert hooks_from_env() is None
        monkeypatch.delenv("NORNICDB_FAKE_BACKEND")
        assert hooks_from_env() is None


class TestCorpusFallback:
    def test_degraded_search_serves_exact_cpu_results(self):
        mgr = _mgr(FakeHooks("hang"), acquire_timeout=0.2)
        c, vecs = _corpus(mgr)
        t0 = time.perf_counter()
        res = c.search(vecs[7], k=5)
        assert time.perf_counter() - t0 < 1.2
        _wait_state(mgr, backend_mod.DEGRADED_CPU, timeout=2.0)
        assert res[0][0][0] == "n7"
        assert res[0][0][1] == pytest.approx(1.0, abs=1e-5)
        # exact CPU reference over normalized rows
        norm = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
        want = np.argsort(-(norm @ norm[7]))[:5]
        assert [r[0] for r in res[0]] == [f"n{i}" for i in want]
        assert mgr.counters.fallbacks >= 1

    def test_degraded_score_subset(self):
        mgr = _mgr(FakeHooks("hang"), acquire_timeout=0.2)
        c, vecs = _corpus(mgr)
        scored = c.score_subset(vecs[3], ["n3", "n5", "missing"])
        ids = [i for i, _ in scored]
        assert ids == ["n3", "n5"]
        assert scored[0][1] == pytest.approx(1.0, abs=1e-5)

    def test_fail_policy_raises_instead_of_fallback(self):
        mgr = _mgr(FakeHooks("hang"), acquire_timeout=0.2, fallback="fail")
        c, vecs = _corpus(mgr)
        with pytest.raises(DeviceUnavailable):
            c.search(vecs[0], k=3)

    def test_recovery_reupload_equivalence_vs_rebuild(self):
        """Writes land while degraded; after recovery the re-uploaded
        device corpus must answer exactly like a from-scratch rebuild."""
        hooks = FakeHooks("hang")
        mgr = _mgr(hooks, acquire_timeout=0.2)
        c, vecs = _corpus(mgr, n=48)
        rng = np.random.default_rng(99)
        extra = rng.standard_normal((16, DIMS)).astype(np.float32)
        c.search(vecs[0], k=3)  # trips degraded
        c.add_batch([f"x{i}" for i in range(16)], extra)  # degraded writes
        c.remove("n5")
        _wait_state(mgr, backend_mod.DEGRADED_CPU, timeout=2.0)

        hooks.set_mode("ok")
        _wait_state(mgr, backend_mod.READY)
        assert mgr.counters.recoveries == 1

        ok_mgr = _mgr(FakeHooks("ok"))
        fresh = DeviceCorpus(dims=DIMS, backend=ok_mgr)
        fresh.add_batch([f"n{i}" for i in range(48)], vecs)
        fresh.add_batch([f"x{i}" for i in range(16)], extra)
        fresh.remove("n5")

        for q in (vecs[2], extra[4], vecs[5]):
            got = c.search(q, k=8, exact=True)[0]
            want = fresh.search(q, k=8, exact=True)[0]
            assert [i for i, _ in got] == [i for i, _ in want]
            for (_, a), (_, b) in zip(got, want):
                assert a == pytest.approx(b, abs=1e-5)
        assert c.sync_stats.full_uploads >= 1

    def test_recovery_dirty_mode_patches_degraded_writes(self):
        """recovery_reupload="dirty" trusts a surviving resident buffer:
        only blocks written while degraded transfer, and results still
        match a rebuild."""
        hooks = FakeHooks("ok")
        mgr = _mgr(hooks, recovery_reupload="dirty", degrade_after=1,
                   recover_after=1)
        # 500 of 512 capacity slots: the degraded write dirties 1 of 4
        # blocks, safely under the patch-vs-full dirty-fraction threshold
        # (and leaves free slots so the write doesn't force a grow)
        c, vecs = _corpus(mgr, n=500)
        assert c.search(vecs[0], k=3)[0][0][0] == "n0"  # device resident
        fulls_before = c.sync_stats.full_uploads

        hooks.set_mode("fail")
        _wait_state(mgr, backend_mod.DEGRADED_CPU)
        v_new = np.ones(DIMS, np.float32)
        c.add("fresh", v_new)
        assert c.search(v_new, k=1)[0][0][0] == "fresh"  # CPU path sees it

        hooks.set_mode("ok")
        _wait_state(mgr, backend_mod.READY)
        res = c.search(v_new, k=1, exact=True)
        assert res[0][0][0] == "fresh"  # device path sees the patched row
        assert c.sync_stats.full_uploads == fulls_before, (
            "dirty-mode recovery must patch, not re-ship the whole corpus"
        )

    def test_cluster_fit_delivered_while_degraded_installs_on_recovery(self):
        """set_clusters during an outage must stash the fit and install it
        when the device comes back — not silently drop it until the next
        periodic re-cluster."""
        from nornicdb_tpu.ops.kmeans import kmeans_fit

        hooks = FakeHooks("ok")
        mgr = _mgr(hooks, degrade_after=1, recover_after=1)
        c, vecs = _corpus(mgr, n=64)
        assert c.search(vecs[0], k=1)[0]  # device resident
        res = kmeans_fit(vecs, k=4, iters=5)
        assignments = {f"n{i}": int(a) for i, a in enumerate(res.assignments)}

        hooks.set_mode("fail")
        _wait_state(mgr, backend_mod.DEGRADED_CPU)
        c.set_clusters(res.centroids, assignments)
        assert c._centroids is None and c._pending_clusters is not None

        hooks.set_mode("ok")
        _wait_state(mgr, backend_mod.READY)
        deadline = time.monotonic() + 5
        while c._centroids is None and time.monotonic() < deadline:
            time.sleep(0.02)
        assert c._centroids is not None, "stashed fit never installed"
        assert c._pending_clusters is None
        # pruned search serves through the recovered cluster index
        res1 = c.search(vecs[9], k=3, n_probe=2)
        assert res1[0][0][0] == "n9"

    def test_full_recovery_reinstalls_cluster_state_from_host_copy(self):
        """Full-mode recovery assumes device memory is lost: the IVF
        blocks/centroids of the old incarnation must be dropped (not
        dereferenced by the next pruned search) and re-installed from the
        fit's host copy."""
        from nornicdb_tpu.ops.kmeans import kmeans_fit

        hooks = FakeHooks("ok")
        mgr = _mgr(hooks, degrade_after=1, recover_after=1)
        c, vecs = _corpus(mgr, n=64)
        assert c.search(vecs[0], k=1)[0]  # warm acquire: manager READY
        res = kmeans_fit(vecs, k=4, iters=5)
        c.set_clusters(res.centroids,
                       {f"n{i}": int(a) for i, a in enumerate(res.assignments)})
        assert c._centroids is not None

        hooks.set_mode("fail")
        _wait_state(mgr, backend_mod.DEGRADED_CPU)
        hooks.set_mode("ok")
        _wait_state(mgr, backend_mod.READY)

        # the reinstall runs on a background thread: wait for it
        deadline = time.monotonic() + 5
        while c._centroids is None and time.monotonic() < deadline:
            time.sleep(0.02)
        assert c._centroids is not None, "host-copy fit never reinstalled"
        res1 = c.search(vecs[9], k=3, n_probe=2)
        assert res1[0][0][0] == "n9"

    def test_cpu_results_match_device_results(self):
        """Acceptance criterion tail: after the fault clears, a device-path
        search returns results identical to the CPU path."""
        hooks = FakeHooks("hang")
        mgr = _mgr(hooks, acquire_timeout=0.2)
        c, vecs = _corpus(mgr)
        cpu = c.search(vecs[11], k=6)[0]
        hooks.set_mode("ok")
        _wait_state(mgr, backend_mod.READY)
        dev = c.search(vecs[11], k=6, exact=True)[0]
        # identical up to bf16 device scoring: the top hit matches exactly,
        # and every rank's score agrees within bf16 tolerance (near-ties
        # may swap order between f32 host and bf16 MXU scoring)
        assert cpu[0][0] == dev[0][0] == "n11"
        for (_, a), (_, b) in zip(cpu, dev):
            assert a == pytest.approx(b, abs=2e-2)


class TestServiceUnderFault:
    """The acceptance criterion end-to-end: with the backend forced
    unreachable, a SearchService.search() issued after a write returns a
    correct CPU-computed result within acquire_timeout + 1s."""

    def _service(self, mgr):
        from nornicdb_tpu.search.service import SearchConfig, SearchService
        from nornicdb_tpu.storage import MemoryEngine
        from nornicdb_tpu.storage.types import Node

        storage = MemoryEngine()
        svc = SearchService(storage, dims=DIMS,
                            config=SearchConfig(min_similarity=-1.0))
        rng = np.random.default_rng(5)
        vecs = rng.standard_normal((20, DIMS)).astype(np.float32)
        for i in range(20):
            node = Node(id=f"doc{i}", labels=["Doc"],
                        properties={"content": f"document number {i}"},
                        embedding=vecs[i])
            storage.create_node(node)
            svc.index_node(node)
        # inject the fault-managed backend into the corpus the service built
        svc._corpus._backend = mgr
        return svc, storage, vecs

    def test_search_after_write_answers_from_cpu_within_deadline(self):
        from nornicdb_tpu.storage.types import Node

        mgr = _mgr(FakeHooks("hang"), acquire_timeout=0.5)
        svc, storage, vecs = self._service(mgr)
        v = np.full(DIMS, 0.5, np.float32)
        node = Node(id="fresh", labels=["Doc"],
                    properties={"content": "the freshest document"},
                    embedding=v)
        storage.create_node(node)
        svc.index_node(node)  # the write that used to wedge _sync

        done = threading.Event()
        out: list = []

        def run():
            out.append(svc.vector_candidates(v, k=3))
            done.set()

        threading.Thread(target=run, daemon=True).start()
        assert done.wait(mgr.acquire_timeout + 1.0), (
            "search blocked past acquire_timeout + 1s with the backend "
            "unreachable — the round-5 deadlock is back"
        )
        assert out[0][0][0] == "fresh"
        _wait_state(mgr, backend_mod.DEGRADED_CPU, timeout=2.0)
        # lifecycle surfaces through the service stats snapshot
        snap = svc.stats_snapshot()
        assert snap["backend"]["state"] == backend_mod.DEGRADED_CPU
        assert snap["backend"]["fallbacks_total"] >= 1

    def test_concurrent_writers_and_searchers_never_wedge(self):
        """Round-5 regression shape: a writer stream plus searchers while
        the backend hangs. Everything completes; nothing deadlocks."""
        from nornicdb_tpu.storage.types import Node

        mgr = _mgr(FakeHooks("hang"), acquire_timeout=0.3)
        svc, storage, vecs = self._service(mgr)
        stop = threading.Event()
        errors: list = []

        def writer():
            rng = np.random.default_rng(17)
            i = 0
            while not stop.is_set():
                node = Node(id=f"w{i % 10}", labels=["Doc"],
                            properties={"content": f"write {i}"},
                            embedding=rng.standard_normal(DIMS).astype(
                                np.float32))
                try:
                    svc.index_node(node)
                except Exception as e:  # pragma: no cover - failure path
                    errors.append(e)
                i += 1
                time.sleep(0.002)

        def searcher():
            for _ in range(10):
                try:
                    svc.vector_candidates(vecs[3], k=5)
                except Exception as e:  # pragma: no cover - failure path
                    errors.append(e)

        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        threads = [threading.Thread(target=searcher, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=15.0)
            assert not t.is_alive(), "searcher wedged under hung backend"
        stop.set()
        wt.join(timeout=5.0)
        assert not errors, errors

    def test_batched_path_serves_under_fault(self):
        from nornicdb_tpu.search.service import SearchConfig, SearchService
        from nornicdb_tpu.storage import MemoryEngine
        from nornicdb_tpu.storage.types import Node

        mgr = _mgr(FakeHooks("hang"), acquire_timeout=0.3)
        storage = MemoryEngine()
        svc = SearchService(storage, dims=DIMS, config=SearchConfig())
        rng = np.random.default_rng(3)
        vecs = rng.standard_normal((12, DIMS)).astype(np.float32)
        for i in range(12):
            node = Node(id=f"d{i}", labels=["Doc"],
                        properties={"content": f"doc {i}"},
                        embedding=vecs[i])
            svc.index_node(node)
        svc._corpus._backend = mgr
        results = []
        threads = [
            threading.Thread(
                target=lambda i=i: results.append(
                    svc.vector_candidates(vecs[i], k=3)
                ),
                daemon=True,
            )
            for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10.0)
            assert not t.is_alive(), "batched search wedged"
        assert len(results) == 6 and all(r for r in results)


class TestLockGuard:
    """The runtime twin of NL-DEV01: backend acquisition refuses to run
    while the caller holds an instrumented lock."""

    def test_guard_raises_when_nornsan_reports_held_locks(self, monkeypatch):
        import importlib

        manager_mod = importlib.import_module("nornicdb_tpu.backend.manager")
        monkeypatch.setattr(
            manager_mod, "_held_lock_sites",
            lambda: ["ops/similarity.py:373"],
        )
        mgr = _mgr(FakeHooks("ok"))
        with pytest.raises(BackendLockHeldError):
            mgr.await_ready()
        assert mgr.counters.lock_violations == 1

    def test_guard_inactive_without_nornsan(self):
        mgr = _mgr(FakeHooks("ok"))
        assert mgr.await_ready() is True  # no instrumented locks -> no-op

    def test_corpus_search_path_holds_no_lock_at_gate(self, monkeypatch):
        """Structural assertion without the full sanitizer: the corpus
        gate must run before _sync_lock is taken."""
        import importlib

        manager_mod = importlib.import_module("nornicdb_tpu.backend.manager")
        mgr = _mgr(FakeHooks("ok"))
        c, vecs = _corpus(mgr)
        sync_lock = c._sync_lock

        def held():
            # RLock._is_owned: does THIS thread hold the corpus lock?
            return ["sync_lock"] if sync_lock._is_owned() else []

        monkeypatch.setattr(manager_mod, "_held_lock_sites", held)
        res = c.search(vecs[0], k=3)  # must not raise BackendLockHeldError
        assert res[0][0][0] == "n0"


class TestDefaultManagerWiring:
    def test_manager_stats_surface(self):
        backend_mod.manager().ensure_started()
        s = backend_mod.manager_stats()
        assert s is not None and "state" in s

    def test_configure_applies_to_fresh_default(self):
        from nornicdb_tpu.config import BackendConfig

        backend_mod.reset_default()
        try:
            backend_mod.configure(BackendConfig(acquire_timeout=3.5,
                                                fallback="cpu"))
            mgr = backend_mod.manager()
            assert mgr.acquire_timeout == 3.5
        finally:
            backend_mod.reset_default()
            backend_mod.configure()  # restore construction defaults


class TestRealHooksTouch:
    def test_touch_names_the_device(self):
        """READY is reached on whatever platform answers (the CPU backend
        here); the touch result says which, down to the device kind."""
        import jax

        info = backend_mod.RealHooks().touch()
        dev = jax.devices()[0]
        assert info == {"platform": dev.platform,
                        "device_kind": dev.device_kind,
                        "device_count": len(jax.devices())}
        mgr = _mgr(FakeHooks("ok"))
        assert mgr.await_ready()
        assert mgr.stats()["device"]["device_kind"] == "fake"

    @pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
    def test_compile_cache_is_placed_from_outside_or_under_the_checkout(
            self, monkeypatch, env_dir):
        """JAX_COMPILATION_CACHE_DIR set: nothing is set in code (JAX
        reads it itself).  Unset: one fixed directory under the checkout —
        never a temp name, pid or time, which could not hit twice."""
        import jax

        before = jax.config.jax_compilation_cache_dir
        try:
            jax.config.update("jax_compilation_cache_dir", "/as/found")
            if env_dir is None:
                monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
                want = manager_mod.COMPILE_CACHE_DIR
            else:
                monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
                want = "/as/found"
            assert manager_mod.place_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert manager_mod.COMPILE_CACHE_DIR == os.path.join(
            repo, ".jax_cache")

