"""Backend lifecycle manager: probe → acquire → serve → degrade → recover.

An earlier round reproduced a production-path deadlock: with the TPU
backend unreachable, the first ``jnp.asarray`` inside ``HostCorpus._sync``
hangs in PJRT init while holding ``_sync_lock``, and every later
``search()`` blocks forever.  This module makes device acquisition a
first-class, *time-bounded* component so that bug class stays dead:

* **One device-owner thread.**  PJRT init and the first-touch
  ``device_put`` run on the manager's worker thread, never on a caller —
  a caller waits on an event with a config timeout and walks away when it
  fires (the hung init keeps running harmlessly in the background; the
  worker discards abandoned results).  Reference shape: the probe chain in
  ``pkg/gpu/gpu.go:354-556``.
* **Explicit lifecycle state machine.**  PROBING → READY → DEGRADED_CPU →
  RECOVERING (→ READY).  A periodic health probe (tiny device round-trip
  with a latency threshold) drives READY→DEGRADED_CPU; hysteresis
  (``degrade_after`` consecutive failures / ``recover_after`` consecutive
  successes) prevents flap-thrash.
* **CPU fallback.**  While DEGRADED_CPU, consumers (``ops/similarity``
  corpora, the embedder) serve from host arrays — the reference's
  device-failure CPU retry, ``pkg/embed/local_gguf.go:202-294``; WindVE
  (PAPERS.md) shows the same CPU↔accelerator decoupling keeping a serving
  stack live.
* **Live recovery.**  When the probe goes green again the manager
  re-acquires on the worker thread, then notifies registered corpora to
  re-upload (full, or trust-the-resident-buffer "dirty" mode) before
  re-entering READY.

The structural invariant — *no device op / backend acquisition under a
held lock* — is enforced three ways: consumers gate through
``await_ready()`` BEFORE taking their locks, nornlint NL-DEV01 flags new
violations statically, and ``await_ready`` itself asserts (under NORNSAN)
that the calling thread holds no instrumented locks.

Import-light by design: ``jax`` is imported lazily inside the real hooks,
so importing this module (or anything that imports it) never triggers
backend init.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from nornicdb_tpu.errors import BackendLockHeldError, DeviceUnavailable
from nornicdb_tpu.telemetry.metrics import REGISTRY as _REGISTRY
from nornicdb_tpu.telemetry.tracing import tracer as _tracer

logger = logging.getLogger(__name__)

# -- lifecycle states --------------------------------------------------------
PROBING = "PROBING"            # initial acquisition in flight
READY = "READY"                # device serving; periodic probe green
DEGRADED_CPU = "DEGRADED_CPU"  # device lost/unreachable; serving from host
RECOVERING = "RECOVERING"      # probe green again; re-acquire + re-upload

STATES = (PROBING, READY, DEGRADED_CPU, RECOVERING)

# -- metrics (cells created at import so the catalog renders before the
#    first transition; only the process-default manager publishes) ----------
_STATE_GAUGE = _REGISTRY.gauge(
    "nornicdb_backend_state",
    "Backend lifecycle state (one-hot: the current state's cell is 1)",
    labels=("state",),
)
_STATE_CELLS = {s: _STATE_GAUGE.labels(s) for s in STATES}
_PROBE_HIST = _REGISTRY.histogram(
    "nornicdb_backend_probe_seconds",
    "Device health-probe round-trip latency",
)
_FALLBACKS = _REGISTRY.counter(
    "nornicdb_backend_fallbacks_total",
    "Device-path requests served from CPU host arrays instead",
    labels=("op",),
)
_FALLBACKS.labels("search")  # eager cells: render at 0 before first use
_FALLBACKS.labels("embed")
_RECOVERIES = _REGISTRY.counter(
    "nornicdb_backend_recoveries_total",
    "DEGRADED_CPU -> READY recoveries (device re-acquired, corpora re-uploaded)",
)
_DEGRADES = _REGISTRY.counter(
    "nornicdb_backend_degrades_total",
    "Transitions into DEGRADED_CPU (acquire timeout or probe failures)",
)
_ACQUIRE_TIMEOUTS = _REGISTRY.counter(
    "nornicdb_backend_acquire_timeouts_total",
    "Device acquisitions abandoned at the configured timeout",
)
_PROBE_FAILURES = _REGISTRY.counter(
    "nornicdb_backend_probe_failures_total",
    "Health probes that timed out, errored, or exceeded the latency threshold",
)
_LOCK_VIOLATIONS = _REGISTRY.counter(
    "nornicdb_backend_lock_violations_total",
    "Backend acquisitions attempted while the caller held a lock (NL-DEV01)",
)


# -- nornsan bridge ----------------------------------------------------------
def _held_lock_sites() -> list[str]:
    """Creation sites of instrumented locks the calling thread holds, when
    the nornsan shim is installed; [] otherwise."""
    import sys

    nornsan = sys.modules.get("nornicdb_tpu.tools.nornsan")
    if nornsan is None or not getattr(nornsan, "active", lambda: False)():
        return []
    held = getattr(nornsan.tracker, "held_sites", None)
    return held() if held is not None else []


# -- persistent compile cache ------------------------------------------------
# fixed path under the checkout: the directory is part of the cache key's
# lookup, so one that moved (mkdtemp, pid, timestamp) would never hit
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def place_compile_cache() -> str:
    """Give JAX's persistent compilation cache a home before the first
    device program compiles: where ``JAX_COMPILATION_CACHE_DIR`` says when
    it is set (JAX reads it itself — nothing is set in code), else
    :data:`COMPILE_CACHE_DIR`.  Returns the directory in effect."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


# -- device hooks ------------------------------------------------------------
class RealHooks:
    """Actual JAX backend operations. Every method may block (that is the
    point — they only ever run on the manager's worker thread)."""

    def touch(self) -> dict:
        """Acquire: PJRT init + first-touch transfer + tiny round-trip.
        Reaches READY on whatever platform answers (the CPU backend under
        tests); the result names the device so status surfaces can say
        which."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        place_compile_cache()
        devs = jax.devices()  # PJRT init happens here on cold processes
        x = jax.device_put(np.ones((8,), np.float32), devs[0])
        float(jnp.sum(x))  # first-touch round trip: compile + transfer back
        return {"platform": devs[0].platform,
                "device_kind": devs[0].device_kind,
                "device_count": len(devs)}

    def probe(self) -> float:
        """Tiny device round-trip; raises if the backend is unhealthy.

        Returns the device's own answer time: two tiny programs go out back
        to back and the seconds between their completions are what is
        judged.  The device runs its queue in order, so however long the
        first waits behind serving work (a 24-layer batch, a corpus upload,
        a k-means fit) the second retires right after it — a healthy, busy
        chip answers in milliseconds here where the wall-clock round trip
        measured on a v5e reached 1.46 s (PERF.md, PR 22).  A sick device
        is slow between the two as well."""
        import jax.numpy as jnp

        first = jnp.asarray(1.0) + 1.0
        second = first + 1.0
        first.block_until_ready()
        t0 = time.perf_counter()
        float(second)
        return time.perf_counter() - t0


class FakeHooks:
    """Fault-injecting backend for tests and the CI chaos step.

    ``mode`` is mutable at runtime so a test can flip a hung backend
    healthy and watch the manager recover:

    * ``ok``   — succeed instantly
    * ``slow`` — succeed after ``delay`` seconds (latency-threshold tests)
    * ``fail`` — raise immediately
    * ``hang`` — block until ``release()`` (or forever)
    """

    def __init__(self, mode: str = "ok", delay: float = 0.0):
        self.mode = mode
        self.delay = delay
        self._release = threading.Event()
        self.touches = 0
        self.probes = 0

    def set_mode(self, mode: str) -> None:
        self.mode = mode
        if mode != "hang":
            self._release.set()
            self._release = threading.Event()

    def release(self) -> None:
        self._release.set()

    def _apply(self) -> None:
        # capture the release event BEFORE reading mode: set_mode sets the
        # old event then swaps in a fresh one, so a waiter that read
        # mode=="hang" must wait on the event set_mode will actually set
        # (waiting on the post-swap event would hang forever)
        release = self._release
        mode = self.mode
        if mode == "hang":
            release.wait()
            # woken by set_mode: re-read and apply the new behavior
            mode = self.mode
        if mode == "fail":
            raise RuntimeError("fake backend failure (NORNICDB_FAKE_BACKEND)")
        if mode == "slow" and self.delay > 0:
            time.sleep(self.delay)

    def touch(self) -> dict:
        self.touches += 1
        self._apply()
        return {"platform": "fake", "device_kind": "fake", "device_count": 1}

    def probe(self) -> None:
        """None: the manager judges the wall-clock round trip instead."""
        self.probes += 1
        self._apply()


def hooks_from_env() -> Optional[FakeHooks]:
    """NORNICDB_FAKE_BACKEND=hang|fail|slow[:seconds]|ok -> FakeHooks."""
    raw = os.environ.get("NORNICDB_FAKE_BACKEND", "").strip().lower()
    if not raw:
        return None
    mode, _, arg = raw.partition(":")
    if mode not in ("ok", "hang", "fail", "slow"):
        logger.warning("NORNICDB_FAKE_BACKEND=%r: unknown mode, ignoring", raw)
        return None
    delay = float(arg) if arg else 0.5
    return FakeHooks(mode=mode, delay=delay)


# -- single-flight device executor ------------------------------------------
class _Result:
    __slots__ = ("event", "value", "error", "abandoned")

    def __init__(self) -> None:
        self.event = threading.Event()
        self.value: Any = None
        self.error: Optional[BaseException] = None
        self.abandoned = False


class _DeviceExecutor:
    """The process's device-owner thread: all potentially-hanging backend
    calls run here.  ``submit()`` waits up to ``timeout`` then abandons the
    call (the worker finishes or hangs in the background; abandoned results
    are discarded).  ``busy`` is True while a call is in flight, so probes
    can count a stuck worker as a failure without stacking work behind it."""

    def __init__(self, name: str = "nornicdb-backend"):
        self._q: "queue.Queue[tuple[Callable[[], Any], _Result]]" = queue.Queue()
        self._busy = 0
        self._mu = threading.Lock()
        self._thread = threading.Thread(
            target=self._loop, name=name, daemon=True
        )
        self._thread.start()

    @property
    def busy(self) -> bool:
        with self._mu:
            return self._busy > 0 or not self._q.empty()

    def _loop(self) -> None:
        while True:
            fn, res = self._q.get()
            if fn is None:  # shutdown sentinel
                return
            with self._mu:
                self._busy += 1
            try:
                res.value = fn()
            except BaseException as e:  # delivered to the waiter, not lost
                res.error = e
            finally:
                with self._mu:
                    self._busy -= 1
                res.event.set()

    def stop(self) -> None:
        """Queue a shutdown sentinel.  The worker exits once any in-flight
        (possibly hung) call finishes; a permanently hung call strands the
        daemon thread — nothing can interrupt a wedged PJRT call."""
        self._q.put((None, None))

    def submit(self, fn: Callable[[], Any], timeout: float) -> Any:
        """Run fn on the worker; TimeoutError if it doesn't finish in time
        (the call itself keeps running — nothing can interrupt a hung PJRT
        init — but the caller walks away)."""
        res = _Result()
        self._q.put((fn, res))
        if not res.event.wait(timeout):
            res.abandoned = True
            raise TimeoutError(f"device op exceeded {timeout:.1f}s")
        if res.error is not None:
            raise res.error
        return res.value


# -- the manager -------------------------------------------------------------
@dataclass
class BackendCounters:
    fallbacks: int = 0
    recoveries: int = 0
    degrades: int = 0
    acquire_timeouts: int = 0
    probes: int = 0
    probe_failures: int = 0
    lock_violations: int = 0
    transitions: list = field(default_factory=list)  # (ts, old, new, reason)


class BackendManager:
    """Owns device acquisition + health for the process (or, in tests, for
    one corpus).  Thread-safe; the state lock is never held across a device
    op — device work runs on the executor thread, bounded by timeouts."""

    def __init__(
        self,
        acquire_timeout: float = 30.0,
        probe_interval: float = 5.0,
        probe_timeout: float = 5.0,
        probe_latency_threshold: float = 1.0,
        degrade_after: int = 3,
        recover_after: int = 2,
        fallback: str = "cpu",
        recovery_reupload: str = "full",
        hooks: Optional[Any] = None,
        publish: bool = False,
    ):
        self.acquire_timeout = acquire_timeout
        self.probe_interval = probe_interval
        self.probe_timeout = probe_timeout
        self.probe_latency_threshold = probe_latency_threshold
        self.degrade_after = max(1, int(degrade_after))
        self.recover_after = max(1, int(recover_after))
        self.fallback = fallback
        self.recovery_reupload = recovery_reupload
        self.hooks = hooks if hooks is not None else (
            hooks_from_env() or RealHooks()
        )
        self._publish = publish
        self._state = PROBING
        self._cond = threading.Condition()
        self._started = False
        self._stop = threading.Event()
        self._executor: Optional[_DeviceExecutor] = None
        self._probe_thread: Optional[threading.Thread] = None
        self._fail_streak = 0
        self._ok_streak = 0
        self._device_info: dict = {}
        self._probe_latency = 0.0  # what the threshold judged
        self._probe_wall = 0.0     # the whole round trip, queueing included
        self.counters = BackendCounters()
        # corpora to re-upload on recovery (weak: test corpora must not be
        # kept alive by the process-default manager)
        self._corpora: list = []  # list[weakref.ref]
        if publish:
            _STATE_CELLS[PROBING].set(1.0)

    # -- lifecycle ----------------------------------------------------------
    def ensure_started(self) -> None:
        with self._cond:
            if self._started:
                return
            self._started = True
            self._executor = _DeviceExecutor()
            self._probe_thread = threading.Thread(
                target=self._probe_loop, name="nornicdb-backend-probe",
                daemon=True,
            )
        # initial acquisition kicks off OUTSIDE the state lock
        self._probe_thread.start()
        threading.Thread(
            target=self._initial_acquire, name="nornicdb-backend-acquire",
            daemon=True,
        ).start()

    def stop(self) -> None:
        self._stop.set()
        if self._executor is not None:
            self._executor.stop()
        with self._cond:
            self._cond.notify_all()

    @property
    def state(self) -> str:
        return self._state

    def ready(self) -> bool:
        """Fast non-blocking check: is the device serving right now?"""
        return self._state == READY

    def await_ready(self, timeout: Optional[float] = None) -> bool:
        """Block (bounded) until the device is serving.  Returns False when
        the wait ends DEGRADED_CPU — callers then serve from host arrays
        (or raise DeviceUnavailable under the "fail" policy via
        require_ready).  Never call this holding a lock: the whole point is
        that the *caller's* locks stay free while acquisition may hang."""
        self._guard_no_locks("await_ready")
        self.ensure_started()
        if self._state == READY:
            return True
        if self._state in (DEGRADED_CPU, RECOVERING):
            # degraded (or mid-recovery, which can include a long corpus
            # re-upload): fail fast to the CPU path — host arrays stay
            # correct, and the probe loop owns getting back to READY
            return False
        deadline = time.monotonic() + (
            self.acquire_timeout if timeout is None else timeout
        )
        with self._cond:
            while self._state == PROBING:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or self._stop.is_set():
                    break
                self._cond.wait(remaining)
        if self._state == READY:
            return True
        if self._state == PROBING:
            # acquisition still in flight past the caller's budget: the
            # caller degrades NOW (its answer can't wait), the manager keeps
            # acquiring in the background
            self._note_acquire_timeout()
        return self._state == READY

    def require_ready(self, timeout: Optional[float] = None) -> None:
        """await_ready that honors the fallback policy: under "fail" a
        degraded backend raises instead of signalling CPU fallback."""
        if not self.await_ready(timeout) and self.fallback != "cpu":
            raise DeviceUnavailable(
                f"backend {self._state}; fallback policy is {self.fallback!r}"
            )

    def note_fallback(self, op: str = "search") -> None:
        """A consumer served a device-path request from CPU host arrays."""
        self.counters.fallbacks += 1
        if self._publish:
            _FALLBACKS.labels(op).inc()

    # -- consumer registration ----------------------------------------------
    def register_corpus(self, corpus: Any) -> None:
        """Corpora re-upload on recovery via _on_backend_recovered(mode)."""
        with self._cond:
            self._corpora = [r for r in self._corpora if r() is not None]
            if not any(r() is corpus for r in self._corpora):
                self._corpora.append(weakref.ref(corpus))

    # -- internals -----------------------------------------------------------
    def _guard_no_locks(self, op: str) -> None:
        held = _held_lock_sites()
        if not held:
            return
        self.counters.lock_violations += 1
        if self._publish:
            _LOCK_VIOLATIONS.inc()
        # held is only ever non-empty under NORNSAN (the instrumented-lock
        # shim), where this is a test failure by contract — the static twin
        # NL-DEV01 covers production builds
        raise BackendLockHeldError(
            f"backend {op} while holding lock(s) {held}: device acquisition "
            "can hang in PJRT init and every thread needing those locks "
            "would block forever (NL-DEV01)"
        )

    def _note_acquire_timeout(self) -> None:
        self.counters.acquire_timeouts += 1
        if self._publish:
            _ACQUIRE_TIMEOUTS.inc()

    def _transition(self, new: str, reason: str) -> None:
        with self._cond:
            old = self._state
            if old == new:
                return
            self._state = new
            self.counters.transitions.append(
                (time.time(), old, new, reason)  # nornlint: disable=NL-TM01
            )
            del self.counters.transitions[:-50]
            self._cond.notify_all()
        logger.warning("backend %s -> %s (%s)", old, new, reason)
        if self._publish:
            for s, cell in _STATE_CELLS.items():
                cell.set(1.0 if s == new else 0.0)
            if new == DEGRADED_CPU:
                _DEGRADES.inc()
            if old in (RECOVERING, DEGRADED_CPU) and new == READY:
                _RECOVERIES.inc()
        if new == DEGRADED_CPU:
            self.counters.degrades += 1
        if old in (RECOVERING, DEGRADED_CPU) and new == READY:
            self.counters.recoveries += 1
        # state transitions are recorded as single-span traces so
        # /admin/traces shows the lifecycle timeline next to request traces
        with _tracer.start_trace(
            "backend.transition",
            attrs={"from": old, "to": new, "reason": reason},
        ):
            pass

    def _initial_acquire(self) -> None:
        try:
            info = self._executor.submit(self.hooks.touch, self.acquire_timeout)
            self._device_info = info or {}
            self._transition(READY, "acquired")
        except TimeoutError:
            self._note_acquire_timeout()
            self._transition(DEGRADED_CPU, "acquire timeout")
        except Exception as e:
            self._transition(DEGRADED_CPU, f"acquire failed: {e}")

    def _run_probe(self) -> bool:
        """One bounded health probe; True when green (and fast enough)."""
        self.counters.probes += 1
        if self._executor.busy:
            # a previous device call is still hung: that IS the failure —
            # don't stack another behind it
            self._note_probe_failure("worker busy/hung")
            return False
        t0 = time.perf_counter()
        try:
            answered = self._executor.submit(
                self.hooks.probe, self.probe_timeout)
        except TimeoutError:
            self._note_probe_failure("probe timeout")
            return False
        except Exception as e:
            self._note_probe_failure(f"probe error: {e}")
            return False
        self._probe_wall = time.perf_counter() - t0
        # hooks that can tell the device's answer time from the time spent
        # queued behind serving work return it; the rest are judged by the
        # wall clock.  Either way the whole round trip is bounded by
        # probe_timeout above.
        latency = self._probe_wall if answered is None else answered
        self._probe_latency = latency
        if self._publish:
            _PROBE_HIST.observe(latency)
        if latency > self.probe_latency_threshold:
            self._note_probe_failure(f"probe latency {latency:.3f}s")
            return False
        return True

    def _note_probe_failure(self, reason: str) -> None:
        self.counters.probe_failures += 1
        if self._publish:
            _PROBE_FAILURES.inc()
        logger.debug("backend probe failed: %s", reason)

    def _probe_loop(self) -> None:
        while not self._stop.wait(self.probe_interval):
            try:
                self._probe_tick()
            except Exception:
                logger.exception("backend probe loop iteration failed")

    def _probe_tick(self) -> None:
        """One probe + hysteresis step (the probe loop's body; tests call
        it directly for deterministic streak scenarios)."""
        state = self._state
        if state == PROBING:
            return  # initial acquisition owns this phase
        ok = self._run_probe()
        if ok:
            self._fail_streak = 0
            self._ok_streak += 1
            if (
                state == DEGRADED_CPU
                and self._ok_streak >= self.recover_after
            ):
                self._recover()
        else:
            self._ok_streak = 0
            self._fail_streak += 1
            if (
                state == READY
                and self._fail_streak >= self.degrade_after
            ):
                self._transition(
                    DEGRADED_CPU,
                    f"{self._fail_streak} consecutive probe failures",
                )

    def _recover(self) -> None:
        """Probe went green while degraded: re-acquire, re-upload corpora,
        go READY.  Any failure drops straight back to DEGRADED_CPU."""
        self._transition(RECOVERING, f"{self._ok_streak} consecutive green probes")
        try:
            info = self._executor.submit(self.hooks.touch, self.acquire_timeout)
            self._device_info = info or {}
        except Exception as e:
            self._ok_streak = 0
            self._transition(DEGRADED_CPU, f"re-acquire failed: {e}")
            return
        mode = self.recovery_reupload
        with self._cond:
            corpora = [r() for r in self._corpora]
            self._corpora = [r for r in self._corpora if r() is not None]
        for corpus in corpora:
            if corpus is None:
                continue
            try:
                corpus._on_backend_recovered(mode)
            except Exception:
                logger.exception("corpus recovery notification failed")
        self._transition(READY, "recovered")
        # second notification AFTER the READY transition lands: the
        # pre-transition wake can be consumed by an uploader that still
        # saw RECOVERING (its _sync no-ops and the wake event is spent) —
        # this one guarantees the background re-upload actually runs, and
        # lets corpora re-apply device state (pending cluster installs)
        # that required a serving backend
        for corpus in corpora:
            if corpus is None:
                continue
            try:
                corpus._on_backend_ready()
            except Exception:
                logger.exception("corpus post-recovery notification failed")

    # -- observability --------------------------------------------------------
    def stats(self) -> dict:
        c = self.counters
        return {
            "state": self._state,
            "device": dict(self._device_info),
            "probe_latency_s": round(self._probe_latency, 6),
            "probe_wall_s": round(self._probe_wall, 6),
            "probe_interval_s": self.probe_interval,
            "acquire_timeout_s": self.acquire_timeout,
            "fallback_policy": self.fallback,
            "recovery_reupload": self.recovery_reupload,
            "fallbacks_total": c.fallbacks,
            "recoveries_total": c.recoveries,
            "degrades_total": c.degrades,
            "acquire_timeouts_total": c.acquire_timeouts,
            "probes_total": c.probes,
            "probe_failures_total": c.probe_failures,
            "lock_violations_total": c.lock_violations,
            "transitions": [
                {"ts": ts, "from": a, "to": b, "reason": r}
                for ts, a, b, r in c.transitions[-10:]
            ],
        }
