"""Test configuration: force an 8-device virtual CPU mesh before JAX initialises.

Multi-chip sharding paths (nornicdb_tpu.parallel) are validated on virtual CPU
devices, mirroring how the reference exercises replication without a cluster
(reference: pkg/replication tests use in-process mock transports).
"""

import os
import sys

import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# -- nornsan: runtime lock sanitizer (opt-in, NORNSAN=1) ---------------------
# Must install BEFORE `import nornicdb_tpu` creates any module-level lock,
# so the module is loaded by file path (importing it through the package
# would execute nornicdb_tpu/__init__.py first). docs/linting.md#nornsan.
nornsan = None
if os.environ.get("NORNSAN") == "1":
    import importlib.util

    _nornsan_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "nornicdb_tpu", "tools", "nornsan", "__init__.py",
    )
    _spec = importlib.util.spec_from_file_location(
        "nornicdb_tpu.tools.nornsan", _nornsan_path
    )
    nornsan = importlib.util.module_from_spec(_spec)
    # pre-seed so later `from nornicdb_tpu.tools import nornsan` resolves to
    # THIS instance (two trackers would split the observed order graph)
    sys.modules["nornicdb_tpu.tools.nornsan"] = nornsan
    _spec.loader.exec_module(nornsan)
    nornsan.install()


@pytest.fixture(autouse=True)
def _nornsan_cycle_gate(request):
    """With NORNSAN=1, fail any test whose execution introduced a new lock
    acquisition-order cycle — an AB/BA inversion observed live."""
    if nornsan is None:
        yield
        return
    before = len(nornsan.tracker.report()["cycles"])
    yield
    rep = nornsan.tracker.report()
    fresh = rep["cycles"][before:]
    assert not fresh, (
        "nornsan: lock-order cycle(s) observed during this test "
        f"(deadlock when the orders race): {fresh}"
    )


# -- clean-exit shim: daemon worker threads vs interpreter teardown ----------
# The serving suites leak daemon threads by design (BackendManager probe
# loops, batcher dispatch loops, broker accept loops, storage flush loops —
# daemon=True so the process can exit without joining them).  When one of
# them is inside XLA C++ at interpreter teardown, the process dies with
# "terminate called without an active exception" (SIGABRT) or SIGSEGV
# *after* the green summary line — the same failure class the bench
# scripts' hard_exit() documents (scripts/_bench_common.py).  The race
# scales with process size: a 4-suite NORNJIT=1 run reproduces it
# deterministically.  So once the session is fully reported, if any such
# thread is still alive we flush and skip interpreter teardown entirely,
# preserving pytest's exit status.
_session_exitstatus = None


def pytest_sessionfinish(session, exitstatus):
    global _session_exitstatus
    _session_exitstatus = int(exitstatus)


def pytest_unconfigure(config):
    # runs after every sessionfinish hook (summary included); nothing of
    # value executes after this point except interpreter teardown
    if _session_exitstatus is None:
        return
    import threading

    leaked = [
        t for t in threading.enumerate()
        if t is not threading.main_thread() and t.daemon and t.is_alive()
    ]
    if leaked:
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(_session_exitstatus)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if nornsan is not None:
        rep = nornsan.report()
        terminalreporter.write_sep(
            "-", f"nornsan: {rep['locks']} instrumented locks, "
            f"{rep['edges']} order edges, {len(rep['cycles'])} cycle(s), "
            f"{len(rep['blocking'])} held-lock blocking event(s) "
            f">= {os.environ.get('NORNSAN_BLOCK_MS', '50')}ms"
        )
        for b in rep["blocking"][:10]:
            terminalreporter.write_line(
                f"  blocked {b['waited_s']*1000:.0f}ms acquiring {b['lock']} "
                f"while holding {', '.join(b['held'])} [{b['thread']}]"
            )
    if nornjit is not None:
        rep = nornjit.report()
        terminalreporter.write_sep(
            "-", f"nornjit: {rep['compiles']} fresh compile(s), "
            f"{len(rep['violations'])} post-warmup violation(s)"
        )
        for key, n in sorted(rep["ledger"].items()):
            terminalreporter.write_line(f"  {n:4d}x {key}")

# Tests run on the CPU backend: pinned through jax.config so it holds even
# where JAX_PLATFORMS is unset (must happen before any backend
# initialisation).  The persistent compile cache stays off: the nornjit
# sentinel counts real compiles, and a test run leaves nothing in the
# checkout.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# -- nornjit: runtime recompile sentinel (opt-in, NORNJIT=1) -----------------
# Installed AFTER the sys.path insert (it imports the package normally —
# unlike nornsan it wraps no module-level state, only jax.monitoring and
# the deviceprof observer hook). docs/linting.md#nornjit.
nornjit = None
if os.environ.get("NORNJIT") == "1":
    from nornicdb_tpu.tools import nornjit  # noqa: E402

    nornjit.install()


@pytest.fixture(autouse=True)
def _nornjit_compile_gate(request):
    """With NORNJIT=1, fail any test that compiled a fresh XLA program
    after calling nornjit.declare_warmup_done() — the runtime shadow of
    NL-JAX05's bounded-shape-class rule.  Tests that never declare a
    warmup phase cannot fail (all-warmup).  The churn fixture inverts the
    gate via the nornjit_expect_violations marker."""
    if nornjit is None:
        yield
        return
    nornjit.sentinel.begin_test(request.node.nodeid)
    yield
    vios = nornjit.sentinel.end_test()
    if request.node.get_closest_marker("nornjit_expect_violations"):
        assert vios, (
            "nornjit churn fixture: expected post-warmup fresh compiles, "
            "observed none — the sentinel is not seeing compile events"
        )
        return
    assert not vios, (
        "nornjit: fresh XLA compile(s) after this test declared its "
        "warmup done (recompile churn — an unbucketed shape class): "
        + "; ".join(
            f"{'/'.join(v['key'])} ({v['duration_s']*1000:.0f}ms "
            f"on {v['thread']})" for v in vios
        )
    )
