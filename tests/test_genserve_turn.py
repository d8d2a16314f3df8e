"""The scheduler's turn and a request's waits, timed where they happen
(``genserve/engine.py`` on ``telemetry/tracing.py``'s ``stage`` /
``add_stage``): the cycle's stages tile the turn in ``GenStats``, a stream
counts its own way out, a capture holds the turn on the profiler's clock,
and the ten ``*.chat`` metrics of ``bench/metrics/`` find what they read.

Counts, orderings and sums of disjoint intervals; no threshold on a clock
(ROADMAP D12).  The engine is the harness's small Qwen on the CPU backend.
"""

import functools
import glob
import json
import os
import threading
import time

import pytest

from genserve_harness import (  # noqa: F401  (the fixture is autouse)
    LIVE,
    engine,
    prompt,
    settle,
    stop_what_the_test_started,
)
from nornicdb_tpu.genserve import engine as engine_mod
from nornicdb_tpu.genserve import stats as gstats
from nornicdb_tpu.genserve.engine import GenStats
from nornicdb_tpu.telemetry import tracing as tracing_mod
from nornicdb_tpu.telemetry.metrics import REGISTRY
from nornicdb_tpu.telemetry.tracing import tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("admit_seconds", "plan_seconds", "dispatch_seconds",
          "read_wait_seconds", "deliver_seconds")
NEW_FIELDS = (
    "turns", "turn_seconds", "admit_seconds", "plan_seconds",
    "dispatch_seconds", "deliver_seconds", "host_turn_seconds",
    "turn_cpu_seconds", "host_offcpu_seconds", "late_dispatches",
    "late_host_seconds", "stream_lag_seconds", "streamed_tokens",
    "queue_wait_seconds", "prefill_wait_seconds")
# what the ten metric files of ISSUE 39 read (numerator, denominator)
CHAT_RATIOS = {
    "host_turn_ms_per_step.chat": ("host_turn_seconds", "decode_steps"),
    "admit_ms_per_step.chat": ("admit_seconds", "decode_steps"),
    "plan_ms_per_step.chat": ("plan_seconds", "decode_steps"),
    "dispatch_ms_per_step.chat": ("dispatch_seconds", "decode_steps"),
    "deliver_ms_per_step.chat": ("deliver_seconds", "decode_steps"),
    "offcpu_ms_per_step.chat": ("host_offcpu_seconds", "decode_steps"),
    "late_dispatch_share.chat": ("late_dispatches", "overlapped_steps"),
    "stream_lag_ms_per_token.chat": ("stream_lag_seconds",
                                     "streamed_tokens"),
    "queue_wait_ms_per_request.chat": ("queue_wait_seconds", "admissions"),
    "prefill_wait_ms_per_request.chat": ("prefill_wait_seconds",
                                         "admissions"),
}
CHAT_CELLS = ["mem-chat-sys4k", "dsv2-chat-sys4k", "lcf-chat-sys4k",
              "cmda-chat-sys6k"]


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracer.clear()
    tracer.configure(enabled=True, sample_rate=1.0)
    yield
    tracer.clear()


def stream_all(eng, prompts, max_new: int) -> list[list[int]]:
    """Every prompt submitted at once, each stream pulled by a thread of
    its own (the SSE threads of a chat cell)."""
    handles = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    got: list = [None] * len(handles)

    def pull(i: int) -> None:
        got[i] = list(handles[i].stream_tokens())

    threads = [threading.Thread(target=pull, args=(i,))
               for i in range(len(handles))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "a stream did not end"
    return got


def make_some_dispatches_late(eng, n: int = 3) -> None:
    """The first ``n`` overlapped turns wait, before they admit and plan,
    until the step in flight has landed: their dispatch finds it finished
    (an ordering, not a sleep of a chosen length)."""
    plain = eng._admit

    def admit_once_the_step_landed():
        flight = eng._inflight
        if flight is not None and eng.stats.late_dispatches < n:
            end = time.monotonic() + 20
            while not flight.ids.is_ready() and time.monotonic() < end:
                time.sleep(0.001)
        plain()

    eng._admit = admit_once_the_step_landed


@pytest.fixture(scope="module")
def chat():
    """Counters after chat traffic: six streamed requests over four seats
    (so two wait in the queue), a few late dispatches among the turns."""
    eng = engine()
    make_some_dispatches_late(eng)
    prompts = [prompt(n, 11) for n in (9, 21, 40, 33, 70, 14)]
    got = stream_all(eng, prompts, 12)
    settle(eng)
    snap = eng.stats_snapshot()
    while LIVE:
        LIVE.pop().stop()
    return {"genserve": snap}, got


def dig(counters: dict, path: str):
    return functools.reduce(lambda d, k: d[k], path.split("."), counters)


# -------------------------------------------------- (a) the fields are there
def test_every_new_field_is_a_number_that_moved(chat):
    counters, got = chat
    snap = counters["genserve"]
    assert set(NEW_FIELDS) <= set(GenStats.__dataclass_fields__)
    for name in NEW_FIELDS:
        assert isinstance(snap[name], (int, float)), name
        assert snap[name] > 0, name
    assert snap["streamed_tokens"] == sum(len(g) for g in got)
    assert snap["admissions"] == 6 and snap["late_dispatches"] >= 3


# ------------------------------- (f) what the ten metric files read is there
@pytest.mark.parametrize("metric", sorted(CHAT_RATIOS))
def test_chat_ratio_reads_above_zero(chat, metric):
    """The file names the pair (so a renamed counter fails here, not as a
    silent ``NOTHING TO READ`` on the chip), lists the four chat cells, and
    both ends read above zero after chat traffic."""
    counters, _ = chat
    with open(os.path.join(ROOT, "bench", "metrics", metric + ".json")) as f:
        spec = json.load(f)
    assert (spec["numerator"], spec["denominator"]) == tuple(
        "genserve." + n for n in CHAT_RATIOS[metric])
    assert spec["reader"] == "counter_ratio" and spec["layer"] == "genserve"
    assert spec["moves"] == "tpot_ms" and spec["workloads"] == CHAT_CELLS
    assert dig(counters, spec["denominator"]) > 0, spec["denominator"]
    assert dig(counters, spec["numerator"]) > 0, spec["numerator"]


def test_the_chat_metric_files_are_these_ten():
    files = {os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(ROOT, "bench", "metrics", "*.chat.json"))}
    assert files == set(CHAT_RATIOS)


# ------------------------------------------ (b) the parts tile the turn
def test_the_stages_tile_the_turn_with_a_drain_in_the_run():
    """A pool sized to force eviction: a ``_drain`` inside the plan reads
    and delivers the step in flight, and its seconds are read and deliver,
    not plan as well."""
    eng = engine(pool_pages=8, page_size=8, max_seq_tokens=56,
                 prefill_chunk=16)
    prompts = [prompt(n, 5) for n in (6, 9, 13)]
    got = stream_all(eng, prompts, 20)
    settle(eng)
    s = eng.stats
    assert s.drains > 0 and s.evictions > 0
    assert [len(g) for g in got] == [20, 20, 20]
    parts = sum(getattr(s, name) for name in PHASES)
    assert all(getattr(s, name) > 0 for name in PHASES)
    assert 0 < parts <= s.turn_seconds
    assert 0 < s.host_turn_seconds <= s.turn_seconds
    # the one identity the reader leans on: a turn less its blocked read
    assert s.host_turn_seconds == pytest.approx(
        s.turn_seconds - s.read_wait_seconds, rel=1e-6)
    assert s.host_offcpu_seconds == pytest.approx(
        s.host_turn_seconds - s.turn_cpu_seconds, rel=1e-6, abs=1e-9)
    assert s.turns >= s.decode_steps
    # a re-admission waits in the queue, and for its first token, again
    assert s.admissions == 3 + s.readmissions


# ------------------------------------------------- (c) streams and waits
def test_a_stream_counts_its_own_tokens_and_result_callers_none():
    eng = engine()
    prompts = [prompt(n, 12) for n in (10, 25, 31)]
    outs = [h.result() for h in
            [eng.submit(p, max_new_tokens=8) for p in prompts]]
    settle(eng)
    assert eng.stats.generated_tokens == sum(len(o) for o in outs) > 0
    assert eng.stats.streamed_tokens == 0
    assert eng.stats.stream_lag_seconds == 0.0
    got = stream_all(eng, prompts, 8)
    settle(eng)
    assert got == outs  # the prefix cache changes no token
    assert eng.stats.streamed_tokens == sum(len(g) for g in got)
    assert eng.stats.stream_lag_seconds > 0.0


def test_stream_text_is_a_stream_too():
    eng = engine()
    handle = eng.submit(prompt(18, 13), max_new_tokens=6)
    text = "".join(handle.stream_text())
    settle(eng)
    assert text == eng.tokenizer.decode(handle.tokens)
    assert eng.stats.streamed_tokens == len(handle.tokens)


def test_tokens_made_before_the_stream_opened_are_replayed_and_counted():
    eng = engine()
    handle = eng.submit(prompt(12, 14), max_new_tokens=5)
    out = handle.result()
    assert list(handle.stream_tokens()) == out
    assert eng.stats.streamed_tokens == len(out)


def test_an_admission_waits_once_in_the_queue_and_once_for_its_token():
    """Traced requests: ``genserve.queue_wait`` and
    ``genserve.prefill_wait`` are one retroactive span an admission, and
    the counters hold the same seconds as the spans."""
    eng = engine()
    ids = []
    handles = []
    for n in (8, 19, 30, 41, 52):  # five on four seats
        with tracer.start_trace(f"chat-{n}") as root:
            handles.append(eng.submit(prompt(n, 15), max_new_tokens=6))
            ids.append(root.trace_id)
    for h in handles:
        h.result()
    settle(eng)
    assert eng.stats.admissions == 5 and eng.stats.readmissions == 0
    spans = [s for tid in ids for s in tracer.trace(tid)["spans"]]
    for name, field in (("genserve.queue_wait", "queue_wait_seconds"),
                        ("genserve.prefill_wait", "prefill_wait_seconds")):
        mine = [s for s in spans if s["name"] == name]
        assert len(mine) == 5, name
        assert sum(s["duration_ms"] for s in mine) / 1e3 == pytest.approx(
            getattr(eng.stats, field), rel=1e-6), name


# ---------------------------------- (d) off: no span, no annotation, a turn
def test_untraced_and_uncaptured_a_turn_builds_nothing(monkeypatch):
    class Spy:
        @staticmethod
        def is_enabled():
            return False

        def __init__(self, *a, **kw):
            raise AssertionError("annotation built with no capture")

    built = []

    class CountingSpan(tracing_mod.Span):
        __slots__ = ()

        def __init__(self, *a, **kw):
            built.append(a[2])  # the span's name
            super().__init__(*a, **kw)

    monkeypatch.setattr(tracer, "_annotation_cls", Spy)
    monkeypatch.setattr(tracing_mod, "Span", CountingSpan)
    eng = engine()
    got = stream_all(eng, [prompt(n, 16) for n in (7, 22)], 10)
    settle(eng)
    assert [len(g) for g in got] == [10, 10]
    assert eng.stats.turns > 0 and eng.stats.errors == 0
    # (the harness's backend manager traces its own transitions)
    assert [name for name in built if name.startswith("genserve")] == []


# --------------------------- (e) on: the turn on the profiler's own clock
def test_a_capture_holds_the_turn_and_its_stages(tmp_path):
    import jax
    from jax.profiler import ProfileData

    eng = engine()
    eng.warmup()
    assert tracer._capturing() is None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tracer.start_trace("chat") as root:
            handle = eng.submit(prompt(37, 17), max_new_tokens=8)
            trace_id = root.trace_id
        other = eng.submit(prompt(20, 18), max_new_tokens=8)
        assert len(list(handle.stream_tokens())) == 8
        other.result()
        settle(eng)
    finally:
        jax.profiler.stop_trace()
    by_line: dict = {}
    joined = {}
    for path in glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            # a line a thread (all named alike: the index tells them apart)
            for index, line in enumerate(plane.lines):
                for ev in line.events:
                    if not ev.name.startswith("nornic.genserve."):
                        continue
                    stats = dict(ev.stats)
                    if ev.name.startswith("nornic.genserve.turn"):
                        by_line.setdefault((path, plane.name, index),
                                           []).append((ev, stats))
                    elif stats.get("trace_id") == trace_id:
                        joined[stats["span_id"]] = (ev.name, stats)
    # the scheduler thread's line, and no other, holds the turns
    assert len(by_line) == 1, sorted(by_line)
    events, = by_line.values()
    turns = [(ev, st) for ev, st in events
             if ev.name == "nornic.genserve.turn"]
    parts = [(ev, st) for ev, st in events
             if ev.name != "nornic.genserve.turn"]
    assert len(turns) >= 8
    assert {ev.name.rsplit(".", 1)[1] for ev, _ in parts} == {
        "admit", "plan", "dispatch", "read", "deliver"}
    for ev, _ in parts:
        lo, hi = ev.start_ns, ev.start_ns + ev.duration_ns
        assert any(t.start_ns <= lo and hi <= t.start_ns + t.duration_ns
                   for t, _ in turns), ev.name
    for _, st in turns:  # WHICH turns are long: the annotation says
        assert {"admitted", "finished", "chunk", "lanes", "late",
                "duration_ms"} <= set(st)
    assert sum(int(st["admitted"]) for _, st in turns) == 2
    assert sum(int(st["finished"]) for _, st in turns) == 2
    assert any(int(st["chunk"]) for _, st in turns)
    assert max(int(st["lanes"]) for _, st in turns) == 2
    # a traced request's waits join the ring's spans by id
    ring = {s["span_id"]: s for s in tracer.trace(trace_id)["spans"]}
    names = {name for name, _ in joined.values()}
    assert {"nornic.genserve.queue_wait",
            "nornic.genserve.prefill_wait"} <= names
    for span_id, (name, stats) in joined.items():
        assert "nornic." + ring[span_id]["name"] == name
        assert float(stats["duration_ms"]) == pytest.approx(
            ring[span_id]["duration_ms"], rel=1e-6)


# --------------------------------------- what the touched code mis-timed
def test_a_steps_histogram_takes_its_own_interval(monkeypatch):
    """``DECODE_HIST`` / ``PREFILL_HIST`` and the spans take ``t1 - max(t0,
    the read before)``: intervals that cannot overlap, so their sum fits
    in the run; ``record_execute`` keeps dispatch to read (ROADMAP D7)."""
    own, whole = [], []
    monkeypatch.setattr(gstats.DECODE_HIST, "observe", own.append)
    monkeypatch.setattr(
        engine_mod._deviceprof, "record_execute",
        lambda sub, kind, shape, seconds: whole.append(seconds))
    eng = engine()
    eng.warmup()
    began = time.perf_counter()
    outs = [h.result() for h in
            [eng.submit(prompt(n, 19), max_new_tokens=24)
             for n in (5, 12, 19, 26)]]
    settle(eng)
    elapsed = time.perf_counter() - began
    assert [len(o) for o in outs] == [24] * 4
    assert len(own) == eng.stats.decode_steps > 0
    assert len(whole) >= len(own)
    assert 0 < sum(own) <= elapsed
    assert sum(own) <= sum(whole)


# ------------------------------------ off the hot path, same at /metrics
def test_a_step_sets_no_gauge_and_announces_a_program_once(monkeypatch):
    eng = engine()
    eng.warmup()
    announced, gauged = [], []
    monkeypatch.setattr(engine_mod._deviceprof, "record_compile",
                        lambda *a: announced.append(a))
    for fam in (gstats.RUNNING_SEQS, gstats.PAGE_POOL_UTIL,
                gstats.PREFIX_PAGES):
        monkeypatch.setattr(fam, "set", gauged.append)
    assert len(eng.generate(prompt(30, 20), max_new_tokens=12)) == 12
    settle(eng)
    assert eng.stats.decode_steps >= 11
    assert announced == [] and gauged == []


def test_metrics_render_the_turn_from_the_engines_stats():
    def sample(text: str, name: str) -> float:
        line, = [ln for ln in text.splitlines() if ln.startswith(name + " ")]
        return float(line.split()[-1])

    phase = "nornicdb_genserve_turn_phase_seconds_total"
    before = REGISTRY.render_prometheus()
    eng = engine()
    # the scheduler stands still after the first token until the scrape
    # mid-stream has been read: a seat is taken then, whatever the pace
    scraped, plain = threading.Event(), eng._admit

    def admit():
        if eng.stats.generated_tokens:
            scraped.wait(30)
        plain()

    eng._admit = admit
    handle = eng.submit(prompt(40, 21), max_new_tokens=30)
    first = next(iter(handle.stream_tokens()))
    held = REGISTRY.render_prometheus()
    scraped.set()
    assert sample(held, "nornicdb_genserve_running_seqs") >= 1
    assert sample(held, "nornicdb_genserve_page_pool_utilization") > 0
    assert handle.result()[0] == first
    settle(eng)
    after = REGISTRY.render_prometheus()
    s = eng.stats
    for label, field in (("admit", "admit_seconds"), ("plan", "plan_seconds"),
                         ("dispatch", "dispatch_seconds"),
                         ("read", "read_wait_seconds"),
                         ("deliver", "deliver_seconds")):
        name = f'{phase}{{phase="{label}"}}'
        assert sample(after, name) - sample(before, name) == pytest.approx(
            getattr(s, field), rel=1e-6), label
    for name, field in (
            ("nornicdb_genserve_host_offcpu_seconds_total",
             "host_offcpu_seconds"),
            ("nornicdb_genserve_late_dispatches_total", "late_dispatches"),
            ("nornicdb_genserve_stream_lag_seconds_total",
             "stream_lag_seconds")):
        assert sample(after, name) - sample(before, name) == pytest.approx(
            getattr(s, field), rel=1e-6, abs=1e-12), name
    assert sample(after, "nornicdb_genserve_prefix_pages") >= 2
    eng.stop()
    gone = REGISTRY.render_prometheus()  # a stopped engine holds no seat
    assert sample(gone, "nornicdb_genserve_running_seqs") == 0
