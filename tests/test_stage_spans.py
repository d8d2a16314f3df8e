"""Stage spans: one timing per layer boundary, three readers.

``tracer.stage`` feeds the layer's own counter (always), a span of the
active trace, and a ``nornic.*`` annotation of a running profiler capture.
These tests hold the two served paths (POST /nornicdb/embed through the
ServingEngine, POST /nornicdb/search by vector) to the span tree, the
tiling of the stage counters, the join between a capture and the ring by
span id, the device scope names, the slow ring, and the names the
benchmark's readers depend on.
"""

from __future__ import annotations

import ast
import glob
import json
import os
import re
import time
import urllib.request

import numpy as np
import pytest

import nornicdb_tpu
from nornicdb_tpu.db import Config
from nornicdb_tpu.embed.base import CachedEmbedder, TPUEmbedder
from nornicdb_tpu.models import bge_m3
from nornicdb_tpu.server import HttpServer
from nornicdb_tpu.serving import ServingEngine
from nornicdb_tpu.serving.engine import EngineStats
from nornicdb_tpu.storage import Node
from nornicdb_tpu.telemetry import tracing as tracing_mod
from nornicdb_tpu.telemetry.slowlog import slow_log
from nornicdb_tpu.telemetry.tracing import tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIMS = 64
F32_CFG = bge_m3.BgeConfig(
    vocab_size=512, hidden=DIMS, layers=2, heads=4, intermediate=128,
    max_positions=512, dims=DIMS, dtype="float32",
)
ENGINE_STAGES = ("queue_wait_seconds", "staging_seconds",
                 "staged_wait_seconds", "device_seconds", "wake_seconds")


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracer.clear()
    tracer.configure(enabled=True, sample_rate=1.0)
    old = slow_log.threshold_s
    yield
    slow_log.configure(threshold_s=old)
    tracer.clear()


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    """The serve stack at a small size: HTTP -> CachedEmbedder ->
    ServingEngine -> TPUEmbedder on the CPU backend, and a DeviceCorpus of
    a few rows behind the search service."""
    db = nornicdb_tpu.open_db(
        str(tmp_path_factory.mktemp("stage") / "db"),
        Config(async_writes=False, inference_enabled=False),
    )
    embedder = TPUEmbedder(cfg=F32_CFG)
    db.set_embedder(CachedEmbedder(ServingEngine(embedder)))
    rng = np.random.default_rng(7)
    for i in range(24):
        v = rng.standard_normal(DIMS).astype(np.float32)
        db.search.index_node(Node(id=f"n{i}", embedding=v / np.linalg.norm(v)))
    server = HttpServer(db, port=0)
    server.start()
    # first calls compile; the tests read warm requests
    _post(server.port, "/nornicdb/embed", {"text": "warm the packed forward"})
    _post(server.port, "/nornicdb/search",
          {"vector": [1.0] * DIMS, "limit": 3, "include_content": False})
    yield db, embedder, server
    server.stop()
    db.serving_engine().stop()
    db.close()


def _post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        trace_id = resp.headers["traceparent"].split("-")[1]
        return trace_id, json.loads(resp.read())


def _trace(trace_id: str, want: int, timeout: float = 5.0) -> dict:
    """The root closes a hair after the response bytes reach the client."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        entry = tracer.trace(trace_id)
        if entry is not None and len(entry["spans"]) >= want:
            return entry
        time.sleep(0.01)
    raise AssertionError(f"trace {trace_id} never reached {want} spans")


def _shape(node: dict):
    return (node["name"], [_shape(c) for c in node["children"]])


def _assert_inside(node: dict, slack_s: float = 2e-3) -> None:
    """Children lie inside their parents.  ``start`` is wall time (the
    retroactive ones back-derived), durations are perf_counter: the slack
    covers the two clocks, not the nesting."""
    lo, hi = node["start"], node["start"] + node["duration_ms"] / 1e3
    for child in node["children"]:
        c_lo = child["start"]
        c_hi = c_lo + child["duration_ms"] / 1e3
        assert c_lo >= lo - slack_s and c_hi <= hi + slack_s, (
            node["name"], child["name"], c_lo - lo, hi - c_hi)
        _assert_inside(child, slack_s)


# ------------------------------------------------------- (i) the primitive
class TestStagePrimitive:
    def test_untraced_stage_moves_counter_and_allocates_no_span(self):
        stats = {"x_seconds": 0.0}
        with tracer.stage("unit.stage", stats, "x_seconds") as st:
            time.sleep(0.002)
        assert st.span_id is None and st._span is None
        assert stats["x_seconds"] == st.seconds >= 0.002
        assert tracer.count() == 0

    def test_stage_feeds_attribute_dict_and_histogram(self):
        class Cell:
            seen: list = []

            def observe(self, v):
                self.seen.append(v)

        engine, flat, cell = EngineStats(), {"s": 1.0}, Cell()
        with tracer.stage("a", engine, "wake_seconds") as a:
            pass
        with tracer.stage("b", flat, "s") as b:
            pass
        with tracer.stage("c", cell) as c:
            pass
        assert engine.wake_seconds == a.seconds
        assert flat["s"] == 1.0 + b.seconds
        assert cell.seen == [c.seconds]

    def test_traced_stage_is_a_child_span_with_the_counter_duration(self):
        stats = {"s": 0.0}
        with tracer.start_trace("root") as root:
            with tracer.stage("outer", stats, "s", {"k": 1}) as outer:
                with tracer.stage("inner") as inner:
                    pass
        tree = tracer.trace(root.trace_id)["tree"]
        assert _shape(tree[0]) == ("root", [("outer", [("inner", [])])])
        rec = tree[0]["children"][0]
        assert rec["span_id"] == outer.span_id and rec["attrs"] == {"k": 1}
        assert rec["duration_ms"] == outer.seconds * 1e3 == stats["s"] * 1e3
        assert inner.seconds <= outer.seconds

    def test_stage_counts_and_records_an_exception(self):
        stats = {"s": 0.0}
        with tracer.start_trace("root") as root:
            with pytest.raises(ValueError):
                with tracer.stage("boom", stats, "s"):
                    raise ValueError("x")
        spans = tracer.trace(root.trace_id)["spans"]
        assert stats["s"] > 0.0
        assert [s["error"] for s in spans if s["name"] == "boom"] == [
            "ValueError: x"]

    def test_add_stage_is_retroactive_counter_and_span(self):
        stats = EngineStats()
        t0 = time.perf_counter()
        with tracer.start_trace("root") as root:
            tracer.add_stage("late", t0 - 0.25, t0, stats,
                             "queue_wait_seconds")
        tracer.add_stage("untraced", t0 - 0.5, t0, stats,
                         "queue_wait_seconds")
        assert stats.queue_wait_seconds == pytest.approx(0.75)
        spans = tracer.trace(root.trace_id)["spans"]
        late = [s for s in spans if s["name"] == "late"]
        assert len(late) == 1
        assert late[0]["duration_ms"] == pytest.approx(250.0)
        assert late[0]["parent_id"] == root.span_id

    def test_no_annotation_is_built_without_a_capture(self, monkeypatch):
        class Spy:
            @staticmethod
            def is_enabled():
                return False

            def __init__(self, *a, **kw):
                raise AssertionError("annotation built with no capture")

        monkeypatch.setattr(tracer, "_annotation_cls", Spy)
        with tracer.start_trace("root"):
            with tracer.stage("quiet"):
                pass
            tracer.add_stage("quiet.retro", 0.0, 1.0)
        assert tracer._capturing() is None


# ---------------------------------------------------- (ii) the two paths
EMBED_TREE = ("http.POST", [
    ("http.parse", []),
    ("embed.cache", [
        ("serving.queue_wait", []),
        ("serving.stage", []),
        ("serving.staged_wait", []),
        ("serving.batch", [("embed.dispatch", []), ("embed.fetch", [])]),
        ("serving.wake", []),
    ]),
    ("http.respond", []),
])


class TestServedPaths:
    def test_embed_request_tree_and_tiling(self, stack):
        db, embedder, server = stack
        engine = db.serving_engine()
        before, e_before = dict(vars(engine.stats)), dict(embedder.stats)
        trace_id, body = _post(server.port, "/nornicdb/embed",
                               {"text": "one fresh text for the tree"})
        assert body["dimensions"] == DIMS
        entry = _trace(trace_id, want=11)
        root = entry["tree"][0]
        assert _shape(root) == EMBED_TREE
        _assert_inside(root)
        after, e_after = dict(vars(engine.stats)), dict(embedder.stats)
        delta = {k: after[k] - before[k] for k in after}
        assert delta["requests"] == delta["texts"] == delta["batches"] == 1
        # one timing per boundary: the counter moved by the span's duration
        by_name = {s["name"]: s["duration_ms"] / 1e3 for s in entry["spans"]}
        for span, field in (("serving.queue_wait", "queue_wait_seconds"),
                            ("serving.stage", "staging_seconds"),
                            ("serving.staged_wait", "staged_wait_seconds"),
                            ("serving.batch", "device_seconds"),
                            ("serving.wake", "wake_seconds")):
            assert delta[field] == pytest.approx(by_name[span], abs=1e-9)
        for span, field in (("embed.dispatch", "dispatch_seconds"),
                            ("embed.fetch", "fetch_seconds")):
            assert e_after[field] - e_before[field] == pytest.approx(
                by_name[span], abs=1e-9)
        # the five stages tile enqueue -> return: they share their edges
        staged = sum(delta[f] for f in ENGINE_STAGES)
        assert staged <= delta["request_seconds"] + 1e-9
        assert staged == pytest.approx(delta["request_seconds"], abs=1e-6)
        assert (by_name["embed.dispatch"] + by_name["embed.fetch"]
                <= by_name["serving.batch"])
        assert by_name["embed.cache"] >= delta["request_seconds"]

    def test_vector_search_tree_and_counters(self, stack):
        db, _, server = stack
        sync = db.search.corpus().sync_stats
        request = {"vector": [0.5] * DIMS, "limit": 5,
                   "include_content": False}
        # the first query of a k compiles that k's class grid (its trace
        # holds one scan a class): the steady tree is the second's
        _post(server.port, "/nornicdb/search",
              {**request, "vector": [0.5, -0.5] * (DIMS // 2)})
        before = sync.as_dict()
        trace_id, body = _post(server.port, "/nornicdb/search", request)
        assert len(body["results"]) == 5
        entry = _trace(trace_id, want=8)
        root = entry["tree"][0]
        # a lone query leads its own scan on its own thread, then formats
        # its own row.  `search.vector` is one retroactive timing from the
        # launch to the end of the read-back (under load two callers share
        # those two halves), so the corpus stages stand beside it
        assert _shape(root) == ("http.POST", [
            ("http.parse", []), ("http.parse", []),
            ("search.queue_wait", []),
            ("search.vector", []),
            ("corpus.dispatch", []), ("corpus.fetch", []),
            ("corpus.format", []),
            ("http.respond", []),
        ])
        _assert_inside(root)
        delta = {k: v - before[k] for k, v in sync.as_dict().items()}
        assert delta["device_dispatches"] == 1
        by_name = {s["name"]: s["duration_ms"] / 1e3 for s in entry["spans"]
                   if s["name"] != "http.parse"}
        for span, field in (("corpus.dispatch", "search_dispatch_seconds"),
                            ("corpus.fetch", "search_fetch_seconds"),
                            ("corpus.format", "search_format_seconds")):
            assert delta[field] == pytest.approx(by_name[span], abs=1e-9)
            assert delta[field] > 0.0
        assert (delta["search_dispatch_seconds"]
                + delta["search_fetch_seconds"]) <= by_name["search.vector"]
        batcher = db.search.stats_snapshot()["batcher"]
        assert batcher["queue_wait_seconds"] >= by_name["search.queue_wait"]

    def test_stage_seconds_reach_metrics_and_admin_stats(self, stack):
        db, _, server = stack
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics", timeout=30) as r:
            text = r.read().decode()
        for name in ("nornicdb_serving_request_seconds_total",
                     "nornicdb_serving_wake_seconds_total",
                     "nornicdb_embed_fetch_seconds_total",
                     "nornicdb_corpus_fetch_seconds_total"):
            value = re.search(rf"^{name} (\S+)$", text, re.M)
            assert value and float(value.group(1)) > 0.0, name

    def test_embed_worker_stages_annotate_without_a_trace(self, stack):
        db, _, _ = stack
        seen = []
        real = tracer.stage

        def spy(name, *a, **kw):
            seen.append(name)
            return real(name, *a, **kw)

        tracer.stage = spy
        try:
            db.store("a document for the embed worker's two stages")
            db.process_pending_embeddings()
        finally:
            del tracer.stage
        assert "embedq.batch" in seen and "embedq.index" in seen
        assert seen.index("embedq.batch") < seen.index("embedq.index")


class TestStagingOverlap:
    def test_overlap_is_the_intersection_with_batch_intervals(self):
        engine = ServingEngine(TPUEmbedder(cfg=F32_CFG))
        engine.stats.staging_seconds = 4.0
        engine._batch_closed.extend([(0.0, 1.0), (2.0, 3.0)])
        engine._note_overlap(0.5, 2.5)  # 0.5 of the first, 0.5 of the second
        assert engine.stats.overlap_seconds == pytest.approx(1.0)
        engine._batch_open = 10.0
        engine._batch_closed.append((10.0, 11.0))  # the open one, closing
        engine._note_overlap(9.0, 10.5)  # counted once, not twice
        assert engine.stats.overlap_seconds == pytest.approx(1.5)
        engine._note_overlap(20.0, 21.0)  # staging with no batch running
        assert engine.stats.overlap_seconds == pytest.approx(2.5)
        assert engine.stats_snapshot()["staging_overlap_ratio"] <= 1.0


# ------------------------------------------------ (iii) the profiler's clock
class TestProfilerJoin:
    def test_capture_holds_nornic_events_that_join_the_ring(
            self, stack, tmp_path):
        import jax
        from jax.profiler import ProfileData

        db, _, server = stack
        assert tracer._capturing() is None  # no capture: no annotation
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            assert tracer._capturing() is not None
            trace_id, _ = _post(server.port, "/nornicdb/embed",
                                {"text": "a text embedded under a capture"})
            entry = _trace(trace_id, want=11)
        finally:
            jax.profiler.stop_trace()
        assert tracer._capturing() is None
        events = {}
        for path in glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                              recursive=True):
            for plane in ProfileData.from_file(path).planes:
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("nornic."):
                            stats = dict(ev.stats)
                            if stats.get("trace_id") == trace_id:
                                events[stats["span_id"]] = (ev, stats)
        ring = {s["span_id"]: s for s in entry["spans"]
                if s["name"] != "http.POST"}  # the root is no stage
        assert set(events) == set(ring)
        for span_id, (ev, stats) in events.items():
            rec = ring[span_id]
            assert ev.name == "nornic." + rec["name"]
            # the same duration as the span and the counter, to the digit
            assert float(stats["duration_ms"]) == pytest.approx(
                rec["duration_ms"], rel=1e-6)
            if not stats.get("retro"):
                # and on the profiler's own clock the annotation encloses
                # the span: it opens just before and closes just after
                assert ev.duration_ns / 1e6 >= rec["duration_ms"] - 0.01
        retro = {ring[i]["name"] for i, (_, s) in events.items()
                 if s.get("retro")}
        assert retro == {"serving.queue_wait", "serving.staged_wait",
                         "serving.wake"}


# ------------------------------------------------------ (iv) device names
class TestDeviceScopes:
    def test_packed_forward_carries_its_scopes(self):
        import jax
        import jax.numpy as jnp

        params = bge_m3.init_params(F32_CFG, jax.random.PRNGKey(0))
        ids = jnp.ones((2, 16), jnp.int32)
        sel = jnp.zeros((2,), jnp.int32)
        packed = jax.jit(lambda p, *a: bge_m3.forward_packed(p, F32_CFG, *a))
        text = packed.lower(params, ids, ids, ids + 2, sel, sel).as_text(
            debug_info=True)
        plain = jax.jit(lambda p, *a: bge_m3.forward(p, F32_CFG, *a)).lower(
            params, ids, ids).as_text(debug_info=True)
        for scope in ("bge.embed", "bge.layer.attn", "bge.layer.mlp",
                      "bge.pool"):
            assert scope in text and scope in plain, scope

    def test_topk_programs_carry_their_scopes(self):
        import jax.numpy as jnp

        from nornicdb_tpu.ops import similarity
        from nornicdb_tpu.ops.pallas_kernels import (
            streaming_cosine_topk,
            streaming_cosine_topk_int8,
        )

        q, c = jnp.ones((1, 128)), jnp.ones((1024, 128))
        valid = jnp.ones((1024,), bool)
        text = streaming_cosine_topk.lower(
            q, c, valid, k=10, tile_n=512, rows=2, interpret=True
        ).as_text(debug_info=True)
        for name in ("topk.stream.scan", "topk.stream.merge",
                     "topk_stream_scan"):
            assert name in text, name
        i8 = streaming_cosine_topk_int8.lower(
            q.astype(jnp.int8), jnp.ones((1,)), c.astype(jnp.int8),
            jnp.ones((1024,)), valid, k=10, tile_n=512, rows=2,
            interpret=True).as_text(debug_info=True)
        assert "topk.stream.scan" in i8 and "topk_stream_scan_int8" in i8
        assert "l2_normalize" in similarity.l2_normalize.lower(q).as_text(
            debug_info=True)
        assert "masked_dot_topk" in similarity.masked_dot_topk.lower(
            q[0], c, valid, k=4).as_text(debug_info=True)


# ------------------------------------- (v) what the benchmark's readers find
def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def _program_modules() -> dict:
    """metric -> XLA module name of the jitted callable it is meant to
    find, lowered from the callable the serve stack runs."""
    import jax
    import jax.numpy as jnp

    from nornicdb_tpu.models import (
        cohere2_moe,
        deepseek_v2,
        longcat_flash,
        nemotron_h,
    )
    from nornicdb_tpu.ops.pallas_kernels import streaming_cosine_topk

    embedder = TPUEmbedder(cfg=F32_CFG)
    ids, sel = jnp.ones((2, 16), jnp.int32), jnp.zeros((2,), jnp.int32)
    q, c = jnp.ones((1, 128)), jnp.ones((1024, 128))
    dsv2 = deepseek_v2.DEEPSEEK_V2_SMALL
    lcf = longcat_flash.LONGCAT_FLASH_SMALL
    lmax, w, f = 4, 8, 16
    cmda, kinds = cohere2_moe.COHERE2_MOE_SMALL, (w, 4)  # a width a kind
    nemo, slots = nemotron_h.NEMOTRON_H_SMALL, (w, 1)  # pages, a state slot
    return {
        "step_roofline.nemo": _module_name(nemotron_h.fused_step.lower(
            jax.eval_shape(lambda: nemotron_h.init_params(
                nemo, jax.random.PRNGKey(0))), nemo,
            jax.ShapeDtypeStruct((4 * f + lmax + sum(
                lmax * (1 + wk) for wk in slots),), jnp.int32),
            jax.eval_shape(lambda: nemotron_h.init_pages(nemo, (9, 5), 16)),
            lmax=lmax, w=slots, tq=16)),
        "step_roofline.cmda": _module_name(cohere2_moe.fused_step.lower(
            jax.eval_shape(lambda: cohere2_moe.init_params(
                cmda, jax.random.PRNGKey(0))), cmda,
            jax.ShapeDtypeStruct((4 * f + lmax + sum(
                lmax * (1 + wk) for wk in kinds),), jnp.int32),
            jax.eval_shape(lambda: cohere2_moe.init_pages(cmda, (9, 9), 16)),
            lmax=lmax, w=kinds, tq=16)),
        "step_roofline.lcf": _module_name(longcat_flash.fused_step.lower(
            jax.eval_shape(lambda: longcat_flash.init_params(
                lcf, jax.random.PRNGKey(0))), lcf,
            jax.ShapeDtypeStruct((4 * f + lmax + lmax * w,), jnp.int32),
            jax.eval_shape(lambda: longcat_flash.init_pages(lcf, 9, 16)),
            lmax=lmax, w=w, tq=16)),
        "step_roofline.dsv2": _module_name(deepseek_v2.fused_step.lower(
            jax.eval_shape(lambda: deepseek_v2.init_params(
                dsv2, jax.random.PRNGKey(0))), dsv2,
            jax.ShapeDtypeStruct((4 * f + lmax + lmax * w,), jnp.int32),
            jax.eval_shape(lambda: deepseek_v2.init_pages(dsv2, 9, 16)),
            lmax=lmax, w=w, tq=16)),
        "fwd_roofline.embed": _module_name(embedder._fwd_packed.lower(
            embedder.params, ids, ids, ids + 2, sel, sel)),
        "knn_roofline.search": _module_name(streaming_cosine_topk.lower(
            q, c, jnp.ones((1024,), bool), k=10, tile_n=512, rows=2,
            interpret=True)),
    }


def _metric_specs(reader: str) -> list[dict]:
    specs = []
    for path in sorted(glob.glob(os.path.join(ROOT, "bench", "metrics",
                                              "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] == reader:
            specs.append(spec)
    return specs


def _bench_functions(*names: str) -> dict:
    """Functions of bench/run.py, compiled from its source (importing the
    file would put bench/'s ``trace`` before the standard library's)."""
    with open(os.path.join(ROOT, "bench", "run.py")) as f:
        tree = ast.parse(f.read())
    keep = [n for n in tree.body
            if isinstance(n, ast.FunctionDef) and n.name in names]
    assert {n.name for n in keep} == set(names)
    space: dict = {}
    exec(compile(ast.Module(keep, []), "bench/run.py", "exec"), space)
    return space


@pytest.fixture(scope="module")
def snapshot(stack):
    """What bench/run.py:counters() returns for the small stack."""
    from nornicdb_tpu import backend

    db, embedder, _ = stack
    fns = _bench_functions("counters", "dig")
    return fns["counters"](db, embedder, backend.manager()), fns["dig"]


# the per-layer metrics of ISSUE 27 that wait for a benchmark PR (run.py's
# counter_ratio raises on a path the parent commit lacks): their paths stay
# resolvable meanwhile
PLANNED_RATIOS = {
    "engine_ms_per_request.embed": ("engine.request_seconds",
                                    "engine.requests"),
    "queue_wait_ms_per_text.embed": ("engine.queue_wait_seconds",
                                     "engine.texts"),
    "staging_ms_per_batch.embed": ("engine.staging_seconds",
                                   "engine.batches"),
    "staged_wait_ms_per_batch.embed": ("engine.staged_wait_seconds",
                                       "engine.batches"),
    "wake_ms_per_request.embed": ("engine.wake_seconds", "engine.requests"),
    "texts_per_batch.embed": ("engine.texts", "engine.batches"),
    "dispatch_ms_per_batch.embed": ("embedder.dispatch_seconds",
                                    "embedder.packed_dispatches"),
    "fetch_ms_per_batch.embed": ("embedder.fetch_seconds",
                                 "embedder.packed_dispatches"),
    "corpus_dispatch_ms_per_query.search": (
        "search.corpus.sync.search_dispatch_seconds",
        "search.corpus.sync.device_dispatches"),
    "corpus_fetch_ms_per_query.search": (
        "search.corpus.sync.search_fetch_seconds",
        "search.corpus.sync.device_dispatches"),
    "corpus_format_ms_per_query.search": (
        "search.corpus.sync.search_format_seconds",
        "search.corpus.sync.device_dispatches"),
}


class TestBenchmarkReaders:
    def test_every_program_pattern_finds_its_module(self):
        modules = _program_modules()
        specs = [s for s in _metric_specs("trace") if "programs" in s]
        assert specs, "no roofline metric left to guard"
        for spec in specs:
            assert spec["name"] in modules, (
                f"{spec['name']}: say in _program_modules which jitted "
                "callable its pattern is meant to find")
            assert re.search(spec["programs"], modules[spec["name"]]), (
                spec["name"], spec["programs"], modules[spec["name"]])

    def test_every_counter_ratio_path_resolves(self, snapshot):
        counters, dig = snapshot
        specs = _metric_specs("counter_ratio")
        assert specs
        for spec in specs:
            for path in (spec["numerator"], spec["denominator"]):
                if path.startswith("bench."):
                    continue  # the benchmark's own numbers
                assert isinstance(dig(counters, path), (int, float)), (
                    spec["name"], path)

    @pytest.mark.parametrize("cell", ["dsv2", "lcf", "gen"])
    def test_the_walk_counters_are_what_attn_walked_share_reads(
            self, snapshot, cell):
        """PR 36's pair (``models/mla.py``'s ``WALK_COUNTERS``, the last
        two of both latent families' ``STEP_COUNTERS``, and since PR 38
        the first two of Qwen's, side by side in each): the metric reads
        them under these names, and every engine's snapshot has them (0
        for a family whose step counts no walk)."""
        from nornicdb_tpu.models import deepseek_v2, longcat_flash, mla, qwen2

        counters, dig = snapshot
        spec, = [s for s in _metric_specs("counter_ratio")
                 if s["name"] == f"attn_walked_share.{cell}"]
        paths = (spec["numerator"], spec["denominator"])
        assert paths == tuple("genserve." + n for n in mla.WALK_COUNTERS)
        family = {"dsv2": deepseek_v2, "lcf": longcat_flash,
                  "gen": qwen2}[cell]
        at = family.STEP_COUNTERS.index(mla.WALK_COUNTERS[0])
        assert family.STEP_COUNTERS[at:at + 2] == mla.WALK_COUNTERS
        for path in paths:
            assert isinstance(dig(counters, path), int), path

    @pytest.mark.parametrize("metric,paths", [
        ("attn_walked_share.cmda", ("attn_pages_walked", "attn_pages_held")),
        ("window_walked_share.cmda", ("window_pages_walked",
                                      "full_pages_walked")),
        ("window_pages_dropped_per_request.cmda", ("window_pages_dropped",
                                                   "completed"))])
    def test_the_page_kind_counters_are_what_the_cmda_metrics_read(
            self, snapshot, metric, paths):
        """PR 37's counts by page kind: the step's four (``models/
        cohere2_moe.py``'s ``WALK_COUNTERS``, the last four of its
        ``STEP_COUNTERS`` before ``shared_run_pages``, ``GenStats`` fields
        of the same names), their
        sums over the kinds and the scheduler's own count of window pages
        let go; every engine's snapshot has them (0 for a family with the
        one kind)."""
        from nornicdb_tpu.genserve.engine import GenStats
        from nornicdb_tpu.models import cohere2_moe

        counters, dig = snapshot
        spec, = [s for s in _metric_specs("counter_ratio")
                 if s["name"] == metric]
        assert (spec["numerator"], spec["denominator"]) == tuple(
            "genserve." + n for n in paths)
        for path in paths:
            assert isinstance(dig(counters, "genserve." + path), int), path
        walk = tuple(n for pair in cohere2_moe.WALK_COUNTERS.values()
                     for n in pair)
        assert cohere2_moe.STEP_COUNTERS[-5:-1] == walk == (
            "full_pages_walked", "full_pages_held", "window_pages_walked",
            "window_pages_held")
        assert set(walk) | {"window_pages_dropped", "window_pages_freed",
                            "attn_pages_walked", "attn_pages_held"} \
            <= set(GenStats.__dataclass_fields__)

    def test_the_shared_run_counter_is_what_its_metric_reads(self, snapshot):
        """PR 40's count of the pages a decode block gathered once for all
        its lanes: the last of the ``STEP_COUNTERS`` of both families that
        walk ``models/kv_walk.py``, a ``GenStats`` field and a Prometheus
        counter of its own; the metric divides it by the steps that
        carried a decode lane, in those two families' cells and no other."""
        from nornicdb_tpu.genserve import stats as gstats
        from nornicdb_tpu.genserve.engine import GenStats
        from nornicdb_tpu.models import cohere2_moe, qwen2
        from nornicdb_tpu.telemetry.metrics import REGISTRY

        counters, dig = snapshot
        spec, = [s for s in _metric_specs("counter_ratio")
                 if s["name"] == "shared_run_pages_per_step.kv"]
        assert (spec["numerator"], spec["denominator"]) == (
            "genserve.shared_run_pages", "genserve.decode_steps")
        assert spec["workloads"] == ["mem-chat-sys4k", "cmda-chat-sys6k"]
        assert spec["layer"] == "kernels" and spec["moves"] == "tpot_ms"
        for family in (qwen2, cohere2_moe):
            assert family.STEP_COUNTERS[-1] == "shared_run_pages"
        assert "shared_run_pages" in GenStats.__dataclass_fields__
        assert isinstance(dig(counters, "genserve.shared_run_pages"), int)
        gstats.SHARED_RUN_PAGES.inc(0)
        assert "nornicdb_genserve_shared_run_pages_total" in \
            REGISTRY.render_prometheus()

    def test_the_page_kind_families_render_at_metrics(self):
        from nornicdb_tpu.genserve import stats as gstats
        from nornicdb_tpu.telemetry.metrics import REGISTRY

        gstats.PAGES_RELEASED.labels("window").inc(0)
        text = REGISTRY.render_prometheus()
        for family in ("nornicdb_genserve_pages_released_total",
                       "nornicdb_genserve_attn_pages_walked_total",
                       "nornicdb_genserve_attn_pages_held_total"):
            for kind in ("full", "window"):
                assert f'{family}{{kind="{kind}"}}' in text, (family, kind)

    @pytest.mark.parametrize("metric", sorted(PLANNED_RATIOS))
    def test_planned_ratio_reads_above_zero(self, snapshot, metric):
        counters, dig = snapshot
        numerator, denominator = PLANNED_RATIOS[metric]
        assert dig(counters, denominator) > 0, denominator
        assert dig(counters, numerator) > 0, numerator


# ---------------------------------------------------- (vi) the slow ring
class TestSlowRing:
    def test_slow_root_outlives_300_faster_roots(self):
        slow_log.configure(threshold_s=0.02)
        with tracer.start_trace("slow.root") as slow:
            with tracer.stage("slow.stage"):
                time.sleep(0.03)
        for i in range(300):
            with tracer.start_trace(f"fast.{i}"):
                pass
        assert tracer.count() == 256  # the main ring turned over
        entry = tracer.trace(slow.trace_id)
        assert entry is not None and entry["root"] == "slow.root"
        assert [s["name"] for s in entry["spans"]] == [
            "slow.stage", "slow.root"]
        listed = tracer.traces(slow=True)
        assert [t["trace_id"] for t in listed] == [slow.trace_id]
        assert all(t["trace_id"] != slow.trace_id for t in tracer.traces())

    def test_threshold_zero_keeps_nothing_and_ring_is_bounded(self):
        slow_log.configure(threshold_s=0.0)
        with tracer.start_trace("r"):
            time.sleep(0.002)
        assert tracer.traces(slow=True) == []
        slow_log.configure(threshold_s=1e-9)
        for i in range(tracing_mod.SLOW_RING_CAPACITY + 10):
            with tracer.start_trace(f"r{i}"):
                pass
        assert len(tracer.traces(limit=1000, slow=True)) == \
            tracing_mod.SLOW_RING_CAPACITY

    def test_entry_in_both_rings_renders_once(self):
        slow_log.configure(threshold_s=1e-9)
        with tracer.start_trace("both") as root:
            with tracer.span("child"):
                pass
        entry = tracer.trace(root.trace_id)
        assert [s["name"] for s in entry["spans"]] == ["child", "both"]

    def test_admin_traces_slow_query_parameter(self, stack):
        _, _, server = stack
        slow_log.configure(threshold_s=1e-9)
        trace_id, _ = _post(server.port, "/nornicdb/embed",
                            {"text": "slow enough at a zero threshold"})
        _trace(trace_id, want=11)
        slow_log.configure(threshold_s=3600.0)
        for _ in range(3):
            _post(server.port, "/nornicdb/embed", {"text": "not slow"})

        def listed(query: str) -> list[str]:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/admin/traces{query}",
                    timeout=30) as r:
                return [t["trace_id"] for t in json.loads(r.read())["traces"]]

        assert listed("?slow=1") == [trace_id]
        assert len(listed("")) > 1 and len(listed("?slow=0")) > 1
