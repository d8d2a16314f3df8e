#!/usr/bin/env python3
"""One run of one cell: load, warm, measure, compare, print one line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name: ``BENCHMARK.json`` names the
cell's configuration (``bench/configs/<configuration>.json``) and traffic
(``bench/workloads/<traffic>.json``), the traffic names its op
(``bench/ops/<op>.py``), every per-layer metric is
``bench/metrics/<metric>.json``, and each model of the configuration names
its family (``bench/models/<family>.py``: the program's config object, the
seeded weights, how ``cmd_serve`` wires it, its plain reference and its work
functions).  The serving stack is built as ``nornicdb serve`` builds it, with
the configuration's options (``backend.fallback = "fail"`` in all of them).

The last stdout line is the result.  Earlier stdout lines are JSON facts
(phase seconds, compiles and sheds in the window, client CPU share, HBM by
component); the last stderr lines are the numbers compared, each beside its
limit.  A run that finds no TPU, or another number of devices than the cell
asks for, exits 2 and prints no result (``--rehearse-cpu`` runs the same
control flow on the CPU backend at the configuration's ``rehearsal`` sizes;
its ``device`` says ``cpu``).
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # setup_s runs from here to the first timed op

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

import numpy as np  # noqa: E402

import loadgen  # noqa: E402
import reference  # noqa: E402
import selfcheck  # noqa: E402
import trace as trace_mod  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

CHUNK_ROWS = 65536


def emit(**facts) -> None:
    print(json.dumps(facts, default=float), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def dig(tree: dict, path: str):
    for key in path.split("."):
        tree = tree[key]
    return tree


def load_family(name: str):
    """``bench/models/<family>.py``, found by the name a configuration
    gives its model: the harness itself names no model."""
    rel = f"models/{name}.py"
    if not str(name).replace("_", "").isalnum() \
            or not os.path.isfile(os.path.join(HERE, rel)):
        sys.exit(f"model family {name!r}: no bench/{rel}")
    return loadgen.load_file(rel)


def overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, val in over.items():
        out[key] = overlay(out[key], val) if isinstance(val, dict) \
            and isinstance(out.get(key), dict) else val
    return out


class Compiles:
    """JAX's own compile events (a persistent-cache hit's retrieval too)."""

    def __init__(self):
        import jax.monitoring

        self.times: list[float] = []
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.monotonic())
            self.seconds += secs

    def between(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.times if lo <= t <= hi)


def host_steal_s() -> float:
    """Seconds the hypervisor kept this machine's CPUs from it (all CPUs
    summed, /proc/stat): a window with a stall and a jump here was stalled
    from outside the process."""
    try:
        with open("/proc/stat") as f:
            return float(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return 0.0


class Pauses:
    """The serving process's own garbage collections: a full one stops
    every thread of the server, and in an open loop that is a tail."""

    def __init__(self):
        import gc

        self.spans: list[tuple[float, float]] = []
        self._at = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._at = time.monotonic()
        else:
            self.spans.append((self._at, time.monotonic() - self._at))

    def between(self, lo: float, hi: float) -> dict:
        ms = [d * 1e3 for t, d in self.spans if lo <= t <= hi and d >= 1e-3]
        return {"gc_pauses_over_1ms": len(ms), "gc_pause_total_ms": sum(ms),
                "gc_pause_max_ms": max(ms, default=0.0)}


# ------------------------------------------------------------------ set-up
def acquire(app_cfg, chips: int, rehearse: bool):
    from nornicdb_tpu import backend

    backend.configure(app_cfg.backend)
    mgr = backend.manager()
    mgr.ensure_started()  # PJRT init runs on the manager's thread from here

    def wait():
        if not mgr.await_ready(timeout=600.0):
            sys.exit(f"backend never reached READY: {mgr.stats()}")
        import jax

        devs = jax.devices()
        if devs[0].platform != "tpu" and not rehearse:
            print(f"no TPU: JAX reports {devs}", file=sys.stderr)
            os._exit(2)
        if len(devs) != chips and not rehearse:
            print(f"the cell asks for {chips} chip(s), JAX shows {len(devs)}: "
                  "with a second device the corpus would promote to a mesh "
                  "that the bulk load does not fill", file=sys.stderr)
            os._exit(2)
        return devs

    return mgr, wait


def when_ready(mgr, call, what: str, wait_s: float = 300.0):
    """``call()``, made again once the backend is READY if it finds the
    backend degraded.  Set-up keeps the device's transfer queue full (a 4.3
    GB upload, 24-layer compiles); on a slow host three health probes in a
    row can time out behind it, and under ``fallback = "fail"`` the next
    device call then raises.  The manager recovers by itself (two green
    probes, re-acquire, re-upload): set-up waits for that, as an operator
    would, and its seconds count in ``setup_s``."""
    from nornicdb_tpu.errors import DeviceUnavailable

    for _ in range(3):
        try:
            return call()
        except DeviceUnavailable as e:
            print(f"{what}: {e}; waiting for READY", file=sys.stderr)
            until = time.monotonic() + wait_s
            while mgr.stats()["state"] != "READY":
                if time.monotonic() > until:
                    raise
                time.sleep(0.5)
    return call()


MODEL_ROLES = ("model", "generator")  # the embedder, the assistant's decoder


def build_stack(app_cfg, config: dict, seed: int, data_dir: str):
    """What cmd_serve wires (nornicdb_tpu/cli.py).  Each model the
    configuration has (``model``: the embedder; ``generator``: optional) is
    made and wired by its family file, with the benchmark's seeded weights.
    Returns the db, the server and ``{role: (family, params, handle)}``."""
    import nornicdb_tpu
    import nornicdb_tpu.telemetry as telemetry
    from nornicdb_tpu import genserve
    from nornicdb_tpu.search import service as search_service
    from nornicdb_tpu.server import HttpServer

    families = {role: load_family(config[role]["family"])
                for role in MODEL_ROLES if config.get(role)}
    telemetry.configure(**vars(app_cfg.telemetry))
    search_service.configure_defaults(**vars(app_cfg.search))
    genserve.configure(app_cfg.genserve)
    db = nornicdb_tpu.open_db(data_dir)
    models = {}
    for role, family in families.items():
        spec = config[role]
        family.program_config(spec)  # refuses sizes that are not the preset's
        params = family.make_params(spec, seed)
        models[role] = (family, params,
                        family.install(db, app_cfg, spec, params))
    http = HttpServer(db, port=0)
    http.start()
    return db, http, models


def load_corpus(db, mgr, config: dict, seed: int, keep: bool):
    """The seeded rows, made on the device chunk by chunk and brought to the
    host (the host's own generator needs ~20 s for 10^9 normals), then loaded
    as today's program allows: row 0 through SearchService.index_node (the
    corpus is born on the normal path), the rest through HostCorpus.add_batch
    in two calls sized so that the capacity doubles to 1,048,576 as it would
    have by single writes, with the fewest copies."""
    import jax

    from nornicdb_tpu.storage import Node

    n, dims = config["corpus"]["rows"], config["corpus"]["dims"]

    @jax.jit
    def chunk(key, i):
        x = jax.random.normal(jax.random.fold_in(key, i), (CHUNK_ROWS, dims))
        return x / jax.numpy.linalg.norm(x, axis=1, keepdims=True)

    key = reference.jax_key(seed + 1)
    took = {"start": time.monotonic()}
    rows = np.empty((n, dims), np.float32)
    ahead = chunk(key, 0)
    for i, at in enumerate(range(0, n, CHUNK_ROWS)):
        now, ahead = ahead, chunk(key, i + 1)  # the next one runs meanwhile
        rows[at:at + CHUNK_ROWS] = np.asarray(now)[:n - at]
    del now, ahead
    took["made"] = time.monotonic()
    ids = [f"v{j:07d}" for j in range(n)]
    half = 1
    while half * 2 < n:
        half *= 2  # the largest power of two under n: 524,288 of 1,000,000
    db.search.index_node(Node(id=ids[0], embedding=rows[0]))
    corpus = db.search.corpus()
    corpus.add_batch(ids[1:half], rows[1:half])
    corpus.add_batch(ids[half:], rows[half:])
    took["added"] = time.monotonic()
    want = config["corpus"]["capacity_rows"]
    if len(corpus) != n or corpus.capacity != want:
        sys.exit(f"corpus holds {len(corpus)} rows of {corpus.capacity}, "
                 f"loaded {n} for a capacity of {want}")
    # one search: the first one uploads the corpus (the one full _sync) and
    # compiles the top-k program; every deployment here holds the rows on
    # the device, also the cell that never scans them
    probe = traffic.unit_rows(traffic.rng_for(seed, 4), 1, dims)[0]
    hits = when_ready(mgr, lambda: db.search.vector_candidates(probe, k=100),
                      "first search")
    if len(hits) != min(100, n):
        sys.exit("the warm search did not answer 100 hits")
    took["uploaded"] = time.monotonic()
    emit(load={"rows_made_s": took["made"] - took["start"],
               "add_batch_s": took["added"] - took["made"],
               "first_search_s": took["uploaded"] - took["added"]})
    return rows if keep else None


def counters(db, embedder, mgr) -> dict:
    """The program's own counters, read as they stand (deltas are taken over
    the window).  ``genserve`` is the generation engine's, and the same
    counters at nought where the deployment has no engine.
    (``tests/test_stage_spans.py`` compiles this function from the source
    and calls it so.)"""
    from nornicdb_tpu.genserve import GenStats

    engine, worker, gen = db.serving_engine(), db._embed_worker, \
        db.genserve_engine()
    return {"search": db.search.stats_snapshot(),
            "engine": dict(vars(engine.stats)),
            "embed_worker": dict(vars(worker.stats)),
            "embedder": dict(embedder.stats),
            "genserve": gen.stats_snapshot() if gen else GenStats().as_dict(),
            "backend": {k: v for k, v in mgr.stats().items()
                        if isinstance(v, (int, float))}}


def sheds_of(c: dict) -> int:
    return sum(v for root in ("engine", "genserve")
               for k, v in c.get(root, {}).items() if k.startswith("sheds_"))


def prewarm_packs(cell: dict, db, mgr, seed: int) -> dict:
    """The pack shape classes the cell's lengths produce, before any traffic:
    batches of the sizes the cell's file lists, with lengths drawn from the
    cell's own multiset, go through the serve stack's own embedder (the entry
    the HTTP handler calls).  Each class's first dispatch is a 24-layer
    compile or a cache load of seconds; met inside a window it would stall
    it."""
    from nornicdb_tpu.errors import ResourceExhausted

    spec, bands = cell.get("prewarm"), cell["params"].get("bands")
    if not spec or not bands:
        return {}
    lengths = traffic.band_lengths(bands)
    rng, t0, calls, sheds = traffic.rng_for(seed, 6), time.monotonic(), 0, 0
    for _ in range(spec["rounds"]):
        for size in spec["sizes"]:
            texts = [traffic.text_of(rng, int(n))
                     for n in rng.choice(lengths, size)]
            for attempt in range(200):
                try:
                    when_ready(mgr, lambda: db.embedder.embed_batch(texts),
                               "pre-warm")
                    break
                except ResourceExhausted:
                    # predictive admission, poisoned by the compile it just
                    # saw; its every 8th would-shed is let through
                    sheds += 1
                    time.sleep(0.005)
            calls += 1
    return {"prewarm_s": time.monotonic() - t0, "prewarm_calls": calls,
            "prewarm_sheds": sheds,
            "pack_classes": len(db.serving_engine().stats_snapshot().get(
                "packed_programs", []))}


def prime(cell: dict, op, seed: int, port: int) -> list:
    """What the deployment has served before the sessions begin: the cell's
    ``prime`` requests, one after the other, from a client of their own
    (stream ``clients``), with ``prime.params`` laid over the cell's.  A chat
    deployment has answered under its system prompt before: the prefix cache
    holds it.  (Sixteen sessions that begin at once against a cold cache
    each prefill the whole prompt: hits are looked up at admission, and a
    prompt's pages are published only when its last chunk lands.)  Returns
    the records, for the count of prompt tokens."""
    spec = cell.get("prime")
    if not spec:
        return []
    stream = traffic.Stream(cell, seed, cell["clients"])
    params = overlay(cell["params"], spec.get("params", {}))
    records = []
    for index in range(spec["requests"]):
        t0 = time.monotonic()
        status, _, raw = loadgen.stream_once(
            op, port, op.encode(stream.request(index), params))
        if status != 200:
            sys.exit(f"the prime request failed: {status} {raw[:300]!r}")
        records.append([cell["clients"], index, t0, time.monotonic(), status,
                        False, raw.decode(), 0.0])
    return records


def warm_up(cell: dict, snap, compiles: Compiles, go_at: float) -> dict:
    """The cell's own traffic, unmeasured, until it is steady: for
    ``quiet_s`` no program compiled, nothing was shed, work completed in
    every second, the counter ``until`` names has risen as far as it says
    (a closed loop of long requests: each session's first one is the
    start-up) and, in an open loop, the server was not behind (a stall
    leaves a backlog that drains for seconds at four fifths of capacity: a
    window that begins inside it measures the stall).  (Each pack class's
    first dispatch is a compile or a cache load that the cost model learns
    as device seconds, and predictive admission then sheds: a fault of the
    program, listed in PERF.md.)"""
    w = cell["warm"]
    rate = cell["rate_per_s"] if cell["loop"] == "open" else 0.0
    last_bad = time.monotonic()
    first_ops = last_ops = dig(snap(), w["progress"])
    until = w.get("until")  # a counter that has to rise first, and by what
    risen_from = dig(snap(), until["counter"]) if until else 0
    last_sheds, behind = sheds_of(snap()), 0.0
    while True:
        time.sleep(1.0)
        now, c = time.monotonic(), snap()
        ops, sheds = dig(c, w["progress"]), sheds_of(c)
        # an open loop's requests that are due and not yet answered
        behind = rate * (now - go_at) - (ops - first_ops)
        if ops == last_ops or sheds != last_sheds or behind > cell["clients"] \
                or compiles.between(now - 1.0, now):
            last_bad = now
        last_ops, last_sheds = ops, sheds
        steady = now - last_bad >= w["quiet_s"] and (
            not until or dig(c, until["counter"]) - risen_from
            >= until["rise"])
        if (now - go_at >= w["min_s"] and steady) or now - go_at >= w["max_s"]:
            if not steady:
                print(f"warm-up not steady after {w['max_s']} s",
                      file=sys.stderr)
            return {"warm_s": now - go_at, "steady": steady,
                    "behind_at_start": max(behind, 0.0)}


# ------------------------------------------------------------ the window
class Tracer:
    """A jax.profiler capture of the window's last ``for_s`` seconds, with a
    host annotation that marks its edges on the trace's own clock.  The
    capture is stopped (written out: seconds of host work) only after the
    window has closed and the traffic has stopped."""

    def __init__(self, directory: str, for_s: float):
        self.dir, self.for_s = directory, for_s
        self.span = None

    def capture(self, until: float) -> None:
        import jax

        time.sleep(max(0.0, until - self.for_s - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        lo = time.monotonic()
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_MARK):
            time.sleep(max(0.0, until - time.monotonic()))
        self.span = (lo, time.monotonic())

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()


def start_child(cell, seed: int, port: int, out: str):
    """The load generator, started early: it builds its bodies while the
    corpus loads."""
    return subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py"), "--cell",
         cell["_file"], "--seed", str(seed), "--port", str(port), "--out",
         out], stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)


def measure(cell, child, seconds, snap, compiles, tracer, out):
    """Start the traffic, warm up on it, then hold the window.  Returns the
    records of every request, the window's edges, the program's counters at
    both, and facts for an earlier line."""
    try:
        if child.stdout.readline().strip() != "ready":
            sys.exit("the load generator did not start")
        child.stdin.write("go\n")
        child.stdin.flush()
        extra = warm_up(cell, snap, compiles, time.monotonic())
        t0, before, stolen = time.monotonic(), snap(), host_steal_s()
        if tracer:
            tracer.capture(until=t0 + seconds)
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        t1, after = time.monotonic(), snap()
        extra["host_steal_s_in_window"] = host_steal_s() - stolen
        child.stdin.write("stop\n")
        child.stdin.flush()
        if tracer:
            tracer.stop()
        if child.stdout.readline().strip() != "done":
            sys.exit("the load generator did not finish")
        child.wait(timeout=60)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    got = load_json(out)
    extra.update(client_cpu_share=got["client_cpu_share"],
                 bodies_built_late=got["built_late"])
    if got["built_late"] and cell.get("rate_metric"):
        print(f"{got['built_late']} request bodies were built after `go`: "
              "the rate may be the load generator's, not the server's "
              "(prebuilt_per_client)", file=sys.stderr)
    return got["records"], (t0, t1), (before, after), extra


# ---------------------------------------------------------------- metrics
def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def counter_ratio(spec: dict, before: dict, after: dict, bench: dict,
                  config: dict | None = None):
    """``numerator`` / ``denominator``, each a dotted path into the
    program's counters (read as a delta over the window) or, as
    ``bench.<name>``, one of the window's own numbers, times ``scale`` (or,
    with ``config_scale``, times a number of the cell's ``config``).  A path
    that does not resolve (a counter that this program does not have: a
    parent laid under a newer benchmark) finds nothing to read."""
    def read(path):
        try:
            return bench[path[6:]] if path.startswith("bench.") \
                else dig(after, path) - dig(before, path)
        except (KeyError, TypeError):
            return None

    num, den = read(spec["numerator"]), read(spec["denominator"])
    scale = selfcheck.config_scale(spec, config) if "config_scale" in spec \
        else spec.get("scale", 1.0)
    if num is None or not den or scale is None:
        return None
    return scale * num / den


def trace_metric(spec: dict, config: dict, peaks: dict, cap: dict, ctx: dict):
    """``cap`` = the reduced capture; ``ctx`` = what completed inside it.  A
    reader that finds nothing to read returns nothing (a kernel taken off
    the path leaves its roofline silent), and says so on an earlier line."""
    kind = spec["kind"]
    if kind == "idle":
        return 100.0 * (1.0 - cap["busy_s"] / cap["window_s"])
    if kind not in ("roofline", "mfu"):
        raise ValueError(f"{spec['name']}: unknown trace metric kind {kind!r}")
    against = cap["window_s"]
    if kind == "roofline":
        against, runs = trace_mod.program_seconds(
            cap["events"], cap["window"], spec["programs"])
        ctx = {**ctx, "executions": runs}
        if not runs:
            return None
    fn = getattr(loadgen.load_file(spec["work"].get("module", "work")
                                   + ".py"), spec["work"]["fn"])
    args = {name: ctx[src] for name, src in spec["work"]["args"].items()}
    need, bound = work.least_seconds(fn(config, **args), peaks)
    if not need or not against:
        return None
    if kind == "roofline":
        emit(roofline=spec["name"], bound=bound, least_s=need,
             program_s=against, executions=runs)
    return 100.0 * need / against


# ------------------------------------------------------------- comparison
class Produced:
    """What an op's ``check`` may use: the requests the window answered, each
    request made again from the seed, the seeded rows and weights, and a
    seeded sample."""

    def __init__(self, cell, config, seed, answered, rows=None, models=None,
                 control=False, records=(), counters=None):
        self.cell, self.config, self.seed = cell, config, seed
        self.answered, self.rows, self.control = answered, rows, control
        self.models = models or {}        # role -> (family, params, handle)
        self.params = self.models.get("model", (None, None))[1]
        self.records = records            # every request sent, warm-up too
        self.counters = counters or {}    # the program's, once traffic ended
        self._streams: dict = {}

    def request(self, rec):
        stream = self._streams.get(rec[0]) or self._streams.setdefault(
            rec[0], traffic.Stream(self.cell, self.seed, rec[0]))
        return stream.request(rec[1])

    def sample(self, pool: list, longest=None) -> list:
        """``check_n`` of ``pool`` drawn from the seed, the ``longest`` in."""
        if not pool:
            return []
        rng = traffic.rng_for(self.seed, 9)
        picks = [pool[i] for i in rng.choice(
            len(pool), min(self.cell["check_n"], len(pool)), replace=False)]
        if longest is not None:
            top = max(pool, key=longest)
            if top not in picks:
                picks[0] = top
        return picks


# -------------------------------------------------------------------- run
def run_cell(args) -> dict:
    manifest = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        sys.exit(f"no workload {args.workload!r} in BENCHMARK.json")
    cell = load_json(HERE, "workloads", entry["traffic"] + ".json")
    cell["_file"] = os.path.join(HERE, "workloads", entry["traffic"] + ".json")
    config = load_json(HERE, "configs", entry["config"] + ".json")
    scratch = os.path.join(ROOT, ".bench_scratch", entry["name"])
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = overlay(config, config["rehearsal"])
        cell = overlay(cell, cell.get("rehearsal", {}))
        cell["_file"] = os.path.join(scratch, "cell.json")
        with open(cell["_file"], "w") as f:
            json.dump(cell, f)
    op = loadgen.load_op(cell["op"])
    if not native_built():
        subprocess.run(["make", "-C", os.path.join(ROOT, "native"), "all"],
                       check=True, capture_output=True, timeout=300)

    from nornicdb_tpu.config import AppConfig

    app_cfg = AppConfig()
    for path, value in config["deployment"]["options"].items():
        section, field = path.split(".")
        setattr(getattr(app_cfg, section), field, value)
    phase = {"start": time.monotonic()}
    mgr, wait_device = acquire(app_cfg, entry["chips"], args.rehearse_cpu)
    devices = wait_device()
    phase["acquired"] = time.monotonic()
    compiles, pauses = Compiles(), Pauses()
    data_dir = os.path.join(scratch, "data") \
        if config["deployment"]["data_dir"] == "fresh" else ""
    if cell["loop"] == "closed" and cell.get("latency_metrics"):
        sys.exit(f"{args.workload}: {selfcheck.CLOSED_LOOP_LATENCY}")
    db, http, models = build_stack(app_cfg, config, args.seed, data_dir)
    phase["stack"] = time.monotonic()
    out = os.path.join(scratch, "loadgen.json")
    child = start_child(cell, args.seed, http.port, out)
    try:
        rows = load_corpus(db, mgr, config, args.seed,
                           keep=getattr(op, "NEEDS_ROWS", False))
        phase["loaded"] = time.monotonic()
        snap = lambda: counters(db, models["model"][2], mgr)  # noqa: E731
        prewarmed = prewarm_packs(cell, db, mgr, args.seed)
        primed = prime(cell, op, args.seed, http.port)
        if primed:
            prewarmed["prime_s"] = primed[-1][3] - primed[0][2]
    except BaseException:
        child.kill()
        child.wait()
        raise

    tracer = None
    if args.trace:
        for_s = min(cell["trace_s"], max(args.seconds - 2.0, 0.5))
        tracer = Tracer(os.path.join(scratch, "trace"), for_s)
    records, (t0, t1), (before, after), extra = measure(
        cell, child, args.seconds, snap, compiles, tracer, out)
    setup_s = t0 - T_START

    at_end = snap()  # every request has ended: the child has exited
    inside = [r for r in records if t0 <= r[3] <= t1]
    done = [r for r in inside if r[4] == 200]
    streamed = getattr(op, "STREAM", False)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    from nornicdb_tpu.telemetry import deviceprof

    hbm = deviceprof.PROFILER.snapshot()["hbm_bytes"]
    late_ms = [r[7] * 1e3 for r in inside] or [0.0]
    in_window = compiles.between(t0, t1)
    emit(phases={a: phase[b] - phase[c] for a, b, c in (
        ("acquire_s", "acquired", "start"), ("stack_s", "stack", "acquired"),
        ("load_s", "loaded", "stack"))}, setup_s=setup_s, **prewarmed, **extra,
        compile_s=compiles.seconds, compiles_in_window=in_window,
        backend_degrades_total=after["backend"].get("degrades_total", 0),
        sheds_in_window=sheds_of(after) - sheds_of(before),
        retried_in_window=sum(1 for r in inside if r[5]),
        sent_late_p95_ms=percentile(late_ms, 95), sent_late_max_ms=max(late_ms),
        **pauses.between(t0, t1),
        statuses={str(k): sum(1 for r in inside if r[4] == k)
                  for k in {r[4] for r in inside}},
        hbm_bytes=hbm, hbm_reckoned=config["hbm_reckoning"],
        memory_peak_bytes=peak)

    lat_ms = [(r[3] - r[2]) * 1e3 for r in done]
    seconds = t1 - t0
    values = {"setup_s": setup_s}
    # the window, as the benchmark saw it
    bench = {"completed": len(done), "seconds": seconds}
    if streamed:
        # content tokens that arrived inside the window, whichever request
        # they belong to (one that ended after the window too): how many a
        # second, and the time from one to the next in its stream (every gap
        # that ended inside the window: all of the window's decoding); and
        # each request's wait for its first one
        got = {id(r): op.decode(r[6]) for r in records if r[4] == 200}
        at = [[a["sent"] + ms / 1e3 for ms in a["chunk_ms"]]
              for a in got.values()]
        gaps = [b - a for times in at for a, b in zip(times, times[1:])
                if t0 <= b <= t1]
        first_ms = [got[id(r)]["chunk_ms"][0] for r in done
                    if got[id(r)]["chunk_ms"]]
        bench["tokens"] = sum(len(got[id(r)]["ids"]) for r in done)
        bench["tokens_per_s"] = sum(
            1 for times in at for t in times if t0 <= t <= t1) / seconds
        if first_ms:
            bench["first_chunk_p50_ms"] = percentile(first_ms, 50)
        if gaps:
            bench["ms_per_token"] = 1e3 * sum(gaps) / len(gaps)
            bench["ms_per_token_p50"] = 1e3 * percentile(gaps, 50)
            if cell.get("token_gap_metric"):
                values[cell["token_gap_metric"]] = bench["ms_per_token"]
    if done:
        if cell.get("rate_metric"):
            values[cell["rate_metric"]] = len(done) / seconds
        for name, q in cell.get("latency_metrics", {}).items():
            values[name] = percentile(lat_ms, q)
        bench.update(p50_ms=percentile(lat_ms, 50),
                     p95_ms=percentile(lat_ms, 95),
                     p99_ms=percentile(lat_ms, 99),
                     rate_per_s=len(done) / seconds)
        emit(requests=len(done), **{k: v for k, v in bench.items()
                                    if k not in ("completed", "seconds")})
        # stalls: requests over three times the median, by when they ended
        slow = sorted((r for r in done if (r[3] - r[2]) * 1e3
                       > 3 * percentile(lat_ms, 50)), key=lambda r: r[3])
        emit(slow_share=len(slow) / len(done), slow_at_s_ms=[
            [round(r[3] - t0, 3), round((r[3] - r[2]) * 1e3, 1)]
            for r in slow[:40]])
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}

    units = {m["name"]: m["unit"] for m in
             manifest["end_to_end"] + manifest["per_layer"]}
    result: dict = {"correct": False, "attempted": len(inside),
                    "failed": len(inside) - len(done)}
    if not args.trace:
        wanted = [m["name"] for m in manifest["end_to_end"]
                  if args.workload in selfcheck.metric_cells(manifest, m)]
        metrics = {n: values[n] for n in wanted if n in values}
    else:
        cap_events = trace_mod.load_events(tracer.dir)
        cap = {**trace_mod.reduce_capture(cap_events), "events": cap_events}
        lo, hi = tracer.span
        in_trace = [r for r in done if lo <= r[3] <= hi]
        if hasattr(op, "trace_context"):  # the op says what the trace held
            ctx = op.trace_context(Produced(
                cell, config, args.seed, [r for r in records if r[4] == 200]),
                in_trace, lo, hi)
        else:
            made = Produced(cell, config, args.seed, in_trace)
            family = models["model"][0]
            ctx = {"completed": len(in_trace), "token_lengths": [
                family.token_length(config["model"], made.request(r))
                for r in in_trace] if cell["request"] == "text" else []}
        peaks = work.peaks_for(devices[0].device_kind) \
            if not args.rehearse_cpu else work.peaks_for("TPU v5e")
        metrics, unread = {}, []
        for m in manifest["per_layer"]:
            if args.workload not in selfcheck.metric_cells(manifest, m):
                continue
            spec = load_json(HERE, "metrics", m["name"] + ".json")
            if spec["reader"] == "counter_ratio":
                value = counter_ratio(spec, before, after, bench, config)
            elif spec["reader"] == "window":
                value = bench.get(spec["value"])
            else:
                value = trace_metric(spec, config, peaks, cap, ctx)
            if value is None:
                unread.append(m["name"])
            else:
                metrics[m["name"]] = value
        if unread:  # left out of the line, never read as 0: say it aloud
            emit(unread=unread)
            print(f"NOTHING TO READ for {unread}: a pattern that matches no "
                  "program, or a counter that did not move", file=sys.stderr)
        emit(xla_ops=trace_mod.op_seconds(cap_events, cap["window"]))
        device.update(busy_s=cap["busy_s"], window_s=cap["window_s"])
        result["breakdown"] = cap["breakdown"]
    result["metrics"] = {n: {"value": v, "unit": units[n]}
                         for n, v in metrics.items()}
    result["device"] = device

    http.stop()
    if db.genserve_engine() is not None:
        db.genserve_engine().stop()
    t_check = time.monotonic()
    # a stream is the window's if any of it fell inside: a window shorter than
    # two requests finishes few, and decodes sixteen at a time
    pool = [r for r in records if r[4] == 200 and r[3] >= t0 and r[2] <= t1] \
        if streamed else done
    numbers, ctl, compared = op.check(Produced(
        cell, config, args.seed, pool, rows, models, args.control,
        primed + records, at_end))
    emit(check_s=time.monotonic() - t_check)
    numbers += [
        reference.number("compared", compared, min(cell["check_n"], 8),
                         "higher"),
        # a window that compiled, or began before the warm-up was steady,
        # measured something else than the cell: it is a failed run
        reference.number("compiles_in_window", in_window, 0, "lower"),
        reference.number("warm_unsteady", not extra["steady"], 0, "lower")]
    result["correct"] = all(n["ok"] for n in numbers)
    if ctl:
        emit(control=ctl, control_correct=all(n["ok"] for n in ctl))
    result["compared"] = numbers
    for n in numbers:
        print(f"compared {n['name']}: {n['value']!r} limit {n['limit']!r} "
              f"{'ok' if n['ok'] else 'FAILS'}", file=sys.stderr)
    return result


def native_built() -> bool:
    return all(os.path.exists(os.path.join(ROOT, "native", so))
               for so in ("libwalcodec.so", "libsegstore.so"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="CPU backend at the configuration's rehearsal sizes")
    ap.add_argument("--control", action="store_true",
                    help="also compare the fp8 control (never in a check)")
    args = ap.parse_args()
    try:
        result = run_cell(args)
    except BaseException:
        traceback.print_exc()
        sys.stderr.flush()
        os._exit(1)
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    os._exit(0)  # daemon threads still inside XLA can abort a clean exit


if __name__ == "__main__":
    main()
