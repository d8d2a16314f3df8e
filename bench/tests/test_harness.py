"""The harness's own rules, on the CPU and without a server: a counter the
program lacks finds nothing to read; a chat request is the same request in
any process and holds what the cell states; a closed loop reports no
latency; a configuration whose family file is missing is refused; the
manifest and every file under ``bench/`` pass ``selfcheck``; no two
per-layer files share a reader; a cell a later PR adds reads the shared
readers with no edit; a scale taken from the configuration; a cut may name a
depth and no width.

    python3 -m pytest bench/tests/test_harness.py -q
"""

import glob
import hashlib
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import run  # noqa: E402
import selfcheck  # noqa: E402
import traffic  # noqa: E402

RATIO = {"numerator": "genserve.decode_lane_tokens",
         "denominator": "genserve.decode_steps", "scale": 1}


def test_counter_ratio_reads_a_delta():
    before = {"genserve": {"decode_lane_tokens": 10, "decode_steps": 2}}
    after = {"genserve": {"decode_lane_tokens": 40, "decode_steps": 4}}
    assert run.counter_ratio(RATIO, before, after, {}) == 15.0


@pytest.mark.parametrize("snapshot", [
    {}, {"genserve": {}}, {"genserve": {"decode_steps": 3}},
    {"genserve": "unstarted"}])
def test_counter_ratio_finds_nothing_where_a_path_does_not_resolve(snapshot):
    """A parent laid under a newer benchmark has none of its new counters:
    the metric stays out of the line, the run goes on."""
    assert run.counter_ratio(RATIO, snapshot, snapshot, {}) is None


def test_counter_ratio_reads_the_window():
    spec = {"numerator": "g.a", "denominator": "bench.seconds"}
    assert run.counter_ratio(spec, {"g": {"a": 0}}, {"g": {"a": 30}},
                             {"seconds": 20.0}) == 1.5
    assert run.counter_ratio(spec, {"g": {"a": 0}}, {"g": {"a": 30}},
                             {}) is None


ROUTED = {"numerator": "g.hits", "denominator": "g.rows",
          "config_scale": {"paths": ["generator.num_experts_per_tok",
                                     "generator.moe_topk"], "power": -1}}


@pytest.mark.parametrize("generator,value", [
    ({"num_experts_per_tok": 6}, 2.0), ({"moe_topk": 12}, 1.0),
    ({"num_experts_per_tok": 4, "moe_topk": 12}, 3.0), ({}, None)])
def test_counter_ratio_takes_its_scale_from_the_configuration(generator,
                                                             value):
    """The first path the cell's configuration has; none, nothing read."""
    got = run.counter_ratio(ROUTED, {"g": {"hits": 0, "rows": 0}},
                            {"g": {"hits": 24, "rows": 2}}, {},
                            {"generator": generator})
    assert got == value


def test_config_scale_indexes_a_list():
    spec = {"config_scale": {"paths": ["generator.held_experts.1"],
                             "power": 1}, "scale": 0.5}
    config = {"generator": {"held_experts": [0, 20]}}
    assert selfcheck.config_scale(spec, config) == 10.0
    assert selfcheck.config_scale(spec, {"generator": {}}) is None


def chat_cell():
    with open(os.path.join(BENCH, "workloads",
                           "chat-sys4k-closed16.json")) as f:
        return json.load(f)


def digest(seed: int) -> str:
    cell = chat_cell()
    h = hashlib.sha256()
    for client in (0, 7, 15):
        stream = traffic.Stream(cell, seed, client)
        for index in (0, 1, 30):
            h.update(json.dumps(stream.request(index)).encode())
    return h.hexdigest()


@pytest.mark.parametrize("seed", [7, 2147483659])
def test_a_chat_request_is_the_same_in_two_processes(seed):
    code = ("import sys; sys.path.insert(0, %r); import test_harness; "
            "print(test_harness.digest(%d))" % (HERE, seed))
    other = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=120)
    assert other.returncode == 0, other.stderr[-2000:]
    assert other.stdout.strip() == digest(seed)
    assert digest(seed) != digest(seed + 1)


def test_chat_traffic_is_what_the_cell_states():
    cell = chat_cell()
    streams = [traffic.Stream(cell, 11, c) for c in range(cell["clients"])]
    first = [s.request(0) for s in streams]
    later = [s.request(5) for s in streams]
    words = lambda text: len(text.split())  # noqa: E731
    assert {r["system"] for r in first + later} == {first[0]["system"]}
    assert words(first[0]["system"]) == 4096
    for deal in (first, later):  # a round: the same work under every seed
        lengths = sorted(words(r["user"]) for r in deal)
        assert lengths[0] == 128 and lengths[-1] == 1024
        assert lengths == sorted(traffic.round_lengths(128, 1024, 16))
    assert len({r["user"] for r in first + later}) == 32


def test_a_closed_loop_reports_no_latency(tmp_path):
    """selfcheck says it of the files, and run.py stops before it builds
    anything."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, json; sys.path[:0] = [%r, %r]; import run\n"
         "plain = run.load_json\n"
         "def more(*parts):\n"
         "    got = plain(*parts)\n"
         "    if parts[-1] == 'chat-sys4k-closed16.json':\n"
         "        got['latency_metrics'] = {'tpot_ms': 50}\n"
         "    return got\n"
         "run.load_json = more\n"
         "sys.argv = ['run.py', '--workload', 'mem-chat-sys4k', '--seed', "
         "'1', '--seconds', '1', '--rehearse-cpu']\n"
         "run.main()" % (os.path.dirname(BENCH), BENCH)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "closed loop" in out.stderr
    assert not out.stdout.strip()


def test_the_seam_refuses_a_missing_family():
    with pytest.raises(SystemExit) as e:
        run.load_family("no_such_family")
    assert "no bench/models/no_such_family.py" in str(e.value)
    with pytest.raises(SystemExit):
        run.load_family("../run")
    assert run.load_family("qwen2").ROLE == "generator"
    assert run.load_family("bge_m3").ROLE == "embedder"


def test_selfcheck_accepts_the_manifest_and_refuses_a_missing_family():
    assert selfcheck.main() == 0
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    # at least: a later PR adds cells and configurations and may not edit this
    assert len(manifest["workloads"]) >= 8 and len(manifest["configs"]) >= 8
    assert [m["name"] for m in manifest["end_to_end"]] == [
        "search_qps", "embed_p50_ms", "tpot_ms", "setup_s"]
    cells = {w["name"] for w in manifest["workloads"]}
    assert len(manifest["per_layer"]) <= 128
    for m in manifest["per_layer"]:
        read_in = selfcheck.metric_cells(manifest, m)
        assert read_in and set(read_in) <= cells, m["name"]
    faults: list = []
    plain = os.path.isfile
    os.path.isfile = lambda p: plain(p) and not p.endswith("qwen2.py")
    try:
        selfcheck.check_files(manifest, faults)
    finally:
        os.path.isfile = plain
    assert any("generator.family" in f for f in faults), faults


def test_selfcheck_refuses_a_closed_loop_latency(monkeypatch):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    plain = json.load

    def with_latency(f):
        got = plain(f)
        if got.get("name") == "chat-sys4k-closed16":
            got["latency_metrics"] = {"tpot_ms": 50}
        return got

    monkeypatch.setattr(json, "load", with_latency)
    faults: list = []
    selfcheck.check_files(manifest, faults)
    assert any("closed loop" in f for f in faults), faults


# what a per-layer file reads: every field but its name, cells and prose
READER_KEYS = ("reader", "kind", "programs", "work", "numerator",
               "denominator", "scale", "config_scale", "value", "unit",
               "better", "layer", "moves", "source")


def tier1_pinned() -> set:
    """Copies of a reader that tier-1 tests pin: ``tests/test_genserve_turn.py``
    reads every ``*.chat`` file and its list of the first four chat cells;
    ``tests/test_stage_spans.py`` finds ``attn_walked_share.<cell>`` by name
    (its ``cell`` cases other than ``gen``) and
    ``shared_run_pages_per_step.kv`` with its two cells."""
    root = os.path.dirname(BENCH)
    with open(os.path.join(root, "tests", "test_stage_spans.py")) as f:
        spans = f.read()
    cells = re.search(r'parametrize\("cell", \[([^]]*)\]\)\s*def '
                      r'test_the_walk_counters_are_what_attn_walked_share_reads',
                      spans)
    walked = {f"attn_walked_share.{c}" for c in re.findall(
        r'"(\w+)"', cells.group(1)) if c != "gen"}
    kv = set(re.findall(r'"(shared_run_pages_per_step\.\w+)"', spans))
    chat = {os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(BENCH, "metrics", "*.chat.json"))}
    return chat | walked | kv


def test_no_two_metric_files_share_a_reader():
    """One file a reader, read in every cell the manifest names for it: of
    the files that share a reader, all but one are copies a tier-1 test
    pins (they go when those tests are edited)."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    files: dict = {}
    for m in manifest["per_layer"]:
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        key = json.dumps({k: spec.get(k) for k in READER_KEYS},
                         sort_keys=True)
        files.setdefault(key, set()).add(m["name"])
    pinned = tier1_pinned()
    assert len(pinned) == 13, pinned
    shared = [names for names in files.values() if len(names - pinned) > 1]
    assert not shared, shared


def test_a_cell_added_later_reads_the_shared_readers_with_no_edit():
    """A chat cell that a later PR appends to the cells and to ``tpot_ms``'s
    list reads every per-layer metric that names no cells; a file of the
    benchmark states a cell list only where a tier-1 test reads it there."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    tpot = next(e for e in manifest["end_to_end"] if e["name"] == "tpot_ms")
    chat = set(tpot["workloads"])
    manifest["workloads"].append(dict(manifest["workloads"][-1],
                                      name="new-chat-sys4k",
                                      traffic="chat-new"))
    tpot["workloads"].append("new-chat-sys4k")
    shared = [m["name"] for m in manifest["per_layer"] if "workloads" not in m]
    assert {"device_idle.gen", "host_turn_ms_per_step.gen",
            "read_wait_ms_per_step.gen"} <= set(shared)
    for m in manifest["per_layer"]:
        cells = selfcheck.metric_cells(manifest, m)
        assert ("new-chat-sys4k" in cells) == (m["name"] in shared)
        if m["name"] in shared:
            assert set(cells) - {"new-chat-sys4k"} == chat, m["name"]
    faults: list = []
    selfcheck.check_manifest(manifest, faults)
    assert not faults, faults
    pinned = tier1_pinned()
    for m in manifest["per_layer"]:
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            assert ("workloads" in json.load(f)) == (
                m["name"] in pinned - {"attn_walked_share.dsv2",
                                       "attn_walked_share.lcf"}), m["name"]


@pytest.mark.parametrize("key,refused", [
    ("num_hidden_layers", False), ("num_layers", False), ("vocab_size", False),
    ("hidden_size", True), ("moe_intermediate_size", True),
    ("kv_lora_rank", True), ("head_dim", True), ("ssm_state_size", True),
    ("num_experts_per_tok", True), ("expand", True)])
def test_reduced_may_name_a_depth_and_no_width(key, refused):
    """Width words match whole: the source's ``num_hidden_layers`` is a
    depth a cut may name; ``hidden_size`` stays refused."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"][0]["reduced"] = [key]
    faults: list = []
    selfcheck.check_manifest(manifest, faults)
    assert any(f"may not name {key!r}" in f for f in faults) == refused, faults
