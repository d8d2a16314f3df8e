"""The harness's own rules, on the CPU and without a server: a counter the
program lacks finds nothing to read; a chat request is the same request in
any process and holds what the cell states; a closed loop reports no
latency; a configuration whose family file is missing is refused; the manifest and every file under ``bench/`` pass ``selfcheck``.

    python3 -m pytest bench/tests/test_harness.py -q
"""

import hashlib
import json
import os
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
    assert len(manifest["workloads"]) == 3 and len(manifest["configs"]) == 3
    assert [m["name"] for m in manifest["end_to_end"]] == [
        "search_qps", "embed_p50_ms", "tpot_ms", "setup_s"]
    assert all(m.get("workloads") for m in manifest["per_layer"])
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
