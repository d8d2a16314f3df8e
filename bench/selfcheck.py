#!/usr/bin/env python3
"""Check the manifest and every file under ``bench/`` before any chip call.

    python3 bench/selfcheck.py

Holds ``BENCHMARK.json`` to the limits a driver refuses a manifest over
(names, units, lengths, counts, the quarter rule for four-chip cells), checks
that every name in it leads to its file, that each per-layer metric's
``moves`` is an end-to-end metric that every cell it is read in reports
(:func:`metric_cells`), and runs ``bench/trace.py`` and ``bench/work.py`` on
a small recorded trace with known answers.  Exits 1 with every fault it
found.
"""

from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}
# a key names a width (a size of a row) where it ends in ``_dim`` or
# ``_rank`` or holds one of these words whole, unless its last word is
# ``layers``: ``num_hidden_layers`` is a depth
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj",
               "projection", "expansion", "expand", "experts_per")


def names_a_width(key: str) -> bool:
    words = key.split("_")
    if key.endswith(("_dim", "_rank")):
        return True
    if words[-1] == "layers":
        return False
    return any(w.split("_") == words[i:i + w.count("_") + 1]
               for w in WIDTH_WORDS for i in range(len(words)))


def metric_cells(m: dict, metric: dict) -> list[str]:
    """The cells in which ``metric`` of manifest ``m`` is read: its
    ``workloads``; without that key every cell, or, for a per-layer metric,
    every cell that reports the end-to-end metric it ``moves`` (so a cell a
    later PR adds reads it with no edit)."""
    cells = [w["name"] for w in m["workloads"]]
    if "workloads" in metric:
        return metric["workloads"]
    moves = next((e for e in m["end_to_end"]
                  if e["name"] == metric.get("moves")), {})
    return moves.get("workloads", cells)


def line_ok(text, limit: int = 200) -> bool:
    return isinstance(text, str) and 1 <= len(text) <= limit \
        and "\n" not in text and "\t" not in text


def check_manifest(m: dict, faults: list[str]) -> None:
    say = faults.append
    if set(m) != TOP_KEYS:
        say(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
        return
    if not (isinstance(m["command"], list) and 1 <= len(m["command"]) <= 32
            and all(line_ok(w) for w in m["command"])):
        say("command: 1 to 32 words of 1 to 200 characters")
    for word in m["command"]:
        if word.startswith("/") or ".." in word.split("/"):
            say(f"command word {word!r} leaves the repo")
        if "/" in word and not any(
                word == p or word.startswith(p + "/") for p in m["paths"]):
            say(f"command word {word!r} names a file outside paths")
    if not (1 <= len(m["paths"]) <= 16 and all(
            PATH.match(p) and not p.startswith("/") and ".." not in p
            for p in m["paths"])):
        say("paths: 1 to 16 relative directories")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        say("run_seconds: a whole number from 1 to 51")
    for group, (lo, hi) in {"configs": (1, 24), "workloads": (1, 24),
                            "end_to_end": (1, 16),
                            "per_layer": (1, 128)}.items():
        if not lo <= len(m[group]) <= hi:
            say(f"{group}: {lo} to {hi} entries, not {len(m[group])}")
        names = [e.get("name") for e in m[group]]
        if len(set(names)) != len(names):
            say(f"{group}: a name appears twice")
        for e in m[group]:
            allowed = KEYS[group] | ({"workloads"} if group in (
                "end_to_end", "per_layer") else set())
            if not KEYS[group] <= set(e) <= allowed:
                say(f"{group} {e.get('name')}: keys {sorted(e)}")
            if not NAME.match(str(e.get("name", ""))):
                say(f"{group}: bad name {e.get('name')!r}")
    metric_names = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    if len(set(metric_names)) != len(metric_names):
        say("a metric name appears twice")
    configs = {c["name"]: c for c in m["configs"]}
    cells = {w["name"]: w for w in m["workloads"]}
    files = [c["file"] for c in m["configs"]]
    if len(set(files)) != len(files):
        say("two configurations share a file")
    for c in m["configs"]:
        if not (line_ok(c["source"]) and line_ok(c["why"])):
            say(f"config {c['name']}: source and why are 1 to 200 characters")
        if not any(c["file"].startswith(p + "/") for p in m["paths"]) \
                or not PATH.match(c["file"]):
            say(f"config {c['name']}: file {c['file']!r} is not under paths")
        elif not os.path.isfile(os.path.join(ROOT, c["file"])):
            say(f"config {c['name']}: {c['file']} does not exist")
        if len(c["reduced"]) > 16:
            say(f"config {c['name']}: reduced has over 16 keys")
        for key in c["reduced"]:
            if not NAME.match(key) or names_a_width(key):
                say(f"config {c['name']}: reduced may not name {key!r}")
        if c["name"] not in {w["config"] for w in m["workloads"]}:
            say(f"config {c['name']}: no cell uses it")
    pairs = set()
    for w in m["workloads"]:
        if w["config"] not in configs:
            say(f"cell {w['name']}: unknown config {w['config']!r}")
        if not NAME.match(w["traffic"]):
            say(f"cell {w['name']}: bad traffic name {w['traffic']!r}")
        if w["chips"] not in (1, 4):
            say(f"cell {w['name']}: chips is 1 or 4")
        if not line_ok(w["why"]):
            say(f"cell {w['name']}: why is one line of 1 to 200 characters")
        if (w["config"], w["traffic"]) in pairs:
            say(f"cell {w['name']}: its (config, traffic) pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    if four > max(1, len(m["workloads"]) // 4):
        say(f"{four} four-chip cells: at most a quarter, rounded down (or 1)")
    e2e = {e["name"]: e for e in m["end_to_end"]}
    if "setup_s" not in e2e:
        say("end_to_end lacks setup_s")
    reports = {name: set() for name in cells}  # cell -> e2e metrics it reports
    for e in m["end_to_end"] + m["per_layer"]:
        if not UNIT.match(str(e.get("unit", ""))):
            say(f"metric {e['name']}: bad unit {e.get('unit')!r}")
        if e.get("better") not in ("lower", "higher"):
            say(f"metric {e['name']}: better is lower or higher")
        if e.get("source") not in SOURCES:
            say(f"metric {e['name']}: source {e.get('source')!r}")
        for cell in e.get("workloads", []):
            if cell not in cells:
                say(f"metric {e['name']}: unknown cell {cell!r}")
    for e in m["end_to_end"]:
        if e["source"] not in ("host_clock", "device_trace"):
            say(f"end-to-end {e['name']}: host_clock or device_trace only")
        if not (isinstance(e.get("bound"), (int, float))
                and 0.01 <= e["bound"] <= 0.1):
            say(f"end-to-end {e['name']}: bound {e.get('bound')} is outside "
                "0.01 to 0.1")
        for cell in metric_cells(m, e):
            if cell in reports:
                reports[cell].add(e["name"])
    layered = {name: 0 for name in cells}
    for e in m["per_layer"]:
        if not NAME.match(str(e.get("layer", ""))):
            say(f"per-layer {e['name']}: layer {e.get('layer')!r} has to be "
                "1 to 64 letters, digits, '_', '.', '-' (no space)")
        if e.get("moves") not in e2e or e.get("moves") == "setup_s":
            say(f"per-layer {e['name']}: moves {e.get('moves')!r} is no "
                "end-to-end metric")
            continue
        for cell in metric_cells(m, e):
            if cell in reports and e["moves"] not in reports[cell]:
                say(f"per-layer {e['name']}: cell {cell} does not report "
                    f"{e['moves']}")
            if cell in layered:
                layered[cell] += 1
    for cell in cells:
        if len(reports[cell] - {"setup_s"}) < 1 or "setup_s" not in reports[cell]:
            say(f"cell {cell}: reports setup_s and one more end-to-end metric")
        if layered[cell] < 1:
            say(f"cell {cell}: reports no per-layer metric")
    runs = 2 + 14 * 24
    need = runs * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    if need > 43200:
        say(f"run_seconds {m['run_seconds']}: a full check of 24 cells needs "
            f"{need} s > 43200")


CLOSED_LOOP_LATENCY = ("a saturating closed loop's latency is connections / "
                       "rate: it reports none (latency_metrics)")


def check_files(m: dict, faults: list[str]) -> None:
    say = faults.append
    import loadgen

    def load(*parts):
        path = os.path.join(HERE, *parts)
        if not os.path.isfile(path):
            say(f"{os.path.relpath(path, ROOT)} does not exist")
            return None
        with open(path) as f:
            return json.load(f)

    for root, _, names in os.walk(HERE):
        for name in names:
            rel = os.path.relpath(os.path.join(root, name), ROOT)
            if "__pycache__" not in rel and not PATH.match(rel):
                say(f"file name {rel!r} holds a character outside a name's")
    for c in m["configs"]:
        cfg = load("configs", c["name"] + ".json")
        if cfg is None:
            continue
        if cfg.get("reduced") != c["reduced"]:
            say(f"config {c['name']}: reduced differs between the manifest "
                "and the file")
        for key in ("source", "deployment", "corpus", "model", "guarantees",
                    "limits", "hbm_reckoning", "assumed", "rehearsal"):
            if key not in cfg:
                say(f"config {c['name']}: the file lacks {key!r}")
        for role in ("model", "generator"):
            family = cfg.get(role, {}).get("family")
            if role in cfg and not (isinstance(family, str) and os.path.isfile(
                    os.path.join(HERE, "models", family + ".py"))):
                say(f"config {c['name']}: {role}.family {family!r} names no "
                    "bench/models/<family>.py")
            elif role in cfg:
                fam = loadgen.load_file(f"models/{family}.py")
                for attr in ("program_config", "make_params", "install"):
                    if not hasattr(fam, attr):
                        say(f"bench/models/{family}.py lacks {attr}")
        reck = cfg.get("hbm_reckoning", {})
        parts = sum(v for k, v in reck.items() if k.endswith("_bytes")
                    and k not in ("total_bytes", "chip_hbm_bytes"))
        if parts != reck.get("total_bytes"):
            say(f"config {c['name']}: hbm_reckoning does not add up")
    for w in m["workloads"]:
        cell = load("workloads", w["traffic"] + ".json")
        if cell is None:
            continue
        op_path = os.path.join(HERE, "ops", cell["op"] + ".py")
        if not os.path.isfile(op_path):
            say(f"cell {w['name']}: no bench/ops/{cell['op']}.py")
        else:
            op = loadgen.load_op(cell["op"])
            for attr in ("PATH", "encode", "decode", "check"):
                if not hasattr(op, attr):
                    say(f"bench/ops/{cell['op']}.py lacks {attr}")
        if cell.get("loop") == "open":
            if cell.get("arrivals") != "uniform" or not cell.get("rate_per_s"):
                say(f"cell {w['name']}: an open loop states arrivals "
                    "'uniform' and rate_per_s")
            if cell.get("rate_metric"):
                say(f"cell {w['name']}: an open loop's rate is offered, not "
                    "a result")
        elif cell.get("loop") != "closed":
            say(f"cell {w['name']}: loop is 'closed' or 'open'")
        elif cell.get("latency_metrics"):
            say(f"cell {w['name']}: {CLOSED_LOOP_LATENCY}")
        if cell.get("request") not in ("vector", "text", "chat"):
            say(f"cell {w['name']}: request is 'vector', 'text' or 'chat'")
        if cell.get("token_gap_metric") and not (
                os.path.isfile(op_path) and getattr(
                    loadgen.load_op(cell["op"]), "STREAM", False)):
            say(f"cell {w['name']}: token_gap_metric is read off a streamed "
                "op (STREAM = True)")
        if not cell.get("assumed"):
            say(f"cell {w['name']}: the traffic file lists what it assumed")
        for name in [cell.get("rate_metric"), cell.get("token_gap_metric"),
                     *cell.get("latency_metrics", {})]:
            metric = next((e for e in m["end_to_end"] if e["name"] == name),
                          None)
            if name and (metric is None or w["name"] not in
                         metric_cells(m, metric)):
                say(f"cell {w['name']}: {name!r} is not an end-to-end "
                    "metric of this cell")
    for e in m["per_layer"]:
        spec = load("metrics", e["name"] + ".json")
        if spec is None:
            continue
        for key in ("layer", "unit", "better", "source", "moves"):
            if spec.get(key) != e[key]:
                say(f"metric {e['name']}: {key} differs between the manifest "
                    "and bench/metrics")
        # the manifest lists a metric's cells; a file that states them too
        # (where a test reads the list off the file) states the same
        if "workloads" in spec and spec["workloads"] != e.get("workloads"):
            say(f"metric {e['name']}: workloads differ between the manifest "
                "and bench/metrics")
        if "config_scale" in spec:
            for w in m["workloads"]:
                if w["name"] in metric_cells(m, e) and config_scale(
                        spec, load("configs", w["config"] + ".json")) is None:
                    say(f"metric {e['name']}: no path of config_scale in "
                        f"configuration {w['config']}")
        if spec.get("reader") not in ("counter_ratio", "trace", "window"):
            say(f"metric {e['name']}: reader {spec.get('reader')!r}")
        if "work" in spec:
            module = spec["work"].get("module", "work") + ".py"
            if not os.path.isfile(os.path.join(HERE, module)) or not hasattr(
                    loadgen.load_file(module), spec["work"]["fn"]):
                say(f"metric {e['name']}: bench/{module} has no "
                    f"{spec['work']['fn']}")
        if spec.get("kind") == "roofline" and \
                not e["name"].split(".")[0].endswith("_roofline"):
            say(f"metric {e['name']}: a roofline share is <kernel>_roofline")


def config_scale(spec: dict, config: dict):
    """A ``counter_ratio``'s ``scale`` times the number at the first of
    ``config_scale.paths`` (dotted, a list index a number) that the cell's
    configuration has, to ``config_scale.power``; None where it has none."""
    for path in spec["config_scale"]["paths"]:
        tree = config
        try:
            for key in path.split("."):
                tree = tree[int(key)] if isinstance(tree, list) else tree[key]
        except (KeyError, IndexError, TypeError, ValueError):
            continue
        return spec.get("scale", 1.0) * tree ** spec["config_scale"]["power"]
    return None


def check_trace(faults: list[str]) -> None:
    """The recorded events (a cut of a real v5e capture plus its answers,
    worked out by hand) through the same functions a run uses."""
    import trace as trace_mod
    import work

    path = os.path.join(HERE, "fixtures", "trace_events.json")
    with open(path) as f:
        fixture = json.load(f)
    events = [tuple(e) for e in fixture["events"]]
    want = fixture["expect"]
    cap = trace_mod.reduce_capture(events)
    got = {"window_s": cap["window_s"], "busy_s": cap["busy_s"]}
    for pattern, (secs, runs) in want["programs"].items():
        s, n = trace_mod.program_seconds(events, cap["window"], pattern)
        got[f"program:{pattern}"] = [s, n]
        if abs(s - secs) > 1e-9 or n != runs:
            faults.append(f"trace fixture: {pattern} gives {s} s x{n}, "
                          f"expected {secs} s x{runs}")
    for key in ("window_s", "busy_s"):
        if abs(got[key] - want[key]) > 1e-9:
            faults.append(f"trace fixture: {key} {got[key]} != {want[key]}")
    gaps = dict(cap["breakdown"]["idle_gaps"])
    if abs(sum(gaps.values()) - (want["window_s"] - want["busy_s"])) > 1e-9:
        faults.append("trace fixture: idle gaps do not add up to the idle "
                      f"time: {gaps}")
    if cap["breakdown"]["device_ops"][0][0] != want["top_program"]:
        faults.append(f"trace fixture: top program "
                      f"{cap['breakdown']['device_ops'][0]}")
    # the yardstick's arithmetic, on round numbers
    cfg = {"corpus": {"rows": 1000, "dims": 10, "score_dtype": "bf16"},
           "model": {"hidden": 4, "intermediate": 8, "layers": 2,
                     "dtype": "bfloat16"}}
    peaks = {"bf16_flops": 1e6, "hbm_bytes_per_s": 1e6}
    scans = work.topk_scans(cfg, scans=3, queries=3)
    if scans != {"flops": 60000.0, "bytes": 60000.0}:
        faults.append(f"work.topk_scans: {scans}")
    if work.least_seconds(scans, peaks) != (0.06, "compute"):
        faults.append(f"work.least_seconds: {work.least_seconds(scans, peaks)}")
    import loadgen

    emb = loadgen.load_file("models/bge_m3.py").embed_texts(cfg, [3, 5])
    if emb["flops"] != 2.0 * 256 * 8 + 4.0 * 2 * 4 * 34:
        faults.append(f"models/bge_m3.embed_texts: {emb}")
    qwen = loadgen.load_file("models/qwen2.py")
    gen = {"generator": {"hidden": 4, "intermediate": 8, "layers": 2,
                         "heads": 2, "kv_heads": 1, "vocab_size": 10,
                         "dtype": "bfloat16"}}
    # per layer q 16 + k 8 + v 8 + o 16 + 3 x 32 = 144 parameters: 288 in all
    toks = qwen.gen_tokens(gen, [[0.5, 2, 6]], [[6, 8]], 3)
    want = 0.5 * (576.0 * 4 + 32.0 * 18) + 576.0 * 2 + 32.0 * 15 + 240.0
    per = qwen.param_bytes(gen["generator"])  # (40 + 2 x 152) x 2 + 5 x 16
    if qwen.matmul_params(gen["generator"]) != 288 or toks != {
            "flops": want, "bytes": 768.0} or per != 768:
        faults.append(f"models/qwen2.gen_tokens: {toks} != {want}, {per} B")
    try:
        work.peaks_for("no such chip")
        faults.append("work.peaks_for: an unknown device is not an error")
    except KeyError:
        pass


def main() -> int:
    faults: list[str] = []
    path = os.path.join(ROOT, "BENCHMARK.json")
    raw = open(path, "rb").read()
    if len(raw) > 64 * 1024:
        faults.append("BENCHMARK.json is over 64 KiB")
    manifest = json.loads(raw)
    check_manifest(manifest, faults)
    if not faults:
        check_files(manifest, faults)
    check_trace(faults)
    for fault in faults:
        print("FAULT " + fault)
    print(f"selfcheck: {len(faults)} fault(s); "
          f"{len(manifest.get('workloads', []))} cell(s), "
          f"{len(manifest.get('per_layer', []))} per-layer metric(s)")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
