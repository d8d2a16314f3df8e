"""From a ``jax.profiler`` capture to numbers.

Two stages, so the arithmetic can be checked without a chip:
:func:`load_events` reads the ``.xplane.pb`` files of a capture directory
into plain tuples ``(plane, line, name, start_s, dur_s)``; everything else
works on those tuples (``bench/fixtures/trace_events.json`` holds a small
recorded set, checked by ``bench/selfcheck.py``).

On a TPU the device planes are named ``/device:TPU:<n>``; their line
``XLA Ops`` holds one event per operation that ran, ``XLA Modules`` one per
executed program (named after the jitted function).  Busy time is the union
of the ``XLA Ops`` intervals; a program's time is the sum of its
``XLA Modules`` events.  The window is the host-side ``bench_window``
annotation that ``run.py`` puts around the traced seconds, else the extent
of the device events.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_MARK = "bench_window"


def load_events(capture_dir: str) -> list[tuple]:
    from jax.profiler import ProfileData

    events = []
    pattern = os.path.join(capture_dir, "**", "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True)):
        for plane in ProfileData.from_file(path).planes:
            device = bool(DEVICE_PLANE.match(plane.name))
            for line in plane.lines:
                for ev in line.events:
                    if device or ev.name == WINDOW_MARK:
                        events.append((plane.name, line.name, ev.name,
                                       ev.start_ns / 1e9,
                                       ev.duration_ns / 1e9))
    return events


def window_of(events: list[tuple]) -> tuple[float, float]:
    marks = [(s, s + d) for _, _, n, s, d in events if n == WINDOW_MARK]
    if marks:
        return max(marks, key=lambda m: m[1] - m[0])
    dev = [(s, s + d) for p, _, _, s, d in events if DEVICE_PLANE.match(p)]
    if not dev:
        raise ValueError("the capture holds no device event")
    return min(a for a, _ in dev), max(b for _, b in dev)


def _clip(events, line, window):
    lo, hi = window
    out = {}
    for plane, ln, name, start, dur in events:
        if ln != line or not DEVICE_PLANE.match(plane):
            continue
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.setdefault(plane, []).append((a, b, name))
    for spans in out.values():
        spans.sort()
    return out


def _union(spans) -> list[list[float]]:
    merged: list[list[float]] = []
    for a, b, *_ in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_seconds(events, window) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    per_plane = _clip(events, OPS_LINE, window) or \
        _clip(events, MODULES_LINE, window)
    if not per_plane:
        return 0.0
    return sum(sum(b - a for a, b in _union(s))
               for s in per_plane.values()) / len(per_plane)


def program_seconds(events, window, pattern: str) -> tuple[float, int]:
    """(summed seconds, executions) of the programs whose module name
    matches ``pattern``, over all device planes."""
    rx = re.compile(pattern)
    total, count = 0.0, 0
    for spans in _clip(events, MODULES_LINE, window).values():
        for a, b, name in spans:
            if rx.search(name):
                total += b - a
                count += 1
    return total, count


def _short(name: str) -> str:
    return re.sub(r"\(.*$", "", name)[:60]


def breakdown(events, window, top: int = 10) -> dict:
    """Device programs by summed seconds, and idle gaps by the program that
    ended them.  Nothing inside the program names its host work yet, so every
    gap is ``host_unattributed`` and carries what ran next."""
    by_name: dict[str, float] = {}
    gaps: dict[str, float] = {}
    modules = _clip(events, MODULES_LINE, window)
    for plane, spans in (_clip(events, OPS_LINE, window) or modules).items():
        edge = window[0]
        starts = [(a, n) for a, _, n in modules.get(plane, [])]
        for a, b in _union(spans) + [[window[1], window[1]]]:
            if a > edge:
                nxt = next((n for s, n in starts if s >= a - 1e-6), "end")
                key = f"host_unattributed_before:{_short(nxt)}"
                gaps[key] = gaps.get(key, 0.0) + a - edge
            edge = max(edge, b)
    for spans in modules.values():
        for a, b, name in spans:
            by_name[_short(name)] = by_name.get(_short(name), 0.0) + b - a
    rank = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_name), "idle_gaps": rank(gaps)}


def op_seconds(events, window, top: int = 12) -> list:
    """The operations inside the programs (the ``XLA Ops`` line) by summed
    seconds: ``[name, seconds, runs]``.  For an earlier line of a traced run,
    not for a metric: the compiler names them (``fusion.12``, ``copy.3``)."""
    by_name: dict[str, list] = {}
    for spans in _clip(events, OPS_LINE, window).values():
        for a, b, name in spans:
            slot = by_name.setdefault(_short(name), [0.0, 0])
            slot[0] += b - a
            slot[1] += 1
    return [[k, v[0], v[1]] for k, v in sorted(
        by_name.items(), key=lambda kv: -kv[1][0])[:top]]


def reduce_capture(events: list[tuple]) -> dict:
    window = window_of(events)
    return {"window": window, "window_s": window[1] - window[0],
            "busy_s": busy_seconds(events, window),
            "breakdown": breakdown(events, window)}


if __name__ == "__main__":  # python bench/trace.py <capture dir> [out.json]
    evs = load_events(sys.argv[1])
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            json.dump(evs, f)
    lines = sorted({(p, ln) for p, ln, *_ in evs})
    print(json.dumps({"lines": lines, **reduce_capture(evs)}, indent=1))
