"""The load generator: a child process that never imports JAX, so it shares
neither the chip nor the server's GIL.  A cell's file says which loop:

* ``"loop": "closed"``: ``clients`` connections, each sends its next request
  when the last one is answered (a saturating load: the rate is the result).
  An op whose answer is a stream (``STREAM = True`` in its file) is read
  event by event: the send time, the arrival of every content event and the
  ids it carried are kept, so the first and the last are known.
* ``"loop": "open"``: requests are due at ``rate_per_s``, evenly paced
  (``"arrivals": "uniform"``, the constant-rate schedule of wrk2 ``-R``,
  vegeta ``-rate`` and k6's ``constant-arrival-rate``), dealt round-robin to
  ``clients`` connections; each is timed FROM ITS DUE TIME, so a stall is
  charged to every request it delays, and how late the generator itself sent
  is reported beside it.

Child protocol (lines on stdin/stdout): the child builds its request bodies,
prints ``ready``; the parent writes ``go``; clients loop until the parent
writes ``stop``; the child writes its records to ``--out`` (one JSON file),
prints ``done`` and exits.  All times are ``time.monotonic()``, which is one
clock for every process of the machine.
"""

from __future__ import annotations

import argparse
import http.client
import importlib.util
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RETRY_CAP_S = 1.0  # a 429's Retry-After is honoured up to this, once


_LOADED: dict = {}


def load_file(rel: str):
    """A module of the benchmark, found by its path under ``bench/`` (one
    instance a process, so that what it caches is cached once)."""
    if rel not in _LOADED:
        if HERE not in sys.path:
            sys.path.insert(0, HERE)  # a family file imports reference.py
        spec = importlib.util.spec_from_file_location(
            "bench_" + rel[:-3].replace("/", "_"), os.path.join(HERE, rel))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[rel] = mod
    return _LOADED[rel]


def load_op(name: str):
    return load_file(f"ops/{name}.py")


def run_clients(n_clients: int, send, stop: threading.Event,
                rate_per_s: float = 0.0) -> list[list]:
    """``send(client, index) -> (status, retried, answer)`` on ``n_clients``
    threads until ``stop`` (status 200 = answered): a closed loop, or with
    ``rate_per_s`` an open one in which client ``c``'s request ``i`` is due
    at ``go + (c + i * n_clients) / rate_per_s``.  Returns one record per
    request: ``[client, index, t_start, t_end, status, retried, answer,
    sent_late_s]``; an open loop's ``t_start`` is the due time."""
    records: list[list] = []
    lock = threading.Lock()
    go = time.monotonic()

    def loop(client: int) -> None:
        mine, index = [], 0
        while not stop.is_set():
            t0 = late = time.monotonic()
            if rate_per_s:
                t0 = go + (client + index * n_clients) / rate_per_s
                if stop.wait(max(0.0, t0 - late)):
                    break
                late = time.monotonic()
            status, retried, answer = send(client, index)
            mine.append([client, index, t0, time.monotonic(), status, retried,
                         answer, late - t0])
            index += 1
        with lock:
            records.extend(mine)

    threads = [threading.Thread(target=loop, args=(c,), daemon=True)
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records


def stream_once(op, port: int, body: bytes):
    """One streamed answer on a connection of its own (the route closes it
    when the stream ends).  The answer kept: when the request was sent, the
    ids of every content event and each event's arrival in ms after the
    send.  A stream that carries an error event, or ends without its
    terminator, is status 598."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    ids, chunk_ms, fault, done = [], [], None, False
    sent = time.monotonic()
    try:
        conn.request("POST", op.PATH, body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            return resp.status, resp.getheader("Retry-After"), resp.read()
        for line in resp:
            got = op.event(line)
            if got is None:
                continue
            kind, payload = got
            if kind == "content":
                chunk_ms.append(round((time.monotonic() - sent) * 1e3, 3))
                ids.extend(payload)
            elif kind == "error":
                fault = payload
            elif kind == "done":
                done = True
    except (http.client.HTTPException, OSError, ValueError) as e:
        fault = fault or repr(e)[:200]
    finally:
        conn.close()
    answer = {"sent": sent, "ids": ids, "chunk_ms": chunk_ms}
    if fault or not done:
        answer["error"] = fault or "the stream ended without [DONE]"
    return (598 if "error" in answer else 200), None, \
        json.dumps(answer).encode()


class HttpSender:
    """One keep-alive connection per client; bodies built before ``go``."""

    def __init__(self, cell: dict, seed: int, port: int):
        sys.path.insert(0, HERE)
        from traffic import Stream

        self.op = load_op(cell["op"])
        self.cell, self.port = cell, port
        self.streams = [Stream(cell, seed, c) for c in range(cell["clients"])]
        self.bodies = [
            [self.op.encode(s.request(i), cell["params"])
             for i in range(cell["prebuilt_per_client"])]
            for s in self.streams]
        self.conns = [None] * cell["clients"]
        self.built_late = 0
        self.keep_every = cell.get("keep_every", 1)
        self.streams_answers = getattr(self.op, "STREAM", False)

    def _post(self, client: int, body: bytes):
        for attempt in (0, 1):  # a dropped keep-alive connection reopens
            conn = self.conns[client]
            if conn is None:
                conn = self.conns[client] = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=120)
            try:
                conn.request("POST", self.op.PATH, body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                return resp.status, resp.getheader("Retry-After"), resp.read()
            except (http.client.HTTPException, OSError):
                conn.close()
                self.conns[client] = None
                if attempt:
                    return 599, None, b""
        return 599, None, b""

    def __call__(self, client: int, index: int):
        ready = self.bodies[client]
        if index < len(ready):
            body = ready[index]
        else:  # outran what was built before the window: say so
            self.built_late += 1
            body = self.op.encode(self.streams[client].request(index),
                                  self.cell["params"])
        post = (lambda: stream_once(self.op, self.port, body)) \
            if self.streams_answers \
            else (lambda: self._post(client, body))
        status, retry_after, raw = post()
        retried = False
        if status == 429:  # the documented shed: one retry, as a client does
            retried = True
            time.sleep(min(float(retry_after or 1), RETRY_CAP_S))
            status, _, raw = post()
        keep = status in (200, 598) and index % self.keep_every == 0
        return status, retried, raw.decode() if keep else None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)   # path of the cell's file
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    with open(args.cell) as f:
        cell = json.load(f)
    rate = 0.0
    if cell["loop"] == "open" and cell["arrivals"] == "uniform":
        rate = float(cell["rate_per_s"])
    elif cell["loop"] != "closed":
        sys.exit(f"loop {cell['loop']!r} / arrivals {cell.get('arrivals')!r}: "
                 "closed, or open with uniform arrivals")
    sender = HttpSender(cell, args.seed, args.port)
    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        return
    stop = threading.Event()
    result: dict = {}

    def work():
        cpu0, wall0 = time.process_time(), time.monotonic()
        result["records"] = run_clients(cell["clients"], sender, stop, rate)
        result["client_cpu_share"] = (time.process_time() - cpu0) / max(
            time.monotonic() - wall0, 1e-9)

    worker = threading.Thread(target=work)
    worker.start()
    sys.stdin.readline()  # "stop" (or EOF if the parent died)
    stop.set()
    worker.join()
    result["built_late"] = sender.built_late
    with open(args.out, "w") as f:
        json.dump(result, f)
    print("done", flush=True)


if __name__ == "__main__":
    main()
