"""Cross-process device broker: one PJRT owner serving every worker.

The chip has one owner — the primary process. Prefork protocol workers
(server/workers.py) are plain subprocesses with no JAX; before this module
their only route to device compute was proxying whole HTTP requests back to
the primary's protocol stack, so worker scaling only scaled cache hits.
The broker is the missing hot path: workers submit **search / embed batch
requests** over a Unix-domain socket with compact length-prefixed binary
framing (f32/int8 query blocks in, top-k ids/scores out — no pickle, no
HTTP, no JSON), and the broker drains every connection's requests into the
primary's existing fused-dispatch machinery:

* search tickets go through ``SearchService.ensure_batcher()`` —
  cross-worker queries coalesce with each other (and with the primary's
  own traffic) into ONE device program per scan, the WindVE
  many-ingest-one-device shape (PAPERS.md);
* embed requests ride ``Embedder.embed_batch`` — behind ``cli serve`` that
  is the continuous ragged batching ServingEngine with its admission
  control.

The PR 8 taxonomy applies end-to-end: a shed (queue full / deadline) comes
back as a ``RESOURCE_EXHAUSTED`` status frame and the worker surfaces
HTTP 429 / gRPC RESOURCE_EXHAUSTED; a degraded backend comes back as a
``DEGRADED`` status frame and the worker serves its local host-search
fallback from the shared-memory read plane (server/readplane.py) instead
of hammering a device that is not there.

Wire protocol (all little-endian)
---------------------------------
Frame: ``u32 length | u8 msg_type | u64 request_id | u8 tp_len |
traceparent | payload`` where ``length`` covers everything after itself
and ``tp_len`` (0 = untraced) carries an optional W3C traceparent — the
trace-context hop that makes a worker's request and the primary's fused
dispatch ONE trace (the replication ``Message.tp`` pattern, PR 5): the
broker handler continues the worker's trace id, so QueryBatcher
queue-wait and fused-batch spans attribute to the worker's caller and
``/admin/traces/<id>`` renders one cross-process span tree. Responses
echo the request id with ``msg_type | 0x80``. Response payloads begin
with a status byte: ``0`` OK, ``1`` RESOURCE_EXHAUSTED, ``2`` DEGRADED,
``3`` ERROR; non-OK payloads carry ``u32 len | utf-8 message``.

SEARCH (0x01): ``u8 dtype (0=f32, 1=int8) | u8 flags (bit0: with_content)
| u32 B | u32 D | u32 k | f32 min_similarity | data`` — data is ``B*D``
f32, or ``B*D`` int8 followed by ``B`` f32 scales (codes/scale, the
quantize_rows convention). OK payload: ``u32 B`` then per query
``u32 n`` of ``f32 score | u16 id_len | id | u32 content_len | content``
(content_len is 0 unless with_content).

EMBED (0x02): ``u32 n | n × (u32 len | utf-8 text)``. OK payload:
``u32 B | u32 D | B*D f32``.

STATUS (0x03): empty. OK payload: ``u32 len | JSON`` (backend state,
corpus size, broker counters) — diagnostics only, never the hot path.
"""

from __future__ import annotations

import json
import logging
import os
import socket
import struct
import tempfile
import threading
import time
import weakref
from typing import Any, Optional

import numpy as np

from nornicdb_tpu.errors import NotFoundError, ResourceExhausted
from nornicdb_tpu.telemetry.metrics import REGISTRY as _REGISTRY
from nornicdb_tpu.telemetry.tracing import tracer as _tracer

log = logging.getLogger(__name__)

# message types
MSG_SEARCH = 0x01
MSG_EMBED = 0x02
MSG_STATUS = 0x03
# finished-trace shipment (fleet telemetry plane): a worker whose traced
# request crossed the broker ships its completed span records back so the
# primary's /admin/traces renders ONE tree spanning both processes.
# Payload: u32 len | JSON {trace_id, root, started, duration_ms, proc,
# spans: [...]}. OK payload: empty.
MSG_SPANS = 0x05
# Qdrant collection search (ROADMAP 1b): the points/search surface takes
# raw vectors, so workers ship it over the broker instead of proxying the
# whole HTTP request — the primary answers from the SHARED
# db.qdrant_registry() (the same per-collection device corpora the REST
# and gRPC transports serve). Payload: u8 flags (bit0 with_payload) |
# u32 limit | f32 score_threshold | u16 coll_len | coll utf-8 | u32 D |
# D f32 vector. OK payload: u32 len | JSON hits (registry.search output
# verbatim, so worker responses are body-identical to the primary's).
MSG_QDRANT = 0x04
RESP = 0x80

# response statuses
OK = 0
STATUS_RESOURCE_EXHAUSTED = 1
STATUS_DEGRADED = 2
STATUS_ERROR = 3

_REQUESTS = _REGISTRY.counter(
    "nornicdb_broker_requests_total",
    "Device-broker requests by operation and outcome",
    labels=("op", "outcome"),
)
for _op in ("search", "embed", "status", "qdrant"):
    for _out in ("ok", "shed", "degraded", "error"):
        _REQUESTS.labels(_op, _out)
_REQ_HIST = _REGISTRY.histogram(
    "nornicdb_broker_request_seconds",
    "Device-broker request service time by operation",
    labels=("op",),
)
_REQ_HIST.labels("search")
_REQ_HIST.labels("embed")
_REQ_HIST.labels("qdrant")
_CONNECTIONS = _REGISTRY.gauge(
    "nornicdb_broker_connections",
    "Worker connections currently attached to the device broker",
)
_QUERIES = _REGISTRY.counter(
    "nornicdb_broker_queries_total",
    "Individual search queries received by the broker (fused downstream "
    "by the QueryBatcher)",
)
_BYTES = _REGISTRY.counter(
    "nornicdb_broker_bytes_total",
    "Bytes moved across the broker socket",
    labels=("direction",),
)
_BYTES.labels("rx")
_BYTES.labels("tx")


class BrokerError(RuntimeError):
    """Broker replied with a protocol/server error."""


class BrokerUnavailable(BrokerError):
    """The broker socket is gone (primary down, not yet started, or the
    connection died twice) — workers fall back to the shared-memory host
    search, then to plain proxying."""


class BrokerDegraded(BrokerError):
    """The broker answered DEGRADED: the backend is serving from host
    arrays, so the worker should serve its own shared-memory host search
    instead of a pointless socket round-trip per query."""


# -- framing helpers ---------------------------------------------------------
def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("broker peer closed")
        buf += chunk
    return bytes(buf)


def _read_frame(sock: socket.socket) -> tuple[int, int, str, bytes]:
    head = _recv_exact(sock, 4)
    (length,) = struct.unpack("<I", head)
    if length < 10 or length > (1 << 30):
        raise ConnectionError(f"bad frame length {length}")
    body = _recv_exact(sock, length)
    mtype = body[0]
    (req_id,) = struct.unpack_from("<Q", body, 1)
    tp_len = body[9]
    if 10 + tp_len > length:
        raise ConnectionError(f"bad traceparent length {tp_len}")
    tp = body[10:10 + tp_len].decode("ascii", "replace") if tp_len else ""
    return mtype, req_id, tp, body[10 + tp_len:]


def _send_frame(sock: socket.socket, mtype: int, req_id: int,
                payload: bytes, traceparent: str = "") -> int:
    tp = traceparent.encode("ascii", "replace")[:255]
    frame = struct.pack("<IBQB", 10 + len(tp) + len(payload), mtype,
                        req_id, len(tp)) + tp + payload
    sock.sendall(frame)
    return len(frame)


def _status_payload(status: int, message: str) -> bytes:
    msg = message.encode()[:4096]
    return bytes([status]) + struct.pack("<I", len(msg)) + msg


def encode_search_request(
    queries: np.ndarray, k: int, min_similarity: float,
    with_content: bool = False,
    scales: Optional[np.ndarray] = None,
) -> bytes:
    """f32 block, or int8 codes + per-row scales when ``scales`` given."""
    q = np.ascontiguousarray(np.atleast_2d(queries))
    b, d = q.shape
    if scales is not None:
        codes = q.astype(np.int8, copy=False)
        body = codes.tobytes() + np.ascontiguousarray(
            scales, np.float32
        ).tobytes()
        dtype = 1
    else:
        body = q.astype(np.float32, copy=False).tobytes()
        dtype = 0
    flags = 1 if with_content else 0
    return struct.pack("<BBIIIf", dtype, flags, b, d, k,
                       float(min_similarity)) + body


def decode_search_request(
    payload: bytes,
) -> tuple[np.ndarray, int, float, bool]:
    dtype, flags, b, d, k, min_sim = struct.unpack_from("<BBIIIf", payload)
    off = struct.calcsize("<BBIIIf")
    if dtype == 0:
        q = np.frombuffer(payload, np.float32, b * d, off).reshape(b, d)
    elif dtype == 1:
        codes = np.frombuffer(payload, np.int8, b * d, off).reshape(b, d)
        scales = np.frombuffer(payload, np.float32, b, off + b * d)
        # codes/scale is the quantize_rows convention: x ~= int8 / scale
        q = codes.astype(np.float32) / np.maximum(scales, 1e-9)[:, None]
    else:
        raise ValueError(f"unknown query dtype {dtype}")
    return q, int(k), float(min_sim), bool(flags & 1)


def encode_search_response(
    results: list[list[tuple]], with_content: bool,
) -> bytes:
    out = bytearray([OK])
    out += struct.pack("<I", len(results))
    for row in results:
        out += struct.pack("<I", len(row))
        for hit in row:
            id_b = hit[0].encode()
            content_b = (hit[2].encode() if with_content and len(hit) > 2
                         else b"")
            out += struct.pack("<fH", float(hit[1]), len(id_b))
            out += id_b
            out += struct.pack("<I", len(content_b))
            out += content_b
    return bytes(out)


def decode_search_response(payload: bytes) -> list[list[tuple]]:
    (b,) = struct.unpack_from("<I", payload, 0)
    off = 4
    out: list[list[tuple]] = []
    for _ in range(b):
        (n,) = struct.unpack_from("<I", payload, off)
        off += 4
        row = []
        for _ in range(n):
            score, id_len = struct.unpack_from("<fH", payload, off)
            off += 6
            id_ = payload[off:off + id_len].decode()
            off += id_len
            (c_len,) = struct.unpack_from("<I", payload, off)
            off += 4
            content = payload[off:off + c_len].decode()
            off += c_len
            row.append((id_, score, content))
        out.append(row)
    return out


def encode_qdrant_request(
    collection: str, vector: np.ndarray, limit: int,
    score_threshold: float, with_payload: bool,
) -> bytes:
    coll = collection.encode()
    vec = np.ascontiguousarray(np.asarray(vector, np.float32).reshape(-1))
    return (
        struct.pack("<BIfH", 1 if with_payload else 0, int(limit),
                    float(score_threshold), len(coll))
        + coll
        + struct.pack("<I", vec.shape[0])
        + vec.tobytes()
    )


def decode_qdrant_request(
    payload: bytes,
) -> tuple[str, np.ndarray, int, float, bool]:
    flags, limit, thresh, coll_len = struct.unpack_from("<BIfH", payload)
    off = struct.calcsize("<BIfH")
    coll = payload[off:off + coll_len].decode()
    off += coll_len
    (d,) = struct.unpack_from("<I", payload, off)
    off += 4
    vec = np.frombuffer(payload, np.float32, d, off)
    return coll, vec, int(limit), float(thresh), bool(flags & 1)


def encode_embed_request(texts: list[str]) -> bytes:
    out = bytearray(struct.pack("<I", len(texts)))
    for t in texts:
        b = t.encode()
        out += struct.pack("<I", len(b))
        out += b
    return bytes(out)


def decode_embed_request(payload: bytes) -> list[str]:
    (n,) = struct.unpack_from("<I", payload, 0)
    off = 4
    texts = []
    for _ in range(n):
        (ln,) = struct.unpack_from("<I", payload, off)
        off += 4
        texts.append(payload[off:off + ln].decode())
        off += ln
    return texts


# -- the broker (primary side) -----------------------------------------------
_ACTIVE: "list[weakref.ref]" = []
_ACTIVE_LOCK = threading.Lock()


def active_broker_stats() -> list[dict]:
    """Stats of every live broker (the /admin/stats "broker" section)."""
    out = []
    with _ACTIVE_LOCK:
        refs = list(_ACTIVE)
    for ref in refs:
        b = ref()
        if b is not None:
            out.append(b.stats())
    return out


class DeviceBroker:
    """The per-host device owner's request plane.

    One listener thread accepts worker connections; one thread per
    connection decodes frames and submits work into the fused-dispatch
    paths. Per-connection threads are correct here because a pool has a
    handful of workers with a handful of connections each — the fan-in
    point is the QueryBatcher, not the socket layer."""

    def __init__(self, db, path: Optional[str] = None):
        self.db = db
        self._own_dir: Optional[str] = None
        if path is None:
            self._own_dir = tempfile.mkdtemp(prefix="nornic-broker-")
            path = os.path.join(self._own_dir, "broker.sock")
        self.path = path
        self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            os.unlink(path)
        except OSError:
            pass  # fresh path
        self._sock.bind(path)
        self._sock.listen(64)
        self._stop = threading.Event()
        self._conns: set[socket.socket] = set()
        self._lock = threading.Lock()
        self.counters = {
            "search_ok": 0, "search_shed": 0, "search_degraded": 0,
            "search_error": 0, "embed_ok": 0, "embed_shed": 0,
            "embed_error": 0, "qdrant_ok": 0, "qdrant_shed": 0,
            "qdrant_error": 0,
            "status": 0, "queries": 0, "connections": 0,
        }
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="nornicdb-broker-accept",
            daemon=True,
        )
        self._accept_thread.start()
        with _ACTIVE_LOCK:
            _ACTIVE[:] = [r for r in _ACTIVE if r() is not None]
            _ACTIVE.append(weakref.ref(self))

    # -- accept / serve ------------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return  # listener closed
            with self._lock:
                self._conns.add(conn)
                self.counters["connections"] += 1
            _CONNECTIONS.set(float(len(self._conns)))
            threading.Thread(
                target=self._serve_conn, args=(conn,),
                name="nornicdb-broker-conn", daemon=True,
            ).start()

    # nornlint: thread-role=serve-loop
    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            while not self._stop.is_set():
                try:
                    mtype, req_id, tp, payload = _read_frame(conn)
                except (ConnectionError, OSError):
                    return
                # 4B length + 1B type + 8B req id + 1B tp_len + tp
                # (ascii: chars == bytes) + payload
                _BYTES.labels("rx").inc(14 + len(tp) + len(payload))
                resp = self._dispatch(mtype, payload, tp)
                try:
                    n = _send_frame(conn, mtype | RESP, req_id, resp)
                except OSError:
                    return
                _BYTES.labels("tx").inc(n)
        finally:
            with self._lock:
                self._conns.discard(conn)
            _CONNECTIONS.set(float(len(self._conns)))
            try:
                conn.close()
            except OSError:
                pass  # peer already gone

    def _dispatch(self, mtype: int, payload: bytes,
                  traceparent: str = "") -> bytes:
        if mtype == MSG_SEARCH:
            # continue the WORKER's trace: the root span's parent is the
            # worker-side span that sent the frame, so the shipped-back
            # worker spans and this handler's spans (queue-wait, fused
            # batch) render as one tree at /admin/traces/<id>
            with _tracer.start_trace(
                "broker.search", traceparent=traceparent or None,
            ):
                return self._handle_search(payload)
        if mtype == MSG_EMBED:
            with _tracer.start_trace(
                "broker.embed", traceparent=traceparent or None,
            ):
                return self._handle_embed(payload)
        if mtype == MSG_QDRANT:
            with _tracer.start_trace(
                "broker.qdrant", traceparent=traceparent or None,
            ):
                return self._handle_qdrant(payload)
        if mtype == MSG_SPANS:
            return self._handle_spans(payload)
        if mtype == MSG_STATUS:
            self.counters["status"] += 1
            _REQUESTS.labels("status", "ok").inc()
            blob = json.dumps(self.status_snapshot()).encode()
            return bytes([OK]) + struct.pack("<I", len(blob)) + blob
        return _status_payload(STATUS_ERROR, f"unknown message {mtype}")

    def _handle_spans(self, payload: bytes) -> bytes:
        """Merge a worker's finished-trace span records into the local
        ring (telemetry.tracing.Tracer.merge_remote) — best-effort: a
        malformed shipment is an error reply, never a crash."""
        try:
            (ln,) = struct.unpack_from("<I", payload, 0)
            data = json.loads(payload[4:4 + ln].decode())
            merged = _tracer.merge_remote(
                str(data.get("trace_id") or ""),
                data.get("spans") or [],
                root=data.get("root"),
                started=data.get("started"),
                duration_ms=data.get("duration_ms"),
                proc=data.get("proc"),
            )
        except Exception as e:
            self.counters["spans_error"] = (
                self.counters.get("spans_error", 0) + 1
            )
            return _status_payload(STATUS_ERROR, f"bad spans frame: {e}")
        self.counters["spans_merged"] = (
            self.counters.get("spans_merged", 0) + (1 if merged else 0)
        )
        return bytes([OK])

    # -- handlers ------------------------------------------------------------
    def _handle_search(self, payload: bytes) -> bytes:
        t0 = time.perf_counter()
        try:
            q, k, min_sim, with_content = decode_search_request(payload)
        except Exception as e:
            self.counters["search_error"] += 1
            _REQUESTS.labels("search", "error").inc()
            return _status_payload(STATUS_ERROR, f"bad search frame: {e}")
        self.counters["queries"] += q.shape[0]
        _QUERIES.inc(q.shape[0])
        service = self.db.search
        corpus = service.corpus()
        if corpus is None:
            # nothing indexed yet: every query legitimately matches nothing
            self.counters["search_ok"] += 1
            _REQUESTS.labels("search", "ok").inc()
            return encode_search_response(
                [[] for _ in range(q.shape[0])], with_content
            )
        if q.shape[1] != corpus.dims:
            # reject BEFORE submit: a wrong-dim block fused into the shared
            # batch would error the np.stack and fan the failure out to
            # every other worker's queries in the same window
            self.counters["search_error"] += 1
            _REQUESTS.labels("search", "error").inc()
            return _status_payload(
                STATUS_ERROR,
                f"query dims {q.shape[1]} != corpus dims {corpus.dims}",
            )
        mgr = corpus._backend_mgr()
        if mgr.state in ("DEGRADED_CPU", "RECOVERING"):
            # tell the worker to serve its shared-memory host fallback
            # locally — same host arrays, no socket hop per query
            self.counters["search_degraded"] += 1
            _REQUESTS.labels("search", "degraded").inc()
            return _status_payload(
                STATUS_DEGRADED, f"backend {mgr.state}"
            )
        batcher = service.ensure_batcher()
        tickets: list = []
        try:
            # submit the whole block THEN wait: tickets from this worker,
            # other workers, and the primary's own callers coalesce into
            # the same scan (what queues while one is in flight goes as
            # the next) — the fused-dispatch invariant the multiproc
            # bench asserts
            for i in range(q.shape[0]):
                tickets.append(batcher.submit(q[i], k, min_sim))
            results = [batcher.wait(t) for t in tickets]
        except ResourceExhausted as e:
            batcher.withdraw(tickets)
            self.counters["search_shed"] += 1
            _REQUESTS.labels("search", "shed").inc()
            return _status_payload(STATUS_RESOURCE_EXHAUSTED, str(e))
        except Exception as e:
            batcher.withdraw(tickets)
            self.counters["search_error"] += 1
            _REQUESTS.labels("search", "error").inc()
            log.exception("broker search failed")
            return _status_payload(STATUS_ERROR, f"search failed: {e}")
        if with_content:
            results = [
                [(id_, score, self._content(id_)) for id_, score in row]
                for row in results
            ]
        self.counters["search_ok"] += 1
        _REQUESTS.labels("search", "ok").inc()
        _REQ_HIST.labels("search").observe(time.perf_counter() - t0)
        return encode_search_response(results, with_content)

    def _handle_qdrant(self, payload: bytes) -> bytes:
        """Worker-shipped Qdrant points/search: answer from the SHARED
        collection registry (db.qdrant_registry()), whose per-collection
        DeviceCorpus dispatch is the same fused device path the REST/gRPC
        transports serve — so worker hits are id/score/payload-identical
        to the primary's by construction. A degraded backend needs no
        redirect here: collection corpora serve their exact host fallback
        internally, and workers hold no shared-memory mirror of
        collection corpora (only the default search corpus rides the shm
        plane today — ROADMAP 1b residual)."""
        t0 = time.perf_counter()
        try:
            coll, vec, limit, thresh, with_payload = decode_qdrant_request(
                payload
            )
        except Exception as e:
            self.counters["qdrant_error"] += 1
            _REQUESTS.labels("qdrant", "error").inc()
            return _status_payload(STATUS_ERROR, f"bad qdrant frame: {e}")
        registry_fn = getattr(self.db, "qdrant_registry", None)
        if not callable(registry_fn):
            self.counters["qdrant_error"] += 1
            _REQUESTS.labels("qdrant", "error").inc()
            return _status_payload(STATUS_ERROR, "no qdrant registry")
        self.counters["queries"] += 1
        _QUERIES.inc()
        try:
            hits = registry_fn().search(
                coll, vec, limit=limit, score_threshold=thresh,
                with_payload=with_payload,
            )
        except ResourceExhausted as e:
            # backpressure, not failure: the worker surfaces 429 +
            # Retry-After instead of proxying onto the overloaded primary
            self.counters["qdrant_shed"] = (
                self.counters.get("qdrant_shed", 0) + 1
            )
            _REQUESTS.labels("qdrant", "shed").inc()
            return _status_payload(STATUS_RESOURCE_EXHAUSTED, str(e))
        except NotFoundError as e:
            # unknown collection: a real error reply, not a proxy fallback
            # (the primary would 404 the same request)
            self.counters["qdrant_error"] += 1
            _REQUESTS.labels("qdrant", "error").inc()
            return _status_payload(STATUS_ERROR, str(e))
        except Exception as e:
            self.counters["qdrant_error"] += 1
            _REQUESTS.labels("qdrant", "error").inc()
            log.exception("broker qdrant search failed")
            return _status_payload(STATUS_ERROR, f"qdrant search failed: {e}")
        blob = json.dumps(hits).encode()
        self.counters["qdrant_ok"] += 1
        _REQUESTS.labels("qdrant", "ok").inc()
        _REQ_HIST.labels("qdrant").observe(time.perf_counter() - t0)
        return bytes([OK]) + struct.pack("<I", len(blob)) + blob

    def _content(self, node_id: str) -> str:
        try:
            node = self.db.storage.get_node(node_id)
        except NotFoundError:
            return ""  # hit evicted between search and fetch
        return str(node.properties.get("content", ""))

    def _handle_embed(self, payload: bytes) -> bytes:
        t0 = time.perf_counter()
        try:
            texts = decode_embed_request(payload)
        except Exception as e:
            self.counters["embed_error"] += 1
            _REQUESTS.labels("embed", "error").inc()
            return _status_payload(STATUS_ERROR, f"bad embed frame: {e}")
        embedder = self.db.embedder
        if embedder is None:
            self.counters["embed_error"] += 1
            _REQUESTS.labels("embed", "error").inc()
            return _status_payload(STATUS_ERROR, "no embedder configured")
        try:
            vecs = embedder.embed_batch(texts)
        except ResourceExhausted as e:
            self.counters["embed_shed"] += 1
            _REQUESTS.labels("embed", "shed").inc()
            return _status_payload(STATUS_RESOURCE_EXHAUSTED, str(e))
        except Exception as e:
            self.counters["embed_error"] += 1
            _REQUESTS.labels("embed", "error").inc()
            log.exception("broker embed failed")
            return _status_payload(STATUS_ERROR, f"embed failed: {e}")
        block = np.ascontiguousarray(np.stack(vecs), np.float32) if vecs \
            else np.zeros((0, 0), np.float32)
        self.counters["embed_ok"] += 1
        _REQUESTS.labels("embed", "ok").inc()
        _REQ_HIST.labels("embed").observe(time.perf_counter() - t0)
        return (bytes([OK])
                + struct.pack("<II", block.shape[0],
                              block.shape[1] if block.ndim > 1 else 0)
                + block.tobytes())

    # -- observability -------------------------------------------------------
    def status_snapshot(self) -> dict[str, Any]:
        service = self.db.search
        corpus = service.corpus()
        mgr_state = None
        if corpus is not None:
            mgr_state = corpus._backend_mgr().state
        out: dict[str, Any] = {
            "backend_state": mgr_state,
            "corpus_rows": len(corpus) if corpus is not None else 0,
            "counters": dict(self.counters),
        }
        batcher = getattr(service, "_batcher", None)
        if batcher is not None:
            out["batcher"] = batcher.stats.as_dict()
        return out

    def stats(self) -> dict[str, Any]:
        with self._lock:
            conns = len(self._conns)
        return {
            "path": self.path,
            "connections": conns,
            "counters": dict(self.counters),
        }

    def stop(self) -> None:
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass  # already closed
        with self._lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass  # peer already gone
            try:
                c.close()
            except OSError:
                pass
        try:
            os.unlink(self.path)
        except OSError:
            pass  # never created / already removed
        if self._own_dir is not None:
            import shutil

            shutil.rmtree(self._own_dir, ignore_errors=True)


# -- the client (worker side) ------------------------------------------------
class BrokerClient:
    """Worker-side broker connection: one socket per calling thread
    (keep-alive, lazily connected, one reconnect attempt per call)."""

    def __init__(self, path: str, timeout: float = 30.0):
        self.path = path
        self.timeout = timeout
        self._local = threading.local()
        self._req_id = 0
        self._id_lock = threading.Lock()

    def _next_id(self) -> int:
        with self._id_lock:
            self._req_id += 1
            return self._req_id

    def _conn(self) -> socket.socket:
        sock = getattr(self._local, "sock", None)
        if sock is None:
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.settimeout(self.timeout)
            sock.connect(self.path)
            self._local.sock = sock
        return sock

    def _drop(self) -> None:
        sock = getattr(self._local, "sock", None)
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass  # already dead
            self._local.sock = None

    def _call(self, mtype: int, payload: bytes) -> bytes:
        req_id = self._next_id()
        # the caller's active span (if any) rides the frame header, so
        # the primary-side handler continues the SAME trace id
        tp = _tracer.current_traceparent() or ""
        for attempt in (0, 1):
            try:
                sock = self._conn()
                _send_frame(sock, mtype, req_id, payload, tp)
                rtype, rid, _tp, body = _read_frame(sock)
                if rtype != (mtype | RESP) or rid != req_id:
                    raise ConnectionError(
                        f"broker protocol desync (type {rtype}, id {rid})"
                    )
                return body
            except (ConnectionError, OSError) as e:
                self._drop()
                if attempt:
                    raise BrokerUnavailable(
                        f"broker at {self.path}: {e}"
                    ) from e
        raise BrokerUnavailable(self.path)  # unreachable

    @staticmethod
    def _check(body: bytes) -> bytes:
        status = body[0]
        if status == OK:
            return body[1:]
        (ln,) = struct.unpack_from("<I", body, 1)
        msg = body[5:5 + ln].decode()
        if status == STATUS_RESOURCE_EXHAUSTED:
            raise ResourceExhausted(msg, reason="broker")
        if status == STATUS_DEGRADED:
            raise BrokerDegraded(msg)
        raise BrokerError(msg)

    def search(
        self, queries: np.ndarray, k: int, min_similarity: float = -1.0,
        with_content: bool = False,
    ) -> list[list[tuple]]:
        """Per-query [(id, score, content)] — content "" unless requested."""
        body = self._call(
            MSG_SEARCH,
            encode_search_request(queries, k, min_similarity, with_content),
        )
        return decode_search_response(self._check(body))

    def qdrant_search(
        self, collection: str, vector, limit: int = 10,
        score_threshold: float = -1.0, with_payload: bool = True,
    ) -> list[dict]:
        """Qdrant points/search via the broker: returns the registry's
        hit dicts ({"id", "score", "version"[, "payload"]}) verbatim."""
        body = self._check(self._call(
            MSG_QDRANT,
            encode_qdrant_request(collection, np.asarray(vector, np.float32),
                                  limit, score_threshold, with_payload),
        ))
        (ln,) = struct.unpack_from("<I", body, 0)
        return json.loads(body[4:4 + ln].decode())

    def embed(self, texts: list[str]) -> np.ndarray:
        body = self._check(self._call(MSG_EMBED,
                                      encode_embed_request(texts)))
        b, d = struct.unpack_from("<II", body, 0)
        return np.frombuffer(body, np.float32, b * d, 8).reshape(b, d)

    def status(self) -> dict[str, Any]:
        body = self._check(self._call(MSG_STATUS, b""))
        (ln,) = struct.unpack_from("<I", body, 0)
        return json.loads(body[4:4 + ln].decode())

    def ship_spans(self, entry: dict, proc: str) -> None:
        """Ship a finished trace's span records to the primary so its
        /admin/traces renders one cross-process tree. Best-effort:
        failure to ship must never fail the request that produced the
        trace."""
        blob = json.dumps({
            "trace_id": entry.get("trace_id"),
            "root": entry.get("root"),
            "started": entry.get("started"),
            "duration_ms": entry.get("duration_ms"),
            "proc": proc,
            "spans": entry.get("spans") or [],
        }).encode()
        try:
            self._check(self._call(
                MSG_SPANS, struct.pack("<I", len(blob)) + blob,
            ))
        except (BrokerError, OSError) as e:
            log.debug("trace shipment failed: %s", e)

    def close(self) -> None:
        self._drop()
