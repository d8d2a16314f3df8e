"""Embedder interfaces and implementations.

Behavioral reference: /root/reference/pkg/embed/embed.go:71 (Embedder:
Embed/EmbedBatch/Dimensions/Model), local_gguf.go (GGUF embedder with crash
recovery), cached_embedder.go:41 (LRU by content hash).

The production embedder here is TPUEmbedder (bge-m3 forward pass on TPU,
replacing the reference's llama.cpp CGO path); HashEmbedder is the
deterministic no-model fallback used by tests and headless deployments.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Optional, Sequence

import numpy as np

from nornicdb_tpu.telemetry.tracing import tracer as _tracer


class Embedder:
    """(ref: embed.Embedder pkg/embed/embed.go:71)"""

    def embed(self, text: str) -> np.ndarray:
        return self.embed_batch([text])[0]

    def embed_batch(self, texts: Sequence[str]) -> list[np.ndarray]:
        raise NotImplementedError

    def dimensions(self) -> int:
        raise NotImplementedError

    def model(self) -> str:
        raise NotImplementedError


class HashEmbedder(Embedder):
    """Deterministic embedding from token hashes: bag-of-hashed-words vectors,
    L2-normalized. Same text -> same vector across processes; similar word
    sets -> high cosine. Replaces the reference's test stubs
    (pkg/localllm/llama_stub.go) with something semantically useful."""

    def __init__(self, dims: int = 256):
        self._dims = dims

    def _word_vec(self, word: str) -> np.ndarray:
        h = hashlib.blake2s(word.lower().encode()).digest()
        seed = int.from_bytes(h[:8], "little") % (2**32)
        rng = np.random.default_rng(seed)
        return rng.standard_normal(self._dims).astype(np.float32)

    def embed_batch(self, texts: Sequence[str]) -> list[np.ndarray]:
        out = []
        for t in texts:
            words = t.split()
            if not words:
                out.append(np.zeros(self._dims, np.float32))
                continue
            v = np.sum([self._word_vec(w) for w in words], axis=0)
            n = np.linalg.norm(v)
            out.append((v / n if n > 1e-12 else v).astype(np.float32))
        return out

    def dimensions(self) -> int:
        return self._dims

    def model(self) -> str:
        return "hash-embedder"


class TPUEmbedder(Embedder):
    """bge-m3 architecture encoder on TPU (replaces pkg/embed/local_gguf.go +
    pkg/localllm llama.cpp path).

    Batching policy: the encoder is under-occupied at small batches (by how
    much on the chip: not measured on today's code), so texts are tokenized
    without padding, grouped into power-of-two sequence-length buckets, and run in
    chunks of `opt_batch` per bucket. Both dims pad to a fixed shape grid,
    so the jit cache stays bounded (len buckets x batch classes) instead of
    recompiling per distinct batch length."""

    _LEN_BUCKETS = (32, 64, 128, 256, 512)

    def __init__(
        self,
        cfg=None,
        params=None,
        tokenizer=None,
        max_len: int = 512,
        seed: int = 0,
        opt_batch: int = 32,
        backend=None,
    ):
        import jax

        from nornicdb_tpu.models import bge_m3
        from nornicdb_tpu.models.tokenizer import HashTokenizer

        # device lifecycle manager: parameter init is a cold first-touch,
        # and every forward gates through it — while DEGRADED_CPU the
        # encoder keeps serving on the JAX CPU backend (the reference's
        # device-failure CPU retry, local_gguf.go:202-294)
        from nornicdb_tpu import backend as _backend_mod

        self._backend = backend if backend is not None else _backend_mod.manager()
        self.cfg = cfg if cfg is not None else bge_m3.BGE_SMALL
        with self._device_scope():
            self.params = (
                params
                if params is not None
                else bge_m3.init_params(self.cfg, jax.random.PRNGKey(seed))
            )
        self.tokenizer = tokenizer or HashTokenizer(self.cfg.vocab_size)
        self.max_len = max_len
        self.opt_batch = max(1, opt_batch)
        self._fwd = jax.jit(
            lambda p, ids, mask: bge_m3.forward(p, self.cfg, ids, mask)
        )
        # ragged token-packed forward (serving engine path): one program
        # per (R, C, S_cap) shape class — the scheduler quantizes packs to
        # a bounded class grid, so this cache stays small (NL-JAX03)
        self._fwd_packed = jax.jit(
            lambda p, ids, seg, pos, cr, cc: bge_m3.forward_packed(
                p, self.cfg, ids, seg, pos, cr, cc
            )
        )
        # shape classes the packed program compiled for (the bench's
        # one-program-per-packed-batch invariant reads this)
        self.packed_shapes: set[tuple[int, int, int]] = set()
        # host mirror of the weights, captured while the device is still
        # reachable: jax.default_device(cpu) does NOT relocate params
        # committed to a dead accelerator, so a real device loss needs a
        # host-side copy to serve from (WindVE-style host staging; 1x
        # extra host RAM). _cpu_params materializes from it lazily on the
        # first degraded batch.
        self._host_params = jax.tree.map(np.asarray, self.params)
        self._cpu_params = None
        # recovery hook (same registry the corpora use): after a device
        # loss, self.params are committed to the DEAD device incarnation —
        # the next READY forward must re-materialize them from the mirror
        self._params_stale = False
        self._backend.register_corpus(self)
        self.stats = {
            "embedded": 0, "batches": 0, "cpu_fallback_batches": 0,
            "packed_dispatches": 0, "packed_tokens": 0,
            # host-observed seconds of the embed.dispatch stage (uploads +
            # the jitted call returning) and the embed.fetch stage (the
            # blocking read-back: device execution + D2H)
            "dispatch_seconds": 0.0, "fetch_seconds": 0.0,
        }
        # fleet telemetry: encoder parameter residency (weakref'd; summed
        # per component at /metrics render — telemetry/deviceprof.py)
        from nornicdb_tpu.telemetry import deviceprof as _deviceprof

        _deviceprof.register_hbm(self, TPUEmbedder._hbm_bytes)

    @staticmethod
    def _hbm_bytes(self) -> dict:
        import jax

        total = 0
        for leaf in jax.tree.leaves(self.params):
            size = getattr(leaf, "size", None)
            dtype = getattr(leaf, "dtype", None)
            if size is not None and dtype is not None:
                total += int(size) * dtype.itemsize
        return {"embedder_params": total}

    def _on_backend_recovered(self, mode: str) -> None:
        """Manager recovery notification: whatever device the old params
        were committed to is gone (or suspect) — re-materialize from the
        host mirror on the next READY forward."""
        self._params_stale = True

    def _on_backend_ready(self) -> None:
        pass  # re-materialization is lazy (next forward), nothing to wake

    def _serving_params(self):
        """Device-path weights; re-materialized from the host mirror after
        a recovery (a warm transfer on a freshly re-acquired backend)."""
        if self._params_stale:
            import jax
            import jax.numpy as jnp

            self.params = jax.tree.map(jnp.asarray, self._host_params)
            self._cpu_params = None
            self._params_stale = False
        return self.params

    def _device_scope(self):
        """Accelerator when the backend manager reports READY (bounded
        wait on ITS worker thread — this caller never cold-inits PJRT);
        otherwise pin to the always-available JAX CPU backend so embedding
        keeps serving while degraded.  Honors the fallback policy: under
        ``fallback="fail"`` a degraded backend raises DeviceUnavailable
        instead of silently serving from CPU."""
        import contextlib

        import jax

        self._backend.require_ready()  # raises under the "fail" policy
        if self._backend.ready():
            return contextlib.nullcontext()
        self._backend.note_fallback("embed")
        return jax.default_device(jax.local_devices(backend="cpu")[0])

    def _fallback_params(self):
        """CPU-committed weights for degraded serving, materialized from
        the host mirror (never from the possibly-dead device)."""
        import jax
        import jax.numpy as jnp

        if self._cpu_params is None:
            with jax.default_device(jax.local_devices(backend="cpu")[0]):
                self._cpu_params = jax.tree.map(jnp.asarray, self._host_params)
        return self._cpu_params

    def _bucket_len(self, n: int) -> int:
        for b in self._LEN_BUCKETS:
            if n <= b and b <= self.max_len:
                return b
        return self.max_len

    def _batch_class(self, n: int) -> int:
        b = 1
        while b < n and b < self.opt_batch:
            b *= 2
        return b

    def embed_batch(self, texts: Sequence[str]) -> list[np.ndarray]:
        import jax.numpy as jnp

        if not texts:
            return []
        seqs = [
            self.tokenizer.encode(t, max_len=self.max_len) or
            [self.tokenizer.pad_id] for t in texts
        ]
        # group by padded-length bucket, preserving input positions
        buckets: dict[int, list[int]] = {}
        for i, s in enumerate(seqs):
            buckets.setdefault(self._bucket_len(len(s)), []).append(i)
        out: list[Optional[np.ndarray]] = [None] * len(texts)
        pad_id = self.tokenizer.pad_id
        scope = self._device_scope()
        import contextlib

        degraded = not isinstance(scope, contextlib.nullcontext)
        params = self._fallback_params() if degraded else self._serving_params()
        with scope:
            for blen, positions in sorted(buckets.items()):
                for start in range(0, len(positions), self.opt_batch):
                    chunk = positions[start:start + self.opt_batch]
                    bcls = self._batch_class(len(chunk))
                    ids = np.full((bcls, blen), pad_id, np.int32)
                    mask = np.zeros((bcls, blen), np.int32)
                    for row, pos in enumerate(chunk):
                        s = seqs[pos]
                        ids[row, : len(s)] = s
                        mask[row, : len(s)] = 1
                    with _tracer.stage("embed.dispatch", self.stats,
                                       "dispatch_seconds"):
                        emb = self._fwd(
                            params, jnp.asarray(ids), jnp.asarray(mask)
                        )
                    with _tracer.stage("embed.fetch", self.stats,
                                       "fetch_seconds"):
                        emb = np.asarray(emb, np.float32)
                    for row, pos in enumerate(chunk):
                        out[pos] = emb[row]
                    self.stats["batches"] += 1
                    if degraded:
                        self.stats["cpu_fallback_batches"] += 1
        self.stats["embedded"] += len(texts)
        return out  # type: ignore[return-value]

    def embed_packed(self, packed) -> np.ndarray:
        """Embed one ragged token-packed grid (serving.PackedBatch) in a
        SINGLE device program: segment-masked attention + per-segment CLS
        pooling, numerically equivalent to the per-request path.

        Device lifecycle matches embed_batch: gated through the backend
        manager, CPU-pinned while degraded, params re-materialized from
        the host mirror after a recovery.  Returns (S_cap, dims) float32;
        callers slice the live segments via ``packed.order``."""
        import contextlib

        import jax.numpy as jnp

        scope = self._device_scope()
        degraded = not isinstance(scope, contextlib.nullcontext)
        params = self._fallback_params() if degraded else self._serving_params()
        with scope:
            with _tracer.stage("embed.dispatch", self.stats,
                               "dispatch_seconds"):
                emb = self._fwd_packed(
                    params,
                    jnp.asarray(packed.ids),
                    jnp.asarray(packed.seg),
                    jnp.asarray(packed.positions),
                    jnp.asarray(packed.cls_rows),
                    jnp.asarray(packed.cls_cols),
                )
            with _tracer.stage("embed.fetch", self.stats, "fetch_seconds"):
                emb = np.asarray(emb, np.float32)
        self.packed_shapes.add(packed.shape_class)
        self.stats["packed_dispatches"] += 1
        self.stats["packed_tokens"] += packed.tokens
        self.stats["batches"] += 1
        self.stats["embedded"] += packed.n_segments
        if degraded:
            self.stats["cpu_fallback_batches"] += 1
        return emb

    def dimensions(self) -> int:
        return self.cfg.dims

    def model(self) -> str:
        return "bge-m3-tpu"


class CachedEmbedder(Embedder):
    """LRU cache keyed by content hash (ref: CachedEmbedder
    pkg/embed/cached_embedder.go:41 — the '450,000x on hits' path)."""

    def __init__(self, inner: Embedder, capacity: int = 10000):
        self.inner = inner
        self.capacity = capacity
        self._lock = threading.Lock()
        self._cache: OrderedDict[str, np.ndarray] = OrderedDict()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def _key(text: str) -> str:
        return hashlib.sha256(text.encode()).hexdigest()

    def embed_batch(self, texts: Sequence[str]) -> list[np.ndarray]:
        # span and profiler annotation only: the layer's counters are
        # hits / misses; its self time is this span less its children
        with _tracer.stage("embed.cache"):
            out: list[Optional[np.ndarray]] = [None] * len(texts)
            miss_idx: list[int] = []
            with self._lock:
                for i, t in enumerate(texts):
                    k = self._key(t)
                    if k in self._cache:
                        self._cache.move_to_end(k)
                        out[i] = self._cache[k]
                        self.hits += 1
                    else:
                        miss_idx.append(i)
                        self.misses += 1
            if miss_idx:
                fresh = self.inner.embed_batch([texts[i] for i in miss_idx])
                with self._lock:
                    for i, v in zip(miss_idx, fresh):
                        out[i] = v
                        self._cache[self._key(texts[i])] = v
                        while len(self._cache) > self.capacity:
                            self._cache.popitem(last=False)
            return out  # type: ignore[return-value]

    def dimensions(self) -> int:
        return self.inner.dimensions()

    def model(self) -> str:
        return self.inner.model()


class OllamaEmbedder(Embedder):
    """Ollama HTTP embedder (ref: OllamaEmbedder pkg/embed/embed.go:215).

    Talks to an Ollama server's /api/embeddings endpoint. The build image is
    zero-egress, so tests exercise this against a local mock; in deployments
    point base_url at a reachable Ollama.
    """

    def __init__(self, base_url: str = "http://127.0.0.1:11434",
                 model: str = "bge-m3", dims: int = 1024, timeout: float = 30.0):
        self.base_url = base_url.rstrip("/")
        self._model = model
        self._dims = dims
        self.timeout = timeout

    def embed_batch(self, texts: Sequence[str]) -> list[np.ndarray]:
        import json
        import urllib.request

        out = []
        for text in texts:
            req = urllib.request.Request(
                f"{self.base_url}/api/embeddings",
                data=json.dumps({"model": self._model, "prompt": text}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=self.timeout) as resp:
                payload = json.loads(resp.read())
            vec = np.asarray(payload["embedding"], np.float32)
            self._dims = vec.shape[0]
            out.append(vec)
        return out

    def dimensions(self) -> int:
        return self._dims

    def model(self) -> str:
        return self._model


class OpenAIEmbedder(Embedder):
    """OpenAI-compatible HTTP embedder (ref: pkg/embed/embed.go:384).

    Works against any /v1/embeddings-compatible server (OpenAI, vLLM, TEI).
    """

    def __init__(self, base_url: str = "https://api.openai.com",
                 model: str = "text-embedding-3-small", api_key: str = "",
                 dims: int = 1536, timeout: float = 30.0):
        self.base_url = base_url.rstrip("/")
        self._model = model
        self.api_key = api_key
        self._dims = dims
        self.timeout = timeout

    def embed_batch(self, texts: Sequence[str]) -> list[np.ndarray]:
        import json
        import urllib.request

        headers = {"Content-Type": "application/json"}
        if self.api_key:
            headers["Authorization"] = f"Bearer {self.api_key}"
        req = urllib.request.Request(
            f"{self.base_url}/v1/embeddings",
            data=json.dumps({"model": self._model, "input": list(texts)}).encode(),
            headers=headers,
        )
        with urllib.request.urlopen(req, timeout=self.timeout) as resp:
            payload = json.loads(resp.read())
        rows = sorted(payload["data"], key=lambda d: d.get("index", 0))
        out = [np.asarray(d["embedding"], np.float32) for d in rows]
        if out:
            self._dims = out[0].shape[0]
        return out

    def dimensions(self) -> int:
        return self._dims

    def model(self) -> str:
        return self._model
