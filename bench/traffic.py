"""The one traffic generator: every cell's requests come from here.

A cell's file (``bench/workloads/<cell>.json``) is data: the op, the number
of clients, and the op's parameters (``k``, ``dims``, length ``bands``, a
chat's ``system_tokens`` / ``user_tokens``).  The
generator turns ``(cell, seed, client, index)`` into the same request in any
process, so the load generator (a child that never imports JAX) and the
comparison in the serving process agree on what was sent without passing
the requests between them.

Every seed gives the SAME multiset of lengths, in another order: a seed must
move the order of the work, not its amount.
"""

from __future__ import annotations

import numpy as np

VOCAB_WORDS = 4096
LENGTH_CYCLE = 200  # lengths repeat with this period, permuted by the seed


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed (seeds pass 2**31)."""
    return np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, *[int(s) for s in stream]])


def unit_rows(rng: np.random.Generator, n: int, dims: int) -> np.ndarray:
    x = rng.standard_normal((n, dims), dtype=np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x


def band_lengths(bands: list[dict], cycle: int = LENGTH_CYCLE) -> np.ndarray:
    """The fixed multiset of lengths one cycle holds: each band gets its
    share of the cycle, spread evenly from ``lo`` to ``hi`` words."""
    out = []
    for i, band in enumerate(bands):
        last = i == len(bands) - 1
        n = cycle - len(out) if last else int(round(band["share"] * cycle))
        out.extend(np.linspace(band["lo"], band["hi"], n).round().astype(int))
    return np.asarray(out[:cycle], np.int64)


def text_of(rng: np.random.Generator, n_words: int) -> str:
    return " ".join(f"w{j:04d}" for j in rng.integers(0, VOCAB_WORDS, n_words))


def vector_wire(vec: np.ndarray) -> list[float]:
    """A query vector as it goes on the wire: 7 decimals, so the body is
    half the size of a float32's shortest repr and parses back to the same
    float32 on both sides."""
    return np.round(vec.astype(np.float64), 7).tolist()


def round_lengths(lo: int, hi: int, clients: int) -> np.ndarray:
    """One round's lengths: ``clients`` values spread evenly from ``lo`` to
    ``hi``.  Each round deals them to the clients in an order drawn from the
    seed, so every round of every seed holds the same work."""
    return np.linspace(lo, hi, clients).round().astype(np.int64)


class Stream:
    """The requests of one client of one cell, by index."""

    def __init__(self, cell: dict, seed: int, client: int):
        self.params = cell["params"]
        self.kind = cell["request"]  # "vector" | "text" | "chat"
        self.seed, self.client, self.clients = seed, client, cell["clients"]
        if self.kind == "text":
            base = band_lengths(self.params["bands"])
            self._lengths = rng_for(seed, 2, client).permutation(base)
        elif self.kind == "chat":
            # the run's one system message: the same words in every request
            self._system = text_of(rng_for(seed, 7),
                                   self.params["system_tokens"])
        elif self.kind != "vector":
            raise ValueError(f"request kind {self.kind!r}: vector, text, chat")

    def request(self, index: int):
        rng = rng_for(self.seed, 3, self.client, index)
        if self.kind == "vector":
            wire = vector_wire(unit_rows(rng, 1, self.params["dims"])[0])
            return np.asarray(wire, np.float32)
        if self.kind == "chat":
            u = self.params["user_tokens"]
            deal = rng_for(self.seed, 2, index).permutation(
                round_lengths(u["lo"], u["hi"], self.clients))
            return {"system": self._system,
                    "user": text_of(rng, int(deal[self.client % len(deal)]))}
        return text_of(rng, int(self._lengths[index % len(self._lengths)]))
