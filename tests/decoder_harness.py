"""What the tests of every decoder family share: a driver of the family's
``fused_step`` that packs rows as the scheduler does, and the readings the
tolerances are stated on.  A family is its module (``models/qwen2.py``,
``models/deepseek_v2.py``, ``models/longcat_flash.py``,
``models/cohere2_moe.py``, ``models/nemotron_h.py``: ``init_pages`` /
``fused_step``, and ``page_kinds`` where its layers keep more than one kind
of cache state, a STATE kind among them or not);
what judges it is the plain float32 forward of
``models/reference/<family>.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.ragged import (
    STATE,
    first_page,
    pack_ragged_meta,
    pages_for,
    round_up_pow2,
)

PAGE, WIDTH, LMAX = 16, 8, 4  # 8 pages a lane = 128 slots; 2 decode lanes


def fp8(x):
    """Round to e4m3 with one scale a tensor: the precision step below
    bfloat16, for the control that has to read outside the tolerance."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def typical(got, want) -> float:
    """Median over positions of the position's largest logit error."""
    return float(np.median(np.abs(np.asarray(got) - np.asarray(want))
                           .max(axis=-1)))


def largest(got, want) -> float:
    return float(np.abs(np.asarray(got) - np.asarray(want)).max())


def tokens(seed: int, n: int, vocab: int) -> list[int]:
    return np.random.default_rng(seed).integers(4, vocab, n).tolist()


def with_norm_scales(params, seed: int):
    """``params`` with every norm's ``scale`` drawn around 1 (spread 0.1),
    so that a norm left out of a forward shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def walk(tree):
        if isinstance(tree, dict):
            return {k: (1.0 + 0.1 * jax.random.normal(next(keys), v.shape)
                        if k == "scale" else walk(v))
                    for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v) for v in tree]
        return tree

    return walk(params)


def blank_step(lmax: int, w: int, f: int):
    """``pack_ragged_meta`` with every row a padding row, every table null:
    (meta, views)."""
    meta, views = pack_ragged_meta(lmax, w, f)
    toks, lane, lpos, pos, rows, tables = views
    toks[:], lane[:], lpos[:], pos[:] = 0, lmax - 1, 0, -1
    rows[:] = 0
    for part in (tables if isinstance(tables, tuple) else [(tables,)]):
        for view in part:  # a kind's bases and its tables, or the table
            view[:] = 0
    return meta, views


def table_of(*pages, width: int = WIDTH):
    table = np.zeros(width, np.int32)
    table[:len(pages)] = pages
    return table


def reference_rows(forward, params, cfg, ids, out, **kw):
    """The reference's logits at every position that produced a token of
    ``out`` after the prompt ``ids``."""
    logits = np.asarray(forward(params, cfg, ids + out[:-1], **kw))
    return logits[len(ids) - 1:]


def greedy_gap(forward, params, cfg, ids, out) -> float:
    """How far under the reference's best logit the served tokens' reference
    logits lie, at worst: the benchmark's ``greedy_gap``, and what the chip
    is held to.  0.0 where every served token is the reference's argmax."""
    logits = reference_rows(forward, params, cfg, list(ids), list(out))
    served = logits[np.arange(len(out)), out]
    return float((logits.max(-1) - served).max())


class Lane:
    """One lane's pages of a family with page kinds, as the scheduler keeps
    them: a table a kind that starts at the first page its window still
    reaches.  Handed to :meth:`Pool.step` where a one-kind family hands a
    table; before a step, :meth:`reach` lets go what the step's first query
    no longer reads (back to the kind's free list) and takes pages up to
    its last.  Of a STATE kind the lane holds ONE slot and names, a step,
    the slot it reads (``begin``: the null slot, or a snapshot; afterwards
    what it wrote last) and the slot it writes (its own, or once a fresh
    one that stays behind as a snapshot: :meth:`snapshot`)."""

    def __init__(self, pool: "Pool", begin: int = 0):
        self.pool = pool
        self.base = [0] * len(pool.kinds)
        self.pages = [[] for _ in pool.kinds]
        self.ever = [set() for _ in pool.kinds]  # every page it has held
        # a state kind: the lane's own slot, what its next step reads, and
        # where its next step writes if not to its own
        self.slot = [pool.free[k].pop(0) if horizon == STATE else None
                     for k, (_, horizon) in enumerate(pool.kinds)]
        self.read = [begin] * len(pool.kinds)
        self.keep = None

    def snapshot(self) -> int:
        """The lane's NEXT step writes a fresh slot, which stays as it is
        from then on (the step after reads it and writes the lane's own
        again): the slot."""
        k, = [k for k, s in enumerate(self.slot) if s is not None]
        self.keep = self.pool.free[k].pop(0)
        return self.keep

    def slots(self, k: int) -> tuple:
        """(read, write) of the step being packed; the next one reads what
        this one writes."""
        write = self.slot[k] if self.keep is None else self.keep
        read, self.read[k], self.keep = self.read[k], write, None
        return read, write

    def reach(self, first: int, last: int) -> None:
        for k, (_, horizon) in enumerate(self.pool.kinds):
            if horizon == STATE:
                continue
            lo = first_page(first, horizon, PAGE)
            while self.base[k] < lo:
                if self.pages[k]:
                    self.pool.free[k].append(self.pages[k].pop(0))
                    self.pool.released[k] += 1
                self.base[k] += 1
            while self.base[k] + len(self.pages[k]) <= last // PAGE:
                self.pages[k].append(self.pool.free[k].pop(0))
                self.ever[k].add(self.pages[k][-1])
            assert len(self.pages[k]) <= self.pool.width[k], "table too narrow"


class Pool:
    """Drives ``family.fused_step`` as the scheduler does: one chunk of one
    lane beside the decode rows of others, through one donated pool (one a
    kind, for a family with ``page_kinds``: a lane is then a :class:`Lane`
    where a one-kind family's is a table)."""

    def __init__(self, family, cfg, params, pages=40,
                 width: int = WIDTH, chunk: int = 16, lmax: int = LMAX):
        self.family, self.cfg, self.params = family, cfg, params
        self.width = width  # pages of a lane's table
        self.lmax = lmax    # decode lanes + the chunk lane + the dump lane
        self.kinds = tuple(family.page_kinds(cfg)) \
            if hasattr(family, "page_kinds") else None
        if self.kinds:
            # a kind with a horizon: its window, a chunk, and a page for
            # where the window starts inside one
            self.width = tuple(
                1 if horizon == STATE else width if horizon is None else
                min(width, pages_for(horizon + chunk, PAGE) + 1)
                for _, horizon in self.kinds)
            if isinstance(pages, int):
                pages = (pages,) * len(self.kinds)
            # first in, first out: a small pool goes round, and a page one
            # lane let go is soon another's
            self.free = [list(range(1, n)) for n in pages]
            self.released = [0] * len(self.kinds)
        self.pool = family.init_pages(cfg, pages, PAGE)
        # the family's own counts, in its STEP_COUNTERS order
        self.counters = tuple(getattr(family, "STEP_COUNTERS", ()))
        self.counts = np.zeros(len(self.counters), np.int64)

    def step(self, decode=(), chunk=None, prev=None, lanes=None):
        """decode: [(token, position, table)]; chunk: (tokens, start,
        table); prev: the int vector of the step before (``self.ints``),
        where a decode row whose token is ``-(src + 1)`` finds it; lanes:
        the decode lane each decode row sits in (0, 1, ... where not
        given).  Returns the logits of each decode row, then of the chunk's
        last row."""
        lmax = self.lmax
        n_valid = len(chunk[0]) if chunk else 0
        tq = round_up_pow2(n_valid, 16) if chunk else 1
        f = round_up_pow2(len(decode) + n_valid, 8)
        meta, (toks, lane, lpos, pos, rows, tables) = blank_step(
            lmax, self.width, f)
        def place(i, table, first, last):
            if not self.kinds:
                tables[i] = table
                return
            table.reach(first, last)
            for k, (kind, base, held) in enumerate(zip(
                    tables, table.base, table.pages)):
                if table.slot[k] is not None:
                    kind.base[i], kind.pages[i, 0] = table.slots(k)
                else:
                    kind.base[i], kind.pages[i, :len(held)] = base, held

        for i, (tok, at, table) in enumerate(decode):
            seat = i if lanes is None else lanes[i]
            toks[i], lane[i], pos[i], rows[i] = tok, seat, at, i
            place(seat, table, at, at)
        if chunk:
            ids, start, table = chunk
            for j, tok in enumerate(ids):
                at = len(decode) + j
                toks[at], lane[at], lpos[at] = tok, lmax - 2, j
                pos[at] = start + j
            place(lmax - 2, table, start, start + n_valid - 1)
            rows[len(decode)] = len(decode) + n_valid - 1
        donated = self.pool
        ints, logits, self.pool = self.family.fused_step(
            self.params, self.cfg, jnp.asarray(meta), donated,
            lmax=lmax, w=self.width, tq=tq,
            **({} if prev is None else {"prev": jnp.asarray(prev)}))
        assert all(a.is_deleted() for a in jax.tree.leaves(donated)), \
            "the step copied the pool"
        self.ints = ints = np.asarray(ints)
        # the greedy ids, then the routing counts of a family that routes
        assert ints.shape[0] - lmax == len(self.counters)  # as it declares
        assert (ints[:lmax] == np.asarray(logits).argmax(-1)).all()
        if ints.shape[0] > lmax:
            self.counts += ints[lmax:]
        return np.asarray(logits)[:len(decode) + bool(chunk)]

    def serve(self, ids, table, start=0, steps=6, chunk=16):
        """Prefill ``ids[start:]`` in chunks, then decode greedily:
        (produced ids, the logits of every produced position)."""
        at, logits = start, None
        while at < len(ids):
            piece = ids[at:at + chunk]
            logits = self.step(chunk=(piece, at, table))[-1]
            at += len(piece)
        rows, out = [logits], [int(logits.argmax())]
        for n in range(len(ids), len(ids) + steps - 1):
            rows.append(self.step(decode=[(out[-1], n, table)])[0])
            out.append(int(rows[-1].argmax()))
        return out, np.stack(rows)
