"""The fused ragged step's row layout: the contract between the genserve
scheduler and every decoder family's ``fused_step``.

The scheduler (``genserve/engine.py``) packs one int32 host array a step;
a family (``models/qwen2.py``, ``models/deepseek_v2.py``,
``models/longcat_flash.py``, ``models/cohere2_moe.py``,
``models/nemotron_h.py``) unpacks it on the device.  Nothing here knows a
model: page arithmetic, the power-of-two
bucketing of program shapes, the null page, and the order of the routing
counts a family may append to the step's greedy ids.

Layout of ``meta`` for ``F`` flat token rows, ``Lmax`` attention lanes and
``W`` pages a lane: ``tokens (F) | lane_id (F) | lane_pos (F) |
positions (F) | logit_rows (Lmax) | lane_tables (Lmax, W)``.  Lane roles are
fixed by ``lane_id``: ``< Lmax-2`` a decode lane, ``Lmax-2`` THE chunk lane,
``Lmax-1`` the dump lane of padding rows; ``positions == -1`` marks a
padding row (its write goes to :data:`NULL_PAGE`).

A decode row whose input token the host has not read yet says where the
PREVIOUS step put it: ``tokens == -(src + 1)`` names entry ``src`` of that
step's int vector (the ``logit_rows`` row that picked it), which the step
takes as ``prev`` and resolves on the device (:func:`unpack_ragged_meta`).
The scheduler keeps one step in flight on this: step N+1 is dispatched
before step N's ids cross to the host.

**Page kinds.**  A family whose layers do not all keep a lane's whole
history says so in ``page_kinds(cfg)``: one ``(name, horizon)`` a kind,
``horizon`` the tokens back a query of that kind's layers reads (``None``:
all of them).  The scheduler then keeps a pool, a free list, reference
counts, prefix registrations and a page table a lane FOR EACH KIND, and
``W`` is a tuple, a width a kind.  ``meta`` ends, instead of the one table,
with ``base (Lmax) | lane_tables (Lmax, W_k)`` for each kind in turn
(:class:`KindTables`): column ``j`` of a lane's table is its logical page
``base + j``, so a kind with a horizon carries only the pages from the one
its lane's window still reaches onward (``base`` is 0 for a kind without).
A family without ``page_kinds`` has the one kind ``full`` and the layout
above, unchanged.

**A state kind.**  Layers that keep no row a token but one fixed block a
lane (a state-space layer's convolution inputs and SSM state) are a kind
whose ``horizon`` is :data:`STATE`.  Its pool is SLOTS, a lane holds one, and
there is no table: the kind's part of ``meta`` is ``read (Lmax) | write
(Lmax)``, the slot a lane's step reads its state from and the slot it writes
the advanced state to (the layout of a kind one page wide:
:class:`KindTables` ``base`` = read, ``pages[:, 0]`` = write; its entry of
``w`` is 1).  They differ when the lane begins from a snapshot or from
nothing (read :data:`NULL_PAGE`: zeros) or leaves a snapshot behind: the
step copies on write, no dispatch of its own.  A write to
:data:`NULL_PAGE` is dropped, so slot 0 stays zeros: lanes without a
sequence, the dump lane and padding rows advance nothing.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

# physical page 0: padded lanes and padded chunk positions write here, so a
# static-shape program never corrupts a live page
NULL_PAGE = 0

# the ``horizon`` of a state kind (module note): no tokens back, a slot a lane
STATE = "state"

# what a family with routed experts appends, in this order, to the ``Lmax``
# greedy ids of its step's one int vector (``GenStats`` fields of the same
# names; a family without experts appends nothing).  A family says what its
# step appends in its module's ``STEP_COUNTERS`` (these four, or these and
# further ``GenStats`` fields of its own after them): the scheduler sizes
# the first step's ``prev`` by it and reads every step's counts by it
ROUTING_COUNTERS = ("expert_assignments", "expert_rows_max", "experts_hit",
                    "routed_rows")


def round_up_pow2(n: int, floor: int = 64) -> int:
    """Bucket a length so jits stay bounded: without this, every distinct
    prompt length compiles a fresh program (the same policy as
    TPUEmbedder's length buckets, embed/base.py)."""
    out = floor
    while out < n:
        out *= 2
    return out


def pages_for(n_tokens: int, page_size: int) -> int:
    """Logical pages needed to hold n_tokens cache slots."""
    return max(1, -(-n_tokens // page_size))


class KindTables(NamedTuple):
    """One page kind's part of ``meta``: each lane's first logical page and
    its table from there on."""
    base: object    # (Lmax,); a state kind: the slot a lane reads
    pages: object   # (Lmax, W_k); a state kind: (Lmax, 1) the slot it writes


def first_page(position: int, horizon, page_size: int) -> int:
    """The first logical page a query at ``position`` still reads in a kind
    of this ``horizon``: it sees the keys ``j`` with ``position - horizon <
    j <= position``."""
    if horizon is None or horizon == STATE:
        return 0
    return max(0, position - horizon + 1) // page_size


def _meta_len(lmax: int, w, f: int) -> int:
    if isinstance(w, tuple):
        return 4 * f + lmax + sum(lmax * (1 + wk) for wk in w)
    return 4 * f + lmax + lmax * w


def _kind_tables(rest, lmax: int, w: tuple) -> tuple:
    out, at = [], 0
    for wk in w:
        out.append(KindTables(rest[at:at + lmax],
                              rest[at + lmax:at + lmax * (1 + wk)]
                              .reshape(lmax, wk)))
        at += lmax * (1 + wk)
    return tuple(out)


def pack_ragged_meta(lmax: int, w, f: int):
    """Allocate the packed int32 metadata array for one fused step and
    return (meta, views): views are writable slices (tokens, lane_id,
    lane_pos, positions, logit_rows, lane_tables) of ``meta``;
    ``lane_tables`` is the (Lmax, W) table, or for a tuple ``w`` one
    :class:`KindTables` a kind (module note)."""
    meta = np.empty((_meta_len(lmax, w, f),), np.int32)
    tokens = meta[:f]
    lane_id = meta[f:2 * f]
    lane_pos = meta[2 * f:3 * f]
    positions = meta[3 * f:4 * f]
    logit_rows = meta[4 * f:4 * f + lmax]
    rest = meta[4 * f + lmax:]
    lane_tables = _kind_tables(rest, lmax, w) if isinstance(w, tuple) \
        else rest.reshape(lmax, w)
    return meta, (tokens, lane_id, lane_pos, positions, logit_rows,
                  lane_tables)


def unpack_ragged_meta(meta, lmax: int, w, prev=None):
    """The device side of :func:`pack_ragged_meta`: the same six views of a
    traced ``meta`` (``F`` follows from its length).  With ``prev`` (the
    previous step's int vector) a negative token ``-(src + 1)`` is read
    from ``prev[src]``; without it every token is taken as written."""
    f = (meta.shape[0] - _meta_len(lmax, w, 0)) // 4
    tokens = meta[:f]
    if prev is not None:
        import jax.numpy as jnp  # traced code only: the host side stays numpy

        tokens = jnp.where(
            tokens < 0, prev[jnp.clip(-1 - tokens, 0, lmax - 1)], tokens)
    rows = (tokens, meta[f:2 * f], meta[2 * f:3 * f], meta[3 * f:4 * f],
            meta[4 * f:4 * f + lmax])
    rest = meta[4 * f + lmax:]
    return (*rows, _kind_tables(rest, lmax, w) if isinstance(w, tuple)
            else rest.reshape(lmax, w))


def split_state(meta, lmax: int):
    """``meta`` of a family whose LAST kind is a state kind (module note)
    -> (the meta of the kinds before it alone, read (Lmax,), write
    (Lmax,)): host array or traced alike."""
    return meta[:-2 * lmax], meta[-2 * lmax:-lmax], meta[-lmax:]
