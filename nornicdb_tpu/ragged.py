"""The fused ragged step's row layout: the contract between the genserve
scheduler and every decoder family's ``fused_step``.

The scheduler (``genserve/engine.py``) packs one int32 host array a step;
a family (``models/qwen2.py``, ``models/deepseek_v2.py``,
``models/longcat_flash.py``) unpacks it on the
device.  Nothing here knows a model: page arithmetic, the power-of-two
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
"""

from __future__ import annotations

import numpy as np

# physical page 0: padded lanes and padded chunk positions write here, so a
# static-shape program never corrupts a live page
NULL_PAGE = 0

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


def pack_ragged_meta(lmax: int, w: int, f: int):
    """Allocate the packed int32 metadata array for one fused step and
    return (meta, views): views are writable slices (tokens, lane_id,
    lane_pos, positions, logit_rows, lane_tables) of ``meta``."""
    meta = np.empty((4 * f + lmax + lmax * w,), np.int32)
    tokens = meta[:f]
    lane_id = meta[f:2 * f]
    lane_pos = meta[2 * f:3 * f]
    positions = meta[3 * f:4 * f]
    logit_rows = meta[4 * f:4 * f + lmax]
    lane_tables = meta[4 * f + lmax:].reshape(lmax, w)
    return meta, (tokens, lane_id, lane_pos, positions, logit_rows,
                  lane_tables)


def unpack_ragged_meta(meta, lmax: int, w: int, prev=None):
    """The device side of :func:`pack_ragged_meta`: the same six views of a
    traced ``meta`` (``F`` follows from its length).  With ``prev`` (the
    previous step's int vector) a negative token ``-(src + 1)`` is read
    from ``prev[src]``; without it every token is taken as written."""
    f = (meta.shape[0] - lmax - lmax * w) // 4
    tokens = meta[:f]
    if prev is not None:
        import jax.numpy as jnp  # traced code only: the host side stays numpy

        tokens = jnp.where(
            tokens < 0, prev[jnp.clip(-1 - tokens, 0, lmax - 1)], tokens)
    return (tokens, meta[f:2 * f], meta[2 * f:3 * f], meta[3 * f:4 * f],
            meta[4 * f:4 * f + lmax], meta[4 * f + lmax:].reshape(lmax, w))
