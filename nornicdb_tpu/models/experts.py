"""The routed experts a process holds, under expert parallelism: a router's
choice as this process sees it, and the masked matmul over the experts it
holds.  What every routed family (``models/deepseek_v2.py``,
``models/longcat_flash.py``, ``models/cohere2_moe.py``,
``models/nemotron_h.py``) runs, written once;
the router itself (softmax or sigmoid, groups, a bias, zero experts) is the
family's.  ``held = (first, count)`` names the experts held here; what the
absent ones would add is left out, and nothing here stands in for the other
ranks or their exchange.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def held_gates(ids: jax.Array, gates: jax.Array, held: tuple,
               valid: jax.Array | None = None):
    """A router's choice (ids, gates: (N, k)) as this process sees it:
    ``weight`` (N, count) f32 = each row's gate on each HELD expert
    (``held = (first, count)``; zero where the row was not routed to it),
    and the counts over the ``valid`` rows, int32 (3,) = (assignments on
    held experts, the fullest held expert's rows, held experts that got a
    row)."""
    first, count = held
    # (N, k, count) one-hot of the held experts' local ids: an id
    # outside first .. first+count-1 gives a zero row
    on = jax.nn.one_hot(ids - first, count, dtype=jnp.float32)
    weight = jnp.einsum("nk,nkc->nc", gates, on)
    rows = on.sum(axis=1)
    if valid is not None:
        rows = rows * valid[:, None].astype(jnp.float32)
    per_expert = rows.sum(axis=0)
    counts = jnp.stack([per_expert.sum(), per_expert.max(),
                        (per_expert > 0).sum()]).astype(jnp.int32)
    return weight, counts


def held_experts(experts: dict, x: jax.Array, weight: jax.Array) -> jax.Array:
    """What the held experts add for rows x (N, hidden) under ``weight``
    (N, held): f32 (N, hidden).  A masked matmul: every held expert's
    gate/up runs over every row (at serving batch sizes the cost is reading
    the expert's weights, once, whoever is routed to it) and a row's gate,
    zero where it was not routed to that expert, scales the activation
    before ONE down projection over (expert, width): no dropped tokens, no
    capacity factor.  An expert with a ``gate`` matrix is the gated form,
    ``down(silu(gate x) * up x)``; one without (Nemotron-H's) the ungated
    one, ``down(relu(up x)^2)``."""
    gated = "gate" in experts
    if gated:
        gate = jnp.einsum("nh,chi->nci", x, experts["gate"],
                          preferred_element_type=jnp.float32)
    up = jnp.einsum("nh,chi->nci", x, experts["up"],
                    preferred_element_type=jnp.float32)
    act = jax.nn.silu(gate) * up if gated else jnp.square(jax.nn.relu(up))
    act = (act * weight[:, :, None]).astype(x.dtype)
    return jnp.einsum("nci,cih->nh", act, experts["down"],
                      preferred_element_type=jnp.float32)
