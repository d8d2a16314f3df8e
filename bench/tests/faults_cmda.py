"""Break the Command A+ path underneath a run, then drive the whole run.

    python3 bench/tests/faults_cmda.py <fault> --workload cmda-chat-sys6k ...

As ``faults.py``, ``faults_dsv2.py`` and ``faults_lcf.py``: each fault
alters the program where it computes (or where it keeps its pages), and
``run.py`` has to come out with ``"correct": false``.  These seven are what
a rank of this architecture, and a scheduler with two kinds of pages, can
get wrong and still stream plausible tokens: the window ignored in a
sliding layer, rope applied in a full layer, the shared experts summed
instead of averaged, the held experts' part dropped, the gates left
unnormalised, a layer's rows written to the other kind's pool, a window
page let go one page early.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def window_ignored():
    """The sliding layers have no window: the family reports their page
    kind without a horizon, so the scheduler keeps a lane's whole history
    in it and the step's mask has no lower edge (rope as before).  (With
    the edge taken from the mask ALONE nothing could show: the scheduler
    hands a window lane only the pages its window reaches, so the step
    would see at most the fifteen keys before the edge in its oldest
    page.)"""
    from nornicdb_tpu.models import cohere2_moe as m

    plain = m._kinds
    m._kinds = lambda cfg: tuple((name, None, layers)
                                 for name, _, layers in plain(cfg))


def rope_in_full():
    """The full layer rotates q and k like its sliding neighbours."""
    from nornicdb_tpu.models import cohere2_moe as m

    plain, last = m.project, []

    def rotated(cfg, blk, x, rotary):
        if rotary is not None:
            last[:] = [rotary]
        return plain(cfg, blk, x, rotary or last[0])

    m.project = rotated


def shared_summed():
    """The four shared experts are summed, not averaged."""
    import jax.numpy as jnp

    from nornicdb_tpu.models import cohere2_moe as m

    plain = m.expert_layer

    def summed(cfg, blk, x, valid=None):
        out, counts = plain(cfg, blk, x, valid)
        even = jnp.full((x.shape[0], cfg.num_shared_experts),
                        1.0 / cfg.num_shared_experts, jnp.float32)
        mean = m.experts.held_experts(blk["shared"], x, even)
        return out + (cfg.num_shared_experts - 1) * mean, counts

    m.expert_layer = summed


def held_dropped():
    """The expert layer adds its shared average only: the held experts'
    matmul contributes nothing."""
    import jax.numpy as jnp

    from nornicdb_tpu.models import cohere2_moe as m

    plain = m.experts.held_gates

    def none(ids, gates, held, valid=None):
        weight, counts = plain(ids, gates, held, valid)
        return jnp.zeros_like(weight), counts

    m.experts.held_gates = none


def gates_unnormalised():
    """The gates are the sigmoid scores themselves, not their share of the
    chosen eight's sum (about eight times too large at this spread)."""
    import jax
    import jax.numpy as jnp

    from nornicdb_tpu.models import cohere2_moe as m

    def raw(cfg, router, x):
        s = jax.nn.sigmoid(jnp.einsum(
            "nh,he->ne", x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        top, ids = jax.lax.top_k(s, cfg.num_experts_per_tok)
        return ids, top

    m.route = raw


def other_kinds_pool():
    """The last sliding layer of a period writes and reads the pages that
    the FULL kind's table names, in its own pool: a page id of the other
    kind, where this kind keeps another lane's rows."""
    from nornicdb_tpu.models import cohere2_moe as m

    plain = m.attend_step

    def crossed(cfg, blk, rows, kind, pool, at, x, rotary, horizon):
        if horizon is not None and at == pool.shape[0] - 1:
            other = rows.kinds[0]
            width = min(kind.dec_tables.shape[1], other.dec_tables.shape[1])
            kind = kind._replace(
                phys=other.phys,
                dec_tables=kind.dec_tables.at[:, :width].set(
                    other.dec_tables[:, :width] % pool.shape[2]))
        return plain(cfg, blk, rows, kind, pool, at, x, rotary, horizon)

    m.attend_step = crossed


def page_released_early():
    """The scheduler lets a window page go one page before the window has
    left it: the lane's oldest sixteen keys are gone (the step masks by
    position, so it attends what another lane wrote there, or zeros)."""
    from nornicdb_tpu.genserve import engine

    plain = engine.first_page

    def early(position, horizon, page_size):
        return plain(position + page_size if horizon is not None
                     else position, horizon, page_size)

    engine.first_page = early


FAULTS = {f.__name__: f for f in (
    window_ignored, rope_in_full, shared_summed, held_dropped,
    gates_unnormalised, other_kinds_pool, page_released_early)}

if __name__ == "__main__":
    FAULTS[sys.argv.pop(1)]()
    import run

    run.main()
