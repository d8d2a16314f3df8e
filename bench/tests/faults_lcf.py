"""Break the LongCat-Flash path underneath a run, then drive the whole run.

    python3 bench/tests/faults_lcf.py <fault> --workload lcf-chat-sys4k ...

As ``faults.py`` and ``faults_dsv2.py``: each fault alters the program where
it computes, and ``run.py`` has to come out with ``"correct": false``.
These six are what an expert-parallel rank of this architecture can get
wrong and still stream plausible tokens: the zero experts' part left out,
the held experts' part left out, the selection bias added to the gates, the
shortcut branch joined a sub-layer early, a layer's second attention block
cached in its first block's pool layer, the latent cached without its LoRA
scale.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def zero_dropped():
    """The branch adds its held experts only: a choice on an identity
    expert adds nothing."""
    import jax.numpy as jnp

    from nornicdb_tpu.models import longcat_flash as m

    plain = m.route

    def no_zero(cfg, router, bias, x):
        ids, gates = plain(cfg, router, bias, x)
        return ids, jnp.where(ids >= cfg.n_routed_experts, 0.0, gates)

    m.route = no_zero


def held_dropped():
    """The branch adds its zero experts only: the held experts' matmul
    contributes nothing."""
    import jax.numpy as jnp

    from nornicdb_tpu.models import mla

    plain = mla.held_experts
    mla.held_experts = lambda experts, x, weight: jnp.zeros_like(
        plain(experts, x, weight))


def bias_in_gates():
    """The gates are scaling x (score + bias): the selection bias leaks
    into the weighting."""
    import jax.numpy as jnp

    from nornicdb_tpu.models import longcat_flash as m

    plain = m.route

    def biased(cfg, router, bias, x):
        ids, gates = plain(cfg, router, bias, x)
        return ids, gates + cfg.routed_scaling_factor * bias.astype(
            jnp.float32)[ids]

    m.route = biased


def joined_early():
    """``m`` joins the stream after the layer's FIRST feed-forward: the
    second attention block and feed-forward then see it."""
    from nornicdb_tpu.models import longcat_flash as m
    from nornicdb_tpu.models import mla

    def layer(cfg, lay, h, attend, pool=None, at=0, valid=None):
        eps = cfg.rms_norm_eps
        a0, pool = attend(lay["attn"][0], at, h, pool)
        x0 = m.rms_norm(lay["mlp_norm"][0], a0, eps)
        branch, counts = m.expert_branch(cfg, lay, x0, valid)
        b0 = a0 + mla.swiglu(lay["mlp"][0], x0) + branch.astype(h.dtype)
        a1, pool = attend(lay["attn"][1], at + 1, b0, pool)
        b1 = a1 + mla.swiglu(lay["mlp"][1],
                             m.rms_norm(lay["mlp_norm"][1], a1, eps))
        return b1, counts, pool

    m._layer = layer


def block1_in_block0s_layer():
    """A layer's second attention block writes and reads its first block's
    pool layer: every cached row of block 0 is block 1's."""
    from nornicdb_tpu.models import mla

    plain = mla.attend_step
    mla.attend_step = lambda cfg, blk, rows, pages, at, *a: plain(
        cfg, blk, rows, pages, at - at % 2, *a)


def ckv_unscaled():
    """The latent goes into the cache without ``sqrt(hidden /
    kv_lora_rank)`` (attention reads nothing but the cache, so every key
    and value is off by it)."""
    from nornicdb_tpu.models import longcat_flash as m
    from nornicdb_tpu.models import mla

    m._project = lambda cfg, blk, h, cos, sin: mla.project(
        cfg, blk, h, cos, sin, q_scale=cfg.q_scale)


FAULTS = {f.__name__: f for f in (zero_dropped, held_dropped, bias_in_gates,
                                  joined_early, block1_in_block0s_layer,
                                  ckv_unscaled)}

if __name__ == "__main__":
    FAULTS[sys.argv.pop(1)]()
    import run

    run.main()
