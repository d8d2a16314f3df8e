"""Break the DeepSeek-V2 path underneath a run, then drive the whole run.

    python3 bench/tests/faults_dsv2.py <fault> --workload dsv2-chat-sys4k ...

As ``faults.py``: each fault alters the program where it computes, and
``run.py`` has to come out with ``"correct": false``.  These five are what
an expert-parallel rank with a latent cache can get wrong and still stream
plausible tokens: the experts of another rank's group under this rank's
ids, the routed part left out, the latent cached before its norm, the
shared rotary key cached before its rotation, gates renormalised.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def wrong_group():
    """The rank computes with its experts' weights but answers for the
    NEXT group's ids: rows routed to experts it does not hold get this
    rank's experts, rows routed to its own get nothing."""
    import dataclasses

    from nornicdb_tpu.genserve.engine import GenerationEngine

    plain = GenerationEngine.__init__

    def shifted(self, params, cfg, *a, **kw):
        first, count = cfg.held_experts
        plain(self, params, dataclasses.replace(
            cfg, held_experts=(first + count, count)), *a, **kw)

    GenerationEngine.__init__ = shifted


def routed_dropped():
    """The expert layer adds its shared experts only."""
    import jax.numpy as jnp

    from nornicdb_tpu.models import deepseek_v2

    plain = deepseek_v2.routed_experts

    def nothing(cfg, blk, x, valid=None):
        out, counts = plain(cfg, blk, x, valid)
        return jnp.zeros_like(out), counts

    deepseek_v2.routed_experts = nothing


def ckv_before_norm():
    """The latent goes into the cache before its RMSNorm (attention reads
    nothing but the cache, so every key and value is off)."""
    from nornicdb_tpu.models import deepseek_v2 as m

    plain = m._project

    def raw(cfg, blk, h, cos, sin):
        q_nope, q_pe, row = plain(cfg, blk, h, cos, sin)
        kv = m.dense(blk["kv_a"],
                     m.rms_norm(blk["attn_norm"], h, cfg.rms_norm_eps))
        return q_nope, q_pe, row.at[:, :cfg.kv_lora_rank].set(
            kv[:, :cfg.kv_lora_rank])

    m._project = raw


def kpe_before_rope():
    """The shared rotary key is cached unrotated (queries are rotated)."""
    from nornicdb_tpu.models import deepseek_v2

    plain = deepseek_v2._rope
    deepseek_v2._rope = lambda x, cos, sin: \
        x if x.ndim == 2 else plain(x, cos, sin)


def gates_renormalised():
    """The top-k gates are renormalised to sum to the scaling factor
    (``norm_topk_prob`` true, which this model is not)."""
    from nornicdb_tpu.models import deepseek_v2

    plain = deepseek_v2.route

    def renormalised(cfg, router, x):
        ids, gates = plain(cfg, router, x)
        return ids, gates / gates.sum(-1, keepdims=True) \
            * cfg.routed_scaling_factor

    deepseek_v2.route = renormalised


FAULTS = {f.__name__: f for f in (wrong_group, routed_dropped,
                                  ckv_before_norm, kpe_before_rope,
                                  gates_renormalised)}

if __name__ == "__main__":
    FAULTS[sys.argv.pop(1)]()
    import run

    run.main()
