"""Break the timed path underneath a run, then drive the whole run.

    python3 bench/tests/faults.py <fault> --workload <cell> [run.py arguments]

Each fault alters an answer where the program produces it; ``run.py`` has to
come out with ``"correct": false``.  (Of the faults a cell can have, these
cells have only this kind: they train nothing and span no chips.)  The chat
cell's four: a produced id shifted by one, a stale prefix page, decode
positions off by one, fp8 weights under the engine.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def wrong_ids():
    """The corpus answers with its neighbours' ids: every hit is off by one
    slot where slots become ids."""
    from nornicdb_tpu.ops import similarity

    plain = similarity.HostCorpus._format_results

    def shifted(self, vals, idx, *a, **kw):
        return plain(self, vals, (idx + 1) % max(len(self._ids), 1), *a, **kw)

    similarity.HostCorpus._format_results = shifted


def wrong_scores():
    """The kernel's scores come back 1 % too high."""
    from nornicdb_tpu.ops import similarity

    plain = similarity.HostCorpus._format_results
    similarity.HostCorpus._format_results = \
        lambda self, vals, *a, **kw: plain(self, vals * 1.01 + 0.003, *a, **kw)


def wrong_vectors():
    """The packed forward hands back the vectors of the dispatch before
    (a stale output buffer): every text gets another text's vector, also
    where a dispatch holds one text."""
    from nornicdb_tpu.embed import TPUEmbedder

    plain, last = TPUEmbedder.embed_packed, {}

    def stale(self, packed):
        out = plain(self, packed)
        old = last.get(out.shape)
        last[out.shape] = out
        return out if old is None else old

    TPUEmbedder.embed_packed = stale


def coarse_vectors():
    """The packed forward's output is rounded to 3 bits of mantissa, as a
    lower-precision path would leave it."""
    import numpy as np
    from nornicdb_tpu.embed import TPUEmbedder

    plain = TPUEmbedder.embed_packed

    def coarse(self, packed):
        m, e = np.frexp(plain(self, packed))
        return np.ldexp(np.round(m * 8) / 8, e).astype(np.float32)

    TPUEmbedder.embed_packed = coarse


def shifted_token():
    """Every token the scheduler emits is the argmax's neighbour: the id is
    altered where it is produced (and decoding goes on from it)."""
    from nornicdb_tpu.genserve.engine import GenerationEngine

    plain = GenerationEngine._emit
    GenerationEngine._emit = lambda self, seq, tok: plain(
        self, seq, (tok + 1) % self.cfg.vocab_size)


def stale_prefix_page():
    """The prefix cache's keys commit to a page's place, not its content:
    a prompt adopts the pages another prompt left there, its user message's
    among them."""
    import hashlib

    from nornicdb_tpu.genserve.engine import GenerationEngine

    GenerationEngine._prefix_page_keys = lambda self, toks: [
        hashlib.sha1(b"page %d" % i).digest()
        for i in range(len(toks) // self._page_size)]


def decode_position_off():
    """Every decode row of the fused step writes and attends one cache slot
    too far (its prefill rows are where they belong)."""
    import numpy as np

    from nornicdb_tpu.models import qwen2

    plain = qwen2.ragged_fused_step

    def off(params, cfg, meta, pages, *, lmax, w, tq, **kw):
        m = np.array(meta)
        f = (m.shape[0] - lmax - lmax * w) // 4
        lane, pos = m[f:2 * f], m[3 * f:4 * f]
        pos[(lane < lmax - 2) & (pos >= 0)] += 1
        return plain(params, cfg, m, pages, lmax=lmax, w=w, tq=tq, **kw)

    qwen2.ragged_fused_step = off


def fp8_weights():
    """The engine serves the weights rounded to fp8 (e4m3, a scale a
    tensor): the precision step below the bf16 the configuration states."""
    import jax

    import reference
    from nornicdb_tpu.genserve.engine import GenerationEngine

    plain = GenerationEngine.__init__

    def low(self, params, *a, **kw):
        plain(self, jax.tree.map(
            lambda x: reference.fp8(x.astype("float32")).astype(x.dtype)
            if x.ndim == 2 else x, params), *a, **kw)

    GenerationEngine.__init__ = low


FAULTS = {f.__name__: f for f in (wrong_ids, wrong_scores, wrong_vectors,
                                  coarse_vectors, shifted_token,
                                  stale_prefix_page, decode_position_off,
                                  fp8_weights)}

if __name__ == "__main__":
    FAULTS[sys.argv.pop(1)]()
    import run

    run.main()
