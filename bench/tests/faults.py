"""Break the timed path underneath a run, then drive the whole run.

    python3 bench/tests/faults.py <fault> --workload <cell> [run.py arguments]

Each fault alters an answer where the program produces it; ``run.py`` has to
come out with ``"correct": false``.  (Of the faults a cell can have, these
cells have only this kind: they train nothing and span no chips.)
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


FAULTS = {f.__name__: f for f in (wrong_ids, wrong_scores, wrong_vectors,
                                  coarse_vectors)}

if __name__ == "__main__":
    FAULTS[sys.argv.pop(1)]()
    import run

    run.main()
