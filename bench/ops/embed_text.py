"""op ``embed_text``: ``POST /nornicdb/embed``, one text, one vector back."""

import json

PATH = "/nornicdb/embed"


def encode(text: str, params: dict) -> bytes:
    return json.dumps({"text": text}).encode()


def decode(raw: bytes):
    return json.loads(raw)["embedding"]


def check(run):
    """A seeded sample of the window's vectors, the longest text in it,
    against the plain per-text forward in float32 ``highest``.  Returns
    (numbers, the control's numbers, compared)."""
    import numpy as np
    import reference

    limits, model = run.config["limits"], run.config["model"]
    family = run.models["model"][0]
    picks = run.sample([r for r in run.answered if r[6]],
                       longest=lambda r: len(run.request(r)))
    if not picks:
        return [], [], 0
    texts = [run.request(r) for r in picks]
    served = np.asarray([decode(r[6].encode()) for r in picks])
    ref = family.embed_reference(model, run.params, texts)
    numbers = reference.check_vectors(limits, served, ref)
    ctl = []
    if run.control:
        low = family.embed_reference(model, run.params, texts, mode="fp8")
        ctl = reference.check_vectors(limits, low, ref)
    return numbers, ctl, len(picks)
