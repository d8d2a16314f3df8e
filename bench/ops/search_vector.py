"""op ``search_vector``: ``POST /nornicdb/search`` by raw vector (the
Qdrant/gRPC SearchRequest.vector shape on the REST surface)."""

import json

PATH = "/nornicdb/search"
NEEDS_ROWS = True  # check() scans the seeded rows the run loaded


def encode(vector, params: dict) -> bytes:
    return json.dumps({"vector": vector.astype(float).round(7).tolist(),
                       "limit": params["k"], "min_score": -1.0,
                       "include_content": False}).encode()


def decode(raw: bytes):
    return json.loads(raw)["results"]


def check(run):
    """A seeded sample of the window's answers against plain numpy over the
    same seeded rows.  Returns (numbers, the control's numbers, compared)."""
    import numpy as np
    import reference

    k, limits = run.cell["params"]["k"], run.config["limits"]
    picks = run.sample([r for r in run.answered if r[6]])
    if not picks:
        return [], [], 0
    queries = np.stack([run.request(r) for r in picks])
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    exact = reference.exact_scores(queries, run.rows)
    answers = []
    for r in picks:
        hits = []
        for h in decode(r[6].encode()):
            ok = h["id"][:1] == "v" and h["id"][1:].isdigit() \
                and int(h["id"][1:]) < len(run.rows)
            hits.append((int(h["id"][1:]) if ok else -1, h["score"]))
        answers.append(hits)
    numbers = reference.check_search(limits, k, exact, answers)
    ctl = []
    if run.control:
        low = reference.topk_answers(
            reference.exact_scores(queries, run.rows, mode="fp8"), k)
        ctl = reference.check_search(limits, k, exact, low)
    return numbers, ctl, len(picks)
