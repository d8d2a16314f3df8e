"""op ``chat_stream``: ``POST /v1/chat/completions`` with ``stream: true``
(the OpenAI-compatible route; ``/api/bifrost/chat/completions`` is the same
handler): server-sent events, one content delta a generated token.

With the hash-word tokenizer a word is a token and the stream's text is
``<id> <id> ...``, so the op reads the produced ids exactly."""

import json
import re

PATH = "/v1/chat/completions"
STREAM = True  # loadgen times every content event as it arrives
_ID = re.compile(r"<(\d+)>")


def encode(request: dict, params: dict) -> bytes:
    return json.dumps({
        "messages": [{"role": "system", "content": request["system"]},
                     {"role": "user", "content": request["user"]}],
        "max_tokens": params["max_tokens"], "stream": True}).encode()


def event(line: bytes):
    """One line of the stream -> ``("content", [ids])``, ``("error", text)``,
    ``("done", None)`` or None for a line that carries none of them."""
    if not line.startswith(b"data: "):
        return None
    data = line[6:].strip()
    if data == b"[DONE]":
        return "done", None
    chunk = json.loads(data)
    if "error" in chunk:
        return "error", str(chunk["error"])[:200]
    for choice in chunk.get("choices", []):
        if choice.get("finish_reason") == "error":
            return "error", "finish_reason error"
        text = (choice.get("delta") or {}).get("content")
        if text:
            return "content", [int(i) for i in _ID.findall(text)]
    return None


def decode(raw: str) -> dict:
    """The answer the load generator kept: ``ids``, ``sent`` and
    ``chunk_ms`` (arrival of each content event after ``sent``)."""
    return json.loads(raw)


def _family(run):
    import loadgen

    spec = run.config["generator"]
    return loadgen.load_file(f"models/{spec['family']}.py"), spec


def trace_context(run, in_trace: list, lo: float, hi: float) -> dict:
    """What was prefilled and decoded in the traced seconds ``lo..hi``, from
    the client's side.  A prompt's tokens after the run's shared prefix were
    prefilled between its send and its first content event: the share of
    that interval inside the trace counts.  The token of content event j > 0
    is one decode step at position ``prompt + j - 1``."""
    family, spec = _family(run)
    prefill, decode_spans, sampled = [], [], 0
    for rec in run.answered:
        got = decode(rec[6])
        req = run.request(rec)
        n = len(family.prompt_ids(spec, req))
        at = [got["sent"] + ms / 1e3 for ms in got["chunk_ms"]]
        if not at:
            continue
        a, b = got["sent"], at[0]
        share = max(0.0, min(b, hi) - max(a, lo)) / max(b - a, 1e-9)
        if share > 0:
            prefill.append([share, family.shared_prefix_tokens(spec, req), n])
        inside = [j for j, t in enumerate(at) if lo <= t <= hi]
        sampled += len(inside)
        steps = [j for j in inside if j > 0]
        if steps:
            decode_spans.append([n + steps[0] - 1, n + steps[-1]])
    return {"completed": len(in_trace), "prefill_spans": prefill,
            "decode_spans": decode_spans, "sampled": sampled}


def check(run):
    """A seeded sample of the window's streams, the longest prompt in it.
    For each, ONE cache-free float32 forward over prompt + produced ids (the
    prompt made again from the seed) and, at every produced position, the
    reference's best logit minus its logit of the served token: prefill,
    decoding through the paged cache and reuse of prefix pages all have to
    agree with it.  Beside it, exact: every id a token of the vocabulary,
    every stream ``max_tokens`` long unless it ended on </s>, and the
    engine's own count of prompt tokens (prefilled + taken from the prefix
    cache, whole process) equal to the prompts' as made here."""
    import numpy as np
    import reference

    family, spec = _family(run)
    limits, want = run.config["limits"], run.cell["params"]["max_tokens"]
    vocab = spec["vocab_size"]
    picks = run.sample([r for r in run.answered if r[6]], longest=lambda r:
                       len(run.request(r)["user"]))
    if not picks:
        return [], [], 0
    seqs, bad, short = [], 0, 0
    for rec in picks:
        out = decode(rec[6])["ids"]
        bad += sum(1 for i in out if not 0 <= i < vocab) + (not out)
        short += len(out) != want and (not out or out[-1] != family.EOS)
        seqs.append((family.prompt_ids(spec, run.request(rec)), out or [0]))
    gaps, low = family.greedy_gaps(spec, run.models["generator"][1], seqs,
                                   run.control)
    # a stream that an error event ended is not counted: it was refused at
    # admission (one that was shed half way leaves the two counts apart)
    sent = sum(len(family.prompt_ids(spec, run.request(r)))
               for r in run.records if r[4] == 200)
    g = run.counters["genserve"]
    seen = g["prefill_tokens_first"] + g["prefix_reused_tokens"]
    numbers = [
        reference.number("greedy_gap_max",
                         max(float(np.max(x)) for x in gaps),
                         limits["greedy_gap_max"], "lower"),
        reference.number("bad_ids", bad, 0, "lower"),
        reference.number("short_streams", short, 0, "lower"),
        reference.number("prompt_tokens_unaccounted", abs(seen - sent)
                         + g["prefill_tokens_re"], 0, "lower")]
    ctl = [reference.number("greedy_gap_max",
                            max(float(np.max(x)) for x in low),
                            limits["greedy_gap_max"], "lower")] if low else []
    return numbers, ctl, len(picks)
