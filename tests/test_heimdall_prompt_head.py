"""The assistant's way in: a prompt's repeated head is tokenized once.

``HeimdallManager._build_prompt`` names the head of every prompt (the
preamble, the system message, earlier turns: everything before the last
message's line), and ``EngineGenerator`` keeps the ids of a few heads in an
LRU, so a request encodes only its own tail.  Every case holds the ids
handed to the engine's ``submit`` to ``tokenizer.encode(whole_prompt)``
exactly: the cache changes how the ids are made, never what they are.
"""

from __future__ import annotations

import json
import random
import sys
import threading
import urllib.request

import pytest

import nornicdb_tpu
from nornicdb_tpu.heimdall.manager import (
    PROMPT_HEADS,
    EngineGenerator,
    HeimdallManager,
    Prompt,
    with_head,
)
from nornicdb_tpu.heimdall.plugins import PluginHost
from nornicdb_tpu.models.tokenizer import HashTokenizer, HFTokenizer
from nornicdb_tpu.telemetry.tracing import tracer

VOCAB = 4096
WORDS = [f"w{i}" for i in range(500)] + ["naïve", "straße", "{", "}", ":"]


class _Handle:
    def stream_text_drafted(self):
        yield "ok", -1

    def result(self):
        return [7]


class FakeEngine:
    """What EngineGenerator reads of a GenerationEngine: the ids of every
    prompt it is handed, in order."""

    def __init__(self, tokenizer=None):
        self.tokenizer = tokenizer or HashTokenizer(VOCAB)
        self.cfg = self.params = None
        self.submitted: list[list[int]] = []

    def submit(self, ids, max_new_tokens=1):
        self.submitted.append(list(ids))
        return _Handle()

    def generate(self, ids, max_new_tokens=1):
        self.submitted.append(list(ids))
        return [7]


class UndeclaredTokenizer:
    """Encodes as HashTokenizer does, but does not declare that no token
    spans white space."""

    def __init__(self):
        self._inner = HashTokenizer(VOCAB)

    def encode(self, text, max_len=0, add_special=True):
        return self._inner.encode(text, max_len, add_special)

    def decode(self, ids):
        return self._inner.decode(ids)


def _text(rng, n):
    return " ".join(rng.choice(WORDS) for _ in range(n))


def _messages(system, user):
    return [{"role": "system", "content": system},
            {"role": "user", "content": user}]


def _assistant(tokenizer=None, max_context=8192):
    engine = FakeEngine(tokenizer)
    mgr = HeimdallManager(EngineGenerator(engine, max_context=max_context))
    return mgr, engine


def _whole(mgr, messages, transform=lambda p: p):
    """The ids of the whole prompt, encoded in one piece."""
    prompt = transform(str(mgr._build_prompt(mgr.build_context(messages),
                                             messages)))
    return mgr.generator.tokenizer.encode(prompt, add_special=False)


def _ask(mgr, messages, path):
    if path == "stream":
        list(mgr.chat_stream(messages, 4))
    else:
        mgr.chat(messages, 4)


def _counts(mgr):
    snap = mgr.metrics_registry.snapshot()
    return {k: snap[k] for k in ("prompt_head_hits", "prompt_head_misses",
                                 "prompt_tokens_cached",
                                 "prompt_tokens_encoded")}


@pytest.mark.parametrize("path", ["stream", "chat"])
class TestHeadCache:
    def test_same_head_twice_is_a_hit(self, path):
        rng = random.Random(1)
        mgr, engine = _assistant()
        system = _text(rng, 600)
        firsts = [_messages(system, _text(rng, 40)) for _ in range(2)]
        for messages in firsts:
            _ask(mgr, messages, path)
            assert engine.submitted[-1] == _whole(mgr, messages)
        c = _counts(mgr)
        assert (c["prompt_head_hits"], c["prompt_head_misses"]) == (1, 1)
        head = mgr._build_prompt(mgr.build_context(firsts[0]), firsts[0]).head
        assert head.endswith("system: " + system + "\n")
        assert c["prompt_tokens_cached"] == len(
            engine.tokenizer.encode(head, add_special=False))
        total = sum(len(ids) for ids in engine.submitted)
        assert c["prompt_tokens_cached"] + c["prompt_tokens_encoded"] == total

    def test_changed_system_message_is_a_miss(self, path):
        rng = random.Random(2)
        mgr, engine = _assistant()
        for system in (_text(rng, 300), _text(rng, 300)):
            messages = _messages(system, _text(rng, 20))
            _ask(mgr, messages, path)
            assert engine.submitted[-1] == _whole(mgr, messages)
        c = _counts(mgr)
        assert (c["prompt_head_hits"], c["prompt_head_misses"]) == (0, 2)
        assert c["prompt_tokens_cached"] == 0

    def test_user_message_with_newlines(self, path):
        rng = random.Random(3)
        mgr, engine = _assistant()
        system = _text(rng, 200)
        for user in ("line one\nline two\n\nuser: not a turn\n",
                     "\n" + _text(rng, 30) + "\t\n", "   x y\n"):
            messages = _messages(system, user)
            _ask(mgr, messages, path)
            assert engine.submitted[-1] == _whole(mgr, messages)
        assert _counts(mgr)["prompt_head_hits"] == 2

    def test_earlier_turns_belong_to_the_head(self, path):
        rng = random.Random(4)
        mgr, engine = _assistant()
        turns = _messages(_text(rng, 100), "first question") + [
            {"role": "assistant", "content": "first answer\n"}]
        for user in ("second question", "another second question"):
            messages = turns + [{"role": "user", "content": user}]
            _ask(mgr, messages, path)
            assert engine.submitted[-1] == _whole(mgr, messages)
        assert _counts(mgr)["prompt_head_hits"] == 1

    @pytest.mark.parametrize("rewrite,hits", [
        (lambda p: p.replace("Heimdall", "Odin"), 0),
        (lambda p: p.replace("SECRET", "[redacted]"), 1),
    ], ids=["head", "tail"])
    def test_plugin_rewrite(self, path, rewrite, hits):
        """A ``pre_prompt`` that rewrites the head leaves a prompt that no
        longer begins with it: the whole prompt is encoded.  One that
        rewrites the tail only keeps the split."""

        class Rewriter:
            name = "rewriter"

            def pre_prompt(self, prompt):
                return rewrite(prompt)

        rng = random.Random(5)
        mgr, engine = _assistant()
        PluginHost(mgr)._plugins["rewriter"] = Rewriter()
        system = _text(rng, 150)
        for _ in range(2):
            messages = _messages(system, "tell SECRET " + _text(rng, 10))
            _ask(mgr, messages, path)
            assert engine.submitted[-1] == _whole(mgr, messages, rewrite)
        c = _counts(mgr)
        assert c["prompt_head_hits"] == hits
        assert c["prompt_head_misses"] == (1 if hits else 0)

    def test_undeclared_tokenizer_encodes_the_whole_prompt(self, path):
        rng = random.Random(6)
        mgr, engine = _assistant(UndeclaredTokenizer())
        system = _text(rng, 100)
        for _ in range(2):
            messages = _messages(system, _text(rng, 10))
            _ask(mgr, messages, path)
            assert engine.submitted[-1] == _whole(mgr, messages)
        c = _counts(mgr)
        assert (c["prompt_head_hits"], c["prompt_head_misses"]) == (0, 0)
        assert c["prompt_tokens_encoded"] == sum(map(len, engine.submitted))

    def test_head_longer_than_max_context(self, path):
        rng = random.Random(7)
        mgr, engine = _assistant(max_context=64)
        system = _text(rng, 500)
        for _ in range(2):
            messages = _messages(system, _text(rng, 12))
            _ask(mgr, messages, path)
            whole = _whole(mgr, messages)
            assert len(whole) > 500
            assert engine.submitted[-1] == whole[-64:]
        assert _counts(mgr)["prompt_head_hits"] == 1


class TestGeneratorPaths:
    def test_generate_many_names_heads_or_encodes_whole(self):
        engine = FakeEngine()
        gen = EngineGenerator(engine, max_context=8192)
        head = "shared preamble words\nsystem: be brief\n"
        prompts = [with_head(head + f"user: q{i}\nassistant:", head)
                   for i in range(3)] + ["plain prompt, no head"]
        gen.generate_many(prompts, 4)
        for prompt, ids in zip(prompts, engine.submitted):
            assert ids == engine.tokenizer.encode(prompt, add_special=False)
        snap = gen.metrics.snapshot()
        assert (snap["prompt_head_hits"], snap["prompt_head_misses"]) == (2, 1)

    def test_generate_and_stream_share_the_cache(self):
        engine = FakeEngine()
        gen = EngineGenerator(engine, max_context=8192)
        head = "one head for both paths\n"
        gen.generate(with_head(head + "user: a\nassistant:", head), 4)
        list(gen.generate_stream(with_head(head + "user: b\nassistant:",
                                           head), 4))
        snap = gen.metrics.snapshot()
        assert (snap["prompt_head_hits"], snap["prompt_head_misses"]) == (1, 1)

    def test_with_head_names_only_a_prefix_that_ends_in_white_space(self):
        assert isinstance(with_head("ab\ncd", "ab\n"), Prompt)
        assert with_head("ab\ncd", "ab\n").head == "ab\n"
        for text, head in (("ab\ncd", "ab"), ("xb\ncd", "ab\n"),
                           ("ab\ncd", "")):
            named = with_head(text, head)
            assert type(named) is str and named == text

    def test_lru_evicts_the_least_recently_used_head(self):
        engine = FakeEngine()
        gen = EngineGenerator(engine, max_context=8192)
        heads = [f"system message number {i}\n" for i in range(PROMPT_HEADS + 1)]

        def ask(head):
            gen.generate(with_head(head + "user: q\nassistant:", head), 4)

        for head in heads[:PROMPT_HEADS]:
            ask(head)
        ask(heads[0])  # a hit: heads[1] is now the least recently used
        ask(heads[PROMPT_HEADS])  # a miss that evicts heads[1]
        assert len(gen._heads) == PROMPT_HEADS
        before = gen.metrics.snapshot()
        ask(heads[0])
        ask(heads[1])
        after = gen.metrics.snapshot()
        assert after["prompt_head_hits"] - before["prompt_head_hits"] == 1
        assert after["prompt_head_misses"] - before["prompt_head_misses"] == 1
        for prompt_ids, head_index in zip(engine.submitted[-2:], (0, 1)):
            text = heads[head_index] + "user: q\nassistant:"
            assert prompt_ids == engine.tokenizer.encode(text,
                                                         add_special=False)

    def test_threads_share_the_cache_exactly(self):
        """More threads than heads the LRU keeps, a short switch interval:
        every prompt's ids stay exact and every request is counted once."""
        engine = FakeEngine()
        gen = EngineGenerator(engine, max_context=8192)
        heads = [f"head {i} " + _text(random.Random(i), 40) + "\n"
                 for i in range(PROMPT_HEADS + 4)]
        errors: list[str] = []
        per_thread = 40

        def work(seed):
            rng = random.Random(seed)
            tok = HashTokenizer(VOCAB)
            for _ in range(per_thread):
                head = rng.choice(heads)
                text = head + "user: " + _text(rng, 5) + "\nassistant:"
                ids = gen._ids(with_head(text, head))
                if ids != tok.encode(text, add_special=False):
                    errors.append(text)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=work, args=(s,))
                       for s in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert not errors
        snap = gen.metrics.snapshot()
        assert snap["prompt_head_hits"] + snap["prompt_head_misses"] == \
            16 * per_thread
        assert len(gen._heads) <= PROMPT_HEADS


def _hf_tokenizer(tmp_path):
    vocab = {"<s>": 0, "<pad>": 1, "</s>": 2, "<unk>": 3}
    for w in WORDS[:200] + ["user", "assistant", "system"]:
        vocab.setdefault("▁" + w, len(vocab))
    path = tmp_path / "tokenizer.json"
    path.write_text(json.dumps({"model": {"vocab": vocab}}))
    return HFTokenizer(str(path))


@pytest.mark.parametrize("which", ["hash", "hf"])
def test_declared_tokenizers_concatenate_at_white_space(which, tmp_path):
    """What the declaration promises: for a head that ends in white space,
    encode(head) + encode(tail) == encode(head + tail)."""
    tok = HashTokenizer(VOCAB) if which == "hash" else _hf_tokenizer(tmp_path)
    assert tok.SPLITS_AT_WHITESPACE
    rng = random.Random(8)
    pieces = WORDS[:50] + [" ", "\n", "\t", " ", "x.y", "a_b", "é", "!?"]
    for _ in range(200):
        head = "".join(rng.choice(pieces) for _ in range(rng.randrange(30)))
        head += rng.choice([" ", "\n", "\t\n", " "])
        tail = "".join(rng.choice(pieces) for _ in range(rng.randrange(30)))
        assert (tok.encode(head, add_special=False)
                + tok.encode(tail, add_special=False)
                == tok.encode(head + tail, add_special=False))


def test_prompt_and_head_are_the_benchmark_references():
    """The benchmark's chat reference rebuilds each prompt from its own copy
    of the preamble (``bench/fixtures/heimdall_preamble.txt``, what the
    assistant assembles for an empty graph) and caches its head: the
    program's prompt is that text, byte for byte, and names that head."""
    import os

    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "bench", "configs",
                           "assistant-1m-qwen2.5-0.5b.json")) as f:
        fmt = json.load(f)["generator"]["prompt"]
    with open(os.path.join(root, "bench", fmt["preamble_file"])) as f:
        preamble = f.read()
    db = nornicdb_tpu.open_db("")
    try:
        mgr = HeimdallManager(EngineGenerator(FakeEngine()), db=db)
        messages = _messages("the deployment's system message",
                             "a user's question\nover two lines")
        prompt = mgr._build_prompt(mgr.build_context(messages), messages)
    finally:
        db.close()
    head = fmt["head"].format(preamble=preamble,
                              system=messages[0]["content"])
    assert str(prompt) == head + fmt["tail"].format(
        user=messages[1]["content"])
    assert prompt.head == head + "\n"


def test_tokenize_stage_lands_on_the_request_trace():
    mgr, _ = _assistant()
    messages = _messages("the system message", "a question")
    for _ in range(2):
        with tracer.start_trace("test.chat") as root:
            list(mgr.chat_stream(messages, 4))
        spans = [s for s in root.trace.spans
                 if s["name"] == "heimdall.tokenize"]
        assert len(spans) == 1 and spans[0]["duration_ms"] >= 0
    assert spans[0]["attrs"]["cached_tokens"] > 0  # the second: a hit
    assert mgr.metrics_registry.get("tokenize_seconds") > 0


class TestMetricsExposition:
    @pytest.fixture
    def served(self):
        from nornicdb_tpu.server.http import HttpServer

        db = nornicdb_tpu.open_db("")
        server = HttpServer(db, port=0)
        server.start()
        yield db, server
        server.stop()
        db.close()

    def _scrape(self, port):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            text = resp.read().decode()
        return {line.split()[0]: float(line.split()[1])
                for line in text.splitlines()
                if line.startswith("heimdall_")}

    def test_way_in_counters_render_on_metrics(self, served):
        db, server = served
        mgr, engine = _assistant()
        db._heimdall = mgr
        system = _text(random.Random(9), 300)
        for i in range(3):
            list(mgr.chat_stream(_messages(system, f"question {i}"), 4))
        got = self._scrape(server.port)
        assert got["heimdall_prompt_head_hits"] == 2
        assert got["heimdall_prompt_head_misses"] == 1
        assert got["heimdall_prompt_tokens_cached"] > 0
        assert (got["heimdall_prompt_tokens_cached"]
                + got["heimdall_prompt_tokens_encoded"]
                == sum(map(len, engine.submitted)))
        assert got["heimdall_tokenize_seconds"] > 0

    def test_way_in_counters_stand_at_zero_without_a_tokenizing_backend(
            self, served):
        db, server = served
        db.heimdall.chat([{"role": "user", "content": "hello"}])
        got = self._scrape(server.port)
        for name in ("prompt_head_hits", "prompt_head_misses",
                     "prompt_tokens_cached", "prompt_tokens_encoded",
                     "tokenize_seconds"):
            assert got[f"heimdall_{name}"] == 0
