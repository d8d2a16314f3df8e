"""ACTION-MODE generalization: the trained in-image assistant must emit
machine-parseable {"action": ...} JSON for database-operation prompts it has
NEVER seen (ref: pkg/heimdall/handler.go:516 tryParseAction; scheduler.go:178
serves a real Qwen — this is the zero-egress analogue with a measured rate).

The corpus splits phrasing x label combinations: training sees every
phrasing and every label, but 20 specific pairings are held out
(pretrain.action_eval_cases), so passing requires compositional
generalization, not memorization.

Micro settings here keep suite time bounded.
"""

import json
import os
import re
import urllib.request

import numpy as np
import pytest

import nornicdb_tpu
from nornicdb_tpu.heimdall.manager import HeimdallManager
from nornicdb_tpu.models import pretrain


def _norm(s: str) -> str:
    return re.sub(r"\s+", "", s)


@pytest.fixture(scope="module")
def action_ckpt(tmp_path_factory):
    """Measured on this preset (CPU, an earlier round): parse 56/57, exact 56/57
    held-out, chat-e2e 37/56; ~3.5 min on one CPU core."""
    out = str(tmp_path_factory.mktemp("assistant_actions"))
    corpus = (pretrain.synth_corpus(0, repeats=6)
              + pretrain.synth_action_corpus(0, repeats=6))
    stats = pretrain.train_assistant(
        out, steps=1200, batch=16, seq_len=64, hidden=128, corpus=corpus,
    )
    return out, stats


class TestActionCorpus:
    def test_holdout_split_is_compositional(self):
        """Held-out pairs never appear in training lines, but every
        phrasing template and every label does appear somewhere."""
        train = "\n".join(pretrain.synth_action_corpus(0, repeats=1))
        cases = pretrain.action_eval_cases()
        assert len(cases) >= 15
        for c in cases:
            assert f"user: {c['prompt']} " not in train
        for _, templates, _ in pretrain._ACTION_INTENTS:
            for tpl in templates:
                stem = tpl.split("{l}")[0].strip()
                assert stem in train, stem
        for label in pretrain._ACTION_LABELS:
            assert label in train

    def test_action_json_roundtrips_tokenizer(self):
        """Corpus action lines survive encode->decode->try_parse_action.
        (The corpus also carries serving-preamble lines with no action —
        only the action-bearing lines must round-trip.)"""
        corpus = pretrain.synth_action_corpus(0, repeats=1)
        tok = pretrain.VocabTokenizer.from_corpus(corpus)
        action_lines = [ln for ln in corpus if '" action "' in ln]
        assert len(action_lines) >= 40
        for line in action_lines[:40]:
            dec = tok.decode(tok.encode(line, add_special=False))
            a = HeimdallManager.try_parse_action(dec)
            assert a is not None, dec
            assert a["action"] in ("query", "status")

    def test_spaced_json_parse_preserves_interior_spaces(self):
        spaced = ('{ " action " : " query " , " params " : '
                  '{ " cypher " : " match ( n ) return n " } }')
        a = HeimdallManager.try_parse_action(spaced)
        assert a == {"action": "query",
                     "params": {"cypher": "match ( n ) return n"}}

    def test_exact_json_still_parses_first(self):
        a = HeimdallManager.try_parse_action(
            'preamble {"action": "status", "params": {}} trailer')
        assert a == {"action": "status", "params": {}}


class TestHeldOutActionRate:
    def test_parse_and_correctness_rate(self, action_ckpt):
        """The STATED RATE contract: >=90% of held-out prompts parse to the
        right action type, and >=80% produce the exact intended Cypher
        (whitespace-insensitive). Measured on this preset: 98%/98%."""
        from nornicdb_tpu.config import GenServeConfig
        from nornicdb_tpu.heimdall import EngineGenerator

        out, _ = action_ckpt
        # the served path: the checkpoint behind a generation engine, the
        # held-out prompts in one continuous batch
        gen = EngineGenerator.serving(
            pretrain.load_generator(out),
            config=GenServeConfig(deadline_ms=0, max_queue=128))
        cases = pretrain.action_eval_cases()
        try:
            texts = gen.generate_many(
                [f"user: {c['prompt']} assistant:" for c in cases],
                max_tokens=64)
        finally:
            gen.engine.stop()
        parsed = correct = 0
        for c, text in zip(cases, texts):
            a = HeimdallManager.try_parse_action(text)
            if a is None or a.get("action") != c["action"]:
                continue
            parsed += 1
            if c["action"] == "status":
                correct += 1
            else:
                got = _norm(str((a.get("params") or {}).get("cypher", "")))
                correct += got == _norm(c["cypher"])
        n = len(cases)
        assert parsed / n >= 0.90, f"parse rate {parsed}/{n}"
        assert correct / n >= 0.80, f"correct rate {correct}/{n}"


class TestChatE2E:
    def test_chat_executes_learned_query_action(self, action_ckpt):
        """Full stack on an unseen prompt: /v1/chat/completions ->
        trained decode -> try_parse_action -> read-only query dispatch ->
        action_result rows from real storage."""
        from nornicdb_tpu.server import HttpServer

        out, _ = action_ckpt
        os.environ["NORNICDB_ASSISTANT_MODEL"] = out
        try:
            db = nornicdb_tpu.open_db("")
            for i in range(3):
                db.cypher(f"create ( n : person {{ idx : {i} }} )")
            server = HttpServer(db, port=0)
            server.start()
            try:
                cases = [c for c in pretrain.action_eval_cases()
                         if c["action"] == "query"]
                hits = 0
                for c in cases:
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{server.port}/v1/chat/completions",
                        data=json.dumps({
                            "messages": [
                                {"role": "user", "content": c["prompt"]}],
                            "max_tokens": 64,
                        }).encode(),
                        headers={"Content-Type": "application/json"},
                        method="POST",
                    )
                    body = json.loads(urllib.request.urlopen(req).read())
                    ar = body.get("action_result")
                    if ar is not None and "error" not in ar:
                        hits += 1
                # the big serving context prompt is harder than the raw
                # generator path (measured 66% on this preset); the
                # contract is a stated rate with wide margin
                assert hits / len(cases) >= 0.40, f"{hits}/{len(cases)}"
            finally:
                server.stop()
                db.close()
        finally:
            os.environ.pop("NORNICDB_ASSISTANT_MODEL", None)
