"""GraphRAG answer pipeline: graph-context retrieval -> packed prompt ->
generation.

The shape follows the on-device RAG system paper (PAPERS.md): retrieval
and generation share one latency budget, so the pipeline is strictly
bounded — vector+hybrid search over the existing search service, ONE hop
of graph expansion over the storage adjacency, a token-budgeted prompt
pack, then a deadline-carrying submit into the continuous-batching
generation engine.  Served at ``POST /nornicdb/rag/answer``.

Without generation weights (no assistant checkpoint, template Heimdall)
the pipeline still answers extractively from the retrieved context — the
same graceful degradation the reference's stub builds apply to chat.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Optional

from nornicdb_tpu.errors import NotFoundError

logger = logging.getLogger(__name__)

# Standardized instruction preamble shared VERBATIM by every GraphRAG
# prompt: its token ids are identical across requests, so the engine's
# shared-prefix KV cache turns the whole block into page-table hits
# after the first request — deliberately long enough to span multiple
# KV pages at the default page_size. Keep it byte-stable: any edit
# invalidates every cached prefix page at once.
_PROMPT_HEADER = (
    "You are the NornicDB graph assistant. Answer the question strictly "
    "from the graph context below; do not invent nodes, relationships, "
    "or properties that are not present. Context lines are ranked most "
    "relevant first and each one is prefixed with its node id in square "
    "brackets. Relationship lines describe directed edges between node "
    "ids in the form start -TYPE-> end. Prefer information from "
    "higher-ranked lines when sources conflict, cite node ids where "
    "they support the answer, and if the context does not contain the "
    "answer, say so plainly instead of guessing. Be concise.\n"
)


def _snippet(node, limit: int = 200) -> str:
    content = str(node.properties.get("content", "")) if node.properties \
        else ""
    if not content:
        content = " ".join(
            f"{k}={v}" for k, v in list((node.properties or {}).items())[:4])
    return content[:limit]


class GraphRAGService:
    """Retrieve graph context for a question and generate an answer."""

    def __init__(self, db, engine=None, config=None):
        if config is None:
            from nornicdb_tpu.genserve import current_config

            config = current_config()
        self.db = db
        self._engine = engine
        self.config = config

    def _resolve_engine(self):
        if self._engine is not None:
            return self._engine
        getter = getattr(self.db, "genserve_engine", None)
        return getter() if getter is not None else None

    # -- retrieval ---------------------------------------------------------
    def retrieve(self, question: str, limit: int) -> tuple[list, list]:
        """Top-k hybrid search hits + ONE hop of graph expansion around
        them (the relationship lines ground the generation in topology,
        not just text)."""
        hits = self.db.recall(question, limit=limit)
        edges = []
        seen_edges = set()
        storage = self.db.storage
        for h in hits[:limit]:
            nid = h["id"]
            try:
                out_edges = storage.get_outgoing_edges(nid)
                in_edges = storage.get_incoming_edges(nid)
            except (NotFoundError, NotImplementedError):
                continue
            for e in (out_edges + in_edges)[:8]:
                if e.id in seen_edges:
                    continue
                seen_edges.add(e.id)
                edges.append(e)
        return hits[:limit], edges

    # -- prompt packing ----------------------------------------------------
    def build_prompt(self, question: str, hits: list, edges: list,
                     budget_tokens: int) -> str:
        """Greedy token-budgeted pack: highest-scoring snippets first,
        then relationship lines, truncated to the engine's context bound
        (estimate_tokens-style whitespace accounting — the engine trims
        the tail again defensively)."""
        lines = [_PROMPT_HEADER, "Context:"]
        spent = sum(len(ln.split()) for ln in lines)
        for h in hits:
            node = h.get("node")
            text = _snippet(node) if node is not None else \
                str(h.get("content", ""))[:200]
            line = f"- [{h['id']}] {text}"
            cost = len(line.split())
            if spent + cost > budget_tokens:
                break
            lines.append(line)
            spent += cost
        if edges:
            lines.append("Relationships:")
            spent += 1
            for e in edges:
                line = f"- {e.start_node} -{e.type}-> {e.end_node}"
                cost = len(line.split())
                if spent + cost > budget_tokens:
                    break
                lines.append(line)
                spent += cost
        lines.append(f"Question: {question}")
        lines.append("Answer:")
        return "\n".join(lines)

    # -- the pipeline ------------------------------------------------------
    def answer(self, question: str, limit: Optional[int] = None,
               max_new_tokens: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> dict[str, Any]:
        t0 = time.perf_counter()
        limit = int(limit or self.config.rag_context_nodes)
        max_new = int(max_new_tokens or self.config.rag_max_new_tokens)
        hits, edges = self.retrieve(question, limit)
        t_retrieve = time.perf_counter() - t0
        engine = self._resolve_engine()
        budget = max(
            32, int(self.config.max_seq_tokens) - max_new - 8)
        prompt = self.build_prompt(question, hits, edges, budget)
        generated = 0
        prefix_reused = 0
        if engine is not None:
            handle = engine.submit(
                engine.tokenizer.encode(prompt, add_special=False),
                max_new_tokens=max_new, deadline_ms=deadline_ms)
            answer = handle.text()  # ResourceExhausted -> 429 at the edge
            generated = len(handle.tokens)
            prefix_reused = getattr(handle, "prefix_reused_tokens", 0)
            mode = "paged"  # API: the engine's one way to serve
        else:
            # extractive fallback: no generation weights mounted — answer
            # from the retrieved context so the endpoint (and its tests /
            # soak traffic) stays functional, like the template assistant
            if hits:
                answer = "Based on the graph context:\n" + "\n".join(
                    f"- {_snippet(h['node']) if h.get('node') is not None else h.get('content', '')}"
                    for h in hits[:3])
            else:
                answer = "No matching graph context was found."
            mode = "extractive"
        return {
            "answer": answer,
            "mode": mode,
            "sources": [
                {"id": h["id"], "score": round(float(h.get("score", 0.0)), 6),
                 "content": str(h.get("content", ""))[:200]}
                for h in hits
            ],
            "context": {
                "nodes": len(hits),
                "edges": len(edges),
                "prompt_tokens_est": len(prompt.split()),
            },
            "generated_tokens": generated,
            "prefix_reused_tokens": prefix_reused,
            "timings_ms": {
                "retrieve": round(t_retrieve * 1e3, 3),
                "total": round((time.perf_counter() - t0) * 1e3, 3),
            },
        }
