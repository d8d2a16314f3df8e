"""Distilled-encoder retrieval-quality delta — runs on CPU today.

The BGE_DISTILL_6L / 12L-512 serving presets exist to close the emb/s gap
to the >=10k north star, but their quality cost was never measured. The
retrieval-quality delta does NOT need the chip: teacher and students are trained in-image on the synthetic corpus and scored
with the eval harness (nornicdb_tpu/eval.py) on held-out augmented queries.

Structural mirror of the real presets (teacher here is the in-image 8L/128h
encoder — real bge-m3 weights cannot be mounted, zero egress):
  depth/4            — BGE_DISTILL_6L    (24L -> 6L)      ~ 8L -> 2L
  depth/2 + width/2  — BGE_DISTILL_12L_512 (24L,1024h -> 12L,512h) ~ 8L -> 4L,64h

Output: a markdown table  config x (P@1, MRR, NDCG, delta vs teacher,
cpu emb/s, speedup)  plus ONE JSON summary line. The emb/s column is
CPU-labeled and says nothing about the chip (on-chip embed throughput: not
measured).

Ref anchors: pkg/localllm/llama.go:635 (reference embed throughput),
neural/ training scripts (reference's offline dataset tooling).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _embed_corpus(embedder, texts, batch=32):
    vecs = []
    for i in range(0, len(texts), batch):
        vecs.append(np.asarray(embedder.embed_batch(texts[i:i + batch])))
    return np.concatenate(vecs, axis=0)


def _measure_emb_s(embedder, texts, reps=3):
    """Docs/sec through embed_batch on the current backend (best-of-reps)."""
    batch = texts[:32]
    embedder.embed_batch(batch)  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(embedder.embed_batch(batch))
        best = min(best, time.perf_counter() - t0)
    return len(batch) / best


def evaluate_checkpoint(model_dir, docs, queries, relevant_ids, k=10):
    """P@1/MRR/NDCG of doc retrieval with the checkpoint's embeddings."""
    from nornicdb_tpu.eval import EvalCase, Harness
    from nornicdb_tpu.models.pretrain import load_embedder

    emb = load_embedder(model_dir)
    doc_vecs = _embed_corpus(emb, docs)  # forward() L2-normalizes

    def search(query, topk):
        q = np.asarray(emb.embed_batch([query]))[0]
        scores = doc_vecs @ q
        order = np.argsort(-scores)[:topk]
        return [str(i) for i in order]

    cases = [EvalCase(q, [str(r)]) for q, r in zip(queries, relevant_ids)]
    report = Harness(search, k=k).run(cases)
    # P@1 = fraction of cases whose top hit is the relevant doc
    p_at_1 = sum(
        1.0 for c, r in zip(report.per_case, relevant_ids)
        if c["results"][:1] == [str(r)]
    ) / max(len(cases), 1)
    m = report.metrics
    return {"p_at_1": p_at_1, "mrr": m.mrr, "ndcg": m.ndcg,
            "emb_s_cpu": _measure_emb_s(emb, docs)}


def run(workdir, steps_teacher=500, steps_distill=400, quick=False,
        seed=0, lr_teacher=0.0):
    from nornicdb_tpu.models import pretrain

    rng = np.random.default_rng(seed + 1)
    texts = sorted(set(pretrain.synth_corpus(seed, repeats=10)))

    # held-out eval queries: word-dropout views of docs the models never
    # see in this augmented form (training uses its own rng stream)
    queries, relevant = [], []
    for i, doc in enumerate(texts):
        q = pretrain._augment(doc, rng, drop=0.3)
        if q.strip() and q != doc:
            queries.append(q)
            relevant.append(i)
    if quick:
        queries, relevant = queries[:24], relevant[:24]

    t_layers, t_hidden = (4, 64) if quick else (8, 128)
    # deeper teachers diverge at the shallow default lr (measured: 8L/128h
    # at 1e-3 went 2.52 -> 3.34 over 600 steps); scale down with depth
    lr = lr_teacher or (1e-3 if quick else 3e-4)
    teacher_dir = os.path.join(workdir, "teacher")
    t0 = time.perf_counter()
    t_stats = pretrain.train_encoder(
        teacher_dir, steps=steps_teacher, batch=32, hidden=t_hidden,
        layers=t_layers, dims=64 if not quick else 32, seed=seed,
        corpus=texts, lr=lr)
    print(f"teacher {t_layers}L/{t_hidden}h trained in "
          f"{time.perf_counter() - t0:.0f}s loss "
          f"{t_stats['loss_first']:.3f}->{t_stats['loss_last']:.3f}",
          file=sys.stderr, flush=True)

    students = {
        # depth/4 — mirror of BGE_DISTILL_6L (24L -> 6L)
        "depth4": dict(layers=max(t_layers // 4, 1)),
        # depth/2 + width/2 — mirror of BGE_DISTILL_12L_512
        "depth2_width2": dict(layers=max(t_layers // 2, 1),
                              hidden=t_hidden // 2),
    }
    rows = {}
    rows["teacher"] = evaluate_checkpoint(
        teacher_dir, texts, queries, relevant)
    rows["teacher"]["agreement"] = 1.0
    for name, kw in students.items():
        sdir = os.path.join(workdir, name)
        t0 = time.perf_counter()
        s_stats = pretrain.distill_encoder(
            teacher_dir, sdir, steps=steps_distill, batch=32, seed=seed,
            corpus=texts, **kw)
        print(f"student {name} distilled in {time.perf_counter() - t0:.0f}s "
              f"agreement={s_stats['agreement']:.3f}",
              file=sys.stderr, flush=True)
        rows[name] = evaluate_checkpoint(sdir, texts, queries, relevant)
        rows[name]["agreement"] = s_stats["agreement"]

    base = rows["teacher"]
    print("\n| config | P@1 | MRR | NDCG | dMRR vs teacher | "
          "emb/s (cpu) | speedup |")
    print("|---|---|---|---|---|---|---|")
    for name, r in rows.items():
        print(f"| {name} | {r['p_at_1']:.3f} | {r['mrr']:.3f} "
              f"| {r['ndcg']:.3f} | {r['mrr'] - base['mrr']:+.3f} "
              f"| {r['emb_s_cpu']:.0f} | "
              f"{r['emb_s_cpu'] / base['emb_s_cpu']:.2f}x |")
    summary = {
        "metric": "distill_quality_delta_mrr",
        "value": round(min(rows[n]["mrr"] - base["mrr"]
                           for n in students), 4),
        "unit": "delta_mrr_worst_student",
        "detail": {
            name: {k: round(v, 4) for k, v in r.items()}
            for name, r in rows.items()
        },
    }
    print(json.dumps(summary), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workdir", default="/tmp/nornicdb_distill_eval")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--steps-teacher", type=int, default=500)
    ap.add_argument("--steps-distill", type=int, default=400)
    args = ap.parse_args()
    # quality delta is backend-independent; pin the CPU backend in-process
    # before first backend use
    import jax

    jax.config.update("jax_platforms", "cpu")
    os.makedirs(args.workdir, exist_ok=True)
    run(args.workdir, steps_teacher=args.steps_teacher,
        steps_distill=args.steps_distill, quick=args.quick)


if __name__ == "__main__":
    main()
