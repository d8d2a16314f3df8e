"""On-chip autotune for the streaming top-k serving kernels.

Sweeps (path, tile_n, rows, epilogue, query-chunk) at the bench shape
(N=1M, D=1024, K=100, batch 1024) and prints one table row per config:
ms/batch (best-of-5, timed through the copy back to the host) + recall vs
exact ground truth on a sampled query set. Needs a chip; the winner gets
wired into the DeviceCorpus defaults.

Usage: python benchmarks/kernel_autotune.py [--quick]
"""

from __future__ import annotations

import argparse
import functools
import sys
import time

N = 1_000_000
D = 1024
K = 100
BATCH = 1024
ITERS = 8  # per timing call; best-of-5 calls


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer configs")
    ap.add_argument("--iters", type=int, default=ITERS)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from nornicdb_tpu.ops import l2_normalize
    from nornicdb_tpu.ops.pallas_kernels import (
        quantize_rows,
        streaming_cosine_topk,
        streaming_cosine_topk_int8,
    )

    dev = jax.devices()[0]
    print(f"device: {dev}", file=sys.stderr)
    on_tpu = dev.platform == "tpu"
    if not on_tpu:
        print("WARNING: not on TPU; timings meaningless", file=sys.stderr)

    tile0 = 512
    np_rows = ((N + tile0 - 1) // tile0) * tile0
    # pad to a multiple of 1024 too so tile_n=1024 divides
    np_rows = ((np_rows + 1023) // 1024) * 1024

    @jax.jit
    def make_corpus(key):
        return l2_normalize(jax.random.normal(key, (np_rows, D), jnp.bfloat16))

    corpus = make_corpus(jax.random.PRNGKey(0))
    valid = jnp.arange(np_rows) < N
    # per-iteration query batches: a loop-INVARIANT scan body would be
    # hoisted by XLA and only run once, wrecking the timing
    qbs = l2_normalize(
        jax.random.normal(
            jax.random.PRNGKey(1), (args.iters, BATCH, D), jnp.bfloat16
        )
    )
    qb = qbs[0]
    c_i8, c_scale = quantize_rows(corpus)
    qi_flat, qs_flat = quantize_rows(qbs.reshape(args.iters * BATCH, D))
    qi_s = qi_flat.reshape(args.iters, BATCH, D)
    qs_s = qs_flat.reshape(args.iters, BATCH)

    # ground truth only on the rows recall_of samples (every 64th query):
    # a full (BATCH, N) f32 score matrix would be ~4 GB of HBM for nothing
    sample = np.arange(0, BATCH, 64)

    @jax.jit
    def exact(qb, corpus, valid):
        s = jax.lax.dot_general(
            qb, corpus, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = jnp.where(valid[None, :], s, -jnp.inf)
        return jax.lax.top_k(s, K)

    _, gt_idx = exact(qb[sample], corpus, valid)
    gt = np.asarray(gt_idx)

    def timed(fn):
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            v = fn()
            np.asarray(v)  # the copy back to the host ends the timed work
            times.append(time.perf_counter() - t0)
        return min(times)

    def recall_of(idx):
        idx = np.asarray(idx)
        return float(np.mean(
            [len(set(idx[r]) & set(gt[j])) / K
             for j, r in enumerate(sample)]
        ))

    configs = []
    tiles = [(512, 4), (512, 2), (1024, 2), (1024, 1)]
    eps = ["sort", "approx", "pallas"]
    if args.quick:
        tiles = [(512, 4), (1024, 2)]
        eps = ["sort", "pallas"]
    for tile_n, rows in tiles:
        for ep in eps:
            configs.append((tile_n, rows, ep))

    print(f"{'path':<5} {'tile':>5} {'rows':>4} {'epilogue':<7} "
          f"{'ms/batch':>9} {'qps':>8} {'recall':>7}")
    results = []
    for path in ("int8", "bf16"):
        for tile_n, rows, ep in configs:
            if np_rows % tile_n:
                continue
            try:
                if path == "bf16":
                    call = functools.partial(
                        streaming_cosine_topk, k=K, tile_n=tile_n,
                        rows=rows, epilogue=ep, interpret=not on_tpu)

                    @jax.jit
                    def fn(qbs, corpus, valid, call=call):
                        def step(c, q):
                            return c, call(q, corpus, valid)[1]
                        _, out = jax.lax.scan(step, 0, qbs)
                        return out

                    xs = (qbs, corpus, valid)
                else:
                    call = functools.partial(
                        streaming_cosine_topk_int8, k=K, tile_n=tile_n,
                        rows=rows, epilogue=ep, interpret=not on_tpu)

                    @jax.jit
                    def fn(qi_s, qs_s, c_i8, c_scale, valid, call=call):
                        def step(c, qc):
                            qi, qsc = qc
                            return c, call(qi, qsc, c_i8, c_scale, valid)[1]
                        _, out = jax.lax.scan(step, 0, (qi_s, qs_s))
                        return out

                    xs = (qi_s, qs_s, c_i8, c_scale, valid)
                idx = fn(*xs)          # compile + correctness
                rec = recall_of(np.asarray(idx)[0])
                dt = timed(lambda: fn(*xs)) / args.iters
                qps = BATCH / dt
                print(f"{path:<5} {tile_n:>5} {rows:>4} {ep:<7} "
                      f"{dt * 1e3:>9.3f} {qps:>8.0f} {rec:>7.3f}", flush=True)
                results.append((path, tile_n, rows, ep, dt, rec))
            except Exception as e:
                print(f"{path:<5} {tile_n:>5} {rows:>4} {ep:<7} "
                      f"FAILED: {type(e).__name__}: {str(e)[:120]}",
                      flush=True)
    if results:
        best = min((r for r in results if r[5] >= 0.95),
                   key=lambda r: r[4], default=None)
        if best:
            print(f"\nbest (recall>=0.95): {best[0]} tile={best[1]} "
                  f"rows={best[2]} ep={best[3]} "
                  f"{best[4]*1e3:.2f} ms/batch = {BATCH/best[4]:.0f} qps")


if __name__ == "__main__":
    main()
