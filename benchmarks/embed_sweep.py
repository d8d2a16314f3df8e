"""Short-seq bge-m3 throughput sweep on the real chip.

Sweeps the T=64/128/256 rows: random ids at the target length, bf16 params,
4 scan iterations per timed call, best-of-3.  The scanned body is loop
invariant, so XLA may compute it once (ROADMAP "What the record bears
out"): its figures are not a throughput.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.models.bge_m3 import BgeConfig, forward, init_params


def measure(cfg: BgeConfig, params, B: int, T: int, iters: int = 4, reps: int = 3):
    ids = jnp.asarray(np.random.randint(0, cfg.vocab_size, (B, T)), jnp.int32)
    mask = jnp.ones((B, T), jnp.int32)

    @jax.jit
    def run(ids, mask):
        def body(c, _):
            out = forward(params, cfg, ids, mask)
            return c + out.mean(), None
        acc, _ = jax.lax.scan(body, jnp.float32(0), None, length=iters)
        return acc

    _ = np.asarray(run(ids, mask))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _ = np.asarray(run(ids, mask))  # D2H fence
        best = min(best, (time.perf_counter() - t0) / iters)
    toks = B * T / best
    return toks, toks / T  # tok/s, emb/s at this doc length


def sweep(name: str, cfg: BgeConfig, grid):
    params = init_params(cfg, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    print(f"\n## {name} ({cfg.layers}L/{cfg.hidden}h)", flush=True)
    print("| B | T | tok/s | emb/s |", flush=True)
    print("|---|---|---|---|", flush=True)
    for B, T in grid:
        try:
            toks, embs = measure(cfg, params, B, T)
            print(f"| {B} | {T} | {toks/1e3:.1f}k | {embs:.0f} |", flush=True)
        except Exception as e:  # OOM etc. — record and continue
            print(f"| {B} | {T} | ERR {type(e).__name__} | - |", flush=True)


def main():
    from nornicdb_tpu.models.bge_m3 import BGE_DISTILL_6L, BGE_DISTILL_12L_512

    print(f"device={jax.devices()[0]}", flush=True)
    # teacher short-seq grid
    sweep("bge-m3 teacher", BgeConfig(),
          [(B, T) for T in (64, 128, 256) for B in (32, 64, 128)
           if B * T <= 32 * 512 * 2])
    # distilled serving shapes (VERDICT item 6): measure the emb/s the
    # small-encoder path buys at the 512-token north-star length
    for name, cfg in (("distill-6L", BGE_DISTILL_6L),
                      ("distill-12L-512h", BGE_DISTILL_12L_512)):
        sweep(name, cfg, [(32, 512), (64, 512), (128, 128), (64, 128)])


if __name__ == "__main__":
    main()
