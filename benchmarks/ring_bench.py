"""Ring-attention scaling profile on the virtual device mesh.

The long-context story (SURVEY aux: ring/sequence parallelism) in numbers:
dense attention materializes an O(T^2) score matrix per device, ring
attention holds one (T/P x T/P) block and streams K/V shards around the
ICI ring — per-device activation memory stays O(T^2/P^2) while results
stay numerically equal to dense (asserted here at every point).

Runs on the 8-device virtual CPU mesh, so WALL TIMES are not TPU numbers —
the measured quantities that transfer are the peak per-device score-block
FOOTPRINT (analytic, printed per config) and the parity check. On-chip
timing: not measured.

Run: python benchmarks/ring_bench.py [--devices 8]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()

    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from nornicdb_tpu.parallel import (
        make_mesh,
        make_ring_attention,
        reference_attention,
    )

    p = args.devices
    mesh = make_mesh({"seq": p})
    ring = make_ring_attention(mesh, "seq", causal=True)
    h, dh, b = 4, 32, 1
    rng = np.random.default_rng(0)

    print(f"devices={p} heads={h} head_dim={dh}")
    print("| T | dense score MB/dev | ring block MB/dev | ratio | "
          "max |err| vs dense | wall ms (cpu mesh) |")
    print("|---|---|---|---|---|---|")
    rows = []
    for t in (512, 1024, 2048, 4096):
        q = (rng.standard_normal((b, t, h, dh)) * 0.3).astype(np.float32)
        k = (rng.standard_normal((b, t, h, dh)) * 0.3).astype(np.float32)
        v = (rng.standard_normal((b, t, h, dh)) * 0.3).astype(np.float32)
        out = np.asarray(ring(q, k, v))  # compile + run
        t0 = time.perf_counter()
        out = np.asarray(ring(q, k, v))
        wall_ms = (time.perf_counter() - t0) * 1000
        err = float(np.max(np.abs(
            out - np.asarray(reference_attention(q, k, v, causal=True)))))
        dense_mb = b * h * t * t * 4 / 2**20            # full (T, T) scores
        ring_mb = b * h * (t // p) * (t // p) * 4 / 2**20  # one block
        rows.append({"T": t, "dense_mb": round(dense_mb, 1),
                     "ring_mb": round(ring_mb, 2),
                     "max_err": err, "wall_ms": round(wall_ms, 1)})
        print(f"| {t} | {dense_mb:.1f} | {ring_mb:.2f} | {p*p}x "
              f"| {err:.2e} | {wall_ms:.1f} |", flush=True)
        assert err < 5e-3, f"ring attention diverged at T={t}"
    print(json.dumps({
        "metric": "ring_attention_score_memory_ratio",
        "value": p * p,
        "unit": "x smaller per-device score block vs dense",
        "detail": {"devices": p, "rows": rows},
    }))


if __name__ == "__main__":
    main()
