"""What the work NEEDS: operations and bytes from the configuration and the
counts of completed operations, never from the program's arrays.  The
yardstick stays the same whatever later implements the work.

Every function returns ``{"flops": ..., "bytes": ...}``; :func:`least_seconds`
turns that into the least time one chip could take and says which bound it.
What a model's tokens need is in its family file (``bench/models/``).
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BYTES_OF = {"bf16": 2, "bfloat16": 2, "f32": 4, "float32": 4, "int8": 1}


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json: add them with their source")
    return table[device_kind]


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    compute = work["flops"] / peaks["bf16_flops"]
    memory = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def topk_scans(config: dict, scans: int, queries: int) -> dict:
    """``scans`` brute-force passes over the corpus that answer ``queries``
    queries between them: each pass reads every row once at the stated score
    precision, each query multiplies against every row."""
    c = config["corpus"]
    row_bytes = c["dims"] * BYTES_OF[c["score_dtype"]]
    return {"flops": 2.0 * queries * c["rows"] * c["dims"],
            "bytes": float(scans) * c["rows"] * row_bytes}


def topk_queries(config: dict, queries: int) -> dict:
    """``queries`` answered, however they were grouped: the algorithm needs
    their multiplies, and the corpus read at least once."""
    return topk_scans(config, 1 if queries else 0, queries)
