"""A builder's tool that touches no cell: Nemotron 3 Nano's fused step ALONE
at the cell's sizes, under the profiler: device ms a step by named scope
(``ssm.*``, ``attn.*``, ``moe.*``; an XLA op's scope is read off the compiled
step's ``op_name``) for a mixed step (12 decode lanes near 5,000 tokens + a
52-token chunk) and a decode-only step (14 lanes), at synthetic tables (a
284-page shared run, as the cell's).  Not the served window: no scheduler,
no traffic; the rows do not add up to a traced run's step to the tenth.
What PERF.md section 5's by-scope table of ``nemo-chat-sys4k`` is from.

    chiprun -- python3 bench/tests/nemo_step_scopes.py
    (NEMO_REHEARSE=1 JAX_PLATFORMS=cpu: the control flow at the rehearsal's
    sizes; the CPU capture has no device plane, so every scope reads empty)

Writes ``chiprun_out/nemo_scopes.json``."""
import glob
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.dirname(HERE)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import loadgen  # noqa: E402
from nornicdb_tpu.models import nemotron_h as nh  # noqa: E402
from nornicdb_tpu.ragged import pack_ragged_meta  # noqa: E402

fam = loadgen.load_file("models/nemotron_h.py")
with open(os.path.join(
        ROOT, "bench/configs/assistant-1m-nemotron-3-nano-ep8.json")) as f:
    config = json.load(f)
spec = config["generator"]
REHEARSE = bool(os.environ.get("NEMO_REHEARSE"))
if REHEARSE:
    spec = {**spec, **config["rehearsal"]["generator"]}
cfg = fam.program_config(spec)
params = fam.make_params(spec, 3)
lmax, w, ps, slots, pages_n = 18, (512, 1), 16, 82, 8193
cases = [("mixed", 12, 52, 5000), ("decode", 14, 0, 5000)]
if REHEARSE:
    lmax, w, slots, pages_n = 6, (64, 1), 22, 257
    cases = [("mixed", 3, 20, 300), ("decode", 4, 0, 300)]
pools = nh.init_pages(cfg, (pages_n, slots), ps)
SCOPES = ("ssm.project", "ssm.conv", "ssm.scan", "ssm.gate", "ssm.out",
          "attn.project", "attn.attend", "moe.route", "moe.experts",
          "moe.shared")


def meta_for(ndec: int, n_chunk: int, length: int):
    """``ndec`` decode lanes at ``length`` tokens behind one shared run of
    pages, each on a state slot of its own, and a chunk lane of ``n_chunk``
    rows that reads one slot and writes another."""
    tq = 64 if n_chunk else 1
    f = 8
    while f < ndec + n_chunk:
        f *= 2
    meta, (tok, lane, lpos, pos, rows, tables) = pack_ragged_meta(lmax, w, f)
    tok[:], lane[:], lpos[:], pos[:], rows[:] = 5, lmax - 1, 0, -1, 0
    for kt in tables:
        kt.base[:], kt.pages[:] = 0, 0
    n_shared = min(284, length // ps - 2)
    shared = np.arange(1, 1 + n_shared)
    npg = length // ps + 1
    for i in range(ndec):
        tok[i], lane[i], pos[i], rows[i] = 7 + i, i, length + i, i
        tables[0].pages[i, :n_shared] = shared
        tables[0].pages[i, n_shared:npg + 1] = (
            300 + i * 80 + np.arange(npg + 1 - n_shared)) % (pages_n - 1) + 1
        tables[1].base[i], tables[1].pages[i, 0] = 1 + i, 1 + i
    for j in range(n_chunk):
        r = ndec + j
        tok[r], lane[r], lpos[r], pos[r] = 9, lmax - 2, j, n_shared * ps + j
    if n_chunk:
        tables[0].pages[lmax - 2, :n_shared] = shared
        tables[0].pages[lmax - 2, n_shared:n_shared + 8] = (
            4000 + np.arange(8)) % (pages_n - 1) + 1
        tables[1].base[lmax - 2] = slots - 2
        tables[1].pages[lmax - 2, 0] = slots - 1
        rows[ndec] = ndec + n_chunk - 1
    return jnp.asarray(meta), tq


def scopes_of(meta, tq: int, prev) -> dict:
    """HLO instruction name -> the named scope its ``op_name`` metadata
    holds, read off the compiled step's text."""
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    p, pg = jax.tree.map(shape, (params, pools))
    text = nh.fused_step.lower(
        p, cfg, shape(meta), pg, lmax=lmax, w=w, tq=tq,
        prev=shape(prev)).compile().as_text()
    table = {}
    for m in re.finditer(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", text,
            re.M):
        hit = [sc for sc in SCOPES if sc in m.group(2)]
        if hit:
            table[m.group(1)] = hit[-1]
    return table


def scope_seconds(tdir: str, table: dict) -> dict:
    """Device seconds of the capture's XLA ops, by scope."""
    from jax.profiler import ProfileData

    total = {}
    for path in glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if not re.match(r"^/device:TPU:\d+$", plane.name):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    text = ev.name + " " + " ".join(
                        f"{k}={v}" for k, v in ev.stats)
                    hit = [s for s in SCOPES if s in text]
                    instr = re.sub(r"^%", "", ev.name.split(" ")[0])
                    name = hit[-1] if hit else table.get(instr) or (
                        "while" if "while" in ev.name else "other")
                    total[name] = total.get(name, 0.0) + ev.duration_ns / 1e9
    return total


def step(meta, tq, prev):
    global pools
    ints, _, pools = nh.fused_step(params, cfg, meta, pools, lmax=lmax, w=w,
                                   tq=tq, prev=prev)
    return ints


prev = jnp.zeros((lmax + len(nh.STEP_COUNTERS),), jnp.int32)
out, n = {}, 20
for name, ndec, n_chunk, length in cases:
    meta, tq = meta_for(ndec, n_chunk, length)
    for _ in range(3):
        ints = step(meta, tq, prev)
    ints.block_until_ready()
    tdir = os.path.join(ROOT, ".bench_scratch", "scope_" + name)
    t0 = time.perf_counter()
    jax.profiler.start_trace(tdir)
    for _ in range(n):
        ints = step(meta, tq, prev)
    ints.block_until_ready()
    jax.profiler.stop_trace()
    wall = (time.perf_counter() - t0) / n
    secs = scope_seconds(tdir, scopes_of(meta, tq, prev))
    out[name] = {"wall_ms_a_step": round(wall * 1e3, 3),
                 "ms_a_step_by_scope": {
                     k: round(v / n * 1e3, 4) for k, v in
                     sorted(secs.items(), key=lambda kv: -kv[1])}}
    print(json.dumps({name: out[name]}), flush=True)
os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
with open(os.path.join(ROOT, "chiprun_out", "nemo_scopes.json"), "w") as f:
    json.dump({"device": jax.devices()[0].device_kind, **out}, f, indent=1)
os._exit(0)
