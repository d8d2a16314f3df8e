"""A builder's tool that touches no cell: ONE engine at the cell's weights
and options (no corpus, no embedder, no HTTP), the cell's own prompts, sound
or under one planted fault of ``faults_nemo.py``: the gap at EVERY produced
position of the compared streams (``bench/models/nemotron_h.py``
``position_gaps``), beside it the controls asked for, saved whole.  What
``greedy_gap_max`` of ``nemo-chat-sys4k`` was set from (PERF.md sections 2
and 6, PR 41): the harness prints one number a run, this prints where it
comes from.

    chiprun -- python3 bench/tests/nemo_gaps.py <fault|sound> <seed> \
        [streams compared] [rounds of 16 requests: the last is compared]
    NEMO_CONTROLS=fp8,bf16,bf16+routing   the reference in that precision,
        teacher-forced on what was served; ``+routing``: with every row's
        experts as the float32 forward chose them
    NEMO_F32_LAYERS=13   the PROGRAM in float32 at ``highest`` matmul
        precision on the first N layers at the published widths
    NEMO_REHEARSE=1 JAX_PLATFORMS=cpu   the control flow at the rehearsal's
        sizes

Appends a line to ``chiprun_out/nemo_gaps.jsonl`` and writes the arrays to
``chiprun_out/gaps/<fault>_<seed>.npz``."""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.dirname(HERE), HERE]

import numpy as np  # noqa: E402

import loadgen  # noqa: E402
import traffic  # noqa: E402

fault, seed = sys.argv[1], int(sys.argv[2])
n_check = int(sys.argv[3]) if len(sys.argv) > 3 else 8
rounds = int(sys.argv[4]) if len(sys.argv) > 4 else 1
if fault != "sound":
    import faults_nemo

    faults_nemo.FAULTS[fault]()
fam = loadgen.load_file("models/nemotron_h.py")
with open(os.path.join(
        ROOT, "bench/configs/assistant-1m-nemotron-3-nano-ep8.json")) as f:
    config = json.load(f)
with open(os.path.join(ROOT, "bench/workloads/chat-sys4k-closed16.json")) as f:
    cell = json.load(f)
spec, options = config["generator"], config["deployment"]["options"]
if os.environ.get("NEMO_REHEARSE"):
    spec = {**spec, **config["rehearsal"]["generator"]}
    options = {**options, **config["rehearsal"]["deployment"]["options"]}
    cell = {**cell, **cell["rehearsal"],
            "params": cell["rehearsal"]["params"]}

import jax  # noqa: E402

f32_layers = int(os.environ.get("NEMO_F32_LAYERS", 0))
if f32_layers:
    jax.config.update("jax_default_matmul_precision", "highest")
    spec = {**spec, "preset": None, "dtype": "float32",
            "num_layers": f32_layers, "hybrid_override_pattern":
            spec["hybrid_override_pattern"][:f32_layers]}
controls = tuple(m for m in os.environ.get("NEMO_CONTROLS", "").split(",")
                 if m)

from nornicdb_tpu.config import GenServeConfig  # noqa: E402
from nornicdb_tpu.genserve import GenerationEngine  # noqa: E402

t0 = time.time()
params = fam.make_params(spec, seed)
gs = GenServeConfig(**{k.split(".")[1]: v for k, v in options.items()
                       if k.startswith("genserve.")})
gs.deadline_ms = 0
engine = GenerationEngine(params, fam.program_config(spec), config=gs)
prime = fam.prompt_ids(
    spec, traffic.Stream(cell, seed, cell["clients"]).request(0))
engine.generate(prime, max_new_tokens=1)
t1 = time.time()
streams = [traffic.Stream(cell, seed, c) for c in range(cell["clients"])]
for rnd in range(rounds):  # from the second round on the lanes are re-seated
    prompts = [fam.prompt_ids(spec, s.request(rnd)) for s in streams]
    handles = [engine.submit(p, max_new_tokens=cell["params"]["max_tokens"])
               for p in prompts]
    outs = [h.result() for h in handles]
t2 = time.time()
stats = engine.stats_snapshot()
engine.stop()
order = sorted(range(len(prompts)), key=lambda i: -len(prompts[i]))
picks = [order[0]] + order[len(order) // 2:][:n_check - 1]
seqs = [(prompts[i], outs[i]) for i in picks]
gaps, low = fam.position_gaps(spec, params, seqs, controls)
t3 = time.time()
tag = f"{fault}_{seed}" + (f"_f32x{f32_layers}" if f32_layers else "")
os.makedirs(os.path.join(ROOT, "chiprun_out", "gaps"), exist_ok=True)
np.savez(os.path.join(ROOT, "chiprun_out", "gaps", tag + ".npz"),
         served=np.stack(gaps),
         prompt_lens=np.asarray([len(p) for p, _ in seqs]),
         **{"ctl_" + m: np.stack(v) for m, v in low.items()})


def brief(rows) -> dict:
    a = np.stack(rows)
    return {"max": round(float(a.max()), 4),
            "stream_mean_max": round(float(a.mean(1).max()), 5),
            "mean": round(float(a.mean()), 5),
            "nonzero": int((a > 0).sum()),
            "over_0.1": int((a > 0.1).sum()), "positions": int(a.size)}


line = {"tag": tag, "served": brief(gaps),
        **{"ctl_" + m: brief(v) for m, v in low.items()},
        "hits": stats["state_snapshot_hits"],
        "reused": stats["prefix_reused_tokens"],
        "snapshots": stats["state_snapshots_taken"],
        "dropped": stats["state_snapshots_dropped"],
        "setup_s": round(t1 - t0, 1), "serve_s": round(t2 - t1, 1),
        "check_s": round(t3 - t2, 1),
        "device": jax.devices()[0].device_kind}
print(json.dumps(line), flush=True)
with open(os.path.join(ROOT, "chiprun_out", "nemo_gaps.jsonl"), "a") as f:
    f.write(json.dumps(line) + "\n")
os._exit(0)
