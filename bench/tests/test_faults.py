"""``correct`` comes out false when the timed path is broken underneath a
whole run (CPU backend, the configurations' rehearsal sizes; the harness's
look for a chip is skipped by ``--rehearse-cpu``, everything else runs).

    python3 -m pytest bench/tests -q
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CASES = [("wrong_ids", "vec-search-k100", "recall_at_k_mean"),
         ("wrong_scores", "vec-search-k100", "score_err_max"),
         ("wrong_vectors", "mem-embed-query", "embed_dist_max"),
         ("coarse_vectors", "mem-embed-query", "embed_dist_max"),
         ("shifted_token", "mem-chat-sys4k", "greedy_gap_max"),
         ("stale_prefix_page", "mem-chat-sys4k", "greedy_gap_max"),
         ("decode_position_off", "mem-chat-sys4k", "greedy_gap_max"),
         ("fp8_weights", "mem-chat-sys4k", "greedy_gap_max")]


def drive(script_args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, *script_args, "--seed", "2147483659",
                          "--seconds", "4", "--trace", "0", "--rehearse-cpu"],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["vec-search-k100", "mem-embed-query",
                                  "mem-chat-sys4k"])
def test_sound_run_is_correct(cell):
    result = drive([os.path.join(HERE, "..", "run.py"), "--workload", cell])
    assert result["correct"] is True, result["compared"]
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("fault,cell,number", CASES)
def test_fault_is_caught(fault, cell, number):
    result = drive([os.path.join(HERE, "faults.py"), fault, "--workload",
                    cell])
    assert result["correct"] is False
    failing = [n["name"] for n in result["compared"] if not n["ok"]]
    assert number in failing, result["compared"]
