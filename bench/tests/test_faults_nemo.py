"""``correct`` comes out false when the Nemotron 3 Nano path is broken
underneath a whole run of ``nemo-chat-sys4k`` (CPU backend, the
configuration's rehearsal sizes: a 704-token shared head whose hit ends on
a state snapshot), on two seeds each, and true when it is sound.

    python3 -m pytest bench/tests/test_faults_nemo.py -q
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "nemo-chat-sys4k"
SEEDS = ["2147483693", "3000000019"]
FAULTS = ["snapshot_a_chunk_early", "conv_state_dropped_at_a_hit",
          "state_not_reset_on_reseat", "padding_row_advances",
          "dt_bias_left_out", "gates_unscaled", "gates_unnormalised",
          "shared_dropped", "held_dropped", "rope_in_attention"]


def drive(script_args, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, *script_args, "--workload", CELL,
                          "--seed", seed, "--seconds", "4", "--trace",
                          "0", "--rehearse-cpu"],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_is_correct(seed):
    result = drive([os.path.join(HERE, "..", "run.py")], seed)
    assert result["correct"] is True, result["compared"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_caught(fault, seed):
    result = drive([os.path.join(HERE, "faults_nemo.py"), fault], seed)
    assert result["correct"] is False
    failing = [n["name"] for n in result["compared"] if not n["ok"]]
    assert "greedy_gap_max" in failing, result["compared"]
