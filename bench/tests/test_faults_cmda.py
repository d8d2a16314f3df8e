"""``correct`` comes out false when the Command A+ path is broken underneath
a whole run of ``cmda-chat-sys6k`` (CPU backend, the configuration's
rehearsal sizes: a window of 128 under contexts of ~900), on two seeds
each, and true when it is sound.

    python3 -m pytest bench/tests/test_faults_cmda.py -q
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "cmda-chat-sys6k"
SEEDS = ["2147483659", "3000000019"]
FAULTS = ["window_ignored", "rope_in_full", "shared_summed", "held_dropped",
          "gates_unnormalised", "other_kinds_pool", "page_released_early"]


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
    result = drive([os.path.join(HERE, "faults_cmda.py"), fault], seed)
    assert result["correct"] is False
    failing = [n["name"] for n in result["compared"] if not n["ok"]]
    assert "greedy_gap_max" in failing, result["compared"]
