"""``correct`` comes out false when the DeepSeek-V2 path is broken
underneath a whole run of ``dsv2-chat-sys4k`` (CPU backend, the
configuration's rehearsal sizes), and true when it is sound.

    python3 -m pytest bench/tests/test_faults_dsv2.py -q
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "dsv2-chat-sys4k"
FAULTS = ["wrong_group", "routed_dropped", "ckv_before_norm",
          "kpe_before_rope", "gates_renormalised"]


def drive(script_args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, *script_args, "--workload", CELL,
                          "--seed", "2147483659", "--seconds", "4", "--trace",
                          "0", "--rehearse-cpu"],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    result = drive([os.path.join(HERE, "..", "run.py")])
    assert result["correct"] is True, result["compared"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_caught(fault):
    result = drive([os.path.join(HERE, "faults_dsv2.py"), fault])
    assert result["correct"] is False
    failing = [n["name"] for n in result["compared"] if not n["ok"]]
    assert "greedy_gap_max" in failing, result["compared"]
