"""The mesh cell's whole run at a small size on four CPU devices
(tests/bench/mesh_cpu.py, in a process of its own): ``correct`` is true
for the program as it is and false for every fault the cell can have,
the exchange between chips left out among them."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.spec import ROOT, load_json

MESH_CELLS = [w["name"] for w in load_json(ROOT / "BENCHMARK.json")["workloads"]
              if w["chips"] == 4]


@pytest.mark.parametrize("name", MESH_CELLS)
def test_mesh_cell_faults_are_not_correct(name):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run([sys.executable, str(Path(__file__).with_name("mesh_cpu.py")),
                           name], env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["sound"], out["sound_checks"]
    for fault in ("unchanged_state", "half_batch", "half_batch_clients",
                  "altered_answer", "no_exchange"):
        assert not out[fault], (fault, out[fault + "_checks"])
