"""The sharded cell's driver and check on four virtual CPU devices: a sound
run comes out correct, and runs with the exchange between chips left out or
with the state left unchanged come out not correct."""
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

SHARDED = r'''
import json, sys, tempfile
from pathlib import Path
from bench.tests.faults import FAULTS
from bench.tests.tiny import CONFIGS, run_cell, tiny_root
cfg = dict(CONFIGS["higgs_tiny"], method="sharded_log", chips=4)
cells = {"x4_tiny.sharded_solve": dict(config="x4_tiny",
         traffic="sharded_solve", driver="sharded_solve",
         params={"pool_seed": 0, "instances": 1})}
root = tiny_root(Path(tempfile.mkdtemp()), {"x4_tiny": cfg}, cells)
out = {}
for name in ("sound", "no_exchange", "unchanged_state"):
    rc, last, err = run_cell(root, "x4_tiny.sharded_solve",
                             call=FAULTS.get(name))
    out[name] = dict(rc=rc, correct=last and last["correct"],
                     checks=last and last["checks"],
                     count=last and last["device"]["count"], err=err[-2000:])
print(json.dumps(out))
'''


def test_sharded_cell_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    proc = subprocess.run([sys.executable, "-c", SHARDED], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["sound"]["rc"] == 0, got["sound"]["err"]
    assert got["sound"]["count"] == 4
    assert got["sound"]["correct"] is True, got["sound"]["checks"]
    for fault in ("no_exchange", "unchanged_state"):
        assert got[fault]["correct"] is False, (fault, got[fault])
