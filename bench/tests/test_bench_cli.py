"""``bench/run.py`` off a TPU, or without the program beside it, exits non-zero
and prints no result; importing the benchmark describes no topology."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import datagen

REPO = Path(__file__).resolve().parents[2]


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        return "correct" not in json.loads(lines[-1])
    except ValueError:
        return True


def test_off_tpu_exits_nonzero_without_result():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "higgs_r256.solve",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "higgs_r256.solve",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert _no_result(proc)


def test_import_describes_no_topology():
    code = ("import sys; import bench.harness, bench.tracefile, "
            "bench.roofline, bench.datagen, bench.control; "
            "print('jax.experimental.topologies' in sys.modules)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=_env(),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "False"


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 33 + 1])
def test_large_seeds_keep_their_high_bits(seed):
    import jax
    a = jax.random.key_data(datagen.key_of(seed))
    b = jax.random.key_data(datagen.key_of(seed & 0xFFFFFFFF))
    assert (seed >> 32 == 0) == bool((a == b).all())
