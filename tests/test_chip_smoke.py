"""The chip smoke script refuses to report off the chip.

``chip_smoke.py`` must exit non-zero, without its ``ok`` line, when JAX
finds no TPU, when the kernel backend is forced to interpret mode, or when
a phase fails. Here, on the CPU, all three are driven directly.
"""
import importlib.util
import pathlib
import types

import jax
import pytest

from repro.core.objective import ExecutionPolicy

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_no_tpu_exits_without_ok_line(smoke, capsys):
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("forced", ["interpret", "gpu-triton"])
def test_backend_override_refused_on_tpu(smoke, monkeypatch, forced):
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    monkeypatch.setenv("REPRO_BACKEND", forced)
    with pytest.raises(smoke.SmokeFailure, match="not 'tpu-mosaic'"):
        smoke.preflight()


def test_failing_phase_raises(smoke):
    """On the XLA operators no megakernel is selected, so phase B fails
    its plan check instead of reporting."""
    meter = smoke.CompileMeter()
    with pytest.raises(smoke.SmokeFailure, match="megakernel"):
        smoke.phase_b(meter, count=2, n=64, r=8,
                      policy=ExecutionPolicy(use_pallas=False))
