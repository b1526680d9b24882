"""chip_smoke.py's phases on the CPU at a small size.

On the chip the script serves a side-256 road grid with compiled
kernels; here the same phase functions run at side 16 with the Pallas
kernels in interpret mode (the engine's own backend rule), so a wrong
path, argument or check fails here before it costs chip time.
"""
import importlib.util
import os

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_run_at_side_16(smoke, tmp_path, capsys):
    smoke.run_phases(16, str(tmp_path))
    out = capsys.readouterr().out
    for phase in ("2 graph", "3 served in-memory", "4 served store-backed",
                  "5 correctness", "6 tropical", "7 memory"):
        assert f"phase {phase}:" in out
    assert "interpret=True" in out
    assert list(tmp_path.iterdir()) == []     # the store was removed


def test_data_parallel_path_at_side_16(smoke, capsys):
    smoke.run_data_parallel(16, jax.devices())
    assert "bit_identical=True" in capsys.readouterr().out


def test_refuses_to_run_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
