"""``chip_smoke.py``'s scan and train phases at tiny size on the CPU —
the rehearsal that keeps the chip's smoke test runnable.  The scan
runs the bitunpack kernel in interpret mode here; on a TPU the script
requires it compiled."""

import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_scan_phase_matches_numpy_through_the_kernel(chip_smoke, capsys):
    chip_smoke.scan_phase(14, 3, on_tpu=False, object_bytes=16 << 10)
    out = capsys.readouterr().out
    assert "bit-equal to numpy" in out
    assert "bitunpack kernel:" in out


def test_train_phase_restores_bit_for_bit(chip_smoke, capsys):
    chip_smoke.train_phase("tiny", 4, 0)
    assert "bit-equal to the saved state" in capsys.readouterr().out
