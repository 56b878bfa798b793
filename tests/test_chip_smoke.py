"""``chip_smoke.py`` refuses to run anywhere but on a TPU, and prints no
result when it does."""

import importlib.util
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.models import model as model_lib

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_the_cpu_before_building_the_model(
        monkeypatch, capsys):
    def no_model(*a, **kw):
        raise AssertionError("chip_smoke built the model off the chip")

    monkeypatch.setattr(model_lib, "init", no_model)
    with pytest.raises(SystemExit) as exc:
        _chip_smoke().main([])
    message = str(exc.value.code)
    assert "needs a TPU" in message and "'cpu'" in message
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_refuses_a_lone_copy(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert "no repro package" in run.stderr
    assert '"ok"' not in run.stdout
