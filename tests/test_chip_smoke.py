"""``chip_smoke.py``'s phases at tiny sizes on the CPU (interpret-mode
Pallas): the same paths, checks and output lines the chip run takes, so
a broken phase fails here before it costs chip time."""

import json
import subprocess
import sys

import jax
import pytest

import chip_smoke


@pytest.mark.parametrize("phase, kwargs", [
    ("dense", dict(grid=64, steps=8, substeps=4)),
    ("field", dict(grid=64)),
    ("ir", dict(grid=64, steps=4)),
    ("serve", dict(grid=32, scenarios=3, steps=2)),
    ("reference", dict()),
])
def test_phase_runs_and_checks(phase, kwargs):
    line = getattr(chip_smoke, f"phase_{phase}")(**kwargs)
    assert line["phase"] == phase
    assert line["impl"] == {"dense": "pallas", "field": "pallas",
                            "ir": "xla", "serve": "xla",
                            "reference": "point"}[phase]
    assert line["run_s"] > 0 and "check" in line
    json.dumps(line)


def test_sharded_phase_on_four_virtual_devices(eight_devices):
    with jax.default_device(eight_devices[0]):
        line = chip_smoke.phase_sharded(grid=64, steps=4, depth=2,
                                        devices=eight_devices[:4])
    assert line["impl"] == "pallas" and line["mesh"] == "2x2"


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    assert "no TPU found" in capsys.readouterr().err


@pytest.mark.slow  # subprocess: the script alone, outside the checkout
def test_script_alone_fails(tmp_path):
    import shutil

    shutil.copy(chip_smoke.__file__, tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and '"ok": true' not in r.stdout
