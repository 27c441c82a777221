"""Mesh-platform interpret resolution + CLI kernel reporting.

A command run OUTSIDE the test rig has no ``jax_default_device`` pin:
with a CPU device mesh, sample-based interpret resolution would fall
through to the process's default backend. The subprocess test runs the
CLI in that environment; the in-process tests pin the reporting
contract: the result JSON names the kernel that actually ran.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from mpi_model_tpu import cli


JUDGE_CMD = ["run", "--flow=diffusion", "--dimx=64", "--dimy=64",
             "--mesh=2x4", "--halo-depth=2", "--impl=pallas", "--steps=8",
             "--json"]


@pytest.mark.slow  # subprocess-spawning: the raw environment
def test_pallas_on_cpu_mesh_without_conftest_pins():
    """A sharded Pallas command in a subprocess WITHOUT the test rig's
    jax_default_device pin: interpret must resolve from the MESH's
    platform, not ambient config."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, "-m", "mpi_model_tpu.cli"] + JUDGE_CMD,
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, (
        f"stdout={proc.stdout!r}\nstderr={proc.stderr[-2000:]!r}")
    row = json.loads(proc.stdout)
    assert row["impl"] == "pallas"
    assert row["halo_depth"] == 2
    assert row["conserved"] is True


def test_cli_reports_pallas_impl(capsys, eight_devices):
    rc = cli.main(list(JUDGE_CMD))
    out = capsys.readouterr().out
    assert rc == 0
    row = json.loads(out)
    assert row["impl"] == "pallas" and row["halo_depth"] == 2


def test_cli_reports_auto_ineligible_impl(capsys):
    """--impl=auto with a point flow is Pallas-ineligible: the JSON must
    name the kernel that ran ("point" — the subsystem fast path), not
    leave the user believing they benchmarked Pallas."""
    rc = cli.main(["run", "--dimx=16", "--dimy=16", "--dtype=float64",
                   "--impl=auto", "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    row = json.loads(out)
    assert row["impl"] == "point"
    assert row["substeps"] == 1


def test_mesh_interpret_resolves_from_mesh_devices():
    from mpi_model_tpu.ops.pallas_stencil import mesh_interpret
    from mpi_model_tpu.parallel import make_mesh

    mesh = make_mesh(4, devices=jax.devices("cpu")[:4])
    assert mesh_interpret(mesh) is True


def test_negative_steps_rejected():
    import pytest

    with pytest.raises(SystemExit, match="steps"):
        cli.main(["run", "--steps=-2"])
