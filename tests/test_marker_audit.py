"""Marker audit (ISSUE 2 satellite; grid check ISSUE 3): the tier-1
wall — the 870 s ``-m "not slow"`` inner-loop profile ROADMAP.md pins —
stays thin only if every test that spawns a subprocess, runs a
multihost/multichip dryrun, or steps a BIG grid is marked ``slow``.
This test enforces that STRUCTURALLY over the test sources, so a new
test (say, an ensemble CLI rig, or an oracle check at a bench-sized
geometry) cannot silently re-fatten the inner loop: it either carries
the marker or fails here.

The detection machinery lives in the shared static-analysis engine
(ISSUE 4): ``mpi_model_tpu.analysis.astlint`` registers it as the
``heavy-test`` rule, so ``python -m mpi_model_tpu.analysis --strict``
and this test enforce the SAME contract from the same code. Heaviness
is detected from the AST exactly as before the migration: a test
function is heavy when it (or a module-local helper it calls,
transitively) references the ``subprocess`` module / ``Popen`` /
``pexpect``, calls anything whose name contains ``dryrun`` (the
multihost/multichip rigs spawn worker processes internally), or makes a
call whose literal arguments (after simple constant propagation through
module/function-level ``name = INT`` assignments, tuples flattened)
contain TWO OR MORE integers >= 2048 — the grid-construction shape
``create(4096, 4096, ...)`` / ``ones((2048, 2048))``, i.e. a >= 2048²
grid (one big literal alone — a 1024x2048 strip, a byte count — does
not trip it). Heavy tests must be marked slow — a ``pytest.mark.slow``
decorator on the function/class or a module-level ``pytestmark``. A
``--durations=15`` audit step (recorded in the verify skill) backstops
what the AST cannot see."""

from __future__ import annotations

from pathlib import Path

from mpi_model_tpu.analysis import audit_test_module as _audit_module

TESTS_DIR = Path(__file__).resolve().parent


def test_subprocess_and_dryrun_tests_are_marked_slow():
    violations = []
    for path in sorted(TESTS_DIR.glob("test_*.py")):
        if path.name == Path(__file__).name:
            continue
        violations.extend(_audit_module(path))
    assert not violations, (
        "these tests spawn subprocesses, run multihost/multichip "
        "dryruns, or construct >= 2048² grids but are not marked slow — "
        "they would fatten the tier-1 inner loop (mark them "
        "@pytest.mark.slow or set a module pytestmark): "
        f"{violations}")


def test_audit_detects_an_unmarked_heavy_test(tmp_path):
    """The audit itself must actually catch offenders (a vacuous auditor
    would defend nothing)."""
    p = tmp_path / "test_fake.py"
    p.write_text(
        "import subprocess\n\n"
        "def _helper():\n"
        "    subprocess.run(['true'])\n\n"
        "def test_spawns():\n"
        "    _helper()\n\n"
        "def test_light():\n"
        "    assert True\n")
    vio = _audit_module(p)
    assert vio == ["test_fake.py::test_spawns"]
    # marking it (or the module) silences the finding
    p.write_text(
        "import pytest, subprocess\n"
        "pytestmark = pytest.mark.slow\n\n"
        "def test_spawns():\n"
        "    subprocess.run(['true'])\n")
    assert _audit_module(p) == []


def test_audit_detects_an_unmarked_big_grid_test(tmp_path):
    """The >= 2048² grid check (ISSUE 3 satellite) must catch literal,
    tuple, keyword and name-propagated grid constructions — and must
    NOT flag a single big literal (a strip, a byte count) or a shape
    description (``jax.ShapeDtypeStruct`` and helpers returning one)."""
    p = tmp_path / "test_fake_grid.py"
    p.write_text(
        "g = 4096\n\n"
        "def _mk():\n"
        "    return create(g, g, 1.0)\n\n"
        "def test_literal():\n"
        "    ones((2048, 2048))\n\n"
        "def test_via_name():\n"
        "    _mk()\n\n"
        "def test_keyword():\n"
        "    create(dimx=2048, dimy=3072)\n\n"
        "def test_strip_ok():\n"
        "    ones((1024, 2048))\n\n"
        "def test_bytes_ok():\n"
        "    limit(65536)\n\n"
        "def _sds(shape):\n"
        "    return jax.ShapeDtypeStruct(shape, 'float32')\n\n"
        "def test_shape_only_ok():\n"
        "    jax.ShapeDtypeStruct((16384, 16384), 'float32')\n"
        "    _sds((16384, 16384))\n")
    vio = _audit_module(p)
    assert vio == ["test_fake_grid.py::test_literal",
                   "test_fake_grid.py::test_via_name",
                   "test_fake_grid.py::test_keyword"]
    # a slow marker silences it
    p.write_text(
        "import pytest\n\n"
        "@pytest.mark.slow\n"
        "def test_literal():\n"
        "    ones((2048, 2048))\n")
    assert _audit_module(p) == []
