"""``correct`` at a size the CPU runs in a moment: a sound run of each
one-chip cell passes its configuration's limits; the control (the
reference in the next lower precision, in the program's place) and each
fault the cell can have, planted under the timed path, come out not
correct. The four-chip cell is in ``test_cellbench_faults_sharded.py``.
"""

import io
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from cellbench import compare, harness, spec

from cellbench_fixtures import tiny_root

SEED = 2**32 + 17


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(Path(tmp_path_factory.mktemp("tiny")))


def run_cell(root, workload, seconds=0.0, **kw):
    cell = spec.load_cell(root, workload)
    out, err = io.StringIO(), io.StringIO()
    harness.run(cell, SEED, seconds, False, time.perf_counter(),
                jax.devices(), {}, out=out, err=err, **kw)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def control_numbers(root, workload, seconds=0.0):
    """The numbers of the configuration's first control, the reference
    in a lower precision, and its verdict on a window's sample."""
    cell = spec.load_cell(root, workload)
    system = harness.build(cell, SEED, jax.devices()[:1])
    harness.warm(cell, system)
    try:
        win, _ = harness.window(cell, system, SEED, seconds, False)
    finally:
        if hasattr(system, "close"):
            system.close()
    ctl = compare.controls(cell.config)[0]
    assert ctl["kind"] == "reference"
    numbers = win.check(store=ctl["store"])
    return numbers, compare.judge(numbers, cell.config["limits"])


ONE_CHIP = ["diffusion16k-bf16-horizon", "grayscott8k-ir-horizon",
            "diffusion2k-sweep-closed16"]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_a_sound_run_is_correct(root, workload):
    seconds = 0.3 if "sweep" in workload else 0.0
    line = run_cell(root, workload, seconds)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_the_control_is_not_correct(root, workload):
    seconds = 0.3 if "sweep" in workload else 0.0
    numbers, ok = control_numbers(root, workload, seconds)
    assert not ok, numbers


def _state_unchanged(monkeypatch):
    from mpi_model_tpu.models.model import SerialExecutor

    real = SerialExecutor.run_model

    def run_model(self, model, space, n):
        real(self, model, space, n)
        return dict(space.values)

    monkeypatch.setattr(SerialExecutor, "run_model", run_model)


def _moved(v):
    # an answer altered where it is produced, keeping the total, so the
    # program's own conservation gate lets it through
    return v.at[1, 1].add(0.25).at[2, 2].add(-0.25)


def _answer_altered(monkeypatch):
    from mpi_model_tpu.models.model import SerialExecutor

    real = SerialExecutor.run_model

    def run_model(self, model, space, n):
        out = dict(real(self, model, space, n))
        ch = sorted(out)[0] if "u" not in out else "u"
        out[ch] = _moved(out[ch])
        return out

    monkeypatch.setattr(SerialExecutor, "run_model", run_model)


@pytest.mark.parametrize("workload", ONE_CHIP[:2])
@pytest.mark.parametrize("fault", [_state_unchanged, _answer_altered])
def test_grid_faults_are_not_correct(root, workload, fault, monkeypatch):
    fault(monkeypatch)
    line = run_cell(root, workload)
    assert line["correct"] is False, line["compared"]


def _half_batch(monkeypatch):
    from mpi_model_tpu.ensemble.batch import EnsembleExecutor

    real = EnsembleExecutor._build_xla

    def build(self, model, espace, donate=False):
        run = real(self, model, espace, donate=False)

        def half(vb, rates, frozens, q, r):
            out = run(vb, rates, frozens, q, r)
            h = espace.batch // 2
            return {k: jnp.concatenate([out[k][:h], vb[k][h:]])
                    for k in out}
        return half

    monkeypatch.setattr(EnsembleExecutor, "_build_xla", build)


def _lane_altered(monkeypatch):
    from mpi_model_tpu.ensemble.batch import EnsembleSpace

    real = EnsembleSpace.scenario

    def scenario(self, i):
        sp = real(self, i)
        return sp.with_values({k: _moved(v) for k, v in sp.values.items()})

    monkeypatch.setattr(EnsembleSpace, "scenario", scenario)


@pytest.mark.parametrize("fault", [_half_batch, _lane_altered])
def test_sweep_faults_are_not_correct(root, fault, monkeypatch):
    fault(monkeypatch)
    line = run_cell(root, "diffusion2k-sweep-closed16", 0.3)
    assert line["correct"] is False, line["compared"]

