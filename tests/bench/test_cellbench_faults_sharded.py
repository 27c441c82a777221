"""``correct`` of the four-chip cell, at 128² on four virtual CPU devices
(interpret-mode Pallas): a sound run passes the configuration's limits;
its control (the program's own bf16 compute path) and each fault the
cell can have come out not correct: a step that returns its state
unchanged, an answer altered where it is produced, and the exchange
between chips left out."""

import io
import json
import time
from pathlib import Path

import pytest

from cellbench import compare, harness, spec

from cellbench_fixtures import tiny_root

SEED = 2**31 + 3
CELL = "diffusion48k-f32-halo-4chip"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(Path(tmp_path_factory.mktemp("tiny4")))


def run_cell(root, devices, **kw):
    cell = spec.load_cell(root, CELL)
    out, err = io.StringIO(), io.StringIO()
    harness.run(cell, SEED, 0.0, False, time.perf_counter(), devices, {},
                out=out, err=err, **kw)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_a_sound_run_is_correct(root, eight_devices):
    line = run_cell(root, eight_devices[:4])
    assert line["correct"] is True, line["compared"]
    assert line["device"]["count"] == 4


def test_the_control_is_not_correct(root, eight_devices):
    cell = spec.load_cell(root, CELL)
    ctl, = compare.controls(cell.config)
    assert ctl["kind"] == "program"
    line = run_cell(root, eight_devices[:4],
                    executor_overrides=ctl["executor"])
    assert line["correct"] is False, line["compared"]
    assert not compare.judge(
        {k: v["value"] for k, v in line["compared"].items()},
        cell.config["limits"])


def test_the_exchange_left_out_is_not_correct(root, eight_devices):
    line = run_cell(root, eight_devices[:4],
                    executor_overrides={"halo_mode": "zero"})
    assert line["correct"] is False, line["compared"]


def _unchanged(monkeypatch):
    from mpi_model_tpu.parallel import ShardMapExecutor

    real = ShardMapExecutor.run_model

    def run_model(self, model, space, n):
        real(self, model, space, n)
        return dict(space.values)

    monkeypatch.setattr(ShardMapExecutor, "run_model", run_model)


def _altered(monkeypatch):
    from mpi_model_tpu.parallel import ShardMapExecutor

    real = ShardMapExecutor.run_model

    def run_model(self, model, space, n):
        out = dict(real(self, model, space, n))
        # moved across the seam between the first two shards, total kept
        out["value"] = out["value"].at[63, 63].add(0.25).at[64, 64].add(
            -0.25)
        return out

    monkeypatch.setattr(ShardMapExecutor, "run_model", run_model)


@pytest.mark.parametrize("fault", [_unchanged, _altered])
def test_faults_are_not_correct(root, eight_devices, fault, monkeypatch):
    fault(monkeypatch)
    line = run_cell(root, eight_devices[:4])
    assert line["correct"] is False, line["compared"]
