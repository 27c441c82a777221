"""Smoke tests for the benchmark harness (benchmarks/ladder.py): the
ladder functions run end-to-end at tiny scale and produce well-formed
rows. Numbers in quick mode are meaningless by design — only structure
and sign are asserted."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.ladder import config1, config2, oracle_cups  # noqa: E402


def test_oracle_cups_positive():
    assert oracle_cups(64, steps=3, point=True) > 0
    assert oracle_cups(64, steps=3, point=False) > 0


def test_ladder_config1_quick():
    row = config1(quick=True)
    assert row["config"] == 1
    assert row["oracle_cups"] > 0
    assert row["framework_impl"] in ("point", "xla", "pallas")
    assert row["native_correctness_cups"] is None  # skipped in quick mode


def test_ladder_config2_quick():
    row = config2(quick=True)
    assert row["config"] == 2
    assert "halo_share" in row
    assert row["strategy"].startswith("1-D row stripes")


def test_bench_tolerance_lookup_clear_error():
    """A dtype outside the gates' calibrated tiers must fail with a
    clear message, not a bare KeyError mid-gate (ISSUE 1 satellite)."""
    import pytest

    import bench

    assert bench._tol_for(4, "float32") == bench._tols(4)["float32"]
    assert bench._tol_for(1, "bfloat16") == 0.04
    with pytest.raises(ValueError, match="no oracle tolerance"):
        bench._tol_for(4, "float64")


class _Chip:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_roofline_fields():
    """Roofline math: traffic amortizes over fused substeps, arithmetic
    does not; the VPU share is labeled an estimate."""
    from mpi_model_tpu.utils import stencil_roofline

    r1 = stencil_roofline(1024, 4, t_step_s=1e-3, substeps=1, device=_Chip)
    r4 = stencil_roofline(1024, 4, t_step_s=1e-3, substeps=4, device=_Chip)
    assert r1["bytes_per_step"] == 2 * 1024 * 1024 * 4
    assert r4["bytes_per_step"] == r1["bytes_per_step"] / 4
    assert r4["flops_per_step"] == r1["flops_per_step"]
    assert r1["achieved_gbps"] == r1["bytes_per_step"] / 1e-3 / 1e9
    assert r1["pct_of_hbm_peak"] == 100.0 * r1["achieved_gbps"] / 819.0
    assert "pct_of_vpu_estimate" in r1 and r1["device_kind"] == "TPU v5 lite"


def test_chip_peaks_prefix_matching_and_unknown_kinds():
    """device_kind strings drift across TPU generations: 'TPU v5p' and
    'TPU v5e' resolve via the ALIAS table to the right chips (letter
    suffixes are different parts — prefix matching would hand v5e the
    v5p peaks), word-boundary prefixes match ('TPU v4 pod slice'), and a
    letter suffix with no alias ('TPU v4i' — a genuinely different
    inference chip) or an unknown kind has no entry."""
    from mpi_model_tpu.utils.roofline import CHIP_PEAKS, _lookup_peaks

    assert _lookup_peaks("TPU v5 lite") == CHIP_PEAKS["TPU v5 lite"]
    assert _lookup_peaks("TPU v5p") == CHIP_PEAKS["TPU v5"]
    assert _lookup_peaks("TPU v5e") == CHIP_PEAKS["TPU v5 lite"]
    assert _lookup_peaks("TPU v4 pod slice") == CHIP_PEAKS["TPU v4"]
    assert _lookup_peaks("TPU  v5   lite") == CHIP_PEAKS["TPU v5 lite"]
    assert _lookup_peaks("TPU v4i") == {}
    assert _lookup_peaks("TPU v99 hyper") == {}
    assert _lookup_peaks("Host CPU") == {}


def test_share_of_peak_on_an_unknown_device_is_an_error():
    from mpi_model_tpu.utils import chip_peaks, stencil_roofline

    assert chip_peaks() is None  # the CPU rig: no entry, no invented peak
    with pytest.raises(ValueError, match="no peak table entry"):
        stencil_roofline(1024, 4, t_step_s=1e-3)


def test_ladder_config3_quick_has_gspmd_row():
    import benchmarks.ladder as L

    row = L.config3(quick=True)
    assert "gspmd_cups" in row and "gspmd_vs_shardmap" in row


def test_timing_trial_helpers():
    """The trial/median helpers the bench discipline rests on: shapes,
    medians, and the interleaved A/B structure (pure-CPU smoke)."""
    import jax.numpy as jnp

    from mpi_model_tpu.utils import (interleaved_ab, marginal_runner_trials,
                                     marginal_step_trials, median_spread)

    ms = median_spread([3.0, 1.0, 2.0])
    assert ms == {"value": 2.0, "spread_lo": 1.0, "spread_hi": 3.0}

    calls = []
    ts = marginal_runner_trials(lambda n: calls.append(n), s1=1, s2=2,
                                trials=3)
    assert len(ts) == 3 and calls == [1, 2] * 3  # back-to-back per trial

    v0 = {"value": jnp.ones((4, 4), jnp.float32)}

    def step(vals):
        return {"value": vals["value"] * 0.5}

    samples = marginal_step_trials(step, v0, s1=1, s2=3, trials=2)
    assert len(samples) == 2

    med = interleaved_ab({"a": step, "b": step}, v0, s1=1, s2=2, reps=2)
    assert set(med) == {"a", "b"}

    # spread mode (the config-4 settle protocol): per-arm median+spread
    # from the warmed-once harness
    ab = interleaved_ab({"a": step, "b": step}, v0, s1=1, s2=2, reps=3,
                        spread=True)
    assert set(ab) == {"a", "b"}
    for arm in ab.values():
        assert set(arm) == {"value", "spread_lo", "spread_hi"}
        assert arm["spread_lo"] <= arm["value"] <= arm["spread_hi"]


def test_bench_checkpoint_rows_well_formed(tmp_path):
    """bench_checkpoint at toy scale: both layouts checkpoint the same
    run, rows carry the honesty fields, and the delta restore gate ran
    (bitwise) before any row was produced."""
    from bench import bench_checkpoint

    r = bench_checkpoint(grid=256, fracs=(0.05,), deltas=2,
                         workdir=str(tmp_path))
    assert r["grid"] == 256 and len(r["rows"]) == 1
    row = r["rows"][0]
    for k in ("full_bytes", "full_wall_s", "delta_bytes", "delta_wall_s",
              "keyframe_bytes", "bytes_ratio", "restore_gate_bitwise"):
        assert k in row
    assert row["restore_gate_bitwise"] is True
    # at 256^2 the whole workload fits in the 128^2 default tiles, so
    # every "delta" degrades to a keyframe (the degenerate-delta rule):
    # bytes match the full snapshot to within the chain's metadata —
    # the real win is a 16384^2 claim (BASELINE round 8), not a toy one
    assert 0 < row["delta_bytes"] <= row["full_bytes"] + 4096
    assert row["full_wall_s"] > 0 and row["delta_wall_s"] > 0
