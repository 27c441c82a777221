"""Cells found by name from their files, ``BENCHMARK.json`` against the
benchmark's contract, a throwaway cell added as new files only, and the
result line's schema."""

import io
import json
import re
import time
from pathlib import Path

import jax
import pytest

from cellbench import compare, harness, run, spec

from cellbench_fixtures import REPO, copy_benchmark, cpu_peaks, tiny_root

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "cellbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_every_cell_reports_what_the_contract_asks():
    cells = {w["name"] for w in BENCH["workloads"]}
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2)
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(cells)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
    for w in BENCH["workloads"]:
        cell = spec.load_cell(str(REPO), w["name"])
        got = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in got and len(got) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            # the metric moves an end-to-end metric this cell reports
            assert m["moves"] in got
            assert callable(spec.metric_reader(str(REPO), m["name"]))


def test_configs_name_their_files_and_references():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["file"].startswith("cellbench/configs/")
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert (REPO / "cellbench" / "references"
                / f"{cfg['reference']}.py").exists()
        assert set(cfg["limits"]) >= {"max_abs_err"}
        assert compare.controls(cfg)
        for ctl in compare.controls(cfg):
            assert ctl["kind"] in ("reference", "program")


def test_loading_a_cell_by_name():
    cell = spec.load_cell(str(REPO), "grayscott8k-ir-horizon")
    assert cell.chips == 1 and cell.config["grid"] == [8192, 8192]
    assert cell.traffic["loop"] == "horizon"
    assert [m["name"] for m in cell.end_to_end] == ["cell_updates_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "ir_step_roofline", "device_idle_pct.grid", "totals_idle_pct.grid"}
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell(str(REPO), "no-such-cell")


def test_a_dotted_metric_falls_back_to_its_stem(tmp_path):
    root = copy_benchmark(tmp_path)
    (root / "cellbench" / "metrics" / "calls.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    assert spec.metric_reader(str(root), "calls.serve.b")(None) == 1.0
    with pytest.raises(FileNotFoundError):
        spec.metric_reader(str(root), "no_reader.serve")


def test_every_tiny_cut_fits_its_grid():
    for c in BENCH["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        cfg.update(cfg["tiny"])
        h, w = cfg["grid"]
        t = cfg.get("check", {}).get("tile")
        assert h * w <= 128 * 128 and (not t or t <= min(h, w)), c["name"]


def test_run_refuses_without_a_tpu(capsys):
    code = run.main(["--workload", "diffusion16k-bf16-horizon", "--seed",
                     "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert code != 0 and out.out == "" and "no TPU found" in out.err


def _add_throwaway(root: Path) -> None:
    """A new configuration, traffic mix and per-layer metric, as files.
    The configuration names no ``tiny`` cut of its own."""
    (root / "cellbench" / "configs" / "throwaway.json").write_text(
        json.dumps({
            "name": "throwaway", "model": {"kind": "diffusion",
                                           "rate": 0.2},
            "grid": [4096, 4096], "dtype": "float32",
            "init": {"value": [0.0, 2.0]},
            "executor": {"kind": "serial", "step_impl": "xla"},
            "expect_impl": "xla", "reference": "diffusion",
            "control": {"kind": "reference", "store": "bfloat16"},
            "limits": {"max_abs_err": 1e-4}}))
    (root / "cellbench" / "traffic" / "horizon3.json").write_text(
        json.dumps({"loop": "horizon", "steps_per_call": 3}))
    (root / "cellbench" / "metrics" / "throwaway_calls.py").write_text(
        "def read(ctx):\n    return float(ctx.work['calls'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "throwaway", "source": "x",
                             "file": "cellbench/configs/throwaway.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway.h3",
                               "config": "throwaway", "traffic": "horizon3",
                               "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("throwaway.h3")
    bench["per_layer"].append({"name": "throwaway_calls", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves":
                               "cell_updates_per_s",
                               "workloads": ["throwaway.h3"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def _throwaway_root(tmp: Path) -> Path:
    """The benchmark with the throwaway cell added as files, then cut to
    tiny sizes the way every test's tree is."""
    src = copy_benchmark(tmp / "src")
    _add_throwaway(src)
    return Path(tiny_root(tmp / "tiny", src))


def _run(cell, traced, seconds=0.2):
    out, err = io.StringIO(), io.StringIO()
    harness.run(cell, 2**33 + 5, seconds, traced, time.perf_counter(),
                jax.devices(), {"backend_s": 0.0}, out=out, err=err)
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


def test_a_cell_added_as_files_runs(tmp_path):
    root = _throwaway_root(tmp_path)
    cell = spec.load_cell(str(root), "throwaway.h3")
    # a configuration with no cut of its own gets the default one
    assert cell.config["grid"] == [64, 64]
    line, err = _run(cell, traced=False)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"cell_updates_per_s", "setup_s"}
    assert line["attempted"] >= 1
    assert "setup split" in err
    last = err.strip().splitlines()[-1]
    assert last.startswith("compared max_abs_err = ") and "limit" in last


def test_the_result_line_schema(tmp_path, monkeypatch):
    cpu_peaks(monkeypatch)
    root = _throwaway_root(tmp_path)
    line, _ = _run(spec.load_cell(str(root), "throwaway.h3"), traced=True)
    keys = list(line)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "compared" and "breakdown" in keys
    assert line["compared"]["max_abs_err"]["limit"] == 1e-4
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
            "window_s"} <= set(dev)
    assert dev["count"] == 1 and 0 < dev["busy_s"] <= dev["window_s"]
    assert line["metrics"]["throwaway_calls"]["unit"] == "calls"
    assert line["metrics"]["throwaway_calls"]["value"] >= 1
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
    b = line["breakdown"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
