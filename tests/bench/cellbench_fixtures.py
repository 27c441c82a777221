"""Shared set-up of the benchmark's tests: a copy of the benchmark's
files with every configuration cut to a size the CPU runs in a moment.

Each configuration and traffic mix carries its own cut under the key
``tiny`` (the keys a tiny copy replaces), so a file added later brings
its own; one without it gets ``DEFAULT_TINY``. The copy keeps the
configurations' limits and controls as they are, so a sound run at the
tiny size has to pass them and a broken one fail.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

#: the cut of a configuration that names none
DEFAULT_TINY = {"grid": [64, 64]}

PARTS = ("metrics", "traffic", "configs", "inits")


def copy_benchmark(dst: Path, src: Path = REPO) -> Path:
    """The benchmark's own files (``BENCHMARK.json``, configurations,
    traffic mixes, metric readers, initial-state makers) of ``src``,
    copied under ``dst``."""
    for part in PARTS:
        shutil.copytree(src / "cellbench" / part, dst / "cellbench" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(src / "BENCHMARK.json", dst / "BENCHMARK.json")
    return dst


def tiny_root(tmp: Path, src: Path = REPO) -> str:
    """A benchmark tree under ``tmp`` holding tiny copies of every cell
    of the benchmark at ``src``."""
    copy_benchmark(tmp, src)
    for f in (tmp / "cellbench" / "traffic").glob("*.json"):
        mix = json.loads(f.read_text())
        mix.update(mix.get("tiny", {}))
        f.write_text(json.dumps(mix))
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        f = tmp / c["file"]
        cfg = json.loads(f.read_text())
        cfg.update(cfg.get("tiny", DEFAULT_TINY))
        f.write_text(json.dumps(cfg))
    return str(tmp)


def cpu_peaks(monkeypatch) -> None:
    """Give the CPU rows in the peak table, so the roofline readers run
    (their values are test numbers, never a device's)."""
    from cellbench import roofline

    monkeypatch.setitem(roofline.PEAKS, "cpu", {
        "hbm_bytes_per_s": (1e11, "test"),
        "vpu_f32_ops_per_s": (1e12, "test"),
    })
