"""Roofline accounting: how close a measured step runs to chip ceilings.

Cell-updates/s alone cannot say how far a kernel sits from the chip's
roofline; since the reference publishes nothing
(``/root/reference/README.md:1``), we own the baseline AND its ceiling
analysis (SURVEY §6).

Peaks are parameterized per ``device_kind`` from public datasheet
numbers (Google Cloud TPU documentation). The VPU figure is an ESTIMATE
(vector-unit throughput is not published the way MXU TFLOPs are:
lanes × ALU slots × clock), and its table key says so. A share of peak
is only ever computed for a chip in the table: an unknown
``device_kind`` is an error there, never a default.

The stencil model (``stencil_roofline``) charges the fused kernel
2·bytes/cell of HBM traffic per ``substeps``-step chunk (one read + one
write of the grid; inter-tile ghost re-reads are a few % and ignored)
and ``flops_per_cell`` VPU ops per cell per step — 11 for the Moore-8
closed-form interior (1 mul rate·v, 1 div-by-count folded to a mul,
7 adds for the 8-share sum, 2 update adds), counting every add/mul as
one op. These are *useful-arithmetic* floors: the kernel also spends
VPU slots on the shifted-window data movement, so pct_of_vpu_estimate
understates true VPU occupancy.
"""

from __future__ import annotations

from typing import Any, Optional

#: public-datasheet peaks per jax ``device_kind`` (Google Cloud TPU
#: documentation; ``vpu_gops_estimate`` is NOT published — see the
#: module docstring). hbm in GB/s; vpu in Gop/s for f32 elementwise.
CHIP_PEAKS: dict[str, dict[str, float]] = {
    # v5e: 819 GB/s HBM, 197 bf16 MXU TFLOPs; VPU ≈ 8·128 lanes ×
    # 4 ALU slots × ~0.94 GHz ≈ 3.9 Top/s
    "TPU v5 lite": {"hbm_gbps": 819.0, "vpu_gops_estimate": 3900.0,
                    "mxu_bf16_tflops": 197.0},
    # v4: 1228 GB/s, 275 bf16 TFLOPs
    "TPU v4": {"hbm_gbps": 1228.0, "vpu_gops_estimate": 4300.0,
               "mxu_bf16_tflops": 275.0},
    # v5p: 2765 GB/s, 459 bf16 TFLOPs
    "TPU v5": {"hbm_gbps": 2765.0, "vpu_gops_estimate": 7000.0,
               "mxu_bf16_tflops": 459.0},
    # v6e (Trillium): 1640 GB/s, 918 bf16 TFLOPs
    "TPU v6 lite": {"hbm_gbps": 1640.0, "vpu_gops_estimate": 7800.0,
                    "mxu_bf16_tflops": 918.0},
}


#: jax ``device_kind`` spellings that mean a chip already in the table.
#: Letter suffixes denote DIFFERENT chips ('v5e' is the lite part, 'v5p'
#: the full part, 'v4i' the inference part) — they must be mapped
#: explicitly, never by prefix, or 'TPU v5e' would inherit v5p's 2765
#: GB/s and report a ~3.4x-understated percent-of-peak.
KIND_ALIASES: dict[str, str] = {
    "TPU v5e": "TPU v5 lite",
    "TPU v5p": "TPU v5",
    "TPU v6e": "TPU v6 lite",
}


def _lookup_peaks(kind: str) -> dict[str, float]:
    """Exact match, then the alias table, then the longest table key
    that prefixes the reported ``device_kind`` AT A WORD BOUNDARY
    ('TPU v4 pod slice' → 'TPU v4'; 'TPU v4i' does NOT match — a letter
    suffix is a different chip); ``{}`` for a kind not in the table."""
    k = " ".join(kind.split())
    if k in CHIP_PEAKS:
        return dict(CHIP_PEAKS[k])
    if k in KIND_ALIASES:
        return dict(CHIP_PEAKS[KIND_ALIASES[k]])
    for key in sorted(CHIP_PEAKS, key=len, reverse=True):
        if k.startswith(key + " "):
            return dict(CHIP_PEAKS[key])
    return {}


def chip_peaks(device=None) -> Optional[dict[str, Any]]:
    """Peak table entry for ``device`` (default: first jax device); None
    for a kind the table does not hold (CPU rigs included)."""
    import jax

    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    peaks = _lookup_peaks(kind)
    if not peaks:
        return None
    peaks["device_kind"] = kind
    return peaks


def stencil_roofline(grid: int, itemsize: int, t_step_s: float,
                     substeps: int = 1, nchannels: int = 1,
                     flops_per_cell: float = 11.0,
                     device=None) -> dict[str, Any]:
    """Achieved bandwidth/throughput and share of peak for one measured
    stencil step of ``t_step_s`` seconds. Raises ``ValueError`` for a
    device whose ``device_kind`` the peak table does not hold.

    ``t_step_s`` is the per-FLOW-step time; with ``substeps``-fused
    kernels the HBM traffic amortizes over the chunk, the arithmetic
    does not."""
    cells = float(grid) * float(grid) * nchannels
    bytes_per_step = 2.0 * cells * itemsize / max(1, substeps)
    flops_per_step = flops_per_cell * cells
    out: dict[str, Any] = {
        "bytes_per_step": bytes_per_step,
        "flops_per_step": flops_per_step,
        "achieved_gbps": bytes_per_step / t_step_s / 1e9,
        "achieved_gflops": flops_per_step / t_step_s / 1e9,
    }
    peaks = chip_peaks(device)
    if peaks is None:
        import jax

        kind = getattr(device or jax.devices()[0], "device_kind", "")
        raise ValueError(
            f"no peak table entry for device_kind {kind!r} (known: "
            f"{sorted(CHIP_PEAKS)}): a share of peak needs a known chip")
    out["device_kind"] = peaks["device_kind"]
    out["pct_of_hbm_peak"] = 100.0 * out["achieved_gbps"] / peaks["hbm_gbps"]
    out["pct_of_vpu_estimate"] = (100.0 * out["achieved_gflops"]
                                  / peaks["vpu_gops_estimate"])
    return out
