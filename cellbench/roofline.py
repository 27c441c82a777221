"""Peaks of the chips the benchmark runs on, and the roofline arithmetic.

A share of a roofline is the least time the chip could take for the
work, over the device time that the work took. The least time is the
larger of operations over the compute peak and bytes over the memory
bandwidth. The work is counted from the cell (the model's equations and
the state's size), never from the implementation, so a fused kernel and
an unfused one are held to the same count. A device kind missing from
the table is an error, never a default.
"""

from __future__ import annotations

#: device_kind -> peak -> (value, source)
PEAKS: dict[str, dict[str, tuple[float, str]]] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": (
            819e9, "published: Google Cloud documentation, TPU v5e"),
        "mxu_bf16_flops_per_s": (
            197e12, "published: Google Cloud documentation, TPU v5e"),
        # the largest median of the probe's variants; see PERF.md
        "vpu_f32_ops_per_s": (
            4266140628263.107,
            "measured on a v5e chip by cellbench/probe.py: f32 multiply-add "
            "chains in VMEM, timed from the device trace"),
    },
}


def peak(kind: str, name: str) -> float:
    """The value of peak ``name`` of ``kind``; KeyError when unknown."""
    try:
        return PEAKS[kind][name][0]
    except KeyError:
        raise KeyError(f"no peak {name!r} for device kind {kind!r} in the "
                       f"table (kinds: {sorted(PEAKS)})") from None


def least_time_s(ops: float, nbytes: float, kind: str,
                 compute: str = "vpu_f32_ops_per_s") -> tuple[float, str]:
    """(least seconds, which bound sets it) for ``ops`` operations on
    the ``compute`` peak and ``nbytes`` moved to and from HBM."""
    t_ops = ops / peak(kind, compute)
    t_mem = nbytes / peak(kind, "hbm_bytes_per_s")
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
