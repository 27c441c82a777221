"""Test rig: 8 virtual CPU devices so 'multi-chip' sharding is testable
without a TPU (SURVEY §4 implication; the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip)."""

import os

# Must be set before jax initializes.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# f64 on CPU so the oracle comparisons are bit-exact.
jax.config.update("jax_enable_x64", True)
jax.config.update("jax_default_device", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices("cpu")
    if len(devs) < 8:
        pytest.skip("needs 8 virtual CPU devices")
    return devs


@pytest.fixture(scope="session")
def assert_gspmd_close():
    """Checks a GSPMD (space-partitioned jit) result against its serial
    twin. Not bitwise, for a stated cause: partitioning moves XLA:CPU's
    fusion boundaries, and LLVM contracts ``v - rate * v`` into one fma
    inside a fusion but not across one, so a cell can differ by an ulp
    per step. ``optimization_barrier`` does not survive to XLA:CPU's
    fusion and no XLA:CPU flag turns contraction off, so the order
    cannot be pinned. The bound is a few ulp of the largest value per
    step; shard_map paths stay bitwise."""
    def check(got, want, steps):
        got, want = np.asarray(got), np.asarray(want)
        eps = np.finfo(want.dtype).eps
        bound = 4 * steps * eps * max(float(np.max(np.abs(want))), 1.0)
        assert float(np.max(np.abs(got - want))) <= bound
    return check
