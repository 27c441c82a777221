"""ctypes bridge to the native C++ runtime (libmmtpu.so).

The pybind11-free Python↔C++ boundary over ``native/src/capi.cpp``. Gives
Python access to the native serial engine and the threaded-rank backend
(in-process Send/Recv halo exchange — the reference's MPI architecture,
``/root/reference/src/Model.hpp:53-262``, without libmpi), used for
cross-backend golden tests: oracle == JAX == native C++.

``NativeExecutor`` plugs the native engine into ``Model.execute`` through
the same Executor protocol the JAX executors implement — the L0 seam
(``abstraction.py``) realized: one model, three backends.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

from .abstraction import DataType, to_native
from .core.cellular_space import CellularSpace
from .ops.flow import Coupled, Diffusion, Flow, PointFlow

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libmmtpu.so")


class _FlowSpec(ctypes.Structure):
    _fields_ = [
        ("type", ctypes.c_int),
        ("attr", ctypes.c_char_p),
        ("modulator", ctypes.c_char_p),
        ("rate", ctypes.c_double),
        ("x", ctypes.c_int),
        ("y", ctypes.c_int),
        ("has_frozen", ctypes.c_int),
        ("frozen", ctypes.c_double),
    ]


def _sources_newer_than(path: str) -> bool:
    """True when any native source, header or the CMake file is newer
    than ``path`` — the gitignored build may predate the checkout."""
    built = os.path.getmtime(path)
    roots = [os.path.join(_NATIVE_DIR, d) for d in ("src", "include")]
    files = [os.path.join(_NATIVE_DIR, "CMakeLists.txt")]
    for root in roots:
        for dirpath, _, names in os.walk(root):
            files += [os.path.join(dirpath, n) for n in names]
    return any(os.path.getmtime(f) > built for f in files)


def build_native(force: bool = False) -> str:
    """Build libmmtpu.so with cmake+ninja when missing or older than its
    sources; returns its path."""
    if (os.path.exists(_LIB_PATH) and not force
            and not _sources_newer_than(_LIB_PATH)):
        return _LIB_PATH
    # analysis: ignore[raw-transport] — a build-tool invocation
    # (cmake), not serving traffic: no fleet bytes cross this edge
    subprocess.run(["cmake", "-B", "build", "-G", "Ninja"],
                   cwd=_NATIVE_DIR, check=True, capture_output=True)
    # analysis: ignore[raw-transport] — same cmake build step
    subprocess.run(["cmake", "--build", "build"],
                   cwd=_NATIVE_DIR, check=True, capture_output=True)
    return _LIB_PATH


_lib = None


def load_library():
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(build_native())
    if not hasattr(lib, "mmtpu_space_create_typed"):  # ABI v2 marker
        # stale libmmtpu.so from an older source tree: rebuild, then load
        # the fresh binary under a UNIQUE path — dlopen would hand back
        # the already-mapped stale object for the original path
        import shutil
        import tempfile

        build_native(force=True)
        fd, tmp = tempfile.mkstemp(suffix=".so")
        os.close(fd)
        try:
            shutil.copy2(_LIB_PATH, tmp)
            lib = ctypes.CDLL(tmp)
        finally:
            # the dlopen mapping survives the unlink on Linux; without
            # this every affected process leaks one temp .so on disk
            os.unlink(tmp)
        if not hasattr(lib, "mmtpu_space_create_typed"):
            raise RuntimeError(
                "libmmtpu.so is stale and rebuilding did not refresh it; "
                "remove native/build and retry")
    lib.mmtpu_last_error.restype = ctypes.c_char_p
    lib.mmtpu_abi_version.restype = ctypes.c_int
    lib.mmtpu_dtype_tag_float64.restype = ctypes.c_int
    lib.mmtpu_dtype_tag_float32.restype = ctypes.c_int
    lib.mmtpu_space_create.restype = ctypes.c_void_p
    lib.mmtpu_space_create.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_double,
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int]
    lib.mmtpu_space_create_typed.restype = ctypes.c_void_p
    lib.mmtpu_space_create_typed.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_double,
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int]
    lib.mmtpu_space_dtype.restype = ctypes.c_int
    lib.mmtpu_space_dtype.argtypes = [ctypes.c_void_p]
    lib.mmtpu_space_destroy.argtypes = [ctypes.c_void_p]
    lib.mmtpu_space_channel.restype = ctypes.POINTER(ctypes.c_double)
    lib.mmtpu_space_channel.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.mmtpu_space_channel_f32.restype = ctypes.POINTER(ctypes.c_float)
    lib.mmtpu_space_channel_f32.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.mmtpu_space_total.restype = ctypes.c_double
    lib.mmtpu_space_total.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.mmtpu_space_set.restype = ctypes.c_int
    lib.mmtpu_space_set.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_double,
        ctypes.c_char_p]
    lib.mmtpu_space_dim_x.argtypes = [ctypes.c_void_p]
    lib.mmtpu_space_dim_x.restype = ctypes.c_int
    lib.mmtpu_space_dim_y.argtypes = [ctypes.c_void_p]
    lib.mmtpu_space_dim_y.restype = ctypes.c_int
    lib.mmtpu_run.restype = ctypes.c_int
    lib.mmtpu_run.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(_FlowSpec), ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_double, ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double)]
    lib.mmtpu_selftest_recv_timeout.restype = ctypes.c_int
    lib.mmtpu_selftest_recv_timeout.argtypes = [ctypes.c_int]
    lib.mmtpu_selftest_typed_wire.restype = ctypes.c_int
    # ABI pin: the native dtype tags must match abstraction.DataType.
    assert lib.mmtpu_dtype_tag_float64() == to_native(DataType.FLOAT64)
    assert lib.mmtpu_dtype_tag_float32() == to_native(DataType.FLOAT32)
    _lib = lib
    return lib


def selftest_recv_timeout(timeout_ms: int = 100) -> bool:
    """Drive the native runtime's failure-detection path: a bounded recv
    on a rank that will never be sent to must raise RecvTimeout inside
    the engine (returned here as True). The reference in the same
    situation hangs forever (SURVEY §5: 'a failed rank = hung job')."""
    rc = load_library().mmtpu_selftest_recv_timeout(int(timeout_ms))
    if rc == -1:
        raise RuntimeError(
            f"native selftest errored: "
            f"{load_library().mmtpu_last_error().decode()}")
    return rc == 1


def selftest_typed_wire() -> bool:
    """Drive the typed wire: an f32 payload received as f64 must raise
    the dtype-mismatch error inside the engine, and the matching-type
    path must round-trip (True = both held)."""
    rc = load_library().mmtpu_selftest_typed_wire()
    if rc == -1:
        raise RuntimeError(
            f"native selftest errored: "
            f"{load_library().mmtpu_last_error().decode()}")
    return rc == 1


def _flow_specs(flows) -> tuple:
    """Python Flow objects → C flow-spec array (keeps byte buffers alive)."""
    keep = []
    specs = (_FlowSpec * len(flows))()
    for i, f in enumerate(flows):
        attr_b = f.attr.encode()
        keep.append(attr_b)
        s = specs[i]
        s.attr = attr_b
        s.rate = float(f.flow_rate)
        if isinstance(f, PointFlow):
            s.type = 0
            s.x, s.y = f.source_xy
            if f.frozen_source_value is not None:
                s.has_frozen, s.frozen = 1, float(f.frozen_source_value)
        elif isinstance(f, Coupled):
            s.type = 2
            mod_b = f.modulator.encode()
            keep.append(mod_b)
            s.modulator = mod_b
        elif isinstance(f, Diffusion):
            s.type = 1
        else:
            raise TypeError(
                f"native backend supports PointFlow/Diffusion/Coupled, "
                f"got {type(f).__name__}")
    return specs, keep


class NativeSpace:
    """RAII wrapper over mmtpu_space with zero-copy TYPED channel views.

    ``dtype`` selects the engine instantiation (float64 — the
    reference's ``double`` default — or float32): field math runs in
    the storage type; conservation totals accumulate in f64 either way."""

    _DTYPES = {"float64": (DataType.FLOAT64, np.float64),
               "float32": (DataType.FLOAT32, np.float32)}

    def __init__(self, dim_x: int, dim_y: int, init: float = 1.0,
                 attrs: tuple[str, ...] = ("value",),
                 dtype: str = "float64"):
        self._lib = load_library()
        if str(dtype) not in self._DTYPES:
            raise ValueError(
                f"native engine instantiates float32/float64, not {dtype!r}")
        tag, self.np_dtype = self._DTYPES[str(dtype)]
        self.dtype = str(dtype)
        arr = (ctypes.c_char_p * len(attrs))(*[a.encode() for a in attrs])
        self._ptr = self._lib.mmtpu_space_create_typed(
            dim_x, dim_y, float(init), arr, len(attrs), to_native(tag))
        if not self._ptr:
            raise RuntimeError(self._lib.mmtpu_last_error().decode())
        assert self._lib.mmtpu_space_dtype(self._ptr) == to_native(tag)
        self.shape = (dim_x, dim_y)
        self.attrs = attrs

    def channel(self, attr: str = "value") -> np.ndarray:
        fn = (self._lib.mmtpu_space_channel if self.dtype == "float64"
              else self._lib.mmtpu_space_channel_f32)
        p = fn(self._ptr, attr.encode())
        if not p:
            raise KeyError(self._lib.mmtpu_last_error().decode())
        return np.ctypeslib.as_array(p, shape=self.shape)

    def set(self, x: int, y: int, v: float, attr: str = "value") -> None:
        if self._lib.mmtpu_space_set(self._ptr, x, y, v, attr.encode()) != 0:
            raise IndexError(self._lib.mmtpu_last_error().decode())

    def total(self, attr: str = "value") -> float:
        return self._lib.mmtpu_space_total(self._ptr, attr.encode())

    def run(self, flows, steps: int, lines: int = 1, columns: int = 1,
            check_conservation: bool = True, tolerance: float = 1e-3) -> dict:
        specs, keep = _flow_specs(flows)
        init_t = ctypes.c_double()
        final_t = ctypes.c_double()
        err = ctypes.c_double()
        rc = self._lib.mmtpu_run(
            self._ptr, specs, len(flows), steps, lines, columns,
            int(check_conservation), tolerance,
            ctypes.byref(init_t), ctypes.byref(final_t), ctypes.byref(err))
        if rc < 0:
            raise RuntimeError(self._lib.mmtpu_last_error().decode())
        report = {"initial_total": init_t.value, "final_total": final_t.value,
                  "conservation_error": err.value,
                  "comm_size": max(1, lines * columns)}
        if rc == 1:
            from .models.model import ConservationError  # circular-safe
            raise ConservationError(self._lib.mmtpu_last_error().decode())
        return report

    def __del__(self):
        if getattr(self, "_ptr", None):
            self._lib.mmtpu_space_destroy(self._ptr)
            self._ptr = None


class NativeExecutor:
    """Runs a Model on the native C++ engine (serial or threaded ranks)
    through the standard Executor protocol. f32 spaces run the native
    f32 engine instantiation (true f32 math — golden-tested against the
    f32 JAX path); every other dtype runs the f64 engine."""

    def __init__(self, lines: int = 1, columns: int = 1):
        self.lines = lines
        self.columns = columns
        #: the native engine's own report from the last run (initial/final
        #: totals and conservation error computed IN C++) — surfaced on
        #: Report.backend_report by Model.execute instead of discarded
        self.last_backend_report: Optional[dict] = None

    @property
    def comm_size(self) -> int:
        return max(1, self.lines * self.columns)

    def run_model(self, model, space: CellularSpace, num_steps: int) -> dict:
        import jax.numpy as jnp

        native_dtype = ("float32" if jnp.dtype(space.dtype) == jnp.float32
                        else "float64")
        ns = NativeSpace(space.dim_x, space.dim_y, 0.0,
                         attrs=tuple(space.values), dtype=native_dtype)
        for attr in space.values:
            np.copyto(ns.channel(attr),
                      np.asarray(space.values[attr], dtype=ns.np_dtype))
        self.last_backend_report = ns.run(
            model.flows, num_steps, self.lines, self.columns,
            check_conservation=False)
        self.last_backend_report["engine"] = "native-c++"
        return {attr: jnp.asarray(ns.channel(attr).copy(),
                                  dtype=space.dtype)
                for attr in space.values}
