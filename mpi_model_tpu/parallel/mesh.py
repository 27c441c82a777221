"""Device meshes and grid sharding: the decomposition layer.

Rebuild of the reference's process-grid decomposition — 1-D row striping
(``/root/reference/src/Model.hpp:62-76``, ``Defines.hpp:8``) and the 2-D
``LINES_REC × COLUMNS_REC`` block grid (``ModelRectangular.hpp:69-80``,
``DefinesRectangular.hpp:7-8``) — as ``jax.sharding.Mesh`` construction plus
``NamedSharding`` placement. ``shard_space`` is the live realization of the
reference's *intended* ``CellularSpace::Scatter`` (dead code at
``CellularSpace.hpp:36-79``): distribution as an operation on the data
structure, not string messages inlined in the model. There is no master
rank holding metadata only — every device holds a block of the one global
``jax.Array``, and XLA moves data over ICI.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.cellular_space import CellularSpace


def _devices(devices=None):
    if devices is not None:
        return list(devices)
    # Honor an explicitly pinned default device (e.g. tests pin "cpu").
    dd = jax.config.jax_default_device
    if dd is not None:
        platform = dd if isinstance(dd, str) else dd.platform
        return jax.devices(platform)
    return jax.devices()


def make_mesh(n: Optional[int] = None, axis: str = "x",
              devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over ``n`` devices — the row-striping decomposition
    (the reference's NWORKERS stripes, ``Defines.hpp:7-8``)."""
    devs = _devices(devices)
    n = len(devs) if n is None else n
    return Mesh(np.array(devs[:n]), (axis,))


def factor2d(n: int) -> tuple[int, int]:
    """Most-square (lines, columns) factorization of n devices."""
    best = (1, n)
    for a in range(1, int(math.isqrt(n)) + 1):
        if n % a == 0:
            best = (a, n // a)
    return best


def resolve_grid2d(lines: Optional[int], columns: Optional[int],
                   n: int) -> tuple[int, int]:
    """The (lines, columns) a 2-D decomposition of ``n`` devices resolves
    to: most-square factorization when both are None, ``n // given``
    one-sided. THE single source of this defaulting — ``make_mesh_2d``
    and ``ModelRectangular``'s partition geometry both call it, so the
    owner/output block map can never diverge from the mesh."""
    if lines is None and columns is None:
        return factor2d(n)
    if lines is None:
        return n // columns, columns
    if columns is None:
        return lines, n // lines
    return lines, columns


def make_mesh_2d(lines: Optional[int] = None, columns: Optional[int] = None,
                 axes: tuple[str, str] = ("x", "y"),
                 devices: Optional[Sequence] = None) -> Mesh:
    """2-D mesh — the block decomposition (``DefinesRectangular.hpp:7-8``:
    LINES_REC × COLUMNS_REC). Defaults to the most-square factorization of
    the available device count."""
    devs = _devices(devices)
    lines, columns = resolve_grid2d(lines, columns, len(devs))
    n = lines * columns
    if n == 0 or n > len(devs):
        raise ValueError(
            f"mesh {lines}x{columns} needs {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]).reshape(lines, columns), axes)


def grid_spec(mesh: Mesh) -> P:
    """PartitionSpec sharding grid rows over the first mesh axis and (for
    2-D meshes) columns over the second."""
    names = mesh.axis_names
    return P(names[0], names[1] if len(names) > 1 else None)


def mesh_spans_processes(mesh: Mesh) -> bool:
    """True when the mesh includes devices owned by other processes
    (multi-host: a 'rank' is a process, SURVEY §5)."""
    pidx = jax.process_index()
    return any(d.process_index != pidx for d in mesh.devices.flat)


def put_global(value, sharding) -> jax.Array:
    """``device_put`` that also works when the sharding spans other
    processes' devices: each process supplies its addressable shards from
    its (identical) host copy — the multi-host scatter. Single-process
    shardings take the plain device_put path; values ALREADY sharded
    across processes are kept on-device (resharded via a compiled
    identity when the layout differs) instead of a crashing device_get."""
    mesh = getattr(sharding, "mesh", None)
    if mesh is None or isinstance(mesh, Mesh) and not mesh_spans_processes(mesh):
        return jax.device_put(value, sharding)
    if isinstance(value, jax.Array) and not value.is_fully_addressable:
        if value.sharding == sharding:
            return value
        return jax.jit(lambda x: x, out_shardings=sharding)(value)
    npv = np.asarray(jax.device_get(value))
    return jax.make_array_from_callback(npv.shape, sharding,
                                        lambda idx: npv[idx])


def shard_space(space: CellularSpace, mesh: Mesh,
                spec: Optional[P] = None) -> CellularSpace:
    """Place the space's channels onto the mesh (the live ``Scatter``).

    Requires dims divisible by the mesh extent along each sharded axis
    (XLA's tiled sharding), which generalizes the reference's compile-time
    ``PROC_DIMX = DIMX/NWORKERS`` divisibility assumption.
    """
    spec = grid_spec(mesh) if spec is None else spec
    sharding = NamedSharding(mesh, spec)
    vals = {k: put_global(v, sharding) for k, v in space.values.items()}
    return space.with_values(vals)
