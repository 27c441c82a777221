"""Stencil transport ops: the compiled core of every flow update.

Rebuild of the reference's flow execution + neighbor redistribution
(``/root/reference/src/Model.hpp:176-235``): the owner computes
``amount = flow.execute()``, subtracts it from the source cell and adds
``amount / count_neighbors`` to each existing Moore neighbor — including
cross-rank neighbors via an explicit halo send (``Model.hpp:202-204``).

TPU-native design: the update is expressed over whole arrays —

- ``transport``: every cell simultaneously sheds ``outflow[c]`` and
  distributes it equally to its in-bounds neighbors. Zero-padded shifts make
  boundary masking implicit (the reference's 9 ``SetNeighbor`` cases), and the
  op is mass-conserving by construction: cell ``n`` emits
  ``count[n] * (outflow[n]/count[n])``.
- ``point_flow_step``: the sparse fast path for single-source flows (the
  reference's only live case) — a scatter-add with ``mode="drop"`` so
  out-of-bounds neighbor writes vanish, and *traced* source coordinates so
  moving the source never recompiles (the reference re-broadcasts a command
  string instead, ``Model.hpp:79-86``).

Both paths are pure functions of arrays → safe under ``jit``, ``scan``,
``shard_map`` and auto-SPMD sharding (XLA inserts the halo exchange for the
shifts when the operand is sharded).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from ..core.cell import MOORE_OFFSETS


def shift2d(x: jax.Array, dx: int, dy: int) -> jax.Array:
    """result[i, j] = x[i+dx, j+dy] if in bounds else 0 (static dx, dy ∈ {-1,0,1})."""
    h, w = x.shape[-2], x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 2) + [(1, 1), (1, 1)]
    padded = jnp.pad(x, pad)
    start = [0] * (x.ndim - 2) + [1 + dx, 1 + dy]
    limit = list(x.shape[:-2]) + [1 + dx + h, 1 + dy + w]
    return jax.lax.slice(padded, start, limit)


def gather_neighbors(share: jax.Array,
                     offsets: Sequence[tuple[int, int]] = MOORE_OFFSETS) -> jax.Array:
    """inflow[c] = Σ_d share[c + d] over in-bounds neighbors.

    Valid because Moore/von Neumann neighborhoods are symmetric on a
    non-periodic grid: c receives from n exactly when n is a neighbor of c.
    """
    inflow = jnp.zeros_like(share)
    for dx, dy in offsets:
        inflow = inflow + shift2d(share, dx, dy)
    return inflow


def neighbor_counts_traced(
    shape: tuple[int, int],
    offsets: Sequence[tuple[int, int]] = MOORE_OFFSETS,
    origin: tuple[int, int] = (0, 0),
    global_shape: tuple[int, int] = None,
    dtype=jnp.float32,
) -> jax.Array:
    """Per-cell in-bounds neighbor counts as TRACED iota arithmetic.

    The numpy twin (``core.cell.neighbor_count_grid``) materializes a
    concrete array — closing a jitted step over that bakes an O(grid)
    constant into the compiled program (256MB at 8192² f32). Recomputing
    from iotas inside
    the step is a handful of VPU compares per cell — cheaper than the
    HBM read of a materialized counts array in a bandwidth-bound step.
    """
    h, w = shape
    gx, gy = global_shape if global_shape is not None else (h, w)
    x0, y0 = origin
    rows = x0 + jnp.arange(h, dtype=jnp.int32)[:, None]
    cols = y0 + jnp.arange(w, dtype=jnp.int32)[None, :]
    cnt = None
    for dx, dy in offsets:
        ok = ((rows + dx >= 0) & (rows + dx < gx)
              & (cols + dy >= 0) & (cols + dy < gy))
        c = ok.astype(dtype)
        cnt = c if cnt is None else cnt + c
    return cnt


def weighted_counts_traced(
    shape: tuple[int, int],
    offsets: Sequence[tuple[int, int]],
    weights: Sequence[float],
    origin: tuple[int, int] = (0, 0),
    global_shape: tuple[int, int] = None,
    dtype=jnp.float32,
) -> jax.Array:
    """Per-cell sum of the in-bounds taps' WEIGHTS — the divisor of the
    weighted-tap Transport term (``ir.terms.Transport(weights=...)``);
    with unit weights this is exactly ``neighbor_counts_traced``. Same
    traced-iota discipline (no O(grid) constant baked into the step)."""
    h, w = shape
    gx, gy = global_shape if global_shape is not None else (h, w)
    x0, y0 = origin
    rows = x0 + jnp.arange(h, dtype=jnp.int32)[:, None]
    cols = y0 + jnp.arange(w, dtype=jnp.int32)[None, :]
    cnt = None
    for wt, (dx, dy) in zip(weights, offsets):
        ok = ((rows + dx >= 0) & (rows + dx < gx)
              & (cols + dy >= 0) & (cols + dy < gy))
        c = ok.astype(dtype) * jnp.asarray(wt, dtype)
        cnt = c if cnt is None else cnt + c
    return cnt


def transport(values: jax.Array, outflow: jax.Array, counts: jax.Array,
              offsets: Sequence[tuple[int, int]] = MOORE_OFFSETS) -> jax.Array:
    """One mass-conserving redistribution step over the whole grid."""
    share = outflow / counts
    return values - outflow + gather_neighbors(share, offsets)


def flow_step(values: jax.Array, rate_field: jax.Array, counts: jax.Array,
              offsets: Sequence[tuple[int, int]] = MOORE_OFFSETS) -> jax.Array:
    """Dense flow step: ``outflow = rate_field * values`` then transport.

    With ``rate_field`` zero everywhere except one source cell this is
    exactly the reference's Exponencial step (``Exponencial.hpp:14-16``);
    with a uniform rate it is the dense diffusion benchmark op.
    """
    return transport(values, rate_field * values, counts, offsets)


def point_flow_step(
    values: jax.Array,
    src_x: jax.Array,
    src_y: jax.Array,
    amount: jax.Array,
    counts: jax.Array,
    offsets: Sequence[tuple[int, int]] = MOORE_OFFSETS,
) -> jax.Array:
    """Sparse single/multi-source step via dropped-out-of-bounds scatter-add.

    ``src_x``/``src_y``/``amount`` are arrays of shape ``[k]`` (traced —
    dynamic sources don't recompile). Each source sheds ``amount[i]`` and
    every in-bounds Moore neighbor gains ``amount[i] / counts[src]``.
    Reference: owner branch ``Model.hpp:176-211`` + halo recv ``:224-235``.
    """
    h, w = values.shape
    share = amount / counts[src_x, src_y]
    out = values.at[src_x, src_y].add(-amount, mode="drop")
    for dx, dy in offsets:
        nx, ny = src_x + dx, src_y + dy
        # mode="drop" only drops indices >= size; negative indices wrap
        # NumPy-style, so zero the share for out-of-bounds neighbors (they
        # then deposit 0.0 at the wrapped location — harmless).
        valid = (nx >= 0) & (nx < h) & (ny >= 0) & (ny < w)
        out = out.at[nx, ny].add(jnp.where(valid, share, 0.0), mode="drop")
    return out
