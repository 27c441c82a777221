"""Active-tile stepping: skip the quiet ocean, step only where the physics is.

BASELINE's round-5/6 analysis proved ~3.2 ms/step is the per-cell-RATE
bound for a radius-1 stencil on this chip — but the reference's live
workload (``/root/reference/src/Main.cpp``: one point flow on the grid)
spends most of a run with the wavefront covering a few percent of the
domain. The remaining order-of-magnitude win is in TOTAL WORK, not rate:
track activity at tile granularity, compute only active tiles, keep
static shapes via fixed-capacity compaction (the sparse-CA /
blockwise-conditional-compute shape: Hashlife-style activity
exploitation, MoE/paged-block routing).

The activity rule and why skipping is EXACT
-------------------------------------------
The grid is cut into ``(th, tw)`` tiles. A tile is **active** this step
iff any cell in it *or in its ring-1 neighbor tiles* is nonzero (the
3x3 tile dilation of the per-tile any-nonzero map). For the uniform-rate
linear flows this engine serves (``Diffusion``: ``out = v - rate*v +
Σ share(neighbors)``), an INACTIVE tile's cells and all cells within
distance 1 of them are zero, so their update is exactly ``0 - rate*0 +
Σ 0 = 0``: skipping the tile is *exactly equal* to computing it —
zero stays zero, and frontier tiles activate one step BEFORE flux can
arrive (the dilation), so no arriving mass is ever missed. One
sign-of-zero caveat: a stored ``-0.0`` cell counts as zero (``v != 0``)
and a skipped tile KEEPS it, while the dense update canonicalizes it
to ``+0.0`` (``-0.0 - (rate*-0.0) = +0.0`` in IEEE). The two outputs
are equal under ``==``/``np.array_equal`` — the contract every gate
and test checks — but differ at the sign bit under ``tobytes()``
hashing; seed grids with ``+0.0`` (the default) for bit-level
reproducibility across impls. The active
tiles' update mirrors the dense XLA path (``ops.stencil.transport``)
term for term — same ops, same accumulation order, same neighbor-count
values — so an active-path step equals the dense step bitwise at every
dtype (proven at f64 and f32 in ``tests/test_active.py``).

Capacity / fallback contract
----------------------------
Tile indices are cumsum-compacted into a fixed-capacity ``[K]`` buffer
(static shapes under ``jit``); per-tile windows are gathered, updated,
and scattered back with trip counts bounded by the *actual* active
count, so work scales with activity, not capacity. When the active
count exceeds the capacity OR the activity-fraction threshold, the
engine falls back to the DENSE step **that same step** (a ``lax.cond``
— never a wrong result, never a silent truncation), and the serial
runner counts those steps so ``Report.backend_report`` stays honest
(the same pattern as the point-subsystem routing in
``parallel/executors.py``).

Integration map
---------------
``Model.make_step(impl="active")`` (stateless per-step form; composes
with point flows, partitions and substeps), the amortized
``SerialExecutor(step_impl="active")`` runner (pads once, carries the
tile map and update buffer across the whole run — the bench path),
shard-local active sets in ``ShardMapExecutor(step_impl="active")``
(activity is per-shard; the ppermute ghost ring both feeds the windows
and activates edge tiles), per-scenario activity in
``ensemble.EnsembleExecutor(impl="active")`` (one lane = one active
set, traced per-lane rates), ``--impl=active`` on the CLI, and
``bench.bench_active`` (speedup-vs-activity-fraction at the timed
geometry).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.cell import MOORE_OFFSETS
from .stencil import neighbor_counts_traced, transport


def _pick_tile_dim(dim: int, preferred: int) -> int:
    """Largest divisor of ``dim`` that is <= preferred (tiles must tile
    the grid exactly — a remainder tile would need its own shape)."""
    for t in range(min(dim, preferred), 0, -1):
        if dim % t == 0:
            return t
    return dim


@dataclasses.dataclass(frozen=True)
class ActivePlan:
    """Static geometry of the active-tile engine for one grid shape:
    tile dims, tile-grid dims, the fixed compaction capacity ``K`` and
    the dense-fallback threshold (in tiles). Hashable — safe to close
    over in jitted steps and to key runner caches with."""

    shape: tuple[int, int]
    tile: tuple[int, int]
    grid: tuple[int, int]          #: (gi, gj) tile-grid dims
    capacity: int                  #: K — compaction buffer lanes
    fallback_tiles: int            #: dense fallback when count exceeds this

    @property
    def ntiles(self) -> int:
        return self.grid[0] * self.grid[1]


def plan_for(shape: tuple[int, int], tile: Optional[tuple[int, int]] = None,
             capacity: Optional[int] = None,
             max_active_frac: float = 0.25,
             preferred_tile: int = 128) -> ActivePlan:
    """Build the engine geometry for ``shape``.

    ``tile`` defaults to the largest divisors <= ``preferred_tile``
    (128² tiles → 16k tiles at the 16384² bench geometry). ``capacity``
    defaults to ``ceil(max_active_frac * ntiles)``; the dense fallback
    engages when the dilated active count exceeds
    ``min(capacity, ceil(max_active_frac * ntiles))`` — capacity
    overflow can therefore NEVER truncate the active set."""
    h, w = shape
    if tile is None:
        tile = (_pick_tile_dim(h, preferred_tile),
                _pick_tile_dim(w, preferred_tile))
    th, tw = int(tile[0]), int(tile[1])
    if th < 1 or tw < 1 or h % th or w % tw:
        raise ValueError(
            f"tile {tile} does not tile grid {shape} exactly; pick "
            "divisors of the grid dims (or tile=None to auto-pick)")
    gi, gj = h // th, w // tw
    ntiles = gi * gj
    if not 0.0 < max_active_frac <= 1.0:
        raise ValueError(
            f"max_active_frac must be in (0, 1], got {max_active_frac}")
    frac_tiles = max(1, min(ntiles, math.ceil(max_active_frac * ntiles)))
    cap = frac_tiles if capacity is None else int(capacity)
    if cap < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    cap = min(cap, ntiles)
    return ActivePlan(shape=(h, w), tile=(th, tw), grid=(gi, gj),
                      capacity=cap, fallback_tiles=min(cap, frac_tiles))


# -- activity map ------------------------------------------------------------

def tile_nonzero_map(v: jax.Array, plan: ActivePlan) -> jax.Array:
    """Per-tile any-nonzero: bool ``[gi, gj]``. (``v != 0`` — a -0.0
    background counts as zero and a skipped tile keeps its sign bit,
    whereas the dense update canonicalizes -0.0 to +0.0: equal under
    ``==``, one sign bit apart under byte hashing — module docstring.)"""
    (th, tw), (gi, gj) = plan.tile, plan.grid
    return jnp.any((v != 0).reshape(gi, th, gj, tw), axis=(1, 3))


def dilate_tile_map(tmap: jax.Array) -> jax.Array:
    """3x3 (ring-1) dilation of the tile map — the frontier rule: a tile
    activates one step before flux can arrive. A superset dilation is
    always exact (extra tiles compute zeros), so one rule serves every
    radius-1 neighborhood."""
    gi, gj = tmap.shape
    p = jnp.pad(tmap, 1)
    out = jnp.zeros_like(tmap)
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            out = out | p[1 + dx:1 + dx + gi, 1 + dy:1 + dy + gj]
    return out


def ghost_flags(padded: jax.Array, plan: ActivePlan) -> jax.Array:
    """Edge-tile activations from a one-cell ghost ring (``[h+2, w+2]``
    padded shard): a nonzero ghost cell activates every edge tile whose
    window contains it — a ghost cell one column past a tile seam sits
    in TWO tiles' windows, so the per-tile strip map is dilated along
    the strip. This is what makes shard-local active sets exact: flux
    arriving from a neighbor shard is seen one step early, exactly like
    the interior dilation."""
    (th, tw), (gi, gj) = plan.tile, plan.grid
    h, w = plan.shape

    def strip(cells: jax.Array, t: int, g: int) -> jax.Array:
        per = jnp.any(cells.reshape(g, t), axis=1)
        pad = jnp.pad(per, 1)
        return per | pad[:-2] | pad[2:]

    flags = jnp.zeros((gi, gj), bool)
    flags = flags.at[0, :].set(flags[0, :]
                               | strip(padded[0, 1:w + 1] != 0, tw, gj))
    flags = flags.at[-1, :].set(flags[-1, :]
                                | strip(padded[h + 1, 1:w + 1] != 0, tw, gj))
    flags = flags.at[:, 0].set(flags[:, 0]
                               | strip(padded[1:h + 1, 0] != 0, th, gi))
    flags = flags.at[:, -1].set(flags[:, -1]
                                | strip(padded[1:h + 1, w + 1] != 0, th, gi))
    # corner ghosts neighbor exactly the corner cell of the corner tile
    flags = flags.at[0, 0].set(flags[0, 0] | (padded[0, 0] != 0))
    flags = flags.at[0, -1].set(flags[0, -1] | (padded[0, w + 1] != 0))
    flags = flags.at[-1, 0].set(flags[-1, 0] | (padded[h + 1, 0] != 0))
    flags = flags.at[-1, -1].set(flags[-1, -1] | (padded[h + 1, w + 1] != 0))
    return flags


def changed_tile_map(prev, new, plan: ActivePlan) -> np.ndarray:
    """Per-tile any-CHANGED map between two states of one channel: bool
    ``[gi, gj]`` host array, True where any byte of the tile differs.
    The delta-checkpoint writer's fallback dirtiness source for dense/
    composed runs (``io.delta``): one vectorized compare over the grid,
    no state carried. Compares raw bytes, not values — a ``-0.0`` vs
    ``+0.0`` flip or a NaN cell reads as changed (NaN != NaN would too,
    but byte compare keeps the map deterministic for any payload), so a
    skipped tile is bit-identical by construction."""
    (th, tw), (gi, gj) = plan.tile, plan.grid
    a = np.ascontiguousarray(prev).view(np.uint8).reshape(gi, th, gj, -1)
    b = np.ascontiguousarray(new).view(np.uint8).reshape(gi, th, gj, -1)
    return np.any(a != b, axis=(1, 3))


def compact_tile_ids(flags: jax.Array,
                     plan: ActivePlan) -> tuple[jax.Array, jax.Array]:
    """Cumsum-compact the active map into the fixed ``[K]`` index buffer:
    returns ``(ids, count)`` — row-major tile indices of the active
    tiles in lanes ``[0, count)`` (lanes past the capacity are dropped
    by the scatter; the caller's fallback predicate fires before such a
    truncated set could ever be consumed)."""
    f = flags.reshape(-1)
    count = jnp.sum(f, dtype=jnp.int32)
    pos = jnp.cumsum(f.astype(jnp.int32)) - 1
    dest = jnp.where(f, pos, plan.capacity)
    ids = jnp.zeros((plan.capacity,), jnp.int32).at[dest].set(
        jnp.arange(f.shape[0], dtype=jnp.int32), mode="drop")
    return ids, count


# -- the per-tile update (bitwise-mirrors ops.stencil.transport) -------------

def vary_like(x: jax.Array, like: jax.Array) -> jax.Array:
    """``x`` cast to vary over every shard_map mesh axis ``like`` varies
    over (a no-op outside shard_map): a loop carry or cond branch that
    starts from a fresh constant and ends holding shard data must carry
    the same varying-manual-axes type on both ends."""
    missing = tuple(sorted(jax.typeof(like).vma - jax.typeof(x).vma))
    return lax.pcast(x, missing, to="varying") if missing else x


def active_pass(padded: jax.Array, upd: jax.Array, ids: jax.Array,
                count: jax.Array, rate, plan: ActivePlan,
                origin, global_shape: tuple[int, int],
                offsets: Sequence[tuple[int, int]],
                dtype) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One flow step over the compacted active set; returns
    ``(padded, upd, anyf)`` where ``anyf`` is the ``[K]`` bool per-lane
    any-nonzero of the computed tiles (lanes past ``count`` are False).

    ``padded`` is the ``[h+2, w+2]`` value array (ring = zeros on a full
    grid / partition boundary, real ghost data under sharding); ``upd``
    the carried ``[K, th, tw]`` update buffer (lanes past ``count`` are
    stale and never scattered). Two dynamic-trip-count loops — gather+
    compute into ``upd``, then scatter back — so every read precedes
    every write (neighboring active tiles must all see PRE-step values)
    and total work is O(active), not O(capacity): the per-lane flags
    are computed HERE, on the tile just produced, precisely so the
    next-step tile map never has to reduce over the whole capacity
    buffer (at the bench geometry that reduction reads 268 MB/step —
    measured ~80 ms on the CPU rig, a third of the entire step).

    The update expression mirrors the dense path term for term:
    ``outflow = rate*v``; ``share = outflow/count``; inflow accumulated
    from zeros in ``offsets`` order; ``(v - outflow) + inflow`` — with
    neighbor counts from ``neighbor_counts_traced`` at the window's
    GLOBAL coordinates, so the result is bitwise equal to
    ``ops.stencil.flow_step`` at every dtype.
    """
    (th, tw), (gi, gj) = plan.tile, plan.grid
    wh, ww = th + 2, tw + 2
    H, W = global_shape
    ox = jnp.asarray(origin[0], jnp.int32)
    oy = jnp.asarray(origin[1], jnp.int32)
    rate_c = jnp.asarray(rate, dtype)
    one = jnp.asarray(1, dtype)
    cmin = jnp.minimum(count, np.int32(plan.capacity))

    def rc_of(i):
        return (i // gj) * th, (i % gj) * tw

    def compute_body(l, carry):
        u, f = carry
        r, c = rc_of(ids[l])
        win = lax.dynamic_slice(padded, (r, c), (wh, ww))
        # off-grid window cells can have count 0; their value is 0 anyway
        cnt = jnp.maximum(
            neighbor_counts_traced((wh, ww), offsets,
                                   (ox + r - 1, oy + c - 1), (H, W), dtype),
            one)
        # the barrier materializes outflow so the subtraction below
        # consumes the SAME value the share divides — without it, XLA's
        # per-consumer recompute inside fusions hands LLVM a single-use
        # multiply that contracts to fma(-rate, v, v), a 1-ulp drift
        # from the dense path's uncontracted v - rate*v (measured; the
        # bitwise gate exists to catch exactly this class)
        outflow = lax.optimization_barrier(rate_c * win)
        share = outflow / cnt
        inflow = jnp.zeros((th, tw), dtype)
        for dx, dy in offsets:
            inflow = inflow + lax.slice(
                share, (1 + dx, 1 + dy), (1 + dx + th, 1 + dy + tw))
        tile_out = (win[1:-1, 1:-1] - outflow[1:-1, 1:-1]) + inflow
        return (lax.dynamic_update_index_in_dim(u, tile_out, l, 0),
                f.at[l].set(jnp.any(tile_out != 0)))

    anyf = vary_like(jnp.zeros((plan.capacity,), bool), padded)
    upd, anyf = lax.fori_loop(0, cmin, compute_body,
                              (vary_like(upd, padded), anyf))

    def scatter_body(l, p):
        r, c = rc_of(ids[l])
        return lax.dynamic_update_slice(p, upd[l], (r + 1, c + 1))

    padded = lax.fori_loop(0, cmin, scatter_body, padded)
    return padded, upd, anyf


def next_tile_map(anyf: jax.Array, ids: jax.Array, count: jax.Array,
                  plan: ActivePlan) -> jax.Array:
    """Exact post-step tile map from ``active_pass``'s per-lane flags:
    tiles outside the active set are zero by the engine invariant, so
    scattering the ``[K]`` any-nonzero flags over a False map is the
    full answer — O(capacity) on BOOLS, never a read of the update
    buffer itself."""
    gi, gj = plan.grid
    lanes = jnp.arange(plan.capacity, dtype=jnp.int32)
    valid = lanes < jnp.minimum(count, np.int32(plan.capacity))
    flat = jnp.zeros((gi * gj,), bool).at[
        jnp.where(valid, ids, np.int32(gi * gj))].set(anyf & valid,
                                                      mode="drop")
    return flat.reshape(gi, gj)


# -- dense fallbacks ---------------------------------------------------------

def dense_from_padded(padded: jax.Array, rate, counts: jax.Array,
                      offsets: Sequence[tuple[int, int]],
                      dtype) -> jax.Array:
    """Full-grid dense step on the padded representation (zero ring):
    ``ops.stencil.flow_step``'s exact expression — the shares crossing
    the ring are the zero-padded shifts — returning a re-padded array
    (the ring stays zero, preserving the engine invariant)."""
    v = padded[1:-1, 1:-1]
    new = transport(v, jnp.asarray(rate, dtype) * v, counts, offsets)
    return jnp.pad(new, 1)


def dense_from_ghost_padded(padded: jax.Array, rate, counts_pad: jax.Array,
                            offsets: Sequence[tuple[int, int]],
                            dtype) -> jax.Array:
    """Per-shard dense step consuming a REAL ghost ring: shares are
    computed on the padded array (a ghost cell's share equals the value
    the owning shard computes — same expression, same operands — so the
    result matches the share-exchanging XLA shard step bitwise).
    Returns the bare ``[h, w]`` interior (the caller re-exchanges)."""
    h, w = padded.shape[0] - 2, padded.shape[1] - 2
    # barrier: same anti-FMA-contraction discipline as active_pass
    outflow_p = lax.optimization_barrier(
        jnp.asarray(rate, dtype) * padded)
    share_p = outflow_p / counts_pad
    inflow = jnp.zeros((h, w), dtype)
    for dx, dy in offsets:
        inflow = inflow + lax.slice(
            share_p, (1 + dx, 1 + dy), (1 + dx + h, 1 + dy + w))
    return (padded[1:-1, 1:-1] - outflow_p[1:-1, 1:-1]) + inflow


# -- stateless per-step form (Model.make_step impl="active") -----------------

class ActiveDiffusionStep:
    """Stateless active-tile flow step for one channel: pad → activity →
    compact → active pass (or dense fallback, same step) → unpad. The
    form ``Model.make_step(impl="active")`` composes with point flows,
    partitions and substeps — activity is recomputed from the values
    each call, so any interleaved update (a point-flow deposit, a
    checkpoint restore) is seen next step. ``SerialExecutor``'s
    amortized runner is the fast path for whole runs (pads once,
    carries the tile map and buffers, and keeps the dense fallback out
    of the per-step path — this form pays a per-step ``lax.cond``
    buffer copy on top of the re-pad).

    ``dense_fn`` (values→values on the bare grid) is the same-step
    fallback — the fused Pallas kernel when the caller proved it runs
    here, else the dense XLA transport (bitwise with the XLA path)."""

    def __init__(self, shape: tuple[int, int], rate: float, dtype,
                 offsets: Sequence[tuple[int, int]] = MOORE_OFFSETS,
                 origin: tuple[int, int] = (0, 0),
                 global_shape: Optional[tuple[int, int]] = None,
                 tile: Optional[tuple[int, int]] = None,
                 capacity: Optional[int] = None,
                 max_active_frac: float = 0.25,
                 dense_fn: Optional[Callable] = None):
        self.shape = tuple(shape)
        self.rate = float(rate)
        self.dtype = jnp.dtype(dtype)
        self.offsets = tuple((int(dx), int(dy)) for dx, dy in offsets)
        self.origin = (int(origin[0]), int(origin[1]))
        self.global_shape = (tuple(global_shape) if global_shape is not None
                             else self.shape)
        self.plan = plan_for(self.shape, tile=tile, capacity=capacity,
                             max_active_frac=max_active_frac)
        if dense_fn is None:
            def dense_fn(v, _s=self):
                counts = neighbor_counts_traced(
                    _s.shape, _s.offsets, _s.origin, _s.global_shape,
                    _s.dtype)
                return transport(
                    v, jnp.asarray(_s.rate, _s.dtype) * v, counts,
                    _s.offsets)
        self.dense_fn = dense_fn

    def __call__(self, v: jax.Array) -> jax.Array:
        plan = self.plan
        th, tw = plan.tile
        tmap = tile_nonzero_map(v, plan)
        flags = dilate_tile_map(tmap)
        count = jnp.sum(flags, dtype=jnp.int32)
        pred = count > np.int32(plan.fallback_tiles)

        def dense_branch(vv):
            return self.dense_fn(vv)

        def active_branch(vv):
            padded = jnp.pad(vv, 1)
            ids, cnt = compact_tile_ids(flags, plan)
            upd = jnp.zeros((plan.capacity, th, tw), self.dtype)
            padded, _, _ = active_pass(padded, upd, ids, cnt, self.rate,
                                       plan, self.origin,
                                       self.global_shape, self.offsets,
                                       self.dtype)
            return padded[1:-1, 1:-1]

        return lax.cond(pred, dense_branch, active_branch, v)


# -- the amortized whole-run runner (SerialExecutor / ensemble lanes) --------

def build_active_runner(shape: tuple[int, int], rates: dict,
                        offsets: Sequence[tuple[int, int]], dtype,
                        origin: tuple[int, int] = (0, 0),
                        global_shape: Optional[tuple[int, int]] = None,
                        plan: Optional[ActivePlan] = None,
                        dense_fns: Optional[dict] = None,
                        traced_rates: bool = False,
                        track_dirty: bool = False) -> Callable:
    """Whole-run active stepper: ``run(values, n[, rates_vec]) ->
    (values, (fallback_events, active_tiles_total))`` — or, with
    ``track_dirty=True``, ``(values, (fallback_events,
    active_tiles_total, dirty_map))`` where ``dirty_map`` is the bool
    ``[gi, gj]`` UNION over the whole run of every tile the engine
    wrote: the compacted active set on active steps (exactly the tiles
    the scatter touched), the ring-1 dilation of the pre-step nonzero
    map on dense-fallback steps (a dense Diffusion step can only change
    cells within distance 1 of pre-step mass). A guaranteed superset of
    the tiles whose bytes changed — the dirty-tile export the
    incremental checkpoint layer (``io.delta``) keys its delta records
    off, costing one [gi, gj] bool OR per step.

    Pads each flow channel ONCE, then carries ``(padded, tile_map,
    update_buffer)`` per channel across all ``n`` steps (a traced trip
    count — one compile serves every run length): per-step work is the
    tiny activity-map update plus O(active tiles), never O(grid), which
    is where the order-of-magnitude win over the dense path lives.
    Non-flow channels ride through untouched.

    Loop structure (measured, not aesthetic): consecutive ACTIVE steps
    run in an inner ``while_loop`` with no ``lax.cond`` anywhere on
    that path — XLA CPU copies a conditional's carried buffers between
    branch allocations every call (~130 ms/step for the padded grid at
    the 16384² bench geometry, 3x the entire active step), while
    while-loop carries alias in place. The dense fallback sits in the
    OUTER loop and is entered only on actual fallback events, so each
    step still independently takes the dense path iff its dilated
    count exceeds the threshold — same per-step contract, none of the
    per-step cond tax. Channels are independent under plain Diffusion,
    so each runs its own while-nest (bitwise identical to
    interleaving).

    ``rates`` maps attr → uniform rate (a float, or — with
    ``traced_rates=True``, the ensemble's per-lane form — an index list
    into the runner's ``rates_vec`` argument whose entries are summed).
    ``dense_fns`` maps attr → dense stepper for fallback steps (None →
    the bitwise XLA transport). Returned stats: ``fallback_events``
    counts (attr, step) pairs that fell back; ``active_tiles_total``
    sums the dilated active counts (for mean-activity reporting)."""
    shape = tuple(shape)
    gshape = tuple(global_shape) if global_shape is not None else shape
    offsets = tuple((int(dx), int(dy)) for dx, dy in offsets)
    dtype = jnp.dtype(dtype)
    if plan is None:
        plan = plan_for(shape)
    th, tw = plan.tile
    dense_fns = dense_fns or {}
    attrs = list(rates)

    def rate_of(attr, rates_vec):
        r = rates[attr]
        if traced_rates:
            acc = jnp.zeros((), rates_vec.dtype)
            for i in r:
                acc = acc + rates_vec[i]
            return acc
        return r

    thresh = np.int32(plan.fallback_tiles)

    def _dilated_count(tmap):
        flags = dilate_tile_map(tmap)
        return flags, jnp.sum(flags, dtype=jnp.int32)

    def run(values, n, rates_vec=None):
        counts = neighbor_counts_traced(shape, offsets, origin, gshape,
                                        dtype)
        fb = jnp.zeros((), jnp.int32)
        at = jnp.zeros((), jnp.float32)
        # dirty union rides the carries ONLY when tracked, so a
        # track_dirty=False build (the ensemble lanes) stays
        # program-identical to a pre-export build
        dm = (jnp.zeros(plan.grid, bool),) if track_dirty else ()
        out = dict(values)
        for a in attrs:
            rate = rate_of(a, rates_vec)

            # carry: (padded, tile_map, upd, steps_done, fb, at[, dirty])
            def inner_cond(c, _n=n):
                _, cnt = _dilated_count(c[1])
                return (c[3] < _n) & (cnt <= thresh)

            def inner_body(c, _rate=rate):
                p, tm, u, i, fb_, at_, *dm_ = c
                flags, cnt = _dilated_count(tm)
                ids, _ = compact_tile_ids(flags, plan)
                p2, u2, anyf = active_pass(p, u, ids, cnt, _rate, plan,
                                           origin, gshape, offsets, dtype)
                if track_dirty:
                    # the scatter wrote exactly the flagged tiles
                    dm_ = (dm_[0] | flags,)
                return (p2, next_tile_map(anyf, ids, cnt, plan), u2,
                        i + 1, fb_, at_ + cnt.astype(jnp.float32), *dm_)

            def outer_body(c, _a=a, _rate=rate, _n=n):
                c = lax.while_loop(inner_cond, inner_body, c)
                p, tm, u, i, fb_, at_, *dm_ = c

                # the inner loop exited: either the run is done, or this
                # step's dilated count crossed the threshold — run the
                # DENSE step for it (one cond per fallback EVENT, so the
                # buffer-copy tax never lands on the active fast path)
                def dense_step(args):
                    pp, tm_, i_, fb__, at__, *dm__ = args
                    _, cnt = _dilated_count(tm_)
                    fn = dense_fns.get(_a)
                    if fn is not None:
                        p2 = jnp.pad(fn(pp[1:-1, 1:-1]), 1)
                    else:
                        p2 = dense_from_padded(pp, _rate, counts, offsets,
                                               dtype)
                    if track_dirty:
                        # a dense Diffusion step changes cells only
                        # within distance 1 of pre-step mass: the ring-1
                        # tile dilation of the pre-step map bounds them
                        dm__ = (dm__[0] | dilate_tile_map(tm_),)
                    return (p2, tile_nonzero_map(p2[1:-1, 1:-1], plan),
                            i_ + 1, fb__ + 1,
                            at__ + cnt.astype(jnp.float32), *dm__)

                p, tm, i, fb_, at_, *dm_ = lax.cond(
                    i < _n, dense_step, lambda args: args,
                    (p, tm, i, fb_, at_, *dm_))
                return (p, tm, u, i, fb_, at_, *dm_)

            c = lax.while_loop(
                lambda c, _n=n: c[3] < _n, outer_body,
                (jnp.pad(values[a], 1), tile_nonzero_map(values[a], plan),
                 jnp.zeros((plan.capacity, th, tw), dtype),
                 jnp.zeros((), jnp.int32), fb, at, *dm))
            padded, _, _, _, fb, at, *dm = c
            out[a] = padded[1:-1, 1:-1]
            dm = tuple(dm)
        if track_dirty:
            return out, (fb, at, dm[0])
        return out, (fb, at)

    return run
