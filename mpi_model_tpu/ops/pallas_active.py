"""Fused Pallas active-tile kernel: sparse streaming + composed-k passes.

ROADMAP direction 2 ("roofline round 2"). The XLA active engine
(``ops.active``, PR 3) wins 18.1x at 1% activity, but every step still
pays a full gather/scatter round-trip through HBM — the compacted tile
windows are materialized by ``lax.dynamic_slice`` one at a time, the
updates land in a ``[K, th, tw]`` buffer, and XLA serializes the whole
thing through its fori_loop. This module moves the sparse iteration
INTO the Pallas layer:

- the compacted ``[K]`` active-tile index buffer is **scalar-prefetched**
  (``pltpu.PrefetchScalarGridSpec``) so tile coordinates are available
  to the DMA engine before the kernel body runs;
- each active tile's halo window streams HBM→VMEM with the same
  **double-buffered DMA discipline** as ``_stencil_call`` (lane ``l+1``'s
  window is in flight while lane ``l`` computes);
- the transport update is computed **in VMEM**, and the NEXT step's
  per-tile activity flag (``any(tile_out != 0)``) is computed **inside
  the same kernel pass** on the tile still resident in VMEM — the
  separate per-lane flag reduction of the XLA path (an extra read of
  the update buffer) is gone, which the jaxpr contract auditor asserts
  (``jaxpr-fused-flags``);
- a second tiny **scatter pass** (aliased output,
  ``input_output_aliases``) lands the updates back in the padded state;
  splitting compute from scatter is what makes every window read
  observe PRE-step values — the same all-reads-before-all-writes
  invariant ``ops.active.active_pass`` enforces with its two loops.

**Composed-k active** (``k > 1``): one tile-resident pass advances ``k``
flow steps — the PR 1 composed tap table on interior, self-lit tiles
(``(2k+1)²`` taps, one pass), and the **exact iterated path** on
near-global-edge and frontier (dilated-in, self-zero) tiles, so the
bitwise activation/boundary gates hold. The window carries a ring-k
halo (``k <= min(th, tw)`` keeps ring-1 tile dilation exact: mass moves
k <= tile cells per pass, so a tile still activates one pass before
flux can arrive). This multiplies arithmetic intensity by k exactly
where the dense roofline analysis says the kernel is bandwidth-bound.

Exactness contract (the PR 3 discipline, extended):

- ``k == 1``: the pass is **bitwise equal** to ``ops.active.active_pass``
  — and hence to the dense XLA step — at every dtype (the kernel
  mirrors the transport expression term for term, barrier included,
  with neighbor counts from global coordinates; proven at f64 and f32
  in ``tests/test_active_fused.py``).
- ``k > 1``: frontier and near-edge tiles run the iterated expression
  on the shrinking in-window region, which is bitwise equal to ``k``
  dense steps; interior tap tiles are algebraically equal (the PR 1
  composed-filter contract — ~k-ulp regrouping). Skipped tiles stay
  exactly zero either way.

Tier-1 proves all of this in interpret mode (the kernels trace to the
same XLA ops the oracle runs); the silicon row is a standing
pending-silicon item in ROADMAP.md. On silicon, note the padded-layout
window offsets are not (sublane, lane)-aligned — the Mosaic build will
want the aligned over-fetch treatment ``_stencil_call`` uses (tracked
with the pending-silicon item, not a correctness concern).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.cell import MOORE_OFFSETS
from .active import (
    ActivePlan,
    compact_tile_ids,
    dilate_tile_map,
    ghost_flags,
    next_tile_map,
    plan_for,
    tile_nonzero_map,
)
from .pallas_stencil import resolve_interpret
from .stencil import neighbor_counts_traced, transport

#: hard cap on the composed pass depth — the window is (th+2k, tw+2k),
#: so beyond this the VMEM window stops resembling the tile it serves;
#: also bounds the tap table at 33² taps
MAX_FUSED_K = 16


def choose_fused_k(substeps: int, plan: ActivePlan) -> int:
    """Largest divisor of ``substeps`` the tile geometry can compose:
    ``k <= min(th, tw)`` (the ring-1 dilation exactness bound — mass
    moves k cells per pass and must not cross a whole tile) and
    ``k <= MAX_FUSED_K``. Degrades to 1 (every pass = one step) when
    ``substeps`` has no such divisor — the clean-degradation contract
    the auditor's ``k·passes == substeps`` check rides on."""
    substeps = int(substeps)
    if substeps < 1:
        raise ValueError(f"substeps must be >= 1, got {substeps}")
    cap = min(plan.tile[0], plan.tile[1], MAX_FUSED_K)
    for k in range(min(substeps, cap), 0, -1):
        if substeps % k == 0:
            return k
    return 1


def pass_count(steps: int, k: int) -> int:
    """How many passes ``build_fused_runner`` executes PER ATTRIBUTE
    for ``steps`` flow steps at depth ``k``: ``steps // k`` full-depth
    passes plus ``steps % k`` depth-1 remainder passes. THE one copy of
    the split — every report that normalizes the runner's per-pass
    counters (fallback_steps, flags_fused, active-tile sums, which all
    accumulate (attr, pass) pairs across the live attributes) derives
    the denominator here, so the accounting identity
    ``flags_fused + fallback_steps == pass_count(n, k) × live attrs``
    cannot drift from the loop structure."""
    steps, k = int(steps), int(k)
    return steps // k + steps % k


def _fused_taps(rate: float, offsets: tuple, k: int) -> Optional[np.ndarray]:
    """The PR 1 composed tap table for the interior fast path (None at
    k=1: the single-step table is algebraically the explicit expression
    but not bitwise it, and k=1 must stay bitwise everywhere)."""
    if k <= 1:
        return None
    from .composed_stencil import composed_taps
    return composed_taps(rate, offsets, k)


# -- the fused pass (two pallas_calls: compute+flags, aliased scatter) -------

def _fused_compute_call(padded, ids, cnt1, selfnz, origin, *, rate, plan,
                        global_shape, offsets, dtype, k, ring, taps,
                        interpret):
    """Pallas pass 1: stream each active tile's ring-``k`` window from
    the ring-``ring`` padded state (``ring >= k``; remainder passes run
    ``k < ring`` on the same buffer), compute ``k`` transport steps in
    VMEM, and emit ``(upd [K, th, tw], anyf [K])`` — the per-lane
    any-nonzero flags computed on the tile still resident in VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (th, tw), (gi, gj) = plan.tile, plan.grid
    K = plan.capacity
    H, W = global_shape
    wh, ww = th + 2 * k, tw + 2 * k
    off = ring - k  # window offset into the (possibly deeper) padding
    # Mosaic DMAs whole (sublane, lane) tiles of HBM from tile-aligned
    # offsets: the compiled kernel fetches from the tile's aligned corner
    # a block rounded up to whole tiles (the extra rows/columns land in
    # the array's tiled-layout padding past its last tile and are never
    # read back) and slices the window out of VMEM at ``lead``; the
    # interpreter fetches the exact window
    sub = 8 * 4 // jnp.dtype(dtype).itemsize
    if interpret:
        lead, fh, fw = 0, wh, ww
    else:
        if th % sub or tw % 128:
            raise ValueError(
                f"the compiled fused active kernel needs tiles of whole "
                f"({sub}, 128) layout tiles; got {(th, tw)} — pass "
                "active_opts tile=... with such divisors")
        lead = off
        fh, fw = -(-(off + wh) // sub) * sub, -(-(off + ww) // 128) * 128
    _i32 = np.int32
    tap_list = (None if taps is None else
                [(dr, dc, float(taps[dr, dc]))
                 for dr in range(2 * k + 1) for dc in range(2 * k + 1)])
    # the barrier pins the CPU interpreter's fma contraction (see
    # ``iterated``); Mosaic has no lowering for it
    pin = lax.optimization_barrier if interpret else (lambda x: x)

    def nonzero(tile):
        # any(tile != 0) as a max: Mosaic lowers a bool reduce through
        # the default float type, which is f64 (and refused) under x64
        return (jnp.max(jnp.abs(tile.astype(jnp.float32))) != 0
                ).astype(jnp.int32)

    def kernel(ids_ref, cnt_ref, self_ref, orig_ref, rate_ref, pad_ref,
               upd_ref, anyf_ref, vwin, sems):
        l = pl.program_id(0)
        cmax = jnp.clip(cnt_ref[0], _i32(1), _i32(K))
        slot = lax.rem(l, _i32(2))
        valid = l < cmax

        def rc_of(lane):
            # the tile's corner in the padded state, ``ring`` above and
            # left of its interior
            t = ids_ref[lane]
            return ((t // _i32(gj)) * _i32(th),
                    lax.rem(t, _i32(gj)) * _i32(tw))

        def window_copy(lane, sl):
            r, c = rc_of(lane)
            return pltpu.make_async_copy(
                pad_ref.at[pl.ds(r + _i32(off - lead), fh),
                           pl.ds(c + _i32(off - lead), fw)],
                vwin.at[sl], sems.at[sl])

        # double-buffered pipeline (the _stencil_call discipline): lane 0
        # fetches its own window; every lane then prefetches its
        # successor's into the other slot before waiting on its own
        @pl.when(l == 0)
        def _():
            pl.when(valid)(window_copy(l, slot).start)

        nxt = l + _i32(1)
        pl.when(nxt < cmax)(
            window_copy(jnp.minimum(nxt, _i32(K - 1)),
                        lax.rem(nxt, _i32(2))).start)
        pl.when(valid)(window_copy(l, slot).wait)

        @pl.when(valid)
        def _():
            win = vwin[slot, lead:lead + wh, lead:lead + ww]
            r, c = rc_of(l)
            # global coords of the window's [0, 0]: the tile's interior
            # starts at global origin + (r, c), and the window k before
            g_r0 = orig_ref[0] + r - _i32(k)
            g_c0 = orig_ref[1] + c - _i32(k)
            row_g = g_r0 + lax.broadcasted_iota(jnp.int32, (wh, ww), 0)
            col_g = g_c0 + lax.broadcasted_iota(jnp.int32, (wh, ww), 1)
            in_grid = ((row_g >= 0) & (row_g < H)
                       & (col_g >= 0) & (col_g < W))
            cnt = jnp.zeros((wh, ww), win.dtype)
            for dx, dy in offsets:
                ok = ((row_g + _i32(dx) >= 0) & (row_g + _i32(dx) < H)
                      & (col_g + _i32(dy) >= 0) & (col_g + _i32(dy) < W))
                cnt = cnt + ok.astype(win.dtype)
            # off-grid window cells can have count 0; their value is 0
            cnt = jnp.maximum(cnt, jnp.asarray(1, win.dtype))
            mask = in_grid.astype(win.dtype)
            rate_v = rate_ref[0].astype(win.dtype)

            def iterated(cur):
                # the exact iterated path, mirroring active_pass (and
                # thus the dense XLA transport) term for term: barrier
                # pins the outflow so LLVM cannot contract v - rate*v
                # into an fma the dense path never emits. Between
                # in-window steps, off-grid cells are re-zeroed (the
                # dense path never computes them, so mass that a gather
                # would park there must not leak back next step —
                # _stencil_call's masked-path invariant); in-grid cells
                # multiply by exactly 1.0, a bitwise no-op. The final
                # step skips the multiply: the output interior is always
                # in-grid, and k=1 must stay the literal active_pass
                # expression (under sharding the ring holds real ghost
                # data and a single step never consumes its own output).
                for s in range(k):
                    hs, ws = cur.shape
                    outflow = pin(rate_v * cur)
                    share = outflow / cnt[s:wh - s, s:ww - s]
                    inflow = jnp.zeros((hs - 2, ws - 2), cur.dtype)
                    for dx, dy in offsets:
                        inflow = inflow + share[1 + dx:hs - 1 + dx,
                                                1 + dy:ws - 1 + dy]
                    cur = ((cur[1:hs - 1, 1:ws - 1]
                            - outflow[1:hs - 1, 1:ws - 1]) + inflow)
                    if s < k - 1:
                        cur = cur * mask[s + 1:wh - s - 1,
                                         s + 1:ww - s - 1]
                return cur

            if tap_list is None:
                tile_out = iterated(win)
                upd_ref[0] = tile_out
                anyf_ref[l] = nonzero(tile_out)
            else:
                # composed-k: the tap table on interior self-lit tiles,
                # the exact iterated path on near-edge tiles (the
                # spatially-varying boundary divisor does not compose)
                # and frontier tiles (dilated in with a zero self-tile —
                # keeping them iterated keeps the activation-timing
                # gates bitwise). Predicates mirror _stencil_call's
                # near-band form.
                tile_r0 = g_r0 + _i32(k)
                tile_c0 = g_c0 + _i32(k)
                near = ((tile_r0 <= _i32(k))
                        | (tile_r0 + _i32(th) >= _i32(H) - _i32(k))
                        | (tile_c0 <= _i32(k))
                        | (tile_c0 + _i32(tw) >= _i32(W) - _i32(k)))
                exact = near | (self_ref[l] == 0)

                @pl.when(exact)
                def _():
                    tile_out = iterated(win)
                    upd_ref[0] = tile_out
                    anyf_ref[l] = nonzero(tile_out)

                @pl.when(jnp.logical_not(exact))
                def _():
                    acc = jnp.zeros((th, tw), win.dtype)
                    for dr, dc, tap in tap_list:
                        acc = acc + jnp.asarray(tap, dtype=win.dtype) * win[
                            dr:dr + th, dc:dc + tw]
                    upd_ref[0] = acc
                    anyf_ref[l] = nonzero(acc)

        @pl.when(jnp.logical_not(valid))
        def _():
            # lanes past the active count: a zero update and a False
            # flag (lane 0 is always "valid" — on an all-zero grid it
            # computes tile 0's identically-zero update, so the scatter
            # pass never flushes an unwritten VMEM block)
            upd_ref[0] = jnp.zeros((th, tw), upd_ref.dtype)
            anyf_ref[l] = jnp.zeros((), jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(K,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=[
            pl.BlockSpec((1, th, tw),
                         lambda l, i, c, s, o, rt: (l, _i32(0), _i32(0))),
            # the [K] flags stay resident in SMEM across the grid: a
            # (1,)-block of a rank-1 VMEM array is below Mosaic's
            # 128-lane tiling (the explicit int32 index map keeps x64
            # from typing the default one i64)
            pl.BlockSpec((K,), lambda l, i, c, s, o, rt: (_i32(0),),
                         memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, fh, fw), jnp.dtype(dtype)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    # SMEM holds 32-bit scalars: a bf16 rate rides as f32 and is cast
    # in the kernel (f64 only ever runs interpreted)
    rate_dt = jnp.promote_types(dtype, jnp.float32)
    rate1 = jnp.reshape(jnp.asarray(rate, dtype=rate_dt), (1,))
    return pl.pallas_call(
        kernel,
        name="mm_active_compute",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((K, th, tw), jnp.dtype(dtype)),
            jax.ShapeDtypeStruct((K,), jnp.int32),
        ],
        interpret=interpret,
    )(ids, cnt1, selfnz, origin, rate1, padded)


def _fused_scatter_call(padded, upd, ids, cnt1, *, plan, ring, interpret):
    """Pallas pass 2: land each lane's update tile back into the padded
    state. The output ALIASES the state operand
    (``input_output_aliases``), so untouched tiles — exactly the zero
    tiles the engine skipped — keep their bytes; splitting this from the
    compute pass is the all-reads-precede-all-writes invariant."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (th, tw), (gi, gj) = plan.tile, plan.grid
    K = plan.capacity
    _i32 = np.int32
    # Mosaic DMAs whole (sublane, lane) tiles of HBM, and a tile's
    # interior sits ``ring`` cells off the tile grid. The compiled kernel
    # therefore read-modify-writes the tile-aligned region holding it
    # (lanes run in order, each DMA completes before the next lane's, so
    # a region shared with a neighbour lane carries that lane's write
    # forward); the interpreter writes the interior directly.
    sub = 8 * 4 // jnp.dtype(padded.dtype).itemsize
    rh, rw = -(-(th + ring) // sub) * sub, -(-(tw + ring) // 128) * 128

    def kernel(ids_ref, cnt_ref, upd_ref, pad_in_ref, out_ref, buf, sem):
        l = pl.program_id(0)
        cmax = jnp.clip(cnt_ref[0], _i32(1), _i32(K))

        @pl.when(l < cmax)
        def _():
            t = ids_ref[l]
            r = (t // _i32(gj)) * _i32(th)
            c = lax.rem(t, _i32(gj)) * _i32(tw)
            if interpret:
                cp = pltpu.make_async_copy(
                    upd_ref.at[0],
                    out_ref.at[pl.ds(r + _i32(ring), th),
                               pl.ds(c + _i32(ring), tw)],
                    sem)
                cp.start()
                cp.wait()
                return
            region = out_ref.at[pl.ds(r, rh), pl.ds(c, rw)]
            cp = pltpu.make_async_copy(region, buf, sem)
            cp.start()
            cp.wait()
            buf[pl.ds(ring, th), pl.ds(ring, tw)] = upd_ref[0]
            cp = pltpu.make_async_copy(buf, region, sem)
            cp.start()
            cp.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, th, tw),
                         lambda l, i, c: (l, _i32(0), _i32(0))),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        scratch_shapes=[pltpu.VMEM((rh, rw), padded.dtype),
                        pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        kernel,
        name="mm_active_scatter",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(padded.shape, padded.dtype),
        # operand order: (ids, cnt1, upd, padded) — index 3 is the state
        input_output_aliases={3: 0},
        interpret=interpret,
    )(ids, cnt1, upd, padded)


def fused_active_pass(padded, ids, count, selfnz, rate, plan: ActivePlan,
                      origin, global_shape: tuple[int, int],
                      offsets: Sequence[tuple[int, int]], dtype,
                      k: int = 1, ring: Optional[int] = None,
                      taps: Optional[np.ndarray] = None,
                      interpret: Optional[bool] = None):
    """One fused pass over the compacted active set: ``k`` flow steps
    per tile-resident window, flags computed in-kernel. Returns
    ``(padded', anyf)`` where ``anyf`` is the ``[K]`` bool per-lane
    any-nonzero of the written tiles (lanes past ``count`` are False —
    feed it straight to ``ops.active.next_tile_map``).

    ``padded`` is the ring-``ring`` padded state (``ring`` defaults to
    ``k``; a remainder pass may run ``k < ring`` on the same buffer —
    the window fetch offsets shift inward). ``origin`` is the state's
    global (row, col) offset as a traced ``[2]`` int32 (zeros on a full
    grid; the shard offset under sharding). ``selfnz`` is the ``[K]``
    pre-pass self-tile-nonzero gather (``int32``; only consulted when a
    tap table is armed — frontier tiles keep the exact iterated path).
    ``interpret`` None resolves from where ``padded`` lives
    (``resolve_interpret``).
    """
    if ring is None:
        ring = k
    if interpret is None:
        interpret = resolve_interpret(padded)
    if k < 1 or k > min(plan.tile):
        raise ValueError(
            f"fused pass depth k={k} must be in [1, min(tile)="
            f"{min(plan.tile)}] (ring-1 dilation exactness bound)")
    if ring < k:
        raise ValueError(f"padding ring {ring} shallower than pass depth "
                         f"{k}")
    cnt1 = jnp.reshape(jnp.asarray(count, jnp.int32), (1,))
    origin = jnp.asarray(origin, jnp.int32)
    upd, anyf = _fused_compute_call(
        padded, ids, cnt1, jnp.asarray(selfnz, jnp.int32), origin,
        rate=rate, plan=plan, global_shape=tuple(global_shape),
        offsets=tuple(offsets), dtype=dtype, k=int(k), ring=int(ring),
        taps=taps, interpret=bool(interpret))
    padded = _fused_scatter_call(padded, upd, ids, cnt1, plan=plan,
                                 ring=int(ring), interpret=bool(interpret))
    return padded, anyf != 0


# -- dense fallback at pass depth k ------------------------------------------

def dense_chunk_from_padded(padded, rate, counts, offsets, dtype, k: int,
                            ring: int):
    """``k`` dense XLA transport steps on the interior of a ring-``ring``
    padded state (the fused runner's fallback: bitwise the serial dense
    path, once per fallback EVENT). Returns the re-padded state with the
    ring re-zeroed (the engine invariant)."""
    v = padded[ring:-ring, ring:-ring]
    for _ in range(k):
        v = transport(v, jnp.asarray(rate, dtype) * v, counts, offsets)
    return jnp.pad(v, ring)


# -- the amortized whole-run runner ------------------------------------------

def build_fused_runner(shape: tuple[int, int], rates: dict,
                       offsets: Sequence[tuple[int, int]], dtype,
                       origin: tuple[int, int] = (0, 0),
                       global_shape: Optional[tuple[int, int]] = None,
                       plan: Optional[ActivePlan] = None,
                       k: int = 1,
                       dense_fns: Optional[dict] = None,
                       traced_rates: bool = False,
                       track_dirty: bool = False,
                       interpret: Optional[bool] = None) -> Callable:
    """Whole-run fused active stepper — ``ops.active.build_active_runner``
    with the gather/compute/flags replaced by the fused Pallas pass:
    ``run(values, n[, rates_vec]) -> (values, stats)`` where ``stats`` is
    ``(fallback_events, active_tiles_total, flags_fused[, dirty_map])``.

    Structure (the measured PR 3 loop shape, per pass instead of per
    step): the state is padded ONCE to ring ``k`` and carried;
    ``q = n // k`` full-depth passes run in an inner while_loop with no
    cond on the fast path, the dense fallback (``k`` transport steps)
    sits in the outer loop and fires per fallback EVENT; the remainder
    ``r = n % k`` steps run the same nest at depth 1 on the same buffer.
    Per-pass flags come from the kernel (``flags_fused`` counts those
    passes); the only per-pass XLA work is the [gi, gj] bool dilation,
    the cumsum compaction and the flag scatter — never a read of the
    grid (the auditor's ``jaxpr-fused-flags`` contract).

    ``rates``/``traced_rates``/``dense_fns``/``track_dirty`` follow
    ``build_active_runner``'s contract; the dirty map unions kernel-
    written tiles (the flagged set) per fused pass and the ring-1
    dilation of the pre-chunk map per dense event (a k-step dense chunk
    moves mass k <= min(tile) cells — within one tile ring)."""
    shape = tuple(shape)
    gshape = tuple(global_shape) if global_shape is not None else shape
    offsets = tuple((int(dx), int(dy)) for dx, dy in offsets)
    dtype = jnp.dtype(dtype)
    if plan is None:
        plan = plan_for(shape)
    k = int(k)
    if k < 1 or k > min(min(plan.tile), MAX_FUSED_K):
        raise ValueError(
            f"fused runner depth k={k} must divide into "
            f"[1, min(min(tile), {MAX_FUSED_K})] for tile {plan.tile}")
    th, tw = plan.tile
    dense_fns = dense_fns or {}
    attrs = list(rates)
    thresh = np.int32(plan.fallback_tiles)
    taps_by_attr = {}

    def rate_of(attr, rates_vec):
        r = rates[attr]
        if traced_rates:
            acc = jnp.zeros((), rates_vec.dtype)
            for i in r:
                acc = acc + rates_vec[i]
            return acc
        return r

    if not traced_rates:
        # tap tables need a CONCRETE rate; per-lane traced rates run the
        # iterated path at every depth (still k steps per window)
        for a in attrs:
            taps_by_attr[a] = _fused_taps(float(rates[a]), offsets, k)

    def _dilated(tmap):
        flags = dilate_tile_map(tmap)
        return flags, jnp.sum(flags, dtype=jnp.int32)

    def run(values, n, rates_vec=None):
        counts = neighbor_counts_traced(shape, offsets, origin, gshape,
                                        dtype)
        orig_vec = jnp.asarray(origin, jnp.int32)
        fb = jnp.zeros((), jnp.int32)
        at = jnp.zeros((), jnp.float32)
        ff = jnp.zeros((), jnp.int32)
        dm = (jnp.zeros(plan.grid, bool),) if track_dirty else ()
        q = n // np.int32(k)
        r = n - q * np.int32(k)
        out = dict(values)
        for a in attrs:
            rate = rate_of(a, rates_vec)
            taps = taps_by_attr.get(a)

            def phase(carry, npasses, depth, _rate=rate, _a=a,
                      _taps=None):
                """One while-nest: ``npasses`` passes of ``depth`` steps
                — fused on the fast path, dense per fallback event."""

                def inner_cond(c, _np=npasses):
                    _, cnt = _dilated(c[1])
                    return (c[2] < _np) & (cnt <= thresh)

                def inner_body(c):
                    p, tm, i, fb_, at_, ff_, *dm_ = c
                    flags, cnt = _dilated(tm)
                    ids, _ = compact_tile_ids(flags, plan)
                    selfnz = tm.reshape(-1)[ids].astype(jnp.int32)
                    p2, anyf = fused_active_pass(
                        p, ids, cnt, selfnz, _rate, plan, orig_vec,
                        gshape, offsets, dtype, k=depth, ring=k,
                        taps=_taps, interpret=interpret)
                    if track_dirty:
                        dm_ = (dm_[0] | flags,)
                    return (p2, next_tile_map(anyf, ids, cnt, plan),
                            i + 1, fb_, at_ + cnt.astype(jnp.float32),
                            ff_ + 1, *dm_)

                def outer_body(c, _np=npasses):
                    c = lax.while_loop(inner_cond, inner_body, c)
                    p, tm, i, fb_, at_, ff_, *dm_ = c

                    def dense_pass(args):
                        pp, tm_, i_, fb__, at__, ff__, *dm__ = args
                        _, cnt = _dilated(tm_)
                        fn = dense_fns.get(_a)
                        if fn is not None:
                            v = pp[k:-k, k:-k]
                            for _s in range(depth):
                                v = fn(v)
                            p2 = jnp.pad(v, k)
                        else:
                            p2 = dense_chunk_from_padded(
                                pp, _rate, counts, offsets, dtype,
                                depth, k)
                        if track_dirty:
                            dm__ = (dm__[0] | dilate_tile_map(tm_),)
                        return (p2,
                                tile_nonzero_map(p2[k:-k, k:-k], plan),
                                i_ + 1, fb__ + 1,
                                at__ + cnt.astype(jnp.float32), ff__,
                                *dm__)

                    p, tm, i, fb_, at_, ff_, *dm_ = lax.cond(
                        i < _np, dense_pass, lambda args: args,
                        (p, tm, i, fb_, at_, ff_, *dm_))
                    return (p, tm, i, fb_, at_, ff_, *dm_)

                return lax.while_loop(
                    lambda c, _np=npasses: c[2] < _np, outer_body, carry)

            c0 = (jnp.pad(values[a], k),
                  tile_nonzero_map(values[a], plan),
                  jnp.zeros((), jnp.int32), fb, at, ff, *dm)
            c1 = phase(c0, q, k, _taps=taps)
            # remainder steps at depth 1 on the same ring-k buffer
            # (taps never apply at depth 1 — the k=1 bitwise contract)
            c2 = phase((c1[0], c1[1], jnp.zeros((), jnp.int32),
                        *c1[3:]), r, 1, _taps=None)
            padded, _, _, fb, at, ff, *dm = c2
            out[a] = padded[k:-k, k:-k]
            dm = tuple(dm)
        if track_dirty:
            return out, (fb, at, ff, dm[0])
        return out, (fb, at, ff)

    return run


# -- stateless per-step form (Model.make_step impl="active_fused") -----------

class FusedActiveStep:
    """Stateless fused active step for one channel: pad → activity →
    compact → fused kernel pass(es) (or the dense fallback, same call)
    → unpad. One ``__call__`` advances ``k * passes`` flow steps (the
    ``make_step(impl='active_fused', substeps=...)`` contract:
    ``k`` auto-chosen dividing ``substeps``, ``passes = substeps / k``).
    Activity is recomputed from the values each call, so interleaved
    point-flow deposits and restores are seen next call — the
    ``ActiveDiffusionStep`` composition contract. ``SerialExecutor``'s
    amortized runner (``build_fused_runner``) is the whole-run fast
    path."""

    def __init__(self, shape: tuple[int, int], rate: float, dtype,
                 offsets: Sequence[tuple[int, int]] = MOORE_OFFSETS,
                 origin: tuple[int, int] = (0, 0),
                 global_shape: Optional[tuple[int, int]] = None,
                 tile: Optional[tuple[int, int]] = None,
                 capacity: Optional[int] = None,
                 max_active_frac: float = 0.25,
                 k: int = 1, passes: int = 1,
                 dense_fn: Optional[Callable] = None,
                 interpret: Optional[bool] = None):
        self.shape = tuple(shape)
        self.rate = float(rate)
        self.dtype = jnp.dtype(dtype)
        self.offsets = tuple((int(dx), int(dy)) for dx, dy in offsets)
        self.origin = (int(origin[0]), int(origin[1]))
        self.global_shape = (tuple(global_shape)
                             if global_shape is not None else self.shape)
        self.plan = plan_for(self.shape, tile=tile, capacity=capacity,
                             max_active_frac=max_active_frac)
        self.k = int(k)
        self.passes = int(passes)
        self.interpret = interpret
        if self.k < 1 or self.k > min(min(self.plan.tile), MAX_FUSED_K):
            raise ValueError(
                f"k={k} outside [1, min(min(tile), {MAX_FUSED_K})] for "
                f"tile {self.plan.tile}")
        self.taps = _fused_taps(self.rate, self.offsets, self.k)
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
        plan, k = self.plan, self.k
        orig_vec = jnp.asarray(self.origin, jnp.int32)
        for _ in range(self.passes):
            tmap = tile_nonzero_map(v, plan)
            flags = dilate_tile_map(tmap)
            count = jnp.sum(flags, dtype=jnp.int32)
            pred = count > np.int32(plan.fallback_tiles)

            def dense_branch(vv):
                out = vv
                for _s in range(k):
                    out = self.dense_fn(out)
                return out

            def active_branch(vv, _tmap=tmap, _flags=flags,
                              _count=count):
                padded = jnp.pad(vv, k)
                ids, _ = compact_tile_ids(_flags, plan)
                selfnz = _tmap.reshape(-1)[ids].astype(jnp.int32)
                padded, _anyf = fused_active_pass(
                    padded, ids, _count, selfnz, self.rate, plan,
                    orig_vec, self.global_shape, self.offsets,
                    self.dtype, k=k, ring=k, taps=self.taps,
                    interpret=self.interpret)
                return padded[k:-k, k:-k]

            v = lax.cond(pred, dense_branch, active_branch, v)
        return v
