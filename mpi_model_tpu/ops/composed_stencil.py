"""Composed k-step stencil filters: break the radius-1 ceiling.

The round-5 roofline investigation (docs/DESIGN.md; measured before PR 1,
not yet re-measured on this tree) put ~3.2 ms/step as
the architectural bound for any *radius-1* fused stencil on this chip's
VPU — ~76% of the slots go to the ±1 slice shifts, work proportional to
the useful flops — and concluded that beating it "needs a different op
— larger effective radius per pass via higher-order composed filters —
not a better schedule of this one".

This module is that op. For uniform-rate Diffusion — the config-5
workload, and the linear update rule of the reference
(``/root/reference/src/Model.hpp:176-235``) — the flow step on interior
cells is a LINEAR operator:

    S = (1 - rate) * δ + (rate / k') * N        (k' = |offsets|,
                                                 N = neighbor-sum)

so k applications compose into ONE pass of the k-fold filter ``S^k``,
an explicit ``(2k+1) x (2k+1)`` tap table — algebraically exact on
cells at distance >= k from the true grid edge. The near-boundary band
(distance < k, where the per-cell divisor corrections make the operator
spatially varying) is NOT composable; it keeps the exact iterated
radius-1 path via the kernels' existing near/interior split
(``ops.pallas_stencil._stencil_call``'s ``interior_fn`` hook replaces
only the interior branch).

Two lowerings of the composed filter:

- ``variant="vpu"``: the binomial factorization.  δ and N commute, so
  ``S^k = Σ_j C(k,j) (1-rate)^(k-j) (rate/k')^j N^j`` — the
  neighborhood-sum powers ``N^j`` are built iteratively (for Moore-8
  the box-power form ``S = α δ + β B``, ``B`` the separable 3x3 sum,
  is used instead: 4 shift-adds per power instead of 8) and blended
  with precomputed f64 coefficients. Shift-slot count is ~identical to
  k iterated steps — this variant measures whether dropping the
  per-step multiplies and round-trips through the output registers
  buys anything on the VPU (the round-5 slot accounting predicts
  it cannot, which is half the point: the null must be measured).
- ``variant="mxu"``: the lane-direction banded contraction, retested at
  the tap counts where round 5 predicted it starts to pay. For each of
  the ``2k+1`` sublane offsets, the row's 1-D taps become a banded
  ``(128 + 2k, 128)`` matrix applied per 128-lane output block with an
  f32-accumulating ``dot`` — at 3 taps the 128-wide contraction wastes
  43/45ths of the MXU (round 5 measured 1.08x); at 9-17 taps the waste
  factor drops 3-6x and the flops/cell-step settle near
  ``2·(128+2k)·(2k+1)/k`` ≈ 550-620, constant in k. The ±k sublane
  shifts ride the cheap direction.

Tap tables are composed once per ``(rate, offsets, k)`` in f64 and
cached by fingerprint (mirroring ``ops.flow.Flow.fingerprint``'s
hashable-key design); the interior hooks are cached on the same key so
``jax.jit``'s static ``interior_fn`` argument sees a stable identity
and never retraces a geometry twice.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.cell import MOORE_OFFSETS
from .pallas_stencil import (
    LANE,
    _pallas_halo_step,
    _pallas_step,
    _pick_block,
    _sublane,
    _validate_block,
    check_offsets,
    resolve_interpret,
)

#: tap count from which the MXU banded contraction is preferred by
#: ``variant="auto"`` — the round-5 break-even analysis: below 9 taps
#: the 128-wide contraction's waste factor eats the MXU's flop
#: advantage (measured 0.85-1.08x at 3 taps)
MXU_MIN_TAPS = 9


# -- tap-table composition (cached by fingerprint) ---------------------------

_TAPS_CACHE: dict[tuple, np.ndarray] = {}


def taps_fingerprint(rate: float, offsets: Sequence[tuple[int, int]],
                     k: int) -> tuple:
    """Hashable identity of a composed tap table — the cache key, same
    design as ``Flow.fingerprint`` (hashable tuples of scalars)."""
    return (float(rate), tuple((int(dx), int(dy)) for dx, dy in offsets),
            int(k))


def _conv2_full(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Full 2-D convolution (f64) — table composition needs no scipy."""
    ha, wa = a.shape
    hb, wb = b.shape
    out = np.zeros((ha + hb - 1, wa + wb - 1), np.float64)
    for p in range(ha):
        for q in range(wa):
            if a[p, q] != 0.0:
                out[p:p + hb, q:q + wb] += a[p, q] * b
    return out


def composed_taps(rate: float, offsets: Sequence[tuple[int, int]],
                  k: int) -> np.ndarray:
    """The ``(2k+1, 2k+1)`` f64 tap table of ``S^k``.

    Correlation with table A then table B equals correlation with the
    plain convolution ``A * B`` (shift algebra; holds for asymmetric
    neighborhoods too), so the k-step table is the k-fold
    self-convolution of the one-step table. Taps sum to 1 up to f64
    rounding — each step conserves interior mass, so the composition
    does. Returns a cached array; treat it as read-only."""
    offsets = check_offsets(offsets)
    k = int(k)
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    key = taps_fingerprint(rate, offsets, k)
    cached = _TAPS_CACHE.get(key)
    if cached is not None:
        return cached
    w1 = np.zeros((3, 3), np.float64)
    w1[1, 1] = 1.0 - float(rate)
    for dx, dy in offsets:
        w1[1 + dx, 1 + dy] += float(rate) / len(offsets)
    wk = w1
    for _ in range(k - 1):
        wk = _conv2_full(w1, wk)
    wk.setflags(write=False)
    _TAPS_CACHE[key] = wk
    return wk


# -- interior hooks ----------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _interior_hook(rate: float, offsets: tuple, k: int, variant: str,
                   compute_dtype_str: str):
    """Build (and cache — jit staticness needs a stable identity) the
    interior-tile hook for ``_stencil_call``: region ``(bh+2k, bw+2k)``
    in ``compute_dtype`` → output ``(bh, bw)``, one composed pass."""
    cdt = jnp.dtype(compute_dtype_str)
    if variant == "vpu":
        return _make_vpu_hook(rate, offsets, k)
    if variant == "mxu":
        return _make_mxu_hook(rate, offsets, k, cdt)
    raise ValueError(f"unknown composed variant {variant!r}")


def _make_vpu_hook(rate: float, offsets: tuple, k: int):
    kk = len(offsets)
    moore = set(offsets) == set(MOORE_OFFSETS)
    # Moore: S = α δ + β B with B the FULL 3x3 box (separable band
    # trick, centre included), α = 1 - rate - rate/8. Other
    # neighborhoods: S = (1-rate) δ + (rate/k') N with N the plain
    # neighbor sum. Both commute with δ, so the binomial expansion is
    # exact; coefficients are composed in f64 at build time.
    if moore:
        alpha = 1.0 - rate - rate / kk
    else:
        alpha = 1.0 - rate
    beta = rate / kk
    coefs = [math.comb(k, j) * (alpha ** (k - j)) * (beta ** j)
             for j in range(k + 1)]

    def hook(region):
        mh, mw = region.shape
        acc = coefs[0] * region[k:mh - k, k:mw - k]
        cur = region
        for j in range(1, k + 1):
            hs, ws = cur.shape
            if moore:
                band = cur[0:hs - 2, :] + cur[1:hs - 1, :] + cur[2:hs, :]
                cur = (band[:, 0:ws - 2] + band[:, 1:ws - 1]
                       + band[:, 2:ws])
            else:
                nxt = None
                for dx, dy in offsets:
                    t = cur[1 + dx:hs - 1 + dx, 1 + dy:ws - 1 + dy]
                    nxt = t if nxt is None else nxt + t
                cur = nxt
            m = k - j
            hs, ws = cur.shape
            acc = acc + coefs[j] * cur[m:hs - m, m:ws - m]
        return acc

    return hook


def _make_mxu_hook(rate: float, offsets: tuple, k: int, cdt):
    taps = composed_taps(rate, offsets, k)

    def hook(region):
        mh, mw = region.shape
        bh, bw = mh - 2 * k, mw - 2 * k
        if bw % LANE != 0:
            raise ValueError(
                f"the MXU composed variant contracts per {LANE}-lane "
                f"output block; block width {bw} is not a multiple "
                f"of {LANE} (use variant='vpu' or a {LANE}-aligned "
                "block)")
        # banded matrices are built once per tile from iotas — band
        # construction is ~1% of the contraction flops and keeps the
        # taps out of the operand list. d_i = m - c picks the diagonal:
        # out[r, c] = Σ_m slab[r, m] · band[m, c] with
        # band[m, c] = taps[k+dr, m - c] on the 0..2k band.
        m_i = lax.broadcasted_iota(jnp.int32, (LANE + 2 * k, LANE), 0)
        c_i = lax.broadcasted_iota(jnp.int32, (LANE + 2 * k, LANE), 1)
        d_i = m_i - c_i
        acc = None
        for dr in range(-k, k + 1):
            band = jnp.zeros((LANE + 2 * k, LANE), jnp.float32)
            for dc in range(2 * k + 1):
                band = band + jnp.where(d_i == dc,
                                        float(taps[k + dr, dc]), 0.0)
            band = band.astype(cdt)
            rows = region[k + dr:k + dr + bh, :]
            blocks = []
            for b in range(bw // LANE):
                slab = rows[:, b * LANE:b * LANE + LANE + 2 * k]
                # bf16 compute_dtype → native bf16 MXU inputs; the
                # accumulator stays f32 either way
                blocks.append(jnp.dot(
                    slab, band, preferred_element_type=jnp.float32))
            part = (jnp.concatenate(blocks, axis=1) if len(blocks) > 1
                    else blocks[0])
            acc = part if acc is None else acc + part
        return acc

    return hook


def _resolve_variant(variant: str, k: int, bw: int) -> str:
    if variant not in ("auto", "vpu", "mxu"):
        raise ValueError(f"unknown composed variant {variant!r}")
    if variant == "auto":
        return ("mxu" if (2 * k + 1) >= MXU_MIN_TAPS and bw % LANE == 0
                else "vpu")
    return variant


# -- k selection -------------------------------------------------------------

def max_k(shape: tuple[int, int], dtype,
          block: Optional[tuple[int, int]] = None) -> int:
    """Deepest composable k for this geometry: the window's ghost depth
    ``min(hr, hc)`` — 8 rows f32 / 16 bf16 at default blocks (the same
    bound the iterated multi-step kernel obeys)."""
    h, w = shape
    sub = _sublane(dtype)
    if block is None:
        block = (_pick_block(h, 512, sub), _pick_block(w, 512, LANE))
    else:
        block = _validate_block(h, w, block)
    return min(sub, block[0], LANE, block[1])


def choose_k(substeps: int, shape: tuple[int, int], dtype,
             block: Optional[tuple[int, int]] = None) -> int:
    """Largest divisor of ``substeps`` that the window geometry can
    compose — the auto-k rule for ``impl="composed"``: a scan of
    ``substeps`` flow steps then runs as ``substeps/k`` composed passes
    with no remainder step."""
    substeps = int(substeps)
    if substeps < 1:
        raise ValueError(f"substeps must be >= 1, got {substeps}")
    cap = max_k(shape, dtype, block)
    for k in range(min(substeps, cap), 0, -1):
        if substeps % k == 0:
            return k
    return 1


# -- public steps ------------------------------------------------------------

def composed_dense_step(
    values: jax.Array,
    rate: float,
    k: int,
    offsets: Sequence[tuple[int, int]] = MOORE_OFFSETS,
    block: Optional[tuple[int, int]] = None,
    interpret: Optional[bool] = None,
    variant: str = "auto",
    compute_dtype=None,
) -> jax.Array:
    """``k`` uniform-rate flow steps as ONE composed-filter pass.

    Semantics: exactly ``pallas_dense_step(values, rate, nsteps=k)`` —
    interior cells get the single ``(2k+1)²``-tap pass (algebraically
    equal to the k iterated steps; floating-point grouping differs by
    ~k ulp), the near-boundary band gets the exact iterated masked
    radius-1 path, and the conservation contract holds to the same
    tolerances. ``variant`` picks the interior lowering (module
    docstring); ``"auto"`` = MXU at >= 9 taps on 128-aligned blocks,
    VPU otherwise."""
    offsets = check_offsets(offsets)
    k = int(k)
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    h, w = values.shape
    if interpret is None:
        interpret = resolve_interpret(values)
    if block is None:
        sub = _sublane(values.dtype)
        block = (_pick_block(h, 512, sub), _pick_block(w, 512, LANE))
    else:
        block = _validate_block(h, w, block)
    var = _resolve_variant(variant, k, block[1])
    cdt = jnp.dtype(compute_dtype or jnp.float32)
    hook = _interior_hook(float(rate), offsets, k, var, str(cdt))
    return _pallas_step(values, rate=float(rate), block=tuple(block),
                        offsets=offsets, interpret=bool(interpret),
                        nsteps=k, compute_dtype=cdt, interior_fn=hook)


def composed_halo_step(
    values: jax.Array,
    ring: dict,
    origin: jax.Array,
    global_shape: tuple[int, int],
    rate: float,
    k: int,
    offsets: Sequence[tuple[int, int]] = MOORE_OFFSETS,
    block: Optional[tuple[int, int]] = None,
    interpret: Optional[bool] = None,
    variant: str = "auto",
    compute_dtype=None,
) -> jax.Array:
    """The sharded form: ``k`` flow steps as one composed pass consuming
    a depth->=k ppermute ghost ring (``parallel.halo.exchange_ring``) —
    one collective round AND one composed pass per k steps. Semantics
    match ``pallas_halo_step(..., nsteps=k)``; near-global-edge tiles
    keep the exact iterated path (origin-aware, like the iterated halo
    kernel)."""
    offsets = check_offsets(offsets)
    k = int(k)
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    h, w = values.shape
    d = int(ring["n"].shape[0])
    if interpret is None:
        interpret = resolve_interpret(values)
    if block is None:
        sub = _sublane(values.dtype)
        block = (_pick_block(h, 512, sub), _pick_block(w, 512, LANE))
    else:
        block = _validate_block(h, w, block)
    hr = min(_sublane(values.dtype), block[0])
    hc = min(LANE, block[1])
    if d > min(hr, hc):
        raise ValueError(
            f"ring depth {d} exceeds the slab capacity min(hr={hr}, "
            f"hc={hc}) for block {tuple(block)}")
    if k > d:
        raise ValueError(
            f"k={k} needs a ghost ring at least that deep; got depth {d} "
            f"(exchange_ring(..., depth={k}))")
    var = _resolve_variant(variant, k, block[1])
    cdt = jnp.dtype(compute_dtype or jnp.float32)
    hook = _interior_hook(float(rate), offsets, k, var, str(cdt))
    origin = jnp.asarray(origin, jnp.int32)
    return _pallas_halo_step(
        values, ring["n"], ring["s"], ring["w"], ring["e"],
        ring["nw"], ring["ne"], ring["sw"], ring["se"], origin,
        rate=float(rate), block=tuple(block), offsets=offsets,
        interpret=bool(interpret), global_shape=tuple(global_shape),
        nsteps=k, compute_dtype=cdt, interior_fn=hook)


class ComposedDiffusionStep:
    """Reusable composed stepper bound to one geometry/rate: each call
    advances ``k`` flow steps in one pass (the composed counterpart of
    ``PallasDiffusionStep`` with ``nsteps=k``)."""

    def __init__(self, shape: tuple[int, int], rate: float, k: int,
                 dtype=jnp.float32,
                 offsets: Sequence[tuple[int, int]] = MOORE_OFFSETS,
                 block: Optional[tuple[int, int]] = None,
                 interpret: Optional[bool] = None,
                 variant: str = "auto", compute_dtype=None):
        self.shape = tuple(shape)
        self.rate = float(rate)
        self.k = int(k)
        self.offsets = check_offsets(offsets)
        self.block = block
        self.interpret = interpret
        self.variant = variant
        self.compute_dtype = compute_dtype
        if self.k > max_k(self.shape, dtype, block):
            raise ValueError(
                f"k={self.k} exceeds the window ghost depth "
                f"{max_k(self.shape, dtype, block)} for shape "
                f"{self.shape} dtype {jnp.dtype(dtype)} block {block}")

    def __call__(self, values: jax.Array) -> jax.Array:
        return composed_dense_step(
            values, self.rate, self.k, self.offsets, self.block,
            self.interpret, variant=self.variant,
            compute_dtype=self.compute_dtype)
