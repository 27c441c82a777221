"""The ONE registered lowering from Flow IR terms to executable steps.

Every step engine — the dense XLA step, the composed path (linear terms
only; nonlinear forces k=1), the active-tile engine and the sharded
per-shard step — consumes terms through THIS module. The engines differ
only in the **context** they construct (how arrays are stored, padded
and gathered); the per-term physics is written exactly once, in the
``@register_lowering`` entry for that term kind, and composed out of
the context's three primitives:

- ``transport_update(channel, rate, weights)`` — the ring-1
  mass-conserving redistribution (``ops.stencil.transport``'s
  expression, term for term, in every context — the cross-impl
  bitwise-at-f64 gates in ``tests/test_ir.py`` pin this);
- ``apply_amount(channel, amount, sign)`` — a pointwise add/subtract;
- ``add_budget(channel, amount, sign)`` — integrate a declared
  source/sink's signed contribution into its hidden budget channel.

The registry is machine-checked: the jaxpr auditor's
``jaxpr-term-registry`` rule asserts every term kind has exactly one
lowering and that it lives HERE (no impl-private term branches), and
the astlint ``hardcoded-physics`` rule warns on new transport-shaped
arithmetic growing outside ``ir/``/``ops/`` — the four-way hand-
mirroring that motivated this subsystem cannot silently return.

All reads are PRE-STEP: every term's amounts are evaluated against the
step's input values, then applied sequentially in term order — the
summed-outflow discipline of the hand-written step, generalized.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.stencil import (neighbor_counts_traced, shift2d, transport,
                           weighted_counts_traced)
from .expr import evaluate
from .terms import Sink, Source, Term, Transfer, Transport

# -- the registry -------------------------------------------------------------

#: term kind -> lowering (the audited single-lowering map)
LOWERINGS: dict[type, object] = {}


def register_lowering(term_cls: type):
    """Register the one lowering for ``term_cls``; a second registration
    is an error (the no-shadow half of the ``jaxpr-term-registry``
    contract)."""
    def deco(obj):
        if term_cls in LOWERINGS:
            raise ValueError(
                f"term kind {term_cls.__name__} already has a registered "
                f"lowering ({LOWERINGS[term_cls]!r}); every kind gets "
                "exactly one")
        LOWERINGS[term_cls] = obj
        return obj
    return deco


def lowering_for(term: Term):
    low = LOWERINGS.get(type(term))
    if low is None:
        raise TypeError(
            f"no registered lowering for term kind "
            f"{type(term).__name__} (register one in ir.lower — the "
            "jaxpr-term-registry rule audits this map)")
    return low


# -- step metadata ------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StepMeta:
    """Static geometry the lowering closes over (the same identity the
    hand-written step builders key their caches with)."""

    shape: tuple[int, int]
    origin: tuple[int, int]
    global_shape: tuple[int, int]
    dtype: object
    offsets: tuple[tuple[int, int], ...]


def _opposite_weights(offsets, weights) -> list[float]:
    """weights reindexed by NEGATED offset: the tap a cell RECEIVES along
    ``d`` is the tap its neighbor SENDS along ``-d``. Weighted transport
    therefore needs a symmetric offset set (Moore/von Neumann are)."""
    idx = {(dx, dy): i for i, (dx, dy) in enumerate(offsets)}
    out = []
    for dx, dy in offsets:
        j = idx.get((-dx, -dy))
        if j is None:
            raise ValueError(
                f"weighted Transport needs a symmetric offset set; "
                f"offset ({dx}, {dy}) has no opposite in {tuple(offsets)}")
        out.append(weights[j])
    return out


# -- contexts -----------------------------------------------------------------

class _Ctx:
    """Shared context machinery: pre-step reads, sequential current-value
    accumulation, the pointwise primitives. Subclasses provide the
    geometry-specific ``transport_update``."""

    def __init__(self, pre: dict, meta: StepMeta):
        self.pre = pre          # channel -> pre-step array (interior view)
        self.cur = dict(pre)    # accumulates term applications in order
        self.meta = meta
        self.dtype = jnp.dtype(meta.dtype)

    def env(self) -> dict:
        return self.pre

    def apply_amount(self, channel: str, amount, sign: int) -> None:
        if sign >= 0:
            self.cur[channel] = self.cur[channel] + amount
        else:
            self.cur[channel] = self.cur[channel] - amount

    def add_budget(self, channel: str, amount, sign: int) -> None:
        if channel not in self.cur:
            raise ValueError(
                f"budget channel {channel!r} missing from the space — "
                "build IR spaces with FlowIRModel.create_space (or "
                "with_budget_channels) so declared sources/sinks have "
                "their integration accumulator")
        self.apply_amount(channel, amount, sign)

    def transport_update(self, channel, rate_c, weights) -> None:
        raise NotImplementedError


class DenseCtx(_Ctx):
    """Full-grid arrays; uniform transport IS the hand-written
    ``ops.stencil.transport`` call — the bitwise single-source-of-truth
    anchor the diffusion re-expression gate checks."""

    def __init__(self, pre: dict, meta: StepMeta, counts):
        super().__init__(pre, meta)
        self.counts = counts

    def transport_update(self, channel, rate_c, weights) -> None:
        # the barrier materializes the outflow once: its VALUE already
        # equals the hand-written step's (outflow has two consumers —
        # the subtraction and the share division — so it was never fma-
        # contracted), but pinning it keeps the value stable when this
        # same lowering compiles inside other fusion contexts (the
        # active engine's lax.cond fallback, the vmapped ensemble step)
        outflow = lax.optimization_barrier(rate_c * self.pre[channel])
        if weights is None:
            self.cur[channel] = transport(self.cur[channel], outflow,
                                          self.counts, self.meta.offsets)
            return
        offsets = self.meta.offsets
        wcnt = weighted_counts_traced(
            self.meta.shape, offsets, weights, self.meta.origin,
            self.meta.global_shape, self.dtype)
        # a STRANDED cell (every in-bounds tap has zero weight, e.g. a
        # one-direction weight set at the boundary) has nowhere to
        # shed: it sheds NOTHING — masking before the clamped divide
        # keeps the term conserving and finite (an unclamped divide
        # would spread inf/NaN; a clamped-but-unmasked one leaks mass).
        # The padded/window contexts apply the identical rule.
        shed = jnp.where(wcnt > 0, outflow, jnp.asarray(0, self.dtype))
        share = shed / jnp.maximum(wcnt, jnp.asarray(1, self.dtype))
        w_opp = _opposite_weights(offsets, weights)
        inflow = jnp.zeros_like(share)
        for w, (dx, dy) in zip(w_opp, offsets):
            inflow = inflow + jnp.asarray(w, self.dtype) * shift2d(
                share, dx, dy)
        self.cur[channel] = self.cur[channel] - shed + inflow


class PaddedCtx(_Ctx):
    """Per-shard ghost-ring context (ShardMapExecutor): transport
    channels arrive one-cell padded with REAL neighbor-shard values
    (zeros beyond the true grid); outflow is computed on the padded
    array and masked to the partition, so a ghost cell's share equals
    the value the owning shard computes — the value-exchange bitwise
    argument of the active engine (``ops.active``)."""

    def __init__(self, pre: dict, meta: StepMeta, padded: dict,
                 counts_pad, wcounts_pad: Callable, mask_pb):
        super().__init__(pre, meta)
        self.padded = padded          # channel -> [h+2, w+2] pre values
        self.counts_pad = counts_pad  # clamped >= 1
        self._wcounts_pad = wcounts_pad  # weights -> padded weighted counts
        self.mask_pb = mask_pb        # bool [h+2, w+2]: inside partition

    def _transport(self, channel, rate_c, counts_p, weights):
        h, w = self.meta.shape
        p = self.padded[channel]
        zero = jnp.asarray(0, self.dtype)
        of_p = jnp.where(self.mask_pb, rate_c * p, zero)
        if weights is not None:
            # a stranded cell sheds nothing (DenseCtx's identical rule —
            # counts_p is RAW here so the mask sees true zeros)
            of_p = jnp.where(counts_p > 0, of_p, zero)
            counts_p = jnp.maximum(counts_p, jnp.asarray(1, self.dtype))
        share_p = of_p / counts_p
        offsets = self.meta.offsets
        taps = ([1.0] * len(offsets) if weights is None
                else _opposite_weights(offsets, weights))
        inflow = jnp.zeros((h, w), self.dtype)
        for wt, (dx, dy) in zip(taps, offsets):
            s = lax.slice(share_p, (1 + dx, 1 + dy),
                          (1 + dx + h, 1 + dy + w))
            inflow = inflow + (s if weights is None
                               else jnp.asarray(wt, self.dtype) * s)
        return ((self.cur[channel] - of_p[1:-1, 1:-1]) + inflow)

    def transport_update(self, channel, rate_c, weights) -> None:
        counts_p = (self.counts_pad if weights is None
                    else self._wcounts_pad(weights))
        self.cur[channel] = self._transport(channel, rate_c, counts_p,
                                            weights)


class WindowCtx(_Ctx):
    """Per-active-tile window context (the active engine): arrays are
    ``[th+2, tw+2]`` windows gathered from the padded grid; neighbor
    counts come from the window's GLOBAL coordinates; the outflow is
    pinned behind an ``optimization_barrier`` exactly like
    ``ops.active.active_pass`` (the anti-FMA-contraction discipline the
    bitwise gates exist to catch)."""

    def __init__(self, pre_int: dict, meta: StepMeta, wins: dict,
                 counts_win, wcounts_win: Callable):
        super().__init__(pre_int, meta)
        self.wins = wins              # channel -> [th+2, tw+2] pre window
        self.counts_win = counts_win  # clamped >= 1
        self._wcounts_win = wcounts_win

    def transport_update(self, channel, rate_c, weights) -> None:
        win = self.wins[channel]
        th = win.shape[0] - 2
        tw = win.shape[1] - 2
        outflow = lax.optimization_barrier(rate_c * win)
        if weights is None:
            counts = self.counts_win
        else:
            counts = self._wcounts_win(weights)  # RAW weighted counts
            # stranded cells shed nothing (the shared weighted rule)
            outflow = jnp.where(counts > 0, outflow,
                                jnp.asarray(0, self.dtype))
            counts = jnp.maximum(counts, jnp.asarray(1, self.dtype))
        share = outflow / counts
        offsets = self.meta.offsets
        taps = ([1.0] * len(offsets) if weights is None
                else _opposite_weights(offsets, weights))
        inflow = jnp.zeros((th, tw), self.dtype)
        for wt, (dx, dy) in zip(taps, offsets):
            s = lax.slice(share, (1 + dx, 1 + dy),
                          (1 + dx + th, 1 + dy + tw))
            inflow = inflow + (s if weights is None
                               else jnp.asarray(wt, self.dtype) * s)
        self.cur[channel] = ((self.cur[channel] - outflow[1:-1, 1:-1])
                             + inflow)


# -- the per-term lowerings (one per kind; composed from ctx primitives) ------

@register_lowering(Transport)
class _LowerTransport:
    @staticmethod
    def apply(term: Transport, ctx: _Ctx, rate_c) -> None:
        ctx.transport_update(term.channel, rate_c, term.weights)


def _amount(term, ctx: _Ctx, rate_c):
    """``rate * expr``, materialized behind ``optimization_barrier``s:
    without the outer one, XLA's per-consumer recompute inside fusions
    hands LLVM single-use multiply-add chains whose fma contraction
    depends on whether the rate is a baked CONSTANT (serial) or a
    traced lane (ensemble) — a 1-ulp drift the cross-impl
    bitwise-at-f64 gates exist to catch (the discipline of
    ``ops.active.active_pass``). The inner barrier pins the SCALAR:
    a concrete rate of exactly 1.0 otherwise lets the algebraic
    simplifier delete the multiply and re-fuse the expression chain
    differently from the traced-lane compile (measured: Gray-Scott's
    unit-rate reaction term, 1 ulp over 10 steps). A CONCRETE unit rate
    skips the multiply outright — deterministically, in Python — since
    XLA folds a baked ``* 1.0`` anyway but does so inconsistently
    across fusion contexts; ``x * 1.0`` is IEEE-exact, so the traced-
    lane path (which cannot skip) still produces bitwise-equal values."""
    amt = evaluate(term.expr, ctx.env(), ctx.dtype)
    try:
        unit = float(rate_c) == 1.0  # concrete scalars only
    except (TypeError, jax.errors.TracerArrayConversionError):
        unit = False  # a traced lane: keep the (exact) multiply
    if unit:
        return lax.optimization_barrier(amt)
    return lax.optimization_barrier(lax.optimization_barrier(rate_c) * amt)


@register_lowering(Transfer)
class _LowerTransfer:
    @staticmethod
    def apply(term: Transfer, ctx: _Ctx, rate_c) -> None:
        amt = _amount(term, ctx, rate_c)
        ctx.apply_amount(term.src, amt, -1)
        ctx.apply_amount(term.dst, amt, +1)


@register_lowering(Source)
class _LowerSource:
    @staticmethod
    def apply(term: Source, ctx: _Ctx, rate_c) -> None:
        amt = _amount(term, ctx, rate_c)
        ctx.apply_amount(term.channel, amt, +1)
        ctx.add_budget(term.budget_channel, amt, +1)


@register_lowering(Sink)
class _LowerSink:
    @staticmethod
    def apply(term: Sink, ctx: _Ctx, rate_c) -> None:
        amt = _amount(term, ctx, rate_c)
        ctx.apply_amount(term.channel, amt, -1)
        ctx.add_budget(term.budget_channel, amt, -1)


def apply_terms(terms: Sequence[Term], ctx: _Ctx,
                rates: Sequence, pin: Optional[bool] = None) -> dict:
    """Run every term's registered lowering against ``ctx`` in order;
    returns the accumulated values. ``rates`` aligns with ``terms`` —
    concrete floats (serial) or traced scalars (ensemble lanes).

    ``pin`` (default: on exactly for nonlinear term sets) materializes
    each term's written channels behind an ``optimization_barrier``
    after applying it: XLA contracts a fused nonlinear term CHAIN
    differently across compile contexts (a flat jit, a fori_loop body,
    a vmapped lane, a lax.cond branch — measured at 1 ulp/step on
    Gray-Scott), and per-term pinning is what makes every engine
    compute the identical bits. Linear all-Transport models skip it:
    their fusion is measured stable and they are the bandwidth-bound
    bench path."""
    if pin is None:
        pin = uniform_rates(terms) is None
    for term, rate in zip(terms, rates):
        rate_c = jnp.asarray(rate, ctx.dtype)
        lowering_for(term).apply(term, ctx, rate_c)
        if pin:
            wrote = set(term.writes())
            if term.budget_channel is not None:
                wrote.add(term.budget_channel)
            for ch in sorted(wrote):
                ctx.cur[ch] = lax.optimization_barrier(ctx.cur[ch])
    return ctx.cur


# -- term-set introspection ---------------------------------------------------

def involved_channels(terms: Sequence[Term]) -> frozenset[str]:
    out: set[str] = set()
    for t in terms:
        out |= t.reads() | t.writes()
        if t.budget_channel is not None:
            out.add(t.budget_channel)
    return frozenset(out)


def budget_channels(terms: Sequence[Term]) -> dict[str, Term]:
    """budget channel -> owning source/sink term."""
    return {t.budget_channel: t for t in terms
            if t.budget_channel is not None}


def max_footprint(terms: Sequence[Term]) -> int:
    """The stencil depth the model's terms read — what drives the
    sharded executors' required halo depth."""
    return max((t.footprint for t in terms), default=0)


def uniform_rates(terms: Sequence[Term]) -> Optional[dict[str, float]]:
    """attr -> summed rate when EVERY term is a uniform (unweighted)
    Transport — the shape the composed/pallas/active fast engines
    accept; None otherwise (the general lowering applies)."""
    rates: dict[str, float] = {}
    for t in terms:
        if not (isinstance(t, Transport) and t.is_uniform):
            return None
        rates[t.channel] = rates.get(t.channel, 0.0) + t.rate
    return rates


@dataclasses.dataclass(frozen=True)
class ActivitySpec:
    """Term-derived activity predicate of one model: a tile is active
    iff ANY term may contribute on it. ``probes`` are ``(channel, ref,
    dilate)`` triples — the term acts where ``channel != ref``, with
    ring-1 tile dilation when its footprint reaches the ring (frontier
    tiles activate one step before flux arrives, exactly the hard-coded
    any-nonzero rule this generalizes). ``always`` = some term offered
    no predicate; the engine then runs every tile (honest dense
    fallback, visible in the run's fallback counters)."""

    probes: tuple[tuple[str, float, bool], ...]
    always: bool


def activity_spec(terms: Sequence[Term]) -> ActivitySpec:
    probes = []
    always = False
    for t in terms:
        p = t.activity()
        if p is None:
            always = True
            continue
        ch, ref = p
        probes.append((ch, float(ref), t.footprint >= 1))
    # dedupe (several terms often share a probe, e.g. two SIR terms on I)
    seen: dict = {}
    for pr in probes:
        seen.setdefault(pr, None)
    return ActivitySpec(tuple(seen), always)


def diffusion_terms(field_flows) -> Optional[tuple[Transport, ...]]:
    """Convert a plain-``Diffusion`` flow list to IR Transport terms —
    the hook that makes this lowering the single source of truth for
    ``Model.make_step``'s dense path. None when any flow is not a plain
    Diffusion or an attr carries several (two same-attr Diffusions sum
    OUTFLOWS in the hand-written step, which is not bitwise-identical
    to one summed-rate Transport — that corner keeps the legacy path)."""
    from ..ops.flow import Diffusion

    seen: set[str] = set()
    out = []
    for f in field_flows:
        if type(f) is not Diffusion or f.attr in seen:
            return None
        seen.add(f.attr)
        out.append(Transport(f.attr, rate=f.flow_rate))
    return tuple(out) if out else None


# -- step builders ------------------------------------------------------------

def maybe_pin(terms, values: dict) -> dict:
    """Pin a NONLINEAR step's input state behind a barrier: inside a
    ``fori_loop`` XLA fuses one iteration's tail into the next's
    expression chains, and the resulting contraction makes the looped
    program drift 1 ulp from the same step compiled alone (measured:
    Gray-Scott's Transfer term) — which would break the cross-engine
    bitwise-at-f64 matrix. Linear all-Transport models skip the pin:
    their looped fusion is measured stable, and they are the
    bandwidth-bound bench path where a barrier could cost real ns."""
    if uniform_rates(terms) is not None:
        return values
    return {k: lax.optimization_barrier(v) for k, v in values.items()}


def dense_apply(terms, values: dict, rates, meta: StepMeta,
                counts) -> dict:
    """One dense step over full-grid arrays (the XLA engine's body —
    also what ``Model.make_step`` delegates its all-Diffusion dense
    path to, making this lowering the single source of truth for the
    hand-written transport step it replaced)."""
    values = maybe_pin(terms, values)
    return maybe_pin(
        terms, apply_terms(terms, DenseCtx(dict(values), meta, counts),
                           rates))


def build_dense_step(terms, meta: StepMeta, rates) -> Callable:
    """``step(values) -> values`` for the serial dense engine."""
    terms = tuple(terms)
    rates = tuple(rates)

    def step(values: dict) -> dict:
        counts = neighbor_counts_traced(
            meta.shape, meta.offsets, meta.origin, meta.global_shape,
            meta.dtype)
        return dense_apply(terms, values, rates, meta, counts)

    return step


def padded_apply(terms, values: dict, padded: dict, rates,
                 meta: StepMeta, counts_pad, mask_pb) -> dict:
    """One per-shard step from ghost-exchanged padded transport
    channels (ShardMapExecutor's IR runner body). ``padded`` needs only
    the channels some ring-1 term reads; ``counts_pad`` is the clamped
    global-true neighbor-count grid over the padded shard; ``mask_pb``
    bounds the partition (ghost outflow beyond it is zeroed, matching
    the serial zero-pad semantics bitwise)."""
    def wcounts_pad(weights):
        # RAW weighted counts: the ctx masks stranded cells against the
        # true zeros, then clamps for the divide
        h, w = meta.shape
        ox, oy = meta.origin
        return weighted_counts_traced(
            (h + 2, w + 2), meta.offsets, weights,
            (ox - 1, oy - 1), meta.global_shape, meta.dtype)

    values = maybe_pin(terms, values)
    padded = maybe_pin(terms, padded)
    ctx = PaddedCtx(dict(values), meta, padded, counts_pad, wcounts_pad,
                    mask_pb)
    return maybe_pin(terms, apply_terms(terms, ctx, rates))


def build_active_step(terms, meta: StepMeta, rates, plan,
                      dense_step: Callable) -> Callable:
    """The generic active-tile step for IR models: the term-derived
    ``ActivitySpec`` replaces the hard-coded any-nonzero rule, the
    compacted active tiles run every term's windowed lowering (two
    phases — all reads before all writes, the ``ops.active`` invariant)
    and the dense fallback is the SAME lowered dense step above the
    capacity/activity threshold. Linear all-Transport models never get
    here (they route to the specialized bitwise active engines via the
    flows view); this is the path that serves nonlinear physics."""
    from ..ops import active as act

    terms = tuple(terms)
    rates = tuple(rates)
    if plan.ntiles == 1:
        # a one-tile plan cannot skip anything: the window IS the grid,
        # so the active machinery is pure overhead — and the dense step
        # is the bitwise anchor every other engine matches
        return dense_step
    spec = activity_spec(terms)
    dtype = jnp.dtype(meta.dtype)
    th, tw = plan.tile
    gi, gj = plan.grid
    H, W = meta.global_shape
    ox, oy = meta.origin
    chans = sorted(involved_channels(terms))
    written = sorted(
        set().union(*(t.writes() for t in terms))
        | set(budget_channels(terms)))

    def tile_flags(values):
        if spec.always:
            return jnp.ones((gi, gj), bool)
        flags = jnp.zeros((gi, gj), bool)
        for ch, ref, dilate in spec.probes:
            tm = jnp.any(
                (values[ch] != jnp.asarray(ref, values[ch].dtype)
                 ).reshape(gi, th, gj, tw), axis=(1, 3))
            flags = flags | (act.dilate_tile_map(tm) if dilate else tm)
        return flags

    # all-active → dense: computing EVERY tile through gathered windows
    # is strictly more work than the dense step, and the dense step is
    # the bitwise anchor (a model whose predicate lights the whole grid
    # — e.g. Gray-Scott's u≈1 background — honestly runs dense)
    thresh = np.int32(min(plan.fallback_tiles, plan.ntiles - 1))

    def step(values: dict) -> dict:
        values = maybe_pin(terms, values)
        flags = tile_flags(values)
        count = jnp.sum(flags, dtype=jnp.int32)
        pred = count > thresh

        def dense_branch(vals):
            return dense_step(vals)

        def active_branch(vals):
            padded = {c: jnp.pad(vals[c], 1) for c in chans}
            ids, cnt = act.compact_tile_ids(flags, plan)
            cmin = jnp.minimum(cnt, np.int32(plan.capacity))
            upd = {c: jnp.zeros((plan.capacity, th, tw), vals[c].dtype)
                   for c in written}

            def rc_of(i):
                return (i // gj) * th, (i % gj) * tw

            def compute_body(lane, u):
                r, c = rc_of(ids[lane])
                wins = {ch: lax.dynamic_slice(padded[ch], (r, c),
                                              (th + 2, tw + 2))
                        for ch in chans}
                counts_win = jnp.maximum(
                    neighbor_counts_traced(
                        (th + 2, tw + 2), meta.offsets,
                        (ox + r - 1, oy + c - 1), (H, W), dtype),
                    jnp.asarray(1, dtype))

                def wcounts_win(weights):
                    # RAW (the ctx masks stranded cells, then clamps)
                    return weighted_counts_traced(
                        (th + 2, tw + 2), meta.offsets, weights,
                        (ox + r - 1, oy + c - 1), (H, W), dtype)

                pre_int = {ch: w[1:-1, 1:-1] for ch, w in wins.items()}
                ctx = WindowCtx(pre_int, meta, wins, counts_win,
                                wcounts_win)
                cur = apply_terms(terms, ctx, rates)
                return {c2: lax.dynamic_update_index_in_dim(
                            u[c2], cur[c2], lane, 0)
                        for c2 in u}

            upd = lax.fori_loop(0, cmin, compute_body, upd)

            def scatter_body(lane, p):
                r, c = rc_of(ids[lane])
                return {c2: lax.dynamic_update_slice(
                            p[c2], upd[c2][lane], (r + 1, c + 1))
                        for c2 in p}

            out_p = lax.fori_loop(
                0, cmin, scatter_body, {c2: padded[c2] for c2 in written})
            out = dict(vals)
            for c2 in written:
                out[c2] = out_p[c2][1:-1, 1:-1]
            return out

        return lax.cond(pred, dense_branch, active_branch, values)

    return step
