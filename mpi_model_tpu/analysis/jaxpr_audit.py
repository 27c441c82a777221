"""Jaxpr contract audit (ISSUE 4 layer 2): abstract-trace every
registered step impl and machine-check the invariants the runtime layer
only ever asserted in one hand-written place.

Each contract builds a small canonical (model, space) pair, obtains the
impl's pure step function, and traces it with ``jax.make_jaxpr`` over
``ShapeDtypeStruct``s — no compilation, no execution, CPU-safe. The
audited contracts:

``jaxpr-dtype``
    every output aval's dtype equals the space dtype — the f64 oracle
    gates rely on no silent f32 (or weak-promotion f64) leak anywhere
    in a step.
``jaxpr-callback``
    no callback/debug/print primitives in the hot path — a stray
    ``jax.debug.print`` or ``io_callback`` serializes every step
    through the host.
``jaxpr-consts``
    no O(grid) array baked into the jaxpr as a constant (the historical
    ``neighbor_counts`` bug: a materialized count grid is a 256 MB
    constant at 8192² f32, re-shipped on every compile), and total
    consts under a byte budget.
``jaxpr-halo``
    stencil radius vs halo contract: the model's offsets must stay
    within the ring depth the impl's sharded configuration declares
    (ring-1 for dense/active/ensemble; ``k`` rings covering ``k``
    composed sub-steps for the composed filter, with ``k·passes ==
    substeps``).

``jaxpr-fused-flags``
    the fused active runner's per-pass while body carries no reduction
    at tile size or larger outside the kernel — the next-step activity
    flags come out of the Pallas pass itself (ISSUE 8's structural
    win), never a separate per-step re-scan.

``jaxpr-batch-psum``
    the mesh-sharded ensemble runner's per-scenario stat lanes reduce
    over the space axes only: exactly one f64 ``reduce_sum`` per
    channel at batch-grid size (``[B,H,W] → [B]``), nothing else that
    large — the batch-sharded conservation contract of ISSUE 16.

Audited impls: ``dense`` (the XLA stencil step), ``composed`` (k-step
filter), ``active`` (tile-skipping engine), ``ensemble`` (the vmapped
parametric scenario step), ``ensemble_mesh`` (the sharding-constrained
ensemble runner over a (batch, space) mesh, with the batch-psum
stat-lane contract), ``active_fused`` (the stateless fused
Pallas active step — scalar-prefetch-argument and halo k·passes ==
substeps contracts) and ``active_fused_runner`` (the amortized fused
loop — the jaxpr-fused-flags contract). The dense Pallas kernel impl
is exercised by its own runtime suite; its jaxpr is backend-shaped and
is audited where it matters — through the composed contract, which
traces the same ``_stencil_call`` machinery in interpret mode.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Optional

from .registry import RULES, Finding, Rule, Severity

#: registry scope tag for contract rules (never run by the AST engine)
SCOPE_JAXPR = "jaxpr"

#: total bytes of jaxpr consts a step may carry at the audit geometry —
#: generous for tap tables / index templates, far below any O(grid) bake
CONST_TOTAL_BUDGET = 1 << 20

#: primitive-name fragments that mean host traffic in the hot path
FORBIDDEN_PRIMITIVE_PARTS = ("callback", "debug", "print", "infeed",
                             "outfeed")


def _register(name: str, doc: str, fix_hint: str = "") -> None:
    if name not in RULES:
        RULES[name] = Rule(name, Severity.ERROR, doc,
                           check=lambda ctx: (), scope=SCOPE_JAXPR,
                           fix_hint=fix_hint)


_register("jaxpr-dtype",
          "every step output dtype must equal the space dtype (no "
          "silent f32/f64 leaks past the oracle gates)",
          fix_hint="cast with .astype(space.dtype) at the leak site "
                   "(usually a bare literal or np constant)")
_register("jaxpr-callback",
          "no callback/debug/print primitives inside a traced step",
          fix_hint="hoist the debug I/O out of the jitted function — "
                   "inspect outputs at the caller instead")
_register("jaxpr-consts",
          "no O(grid) constant baked into a step jaxpr; total consts "
          "within budget (recompile/memory bloat)",
          fix_hint="pass the array as a traced argument (donate if "
                   "large) instead of closing over it")
_register("jaxpr-halo",
          "stencil radius must fit the halo depth the impl's sharded "
          "configuration declares",
          fix_hint="widen halo_depth in the impl's sharding config or "
                   "shrink the stencil radius")
_register("jaxpr-term-registry",
          "every Flow IR term kind has exactly one registered, audited "
          "lowering, and it lives in ir.lower — no impl-private term "
          "branches",
          fix_hint="move the term's lowering into ir.lower and register "
                   "it there; delete the impl-local branch")
_register("jaxpr-fused-flags",
          "the fused active runner's per-pass loop must carry no "
          "reduction at tile size or larger outside the kernel — "
          "activity flags come out of the Pallas pass, never a "
          "separate per-step reduction",
          fix_hint="emit the activity flag from the Pallas kernel's "
                   "accumulator output rather than reducing the field "
                   "again outside it")
_register("jaxpr-batch-psum",
          "the mesh-sharded ensemble runner's per-scenario stat lanes "
          "must reduce over the space axes only (one f64 reduce_sum "
          "per channel, [B,H,W] -> [B]) — a full-batch or "
          "wrong-dtype reduction would break the batch-sharded "
          "conservation contract",
          fix_hint="reduce with axis=(1, 2) (space only) and cast the "
                   "accumulator to f64 before the sum")


@dataclasses.dataclass
class BuiltStep:
    """What a contract build hands the checker."""

    impl: str
    fn: Callable                 # traced as fn(*args)
    args: tuple                  # ShapeDtypeStructs / pytrees thereof
    space_dtype: object
    grid_nbytes: int             # one channel's bytes at audit geometry
    offsets: tuple
    halo_depth: int              # ring depth the sharded config declares
    composed_k: Optional[int] = None
    composed_passes: Optional[int] = None
    substeps: int = 1
    #: False for runner-shaped contracts whose outputs legitimately
    #: carry stat counters beside the space-dtype values
    dtype_check: bool = True
    #: the fused impls: every pallas_call must scalar-prefetch its
    #: index buffer as a traced ARGUMENT (never a baked literal)
    expect_prefetch_arg: bool = False
    #: when set (tile cell count), enforce jaxpr-fused-flags on every
    #: innermost while body that contains a pallas_call
    fused_flags_tile_elems: Optional[int] = None
    #: mesh-runner stat-lane contract (ISSUE 16): {"count": n_channels,
    #: "dtype": np dtype, "min_elems": B*H*W} — exactly ``count``
    #: reduce_sum eqns at batch-grid size, each producing ``dtype``
    #: (the [B,H,W] -> [B] per-scenario reductions, and nothing else
    #: at that size)
    batch_psum: Optional[dict] = None


#: impl name → zero-arg builder (registered below)
CONTRACTS: dict[str, Callable[[], BuiltStep]] = {}


def contract(name: str):
    def deco(fn):
        CONTRACTS[name] = fn
        return fn
    return deco


def _sds(arr):
    import jax
    return jax.ShapeDtypeStruct(arr.shape, arr.dtype)


def _space_model(dtype: str, grid: int = 16, with_point: bool = True):
    from ..core.cellular_space import CellularSpace
    from ..models.model import Model
    from ..ops.flow import Diffusion, Exponencial
    space = CellularSpace.create(grid, grid, 1.0, dtype=dtype)
    flows = [Diffusion(0.1)]
    if with_point:
        flows.append(Exponencial((3, 3), 0.05))
    return space, Model(flows, 10.0, 0.2)


@contract("dense")
def _build_dense() -> BuiltStep:
    space, model = _space_model("float64", 16)
    step = model.make_step(space, impl="xla")
    args = {k: _sds(v) for k, v in space.values.items()}
    v0 = next(iter(space.values.values()))
    return BuiltStep("dense", step, (args,), space.dtype,
                     v0.dtype.itemsize * v0.size, model.offsets, 1)


@contract("composed")
def _build_composed() -> BuiltStep:
    # composed eligibility: all-Diffusion, full f32 grid; 64² admits
    # k=4 (max_k is the window ghost depth, 8 rows at f32)
    space, model = _space_model("float32", 64, with_point=False)
    step = model.make_step(space, impl="composed", substeps=4)
    args = {k: _sds(v) for k, v in space.values.items()}
    v0 = next(iter(space.values.values()))
    return BuiltStep("composed", step, (args,), space.dtype,
                     v0.dtype.itemsize * v0.size, model.offsets,
                     halo_depth=step.composed_k,
                     composed_k=step.composed_k,
                     composed_passes=step.composed_passes, substeps=4)


@contract("active")
def _build_active() -> BuiltStep:
    space, model = _space_model("float64", 64, with_point=False)
    with warnings.catch_warnings():
        # the CPU rig cannot compile the real Pallas dense fallback; the
        # probe's RuntimeWarning is expected and the XLA fallback is the
        # path we audit
        warnings.simplefilter("ignore")
        step = model.make_step(space, impl="active")
    args = {k: _sds(v) for k, v in space.values.items()}
    v0 = next(iter(space.values.values()))
    return BuiltStep("active", step, (args,), space.dtype,
                     v0.dtype.itemsize * v0.size, model.offsets, 1)


@contract("ensemble")
def _build_ensemble() -> BuiltStep:
    import jax
    import numpy as np
    from ..ensemble.batch import flow_params, make_scenario_step
    space, model = _space_model("float64", 16)
    single = make_scenario_step(model, space)
    B = 3
    rates, frozens = flow_params([model] * B)
    vals_b = {k: jax.ShapeDtypeStruct((B,) + v.shape, v.dtype)
              for k, v in space.values.items()}
    fn = jax.vmap(single)
    v0 = next(iter(space.values.values()))
    return BuiltStep(
        "ensemble", fn,
        (vals_b, jax.ShapeDtypeStruct(rates.shape, np.float64),
         jax.ShapeDtypeStruct(frozens.shape, np.float64)),
        space.dtype, v0.dtype.itemsize * v0.size, model.offsets, 1)


@contract("ensemble_mesh")
def _build_ensemble_mesh() -> BuiltStep:
    # the mesh-sharded ensemble runner (ISSUE 16): the REAL compiled
    # artifact — EnsembleExecutor._build_xla with the (batch, space)
    # carry constraint — plus the per-scenario stat lanes
    # (batched_totals' float branch: f64 sums over the space axes).
    # Degrades to a 1-device mesh when the rig has a single CPU device
    # (`analysis --strict` runs without the test conftest's 8-device
    # XLA flag), which still audits the sharding-constrained lowering.
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..ensemble.batch import EnsembleExecutor, EnsembleSpace, flow_params
    from ..ensemble.mesh import make_ensemble_mesh

    space, model = _space_model("float64", 16)
    cpu = jax.devices("cpu")
    n = max(1, min(2, len(cpu)))
    emesh = make_ensemble_mesh(batch=n, devices=cpu[:n])
    B = 2 * n
    espace = EnsembleSpace.stack([space] * B)
    ex = EnsembleExecutor(mesh=emesh)
    run = ex.runner_for(model, espace)
    rates, frozens = flow_params([model] * B)

    def fn(vb, rates_b, frozens_b, q, r):
        out = run(vb, rates_b, frozens_b, q, r)
        # the per-scenario stat lanes: device-side f64 sums over the
        # space axes only — [B,H,W] -> [B], batch-sharded throughout
        return {k: jnp.sum(v, axis=(1, 2), dtype=jnp.float64)
                for k, v in out.items()}

    vals_b = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for k, v in espace.values.items()}
    args = (vals_b, _sds(rates), _sds(frozens),
            jax.ShapeDtypeStruct((), np.dtype("int32")),
            jax.ShapeDtypeStruct((), np.dtype("int32")))
    v0 = next(iter(space.values.values()))
    return BuiltStep(
        "ensemble_mesh", fn, args, space.dtype,
        v0.dtype.itemsize * v0.size, model.offsets, 1,
        batch_psum={"count": len(espace.values),
                    "dtype": np.dtype("float64"),
                    "min_elems": B * space.shape[0] * space.shape[1]})


@contract("active_fused")
def _build_active_fused() -> BuiltStep:
    # the stateless fused step: substeps=4 on a 64² f64 grid composes
    # one k=4 pass per call (tile (64, 64) admits k up to MAX_FUSED_K);
    # the runner-shaped loop contract is audited separately below
    space, model = _space_model("float64", 64, with_point=False)
    with warnings.catch_warnings():
        # CPU rig: the dense-fallback Pallas probe warns and degrades
        # to the XLA transport — expected, and the path we audit
        warnings.simplefilter("ignore")
        step = model.make_step(space, impl="active_fused", substeps=4)
    args = {k: _sds(v) for k, v in space.values.items()}
    v0 = next(iter(space.values.values()))
    return BuiltStep("active_fused", step, (args,), space.dtype,
                     v0.dtype.itemsize * v0.size, model.offsets,
                     halo_depth=step.composed_k,
                     composed_k=step.composed_k,
                     composed_passes=step.composed_passes, substeps=4,
                     expect_prefetch_arg=True)


@contract("active_fused_runner")
def _build_active_fused_runner() -> BuiltStep:
    # the amortized whole-run form (SerialExecutor's fast path): the
    # jaxpr-fused-flags contract lives HERE — its per-pass while body
    # must carry no tile-or-larger reduction outside the kernel
    import jax
    import numpy as np
    from ..ops.active import plan_for
    from ..ops.pallas_active import build_fused_runner, choose_fused_k
    space, model = _space_model("float64", 64, with_point=False)
    plan = plan_for(space.shape)
    k = choose_fused_k(4, plan)
    rates = model.pallas_rates()
    # interpret=True: the audit traces the same program on any machine
    run = build_fused_runner(space.shape, rates, model.offsets,
                             space.dtype, plan=plan, k=k,
                             track_dirty=True, interpret=True)
    args = ({kk: _sds(v) for kk, v in space.values.items()},
            jax.ShapeDtypeStruct((), np.dtype("int32")))
    v0 = next(iter(space.values.values()))
    return BuiltStep("active_fused_runner", run, args, space.dtype,
                     v0.dtype.itemsize * v0.size, model.offsets,
                     halo_depth=k, composed_k=k, composed_passes=1,
                     substeps=k, dtype_check=False,
                     expect_prefetch_arg=True,
                     fused_flags_tile_elems=plan.tile[0] * plan.tile[1])


def _ir_contract(model_name: str, impl: str, grid: int = 32):
    """One Flow IR lowering golden: trace ``FlowIRModel.make_step`` for
    a registered library model under one eligible impl (the per-term
    lowering goldens satellite — ISSUE 11). The audited jaxpr is the
    SAME registered lowering every engine consumes, so dtype/callback/
    const/halo violations in any term's lowering surface here once."""
    def build() -> BuiltStep:
        import jax
        from ..ir import library
        from ..ir.model import FlowIRModel
        model, space = library.build_model(model_name, grid,
                                           dtype="float64")
        if impl == "active":
            # a sub-grid tile plan so the WINDOW machinery (not the
            # one-tile dense degeneration) is what gets audited
            model = FlowIRModel(model.ir_terms, model.time,
                                model.time_step,
                                active_opts={"tile": (grid // 4,
                                                      grid // 4)})
        step = model.make_step(space, impl=impl)
        args = {k: _sds(v) for k, v in space.values.items()}
        v0 = next(iter(space.values.values()))
        return BuiltStep(f"ir_{model_name}_{impl}", step, (args,),
                         space.dtype, v0.dtype.itemsize * v0.size,
                         model.offsets, 1)
    return build


for _m in ("gray_scott", "sir", "predator_prey"):
    for _i in ("xla", "composed", "active"):
        CONTRACTS[f"ir_{_m}_{_i}"] = _ir_contract(_m, _i)
CONTRACTS["ir_diffusion_xla"] = _ir_contract("diffusion", "xla")


def check_term_registry() -> list[Finding]:
    """The ``jaxpr-term-registry`` rule: walk every Term subclass the
    package defines (transitively) and assert the ir.lower registry
    holds exactly one lowering for each, defined IN ir.lower. A term
    kind lowered elsewhere — an impl-private branch — is exactly the
    hand-mirroring the IR exists to end."""
    from ..ir import lower as ir_lower
    from ..ir.terms import Term

    findings: list[Finding] = []

    def subclasses(cls):
        for sub in cls.__subclasses__():
            yield sub
            yield from subclasses(sub)

    for kind in subclasses(Term):
        low = ir_lower.LOWERINGS.get(kind)
        inherited = None
        if low is None:
            # a subclass may legitimately inherit its base kind's
            # registered lowering (same apply contract); only a kind
            # with NO lowering anywhere in its MRO is unregistered
            for base in kind.__mro__[1:]:
                if base in ir_lower.LOWERINGS:
                    inherited = ir_lower.LOWERINGS[base]
                    break
            if inherited is None:
                findings.append(Finding(
                    "jaxpr-term-registry", Severity.ERROR,
                    "jaxpr:term-registry", 0,
                    f"term kind {kind.__name__} has no registered "
                    "lowering — register exactly one in ir.lower"))
                continue
        target = low if low is not None else inherited
        mod = getattr(target, "__module__", "")
        if mod != ir_lower.__name__:
            findings.append(Finding(
                "jaxpr-term-registry", Severity.ERROR,
                "jaxpr:term-registry", 0,
                f"term kind {kind.__name__}'s lowering {target!r} is "
                f"defined in {mod!r}, not ir.lower — impl-private term "
                "lowerings reintroduce the hand-mirroring the IR "
                "replaces"))
    return findings


# -- jaxpr walks --------------------------------------------------------------

def _iter_eqns(jaxpr):
    """Every eqn in ``jaxpr`` and (recursively) in any sub-jaxpr held in
    eqn params (pjit/scan/while/cond/closed_call/pallas grids)."""
    from jax.extend.core import Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _as_jaxprs(val, Jaxpr):
                yield from _iter_eqns(sub)


def _as_jaxprs(val, Jaxpr):
    if isinstance(val, Jaxpr):
        yield val
    elif hasattr(val, "jaxpr") and isinstance(val.jaxpr, Jaxpr):
        yield val.jaxpr  # ClosedJaxpr
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _as_jaxprs(v, Jaxpr)


#: reduction primitives the jaxpr-fused-flags contract scans for —
#: genuine cross-element reductions only (``reduce_precision`` is an
#: elementwise cast and must NOT match, hence no substring matching)
REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_prod", "reduce_max", "reduce_min",
    "reduce_or", "reduce_and", "reduce_xor", "argmax", "argmin",
    "reduce_window_sum", "reduce_window_max", "reduce_window_min",
})


def _iter_eqns_outside_pallas(jaxpr):
    """Like ``_iter_eqns`` but does NOT descend into a pallas_call's
    kernel jaxpr — the fused-flags contract is about what runs OUTSIDE
    the kernel (in-kernel reductions over the VMEM-resident tile are
    the whole point)."""
    from jax.extend.core import Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        if "pallas" in eqn.primitive.name:
            continue
        for val in eqn.params.values():
            for sub in _as_jaxprs(val, Jaxpr):
                yield from _iter_eqns_outside_pallas(sub)


def _has_eqn(jaxpr, pred) -> bool:
    return any(pred(eqn) for eqn in _iter_eqns_outside_pallas(jaxpr))


def _grid_reductions(jaxpr, min_elems: int):
    """Reduction eqns (outside kernels) whose any input reaches
    ``min_elems`` elements — the per-pass loop of the fused runner must
    have none (flags come out of the kernel)."""
    import math
    for eqn in _iter_eqns_outside_pallas(jaxpr):
        if eqn.primitive.name not in REDUCE_PRIMS:
            continue
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            size = int(math.prod(getattr(aval, "shape", ())))
            if size >= min_elems:
                yield eqn, size
                break


def stencil_radius(offsets) -> int:
    """Chebyshev radius of a neighborhood: rings of halo a step needs."""
    return max(max(abs(int(dx)), abs(int(dy))) for dx, dy in offsets)


def _const_nbytes(c) -> int:
    size = getattr(c, "size", None)
    itemsize = getattr(getattr(c, "dtype", None), "itemsize", None)
    if size is None or itemsize is None:
        return 0
    return int(size) * int(itemsize)


# -- the audit ----------------------------------------------------------------

def audit_built(built: BuiltStep) -> list[Finding]:
    import jax
    where = f"jaxpr:{built.impl}"
    findings: list[Finding] = []
    try:
        closed = jax.make_jaxpr(built.fn)(*built.args)
    # analysis: ignore[broad-except] — the audit must report a trace
    # failure as a finding, not crash the analyzer, whatever it raised
    except Exception as e:
        findings.append(Finding(
            "jaxpr-dtype", Severity.ERROR, where, 0,
            f"step impl {built.impl!r} failed to trace: "
            f"{type(e).__name__}: {e}"))
        return findings

    # dtype stability: every output aval carries the space dtype
    # (runner-shaped contracts opt out — their stat counters are
    # integer outputs by design)
    import numpy as np
    want = np.dtype(built.space_dtype)
    if built.dtype_check:
        for i, aval in enumerate(closed.out_avals):
            got = np.dtype(aval.dtype)
            if got != want:
                findings.append(Finding(
                    "jaxpr-dtype", Severity.ERROR, where, 0,
                    f"output {i} of the {built.impl} step has dtype "
                    f"{got.name}, space dtype is {want.name} — a silent "
                    "promotion/downcast crossed the step boundary"))

    # hot-path purity: no host-callback/debug primitives anywhere
    for eqn in _iter_eqns(closed.jaxpr):
        pname = eqn.primitive.name
        if any(part in pname for part in FORBIDDEN_PRIMITIVE_PARTS):
            findings.append(Finding(
                "jaxpr-callback", Severity.ERROR, where, 0,
                f"primitive `{pname}` inside the {built.impl} step — "
                "host traffic in the traced hot path"))

    # consts budget: nothing O(grid), total bounded
    total = 0
    for c in closed.consts:
        nb = _const_nbytes(c)
        total += nb
        if nb >= built.grid_nbytes:
            findings.append(Finding(
                "jaxpr-consts", Severity.ERROR, where, 0,
                f"a {nb}-byte constant (>= one {built.grid_nbytes}-byte "
                f"grid channel) is baked into the {built.impl} jaxpr — "
                "compute it traced (the neighbor_counts_traced "
                "discipline) or pass it as an argument"))
    if total > CONST_TOTAL_BUDGET:
        findings.append(Finding(
            "jaxpr-consts", Severity.ERROR, where, 0,
            f"jaxpr consts total {total} bytes for the {built.impl} "
            f"step (budget {CONST_TOTAL_BUDGET}) — recompile/memory "
            "bloat; move large tables to arguments"))

    # halo contract
    radius = stencil_radius(built.offsets)
    per_exchange = built.composed_k or 1
    need = radius * per_exchange
    if need > built.halo_depth:
        findings.append(Finding(
            "jaxpr-halo", Severity.ERROR, where, 0,
            f"{built.impl} step needs {need} halo ring(s) (offsets "
            f"radius {radius} × {per_exchange} sub-step(s) per "
            f"exchange) but its sharded config declares halo_depth="
            f"{built.halo_depth} — shard edges would read stale ghosts"))
    if built.composed_k is not None:
        k, passes = built.composed_k, built.composed_passes
        if k * passes != built.substeps:
            findings.append(Finding(
                "jaxpr-halo", Severity.ERROR, where, 0,
                f"composed k={k} × passes={passes} != substeps="
                f"{built.substeps} — the composed call no longer equals "
                "the iterated step count"))

    # fused-impl contracts (ISSUE 8): the kernel actually lowered, and
    # its scalar-prefetched operands — the compacted index buffer above
    # all — are traced ARGUMENTS, never baked literals (a literal ids
    # buffer would freeze one activity pattern into the compile)
    if built.expect_prefetch_arg:
        from jax.extend.core import Literal
        n_pallas = 0
        for eqn in _iter_eqns(closed.jaxpr):
            if "pallas" not in eqn.primitive.name:
                continue
            n_pallas += 1
            gm = eqn.params.get("grid_mapping")
            nsp = int(getattr(gm, "num_index_operands", 0) or 0)
            if nsp < 1:
                findings.append(Finding(
                    "jaxpr-consts", Severity.ERROR, where, 0,
                    f"a pallas_call in the {built.impl} step prefetches "
                    "no scalar operands — the fused contract requires "
                    "the compacted index buffer to ride scalar prefetch"))
                continue
            for v in eqn.invars[:nsp]:
                if isinstance(v, Literal):
                    findings.append(Finding(
                        "jaxpr-consts", Severity.ERROR, where, 0,
                        f"a scalar-prefetch operand of a pallas_call in "
                        f"the {built.impl} step is a baked literal — the "
                        "index buffer must be a traced argument"))
        if n_pallas == 0:
            findings.append(Finding(
                "jaxpr-consts", Severity.ERROR, where, 0,
                f"the {built.impl} step lowered no pallas_call at all — "
                "the fused kernel is not in the hot path"))

    # jaxpr-batch-psum (ISSUE 16): the mesh runner's per-scenario stat
    # lanes — exactly one batch-grid-size reduce_sum per channel, each
    # producing f64. More means a stray whole-state reduction crept
    # into the hot path; fewer (or a narrower dtype) means the [B]
    # conservation lanes are no longer the audited f64 space-axis sums
    if built.batch_psum is not None:
        import math
        spec = built.batch_psum
        found = []
        for eqn in _iter_eqns(closed.jaxpr):
            if eqn.primitive.name != "reduce_sum":
                continue
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                size = int(math.prod(getattr(aval, "shape", ())))
                if size >= int(spec["min_elems"]):
                    found.append(eqn)
                    break
        if len(found) != int(spec["count"]):
            findings.append(Finding(
                "jaxpr-batch-psum", Severity.ERROR, where, 0,
                f"{len(found)} batch-grid-size reduce_sum eqn(s) in the "
                f"{built.impl} runner, contract expects exactly "
                f"{spec['count']} (one [B,H,W] -> [B] stat reduction "
                "per channel, nothing else at that size)"))
        for eqn in found:
            got = np.dtype(eqn.outvars[0].aval.dtype)
            if got != np.dtype(spec["dtype"]):
                findings.append(Finding(
                    "jaxpr-batch-psum", Severity.ERROR, where, 0,
                    f"a batch-axis stat reduction in the {built.impl} "
                    f"runner produces {got.name}, contract requires "
                    f"{np.dtype(spec['dtype']).name} — the conservation "
                    "lanes must stay f64"))

    # jaxpr-fused-flags: every innermost while body that runs the
    # kernel must be free of tile-or-larger reductions outside it —
    # the per-pass activity flags come out of the Pallas pass, never a
    # separate per-step reduction (the O(grid)/O(capacity-buffer)
    # re-scan the fused engine exists to eliminate)
    if built.fused_flags_tile_elems is not None:
        thresh = int(built.fused_flags_tile_elems)
        for eqn in _iter_eqns(closed.jaxpr):
            if eqn.primitive.name != "while":
                continue
            body = eqn.params["body_jaxpr"].jaxpr
            if not _has_eqn(body, lambda e: "pallas" in e.primitive.name):
                continue
            if _has_eqn(body, lambda e: e.primitive.name == "while"):
                continue  # outer nest: the dense-fallback branch may scan
            for bad, size in _grid_reductions(body, thresh):
                findings.append(Finding(
                    "jaxpr-fused-flags", Severity.ERROR, where, 0,
                    f"`{bad.primitive.name}` over {size} elements inside "
                    f"the {built.impl} per-pass loop — activity flags "
                    "must come out of the fused kernel, not a separate "
                    "per-step reduction"))
    return findings


def run_jaxpr_audit(impls=None) -> list[Finding]:
    """Audit the registered step impls (all four by default). Pins jax
    to CPU-compatible tracing only — nothing compiles or executes."""
    import jax
    # the dtype contract is about the f64 oracle tier: without x64 the
    # canonical f64 spaces silently truncate to f32 and the check is
    # vacuous (the test rig's conftest sets the same two knobs); both
    # knobs are restored on exit so a library caller's ambient config
    # survives the audit
    prev_x64 = jax.config.jax_enable_x64
    prev_dev = jax.config.jax_default_device
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_default_device", "cpu")
    findings: list[Finding] = []
    if impls is None or "term-registry" in impls:
        findings.extend(check_term_registry())
    try:
        for name, build in CONTRACTS.items():
            if impls is not None and name not in impls:
                continue
            try:
                built = build()
            # analysis: ignore[broad-except] — a broken contract build
            # must surface as a finding for ITS impl; the other
            # contracts run on
            except Exception as e:
                findings.append(Finding(
                    "jaxpr-dtype", Severity.ERROR, f"jaxpr:{name}", 0,
                    f"contract build for {name!r} failed: "
                    f"{type(e).__name__}: {e}"))
                continue
            findings.extend(audit_built(built))
    finally:
        jax.config.update("jax_enable_x64", prev_x64)
        jax.config.update("jax_default_device", prev_dev)
    return findings
