"""The comparison that decides ``correct``: what the timed path produced
against the plain reference (``cellbench/references/<name>.py``).

Numbers compared, each against the limit of its own in the
configuration's ``limits``:

- ``max_abs_err``: the widest gap, over every channel and every cell
  checked, between the program's output and the reference's output
  from the same input after the same steps;
- ``budget_rel_gap`` (configurations whose ``check`` lists ``totals``):
  for each listed channel, the gap between the total the program
  reported and the reference's total (summed in f64 on the host), over
  the reference's total. For the Flow IR these are the source and sink
  budgets: the ledger that the budget reconciliation keeps.

A grid is checked whole, or (``check.tile``) in tiles centred where the
configuration says: the shard seams, corners and edges, and the middle
of each shard (``Sampler``). The seed draws which call is checked.

A configuration's ``control`` is one control or a list of them: the
reference rounded to a lower precision after every step (``store``),
put in the program's place, or the program with a lower-precision path
of its own switched on (``executor``). Each has to come out not
correct.
"""

from __future__ import annotations

import importlib

import numpy as np


def controls(config: dict) -> list:
    c = config["control"]
    return list(c) if isinstance(c, list) else [c]


def reference(config: dict):
    return importlib.import_module("cellbench.references."
                                   + config["reference"])


def params(config: dict) -> dict:
    m = config["model"]
    return dict(m.get("params", {})) if m["kind"] != "diffusion" \
        else {"rate": m["rate"]}


def regions(config: dict) -> list:
    """``(row, col, height, width)`` of each part of the grid checked:
    the whole grid, or square tiles of side ``check.tile`` centred on
    ``check.centres`` (moved inside the grid where a centre is near an
    edge). The same for every seed, so their cuts compile once."""
    h, w = config["grid"]
    chk = config.get("check", {})
    t = chk.get("tile")
    if not t:
        return [(0, 0, h, w)]
    return [(min(max(r - t // 2, 0), h - t),
             min(max(c - t // 2, 0), w - t), t, t)
            for r, c in chk["centres"]]


class Sampler:
    """What the check keeps of one call, taken as soon as the call has
    run: per region, its origin, the input ``steps`` cells wider (as far
    as the grid goes, so the reference's spoiled rim falls outside the
    region) and the program's output there.

    A grid checked whole is kept as it is. Tiles are cut by jitted static
    slices, one small program per region run one after another, and
    copied to ``device``: a slice of a sharded array taken any other way
    gathers the whole grid onto each chip, and all regions in one
    program reserve their reshard buffers at once. ``compile`` builds
    the programs in set-up, so the window compiles nothing."""

    def __init__(self, config: dict, steps: int, device):
        self.channels = reference(config).CHANNELS
        self.device = device
        h, w = config["grid"]
        self.boxes = []
        for r0, c0, th, tw in regions(config):
            e0, e1 = max(r0 - steps, 0), min(r0 + th + steps, h)
            f0, f1 = max(c0 - steps, 0), min(c0 + tw + steps, w)
            self.boxes.append(((r0, c0, th, tw), (e0, f0, e1, f1)))
        self.whole = [b for b, _ in self.boxes] == [(0, 0, h, w)]
        self._cuts = [] if self.whole else [self._program(*b)
                                            for b in self.boxes]

    def _program(self, region, box):
        import jax
        from jax import lax

        (r0, c0, th, tw), (e0, f0, e1, f1) = region, box

        def cut(inp, out):
            return ({k: lax.slice(v, (e0, f0), (e1, f1))
                     for k, v in inp.items()},
                    {k: lax.slice(v, (r0, c0), (r0 + th, c0 + tw))
                     for k, v in out.items()})

        return jax.jit(cut)

    def _pick(self, values: dict) -> dict:
        return {k: values[k] for k in self.channels}

    def compile(self, values: dict) -> None:
        """Run each cut once (on the warm state), so the window's call
        finds it compiled."""
        import jax

        v = self._pick(values)
        for cut in self._cuts:
            jax.block_until_ready(cut(v, v))

    def __call__(self, inp: dict, out: dict) -> list:
        import jax

        inp, out = self._pick(inp), self._pick(out)
        if self.whole:
            (region, _), = self.boxes
            return [(region, (0, 0), inp, out)]
        kept = []
        for (region, box), cut in zip(self.boxes, self._cuts):
            x, got = jax.block_until_ready(
                jax.device_put(cut(inp, out), self.device))
            kept.append((region, box[:2], x, got))
        return kept


def grid_numbers(config: dict, steps: int, cuts: list, report,
                 store=None) -> dict:
    """The numbers compared for one call: each region's input stepped
    ``steps`` times by the reference, against the program's output (or,
    with ``store`` set, against the control's)."""
    import jax.numpy as jnp

    ref = reference(config)
    p = params(config)
    h, w = config["grid"]
    worst = 0.0
    whole = None
    for (r0, c0, th, tw), (e0, f0), x, out in cuts:
        kw = dict(steps=steps, origin=(e0, f0), global_shape=(h, w))
        want = ref.run(x, p, **kw)
        sl = (slice(r0 - e0, r0 - e0 + th), slice(c0 - f0, c0 - f0 + tw))
        got = out if store is None else {
            k: v[sl] for k, v in ref.run(x, p, store=jnp.dtype(store),
                                         **kw).items()}
        for k in ref.CHANNELS:
            d = jnp.max(jnp.abs(got[k].astype(jnp.float32) - want[k][sl]))
            worst = max(worst, float(d))
        if (r0, c0, th, tw) == (0, 0, h, w):
            whole = (want, got)
    numbers = {"max_abs_err": worst}
    totals = config.get("check", {}).get("totals")
    if totals:
        if whole is None:
            raise ValueError("totals are compared on a grid checked whole")
        want, got = whole
        gap = 0.0
        for k in totals:
            t_ref = float(np.asarray(want[k], np.float64).sum())
            t_got = (float(report.final_total[k]) if store is None
                     else float(np.asarray(got[k], np.float64).sum()))
            gap = max(gap, abs(t_got - t_ref) / abs(t_ref))
        numbers["budget_rel_gap"] = gap
    return numbers


def scenario_numbers(config: dict, steps: int, kept: list, device,
                     store=None) -> dict:
    """``kept`` is ``[(input values, rate, output values)]``: each
    sampled scenario against the reference from its own input."""
    import jax
    import jax.numpy as jnp

    ref = reference(config)
    worst = 0.0
    for inp, rate, out in kept:
        x = {k: jax.device_put(inp[k], device) for k in ref.CHANNELS}
        want = ref.run(x, {"rate": rate}, steps=steps)
        got = out if store is None else ref.run(
            x, {"rate": rate}, steps=steps, store=jnp.dtype(store))
        for k in ref.CHANNELS:
            d = jnp.max(jnp.abs(got[k].astype(jnp.float32) - want[k]))
            worst = max(worst, float(d))
    return {"max_abs_err": worst}


def judge(numbers: dict, limits: dict) -> bool:
    """Every number at or under its limit (a NaN, or a number with no
    limit set, fails)."""
    return all(limits.get(k) is not None and numbers[k] <= limits[k]
               for k in numbers)
