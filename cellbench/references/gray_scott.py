"""Plain reference of the Gray-Scott model as the Flow IR states it,
importing nothing of the program.

Two channels ``u`` and ``v`` and two budget channels. Every amount is
taken from the state at the start of the step:

    u' = T(u; Du) - v*v*u + F*(1 - u)
    v' = T(v; Dv) + v*v*u - (F + k)*v
    feed' = feed + F*(1 - u)          (the integrated source)
    kill' = kill - (F + k)*v          (the integrated sink)

where ``T(x; D)`` is one Moore-8 Diffusion step of rate ``D``
(``references/diffusion.py``). Channel names are those of the program's
state: ``u``, ``v``, ``_b_feed``, ``_b_kill``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from cellbench.references.diffusion import counts, rounded, transport

CHANNELS = ("u", "v", "_b_feed", "_b_kill")


def step(s: dict, p: dict, cnt) -> dict:
    u, v = s["u"], s["v"]
    reaction = v * v * u
    feed = p["F"] * (1.0 - u)
    kill = (p["F"] + p["k"]) * v
    return {
        "u": transport(u, p["Du"], cnt) - reaction + feed,
        "v": transport(v, p["Dv"], cnt) + reaction - kill,
        "_b_feed": s["_b_feed"] + feed,
        "_b_kill": s["_b_kill"] - kill,
    }


@functools.partial(jax.jit, static_argnames=("steps", "origin",
                                             "global_shape", "store"))
def run(state: dict, params: dict, *, steps: int, origin=(0, 0),
        global_shape=None, store=None) -> dict:
    """``steps`` steps in f32; ``store`` rounds the state to a lower
    precision after every step (the control)."""
    def rnd(s):
        return {k: rounded(x, store) for k, x in s.items()}

    s = rnd({k: state[k].astype(jnp.float32) for k in CHANNELS})
    shape = s["u"].shape
    cnt = counts(shape, origin, global_shape or shape)
    p = {k: jnp.asarray(params[k], jnp.float32) for k in ("Du", "Dv", "F",
                                                          "k")}
    return jax.lax.fori_loop(0, steps, lambda _, s: rnd(step(s, p, cnt)), s)
