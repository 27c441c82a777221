"""Plain reference of Moore-8 Diffusion, written from the model's
definition and importing nothing of the program.

Every cell sheds ``rate * value`` and splits it equally among its
in-bounds Moore neighbours (3 at a corner, 5 on an edge, 8 inside);
the grid does not wrap. One step:

    v'[c] = v[c] - rate * v[c] + sum over neighbours n of c of
            rate * v[n] / count[n]

A block of a larger grid is stepped with its global origin, so counts
are those of the whole grid; cells beyond the block are missing, which
spoils a ring one cell deep per step and no more. Callers extend each
block by the step count and keep the middle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

OFFSETS = ((-1, -1), (-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1), (1, 0),
           (1, 1))

#: the state's channels, in the order the tests build them
CHANNELS = ("value",)


def shifted(x, dx: int, dy: int):
    """``out[i, j] = x[i + dx, j + dy]``, zero beyond ``x``."""
    h, w = x.shape
    p = jnp.pad(x, 1)
    return jax.lax.dynamic_slice(p, (1 + dx, 1 + dy), (h, w))


def counts(shape, origin, global_shape):
    """In-bounds Moore neighbours of each cell of a block."""
    rows = origin[0] + jnp.arange(shape[0], dtype=jnp.int32)[:, None]
    cols = origin[1] + jnp.arange(shape[1], dtype=jnp.int32)[None, :]
    n = jnp.zeros(shape, jnp.float32)
    for dx, dy in OFFSETS:
        ok = ((rows + dx >= 0) & (rows + dx < global_shape[0])
              & (cols + dy >= 0) & (cols + dy < global_shape[1]))
        n = n + ok.astype(jnp.float32)
    return n


def transport(x, rate, cnt):
    """One Diffusion step of ``x`` (f32)."""
    share = rate * x / cnt
    inflow = jnp.zeros_like(x)
    for dx, dy in OFFSETS:
        inflow = inflow + shifted(share, dx, dy)
    return x - rate * x + inflow


def rounded(x, store):
    """``x`` stored in ``store`` and read back as f32. The barrier keeps
    XLA from folding the round trip away (it may drop a narrowing
    convert pair as excess precision)."""
    if store is None:
        return x
    return jax.lax.optimization_barrier(x.astype(store)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("steps", "origin",
                                             "global_shape", "store"))
def run(state: dict, params: dict, *, steps: int, origin=(0, 0),
        global_shape=None, store=None) -> dict:
    """``steps`` steps from ``state`` in f32. ``store`` names a dtype
    the state is rounded to after every step (the control's lower
    precision); None keeps f32 throughout."""
    x = rounded(state["value"].astype(jnp.float32), store)
    gs = global_shape or x.shape
    cnt = counts(x.shape, origin, gs)
    rate = jnp.asarray(params["rate"], jnp.float32)

    def body(_, x):
        return rounded(transport(x, rate, cnt), store)

    return {"value": jax.lax.fori_loop(0, steps, body, x)}
