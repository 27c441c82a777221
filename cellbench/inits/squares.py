"""Squares of values in a grid of zeros: point sources grown to a block,
the sparse fronts that the active-tile engines step.

Parameters, all sizes as fractions of the grid so that a configuration's
tiny cut keeps its shape:

- ``centres``: ``[row, col]`` of each square's centre, as fractions of
  the grid's height and width;
- ``area``: each square's share of the grid's area. Its sides are
  ``round(h * sqrt(area))`` rows and ``round(w * sqrt(area))`` columns
  (a square on a square grid). A square near an edge is moved inside
  the grid;
- ``value``: ``[low, high)`` of a uniform draw from the seed, inside
  the squares.

Everywhere else the value is +0.0, never -0.0: the active engines count
a -0.0 cell as zero and keep its sign bit where the dense step would
write +0.0 (``mpi_model_tpu/ops/active.py``).
"""

import math

import jax
import jax.numpy as jnp


def boxes(shape, params) -> list:
    """``(row, col, height, width)`` of each square."""
    h, w = shape
    scale = math.sqrt(float(params["area"]))
    sh, sw = round(h * scale), round(w * scale)
    out = []
    for fr, fc in params["centres"]:
        r0 = min(max(round(fr * h) - sh // 2, 0), h - sh)
        c0 = min(max(round(fc * w) - sw // 2, 0), w - sw)
        out.append((r0, c0, sh, sw))
    return out


def make(key, shape, params):
    """The channel, f32, traced inside the caller's jitted build."""
    lo, hi = params["value"]
    rows = jnp.arange(shape[0], dtype=jnp.int32)[:, None]
    cols = jnp.arange(shape[1], dtype=jnp.int32)[None, :]
    inside = jnp.zeros(shape, bool)
    for r0, c0, sh, sw in boxes(shape, params):
        inside = inside | ((rows >= r0) & (rows < r0 + sh)
                           & (cols >= c0) & (cols < c0 + sw))
    x = jax.random.uniform(key, shape, jnp.float32, lo, hi)
    return jnp.where(inside, x, jnp.float32(0.0))
