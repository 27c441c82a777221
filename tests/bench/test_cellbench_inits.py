"""Initial states: a channel whose ``init`` names a maker is made by
``cellbench/inits/<name>.py`` inside the one jitted build; the uniform
and constant forms draw the same bits as they always did."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from cellbench import systems

from cellbench_fixtures import REPO

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
SEED = 2**33 + 5
ROOT = str(REPO)


def squares(grid, centres, area, value=(0.5, 2.0)):
    return {"grid": list(grid), "dtype": "float32",
            "init": {"value": {"maker": "squares", "centres": centres,
                               "area": area, "value": list(value)}}}


# (grid, centres, area, the squares as (row, col, height, width)),
# worked out by hand from the maker's rule: sides round(dim * sqrt(area)),
# centred on round(fraction * dim), moved inside the grid
CASES = [
    # sides 14; the second centre (6, 58) lies 1 row above and 1 column
    # right of where a square of 14 fits, so it moves to (0, 50)
    ((64, 64), [[0.5, 0.5], [0.1, 0.9]], 0.05,
     [(25, 25, 14, 14), (0, 50, 14, 14)]),
    # an uneven grid: 10 rows by 16 columns, 4% of the area
    ((48, 80), [[0.5, 0.25]], 0.04, [(19, 12, 10, 16)]),
]


@pytest.mark.parametrize("grid, centres, area, boxes", CASES)
def test_squares_makes_the_squares_its_parameters_give(grid, centres, area,
                                                       boxes):
    cfg = squares(grid, centres, area)
    def make(seed):
        return np.asarray(systems.make_values(cfg, seed, root=ROOT)["value"])

    a = make(SEED)
    assert a.dtype == np.float32 and a.shape == grid
    inside = np.zeros(grid, bool)
    for r0, c0, h, w in boxes:
        inside[r0:r0 + h, c0:c0 + w] = True
    # inside: drawn in [0.5, 2.0); outside: +0.0, sign bit clear
    assert np.all((a[inside] >= 0.5) & (a[inside] < 2.0))
    assert np.all(a[~inside] == 0.0) and not np.signbit(a[~inside]).any()
    assert len(np.unique(a[inside])) > inside.sum() // 2
    other = make(SEED + 1)
    assert make(SEED).tobytes() == a.tobytes()
    assert np.all(other[~inside] == 0.0)
    assert not np.array_equal(other[inside], a[inside])


def parent_make_values(config, seed, count=0, sharding=None):
    """``make_values`` as it was before makers, kept to show that the
    uniform and constant forms still draw the same bits."""
    shape = tuple(config["grid"])
    dtype = jnp.dtype(config["dtype"])
    init = config["init"]

    def one(key):
        keys = jax.random.split(key, len(init))
        out = {}
        for k, (ch, spec) in zip(keys, init.items()):
            if isinstance(spec, list):
                lo, hi = spec
                x = jax.random.uniform(k, shape, jnp.float32, lo, hi)
            else:
                x = jnp.full(shape, spec, jnp.float32)
            out[ch] = x.astype(dtype)
        return out

    def build(key):
        if not count:
            return one(key)
        return [one(k) for k in jax.random.split(key, count)]

    fn = jax.jit(build, out_shardings=sharding) if sharding is not None \
        else jax.jit(build)
    return fn(systems.seed_key(seed))


def _tiny(entry):
    cfg = json.loads((REPO / entry["file"]).read_text())
    cfg.update(cfg["tiny"])
    return cfg


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_the_configurations_draw_the_parents_states(name, eight_devices):
    cfg = _tiny(next(c for c in BENCH["configs"] if c["name"] == name))
    kw = {}
    ex = cfg["executor"]
    if ex["kind"] == "service":
        kw["count"] = 4
    if ex["kind"] == "shard_map":
        from mpi_model_tpu.parallel import make_mesh_2d

        mesh = make_mesh_2d(*ex["mesh"], devices=eight_devices[:4])
        kw["sharding"] = NamedSharding(mesh, P(*mesh.axis_names))
    got = systems.make_values(cfg, SEED, root=ROOT, **kw)
    want = parent_make_values(cfg, SEED, **kw)
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want) == len(cfg["init"]) * kw.get("count", 1)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.sharding == w.sharding
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()


def test_a_maker_is_found_under_the_root_it_is_given(tmp_path):
    inits = Path(tmp_path) / "cellbench" / "inits"
    inits.mkdir(parents=True)
    (inits / "ramp.py").write_text(
        "import jax.numpy as jnp\n\n"
        "def make(key, shape, params):\n"
        "    return jnp.full(shape, params['at'], jnp.float32)\n")
    cfg = {"grid": [8, 8], "dtype": "bfloat16",
           "init": {"a": [0.0, 1.0], "b": {"maker": "ramp", "at": 3.0}}}
    v = systems.make_values(cfg, SEED, root=str(tmp_path))
    assert v["b"].dtype == jnp.bfloat16 and np.all(np.asarray(v["b"]) == 3)
    # the uniform channel beside it draws what it would alone
    alone = parent_make_values(dict(cfg, init={"a": [0.0, 1.0], "b": 0.0}),
                               SEED)
    assert np.asarray(v["a"]).tobytes() == np.asarray(alone["a"]).tobytes()
