"""The system under test, built from a configuration file.

Everything here goes through the program's public entry points:
``Model.execute`` with a ``SerialExecutor`` or ``ShardMapExecutor``, a
``FlowIRModel`` from ``mpi_model_tpu.build_model``, and the
``AsyncEnsembleService``. A configuration says which:

- ``model``: ``{"kind": "diffusion", "rate": r}`` or
  ``{"kind": "flow_ir", "name": <registry name>, "params": {...}}``;
- ``executor``: ``{"kind": "serial" | "shard_map", ...}`` (the rest
  are the executor's own arguments; ``mesh`` is the shard_map mesh) or
  ``{"kind": "service", "max_batch": n, "impl": ...}``;
- ``grid``, ``dtype`` and ``init``: per channel, ``[low, high)`` of a
  uniform draw, a constant, or ``{"maker": <name>, ...}``, whose
  ``cellbench/inits/<name>.py`` ``make(key, shape, params)`` returns
  the channel in f32 (``params`` is the whole object). All channels
  are made on the device from the seed in one jitted call, sharded
  over the mesh where there is one.
"""

from __future__ import annotations

import numpy as np


def seed_key(seed: int):
    """A JAX key for ``seed``: seeds wider than 32 bits stay distinct
    (``jax.random.key`` keeps only the low 32)."""
    import jax

    r = np.random.default_rng([int(seed) % 2**63, 0])
    return jax.random.key(int(r.integers(0, 2**31)))


def _dtype(name):
    import jax.numpy as jnp

    return None if name is None else jnp.dtype(name)


def make_values(config: dict, seed: int, count: int = 0, sharding=None,
                *, root: str):
    """The initial channels, drawn on the device in one jitted call: one
    state, or with ``count`` a list of that many independent ones.
    Makers are found under the checkout ``root``."""
    import jax
    import jax.numpy as jnp

    from cellbench.spec import init_maker

    shape = tuple(config["grid"])
    dtype = jnp.dtype(config["dtype"])
    init = config["init"]
    makers = {ch: init_maker(root, s["maker"])
              for ch, s in init.items() if isinstance(s, dict)}

    def one(key):
        keys = jax.random.split(key, len(init))
        out = {}
        for k, (ch, spec) in zip(keys, init.items()):
            if ch in makers:
                x = makers[ch](k, shape, spec)
            elif isinstance(spec, list):
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
    return fn(seed_key(seed))


def build_model(spec: dict):
    import mpi_model_tpu as mm

    if spec["kind"] == "diffusion":
        return mm.Model([mm.Diffusion(float(spec["rate"]))])
    if spec["kind"] == "flow_ir":
        model, _ = mm.build_model(spec["name"], 8, **spec.get("params", {}))
        return model
    raise ValueError(f"unknown model kind {spec['kind']!r}")


def build_executor(spec: dict, devices):
    from mpi_model_tpu.models.model import SerialExecutor
    from mpi_model_tpu.parallel import ShardMapExecutor, make_mesh_2d

    kw = {k: v for k, v in spec.items() if k not in ("kind", "mesh")}
    if "compute_dtype" in kw:
        kw["compute_dtype"] = _dtype(kw["compute_dtype"])
    if "active_opts" in kw:
        # a tile comes as a JSON list; the executor keys its cache on it
        kw["active_opts"] = {k: tuple(v) if isinstance(v, list) else v
                             for k, v in kw["active_opts"].items()}
    if spec["kind"] == "serial":
        return SerialExecutor(**kw)
    if spec["kind"] == "shard_map":
        lines, cols = spec["mesh"]
        mesh = make_mesh_2d(lines, cols, devices=list(devices))
        return ShardMapExecutor(mesh, **kw)
    raise ValueError(f"unknown executor kind {spec['kind']!r}")


class GridSystem:
    """One model stepped over one grid by ``Model.execute``."""

    def __init__(self, config: dict, seed: int, devices, steps: int,
                 executor_overrides=None, check_conservation: bool = True,
                 *, root: str):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from mpi_model_tpu import CellularSpace

        self.config = config
        self.devices = list(devices)
        self.steps = int(steps)
        self.check_conservation = check_conservation
        self.model = build_model(config["model"])
        spec = dict(config["executor"], **(executor_overrides or {}))
        self.executor = build_executor(spec, devices)
        mesh = getattr(self.executor, "mesh", None)
        sharding = (NamedSharding(mesh, P(*mesh.axis_names))
                    if mesh is not None else None)
        with jax.default_device(devices[0]):
            values = make_values(config, seed, sharding=sharding, root=root)
        h, w = config["grid"]
        self.space = CellularSpace(values=values, dim_x=h, dim_y=w)

    def arrays(self):
        return self.space.values

    def call(self, space):
        return self.model.execute(space, self.executor, steps=self.steps,
                                  check_conservation=self.check_conservation)

    @property
    def impl(self):
        return self.executor.last_impl


class ServiceSystem:
    """Scenarios served by an ``AsyncEnsembleService``: a pool of input
    grids and one model per rate of the mix."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 *, root: str):
        import jax

        import mpi_model_tpu as mm
        from mpi_model_tpu import CellularSpace
        from mpi_model_tpu.ensemble.scheduler import buckets_for

        self.config = config
        self.devices = list(devices)
        self.steps = int(traffic["steps"])
        ex = config["executor"]
        self.buckets = buckets_for(int(ex["max_batch"]))
        h, w = config["grid"]
        with jax.default_device(devices[0]):
            pool = make_values(config, seed, count=int(traffic["pool"]),
                               root=root)
        self.pool = [CellularSpace(values=v, dim_x=h, dim_y=w) for v in pool]
        self.models = {r: mm.Model([mm.Diffusion(float(r))])
                       for r in traffic["rates"]}
        first = self.models[traffic["rates"][0]]
        self.service = mm.AsyncEnsembleService(
            first, steps=self.steps, buckets=self.buckets,
            impl=ex.get("impl", "xla"), start=False)

    def arrays(self):
        return [p.values for p in self.pool]

    def warm(self) -> None:
        """Compile (or load) every bucket the loop can dispatch, full and
        padded, largest first, by driving the pump by hand; then start
        the pump."""
        svc = self.service
        rates = list(self.models)
        sizes = []
        for b in sorted(self.buckets, reverse=True):
            sizes += [b] + ([b - 1] if b > 2 else [])
        for k in sizes:
            tickets = [svc.submit(self.pool[i % len(self.pool)],
                                  model=self.models[rates[i % len(rates)]])
                       for i in range(k)]
            for t in tickets:
                svc.result(t)
        svc.start()

    def submit(self, req) -> int:
        idx, rate = req
        return self.service.submit(self.pool[idx], model=self.models[rate])

    def wait(self, ticket):
        return self.service.result(ticket)[0].values

    def poll(self, ticket):
        r = self.service.poll(ticket)
        return None if r is None else r[0].values

    def counters(self) -> dict:
        c = self.service.scheduler.counter
        return {"scenarios": c.scenarios, "lanes": c.lanes,
                "dispatches": c.dispatches}

    def close(self) -> None:
        self.service.stop()
