"""Persistent JAX compilation cache, placed by one rule.

Every process restart recompiles every kernel — seconds each for the
Pallas kernels and the runner loops. The JAX persistent compilation
cache keys compiled executables by (HLO, jaxlib version, flags, device
kind) and serves them across processes, so a machine pays each compile
ONCE — a restarted service reaches full throughput on its first batch.

The placement rule (``cache_dir``): ``$JAX_COMPILATION_CACHE_DIR`` when
it is set — jax reads it itself and no directory is set in code, so a
``--compile-cache DIR`` request gives way to it; otherwise the requested
directory, or ``<checkout>/.jax_cache`` (gitignored) for ``"auto"``. A
fixed path inside the checkout keeps the cache's key stable across runs
and writes nothing outside the checkout.

``configure_compile_cache(dir)`` is the ONE place the knobs are set;
the CLI's ``--compile-cache DIR`` flag, ``EnsembleService(
compile_cache=...)``, ``bench.enable_compile_cache`` and
``chip_smoke.py`` all route here. The bar for entry is dropped to zero
compile seconds / any entry size — on the CPU test rigs even the tiny
kernels should populate, which is what the cross-process test asserts.
"""

from __future__ import annotations

import os
from typing import Optional

#: config knobs to apply: (name, value)
_KNOBS = (
    ("jax_persistent_cache_min_compile_time_secs", 0),
    ("jax_persistent_cache_min_entry_size_bytes", -1),
)

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout-local default (listed in .gitignore)
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_configured: Optional[str] = None


def cache_dir(requested: Optional[str] = None) -> str:
    """Where the cache lives: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``requested``, else ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV) or requested or CHECKOUT_CACHE


def configure_compile_cache(spec: Optional[str]) -> Optional[str]:
    """Arm the JAX persistent compilation cache at
    ``resolve_compile_cache(spec)`` and lower the entry bars so every
    compile is cached. Returns the directory in use, or None when
    ``spec`` is None/empty (explicitly disabled).

    Idempotent: reconfiguring with the same directory is a no-op; a
    DIFFERENT directory re-points the cache (jax allows updating the
    config between compiles)."""
    global _configured
    target = resolve_compile_cache(spec)
    if target is None:
        return None
    import jax

    env = os.environ.get(ENV)
    target = os.path.abspath(os.path.expanduser(target))
    if _configured == target:
        return target
    os.makedirs(target, exist_ok=True)
    if not env:
        jax.config.update("jax_compilation_cache_dir", target)
    for name, value in _KNOBS:
        jax.config.update(name, value)
    # jax memoizes its cache-used decision at the FIRST compile of the
    # process; a process that compiled anything before this call (test
    # rigs, library embedders) would silently keep the cache off —
    # reset so the next compile re-initializes against the new dir
    from jax.experimental.compilation_cache import compilation_cache as _cc

    _cc.reset_cache()
    _configured = target
    return target


def configured_dir() -> Optional[str]:
    """The directory the cache was last pointed at via
    ``configure_compile_cache`` (None = never configured here)."""
    return _configured


def resolve_compile_cache(spec) -> Optional[str]:
    """Map a ``compile_cache`` knob value to a directory: ``"auto"`` →
    ``cache_dir()`` (the persistent cache rides under the scheduler's
    runner cache BY DEFAULT, so a restarted service reaches full
    throughput on its first batch); ``None``/empty → disabled; any
    other string → ``cache_dir(that)``."""
    if not spec:
        return None
    return cache_dir(None if spec == "auto" else spec)
