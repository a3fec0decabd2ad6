"""Runtime platform configuration + host-device sync accounting.

The device search path (``core.engine.DeviceBeamEngine``) and the evaluator
backends run the same code on CPU, interpret-mode Pallas, and real
accelerators; this module holds the little runtime configuration they share:

* ``enable_x64()`` — scoped float64 (the device search path scopes x64
  per-program with it, built on ``jax.enable_x64``);
* ``use_compile_cache(root)`` — place JAX's persistent compilation cache.  Entry
  points (scripts, examples, the benchmark harness) call it; library code
  and tests never do.

It is also the *accounting point* for host-device synchronisation:
``device_fetch`` is the sanctioned way to materialise device values on the
host (both the evaluator bridge and the device search engine route through
it), and it counts every call.  ``sync_count`` / ``reset_sync_count`` let
tests and benchmarks assert the sync model — e.g. that a fused
``algo="beam_jax"`` schedule performs exactly one fetch per window instead
of one per (model, window) like the split pipeline.

Since the telemetry layer landed, both are thin shims over the
``launch.platform.sync_count`` counter in the process-global registry
(``repro.obs.registry``): the PR 6 counted-sync assertions and the
telemetry exporters read the *same* integer, so they can never disagree.
"""
from __future__ import annotations

import os

from repro.obs import registry as _obs_registry

__all__ = ["enable_x64", "use_compile_cache", "device_fetch", "sync_count",
           "reset_sync_count"]

# The one sync counter; module-level handle so device_fetch pays a single
# attribute increment per call.
_SYNC = _obs_registry.counter("launch.platform.sync_count")


def enable_x64():
    """Scoped 64-bit mode: ``with enable_x64(): ...``.

    Arrays created and programs traced inside the block default to 64-bit
    types; the previous setting returns on exit.
    """
    import jax

    return jax.enable_x64(True)


def use_compile_cache(root: str) -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing.  Otherwise the cache goes to ``<root>/.jax_cache``, where
    ``root`` is the checkout the calling entry point runs from (each derives
    it from its own ``__file__``, so an installed ``repro`` package never
    decides the path): a fixed path, because the path is part of what a
    later process must find again.  Call it from entry points before the
    first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_fetch(tree):
    """Materialise a device value (or pytree of them) as numpy arrays.

    The counted host-transfer point of the scheduling pipeline: one call ==
    one device->host synchronisation (``jax.device_get`` blocks until the
    value is ready, so no separate ``block_until_ready`` is needed).  Tests
    assert sync-count invariants through ``sync_count``.
    """
    _SYNC.inc()
    import jax

    return jax.device_get(tree)


def sync_count() -> int:
    """Number of ``device_fetch`` calls since the last reset.

    Reads the ``launch.platform.sync_count`` registry counter — the same
    value ``repro.obs`` exports, by construction.
    """
    return _SYNC.value


def reset_sync_count() -> None:
    """Zero the sync counter (tests/benchmarks bracket a measured region)."""
    _SYNC.reset()
