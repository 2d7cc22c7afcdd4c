"""Pre-jax bootstrap shared by the end-to-end drivers.

This module must stay free of jax (and jax-importing repro modules): it
sets process-wide JAX settings before the backends initialize. The
drivers (examples/dist_eigen_e2e.py, benchmarks/bench_dist_e2e.py,
chip_smoke.py, launch/serve.py) import it before anything else touches
jax.
"""
from __future__ import annotations

import os
import sys

# the checkout root: src/repro/hostdev.py -> ../..
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache lives at
    the fixed `<checkout>/.jax_cache` (a fixed path, since the directory
    is part of what a later run must find again). Call it before jax is
    imported; if jax already is, its config is updated in place.
    """
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_host_devices(n: int = 8) -> None:
    """Force a multi-device host platform before jax initializes.

    Honors an explicit XLA_FLAGS already carrying a device-count pin, and
    falls back to the scripts/run_tier1.sh subprocess pin
    (DIST_SUBPROCESS_XLA_FLAGS) so the tier-1 smoke runs and the manual
    drivers agree on the mesh.
    """
    flags = os.environ.get("XLA_FLAGS",
                           os.environ.get("DIST_SUBPROCESS_XLA_FLAGS", ""))
    if "xla_force_host_platform_device_count" not in flags:
        flags = f"{flags} --xla_force_host_platform_device_count={n}".strip()
    os.environ["XLA_FLAGS"] = flags
