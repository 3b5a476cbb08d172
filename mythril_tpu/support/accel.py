"""Process-wide accelerator setup: is a chip present, and where JAX keeps
its compiled kernels.

The device prepass, the overlapped corpus pipeline, and the solver's
first-line device attempt must agree on whether a chip exists —
independent copies of the backend probe drifting apart would let one
half of the pipeline dispatch to a device the other half refuses.

This module stays jax-free at import: the CLI calls
`ensure_compile_cache` before it knows whether the command touches
the device at all (`myth lint` must never load JAX).
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

#: the checkout's root; the compile cache lives inside it unless the
#: environment names one
_REPO_ROOT = Path(__file__).resolve().parents[2]


def accelerator_present() -> bool:
    """True when JAX's default backend is an accelerator. Only a CPU
    platform answers False: a backend that fails to initialize raises,
    so a broken chip never turns into a silent host-only run."""
    import jax

    return jax.default_backend() != "cpu"


def ensure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one directory and
    return it. Call once at process start, before the first compile:
    JAX decides at its first compile whether the process uses a cache.

    `JAX_COMPILATION_CACHE_DIR`, when set, is the cache and nothing
    else is configured. Otherwise the cache is `<checkout>/.jax_cache`
    — a fixed path, because the path is part of the cache's key. The
    choice goes into the environment too, so spawned workers share it."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir:
        return cache_dir
    cache_dir = str(_REPO_ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    jax = sys.modules.get("jax")
    if jax is not None:
        # JAX read the (then unset) variable at import
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def pin_to_cpu() -> None:
    """Pool-worker initializer: this process never initializes a JAX
    backend on the chip. The parent owns the chip, and a child that
    reached for it would fail or hang. A pool runs its initializer in
    every worker it starts, respawned ones included, before their first
    task; the parent's own environment is never touched."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:
        # imported with the parent's platforms (spawn re-imports the
        # parent's main module); no backend exists yet
        jax.config.update("jax_platforms", "cpu")
