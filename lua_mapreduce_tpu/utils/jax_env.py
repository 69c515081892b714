"""The one bootstrap every entry point that compiles shares: where
compiled programs are kept.

Platform choice is JAX's own (``JAX_PLATFORMS``); a missing accelerator
is the error JAX raises, never a quiet CPU run. A process that holds a
chip is the only one that may use it, so nothing here starts a child.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache: a fixed path beside the package, because the
# directory is part of the persistent cache's key — one that moves
# (tempdir, pid, time) never hits
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory
    and return it. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads
    it itself and no directory is set in code; otherwise the cache
    lives in :data:`DEFAULT_CACHE_DIR`. Must run before the process's
    first compilation (JAX decides once whether a cache is in use)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every program, however quick to compile: a fresh machine
    # starts cold and pays each of them again (JAX's default skips
    # anything that compiled in under a second)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def require_tpu(what: str) -> None:
    """Refuse to measure anything but a TPU: a CPU timing must never be
    written under a device metric's name."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"{what}: measures the TPU, but the JAX backend "
                         f"is {backend!r}; refusing to run")


def ensure_backend() -> str:
    """The one deliberate CPU fallback, for host-path pool members.

    A chip belongs to one process. While a sibling holds it, a second
    process that asks JAX for it gets ``RuntimeError: Unable to
    initialize backend 'tpu': ABORTED: ... libtpu multi-process
    lockfile`` within seconds (it raises, it does not hang: TPU v5 lite,
    jax 0.9.0 / libtpu 0.0.34, PR 21's chip run). A pool worker whose
    task module touches JAX only for host-side arithmetic (the
    six-function digits trainer) should keep working then, so it pins
    itself to the CPU, loudly. Entry points that compile for the device
    never call this. Returns the platform in use."""
    import sys

    import jax

    try:
        return jax.devices()[0].platform
    except RuntimeError as e:
        print(f"[jax_env] accelerator unavailable to this process "
              f"({str(e).splitlines()[0]}); THIS PROCESS RUNS ON THE CPU",
              file=sys.stderr)
        jax.config.update("jax_platforms", "cpu")
        return jax.devices()[0].platform
