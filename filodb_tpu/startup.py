"""Process start-up shared by every entry point that runs device programs
(``standalone.main``, ``cli.main``, ``bench.py``, ``chip_smoke.py``).

Two jobs, both about the accelerator the process was given:

- the persistent compile cache. Cold, the served path compiles a dozen
  programs of 1-10 s each on a TPU; a restarted server should pay that once
  per installation, not once per process. The directory is part of the cache
  key, so it must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` wins
  when the operator (or the harness) sets it, and JAX's own handling of that
  variable is then the only configuration; otherwise the cache lives at one
  fixed path inside the checkout.
- naming the device. Whatever JAX selected is what runs; nothing here
  probes, retries or falls back to another backend.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")

# JAX's default skips programs that compiled in under a second (its size
# floor is already 0); the mesh engine's group-reduce and bounds programs
# compile in 0.2-1 s each and a cold query runs several of them.
_MIN_COMPILE_TIME_SECS = 0.1


def configure_jax() -> str:
    """Turn on JAX's persistent compile cache; returns its directory."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _MIN_COMPILE_TIME_SECS)
    return cache_dir


def device_info() -> dict:
    """The backend JAX gave this process, as the harness reads it:
    ``{"platform", "kind", "count"}``. Initializes the backend, so a
    process that cannot reach its accelerator fails here."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
