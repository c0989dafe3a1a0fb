"""JAX's persistent compilation cache, placed once for every entry point.

Called from ``paddle_tpu/__init__.py``, so the trainer, the serving
engine, ``bench.py``, the tools and ``chip_smoke.py`` share one cache: a
24-layer model compiles one program per prefill bucket plus the decode and
train steps, tens of seconds each, and a second process (or a second run
in the same checkout) should pay for none of them again.

The directory is part of the cache's key, so it never moves:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this module
  sets no directory in code — whoever runs the program owns the place.
- otherwise ``<checkout>/.jax_cache``, a fixed path inside the checkout
  (git-ignored), never derived from a temp name, a pid or a time.
- a process that asked for the CPU (``JAX_PLATFORMS=cpu``) is given none:
  the cache is there for the chip's compiler, and XLA:CPU reloads a cached
  executable with a page of machine-feature warnings on every hit.

Tests turn the cache off altogether (``tests/conftest.py``).
"""
from __future__ import annotations

import os

import jax

#: the fixed in-checkout location used when the environment names none
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

#: programs that took at least this long to compile are kept. JAX's own
#: default (1 s) would already keep every per-bucket prefill program of a
#: real model; half a second also keeps the mid-sized ones while leaving
#: out the hundreds of one-op eager programs the Paddle API surface makes
MIN_COMPILE_SECS = 0.5


def cache_dir() -> str | None:
    """The directory the cache lives in: the environment's, else the
    fixed one inside the checkout — or None where this module leaves the
    cache alone (a process that asked for the CPU)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    return DEFAULT_DIR


def configure() -> None:
    """Point JAX at the cache directory, unless the environment already
    has (JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself)."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    if cache_dir() == DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
