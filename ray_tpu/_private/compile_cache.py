"""Persistent XLA compilation cache.

A cold start compiles every program of a path (the LLM engine's bucket
ladder, the train step); with JAX's on-disk cache every later process
loads the executables instead. Where the cache lives follows one rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this module sets no directory.
- unset: one fixed directory in the checkout, beside the package. The
  path is part of the cache's key, so it is derived from the package's
  location and never from the working directory, the home directory, a
  pid or a time.

Both entry paths (`JaxTrainer` workers, `LLMEngine`) call
:func:`enable_persistent_cache` before their first compile: JAX
initialises its cache once, at the first compilation of the process.
"""

from __future__ import annotations

import os
from typing import Optional

_IN_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The directory compiled programs are cached in."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _IN_CHECKOUT_DIR


def enable_persistent_cache() -> Optional[str]:
    """Idempotently turn JAX's on-disk compilation cache on for this
    process. Returns the directory in use, or None on the CPU backend:
    cached CPU executables are machine-feature-sensitive (XLA warns a
    mismatched load "could lead to SIGILL") and the cache's win is on
    accelerators."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_IN_CHECKOUT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _IN_CHECKOUT_DIR)
    # Cache even quick compiles: the serving path compiles many
    # small-bucket programs whose combined cost is what hurts.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    return cache_dir()
