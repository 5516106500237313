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

An entry's key holds the program's metadata too (source lines and the
`jax.named_scope` / autodiff name stack that becomes every op's
`op_name`). JAX leaves it out by default, so that a program whose
source lines moved still hits; the price is that the executable
loaded is the one compiled before the move, with the old names, and a
profiler's trace of it names scopes the source no longer has and
lacks the ones it gained. The scope paths are what the traced
benchmark splits a step by (`PERF.md` section 3), so here a program
whose names changed compiles once more instead.

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
    # Cache every compile, however quick: the serving path compiles
    # dozens of small programs whose combined cost is what hurts, and
    # with the metadata in the key (below) no entry is shared between
    # two call sites of one program, so none would pass a threshold by
    # the luck of one slow compile.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # A trace must show the names the source has: see the docstring.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return cache_dir()
