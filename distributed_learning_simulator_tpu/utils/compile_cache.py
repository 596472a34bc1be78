"""Placement of JAX's persistent compilation cache.

The one function in the tree that sets ``jax_compilation_cache_dir``.
The directory is part of nothing but the lookup, yet it has to be the
same on every run for a later run to hit it, so it is never derived from
the CWD, a temp name, a pid or the time:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already took the directory from
  the environment at import; the program sets no directory in code,
  whatever ``config.compilation_cache_dir`` holds.
* unset: ``config.compilation_cache_dir`` resolves against the checkout
  (the directory that holds this package) — the default ``.jax_cache``
  is ``<checkout>/.jax_cache`` from any CWD; an absolute path stays as
  given; ``None`` means no persistent cache.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def configure_compilation_cache(cache_dir: str | None) -> str | None:
    """Apply ``config.compilation_cache_dir``; returns the directory in
    effect (``None`` = no persistent cache).

    The setting is process-global, so ``None`` resets it: a cache enabled
    by an earlier run in this process must not leak into a run that
    asked for none.
    """
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = os.path.join(_CHECKOUT, cache_dir) if cache_dir else None
    jax.config.update("jax_compilation_cache_dir", path)
    return path
