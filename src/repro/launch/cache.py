"""Where compiled programs are kept between runs.

JAX's persistent compilation cache is keyed by, among other things, its
directory, so a path that moves never hits.  ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself); otherwise the cache lives at a
fixed path inside the checkout.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.

    Call once at program start, before the first compile — never at
    import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
