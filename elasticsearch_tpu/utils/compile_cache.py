"""Where JAX keeps its persistent compilation cache.

The entry points (rest/server.py, chip_smoke.py, bench.py,
scripts/profile_capture.py) call `configure_compile_cache()` before their
first compile; tests never do. The cache directory is part of what makes an
entry found again, so it is fixed: JAX_COMPILATION_CACHE_DIR where the
environment sets it (JAX reads that variable itself, and no other directory
is set in code), otherwise `<checkout>/.jax_cache` — never a temporary,
pid- or time-named directory.
"""

from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at its fixed place; returns it."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
