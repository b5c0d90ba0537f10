"""Where JAX's persistent compilation cache lives.

Compiles on the TPU are large (a whole-network executable takes seconds to
tens of seconds), so the entry points that run on the chip (`chip_smoke.py`,
`examples/serve_conv.py`, `benchmarks/run.py`) share one persistent cache.
Its path is part of the cache's key, so it is fixed: `JAX_COMPILATION_CACHE_DIR`
when the environment sets it (JAX reads that variable itself, and nothing is
set here), otherwise `.jax_cache/` at the root of the checkout (gitignored).
"""

from __future__ import annotations

import os

import jax

#: the in-checkout default: <repo>/.jax_cache (this file is
#: <repo>/src/repro/runtime/cache.py).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Without `JAX_COMPILATION_CACHE_DIR` this sets the in-checkout default
    for this process and exports it, so child processes share the cache.
    Touches no device: a parent that must stay off the chip may call it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    os.environ["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
