"""Where the persistent XLA compilation cache lives.

One rule for every entry point that compiles (`chip_smoke.py`,
`benchmark/run.py`, the node CLI, the examples, `tests/conftest.py`):

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it at import and the
  cache lives there — code sets no directory, so whoever launches the
  process can place the cache (and keep it across machines).
- unset: `<checkout>/.jax_cache`, a fixed path inside the checkout
  (listed in `.gitignore`). The directory is part of every cache key,
  so a path that moves — `/tmp`, a pid, a `mkdtemp`, a timestamp —
  never hits.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect. Safe
    before or after the first backend use — `jax.config.update` takes
    effect for every later compile."""
    import jax

    placed = os.environ.get(CACHE_ENV)
    if not placed:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # cache every program that takes >= 1 s to compile, however small
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return placed or DEFAULT_CACHE_DIR
