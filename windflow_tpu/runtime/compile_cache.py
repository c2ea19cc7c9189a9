"""Placement of JAX's persistent compilation cache.

A streaming graph re-runs the SAME program signatures forever, and every
process start (supervised restart, rescale, a fresh benchmark or smoke
run) would otherwise re-compile all of them. One rule, applied by
``PipeGraph.start`` and by the scripts that drive replicas directly:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no directory
  is set in code, so whoever launched the process decides where the
  cache lives;
- otherwise ``cache_dir`` if the caller gave one
  (``PipeGraph.with_compile_cache``), else ``<checkout>/.jax_cache``.
  The path is part of the cache key, so it is fixed — never a temp,
  pid or time-derived directory.

Both persistence thresholds drop to zero so small chain programs
persist too.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compile_cache(cache_dir: Optional[str] = None) -> str:
    """Apply the rule above before the first device program traces;
    returns the directory in effect."""
    import jax

    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        if cache_dir and os.path.abspath(cache_dir) != os.path.abspath(
                env_dir):
            print(f"windflow_tpu: compile cache dir {cache_dir!r} ignored, "
                  f"JAX_COMPILATION_CACHE_DIR={env_dir!r} is set",
                  file=sys.stderr)
        return env_dir
    path = os.path.abspath(cache_dir or DEFAULT_CACHE_DIR)
    prev = jax.config.jax_compilation_cache_dir
    if prev != path:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        if prev:
            # JAX opens its cache once per process; an earlier graph
            # already opened it elsewhere, so re-open at the new path
            from jax.experimental.compilation_cache import compilation_cache
            compilation_cache.reset_cache()
    return path
