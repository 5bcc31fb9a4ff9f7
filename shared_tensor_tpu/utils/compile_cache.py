"""Where compiled programs are kept between runs.

A chip machine is handed out per command and thrown away after it, and the
flagship train step takes tens of seconds to compile, so every entry point
that compiles for the chip (chip_smoke.py, bench.py's worker,
benchmarks/train_bench.py, benchmarks/pareto.py,
examples/train_char_rnn.py) calls :func:`enable_compile_cache` before its
first jit. Nothing here runs at package import.

The directory is part of JAX's cache key, so it never comes from a
temporary name, a pid or the time: it is where ``JAX_COMPILATION_CACHE_DIR``
says (JAX reads that variable itself, and this module then sets nothing) or
``<checkout>/.jax_cache`` (in .gitignore).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Call before the first compilation of the process."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
