"""The ONE place jax's persistent compilation cache is placed.

Every entry point that may compile (the CLI, ``serve``, CPU pool
workers, the bench scripts; ``launch`` ranks and ``chip_smoke.py``'s
phases are CLI processes) calls ``wire_compile_cache()`` before the
first backend use, so a repeat sweep, a supervisor restart and every
tenant of a resident server load their programs from disk instead of
compiling them again.

Where it lives is decided from outside when ``JAX_COMPILATION_CACHE_DIR``
is set: jax reads that variable itself, and this module then sets no
directory in code at all. Otherwise it is one fixed directory inside
the checkout. Fixed, because the lookup is by path: a temp name, a pid
or a timestamp in it would give every process an empty cache of its
own. Entries are keyed by program, compile options and backend, so the
CPU and the TPU share the directory without meeting.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (git-ignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def wire_compile_cache() -> str:
    """Place the cache (see module docstring); returns the directory in
    effect. Idempotent. Call it before the first compile: jax opens the
    cache once, at first use, and keeps that directory from then on."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
