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

import contextlib
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


@contextlib.contextmanager
def keyed_by_names(on: bool = True):
    """For a run under the profiler: make the operations' names part of
    the cache key.

    jax keys the persistent cache on the program with its debug info
    stripped, so two programs that differ only in their ``op_name``
    paths (a ``jax.named_scope`` added or renamed: obs/events.py
    ``DEVICE_SCOPES``) share one entry, and whichever was compiled
    first comes back with ITS names. Measured, PR 25: the first traced
    chip run after the scopes landed loaded the parent commit's
    executable and its trace carried not one scope. A profiled run is
    read by those names, so inside this context the key includes them
    (``jax_compilation_cache_include_metadata_in_key``), and the
    locations carry the name stack alone, no file or line
    (``jax_traceback_in_locations_limit`` 0): the key then moves with a
    name, not with every edit that shifts a line or with the checkout's
    path. Runs without the profiler keep jax's default key.
    """
    if not on:
        yield
        return
    import jax

    names = ("jax_compilation_cache_include_metadata_in_key", "jax_traceback_in_locations_limit")
    prior = [getattr(jax.config, n) for n in names]
    for n, v in zip(names, (True, 0)):
        jax.config.update(n, v)
    try:
        yield
    finally:
        for n, v in zip(names, prior):
            jax.config.update(n, v)
