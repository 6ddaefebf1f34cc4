"""Process-level runtime set-up shared by the entry points (``cli.main``,
``bench.py``, ``chip_smoke.py``)."""

from __future__ import annotations

import os

import jax

#: the checkout this package was imported from
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. ``JAX_COMPILATION_CACHE_DIR`` places it from outside: JAX
    reads that variable itself, so nothing is set here (nor when the
    process has already placed one, as the test harness does).
    Otherwise it lives at ``<checkout>/.jax_cache`` — a fixed path,
    because the path is part of what a cache entry is found by.

    An entry is found by the computation alone: JAX leaves operation
    names and source lines out of the key, so an executable carries the
    ``jax.named_scope`` names of whoever compiled it first, and one
    compiled before a scope was added reads ``unscoped`` in a device
    trace (seen on the v5e, PR 24). That is left so on purpose: with
    names in the key (``JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY=1``,
    JAX's own variable, for the one run that must see new scopes) every
    program of the process compiles anew after an edit that moves a
    line, and the two sides of a comparison stop sharing executables
    (``PERF.md`` section 6, PR 24)."""
    placed = jax.config.jax_compilation_cache_dir
    if placed:
        return placed
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
