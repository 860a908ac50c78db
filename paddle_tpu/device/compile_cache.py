"""Persistent XLA compilation cache wiring.

Every ``jax.jit(...).lower(...).compile()`` in the process (the static
Executor, ``run_steps`` fused loops, ``jit.to_static``, eager segment
compiles) writes its executable to JAX's on-disk compilation cache and
warm-process compiles are served from disk — a BERT-base train step is
a minutes-class compile on the TPU.

Where the cache lives is decided outside the program: JAX itself reads
``JAX_COMPILATION_CACHE_DIR`` at import, and when that variable is set
this module sets no directory.  Otherwise the cache goes to one fixed,
git-ignored directory in the checkout (``DEFAULT_CACHE_DIR``) — the
path is part of the cache key, so it never carries a pid, a time or a
temp name.  ``jax_enable_compilation_cache=False`` (the test suite sets
it) keeps the cache off altogether.  JAX leaves an instruction's
metadata out of the key; a step program whose metadata is read
(``observability.note_program``: its block scopes) compiles through
``compile_keyed_by_metadata``, which puts it in.

The in-process layer above it is the Executor's program-fingerprint
-keyed executable cache (``static/executor.py``): a structurally
identical (program, feed-spec, fetch-spec) triple reuses the compiled
entry across Executor instances without even re-lowering.

``ensure_compile_cache()`` is called lazily right before the first
compile; it is idempotent and near-free after the first call.  Every
compile site records ``compile.count`` / ``compile.ms`` in the
observability metrics registry so cold vs warm compile cost is
measurable.
"""
from __future__ import annotations

import os
import threading

__all__ = ["DEFAULT_CACHE_DIR", "ensure_compile_cache",
           "compile_keyed_by_metadata", "record_compile_metrics"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = threading.Lock()
_applied = False


def ensure_compile_cache():
    """Turn JAX's persistent compilation cache on (idempotent) and
    return its directory, or None when the cache is disabled.

    Thresholds are zeroed so even fast compiles persist — the default
    min-compile-time gate would skip the many small programs the eager
    tiers compile.
    """
    global _applied
    import jax
    if not jax.config.jax_enable_compilation_cache:
        return None
    if _applied:
        return jax.config.jax_compilation_cache_dir
    with _lock:
        if not _applied:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
            if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                from jax.experimental.compilation_cache import (
                    compilation_cache)
                os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
                jax.config.update("jax_compilation_cache_dir",
                                  DEFAULT_CACHE_DIR)
                # jax's disk cache is initialized once, on the first
                # compile — a compile that ran before the dir was set
                # latches it off, so force re-initialization
                compilation_cache.reset_cache()
            _applied = True
    return jax.config.jax_compilation_cache_dir


def compile_keyed_by_metadata(lowered):
    """``lowered.compile()`` with the instructions' metadata (``op_name``
    and with it the block scopes, source lines) in the persistent
    cache's key, for a step program whose map is kept
    (``observability.note_program``).  By default JAX leaves it out, so
    a tree that only moved a scope would be handed the older tree's
    executable, and read the older tree's map off it.  The many small
    host-path programs keep the default and hit across trees."""
    import jax
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return lowered.compile()
    finally:
        jax.config.update(flag, before)


def record_compile_metrics(ms, kind="compile"):
    """Land one compile's wall time in the metrics registry
    (``compile.count`` counter + ``compile.ms`` histogram, plus a
    per-kind histogram)."""
    from .. import observability as obs
    reg = obs.get_registry()
    reg.counter("compile.count").inc()
    reg.histogram("compile.ms").observe(ms)
    reg.histogram(f"compile.ms.{kind}").observe(ms)
