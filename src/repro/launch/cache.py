"""Persistent compilation cache + profiler wiring for the launchers.

Two small, launcher-shared concerns live here so train / dryrun / serve
stay flag-thin:

* :func:`enable_compilation_cache` — keep jax's persistent compilation
  cache in one directory, so a fresh process re-loads compiled
  executables instead of repaying the cold compile.  The directory is
  part of every entry's key, so it never moves: ``JAX_COMPILATION_CACHE_DIR``
  where the environment sets it, else ``.jax_cache/`` at the checkout's
  root.

* :func:`profile_trace` — a context manager around
  ``jax.profiler.start_trace`` / ``stop_trace`` emitting a TensorBoard-
  loadable trace.  The train step names its stages with
  ``jax.named_scope`` — each oracle round (``lookahead``, ``commit``) with
  ``forward``, ``backward``, ``update`` and the ``exchange`` inside it,
  whose kernels are ``noise``, ``quantize``, ``reduce`` and
  ``dequantize``; then ``step_metrics`` and ``guard`` — so
  communication/compute overlap is visible per bucket in the trace viewer
  (workflow documented in DESIGN.md §10).

Both are failure-tolerant by design: a launcher must never die because a
cache directory is read-only or a profiler backend is missing — the
feature degrades to a warning and the run proceeds uncached/unprofiled.
"""

from __future__ import annotations

import contextlib
import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the in-checkout cache directory used when ``ENV_VAR`` is unset
#: (git-ignored): <repo>/.jax_cache, fixed so its entries are found again
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def cache_dir() -> str:
    """The persistent cache's directory: ``$JAX_COMPILATION_CACHE_DIR``
    when set (and non-empty), else :data:`DEFAULT_DIR`."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable_compilation_cache() -> str:
    """Turn jax's persistent on-disk compilation cache on; return its
    directory ("" when enabling failed: warning printed, run continues
    uncached).  Must be called BEFORE the first jit compile to be of any
    use; the launchers call it right after arg parsing.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax reads the variable itself
    and nothing is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    path = cache_dir()
    try:
        os.makedirs(path, exist_ok=True)
        if not os.environ.get(ENV_VAR):
            from jax.experimental.compilation_cache import compilation_cache

            compilation_cache.set_cache_dir(path)
        return path
    except OSError as e:
        print(f"[cache] WARNING: compilation cache disabled ({e})",
              file=sys.stderr, flush=True)
        return ""


@contextlib.contextmanager
def profile_trace(profile_dir: str):
    """Emit a ``jax.profiler`` trace of the enclosed block to
    ``profile_dir`` (TensorBoard / Perfetto loadable).  Yields True when
    tracing is active, False when ``profile_dir`` is empty or the
    profiler could not start (warning printed, block runs unprofiled).
    """
    if not profile_dir:
        yield False
        return
    import jax

    try:
        os.makedirs(profile_dir, exist_ok=True)
        jax.profiler.start_trace(profile_dir)
    except (OSError, RuntimeError) as e:
        print(f"[profile] WARNING: trace disabled ({e})",
              file=sys.stderr, flush=True)
        yield False
        return
    try:
        yield True
    finally:
        jax.profiler.stop_trace()
        print(f"[profile] trace written to {profile_dir}", flush=True)
