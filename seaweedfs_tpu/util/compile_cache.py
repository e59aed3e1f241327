"""One owner of JAX's persistent compilation cache.

The RS kernels compile once per (slab width, output rows) pair — seven
slab widths (ops/rs_kernel.py) times the 4-row encode map and the 1- to
4-row decode maps — and without a persistent cache every process pays
all of them again. Callers that are about to compile for an
accelerator (the volume server's start-up under ``-ec.encoder jax``,
``bench.py``, ``chip_smoke.py``) call :func:`configure` once, before
their first dispatch.

Placement rule: when ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
itself and this module sets NO path; otherwise the cache goes to ONE
fixed directory inside the checkout (listed in .gitignore). The
directory is part of the cache key, so it is never derived from a
tempfile, a pid or the time.

The kernels compile in about a second, which is JAX's default minimum
for persisting an entry, so the minimum is dropped to zero either way —
otherwise most of them would never be written.

A process held to the CPU platform (the test suite, the smoke's
rehearsal) keeps no cache: the CPU backend compiles these kernels in
well under a second, and six test workers would only contend on the
directory.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — fixed, ignored by git
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_configured: str | None = None


def placement(platforms: str | None, env_dir: str | None) -> tuple[str, bool]:
    """(directory, whether this module must set it in code) for a
    process whose jax_platforms is `platforms` and whose environment
    has JAX_COMPILATION_CACHE_DIR = `env_dir`. The whole rule, as a
    pure function."""
    if (platforms or "").strip().lower() == "cpu":
        return "", False
    if env_dir:
        return env_dir, False       # JAX reads the variable itself
    return DEFAULT_DIR, True


def configure() -> str:
    """Point JAX's persistent compilation cache at its one place.

    Returns the directory in use ("" when the process is held to the
    CPU and keeps none). Idempotent; must run before the first compile
    to cover it."""
    global _configured
    if _configured is not None:
        return _configured
    import jax

    path, set_here = placement(jax.config.jax_platforms,
                               os.environ.get(ENV_VAR))
    if path:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if set_here:
        jax.config.update("jax_compilation_cache_dir", path)
    _configured = path
    return _configured
