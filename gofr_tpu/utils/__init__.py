"""gofr_tpu.utils — small shared helpers."""

from __future__ import annotations

import re

_SNAKE_RE = re.compile(r"(?<!^)(?=[A-Z])")


def snake_case(name: str) -> str:
    """CamelCase -> snake_case; shared by ORM column mapping (datasource.sql)
    and CRUD table/path derivation (crud) so the two never diverge."""
    return _SNAKE_RE.sub("_", name).lower()


_CACHE_DIR: str | None = None  # set once per process by enable_compilation_cache


def enable_compilation_cache(logger=None) -> str:
    """Turn on JAX's persistent (on-disk) compilation cache, idempotently,
    and return its directory ("" when it could not be created).

    One cache, placed from outside: where JAX_COMPILATION_CACHE_DIR is set
    jax already carries that directory and none is set here; otherwise the
    cache is ``<checkout>/.xla_cache`` — a fixed path beside the package,
    never the home directory, a temp name, a pid or a time, because a
    directory that moves never hits. Either way the persistence thresholds
    drop to zero (an engine builds dozens of programs that compile in
    under a second and a cold start pays for every one) and jax's cache
    object is reset: jax initializes it on the FIRST compile and never
    re-reads the config, so without the reset anything compiled before
    this call leaves every later compile uncached.
    """
    global _CACHE_DIR
    if _CACHE_DIR is not None:
        return _CACHE_DIR
    import os

    import jax
    from jax._src.compilation_cache import reset_cache

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not directory:
        checkout = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        directory = os.path.join(checkout, ".xla_cache")
        try:
            os.makedirs(directory, exist_ok=True)
        except OSError as e:  # read-only checkout: cold compiles, not a crash
            if logger is not None:
                logger.warn(f"compilation cache disabled: {e}")
            _CACHE_DIR = ""
            return ""
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    reset_cache()
    _CACHE_DIR = directory
    if logger is not None:
        logger.debug(f"XLA persistent compilation cache at {directory}")
    return directory


def pin_jax_platform(platform: str) -> None:
    """Pin the jax backend (jax.config jax_platforms) and VERIFY it took.

    jax.config.update silently no-ops once a backend is initialized, so the
    only reliable failure signal is comparing jax.default_backend() after
    the update. A platform that did not take raises: serving on a backend
    other than the one the operator named is an error, not a warning.
    """
    if not platform:
        return
    import jax

    jax.config.update("jax_platforms", platform)
    active = jax.default_backend()
    # jax_platforms may list fallbacks ("tpu,cpu"); accept any listed entry.
    wanted = [p.strip() for p in platform.split(",") if p.strip()]
    if active not in wanted:
        raise RuntimeError(
            f"TPU_PLATFORM={platform} did not take: jax is already "
            f"initialized on '{active}' (set it before any jax usage)"
        )
