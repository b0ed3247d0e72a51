"""Small helpers (parity with reference ``src/torchgems/utils.py``)."""

import os

# Last enable_compilation_cache decision — read back by
# telemetry.coldstart.publish_cache_status so fleet runs are honest about
# cache state instead of silently paying compiles they believe cached.
_CACHE_STATUS = {"enabled": False, "reason": "never attempted"}


def compilation_cache_status() -> dict:
    """``{"enabled": bool, "reason": str, "dir": str|absent}`` of the last
    :func:`enable_compilation_cache` call (reason "never attempted" when
    nothing ever called it)."""
    return dict(_CACHE_STATUS)


def enable_compilation_cache(default_dir: str | None = None) -> None:
    """Turn on JAX's persistent compilation cache.

    The multi-minute XLA compiles of the 1024-2048px training programs
    dominate benchmark wall time. Directory: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and no other
    directory is set here; otherwise ``default_dir``, else
    ``<checkout>/.cache/jax`` — a fixed path, because the path is part of
    the cache key and a directory that moves never hits.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = default_dir or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".cache",
            "jax",
        )
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    _CACHE_STATUS.clear()
    _CACHE_STATUS.update(
        {"enabled": True, "reason": "persistent cache on", "dir": cache_dir}
    )


def is_power_two(n: int) -> bool:
    """True iff n is a positive power of two (ref ``utils.py:20-21``)."""
    return n > 0 and (n & (n - 1)) == 0


def get_depth(version: int, n: int) -> int:
    """ResNet depth from block multiplier n (ref ``utils.py:26-30``).

    v1: depth = 6n + 2, v2 (bottleneck): depth = 9n + 2.
    """
    if version == 1:
        return n * 6 + 2
    elif version == 2:
        return n * 9 + 2
    raise ValueError(f"unknown resnet version {version}")
