"""Process-level JAX set-up shared by every entry point: where the
persistent compilation cache lives, and virtual host devices for the CPU
lanes. The platform itself is stock JAX's business (``JAX_PLATFORMS=cpu``
for the test lanes; unset on a TPU host).
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the one fallback location: the directory is part of the cache key's
# lookup path, so a cache that moves (temp name, pid, time) never hits
DEFAULT_COMPILE_CACHE = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Every entry point (the four roles,
    chip_smoke.py, both conftests) calls this before its first
    compile, so a restarted role — or the next command on the same
    machine — deserializes the previous process's XLA executables
    instead of recompiling them.

    Placement has one knob, outside the code: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already honours it and no
    directory is set here; otherwise the cache goes to
    ``<repo>/.jax_cache``. Every program is cached, however quick its
    compile (the tier-1 suite is thousands of sub-second compiles of
    identical tiny-model HLO)."""
    import jax
    from jax._src import compilation_cache

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the cache module memoizes "disabled" the first time ANY compile
    # runs without a directory configured; reset so a call that comes
    # after an early compile still takes effect
    compilation_cache.reset_cache()
    logger.info("persistent compilation cache at %s", path)
    return path


def ensure_virtual_devices(n: int) -> None:
    """Guarantee XLA_FLAGS requests >= ``n`` host-platform devices.

    Must run before the first backend touch. An existing smaller count
    (stale operator env) is RAISED in place — appending a second flag
    instance would rely on unspecified last-wins parsing, and keeping
    the stale value fails later with a mesh-size error that never
    mentions the env var. Shared by the AOT scale artifact and the
    sharded E2E runners."""
    import re

    flag = "--xla_force_host_platform_device_count"
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{flag}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = f"{flags} {flag}={n}".strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = flags.replace(m.group(0), f"{flag}={n}")
