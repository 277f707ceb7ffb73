"""Which device: the one answer, and where compiled programs are kept.

Every "is there a chip" question in the package is `info()`.  It
initialises JAX once and reports what JAX found; nothing downstream
infers a platform, picks interpret mode, or swaps in a host codec from a
caught exception.  Importing this module does not import JAX — a server
pinned to the host codec never touches it, which is what lets one
process own the chip.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class BackendUnavailable(RuntimeError):
    """A device backend was asked for and JAX found no such device."""


class DeviceInfo(NamedTuple):
    platform: str  # jax.devices()[0].platform: "tpu" | "cpu" | ...
    kind: str      # jax.devices()[0].device_kind, e.g. "TPU v5 lite"
    count: int     # len(jax.devices())


@functools.cache
def info() -> DeviceInfo:
    import jax

    devs = jax.devices()
    return DeviceInfo(devs[0].platform, devs[0].device_kind, len(devs))


def require_tpu(what: str) -> DeviceInfo:
    """`info()`; BackendUnavailable unless the default platform is a TPU."""
    dev = info()
    if dev.platform != "tpu":
        raise BackendUnavailable(
            f"{what} needs a TPU; JAX found {dev.count} x {dev.platform} "
            f"({dev.kind})")
    return dev


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    The server's entry point calls this before its first compile.
    JAX_COMPILATION_CACHE_DIR wins when set (JAX reads it itself; no
    directory is set here); otherwise the cache lives at
    <checkout>/.jax_cache — a fixed path, because the path is part of
    the cache key.  Small programs are cached too: the codec kernels
    compile in well under JAX's default one-second floor."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


def compile_cache_dir() -> str | None:
    """The persistent-cache directory in use (None: cache not enabled)."""
    import jax

    return jax.config.jax_compilation_cache_dir


def peak_bytes_in_use() -> int | None:
    """Device 0's peak allocation, where the backend reports one."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None
