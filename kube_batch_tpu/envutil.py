"""Process-environment helpers: integer/boolean knobs, the "CPU with N
virtual devices" environment the tests and harness children run under, and
the placement of JAX's persistent compilation cache.

This module (and the package __init__) must stay jax-free at import: the
CPU environment only takes effect when it is in place before the
process's first jax import.
"""

from __future__ import annotations

import logging
import os

_DEVCOUNT_FLAG = "--xla_force_host_platform_device_count"

#: where compiles persist when JAX_COMPILATION_CACHE_DIR does not say: one
#: fixed path inside the checkout (git-ignored).  The path is part of the
#: cache key, so it must not move between runs.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)

_logger = logging.getLogger("kube_batch_tpu")


def env_int(name: str, default: int) -> int:
    """Parse an integer knob; an unparsable value logs and keeps the
    default (the ONE shared implementation — guard/plane, serve/batcher,
    and the obs/ modules all read knobs this way)."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        _logger.warning("unparsable %s=%r; using %d", name, raw, default)
        return default


def env_flag(name: str, default: bool) -> bool:
    """Parse a boolean knob: unset → default; anything but
    0/false/off/no → True."""
    raw = os.environ.get(name, "").strip().lower()
    if not raw:
        return default
    return raw not in ("0", "false", "off", "no")


def cpu_env(n_devices: int | None = None, base: dict | None = None) -> dict:
    """A copy of `base` (default os.environ) that pins JAX to the CPU
    backend, optionally with `n_devices` virtual devices."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith(_DEVCOUNT_FLAG)]
        flags.append(f"{_DEVCOUNT_FLAG}={n_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env


def apply_cpu_env(n_devices: int | None = None) -> None:
    """Mutate os.environ in place; call before the first jax import."""
    os.environ.update(cpu_env(n_devices))


def compile_cache_dir() -> str:
    """Where this program's compiles persist: JAX's own variable where it
    is set, else COMPILE_CACHE_DIR.  (jax-free: chip_smoke.py's parent
    counts the entries there.)"""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE_DIR


def enable_persistent_compilation_cache() -> str:
    """Keep compiles on disk so a restarted process re-pays none of them.
    Where JAX_COMPILATION_CACHE_DIR is set, whoever runs the program chose
    the directory and JAX reads the variable itself — this sets none.
    Otherwise the cache lives at COMPILE_CACHE_DIR.  Returns the directory
    in effect.  Call before the first compile (it only configures jax, it
    does not initialise the backend)."""
    import jax

    # also persist the many sub-second host-jnp helpers (the default only
    # caches compiles over 1 s)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return compile_cache_dir()
