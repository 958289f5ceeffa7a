"""The device under test: the refusal without a chip, the compile counter,
the compile cache and the peak memory read."""
from __future__ import annotations

import contextlib
import os
import pathlib

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for: exits non-zero
    before any work and before any result is printed."""


def use_compile_cache(root) -> str:
    """JAX's persistent compilation cache: `JAX_COMPILATION_CACHE_DIR` if
    set, else the fixed `<checkout>/.jax_cache` (the path is part of the
    cache key, so it never moves). Every program is cached, however short
    its compile, so that only a checkout's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        pathlib.Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_tpu(chips: int):
    """JAX's devices, when they are at least `chips` TPUs; else NoChip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}; "
                     "nothing was run")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    return devs


@contextlib.contextmanager
def compile_log():
    """Counts what JAX hands to the backend inside the block:
    {"count", "loads"}.

    `jax.monitoring` times every program handed to XLA as a backend
    compile, also when the persistent cache has it; `loads` counts those
    cache hits, and `count` the programs that were really compiled.
    """
    import jax
    log = {"count": 0, "loads": 0}

    def on_duration(event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            log["count"] += 1

    def on_event(event, **_):
        if event == CACHE_HIT_EVENT:
            log["loads"] += 1
            log["count"] -= 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def peak_bytes(devices) -> int | None:
    """`peak_bytes_in_use` of the fullest device, where the backend has it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
