"""The chip a run measures: its checks, its compile cache, its memory."""
from __future__ import annotations

import os

from harness import peaks

CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No accelerator of a known kind, or fewer chips than the cell asks."""


def use_checkout_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache key), caching every program
    however quickly it compiled. Runs before the first compile."""
    import jax
    settings = {"jax_compilation_cache_dir": CACHE_DIR,
                "jax_persistent_cache_min_compile_time_secs": 0,
                "jax_persistent_cache_min_entry_size_bytes": 0}
    for name, value in settings.items():
        # the environment too, for anything that reads it afresh
        os.environ[name.upper()] = str(value)
        jax.config.update(name, value)
    return CACHE_DIR


def require_chips(n: int):
    """The first ``n`` TPU devices and their peak row, or :class:`NoChip`."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX's default platform is {dev.platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees {len(devices)}")
    try:
        peak = peaks.peak_for(dev.device_kind)
    except peaks.UnknownDevice as e:
        raise NoChip(str(e)) from None
    return devices[:n], peak


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no statistics)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileClock:
    """Backend compiles (persistent-cache reads included) as they happen."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.seconds += secs
            self.count += 1
