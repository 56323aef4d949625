"""Memory samplers and the optional ``jax.profiler`` session hook.

Two memory sources feed gauges in the registry:

* host peak RSS — ``resource.getrusage(RUSAGE_SELF).ru_maxrss`` (kilobytes
  on Linux, bytes on macOS; normalized to bytes here). This is the number
  PR 9's out-of-core work gates on, so the pipeline samples it after every
  stage into ``process.peak_rss_bytes``.
* JAX device memory — ``device.memory_stats()`` where the backend exposes
  it (TPU/GPU do; CPU returns None), the maximum over this process's
  local devices so a run over four chips reports its fullest chip.
  Sampled into ``jax.device.bytes_in_use`` / ``jax.device.peak_bytes_in_use``.

Everything JAX-touching imports lazily: ``repro.obs`` must stay importable
(and fast) in processes that never load JAX, e.g. the ``summarize`` CLI
reading a trace file.
"""
from __future__ import annotations

import sys
from typing import Optional

__all__ = ["peak_rss_bytes", "sample_memory", "jax_profiler_session"]


def peak_rss_bytes() -> Optional[int]:
    """Process peak RSS in bytes, or None where unsupported."""
    try:
        import resource
    except ImportError:          # non-POSIX
        return None
    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    if sys.platform == "darwin":
        return int(ru)           # macOS reports bytes
    return int(ru) * 1024        # Linux reports kilobytes


def _device_memory() -> Optional[dict]:
    """Each ``memory_stats()`` key's maximum over the local devices."""
    try:
        import jax
    except ImportError:
        return None
    stats: dict = {}
    for device in jax.local_devices():
        for key, value in (device.memory_stats() or {}).items():
            stats[key] = max(stats.get(key, value), value)
    return stats or None


def sample_memory(registry) -> None:
    """Record current memory readings into ``registry`` gauges."""
    rss = peak_rss_bytes()
    if rss is not None:
        registry.gauge("process.peak_rss_bytes").set(rss)
    stats = _device_memory()
    if stats:
        for key in ("bytes_in_use", "peak_bytes_in_use"):
            if key in stats:
                registry.gauge(f"jax.device.{key}").set(stats[key])


class jax_profiler_session:
    """Context manager running a ``jax.profiler`` trace over its body.

    Used around the training stage when the pipeline is given a profile
    directory (``--jax-profile DIR``). A profile that was asked for and
    cannot start or stop is an error, not a run without a trace: the
    failure is counted as ``jax.profiler.failed`` and raised (on stop,
    unless the body is already raising).
    """

    def __init__(self, out_dir: Optional[str], registry=None):
        self.out_dir = out_dir
        self._registry = registry
        self._active = False

    def _failed(self) -> None:
        if self._registry is not None:
            self._registry.counter("jax.profiler.failed").inc()

    def __enter__(self):
        if not self.out_dir:
            return self
        import jax
        try:
            jax.profiler.start_trace(self.out_dir)
        except RuntimeError:
            self._failed()
            raise
        self._active = True
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._active:
            self._active = False
            import jax
            try:
                jax.profiler.stop_trace()
            except RuntimeError:
                self._failed()
                if exc_type is None:
                    raise
        return False
