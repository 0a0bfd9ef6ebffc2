"""The chip: is it there, what are its peaks, what does it hold."""

import json
import os

from .resolve import BENCH_DIR


class NoChip(RuntimeError):
    pass


def require_chips(chips, rehearse=False):
    """The devices JAX found.  A measurement without a TPU, or with fewer
    chips than the cell asks for, raises: it never falls back."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if not rehearse and dev.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def peaks(device_kind, path=None):
    """The chip's published peaks, keyed by ``device_kind`` as JAX reports
    it.  A device that is not in the table is an error, not a default."""
    with open(path or os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (it has {sorted(table)})")
    return table[device_kind]


def memory_peak_bytes(devices):
    """The peak footprint on the fullest chip: the allocator's
    ``peak_bytes_in_use`` (live buffers: arguments, outputs, donated state)
    plus ``peak_bytes_reserved`` (what loaded programs reserve for their
    temporaries, which this backend keeps apart from the buffers and out of
    ``peak_bytes_in_use``)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def describe(devices, **extra):
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), **extra}


class CompileCount:
    """Backend compiles and persistent-cache hits, by JAX's own events."""

    def __init__(self):
        from jax import monitoring

        self.compiles = self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
