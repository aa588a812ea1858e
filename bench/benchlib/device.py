"""The chip: finding it, its published peaks, the compile cache, memory.

A run that finds no TPU, or fewer chips than its cell asks for, stops
with a non-zero exit and prints no result; it never falls back to the
CPU.  Peaks come from ``bench/peaks.json`` keyed by ``device_kind``; a
device that is not in the table is an error.
"""
from __future__ import annotations

import json
import os
import pathlib

from benchlib.spec import BENCH_DIR, ROOT

PEAKS_FILE = BENCH_DIR / "peaks.json"
# a fixed path inside the checkout: the directory is part of the key
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def require_tpu(chips: int):
    """Pin JAX to the TPU and return its devices; no chip is an error."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "tpu" not in plats.split(","):
        raise NoChip(f"JAX_PLATFORMS={plats!r} excludes the TPU")
    import jax
    jax.config.update("jax_platforms", "tpu")
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no TPU: {e}") from None
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU, found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def peaks(device_kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {path}; have {sorted(table)}")
    return table[device_kind]


def enable_compile_cache() -> str:
    """Every program, however quick to compile, goes to the fixed cache
    directory, so only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    out = 0
    for d in devs:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out


class CompileCounter:
    """Counts programs compiled or loaded from the cache while ``on``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event: str, secs: float, **_) -> None:
        if self.on and event == self.EVENT:
            self.count += 1
            self.seconds += secs
