"""Pieces every driver shares: where things are, the look for the chip,
the compile counter, peak memory and percentiles."""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, Optional, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))       # the checkout


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def find_cell(bench: Dict, workload: str):
    """(cell, config entry) of the named workload in BENCHMARK.json."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return cell, configs[cell["config"]]


def use_program() -> None:
    """Put the program under test on the import path."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def devices_for(chips: int, require_tpu: bool = True):
    """The first `chips` devices; NoChip where JAX finds no TPU or fewer
    chips than asked."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: $JAX_COMPILATION_CACHE_DIR
    where set, else the checkout's fixed `.jax_cache`. Every program is
    written to it, however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Backend compilations JAX reports (count and seconds), from its own
    monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


class GcClock:
    """Pauses of Python's garbage collector (count, seconds, longest), so
    that a host stall in the window can be told from one on the device."""

    def __init__(self):
        import gc
        import time
        self.count, self.seconds, self.longest = 0, 0.0, 0.0
        self._t0 = None

        def on_gc(phase, info):
            if phase == "start":
                self._t0 = time.perf_counter()
            elif self._t0 is not None:
                d = time.perf_counter() - self._t0
                self.count += 1
                self.seconds += d
                self.longest = max(self.longest, d)

        gc.callbacks.append(on_gc)


def peak_bytes(devices) -> Optional[int]:
    """Peak memory in use on the fullest of the devices, where the
    backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def percentile(xs: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def free_device_memory() -> None:
    """Delete every live array: the reference runs after the program's
    state is gone, so that it never sets the memory peak."""
    import jax
    for a in jax.live_arrays():
        a.delete()


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)

