"""The chip: presence check, peak table, memory peak, compile counting."""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, List, Tuple

from harness.spec import BENCH

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(device_kind: str, path: str = os.path.join(BENCH, "peaks.json")
          ) -> Dict[str, float]:
    """Peak rates of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in the peak "
                       f"table ({sorted(table)})")
    return {k: float(v) for k, v in table[device_kind].items()}


def require_chips(n: int):
    """The first ``n`` TPU devices, with their peaks; raises ``NoChip``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chip(s), JAX found {len(devices)}")
    peaks(devices[0].device_kind)
    return devices[:n]


def device_stamp(devices) -> Dict[str, Any]:
    """The result line's ``device`` object: as JAX reports it, with the peak
    bytes in use on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileLog:
    """Wall-clock times and durations of XLA backend compiles (persistent
    cache reads included), from JAX's monitoring events."""

    def __init__(self):
        self.events: List[Tuple[float, float]] = []

    def _on(self, event: str, secs: float, **_):
        if event == COMPILE_EVENT:
            self.events.append((time.time(), secs))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def within(self, t0: float, t1: float) -> int:
        """Compiles that ended inside the wall-clock window [t0, t1]."""
        return sum(1 for t, _ in self.events if t0 <= t <= t1)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
