"""One run of one cell: chip check, generator, result line, compared numbers.

A traffic generator (``bench/traffic/<generator>.py``) exposes
``run(r: Run) -> Outcome``.
It sets up, warms up, measures for ``r.seconds``, optionally traces a short
steady stretch, reads the device's memory peak, frees the program's state and
then runs the plain reference. This module turns its ``Outcome`` into the
last line of standard output and prints every compared number beside its
limit as the last lines of standard error.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from harness import device, spec
from harness.device import log


@dataclass
class Check:
    """One compared number: passes when ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Run:
    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    devices: Any
    t_start: float                      # wall clock at process start
    compiles: device.CompileLog
    keep_trace: str = ""                # copy the raw trace here if set
    peak_table: Optional[Dict[str, float]] = None   # tests off the chip
    # test hook: called with (name, obj) at the points where a fault may be
    # planted under the timed path; returns the object to use
    plant: Optional[Callable[[str, Any], Any]] = None

    def hook(self, name: str, obj):
        return obj if self.plant is None else self.plant(name, obj)

    def peaks(self) -> Dict[str, float]:
        if self.peak_table is not None:
            return self.peak_table
        return device.peaks(self.devices[0].device_kind)

    def trace_dir(self) -> str:
        return tempfile.mkdtemp(prefix="bench_trace_")

    def limits(self) -> Dict[str, float]:
        path = os.path.join(spec.BENCH, "limits", self.cell.name + ".json")
        with open(path) as f:
            return {k: float(v) for k, v in json.load(f)["limits"].items()}


@dataclass
class Outcome:
    metrics: Dict[str, float]           # end-to-end values by name
    attempted: int
    failed: int
    checks: List[Check]
    device: Dict[str, Any]
    window: tuple                       # wall-clock (t0, t1) of the window
    context: Dict[str, Any] = field(default_factory=dict)  # for readers
    reduced: Any = None                 # xtrace.Reduced of a traced run


def result_line(r: Run, out: Outcome) -> Dict[str, Any]:
    units = {m["name"]: m["unit"] for m in
             r.cell.end_to_end + r.cell.per_layer}
    metrics: Dict[str, Any] = {}
    dev = dict(out.device)
    res: Dict[str, Any] = {}
    if r.trace:
        ctx = dict(out.context, reduced=out.reduced, e2e=out.metrics)
        for m in r.cell.per_layer:
            v = spec.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": units[m["name"]]}
        if out.reduced is not None:
            dev["busy_s"] = out.reduced.busy_s
            dev["window_s"] = out.reduced.window_s
            res["breakdown"] = out.reduced.breakdown()
    else:
        for m in r.cell.end_to_end:
            metrics[m["name"]] = {"value": float(out.metrics[m["name"]]),
                                  "unit": units[m["name"]]}
    correct = out.failed == 0 and all(c.ok for c in out.checks)
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": dev}
    line.update(res)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def run_cell(r: Run) -> Dict[str, Any]:
    out = spec.generator(r.cell).run(r)
    t0, t1 = out.window
    log(f"compiles inside the window: {r.compiles.within(t0, t1)}")
    return result_line(r, out)


def enable_cache() -> str:
    """The program's persistent compilation cache, keeping every program
    (the warm-up's small ones too, so a second run compiles nothing)."""
    import jax
    from repro.util.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv: Optional[List[str]], t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="also copy the raw profiler trace to this directory")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    cell = spec.load_cell(args.workload)
    try:
        devices = device.require_chips(cell.chips)
    except (device.NoChip, KeyError) as e:
        log(f"bench: {e}")
        return 3
    log(f"compile cache: {enable_cache()}")
    with device.CompileLog() as compiles:
        r = Run(cell=cell, seed=args.seed, seconds=args.seconds,
                trace=bool(args.trace), devices=devices, t_start=t_start,
                compiles=compiles, keep_trace=args.keep_trace)
        line = run_cell(r)
    for name, c in line["checks"].items():
        ok = math.isfinite(c["value"]) and c["value"] <= c["limit"]
        log(f"check {name} = {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if ok else 'FAIL'}")
    print(json.dumps(line), flush=True)
    return 0


def reduce_trace(r: Run, trace_dir: str, host_spans) -> Any:
    """Reduce the trace in ``trace_dir``, keep a copy if asked, delete it."""
    from harness import xtrace
    path = xtrace.find_xplane(trace_dir)
    if r.keep_trace:
        os.makedirs(r.keep_trace, exist_ok=True)
        shutil.copy(path, os.path.join(
            r.keep_trace, f"{r.cell.name}.{r.seed}.xplane.pb"))
    t = time.time()
    red = xtrace.reduce(path, host_spans)
    log(f"trace reduced in {time.time() - t:.1f}s: window "
        f"{red.window_s:.3f}s busy {red.busy_s:.3f}s")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return red
