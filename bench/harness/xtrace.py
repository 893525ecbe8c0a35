"""Profiler trace (``.xplane.pb``) → device busy time, per-program device
time, top device operations, and idle gaps named by the host span open.

The traced stretch is marked by a host annotation (``WINDOW``) that the
generators open around it; everything is clipped to that window. Device planes
are ``/device:TPU:<n>`` (other ``/device:`` planes, such as
``/device:CUSTOM:Megascale Trace``, are not chips). Their ``XLA Modules``
line holds one event per run of a jitted program (``jit_<function>(<id>)``):
the union of those intervals is the busy time. The ``XLA Ops`` line names
the operations inside them; its events do not cover every busy instant (on a
TPU v5e they covered 1.8 s of a step program's 3.0 s), so it ranks ops and
does not measure busy time. Host annotations (``repro.obs`` spans in trace
mode, and the generators' own ``bench.*`` spans) live on the host plane's
thread lines, on the same clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over device planes
    devices: int
    programs: Dict[str, Tuple[float, int]]   # module → (device s, runs)
    ops: Dict[str, float]               # op name → device s
    gaps: Dict[str, float]              # host span name → idle s
    spans: Dict[str, List[float]] = field(default_factory=dict)  # name → durs

    def program_seconds(self, names: Iterable[str]) -> Tuple[float, int]:
        """Device seconds and runs of the programs whose jitted function is
        one of ``names`` (module name ``jit_<name>``)."""
        want = {"jit_" + n for n in names}
        s = sum(v[0] for k, v in self.programs.items() if k in want)
        n = sum(v[1] for k, v in self.programs.items() if k in want)
        return s, n

    def breakdown(self, top: int = 10) -> Dict[str, List[list]]:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


def _is_device(name: str) -> bool:
    return bool(_DEVICE.match(name))


def _op_name(name: str) -> str:
    """An op event's name is its HLO instruction; keep ``%name = type op``
    up to the operands, which is what tells two ops apart."""
    head = name.split("(", 1)[0].strip()
    return head if len(head) <= 120 else head[:120]


def _name_gap(host, starts, longest, gs, ge, rank) -> Dict[str, float]:
    """Split the idle gap [gs, ge) at host span edges and give each piece
    to the open span that ranks first in the generator's list (its most
    specific span); ``none`` where no listed span is open."""
    lo = bisect.bisect_left(starts, gs - longest)
    hi = bisect.bisect_left(starts, ge)
    open_ = [(max(s, gs), min(e, ge), n) for s, e, n in host[lo:hi]
             if min(e, ge) > max(s, gs)]
    cuts = sorted({gs, ge} | {x for s, e, _ in open_ for x in (s, e)})
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        names = [n for s, e, n in open_ if s <= a and b <= e]
        name = min(names, key=rank.__getitem__) if names else "none"
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def reduce(path: str, host_spans: Iterable[str] = ()) -> Reduced:
    """Reduce one trace file. ``host_spans`` are the annotation names that
    may name an idle gap, most specific first, and whose durations are
    collected."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    names = list(host_spans)
    rank = {n: i for i, n in enumerate(names)}
    win: Optional[Tuple[float, float]] = None
    host: List[Tuple[float, float, str]] = []
    device_planes = []
    for plane in pd.planes:
        if _is_device(plane.name):
            device_planes.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    win = (s, e) if win is None else (min(win[0], s),
                                                      max(win[1], e))
                elif ev.name in rank:
                    host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
    if win is None:
        raise ValueError(f"trace {path} has no {WINDOW!r} annotation")
    if not device_planes:
        raise ValueError(f"trace {path} has no device plane")
    lo, hi = win
    spans: Dict[str, List[float]] = {}
    for s, e, n in host:
        if lo <= s and e <= hi:
            spans.setdefault(n, []).append((e - s) / 1e9)

    host.sort()
    starts = [h[0] for h in host]
    longest = max((e - s for s, e, _ in host), default=0.0)
    programs: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    busy_total = 0.0
    gap_names: Dict[str, float] = {}
    for plane in device_planes:
        lines = {line.name: line for line in plane.lines}
        busy: List[Tuple[float, float]] = []
        if "XLA Ops" in lines:
            for ev in lines["XLA Ops"].events:
                c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if c is not None:
                    name = _op_name(ev.name)
                    ops[name] = ops.get(name, 0.0) + (c[1] - c[0]) / 1e9
        if "XLA Modules" in lines:
            for ev in lines["XLA Modules"].events:
                c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if c is None:
                    continue
                busy.append(c)
                key = _MODULE_ID.sub("", ev.name)
                acc = programs.setdefault(key, [0.0, 0])
                acc[0] += (c[1] - c[0]) / 1e9
                acc[1] += 1
        merged = _union(busy)
        busy_total += sum(e - s for s, e in merged) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                for name, sec in _name_gap(host, starts, longest, gs, ge,
                                           rank).items():
                    gap_names[name] = gap_names.get(name, 0.0) + sec
    k = len(device_planes)
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy_total / k,
                   devices=k,
                   programs={n: (v[0] / k, v[1] // k)
                             for n, v in programs.items()},
                   ops={n: v / k for n, v in ops.items()},
                   gaps={n: v / k for n, v in gap_names.items()},
                   spans=spans)
