"""Reduction of a profiler trace to the numbers the per-layer metrics
read: device busy time and idle share, device time per program, time in
collectives, and the breakdown of the longest device operations and idle
gaps.

A trace is first turned into a neutral form, a list of planes, each with
lines of events `[name, start_ns, duration_ns]`, so that the reduction can
be checked on a small recorded trace kept with the tests. Device planes
are those named `/device:TPU:<n>`; their `XLA Ops` line holds one event per
operation and their `XLA Modules` line one event per program run. The host
plane's events are the spans the benchmark writes around its calls
(`bench.*`) and the runtime's own, which name what the host was doing in
each idle gap.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]            # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced_window"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"allgather|allreduce|reducescatter", re.I)


def from_xspace(path: str) -> List[Dict]:
    """Neutral form of one `.xplane.pb` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        lines = [{"name": ln.name,
                  "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in ln.events]}
                 for ln in pl.lines]
        planes.append({"name": pl.name, "lines": lines})
    return planes


def find_xspace(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def device_planes(planes: Sequence[Dict]) -> Dict[int, Dict]:
    out = {}
    for pl in planes:
        m = DEVICE_PLANE.match(pl["name"])
        if m:
            out[int(m.group(1))] = pl
    return out


def line_events(plane: Dict, name: str) -> List[Event]:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return [tuple(e) for e in ln["events"]]
    return []


def host_events(planes: Sequence[Dict]) -> List[Event]:
    out: List[Event] = []
    for pl in planes:
        if pl["name"].startswith("/host:"):
            for ln in pl["lines"]:
                out.extend(tuple(e) for e in ln["events"])
    return out


def window(planes: Sequence[Dict]) -> Tuple[float, float]:
    """[start, end] ns of the benchmark's traced-window span."""
    spans = [e for e in host_events(planes) if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    s = max(spans, key=lambda e: e[2])
    return s[1], s[1] + s[2]


def clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to [t0, t1]; those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end] intervals covered by any event."""
    iv = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[List[float]] = []
    for a, b in iv:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in union(events))


def gaps(intervals: Sequence[Tuple[float, float]], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """Idle [start, end] stretches of [t0, t1] between busy intervals."""
    out, cur = [], t0
    for a, b in intervals:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        out.append((cur, t1))
    return out


def program_name(module_event: str) -> str:
    """`jit__decode_masked(12)` -> `_decode_masked`."""
    name = re.sub(r"\(\d+\)$", "", module_event)
    name = re.sub(r"\.\d+$", "", name)
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """`%fusion.3 = f32[...] fusion(...)` -> `fusion.3`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """{op: seconds} of time not covered by ops nested inside it (a loop
    op's interval holds the ops of its body)."""
    out: Dict[str, float] = {}
    stack: List[List] = []                  # [end, name, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + own * 1e-9

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(d, stack[-1][0] - s)
        stack.append([s + d, op_name(name), d])
    close(float("inf"))
    return out


def per_program(events: Sequence[Event]) -> Dict[str, Tuple[int, float]]:
    """{program: (runs, device seconds)} from module events."""
    out: Dict[str, List[float]] = {}
    for name, _, d in events:
        agg = out.setdefault(program_name(name), [0, 0.0])
        agg[0] += 1
        agg[1] += d * 1e-9
    return {k: (int(v[0]), v[1]) for k, v in out.items()}


def _host_doing(host: Sequence[Event], t: float, longest: float) -> str:
    """Name of the shortest host event covering t (spans longer than the
    traced window itself say nothing)."""
    best: Optional[Event] = None
    for e in host:
        if e[0] == WINDOW_SPAN or e[2] >= longest:
            continue
        if e[1] <= t <= e[1] + e[2] and (best is None or e[2] < best[2]):
            best = e
    return best[0] if best else "(no host span)"


def reduce(planes: Sequence[Dict], chips: int, top: int = 10) -> Dict:
    """Everything the per-layer metrics read from one trace."""
    t0, t1 = window(planes)
    devs = device_planes(planes)
    if not devs:
        raise ValueError("trace has no TPU device plane")
    used = sorted(devs)[:chips]
    busy = [busy_ns(clip(line_events(devs[i], OPS_LINE), t0, t1)) * 1e-9
            for i in used]
    dev0 = devs[used[0]]
    ops0 = clip(line_events(dev0, OPS_LINE), t0, t1)
    coll = [e for e in ops0 if COLLECTIVE.search(op_name(e[0]))]
    kernels: Dict[str, List[float]] = {}
    for name, _, d in ops0:
        k = kernels.setdefault(op_name(name).split(".")[0], [0, 0.0])
        k[0] += 1
        k[1] += d * 1e-9
    ops_total = self_times(ops0)
    mods0 = [e for e in line_events(dev0, MODULES_LINE)
             if e[1] >= t0 and e[1] + e[2] <= t1]
    host = clip(host_events(planes), t0, t1)
    idle = sorted(gaps(union(ops0), t0, t1), key=lambda g: g[0] - g[1])
    win_ns = t1 - t0
    return {
        "window_s": win_ns * 1e-9,
        "busy_s": sum(busy) / len(busy),
        "programs": per_program(mods0),
        "collective_s": busy_ns(coll) * 1e-9,
        "op_kinds": {k: (int(v[0]), v[1]) for k, v in kernels.items()},
        "device_ops": sorted(ops_total.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_host_doing(host, (a + b) / 2, win_ns), (b - a) * 1e-9]
                      for a, b in idle[:top]],
    }
