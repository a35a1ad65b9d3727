"""The program's own host spans in a reduced trace: the `serve.*` spans of
the serving engine and the `kernel.*` spans of the kernel dispatch, which
the program writes through `jax.profiler.TraceAnnotation` on the
profiler's clock.

Reads the neutral form of `trace_reduce` (planes of lines of events
`[name, start_ns, duration_ns]`). A trace of a program that writes no such
spans gives no spans here, and the readers built on them report nothing.

A reader's `ctx` (`run_cell.Context`) carries the reduced trace but not
its neutral form; `planes_of` finds that on the driver of the run that
made `ctx`.
"""

from __future__ import annotations

import bisect
import heapq
import sys
from typing import Dict, List, Optional, Sequence, Tuple, Union

from benchmarks.chip import trace_reduce as tr

OUTSIDE = "(outside the program)"
PROGRAM = ("serve.", "kernel.")     # the prefixes of the program's spans

Prefixes = Union[str, Tuple[str, ...]]


def planes_of(ctx) -> Optional[List[Dict]]:
    """The traced stretch in neutral form of the run `ctx` was made for,
    None untraced: `ctx.planes` where the Context has it, else the
    `trace_planes` of the driver `drv` in the frame of `run_cell.run`
    that holds this very `ctx` (the caller of the reader)."""
    planes = getattr(ctx, "planes", None)
    if planes is not None:
        return planes
    frame = sys._getframe(1)
    while frame is not None:
        loc = frame.f_locals
        if loc.get("ctx") is ctx and "drv" in loc:
            return getattr(loc["drv"], "trace_planes", None)
        frame = frame.f_back
    return None


def program_spans(planes: Sequence[Dict], prefix: Prefixes) -> List[tr.Event]:
    """[name, start_ns, duration_ns] of the host spans whose names start
    with `prefix` (a string or a tuple of them), clipped to the traced
    window and sorted by start."""
    prefix = (prefix,) if isinstance(prefix, str) else tuple(prefix)
    t0, t1 = tr.window(planes)
    spans = [e for e in tr.host_events(planes) if e[0].startswith(prefix)]
    return sorted(tr.clip(spans, t0, t1), key=lambda e: (e[1], -e[2]))


def whole_spans(planes: Sequence[Dict], name: str) -> List[tr.Event]:
    """The spans called `name` that lie wholly inside the traced window
    (a span cut by the window's edge starts or ends on it)."""
    t0, t1 = tr.window(planes)
    return [e for e in program_spans(planes, name)
            if e[0] == name and t0 < e[1] and e[1] + e[2] < t1]


def innermost(spans: Sequence[tr.Event]) -> List[Tuple[float, float, str]]:
    """[start, end, name] stretches of time, each labelled with the
    innermost span open in it (the one that opened last; spans on one
    thread nest). Stretches inside no span are left out."""
    spans = sorted(spans, key=lambda e: (e[1], -e[2]))
    points = sorted({p for _, s, d in spans for p in (s, s + d)})
    out: List[Tuple[float, float, str]] = []
    heap: List[Tuple[float, float, int]] = []   # (-start, duration, index)
    nxt = 0
    for a, b in zip(points, points[1:]):
        while nxt < len(spans) and spans[nxt][1] <= a:
            heapq.heappush(heap, (-spans[nxt][1], spans[nxt][2], nxt))
            nxt += 1
        # the latest-opened span still open; closed ones leave as they
        # surface
        while heap and -heap[0][0] + heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            out.append((a, b, spans[heap[0][2]][0]))
    return out


def idle_by_span(planes: Sequence[Dict], prefixes: Prefixes,
                 chips: int) -> Dict[str, float]:
    """Seconds of device idle time in the traced window, grouped by the
    innermost program span (names starting with `prefixes`) open on the
    host at each idle instant; idle time under no such span goes to
    `OUTSIDE`. Averaged over the first `chips` devices, as the reducer's
    busy time is, so the parts sum to the window less its busy time."""
    t0, t1 = tr.window(planes)
    labelled = innermost(program_spans(planes, prefixes))
    devs = tr.device_planes(planes)
    used = sorted(devs)[:chips]
    out: Dict[str, float] = {}
    for d in used:
        ops = tr.clip(tr.line_events(devs[d], tr.OPS_LINE), t0, t1)
        for a, b in tr.gaps(tr.union(ops), t0, t1):
            for name, secs in _split(a, b, labelled):
                out[name] = out.get(name, 0.0) + secs / len(used)
    return out


def _split(a: float, b: float, labelled: Sequence[Tuple[float, float, str]]
           ) -> List[Tuple[str, float]]:
    """[(name, seconds)] of the idle stretch [a, b] under each label, the
    rest under `OUTSIDE`."""
    parts, covered = [], 0.0
    i = max(0, bisect.bisect_right(labelled, (a, float("inf"), "")) - 1)
    while i < len(labelled) and labelled[i][0] < b:
        s, e, name = labelled[i]
        ov = min(b, e) - max(a, s)
        if ov > 0:
            parts.append((name, ov * 1e-9))
            covered += ov
        i += 1
    if b - a - covered > 0:
        parts.append((OUTSIDE, (b - a - covered) * 1e-9))
    return parts
