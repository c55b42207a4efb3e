"""What the engine's own spans and per-token times say about a run.

The engine marks each phase of its loop with a flat host span
(``engine.wait``, ``engine.admit``, ``engine.prefill``, ``engine.pages``,
``engine.decode``, ``engine.sync``, ``engine.finish``; ``engine.submit`` on
the caller's thread), recorded by the profiler on the device ops' clock.
Device idle is the complement, inside the traced window, of the merged
``XLA Ops`` intervals of the first device that ran ops (the device and
union ``Trace.idle_gaps`` uses).  That idle is split into the part under
``engine.sync`` (the host waits on a device-to-host read) and the part
under any other ``engine.*`` span (host work); overlapping spans count
once, and idle under no engine span is left to neither.

A program without these spans or times gives ``None``: nothing to read.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from benchlib.readers import RunView, ticks
from benchlib.tracefile import Trace, _merged
from benchlib.traffic import percentile

ENGINE = "engine."
SYNC = "engine.sync"

Interval = Tuple[float, float]


def _union(spans: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(trace: Trace) -> Optional[List[Interval]]:
    """The first op-running device's idle intervals inside the window, in
    ns; ``None`` when no device ran an op there."""
    ops = next((o for o in trace.devices if o), None)
    if ops is None:
        return None
    idle = []
    t = trace.window[0]
    for s, e in _merged(ops):
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if trace.window[1] > t:
        idle.append((t, trace.window[1]))
    return idle


def idle_split_s(trace: Optional[Trace]) -> Optional[Dict[str, float]]:
    """Seconds of device idle in the window: ``idle`` in all, ``sync``
    under ``engine.sync`` spans, ``host`` under other ``engine.*`` spans
    and not under ``engine.sync``.  ``None`` without device ops or engine
    spans in the window."""
    if trace is None:
        return None
    idle = idle_intervals(trace)
    engine = [(s, e) for name, s, e in trace.host if name.startswith(ENGINE)]
    if idle is None or not engine:
        return None
    sync = _overlap(idle, _union([(s, e) for name, s, e in trace.host
                                  if name == SYNC]))
    under_any = _overlap(idle, _union(engine))
    return {"idle": sum(e - s for s, e in idle) * 1e-9,
            "sync": sync * 1e-9, "host": (under_any - sync) * 1e-9}


def idle_ms_per_tick(run: RunView, part: str) -> Optional[float]:
    """``idle_split_s(...)[part]`` in ms per engine tick in the trace."""
    split = idle_split_s(run.trace)
    n = ticks(run, "trace0", "trace1")
    if split is None or n <= 0:
        return None
    return 1e3 * split[part] / n


def token_gaps_s(run: RunView, a: str = "window0",
                 b: str = "window1") -> Optional[List[float]]:
    """Every request's gaps between consecutive ``token_times`` whose later
    token was committed between edges ``a`` and ``b``; ``None`` when no
    request carries token times."""
    t0, t1 = run.served.times[a], run.served.times[b]
    gaps: List[float] = []
    seen = False
    for t in run.served.requests:
        times = getattr(t.req, "token_times", None)
        if times is None:
            continue
        seen = True
        gaps += [y - x for x, y in zip(times, times[1:]) if t0 <= y <= t1]
    return gaps if seen else None


def token_gap_ms(run: RunView, q: float) -> Optional[float]:
    gaps = token_gaps_s(run)
    return 1e3 * percentile(gaps, q) if gaps else None
