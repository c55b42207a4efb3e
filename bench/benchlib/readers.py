"""What a metric reader gets (``RunView``) and the helpers readers share.

A reader is ``bench/metrics/<metric>.py`` with ``read(run) -> float |
None``.  ``None`` means it found nothing to read, and the metric is left
out of the result line.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

from benchlib.peaks import Peaks
from benchlib.serve import Served
from benchlib.tracefile import Trace
from benchlib.traffic import percentile
from benchlib.work import Progress, Work, least_seconds


@dataclasses.dataclass
class RunView:
    served: Served
    work: Work             # the configuration's counts (its architecture)
    peaks: Peaks
    setup_s: float
    trace: Optional[Trace]


def window_s(run: RunView, a: str = "window0", b: str = "window1") -> float:
    return run.served.times[b] - run.served.times[a]


def progress(run: RunView, a: str, b: str) -> List[Progress]:
    """Each request's (prompt length, prefilled and generated at edge a,
    at edge b).  Tokens a prefix match skipped were never prefilled."""
    out = []
    for t in run.served.requests:
        if b not in t.edges:
            continue
        p0, g0 = t.edges.get(a, (0, 0))
        p1, g1 = t.edges[b]
        shared = getattr(t.req, "kv_shared_tokens", 0) or 0
        out.append((len(t.plan.prompt), max(p0, shared), max(p1, shared),
                    g0, g1))
    return out


def counted(run: RunView) -> List[Any]:
    """Requests due in the window."""
    return [t for t in run.served.requests if t.counted]


def tail_ms(values: List[Optional[float]], q: float) -> Optional[float]:
    """The q-th percentile in ms; a missing value (failed or unfinished)
    counts as beyond any limit."""
    if not values:
        return None
    xs = [float("inf") if v is None else v * 1e3 for v in values]
    p = percentile(xs, q)
    return None if p == float("inf") else p


def served_tokens(run: RunView, a: str = "window0",
                  b: str = "window1") -> int:
    return sum(max(g1 - g0, 0) for _pl, _p0, _p1, g0, g1
               in progress(run, a, b))


def ticks(run: RunView, a: str = "window0", b: str = "window1") -> int:
    return run.served.ticks[b] - run.served.ticks[a]


def mfu_percent(run: RunView) -> Optional[float]:
    flops = run.work.served_flops(progress(run, "window0", "window1"))
    if flops == 0:
        return None
    return 100.0 * flops / window_s(run) / run.peaks.bf16_flops


def roofline_percent(run: RunView, flops: int, nbytes: int,
                     module_prefix: str) -> Optional[float]:
    """Least time of the work over the device time of the Pallas kernels
    in the named programs, as a percentage."""
    if run.trace is None:
        return None
    spent = run.trace.kernel_s(module_prefix)
    if spent <= 0.0 or (flops == 0 and nbytes == 0):
        return None
    least = least_seconds(flops, nbytes, run.peaks.bf16_flops,
                          run.peaks.hbm_bytes_per_s)
    return 100.0 * least / spent


def idle_percent(run: RunView) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)


def trace_progress(run: RunView) -> List[Progress]:
    return progress(run, "trace0", "trace1")
