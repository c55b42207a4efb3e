"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-kernel
device time and idle gaps, over the window that the benchmark marked with
a host span.

Planes ``/device:TPU:<n>`` hold the device: line ``XLA Modules`` has one
event per program run (``jit_<function>(<hash>)``) and line ``XLA Ops``
one per operation, nested ones included.  A Pallas kernel is an op whose
HLO text has ``custom_call_target="tpu_custom_call"``; it is told apart
by the program (module) it runs in.  Plane ``/host:CPU`` holds host
spans on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_MARK = "bench.trace_window"
_MODULE_HASH = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Op:
    module: str        # program name without its hash, e.g. jit__decode_paged_fn
    text: str          # the op's HLO text
    start: float       # ns
    end: float         # ns

    @property
    def name(self) -> str:
        return self.text.split(" = ", 1)[0].lstrip("%")

    @property
    def kernel(self) -> bool:
        return 'custom_call_target="tpu_custom_call"' in self.text

    @property
    def control_flow(self) -> bool:
        return " while(" in self.text or " conditional(" in self.text


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]             # ns, on the trace's clock
    devices: List[List[Op]]                 # ops per device, clipped
    host: List[Tuple[str, float, float]]    # host spans (name, start, end)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Union of op intervals, averaged over devices that ran ops."""
        used = [ops for ops in self.devices if ops]
        if not used:
            return 0.0
        return sum(_union_length(ops) for ops in used) / len(used) * 1e-9

    def kernel_s(self, module_prefix: str) -> float:
        """Device seconds of Pallas kernels in programs whose name starts
        with ``module_prefix``, summed over devices."""
        return sum(o.end - o.start for ops in self.devices for o in ops
                   if o.kernel and o.module.startswith(module_prefix)) * 1e-9

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` leaf ops that took the most device time."""
        tot: Dict[str, float] = {}
        for ops in self.devices:
            for o in ops:
                if not o.control_flow:
                    key = f"{o.module}/{o.name}"
                    tot[key] = tot.get(key, 0.0) + (o.end - o.start)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest device idle gaps of the first device, each
        named by the host span that overlapped it most."""
        ops = next((o for o in self.devices if o), [])
        gaps = []
        t = self.window[0]
        for s, e in _merged(ops):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if self.window[1] > t:
            gaps.append((t, self.window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._host_at(s, e), (e - s) * 1e-9] for s, e in gaps[:n]]

    def _host_at(self, s: float, e: float) -> str:
        best, most = "no host span", 0.0
        for name, hs, he in self.host:
            ov = min(e, he) - max(s, hs)
            if ov > most:
                best, most = name, ov
        return f"host: {best}"


def _merged(ops: Sequence[Op]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for o in sorted(ops, key=lambda o: o.start):
        if out and o.start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], o.end))
        else:
            out.append((o.start, o.end))
    return out


def _union_length(ops: Sequence[Op]) -> float:
    return sum(e - s for s, e in _merged(ops))


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {files}")
    return files[0]


def reduce_xplane(path: str, mark: str = WINDOW_MARK,
                  window: Optional[Tuple[float, float]] = None) -> Trace:
    """Read ``path``; the window is the host span named ``mark`` (or
    ``window`` in ns, when given)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host: List[Tuple[str, float, float]] = []
    marks = []
    raw_devices = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns
                    e = s + ev.duration_ns
                    if ev.name == mark:
                        marks.append((s, e))
                    elif not ev.name.startswith("$"):   # python tracer
                        host.append((ev.name, s, e))
        elif plane.name.startswith("/device:TPU:"):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           _MODULE_HASH.sub("", ev.name))
                          for ev in lines.get("XLA Modules", []))
            raw_devices.append((mods, lines.get("XLA Ops", [])))
    if window is None:
        if len(marks) != 1:
            raise ValueError(f"expected one {mark!r} span, found "
                             f"{len(marks)}")
        window = marks[0]
    w0, w1 = window
    devices = []
    for mods, evs in raw_devices:
        ops = []
        mi = 0
        for ev in sorted(evs, key=lambda ev: ev.start_ns):
            s = ev.start_ns
            e = s + ev.duration_ns
            if e <= w0 or s >= w1:
                continue
            while mi < len(mods) and mods[mi][1] < s:
                mi += 1
            module = mods[mi][2] if mi < len(mods) and mods[mi][0] <= s \
                else "?"
            ops.append(Op(module, ev.name, max(s, w0), min(e, w1)))
        devices.append(ops)
    host = [(n, max(s, w0), min(e, w1)) for n, s, e in host
            if e > w0 and s < w1]
    return Trace((w0, w1), devices, host)
