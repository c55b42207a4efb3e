"""Drive one cell's served path: build the configuration's ``EdgeSystem``,
warm its programs, offer the traffic mix, and record what the window saw.

LLM requests go through the container-class ``ServingEngine`` that
``EdgeSystem.apply`` deploys, with the engine's background loop running.
The engine keeps its own defaults for everything but the cell's sizes
(slots, ``max_seq``, pages).
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchlib import traffic as tr

class CompileCounter:
    """Counts backend compiles through ``jax.monitoring`` (a persistent
    cache hit is reported as a compile event too, and counted apart)."""

    def __init__(self):
        import jax

        self.events = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# tokens an unfinished request must have produced before its time per
# token is read (the poll sees each new token within 10 ms, so the read is
# off by at most 10 ms over TPOT_TOKENS - 1 tokens)
TPOT_TOKENS = 16


@dataclasses.dataclass
class Tracked:
    """One request as the benchmark saw it."""
    plan: tr.Planned
    due: float                     # monotonic time it was due
    counted: bool                  # due inside the window (open loop)
    rid: Optional[int] = None
    handle: Any = None
    submitted: Optional[float] = None
    edges: Dict[str, Tuple[int, int]] = dataclasses.field(
        default_factory=dict)      # edge -> (prompt pos, generated)
    req: Any = None                # the engine's Request, at the end
    # (tokens generated, when that count was first seen, seen to change)
    seen: Optional[Tuple[int, float, bool]] = None


@dataclasses.dataclass
class Served:
    """Everything the metric readers and the check read."""
    requests: List[Tracked]
    times: Dict[str, float]        # edge -> monotonic time
    ticks: Dict[str, int]          # edge -> engine.ticks
    chunk_tokens: int
    compiles: Dict[str, Tuple[int, int]]   # edge -> (compiles, cache hits)


def build_system(cfg, conf: Dict[str, Any], weights, capacity=None):
    """The configuration's ``EdgeSystem``: the LLM on a container-class
    engine."""
    from repro.core import (EdgeSystem, ExecutorClass, ServiceSpec, Workload,
                            WorkloadClass, WorkloadKind)
    from repro.serving.router import make_engine_builder

    sizes = conf["serving"]
    engine_kw = {}
    if sizes.get("num_pages") is not None:
        engine_kw["num_pages"] = int(sizes["num_pages"])
    system = EdgeSystem()
    system.add_node("edge0", capacity)
    system.register_builder(
        "decode", WorkloadClass.HEAVY,
        make_engine_builder(cfg, max_slots=int(sizes["max_slots"]),
                            max_seq=int(sizes["max_seq"]), params=weights,
                            **engine_kw))
    (llm,) = system.apply(ServiceSpec(
        name="llm-serving",
        workload=Workload("serve", WorkloadKind.DECODE, cfg,
                          batch=int(sizes["max_slots"]), seq_len=1),
        executor_class=ExecutorClass.CONTAINER, tenant="serving"))
    return system, llm.executor.engine


def _progress(engine, rid: int) -> Tuple[int, int]:
    """(prompt tokens prefilled, tokens generated) of request ``rid``,
    read from the engine's public request tables without its lock."""
    for _ in range(2000):
        for table in (engine.active, engine.completed, engine.failed):
            r = table.get(rid)
            if r is not None:
                return r.pos, len(r.generated)
        if any(q.rid == rid for q in list(engine.queue)):
            return 0, 0
        time.sleep(0.0005)          # between two tables: look again
    raise RuntimeError(f"request {rid} is in no table of the engine")


def _snapshot(engine, served: Served, edge: str,
              counter: CompileCounter) -> None:
    served.times[edge] = time.monotonic()
    served.ticks[edge] = engine.ticks
    served.compiles[edge] = (counter.events, counter.hits)
    for t in list(served.requests):
        t.edges[edge] = (0, 0) if t.rid is None \
            else _progress(engine, t.rid)


def _submit(engine, t: Tracked) -> None:
    t.submitted = time.monotonic()
    t.handle = engine.submit(t.plan.prompt, max_new_tokens=t.plan.max_new)
    t.rid = t.handle.rid


def _open_loop(engine, items: List[Tracked],
               stop: threading.Event) -> None:
    for t in items:
        wait = t.due - time.monotonic()
        if wait > 0 and stop.wait(wait):
            return
        if stop.is_set():
            return
        _submit(engine, t)


def _closed_loop(engine, served: Served, source, outstanding: int,
                 stop: threading.Event) -> None:
    done: "queue.Queue[int]" = queue.Queue()

    def one():
        t = Tracked(next(source), time.monotonic(), False)
        served.requests.append(t)
        _submit(engine, t)
        t.handle.future.add_done_callback(lambda _f: done.put(1))

    for _ in range(outstanding):
        one()
    while not stop.is_set():
        try:
            done.get(timeout=0.05)
        except queue.Empty:
            continue
        if not stop.is_set():
            one()


def drive(engine, traffic: Dict[str, Any], seed: int,
          seconds: float, vocab: int, counter: CompileCounter,
          trace_dir: Optional[str] = None,
          wait_s: float = 60.0) -> Served:
    """Offer the traffic: a ramp, the measured window of ``seconds``, and
    the wait (at most ``wait_s``) until every request due in the window has
    finished or shown ``TPOT_TOKENS`` tokens, with the load kept on until
    then.  With ``trace_dir`` a sub-window is traced."""
    import jax

    served = Served([], {}, {}, engine.chunk_tokens, {})
    stop = threading.Event()
    ramp = float(traffic.get("ramp_s", 0.0))
    engine.start()
    t_load = time.monotonic() + 0.05
    t0 = t_load + ramp
    if traffic["loop"] == "open":
        # enough cycles to cover the ramp and the wait after the close
        span = tr.cycle_span(traffic)
        first = -int(np.ceil(ramp / span))
        last = int(np.ceil((seconds + wait_s) / span))
        for p in tr.open_schedule(traffic, seed, vocab, first,
                                  last - first):
            due = t0 + p.due_s
            if due >= t_load:
                served.requests.append(
                    Tracked(p, due, 0.0 <= p.due_s < seconds))
        load = threading.Thread(
            target=_open_loop, args=(engine, list(served.requests), stop),
            name="bench-open-loop", daemon=True)
    else:
        outstanding = int(traffic["outstanding_per_slot"]) * engine.max_slots
        load = threading.Thread(
            target=_closed_loop,
            args=(engine, served, tr.closed_stream(traffic, seed, vocab),
                  outstanding, stop),
            name="bench-closed-loop", daemon=True)
    load.start()
    try:
        time.sleep(max(0.0, t0 - time.monotonic()))
        _snapshot(engine, served, "window0", counter)
        # a program compiled or loaded from the cache inside the window is
        # named on stderr
        jax.config.update("jax_log_compiles", True)
        if trace_dir is not None:
            tspec = traffic["trace"]
            time.sleep(float(tspec["offset_s"]))
            jax.profiler.start_trace(trace_dir)
            try:
                with jax.profiler.TraceAnnotation("bench.trace_window"):
                    _snapshot(engine, served, "trace0", counter)
                    time.sleep(float(tspec["seconds"]))
                    _snapshot(engine, served, "trace1", counter)
            finally:
                jax.profiler.stop_trace()
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        _snapshot(engine, served, "window1", counter)
        jax.config.update("jax_log_compiles", False)
        # the load stays on until every request due in the window has
        # finished or been seen to produce its TPOT_TOKENS-th token or a
        # later one (so its time per token is read over that many)
        deadline = time.monotonic() + wait_s
        while time.monotonic() < deadline:
            now = time.monotonic()
            pending = False
            for t in served.requests:
                if not t.counted:
                    continue
                if t.handle is None:
                    pending = True
                elif not t.handle.done():
                    n = _progress(engine, t.rid)[1]
                    if t.seen is not None and n > t.seen[0]:
                        t.seen = (n, now, True)
                    elif t.seen is None:
                        t.seen = (n, now, False)
                    pending |= n < TPOT_TOKENS or not t.seen[2]
            if not pending:
                break
            time.sleep(0.01)
    finally:
        stop.set()
        load.join(timeout=60.0)
        engine.stop(drain=False, timeout=60.0)
    for t in served.requests:
        if t.rid is not None:
            t.req = (engine.completed.get(t.rid) or engine.failed.get(t.rid)
                     or engine.active.get(t.rid))
    return served
