"""Run one benchmark cell once, on the chip it was started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: read the cell's configuration and traffic files by name
(``BENCHMARK.json``), take the model from the architecture module the
configuration names, build its ``EdgeSystem`` with weights made on the
device from the seed, warm its programs (from the persistent compile cache
where it holds them), offer the traffic for the window, check what was
served against the configuration's plain reference, and print one JSON
line.  With ``--trace 1`` a sub-window is traced and the line carries
the cell's per-layer metrics; with ``--trace 0`` its end-to-end metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits
nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for p in (str(ROOT / "src"), str(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import check as chk  # noqa: E402
from benchlib import serve, spec  # noqa: E402
from benchlib.peaks import Peaks, peaks_for  # noqa: E402
from benchlib.readers import RunView  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    pass


def _note(t_start: float, msg: str) -> None:
    print(f"[{time.monotonic() - t_start:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program however
    fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _attempted_failed(served: serve.Served, loop: str) -> Tuple[int, int]:
    t0, t1 = served.times["window0"], served.times["window1"]
    if loop == "open":
        due = [t for t in served.requests if t.counted]
    else:   # every request in flight at some time of the window
        due = [t for t in served.requests
               if t.submitted is not None and t.submitted < t1
               and (t.req is None or t.req.finished_at is None
                    or t.req.finished_at >= t0)]
    # open loop: a request due in the window that failed or never gave
    # its first token is missing
    failed = sum(1 for t in due if (t.req is not None and t.req.error)
                 or (loop == "open" and (t.req is None
                                         or t.req.first_token_at is None)))
    return len(due), failed


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START, require_tpu: bool = True,
             capacity=None, peaks: Optional[Peaks] = None,
             use_cache: bool = True,
             control: bool = False) -> Tuple[Dict[str, Any], List[str]]:
    """One run of ``cell``; returns the result line and the check lines.
    With ``control`` the check reads the lower-precision control's tokens
    in place of the served ones (a test of the check, never a run of the
    benchmark)."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU, found {dev.platform!r}")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, found "
                     f"{len(devices)}")
    peaks = peaks or peaks_for(dev.device_kind)
    if use_cache:
        print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    counter = serve.CompileCounter()
    conf, traffic = cell.config, cell.traffic
    arch = spec.load_architecture(conf, cell.config_dir)
    cfg = arch.model_config(conf)

    from repro.models.model import build_model
    from benchlib.weights import make_weights

    _note(t_start, f"{cell.name} on {dev.device_kind} x{len(devices)}")
    weights = make_weights(build_model(cfg).init, cfg.d_model, seed,
                           dtype=cfg.pdtype, arch_rule=arch.weight_rule)
    jax.block_until_ready(weights)
    _note(t_start, "weights made")
    system, engine = serve.build_system(cfg, conf, weights,
                                        capacity=capacity)
    engine.warmup()
    warm = (counter.events, counter.hits)
    _note(t_start, f"warm: {warm[0]} programs built, {warm[1]} of them "
          f"from the persistent cache")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        served = serve.drive(engine, traffic, seed, seconds,
                             cfg.vocab_size, counter, trace_dir=trace_dir)
        setup_s = served.times["window0"] - t_start
        _note(t_start, f"window closed; {len(served.requests)} requests "
              f"offered")
        mem = [d.memory_stats() or {} for d in devices[:cell.chips]]
        peak_bytes = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
        # the program's state goes before the reference runs
        for leaf in jax.tree.leaves(engine.kv.pools):
            leaf.delete()
        finished = [r for r in engine.completed.values() if r.generated]
        del system, engine
        gc.collect()

        checks: Dict[str, Dict[str, float]] = {}
        ref = spec.load_reference(conf, cell.config_dir)
        sample = chk.sample(finished, seed, int(traffic["check"]["requests"]),
                            int(traffic["check"]["tokens"]))
        pad = int(conf["serving"]["max_seq"])
        if control:
            sample = chk.as_control(ref, weights, conf, sample, pad)
        gaps = chk.logit_gaps(ref, weights, conf, sample, pad)
        checks["logit_gap"] = {
            "value": float(gaps.max()) if gaps.size else 0.0,
            "limit": float(conf["check"]["logit_gap_limit"])}
        checks["tokens_checked"] = {"value": int(gaps.size),
                                    "limit": int(traffic["check"]["min_tokens"])}
        attempted, failed = _attempted_failed(served, traffic["loop"])
        checks["missing"] = {"value": failed, "limit": 0}
        correct = (checks["logit_gap"]["value"]
                   <= checks["logit_gap"]["limit"]
                   and gaps.size >= checks["tokens_checked"]["limit"]
                   and failed == 0)

        _note(t_start, "reference check done")
        tr = None
        if trace_dir is not None:
            from benchlib.tracefile import find_xplane, reduce_xplane
            tr = reduce_xplane(find_xplane(trace_dir))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    view = RunView(served, arch.work(conf), peaks, setup_s, tr)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(view)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": attempted, "failed": failed,
                              "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    c0, c1 = served.compiles["window0"], served.compiles["window1"]
    notes = [f"compiles: {warm[0]} in set-up ({warm[1]} from the persistent "
             f"cache), {c1[0] - c0[0]} inside the window "
             f"({c1[1] - c0[1]} from the cache)"]
    result["compiles_in_window"] = c1[0] - c0[0]
    result["checks"] = checks
    lines = notes + [f"check {k}: {c['value']} (limit {c['limit']})"
                     for k, c in checks.items()]
    return result, lines


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        result, lines = run_cell(cell, args.seed, args.seconds,
                                 bool(args.trace))
    except NoChip as e:
        print(f"bench/run.py: {e}; nothing was measured", file=sys.stderr)
        return 2
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
