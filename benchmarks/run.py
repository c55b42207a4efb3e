"""Benchmark driver — one function per paper figure/table.

Prints ``name,us_per_call,derived`` CSV and persists the same results as
machine-readable ``BENCH_benchmarks.json`` (name → us_per_call + parsed
derived metrics) so CI and later PRs can diff the perf trajectory
without re-scraping stdout.  Roofline tables (the scale-side "figures")
are produced from the dry-run artifacts by ``benchmarks/roofline_table.py``
since they derive from compiled programs, not wall time.
"""
from __future__ import annotations

import json
import os
import sys
import traceback

BENCH_JSON = "BENCH_benchmarks.json"


def _parse_derived(derived: str) -> dict:
    """``k=v;k=v`` pairs → dict, numbers coerced; free-form text kept raw."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            if part:
                out.setdefault("notes", []).append(part)
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v) if "." in v or "e" in v.lower() \
                else int(v)
        except ValueError:
            out[k] = v
    return out


def rows_to_json(rows: list) -> dict:
    results = {}
    for row in rows:
        name, us, derived = row.split(",", 2)
        try:
            us_val = float(us)
        except ValueError:
            us_val = None
        results[name] = {"us_per_call": us_val,
                         "derived": _parse_derived(derived)}
    return {"version": 1, "results": results}


def write_bench_json(rows: list, path: str = BENCH_JSON) -> dict:
    doc = rows_to_json(rows)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return doc


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_fleet, bench_paged_serving,
                            bench_trace_replay, fig3_container_heavy,
                            fig4_unikernel_light, fig5_hybrid_saving,
                            fig6_processing_time, fig7_orchestration)

    print("name,us_per_call,derived")
    ok = True
    rows: list = []
    for mod in (fig3_container_heavy, fig4_unikernel_light,
                fig5_hybrid_saving, fig6_processing_time,
                fig7_orchestration, bench_paged_serving,
                bench_trace_replay, bench_fleet):
        try:
            for line in mod.run():
                rows.append(line)
                print(line, flush=True)
        except Exception:  # noqa: BLE001
            ok = False
            print(f"{mod.__name__},ERROR,", flush=True)
            traceback.print_exc()
    # roofline summary (table form of EXPERIMENTS.md §Roofline)
    try:
        from benchmarks import roofline_table
        for line in roofline_table.run():
            rows.append(line)
            print(line, flush=True)
    except Exception:  # noqa: BLE001
        ok = False
        print("benchmarks.roofline_table,ERROR,", flush=True)
        traceback.print_exc()
    write_bench_json(rows)
    print(f"# wrote {BENCH_JSON} ({len(rows)} rows)", flush=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
