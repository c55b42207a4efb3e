"""Serving driver: continuous-batching engine deployed as a ServiceSpec.

The engine is not hand-built: a declarative spec is applied to an
``EdgeSystem`` whose builder wraps a ``ServingEngine`` in a
container-class executor, and request/latency telemetry comes out of the
same structured ``DispatchStats`` the rest of the runtime reports.

Requests flow through the BACKGROUND engine loop: every prompt is
submitted up front (``submit`` returns a ``RequestHandle``), the loop
overlaps one request's prefill with the others' decode, and the driver
blocks on the handles — so the reported tick count is the overlapped
cost, not the sum of per-request costs.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --requests 8
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="per-request latency SLO; every 4th request gets "
                         "a tight SLO and should jump the queue")
    ap.add_argument("--save-state", default="",
                    help="persist applied specs + quotas to this path")
    args = ap.parse_args()

    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.enable()}")

    from repro.configs import get_config, get_reduced_config
    from repro.core import (EdgeSystem, ExecutorClass, QoSClass, ServiceSpec,
                            Workload, WorkloadClass, WorkloadKind)
    from repro.serving.router import make_engine_builder

    cfg = get_reduced_config(args.arch) if args.reduced \
        else get_config(args.arch)
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")

    system = EdgeSystem()
    system.add_node("edge0")
    system.register_builder(
        "decode", WorkloadClass.HEAVY,
        make_engine_builder(cfg, max_slots=args.slots, max_seq=args.max_seq))
    spec = ServiceSpec(
        name="llm-serving",
        workload=Workload("serve", WorkloadKind.DECODE, cfg,
                          batch=args.slots, seq_len=args.max_new),
        executor_class=ExecutorClass.CONTAINER,
        tenant="serving", qos=QoSClass.GUARANTEED,
        latency_slo_ms=args.slo_ms)
    (dep,) = system.apply(spec)
    engine = dep.executor.engine

    # pre-compile the decode step + every prefill chunk bucket BEFORE
    # traffic: the first burst then streams through warm programs instead
    # of paying serial JIT walls mid-traffic
    engine.warmup()
    print(f"warmup: decode + {len(engine.chunk_buckets)} chunk buckets "
          f"in {engine.warmup_s:.2f}s "
          f"({'paged' if engine.paged else 'dense'} KV, "
          f"chunk={engine.chunk_tokens}, "
          f"budget={engine.prefill_budget} tok/tick)")

    rng = np.random.default_rng(0)
    t0 = time.monotonic()
    with engine:                       # start the background engine loop
        handles = []
        for i in range(args.requests):
            plen = int(rng.integers(4, args.max_seq // 2))
            # a tight-SLO request every 4th submission: the engine's
            # SLO-slack ordering admits these ahead of FIFO arrivals
            slo = args.slo_ms if (args.slo_ms and i % 4 == 3) else 0.0
            handles.append(engine.submit(
                rng.integers(0, cfg.vocab_size, size=plen),
                max_new_tokens=args.max_new, latency_slo_ms=slo))
        done = [h.result(timeout=300.0) for h in handles]
    dt = time.monotonic() - t0
    toks = sum(len(r.generated) for r in done)
    print(f"served {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s, {engine.ticks} overlapped ticks vs "
          f"~{args.requests * args.max_new} serialized) "
          f"via {dep.name} on {dep.node_id}")
    for r in done[:3]:
        ttft = (r.first_token_at - r.submitted_at) * 1e3
        print(f"  rid={r.rid} prompt={len(r.prompt)} ttft={ttft:.0f}ms "
              f"generated={r.generated[:8]}...")

    stats = engine.stats()
    for key in ("p50_request_wall_s", "p95_request_wall_s",
                "p99_request_wall_s", "p50_ttft_s", "p95_ttft_s",
                "p50_prefill_tick_s", "p95_prefill_tick_s",
                "p50_decode_tick_s", "p95_decode_tick_s"):
        if key in stats:
            print(f"  {key}={stats[key] * 1e3:.1f}ms")
    if stats.get("paged"):
        print(f"  kv: dense-equivalent "
              f"{stats['kv_dense_equivalent_bytes'] / 2**20:.1f}MiB -> "
              f"pool {stats['kv_capacity_bytes'] / 2**20:.1f}MiB, "
              f"peak in-tick budget "
              f"{stats.get('max_prefill_tokens_tick', 0)} prefill tok")

    if args.slo_ms:
        slo_reqs = [r for r in done if r.latency_slo_ms > 0]
        met = sum((r.finished_at - r.submitted_at) * 1e3 <= r.latency_slo_ms
                  for r in slo_reqs)
        print(f"  slo: {met}/{len(slo_reqs)} tight-SLO requests "
              f"within {args.slo_ms:.0f}ms; "
              f"p95_queue_s={stats.get('p95_queue_s', 0.0) * 1e3:.1f}ms")
        n = system.autoscale("llm-serving", mode="slo", max_n=4)
        print(f"  slo-autoscale: engine replicas -> {n}")
    if args.save_state:
        system.save_state(args.save_state)
        print(f"  state saved to {args.save_state} "
              f"(EdgeSystem.restore re-applies it after a manager restart)")


if __name__ == "__main__":
    main()
