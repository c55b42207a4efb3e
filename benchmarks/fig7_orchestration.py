"""Paper Fig. 7 — resource usage under container orchestration.

The paper deploys 16 CV-app instances across 4 worker nodes (manager on a
5th) and shows the orchestrator balancing load and redistributing when a
node is overloaded.  Analogue: ONE declarative ``ServiceSpec`` (16
replicas) applied to an ``EdgeSystem`` under each placement policy
(≙ Swarm / K3s / Nomad), then a node failure → failover redeploys from
the stored spec; we report per-node instance counts, HBM balance
(stddev), redeploy latency, and dispatch percentiles from the system's
``DispatchStats``.
"""
from __future__ import annotations

import time

import jax.numpy as jnp

from benchmarks.common import csv_line, stats_suffix
from repro.core import (ContainerExecutor, EdgeSystem, ExecutorClass,
                        NodeCapacity, POLICIES, QoSClass, ServiceSpec,
                        Workload, WorkloadClass, WorkloadKind)

import numpy as np

N_NODES = 4
N_INSTANCES = 16
FOOTPRINT = 10 * 2 ** 20          # 10 MiB per instance


def _builder(workload, mesh):
    ex = ContainerExecutor("cv-app", {"generic": lambda x: x}, mesh=mesh)
    return ex, FOOTPRINT


def run() -> list[str]:
    rows = []
    for pname, pcls in POLICIES.items():
        system = EdgeSystem(policy=pcls())
        for i in range(N_NODES):
            system.add_node(f"worker{i}",
                            NodeCapacity(hbm_bytes=16 << 30))
        system.register_builder("generic", WorkloadClass.HEAVY, _builder)

        spec = ServiceSpec(
            name="cv",
            workload=Workload("cv-app", WorkloadKind.GENERIC),
            executor_class=ExecutorClass.CONTAINER,
            replicas=N_INSTANCES,
            footprint_hint=FOOTPRINT)
        t0 = time.perf_counter()
        system.apply(spec)
        deploy_us = (time.perf_counter() - t0) / N_INSTANCES * 1e6

        counts = {n: 0 for n in system.orchestrator.nodes}
        for d in system.orchestrator.deployments.values():
            counts[d.node_id] += 1
        load = np.array(list(counts.values()), float)

        # spread dispatches across the replica set (least-inflight), and
        # report serialized vs overlapped throughput: the concurrent mode
        # has every request in flight before any result is collected
        x = jnp.zeros((4,), jnp.float32)

        def batch(tag):
            return [(Workload(f"{tag}{i}", WorkloadKind.GENERIC,
                              est_flops=1e10), (x,)) for i in range(32)]

        t_ser = time.perf_counter()
        system.submit_many(batch("ser"), speculative=False,
                           concurrent=False)
        ser_rps = 32 / (time.perf_counter() - t_ser)
        t_par = time.perf_counter()
        system.submit_many(batch("par"), speculative=False,
                           concurrent=True)
        par_rps = 32 / (time.perf_counter() - t_par)

        # per-replica latency attribution: which instance caused the p95
        # (the same split the fleet scorecards use), plus how evenly the
        # least-inflight router spread the 64 dispatches
        per_rep = system.stats.per_replica()
        rep_counts = [v["count"] for v in per_rep.values()]
        hot = max(per_rep, key=lambda r: per_rep[r]["p95_wall_s"]) \
            if per_rep else ""

        # node failure → redeploy from the stored spec (paper: redistribute)
        t1 = time.perf_counter()
        moved = system.orchestrator.on_node_failure("worker0")
        failover_us = (time.perf_counter() - t1) * 1e6
        counts2 = {}
        for d in system.orchestrator.deployments.values():
            counts2[d.node_id] = counts2.get(d.node_id, 0) + 1
        assert sum(counts2.values()) == N_INSTANCES
        rows.append(csv_line(
            f"fig7/{pname}", deploy_us,
            f"load_per_node={'/'.join(str(int(c)) for c in load)};"
            f"stddev={load.std():.2f};moved={len(moved)};"
            f"failover_us={failover_us:.0f};"
            f"serial_rps={ser_rps:.0f};overlap_rps={par_rps:.0f};"
            f"overlap_speedup={par_rps / ser_rps:.2f}x;"
            f"replicas={len(per_rep)};"
            f"rep_disp_max/min={max(rep_counts)}/{min(rep_counts)};"
            f"hot_replica={hot}:"
            f"{per_rep[hot]['p95_wall_s'] * 1e6:.0f}us;"
            f"{stats_suffix(system.stats, 'heavy')}"))
    rows.append(run_tenants())
    return rows


class _PrefixExecutor(ContainerExecutor):
    """Routes by workload-name prefix so each tenant's items land on (and
    are attributed to) that tenant's own service — least-inflight routing
    is otherwise tenant-blind across identical generic executors."""

    def __init__(self, name, prefix, mesh=None):
        super().__init__(name, {"generic": lambda x: x}, mesh=mesh)
        self.prefix = prefix

    def can_run(self, workload, args):
        return workload.name.startswith(self.prefix + "-")


def _tenant_builder(workload, mesh):
    ex = _PrefixExecutor(f"cv[{workload.name}]", workload.name, mesh=mesh)
    return ex, FOOTPRINT


def run_tenants() -> str:
    """Mixed GUARANTEED/BEST_EFFORT load: per-tenant p95 latency, a Jain
    fairness index over per-tenant mean latency, and the preemption path
    (a saturating BEST_EFFORT tenant cannot refuse a GUARANTEED apply)."""
    system = EdgeSystem()
    for i in range(N_NODES):
        # 4 instance slots per node: saturation takes a handful of filler
        # instances, not thousands of 10MiB ones against 16GiB nodes
        system.add_node(f"worker{i}",
                        NodeCapacity(chips=1, hbm_bytes=4 * FOOTPRINT))
    system.register_builder("generic", WorkloadClass.HEAVY, _tenant_builder)

    def spec(name, tenant, qos, replicas, priority=0):
        return ServiceSpec(
            name=name, workload=Workload(name, WorkloadKind.GENERIC),
            executor_class=ExecutorClass.CONTAINER, replicas=replicas,
            footprint_hint=FOOTPRINT, tenant=tenant, qos=qos,
            priority=priority)

    system.apply(spec("gold", "ops", QoSClass.GUARANTEED, 4, priority=5))
    system.apply(spec("noise", "batch", QoSClass.BEST_EFFORT, 8))

    x = jnp.zeros((4,), jnp.float32)
    items = []
    for i in range(32):                   # noisy tenant floods 3:1
        tag = "gold" if i % 4 == 0 else "noise"
        items.append((Workload(f"{tag}-{i}", WorkloadKind.GENERIC,
                               est_flops=1e10), (x,)))
    t0 = time.perf_counter()
    system.submit_many(items, speculative=False, concurrent=True)
    dt = time.perf_counter() - t0

    lat = system.stats.per_tenant()
    means = [lat[t]["mean_wall_s"] for t in ("ops", "batch") if t in lat]
    jain = (sum(means) ** 2 / (len(means) * sum(m * m for m in means))
            if means else float("nan"))

    # preemption: BEST_EFFORT saturates the cluster, GUARANTEED still lands
    filler = ServiceSpec(
        name="filler", workload=Workload("filler", WorkloadKind.GENERIC),
        executor_class=ExecutorClass.CONTAINER, replicas=0,
        footprint_hint=FOOTPRINT, tenant="batch", qos=QoSClass.BEST_EFFORT)
    system.apply(filler)
    while True:                           # fill every remaining slot
        try:
            system.scale("filler", len(system.instances("filler")) + 1)
        except Exception:  # noqa: BLE001 — cluster is full
            break
    t1 = time.perf_counter()
    system.apply(spec("gold2", "ops", QoSClass.GUARANTEED, 2, priority=5))
    preempt_us = (time.perf_counter() - t1) * 1e6
    preempts = sum(1 for e in system.events if e.startswith("preempt "))
    assert len(system.instances("gold2")) == 2, "preemption must fire"

    def p95(t):
        return (f"{lat[t]['p95_wall_s'] * 1e6:.1f}"
                if t in lat else "n/a")

    return csv_line(
        "fig7/tenants", dt / 32 * 1e6,
        f"ops_p95_us={p95('ops')};batch_p95_us={p95('batch')};"
        f"fairness_jain={jain:.3f};preempted={preempts};"
        f"preempt_apply_us={preempt_us:.0f}")


if __name__ == "__main__":
    print("\n".join(run()))
