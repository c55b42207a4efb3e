"""Declarative API tests: ServiceSpec round-trips through
failover→rejoin→scale, least-inflight replica selection, batched
``submit_many`` with speculative backup dispatch, and the
single-probe-build guarantee."""
import itertools
import threading
import time

import pytest

from repro.core import (BaseExecutor, EdgeSystem, ExecutorClass,
                        NodeCapacity, PlacementError, ServiceSpec,
                        SpeculativeRunner, Workload, WorkloadClass,
                        WorkloadKind, percentile)
from repro.core.executor import DispatchRecord


class ToyExecutor(BaseExecutor):
    """Pure-python executor: no jax, deterministic, optional delay/block."""

    executor_class = ExecutorClass.CONTAINER

    def __init__(self, name, mesh=None, delay=0.0,
                 gate: threading.Event = None):
        super().__init__(name, mesh)
        self.delay = delay
        self.gate = gate

    def footprint_bytes(self):
        return 10

    def can_run(self, workload, args):
        return True

    def dispatch(self, workload, args):
        self.inflight += 1
        try:
            if self.gate is not None:
                self.gate.wait(timeout=10.0)
            if self.delay:
                time.sleep(self.delay)
            self.history.append(DispatchRecord(workload.name, self.delay,
                                               False))
            return (self.name, workload.name)
        finally:
            self.inflight -= 1


def _toy_builder(delays=(0.0,), gates=None):
    counter = itertools.count()

    def builder(workload, mesh):
        i = next(counter)
        gate = gates[i] if gates and i < len(gates) else None
        ex = ToyExecutor(f"toy{i}", mesh=mesh,
                         delay=delays[i % len(delays)], gate=gate)
        return ex, 10
    return builder


def _system(n_nodes=3, builder=None, runner=None):
    system = EdgeSystem(runner=runner)
    for i in range(n_nodes):
        system.add_node(f"n{i}", NodeCapacity(chips=1, hbm_bytes=1000,
                                              flops_per_s=1.0))
    system.register_builder("generic", WorkloadClass.HEAVY,
                            builder or _toy_builder())
    return system


def _spec(name="svc", replicas=1):
    return ServiceSpec(name=name,
                       workload=Workload(name, WorkloadKind.GENERIC),
                       executor_class=ExecutorClass.CONTAINER,
                       replicas=replicas, footprint_hint=10)


# ----------------------------------------------------------- spec lifecycle
def test_spec_roundtrip_failover_rejoin_scale():
    system = _system(n_nodes=3)
    deps = system.apply(_spec(replicas=2))
    assert [d.name for d in deps] == ["svc/0", "svc/1"]
    assert all(d.spec.name == "svc" for d in deps)

    # failover: instances redeploy from the STORED spec — no factory args
    victim = deps[0].node_id
    moved = system.orchestrator.on_node_failure(victim)
    assert moved == [deps[0].name]
    survivor = system.orchestrator.deployments[moved[0]]
    assert survivor.node_id != victim
    assert survivor.spec.name == "svc"

    # rejoin: the node comes back and takes new instances again
    system.orchestrator.on_node_rejoin(victim)
    assert system.orchestrator.nodes[victim].healthy

    # scale: up from the stored spec, then down
    assert system.scale("svc", 4) == 4
    assert all(d.spec.name == "svc"
               for d in system.instances("svc"))
    assert system.scale("svc", 1) == 1
    assert system.report()["services"]["svc"] == 1

    # a second failover cycle still works after scaling
    dep = system.instances("svc")[0]
    moved = system.orchestrator.on_node_failure(dep.node_id)
    assert moved == [dep.name]


def test_apply_is_declarative_reconcile():
    system = _system()
    system.apply(_spec(replicas=3))
    assert len(system.instances("svc")) == 3
    system.apply(_spec(replicas=1))          # re-apply with fewer replicas
    assert len(system.instances("svc")) == 1


def test_scale_down_removes_newest_instances():
    # numeric instance ordering: 'svc/10' sorts after 'svc/9', so a
    # scale-down culls the newest replicas, not the lexicographic tail
    system = _system(n_nodes=3)
    system.apply(_spec(replicas=12))
    assert system.scale("svc", 10) == 10
    names = [d.name for d in system.instances("svc")]
    assert names == [f"svc/{i}" for i in range(10)]


def test_autoscale_keeps_report_in_sync():
    system = _system(n_nodes=4)
    system.apply(_spec(replicas=1))
    for i in range(20):
        system.queue.put((Workload(f"p{i}", WorkloadKind.GENERIC), ()))
    n = system.autoscale("svc", per_instance=4, max_n=8)
    assert n == 5
    assert system.report()["services"]["svc"] == 5


def test_submit_many_rejects_foreign_queue_items():
    system = _system()
    system.apply(_spec(replicas=1))
    system.queue.put(42)                     # not a (Workload, args) pair
    with pytest.raises(TypeError):
        system.submit_many(
            [(Workload("w", WorkloadKind.GENERIC, est_flops=1e10), ())])


def test_apply_builds_executor_exactly_once_per_instance():
    calls = []
    base = _toy_builder()

    def counting_builder(workload, mesh):
        calls.append(mesh)
        return base(workload, mesh)

    system = _system(builder=counting_builder)
    system.apply(_spec(name="one", replicas=1))
    # the probe build IS the first instance — no double compile (satellite:
    # unikernel images must not build twice on the cold path)
    assert len(calls) == 1
    system.scale("one", 2)
    assert len(calls) == 2                   # one more build per new replica


def test_submit_autoapplies_single_replica_spec():
    system = _system()
    w = Workload("adhoc", WorkloadKind.GENERIC, est_flops=1e10)
    res = system.submit(w, ())
    assert res.deployed_fresh
    res2 = system.submit(w, ())
    assert not res2.deployed_fresh
    assert "heavy:generic:adhoc" in system.report()["services"]


# ----------------------------------------------------- least-inflight picks
def test_replicas_spread_dispatches():
    system = _system()
    system.apply(_spec(replicas=3))
    results = [system.submit(
        Workload(f"w{i}", WorkloadKind.GENERIC, est_flops=1e10), ())
        for i in range(6)]
    by_executor = {}
    for r in results:
        by_executor[r.executor_name] = by_executor.get(r.executor_name,
                                                       0) + 1
    assert len(by_executor) == 3
    assert set(by_executor.values()) == {2}


def test_least_inflight_avoids_busy_replica_under_concurrency():
    gate = threading.Event()
    system = _system(builder=_toy_builder(gates=[gate, None]))
    deps = system.apply(_spec(replicas=2))
    blocked, free = deps[0].executor, deps[1].executor

    w = Workload("wa", WorkloadKind.GENERIC, est_flops=1e10)
    results = {}
    t = threading.Thread(
        target=lambda: results.update(a=system.submit(w, ())))
    t.start()
    deadline = time.monotonic() + 5.0
    while blocked.inflight == 0:             # wait for the submit to park
        assert time.monotonic() < deadline
        time.sleep(0.001)

    # concurrent submit must route to the idle replica, not queue behind
    res = system.submit(Workload("wb", WorkloadKind.GENERIC,
                                 est_flops=1e10), ())
    assert res.executor_name == free.name
    gate.set()
    t.join(timeout=5.0)
    assert results["a"].executor_name == blocked.name


# ------------------------------------------------------------- submit_many
def test_submit_many_speculative_backup_wins():
    runner = SpeculativeRunner(threshold=2.0, min_history=2)
    for _ in range(3):                       # seed the latency history
        runner.run(lambda: time.sleep(0.01) or "warm")
    system = _system(builder=_toy_builder(delays=(1.0, 0.01)),
                     runner=runner)
    system.apply(_spec(replicas=2))

    items = [(Workload(f"w{i}", WorkloadKind.GENERIC, est_flops=1e10), ())
             for i in range(2)]
    results = system.submit_many(items)
    assert len(results) == 2
    # the straggling primary (toy0, 1s) lost to the backup replica (toy1)
    assert results[0].winner == "backup"
    assert results[0].executor_name == "toy1"
    assert results[0].wall_s < 0.9
    backups = system.report()["backups"]
    assert backups["launched"] >= 1 and backups["wins"] >= 1


def test_submit_many_without_speculation_is_serial():
    system = _system()
    system.apply(_spec(replicas=2))
    items = [(Workload(f"w{i}", WorkloadKind.GENERIC, est_flops=1e10), ())
             for i in range(4)]
    results = system.submit_many(items, speculative=False)
    assert len(results) == 4
    assert all(r.winner == "primary" for r in results)
    q = system.report()["queue"]
    assert q["enqueued"] == 4 and q["dequeued"] == 4 and q["depth"] == 0


# --------------------------------------------------------------- telemetry
def test_dispatch_stats_percentiles():
    xs = list(range(1, 101))
    assert percentile(xs, 50) == pytest.approx(50.5)
    assert percentile(xs, 99) == pytest.approx(99.01)
    assert percentile([7.0], 95) == 7.0

    system = _system()
    system.apply(_spec(replicas=1))
    for i in range(10):
        system.submit(Workload(f"w{i}", WorkloadKind.GENERIC,
                               est_flops=1e10), ())
    rep = system.report()["heavy"]
    assert rep["count"] == 10
    assert rep["p50_wall_s"] <= rep["p95_wall_s"] <= rep["p99_wall_s"]
    assert rep["cold_count"] == 0            # spec applied before submits
    assert rep["warm_count"] == 10


def test_spec_validation_and_unknown_builder():
    with pytest.raises(ValueError):
        ServiceSpec(name="bad",
                    workload=Workload("w", WorkloadKind.GENERIC),
                    replicas=-1)
    system = EdgeSystem()
    system.add_node("n0", NodeCapacity(hbm_bytes=16 << 30))
    with pytest.raises(PlacementError):
        system.apply(_spec())                # no builder registered