"""Background engine loop + the serving/scheduler/orchestrator bugfix
sweep: prompt validation at submit, in-loop failures that must not kill
the loop, primary-error→backup fallback, rejoin re-reconcile, and the
overlapped-ticks guarantees (concurrent dispatches share one decode
batch; ``submit_many`` over a mixed batch beats serialized ticks)."""
import threading
import time

import numpy as np
import pytest

from repro.core import (EdgeSystem, ExecutorClass, NodeCapacity,
                        ServiceSpec, SpeculativeRunner, Workload,
                        WorkloadClass, WorkloadKind)
from repro.serving.engine import EngineExecutor, Request, ServingEngine


@pytest.fixture(scope="module")
def tiny_cfg(exact_config):
    return exact_config("tinyllama-1.1b")


# ------------------------------------------------------- prompt validation
def test_submit_rejects_empty_and_overlong_prompt(tiny_cfg):
    eng = ServingEngine(tiny_cfg, max_slots=2, max_seq=32)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="exceeds max_seq"):
        eng.submit(np.zeros((33,), np.int32))
    with pytest.raises(ValueError, match="1-D"):
        eng.submit(np.zeros((2, 4), np.int32))
    assert not eng.queue                       # nothing leaked into the queue

    # the engine still serves fine after rejecting bad submissions
    h = eng.submit(np.arange(4) % tiny_cfg.vocab_size, max_new_tokens=3)
    req = h.result(timeout=60.0)
    assert req.done and len(req.generated) == 3


def test_bad_queue_item_fails_request_not_engine(tiny_cfg):
    """A malformed request that sneaks past submit() must mark itself
    failed (future raises) instead of crashing the shared loop."""
    from concurrent.futures import Future

    eng = ServingEngine(tiny_cfg, max_slots=2, max_seq=32)
    bad = Request(rid=10_000, prompt=np.zeros((0,), np.int32),
                  future=Future())
    good_h = eng.submit(np.arange(5) % tiny_cfg.vocab_size,
                        max_new_tokens=3)
    eng.queue.insert(0, bad)                   # bad item ahead of good one
    good = good_h.result(timeout=60.0)         # loop survives, good completes
    assert good.done and len(good.generated) == 3
    assert bad.rid in eng.failed
    with pytest.raises(ValueError):
        bad.future.result(timeout=0)
    assert eng.stats()["failed"] == 1


def test_decode_error_fails_batch_instead_of_spinning_loop(tiny_cfg):
    """A decode-phase error poisons the batch: every active request's
    future must surface it, and the loop must go idle, not hot-spin."""
    eng = ServingEngine(tiny_cfg, max_slots=2, max_seq=32)

    def boom(*a, **k):
        raise RuntimeError("decode exploded")

    eng._decode = boom
    with eng:
        h = eng.submit(np.arange(4) % tiny_cfg.vocab_size,
                       max_new_tokens=4)
        with pytest.raises(RuntimeError, match="decode exploded"):
            h.result(timeout=30.0)
        assert eng.loop_running                # the loop itself survived
    assert not eng.queue and not eng.active    # nothing stuck
    assert eng.stats()["failed"] == 1


# -------------------------------------------------- engine loop lifecycle
def test_engine_loop_start_stop_drain(tiny_cfg):
    eng = ServingEngine(tiny_cfg, max_slots=2, max_seq=32)
    with eng:
        assert eng.loop_running
        handles = [eng.submit(np.arange(3 + i) % tiny_cfg.vocab_size,
                              max_new_tokens=4) for i in range(3)]
        done = eng.drain(timeout=120.0)
        assert len(done) == 3
        assert all(h.done() for h in handles)
    assert not eng.loop_running                # stopped on exit
    eng.start().start()                        # idempotent restart
    assert eng.loop_running
    eng.stop()
    assert not eng.loop_running


# ---------------------------------------------- scheduler: backup on error
def test_primary_error_triggers_backup_with_history():
    r = SpeculativeRunner(threshold=2.0, min_history=3)
    for _ in range(5):
        r.run(lambda: time.sleep(0.005) or "warm")

    def bad_primary():
        raise RuntimeError("replica died")

    out = r.run(bad_primary, backup=lambda: "rescued")
    assert out.value == "rescued"
    assert out.winner == "backup" and out.backup_launched


def test_primary_error_triggers_backup_without_history():
    r = SpeculativeRunner(min_history=5)       # no budget yet

    def bad_primary():
        raise RuntimeError("replica died")

    out = r.run(bad_primary, backup=lambda: "rescued")
    assert out.value == "rescued" and out.winner == "backup"


def test_raises_only_when_all_copies_fail():
    r = SpeculativeRunner(threshold=2.0, min_history=3)
    for _ in range(5):
        r.run(lambda: time.sleep(0.005) or "warm")

    def boom(msg):
        def go():
            raise RuntimeError(msg)
        return go

    with pytest.raises(RuntimeError):
        r.run(boom("primary"), backup=boom("backup"))
    with pytest.raises(RuntimeError, match="alone"):
        r.run(boom("alone"))                   # no backup → propagate


def test_race_wall_does_not_inflate_latency_history():
    r = SpeculativeRunner(threshold=2.0, min_history=3)
    for _ in range(5):
        r.run(lambda: time.sleep(0.01) or "warm")
    out = r.run(lambda: time.sleep(1.0) or "slow", backup=lambda: "fast")
    assert out.winner == "backup"
    # the recorded sample is the backup's OWN latency (~0), not the
    # race wall (budget-wait + backup) — medians must stay honest
    assert r._latencies[-1] < 0.01
    assert r._budget() < 0.1                   # future backups stay enabled


# ------------------------------------------------ orchestrator: rejoin heal
def test_rejoin_reconciles_replicas_lost_to_failed_failover():
    system = EdgeSystem()
    # each node fits exactly ONE instance (footprint 10 vs capacity 15)
    for i in range(2):
        system.add_node(f"n{i}", NodeCapacity(chips=1, hbm_bytes=15,
                                              flops_per_s=1.0))

    def builder(workload, mesh):
        from repro.core import ContainerExecutor
        return ContainerExecutor("cv", {"generic": lambda x: x},
                                 mesh=mesh), 10

    system.register_builder("generic", WorkloadClass.HEAVY, builder)
    spec = ServiceSpec(name="svc",
                       workload=Workload("w", WorkloadKind.GENERIC),
                       executor_class=ExecutorClass.CONTAINER,
                       replicas=2, footprint_hint=10)
    system.apply(spec)
    assert len(system.instances("svc")) == 2

    victim = system.instances("svc")[0].node_id
    moved = system.orchestrator.on_node_failure(victim)
    assert moved == []                         # nowhere to go → FAILED
    assert any(e.startswith("failover-FAILED")
               for e in system.orchestrator.events)
    assert len(system.instances("svc")) == 1   # capacity lost

    healed = system.orchestrator.on_node_rejoin(victim)
    assert len(healed) == 1
    assert len(system.instances("svc")) == 2   # capacity returned → healed
    assert any(e.startswith("reconcile ")
               for e in system.orchestrator.events)
    # idempotent: a second rejoin of a healthy node changes nothing
    assert system.orchestrator.on_node_rejoin(victim) == []


# ------------------------------------------- overlap: shared decode batch
def _serial_ticks(cfg, prompts, max_new):
    eng = ServingEngine(cfg, max_slots=4, max_seq=64)
    ex = EngineExecutor("serial", eng, autostart=False)
    outs = []
    for i, p in enumerate(prompts):
        w = Workload(f"s{i}", WorkloadKind.DECODE, cfg, seq_len=max_new)
        outs.append(ex.dispatch(w, (p,)))
    return eng.ticks, [r.generated for r in outs]


def test_concurrent_dispatches_share_one_decode_batch(tiny_cfg):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, tiny_cfg.vocab_size, size=n) for n in (5, 8)]
    max_new = 8
    ticks_serial, gen_serial = _serial_ticks(tiny_cfg, prompts, max_new)

    eng = ServingEngine(tiny_cfg, max_slots=4, max_seq=64)
    ex = EngineExecutor("looped", eng, autostart=True)
    barrier = threading.Barrier(2)
    results = {}

    def dispatch(i):
        barrier.wait()
        w = Workload(f"c{i}", WorkloadKind.DECODE, tiny_cfg,
                     seq_len=max_new)
        results[i] = ex.dispatch(w, (prompts[i],))

    threads = [threading.Thread(target=dispatch, args=(i,))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    eng.stop()
    assert len(results) == 2
    # batching never changes outputs...
    for i in range(2):
        assert results[i].generated == gen_serial[i]
    # ...but the two requests rode the SAME decode batch: strictly fewer
    # ticks than the serialized sum
    assert eng.ticks < ticks_serial


def test_submit_many_mixed_batch_overlaps_engine_ticks(tiny_cfg):
    """Acceptance: N concurrent container requests take strictly fewer
    engine ticks than the serialized sum, while unikernel-class stream
    work proceeds in the same batch."""
    from repro.data import stream as stream_lib
    from repro.serving.router import make_engine_builder, make_stream_builder

    scfg = stream_lib.StreamConfig(num_users=8, batch_records=16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, tiny_cfg.vocab_size, size=4 + i)
               for i in range(3)]
    max_new = 6

    def build_system():
        system = EdgeSystem()
        node = NodeCapacity(hbm_bytes=16 << 30)
        system.add_node("edge0", node).add_node("edge1", node)
        system.register_builder(
            "decode", WorkloadClass.HEAVY,
            make_engine_builder(tiny_cfg, max_slots=4, max_seq=64))
        system.register_builder(
            "stream", WorkloadClass.LIGHT,
            make_stream_builder(system.registry, scfg))
        (dep,) = system.apply(ServiceSpec(
            name="llm", workload=Workload("serve", WorkloadKind.DECODE,
                                          tiny_cfg, seq_len=max_new),
            executor_class=ExecutorClass.CONTAINER))
        system.apply(ServiceSpec(
            name="stream", workload=Workload("fitbit", WorkloadKind.STREAM),
            executor_class=ExecutorClass.UNIKERNEL))
        return system, dep.executor.engine

    rec = {k: np.asarray(v) for k, v in
           next(stream_lib.make_record_stream(scfg)).items()}

    def batch(tag):
        items = [(Workload(f"{tag}-p{i}", WorkloadKind.DECODE, tiny_cfg,
                           seq_len=max_new, est_flops=1e10), (p,))
                 for i, p in enumerate(prompts)]
        items += [(Workload(f"{tag}-s{i}", WorkloadKind.STREAM),
                   (stream_lib.init_state(scfg), rec)) for i in range(2)]
        return items

    sys_serial, eng_serial = build_system()
    res_serial = sys_serial.submit_many(batch("ser"), speculative=False,
                                        concurrent=False)
    eng_serial.stop()
    ticks_serial = eng_serial.ticks

    sys_conc, eng_conc = build_system()
    res_conc = sys_conc.submit_many(batch("par"), speculative=False,
                                    concurrent=True)
    eng_conc.stop()

    assert len(res_serial) == len(res_conc) == 5
    # container requests produced identical generations in both modes
    for rs, rc in zip(res_serial[:3], res_conc[:3]):
        assert rs.output.generated == rc.output.generated
    # the overlapped batch shares decode ticks: strictly fewer than the
    # serialized per-request sum
    assert eng_conc.ticks < ticks_serial
    # unikernel-class stream results completed alongside
    for r in res_conc[3:]:
        _state, out = r.output
        assert float(out["max_avg_steps"]) >= 0.0
