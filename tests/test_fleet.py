"""Fleet routing: prefix-affinity index semantics, deterministic router
policy tests against stub engines (affinity hit/miss, session
stickiness, least-pages tiebreak, steal threshold, failure/replica-loss
rerouting of GUARANTEED work, stall evasion), and one real-engine
integration pass through the control plane (deploy_fleet charges every
replica with admission, node-loss failover is healed by refresh)."""
import itertools
from concurrent.futures import Future

import numpy as np
import pytest

from repro.core import EdgeSystem, NodeCapacity, WorkloadClass
from repro.fleet import FleetRouter, PrefixAffinityIndex, prefix_fingerprints
from repro.serving.router import fleet_service_spec, make_fleet_builder


# --------------------------------------------------------------------------
# affinity index
# --------------------------------------------------------------------------

def test_fingerprints_chain_and_prefix_property():
    toks = np.arange(48, dtype=np.int32)
    fps = prefix_fingerprints(toks, block=16)
    assert len(fps) == 3                       # one per complete block
    # prefix property: a longer prompt's fingerprints extend the
    # shorter's — the chained digest makes block k depend on blocks <= k
    assert prefix_fingerprints(toks[:32], block=16) == fps[:2]
    # partial trailing block contributes nothing
    assert prefix_fingerprints(toks[:40], block=16) == fps[:2]
    # a change inside block 0 changes every downstream fingerprint
    other = toks.copy()
    other[3] += 1
    assert all(a != b for a, b in zip(fps, prefix_fingerprints(other)))


def test_affinity_index_longest_match_and_miss():
    idx = PrefixAffinityIndex(block=16)
    toks = np.arange(64, dtype=np.int32)
    idx.record(toks[:32], "e0")
    rep, blocks = idx.lookup(toks)             # blocks 0-1 known, 2-3 not
    assert rep == "e0" and blocks == 2
    assert idx.lookup(np.arange(100, 116, dtype=np.int32)) == (None, 0)
    # later claims win: the same prefix re-recorded moves the mapping
    idx.record(toks[:16], "e1")
    assert idx.lookup(toks[:16]) == ("e1", 1)


def test_affinity_index_lru_and_drop_replica():
    idx = PrefixAffinityIndex(block=4, capacity=3)
    for i in range(4):
        idx.record(np.full(4, i, dtype=np.int32), f"e{i}")
    assert len(idx) == 3                       # oldest fingerprint evicted
    assert idx.lookup(np.full(4, 0, dtype=np.int32)) == (None, 0)
    idx.drop_replica("e2")
    assert idx.lookup(np.full(4, 2, dtype=np.int32)) == (None, 0)
    assert idx.lookup(np.full(4, 3, dtype=np.int32)) == ("e3", 1)


# --------------------------------------------------------------------------
# stub engines
# --------------------------------------------------------------------------

class StubHandle:
    def __init__(self, rid, future):
        self.rid = rid
        self.future = future


class StubEngine:
    """Engine-shaped stub: submissions queue as futures the test resolves
    explicitly, so routing decisions are fully deterministic."""

    def __init__(self, kv_bytes=0, responsive=True):
        self.replica_id = ""
        self.kv_bytes = kv_bytes
        self.ok = responsive
        self.fail_submit = False
        self.queued = {}
        self.active = 0
        self.notes = []
        self._rids = itertools.count()

    def submit(self, prompt, max_new_tokens=16, eos_token=None,
               latency_slo_ms=0.0, qos="burstable"):
        if self.fail_submit:
            raise RuntimeError("engine refused")
        self.last_qos = qos
        rid = next(self._rids)
        fut = Future()
        self.queued[rid] = fut
        return StubHandle(rid, fut)

    def finish(self, rid=None, result="done"):
        rid = rid if rid is not None else next(iter(self.queued))
        self.queued.pop(rid).set_result(result)

    def load(self):
        return (len(self.queued), self.active, self.kv_bytes)

    def queue_depth(self):
        return len(self.queued)

    def responsive(self, timeout=0.05):
        return self.ok

    def cancel_queued(self, rid, timeout=0.1):
        return self.queued.pop(rid, None)

    def note_prefix(self, hit):
        self.notes.append(hit)

    def recent_queue_p95(self):
        return 0.0


def make_fleet(n=2, policy="affinity", **kw):
    engines = [StubEngine() for _ in range(n)]
    router = FleetRouter(engines, policy=policy, **kw)
    return router, engines


P0 = np.arange(32, dtype=np.int32)             # two affinity blocks


# --------------------------------------------------------------------------
# routing policy
# --------------------------------------------------------------------------

def test_prefix_affinity_hit_and_miss():
    router, (e0, e1) = make_fleet()
    h = router.submit(P0)                      # cold: least-load miss
    assert router.counters["misses"] == 1
    first = h._rec.replica
    e0.finish() if first == "replica/0" else e1.finish()
    assert h.result(timeout=5.0) == "done"
    # longer prompt sharing the recorded prefix → same replica, a hit
    h2 = router.submit(np.concatenate([P0, P0 + 100]))
    assert h2._rec.replica == first
    assert router.counters["prefix_hits"] == 1
    # unrelated prompt → miss again
    router.submit(np.arange(200, 232, dtype=np.int32))
    assert router.counters["misses"] == 2


def test_session_stickiness_beats_least_load():
    router, (e0, e1) = make_fleet()
    h = router.submit(P0, session="s1")
    pinned = h._rec.replica
    pinned_eng = e0 if pinned == "replica/0" else e1
    other_eng = e1 if pinned_eng is e0 else e0
    # pile work onto the pinned replica: least-load would now pick the
    # other one, stickiness must not
    for _ in range(4):
        pinned_eng.submit(P0)
    h2 = router.submit(np.arange(500, 532, dtype=np.int32), session="s1")
    assert h2._rec.replica == pinned
    assert router.counters["session_hits"] == 1
    assert other_eng.queue_depth() == 0


def test_least_pages_tiebreak_on_equal_depth():
    router, (e0, e1) = make_fleet()
    e0.kv_bytes = 1 << 20                      # fuller page pool
    e1.kv_bytes = 1 << 10
    h = router.submit(np.arange(900, 932, dtype=np.int32))
    assert h._rec.replica == "replica/1"


def test_round_robin_policy_rotates_blindly():
    router, (e0, e1) = make_fleet(policy="round-robin")
    reps = [router.submit(P0, session="s")._rec.replica
            for _ in range(4)]
    assert reps == ["replica/0", "replica/1"] * 2
    assert len(router._affinity) == 0          # baseline records nothing
    assert router.counters["session_hits"] == 0


def test_stall_evasion_routes_around_wedged_replica():
    router, (e0, e1) = make_fleet()
    h = router.submit(P0, session="s1")
    wedged = e0 if h._rec.replica == "replica/0" else e1
    wedged.ok = False                          # replica stops responding
    h2 = router.submit(P0, session="s1")       # stickiness says wedged...
    assert h2._rec.replica != h._rec.replica   # ...probe evades it
    assert router.counters["stall_evasions"] == 1


# --------------------------------------------------------------------------
# work stealing
# --------------------------------------------------------------------------

def test_steal_threshold_and_median_floor():
    router, (e0, e1) = make_fleet()
    for _ in range(6):                         # all pinned to one replica
        router.submit(P0, session="hot")
    donor = e0 if e0.queue_depth() else e1
    idle = e1 if donor is e0 else e0
    assert donor.queue_depth() == 6 and idle.queue_depth() == 0
    out = router.rebalance()                   # median 3 → steal to floor
    assert out == {"moved": 3, "median_depth": 3.0}
    assert donor.queue_depth() == 3 and idle.queue_depth() == 3
    assert router.counters["steals"] == 3
    # below threshold now: a second pass must not ping-pong work back
    assert router.rebalance()["moved"] == 0


def test_steal_below_threshold_is_a_noop():
    router, (e0, e1) = make_fleet()
    router.submit(P0, session="a")
    router.submit(P0, session="a")             # depth 2 vs 0: median 1,
    assert router.rebalance()["moved"] == 0    # threshold max(1.5, 3)=3


# --------------------------------------------------------------------------
# failure + replica-loss rerouting
# --------------------------------------------------------------------------

def test_guaranteed_failure_reroutes_nonguaranteed_fails():
    router, (e0, e1) = make_fleet()
    h = router.submit(P0, session="s1")        # establish the pin
    bad = e0 if h._rec.replica == "replica/0" else e1
    good = e1 if bad is e0 else e0
    bad.finish()
    assert h.result(timeout=5.0) == "done"
    bad.fail_submit = True
    hg = router.submit(P0, session="s1", guaranteed=True)
    router.poke()                              # drain the failure mailbox
    assert hg._rec.replica != h._rec.replica
    good.finish(hg._rec.inner.rid)
    assert hg.result(timeout=5.0) == "done"
    assert router.counters["reroutes"] == 1
    hb = router.submit(P0, session="s1")       # sticky → still the bad one
    with pytest.raises(RuntimeError, match="engine refused"):
        hb.result(timeout=5.0)
    assert router.counters["failed"] == 1


def test_replica_loss_reroutes_guaranteed_work():
    router, (e0, e1) = make_fleet()
    hg = router.submit(P0, session="s1", guaranteed=True)
    hb = router.submit(P0, session="s1")
    lost_key = hg._rec.replica
    lost = e0 if lost_key == "replica/0" else e1
    survivor = e1 if lost is e0 else e0
    assert router.mark_replica_lost(lost_key) == 1   # only the GUARANTEED
    assert hg._rec.replica != lost_key
    survivor.finish(hg._rec.inner.rid)
    assert hg.result(timeout=5.0) == "done"
    assert router.counters["reroutes"] == 1
    # session + affinity pins to the dead replica are gone: new traffic
    # for the session lands on the survivor
    h2 = router.submit(P0, session="s1")
    assert h2._rec.replica != lost_key
    # the orphaned non-GUARANTEED binding may still be finished by the
    # old engine (node loss is a control-plane event)
    lost.finish(hb._rec.inner.rid)
    assert hb.result(timeout=5.0) == "done"


def test_stats_rollup_shape():
    router, (e0, e1) = make_fleet()
    router.submit(P0, session="s")
    s = router.stats()
    assert s["policy"] == "affinity" and s["submitted"] == 1
    assert set(s["replicas"]) == {"replica/0", "replica/1"}
    for d in s["replicas"].values():
        assert {"alive", "submitted", "completed", "queue_depth",
                "kv_bytes_in_use"} <= set(d)
    assert s["outstanding"] == 1 and s["sessions"] == 1


# --------------------------------------------------------------------------
# real engines through the control plane
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet_cfg(exact_config):
    return exact_config("tinyllama-1.1b")


def test_deploy_fleet_admission_and_failover(fleet_cfg):
    system = EdgeSystem()
    system.add_node("edge0", NodeCapacity(chips=1, hbm_bytes=8 << 30))
    system.add_node("edge1", NodeCapacity(chips=1, hbm_bytes=8 << 30))
    system.register_builder(
        "generic", WorkloadClass.HEAVY,
        make_fleet_builder(fleet_cfg, max_slots=2, max_seq=64))
    spec = fleet_service_spec(fleet_cfg, name="fleet-it", replicas=2,
                              tenant="pro")
    router = system.deploy_fleet(spec)
    try:
        # each replica individually charged through admission
        charged = {k: v for k, v in
                   system.admission.instance_commitments().items()
                   if k.startswith("fleet-it/")}
        assert len(charged) == 2
        assert all(v["hbm_bytes"] > 0 and v["tenant"] == "pro"
                   for v in charged.values())
        assert len({v["node"] for v in charged.values()}) == 2

        prompt = np.arange(12, dtype=np.int32) % fleet_cfg.vocab_size
        h = router.submit(prompt, max_new_tokens=3, session="it",
                          guaranteed=True)
        req = h.result(timeout=180.0)
        assert req.done and len(req.generated) == 3

        # kill the node hosting one replica: orchestrator failover
        # redeploys from spec, refresh() swaps the replaced engine in
        victim = system.instances("fleet-it")[0].node_id
        system.on_node_loss(victim)
        router.refresh()
        stats = router.stats()
        assert sum(1 for d in stats["replicas"].values()
                   if d["alive"]) == 2
        h2 = router.submit(prompt, max_new_tokens=3, session="it",
                           guaranteed=True)
        req2 = h2.result(timeout=180.0)
        assert req2.done and len(req2.generated) == 3
    finally:
        router.shutdown()


def test_fleet_replicas_pinned_one_per_device():
    """Four one-device nodes (four virtual CPU devices, in a subprocess):
    ``deploy_fleet`` puts each replica's params and KV pools on its own
    node's device, the routed fleet's tokens equal one replica's, and an
    engine refuses a multi-device mesh it cannot shard over."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_reduced_config
        from repro.core import EdgeSystem, NodeCapacity, WorkloadClass
        from repro.serving.engine import ServingEngine
        from repro.serving.router import (fleet_service_spec,
                                          make_fleet_builder)

        cfg = get_reduced_config("tinyllama-1.1b")
        devs = jax.devices()
        system = EdgeSystem()
        for i, d in enumerate(devs):
            system.add_node(f"chip{i}", NodeCapacity(hbm_bytes=1 << 34),
                            mesh=Mesh(np.array([d]), ("replica",)))
        system.register_builder("generic", WorkloadClass.HEAVY,
                                make_fleet_builder(cfg, max_slots=2,
                                                   max_seq=64))
        router = system.deploy_fleet(fleet_service_spec(
            cfg, name="f", replicas=4, max_new_tokens=4))
        engines = [d.executor.engine for d in system.instances("f")]
        held = [{d for x in jax.tree.leaves((e.params, e.kv.pools))
                 for d in x.devices()} for e in engines]
        assert held == [{d} for d in devs], held
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, size=n)
                   for n in (5, 9, 17, 30)]
        solo = [engines[0].submit(p, max_new_tokens=4).result(timeout=300)
                for p in prompts]
        engines[0].release_prefix_cache()
        fleet = [router.submit(p, max_new_tokens=4) for p in prompts]
        fleet = [h.result(timeout=300) for h in fleet]
        assert [r.generated for r in fleet] == [r.generated for r in solo]
        assert all(not e.failed for e in engines)
        router.shutdown()
        try:
            ServingEngine(cfg, max_slots=1, max_seq=32,
                          mesh=Mesh(np.array(devs[:2]), ("x",)))
        except ValueError as e:
            print("refused:", e)
        print("placed", len(held))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "placed 4" in r.stdout and "refused: a ServingEngine" in r.stdout
