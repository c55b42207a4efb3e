"""Hybrid-runtime tests: classifier, executors, registry, orchestrator,
manager routing, failover, elastic scaling — the paper's P1–P4."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import (BinPackPolicy, ClassifierConfig, ConfigurationManager,
                        ContainerExecutor, ExecutableImage, ExecutorClass,
                        ImageRegistry, IncompatibleWorkload,
                        LeastLoadedPolicy, NodeCapacity, Orchestrator,
                        PlacementError, ResourceMonitor, RoundRobinPolicy,
                        ServiceSpec, UnikernelExecutor, Workload,
                        WorkloadClass, WorkloadKind, classify)
from repro.data import stream as stream_lib
from repro.serving import router


# ---------------------------------------------------------------- classify
def test_classifier_paper_rules():
    heavy_cfg = get_reduced_config("chameleon-34b")
    # stream data → LIGHT (the paper's fitbit→unikernel rule)
    assert classify(Workload("s", WorkloadKind.STREAM)) == WorkloadClass.LIGHT
    # training → HEAVY always
    assert classify(Workload("t", WorkloadKind.TRAIN, heavy_cfg)) == \
        WorkloadClass.HEAVY
    # big-model decode → HEAVY via params threshold
    from repro.configs import get_config
    assert classify(Workload("d", WorkloadKind.DECODE,
                             get_config("chameleon-34b"), batch=1,
                             seq_len=128)) == WorkloadClass.HEAVY
    # tiny-model single-stream decode → LIGHT
    light_cfg = get_reduced_config("tinyllama-1.1b")
    assert classify(Workload("d", WorkloadKind.DECODE, light_cfg, batch=1,
                             seq_len=32)) == WorkloadClass.LIGHT


# ---------------------------------------------------------------- executors
def test_unikernel_rejects_mismatched_workload():
    def f(x):
        return x * 2.0
    img = ExecutableImage.build("double", f, (jnp.zeros((4,)),))
    ex = UnikernelExecutor("u", img)
    w = Workload("w", WorkloadKind.GENERIC)
    out = ex.dispatch(w, (jnp.ones((4,)),))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((4,)))
    with pytest.raises(IncompatibleWorkload):
        ex.dispatch(w, (jnp.ones((8,)),))          # wrong shape → rejected
    with pytest.raises(IncompatibleWorkload):
        ex.dispatch(w, (jnp.ones((4,), jnp.int32),))  # wrong dtype


def test_container_retraces_new_shapes():
    ex = ContainerExecutor("c", {"generic": lambda x: x + 1.0})
    w = Workload("w", WorkloadKind.GENERIC)
    ex.dispatch(w, (jnp.zeros((4,)),))
    ex.dispatch(w, (jnp.zeros((8,)),))              # flexible: retraces
    ex.dispatch(w, (jnp.zeros((4,)),))              # cached now
    fresh = [h.compiled_fresh for h in ex.history]
    assert fresh == [True, True, False]


def test_registry_caches_builds():
    reg = ImageRegistry()
    f = lambda x: x * 3.0
    args = (jnp.zeros((4,)),)
    a = reg.get_or_build("f", f, args)
    b = reg.get_or_build("f", f, args)
    assert a is b
    assert reg.stats() == {"builds": 1, "hits": 1, "images": 1}
    reg.get_or_build("f", f, (jnp.zeros((8,)),))
    assert reg.stats()["builds"] == 2


# ------------------------------------------------------------- orchestrator
def _orch(policy, n=4, hbm=100):
    o = Orchestrator(policy=policy)
    for i in range(n):
        o.add_node(f"n{i}", NodeCapacity(chips=1, hbm_bytes=hbm,
                                         flops_per_s=1.0))
    return o


def _dummy_factory(mesh):
    return ContainerExecutor("dummy", {"generic": lambda x: x})


def _spec(name, replicas=1, footprint=10):
    return ServiceSpec(name=name,
                       workload=Workload(name, WorkloadKind.GENERIC),
                       executor_class=ExecutorClass.CONTAINER,
                       replicas=replicas, footprint_hint=footprint)


def test_round_robin_spreads():
    o = _orch(RoundRobinPolicy())
    deps = o.apply(_spec("i", replicas=4), _dummy_factory)
    assert sorted(d.node_id for d in deps) == ["n0", "n1", "n2", "n3"]


def test_round_robin_full_node_does_not_skew_spread():
    # a node with no headroom drops out of the rotation instead of
    # permanently skewing picks toward whichever node follows it
    o = _orch(RoundRobinPolicy(), n=4, hbm=100)
    o.monitor.commit("n0", "hog", 95)            # n0 is (almost) full
    deps = o.apply(_spec("i", replicas=6), _dummy_factory)
    counts = {}
    for d in deps:
        counts[d.node_id] = counts.get(d.node_id, 0) + 1
    assert counts == {"n1": 2, "n2": 2, "n3": 2}


def test_least_loaded_balances():
    o = _orch(LeastLoadedPolicy())
    o.apply(_spec("big", footprint=60), _dummy_factory)
    (d2,) = o.apply(_spec("next", footprint=10), _dummy_factory)
    assert d2.node_id != o.instances("big")[0].node_id


def test_bin_pack_fills_tightest():
    o = _orch(BinPackPolicy())
    o.apply(_spec("a", footprint=60), _dummy_factory)
    first = o.instances("a")[0].node_id
    (d,) = o.apply(_spec("b", footprint=30), _dummy_factory)
    assert d.node_id == first                   # tightest fit = same node


def test_spec_placement_override():
    # the spec's placement policy wins over the orchestrator default
    o = _orch(BinPackPolicy())
    spread = ServiceSpec(name="s", workload=Workload("s",
                                                     WorkloadKind.GENERIC),
                         executor_class=ExecutorClass.CONTAINER, replicas=4,
                         placement="round-robin", footprint_hint=10)
    deps = o.apply(spread, _dummy_factory)
    assert len({d.node_id for d in deps}) == 4


def test_admission_respects_capacity():
    o = _orch(LeastLoadedPolicy(), n=1, hbm=100)
    o.apply(_spec("a", footprint=80), _dummy_factory)
    with pytest.raises(PlacementError):
        o.apply(_spec("b", footprint=40), _dummy_factory)  # 80+40 > 100


def test_failover_redeployes_instances():
    o = _orch(LeastLoadedPolicy(), n=3)
    deps = o.apply(_spec("i", replicas=6), _dummy_factory)
    victim = deps[0].node_id
    on_victim = [d.name for d in deps if d.node_id == victim]
    moved = o.on_node_failure(victim)
    assert sorted(moved) == sorted(on_victim)
    for name in on_victim:
        assert o.deployments[name].node_id != victim
        # redeployed instances still carry their spec
        assert o.deployments[name].spec.name == "i"
    # capacity of dead node is gone
    assert victim not in o.monitor.capacity


def test_elastic_scale_up_down():
    o = _orch(LeastLoadedPolicy())
    o.apply(_spec("svc", replicas=0), _dummy_factory)
    assert o.scale("svc", 5) == 5
    assert o.scale("svc", 2) == 2
    assert len(o.instances("svc")) == 2
    # the stored spec tracks the scaled replica count
    assert o.services["svc"].spec.replicas == 2
    # autoscale from queue depth
    n = o.autoscale("svc", queue_depth=17, per_instance=4, max_n=8)
    assert n == 5  # ceil(17/4)
    # unknown services can't scale — specs are the only entry point
    with pytest.raises(PlacementError):
        o.scale("ghost", 3)


# ------------------------------------------------------------------ manager
def test_manager_routes_heavy_and_light_end_to_end():
    o = _orch(LeastLoadedPolicy(), n=2, hbm=10 ** 12)
    mgr = ConfigurationManager(o)
    heavy_cfg = get_reduced_config("edge-cv-heavy", )
    light_cfg = get_reduced_config("edge-stream-light")
    scfg = stream_lib.StreamConfig(num_users=8, batch_records=16)
    router.assemble_edge_system(mgr, heavy_cfg=light_cfg, light_cfg=light_cfg,
                                scfg=scfg)

    # stream workload → unikernel-class
    state = stream_lib.init_state(scfg)
    batch = next(stream_lib.make_record_stream(scfg))
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    w = Workload("fitbit", WorkloadKind.STREAM)
    res = mgr.submit(w, (state, batch))
    assert res.workload_class == WorkloadClass.LIGHT
    assert "unikernel" in res.executor_name
    (new_state, out) = res.output
    avg, mx, am = stream_lib.reference_analytics(
        {k: np.asarray(v) for k, v in batch.items()}, scfg.num_users)
    np.testing.assert_allclose(np.asarray(out["max_avg_steps"]), mx,
                               rtol=1e-5)

    # train workload → container-class
    toks = jnp.zeros((2, 16), jnp.int32)
    from repro.optim import adamw
    from repro.launch import programs
    from repro.models.model import build_model
    params = build_model(light_cfg).init(jax.random.key(0))
    # (the container builder creates its own params; just verify routing)
    w2 = Workload("train", WorkloadKind.TRAIN, light_cfg, batch=2, seq_len=16)
    opt = adamw.init_state(params, programs.TrainConfig().adamw)
    res2 = mgr.submit(w2, (opt, {"tokens": toks, "labels": toks}))
    assert res2.workload_class == WorkloadClass.HEAVY
    assert "container" in res2.executor_name
    rep = mgr.report()
    assert rep["heavy"]["count"] == 1 and rep["light"]["count"] == 1


# ---------------------------------------------------------------- capacity
def test_node_capacity_reads_accelerator_devices():
    from types import SimpleNamespace

    from repro.core.resources import PEAK_FLOPS_BY_KIND

    chip = SimpleNamespace(device_kind="TPU v5 lite",
                           memory_stats=lambda: {"bytes_limit": 1000})
    cap = NodeCapacity.of_devices([chip, chip])
    assert (cap.chips, cap.hbm_bytes) == (2, 2000)
    assert cap.flops_per_s == 2 * PEAK_FLOPS_BY_KIND["TPU v5 lite"]


def test_node_capacity_refuses_device_without_known_peak():
    """The CPU backend (and any unlisted kind) has no capacity to read: a
    node there must be given one explicitly, never a v5e default."""
    from repro.core import EdgeSystem

    with pytest.raises(ValueError, match="explicit NodeCapacity"):
        NodeCapacity.of_devices(jax.devices()[:1])
    with pytest.raises(ValueError, match="explicit NodeCapacity"):
        EdgeSystem().add_node("n0")
