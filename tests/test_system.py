"""End-to-end behaviour of the hybrid edge system (the paper's fig 1 flow):
mixed workloads arrive → configuration manager classifies and routes →
container/unikernel executors on orchestrated nodes → node failure mid-run
→ failover → work completes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import (EdgeSystem, ExecutorClass, LeastLoadedPolicy,
                        NodeCapacity, ServiceSpec, Workload, WorkloadClass,
                        WorkloadKind)
from repro.data import stream as stream_lib
from repro.serving import router


def _system(n_nodes=3):
    system = EdgeSystem(policy=LeastLoadedPolicy())
    for i in range(n_nodes):
        system.add_node(f"edge{i}",
                        NodeCapacity(chips=1, hbm_bytes=10 ** 12))
    light_cfg = get_reduced_config("edge-stream-light")
    scfg = stream_lib.StreamConfig(num_users=8, batch_records=16)
    router.assemble_edge_system(system, heavy_cfg=light_cfg,
                                light_cfg=light_cfg, scfg=scfg)
    return system, system.orchestrator, light_cfg, scfg


def test_mixed_workloads_route_and_complete():
    mgr, orch, cfg, scfg = _system()
    gen = stream_lib.make_record_stream(scfg)
    state = stream_lib.init_state(scfg)

    light_results, heavy_results = [], []
    # interleave: stream records (light) + prefill requests (heavy-by-kind
    # via generic container) like the paper's image-vs-stream mix
    from repro.models.model import build_model
    for i in range(4):
        rec = {k: jnp.asarray(v) for k, v in next(gen).items()}
        res = mgr.submit(Workload(f"stream{i}", WorkloadKind.STREAM),
                         (state, rec))
        state, out = res.output
        light_results.append(res)

        w = Workload(f"train{i}", WorkloadKind.TRAIN, cfg, batch=2,
                     seq_len=16)
        from repro.launch import programs
        from repro.optim import adamw
        params = build_model(cfg).init(jax.random.key(0))
        opt = adamw.init_state(params, programs.TrainConfig().adamw)
        toks = jnp.zeros((2, 16), jnp.int32)
        res2 = mgr.submit(w, (opt, {"tokens": toks, "labels": toks}))
        heavy_results.append(res2)

    assert all(r.workload_class == WorkloadClass.LIGHT
               for r in light_results)
    assert all(r.workload_class == WorkloadClass.HEAVY
               for r in heavy_results)
    # instances were REUSED after first deploy (continuous serving)
    assert sum(r.deployed_fresh for r in light_results) == 1
    assert sum(r.deployed_fresh for r in heavy_results) == 1
    # both classes live on registered nodes, resources accounted
    rep = mgr.report()
    assert rep["light"]["mean_footprint_bytes"] <= \
        rep["heavy"]["mean_footprint_bytes"]


def test_node_failure_mid_service_failover_and_continue():
    mgr, orch, cfg, scfg = _system(n_nodes=3)
    gen = stream_lib.make_record_stream(scfg)
    state = stream_lib.init_state(scfg)
    rec = {k: jnp.asarray(v) for k, v in next(gen).items()}
    res = mgr.submit(Workload("s0", WorkloadKind.STREAM), (state, rec))
    state, _ = res.output
    victim = res.node_id

    moved = orch.on_node_failure(victim)           # paper P4: redeploy
    assert moved, "instance should have been redeployed"
    assert orch.deployments[moved[0]].node_id != victim

    rec2 = {k: jnp.asarray(v) for k, v in next(gen).items()}
    res2 = mgr.submit(Workload("s1", WorkloadKind.STREAM), (state, rec2))
    assert res2.node_id != victim
    state, out = res2.output
    assert np.isfinite(float(out["max_avg_steps"]))


def test_elastic_scale_with_load():
    system, orch, cfg, scfg = _system(n_nodes=4)
    for i in range(20):
        system.queue.put((Workload(f"pending{i}", WorkloadKind.GENERIC),
                          ()))

    def builder(workload, mesh):
        from repro.core import ContainerExecutor
        ex = ContainerExecutor("svc", {"generic": lambda x: x}, mesh=mesh)
        return ex, 10 ** 6

    system.register_builder("generic", WorkloadClass.HEAVY, builder)
    system.apply(ServiceSpec(
        name="svc", workload=Workload("svc", WorkloadKind.GENERIC),
        executor_class=ExecutorClass.CONTAINER, replicas=1,
        footprint_hint=10 ** 6))
    n = system.autoscale("svc", per_instance=4, max_n=8)
    assert n == 5                                   # ceil(20/4)
    while system.queue.depth() > 4:
        system.queue.get()
    n = system.autoscale("svc", per_instance=4, min_n=1)
    assert n == 1                                   # scaled down: saves power


@pytest.mark.parametrize("env_dir", [None, "/var/cache/jax-example"])
def test_compile_cache_dir_prefers_environment(monkeypatch, env_dir):
    """Entry points cache compiles where JAX_COMPILATION_CACHE_DIR says,
    else at the fixed <repo>/.jax_cache — never a per-run path."""
    from repro.launch import compile_cache

    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = str(compile_cache.REPO_ROOT / ".jax_cache")
        assert (compile_cache.REPO_ROOT / "chip_smoke.py").exists()
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
        want = env_dir
    assert compile_cache.cache_dir() == want
