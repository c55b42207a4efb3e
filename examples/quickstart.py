"""Quickstart — train a model, then serve it through the EdgeSystem.

The whole runtime sits behind two declarative objects:

``ServiceSpec`` — WHAT to run: a name, a workload template, an optional
executor-class override (container vs unikernel), replicas, placement
policy, latency SLO, and an optional footprint hint.

``EdgeSystem`` — the facade that owns the configuration manager,
orchestrator, image registry and work queue.  The core loop is:

    from repro.core import (EdgeSystem, ServiceSpec, Workload,
                            WorkloadKind, WorkloadClass)

    system = EdgeSystem()                      # 1. build the system
    system.add_node("edge0")                   # 2. register nodes (capacity
                                               #    read from the device)
    system.register_builder(kind, wclass, builder)   # 3. teach it to build
    system.apply(ServiceSpec(name="svc", workload=..., replicas=2))
    result = system.submit(workload, args)     # routed, least-inflight
    results = system.submit_many(items)        # batched + speculative
    system.scale("svc", 4)                     # redeploys from the spec
    print(system.report())                     # DispatchStats percentiles

Below: train a tiny LM for a few steps, deploy the trained params as a
continuous-batching serving service via a spec, and submit prompts.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

import numpy as np

from repro.configs import get_reduced_config
from repro.core import (EdgeSystem, ExecutorClass, NodeCapacity,
                        ServiceSpec, Workload, WorkloadClass, WorkloadKind)
from repro.data.tokens import make_lm_iterator
from repro.launch.mesh import make_test_mesh
from repro.serving.router import make_engine_builder
from repro.train.trainer import Trainer, TrainerConfig


def main():
    # 1) pick an architecture (any of the 10 assigned, reduced for CPU)
    cfg = get_reduced_config("tinyllama-1.1b", num_layers=2, d_model=64,
                             head_dim=16, d_ff=128, vocab_size=128)
    print(f"arch={cfg.name} params={cfg.num_params():,}")

    # 2) train a few steps
    from repro.launch.programs import TrainConfig
    from repro.optim import adamw, schedule
    tcfg = TrainConfig(adamw=adamw.AdamWConfig(lr=3e-3),
                       sched=schedule.ScheduleConfig(warmup_steps=5,
                                                     decay_steps=200))
    trainer = Trainer(cfg, make_test_mesh(), tcfg,
                      run_cfg=TrainerConfig(ckpt_dir="/tmp/quickstart_ckpt",
                                            ckpt_every=0))
    trainer.initialize(restore=False)
    data = make_lm_iterator(cfg, batch_size=8, seq_len=32)
    hist = trainer.fit(data, num_steps=20)
    print(f"loss: {hist['loss'][0]:.3f} -> {hist['loss'][-1]:.3f}")

    # 3) declare the serving service and submit prompts through the system
    system = EdgeSystem()
    system.add_node("edge0", NodeCapacity(hbm_bytes=16 << 30))  # logical
    system.register_builder(
        "decode", WorkloadClass.HEAVY,
        make_engine_builder(cfg, max_slots=2, max_seq=64,
                            params=trainer.params))
    system.apply(ServiceSpec(
        name="lm-serving",
        workload=Workload("serve", WorkloadKind.DECODE, cfg, batch=2,
                          seq_len=8),
        executor_class=ExecutorClass.CONTAINER))

    for plen in (8, 5):
        w = Workload(f"prompt{plen}", WorkloadKind.DECODE, cfg, batch=1,
                     seq_len=8)
        res = system.submit(w, (np.arange(plen) % cfg.vocab_size,))
        req = res.output
        print(f"request {req.rid} on {res.node_id}: "
              f"generated {req.generated}")
    rep = system.report()
    # tiny decode requests classify LIGHT even though the spec overrode the
    # substrate to container-class — telemetry buckets by classification
    served = rep["light"] or rep["heavy"]
    print(f"served: {served['count']} requests, "
          f"p95 wall {served['p95_wall_s'] * 1e3:.1f}ms")


if __name__ == "__main__":
    main()
