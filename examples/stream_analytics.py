"""The paper's unikernel workload: Fitbit-style stream analytics on a
single-purpose AOT executable with donated state — declared as a
``ServiceSpec`` and dispatched through the ``EdgeSystem``.

    PYTHONPATH=src python examples/stream_analytics.py
"""
import sys
import time

sys.path.insert(0, "src")

import jax.numpy as jnp

from repro.core import (EdgeSystem, ExecutorClass, NodeCapacity,
                        ServiceSpec, Workload, WorkloadClass, WorkloadKind)
from repro.data import stream as stream_lib
from repro.serving.router import make_stream_builder


def main():
    scfg = stream_lib.StreamConfig(num_users=32, batch_records=64)

    system = EdgeSystem()
    node = NodeCapacity(hbm_bytes=16 << 30)      # logical edge nodes
    system.add_node("edge0", node).add_node("edge1", node)
    system.register_builder("stream", WorkloadClass.LIGHT,
                            make_stream_builder(system.registry, scfg))

    t0 = time.monotonic()
    (dep,) = system.apply(ServiceSpec(
        name="fitbit-analytics",
        workload=Workload("fitbit", WorkloadKind.STREAM),
        executor_class=ExecutorClass.UNIKERNEL))
    print(f"built unikernel image in {time.monotonic() - t0:.2f}s "
          f"(footprint {dep.footprint} bytes) on {dep.node_id}")

    state = stream_lib.init_state(scfg)
    records = stream_lib.make_record_stream(scfg)
    for i in range(8):
        rec = {k: jnp.asarray(v) for k, v in next(records).items()}
        res = system.submit(Workload(f"batch{i}", WorkloadKind.STREAM),
                            (state, rec))
        state, out = res.output
        print(f"batch {i}: max_avg_steps={float(out['max_avg_steps']):8.1f} "
              f"(user {int(out['argmax_user'])}) "
              f"[{res.wall_s * 1e3:.1f} ms on {res.node_id}]")

    # cached: scaling up pulls the image from the registry, no rebuild
    t1 = time.monotonic()
    system.scale("fitbit-analytics", 2)
    print(f"scale-up image pull: {time.monotonic() - t1:.4f}s "
          f"(registry {system.registry.stats()})")

    rep = system.report()
    print(f"light dispatches: count={rep['light']['count']} "
          f"p95={rep['light']['p95_wall_s'] * 1e3:.1f}ms")


if __name__ == "__main__":
    main()
