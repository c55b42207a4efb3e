"""Paper Fig. 5 — the SAME data-science task on container vs unikernel.

The paper's headline number: the unikernel runs the Fitbit job in 45 MB vs
the container's 71 MB — a 36.6% memory saving — while the container
processes faster (fig 6c vs 6b).  Analogue here:

  container-class : general executor — fp32 state, no donation, and it
                    keeps compiled variants for every record-batch shape it
                    has ever seen (generality costs memory);
  unikernel-class : one AOT image — bf16 state, donated buffers, exactly
                    one frozen shape.

We measure real compiled-artifact footprints (memory_analysis) and
dispatch times, and report the saving percentage next to the paper's 36.6%.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import csv_line, stats_suffix, time_samples
from repro.core import (ContainerExecutor, DispatchStats, ExecutableImage,
                        UnikernelExecutor, Workload, WorkloadKind)
from repro.data import stream as stream_lib

PAPER_SAVING = 36.6


def run() -> list[str]:
    scfg = stream_lib.StreamConfig(num_users=64, batch_records=256)
    w = Workload("fitbit", WorkloadKind.STREAM)
    rows = []

    # ---------------- container-class: general, fp32, multi-shape
    state32 = stream_lib.init_state(scfg)
    shapes = [256, 128, 64]            # it has served many batch sizes
    footprint_c = 0
    fns = {}
    for n in shapes:
        rec = {k: jnp.asarray(v[:n]) for k, v in
               next(stream_lib.make_record_stream(scfg)).items()}
        lowered = jax.jit(stream_lib.analytics_step).lower(state32, rec)
        comp = lowered.compile()
        ma = comp.memory_analysis()
        footprint_c += ma.argument_size_in_bytes + ma.temp_size_in_bytes + \
            ma.output_size_in_bytes
        fns[n] = comp
    rec = {k: jnp.asarray(v) for k, v in
           next(stream_lib.make_record_stream(scfg)).items()}
    walls_c, _ = time_samples(lambda: fns[256](state32, rec), iters=20)
    stats_c = DispatchStats.from_walls("fig5/container", walls_c,
                                       workload_class="light",
                                       executor_class="container",
                                       footprint_bytes=footprint_c)
    us_c = sum(walls_c) / len(walls_c) * 1e6
    rows.append(csv_line("fig5/container", us_c,
                         f"footprint={footprint_c};"
                         f"{stats_suffix(stats_c, 'light')}"))

    # ---------------- unikernel-class: one donated bf16 image
    state16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                           stream_lib.init_state(scfg))

    def analytics_bf16(state, batch):
        s32 = jax.tree.map(lambda x: x.astype(jnp.float32), state)
        new_state, out = stream_lib.analytics_step(s32, batch)
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16), new_state), out

    img = ExecutableImage.build("uk", analytics_bf16, (state16, rec),
                                donate_argnums=(0,))
    ex = UnikernelExecutor("unikernel", img)
    cur = {"state": jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                                 stream_lib.init_state(scfg))}

    def once():
        cur["state"], out = ex.dispatch(w, (cur["state"], rec))
        return out
    walls_u, _ = time_samples(once, iters=20)
    footprint_u = img.footprint_bytes + img.output_bytes
    stats_u = DispatchStats.from_walls("fig5/unikernel", walls_u,
                                       workload_class="light",
                                       executor_class="unikernel",
                                       footprint_bytes=footprint_u)
    us_u = sum(walls_u) / len(walls_u) * 1e6
    saving = 100.0 * (1.0 - footprint_u / footprint_c)
    rows.append(csv_line("fig5/unikernel", us_u,
                         f"footprint={footprint_u};saving_pct={saving:.1f};"
                         f"paper_saving_pct={PAPER_SAVING};"
                         f"{stats_suffix(stats_u, 'light')}"))

    # ---------------- overlapped vs serialized dispatch through the system
    # the same stream task, declared as a 2-replica unikernel service;
    # concurrent submit_many has every item in flight before collecting,
    # serialized drains one at a time.  Each item carries its OWN state so
    # the image's donated buffers are never re-dispatched.
    from repro.core import (EdgeSystem, ExecutorClass, NodeCapacity,
                            ServiceSpec, WorkloadClass)
    from repro.serving.router import make_stream_builder

    system = EdgeSystem()
    node = NodeCapacity(hbm_bytes=16 << 30)      # logical edge nodes
    system.add_node("edge0", node).add_node("edge1", node)
    system.register_builder("stream", WorkloadClass.LIGHT,
                            make_stream_builder(system.registry, scfg))
    system.apply(ServiceSpec(name="stream-analytics", workload=w,
                             executor_class=ExecutorClass.UNIKERNEL,
                             replicas=2))
    n_items = 8

    def batch(tag):
        return [(Workload(f"{tag}{i}", WorkloadKind.STREAM),
                 (stream_lib.init_state(scfg), rec)) for i in range(n_items)]

    import time as _time
    t = _time.perf_counter()
    system.submit_many(batch("ser"), speculative=False, concurrent=False)
    ser_rps = n_items / (_time.perf_counter() - t)
    t = _time.perf_counter()
    system.submit_many(batch("par"), speculative=False, concurrent=True)
    par_rps = n_items / (_time.perf_counter() - t)
    rows.append(csv_line("fig5/overlap", 1e6 / par_rps,
                         f"serial_rps={ser_rps:.0f};"
                         f"overlap_rps={par_rps:.0f};"
                         f"overlap_speedup={par_rps / ser_rps:.2f}x;"
                         f"{stats_suffix(system.stats, 'light')}"))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
