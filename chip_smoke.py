"""Chip smoke run: the hybrid edge runtime's main path on a TPU, end to end.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # fleet path on a four-chip host

One chip (default).  One ``EdgeSystem`` node holds both halves of the
paper's split: a GUARANTEED ``llm-serving`` spec on the container-class
``ServingEngine`` (tinyllama-1.1b at its published widths and depth,
random weights from ``--seed``; paged bf16 KV, page 16, prefix sharing on,
8 slots, max_seq 1024) and the ``fitbit-analytics`` stream spec on a
unikernel-class AOT image.  After ``warmup()`` the engine's background
loop serves 8 requests (64-700 prompt tokens, two sharing a 256-token
prefix, 32 new tokens each) and ``system.submit`` dispatches 8 stream
batches.  Two short phases keep the published widths at 2 layers: int8 KV
pages, and speculative decoding with a 1-layer draft (4 requests each).

Checks, each fatal:
  * every generated token's logit under a float32 reference forward
    (``ops.set_impl("ref")``, ``default_matmul_precision("highest")``,
    teacher-forced with the engine's tokens) lies within the phase's
    tolerance of that position's max logit;
  * every stream output matches ``analytics_step`` run on the host CPU;
  * no failed request, no incomplete handle, speculation never disabled;
  * every served decode, prefill-chunk and verify program contains a
    Pallas kernel (``tpu_custom_call``).

Four chips (``--chips 4``).  Only the fleet path: ``deploy_fleet`` places
4 full-width replicas, one per chip (asserted from the arrays' devices);
the ``FleetRouter`` serves the same 8 requests, whose tokens must equal
those of the same requests served by one replica alone.

Times printed are smoke readings, not benchmark measurements.  The script
needs an accelerator: without one it exits nonzero.  The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "tinyllama-1.1b"
SLOTS, MAX_SEQ, MAX_NEW = 8, 1024, 32
SHORT_SLOTS, SHORT_MAX_SEQ, SHORT_REQUESTS = 4, 256, 4
# Largest allowed gap between the f32 reference's max logit and its logit
# for the engine's token.  The engine computes in bf16 and emits bf16
# logits: near the top (|logit| ~ 4 for this init, std ~ 1) the bf16 grid
# alone is 2^-5 apart, and 22 layers of bf16 rounding move logits by a few
# hundredths more, so a near-tie may flip.  A wrong kernel picks tokens
# whose reference gap is of the order of the logit spread (~4).
LOGIT_TOL = 0.25
STREAM_RTOL, STREAM_ATOL = 1e-5, 1e-3


class SmokeFailure(RuntimeError):
    pass


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def make_prompts(rng, vocab: int, n: int, lo: int, hi: int,
                 shared: int = 0):
    """``n`` prompts of lo..hi tokens; the first two share ``shared``
    leading tokens when ``shared`` > 0."""
    lens = rng.integers(lo, hi + 1, size=n)
    prompts = [rng.integers(0, vocab, size=int(n_), dtype=np.int32)
               for n_ in lens]
    if shared:
        prefix = rng.integers(0, vocab, size=shared, dtype=np.int32)
        for i in (0, 1):
            tail = rng.integers(0, vocab, size=int(lens[i]) % 64 + 16,
                                dtype=np.int32)
            prompts[i] = np.concatenate([prefix, tail])
    return prompts


def serve(engine, prompts, max_new: int, timeout_s: float):
    """Submit everything up front to the engine's background loop and
    block on the handles; returns (requests, wall seconds)."""
    t0 = time.monotonic()
    engine.start()
    handles = [engine.submit(p, max_new_tokens=max_new, qos="guaranteed")
               for p in prompts]
    done = []
    for h in handles:
        try:
            done.append(h.result(timeout=timeout_s))
        except Exception as e:  # noqa: BLE001 — reported as the failure
            raise SmokeFailure(f"request {h.rid} did not complete: "
                               f"{type(e).__name__}: {e}") from e
    wall = time.monotonic() - t0
    require(not engine.failed,
            f"engine.failed is not empty: "
            f"{ {r: q.error for r, q in engine.failed.items()} }")
    require(all(h.done() for h in handles), "a handle did not complete")
    return done, wall


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def reference_margins(cfg, params, requests, length: int):
    """Teacher-forced float32 reference: for each request, the gap between
    the reference's max logit and its logit for each generated token."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models.model import build_model

    model = build_model(dataclasses.replace(cfg, compute_dtype="float32"))

    def gaps(params, tokens, pos, chosen):
        logits, _ = model.forward(params, {"tokens": tokens})
        rows = logits[0, pos].astype(jnp.float32)          # [n, V]
        picked = jnp.take_along_axis(rows, chosen[:, None], axis=1)[:, 0]
        return jnp.max(rows, axis=1) - picked, jnp.std(rows)

    fn = jax.jit(gaps)
    out = []
    ops.set_impl("ref")
    try:
        with jax.default_matmul_precision("highest"):
            for r in requests:
                gen = np.asarray(r.generated, np.int32)
                seq = np.concatenate([r.prompt, gen[:-1]])
                tokens = np.zeros((1, length), np.int32)
                tokens[0, :seq.size] = seq
                pos = np.arange(gen.size) + r.prompt.size - 1
                g, spread = fn(params, tokens, pos, gen)
                out.append((np.asarray(g), float(spread)))
    finally:
        ops.set_impl(None)
    return out


def check_llm(phase: str, cfg, params, requests, length: int) -> None:
    tol = LOGIT_TOL
    res = reference_margins(cfg, params, requests, length)
    margins = np.concatenate([g for g, _ in res])
    worst = float(margins.max())
    agree = int((margins == 0.0).sum())
    line = (f"phase {phase}: {'PASS' if worst <= tol else 'FAIL'} "
            f"{len(requests)}/{len(requests)} requests, {margins.size} "
            f"tokens; tolerance {tol} logits, worst margin {worst:.6f}, "
            f"reference argmax agreement {agree}/{margins.size}, "
            f"reference logit std {np.mean([s for _, s in res]):.4f}")
    print(line, flush=True)
    require(worst <= tol, f"phase {phase}: worst margin {worst} > {tol}")


def check_kernels(name: str, programs) -> None:
    """``programs``: (label, jitted fn, args, kwargs) of served programs;
    lowering with the served arguments reuses the compiled executable."""
    for label, fn, args, kw in programs:
        text = fn.lower(*args, **kw).compile().as_text()
        require("tpu_custom_call" in text,
                f"{name}: served {label} program has no tpu_custom_call")
    print(f"kernels {name}: tpu_custom_call in "
          f"{', '.join(p[0] for p in programs)}", flush=True)


def engine_programs(engine):
    """The engine's served decode and prefill-chunk programs (plus verify
    and the draft's propose when speculating), with served arguments."""
    import jax.numpy as jnp

    S = engine.max_slots
    inactive = jnp.zeros((S,), bool)
    zero1 = jnp.zeros((1,), jnp.int32)
    b = engine.chunk_tokens                  # every prompt's first chunk
    span = engine._kv_span_pages(b)
    row = jnp.zeros((1, engine.kv.pages_per_slot), jnp.int32)
    progs = [
        ("decode", engine._decode,
         (engine.params, engine.kv.pools, engine.kv.page_table,
          engine.last_tokens, engine.kv.cache_len, inactive), {}),
        (f"prefill-chunk[{b}]", engine._chunk,
         (engine.params, engine.kv.pools, jnp.zeros((1, b), jnp.int32),
          row[:, :span], zero1, zero1), {}),
    ]
    if engine._draft is not None:
        k = engine.spec_k_max
        draft = engine._draft
        progs += [
            (f"verify[k={k}]", engine._verify,
             (engine.params, engine.kv.pools, engine.kv.page_table,
              jnp.zeros((S, k + 1), jnp.int32), engine.kv.cache_len,
              engine.last_tokens, inactive), {}),
            (f"draft-propose[k={k}]", draft._propose,
             (draft.params, draft.kv.caches, engine.last_tokens,
              draft.kv.cache_len, inactive), {"k": k}),
        ]
    return progs


def smoke_reading(name: str, requests, wall: float) -> None:
    toks = sum(len(r.generated) for r in requests)
    ttft = sorted((r.first_token_at - r.submitted_at) * 1e3
                  for r in requests)
    print(f"smoke reading {name} (not a benchmark): {toks} tokens in "
          f"{wall:.3f}s = {toks / wall:.1f} tok/s; TTFT ms "
          f"min {ttft[0]:.1f} median {ttft[len(ttft) // 2]:.1f} "
          f"max {ttft[-1]:.1f}", flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts backend compiles and persistent-cache hits via jax.monitoring
    (a cache hit is also reported as a backend compile event)."""

    def __init__(self):
        import jax

        self.events = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.events, self.hits


def main_phase(cfg, seed: int, counter: CompileCounter) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import (EdgeSystem, ExecutorClass, QoSClass,
                            ServiceSpec, Workload, WorkloadClass,
                            WorkloadKind)
    from repro.data import stream as stream_lib
    from repro.serving.kv_cache import _tree_bytes
    from repro.serving.router import make_engine_builder, make_stream_builder

    system = EdgeSystem()
    system.add_node("edge0")
    cap = system.orchestrator.nodes["edge0"].capacity
    print(f"node edge0: {cap.hbm_bytes} HBM bytes (memory_stats "
          f"bytes_limit), {cap.flops_per_s:.3e} peak FLOP/s", flush=True)
    scfg = stream_lib.StreamConfig(num_users=32, batch_records=64,
                                   seed=seed + 7)
    system.register_builder(
        "decode", WorkloadClass.HEAVY,
        make_engine_builder(cfg, max_slots=SLOTS, max_seq=MAX_SEQ,
                            seed=seed))
    system.register_builder("stream", WorkloadClass.LIGHT,
                            make_stream_builder(system.registry, scfg))
    (llm,) = system.apply(ServiceSpec(
        name="llm-serving",
        workload=Workload("serve", WorkloadKind.DECODE, cfg, batch=SLOTS,
                          seq_len=MAX_NEW),
        executor_class=ExecutorClass.CONTAINER, tenant="serving",
        qos=QoSClass.GUARANTEED))
    (stream_dep,) = system.apply(ServiceSpec(
        name="fitbit-analytics",
        workload=Workload("fitbit", WorkloadKind.STREAM),
        executor_class=ExecutorClass.UNIKERNEL))
    engine = llm.executor.engine
    print(f"llm-serving on {llm.node_id} ({llm.executor.executor_class.value}"
          f"); fitbit-analytics on {stream_dep.node_id} "
          f"({stream_dep.executor.executor_class.value})", flush=True)
    print(f"config {cfg.name}: layers {cfg.num_layers}, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}, kv heads "
          f"{cfg.num_kv_heads}, head_dim {cfg.head_dim_}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}; param bytes "
          f"{_tree_bytes(engine.params)} ({cfg.param_dtype}); KV "
          f"{engine.stats()['kv_dtype']} pages of {engine.kv.page_size}, "
          f"pool {engine.kv.capacity_bytes()} bytes", flush=True)

    try:
        c0 = counter.snapshot()
        engine.warmup()
        c1 = counter.snapshot()
        print(f"warmup: {engine.warmup_s:.3f}s, {c1[0] - c0[0]} programs "
              f"built ({c1[1] - c0[1]} from the persistent cache), "
              f"{len(engine.chunk_buckets)} chunk buckets x KV spans + "
              f"decode", flush=True)

        rng = np.random.default_rng(seed)
        prompts = make_prompts(rng, cfg.vocab_size, 8, 64, 700, shared=256)
        done, wall = serve(engine, prompts, MAX_NEW, timeout_s=600.0)
        c2 = counter.snapshot()
        print(f"served {len(done)}/8 requests via the background loop "
              f"({engine.ticks} ticks, prefix hits "
              f"{engine.kv_prefix_hits}, programs built in the window "
              f"{c2[0] - c1[0]})", flush=True)
        smoke_reading("llm bf16", done, wall)
    finally:
        engine.stop(drain=False)
    check_kernels("bf16", engine_programs(engine))
    check_llm("bf16", cfg, engine.params, done, MAX_SEQ)

    # ---- stream: the unikernel image vs the same step on the host CPU
    cpu = jax.devices("cpu")[0]
    host_step = jax.jit(stream_lib.analytics_step)
    state = stream_lib.init_state(scfg)
    host_state = jax.device_put(stream_lib.init_state(scfg), cpu)
    records = stream_lib.make_record_stream(scfg)
    for i in range(8):
        rec = next(records)
        res = system.submit(Workload(f"batch{i}", WorkloadKind.STREAM),
                            (state, {k: jnp.asarray(v)
                                     for k, v in rec.items()}))
        state, out = res.output
        host_state, want = host_step(host_state, jax.device_put(rec, cpu))
        for key in want:
            np.testing.assert_allclose(
                np.asarray(out[key]), np.asarray(want[key]),
                rtol=STREAM_RTOL, atol=STREAM_ATOL,
                err_msg=f"stream batch {i} output {key}")
        require(res.node_id == "edge0", "stream ran off the node")
    print(f"phase stream: PASS 8/8 batches on the unikernel image vs "
          f"analytics_step on the host CPU device (rtol {STREAM_RTOL}, "
          f"atol {STREAM_ATOL})", flush=True)


def short_phase(phase: str, cfg, seed: int, **engine_kw) -> None:
    """4 requests through a 2-layer full-width engine on the chip."""
    from repro.serving.engine import ServingEngine

    engine = ServingEngine(cfg, max_slots=SHORT_SLOTS,
                           max_seq=SHORT_MAX_SEQ, seed=seed, **engine_kw)
    rng = np.random.default_rng(seed + 1)
    prompts = make_prompts(rng, cfg.vocab_size, SHORT_REQUESTS, 64, 200)
    try:
        done, wall = serve(engine, prompts, MAX_NEW, timeout_s=600.0)
    finally:
        engine.stop(drain=False)
    st = engine.stats()
    require("spec_disabled_reason" not in st,
            f"speculation disabled: {st.get('spec_disabled_reason')}")
    if engine._draft is not None:
        require(st["spec_rounds"] > 0, "no speculative round ran")
        print(f"speculation: {st['spec_rounds']} verify rounds, "
              f"{st['spec_accepted']}/{st['spec_proposed']} draft tokens "
              f"accepted, never disabled", flush=True)
    smoke_reading(f"llm {phase}", done, wall)
    check_kernels(phase, engine_programs(engine))
    check_llm(phase, cfg, engine.params, done, SHORT_MAX_SEQ)


def fleet_phase(cfg, seed: int, devices) -> None:
    """Four one-chip replicas behind the FleetRouter vs one replica."""
    import jax
    from jax.sharding import Mesh

    from repro.core import EdgeSystem, WorkloadClass
    from repro.serving.router import fleet_service_spec, make_fleet_builder

    system = EdgeSystem()
    for i, dev in enumerate(devices):
        system.add_node(f"chip{i}", mesh=Mesh(np.array([dev]), ("replica",)))
    system.register_builder(
        "generic", WorkloadClass.HEAVY,
        make_fleet_builder(cfg, max_slots=SLOTS, max_seq=MAX_SEQ, seed=seed))
    router = system.deploy_fleet(fleet_service_spec(
        cfg, name="llm-fleet", replicas=len(devices),
        max_new_tokens=MAX_NEW))
    engines = [d.executor.engine for d in system.instances("llm-fleet")]

    def placement():
        out = []
        for e in engines:
            held = {d for x in jax.tree.leaves((e.params, e.kv.pools))
                    for d in x.devices()}
            require(held == {e.device},
                    f"replica {e.replica_id} arrays on {held}, "
                    f"expected {e.device}")
            out.append(e.device)
        require(len(set(out)) == len(devices),
                f"replicas share devices: {out}")
        return out

    try:
        for e, dev in zip(engines, placement()):
            print(f"replica {e.replica_id}: params and pools on {dev}",
                  flush=True)
        rng = np.random.default_rng(seed)
        prompts = make_prompts(rng, cfg.vocab_size, 8, 64, 700, shared=256)
        solo, wall = serve(engines[0], prompts, MAX_NEW, timeout_s=900.0)
        smoke_reading("one replica", solo, wall)
        engines[0].release_prefix_cache()

        t0 = time.monotonic()
        handles = [router.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
        fleet = [h.result(timeout=900.0) for h in handles]
        wall = time.monotonic() - t0
        require(all(h.done() for h in handles), "a fleet handle is open")
        for e in engines:
            require(not e.failed, f"replica {e.replica_id} failed requests")
        smoke_reading("fleet of 4", fleet, wall)
        per = {k: v["completed"] for k, v in router.stats()["replicas"]
               .items()}
        same = sum(a.generated == b.generated for a, b in zip(solo, fleet))
        print(f"fleet: 8/8 served, completions per replica {per}; tokens "
              f"identical to one replica for {same}/8 requests", flush=True)
        require(same == len(prompts), "fleet tokens differ from one replica")
        placement()
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from repro.configs import get_config
    from repro.launch import compile_cache

    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev0.platform!r}; "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    print(f"device: {dev0.device_kind} x{len(devices)} ({dev0.platform})",
          flush=True)
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    counter = CompileCounter()
    cfg = get_config(ARCH)
    t0 = time.monotonic()
    try:
        if args.chips == 4:
            fleet_phase(cfg, args.seed, devices[:4])
        else:
            main_phase(cfg, args.seed, counter)
            short = dataclasses.replace(cfg, num_layers=2)
            short_phase("int8", short, args.seed, kv_dtype="int8")
            short_phase("spec", short, args.seed,
                        draft_cfg=dataclasses.replace(cfg, num_layers=1))
    except (SmokeFailure, AssertionError) as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(f"all phases passed in {time.monotonic() - t0:.1f}s "
          f"({counter.events} compile events, {counter.hits} persistent "
          f"cache hits)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
