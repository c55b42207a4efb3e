"""The engine's host spans on the profiler's clock, per-token times and
warm-up: the loop thread's ``engine.*`` spans never overlap and every tick
carries its admit, decode and sync spans; ``Request.token_times`` follows
``generated`` through plain, speculative and preempted-then-requeued
decoding; a warmed engine compiles nothing while it serves."""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.serving.engine import ServingEngine

LOOP_SPANS = {"engine.wait", "engine.admit", "engine.prefill",
              "engine.pages", "engine.decode", "engine.sync",
              "engine.finish"}


@pytest.fixture(scope="module")
def cfg(exact_config):
    return exact_config("tinyllama-1.1b")


def _engine_spans(log_dir):
    """``engine.*`` host events of the one trace under ``log_dir``, per
    thread: [[(name, start_ns, end_ns, stats), ...], ...]."""
    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    plane = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    out = []
    for line in plane.lines:
        evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                dict(ev.stats)) for ev in line.events
               if ev.name.startswith("engine.")]
        if evs:
            out.append(sorted(evs, key=lambda e: e[1]))
    return out


def test_loop_spans_are_flat_and_mark_every_tick(cfg, tmp_path):
    eng = ServingEngine(cfg, max_slots=4, max_seq=128, page_size=16)
    eng.warmup()
    rng = np.random.default_rng(5)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with eng:
            hs = [eng.submit(rng.integers(0, cfg.vocab_size, size=n),
                             max_new_tokens=m)
                  for n, m in ((20, 6), (40, 9), (12, 4))]
            for h in hs:
                h.result(timeout=120.0)
    finally:
        jax.profiler.stop_trace()
    threads = _engine_spans(tmp_path)
    loop = [evs for evs in threads
            if {e[0] for e in evs} & LOOP_SPANS]
    assert len(loop) == 1, "engine phase spans came from several threads"
    (loop,) = loop
    assert {e[0] for e in loop} <= LOOP_SPANS
    for a, b in zip(loop, loop[1:]):
        assert a[2] <= b[1], f"{a[0]} overlaps {b[0]}"
    by_tick = {}
    for name, _s, _e, stats in loop:
        by_tick.setdefault(stats["tick"], set()).add(name)
    assert eng.ticks > 0
    for t in range(eng.ticks):
        assert {"engine.admit", "engine.decode",
                "engine.sync"} <= by_tick.get(t, set()), t
    prefill = [st for name, _s, _e, st in loop if name == "engine.prefill"]
    assert sorted(st["rid"] for st in prefill) == [h.rid for h in hs]
    assert all({"start", "tokens", "bucket"} <= set(st) for st in prefill)
    submits = [e for evs in threads for e in evs
               if e[0] == "engine.submit"]
    assert sorted(e[3]["rid"] for e in submits) == [h.rid for h in hs]


def _assert_token_times(reqs):
    for r in reqs:
        assert not r.error and r.done
        assert len(r.token_times) == len(r.generated)
        assert all(a <= b for a, b in zip(r.token_times, r.token_times[1:]))
        assert r.token_times[0] == r.first_token_at
        assert r.token_times[-1] == r.finished_at


def test_token_times_through_preemption_and_running_counters(cfg):
    """Two long decoders oversubscribe an 11-page pool, so growth requeues
    the best-effort one; its times restart with its tokens."""
    eng = ServingEngine(cfg, max_slots=2, max_seq=64, page_size=8,
                        num_pages=11)
    rng = np.random.default_rng(7)
    eng.submit(rng.integers(0, cfg.vocab_size, size=24), max_new_tokens=24,
               qos="guaranteed")
    eng.submit(rng.integers(0, cfg.vocab_size, size=24), max_new_tokens=24,
               qos="best-effort")
    done = eng.run_until_drained()
    assert eng.preemptions > 0
    _assert_token_times(done)
    # the totals are counters since start, not sums over the tick window
    st = eng.stats()
    eng._tick_log.clear()
    again = eng.stats()
    for key in ("decode_tokens_committed", "max_prefill_tokens_tick"):
        assert again[key] == st[key] > 0
    # every token after each request's first came from a decode tick
    # (a requeued request's discarded tokens were committed too)
    assert st["decode_tokens_committed"] >= sum(len(r.generated) - 1
                                                for r in done)


def test_token_times_on_speculative_ticks(exact_config):
    cfg = exact_config("tinyllama-1.1b")
    dcfg = exact_config("tinyllama-1.1b", num_layers=1, num_heads=1,
                        num_kv_heads=1, d_ff=32)
    eng = ServingEngine(cfg, max_slots=3, max_seq=64, seed=0,
                        draft_cfg=dcfg, spec_k_max=3)
    rng = np.random.default_rng(11)
    for n in (9, 14, 5):
        eng.submit(rng.integers(0, cfg.vocab_size, size=n),
                   max_new_tokens=12)
    done = eng.run_until_drained()
    assert eng.spec_rounds > 0
    _assert_token_times(done)


def test_warmed_engine_compiles_nothing_while_serving(cfg):
    """Prompts of one and of two chunks, decode across page boundaries and
    requests that finish (releasing their rows): every program was built
    in ``warmup()``."""
    jax.clear_caches()      # what earlier tests compiled counts for nothing
    eng = ServingEngine(cfg, max_slots=4, max_seq=128, page_size=16)
    eng.warmup()
    compiles = []

    def on_duration(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        rng = np.random.default_rng(0)
        with eng:
            hs = [eng.submit(rng.integers(0, cfg.vocab_size, size=n),
                             max_new_tokens=m)
                  for n, m in ((40, 30), (100, 20), (7, 3))]
            done = [h.result(timeout=120.0) for h in hs]
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert all(not r.error for r in done)
    assert compiles == []
