"""Paged KV data plane: kernel numerics vs ref, page-table allocator,
chunked prefill exactness per family, the per-tick prefill budget,
warmup state-neutrality, paged footprint accounting, and the
evicted-instance requeue control-plane follow-on."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.models.attention import _quantize
from repro.models.model import build_model
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PagedKVCache


def _rel_err(want, got):
    w = np.asarray(want, np.float32)
    g = np.asarray(got, np.float32)
    return np.max(np.abs(w - g)) / max(np.max(np.abs(w)), 1e-6)


def _tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 3.5e-2


# ---------------------------------------------------------------------------
# kernel numerics (interpret mode) vs the gather+dense oracle
# ---------------------------------------------------------------------------

PAGED_CASES = [
    # B, Hq, Hkv, D, page, MP, num_pages, window, softcap, cache_len (None:
    # random), all-zero table rows
    (2, 4, 2, 32, 16, 4, 11, 0, 0.0, None, ()),          # GQA
    (3, 8, 1, 64, 16, 8, 30, 0, 0.0, None, ()),          # MQA, more pages
    (1, 4, 4, 32, 32, 4, 9, 48, 0.0, None, ()),          # MHA + sliding window
    (2, 8, 2, 32, 16, 6, 15, 0, 20.0, None, ()),         # logit softcap
    (2, 16, 2, 128, 8, 4, 12, 0, 0.0, None, ()),         # MXU-wide head, small page
    # multi-page blocks (D a multiple of 128): 32 pages of 16 per block
    (2, 8, 2, 128, 16, 40, 90, 0, 0.0, (1, 640), ()),    # MP % ppb, len 1, MP·page
    (3, 8, 2, 128, 16, 40, 90, 0, 0.0, (300, 1, 517), (1,)),   # zero table row
    (3, 8, 2, 128, 16, 40, 90, 0, 0.0, (33, 200, 1), (1, 2)),  # zero rows, long
    (2, 8, 2, 128, 16, 80, 170, 300, 0.0, (1000, 250), ()),    # window mid-block
    (2, 8, 2, 128, 16, 528, 600, 0, 0.0, None, ()),      # the rag cell's table
    (2, 8, 2, 64, 16, 40, 90, 300, 0.0, (1, 640), (0,)),  # page walk: edges
]


def _paged_inputs(case, dtype):
    B, Hq, Hkv, D, page, MP, P, window, softcap, lens, zero_rows = case
    ks = jax.random.split(jax.random.key(B * 31 + MP), 5)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    kp = jax.random.normal(ks[1], (P, page, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (P, page, Hkv, D), dtype)
    table = jax.random.randint(ks[3], (B, MP), 0, P)
    for row in zero_rows:
        table = table.at[row].set(0)
    clen = (jax.random.randint(ks[4], (B,), 1, MP * page + 1) if lens is None
            else jnp.asarray(lens, jnp.int32))
    return q, kp, vp, table, clen, window, softcap


@pytest.mark.parametrize("case", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_kernel_vs_ref(case, dtype):
    q, kp, vp, table, clen, window, softcap = _paged_inputs(case, dtype)
    want = ref.paged_decode_attention(q, kp, vp, table, clen,
                                      window=window, softcap=softcap)
    got = paged_decode_attention(q, kp, vp, table, clen, window=window,
                                 softcap=softcap, interpret=True)
    assert _rel_err(want, got) < _tol(dtype)


@pytest.mark.parametrize("kv", ["bf16", "int8", "bf16_d64"])
def test_paged_decode_kernel_ignores_dead_pages(kv):
    """Pages the table references only past a row's live length hold NaN
    (int8: NaN and inf scale planes too); the kernel must neither read
    them into the result nor let ``0 · NaN`` through ``p·v``, and must
    still equal the oracle on the live context."""
    D = 64 if kv == "bf16_d64" else 128
    B, Hq, Hkv, page, MP = 3, 8, 2, 16, 40
    P = B * MP + 1
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (P, page, Hkv, D), jnp.float32)
    vp = jax.random.normal(ks[2], (P, page, Hkv, D), jnp.float32)
    # distinct pages per row; lengths end mid-page, mid-block and at 1
    table = np.arange(1, P).reshape(B, MP).astype(np.int32)
    clen = np.array([37, 555, 1], np.int32)
    live = {int(table[b, m]) for b in range(B)
            for m in range(-(-int(clen[b]) // page))}
    dead = np.array(sorted(set(table.ravel().tolist()) - live))
    scales = {}
    if kv == "int8":
        kp, k_s = _quantize(kp)
        vp, v_s = _quantize(vp)
        scales = dict(k_scale=k_s, v_scale=v_s)
        dirty = dict(k_scale=k_s.at[dead].set(jnp.nan),
                     v_scale=v_s.at[dead].set(jnp.inf))
    else:
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
        dirty = {}
    want = ref.paged_decode_attention(q, kp, vp, jnp.asarray(table),
                                      jnp.asarray(clen), **scales)
    if kv != "int8":
        kp = kp.at[dead].set(jnp.nan)
        vp = vp.at[dead].set(jnp.nan)
    got = paged_decode_attention(q, kp, vp, jnp.asarray(table),
                                 jnp.asarray(clen), interpret=True,
                                 **(dirty or scales))
    assert np.all(np.isfinite(np.asarray(got, np.float32)))
    assert _rel_err(want, got) < _tol(jnp.bfloat16)


def test_paged_ref_equals_dense_layout():
    """Scrambled physical pages gathered through the table must reproduce
    the dense-cache decode exactly (the paging is a pure relayout)."""
    B, Hq, Hkv, D, page, MP = 2, 4, 2, 32, 16, 4
    S = MP * page
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (B, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    clen = jax.random.randint(ks[3], (B,), 1, S + 1)
    # scatter the dense cache into distinct physical pages per sequence
    P = B * MP + 1
    kp = jnp.zeros((P, page, Hkv, D))
    vp = jnp.zeros((P, page, Hkv, D))
    table = np.zeros((B, MP), np.int32)
    pid = 1
    for b in range(B):
        for m in np.random.default_rng(b).permutation(MP):
            kp = kp.at[pid].set(k[b, m * page:(m + 1) * page])
            vp = vp.at[pid].set(v[b, m * page:(m + 1) * page])
            table[b, m] = pid
            pid += 1
    want = ref.decode_attention(q, k, v, clen)
    got = ref.paged_decode_attention(q, kp, vp, jnp.asarray(table), clen)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# page-table allocator
# ---------------------------------------------------------------------------

def _tiny_cfg(exact_config):
    return exact_config("tinyllama-1.1b")


def test_page_alloc_free_and_fragmentation(exact_config):
    cfg = _tiny_cfg(exact_config)
    kv = PagedKVCache(cfg, max_slots=3, max_seq=64, page_size=16,
                      num_pages=10)                  # 9 usable pages
    assert kv.pages_needed(1) == 1 and kv.pages_needed(17) == 2
    assert kv.pages_needed(10_000) == kv.pages_per_slot   # capped at max_seq
    a = kv.alloc(40)                                 # 3 pages
    b = kv.alloc(64)                                 # 4 pages
    assert a is not None and b is not None
    assert kv.pages_in_use() == 7
    assert 0 not in kv.slot_pages[a[0]] + kv.slot_pages[b[0]]  # trash page
    assert kv.alloc(40) is None                      # 2 pages left < 3
    assert kv.can_admit(30) and not kv.can_admit(40)
    c = kv.alloc(20)                                 # fits in the remainder
    assert c is not None and kv.pages_in_use() == 9
    # free the middle allocation: its pages return and are reused even
    # though the free list is now fragmented (non-contiguous ids)
    kv.free(b[0])
    assert kv.pages_in_use() == 5
    d = kv.alloc(60)
    assert d is not None and kv.pages_in_use() == 9
    assert len(kv.slot_pages[d[0]]) == 4     # served from the fragmented list
    # a freed slot's table row is zeroed → stale writes hit the trash page
    kv.free(a[0])
    assert int(jnp.sum(kv.page_table[a[0]])) == 0
    assert int(kv.cache_len[a[0]]) == 0
    # bytes accounting: in-use tracks pages, dense equivalent is fixed
    assert kv.bytes_in_use() == kv.pages_in_use() * kv._page_bytes
    assert kv.dense_equivalent_bytes() == \
        kv.max_slots * kv.pages_per_slot * kv._page_bytes


def test_page_pool_must_hold_one_sequence(exact_config):
    cfg = _tiny_cfg(exact_config)
    with pytest.raises(ValueError, match="trash page"):
        PagedKVCache(cfg, max_slots=2, max_seq=64, page_size=16, num_pages=4)


# ---------------------------------------------------------------------------
# engine exactness: chunked prefill + paged decode vs direct generation
# ---------------------------------------------------------------------------

def _oracle(model, params, prompt, n, max_seq):
    caches = model.init_caches(1, max_seq, dtype=jnp.float32)
    lg, caches, clen = model.prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, caches)
    out = [int(jnp.argmax(lg[0]))]
    for _ in range(n - 1):
        lg, caches = model.decode(params,
                                  jnp.asarray([out[-1]], jnp.int32),
                                  caches, clen)
        clen = clen + 1
        out.append(int(jnp.argmax(lg[0])))
    return out


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-2.7b",
                                  "zamba2-1.2b"])
def test_multi_chunk_prefill_matches_oracle(arch, exact_config):
    """Prompts longer than the chunk size stream in over several chunks
    (paged pages for dense attn; carried conv/ssm state for SSM/hybrid)
    and must reproduce the one-shot prefill exactly."""
    cfg = exact_config(arch)
    eng = ServingEngine(cfg, max_slots=2, max_seq=64, prefill_chunk=16,
                        prefill_budget=16)
    if arch == "tinyllama-1.1b":
        assert eng.paged
    else:
        assert not eng.paged and eng._chunkable_stateful
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n)
               for n in (40, 37, 5)]               # 3 reqs > 2 slots → churn
    for p in prompts:
        eng.submit(p, max_new_tokens=5)
    done = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    assert len(done) == 3
    for p, req in zip(prompts, done):
        assert req.chunks >= (3 if len(p) > 32 else 1)
        assert req.generated == _oracle(eng.model, eng.params, p, 5, 64)


def test_prefill_budget_bounds_tick(exact_config):
    """No tick may admit more prefill tokens than the budget allows (plus
    one tail chunk) — the invariant behind flat decode latency."""
    cfg = exact_config("tinyllama-1.1b")
    eng = ServingEngine(cfg, max_slots=4, max_seq=128, prefill_chunk=16,
                        prefill_budget=32)
    rng = np.random.default_rng(1)
    # short decoders + two long prompts arriving as a burst
    for n in (4, 6):
        eng.submit(rng.integers(0, cfg.vocab_size, size=n),
                   max_new_tokens=12)
    for _ in range(2):
        eng.submit(rng.integers(0, cfg.vocab_size, size=100),
                   max_new_tokens=3)
    done = eng.run_until_drained()
    assert len(done) == 4
    stats = eng.stats()
    assert stats["max_prefill_tokens_tick"] <= 32 + eng.chunk_tokens
    # the long prompts really did stream over multiple ticks
    long_reqs = [r for r in done if len(r.prompt) == 100]
    assert all(r.chunks >= 4 for r in long_reqs)


def test_pages_freed_after_drain_and_memory_below_dense(exact_config):
    cfg = exact_config("tinyllama-1.1b")
    eng = ServingEngine(cfg, max_slots=4, max_seq=128)
    rng = np.random.default_rng(2)
    handles = [eng.submit(rng.integers(0, cfg.vocab_size, size=20),
                          max_new_tokens=4) for _ in range(2)]
    eng.step()
    # half-full engine: pages-in-use well under the dense equivalent
    assert 0 < eng.kv.bytes_in_use() < eng.kv.dense_equivalent_bytes() // 2
    eng.run_until_drained()
    assert all(h.done() for h in handles)
    # after drain the only pages still held belong to the prefix radix
    # index (finished requests donate their prefixes for reuse); every
    # slot is back and an explicit cache release empties the pool
    assert eng.kv.pages_in_use() == eng.prefix.pages
    assert len(eng.kv.free_slots) == 4
    eng.release_prefix_cache()
    assert eng.kv.pages_in_use() == 0 and not eng.kv.page_refs


def test_warmup_is_state_neutral_and_idempotent(exact_config):
    cfg = exact_config("tinyllama-1.1b")
    eng = ServingEngine(cfg, max_slots=2, max_seq=64, prefill_chunk=16,
                        prefill_budget=16)
    eng.warmup().warmup()                   # idempotent
    assert eng._warm and eng.warmup_s >= 0.0
    assert eng.ticks == 0                   # warmup is not traffic
    p = np.random.default_rng(3).integers(0, cfg.vocab_size, size=40)
    eng.submit(p, max_new_tokens=5)
    (req,) = eng.run_until_drained()
    assert req.generated == _oracle(eng.model, eng.params, p, 5, 64)


def test_full_length_prompt_decode_does_not_corrupt_pages(exact_config):
    """A prompt of exactly max_seq tokens fills every logical page; the
    first decode's append lands past the table span and must be dropped
    to the trash page, not clamped into a live page (which would corrupt
    the cached KV mid-request)."""
    cfg = exact_config("tinyllama-1.1b")
    max_seq = 64
    eng = ServingEngine(cfg, max_slots=2, max_seq=max_seq)
    p = np.random.default_rng(5).integers(0, cfg.vocab_size, size=max_seq)
    eng.submit(p, max_new_tokens=8)
    (req,) = eng.run_until_drained()
    # engine stops at the cache boundary; the tokens it DID produce must
    # match the oracle (corruption would flip the post-prefill tokens)
    want = _oracle(eng.model, eng.params, p, len(req.generated), max_seq + 8)
    assert req.generated == want[:len(req.generated)]


def test_scale_down_does_not_resurrect_pending_redeploys():
    """Scale-down frees capacity and triggers the pending-redeploy drain;
    the drain must see the NEW replica target, not redeploy the instances
    being scaled away."""
    from repro.core import (ContainerExecutor, EdgeSystem, ExecutorClass,
                            NodeCapacity, QoSClass, ServiceSpec, Workload,
                            WorkloadClass, WorkloadKind)

    system = EdgeSystem()
    system.add_node("n0", NodeCapacity(chips=1, hbm_bytes=45,
                                       flops_per_s=1.0))

    def builder(workload, mesh):
        return ContainerExecutor("c", {"generic": lambda x: x},
                                 mesh=mesh), 10

    system.register_builder("generic", WorkloadClass.HEAVY, builder)
    be = ServiceSpec(name="be",
                     workload=Workload("w", WorkloadKind.GENERIC),
                     executor_class=ExecutorClass.CONTAINER, replicas=3,
                     footprint_hint=10, qos=QoSClass.BEST_EFFORT)
    system.apply(be)
    g = ServiceSpec(name="g",
                    workload=Workload("w2", WorkloadKind.GENERIC),
                    executor_class=ExecutorClass.CONTAINER, replicas=2,
                    footprint_hint=10, qos=QoSClass.GUARANTEED)
    system.apply(g)                          # preempts one BE instance
    assert len(system.instances("be")) == 2
    assert "be" in system.pending_redeploys
    assert system.scale("be", 1) == 1        # must NOT bounce back to 3
    assert len(system.instances("be")) == 1


def test_dense_fallback_paths_still_serve(exact_config):
    """paged=False forces the dense plane for a paged-capable arch, and
    SWA archs fall back automatically — both still match the oracle."""
    cfg = exact_config("tinyllama-1.1b")
    eng = ServingEngine(cfg, max_slots=2, max_seq=64, paged=False)
    assert not eng.paged
    p = np.random.default_rng(4).integers(0, cfg.vocab_size, size=20)
    eng.submit(p, max_new_tokens=4)
    (req,) = eng.run_until_drained()
    assert req.generated == _oracle(eng.model, eng.params, p, 4, 64)

    swa = exact_config("mixtral-8x7b", sliding_window=8)
    eng2 = ServingEngine(swa, max_slots=2, max_seq=64)
    assert not eng2.paged                    # ring cache keeps dense slots
    eng2.submit(p, max_new_tokens=3)
    (req2,) = eng2.run_until_drained()
    assert req2.generated == _oracle(eng2.model, eng2.params, p, 3, 64)


def test_engine_executor_paged_footprint(exact_config):
    from repro.serving.engine import EngineExecutor

    cfg = exact_config("tinyllama-1.1b")
    eng = ServingEngine(cfg, max_slots=4, max_seq=128)
    ex = EngineExecutor("e", eng, autostart=False)
    # static footprint covers params + the pool; dynamic starts at params
    assert ex.footprint_bytes() == \
        ex._params_bytes + eng.kv.capacity_bytes()
    assert ex.dynamic_footprint_bytes() == ex._params_bytes
    eng.submit(np.arange(30) % cfg.vocab_size, max_new_tokens=4)
    eng.step()
    assert ex.dynamic_footprint_bytes() > ex._params_bytes
    eng.run_until_drained()
    # the finished request donated its prefix to the radix, which keeps
    # those pages resident — the dynamic footprint charges them
    radix_bytes = eng.prefix.pages * eng.kv._page_bytes
    assert radix_bytes > 0
    assert ex.dynamic_footprint_bytes() == ex._params_bytes + radix_bytes
    eng.release_prefix_cache()
    assert ex.dynamic_footprint_bytes() == ex._params_bytes
    # an undersized pool really shrinks the static reservation
    small = ServingEngine(cfg, max_slots=4, max_seq=128,
                          num_pages=2 * (128 // 16) + 1)
    assert small.kv.capacity_bytes() < small.kv.dense_equivalent_bytes()


# ---------------------------------------------------------------------------
# evicted-instance requeue (control-plane follow-on)
# ---------------------------------------------------------------------------

def test_preempted_best_effort_requeues_when_capacity_frees():
    from repro.core import (ContainerExecutor, EdgeSystem, ExecutorClass,
                            NodeCapacity, QoSClass, ServiceSpec, Workload,
                            WorkloadClass, WorkloadKind)

    system = EdgeSystem()
    system.add_node("n0", NodeCapacity(chips=1, hbm_bytes=25,
                                       flops_per_s=1.0))
    evictions = []
    system.on_eviction(lambda inst, svc, node:
                       evictions.append((inst, svc, node)))

    def builder(workload, mesh):
        return ContainerExecutor("c", {"generic": lambda x: x},
                                 mesh=mesh), 10

    system.register_builder("generic", WorkloadClass.HEAVY, builder)
    be = ServiceSpec(name="be",
                     workload=Workload("w", WorkloadKind.GENERIC),
                     executor_class=ExecutorClass.CONTAINER, replicas=2,
                     footprint_hint=10, qos=QoSClass.BEST_EFFORT)
    system.apply(be)
    g = ServiceSpec(name="g",
                    workload=Workload("w2", WorkloadKind.GENERIC),
                    executor_class=ExecutorClass.CONTAINER, replicas=1,
                    footprint_hint=10, qos=QoSClass.GUARANTEED)
    system.apply(g)                          # preempts one BE instance
    assert len(system.instances("be")) == 1
    assert evictions == [("be/1", "be", "n0")]
    assert system.pending_redeploys == ["be"]

    # freeing capacity (scale the preemptor away) auto-heals the victim
    system.scale("g", 0)
    assert len(system.instances("be")) == 2
    assert system.pending_redeploys == []
    assert any(e.startswith("requeue be") for e in system.events)
    assert any(e.startswith("redeploy be/") for e in system.events)


def test_failed_preemption_drains_victims_back():
    """A preemptor that evicts victims and then still fails to fit must
    not strand them: their capacity is genuinely free and no later
    undeploy may ever arrive, so the refusal itself drains the queue."""
    import pytest

    from repro.core import (ContainerExecutor, EdgeSystem, ExecutorClass,
                            NodeCapacity, QoSClass, ServiceSpec, Workload,
                            WorkloadClass, WorkloadKind)
    from repro.core.orchestrator import PlacementError

    system = EdgeSystem()
    system.add_node("n0", NodeCapacity(chips=1, hbm_bytes=20,
                                       flops_per_s=1.0))

    def builder(workload, mesh):
        return ContainerExecutor("c", {"generic": lambda x: x},
                                 mesh=mesh), 10

    system.register_builder("generic", WorkloadClass.HEAVY, builder)
    be = ServiceSpec(name="be",
                     workload=Workload("w", WorkloadKind.GENERIC),
                     executor_class=ExecutorClass.CONTAINER, replicas=2,
                     footprint_hint=10, qos=QoSClass.BEST_EFFORT)
    system.apply(be)

    # force the preemptor's post-eviction commit to fail (in production
    # this is a concurrent commit racing into the freed hole)
    monitor = system.orchestrator.monitor
    orig_commit = monitor.commit
    monitor.commit = lambda node, key, b: (
        False if key.startswith("g/") else orig_commit(node, key, b))
    g = ServiceSpec(name="g",
                    workload=Workload("w2", WorkloadKind.GENERIC),
                    executor_class=ExecutorClass.CONTAINER, replicas=1,
                    footprint_hint=10, qos=QoSClass.GUARANTEED)
    with pytest.raises(PlacementError):
        system.apply(g)
    # the evicted BE instance was redeployed by the refusal-path drain,
    # not left waiting for an undeploy that never comes
    assert len(system.instances("be")) == 2
    assert system.pending_redeploys == []


def test_eviction_hook_drain_cannot_bounce_victim_mid_preemption():
    """Eviction hooks fire only after the preempting admission commits,
    so a hook calling drain_pending_redeploys() cannot redeploy the
    victim into the hole its preemptor is about to fill."""
    from repro.core import (ContainerExecutor, EdgeSystem, ExecutorClass,
                            NodeCapacity, QoSClass, ServiceSpec, Workload,
                            WorkloadClass, WorkloadKind)

    system = EdgeSystem()
    system.add_node("n0", NodeCapacity(chips=1, hbm_bytes=30,
                                       flops_per_s=1.0))
    evictions = []

    def hook(inst, svc, node):
        evictions.append((inst, svc, node))
        # at hook time the preemptor must already occupy the hole, so
        # this drain finds no room and the victim stays queued
        system.drain_pending_redeploys()

    system.on_eviction(hook)

    def builder(workload, mesh):
        return ContainerExecutor("c", {"generic": lambda x: x},
                                 mesh=mesh), 10

    system.register_builder("generic", WorkloadClass.HEAVY, builder)
    be = ServiceSpec(name="be",
                     workload=Workload("w", WorkloadKind.GENERIC),
                     executor_class=ExecutorClass.CONTAINER, replicas=2,
                     footprint_hint=10, qos=QoSClass.BEST_EFFORT)
    system.apply(be)                         # 20 of 30 used
    g = ServiceSpec(name="g",
                    workload=Workload("w2", WorkloadKind.GENERIC),
                    executor_class=ExecutorClass.CONTAINER, replicas=1,
                    footprint_hint=20, qos=QoSClass.GUARANTEED)
    system.apply(g)                          # 10 free → evicts one BE
    assert evictions == [("be/1", "be", "n0")]
    assert len(system.instances("g")) == 1   # preemptor kept its hole
    assert len(system.instances("be")) == 1  # victim NOT bounced back
    assert "be" in system.pending_redeploys  # still queued for later
    system.scale("g", 0)                     # real capacity frees → heal
    assert len(system.instances("be")) == 2


def test_requeue_waits_until_capacity_actually_frees():
    from repro.core import (ContainerExecutor, EdgeSystem, ExecutorClass,
                            NodeCapacity, QoSClass, ServiceSpec, Workload,
                            WorkloadClass, WorkloadKind)

    system = EdgeSystem()
    system.add_node("n0", NodeCapacity(chips=1, hbm_bytes=20,
                                       flops_per_s=1.0))

    def builder(workload, mesh):
        return ContainerExecutor("c", {"generic": lambda x: x},
                                 mesh=mesh), 10

    system.register_builder("generic", WorkloadClass.HEAVY, builder)
    be = ServiceSpec(name="be",
                     workload=Workload("w", WorkloadKind.GENERIC),
                     executor_class=ExecutorClass.CONTAINER, replicas=2,
                     footprint_hint=10, qos=QoSClass.BEST_EFFORT)
    system.apply(be)
    g = ServiceSpec(name="g",
                    workload=Workload("w2", WorkloadKind.GENERIC),
                    executor_class=ExecutorClass.CONTAINER, replicas=2,
                    footprint_hint=10, qos=QoSClass.GUARANTEED)
    system.apply(g)                          # evicts BOTH BE instances
    assert len(system.instances("be")) == 0
    assert "be" in system.pending_redeploys
    # manual drain with no freed capacity: stays pending
    assert system.drain_pending_redeploys() == []
    assert "be" in system.pending_redeploys
    system.scale("g", 1)                     # frees one instance worth
    assert len(system.instances("be")) == 1  # partial heal
    assert "be" in system.pending_redeploys  # still missing one replica
    system.scale("g", 0)
    assert len(system.instances("be")) == 2
    assert system.pending_redeploys == []
