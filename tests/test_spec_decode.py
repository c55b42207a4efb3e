"""Speculative decoding + int8 KV pages: verify-kernel numerics vs the
ref oracle, int8 round-trip error bounds across dtypes/page sizes,
engine-level greedy token-exactness (speculation changes throughput,
never content), capacity accounting, spec/telemetry plumbing, and the
pinned in-flight prefix-publication gap (ISSUE 11 acceptance test)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.paged_verify_attention import paged_verify_attention
from repro.models.attention import _quantize
from repro.models.model import build_model
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import kv_bytes_per_token


def _rel_err(want, got):
    w = np.asarray(want, np.float32)
    g = np.asarray(got, np.float32)
    return np.max(np.abs(w - g)) / max(np.max(np.abs(w)), 1e-6)


def _tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 3.5e-2


# ---------------------------------------------------------------------------
# verify kernel (interpret mode) vs the gather+dense oracle
# ---------------------------------------------------------------------------

VERIFY_CASES = [
    # B, K1, Hq, Hkv, D, page, MP, num_pages, softcap
    (2, 3, 4, 2, 32, 16, 4, 11, 0.0),          # GQA
    (1, 5, 8, 1, 64, 16, 8, 30, 0.0),          # MQA, deep k
    (2, 1, 4, 4, 32, 32, 4, 9, 0.0),           # K1=1 degenerates to decode
    (2, 4, 8, 2, 32, 16, 6, 15, 20.0),         # logit softcap
]


def _verify_inputs(case, dtype):
    B, K1, Hq, Hkv, D, page, MP, P, softcap = case
    ks = jax.random.split(jax.random.key(B * 131 + K1), 5)
    q = jax.random.normal(ks[0], (B, K1, Hq, D), dtype)
    kp = jax.random.normal(ks[1], (P, page, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (P, page, Hkv, D), dtype)
    table = jax.random.randint(ks[3], (B, MP), 0, P)
    clen = jax.random.randint(ks[4], (B,), K1, MP * page + 1)
    return q, kp, vp, table, clen, softcap


@pytest.mark.parametrize("case", VERIFY_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_verify_kernel_vs_ref(case, dtype):
    q, kp, vp, table, clen, softcap = _verify_inputs(case, dtype)
    want = ref.paged_verify_attention(q, kp, vp, table, clen,
                                      softcap=softcap)
    got = paged_verify_attention(q, kp, vp, table, clen, softcap=softcap,
                                 interpret=True)
    assert _rel_err(want, got) < _tol(dtype)


@pytest.mark.parametrize("case", VERIFY_CASES)
def test_paged_verify_kernel_vs_ref_int8(case):
    """int8 pools: kernel folds per-token scales in-flight (k into the
    logits pre-softcap, v into the probabilities) and must match the
    oracle's dequantize-then-attend to fp32 tolerance of the same data."""
    q, kp, vp, table, clen, softcap = _verify_inputs(case, jnp.float32)
    kq, ks = _quantize(kp)
    vq, vs = _quantize(vp)
    want = ref.paged_verify_attention(q, kq, vq, table, clen,
                                      softcap=softcap, k_scale=ks,
                                      v_scale=vs)
    got = paged_verify_attention(q, kq, vq, table, clen, softcap=softcap,
                                 k_scale=ks, v_scale=vs, interpret=True)
    assert _rel_err(want, got) < _tol(jnp.float32)


def test_verify_k1_matches_decode_attention():
    """A 1-token verify IS a decode step: both paths must agree on the
    same pools (the engine relies on this when adaptive k falls to 0)."""
    case = (2, 1, 4, 2, 32, 16, 4, 11, 0.0)
    q, kp, vp, table, clen, _ = _verify_inputs(case, jnp.float32)
    via_verify = ref.paged_verify_attention(q, kp, vp, table, clen)[:, 0]
    via_decode = ref.paged_decode_attention(q[:, 0], kp, vp, table, clen)
    np.testing.assert_allclose(np.asarray(via_verify),
                               np.asarray(via_decode), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# int8 round-trip bounds + capacity accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("page", [8, 16, 32])
def test_int8_round_trip_error_bound(dtype, page):
    """Per-token symmetric quantization: |x - dq(q(x))| <= amax/254 per
    (token, head) — half a quantization step of that token's own scale."""
    x = jax.random.normal(jax.random.key(page), (5, page, 3, 32), dtype)
    q, s = _quantize(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    dq = ref.dequantize_pages(q, s)
    err = np.abs(np.asarray(x, np.float32) - np.asarray(dq))
    bound = np.asarray(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
                       / 254.0)[..., None]
    # bf16 inputs carry their own representation error; scales are exact
    # fp32 so the half-step bound still holds with a tiny epsilon
    assert np.all(err <= bound + 1e-5), np.max(err - bound)


def test_int8_bytes_per_token_ratio():
    """int8 pages + fp32 per-token scales must price ≥ 1.7x the tokens of
    the bf16 pool per byte (the ~2x capacity headline, minus scales)."""
    from repro.configs import get_reduced_config

    cfg = get_reduced_config("tinyllama-1.1b")
    bpt_fp = kv_bytes_per_token(cfg, cfg.cdtype)
    bpt_i8 = kv_bytes_per_token(cfg, jnp.int8)
    assert 1.7 <= bpt_fp / bpt_i8 <= 2.0


def test_engine_int8_pool_capacity(exact_config):
    cfg = exact_config("tinyllama-1.1b")
    fp = ServingEngine(cfg, max_slots=2, max_seq=64)
    i8 = ServingEngine(cfg, max_slots=2, max_seq=64, kv_dtype="int8",
                       page_size=fp.kv.page_size)
    assert i8.stats()["kv_dtype"] == "int8"
    ratio = fp.kv.capacity_bytes() / i8.kv.capacity_bytes()
    # fp32 compute dtype here → int8 pages save ≥ 2.8x at equal pages
    assert ratio >= 2.5, ratio
    with pytest.raises(ValueError):
        ServingEngine(cfg, max_slots=2, max_seq=64, paged=False,
                      kv_dtype="int8")


# ---------------------------------------------------------------------------
# engine-level greedy token-exactness
# ---------------------------------------------------------------------------

def _drain_tokens(eng, prompts, max_new):
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    done = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    out = [list(r.generated) for r in done]
    eng.stop(drain=False)
    return out


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_spec_greedy_exactness_any_draft(kv_dtype, exact_config):
    """A RANDOM draft (near-zero acceptance) must still produce exactly
    the non-speculative greedy stream — the correction token is always
    the target's own argmax at the first disagreement.  The invariant
    holds per kv_dtype (int8 quantization may flip tokens vs the fp
    baseline, but speculation at matched dtype must not): the rejected
    suffix's quantized KV really is rewound, never re-read."""
    cfg = exact_config("tinyllama-1.1b")
    dcfg = exact_config("tinyllama-1.1b", num_layers=1, num_heads=1,
                        num_kv_heads=1, d_ff=32)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (9, 14, 5)]

    base = ServingEngine(cfg, max_slots=3, max_seq=64, seed=0,
                         kv_dtype=kv_dtype)
    want = _drain_tokens(base, prompts, 12)

    spec = ServingEngine(cfg, max_slots=3, max_seq=64, seed=0,
                         kv_dtype=kv_dtype, draft_cfg=dcfg, spec_k_max=3)
    got = _drain_tokens(spec, prompts, 12)
    assert got == want
    st = spec.stats()
    assert st["speculative"] and st["spec_rounds"] > 0
    assert st["spec_proposed"] >= st["spec_accepted"] >= 0
    assert st.get("spec_disabled_reason") is None


def _zero_residual(params):
    names = {"w_o", "b_o", "w_down", "b_down"}

    def z(path, leaf):
        return (jnp.zeros_like(leaf)
                if getattr(path[-1], "key", None) in names else leaf)

    return jax.tree_util.tree_map_with_path(z, params)


def test_spec_int8_full_acceptance_and_telemetry(exact_config):
    """Zeroed residual projections make draft == target greedy streams:
    acceptance must be exactly 1.0, the spec+int8 engine must reproduce
    the fp baseline (quantization error never reaches the logits when
    w_o is zero), and the acceptance counters must flow into
    DispatchStats extras for fig7/scorecards."""
    cfg = exact_config("tinyllama-1.1b")
    dcfg = exact_config("tinyllama-1.1b", num_layers=1, num_heads=1,
                        num_kv_heads=1, d_ff=32)
    tp = _zero_residual(build_model(cfg).init(jax.random.key(0)))
    dp = _zero_residual(build_model(dcfg).init(jax.random.key(0)))
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (7, 11)]

    base = ServingEngine(cfg, max_slots=2, max_seq=64, params=tp, seed=0)
    want = _drain_tokens(base, prompts, 10)

    spec = ServingEngine(cfg, max_slots=2, max_seq=64, params=tp, seed=0,
                         kv_dtype="int8", draft_cfg=dcfg, draft_params=dp,
                         spec_k_max=4)
    spec.warmup()                              # pre-compiles every k
    got = _drain_tokens(spec, prompts, 10)
    assert got == want
    st = spec.stats()
    assert st["acceptance_rate"] == 1.0
    assert st["spec_accepted"] == st["spec_proposed"] > 0
    extra = spec.dispatch_stats.to_dict()["extra"]["speculation"]
    assert extra["acceptance_rate"] == 1.0
    assert extra["spec_accepted"] == st["spec_accepted"]


def test_spec_warmup_state_neutral(exact_config):
    cfg = exact_config("tinyllama-1.1b")
    dcfg = exact_config("tinyllama-1.1b", num_layers=1, num_heads=1,
                        num_kv_heads=1, d_ff=32)
    eng = ServingEngine(cfg, max_slots=2, max_seq=64, seed=0,
                        draft_cfg=dcfg, spec_k_max=3)
    eng.warmup().warmup()
    assert eng.ticks == 0 and eng.spec_rounds == 0
    assert int(jnp.sum(eng._draft.kv.cache_len)) == 0
    assert eng.kv.pages_in_use() == 0


def test_spec_counters_reach_system_stats(exact_config):
    """The speculation block must surface in the SYSTEM-wide
    DispatchStats (what fig7/scorecards render), not just the engine's
    private one — the manager merges executor ``stats_extras()`` on
    every recorded dispatch."""
    from benchmarks.common import stats_suffix
    from repro.core import (EdgeSystem, ExecutorClass, NodeCapacity,
                            ServiceSpec, Workload, WorkloadClass,
                            WorkloadKind)
    from repro.serving.router import make_engine_builder

    cfg = exact_config("tinyllama-1.1b")
    dcfg = exact_config("tinyllama-1.1b", num_layers=1, num_heads=1,
                        num_kv_heads=1, d_ff=32)
    system = EdgeSystem()
    system.add_node("edge0", NodeCapacity(hbm_bytes=16 << 30))
    system.register_builder(
        "decode", WorkloadClass.HEAVY,
        make_engine_builder(cfg, max_slots=2, max_seq=64, autostart=False,
                            draft_cfg=dcfg, spec_k_max=3))
    system.apply(ServiceSpec(
        name="llm", workload=Workload("serve", WorkloadKind.DECODE, cfg,
                                      seq_len=8),
        executor_class=ExecutorClass.CONTAINER))
    p = np.random.default_rng(14).integers(0, cfg.vocab_size, size=6)
    system.submit(Workload("req", WorkloadKind.DECODE, cfg, seq_len=8,
                           est_flops=1e10), (p,))
    spec = system.stats.extras()["speculation"]
    assert spec["spec_proposed"] > 0 and "acceptance_rate" in spec
    assert "spec_acceptance=" in stats_suffix(system.stats, "heavy")


def test_service_spec_kv_dtype_round_trip():
    from repro.serving.router import fleet_service_spec
    from repro.core.spec import ServiceSpec
    from repro.configs import get_reduced_config

    spec = fleet_service_spec(get_reduced_config("tinyllama-1.1b"),
                              kv_dtype="int8")
    assert spec.kv_dtype == "int8"
    again = ServiceSpec.from_dict(spec.to_dict())
    assert again == spec
    # legacy manifests (no kv_dtype key) default to "auto"
    d = spec.to_dict()
    del d["kv_dtype"]
    assert ServiceSpec.from_dict(d).kv_dtype == "auto"


# ---------------------------------------------------------------------------
# pinned limitation: prefixes publish at finish, not in flight (ISSUE 11)
# ---------------------------------------------------------------------------

@pytest.mark.xfail(
    strict=True,
    reason="v1 radix publishes prefixes only at request FINISH "
           "(serving/prefix/README.md); a simultaneous burst sharing one "
           "prefix gets zero hits unless a resident request is seeded "
           "first — bench_paged_serving.run_shared_prefix masks this by "
           "pre-seeding.  In-flight publication (share pages as soon as "
           "a prefill chunk completes) is ISSUE 11; this test is its "
           "acceptance test and should XPASS→pass when it lands.")
def test_inflight_prefix_publication_gap(exact_config):
    cfg = exact_config("tinyllama-1.1b")
    eng = ServingEngine(cfg, max_slots=4, max_seq=128, prefill_chunk=64,
                        prefill_budget=512, prefix_sharing=True, seed=0)
    rng = np.random.default_rng(13)
    common = rng.integers(0, cfg.vocab_size, size=48)
    prompts = [np.concatenate(
        [common, rng.integers(0, cfg.vocab_size, size=4)])
        for _ in range(4)]
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    eng.run_until_drained()
    hits = eng.kv_prefix_hits
    eng.stop(drain=False)
    # with in-flight publication every request after the first attaches
    # the common pages by reference
    assert hits >= len(prompts) - 1, hits
