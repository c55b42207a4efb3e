"""The pattern family (Nemotron-H's per-layer mixers): the grouped gated
norm, runs of one kind scanned, and recurrent state held by the paged
cache manager beside the attention pages."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import mamba2
from repro.models.config import ModelConfig, SSMConfig
from repro.models.layers import rms_norm_simple
from repro.models.model import build_model


def _cfg(pattern="M-M*-", groups=2, **over):
    return ModelConfig(
        name="tiny-pattern", family="pattern", layer_pattern=pattern,
        num_layers=len(pattern), d_model=64, num_heads=4, num_kv_heads=2,
        head_dim=16, d_ff=128, vocab_size=128, activation="relu2",
        use_rope=False, ssm=SSMConfig(d_state=16, head_dim=16,
                                      chunk_size=128, n_groups=groups),
        param_dtype="float32", compute_dtype="float32", **over)


def _yzs(di, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (3, 5, di)),
            jax.random.normal(ks[1], (3, 5, di)),
            1.0 + 0.1 * jax.random.normal(ks[2], (di,)))


def test_one_group_gated_norm_is_the_norm_over_all_channels():
    cfg = _cfg(groups=1)
    y, z, scale = _yzs(cfg.d_inner)
    want = rms_norm_simple(y * jax.nn.silu(z), scale, cfg.norm_eps)
    got = mamba2.gated_out_norm(y, z, scale, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_eight_group_gated_norm_normalises_each_group():
    cfg = dataclasses.replace(
        _cfg(), d_model=128,
        ssm=dataclasses.replace(_cfg().ssm, n_groups=8))
    y, z, scale = _yzs(cfg.d_inner, seed=1)
    h = np.asarray(y * jax.nn.silu(z), np.float64)
    out = np.empty_like(h)
    size = cfg.d_inner // 8
    for g in range(8):
        part = h[..., g * size:(g + 1) * size]
        ms = np.mean(part * part, axis=-1, keepdims=True)
        out[..., g * size:(g + 1) * size] = part / np.sqrt(ms + cfg.norm_eps)
    out *= np.asarray(scale, np.float64)
    got = np.asarray(mamba2.gated_out_norm(y, z, scale, cfg))
    np.testing.assert_allclose(got, out, rtol=2e-5, atol=2e-6)
    whole = np.asarray(rms_norm_simple(jnp.asarray(h, jnp.float32), scale,
                                       cfg.norm_eps))
    assert np.abs(whole - out).max() > 1e-2      # not the one-group norm


def test_runs_of_one_kind():
    assert _cfg("M-M*-").pattern_runs == (("M", 1), ("-", 1), ("M", 1),
                                          ("*", 1), ("-", 1))
    assert _cfg("MM--*").pattern_runs == (("M", 2), ("-", 2), ("*", 1))
    with pytest.raises(ValueError, match="unknown layer kind"):
        _ = _cfg("M+").pattern_runs


def test_scanned_runs_give_the_unrolled_forward():
    cfg = _cfg("MM--*-")
    model = build_model(cfg)
    params = model.init(jax.random.key(3))
    assert jax.tree.leaves(params["stack"]["runs"][0])[0].shape[0] == 2
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 128, (1, 40)))
    scanned, _ = model.forward(params, {"tokens": toks})
    unrolled, _ = build_model(dataclasses.replace(
        cfg, scan_layers=False)).forward(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(scanned), np.asarray(unrolled),
                               rtol=1e-5, atol=1e-5)
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))


def test_the_engine_pages_a_pattern_model_and_holds_its_state():
    from repro.serving.engine import ServingEngine
    from repro.serving.kv_cache import PagedKVCache

    cfg = _cfg()
    eng = ServingEngine(cfg, max_slots=3, max_seq=64)
    kv = eng.kv
    assert eng.paged and eng.recurrent and isinstance(kv, PagedKVCache)
    assert eng.prefix is None                    # pages carry no state
    assert [jax.tree.leaves(a)[0].shape[:2] for a in kv.pools["mamba"]] \
        == [(1, 3), (1, 3)]
    H, P, N = cfg.ssm_heads, cfg.ssm.head_dim, cfg.ssm.d_state
    conv = (cfg.ssm.d_conv - 1) * (cfg.d_inner + 2 * cfg.ssm.n_groups * N)
    assert kv.state_bytes_per_slot == 2 * 4 * (H * P * N + conv)
    assert kv.state_bytes() == 0
    slot, _row = kv.alloc(10)
    assert kv.state_bytes() == kv.state_bytes_per_slot
    assert kv.bytes_in_use() == kv.pages_in_use() * kv._page_bytes \
        + kv.state_bytes_per_slot
    kv.pools["mamba"] = jax.tree.map(jnp.ones_like, kv.pools["mamba"])
    kv.reset_state(slot)
    for a in jax.tree.leaves(kv.pools["mamba"]):
        assert not np.asarray(a[:, slot]).any()
        assert np.asarray(a[:, 1 - slot]).all()
    dense = ServingEngine(dataclasses.replace(
        cfg, family="dense", layer_pattern="", num_layers=2, ssm=None,
        activation="swiglu", use_rope=True), max_slots=2, max_seq=64)
    assert not dense.recurrent and not dense.kv.has_state
    assert dense.prefix is not None
