"""Compile the main path's kernels for a described TPU v5e at TinyLlama-1.1B
widths (Hq=32, Hkv=4, D=64, page 16, d_model 2048).

Nothing runs: the TPU compiler refuses here what the chip would refuse
(unaligned block shapes, over-budget VMEM) — faults interpret mode cannot
see.  Every test asserts the compiled program holds the Pallas kernel
(``tpu_custom_call``).  The topology is described inside a fixture, so
only the worker that runs this file loads the TPU compiler.
"""
import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention_bwd import flash_mha
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.paged_verify_attention import paged_verify_attention
from repro.kernels.rmsnorm import rmsnorm

HQ, HKV, D, PAGE = 32, 4, 64, 16        # tinyllama-1.1b attention widths
MAX_SEQ, SLOTS = 1024, 8
MP = MAX_SEQ // PAGE                    # page-table width
POOL = SLOTS * MP + 1                   # full provisioning + trash page


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    a described-device compile written there cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _pool_args(s, kv_dtype):
    pool = [_sds(s, (POOL, PAGE, HKV, D), kv_dtype)] * 2
    scales = ([_sds(s, (POOL, PAGE, HKV), jnp.float32)] * 2
              if kv_dtype == jnp.int8 else [None, None])
    return pool, [_sds(s, (SLOTS, MP), jnp.int32),
                  _sds(s, (SLOTS,), jnp.int32)], scales


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("k1", [0, 5], ids=["decode", "verify_k1_5"])
def test_paged_attention_compiles(one_chip, kv_dtype, k1):
    pool, table, (ks, vs) = _pool_args(one_chip, kv_dtype)
    if k1:
        q = _sds(one_chip, (SLOTS, k1, HQ, D), jnp.bfloat16)
        kernel = paged_verify_attention
    else:
        q = _sds(one_chip, (SLOTS, HQ, D), jnp.bfloat16)
        kernel = paged_decode_attention
    if ks is None:
        _assert_kernel(kernel, q, *pool, *table)
    else:
        _assert_kernel(
            lambda q, k, v, t, n, a, b: kernel(q, k, v, t, n,
                                               k_scale=a, v_scale=b),
            q, *pool, *table, ks, vs)


@pytest.mark.parametrize("batch", [1, SLOTS])
def test_flash_forward_prefill_chunk_compiles(one_chip, batch):
    """A 64-token prefill chunk over a 1024-token KV span, as
    ``attention.prefill_chunk_paged`` calls it."""
    s, tq, tk = one_chip, 64, MAX_SEQ
    _assert_kernel(
        lambda q, k, v, qp, kp, n: flash_attention(
            q, k, v, q_positions=qp, kv_positions=kp, kv_valid_len=n),
        _sds(s, (batch, tq, HQ, D), jnp.bfloat16),
        _sds(s, (batch, tk, HKV, D), jnp.bfloat16),
        _sds(s, (batch, tk, HKV, D), jnp.bfloat16),
        _sds(s, (batch, tq), jnp.int32), _sds(s, (batch, tk), jnp.int32),
        _sds(s, (batch,), jnp.int32))


def test_flash_backward_compiles(one_chip):
    def grads(q, k, v):
        return jax.grad(lambda *a: flash_mha(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    x = [_sds(one_chip, (2, 256, h, D), jnp.bfloat16) for h in (HQ, HKV, HKV)]
    _assert_kernel(grads, *x)


def test_dense_decode_compiles(one_chip):
    s = one_chip
    _assert_kernel(decode_attention, _sds(s, (SLOTS, HQ, D), jnp.bfloat16),
                   _sds(s, (SLOTS, MAX_SEQ, HKV, D), jnp.bfloat16),
                   _sds(s, (SLOTS, MAX_SEQ, HKV, D), jnp.bfloat16),
                   _sds(s, (SLOTS,), jnp.int32))


def test_rmsnorm_compiles(one_chip):
    _assert_kernel(rmsnorm, _sds(one_chip, (SLOTS, 1, 2048), jnp.bfloat16),
                   _sds(one_chip, (2048,), jnp.float32))


def test_engine_decode_step_compiles(one_chip):
    """The serving engine's paged decode step (``ServingEngine._decode``)
    at full TinyLlama width, cut to 2 layers, with the Pallas kernels
    forced on (the described chip is not this process's backend)."""
    from repro.models import transformer
    from repro.models.model import build_model
    from repro.serving.engine import ServingEngine

    cfg = dataclasses.replace(get_config("tinyllama-1.1b"), num_layers=2)
    model = build_model(cfg)
    step = functools.partial(ServingEngine._decode_paged_fn,
                             types.SimpleNamespace(model=model))

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = on_chip(model.init_abstract())
    pools = on_chip(jax.eval_shape(
        lambda: transformer.init_paged_cache_tree(cfg, POOL, PAGE,
                                                  jnp.bfloat16)))
    ops.set_impl("pallas")
    try:
        _assert_kernel(step, params, pools,
                       _sds(one_chip, (SLOTS, MP), jnp.int32),
                       _sds(one_chip, (SLOTS,), jnp.int32),
                       _sds(one_chip, (SLOTS,), jnp.int32),
                       _sds(one_chip, (SLOTS,), jnp.bool_))
    finally:
        ops.set_impl(None)
