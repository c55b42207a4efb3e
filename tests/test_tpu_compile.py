"""Compile the main path's kernels for a described TPU v5e at TinyLlama-1.1B
widths (Hq=32, Hkv=4, D=64, page 16, d_model 2048).

Nothing runs: the TPU compiler refuses here what the chip would refuse
(unaligned block shapes, over-budget VMEM) — faults interpret mode cannot
see.  Every test asserts the compiled program holds the Pallas kernels
(``tpu_custom_call``) under their own names, the names a device trace
shows.  The topology is described inside a fixture, so only the worker
that runs this file loads the TPU compiler.
"""
import dataclasses
import functools
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
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


_CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT )?%([\w-]+?)(?:\.\d+)? = "
    r".*custom_call_target=\"tpu_custom_call\"",
    re.M)


def _assert_kernel(names, fn, *args):
    """``fn`` compiles for the chip, and its Pallas kernels are the
    ``tpu_custom_call`` instructions, each named after one of ``names``
    (under ``jax.grad`` the name gains a ``jvp_``/``transpose_`` wrap)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = _CUSTOM_CALL.findall(text)
    assert calls
    for call in calls:
        assert any(n in call for n in names), call
    for n in names:
        assert any(n in call for call in calls), n


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
    names = [kernel.__name__]
    if ks is None:
        _assert_kernel(names, kernel, q, *pool, *table)
    else:
        _assert_kernel(
            names,
            lambda q, k, v, t, n, a, b: kernel(q, k, v, t, n,
                                               k_scale=a, v_scale=b),
            q, *pool, *table, ks, vs)


# the benchmark cells' decode shapes: B, Hq, Hkv, D, page, MP
CELL_DECODE_SHAPES = {"chat": (32, 64, 8, 128, 16, 128),   # chameleon-34b.l6
                      "rag": (8, 64, 8, 128, 16, 528)}     # command-r-35b.l5
_MOVE = re.compile(r"^\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]*)\]\S* .*?"
                   r"\b(copy|copy-start|transpose|fusion)\(", re.M)


@pytest.mark.parametrize("cell", sorted(CELL_DECODE_SHAPES))
def test_paged_decode_compiles_at_cell_shapes(one_chip, cell):
    """At the cells' widths and table sizes the decode kernel is one
    ``paged_decode_attention`` call that reads the pool as stored: no copy,
    transpose or fusion around it writes a pool-sized array."""
    B, Hq, Hkv, D, page, MP = CELL_DECODE_SHAPES[cell]
    pool = (B * MP + 1, page, Hkv, D)
    text = jax.jit(paged_decode_attention).lower(
        _sds(one_chip, (B, Hq, D), jnp.bfloat16),
        _sds(one_chip, pool, jnp.bfloat16), _sds(one_chip, pool, jnp.bfloat16),
        _sds(one_chip, (B, MP), jnp.int32),
        _sds(one_chip, (B,), jnp.int32)).compile().as_text()
    assert _CUSTOM_CALL.findall(text) == ["paged_decode_attention"]
    pool_elems = int(np.prod(pool))
    moves = [(dims, op) for dims, op in _MOVE.findall(text)
             if np.prod([int(d) for d in dims.split(",") if d]) >= pool_elems]
    assert not moves, moves


@pytest.mark.parametrize("batch", [1, SLOTS])
def test_flash_forward_prefill_chunk_compiles(one_chip, batch):
    """A 64-token prefill chunk over a 1024-token KV span, as
    ``attention.prefill_chunk_paged`` calls it."""
    s, tq, tk = one_chip, 64, MAX_SEQ
    _assert_kernel(
        ["flash_attention"],
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
    _assert_kernel(["flash_attention", "flash_attention_bwd_dq",
                    "flash_attention_bwd_dkv"], grads, *x)


def test_dense_decode_compiles(one_chip):
    s = one_chip
    _assert_kernel(["decode_attention"], decode_attention, _sds(s, (SLOTS, HQ, D), jnp.bfloat16),
                   _sds(s, (SLOTS, MAX_SEQ, HKV, D), jnp.bfloat16),
                   _sds(s, (SLOTS, MAX_SEQ, HKV, D), jnp.bfloat16),
                   _sds(s, (SLOTS,), jnp.int32))


def test_rmsnorm_compiles(one_chip):
    _assert_kernel(["rmsnorm"], rmsnorm, _sds(one_chip, (SLOTS, 1, 2048), jnp.bfloat16),
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
        _assert_kernel(["paged_decode_attention"], step, params, pools,
                       _sds(one_chip, (SLOTS, MP), jnp.int32),
                       _sds(one_chip, (SLOTS,), jnp.int32),
                       _sds(one_chip, (SLOTS,), jnp.int32),
                       _sds(one_chip, (SLOTS,), jnp.bool_))
    finally:
        ops.set_impl(None)


def test_engine_programs_keep_their_names(exact_config):
    """A device trace finds the engine's decode and prefill-chunk programs
    by name (``jit__decode_paged_fn``, ``jit__chunk_paged_fn``)."""
    from repro.serving.engine import ServingEngine

    eng = ServingEngine(exact_config("tinyllama-1.1b"), max_slots=2,
                        max_seq=64)
    zero1 = jnp.zeros((1,), jnp.int32)
    row = jnp.zeros((1, eng.kv.pages_per_slot), jnp.int32)
    decode = eng._decode.lower(eng.params, eng.kv.pools, eng.kv.page_table,
                               eng.last_tokens, eng.kv.cache_len,
                               jnp.zeros((2,), bool))
    chunk = eng._chunk.lower(eng.params, eng.kv.pools,
                             jnp.zeros((1, 16), jnp.int32), row, zero1, zero1)
    assert decode.as_text().startswith("module @jit__decode_paged_fn ")
    assert chunk.as_text().startswith("module @jit__chunk_paged_fn ")


# the hybrid cell (nemotron-h-47b.l11.gen): 16 slots x 8,192 tokens, page 16
HYBRID_SLOTS, HYBRID_MAX_SEQ = 16, 8192
HYBRID_CHIP_BYTES = 15.75e9             # a v5e's usable HBM


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_pattern_engine_programs_compile_at_cell_shapes(one_chip, program):
    """The hybrid cell's decode and 64-token prefill-chunk programs
    (``ServingEngine._decode_paged_fn``, ``_chunk_paged_fn`` at the widest
    KV span) at its published widths, pattern, slots and context compile
    for the chip within its memory; the chunk program holds the SSD scan
    and flash attention, the decode program the paged decode kernel."""
    import json
    import pathlib
    import sys

    from repro.models import transformer
    from repro.models.model import build_model
    from repro.serving.engine import ServingEngine

    bench = pathlib.Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    from benchlib import spec

    conf = json.loads((bench / "configs" / "nemotron-h-47b.l11.json")
                      .read_text())
    assert conf["serving"]["max_slots"] == HYBRID_SLOTS
    assert conf["serving"]["max_seq"] == HYBRID_MAX_SEQ
    cfg = spec.load_architecture(conf).model_config(conf)
    model = build_model(cfg)
    mp = HYBRID_MAX_SEQ // PAGE

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = on_chip(model.init_abstract())
    pools = on_chip(jax.eval_shape(
        lambda: transformer.init_paged_cache_tree(
            cfg, HYBRID_SLOTS * mp + 1, PAGE, jnp.bfloat16,
            slots=HYBRID_SLOTS)))
    ns = types.SimpleNamespace(model=model)
    i32 = functools.partial(_sds, one_chip, dtype=jnp.int32)
    if program == "decode":
        fn = functools.partial(ServingEngine._decode_paged_fn, ns)
        args = (params, pools, i32((HYBRID_SLOTS, mp)), i32((HYBRID_SLOTS,)),
                i32((HYBRID_SLOTS,)),
                _sds(one_chip, (HYBRID_SLOTS,), jnp.bool_))
        names = ["paged_decode_attention"]
    else:
        fn = functools.partial(ServingEngine._chunk_paged_fn, ns)
        args = (params, pools, i32((1, 64)), i32((1, mp)), i32((1,)),
                i32((1,)), i32((1,)))
        names = ["ssd_scan", "flash_attention"]
    ops.set_impl("pallas")
    try:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    finally:
        ops.set_impl(None)
    calls = _CUSTOM_CALL.findall(compiled.as_text())
    assert sorted(set(calls)) == sorted(names), calls
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < HYBRID_CHIP_BYTES, total
