"""Single-token decode attention as a Pallas TPU kernel.

Decode is memory-bound: the whole KV cache streams HBM→VMEM once while the
query tile stays VMEM-resident.  Grid = (B·Hkv, S/bk) with the cache-block
dimension sequential; the [G, D] query tile (G = GQA group) does one
[G, D]×[D, bk] matmul per cache block — arithmetic intensity is ~G, so
block_k only needs to be large enough (≥512) to hide latency, not to feed
the MXU.  Ring-buffer semantics (SWA) are handled by the caller via
``cache_len``; masking here is pure slot-validity.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import pl_scratch

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(valid_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref,
            *, sm_scale: float, softcap: float, window: int,
            block_k: int, n_blocks: int, hkv: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * sm_scale          # [G, D]
    k = k_ref[0].astype(jnp.float32)                     # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [G, bk]
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    valid = valid_ref[pl.program_id(0) // hkv]
    pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = pos < valid
    if window > 0:
        mask &= pos >= valid - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)                     # [bk, Dv]
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ik == n_blocks - 1)
    def _finish():
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = jnp.where(l == 0.0, 0.0, o).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,                  # [B, Hq, D]
    k_cache: jax.Array,            # [B, S, Hkv, D]
    v_cache: jax.Array,            # [B, S, Hkv, Dv]
    cache_len: jax.Array,          # [B]
    *,
    softcap: float = 0.0,
    window: int = 0,
    sm_scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, D = q.shape
    S, Hkv, Dv = k_cache.shape[1], k_cache.shape[2], v_cache.shape[3]
    G = Hq // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5
    bk = min(block_k, S)
    pk = (-S) % bk
    if pk:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
    S_p = S + pk

    qr = q.reshape(B * Hkv, G, D)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, S_p, D)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, S_p, Dv)
    n_blocks = S_p // bk
    grid = (B * Hkv, n_blocks)

    kernel = functools.partial(
        _kernel, sm_scale=scale, softcap=softcap, window=window,
        block_k=bk, n_blocks=n_blocks, hkv=Hkv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # cache_len
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, D), lambda bh, ik, cl: (bh, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ik, cl: (bh, ik, 0)),
            pl.BlockSpec((1, bk, Dv), lambda bh, ik, cl: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, Dv), lambda bh, ik, cl: (bh, 0, 0)),
        scratch_shapes=[
            pl_scratch((G, Dv)), pl_scratch((G, 1)), pl_scratch((G, 1)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, Dv), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(cache_len.astype(jnp.int32), qr, kr, vr)
    return out.reshape(B, Hq, Dv)
