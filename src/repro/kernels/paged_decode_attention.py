"""Paged single-token decode attention as a Pallas TPU kernel.

The KV cache lives in a pool of fixed-size pages (``[P, page, Hkv, D]``)
instead of one dense ``[B, S, Hkv, D]`` tensor; each sequence owns a row
of a page table mapping its logical pages to physical page ids.  The
kernel keeps the online-softmax structure of ``decode_attention`` — the
query tile stays VMEM-resident while the cache streams HBM→VMEM — but the
cache blocks are *gathered through the page table*: the page table (and
``cache_len``) ride in scalar-prefetch SMEM so the block index map can
pick the physical page before the DMA is issued
(``pltpu.PrefetchScalarGridSpec``).

Grid = (B·Hkv, MP) with the page dimension sequential.  Logical pages at
or beyond ``ceil(cache_len / page)`` may map to any physical page (the
pool's page 0 is the allocator's trash page) — the validity mask zeroes
their contribution, so stale table entries only cost the DMA.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_attention import pl_scratch

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(table_ref, len_ref, q_ref, k_ref, v_ref, *rest,
            sm_scale: float, softcap: float, window: int,
            page: int, n_pages: int, hkv: int):
    if len(rest) == 6:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * sm_scale          # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)                  # [page, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [G, page]
    if ks_ref is not None:
        # int8 pool: fold the per-token dequant scale into the logits
        # (q·(k·s) == (q·k)·s) instead of dequantizing the tile
        s = s * ks_ref[0, 0]                             # [1, page]
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    valid = len_ref[pl.program_id(0) // hkv]
    pos = ip * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = pos < valid
    if window > 0:
        mask &= pos >= valid - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    if vs_ref is not None:
        p = p * vs_ref[0, 0]                             # p·(v·s) == (p·s)·v
    v = v_ref[0, 0].astype(jnp.float32)                  # [page, Dv]
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ip == n_pages - 1)
    def _finish():
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = jnp.where(l == 0.0, 0.0, o).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,                  # [B, Hq, D] one query token per sequence
    k_pages: jax.Array,            # [P, page, Hkv, D] physical page pool
    v_pages: jax.Array,            # [P, page, Hkv, Dv]
    page_table: jax.Array,         # [B, MP] int32 physical page ids
    cache_len: jax.Array,          # [B] valid tokens (incl. the new one)
    *,
    softcap: float = 0.0,
    window: int = 0,
    sm_scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,   # [P, page, Hkv] f32 (int8 pools)
    v_scale: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    from jax.experimental.pallas import tpu as pltpu

    B, Hq, D = q.shape
    P, page, Hkv, Dv = (k_pages.shape[0], k_pages.shape[1],
                        k_pages.shape[2], v_pages.shape[3])
    MP = page_table.shape[1]
    G = Hq // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5

    qr = q.reshape(B * Hkv, G, D)
    # [P, Hkv, page, D]: one (page, head) tile per gathered cache block
    kr = k_pages.transpose(0, 2, 1, 3)
    vr = v_pages.transpose(0, 2, 1, 3)
    grid = (B * Hkv, MP)

    kernel = functools.partial(
        _kernel, sm_scale=scale, softcap=softcap, window=window,
        page=page, n_pages=MP, hkv=Hkv)

    in_specs = [
        pl.BlockSpec((1, G, D), lambda bh, ip, pt, cl: (bh, 0, 0)),
        pl.BlockSpec((1, 1, page, D),
                     lambda bh, ip, pt, cl: (pt[bh // Hkv, ip],
                                             bh % Hkv, 0, 0)),
        pl.BlockSpec((1, 1, page, Dv),
                     lambda bh, ip, pt, cl: (pt[bh // Hkv, ip],
                                             bh % Hkv, 0, 0)),
    ]
    inputs = [qr, kr, vr]
    if k_scale is not None:
        sc_spec = pl.BlockSpec((1, 1, 1, page),
                               lambda bh, ip, pt, cl: (pt[bh // Hkv, ip],
                                                       bh % Hkv, 0, 0))
        in_specs += [sc_spec, sc_spec]
        # [P, Hkv, 1, page]: one lane-major scale row per (page, head)
        # tile — the unit axis keeps the block's last two dims whole
        inputs += [k_scale.transpose(0, 2, 1)[:, :, None].astype(jnp.float32),
                   v_scale.transpose(0, 2, 1)[:, :, None].astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # page_table, cache_len
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, Dv), lambda bh, ip, pt, cl: (bh, 0, 0)),
        scratch_shapes=[
            pl_scratch((G, Dv)), pl_scratch((G, 1)), pl_scratch((G, 1)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, Dv), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), cache_len.astype(jnp.int32), *inputs)
    return out.reshape(B, Hq, Dv)
