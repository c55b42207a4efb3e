"""Multi-token paged verify attention as a Pallas TPU kernel.

Generalizes ``paged_decode_attention`` from q_len=1 to q_len=K1: the
speculative-decoding verify pass scores the draft's k proposals plus the
resumption position against the target model in ONE kernel launch
instead of K1 sequential decode steps.  The K1 query tokens occupy the
*last* K1 cache slots — query i of a sequence with ``cache_len`` valid
tokens sits at absolute position ``cache_len - K1 + i`` — so each query
row gets a causal intra-chunk mask ``pos <= cache_len - K1 + i``.

Everything else keeps the decode kernel's gathered-page streaming
structure: grid (B·Hkv, MP) with the page dimension sequential, page
table + cache_len riding in scalar-prefetch SMEM so the block index map
picks the physical page before the DMA is issued, online softmax over
pages with the query tile VMEM-resident.  The query tile is the K1·G
rows of one (sequence, kv-head) pair.

int8 page pools are supported via per-token ``k_scale``/``v_scale``
([P, page, Hkv] float32): rather than dequantizing the KV tiles, the
scales fold into the logits (``q·(k·s) = (q·k)·s``) and the softmax
probabilities (``p·(v·s) = (p·s)·v``), two cheap [rows, page] broadcasts.
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
            page: int, n_pages: int, hkv: int, groups: int, k1: int):
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

    q = q_ref[0].astype(jnp.float32) * sm_scale          # [K1*G, D]
    k = k_ref[0, 0].astype(jnp.float32)                  # [page, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [K1*G, page]
    if ks_ref is not None:
        s = s * ks_ref[0, 0]                             # [1, page]
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    valid = len_ref[pl.program_id(0) // hkv]
    pos = ip * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // groups
    qpos = valid - k1 + qi                               # absolute query pos
    mask = pos <= qpos
    if window > 0:
        mask &= pos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    if vs_ref is not None:
        p = p * vs_ref[0, 0]
    v = v_ref[0, 0].astype(jnp.float32)                  # [page, Dv]
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ip == n_pages - 1)
    def _finish():
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = jnp.where(l == 0.0, 0.0, o).astype(o_ref.dtype)


def paged_verify_attention(
    q: jax.Array,                  # [B, K1, Hq, D] the K1 newest tokens
    k_pages: jax.Array,            # [P, page, Hkv, D] physical page pool
    v_pages: jax.Array,            # [P, page, Hkv, Dv]
    page_table: jax.Array,         # [B, MP] int32 physical page ids
    cache_len: jax.Array,          # [B] valid tokens (incl. all K1 new ones)
    *,
    softcap: float = 0.0,
    window: int = 0,
    sm_scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,   # [P, page, Hkv] f32 (int8 pools)
    v_scale: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    from jax.experimental.pallas import tpu as pltpu

    B, K1, Hq, D = q.shape
    P, page, Hkv, Dv = (k_pages.shape[0], k_pages.shape[1],
                        k_pages.shape[2], v_pages.shape[3])
    MP = page_table.shape[1]
    G = Hq // Hkv
    R = K1 * G
    scale = sm_scale if sm_scale is not None else D ** -0.5

    # [B*Hkv, K1*G, D]: all K1 query tokens of one (seq, kv-head) per tile
    qr = (q.reshape(B, K1, Hkv, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(B * Hkv, R, D))
    # [P, Hkv, page, D]: one (page, head) tile per gathered cache block
    kr = k_pages.transpose(0, 2, 1, 3)
    vr = v_pages.transpose(0, 2, 1, 3)
    grid = (B * Hkv, MP)

    kernel = functools.partial(
        _kernel, sm_scale=scale, softcap=softcap, window=window,
        page=page, n_pages=MP, hkv=Hkv, groups=G, k1=K1)

    kv_spec = pl.BlockSpec((1, 1, page, D),
                           lambda bh, ip, pt, cl: (pt[bh // Hkv, ip],
                                                   bh % Hkv, 0, 0))
    vv_spec = pl.BlockSpec((1, 1, page, Dv),
                           lambda bh, ip, pt, cl: (pt[bh // Hkv, ip],
                                                   bh % Hkv, 0, 0))
    in_specs = [
        pl.BlockSpec((1, R, D), lambda bh, ip, pt, cl: (bh, 0, 0)),
        kv_spec,
        vv_spec,
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
        out_specs=pl.BlockSpec((1, R, Dv), lambda bh, ip, pt, cl: (bh, 0, 0)),
        scratch_shapes=[
            pl_scratch((R, Dv)), pl_scratch((R, 1)), pl_scratch((R, 1)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, R, Dv), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), cache_len.astype(jnp.int32), *inputs)
    return (out.reshape(B, Hkv, K1, G, Dv).transpose(0, 2, 1, 3, 4)
            .reshape(B, K1, Hq, Dv))
