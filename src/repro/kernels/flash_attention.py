"""Flash attention (fwd) as a Pallas TPU kernel.

TPU-native adaptation of the flash algorithm (DESIGN.md §2): the online-
softmax accumulator lives in VMEM scratch; the KV loop is the innermost
*sequential* grid dimension so the MXU sees back-to-back [bq, D]×[D, bk]
matmuls from VMEM-resident tiles; block shapes are multiples of (8, 128)
sublane×lane tiles.  GQA is handled by mapping each query head to its KV
head in the BlockSpec index maps — no KV replication in memory.

VMEM budget per grid step (bq = bk = 128, D ≤ 256, f32 accum):
  q/k/v tiles ≈ 3·128·256·2 B ≈ 0.2 MiB; acc 128·256·4 B ≈ 0.13 MiB —
  comfortably under the ~16 MiB/core VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(valid_ref, q_pos_ref, kv_pos_ref,
            q_ref, k_ref, v_ref, o_ref, lse_ref,
            acc_ref, m_ref, l_ref,
            *, sm_scale: float, causal: bool, window: int, softcap: float,
            n_kv_blocks: int, use_valid: bool, hq: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * sm_scale          # [bq, D]
    k = k_ref[0].astype(jnp.float32)                     # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # [bq, bk]
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    qp = q_pos_ref[0]                                    # [bq, 1]
    kp = kv_pos_ref[0]                                   # [1, bk]
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kp <= qp
    if window > 0:
        mask &= qp - kp < window
    if use_valid:
        mask &= kp < valid_ref[pl.program_id(0) // hq]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)                       # [bq, 1]
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)                     # [bk, Dv]
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finish():
        l = l_ref[...]
        lsafe = jnp.where(l == 0.0, 1.0, l)
        o = acc_ref[...] / lsafe
        o_ref[0] = jnp.where(l == 0.0, 0.0, o).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(lsafe)


def flash_attention(  # analysis: oracle=mha
    q: jax.Array,                  # [B, Tq, Hq, D]
    k: jax.Array,                  # [B, Tk, Hkv, D]
    v: jax.Array,                  # [B, Tk, Hkv, Dv]
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    kv_valid_len: Optional[jax.Array] = None,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    return_lse: bool = False,
):
    B, Tq, Hq, D = q.shape
    Tk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    groups = Hq // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    # pad seq dims to block multiples (mask handles the tail)
    pq = (-Tq) % bq
    pk = (-Tk) % bk

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(Tq, dtype=jnp.int32)[None],
                                       (B, Tq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(Tk, dtype=jnp.int32)[None],
                                        (B, Tk))
    use_valid = kv_valid_len is not None
    if not use_valid:
        kv_valid_len = jnp.full((B,), Tk, jnp.int32)

    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pq)),
                              constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        # padded kv positions sit beyond every query (masked out by causal /
        # valid_len via a sentinel that fails `kp <= qp` for real qp ≥ 0)
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pk)),
                               constant_values=jnp.iinfo(jnp.int32).max - 1)
        if not use_valid and not causal:
            use_valid = True          # non-causal needs explicit tail mask
    Tq_p, Tk_p = Tq + pq, Tk + pk

    qr = q.transpose(0, 2, 1, 3).reshape(B * Hq, Tq_p, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Tk_p, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Tk_p, Dv)

    n_q = Tq_p // bq
    n_k = Tk_p // bk
    grid = (B * Hq, n_q, n_k)

    def kv_head(bh):
        return (bh // Hq) * Hkv + (bh % Hq) // groups

    kernel = functools.partial(
        _kernel, sm_scale=scale, causal=causal, window=window,
        softcap=softcap, n_kv_blocks=n_k, use_valid=use_valid, hq=Hq)

    # Mosaic tiling: the last two block dims must be (8, 128)-aligned or
    # span the whole array dim.  Per-row vectors therefore ride as columns
    # ([.., T, 1]: q positions, lse) or rows ([.., 1, T]: kv positions),
    # and the per-sequence valid length is a scalar-prefetch (SMEM) input.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # kv_valid_len
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda bh, iq, ik, vl: (bh // Hq, iq, 0)),
            pl.BlockSpec((1, 1, bk), lambda bh, iq, ik, vl: (bh // Hq, 0, ik)),
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik, vl: (bh, iq, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda bh, iq, ik, vl: (kv_head(bh), ik, 0)),
            pl.BlockSpec((1, bk, Dv),
                         lambda bh, iq, ik, vl: (kv_head(bh), ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda bh, iq, ik, vl: (bh, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, iq, ik, vl: (bh, iq, 0)),
        ],
        scratch_shapes=[
            pl_scratch((bq, Dv)), pl_scratch((bq, 1)), pl_scratch((bq, 1)),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, Tq_p, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, Tq_p, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(kv_valid_len.astype(jnp.int32), q_positions[:, :, None],
      kv_positions[:, None, :], qr, kr, vr)

    out = out.reshape(B, Hq, Tq_p, Dv).transpose(0, 2, 1, 3)[:, :Tq]
    if return_lse:
        lse = lse.reshape(B, Hq, Tq_p).transpose(0, 2, 1)[:, :Tq]
        return out, lse
    return out


def pl_scratch(shape):
    """f32 VMEM scratch buffer (interpret mode emulates VMEM on the host)."""
    return pltpu.VMEM(shape, jnp.float32)
