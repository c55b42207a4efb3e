"""Flash attention backward as Pallas TPU kernels + integrated custom_vjp.

Two kernels, same recomputation strategy the jnp reference
(``blocked_attention``) validates:

  dq kernel : grid (B·Hq, nQ, nK)   — kv blocks sequential, dq accumulates
              in VMEM scratch; logits recomputed from (q, k, lse).
  dkv kernel: grid (B·Hkv, nK, nQ·G) — (q-block × GQA-group) sequential,
              dk/dv accumulate in VMEM scratch (the group sum that the jnp
              reference does with an einsum reduction happens for free in
              the accumulator).

``flash_mha`` wraps the forward kernel (which emits lse) and these two into
a ``jax.custom_vjp`` — the full TPU training path for attention.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (NEG_INF, flash_attention,
                                           pl_scratch)


# ---------------------------------------------------------------------------
# dq kernel
# ---------------------------------------------------------------------------

def _dq_kernel(valid_ref, q_pos_ref, kv_pos_ref,
               q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
               dq_ref, acc_ref,
               *, sm_scale, causal, window, softcap, n_kv_blocks, use_valid,
               hq):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * sm_scale          # [bq, D]
    k = k_ref[0].astype(jnp.float32)                     # [bk, D]
    v = v_ref[0].astype(jnp.float32)                     # [bk, Dv]
    do = do_ref[0].astype(jnp.float32)                   # [bq, Dv]
    lse = lse_ref[0]                                     # [bq, 1]
    dsum = dsum_ref[0]                                   # [bq, 1]

    s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    if softcap > 0.0:
        t = jnp.tanh(s_raw / softcap)
        s = t * softcap
        dcap = 1.0 - jnp.square(t)
    else:
        s, dcap = s_raw, None

    qp = q_pos_ref[0]                                    # [bq, 1]
    kp = kv_pos_ref[0]                                   # [1, bk]
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kp <= qp
    if window > 0:
        mask &= qp - kp < window
    if use_valid:
        mask &= kp < valid_ref[pl.program_id(0) // hq]

    p = jnp.where(mask, jnp.exp(s - lse), 0.0)           # [bq, bk]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - dsum)
    if dcap is not None:
        ds = ds * dcap
    acc_ref[...] += jax.lax.dot(ds, k)                   # [bq, D]

    @pl.when(ik == n_kv_blocks - 1)
    def _emit():
        dq_ref[0] = (acc_ref[...] * sm_scale).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# dkv kernel
# ---------------------------------------------------------------------------

def _dkv_kernel(valid_ref, q_pos_ref, kv_pos_ref,
                q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, sm_scale, causal, window, softcap, n_q_steps, use_valid,
                hkv):
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # [bq, D]
    k = k_ref[0].astype(jnp.float32)                     # [bk, D]
    v = v_ref[0].astype(jnp.float32)                     # [bk, Dv]
    do = do_ref[0, 0].astype(jnp.float32)                # [bq, Dv]
    lse = lse_ref[0, 0]                                  # [bq, 1]
    dsum = dsum_ref[0, 0]                                # [bq, 1]

    s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    if softcap > 0.0:
        t = jnp.tanh(s_raw / softcap)
        s = t * softcap
        dcap = 1.0 - jnp.square(t)
    else:
        s, dcap = s_raw, None

    qp = q_pos_ref[0]                                    # [bq, 1]
    kp = kv_pos_ref[0]                                   # [1, bk]
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kp <= qp
    if window > 0:
        mask &= qp - kp < window
    if use_valid:
        mask &= kp < valid_ref[pl.program_id(0) // hkv]

    p = jnp.where(mask, jnp.exp(s - lse), 0.0)           # [bq, bk]
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - dsum)
    if dcap is not None:
        ds = ds * dcap
    # dk += dsᵀ · (q·scale)   (q here is already scaled)
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    @pl.when(iq == n_q_steps - 1)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# wrapper
# ---------------------------------------------------------------------------

# no ref.py oracle carries this signature: the backward kernel is
# validated indirectly — tests compare flash_mha gradients against
# jax.grad of ref.mha (baselined KL003)
def flash_attention_bwd(
    q, k, v, out, lse, do, *,
    causal=True, window=0, softcap=0.0,
    q_positions=None, kv_positions=None, kv_valid_len=None,
    sm_scale=None, block_q=128, block_k=128, interpret=False,
):
    """Returns (dq, dk, dv).  lse: [B, Tq, Hq] from the forward kernel."""
    B, Tq, Hq, D = q.shape
    Tk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = Hq // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
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

    dsum = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1)                               # [B, Tq, Hq]

    if pq:
        pad4 = ((0, 0), (0, pq), (0, 0), (0, 0))
        q, do = jnp.pad(q, pad4), jnp.pad(do, pad4)
        lse = jnp.pad(lse, ((0, 0), (0, pq), (0, 0)),
                      constant_values=NEG_INF)
        dsum = jnp.pad(dsum, ((0, 0), (0, pq), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pq)),
                              constant_values=-1)
    if pk:
        pad4 = ((0, 0), (0, pk), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad4), jnp.pad(v, pad4)
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pk)),
                               constant_values=jnp.iinfo(jnp.int32).max - 1)
    Tq_p, Tk_p = Tq + pq, Tk + pk

    # per-row vectors in the forward kernel's Mosaic-legal layouts:
    # q positions / lse / dsum as columns, kv positions as rows
    qr = q.transpose(0, 2, 1, 3).reshape(B * Hq, Tq_p, D)
    dor = do.transpose(0, 2, 1, 3).reshape(B * Hq, Tq_p, Dv)
    lser = lse.transpose(0, 2, 1).reshape(B * Hq, Tq_p, 1)
    dsr = dsum.transpose(0, 2, 1).reshape(B * Hq, Tq_p, 1)
    qpos = q_positions[:, :, None]                        # [B, Tq_p, 1]
    kvpos = kv_positions[:, None, :]                      # [B, 1, Tk_p]
    valid = kv_valid_len.astype(jnp.int32)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Tk_p, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Tk_p, Dv)
    n_q, n_k = Tq_p // bq, Tk_p // bk

    def kv_head(bh):
        return (bh // Hq) * Hkv + (bh % Hq) // G

    # ---- dq
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=scale, causal=causal,
                          window=window, softcap=softcap, n_kv_blocks=n_k,
                          use_valid=use_valid, hq=Hq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,      # kv_valid_len
            grid=(B * Hq, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, bq, 1),
                             lambda bh, iq, ik, vl: (bh // Hq, iq, 0)),
                pl.BlockSpec((1, 1, bk),
                             lambda bh, iq, ik, vl: (bh // Hq, 0, ik)),
                pl.BlockSpec((1, bq, D), lambda bh, iq, ik, vl: (bh, iq, 0)),
                pl.BlockSpec((1, bk, D),
                             lambda bh, iq, ik, vl: (kv_head(bh), ik, 0)),
                pl.BlockSpec((1, bk, Dv),
                             lambda bh, iq, ik, vl: (kv_head(bh), ik, 0)),
                pl.BlockSpec((1, bq, Dv), lambda bh, iq, ik, vl: (bh, iq, 0)),
                pl.BlockSpec((1, bq, 1), lambda bh, iq, ik, vl: (bh, iq, 0)),
                pl.BlockSpec((1, bq, 1), lambda bh, iq, ik, vl: (bh, iq, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, D),
                                   lambda bh, iq, ik, vl: (bh, iq, 0)),
            scratch_shapes=[pl_scratch((bq, D))],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Tq_p, D), q.dtype),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(valid, qpos, kvpos, qr, kr, vr, dor, lser, dsr)

    # ---- dk/dv: q laid out per-kv-head [B*Hkv, G, Tq, D]
    q5 = qr.reshape(B, Hq, Tq_p, D).reshape(B, Hkv, G, Tq_p, D) \
        .reshape(B * Hkv, G, Tq_p, D)
    do5 = dor.reshape(B, Hkv, G, Tq_p, Dv).reshape(B * Hkv, G, Tq_p, Dv)
    lse5 = lser.reshape(B * Hkv, G, Tq_p, 1)
    ds5 = dsr.reshape(B * Hkv, G, Tq_p, 1)
    n_qg = n_q * G

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=scale, causal=causal,
                          window=window, softcap=softcap, n_q_steps=n_qg,
                          use_valid=use_valid, hkv=Hkv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,      # kv_valid_len
            grid=(B * Hkv, n_k, n_qg),
            in_specs=[
                pl.BlockSpec((1, bq, 1),
                             lambda bh, ik, iqg, vl, n=n_q: (bh // Hkv,
                                                             iqg % n, 0)),
                pl.BlockSpec((1, 1, bk),
                             lambda bh, ik, iqg, vl: (bh // Hkv, 0, ik)),
                pl.BlockSpec((1, 1, bq, D),
                             lambda bh, ik, iqg, vl, n=n_q: (bh, iqg // n,
                                                             iqg % n, 0)),
                pl.BlockSpec((1, bk, D), lambda bh, ik, iqg, vl: (bh, ik, 0)),
                pl.BlockSpec((1, bk, Dv),
                             lambda bh, ik, iqg, vl: (bh, ik, 0)),
                pl.BlockSpec((1, 1, bq, Dv),
                             lambda bh, ik, iqg, vl, n=n_q: (bh, iqg // n,
                                                             iqg % n, 0)),
                pl.BlockSpec((1, 1, bq, 1),
                             lambda bh, ik, iqg, vl, n=n_q: (bh, iqg // n,
                                                             iqg % n, 0)),
                pl.BlockSpec((1, 1, bq, 1),
                             lambda bh, ik, iqg, vl, n=n_q: (bh, iqg // n,
                                                             iqg % n, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, D), lambda bh, ik, iqg, vl: (bh, ik, 0)),
                pl.BlockSpec((1, bk, Dv),
                             lambda bh, ik, iqg, vl: (bh, ik, 0)),
            ],
            scratch_shapes=[pl_scratch((bk, D)), pl_scratch((bk, Dv))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, Tk_p, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, Tk_p, Dv), v.dtype),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(valid, qpos, kvpos, q5, kr, vr, do5, lse5, ds5)

    dq = dq.reshape(B, Hq, Tq_p, D).transpose(0, 2, 1, 3)[:, :Tq]
    dk = dk.reshape(B, Hkv, Tk_p, D).transpose(0, 2, 1, 3)[:, :Tk]
    dv = dv.reshape(B, Hkv, Tk_p, Dv).transpose(0, 2, 1, 3)[:, :Tk]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# integrated custom_vjp — the full TPU attention training path
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_mha(q, k, v, causal=True, window=0, softcap=0.0,
              block_q=128, block_k=128, interpret=False):
    out, _ = _flash_mha_fwd(q, k, v, causal, window, softcap,
                            block_q, block_k, interpret)
    return out


def _flash_mha_fwd(q, k, v, causal, window, softcap, block_q, block_k,
                   interpret):
    out, lse = flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               return_lse=True)
    return out, (q, k, v, out, lse)


def _flash_mha_bwd(causal, window, softcap, block_q, block_k, interpret,
                   res, g):
    q, k, v, out, lse = res
    dq, dk, dv = flash_attention_bwd(
        q, k, v, out, lse, g, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return dq, dk, dv


flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)
