"""Mamba2 SSD chunk scan as a Pallas TPU kernel.

The state-space-dual form turns the recurrence into per-chunk matmuls (MXU
food) plus a tiny cross-chunk state recurrence.  Grid = (B·H, T/Q) with the
chunk dimension sequential: the [P, N] running state lives in VMEM scratch
and is carried across chunk iterations — the cross-chunk recurrence never
touches HBM.  Per chunk (Q=64..256, P=64, N=64..256) the working set is a
few hundred KiB of VMEM.  The state is kept as [P, N], the layout the
decode step and the serving pools hold, so no transpose meets it.

Inputs are pre-activated (softplus'd dt, A = −exp(a_log)); the wrapper
forms the log-decay dt·A, lays dt and it out as [T, 1] columns (the
kernel takes its cumulative sums by masked reductions: Mosaic has no
cumsum), handles B/C group broadcast (GQA-style) via BlockSpec index maps,
chunk padding, and optional initial/final state threading
(prefill→decode).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_attention import pl_scratch


def _kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, s0_ref,
            y_ref, sfin_ref, state_ref,
            *, n_chunks: int, chunk: int, use_init: bool):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        if use_init:
            state_ref[...] = s0_ref[0, 0].astype(jnp.float32)
        else:
            state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)            # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)          # [Q, 1]
    da = da_ref[0].astype(jnp.float32)          # [Q, 1] log-decay dt·A ≤ 0
    B_ = b_ref[0].astype(jnp.float32)           # [Q, N]
    C = c_ref[0].astype(jnp.float32)            # [Q, N]

    # cumulative log-decay as a column and as a row, by masked sums (no
    # cumsum or transpose in the kernel): cum_i = Σ_{j≤i} da_j
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iota_j <= iota_i
    da_row = jnp.sum(jnp.where(iota_i == iota_j, da, 0.0), axis=0,
                     keepdims=True)                              # [1, Q]
    cum = jnp.sum(jnp.where(causal, da_row, 0.0), axis=1,
                  keepdims=True)                                 # [Q, 1]
    cum_row = jnp.sum(jnp.where(iota_i <= iota_j, da, 0.0), axis=0,
                      keepdims=True)                             # [1, Q]
    total = jnp.sum(da, axis=0, keepdims=True)                   # [1, 1]
    dx = x * dt                                  # [Q, P]

    # intra-chunk: y_i = Σ_{j≤i} (C_i·B_j) exp(cum_i − cum_j) dx_j
    cb = jax.lax.dot_general(C, B_, (((1,), (1,)), ((), ())))   # [Q, Q]
    L = jnp.where(causal, jnp.exp(cum - cum_row), 0.0)
    y = jax.lax.dot(cb * L, dx)                  # [Q, P]

    # inter-chunk: y_i += exp(cum_i) S_prev·C_i   (S_prev: [P, N])
    s_prev = state_ref[...]
    y += jnp.exp(cum) * jax.lax.dot_general(
        C, s_prev, (((1,), (1,)), ((), ())))                    # [Q, P]

    # state update:
    #   S = exp(cum_end) S_prev + Σ_j dx_j ⊗ exp(cum_end − cum_j) B_j
    upd = jax.lax.dot_general(dx, B_ * jnp.exp(total - cum),
                              (((0,), (0,)), ((), ())))          # [P, N]
    state_ref[...] = jnp.exp(total) * s_prev + upd

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        sfin_ref[0] = state_ref[...].astype(sfin_ref.dtype)


def ssd_scan(
    x: jax.Array,        # [B, T, H, P]
    dt: jax.Array,       # [B, T, H]  (softplus'd, > 0)
    A: jax.Array,        # [H]        (negative)
    B_: jax.Array,       # [B, T, G, N]
    C: jax.Array,        # [B, T, G, N]
    *,
    chunk: int = 64,
    initial_state: Optional[jax.Array] = None,   # [B, H, P, N]
    return_final_state: bool = False,
    interpret: bool = False,
):
    Bb, T, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    rep = H // G
    T0 = T
    pad = (-T) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        T = T + pad
    nC = T // chunk

    xr = x.transpose(0, 2, 1, 3).reshape(Bb * H, T, P)
    # per-step scalars as [T, 1] columns (a block's last two dims tile as
    # (8k, full)); the log-decay dt·A is formed here, so A needs no block
    dt = dt.astype(jnp.float32)
    dtr = dt.transpose(0, 2, 1).reshape(Bb * H, T, 1)
    dar = (dt * A.astype(jnp.float32)).transpose(0, 2, 1).reshape(
        Bb * H, T, 1)
    br = B_.transpose(0, 2, 1, 3).reshape(Bb * G, T, N)
    cr = C.transpose(0, 2, 1, 3).reshape(Bb * G, T, N)
    use_init = initial_state is not None
    if initial_state is None:
        # dummy (read only under use_init, but must exist for the BlockSpec)
        s0 = jnp.zeros((Bb * H, 1, P, N), jnp.float32)
    else:
        s0 = initial_state.reshape(Bb * H, 1, P, N)
    s0 = s0.astype(jnp.float32)

    grid = (Bb * H, nC)

    def g_idx(bh):
        return (bh // H) * G + (bh % H) // rep

    kernel = functools.partial(_kernel, n_chunks=nC, chunk=chunk,
                               use_init=use_init)

    y, s_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ic: (g_idx(bh), ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ic: (g_idx(bh), ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bh, ic: (bh, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, P, N), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb * H, T, P), x.dtype),
            jax.ShapeDtypeStruct((Bb * H, P, N), jnp.float32),
        ],
        scratch_shapes=[pl_scratch((P, N))],
        interpret=interpret,
        name="ssd_scan",
    )(xr, dtr, dar, br, cr, s0)

    y = y.reshape(Bb, H, T, P).transpose(0, 2, 1, 3)[:, :T0]
    if return_final_state:
        return y, s_fin.reshape(Bb, H, P, N)
    return y
