"""Mamba2 SSD chunk scan as a Pallas TPU kernel.

The state-space-dual form turns the recurrence into per-chunk matmuls (MXU
food) plus a tiny cross-chunk state recurrence.  Grid = (B·H, T/Q) with the
chunk dimension sequential: the [P, N] running state lives in VMEM scratch
and is carried across chunk iterations — the cross-chunk recurrence never
touches HBM.  Per chunk (Q=64..256, P=64, N=64..128) the working set is a
few hundred KiB of VMEM.

Inputs are pre-activated (softplus'd dt, A = −exp(a_log)); the wrapper
handles B/C group broadcast (GQA-style) via BlockSpec index maps, chunk
padding, and optional initial/final state threading (prefill→decode).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_attention import pl_scratch


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref,
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
    dt = dt_ref[0].astype(jnp.float32)          # [Q]
    A = a_ref[0].astype(jnp.float32)            # scalar (this head)
    B_ = b_ref[0].astype(jnp.float32)           # [Q, N]
    C = c_ref[0].astype(jnp.float32)            # [Q, N]

    da = dt * A                                  # [Q] log-decay ≤ 0
    cum = jnp.cumsum(da)                         # [Q]
    dx = x * dt[:, None]                         # [Q, P]

    # intra-chunk: y_i = Σ_{j≤i} (C_i·B_j) exp(cum_i − cum_j) dx_j
    cb = jax.lax.dot_general(C, B_, (((1,), (1,)), ((), ())))   # [Q, Q]
    li = cum[:, None]
    lj = cum[None, :]
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(iota_j <= iota_i, jnp.exp(li - lj), 0.0)
    y = jax.lax.dot(cb * L, dx)                  # [Q, P]

    # inter-chunk: y_i += exp(cum_i) C_i · S_prev   (S_prev: [N, P])
    s_prev = state_ref[...]
    y += jnp.exp(cum)[:, None] * jax.lax.dot(C, s_prev)

    # state update: S = exp(cum_end) S_prev + Σ_j exp(cum_end − cum_j) B_j dx_j^T
    decay_end = jnp.exp(cum[-1] - cum)           # [Q]
    upd = jax.lax.dot_general(B_ * decay_end[:, None], dx,
                              (((0,), (0,)), ((), ())))          # [N, P]
    state_ref[...] = jnp.exp(cum[-1]) * s_prev + upd

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
    dtr = dt.transpose(0, 2, 1).reshape(Bb * H, T)
    br = B_.transpose(0, 2, 1, 3).reshape(Bb * G, T, N)
    cr = C.transpose(0, 2, 1, 3).reshape(Bb * G, T, N)
    use_init = initial_state is not None
    if initial_state is None:
        # dummy (read only under use_init, but must exist for the BlockSpec)
        s0 = jnp.zeros((Bb * H, 1, N, P), jnp.float32)
    else:
        s0 = jnp.swapaxes(initial_state, -1, -2).reshape(Bb * H, 1, N, P)
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
            pl.BlockSpec((1, chunk), lambda bh, ic: (bh, ic)),
            pl.BlockSpec((1,), lambda bh, ic: (bh % H,)),
            pl.BlockSpec((1, chunk, N), lambda bh, ic: (g_idx(bh), ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ic: (g_idx(bh), ic, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bh, ic: (bh, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, N, P), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb * H, T, P), x.dtype),
            jax.ShapeDtypeStruct((Bb * H, N, P), jnp.float32),
        ],
        scratch_shapes=[pl_scratch((N, P))],
        interpret=interpret,
        name="ssd_scan",
    )(xr, dtr, A, br, cr, s0)

    y = y.reshape(Bb, H, T, P).transpose(0, 2, 1, 3)[:, :T0]
    if return_final_state:
        return y, jnp.swapaxes(s_fin.reshape(Bb, H, N, P), -1, -2)
    return y
