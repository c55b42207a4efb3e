"""Fused RMSNorm as a Pallas TPU kernel (forward; the training path uses the
custom_vjp in models/layers.py, which a fused bwd kernel would mirror).

One pass over HBM: each grid step loads a [rows, d] tile into VMEM, computes
f32 row statistics on-tile and writes the normalized tile — versus the
unfused XLA path that can materialize an f32 upcast.  d stays whole per tile
(row statistics need the full row; d ≤ 18432 → ≤ 9 MiB bf16 tile at rows=128
still fits VMEM for every assigned arch at rows ≥ 8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...]
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps).astype(x.dtype)
    o_ref[...] = x * inv * scale_ref[...].astype(x.dtype)


def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 128, interpret: bool = False) -> jax.Array:
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    xr = x.reshape(rows, d)
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
    n = (rows + pad) // br

    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(xr, scale)
    return out[:rows].reshape(orig_shape)
