"""Mamba2 (SSD — state-space duality) block, full-sequence and decode paths.

Full-sequence path uses the chunked SSD algorithm (``kernels.ops.ssd_scan``,
Pallas on TPU / jnp oracle elsewhere).  Decode is the O(1) recurrent step on a
carried state — this is what makes ``long_500k`` decoding trivial for SSM
archs (state is constant-size; no KV cache).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.config import ModelConfig
from repro.models.layers import dense_init, rms_norm_simple


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    di = cfg.d_inner
    H = cfg.ssm_heads
    conv_dim = di + 2 * s.n_groups * s.d_state
    return s, di, H, conv_dim


def init_mamba2(key, cfg: ModelConfig):
    s, di, H, conv_dim = _dims(cfg)
    d = cfg.d_model
    dt = cfg.pdtype
    ks = jax.random.split(key, 5)
    proj_dim = 2 * di + 2 * s.n_groups * s.d_state + H   # z, x, B, C, dt
    # dt bias init so softplus(dt_bias) spans [dt_min, dt_max]
    u = jax.random.uniform(ks[2], (H,), jnp.float32)
    dt_init = jnp.exp(u * (jnp.log(s.dt_max) - jnp.log(s.dt_min))
                      + jnp.log(s.dt_min))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))    # inverse softplus
    a_lo, a_hi = s.a_init_range
    A = jax.random.uniform(ks[3], (H,), jnp.float32, a_lo, a_hi)
    return {
        "in_proj": dense_init(ks[0], (d, proj_dim), dt),
        "conv_w": (jax.random.normal(ks[1], (conv_dim, s.d_conv), jnp.float32)
                   * (s.d_conv ** -0.5)).astype(dt),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "dt_bias": dt_bias.astype(jnp.float32),
        "a_log": jnp.log(A).astype(jnp.float32),
        "d_skip": jnp.ones((H,), jnp.float32),
        "out_norm": jnp.ones((di,), dt),
        "out_proj": dense_init(ks[4], (di, d), dt, fan_in=di),
    }


def init_mamba2_state(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    s, di, H, conv_dim = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, H, s.head_dim, s.d_state), jnp.float32),
    }


def _causal_depthwise_conv(x, w, b):
    """x: [B, T, C]; w: [C, W] — causal depthwise conv via shifted adds."""
    W = w.shape[1]
    out = x * w[:, W - 1]
    for i in range(1, W):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, : x.shape[1]]
        out = out + shifted * w[:, W - 1 - i]
    return out + b


def _split_proj(zxbcdt, cfg: ModelConfig):
    s, di, H, conv_dim = _dims(cfg)
    gn = s.n_groups * s.d_state
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di: di + conv_dim]
    dt = zxbcdt[..., di + conv_dim:]
    return z, xBC, dt


def _split_xbc(xBC, cfg: ModelConfig):
    s, di, H, _ = _dims(cfg)
    gn = s.n_groups * s.d_state
    x_in = xBC[..., :di]
    B_ = xBC[..., di: di + gn]
    C_ = xBC[..., di + gn:]
    return x_in, B_, C_


def gated_out_norm(y, z, scale, cfg: ModelConfig):
    """The gated RMSNorm before ``out_proj``: ``y·silu(z)`` normalised over
    each of ``n_groups`` groups of ``d_inner / n_groups`` channels (Mamba-2's
    and Nemotron-H's ``RMSNormGated``), then scaled.  One group is the
    whole of ``d_inner``."""
    h = y * jax.nn.silu(z)
    g = cfg.ssm.n_groups
    if g == 1:
        return rms_norm_simple(h, scale, cfg.norm_eps)
    shape = h.shape
    hg = h.reshape(*shape[:-1], g, shape[-1] // g)
    hg = rms_norm_simple(hg, jnp.ones((shape[-1] // g,), h.dtype),
                         cfg.norm_eps)
    return hg.reshape(shape) * scale.astype(h.dtype)


def apply_mamba2(
    p, x: jax.Array, cfg: ModelConfig,
    state: Optional[dict] = None,
    valid_len: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[dict]]:
    """Full-sequence SSD pass.  If ``state`` is given, the final recurrent
    state is returned (prefill → decode handoff), AND the carried conv tail
    is prepended to the conv input — so a prefill can resume mid-prompt
    (chunked prefill): the first ``d_conv - 1`` tokens of a chunk see the
    previous chunk's pre-conv stream instead of zero padding.  A zero
    conv state reproduces the stateless path exactly.

    ``valid_len`` [B]: tokens of the chunk that are real; the rest is
    bucket padding, which leaves the state as the last real token left it
    (its ``dt`` is 0, so it neither decays nor feeds the state, and the conv
    tail is taken from the real tokens)."""
    s, di, H, conv_dim = _dims(cfg)
    B, T, _ = x.shape
    dt_c = cfg.cdtype
    zxbcdt = x.astype(dt_c) @ p["in_proj"].astype(dt_c)
    z, xBC_raw, dt_raw = _split_proj(zxbcdt, cfg)
    if state is not None:
        pre = jnp.concatenate([state["conv"].astype(dt_c), xBC_raw], axis=1)
        conv_out = _causal_depthwise_conv(
            pre, p["conv_w"].astype(dt_c),
            p["conv_b"].astype(dt_c))[:, s.d_conv - 1:]
    else:
        conv_out = _causal_depthwise_conv(
            xBC_raw, p["conv_w"].astype(dt_c), p["conv_b"].astype(dt_c))
    xBC = jax.nn.silu(conv_out)
    x_in, B_, C_ = _split_xbc(xBC, cfg)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])   # [B,T,H]
    if valid_len is not None:
        real = jnp.arange(T)[None, :] < valid_len[:, None]            # [B,T]
        dt = jnp.where(real[..., None], dt, 0.0)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    xh = x_in.reshape(B, T, H, s.head_dim)
    Bh = B_.reshape(B, T, s.n_groups, s.d_state)
    Ch = C_.reshape(B, T, s.n_groups, s.d_state)

    # the SSD block is the sequence when it is shorter than a block and
    # tiles as one (an engine chunk): the result does not depend on it
    chunk = T if T < s.chunk_size and T % 8 == 0 else s.chunk_size
    init_ssm = state["ssm"] if state is not None else None
    if state is not None:
        y, final = ops.ssd_scan(xh, dt, A, Bh, Ch, chunk=chunk,
                                initial_state=init_ssm, return_final_state=True)
    else:
        y = ops.ssd_scan(xh, dt, A, Bh, Ch, chunk=chunk)
        final = None

    y = y + xh * p["d_skip"][None, None, :, None].astype(y.dtype)
    y = y.reshape(B, T, di)
    y = gated_out_norm(y, z, p["out_norm"], cfg)
    out = y @ p["out_proj"].astype(dt_c)

    if state is not None:
        # conv state holds the *pre-conv* xBC stream tail: the d_conv - 1
        # entries before the first padded token
        stream = jnp.concatenate([state["conv"].astype(dt_c), xBC_raw], axis=1)
        if valid_len is None:
            new_conv = stream[:, -(s.d_conv - 1):]
        else:
            new_conv = jax.vmap(
                lambda r, n: jax.lax.dynamic_slice_in_dim(
                    r, n, s.d_conv - 1, axis=0))(stream, valid_len)
        state = {"conv": new_conv, "ssm": final}
    return out, state


def decode_step_mamba2(
    p, x: jax.Array, cfg: ModelConfig, state: dict,
    active: Optional[jax.Array] = None,
) -> Tuple[jax.Array, dict]:
    """x: [B, 1, d] → (out [B, 1, d], new state).  O(1) per token.  Rows
    where ``active`` [B] is False keep their state as it was."""
    s, di, H, conv_dim = _dims(cfg)
    B = x.shape[0]
    dt_c = cfg.cdtype
    zxbcdt = x[:, 0].astype(dt_c) @ p["in_proj"].astype(dt_c)   # [B, proj]
    z, xBC, dt_raw = _split_proj(zxbcdt, cfg)

    window = jnp.concatenate([state["conv"].astype(dt_c), xBC[:, None]], axis=1)
    w = p["conv_w"].astype(dt_c)                                # [C, W]
    # window[:, i] holds x_{t-(W-1-i)} → tap weight w[:, i]
    conv_out = jnp.einsum("bwc,cw->bc", window, w)
    xBC_c = jax.nn.silu(conv_out + p["conv_b"].astype(dt_c))
    x_in, B_, C_ = _split_xbc(xBC_c, cfg)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # [B,H]
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    xh = x_in.reshape(B, H, s.head_dim)
    Bh = B_.reshape(B, s.n_groups, s.d_state)
    Ch = C_.reshape(B, s.n_groups, s.d_state)
    y, new_ssm = ops.ssd_decode_step(xh, dt, A, Bh, Ch, state["ssm"])
    y = y + xh * p["d_skip"][None, :, None].astype(y.dtype)
    y = y.reshape(B, di)
    y = gated_out_norm(y, z, p["out_norm"], cfg)
    out = (y @ p["out_proj"].astype(dt_c))[:, None]
    new_state = {"conv": window[:, 1:], "ssm": new_ssm}
    if active is not None:
        new_state = jax.tree.map(
            lambda new, old: jnp.where(
                active.reshape((B,) + (1,) * (new.ndim - 1)), new,
                old.astype(new.dtype)), new_state, state)
    return out, new_state
