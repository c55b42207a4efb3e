"""Dispatching wrappers around the Pallas kernels.

Every op has these implementations:
  * ``pallas``   — the TPU kernel (``pl.pallas_call`` with VMEM BlockSpecs);
  * ``interpret``— the same kernel body executed in interpret mode (CPU
                   correctness validation);
  * ``ref``      — the pure-jnp oracle in ``ref.py``;
  * ``blocked``  — flash attention only: the jnp blocked-softmax path
                   (``blocked_attention.py``); every other op maps it to
                   ``ref``.

Dispatch default: TPU backend → pallas, anything else → blocked.  The
dry-run intentionally uses the ref path so ``cost_analysis`` sees XLA
einsum FLOPs.  Force a path globally with ``set_impl("interpret")`` or
per-call with ``impl=...``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref

_IMPL_OVERRIDE: Optional[str] = None


def set_impl(impl: Optional[str]) -> None:
    """Force an implementation globally:
    'pallas' | 'interpret' | 'ref' | 'blocked' | None (auto)."""
    global _IMPL_OVERRIDE
    assert impl in (None, "pallas", "interpret", "ref", "blocked"), impl
    _IMPL_OVERRIDE = impl


def _resolve(impl: Optional[str]) -> str:
    if impl is not None:
        return impl
    if _IMPL_OVERRIDE is not None:
        return _IMPL_OVERRIDE
    # non-TPU default is the blocked flash-semantics path: same O(T)
    # residual memory the TPU kernel has, visible to XLA cost analysis
    return "pallas" if jax.default_backend() == "tpu" else "blocked"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def flash_attention(
    q, k, v, *, causal: bool = True, window: int = 0, softcap: float = 0.0,
    q_positions=None, kv_positions=None, kv_valid_len=None,
    sm_scale: Optional[float] = None, impl: Optional[str] = None,
):
    """[B,Tq,Hq,D] x [B,Tk,Hkv,D] -> [B,Tq,Hq,Dv].  GQA broadcast inside."""
    mode = _resolve(impl)
    if mode == "ref":
        return ref.mha(q, k, v, causal=causal, window=window, softcap=softcap,
                       q_positions=q_positions, kv_positions=kv_positions,
                       kv_valid_len=kv_valid_len, sm_scale=sm_scale)
    if mode == "blocked":
        from repro.kernels.blocked_attention import mha_blocked
        return mha_blocked(q, k, v, causal=causal, window=window,
                           softcap=softcap, q_positions=q_positions,
                           kv_positions=kv_positions,
                           kv_valid_len=kv_valid_len, sm_scale=sm_scale)
    from repro.kernels import flash_attention as fa
    return fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_positions=q_positions, kv_positions=kv_positions,
        kv_valid_len=kv_valid_len, sm_scale=sm_scale,
        interpret=(mode == "interpret"))


def decode_attention(
    q, k_cache, v_cache, cache_len, *, softcap: float = 0.0, window: int = 0,
    sm_scale: Optional[float] = None, impl: Optional[str] = None,
):
    """One-token query [B,Hq,D] against KV cache [B,S,Hkv,D]."""
    mode = _resolve(impl)
    if mode in ("ref", "blocked"):   # decode is already O(S): ref path
        return ref.decode_attention(q, k_cache, v_cache, cache_len,
                                    softcap=softcap, window=window,
                                    sm_scale=sm_scale)
    from repro.kernels import decode_attention as da
    return da.decode_attention(q, k_cache, v_cache, cache_len, softcap=softcap,
                               window=window, sm_scale=sm_scale,
                               interpret=(mode == "interpret"))


def paged_decode_attention(
    q, k_pages, v_pages, page_table, cache_len, *, softcap: float = 0.0,
    window: int = 0, sm_scale: Optional[float] = None,
    k_scale=None, v_scale=None, impl: Optional[str] = None,
):
    """One-token query [B,Hq,D] against a paged pool [P,page,Hkv,D] gathered
    through ``page_table`` [B,MP] (see ``serving.kv_cache.PagedKVCache``).
    ``k_scale``/``v_scale`` [P,page,Hkv] dequantize int8 pools in-kernel."""
    mode = _resolve(impl)
    if mode in ("ref", "blocked"):   # gather + dense decode oracle
        return ref.paged_decode_attention(
            q, k_pages, v_pages, page_table, cache_len, softcap=softcap,
            window=window, sm_scale=sm_scale,
            k_scale=k_scale, v_scale=v_scale)
    from repro.kernels import paged_decode_attention as pda
    return pda.paged_decode_attention(
        q, k_pages, v_pages, page_table, cache_len, softcap=softcap,
        window=window, sm_scale=sm_scale, k_scale=k_scale, v_scale=v_scale,
        interpret=(mode == "interpret"))


def paged_verify_attention(
    q, k_pages, v_pages, page_table, cache_len, *, softcap: float = 0.0,
    window: int = 0, sm_scale: Optional[float] = None,
    k_scale=None, v_scale=None, impl: Optional[str] = None,
):
    """K1-token query [B,K1,Hq,D] (the K1 newest cache slots) against a
    paged pool — the speculative-decoding verify pass: one kernel launch
    scores the draft's k proposals plus the resumption position."""
    mode = _resolve(impl)
    if mode in ("ref", "blocked"):   # gather + dense mha oracle
        return ref.paged_verify_attention(
            q, k_pages, v_pages, page_table, cache_len, softcap=softcap,
            window=window, sm_scale=sm_scale,
            k_scale=k_scale, v_scale=v_scale)
    from repro.kernels import paged_verify_attention as pva
    return pva.paged_verify_attention(
        q, k_pages, v_pages, page_table, cache_len, softcap=softcap,
        window=window, sm_scale=sm_scale, k_scale=k_scale, v_scale=v_scale,
        interpret=(mode == "interpret"))


# ---------------------------------------------------------------------------
# Mamba2 SSD scan
# ---------------------------------------------------------------------------

def ssd_scan(x, dt, A, B_, C, *, chunk: int = 64, initial_state=None,
             return_final_state: bool = False, impl: Optional[str] = None):
    mode = _resolve(impl)
    if mode in ("ref", "blocked"):
        return ref.ssd_scan(x, dt, A, B_, C, chunk=chunk,
                            initial_state=initial_state,
                            return_final_state=return_final_state)
    from repro.kernels import ssd_scan as ss
    return ss.ssd_scan(x, dt, A, B_, C, chunk=chunk,
                       initial_state=initial_state,
                       return_final_state=return_final_state,
                       interpret=(mode == "interpret"))


def ssd_decode_step(x, dt, A, B_, C, state):
    # the decode step is a handful of small einsums; no kernel needed
    return ref.ssd_decode_step(x, dt, A, B_, C, state)


# ---------------------------------------------------------------------------
# fused rmsnorm
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, *, eps: float = 1e-6, impl: Optional[str] = None):
    mode = _resolve(impl)
    if mode in ("ref", "blocked"):
        return ref.rmsnorm(x, scale, eps)
    from repro.kernels import rmsnorm as rn
    return rn.rmsnorm(x, scale, eps=eps, interpret=(mode == "interpret"))
