"""Paged single-token decode attention as a Pallas TPU kernel.

The KV cache lives in a pool of fixed-size pages (``[P, page, Hkv, D]``)
instead of one dense ``[B, S, Hkv, D]`` tensor; each sequence owns a row
of a page table mapping its logical pages to physical page ids.  The
kernel reads the pool in that stored layout, straight from HBM: each
page, all ``Hkv`` heads of its ``page`` tokens, is one contiguous async
copy into a VMEM buffer.

Grid = (B, cdiv(MP, ppb)), both axes sequential.  One grid step takes a
block of ``ppb`` pages (about 512 tokens, fixed by the static page and
table shapes) of one sequence and computes every query head against it
with an online softmax carried in VMEM across the row's blocks.  Only
live pages are copied and computed: for row ``b`` they are
``[first, ceil(cache_len[b] / page))``, ``first`` being 0, or the page
holding ``cache_len - window`` under a sliding window.  A block outside
that range issues no copy and does no work; the last block copies only
its live pages, and tokens outside the live range (unfetched buffer
rows, or a live page's unwritten tail) are masked out of the logits and
zeroed in V before ``p·v``.  Copies are double-buffered: while a block
computes, the next live block (the row's next, or the next row's first)
is already in flight into the other buffer, tracked by one DMA
semaphore per buffer.

Pools whose pages a copy cannot slice out of HBM (a last dim that is not
a multiple of 128 lanes: head dims below 128, and the per-token scale
planes ``[P, page, Hkv]`` of int8 pools) take a page walk instead: grid
(B, MP), one page of all heads per step through BlockSpecs, whose index
map pins every dead step to the nearest live page so that it issues no
copy.  Both walks share the block computation.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_attention import pl_scratch

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
BLOCK_TOKENS = 512          # tokens a grid step covers (whole pages)


def _live_pages(len_ref, row, *, page: int, window: int):
    """Page range ``[first, end)`` that holds row ``row``'s live tokens."""
    n = len_ref[row]
    end = (n + page - 1) // page
    first = jnp.maximum(n - window, 0) // page if window > 0 else 0
    return first, end


def _copy_kernel(table_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                 v_buf, sem, slot_ref, acc_ref, m_ref, l_ref, *, page: int,
                 ppb: int, batch: int, n_steps: int, window: int, **attend):
    """Multi-page walk: the pool stays in HBM and the kernel copies the
    live pages of each ``ppb``-page block itself, double-buffered."""
    from jax.experimental.pallas import tpu as pltpu

    b, j = pl.program_id(0), pl.program_id(1)
    live = functools.partial(_live_pages, len_ref, page=page, window=window)

    def blocks(row):
        """Block range ``[lo, hi)`` holding the row's live pages."""
        first, end = live(row)
        return first // ppb, (end + ppb - 1) // ppb

    def copy_block(row, blk, slot, wait: bool):
        """Start (or wait for) the copies of block ``blk`` of ``row``'s
        live pages into buffer ``slot``."""
        first, end = live(row)

        def one_page(p, carry):
            pid = table_ref[row, p]
            for src, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                cp = pltpu.make_async_copy(src.at[pid],
                                           buf.at[slot, p - blk * ppb],
                                           sem.at[slot])
                if wait:
                    cp.wait()
                else:
                    cp.start()
            return carry

        jax.lax.fori_loop(jnp.maximum(first, blk * ppb),
                          jnp.minimum(end, (blk + 1) * ppb), one_page, 0)

    _init(j, acc_ref, m_ref, l_ref)

    @pl.when((b == 0) & (j == 0))
    def _first_step():
        slot_ref[0] = 0

    lo, hi = blocks(b)

    @pl.when((j >= lo) & (j < hi))
    def _block():
        slot = slot_ref[0]
        prev_lo, prev_hi = blocks(jnp.maximum(b - 1, 0))

        # the previous live block prefetched this one, unless this is the
        # row's first and the previous row had no live pages
        @pl.when((j == lo) & ((b == 0) | (prev_hi == prev_lo)))
        def _fetch_now():
            copy_block(b, j, slot, wait=False)

        nxt = jnp.minimum(b + 1, batch - 1)
        next_lo, next_hi = blocks(nxt)

        @pl.when(j + 1 < hi)
        def _prefetch_in_row():
            copy_block(b, j + 1, 1 - slot, wait=False)

        @pl.when((j + 1 == hi) & (b + 1 < batch) & (next_hi > next_lo))
        def _prefetch_next_row():
            copy_block(nxt, next_lo, 1 - slot, wait=False)

        copy_block(b, j, slot, wait=True)
        _attend(q_ref, (k_buf.at[slot], v_buf.at[slot]), acc_ref, m_ref,
                l_ref, n=len_ref[b], base=j * ppb * page, window=window,
                **attend)
        slot_ref[0] = 1 - slot

    _finish(j, n_steps - 1, o_ref, acc_ref, l_ref)


def _page_kernel(table_ref, len_ref, q_ref, *rest, page: int, n_steps: int,
                 window: int, **attend):
    """Page-per-step walk, for pools the copies cannot slice (see
    ``paged_decode_attention``): the BlockSpec index map pins dead steps to
    the nearest live page, so they issue no copy, and ``pl.when`` skips
    their compute."""
    del table_ref
    o_ref, acc_ref, m_ref, l_ref = rest[-4:]
    b, j = pl.program_id(0), pl.program_id(1)
    first, end = _live_pages(len_ref, b, page=page, window=window)
    _init(j, acc_ref, m_ref, l_ref)

    @pl.when((j >= first) & (j < end))
    def _page():
        _attend(q_ref, rest[:-4], acc_ref, m_ref, l_ref, n=len_ref[b],
                base=j * page, window=window, **attend)

    _finish(j, n_steps - 1, o_ref, acc_ref, l_ref)


def _init(j, acc_ref, m_ref, l_ref):
    """A row's first grid step resets the online-softmax state."""
    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)


def _finish(j, last, o_ref, acc_ref, l_ref):
    """A row's last grid step writes its output (0 for no live token)."""
    @pl.when(j == last)
    def _():
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = jnp.where(l == 0.0, 0.0, o).astype(o_ref.dtype)


def _attend(q_ref, pages, acc_ref, m_ref, l_ref, *, n, base, window,
            sm_scale, softcap):
    """Fold one block of pages into the online softmax: ``pages`` holds K
    and V refs ``[ppb, page, Hkv, D]`` and, for int8 pools, their scale
    refs ``[ppb, page, Hkv]``; the block's first token is ``base``."""
    k_ref, v_ref, *scales = pages
    ppb, page, hkv, d = k_ref.shape
    rows = ppb * page * hkv
    group = q_ref.shape[1] // hkv
    k = k_ref[...].astype(jnp.float32)                   # [ppb, page, Hkv, D]
    v = v_ref[...].astype(jnp.float32)
    if scales:
        k = k * scales[0][...][..., None]
        v = v * scales[1][...][..., None]
    k = k.reshape(rows, d)
    v = v.reshape(rows, v.shape[-1])
    q = q_ref[0].astype(jnp.float32) * sm_scale          # [Hq, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Hq, rows]
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    def live(pos):
        ok = pos < n
        return ok & (pos >= n - window) if window > 0 else ok

    # row r of the flat block is token r // Hkv of KV head r % Hkv; the
    # query heads of group h see only head h's rows
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
    mask = live(base + col // hkv) & (col % hkv == head)
    s = jnp.where(mask, s, NEG_INF)
    # rows not copied this block (or past the row's length) may hold
    # anything, NaN included: zero them, since 0 · NaN is NaN
    vrow = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    v = jnp.where(live(base + vrow // hkv), v, 0.0)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


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
    page, Dv = k_pages.shape[1], v_pages.shape[3]
    MP = page_table.shape[1]
    scale = sm_scale if sm_scale is not None else D ** -0.5
    quantized = k_scale is not None
    attend = dict(sm_scale=scale, softcap=softcap, window=window, page=page)
    q_spec = pl.BlockSpec((1, Hq, D), lambda b, j, pt, cl: (b, 0, 0))
    o_spec = pl.BlockSpec((1, Hq, Dv), lambda b, j, pt, cl: (b, 0, 0))
    pools = [k_pages, v_pages]
    if quantized:
        pools += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    state = [pl_scratch((Hq, Dv)), pl_scratch((Hq, 1)), pl_scratch((Hq, 1))]

    # A copy cannot slice a page out of an HBM array whose last dim is not
    # a multiple of 128 lanes (head dims below 128, the int8 scale planes):
    # such pools take the page walk, fed one page a step by BlockSpecs.
    if D % 128 == 0 and Dv % 128 == 0 and not quantized:
        ppb = max(1, min(MP, BLOCK_TOKENS // page))
        grid = (B, pl.cdiv(MP, ppb))
        kernel = functools.partial(_copy_kernel, ppb=ppb, batch=B,
                                   n_steps=grid[1], **attend)
        # HBM, not ANY: under ANY the compiler may stage a small pool in
        # VMEM, a copy of the whole pool on every call
        in_specs = [q_spec] + [pl.BlockSpec(memory_space=pltpu.HBM)] * 2
        scratch = [pltpu.VMEM((2, ppb) + x.shape[1:], x.dtype) for x in pools]
        scratch += [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)]
    else:
        def page_at(b, j, pt, cl):
            first, end = _live_pages(cl, b, page=page, window=window)
            return pt[b, jnp.minimum(jnp.maximum(j, first),
                                     jnp.maximum(end - 1, 0))]

        grid = (B, MP)
        kernel = functools.partial(_page_kernel, n_steps=MP, **attend)
        in_specs = [q_spec] + [
            pl.BlockSpec((1,) + x.shape[1:],
                         lambda b, j, pt, cl, r=x.ndim - 1:
                         (page_at(b, j, pt, cl),) + (0,) * r)
            for x in pools]
        scratch = []

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # page_table, cache_len
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        scratch_shapes=scratch + state,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table.astype(jnp.int32), cache_len.astype(jnp.int32), q, *pools)
