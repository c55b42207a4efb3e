"""Block stacks for every family, with scan-over-layers + remat.

Layouts
-------
dense / encoder : L × [attn + MLP]                  (scan over L)
moe             : n_dense × [attn + MLP] then (L−n_dense) × [attn + MoE]
ssm             : L × [mamba2]
hybrid (zamba2) : ⌊L/e⌋ super-blocks of (e × mamba2 + 1 shared attn+MLP
                  application, weights shared) + (L mod e) trailing mamba2
pattern (nemotron-h) : ``layer_pattern``, one pre-norm mixer per layer with
                  its own weights — mamba2 (M), MLP (-) or attention (*);
                  each run of one kind is stacked and scanned

All stacks run in three modes sharing one code path:
  train   — no cache;
  prefill — per-layer caches filled, returned stacked;
  decode  — one token, caches updated in place (functionally).

Caches are stacked along a leading layer axis and threaded through
``lax.scan`` as per-iteration slices, so the HLO stays O(1) in depth.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models import attention, mamba2, moe as moe_lib
from repro.models.config import ModelConfig
from repro.models.layers import apply_mlp, apply_norm, init_mlp, init_norm

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# single blocks
# ---------------------------------------------------------------------------

def init_attn_block(key, cfg: ModelConfig, d_ff: Optional[int] = None,
                    use_moe: bool = False) -> Params:
    k1, k2 = jax.random.split(key)
    p = {
        "attn": attention.init_attention(k1, cfg),
        "norm1": init_norm(cfg),
    }
    if not cfg.parallel_block:
        p["norm2"] = init_norm(cfg)
    if use_moe:
        p["moe"] = moe_lib.init_moe(k2, cfg)
    else:
        p["mlp"] = init_mlp(k2, cfg, d_ff=d_ff)
    return p


def init_mamba_block(key, cfg: ModelConfig) -> Params:
    return {"mamba": mamba2.init_mamba2(key, cfg), "norm": init_norm(cfg)}


def _attn_call(bp, x, cfg, positions, cache, cache_len, mode,
               page_table=None, chunked=False):
    if page_table is not None:
        # paged serving path: `cache` is this layer's page pool; in chunked
        # prefill `cache_len` carries the post-chunk valid length
        if mode == "decode":
            return attention.decode_step_paged(bp["attn"], x, cfg, cache,
                                               page_table, cache_len)
        if mode == "verify":
            # speculative verify: all K1 draft/resumption tokens in one pass
            return attention.verify_step_paged(bp["attn"], x, cfg, cache,
                                               page_table, cache_len)
        return attention.prefill_chunk_paged(bp["attn"], x, cfg, cache,
                                             page_table, positions, cache_len)
    if mode == "decode":
        return attention.decode_step(bp["attn"], x, cfg, cache, cache_len)
    if chunked and mode == "prefill":
        # chunk-resume prefill into a dense staging cache: attend over the
        # already-cached prefix, not just the chunk
        return attention.prefill_chunk_dense(bp["attn"], x, cfg, cache,
                                             positions, cache_len)
    return attention.attend(bp["attn"], x, cfg, positions=positions,
                            causal=not cfg.encoder_only,
                            cache=cache if mode == "prefill" else None)


def attn_block(bp: Params, x, cfg: ModelConfig, *, positions, mode: str,
               cache=None, cache_len=None, use_moe: bool = False,
               page_table=None, chunked: bool = False):
    """Returns (x, aux, new_cache)."""
    x = shard(x, "batch", "act_seq", "embed")
    h = apply_norm(bp["norm1"], x, cfg)
    attn_out, new_cache = _attn_call(bp, h, cfg, positions, cache, cache_len,
                                     mode, page_table, chunked)
    aux = jnp.zeros((), jnp.float32)
    if cfg.parallel_block:
        ff = apply_mlp(bp["mlp"], h, cfg)
        x = x + attn_out + ff
    else:
        x = x + attn_out
        h2 = apply_norm(bp["norm2"], x, cfg)
        if use_moe:
            ff, aux = moe_lib.apply_moe(bp["moe"], h2, cfg)
        else:
            ff = apply_mlp(bp["mlp"], h2, cfg)
        x = x + ff
    x = shard(x, "batch", "act_seq", "embed")
    return x, aux, new_cache


def mamba_block(bp: Params, x, cfg: ModelConfig, *, mode: str, state=None,
                valid_len=None, active=None):
    x = shard(x, "batch", "act_seq", "embed")
    h = apply_norm(bp["norm"], x, cfg)
    if mode == "decode":
        out, new_state = mamba2.decode_step_mamba2(bp["mamba"], h, cfg, state,
                                                   active=active)
    else:
        out, new_state = mamba2.apply_mamba2(
            bp["mamba"], h, cfg, state=state if mode == "prefill" else None,
            valid_len=valid_len)
    return x + out, new_state


def init_pattern_layer(key, cfg: ModelConfig, kind: str) -> Params:
    """One layer of a ``layer_pattern``: its norm and its one mixer."""
    if kind == "M":
        return init_mamba_block(key, cfg)
    if kind == "-":
        return {"mlp": init_mlp(key, cfg), "norm": init_norm(cfg)}
    return {"attn": attention.init_attention(key, cfg), "norm": init_norm(cfg)}


# ---------------------------------------------------------------------------
# stacked init
# ---------------------------------------------------------------------------

def _stack_init(fn, key, n: int):
    if n == 0:
        return None
    return jax.vmap(fn)(jax.random.split(key, n))


def init_stack(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4)
    fam = cfg.family
    if fam in ("dense", "encoder"):
        blocks = _stack_init(lambda k: init_attn_block(k, cfg), ks[0],
                             cfg.num_layers)
        return {"blocks": blocks, "final_norm": init_norm(cfg)}
    if fam == "moe":
        m = cfg.moe
        nd = m.first_dense_layers
        p: Params = {
            "blocks": _stack_init(
                lambda k: init_attn_block(k, cfg, use_moe=True), ks[0],
                cfg.num_layers - nd),
            "final_norm": init_norm(cfg),
        }
        if nd:
            p["dense_blocks"] = _stack_init(
                lambda k: init_attn_block(k, cfg, d_ff=m.first_dense_d_ff),
                ks[1], nd)
        return p
    if fam == "ssm":
        blocks = _stack_init(lambda k: init_mamba_block(k, cfg), ks[0],
                             cfg.num_layers)
        return {"blocks": blocks, "final_norm": init_norm(cfg)}
    if fam == "hybrid":
        e = cfg.hybrid_attn_every
        n_super = cfg.num_layers // e
        rem = cfg.num_layers - n_super * e
        p = {
            "super_blocks": jax.vmap(
                lambda k: jax.vmap(lambda kk: init_mamba_block(kk, cfg))(
                    jax.random.split(k, e)))(jax.random.split(ks[0], n_super)),
            "shared_attn": init_attn_block(ks[1], cfg),
            "final_norm": init_norm(cfg),
        }
        if rem:
            p["tail_blocks"] = _stack_init(lambda k: init_mamba_block(k, cfg),
                                           ks[2], rem)
        return p
    if fam == "pattern":
        runs = cfg.pattern_runs
        return {"runs": [
            _stack_init(functools.partial(init_pattern_layer, cfg=cfg,
                                          kind=kind), k, n)
            for (kind, n), k in zip(runs, jax.random.split(ks[0], len(runs)))],
            "final_norm": init_norm(cfg)}
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# cache init (stacked along layer axis)
# ---------------------------------------------------------------------------

def _stack_tree(tree, n: int):
    return jax.tree.map(lambda a: jnp.zeros((n,) + a.shape, a.dtype), tree)


def init_paged_cache_tree(cfg: ModelConfig, num_pages: int, page_size: int,
                          dtype=jnp.bfloat16, slots: int = 0) -> Params:
    """Paged pools stacked along the layer axis (``[L, P, page, H, D]``
    leaves).  One page table row (owned by the serving engine) addresses
    the same logical pages in every layer's pool.  Full-attention families
    page; the pattern family holds, beside its attention runs' pools, each
    mamba2 run's recurrent state for ``slots`` rows under ``"mamba"``
    (``[n, slots, ...]`` leaves).  Other stateful families keep the dense
    slot cache."""
    fam = cfg.family
    one = attention.init_paged_pool(cfg, num_pages, page_size, dtype)
    if fam == "pattern":
        runs = cfg.pattern_runs
        return {"attn": [_stack_tree(one, n) for k, n in runs if k == "*"],
                # float32 SSM state; the conv tail in the compute dtype,
                # the dtype its stream is made in
                "mamba": [_stack_tree(mamba2.init_mamba2_state(
                    cfg, slots, cfg.cdtype), n) for k, n in runs if k == "M"]}
    if fam in ("dense", "encoder"):
        return {"attn": _stack_tree(one, cfg.num_layers)}
    if fam == "moe":
        nd = cfg.moe.first_dense_layers
        c = {"attn": _stack_tree(one, cfg.num_layers - nd)}
        if nd:
            c["attn_dense"] = _stack_tree(one, nd)
        return c
    raise ValueError(f"paged KV cache unsupported for family {fam!r}")


def init_cache_tree(cfg: ModelConfig, batch: int, max_seq: int,
                    dtype=jnp.bfloat16) -> Params:
    fam = cfg.family
    if fam in ("dense", "encoder"):
        one = attention.init_cache(cfg, batch, max_seq, dtype)
        return {"attn": _stack_tree(one, cfg.num_layers)}
    if fam == "moe":
        one = attention.init_cache(cfg, batch, max_seq, dtype)
        nd = cfg.moe.first_dense_layers
        c = {"attn": _stack_tree(one, cfg.num_layers - nd)}
        if nd:
            c["attn_dense"] = _stack_tree(one, nd)
        return c
    if fam == "ssm":
        one = mamba2.init_mamba2_state(cfg, batch)
        return {"mamba": _stack_tree(one, cfg.num_layers)}
    if fam == "hybrid":
        e = cfg.hybrid_attn_every
        n_super = cfg.num_layers // e
        rem = cfg.num_layers - n_super * e
        mstate = mamba2.init_mamba2_state(cfg, batch)
        astate = attention.init_cache(cfg, batch, max_seq, dtype)
        c = {"mamba": _stack_tree(_stack_tree(mstate, e), n_super),
             "attn": _stack_tree(astate, n_super)}
        if rem:
            c["mamba_tail"] = _stack_tree(mstate, rem)
        return c
    if fam == "pattern":
        runs = cfg.pattern_runs
        astate = attention.init_cache(cfg, batch, max_seq, dtype)
        return {"attn": [_stack_tree(astate, n) for k, n in runs if k == "*"],
                "mamba": [_stack_tree(mamba2.init_mamba2_state(
                    cfg, batch, cfg.cdtype), n) for k, n in runs if k == "M"]}
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# scanning machinery
# ---------------------------------------------------------------------------

def _remat(fn, cfg: ModelConfig, mode: str):
    if mode != "train" or cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "full":
        policy = jax.checkpoint_policies.nothing_saveable
    else:
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint(fn, policy=policy)


def _scan_attn_blocks(blocks, x, cfg, *, positions, mode, caches, cache_len,
                      use_moe: bool, page_table=None, chunked: bool = False):
    def body(carry, xs):
        x, aux = carry
        bp, cache = xs
        x, aux_i, new_cache = attn_block(
            bp, x, cfg, positions=positions, mode=mode, cache=cache,
            cache_len=cache_len, use_moe=use_moe, page_table=page_table,
            chunked=chunked)
        return (x, aux + aux_i), new_cache

    body = _remat(body, cfg, mode)
    n = jax.tree.leaves(blocks)[0].shape[0]
    xs = (blocks, caches)
    if cfg.scan_layers:
        (x, aux), new_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    else:
        aux = jnp.zeros((), jnp.float32)
        outs = []
        for i in range(n):
            sl = jax.tree.map(lambda a: a[i], xs)
            (x, aux), nc = body((x, aux), sl)
            outs.append(nc)
        new_caches = (jax.tree.map(lambda *ls: jnp.stack(ls), *outs)
                      if outs and outs[0] is not None else None)
    return x, aux, new_caches


def _scan_mamba_blocks(blocks, x, cfg, *, mode, states):
    def body(carry, xs):
        bp, st = xs
        x, new_state = mamba_block(bp, carry, cfg, mode=mode, state=st)
        return x, new_state

    body = _remat(body, cfg, mode)
    if cfg.scan_layers:
        x, new_states = jax.lax.scan(body, x, (blocks, states))
    else:
        n = jax.tree.leaves(blocks)[0].shape[0]
        outs = []
        for i in range(n):
            sl = jax.tree.map(lambda a: a[i], (blocks, states))
            x, ns = body(x, sl)
            outs.append(ns)
        new_states = (jax.tree.map(lambda *ls: jnp.stack(ls), *outs)
                      if outs and outs[0] is not None else None)
    return x, new_states


def _none_like(tree, n: int):
    """Scan xs placeholder when no cache is threaded (train mode)."""
    return None


def _pattern_layer(kind: str, bp, x, cache, cfg: ModelConfig, *, positions,
                   mode, cache_len, page_table, chunked, valid_len, active):
    """One layer of a ``layer_pattern``: x + mixer(norm(x))."""
    if kind == "M":
        return mamba_block(bp, x, cfg, mode=mode, state=cache,
                           valid_len=valid_len, active=active)
    x = shard(x, "batch", "act_seq", "embed")
    h = apply_norm(bp["norm"], x, cfg)
    if kind == "-":
        return x + apply_mlp(bp["mlp"], h, cfg), None
    out, new_cache = _attn_call(bp, h, cfg, positions, cache, cache_len, mode,
                                page_table, chunked)
    return x + out, new_cache


def _forward_pattern(params, x, cfg: ModelConfig, *, positions, mode, caches,
                     cache_len, page_table, chunked, state_slot, active):
    """Walk ``cfg.layer_pattern`` run by run.  Caches are
    ``{"attn": [...], "mamba": [...]}``, one entry per run of that kind in
    pattern order.  With ``state_slot`` (paged chunked prefill, batch 1)
    the mamba runs read and write row ``state_slot[0]`` of their state;
    a paged decode's rows are the slots themselves."""
    new_caches = {"attn": [], "mamba": []} if caches else None
    # prefill chunks past the prompt's end are bucket padding
    valid_len = cache_len - positions[:, 0] \
        if mode == "prefill" and chunked else None
    nxt = {"*": 0, "M": 0}
    for blocks, (kind, n) in zip(params["runs"], cfg.pattern_runs):
        key = {"*": "attn", "M": "mamba"}.get(kind)
        c = None
        if caches and key is not None:
            c = caches[key][nxt[kind]]
            nxt[kind] += 1
        whole = c
        if c is not None and kind == "M" and state_slot is not None:
            c = jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(
                a, state_slot[0], 1, axis=1), whole)

        def body(x, xs, kind=kind):
            bp, cache = xs
            return _pattern_layer(kind, bp, x, cache, cfg,
                                  positions=positions, mode=mode,
                                  cache_len=cache_len, page_table=page_table,
                                  chunked=chunked, valid_len=valid_len,
                                  active=active)

        body = _remat(body, cfg, mode)
        if n > 1 and cfg.scan_layers:
            x, nc = jax.lax.scan(body, x, (blocks, c))
        else:
            outs = []
            for i in range(n):
                x, o = body(x, jax.tree.map(lambda a: a[i], (blocks, c)))
                outs.append(o)
            nc = None if outs[0] is None else \
                jax.tree.map(lambda *ls: jnp.stack(ls), *outs)
        if nc is None:
            continue
        nc = jax.tree.map(lambda new, old: new.astype(old.dtype), nc, c)
        if kind == "M" and state_slot is not None:
            nc = jax.tree.map(lambda a, r: jax.lax.dynamic_update_slice_in_dim(
                a, r, state_slot[0], axis=1), whole, nc)
        new_caches[key].append(nc)
    return x, new_caches


# ---------------------------------------------------------------------------
# full stacks
# ---------------------------------------------------------------------------

def forward_stack(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    mode: str = "train",                    # train | prefill | decode | verify
    caches: Optional[Params] = None,
    cache_len: Optional[jax.Array] = None,
    page_table: Optional[jax.Array] = None,  # [B, MP] → paged attn caches
    chunked: bool = False,                   # prefill resumes a cached prefix
    state_slot: Optional[jax.Array] = None,  # [1] slot of a paged chunk
    active: Optional[jax.Array] = None,      # [B] decode rows that advance
) -> Tuple[jax.Array, jax.Array, Optional[Params]]:
    """Returns (hidden, aux_loss, new_caches).  ``state_slot`` and
    ``active`` concern recurrent state (the pattern family) alone."""
    fam = cfg.family
    assert mode in ("train", "prefill", "decode", "verify")
    assert mode != "verify" or page_table is not None, \
        "verify mode is paged-only (speculative decoding)"
    if page_table is not None:
        assert fam in ("dense", "encoder", "moe", "pattern"), \
            f"paged attention unsupported for family {fam!r}"
    if mode == "train":
        caches = None
    new_caches: Optional[Params] = None

    if fam == "pattern":
        assert mode != "verify", "the pattern family does not verify drafts"
        aux = jnp.zeros((), jnp.float32)
        x, new_caches = _forward_pattern(
            params, x, cfg, positions=positions, mode=mode, caches=caches,
            cache_len=cache_len, page_table=page_table, chunked=chunked,
            state_slot=state_slot, active=active)

    elif fam in ("dense", "encoder"):
        c = caches["attn"] if caches else None
        x, aux, nc = _scan_attn_blocks(
            params["blocks"], x, cfg, positions=positions, mode=mode,
            caches=c, cache_len=cache_len, use_moe=False,
            page_table=page_table, chunked=chunked)
        new_caches = {"attn": nc} if nc is not None else None

    elif fam == "moe":
        aux = jnp.zeros((), jnp.float32)
        new_caches = {} if caches else None
        if "dense_blocks" in params:
            cd = caches["attn_dense"] if caches else None
            x, aux_d, ncd = _scan_attn_blocks(
                params["dense_blocks"], x, cfg, positions=positions, mode=mode,
                caches=cd, cache_len=cache_len, use_moe=False,
                page_table=page_table, chunked=chunked)
            aux = aux + aux_d
            if ncd is not None:
                new_caches["attn_dense"] = ncd
        c = caches["attn"] if caches else None
        x, aux_m, nc = _scan_attn_blocks(
            params["blocks"], x, cfg, positions=positions, mode=mode,
            caches=c, cache_len=cache_len, use_moe=True,
            page_table=page_table, chunked=chunked)
        aux = aux + aux_m
        if nc is not None:
            new_caches["attn"] = nc

    elif fam == "ssm":
        c = caches["mamba"] if caches else None
        x, nc = _scan_mamba_blocks(params["blocks"], x, cfg, mode=mode, states=c)
        aux = jnp.zeros((), jnp.float32)
        new_caches = {"mamba": nc} if nc is not None else None

    elif fam == "hybrid":
        aux = jnp.zeros((), jnp.float32)
        shared = params["shared_attn"]
        new_caches = {} if caches else None

        def super_body(carry, xs):
            x, aux = carry
            mamba_params, mamba_states, attn_cache = xs
            x, new_mstates = _scan_mamba_blocks(
                mamba_params, x, cfg, mode=mode, states=mamba_states)
            x, aux_i, new_acache = attn_block(
                shared, x, cfg, positions=positions, mode=mode,
                cache=attn_cache, cache_len=cache_len, use_moe=False,
                chunked=chunked)
            return (x, aux + aux_i), (new_mstates, new_acache)

        super_body = _remat(super_body, cfg, mode)
        mc = caches["mamba"] if caches else None
        ac = caches["attn"] if caches else None
        if cfg.scan_layers:
            (x, aux), (new_m, new_a) = jax.lax.scan(
                super_body, (x, aux), (params["super_blocks"], mc, ac))
        else:
            n_super = jax.tree.leaves(params["super_blocks"])[0].shape[0]
            outs = []
            for i in range(n_super):
                sl = jax.tree.map(lambda a: a[i],
                                  (params["super_blocks"], mc, ac))
                (x, aux), o = super_body((x, aux), sl)
                outs.append(o)
            if outs and outs[0][0] is not None:
                new_m = jax.tree.map(lambda *ls: jnp.stack(ls),
                                     *[o[0] for o in outs])
                new_a = jax.tree.map(lambda *ls: jnp.stack(ls),
                                     *[o[1] for o in outs])
            else:
                new_m = new_a = None
        if caches:
            new_caches["mamba"], new_caches["attn"] = new_m, new_a
        if "tail_blocks" in params:
            tc = caches["mamba_tail"] if caches else None
            x, ntc = _scan_mamba_blocks(params["tail_blocks"], x, cfg,
                                        mode=mode, states=tc)
            if ntc is not None:
                new_caches["mamba_tail"] = ntc
    else:
        raise ValueError(fam)

    x = apply_norm(params["final_norm"], x, cfg)
    return x, aux, new_caches
