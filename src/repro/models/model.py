"""Top-level model API: init / loss / prefill / decode for every family.

``Model`` is a thin, pure-functional bundle:

    model = Model(cfg)
    params = model.init(rng)
    loss, metrics = model.loss(params, batch)            # train objective
    logits, caches, clen = model.prefill(params, tokens)  # serving prefill
    logits, caches = model.decode(params, tok, caches, clen)

Inputs per frontend:
  none / vq_tokens : batch["tokens"], batch["labels"]  (int32 [B, T])
  audio_frames     : batch["features"] [B, T, F], batch["targets"] [B, T],
                     batch["mask"] [B, T] (HuBERT masked prediction)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.layers import (apply_embedding, apply_lm_head, dense_init,
                                 init_embedding, init_lm_head)

Params = Dict[str, Any]


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def init(self, key) -> Params:
        cfg = self.cfg
        k_embed, k_stack, k_head, k_front = jax.random.split(key, 4)
        p: Params = {
            "embed": init_embedding(k_embed, cfg),
            "stack": transformer.init_stack(k_stack, cfg),
        }
        head = init_lm_head(k_head, cfg)
        if head is not None:
            p["head"] = head
        if cfg.frontend == "audio_frames":
            p["frontend"] = {
                "w_frontend": dense_init(k_front, (cfg.frontend_dim,
                                                   cfg.d_model), cfg.pdtype),
                "mask_embed": jnp.zeros((cfg.d_model,), cfg.pdtype),
            }
        return p

    def init_abstract(self, key=None) -> Params:
        """Shape/dtype-only params (no allocation) — dry-run & planners."""
        k = jax.random.key(0) if key is None else key
        return jax.eval_shape(self.init, k)

    # -------------------------------------------------------------- embedding
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        if cfg.frontend == "audio_frames":
            feats = batch["features"].astype(cfg.cdtype)
            x = feats @ params["frontend"]["w_frontend"].astype(cfg.cdtype)
            if "mask" in batch:
                me = params["frontend"]["mask_embed"].astype(cfg.cdtype)
                x = jnp.where(batch["mask"][..., None], me[None, None], x)
            return x
        return apply_embedding(params["embed"], batch["tokens"], cfg)

    # ------------------------------------------------------------------ fwd
    def forward(self, params: Params, batch: Dict[str, jax.Array],
                positions: Optional[jax.Array] = None):
        """Full-sequence logits (train path)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        B, T = x.shape[:2]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        x = shard(x, "batch", "act_seq", "embed")
        x, aux, _ = transformer.forward_stack(params["stack"], x, cfg,
                                              positions=positions, mode="train")
        logits = apply_lm_head(params["embed"], params.get("head"), x, cfg)
        logits = shard(logits, "batch", "act_seq", "vocab")
        return logits, aux

    # ----------------------------------------------------------------- loss
    def loss(self, params: Params, batch: Dict[str, jax.Array]):
        cfg = self.cfg
        logits, aux = self.forward(params, batch)
        if cfg.encoder_only:
            targets = batch["targets"]
            weights = batch.get("mask", jnp.ones_like(targets)).astype(jnp.float32)
        else:
            targets = batch["labels"]
            weights = (targets >= 0).astype(jnp.float32)
            targets = jnp.maximum(targets, 0)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        # one-hot contraction instead of take_along_axis: GSPMD turns this
        # into a local einsum + psum over the sharded vocab axis (a gather
        # would all-gather the fp32 logits).
        onehot = jax.nn.one_hot(targets, cfg.vocab_size, dtype=logits.dtype)
        gold = jnp.einsum("btv,btv->bt", logits, onehot).astype(jnp.float32)
        nll = (lse - gold) * weights
        denom = jnp.maximum(jnp.sum(weights), 1.0)
        ce = jnp.sum(nll) / denom
        loss = ce + aux
        metrics = {"loss": loss, "ce": ce, "aux": aux,
                   "tokens": jnp.sum(weights)}
        return loss, metrics

    # -------------------------------------------------------------- serving
    def init_caches(self, batch: int, max_seq: int, dtype=jnp.bfloat16):
        return transformer.init_cache_tree(self.cfg, batch, max_seq, dtype)

    def init_paged_caches(self, num_pages: int, page_size: int,
                          dtype=jnp.bfloat16):
        """Paged KV pools (full-attention families): ``num_pages`` physical
        pages shared across sequences via per-request page tables."""
        return transformer.init_paged_cache_tree(self.cfg, num_pages,
                                                 page_size, dtype)

    def prefill_chunk(self, params: Params, batch: Dict[str, jax.Array],
                      caches: Params, start: jax.Array, new_len: jax.Array,
                      page_table: Optional[jax.Array] = None,
                      state_slot: Optional[jax.Array] = None):
        """Prefill ONE chunk of a prompt, resuming from cached state.

        ``batch["tokens"]`` is the [B, C] chunk (possibly right-padded to a
        bucket on the paged path); ``start`` [B] is the absolute position
        of its first token and ``new_len`` [B] the valid prompt length
        after the chunk.  With ``page_table`` the chunk's KV lands in the
        request's pages and attention gathers the whole cached prefix;
        without it the chunk resumes a dense staging cache (attention over
        the cache prefix; SSM layers resume their carried conv/ssm state).
        ``state_slot`` [1]: the slot whose recurrent state a paged chunk
        resumes and leaves (the pattern family).
        Returns (last-valid-token logits [B, V], updated caches)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        B, T = x.shape[:2]
        positions = start[:, None] + jnp.arange(T)[None]
        x, _, caches = transformer.forward_stack(
            params["stack"], x, cfg, positions=positions, mode="prefill",
            caches=caches, cache_len=new_len, page_table=page_table,
            chunked=True, state_slot=state_slot)
        local_last = jnp.maximum(new_len - start - 1, 0).astype(jnp.int32)
        last = jnp.take_along_axis(x, local_last[:, None, None],
                                   axis=1)[:, 0]
        logits = apply_lm_head(params["embed"], params.get("head"),
                               last[:, None], cfg)
        return logits[:, 0], caches

    def decode_paged(self, params: Params, tokens: jax.Array, caches: Params,
                     page_table: jax.Array, cache_len: jax.Array,
                     active: Optional[jax.Array] = None):
        """One decode step against paged KV pools.  tokens: [B] int32 →
        (logits [B, V], caches); the new token's KV is appended at
        ``cache_len`` through the page table.  Recurrent state advances
        only in the ``active`` [B] rows."""
        cfg = self.cfg
        x = apply_embedding(params["embed"], tokens[:, None], cfg)
        x, _, caches = transformer.forward_stack(
            params["stack"], x, cfg, positions=None, mode="decode",
            caches=caches, cache_len=cache_len, page_table=page_table,
            active=active)
        logits = apply_lm_head(params["embed"], params.get("head"), x, cfg)
        return logits[:, 0], caches

    def verify_paged(self, params: Params, tokens: jax.Array, caches: Params,
                     page_table: jax.Array, cache_len: jax.Array):
        """Speculative verify step against paged KV pools.  tokens:
        [B, K1] int32 (the last committed token + the draft's k proposals)
        → (logits [B, K1, V], caches).  All K1 tokens' KV is appended at
        ``cache_len .. cache_len+K1-1``; the caller winds ``cache_len``
        back past any rejected suffix (stale KV is masked garbage)."""
        cfg = self.cfg
        x = apply_embedding(params["embed"], tokens, cfg)
        x, _, caches = transformer.forward_stack(
            params["stack"], x, cfg, positions=None, mode="verify",
            caches=caches, cache_len=cache_len, page_table=page_table)
        logits = apply_lm_head(params["embed"], params.get("head"), x, cfg)
        return logits, caches

    def prefill(self, params: Params, batch: Dict[str, jax.Array],
                caches: Params, positions: Optional[jax.Array] = None,
                last_index: Optional[jax.Array] = None):
        """Fill caches with a prompt; returns (last-token logits, caches, len).

        ``last_index`` ([B] int32): position of the last *real* prompt token
        when the prompt is right-padded to a bucket (full-attention archs
        only — stateful families must prefill exact lengths)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        B, T = x.shape[:2]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        x, _, caches = transformer.forward_stack(
            params["stack"], x, cfg, positions=positions, mode="prefill",
            caches=caches)
        if last_index is None:
            last = x[:, -1]
            cache_len = positions[:, -1] + 1
        else:
            last = jnp.take_along_axis(
                x, last_index[:, None, None].astype(jnp.int32), axis=1)[:, 0]
            cache_len = last_index + 1
        logits = apply_lm_head(params["embed"], params.get("head"),
                               last[:, None], cfg)
        return logits[:, 0], caches, cache_len

    def decode(self, params: Params, tokens: jax.Array, caches: Params,
               cache_len: jax.Array):
        """One decode step.  tokens: [B] int32 → (logits [B, V], caches)."""
        cfg = self.cfg
        x = apply_embedding(params["embed"], tokens[:, None], cfg)
        x, _, caches = transformer.forward_stack(
            params["stack"], x, cfg, positions=None, mode="decode",
            caches=caches, cache_len=cache_len)
        logits = apply_lm_head(params["embed"], params.get("head"), x, cfg)
        return logits[:, 0], caches


# ---------------------------------------------------------------------------

def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
