"""Draft-model speculator for hybrid-split speculative decoding.

The serving engine's decode loop is memory-bound: every tick streams the
whole target model's weights to produce ONE token per sequence.  A small
draft model (same tokenizer/vocab, far fewer layers) can propose ``k``
tokens cheaply; the target then scores all ``k+1`` positions in a single
paged verify pass (``kernels.paged_verify_attention``) and commits the
accepted prefix plus its own correction token.  Greedy decoding stays
token-exact for ANY draft: the correction token is always the target's
argmax at the first disagreement, so output = what non-speculative greedy
would have produced — the draft only changes *throughput*, never content.

``DraftSpeculator`` owns the draft side: a dense ``SlotKVCache`` whose
slot ids mirror the engine's paged slots, a bucketed prompt prefill, and
a ``propose`` step that runs ``k+1`` draft decode steps under one jit.

Sync invariant (per slot): draft ``cache_len`` == target ``cache_len`` C,
and draft positions ``0..C-1`` hold the same tokens the target has cached;
the pending last token L (KV unwritten) is shared via the engine's
``last_tokens``.  ``propose`` feeds L, d1..dk — k+1 steps, so the LAST
draft token's KV is written too (position C+k); without that extra step a
fully-accepted round (a == k) would leave the draft cache one position
short and the next round would silently skip d_k's KV.  After the target
verifies, the engine calls ``observe`` with its post-commit lengths: the
draft winds back to ``C+1+a`` — positions <= C+a already hold the accepted
tokens, so rewind is a length update, never a copy; rejected suffix KV
beyond the new length is masked garbage that the next round overwrites.

Concurrency: the speculator has NO lock of its own.  Every method is
called with the engine's ``_lock`` held (same discipline as the engine's
``kv``/``last_tokens`` state), so no new lock-order edges appear in the
static analysis.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import build_model
from repro.serving.kv_cache import SlotKVCache, _tree_bytes


def _bucket(n: int, lo: int, hi: int) -> int:
    b = lo
    while b < n and b < hi:
        b *= 2
    return min(b, hi)


class DraftSpeculator:
    """Draft model + dense slot KV mirroring the engine's slots."""

    def __init__(self, cfg, max_slots: int, max_seq: int,
                 params=None, seed: int = 0, min_bucket: int = 16):
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.min_bucket = min_bucket
        self.model = build_model(cfg)
        self.params = (params if params is not None
                       else self.model.init(jax.random.key(seed)))
        self.kv = SlotKVCache(cfg, max_slots, max_seq, dtype=cfg.cdtype)
        self._params_bytes = _tree_bytes(self.params)
        self._prefill = jax.jit(self._prefill_fn)
        # draft caches are donated: propose updates them in place
        self._propose = jax.jit(self._propose_fn, static_argnames=("k",),
                                donate_argnums=(1,))

    # ------------------------------------------------------------- jit fns
    def _prefill_fn(self, params, tokens, last_index, caches):
        _, caches, _ = self.model.prefill(params, {"tokens": tokens}, caches,
                                          last_index=last_index)
        return caches

    def _propose_fn(self, params, caches, tokens, cache_len, active, *, k):
        """k+1 greedy draft steps.  Returns (drafts [B,k], caches, new_len).

        Step i feeds token_i and writes its KV at ``cache_len + i``; the
        extra (k+1)-th step writes d_k's KV so a fully-accepted round
        leaves the cache complete.  Inactive rows re-write one position in
        place and never advance — harmless, overwritten on next use.
        """
        def body(carry, _):
            toks, caches, clen = carry
            logits, caches = self.model.decode(params, toks, caches, clen)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            nxt = jnp.where(active, nxt, toks)
            clen = jnp.where(active, clen + 1, clen)
            return (nxt, caches, clen), nxt

        (_, caches, clen), outs = jax.lax.scan(
            body, (tokens, caches, cache_len), None, length=k + 1)
        drafts = jnp.swapaxes(outs, 0, 1)[:, :k]    # drop the throwaway step
        return drafts, caches, clen

    # -------------------------------------------------------------- public
    def prefill(self, prompt: Sequence[int], slot: int) -> None:
        """Prefill the FULL prompt into the draft cache for ``slot``.

        Monolithic (pow2-bucketed) — the draft has no prefix sharing, so a
        shared-prefix hit on the target still pays a full draft prefill;
        that cost is bounded by the draft being small by construction.
        """
        plen = len(prompt)
        bucket = _bucket(plen, self.min_bucket, self.max_seq)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = np.asarray(prompt, np.int32)
        caches = self.model.init_caches(1, self.max_seq, self.cfg.cdtype)
        caches = self._prefill(self.params, jnp.asarray(toks),
                               jnp.array([plen - 1], jnp.int32), caches)
        self.kv.insert(caches, slot, plen)

    def propose(self, last_tokens: jax.Array, active: jax.Array,
                k: int) -> jax.Array:
        """Greedy-propose k tokens per active slot; returns drafts [B, k]."""
        drafts, self.kv.caches, self.kv.cache_len = self._propose(
            self.params, self.kv.caches, last_tokens, self.kv.cache_len,
            active, k=k)
        return drafts

    def commit_to(self, device) -> None:
        """Pin the draft's params and cache to the engine's device."""
        self.params = jax.device_put(self.params, device)
        self.kv.commit_to(device)

    def observe(self, new_len: jax.Array, active: jax.Array) -> None:
        """Adopt the target's post-commit lengths (rewind past rejects)."""
        self.kv.cache_len = jnp.where(active, new_len, self.kv.cache_len)

    def footprint_bytes(self) -> int:
        """Draft params + dense slot cache — charged to admission/QoS."""
        return self._params_bytes + self.kv.capacity_bytes()
