"""KV cache managers for continuous batching: paged pools + dense slots.

``PagedKVCache`` (full-attention families) replaces the dense
``max_slots × max_seq`` pre-allocation with a pool of fixed-size pages:
every layer holds a ``[num_pages, page_size, Hkv, D]`` pool, and each
admitted request owns a page-table row mapping its logical pages to
physical ones.  Admission reserves exactly ``ceil(tokens / page_size)``
pages, so the engine's HBM story is *pages-in-use*, not worst-case rows —
a half-full engine serving short prompts holds a fraction of the dense
cache's bytes, and ``num_pages`` can be provisioned below the dense
equivalent to shrink the static pool itself.  Physical page 0 is the
trash page: masked writes (bucket padding, unowned decode rows) are
redirected there, so it is never handed to a request.

Pages are **refcounted** so the prefix-sharing layer
(``serving/prefix/``) can attach one physical page to many requests:
``alloc`` accepts matched prefix pages by reference (refcount bump, no
copy), a mid-page divergence is resolved *eagerly* at admission by
copying the boundary page into a private one (``cow_src``), and ``free``
decrements instead of unconditionally returning pages — a page rejoins
the free list only when its last holder (request or radix node) lets go.
Shared pages are never written: the engine prefills from the divergence
point into private pages and decode appends land past the prompt, so the
trash-page story for masked writes is unchanged.  ``bytes_in_use`` counts
each physical page once, which makes admission and
``dynamic_footprint_bytes`` automatically *marginal* (post-sharing).

Recurrent state (the pattern family's mamba2 layers) lives in the same
``pools`` tree, under ``"mamba"``: each slot holds its conv tail and SSM
state beside the attention pages.  A slot's state is zeroed when a request
is admitted to it (``reset_state``), carried across that request's prefill
chunks and advanced by its decode steps; a freed slot's state is left for
the next admission to zero.  Pages carry no state, so such a model shares
no prefix.

``SlotKVCache`` keeps the original dense design for the stateful families
(SSM state / SWA ring buffers / MLA latent caches), where the per-layer
cache is already recurrent-state- or window-bounded and paging the
sequence axis buys nothing.  Both managers expose the same byte
accounting (``bytes_in_use`` / ``capacity_bytes`` /
``dense_equivalent_bytes``) so telemetry and admission read one surface.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models import transformer


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def kv_bytes_per_token(cfg: ModelConfig, dtype=jnp.bfloat16) -> int:
    """Exact per-token KV footprint for an arch: probe a 1-page,
    1-token-per-page paged tree (cheap — a few KiB) and sum its leaves."""
    return _tree_bytes(transformer.init_paged_cache_tree(cfg, 1, 1, dtype))


def autotune_page_size(cfg: ModelConfig, dtype=jnp.bfloat16,
                       target_page_bytes: int = 256 * 1024) -> int:
    """Pick ``page_size`` from the arch's KV bytes-per-token: the
    power-of-two in [8, 128] whose page lands nearest
    ``target_page_bytes``.  Wide-KV archs get small pages (fine-grained
    sharing/eviction without blowing up the page-table transfer); skinny
    archs get big pages (fewer table entries per sequence, less
    fragmentation).  Pure host math — no device allocation beyond the
    one-token probe."""
    bpt = max(kv_bytes_per_token(cfg, dtype), 1)
    best = min((8 << i for i in range(5)),        # 8, 16, 32, 64, 128
               key=lambda ps: abs(ps * bpt - target_page_bytes))
    return best


class SlotKVCache:
    """Dense slot cache: one big tree of ``max_slots`` sequences (stacked
    along the batch axis of every leaf).  Requests claim a slot, prefill
    produces a batch-1 cache that is scattered into the slot, and the
    decode step advances all slots together.  Sliding-window archs keep
    their ring-buffer semantics (the per-layer cache capacity is already
    window-bounded by ``attention.cache_capacity``); SSM/hybrid archs
    store recurrent states in the same tree."""

    def __init__(self, cfg: ModelConfig, max_slots: int, max_seq: int,
                 dtype=jnp.bfloat16):
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.caches = transformer.init_cache_tree(cfg, max_slots, max_seq,
                                                  dtype)
        # probe batch axes by diffing TWO tiny trees (1 vs 2 slots): O(1)
        # memory regardless of max_slots — probing against the real cache
        # would transiently double KV HBM — and well-defined for
        # max_slots == 1 (where a 1-slot probe has no differing axis)
        p1 = transformer.init_cache_tree(cfg, 1, max_seq, dtype)
        p2 = transformer.init_cache_tree(cfg, 2, max_seq, dtype)
        self.batch_axes = jax.tree.map(
            lambda two, one: next(
                i for i, (a, b) in enumerate(zip(two.shape, one.shape))
                if a != b),
            p2, p1)
        self.free_slots: List[int] = list(range(max_slots))
        self.cache_len = jnp.zeros((max_slots,), jnp.int32)
        self._capacity_bytes = _tree_bytes(self.caches)

    # ------------------------------------------------------------------
    def alloc(self) -> Optional[int]:
        return self.free_slots.pop(0) if self.free_slots else None

    def free(self, slot: int):
        assert 0 <= slot < self.max_slots
        self.free_slots.append(slot)

    def insert(self, slot_caches: Any, slot: int, length: int):
        """Scatter a 1-sequence cache tree into `slot` (jit-friendly)."""
        def put(big, small, axis):
            idx = [0] * big.ndim
            idx[axis] = slot
            return jax.lax.dynamic_update_slice(big, small.astype(big.dtype),
                                                tuple(idx))
        self.caches = jax.tree.map(put, self.caches, slot_caches,
                                   self.batch_axes)
        self.cache_len = self.cache_len.at[slot].set(length)

    def utilization(self) -> float:
        return 1.0 - len(self.free_slots) / self.max_slots

    def commit_to(self, device) -> None:
        """Pin the cache arrays to ``device`` (one engine per chip)."""
        self.caches, self.cache_len = jax.device_put(
            (self.caches, self.cache_len), device)

    # ----------------------------------------------------- byte accounting
    def capacity_bytes(self) -> int:
        return self._capacity_bytes

    def bytes_in_use(self) -> int:
        """Dense cache commits whole ``max_seq`` rows per claimed slot."""
        used = self.max_slots - len(self.free_slots)
        return self._capacity_bytes * used // self.max_slots

    def dense_equivalent_bytes(self) -> int:
        return self._capacity_bytes


class PagedKVCache:
    """Page-pool KV manager for full-attention families.

    Host-side allocator state (free page list, per-slot page ownership)
    plus device-side pools / page table / lengths.  A request's prefill
    runs against a *standalone* table row (handed out by ``alloc``) and is
    only installed into the shared device table when the prefill
    completes — decode therefore never gathers half-written pages, and
    unowned rows stay all-zero (the trash page)."""

    def __init__(self, cfg: ModelConfig, max_slots: int, max_seq: int,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 dtype=jnp.bfloat16):
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.pages_per_slot = -(-max_seq // page_size)     # table width MP
        if num_pages is None:
            # full provisioning (+1 trash page): every slot can hold a
            # max_seq sequence; shrink num_pages to oversubscribe
            num_pages = max_slots * self.pages_per_slot + 1
        if num_pages < self.pages_per_slot + 1:
            raise ValueError(
                f"num_pages={num_pages} cannot hold one max_seq sequence "
                f"({self.pages_per_slot} pages) plus the trash page")
        self.num_pages = num_pages
        self.pools = transformer.init_paged_cache_tree(
            cfg, num_pages, page_size, dtype, slots=max_slots)
        self.page_table = jnp.zeros((max_slots, self.pages_per_slot),
                                    jnp.int32)
        self.cache_len = jnp.zeros((max_slots,), jnp.int32)
        self.free_slots: List[int] = list(range(max_slots))
        self.free_pages: List[int] = list(range(1, num_pages))  # 0 = trash
        self.slot_pages: Dict[int, List[int]] = {}
        # refcount per allocated page: private pages sit at 1; a shared
        # prefix page carries one ref per attached request plus one per
        # radix node.  Invariant: pages_in_use() == len(page_refs).
        self.page_refs: Dict[int, int] = {}
        # slot -> count of leading pages attached by reference (telemetry;
        # those pages may still be referenced by others after free)
        self.slot_shared: Dict[int, int] = {}
        self.cow_copies = 0
        self._copy_page_fn = None
        self._reset_state_fn = None
        self._capacity_bytes = _tree_bytes(self.pools)
        state_bytes = _tree_bytes(self.pools.get("mamba", []))
        self.state_bytes_per_slot = state_bytes // max_slots
        self._page_bytes = (self._capacity_bytes - state_bytes) // num_pages

    @property
    def has_state(self) -> bool:
        """Whether slots hold recurrent state beside their pages."""
        return self.state_bytes_per_slot > 0

    # ------------------------------------------------------------- queries
    def pages_needed(self, n_tokens: int) -> int:
        return -(-min(n_tokens, self.max_seq) // self.page_size)

    def can_admit(self, n_tokens: int) -> bool:
        return bool(self.free_slots) and \
            len(self.free_pages) >= self.pages_needed(n_tokens)

    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self.free_pages)

    def utilization(self) -> float:
        return 1.0 - len(self.free_slots) / self.max_slots

    def page_utilization(self) -> float:
        return self.pages_in_use() / max(self.num_pages - 1, 1)

    def commit_to(self, device) -> None:
        """Pin the pools, table and lengths to ``device`` (one engine per
        chip)."""
        self.pools, self.page_table, self.cache_len = jax.device_put(
            (self.pools, self.page_table, self.cache_len), device)

    # ----------------------------------------------------- byte accounting
    def capacity_bytes(self) -> int:
        return self._capacity_bytes

    def state_bytes(self) -> int:
        """Recurrent state held by the slots in use."""
        return (self.max_slots - len(self.free_slots)) \
            * self.state_bytes_per_slot

    def bytes_in_use(self) -> int:
        return self.pages_in_use() * self._page_bytes + self.state_bytes()

    def dense_equivalent_bytes(self) -> int:
        """What the dense ``max_slots × max_seq`` cache would allocate."""
        return self.max_slots * (self.pages_per_slot * self._page_bytes
                                 + self.state_bytes_per_slot)

    # --------------------------------------------------------- refcounting
    def _take_page(self) -> int:
        """Pop a free page and start its refcount at 1."""
        pid = self.free_pages.pop(0)
        assert pid not in self.page_refs
        self.page_refs[pid] = 1
        return pid

    def ref_page(self, pid: int) -> int:
        """Add a reference to an already-allocated page."""
        assert pid in self.page_refs, f"ref on unallocated page {pid}"
        self.page_refs[pid] += 1
        return self.page_refs[pid]

    def unref_page(self, pid: int) -> bool:
        """Drop one reference; returns True when the page actually went
        back to the free list (last holder let go)."""
        refs = self.page_refs.get(pid)
        assert refs is not None and refs > 0, f"unref of free page {pid}"
        if refs == 1:
            del self.page_refs[pid]
            self.free_pages.append(pid)
            return True
        self.page_refs[pid] = refs - 1
        return False

    # ---------------------------------------------------------- allocation
    def alloc(self, n_tokens: int, shared_pages=(), cow_src=None):
        """Reserve a slot + pages for ``n_tokens`` (prompt + planned new
        tokens).  ``shared_pages`` attach an already-resident prefix by
        reference (refcount bump — the leading logical pages alias those
        physical pages and are **never written** by this request); if
        ``cow_src`` is given the first private page is copy-seeded from it
        (mid-page divergence: copy the shared boundary page, then the
        prefill overwrites from the divergence point).  Returns ``(slot,
        table_row)`` — the row is a standalone [1, MP] device array the
        prefill chunks write through — or ``None`` when slots or private
        pages are exhausted (caller keeps the request queued; nothing is
        reserved on failure)."""
        need = self.pages_needed(n_tokens)
        shared = list(shared_pages)
        assert len(shared) < need or (len(shared) == need and need == 0), \
            "shared prefix must leave at least one private page"
        priv_need = need - len(shared)
        if not self.free_slots or len(self.free_pages) < priv_need:
            return None
        slot = self.free_slots.pop(0)
        for pid in shared:
            self.ref_page(pid)
        priv = [self._take_page() for _ in range(priv_need)]
        if cow_src is not None and priv:
            self.copy_page(cow_src, priv[0])
            self.cow_copies += 1
        pages = shared + priv
        self.slot_pages[slot] = pages
        self.slot_shared[slot] = len(shared)
        row = np.zeros((1, self.pages_per_slot), np.int32)
        row[0, :need] = pages
        return slot, jnp.asarray(row)

    def copy_page(self, src: int, dst: int):
        """Device-side copy of one physical page across every layer pool
        (page axis 1 of each ``[L, num_pages, page_size, H, D]`` leaf).
        Indices stay traced so one compilation covers all (src, dst)."""
        if self._copy_page_fn is None:
            def _copy(pools, s, d):
                return {k: v if k == "mamba" else jax.tree.map(
                    lambda a: a.at[:, d].set(a[:, s]), v)
                    for k, v in pools.items()}
            self._copy_page_fn = jax.jit(_copy, donate_argnums=(0,))
        self.pools = self._copy_page_fn(
            self.pools, jnp.int32(src), jnp.int32(dst))

    def reset_state(self, slot: int):
        """Zero ``slot``'s recurrent state (slot axis 1 of each ``[n,
        slots, ...]`` leaf) for the request just admitted to it.  The index
        stays traced so one compilation covers every slot."""
        if self._reset_state_fn is None:
            def _reset(pools, s):
                out = dict(pools)
                out["mamba"] = jax.tree.map(
                    lambda a: a.at[:, s].set(jnp.zeros((), a.dtype)),
                    pools["mamba"])
                return out
            self._reset_state_fn = jax.jit(_reset, donate_argnums=(0,))
        self.pools = self._reset_state_fn(self.pools, jnp.int32(slot))

    def append_page(self, slot: int) -> Optional[int]:
        """Grow an installed slot by one private page (on-demand decode
        growth).  Publishes the new physical page directly into the shared
        device table — safe mid-flight because the row's valid length
        still points below the new page.  Returns the page id, or ``None``
        when the pool is dry or the slot is at ``max_seq`` width."""
        pages = self.slot_pages.get(slot)
        assert pages is not None, f"append_page on unallocated slot {slot}"
        if len(pages) >= self.pages_per_slot or not self.free_pages:
            return None
        pid = self._take_page()
        idx = len(pages)
        pages.append(pid)
        self.page_table = self.page_table.at[slot, idx].set(pid)
        return pid

    def install(self, slot: int, table_row, length: int):
        """Publish a finished prefill: the slot's row becomes visible to
        the decode batch and its valid length is set."""
        self.page_table = self.page_table.at[slot].set(table_row[0])
        self.cache_len = self.cache_len.at[slot].set(length)

    def free(self, slot: int):
        """Drop the slot's references and zero its table row, so any stale
        masked decode write for this row lands on the trash page.  Pages
        still referenced elsewhere (radix nodes, sibling requests) stay
        allocated; exclusively-held pages rejoin the free list."""
        assert 0 <= slot < self.max_slots
        for pid in self.slot_pages.pop(slot, []):
            self.unref_page(pid)
        self.slot_shared.pop(slot, None)
        self.page_table = self.page_table.at[slot].set(0)
        self.cache_len = self.cache_len.at[slot].set(0)
        self.free_slots.append(slot)
