"""Continuous-batching serving engine: paged KV + chunked-prefill ticks.

Data plane
----------
Full-attention families serve from a **paged KV cache**
(``serving.kv_cache.PagedKVCache``): admission reserves
``ceil((prompt + max_new) / page_size)`` fixed-size pages instead of a
whole ``max_seq`` row, decode gathers pages through per-request page
tables (``kernels.paged_decode_attention``), and HBM accounting is
pages-in-use.  Stateful families (SSM state, SWA ring buffers, MLA latent
caches) keep the dense ``SlotKVCache``.

Every tick is a **mixed prefill/decode tick**: queued prompts are split
into fixed-size chunks (the pow2 prefill buckets double as chunk sizes)
and at most ``prefill_budget`` tokens' worth of chunks run per tick —
round-robin across prefilling requests in SLO-slack order — before the
full decode batch advances.  A long prompt therefore streams in over
several ticks while decode latency stays flat, instead of one prefill
monopolizing the tick (the head-of-line blocking the dense design had).
Chunk resume state per family: the paged path resumes via (pages already
written + start offset); SSM/hybrid resume via the carried conv/ssm state
of a batch-1 staging cache; MLA/SWA prefill monolithically (one
plen-sized "chunk" charged against the same budget).

Engine-loop lifecycle
---------------------
The engine can run in two modes:

* **caller-driven** (default): nothing steps the engine until someone calls
  ``step()`` / ``run_until_drained()`` or blocks on a ``RequestHandle`` —
  ``handle.result()`` drives ticks inline.  Multiple threads may drive
  concurrently; ticks are serialized under the engine lock, so requests
  submitted by different threads still share one decode batch.
* **background loop**: ``start()`` spawns a daemon thread that owns
  ``step()``.  Callers then only ``submit()`` (returns a ``RequestHandle``)
  and block on ``handle.result()`` — one request's prefill chunks overlap
  another request's decode because every tick mixes both phases.
  ``drain()`` waits for queue+active to empty; ``stop()`` (optionally
  draining first) shuts the thread down.  ``with engine:`` is
  start/stop(drain=True) sugar.

``warmup()`` pre-compiles the decode step and every prefill chunk bucket
state-neutrally (masked writes land on the paged pool's trash page), so
the first burst doesn't pay serial JIT walls mid-traffic.

Requests are validated at ``submit()`` time (empty or over-``max_seq``
prompts raise ``ValueError`` immediately); anything that fails *inside*
the loop marks the request failed and surfaces the error through its
future instead of crashing the loop thread.

SLO-aware admission: requests carry ``latency_slo_ms``; both the
admission pass and the per-tick chunk scheduler order by remaining SLO
slack (``slo_slack``), so tight-SLO requests jump ahead of slack FIFO
arrivals.  ``stats()`` reports the prefill-vs-decode tick-time split,
pages-in-use vs the dense-equivalent HBM, and feeds the SLO mode of
``EdgeSystem.autoscale`` via ``p95_queue_s``.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.executor import (BaseExecutor, DispatchRecord,
                                 ExecutorClass)
from repro.core.telemetry import DispatchSample, DispatchStats, percentile
from repro.core.workload import Workload, WorkloadKind
from repro.models.config import ModelConfig
from repro.models.model import build_model
from repro.serving.kv_cache import (PagedKVCache, SlotKVCache, _tree_bytes,
                                    autotune_page_size)
from repro.serving.prefix import PrefixRadixIndex

# page-growth preemption order: a dry pool preempts strictly-lower-rank
# requests only (BEST_EFFORT first), mirroring the AdmissionController's
# QoS ladder; preemption requeues — it never drops
_QOS_RANK = {"best-effort": 0, "burstable": 1, "guaranteed": 2}


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [T] int32
    max_new_tokens: int = 16
    eos_token: Optional[int] = None
    latency_slo_ms: float = 0.0
    qos: str = "burstable"             # best-effort | burstable | guaranteed
    submitted_at: float = 0.0
    # filled by the engine
    slot: Optional[int] = None
    phase: str = "queued"              # queued | prefill | decode
    pos: int = 0                       # prompt tokens prefilled so far
    chunks: int = 0                    # prefill chunks executed
    staging: Any = None                # batch-1 resume cache (stateful chunk)
    table_row: Any = None              # [1, MP] page-table row (paged)
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    future: Optional["Future[Request]"] = None
    # prefix sharing: pinned radix nodes backing this request's shared
    # pages, and how many prompt tokens prefill skipped via the match
    shared_nodes: List[Any] = dataclasses.field(default_factory=list)
    kv_shared_tokens: int = 0
    # speculative decoding: running acceptance-rate EMA driving this
    # request's preferred draft length k (0.5 = neutral prior)
    spec_ema: float = 0.5


def slo_slack(req: Request, now: float) -> float:
    """Seconds of SLO budget left before ``req`` busts its latency SLO
    (already counting time spent queued).  No SLO → infinite slack, so
    SLO-less requests sort behind every deadline-bearing one and keep
    their FIFO order among themselves (stable sort)."""
    if req.latency_slo_ms <= 0:
        return float("inf")
    return req.latency_slo_ms / 1e3 - (now - req.submitted_at)


class RequestHandle:
    """Caller-side view of a submitted request.

    ``result()`` blocks until the request completes.  When the background
    loop is running it simply waits on the request's future; otherwise it
    drives ``engine.step()`` inline (so single-threaded callers and tests
    need no thread).  A failed request re-raises its error here.
    """

    def __init__(self, engine: "ServingEngine", req: Request):
        self._engine = engine
        self._req = req

    @property
    def rid(self) -> int:
        return self._req.rid

    @property
    def future(self) -> Future:
        """The request's completion future (the fleet router chains its
        own completion off this without polling)."""
        return self._req.future

    def done(self) -> bool:
        return self._req.future.done()

    def result(self, timeout: Optional[float] = None) -> Request:
        if self._engine.loop_running:
            return self._req.future.result(timeout)
        return self._engine._drive(self._req, timeout)


def _mesh_device(mesh):
    """The one device an engine on ``mesh`` runs on (``None``: default)."""
    if mesh is None:
        return None
    devices = list(mesh.devices.flat)
    if len(devices) != 1:
        raise ValueError(f"a ServingEngine runs on one device; got a "
                         f"{len(devices)}-device mesh")
    return devices[0]


def _buckets(max_seq: int) -> List[int]:
    out, b = [], 16
    while b < max_seq:
        out.append(b)
        b *= 2
    out.append(max_seq)
    return out


class ServingEngine:
    def __init__(self, cfg: ModelConfig, max_slots: int = 4,
                 max_seq: int = 256, params: Optional[Any] = None,
                 seed: int = 0, mesh=None,
                 paged: Optional[bool] = None, page_size=16,
                 num_pages: Optional[int] = None,
                 prefill_chunk: int = 64,
                 prefill_budget=None,
                 prefix_sharing: bool = True,
                 replica_id: str = "",
                 kv_dtype: str = "auto",
                 draft_cfg: Optional[ModelConfig] = None,
                 draft_params: Optional[Any] = None,
                 spec_k_max: int = 4):
        self.cfg = cfg
        self.replica_id = replica_id     # fleet membership tag ("" = solo)
        self.model = build_model(cfg)
        # one engine, one device: a node's one-device mesh pins the engine
        # to that chip (fleet replicas one per chip); without a mesh it
        # runs on the default device.  Its state is committed there at the
        # end of __init__, so every jitted tick runs on that chip.
        self.mesh = mesh
        self.device = _mesh_device(mesh)
        with jax.default_device(self.device):
            self.params = params if params is not None else self.model.init(
                jax.random.key(seed))
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.buckets = _buckets(max_seq)

        # ---- data-plane selection: paged pools vs dense slots ----------
        paged_capable = (cfg.family in ("dense", "moe")
                         and cfg.attn_type == "full"
                         and cfg.sliding_window == 0
                         and not cfg.encoder_only)
        self.paged = paged_capable if paged is None \
            else bool(paged) and paged_capable
        if self.paged:
            # "auto" keeps the compute dtype; "int8" switches the pools to
            # per-token-quantized pages (~half the bytes per cached token,
            # dequantized inside the paged kernels' gather)
            kv_dt = cfg.cdtype if kv_dtype == "auto" else jnp.dtype(kv_dtype)
            self.kv_dtype = kv_dt
            if page_size == "auto":
                # config hook: size pages from the arch's measured KV
                # bytes-per-token instead of the hardcoded default
                page_size = autotune_page_size(cfg, dtype=kv_dt)
            # pools live in the serving KV dtype so the scatter never has
            # to re-materialize them and buffer donation stays in place
            self.kv: Any = PagedKVCache(cfg, max_slots, max_seq,
                                        page_size=page_size,
                                        num_pages=num_pages,
                                        dtype=kv_dt)
        else:
            if kv_dtype != "auto":
                raise ValueError(
                    "kv_dtype is a paged-data-plane knob; the dense slot "
                    "cache serves in the compute dtype")
            self.kv = SlotKVCache(cfg, max_slots, max_seq)

        # ---- prefix sharing (paged only): radix index + COW accounting --
        # guarded by the engine lock like every other allocator structure;
        # the router's lock-free estimate_marginal_pages probe is the one
        # sanctioned reader outside it (match(touch=False), racy-tolerant)
        self.prefix: Optional[PrefixRadixIndex] = (
            PrefixRadixIndex(self.kv.page_size)
            if self.paged and prefix_sharing else None)
        self.kv_prefix_hits = 0       # admissions that attached shared pages
        self.kv_prefix_misses = 0
        self.preemptions = 0          # page-pressure requeues
        self.decode_stalls = 0        # decode rows skipped for want of a page

        # ---- chunked-prefill plan --------------------------------------
        # chunk sizes reuse the pow2 prefill buckets → a bounded compile
        # set; stateful chunking needs exact lengths, so only the pure-SSM
        # and windowless hybrid families chunk on the dense path
        self.chunk_tokens = max(
            [b for b in self.buckets if b <= prefill_chunk] or
            [self.buckets[0]])
        self.chunk_buckets = [b for b in self.buckets
                              if b <= self.chunk_tokens]
        self._chunkable_stateful = (
            cfg.family == "ssm"
            or (cfg.family == "hybrid" and cfg.sliding_window == 0
                and cfg.attn_type == "full"))
        self._chunkable = self.paged or self._chunkable_stateful
        # "auto" starts from the same 2-chunk provisional and is refined
        # from measured chunk/decode walls during warmup()
        self._budget_auto = prefill_budget == "auto"
        self.prefill_budget = prefill_budget \
            if prefill_budget is not None and not self._budget_auto \
            else 2 * self.chunk_tokens

        self.queue: List[Request] = []
        self.active: Dict[int, Request] = {}
        self.completed: Dict[int, Request] = {}      # rid → finished request
        self.failed: Dict[int, Request] = {}         # rid → failed request
        self.last_tokens = jnp.zeros((max_slots,), jnp.int32)
        self._rid = itertools.count()
        self.ticks = 0
        self.dispatch_stats = DispatchStats()
        # fleet routing surfaces: recent queue waits (admission-time) for
        # fleet-aggregate p95 autoscale, prefix-affinity hit counters
        self.recent_queue_s: collections.deque = collections.deque(
            maxlen=256)
        self.prefix_hits = 0
        self.prefix_misses = 0
        # per-tick (prefill_s, decode_s, prefill_tokens, decode_rows,
        # decode_tokens) — tokens > rows on speculative ticks
        self._tick_log: collections.deque = collections.deque(maxlen=512)
        self._warm = False
        self.warmup_s = 0.0

        # loop lifecycle: the RLock serializes ticks and bookkeeping; the
        # conditions wake the loop on new work and drainers on each tick
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        self._tick = threading.Condition(self._lock)
        self._thread: Optional[threading.Thread] = None
        self._running = False

        # ---- speculative decoding (paged only) -------------------------
        # a small draft model proposes k tokens per tick; the target
        # verifies all k+1 in ONE paged pass and commits the accepted
        # prefix + its own correction token.  Greedy output is token-exact
        # regardless of the draft, so this is pure throughput.
        self.spec_k_max = int(spec_k_max)
        self._draft = None
        self._spec_disabled_reason: Optional[str] = None
        self.spec_proposed = 0        # draft tokens offered to the target
        self.spec_accepted = 0        # draft tokens the target kept
        self.spec_rounds = 0          # verify launches
        self.draft_ticks = 0          # draft propose launches
        if draft_cfg is not None:
            if not self.paged:
                raise ValueError(
                    "speculative decoding needs the paged data plane "
                    f"(family={cfg.family!r}, attn={cfg.attn_type!r})")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}: the models must share a tokenizer")
            if self.spec_k_max < 1:
                raise ValueError(f"spec_k_max must be >= 1, "
                                 f"got {spec_k_max}")
            from repro.serving.spec_decode import DraftSpeculator
            self._draft = DraftSpeculator(draft_cfg, max_slots, max_seq,
                                          params=draft_params,
                                          seed=seed + 1)
            self._verify = jax.jit(self._verify_paged_fn,
                                   donate_argnums=(1,))

        # `_decode` is ALWAYS the live decode callable (paged or dense) —
        # tests and tooling monkeypatch it by name
        if self.paged:
            self._decode = jax.jit(self._decode_paged_fn,
                                   donate_argnums=(1,))
            self._chunk = jax.jit(self._chunk_paged_fn, donate_argnums=(1,))
        else:
            self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
            self._prefill = jax.jit(self._prefill_fn,
                                    static_argnames=("bucket",))
            if self._chunkable_stateful:
                self._chunk = jax.jit(self._chunk_stateful_fn,
                                      donate_argnums=(1,))

        if self.device is not None:
            self.params = jax.device_put(self.params, self.device)
            self.kv.commit_to(self.device)
            self.last_tokens = jax.device_put(self.last_tokens, self.device)
            if self._draft is not None:
                self._draft.commit_to(self.device)

    # ------------------------------------------------------------------
    @property
    def _stateful(self) -> bool:
        """Families whose prefill must not see pad tokens (SSM state / SWA
        ring cache) → exact-length prefill instead of pow2 buckets."""
        return self.cfg.family in ("ssm", "hybrid") or \
            self.cfg.sliding_window > 0

    def _prefill_fn(self, params, tokens, last_index, *, bucket: int):
        """Monolithic whole-prompt prefill (non-chunkable dense path)."""
        caches = self.model.init_caches(1, self.max_seq)
        batch = {"tokens": tokens}
        logits, caches, clen = self.model.prefill(
            params, batch, caches, last_index=last_index)
        return logits, caches, clen

    def _chunk_paged_fn(self, params, pools, tokens, table_row, start,
                        new_len):
        """One prefill chunk straight into the request's pages."""
        return self.model.prefill_chunk(params, {"tokens": tokens}, pools,
                                        start, new_len,
                                        page_table=table_row)

    def _chunk_stateful_fn(self, params, staging, tokens, start, new_len):
        """One exact-length chunk resuming a batch-1 staging cache."""
        return self.model.prefill_chunk(params, {"tokens": tokens}, staging,
                                        start, new_len)

    def _decode_fn(self, params, caches, tokens, cache_len, active):
        logits, caches = self.model.decode(params, tokens, caches, cache_len)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        next_tokens = jnp.where(active, next_tokens, tokens)
        new_len = jnp.where(active, cache_len + 1, cache_len)
        return next_tokens, caches, new_len

    def _decode_paged_fn(self, params, pools, page_table, tokens, cache_len,
                         active):
        logits, pools = self.model.decode_paged(params, tokens, pools,
                                                page_table, cache_len)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        next_tokens = jnp.where(active, next_tokens, tokens)
        new_len = jnp.where(active, cache_len + 1, cache_len)
        return next_tokens, pools, new_len

    def _verify_paged_fn(self, params, pools, page_table, tokens_blk,
                         cache_len, last_tokens, active):
        """Target-verify one speculative block.

        ``tokens_blk`` [B, K1=k+1] is ``[last, d1..dk]`` per row.  The
        target scores all K1 positions in one paged pass (their KV lands
        at ``cache_len..cache_len+k``); ``acc`` counts the leading drafts
        that match the target's greedy choice, and the committed batch is
        the accepted prefix plus the target's own token at the first
        disagreement (= plain greedy continuation when a == k).  The new
        length winds back past the rejected suffix — the stale KV beyond
        it is masked garbage the next tick overwrites.
        """
        logits, pools = self.model.verify_paged(params, tokens_blk, pools,
                                                page_table, cache_len)
        tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # [B, K1]
        match = (tgt[:, :-1] == tokens_blk[:, 1:]).astype(jnp.int32)
        acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)         # [B]
        acc = jnp.where(active, acc, 0)
        new_len = jnp.where(active, cache_len + 1 + acc, cache_len)
        nxt = jnp.take_along_axis(tgt, acc[:, None], axis=1)[:, 0]
        nxt = jnp.where(active, nxt, last_tokens)
        return tgt, acc, nxt, pools, new_len

    # ------------------------------------------------------------- warmup
    def warmup(self) -> "ServingEngine":
        """Pre-compile the decode step and every prefill chunk bucket so
        the first burst doesn't pay serial JIT walls mid-traffic.

        State-neutral by construction: chunk warmup runs against an
        all-zero page table row with ``new_len = 0`` (every token is
        masked padding → writes land on the trash page / are discarded),
        and decode warmup runs with an all-inactive mask (unowned rows
        write to the trash page on the paged path; dense rows are
        overwritten wholesale by the next ``insert``).  Idempotent.
        """
        with self._lock:
            if self._warm:
                return self
            t0 = time.monotonic()
            zero1 = jnp.zeros((1,), jnp.int32)
            if self.paged:
                row = jnp.zeros((1, self.kv.pages_per_slot), jnp.int32)
                logits = None
                for b in self.chunk_buckets:
                    # every (chunk bucket, pow2 KV span) pair a long prompt
                    # can hit — one compile each, all before traffic
                    for span in self.buckets:
                        if span < b:
                            continue
                        kv_pages = self._kv_span_pages(span)
                        logits, pools = self._chunk(
                            self.params, self.kv.pools,
                            jnp.zeros((1, b), jnp.int32),
                            row[:, :kv_pages], zero1, zero1)
                        self.kv.pools = pools
                # absorb the first-token host programs (argmax, table/len
                # scatters) — all no-ops on an idle engine's zero state
                if logits is not None and not self.active and not self.queue:
                    int(np.asarray(jnp.argmax(logits, -1))[0])
                    self.last_tokens = self.last_tokens.at[0].set(
                        jnp.asarray(0, jnp.int32))
                    self.kv.install(0, row, 0)
                toks, pools, clen = self._decode(
                    self.params, self.kv.pools, self.kv.page_table,
                    self.last_tokens, self.kv.cache_len,
                    jnp.zeros((self.max_slots,), bool))
                self.kv.pools = pools
                self.kv.cache_len = clen
                self.last_tokens = toks
                if self._draft is not None:
                    # every speculative depth k the adaptive policy can
                    # pick compiles its own (propose, verify) pair — all
                    # state-neutral under the all-inactive mask, like the
                    # decode warmup above
                    inactive = jnp.zeros((self.max_slots,), bool)
                    for kk in range(1, self.spec_k_max + 1):
                        drafts = self._draft.propose(self.last_tokens,
                                                     inactive, kk)
                        blk = jnp.concatenate(
                            [self.last_tokens[:, None], drafts], axis=1)
                        _, _, nxt, pools, clen = self._verify(
                            self.params, self.kv.pools, self.kv.page_table,
                            blk, self.kv.cache_len, self.last_tokens,
                            inactive)
                        self.kv.pools = pools
                        self.kv.cache_len = clen
                        self.last_tokens = nxt
                    # draft prompt-prefill buckets; slot 0's draft state is
                    # scratch until a real insert overwrites it — zero the
                    # length back so nothing looks resident
                    for b in self.buckets:
                        self._draft.prefill(
                            np.zeros((min(b, self.max_seq),), np.int32), 0)
                    self._draft.kv.cache_len = jnp.zeros_like(
                        self._draft.kv.cache_len)
            else:
                if self._chunkable_stateful:
                    staging = self.model.init_caches(1, self.max_seq)
                    self._chunk(self.params, staging,
                                jnp.zeros((1, self.chunk_tokens), jnp.int32),
                                zero1, zero1)
                elif not self._stateful:
                    for b in self.buckets:
                        self._prefill(self.params,
                                      jnp.zeros((1, b), jnp.int32),
                                      zero1, bucket=b)
                # stateful monolithic (e.g. SWA) compiles per exact prompt
                # length — nothing to pre-compile without knowing lengths
                toks, caches, clen = self._decode(
                    self.params, self.kv.caches, self.last_tokens,
                    self.kv.cache_len, jnp.zeros((self.max_slots,), bool))
                self.kv.caches = caches
                self.kv.cache_len = clen
                self.last_tokens = toks
            jax.block_until_ready(self.last_tokens)
            if self._budget_auto and self.paged:
                self._autotune_budget()
            self.warmup_s = time.monotonic() - t0
            self._warm = True
        return self

    def _autotune_budget(self):
        """Refine ``prefill_budget`` from measured walls (both callables
        are compiled by now, so these are pure execute timings): allow as
        many chunk-tokens per tick as keep the prefill phase within ~4
        decode steps' worth of wall, clamped to [1, 8] chunks — decode
        latency stays flat without starving prompt streaming."""
        b = self.chunk_tokens
        kvp = self._kv_span_pages(next(s for s in self.buckets if s >= b))
        row = jnp.zeros((1, self.kv.pages_per_slot), jnp.int32)
        zero1 = jnp.zeros((1,), jnp.int32)
        chunk_wall = decode_wall = float("inf")
        for _ in range(2):                       # min-of-2: absorb jitter
            t = time.monotonic()
            logits, pools = self._chunk(
                self.params, self.kv.pools,
                jnp.zeros((1, b), jnp.int32), row[:, :kvp], zero1, zero1)
            self.kv.pools = pools
            jax.block_until_ready(logits)
            chunk_wall = min(chunk_wall, time.monotonic() - t)
            t = time.monotonic()
            toks, pools, clen = self._decode(
                self.params, self.kv.pools, self.kv.page_table,
                self.last_tokens, self.kv.cache_len,
                jnp.zeros((self.max_slots,), bool))
            self.kv.pools = pools
            self.kv.cache_len = clen
            self.last_tokens = toks
            jax.block_until_ready(toks)
            decode_wall = min(decode_wall, time.monotonic() - t)
        chunks = max(1, min(8, round(4 * decode_wall / max(chunk_wall,
                                                           1e-9))))
        self.prefill_budget = chunks * self.chunk_tokens

    # ------------------------------------------------------- loop lifecycle
    @property
    def loop_running(self) -> bool:  # analysis: unguarded-ok (racy fast path: single atomic bool/ref reads; lifecycle methods re-check under the lock)
        return self._running and self._thread is not None \
            and self._thread.is_alive()

    def start(self) -> "ServingEngine":
        """Start the background engine loop (idempotent)."""
        with self._lock:
            if self.loop_running:
                return self
            self._running = True
            self._thread = threading.Thread(
                target=self._loop, name=f"engine-loop-{id(self):x}",
                daemon=True)
            self._thread.start()
        return self

    def stop(self, drain: bool = True, timeout: Optional[float] = 30.0):
        """Stop the loop thread; by default finish in-flight work first."""
        if drain and self.loop_running:
            self.drain(timeout=timeout)
        with self._lock:
            self._running = False
            self._work.notify_all()
            # claim the thread ref under the lock: concurrent stop()
            # callers race the read-join-clear sequence otherwise
            t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout)

    def __enter__(self) -> "ServingEngine":
        return self.start()

    def __exit__(self, *exc):
        self.stop(drain=exc[0] is None)

    def _loop(self):
        while True:
            with self._lock:
                while self._running and not self.queue and not self.active:
                    self._work.wait(timeout=0.5)
                if not self._running:
                    return
            try:
                self.step()
            except Exception:  # noqa: BLE001 — step fails the offending
                # requests itself; this is a last-resort guard, so back
                # off rather than hot-spin if something still escapes
                time.sleep(0.05)
            # yield the interpreter between ticks: the lock is not fair,
            # and without this the loop re-takes it before a woken
            # submit()/start() caller runs, starving it for a whole
            # request's worth of ticks
            time.sleep(0)

    def drain(self, timeout: Optional[float] = None) -> List[Request]:
        """Block until the queue and active set are empty."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            while self.queue or self.active:
                if not self.loop_running:
                    self.step()             # no loop → drive inline
                    continue
                wait = 0.1 if deadline is None else \
                    min(0.1, deadline - time.monotonic())
                if wait <= 0 or not self._tick.wait(timeout=wait):
                    if deadline is not None and \
                            time.monotonic() >= deadline:
                        raise TimeoutError(
                            f"engine drain timed out: "
                            f"{len(self.queue)} queued, "
                            f"{len(self.active)} active")
            return list(self.completed.values())

    def _drive(self, req: Request, timeout: Optional[float] = None
               ) -> Request:
        """Caller-driven mode: step until ``req`` completes (or fails)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while not req.future.done():
            with self._lock:
                if self.loop_running:       # a loop started mid-wait
                    break
                self.step()
                if not req.future.done() and not self.queue \
                        and not self.active:
                    raise RuntimeError(
                        f"request {req.rid} cannot complete: engine idle")
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(f"request {req.rid} timed out")
        return req.future.result(timeout)

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 16,
               eos_token: Optional[int] = None,
               latency_slo_ms: float = 0.0,
               qos: str = "burstable") -> RequestHandle:
        """Enqueue a request; returns a handle whose ``result()`` blocks.

        Invalid prompts are rejected HERE with ``ValueError`` — never
        inside the loop thread, where they'd kill the shared loop.
        """
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1:
            raise ValueError(
                f"prompt must be 1-D, got shape {prompt.shape}")
        if prompt.size == 0:
            raise ValueError("empty prompt: prefill needs >= 1 token")
        if prompt.size > self.max_seq:
            raise ValueError(
                f"prompt length {prompt.size} exceeds max_seq "
                f"{self.max_seq}")
        if qos not in _QOS_RANK:
            raise ValueError(f"unknown qos {qos!r}; "
                             f"expected one of {sorted(_QOS_RANK)}")
        req = Request(next(self._rid), prompt,
                      max_new_tokens, eos_token, latency_slo_ms, qos,
                      submitted_at=time.monotonic(), future=Future())
        with self._lock:
            self.queue.append(req)
            self._work.notify_all()
        return RequestHandle(self, req)

    # -------------------------------------------------- fleet probe surface
    # The fleet router scores and probes replicas while it may itself be
    # holding the router lock, and a chaos-stalled engine holds THIS lock
    # for seconds — so every probe below is either lock-free (racy O(1)
    # snapshots are fine for load scoring) or takes the lock with a
    # bounded timeout.  Blocking here would let one stalled replica
    # head-of-line-block routing for the whole fleet.

    def queue_depth(self) -> int:
        """Racy queued-request count (router steal trigger)."""
        return len(self.queue)  # analysis: unguarded-ok — racy len() snapshot for routing

    def load(self) -> Tuple[int, int, int]:
        """Racy ``(queued, active, kv_bytes_in_use)`` snapshot — the
        router's least-pages / least-inflight scoring tuple."""
        return (len(self.queue), len(self.active), self.kv.bytes_in_use())  # analysis: unguarded-ok — racy load snapshot for routing

    def responsive(self, timeout: float = 0.05) -> bool:
        """Can the engine lock be taken within ``timeout``?  False means
        the loop is wedged or chaos-stalled; the router routes around."""
        if not self._lock.acquire(timeout=timeout):
            return False
        self._lock.release()
        return True

    def cancel_queued(self, rid: int,
                      timeout: float = 0.1) -> Optional[Request]:
        """Remove a still-queued request (work stealing / orphan cleanup).

        Returns the request if it was cancelled, ``None`` if it already
        started (active decodes own KV pages and stay put) or the lock
        could not be taken in time.  The request's future is left
        unresolved — the caller re-binds it elsewhere.
        """
        if not self._lock.acquire(timeout=timeout):
            return None
        try:
            for i, req in enumerate(self.queue):  # analysis: unguarded-ok — held via timed acquire above
                if req.rid == rid:
                    self.queue.pop(i)  # analysis: unguarded-ok — held via timed acquire above
                    req.phase = "cancelled"
                    return req
            return None
        finally:
            self._lock.release()

    def estimate_marginal_pages(self, prompt) -> int:  # analysis: unguarded-ok — racy routing estimate by contract
        """Racy post-sharing page estimate for a prospective prompt — the
        router's least-pages score charges only the pages this replica
        would actually allocate (a warm radix makes the replica cheap).
        Lock-free by contract: ``match(touch=False)`` mutates nothing and
        any torn read just degrades one routing decision."""
        try:
            p = np.asarray(prompt, np.int32).reshape(-1)
            need = self.kv.pages_needed(min(p.size + 1, self.max_seq))
            if self.prefix is None or p.size == 0:
                return need
            m = self.prefix.match(p, touch=False)
            w = min(m.matched_tokens, p.size - 1)
            return max(need - w // self.kv.page_size, 1)
        except Exception:  # noqa: BLE001 — a torn racy walk is a miss
            return self.kv.pages_needed(
                min(np.asarray(prompt).size + 1, self.max_seq))

    def release_prefix_cache(self) -> int:
        """Drop every unpinned radix node, returning its pages to the
        pool (idle-time cache release / tests).  Pages still referenced
        by in-flight requests stay allocated until those finish."""
        with self._lock:
            if self.prefix is None:
                return 0
            return self.prefix.clear(self.kv)

    def note_prefix(self, hit: bool) -> None:
        """Router-reported prefix-affinity outcome for this replica."""
        if hit:
            self.prefix_hits += 1  # analysis: unguarded-ok — monotonic counter, router thread only
        else:
            self.prefix_misses += 1  # analysis: unguarded-ok — monotonic counter, router thread only

    def queue_samples(self) -> List[float]:
        """Recent admission queue waits (seconds) — pooled across
        replicas for fleet-aggregate p95 autoscale."""
        with self._lock:
            return list(self.recent_queue_s)

    def recent_queue_p95(self) -> float:
        """Racy p95 of recent queue waits (router steal trigger)."""
        xs = list(self.recent_queue_s)  # analysis: unguarded-ok — deque snapshot for routing
        return percentile(xs, 95) if xs else 0.0

    def _fail(self, req: Request, err: Exception):
        req.done = True
        req.error = str(err)
        req.staging = None
        req.finished_at = time.monotonic()
        self.failed[req.rid] = req
        if req.future is not None and not req.future.done():
            req.future.set_exception(err)
        self._tick.notify_all()

    def _release(self, req: Request):
        """Return the request's slot (and pages) to the cache manager.
        Shared pages only drop this request's reference; the radix nodes
        backing them are unpinned (eviction may now consider them)."""
        if req.slot is not None:
            self.kv.free(req.slot)
            req.slot = None
        if req.shared_nodes:
            if self.prefix is not None:
                self.prefix.unpin(req.shared_nodes)
            req.shared_nodes = []
        req.staging = None
        req.table_row = None

    # ---------------------------------------------------- prefix matching
    def _match_prefix(self, prompt: np.ndarray):
        """Longest shared prefix for an incoming prompt.

        Returns ``(pins, shared_pages, cow_src, w)``: ``w`` prompt tokens
        are already resident (capped at ``plen - 1`` so prefill always
        runs ≥ 1 real token and produces the first-token logits), the
        ``w // page_size`` whole pages attach by reference, and a mid-page
        boundary (``w`` not page-aligned) names the page to copy-seed the
        first private page from (divergence → copy-then-append).  ``pins``
        are the radix nodes the request depends on — pinned before any
        eviction can run."""
        plen = len(prompt)
        m = self.prefix.match(prompt)
        w = min(m.matched_tokens, plen - 1)
        ps = self.kv.page_size
        boundary = w // ps
        chain = m.nodes[:boundary]
        shared = [n.page for n in chain]
        pins = list(chain)
        cow_src = None
        if w > boundary * ps:                    # divergence mid-page
            if boundary < len(m.nodes):
                cow_node = m.nodes[boundary]
            else:
                cow_node = m.tail               # tail covered tokens ⇒ set
            cow_src = cow_node.page
            pins.append(cow_node)
        return pins, shared, cow_src, w

    # ---------------------------------------------------------- admission
    def _admit(self):
        """Move queued requests into the prefilling set while capacity
        (slots, and pages on the paged path) lasts.  No prefill compute
        happens here — chunks run in the tick's budgeted prefill phase.
        Head-of-line order is SLO slack, and admission stops at the first
        request that doesn't fit (no small-request bypass, so large
        prompts cannot starve)."""
        if len(self.queue) > 1:
            # SLO-slack admission ordering: least remaining budget first
            now = time.monotonic()
            self.queue.sort(key=lambda r: slo_slack(r, now))
        while self.queue:
            req = self.queue[0]
            plen = len(req.prompt)
            # requests normally can't get here invalid (submit validates),
            # but a bad item must fail its future, not crash the loop
            if plen == 0 or plen > self.max_seq:
                self.queue.pop(0)
                self._fail(req, ValueError(
                    f"prompt length {plen} outside (0, {self.max_seq}]"))
                continue
            if self.paged:
                # marginal admission: reserve the prompt + ONE decode
                # token (further decode pages grow on demand), attach any
                # radix-matched prefix by reference, and copy-seed the
                # divergence page — pages-in-use stays the engine's true
                # (post-sharing) HBM commitment
                if not self.kv.free_slots:
                    break
                pins, shared, cow_src, w = [], [], None, 0
                if self.prefix is not None:
                    pins, shared, cow_src, w = self._match_prefix(
                        req.prompt)
                    self.prefix.pin(pins)
                n_alloc = min(plen + 1, self.max_seq)
                got = self.kv.alloc(n_alloc, shared_pages=shared,
                                    cow_src=cow_src)
                if got is None and self.prefix is not None:
                    # pool dry: evict LRU unpinned radix leaves (the
                    # request's own nodes are pinned above) and retry once
                    deficit = (self.kv.pages_needed(n_alloc) - len(shared)
                               - len(self.kv.free_pages))
                    if deficit > 0 and \
                            self.prefix.evict(self.kv, deficit) >= deficit:
                        got = self.kv.alloc(n_alloc, shared_pages=shared,
                                            cow_src=cow_src)
                if got is None:
                    if self.prefix is not None:
                        self.prefix.unpin(pins)
                    break
                req.slot, req.table_row = got
                req.shared_nodes = pins
                req.kv_shared_tokens = w
                if self.prefix is not None:
                    if w:
                        self.kv_prefix_hits += 1
                    else:
                        self.kv_prefix_misses += 1
            else:
                if not self.kv.free_slots:
                    break
                req.slot = self.kv.alloc()
                if self._chunkable_stateful:
                    req.staging = self.model.init_caches(1, self.max_seq)
            self.queue.pop(0)
            req.phase = "prefill"
            # prefill resumes AFTER the shared prefix: matched tokens are
            # already resident, only the suffix streams through chunks
            req.pos = req.kv_shared_tokens
            req.admitted_at = time.monotonic()
            self.recent_queue_s.append(req.admitted_at - req.submitted_at)
            self.active[req.rid] = req

    # ------------------------------------------------------ prefill phase
    def _chunk_plan(self, req: Request):
        """(bucket, real) for the request's next chunk: full chunks at
        ``chunk_tokens``, then the smallest bucket covering the tail
        (padded on the paged path; stateful chunks are always exact)."""
        remaining = len(req.prompt) - req.pos
        if not self._chunkable:
            return len(req.prompt), len(req.prompt)      # monolithic
        if self._chunkable_stateful and not self.paged:
            return None, min(self.chunk_tokens, remaining)  # exact length
        if remaining >= self.chunk_tokens:
            return self.chunk_tokens, self.chunk_tokens
        return next(b for b in self.buckets if b >= remaining), remaining

    def _prefill_cost(self, req: Request) -> int:
        return self._chunk_plan(req)[1]

    def _kv_span_pages(self, valid_len: int) -> int:
        """Pages covering the smallest pow2 bucket ≥ ``valid_len`` — the
        static KV span a prefill chunk gathers/attends over."""
        span = next(b for b in self.buckets if b >= valid_len)
        return -(-span // self.kv.page_size)

    def _run_chunk(self, req: Request) -> int:
        """Execute one prefill chunk (or the whole prompt when the family
        can't chunk); returns real prompt tokens processed.  On error the
        request fails and its capacity is returned."""
        plen = len(req.prompt)
        bucket, real = self._chunk_plan(req)
        start = req.pos
        try:
            if self.paged:
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :real] = req.prompt[start:start + real]
                # gather only a pow2-bucketed prefix of the page table:
                # early chunks attend tens of tokens, not max_seq — the
                # sliced row's width keys the (chunk, span) compile
                kv_pages = self._kv_span_pages(start + real)
                logits, pools = self._chunk(
                    self.params, self.kv.pools, jnp.asarray(padded),
                    req.table_row[:, :kv_pages],
                    jnp.asarray([start], jnp.int32),
                    jnp.asarray([start + real], jnp.int32))
                self.kv.pools = pools
            elif self._chunkable_stateful:
                toks = jnp.asarray(req.prompt[None, start:start + real],
                                   jnp.int32)
                logits, req.staging = self._chunk(
                    self.params, req.staging, toks,
                    jnp.asarray([start], jnp.int32),
                    jnp.asarray([start + real], jnp.int32))
            else:
                # monolithic: exact length for stateful archs, pow2 bucket
                # (with last_index masking) for full attention
                bucket = plen if self._stateful else next(
                    b for b in self.buckets if b >= plen)
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :plen] = req.prompt
                logits, pcache, _ = self._prefill(
                    self.params, jnp.asarray(padded),
                    jnp.asarray([plen - 1], jnp.int32), bucket=bucket)
                real = plen
        except Exception as e:  # noqa: BLE001
            if self.paged:
                # the chunk donates the SHARED pools: a runtime failure
                # leaves every admitted request's cache state suspect, so
                # fail the batch (mirrors the decode error path) instead
                # of ticking on with poisoned pools
                for other in list(self.active.values()):
                    self._release(other)
                    del self.active[other.rid]
                    self._fail(other, e)
            else:
                # stateful chunks donate only the request's own staging
                self._release(req)
                del self.active[req.rid]
                self._fail(req, e)
            return 0
        req.pos += real
        req.chunks += 1
        if req.pos < plen:
            return real
        # ---- prompt complete: publish the cache and enter decode -------
        first = int(np.asarray(jnp.argmax(logits, -1))[0])
        if self.paged:
            self.kv.install(req.slot, req.table_row, plen)
        elif self._chunkable_stateful:
            self.kv.insert(req.staging, req.slot, plen)
            req.staging = None
        else:
            self.kv.insert(pcache, req.slot, plen)
        self.last_tokens = self.last_tokens.at[req.slot].set(first)
        if self._draft is not None and req.max_new_tokens > 1:
            # mirror the prompt into the draft's slot cache so the first
            # speculative tick starts in sync (draft clen == target clen,
            # same pending token).  A draft-side failure never fails the
            # request — speculation just turns itself off.
            try:
                self._draft.prefill(req.prompt, req.slot)
            except Exception as e:  # noqa: BLE001 — draft state is its own
                # tree; the target's pools are untouched
                self._draft = None
                self._spec_disabled_reason = f"draft prefill: {e}"
        req.generated.append(first)
        now = time.monotonic()
        req.first_token_at = now
        req.phase = "decode"
        if (req.eos_token is not None and first == req.eos_token) or \
                req.max_new_tokens <= 1:
            self._finish(req, now)
        return real

    def _prefill_tick(self) -> int:
        """Run up to ``prefill_budget`` prompt tokens of chunks, round-robin
        over prefilling requests in SLO-slack order.  A monolithic prefill
        larger than the whole budget only runs as the tick's first prefill
        work — it can stretch one tick, never ride along with others."""
        pref = [r for r in self.active.values() if r.phase == "prefill"]
        if not pref:
            return 0
        now = time.monotonic()
        pref.sort(key=lambda r: slo_slack(r, now))
        budget = self.prefill_budget
        total = 0
        progressed = True
        while budget > 0 and pref and progressed:
            progressed = False
            for req in list(pref):
                if budget <= 0:
                    break
                if req.rid not in self.active:   # failed by a batch error
                    pref.remove(req)
                    continue
                cost = self._prefill_cost(req)
                if cost > budget and total > 0:
                    continue                    # wait for a fresh budget
                done = self._run_chunk(req)
                total += done
                budget -= max(done, 1)          # failed chunk: no hot loop
                progressed = True
                if req.phase != "prefill":
                    pref.remove(req)
        return total

    # ----------------------------------------------- on-demand page growth
    def _requeue(self, victim: Request):
        """Preempt via the existing requeue path: release the victim's
        capacity and re-run it from scratch at the queue head.  Its future
        stays pending (never a drop) and greedy decode is deterministic,
        so the re-run reproduces the same tokens."""
        self.preemptions += 1
        self._release(victim)
        self.active.pop(victim.rid, None)
        victim.phase = "queued"
        victim.pos = 0
        victim.chunks = 0
        victim.generated = []
        victim.first_token_at = None
        victim.admitted_at = None
        victim.kv_shared_tokens = 0
        self.queue.insert(0, victim)

    def _preempt_for(self, req: Request) -> Optional[Request]:
        """Requeue one strictly-lower-QoS active request to reclaim its
        pages (BEST_EFFORT goes first, youngest-admitted within a rank).
        Returns the victim, or ``None`` when nothing outranks."""
        rank = _QOS_RANK.get(req.qos, 1)
        victims = [r for r in self.active.values()
                   if r.rid != req.rid and _QOS_RANK.get(r.qos, 1) < rank]
        if not victims:
            return None
        victim = min(victims, key=lambda r: (_QOS_RANK.get(r.qos, 1),
                                             -(r.admitted_at or 0.0)))
        self._requeue(victim)
        return victim

    def _grow_decode_pages(self, dec: List[Request], span: int = 1) -> set:
        """Grow each decoding row that is about to write past its last
        page (one page at a time — marginal footprint).  A dry pool
        reclaims in order: LRU radix eviction, then BEST_EFFORT-style
        preemption of a strictly-lower-QoS request; a row that still can't
        get a page is *stalled* for this tick (masked inactive — its
        unallocated logical page maps to table entry 0, the trash page, so
        even a stray write is harmless).  Returns the stalled rids.

        ``span`` is how many consecutive KV positions the tick writes: 1
        for plain decode, k+1 for a speculative tick (pending token + k
        draft proposals), which can cross more than one page boundary.
        """
        stalled = set()
        order = sorted(dec, key=lambda r: (-_QOS_RANK.get(r.qos, 1),
                                           r.admitted_at or 0.0))
        for req in order:                        # guaranteed rows first
            if req.rid not in self.active:       # preempted below us
                continue
            # decode writes KV at cache_len = plen + generated - 1 (the
            # final sampled token's KV is never written) — host-derivable,
            # no device sync
            pos = len(req.prompt) + len(req.generated) - 1
            if pos >= self.max_seq:
                continue
            last = min(pos + span - 1, self.max_seq - 1)
            need = last // self.kv.page_size + 1
            ok = True
            while len(self.kv.slot_pages[req.slot]) < need:
                if self.kv.append_page(req.slot) is not None:
                    continue
                if self.prefix is not None and \
                        self.prefix.evict(self.kv, 1) and \
                        self.kv.append_page(req.slot) is not None:
                    continue
                if self._preempt_for(req) is not None and \
                        self.kv.append_page(req.slot) is not None:
                    continue
                ok = False
                break
            if ok:
                continue
            stalled.add(req.rid)
            self.decode_stalls += 1
        # deadlock valve: every decode row stalled and no prefill under
        # way means nothing will free a page on its own — requeue the
        # lowest-QoS youngest stalled row so the rest make progress
        still = [r for r in dec if r.rid in self.active
                 and r.phase == "decode"]
        if stalled and len(stalled) == len(still) and \
                not any(r.phase == "prefill"
                        for r in self.active.values()):
            victim = min(still, key=lambda r: (_QOS_RANK.get(r.qos, 1),
                                               -(r.admitted_at or 0.0)))
            self._requeue(victim)
            stalled.discard(victim.rid)
            for req in still:
                if req.rid in stalled and \
                        self.kv.append_page(req.slot) is not None:
                    stalled.discard(req.rid)
        return stalled

    # -------------------------------------------------- speculative decode
    def _spec_k(self, dec: List[Request]) -> int:
        """Batch draft length for this tick: the min over rows of each
        request's EMA-preferred k, clamped so the k+1 verify positions fit
        under ``max_seq`` for every row (conservative batch-min keeps one
        launch shape; near-capacity rows drag k down only near the end of
        their sequence).  < 1 → the caller falls back to a normal tick."""
        k = self.spec_k_max
        for r in dec:
            pos = len(r.prompt) + len(r.generated) - 1
            room = self.max_seq - 1 - pos     # need pos + k <= max_seq - 1
            pref = max(1, round(r.spec_ema * self.spec_k_max))
            k = min(k, pref, room)
        return k

    def _spec_decode_tick(self, dec: List[Request],
                          k: int) -> Optional[Tuple[int, int]]:
        """One speculative tick: the draft proposes k tokens per decoding
        row, the target verifies all k+1 positions in one paged pass, and
        the accepted prefix plus the target's correction token commit in
        bulk.  Returns ``(rows, committed_tokens)``, or ``None`` when the
        draft died — speculation disables itself and the caller serves the
        batch with the normal tick instead."""
        stalled = self._grow_decode_pages(dec, span=k + 1)
        dec = [r for r in dec if r.rid in self.active
               and r.phase == "decode" and r.rid not in stalled]
        if not dec:
            return 0, 0
        active_mask = np.zeros((self.max_slots,), bool)
        for req in dec:
            active_mask[req.slot] = True
        active = jnp.asarray(active_mask)
        try:
            drafts = self._draft.propose(self.last_tokens, active, k)
            self.draft_ticks += 1
        except Exception as e:  # noqa: BLE001 — the draft donates only its
            # own cache tree; the target's pools are untouched, so drop to
            # non-speculative serving instead of failing the batch
            self._draft = None
            self._spec_disabled_reason = f"draft propose: {e}"
            return None
        tokens_blk = jnp.concatenate([self.last_tokens[:, None], drafts],
                                     axis=1)
        try:
            tgt, acc, nxt, pools, new_len = self._verify(
                self.params, self.kv.pools, self.kv.page_table, tokens_blk,
                self.kv.cache_len, self.last_tokens, active)
            self.kv.pools = pools
            self.kv.cache_len = new_len
        except Exception as e:  # noqa: BLE001 — verify donates the SHARED
            # pools: same blast radius as the normal decode error path
            for req in list(self.active.values()):
                self._release(req)
                del self.active[req.rid]
                self._fail(req, e)
            return 0, 0
        self._draft.observe(new_len, active)
        self.last_tokens = nxt
        # ONE device sync per tick (not one per request)
        tgt_np = np.asarray(tgt)
        drafts_np = np.asarray(drafts)
        accs = np.asarray(acc)
        clens = np.asarray(self.kv.cache_len)
        now = time.monotonic()
        committed_total = 0
        finished = []
        for req in dec:
            a = int(accs[req.slot])
            committed = [int(x) for x in drafts_np[req.slot, :a]]
            committed.append(int(tgt_np[req.slot, a]))
            self.spec_proposed += k
            self.spec_accepted += a
            req.spec_ema = 0.7 * req.spec_ema + 0.3 * (a / k)
            for t in committed:
                req.generated.append(t)
                committed_total += 1
                if (req.eos_token is not None and t == req.eos_token) or \
                        len(req.generated) >= req.max_new_tokens:
                    finished.append(req)
                    break
            else:
                if int(clens[req.slot]) >= self.kv.max_seq - 1:
                    finished.append(req)
        self.spec_rounds += 1
        self.dispatch_stats.set_extra("speculation", {
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "acceptance_rate": self.spec_accepted / self.spec_proposed
            if self.spec_proposed else 0.0,
            "draft_ticks": self.draft_ticks,
        })
        for req in finished:
            self._finish(req, now)
        return len(dec), committed_total

    # ------------------------------------------------------- decode phase
    def _decode_tick(self) -> Tuple[int, int]:
        """Advance the decode batch once; returns (rows, tokens committed).
        A speculative tick commits up to k+1 tokens per row; the normal
        tick commits exactly one."""
        dec = [r for r in self.active.values() if r.phase == "decode"]
        if not dec:
            return 0, 0
        if self._draft is not None and self.paged:
            k = self._spec_k(dec)
            if k >= 1:
                out = self._spec_decode_tick(dec, k)
                if out is not None:
                    return out
                # draft died mid-tick: recompute the batch (growth above
                # may have requeued rows) and serve it non-speculatively
                dec = [r for r in self.active.values()
                       if r.phase == "decode"]
                if not dec:
                    return 0, 0
        if self.paged:
            stalled = self._grow_decode_pages(dec)
            dec = [r for r in dec if r.rid in self.active
                   and r.phase == "decode" and r.rid not in stalled]
            if not dec:
                return 0, 0
        active_mask = np.zeros((self.max_slots,), bool)
        for req in dec:
            active_mask[req.slot] = True
        try:
            if self.paged:
                tokens, pools, new_len = self._decode(
                    self.params, self.kv.pools, self.kv.page_table,
                    self.last_tokens, self.kv.cache_len,
                    jnp.asarray(active_mask))
                self.kv.pools = pools
                self.kv.cache_len = new_len
            else:
                tokens, self.kv.caches, self.kv.cache_len = self._decode(
                    self.params, self.kv.caches, self.last_tokens,
                    self.kv.cache_len, jnp.asarray(active_mask))
        except Exception as e:  # noqa: BLE001 — a decode error poisons
            # the donated cache state for EVERY admitted request
            # (prefilling rows share the pools): fail them all so blocked
            # handles surface the error instead of hanging
            for req in list(self.active.values()):
                self._release(req)
                del self.active[req.rid]
                self._fail(req, e)
            return 0, 0
        self.last_tokens = tokens
        toks = np.asarray(tokens)
        # ONE device sync per tick (not one per request)
        clens = np.asarray(self.kv.cache_len)
        now = time.monotonic()
        finished = []
        for req in dec:
            t = int(toks[req.slot])
            req.generated.append(t)
            if req.first_token_at is None:
                req.first_token_at = now
            if (req.eos_token is not None and t == req.eos_token) or \
                    len(req.generated) >= req.max_new_tokens or \
                    int(clens[req.slot]) >= self.kv.max_seq - 1:
                finished.append(req)
        for req in finished:
            self._finish(req, now)
        return len(dec), len(dec)

    # ---------------------------------------------------------------- tick
    def step(self) -> int:
        """One engine tick: admit, run budgeted prefill chunks, then one
        decode for all decoding slots.

        Thread-safe: the whole tick runs under the engine lock, so exactly
        one tick advances at a time whether it's the background loop or a
        caller-driven thread stepping.
        """
        with self._lock:
            self._admit()
            if not self.active:
                self._tick.notify_all()
                return 0
            t0 = time.monotonic()
            prefill_tokens = self._prefill_tick()
            t1 = time.monotonic()
            decode_rows, decode_tokens = self._decode_tick()
            t2 = time.monotonic()
            if prefill_tokens or decode_rows:
                self.ticks += 1
                self._tick_log.append((t1 - t0, t2 - t1, prefill_tokens,
                                       decode_rows, decode_tokens))
            self._tick.notify_all()
            return len(self.active)

    def _finish(self, req: Request, now: float):
        req.done = True
        req.finished_at = now
        if self.prefix is not None and req.slot is not None:
            # donate the request's written pages to the radix index BEFORE
            # release: nodes take their own page references, so the pages
            # survive this request's free and the next same-prefix request
            # attaches instead of re-prefilling.  Valid coverage is
            # prompt + generated[:-1] (the final token's KV is never
            # written).
            cached = min(len(req.prompt) + max(len(req.generated) - 1, 0),
                         self.max_seq)
            tokens = np.concatenate(
                [req.prompt,
                 np.asarray(req.generated[:-1], np.int32)])[:cached]
            self.prefix.insert(tokens, self.kv.slot_pages[req.slot],
                               self.kv)
        self._release(req)
        del self.active[req.rid]
        self.completed[req.rid] = req
        self.dispatch_stats.record(DispatchSample(
            workload=f"request-{req.rid}", workload_class="heavy",
            executor_class="container", executor="serving-engine",
            node="local", wall_s=now - req.submitted_at, cold=False,
            footprint_bytes=self.kv.bytes_in_use(),
            replica=self.replica_id))
        if req.future is not None and not req.future.done():
            req.future.set_result(req)

    def run_until_drained(self, max_ticks: int = 10_000) -> List[Request]:
        if self.loop_running:
            return self.drain()
        for _ in range(max_ticks):
            with self._lock:
                if not self.queue and not self.active:
                    break
            self.step()
        with self._lock:
            return list(self.completed.values())

    # ------------------------------------------------------------------
    def spec_overhead_bytes(self) -> int:
        """Draft-side HBM the speculator adds (draft params + its dense
        slot cache); 0 when speculation is off.  Charged into the
        executor's footprint so admission/QoS arbitrates draft capacity
        like any other tenant demand."""
        with self._lock:
            d = self._draft
        return d.footprint_bytes() if d is not None else 0

    def stats(self) -> Dict[str, float]:
        with self._lock:
            done = list(self.completed.values())
            out = {
                "ticks": self.ticks,
                "active": len(self.active),
                "queued": len(self.queue),
                "queue_depth": len(self.queue),
                "replica_id": self.replica_id,
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "prefix_hit_rate": self.prefix_hits /
                (self.prefix_hits + self.prefix_misses)
                if (self.prefix_hits + self.prefix_misses) else 0.0,
                "failed": len(self.failed),
                "slot_utilization": self.kv.utilization(),
                "paged": self.paged,
                "kv_bytes_in_use": self.kv.bytes_in_use(),
                "kv_capacity_bytes": self.kv.capacity_bytes(),
                "kv_dense_equivalent_bytes":
                    self.kv.dense_equivalent_bytes(),
            }
            # speculative decoding surface (zeros while disabled/off)
            out["speculative"] = self._draft is not None
            out["spec_proposed"] = self.spec_proposed
            out["spec_accepted"] = self.spec_accepted
            out["acceptance_rate"] = (self.spec_accepted /
                                      self.spec_proposed
                                      if self.spec_proposed else 0.0)
            out["spec_rounds"] = self.spec_rounds
            out["draft_ticks"] = self.draft_ticks
            if self._spec_disabled_reason:
                out["spec_disabled_reason"] = self._spec_disabled_reason
            if self.paged:
                out["kv_dtype"] = str(jnp.dtype(self.kv_dtype))
                out["pages_in_use"] = self.kv.pages_in_use()
                out["page_utilization"] = self.kv.page_utilization()
                out["cow_copies"] = self.kv.cow_copies
                out["kv_prefix_hits"] = self.kv_prefix_hits
                out["kv_prefix_misses"] = self.kv_prefix_misses
                out["preemptions"] = self.preemptions
                out["decode_stalls"] = self.decode_stalls
                out["kv_shared_pages_attached"] = sum(
                    self.kv.slot_shared.values())
                if self.prefix is not None:
                    for k, v in self.prefix.stats().items():
                        out[f"radix_{k}"] = v
            recent = list(self.recent_queue_s)
            ticks = list(self._tick_log)
        if recent:
            out["p95_queue_recent_s"] = percentile(recent, 95)
        # prefill-vs-decode tick-time split (only ticks that did the work)
        pre = [p for p, _d, ptoks, _n, _tk in ticks if ptoks]
        dec = [d for _p, d, _t, n, _tk in ticks if n]
        # per-committed-token decode latency: the spec-vs-baseline metric
        # (a speculative tick's wall amortizes over its committed tokens)
        dec_tok = [d / tk for _p, d, _t, n, tk in ticks if n and tk]
        for name, xs in (("prefill_tick_s", pre), ("decode_tick_s", dec),
                         ("decode_s_per_token", dec_tok)):
            if xs:
                for q in (50, 95):
                    out[f"p{q}_{name}"] = percentile(xs, q)
        if ticks:
            out["max_prefill_tokens_tick"] = max(t[2] for t in ticks)
            out["decode_tokens_committed"] = sum(t[4] for t in ticks)
        ttfts = [r.first_token_at - r.submitted_at for r in done
                 if r.first_token_at is not None]
        queued = [r.admitted_at - r.submitted_at for r in done
                  if r.admitted_at is not None]
        walls = [r.finished_at - r.submitted_at for r in done
                 if r.finished_at is not None]
        for name, xs in (("ttft_s", ttfts), ("queue_s", queued),
                         ("request_wall_s", walls)):
            if xs:
                for q in (50, 95, 99):
                    out[f"p{q}_{name}"] = percentile(xs, q)
        return out


class EngineExecutor(BaseExecutor):
    """Container-class executor wrapping a continuous-batching engine, so a
    serving deployment is declared through ``ServiceSpec``/``EdgeSystem``
    like every other service.

    ``dispatch`` submits the prompt and blocks on the request's handle:
    with the background loop running (``autostart=True`` starts it on
    first dispatch), concurrent dispatches from different threads batch in
    the shared engine — one request's prefill chunks overlap another's
    decode.  Without a loop, the handle drives ticks inline (still
    lock-serialized, so concurrent callers share the decode batch either
    way).

    Footprints follow the paged accounting: the *static* footprint (what
    placement reserves) is params + the KV pool's actual capacity — which
    shrinks when ``num_pages`` undercuts the dense ``max_slots × max_seq``
    layout — and ``dynamic_footprint_bytes`` reports params + pages
    currently in use, the number telemetry samples carry.
    """

    executor_class = ExecutorClass.CONTAINER

    def __init__(self, name: str, engine: ServingEngine, mesh=None,
                 autostart: bool = True,
                 result_timeout: Optional[float] = 120.0):
        super().__init__(name, mesh)
        self.engine = engine
        self.autostart = autostart
        self.result_timeout = result_timeout
        # params are fixed at engine init — size them once, not per
        # dispatch.  The speculator's draft (params + dense slot cache) is
        # part of the reservation: admission/QoS charges draft capacity
        # like any other demand, and the charge is sized at init so a
        # mid-service speculation disable doesn't shrink a placed
        # footprint out from under the orchestrator.
        self._params_bytes = _tree_bytes(self.engine.params)
        self._spec_bytes = self.engine.spec_overhead_bytes()
        self._footprint = self._params_bytes + self._spec_bytes + \
            self.engine.kv.capacity_bytes()

    def footprint_bytes(self) -> int:
        return self._footprint

    def dynamic_footprint_bytes(self) -> int:
        """Live HBM commitment: params + KV pages (or slots) in use."""
        return self._params_bytes + self._spec_bytes + \
            self.engine.kv.bytes_in_use()

    def can_run(self, workload: Workload, args) -> bool:
        if workload.kind not in (WorkloadKind.PREFILL, WorkloadKind.DECODE,
                                 WorkloadKind.GENERIC):
            return False
        if len(args) != 1:           # dispatch unpacks exactly one prompt
            return False
        try:
            a = np.asarray(args[0])
        except Exception:  # noqa: BLE001
            return False
        return a.ndim == 1 and np.issubdtype(a.dtype, np.integer)

    def dispatch(self, workload: Workload, args):
        (prompt,) = args
        t0 = time.monotonic()
        if self.autostart:
            self.engine.start()
        self.inflight += 1
        try:
            handle = self.engine.submit(
                prompt, max_new_tokens=max(workload.seq_len, 1),
                latency_slo_ms=workload.latency_slo_ms)
            req = handle.result(timeout=self.result_timeout)
        finally:
            self.inflight -= 1
        self.history.append(DispatchRecord(workload.name,
                                           time.monotonic() - t0, False))
        return req

    def stats_extras(self) -> Dict[str, object]:
        """Engine-side annotations (speculation acceptance counters) for
        the manager to merge into the system-wide ``DispatchStats``."""
        return self.engine.dispatch_stats.extras()
