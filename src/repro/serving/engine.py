"""The real JAX continuous-batching engine (slot-ring design).

XLA needs static shapes, so the iteration-level batching of Orca/vLLM
becomes a fixed-size ring of decode slots:

* ``n_slots`` sequences decode in lockstep, one token per engine step
  (a single jitted ``serve_step`` on the whole slot batch);
* join = prefill the prompt (jitted per prompt-length bucket) and
  scatter the resulting cache into the slot's batch index;
* leave = mark the slot free (its lane keeps computing garbage that is
  masked out — the standard TPU serving trade);
* per-slot positions: each lane decodes at its own depth (the
  ``pos``-vector decode path in models/layers.py).

The engine drives the *identical* DriftScheduler state machine the
simulator uses — admission, dispatch, completion feedback (Eq. 5-6) —
so scheduling behaviour validated on the simulator transfers 1:1.

Iteration-level execution (mirrors the simulator's step engine,
``serving/simulator.py``; pinned by ``tests/test_engine_parity.py``):

* **Chunked prefill** (``EngineConfig.chunk_prefill_tokens``): a
  joining slot's prompt is consumed across iterations against a
  per-step prefill token budget shared by prefilling slots in join
  order (Sarathi-style). The slot's first token — and its honest TTFT
  anchor ``Request.prefill_end`` — lands at the iteration its last
  chunk completes; admissions keep interleaving with decode under
  ``DriftScheduler.max_new_per_step``, and slot state only changes at
  iteration boundaries. ``None`` (unbounded) is the legacy contract:
  the whole bucket prefills in the admission step, which the parity
  suite locks bit-for-bit against the pre-chunking engine.
  Chunk accounting runs in *request* prompt tokens (clipped to the
  bucket): the XLA padding a bucket adds is a static-shape artifact,
  not billable workload. On the paged transformer path every chunk
  *executes on device* the iteration its budget is consumed, through
  the fused chunked-prefill kernel
  (``kernels/chunked_prefill.py``): scatter the slab's K/V into the
  sequence's pages, then attend it against everything resident —
  prefix-tree pages and earlier chunks — under query-offset causal
  masking. The final chunk covers the bucket's padding tail and its
  last-position logits produce the first token. (The vlm family and
  the slot-ring cache keep the legacy single-shot bucket prefill.)
* **Shared-prefix reuse** (``EngineConfig.prefix_cache``, paged mode
  only): ``kv_cache.PrefixTree`` runs over the engine's own page pool.
  A joining request whose prompt starts with a resident shared prefix
  (``Request.prefix_group`` / ``shared_prefix_tokens``) skips
  prefilling the cached full pages — its page table references the
  tree's refcount-pinned pages directly and chunked prefill starts at
  the cached boundary. At prefill completion the freshly-written full
  prefix pages are *donated* to the tree (``insert(pages=...)``: page
  identity survives because the KV is already on device), and the pin
  is released at retirement. ``prefix_cache_pages`` extra pool pages
  back residency; unreferenced LRU leaves evict under pressure.
  Shared-prefix prompts are tokenized with a deterministic per-group
  prefix (content-hashed, positions 0..shared-1) so donated pages hold
  exactly the KV any group member would compute.

EOS: with randomly-initialised smoke models there is no semantic EOS,
so requests stop at their ground-truth output length (oracle EOS,
clipped by max_tokens) — exactly the signal the drift compensator must
learn. A real deployment swaps in token-id EOS detection. Note the
cache interaction: a prefix served from cache re-observes no drift —
feedback comes only from the decode the request actually ran.
"""

from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.request import Request, RequestState
from ..core.scheduler import DriftScheduler
from ..models.config import ModelConfig
from ..models.registry import get_api
from ..models.steps import sample_logits
from ..obs import events as tr
from ..obs import resolve_recorder
from ..obs import spans as sp
from .kv_cache import PagedPool, PagedSeqLedger, prefix_page_key
from .metrics import RunMetrics, summarize_run


@dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 8
    max_len: int = 256               # per-slot cache capacity
    prompt_buckets: Tuple[int, ...] = (16, 32, 64)
    temperature: float = 0.0
    batch_wait_steps: int = 0
    # vLLM-style paged KV pool instead of the slot-ring cache
    # (transformer-family archs; kernels/paged_attention on TPU)
    paged: bool = False
    page_size: int = 16
    # --- iteration-level prefill (Sarathi chunking) ---
    # per-STEP prefill token budget shared by prefilling slots in join
    # order; None = unbounded (whole-bucket prefill in the admission
    # step — the legacy contract, locked by tests/test_engine_parity.py)
    chunk_prefill_tokens: Optional[int] = None
    # --- shared-prefix radix cache over the paged pool ---
    # requires paged=True: sharing is physical (page-table aliasing)
    prefix_cache: bool = False
    # extra pool pages reserved for cache residency; also the LRU
    # budget the tree is evicted back to after each donation
    prefix_cache_pages: int = 64


def _bucket(n: int, buckets: Tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


@dataclass
class SlotState:
    req: Optional[Request] = None
    generated: int = 0
    target: int = 0
    last_token: int = 0
    # --- chunked-prefill progress (request prompt tokens, bucket-clipped)
    prompt_len: int = 0
    prefill_remaining: int = 0     # uncached prompt tokens not yet consumed
    cached_tokens: int = 0         # prompt tokens served from the cache
    pending_prefill: bool = False  # device prefill not yet executed
    batch: Optional[Dict] = None   # tokenized prompt awaiting prefill
    bucket: int = 0
    prefill_pos: int = 0           # bucket position device prefill reached


class ServingEngine:
    """Continuous-batching engine for one model on one device.

    ``device`` is where the engine's params and KV cache live (None:
    JAX's default device, arrays placed as the caller made them); every
    jitted step follows them there."""

    def __init__(self, cfg: ModelConfig, params, scheduler: DriftScheduler,
                 config: Optional[EngineConfig] = None,
                 extras: Optional[Dict] = None,
                 trace=None, device: Optional[jax.Device] = None) -> None:
        self.cfg = cfg
        self.params = (params if device is None
                       else jax.device_put(params, device))
        self.sched = scheduler
        self.ecfg = config or EngineConfig()
        self.extras = extras or {}
        self.trace = resolve_recorder(trace)
        # replica id stamped on emitted events; the cluster driver sets
        # it after construction (None = standalone / unset)
        self.trace_rid: Optional[int] = None
        if self.trace.enabled:
            self.sched.drift.trace = self.trace
        self.api = get_api(cfg)
        c = self.ecfg.chunk_prefill_tokens
        if c is not None and c < 1:
            raise ValueError(
                f"chunk_prefill_tokens must be >= 1 or None, got {c}")
        if self.ecfg.prefix_cache and not self.ecfg.paged:
            raise ValueError(
                "prefix_cache requires paged=True: prefix sharing is "
                "physical page-table aliasing over the paged pool")
        n, S = self.ecfg.n_slots, self.ecfg.max_len
        self.slots: List[SlotState] = [SlotState() for _ in range(n)]
        self.step_count = 0
        self.busy_steps = 0
        self._rng = jax.random.PRNGKey(0)
        self._prefill_cache = {}
        self._chunk_cache = {}             # jitted chunk fns, keyed by length
        self._join_order: List[int] = []   # slot ids, chunk-budget order
        # --- per-chunk device execution counters (paged prefill path) ---
        self.n_prefill_launches = 0
        self.prefill_chunk_log: List[Tuple[int, int]] = []  # (slot, length)
        # --- P/D disaggregation plumbing (cluster driver) ---
        # called as hook(slot, req, now) when a slot's prefill completes;
        # returning True means the driver took the request over (KV
        # extracted for transfer) and the engine must not decode it
        self.handoff_hook = None
        self._pending_injections: Dict = {}   # req_id -> KV payload
        # --- prefix-cache counters (mirror WorkerSimulator's) ---
        self.prefix_tree = None
        self.n_prefix_hits = 0
        self.n_prefix_misses = 0
        self.prefix_tokens_saved = 0

        if self.ecfg.paged:
            if cfg.family not in ("dense", "moe", "vlm"):
                raise ValueError(
                    f"paged engine supports transformer-family archs, "
                    f"not {cfg.family!r} (SSM state is O(1) already)")
            from .kv_cache import PagedAllocator, PrefixTree
            pages_per_seq = -(-S // self.ecfg.page_size)
            extra = (self.ecfg.prefix_cache_pages
                     if self.ecfg.prefix_cache else 0)
            # pool has one extra page the allocator never hands out:
            # inactive slots scatter their (masked) writes into it
            self.alloc = PagedAllocator(
                n_pages=n * pages_per_seq + extra,
                page_size=self.ecfg.page_size,
                pages_per_seq=pages_per_seq)
            if self.ecfg.prefix_cache:
                self.prefix_tree = PrefixTree(self.alloc)
            self.ledger = PagedSeqLedger(
                self.alloc, self.prefix_tree,
                cache_pages_budget=(self.ecfg.prefix_cache_pages
                                    if self.ecfg.prefix_cache else None))
            self.pool = PagedPool.create(cfg, self.alloc.n_pages + 1,
                                         self.ecfg.page_size, device=device)
            self._decode_paged = jax.jit(self._decode_paged_fn,
                                         static_argnames=("max_pages",))
        else:
            self.cache = self.api.init_cache(cfg, n, S)
            if device is not None:
                self.cache = jax.device_put(self.cache, device)
            self._decode = jax.jit(self._decode_fn)

    # --- jitted units ---------------------------------------------------
    def _decode_fn(self, params, cache, tokens, pos, rng):
        logits, cache = self.api.decode_step(self.cfg, params, cache,
                                             tokens, pos)
        toks = sample_logits(logits, rng, self.ecfg.temperature)
        return toks, cache

    def _decode_paged_fn(self, params, pool, tokens, page_table,
                         seq_lens, rng, *, max_pages=None):
        from ..models import transformer
        logits, pool = transformer.decode_step_paged(
            self.cfg, params, pool, tokens, page_table, seq_lens,
            max_pages=max_pages)
        toks = sample_logits(logits, rng, self.ecfg.temperature)
        return toks, pool

    def _chunk_fn_for(self, chunk_len: int):
        """Jitted fused-chunked-prefill step, cached per chunk length
        (the engine's analogue of per-bucket prefill jitting)."""
        if chunk_len not in self._chunk_cache:
            def fn(params, pool, tokens, page_table, q_offset):
                from ..models import transformer
                return transformer.prefill_chunk_paged(
                    self.cfg, params, pool, tokens, page_table, q_offset)
            self._chunk_cache[chunk_len] = jax.jit(fn)
        return self._chunk_cache[chunk_len]

    def _prefill_fn_for(self, bucket: int):
        if bucket not in self._prefill_cache:
            def fn(params, batch, rng):
                logits, cache = self.api.prefill(
                    self.cfg, params, batch, max_len=self.ecfg.max_len)
                return sample_logits(logits, rng,
                                     self.ecfg.temperature), cache
            self._prefill_cache[bucket] = jax.jit(fn)
        return self._prefill_cache[bucket]

    # --- slot management --------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s.req is None]

    def active_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s.req is not None]

    def _insert_cache(self, slot: int, cache_1: Dict) -> None:
        """Scatter a batch-1 prefill cache into slot ``slot``."""
        def ins(full, one):
            axis = 1 if full.ndim > 1 else 0
            idx = [slice(None)] * full.ndim
            idx[axis] = slot
            return full.at[tuple(idx)].set(
                jnp.take(one, 0, axis=axis).astype(full.dtype))
        self.cache = jax.tree_util.tree_map(ins, self.cache, cache_1)

    # --- prefix-cache plumbing -------------------------------------------
    def _shared_eff(self, req: Request, prompt_len: int) -> int:
        """Shareable prefix tokens after bucket clipping."""
        if req.prefix_group is None:
            return 0
        return min(req.shared_prefix_tokens, prompt_len)

    def _prefix_key(self, req: Request, prompt_len: int) -> tuple:
        return prefix_page_key(req.prefix_group,
                               self._shared_eff(req, prompt_len),
                               self.ecfg.page_size)

    def prefix_cached_tokens(self, req: Request) -> int:
        """Resident shared-prefix overlap this engine holds for
        ``req``, in tokens. Pure probe (no LRU/refcount perturbation) —
        the cluster router calls this per routable replica per
        placement."""
        if self.prefix_tree is None:
            return 0
        prompt_len = min(max(req.prompt_tokens, 1),
                         _bucket(max(req.prompt_tokens, 1),
                                 self.ecfg.prompt_buckets))
        key = self._prefix_key(req, prompt_len)
        if not key:
            return 0
        return min(self.prefix_tree.cached_tokens(key), prompt_len)

    def prefix_cache_stats(self) -> Dict[str, int]:
        """Cumulative cache counters (all zero when disabled)."""
        return {
            "hits": self.n_prefix_hits,
            "misses": self.n_prefix_misses,
            "tokens_saved": self.prefix_tokens_saved,
            "evicted_pages": (self.prefix_tree.n_evicted_pages
                              if self.prefix_tree else 0),
            "resident_pages": (self.prefix_tree.total_pages()
                               if self.prefix_tree else 0),
            "invalidations": 0,
        }

    # --- tokenization -----------------------------------------------------
    def _tokenize(self, req: Request, bucket: int, prompt_len: int,
                  shared_eff: int) -> np.ndarray:
        """[1, bucket] int32 prompt ids.

        Legacy layout (no shareable prefix): prompt bytes right-aligned,
        zero padding in front — bit-identical to the pre-chunking
        engine. Prefix layout (``prefix_cache`` + a shareable prefix):
        a deterministic content-hashed group prefix occupies positions
        ``[0, shared_eff)`` — every member of a prefix group computes
        identical KV there, which is what makes donated pages reusable
        — and the request's own bytes fill the rest cyclically (no
        trailing padding, so the last position stays a real token for
        the prefill logits)."""
        vocab = max(self.cfg.vocab - 1, 1)
        tokens = np.zeros((1, bucket), np.int32)
        if self.prefix_tree is not None and shared_eff > 0:
            seed = zlib.crc32(repr(req.prefix_group).encode())
            pos = np.arange(bucket, dtype=np.int64)
            tokens[0] = (seed + pos * 2654435761) % vocab + 1
            body = np.frombuffer(req.prompt.encode() or b"\x01",
                                 dtype=np.uint8).astype(np.int64)
            tail = bucket - shared_eff
            if tail > 0:
                reps = np.resize(body, tail)
                tokens[0, shared_eff:] = reps % vocab + 1
        else:
            ids = np.frombuffer(req.prompt.encode()[:prompt_len * 4],
                                dtype=np.uint8)[:prompt_len].astype(np.int64)
            if len(ids):
                tokens[0, -len(ids):] = ids % vocab + 1
        return tokens

    # --- admission --------------------------------------------------------
    def _admit(self, req: Request, slot: int, now: float) -> None:
        """Open a slot for ``req``: tokenize, probe/pin the prefix
        cache, allocate pages (paged mode) and queue the prompt for
        chunked prefill. The device prefill itself runs at the
        iteration the last chunk is consumed (:meth:`_run_prefill`)."""
        prompt_len = max(req.prompt_tokens, 1)
        bucket = _bucket(prompt_len, self.ecfg.prompt_buckets)
        prompt_len = min(prompt_len, bucket)      # truncate to the bucket
        shared_eff = self._shared_eff(req, prompt_len)
        tokens = self._tokenize(req, bucket, prompt_len, shared_eff)
        batch = {"tokens": jnp.asarray(tokens)}
        if self.cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (1, self.cfg.prefix_len, self.cfg.d_model), jnp.bfloat16)
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (1, self.cfg.enc_seq, self.cfg.d_model), jnp.bfloat16)
        cached = 0
        if self.ecfg.paged:
            key = (self._prefix_key(req, prompt_len)
                   if self.prefix_tree is not None else ())
            evicted_before = (self.prefix_tree.n_evicted_pages
                              if self.prefix_tree is not None else 0)
            cached = self.ledger.admit(slot, bucket, key, now)
            cached = min(cached, shared_eff)
            if key:
                if cached > 0:
                    self.n_prefix_hits += 1
                    self.prefix_tokens_saved += cached
                else:
                    self.n_prefix_misses += 1
                if self.trace.enabled:
                    self.trace.emit(
                        now, tr.PREFIX_HIT if cached > 0
                        else tr.PREFIX_MISS,
                        req_id=req.req_id, rid=self.trace_rid,
                        tenant=req.tenant.label,
                        **({"tokens": cached} if cached > 0 else {}))
            if self.trace.enabled and self.prefix_tree is not None:
                delta = self.prefix_tree.n_evicted_pages - evicted_before
                if delta > 0:
                    self.trace.emit(now, tr.PREFIX_EVICT,
                                    rid=self.trace_rid, pages=delta)
        req.cached_prompt_tokens = cached
        st = self.slots[slot]
        st.req = req
        st.generated = 0
        st.target = max(1, min(req.true_output_tokens, req.max_tokens,
                               self.ecfg.max_len - bucket - 2))
        st.prompt_len = prompt_len
        st.cached_tokens = cached
        st.prefill_remaining = prompt_len - cached
        st.pending_prefill = True
        st.batch = batch
        st.bucket = bucket
        st.prefill_pos = cached
        self._join_order.append(slot)
        req.state = RequestState.EXECUTING
        req.exec_start = now

    @property
    def _chunked_device_prefill(self) -> bool:
        """Paged transformer prefill goes through the fused
        chunked-prefill kernel per chunk. The vlm family keeps the
        legacy single-shot path: its patch-embedding prefix-LM prefill
        has no chunked counterpart."""
        return self.ecfg.paged and self.cfg.family != "vlm"

    def _run_prefill(self, slot: int, now: float) -> None:
        """Legacy single-shot device prefill (slot-ring cache, and the
        vlm-paged path): the slot's last prompt chunk landed, execute
        the whole bucket at once and emit the first token."""
        st = self.slots[slot]
        with sp.span(sp.ENGINE_PREFILL):
            self._rng, sub = jax.random.split(self._rng)
            if self.ecfg.paged:
                from ..models import transformer
                from .kv_cache import write_prefill_pages
                logits, k_lv, v_lv = transformer.prefill_kv(
                    self.cfg, self.params, st.batch["tokens"],
                    patches=st.batch.get("patches"))
                cached = self.ledger.cached_tokens(slot)
                pages = self.ledger.table(slot)[
                    cached // self.ecfg.page_size:]
                self.pool = write_prefill_pages(
                    self.pool, (k_lv[:, 0], v_lv[:, 0]), pages, st.bucket,
                    start_token=cached)
                if self.prefix_tree is not None:
                    self.ledger.donate(slot, now)
                tok = sample_logits(logits, sub, self.ecfg.temperature)
            else:
                tok, cache_1 = self._prefill_fn_for(st.bucket)(
                    self.params, st.batch, sub)
                self._insert_cache(slot, cache_1)
            with sp.span(sp.FIRST_TOKEN_SYNC):
                tok = int(tok[0])
            self._emit_first_token(slot, tok, now)

    def _advance_prefill(self, slot: int, take: int, now: float) -> None:
        """Execute this iteration's device chunk for a paged slot
        through the fused kernel. The ``take`` prompt tokens the budget
        loop just consumed map 1:1 onto bucket positions
        ``[prefill_pos, prefill_pos + take)``; the final chunk extends
        through the bucket's padding tail (a static-shape artifact, not
        billable workload) so its last position yields the first-token
        logits. The prefill-completing iteration donates shareable
        pages and emits the first token."""
        st = self.slots[slot]
        final = st.prefill_remaining <= 0
        start, end = st.prefill_pos, st.prefill_pos + take
        if final:
            end = st.bucket
            if end <= start:
                # fully-cached prompt: re-run the last bucket position
                # (recomputing KV already resident in the cached pages)
                # purely for the first-token logits
                start = end - 1
        if end <= start:
            return                       # budget exhausted, mid-prompt
        with sp.span(sp.ENGINE_PREFILL):
            chunk_len = end - start
            pt = jnp.asarray(self.ledger.table_array(
                [slot], self.alloc.pages_per_seq))
            logits, new_pool = self._chunk_fn_for(chunk_len)(
                self.params, {"k": self.pool.k, "v": self.pool.v},
                st.batch["tokens"][:, start:end], pt,
                jnp.asarray([start], jnp.int32))
            self.pool = PagedPool(k=new_pool["k"], v=new_pool["v"],
                                  page_size=self.ecfg.page_size)
            self.n_prefill_launches += 1
            self.prefill_chunk_log.append((slot, chunk_len))
            st.prefill_pos = end
            if final:
                self._rng, sub = jax.random.split(self._rng)
                if self.prefix_tree is not None:
                    self.ledger.donate(slot, now)
                tok = sample_logits(logits, sub, self.ecfg.temperature)
                with sp.span(sp.FIRST_TOKEN_SYNC):
                    tok = int(tok[0])
                self._emit_first_token(slot, tok, now)

    def _emit_first_token(self, slot: int, tok: int, now: float) -> None:
        """Prefill-completion bookkeeping shared by the single-shot and
        per-chunk paths: first token, honest TTFT anchor, and the P/D
        handoff hook (a hooked request leaves for a decode replica
        instead of joining this engine's decode set)."""
        st = self.slots[slot]
        st.generated = 1                       # prefill emitted one token
        st.last_token = tok
        st.pending_prefill = False
        st.batch = None
        st.req.prefill_end = now               # first token exists now
        if self.trace.enabled:
            self.trace.emit(now, tr.FIRST_TOKEN, req_id=st.req.req_id,
                            rid=self.trace_rid,
                            tenant=st.req.tenant.label,
                            ttft=now - st.req.arrival_time, token=tok)
        if self.handoff_hook is not None \
                and self.handoff_hook(slot, st.req, now):
            self._release_slot(slot)

    # --- P/D disaggregation: KV extraction / injection --------------------
    def _release_slot(self, slot: int) -> None:
        """Free a slot without completing its request (the request
        lives on elsewhere — P/D handoff or failure reroute): no
        ``sched.complete``, so no drift feedback fires here."""
        st = self.slots[slot]
        if self.ecfg.paged:
            self.ledger.free(slot)
        self._join_order.remove(slot)
        st.req = None
        st.generated = 0
        st.target = 0
        st.prefill_remaining = 0
        st.cached_tokens = 0
        st.pending_prefill = False
        st.batch = None

    def extract_sequence(self, slot: int) -> Dict:
        """Snapshot a prefilled slot's state for a P/D handoff: the
        sequence's page *contents* gathered off the pool (the real KV
        page movement — prefix-tree pages are copied too, the receiver
        gets private pages) plus the decode-resume scalars."""
        assert self.ecfg.paged, "KV extraction requires the paged pool"
        st = self.slots[slot]
        pages = jnp.asarray(self.ledger.table(slot), jnp.int32)
        return {
            "k": np.asarray(self.pool.k[:, pages]),
            "v": np.asarray(self.pool.v[:, pages]),
            "seq_len": st.bucket,          # resident tokens (whole bucket)
            "bucket": st.bucket,
            "prompt_len": st.prompt_len,
            "last_token": st.last_token,
            "generated": st.generated,
            "target": st.target,
        }

    def accept_handoff(self, req: Request, payload: Dict) -> None:
        """Queue a prefilled request whose KV transfer just landed.
        Mirrors ``SimReplica.accept_handoff``: back of its tenant queue
        at the original enqueue timestamp, estimate untouched; the KV
        payload is injected into the paged pool when a slot dispatches
        it."""
        self._pending_injections[req.req_id] = payload
        self.sched.queues.enqueue(req, req.enqueue_time)

    def pop_pending_injection(self, req_id: int) -> Optional[Dict]:
        """Detach a queued request's undispatched KV payload (the
        cluster driver re-transfers it when the request is stolen off
        this engine's queue)."""
        return self._pending_injections.pop(req_id, None)

    def _admit_prefilled(self, req: Request, slot: int, payload: Dict,
                         now: float) -> None:
        """Open a slot for a request that already prefilled elsewhere:
        allocate pages, write the transferred KV into them, and enter
        decode directly (no prefill chunks, no first token — both
        happened on the source replica)."""
        assert self.ecfg.paged, "KV injection requires the paged pool"
        self.ledger.admit(slot, payload["seq_len"], (), now)
        pages = jnp.asarray(self.ledger.table(slot), jnp.int32)
        # the payload is host memory; land it on this engine's device
        k, v = jax.device_put((payload["k"], payload["v"]),
                              self.pool.k.sharding)
        self.pool = PagedPool(
            k=self.pool.k.at[:, pages].set(k.astype(self.pool.k.dtype)),
            v=self.pool.v.at[:, pages].set(v.astype(self.pool.v.dtype)),
            page_size=self.ecfg.page_size)
        st = self.slots[slot]
        st.req = req
        st.generated = payload["generated"]
        st.target = payload["target"]
        st.last_token = payload["last_token"]
        st.prompt_len = payload["prompt_len"]
        st.cached_tokens = 0
        st.prefill_remaining = 0
        st.pending_prefill = False
        st.batch = None
        st.bucket = payload["bucket"]
        st.prefill_pos = payload["seq_len"]
        self._join_order.append(slot)
        req.state = RequestState.EXECUTING
        if req.exec_start is None:
            req.exec_start = now

    def abort_all(self, now: float) -> List[Request]:
        """Failure path: drop every in-flight slot and pending KV
        injection; stranded requests go back to the caller (the cluster
        driver resets and reroutes them). Pool contents die with the
        replica, so a prefix tree is emptied too."""
        stranded = []
        for slot in list(self._join_order):
            stranded.append(self.slots[slot].req)
            self._release_slot(slot)
        self._pending_injections.clear()
        if self.prefix_tree is not None:
            self.prefix_tree.clear()
        return stranded

    def _retire(self, slot: int, now: float) -> None:
        st = self.slots[slot]
        req = st.req
        req.exec_end = now
        with sp.span(sp.SCHED_COMPLETE):
            self.sched.complete(req, st.generated, now)
        if self.trace.enabled:
            self.trace.emit(now, tr.COMPLETE, req_id=req.req_id,
                            rid=self.trace_rid, tenant=req.tenant.label,
                            observed=st.generated, e2e=req.e2e_latency,
                            ttft=req.ttft,
                            inter_token=req.inter_token_latency)
        if self.ecfg.paged:
            self.ledger.free(slot)
        self._join_order.remove(slot)
        st.req = None
        st.generated = 0
        st.target = 0
        st.prefill_remaining = 0
        st.cached_tokens = 0
        st.pending_prefill = False

    # --- main loop ----------------------------------------------------------
    def step(self, now: float) -> int:
        """One engine iteration: admit into free slots, consume the
        per-step prefill chunk budget in join order (running the device
        prefill for slots whose last chunk landed), advance every
        decoding slot one token, retire finished ones. Returns number
        of completions this step. Per-iteration admission honours the
        scheduler's ``max_new_per_step`` knob — the same slot-granular
        contract the discrete-event step engine uses
        (``DriftScheduler.dispatch_step``). Each phase is a host span of
        ``obs/spans.py``."""
        with sp.span(sp.ENGINE_STEP):
            return self._step(now)

    def _step(self, now: float) -> int:
        # admission (iteration boundary, interleaving with decode)
        with sp.span(sp.ENGINE_ADMIT):
            joined = 0
            cap = self.sched.max_new_per_step
            pages_per_seq = (self.alloc.pages_per_seq if self.ecfg.paged
                             else 0)
            for slot in self.free_slots():
                if self.sched.queue_depth() == 0:
                    break
                if cap is not None and joined >= cap:
                    break
                if self.ecfg.paged and self.prefix_tree is not None \
                        and not self.ledger.can_admit(
                            pages_per_seq * self.ecfg.page_size):
                    # conservative page guard: admission waits for
                    # retirements/evictions to free room (only reachable
                    # with a prefix cache — the plain pool is sized
                    # exactly)
                    break
                with sp.span(sp.SCHED_DISPATCH):
                    req = self.sched.dispatch(now)
                if req is None:
                    break
                payload = self._pending_injections.pop(req.req_id, None)
                if payload is not None:
                    self._admit_prefilled(req, slot, payload, now)
                else:
                    self._admit(req, slot, now)
                joined += 1

        # chunked prefill: apportion the per-step budget in join order;
        # a slot's prefill-completing iteration also emits its first
        # token (and, slot-ring legacy, joins this step's decode batch)
        budget = (math.inf if self.ecfg.chunk_prefill_tokens is None
                  else self.ecfg.chunk_prefill_tokens)
        for slot in list(self._join_order):
            st = self.slots[slot]
            if not st.pending_prefill:
                continue
            take = int(min(st.prefill_remaining, budget))
            st.prefill_remaining -= take
            budget -= take
            if take and self.trace.enabled:
                self.trace.emit(now, tr.PREFILL_CHUNK,
                                req_id=st.req.req_id, rid=self.trace_rid,
                                tenant=st.req.tenant.label, tokens=take)
            if self._chunked_device_prefill:
                # every chunk executes on device the iteration its
                # budget is consumed (fused chunked-prefill kernel)
                self._advance_prefill(slot, take, now)
            elif st.prefill_remaining <= 0:
                self._run_prefill(slot, now)
            if budget <= 0:
                break

        decoding = [i for i in self.active_slots()
                    if not self.slots[i].pending_prefill]
        if not decoding:
            if self.active_slots():
                # prefill-only iteration (budget exhausted mid-prompt)
                self.step_count += 1
                self.busy_steps += 1
            return 0

        with sp.span(sp.DECODE_PREP):
            tokens = np.zeros((self.ecfg.n_slots,), np.int32)
            for i in decoding:
                tokens[i] = self.slots[i].last_token
            self._rng, sub = jax.random.split(self._rng)
            if self.ecfg.paged:
                sids = [i if (self.slots[i].req is not None
                              and not self.slots[i].pending_prefill)
                        else None for i in range(self.ecfg.n_slots)]
                lens = self.ledger.lens_array(sids)  # tokens before step
                for i in decoding:
                    # grow the table first: this step writes the token's
                    # KV at position lens[i], which opens a new page
                    # whenever lens[i] is page-aligned
                    _, cows = self.ledger.extend(i, 1)
                    for old, new in cows:
                        # copy-on-write boundary: the ledger handed this
                        # slot a private copy of a shared page — mirror
                        # it device-side before this step's write lands
                        # there. Unreachable with full-page prefix keys
                        # (suffix pages are always private) but wired
                        # for the partial-page layouts cow_extend exists
                        # for.
                        self.pool = PagedPool(
                            k=self.pool.k.at[:, new].set(
                                self.pool.k[:, old]),
                            v=self.pool.v.at[:, new].set(
                                self.pool.v[:, old]),
                            page_size=self.ecfg.page_size)
                pt = self.ledger.table_array(sids, self.alloc.pages_per_seq)
                scratch = self.pool.n_pages - 1  # never allocated: inactive
                for i, sid in enumerate(sids):   # slots write there
                    if sid is None:
                        pt[i, :] = scratch
                # static page-grid trim for the batched kernel: next
                # power of two above the deepest live sequence (bounded
                # set of jit variants), clamped to the table width
                needed = max(1, -(-int(lens.max()) // self.ecfg.page_size))
                max_pages = 1
                while max_pages < needed:
                    max_pages *= 2
                max_pages = min(max_pages, self.alloc.pages_per_seq)
                inputs = (jnp.asarray(tokens), jnp.asarray(pt),
                          jnp.asarray(lens))
            else:
                pos = np.asarray(self.cache["lens"])     # per-slot depth
                inputs = (jnp.asarray(tokens), jnp.asarray(pos, np.int32))
        with sp.span(sp.DECODE_LAUNCH):
            if self.ecfg.paged:
                toks, new_pool = self._decode_paged(
                    self.params, {"k": self.pool.k, "v": self.pool.v},
                    *inputs, sub, max_pages=max_pages)
                self.pool = PagedPool(k=new_pool["k"], v=new_pool["v"],
                                      page_size=self.ecfg.page_size)
            else:
                toks, self.cache = self._decode(
                    self.params, self.cache, *inputs, sub)
        with sp.span(sp.DECODE_SYNC):
            toks = np.asarray(toks)

        with sp.span(sp.ENGINE_RETIRE):
            done = 0
            tron = self.trace.enabled
            for i in decoding:
                st = self.slots[i]
                st.generated += 1
                st.last_token = int(toks[i])
                if tron:
                    self.trace.emit(now, tr.DECODE_STEP,
                                    req_id=st.req.req_id,
                                    rid=self.trace_rid,
                                    n=st.generated, token=st.last_token)
                if st.generated >= st.target:       # oracle EOS
                    self._retire(i, now)
                    done += 1
            self.step_count += 1
            self.busy_steps += 1
            if tron:
                self.trace.emit(now, tr.GAUGE, rid=self.trace_rid,
                                name="queue_depth",
                                value=self.sched.queue_depth())
                self.trace.emit(now, tr.GAUGE, rid=self.trace_rid,
                                name="active_slots",
                                value=len(self.active_slots()))
                if self.ecfg.paged:
                    self.trace.emit(now, tr.GAUGE, rid=self.trace_rid,
                                    name="kv_free_pages",
                                    value=self.alloc.free_pages)
        return done

    def run_until_drained(self, *, max_steps: int = 100_000,
                          dt: float = 1.0) -> RunMetrics:
        """Process everything queued in the scheduler; ``dt`` is the
        simulated wall-clock per engine step (CPU steps are not
        representative of TPU step time)."""
        now = 0.0
        if self.trace.enabled:
            self.trace.begin_segment(
                f"engine:{self.sched.policy.name}")
        for _ in range(max_steps):
            if (self.sched.queue_depth() == 0
                    and not self.active_slots()):
                break
            self.step(now)
            now += dt
        return summarize_run(self.sched.policy.name,
                             self.sched.config.bias_enabled,
                             self.sched.completed,
                             busy_time=float(self.busy_steps) * dt)
