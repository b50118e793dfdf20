"""Generation engine: compiled prefill + batched decode with slot management.

The engine owns a fixed-capacity decode batch (``max_batch`` slots, each
with a ``max_seq`` cache). Requests are prefetched one at a time (prompt
padded to a power-of-two bucket so the number of compiled prefill programs
stays small) and *inserted* into a free slot of the running batch cache —
the mechanism continuous batching (scheduler.py) is built on.

All hot functions are jitted once per (bucket) shape:
- ``_prefill_one``: prompt [1, bucket] -> (last logits, single-slot cache)
- ``_insert``: copy a single-slot cache into slot ``i`` of the batch cache
- ``_decode``: one step for all slots (+ sampling), inactive slots masked
- ``_chunk``: ``lax.scan`` over ``decode_chunk`` fused decode steps with
  on-device sampling and per-slot termination masks (EOS / token budget /
  ``max_seq`` capacity) — the scheduler syncs to host once per chunk
  instead of once per token.

The decode fast path is *sync-free*: the engine keeps the next input token
per slot on device (``_next_tok``). ``insert_request`` computes the first
generated token with an on-device argmax and returns it as an unforced
device scalar, so admitting a request never blocks the host on a
device->host read — the prefill dispatch overlaps the in-flight decode
chunk and the scheduler reads tokens at its single per-chunk sync point.

KV memory is either *contiguous* (each slot owns a ``max_seq`` cache —
memory scales with capacity) or *paged* (``paged=True``: a shared pool of
``kv_pool_blocks`` pages of ``page_size`` tokens, addressed through
per-slot block tables — memory scales with actual context). The engine
owns the page allocator host-side (free list + table mirror; device rows
are pushed asynchronously, never a sync): prefill allocates the prompt's
pages plus the first decode write's page, ``ensure_capacity`` secures one
page per upcoming KV write, and retire/cancel returns every page. Paging
applies to linear attention caches only; ring families (ssm / hybrid /
sliding-window) silently keep the linear layout.

Prompt accounting is two-track: ``_lengths`` / ``context_len`` are the
PHYSICAL cache lengths (ring families pad prompts to their bucket and
treat pads as context), while ``logical_len`` / ``kv_stats`` report what
the client actually sent — padding is never billed as usage.

``prefix_cache=True`` (paged engines only) layers a content-addressed
prefix cache (serving/prefix_cache.py) on the page allocator. Admission
then splits a prompt at the largest page boundary below its length:
the aligned *prefix* comes from cached pages when its chained hash
matches (refcount bumped, no prefill compute) or is prefilled and
registered, and the *tail* is force-fed through the fused decode path
(``_fill``) — one scan dispatch that writes the tail's KV and yields the
first-token logits. Cold and warm admissions thus share the exact same
numeric path for everything past the prefix boundary, which is what makes
a warm replay token-identical to its cold run. Shared and cache-
registered pages are READ-ONLY: the one write that can target one (the
full-hit replay of the last prompt token) copy-on-writes the page first,
and retire/cancel parks unreferenced cached pages in an LRU the allocator
evicts from before declaring the pool exhausted.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampling import mask_padded_vocab
from repro.serving.tracing import now as _now, span as _span

F32 = jnp.float32


def _named(name: str, fn, *args):
    """``partial(fn, *args)`` under a stable name: a bare partial compiles
    as ``jit__unknown``, which a profiler trace cannot tell apart from
    any other, and this one compiles as ``jit_<name>``."""
    p = partial(fn, *args)
    p.__name__ = name
    return p


def _bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


@dataclass
class GenerationResult:
    tokens: List[int]
    prompt_len: int
    steps: int
    finished: bool
    latency_s: float = 0.0
    # time-to-first-token, measured at the first host sync that revealed a
    # token (None on paths that don't time it, e.g. format_generation's
    # synthetic results) — lets the sync service report real TTFT
    first_token_s: Optional[float] = None


class GenerationEngine:
    """Single-host serving engine for one model asset."""

    def __init__(self, model: Model, params, *, max_batch: int = 8,
                 max_seq: int = 512, eos_id: Optional[int] = None,
                 decode_chunk: int = 8, paged: bool = False,
                 page_size: int = 16, kv_pool_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 prefix_cache_pages: Optional[int] = None,
                 extra_inputs: Optional[Dict[str, Any]] = None):
        self.model = model
        self.params = params
        self.cfg = model.cfg
        # device the engine's state is committed to (None: JAX's default)
        self._device = None
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_id = eos_id
        # fused decode steps per host sync (compile-stable; per-slot budgets
        # stop individual sequences mid-chunk). Floored to a power of two
        # up front: the scheduler's budget alignment only ever uses pow2
        # lengths, so accepting e.g. 12 verbatim would silently run 8
        self.decode_chunk = 1 << (max(1, int(decode_chunk)).bit_length() - 1)
        # static per-request extra inputs (e.g. image embeds builder)
        self.extra_inputs = extra_inputs or {}
        # host-side summary of the most recent admission (prompt tokens,
        # prefix-cache hit tokens, pages allocated, COW) — read by the
        # scheduler's tracer immediately after insert_request
        self.last_admission: Optional[Dict[str, Any]] = None
        # admissions, and their host wall time (the prefill_prep and
        # prefill_dispatch spans): serial with every first token
        self.inserts = 0
        self.insert_host_s = 0.0

        # Ring-cache families (sliding-window / hybrid local attention / SSM
        # state) left-pad prompts and wrap or accumulate their caches —
        # they keep the linear layout. A sliding window >= max_seq never
        # wraps, so such engines are plain linear caches (no bucket
        # padding charged, pageable). Paged KV applies to linear attention
        # caches only; asking for it elsewhere falls back silently (linear
        # stays the default for ring families).
        self._ring = (self.cfg.family in ("hybrid", "ssm")
                      or (self.cfg.sliding_window is not None
                          and self.cfg.sliding_window < max_seq))
        pageable = not self._ring and self.cfg.family != "audio"
        self.paged = bool(paged) and pageable
        if self.paged:
            if max_seq % page_size:
                raise ValueError(
                    f"page_size {page_size} must divide max_seq {max_seq}")
            self.page_size = page_size
            self._pages_per_slot = max_seq // page_size
            # default pool = same capacity as the contiguous layout; the
            # win is that admission and occupancy are charged per page in
            # use, and a smaller pool (oversubscription) is a valid config
            self.kv_pool_blocks = int(kv_pool_blocks) if kv_pool_blocks \
                else max_batch * self._pages_per_slot
            self._free_pool: List[int] = list(range(self.kv_pool_blocks))
            self._slot_blocks: List[List[int]] = [[] for _ in range(max_batch)]
            # host mirror of the device block table (sentinel = pool size)
            self._table = np.full((max_batch, self._pages_per_slot),
                                  self.kv_pool_blocks, np.int32)
            self._cache = model.init_cache(
                max_batch, max_seq, paged=(self.kv_pool_blocks, page_size))
            self._insert = jax.jit(self._insert_paged_impl,
                                   donate_argnums=(0,))
        else:
            self.page_size = 0
            self.kv_pool_blocks = 0
            self._free_pool = []
            self._slot_blocks = [[] for _ in range(max_batch)]
            self._cache = model.init_cache(max_batch, max_seq)
            self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
        # prefix caching rides the paged layout (block tables are what make
        # cross-slot page sharing possible); asking for it elsewhere falls
        # back silently, like paged itself on ring families
        self.prefix_cache: Optional[PrefixCache] = None
        if self.paged and prefix_cache:
            self.prefix_cache = PrefixCache(
                self.page_size, max_unreferenced=prefix_cache_pages)
            # block-table references per pool page (1 = uniquely owned,
            # >1 = shared; shared or cache-registered pages are read-only)
            self._page_refs = np.zeros((self.kv_pool_blocks,), np.int32)
            # slots whose KV is keyed purely by token-ids — requests with
            # extra inputs (image embeds…) bypass the cache entirely
            self._slot_cacheable = [False] * max_batch
            self._fill_jit: Dict[int, Any] = {}
            self._copy_page = jax.jit(self._copy_page_impl,
                                      donate_argnums=(0,))
        self._lengths = np.zeros((max_batch,), np.int32)
        self._active = np.zeros((max_batch,), bool)
        # logical vs physical prompt accounting: ring families pad prompts
        # to their bucket and treat pads as context, so _lengths (physical,
        # cache bookkeeping) may exceed the user's prompt. Usage and stats
        # report the logical numbers.
        self._prompt_lens = np.zeros((max_batch,), np.int32)   # logical
        self._prefill_lens = np.zeros((max_batch,), np.int32)  # physical
        # device-resident next input token per slot (sync-free admission:
        # insert_request writes it with an on-device argmax, step_chunk
        # carries it forward — the host never has to know it)
        self._next_tok = jnp.zeros((max_batch,), jnp.int32)

        self._kv_bytes_per_token = self._bytes_per_token(self._cache)
        self._prefill_jit: Dict[int, Any] = {}
        self._decode = jax.jit(self._decode_impl)
        # one compiled scan per chunk length actually used (lazy, bounded
        # by decode_chunk): the scheduler aligns chunks to the earliest
        # completion, so short lengths recur and long ones amortize
        self._chunk_jit: Dict[int, Any] = {}
        self._first_tok = jax.jit(self._first_tok_impl)

    @staticmethod
    def _bytes_per_token(cache) -> int:
        """Device bytes one token of context costs across all layers (the
        unit for KV-memory accounting; 0 for constant-state SSM caches)."""
        if "k_pool" in cache:
            kp = cache["k_pool"]                   # [L, N, P, KV, hd]
            per_entry = int(np.prod(kp.shape[3:])) * kp.dtype.itemsize
            return 2 * kp.shape[0] * per_entry
        for key in ("k", "attn_k"):                # [L|nb, B, S, KV, hd]
            if key in cache:
                k = cache[key]
                per_entry = int(np.prod(k.shape[3:])) * k.dtype.itemsize
                return 2 * k.shape[0] * per_entry
        return 0

    def place(self, device) -> None:
        """Commit params, KV cache and the next-token buffer to ``device``.
        Jitted programs run where their committed operands live, so every
        later prefill/decode of this engine runs on ``device``; ``reset``
        re-commits the state it rebuilds."""
        self._device = device
        self.params = jax.device_put(self.params, device)
        self._cache = jax.device_put(self._cache, device)
        self._next_tok = jax.device_put(self._next_tok, device)

    # -- jitted internals ---------------------------------------------------

    def _prefill_impl(self, params, batch):
        return self.model.prefill(params, batch, cache_len=self.max_seq)

    def _insert_impl(self, batch_cache, one_cache, slot):
        """Copy a B=1 cache into slot ``slot`` of the batch cache.

        The batch axis of each leaf is located structurally: the first axis
        where the source is 1 and the destination is ``max_batch``. (Leading
        layer-stack dims match between src and dst, so they never trigger.)
        """
        def put(dst, src):
            if dst.ndim == 1:                       # lengths [B]
                return dst.at[slot].set(src[0])
            for ax in range(dst.ndim):
                if src.shape[ax] == 1 and dst.shape[ax] == self.max_batch:
                    idx = (slice(None),) * ax + (slot,)
                    return dst.at[idx].set(jnp.squeeze(src, ax))
            return dst
        return jax.tree.map(put, batch_cache, one_cache)

    def _insert_paged_impl(self, batch_cache, one_cache, table_row, slot):
        """Scatter a B=1 linear prefill cache into the slot's pool pages.

        ``table_row`` [pages_per_slot] holds the slot's pool page ids
        (sentinel ``kv_pool_blocks`` for pages past the prompt — their
        scatters drop). The prefill cache is always ``max_seq`` long, so it
        reshapes exactly into pages_per_slot pages.
        """
        nb, P = self._pages_per_slot, self.page_size

        def put_pool(pool, src):
            pages = jnp.squeeze(src, 1).reshape(
                src.shape[0], nb, P, *src.shape[3:])
            return pool.at[:, table_row].set(pages.astype(pool.dtype),
                                             mode="drop")

        cache = dict(batch_cache)
        cache["k_pool"] = put_pool(batch_cache["k_pool"], one_cache["k"])
        cache["v_pool"] = put_pool(batch_cache["v_pool"], one_cache["v"])
        cache["lengths"] = batch_cache["lengths"].at[slot].set(
            one_cache["lengths"][0])
        cache["block_table"] = batch_cache["block_table"].at[slot].set(
            table_row)
        return cache

    # -- paged pool management (host side; device work stays sync-free) -----

    def _alloc_blocks(self, slot: int, n: int) -> bool:
        """Move ``n`` pool pages to ``slot`` (all-or-nothing). With a
        prefix cache attached, unreferenced cached pages are LRU-evicted
        into the free list first — retained cache never shrinks the pool
        capacity admission can claim."""
        if self.prefix_cache is not None:
            while len(self._free_pool) < n:
                page = self.prefix_cache.pop_evictable()
                if page is None:
                    break
                self._free_pool.append(page)
        if len(self._free_pool) < n:
            return False
        start = len(self._slot_blocks[slot])
        for i in range(n):
            blk = self._free_pool.pop()
            self._slot_blocks[slot].append(blk)
            self._table[slot, start + i] = blk
            if self.prefix_cache is not None:
                self._page_refs[blk] = 1
        return True

    def _take_free_page(self) -> Optional[int]:
        """One pool page for a copy-on-write target (evicting from the
        prefix cache if the free list is dry); None when truly exhausted."""
        if not self._free_pool and self.prefix_cache is not None:
            page = self.prefix_cache.pop_evictable()
            if page is not None:
                self._free_pool.append(page)
        if not self._free_pool:
            return None
        blk = self._free_pool.pop()
        self._page_refs[blk] = 1
        return blk

    def _decref(self, blk: int):
        """Drop one block-table reference to ``blk``. The last reference
        frees the page — unless it is cache-registered, where it parks as
        an LRU eviction candidate instead (cap overflow evicts to free)."""
        self._page_refs[blk] -= 1
        assert self._page_refs[blk] >= 0, f"page {blk} refcount underflow"
        if self._page_refs[blk] == 0:
            if self.prefix_cache.contains_page(blk):
                self._free_pool.extend(
                    self.prefix_cache.release_page(blk))
            else:
                self._free_pool.append(blk)

    def _page_writable(self, blk: int) -> bool:
        """A page may take KV writes only while it is uniquely owned and
        not content-addressed: a shared page backs other slots' context,
        and a registered page backs the cache's hash -> content promise."""
        return (self._page_refs[blk] == 1
                and not self.prefix_cache.contains_page(blk))

    def _make_writable(self, slot: int, pos: int) -> bool:
        """Copy-on-write guard for the page holding position ``pos`` of
        ``slot``: shared / cache-registered pages are read-only, so the
        first write into one copies its content into a fresh page, repoints
        the slot's table entry, and drops the shared reference. Returns
        False when no page can be obtained for the copy (pool exhausted —
        the caller retires the slot cleanly)."""
        pi = pos // self.page_size
        if pi >= len(self._slot_blocks[slot]):
            return True                     # next write page not allocated yet
        blk = self._slot_blocks[slot][pi]
        if self._page_writable(blk):
            return True
        fresh = self._take_free_page()
        if fresh is None:
            return False
        self._cache = self._copy_page(
            self._cache, jnp.asarray(blk, jnp.int32),
            jnp.asarray(fresh, jnp.int32))
        self._slot_blocks[slot][pi] = fresh
        self._table[slot, pi] = fresh
        self._push_table_row(slot)
        self._decref(blk)
        self.prefix_cache.cow_copies += 1
        return True

    def _copy_page_impl(self, cache, src, dst):
        """Device-side pool page copy (all layers, k and v) — an async
        dispatch like every other cache op, never a host sync."""
        cache = dict(cache)
        cache["k_pool"] = cache["k_pool"].at[:, dst].set(
            cache["k_pool"][:, src])
        cache["v_pool"] = cache["v_pool"].at[:, dst].set(
            cache["v_pool"][:, src])
        return cache

    def _push_table_row(self, slot: int):
        """Mirror the slot's host table row to the device cache (a tiny
        async host->device transfer — never a sync)."""
        self._cache["block_table"] = self._cache["block_table"].at[slot].set(
            jnp.asarray(self._table[slot]))

    def free_blocks(self) -> int:
        """Unallocated pool pages (0 for contiguous engines)."""
        return len(self._free_pool)

    def available_blocks(self) -> int:
        """Pool pages admission may claim: the free list plus every
        unreferenced cached page the allocator could evict."""
        avail = len(self._free_pool)
        if self.prefix_cache is not None:
            avail += self.prefix_cache.evictable()
        return avail

    def blocks_in_use(self) -> int:
        """Pages referenced by live slots — shared pages count ONCE, and
        cache-retained (unreferenced) pages are not live context."""
        used = self.kv_pool_blocks - len(self._free_pool)
        if self.prefix_cache is not None:
            used -= self.prefix_cache.evictable()
        return used

    def _prompt_page_plan(self, prompt: List[int]
                          ) -> Tuple[int, List[int], int]:
        """(total pages the seated prompt references, cached pages backing
        its longest hashed prefix, extra pages copy-on-write will draw).
        The COW page appears exactly when the *whole* prompt is cached:
        the last prompt token must be replayed for its logits, and its KV
        write targets the final shared page."""
        n = len(prompt)
        total = -(-(n + 1) // self.page_size)
        hits = self.prefix_cache.match(prompt, peek=True)
        cow = 1 if len(hits) * self.page_size >= n else 0
        return total, hits, cow

    def blocks_for_prompt(self, prompt) -> int:
        """Pool pages admission must see claimable before taking this
        prompt: its prefill pages plus room for the first decode write.
        Accepts a token list (a prefix-cached engine then charges only the
        pages the cache cannot seat) or a bare length (full charge — used
        for worst-case bounds and requests with extra inputs, which bypass
        the cache)."""
        if isinstance(prompt, (int, np.integer)):
            n, toks = int(prompt), None
        else:
            toks = list(prompt)
            n = len(toks)
        true_len = _bucket(n) if self._ring else n
        total = -(-(true_len + 1) // self.page_size)
        if toks is None or self.prefix_cache is None:
            return total
        _, hits, cow = self._prompt_page_plan(toks)
        return total - len(hits) + cow

    def can_admit(self, prompt) -> bool:
        """Block-aware admission gate: beyond :meth:`fits_prompt`, a paged
        engine also needs enough claimable pool pages for the prompt.
        Like :meth:`blocks_for_prompt`, accepts a token list or a length;
        with a token list a prefix-cached engine charges only non-cached
        pages — but never counts the prompt's own prospective hits as
        evictable headroom."""
        if isinstance(prompt, (int, np.integer)):
            n, toks = int(prompt), None
        else:
            toks = list(prompt)
            n = len(toks)
        if not self.fits_prompt(n):
            return False
        if not self.paged:
            return True
        if toks is None or self.prefix_cache is None:
            return self.available_blocks() >= self.blocks_for_prompt(n)
        total, hits, cow = self._prompt_page_plan(toks)
        avail = (len(self._free_pool)
                 + self.prefix_cache.evictable_excluding(hits))
        return avail >= total - len(hits) + cow

    def ensure_capacity(self, slot: int, want: int) -> int:
        """Secure write headroom for up to ``want`` more KV entries on
        ``slot``, allocating pool pages as needed and available. Returns
        the writes actually available — may be < ``want`` when the pool is
        tight, 0 when the slot cannot take a single further write (the
        caller retires it). Contiguous engines just report the remaining
        ``max_seq`` headroom. Idempotent and allocation-only (pages free on
        retire, never mid-flight)."""
        length = int(self._lengths[slot])
        phys = self.max_seq - length
        if not self.paged:
            return max(0, min(want, phys))
        want = min(want, phys)
        have = len(self._slot_blocks[slot]) * self.page_size - length
        dirty = False
        while have < want and self.available_blocks() \
                and len(self._slot_blocks[slot]) < self._pages_per_slot:
            self._alloc_blocks(slot, 1)
            have += self.page_size
            dirty = True
        if dirty:
            self._push_table_row(slot)
        if self.prefix_cache is not None and want > 0 and have > 0:
            # read-only page invariant: the next KV write lands at
            # ``length`` — if that position sits in a shared or cache-
            # registered page, copy-on-write it now (steady-state this
            # never fires: insert COWs the one replay write, and decode
            # writes land past every shared page — but direct step()
            # drivers and the property harness exercise it)
            if not self._make_writable(slot, length):
                return 0
        return max(0, min(want, have))

    def _first_tok_impl(self, logits, next_tok, slot):
        """First generated token from prefill logits (greedy over the
        logical vocab), written into the device next-token buffer."""
        masked = mask_padded_vocab(logits[0], self.cfg.vocab_size)
        first = jnp.argmax(masked).astype(jnp.int32)
        return first, next_tok.at[slot].set(first)

    def _sample(self, logits, rng, temperature):
        """Per-slot-temperature sampling: rows at 0 take the greedy argmax."""
        masked = mask_padded_vocab(logits, self.cfg.vocab_size)
        greedy = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        scaled = masked / jnp.maximum(temperature, 1e-6)[:, None]
        sampled = jax.random.categorical(rng, scaled, axis=-1) \
            .astype(jnp.int32)
        return jnp.where(temperature > 0, sampled, greedy)

    def _decode_impl(self, params, cache, tokens, rng, temperature, active):
        """One decode step; ``temperature`` is a per-slot [max_batch]
        vector so mixed-temperature batches don't interfere — each row
        samples at its own temperature. The fixed vector shape keeps the
        step compile-stable. ``active`` gates both sampling output and the
        per-slot cache-length advance (a slot at ``max_seq`` capacity must
        not write past its cache)."""
        logits, cache = self.model.decode_step(params, cache, tokens,
                                               active=active)
        nxt = self._sample(logits, rng, temperature)
        nxt = jnp.where(active, nxt, 0)
        return nxt, cache

    def _runnable(self, tok, left, lengths, run):
        """Per-slot continuation mask: a slot keeps decoding while it has
        token budget, cache capacity for the next KV write, and its input
        token is not EOS."""
        run = run & (left > 0) & (lengths < self.max_seq)
        if self.eos_id is not None:
            run = run & (tok != self.eos_id)
        return run

    def _unpage(self, cache):
        """Gather the block-table view into a contiguous linear cache
        (``[L, B, S, KV, hd]``). Sentinel table entries clamp to an
        arbitrary page whose data sits past the owner's length — masked."""
        bt = jnp.clip(cache["block_table"], 0, self.kv_pool_blocks - 1)

        def gather(pool):
            g = pool[:, bt]                       # [L, B, nb, P, KV, hd]
            return g.reshape(g.shape[0], g.shape[1], -1, *g.shape[4:])

        return {"lengths": cache["lengths"],
                "k": gather(cache["k_pool"]), "v": gather(cache["v_pool"])}

    def _repage(self, cache, work):
        """Scatter a chunk's updated contiguous view back into the pool.
        Unallocated (sentinel) pages scatter out of bounds and drop, so
        writes past a slot's allocation never touch foreign pages."""
        table = cache["block_table"]
        nb = table.shape[1]

        def scatter(pool, kc):
            pages = kc.reshape(kc.shape[0], kc.shape[1], nb, self.page_size,
                               *kc.shape[3:])
            return pool.at[:, table].set(pages.astype(pool.dtype),
                                         mode="drop")

        return dict(cache,
                    k_pool=scatter(cache["k_pool"], work["k"]),
                    v_pool=scatter(cache["v_pool"], work["v"]),
                    lengths=work["lengths"])

    def _chunk_impl(self, k, params, cache, next_tok, rng, temperature,
                    budgets, active):
        """Fused multi-step decode: ``lax.scan`` over ``k`` steps with
        on-device sampling and termination.

        Per step, slots whose mask is off keep their input token and do not
        advance their cache length; the step's KV/state writes for them land
        past their valid length (invisible) and are overwritten on the next
        insert. Returns (cache, next_tok, tokens [B, K], emitted [B, K])
        where ``emitted[b]`` is a contiguous prefix mask — once a slot
        terminates it never resumes within the chunk.

        Paged caches on the ORACLE backend are translated at the CHUNK
        boundary: the block table is fixed across a chunk (the scheduler
        secures every page before dispatch), so the pages gather into a
        contiguous working view once, the whole chunk runs on the linear
        fast path, and the touched pages scatter back once —
        layout-translation cost amortizes over the chunk exactly like the
        host sync does. On the Pallas backends no translation happens at
        all: each step runs the block-table decode kernel against the pool
        in place. (The backend is baked in at trace time like every other
        kernel dispatch; engines are built per backend.)

        RNG parity contract (property-tested): step ``i`` uses ``sub_i``
        from the chain ``rng_i, sub_i = split(rng_{i-1})`` — identical to
        driving ``decode_chunk`` single ``step()`` calls with the same
        chain, so fused and stepwise decode are token-identical.
        """
        from repro.kernels import ops as _kops
        translate = "k_pool" in cache and _kops.get_backend() == "ref"
        work = self._unpage(cache) if translate else cache

        def body(carry, _):
            work, tok, rng, run, left = carry
            rng, sub = jax.random.split(rng)
            logits, work = self.model.decode_step(params, work, tok,
                                                  active=run)
            nxt = self._sample(logits, sub, temperature)
            # dead slots hold their token: keeps the carry stable and the
            # (batch-coupled, e.g. MoE-capacity) compute deterministic
            nxt = jnp.where(run, nxt, tok)
            left = left - run.astype(jnp.int32)
            run_next = self._runnable(nxt, left, work["lengths"], run)
            return (work, nxt, rng, run_next, left), (nxt, run)

        run0 = self._runnable(next_tok, budgets, work["lengths"], active)
        (work, tok, _, _, _), (toks, emitted) = jax.lax.scan(
            body, (work, next_tok, rng, run0, budgets), None, length=k)
        cache = self._repage(cache, work) if translate else work
        return (cache, tok,
                jnp.swapaxes(toks, 0, 1), jnp.swapaxes(emitted, 0, 1))

    def _fill_impl(self, k, params, cache, tokens, count, start, slot,
                   next_tok):
        """Force-feed ``count`` prompt tokens into ``slot`` as one fused
        scan of ``k`` (>= count, compile-stable pow2) decode steps starting
        at position ``start`` — the prefix-cache tail path. Each step
        writes one KV entry exactly like regular decode (so the tail's
        pages end up byte-identical to decode-produced ones), and the
        final fed token's logits yield the first generated token, written
        into the device next-token buffer (sync-free admission, same
        contract as ``_first_tok``).

        Other slots run masked (inactive): their lengths hold and their
        KV writes land past their valid length, the same invisible-write
        convention the chunk path uses. On the ORACLE backend the paged
        cache translates at the fill boundary exactly like ``_chunk_impl``
        — the block table is fixed across the fill (every page was secured
        before dispatch), and shared read-only pages scatter back the very
        bytes they gathered (the linear steps only write at this slot's
        positions), so the round-trip never mutates them.
        """
        from repro.kernels import ops as _kops
        translate = "k_pool" in cache and _kops.get_backend() == "ref"
        cache = dict(cache)
        cache["lengths"] = cache["lengths"].at[slot].set(start)
        work = self._unpage(cache) if translate else cache
        mine = jnp.arange(self.max_batch) == slot

        def body(carry, tok):
            work, i = carry
            active = mine & (i < count)
            tok_vec = jnp.where(mine, tok, 0).astype(jnp.int32)
            logits, work = self.model.decode_step(params, work, tok_vec,
                                                  active=active)
            return (work, i + 1), logits[slot]

        (work, _), logit_seq = jax.lax.scan(
            body, (work, jnp.int32(0)), tokens, length=k)
        cache = self._repage(cache, work) if translate else work
        masked = mask_padded_vocab(logit_seq[count - 1], self.cfg.vocab_size)
        first = jnp.argmax(masked).astype(jnp.int32)
        return cache, next_tok.at[slot].set(first), first

    def _fill(self, tail: List[int], start: int, slot: int) -> jax.Array:
        """Dispatch the fused tail fill; returns the first-token scalar."""
        k = _bucket(len(tail), minimum=1)
        if k not in self._fill_jit:
            self._fill_jit[k] = jax.jit(
                _named("prefix_fill", self._fill_impl, k),
                donate_argnums=(1,))
        padded = np.zeros((k,), np.int32)
        padded[:len(tail)] = tail
        self._cache, self._next_tok, first = self._fill_jit[k](
            self.params, self._cache, jnp.asarray(padded),
            jnp.asarray(len(tail), jnp.int32),
            jnp.asarray(start, jnp.int32),
            jnp.asarray(slot, jnp.int32), self._next_tok)
        return first

    # -- public API ------------------------------------------------------------

    def fits_prompt(self, n: int) -> bool:
        """Whether an ``n``-token prompt is admissible: its padding bucket
        must not exceed ``max_seq`` AND its *physical* prefill length
        (the bucket itself for ring families, which treat pads as context)
        must leave at least one KV write of generation headroom. A prompt
        that fills the cache would burn a prefill + slot only to retire
        with nothing generated beyond the prefill token — callers reject
        it at validation time (``PROMPT_TOO_LONG``) instead."""
        bucket = _bucket(n)
        if bucket > self.max_seq:
            return False
        true_len = bucket if self._ring else n
        return true_len < self.max_seq

    def max_prompt_len(self) -> int:
        """Longest admissible prompt in tokens — consistent with
        :meth:`fits_prompt` by construction, so a caller that truncates to
        this length is never rejected. Ring families are bounded by the
        padding bucket (largest bucket strictly below ``max_seq``); linear
        engines by ``max_seq - 1`` — unless ``max_seq`` is not a bucket
        size itself, where the bound drops to the largest bucket that
        still fits (e.g. max_seq=100 admits at most 64: a 99-token prompt
        would pad to a 128 bucket)."""
        if not self._ring:
            n = self.max_seq - 1
            if n > 0 and _bucket(n) <= self.max_seq:
                return n
        b = 16                       # _bucket's minimum
        if b > self.max_seq or (self._ring and b >= self.max_seq):
            return 0
        limit = self.max_seq - 1 if self._ring else self.max_seq
        while b * 2 <= limit:
            b *= 2
        return b

    def free_slots(self) -> List[int]:
        return [i for i in range(self.max_batch) if not self._active[i]]

    def context_len(self, slot: int) -> int:
        """Physical cache length of ``slot`` (cache bookkeeping: includes
        ring-family padding)."""
        return int(self._lengths[slot])

    def logical_len(self, slot: int) -> int:
        """User-visible context of ``slot``: prompt tokens as submitted
        plus generated tokens — ring-family padding is not billed."""
        return int(self._prompt_lens[slot]
                   + (self._lengths[slot] - self._prefill_lens[slot]))

    def active_logical_tokens(self) -> int:
        gen = self._lengths - self._prefill_lens
        return int(((self._prompt_lens + gen) * self._active).sum())

    def capacity_left(self, slot: int) -> int:
        """KV writes remaining before ``slot`` cannot decode another token.
        Pool-aware on paged engines: bounded by ``max_seq`` AND by the
        slot's allocated pages plus what the shared pool could still
        provide."""
        left = int(self.max_seq - self._lengths[slot])
        if self.paged:
            have = (len(self._slot_blocks[slot]) * self.page_size
                    - int(self._lengths[slot]))
            left = min(left, have + self.available_blocks() * self.page_size)
        return max(0, left)

    def kv_stats(self) -> Dict[str, Any]:
        """KV memory accounting. A contiguous cache charges the full
        ``max_seq`` per occupied slot (memory scales with *capacity*); a
        paged cache charges the pool pages actually allocated (memory
        scales with *actual context*). ``active_tokens`` is the logical
        context — ring-family padding is not billed as context."""
        bpt = self._kv_bytes_per_token
        active = int(self._active.sum())
        logical = self.active_logical_tokens()
        if self.paged:
            used = self.blocks_in_use()
            in_use = used * self.page_size * bpt
            out: Dict[str, Any] = {
                "paged": True, "page_size": self.page_size,
                "pool_blocks": self.kv_pool_blocks,
                "blocks_in_use": used,
                "free_blocks": len(self._free_pool),
            }
            if self.prefix_cache is not None:
                # cache-retained pages are claimable, not live context
                out["cached_blocks"] = self.prefix_cache.evictable()
                out["prefix_cache"] = self.prefix_stats()
        else:
            in_use = active * self.max_seq * bpt
            out = {"paged": False}
        out.update(
            active_slots=active,
            active_tokens=logical,
            kv_bytes_per_token=bpt,
            kv_bytes_in_use=int(in_use),
            kv_bytes_per_active_token=(round(in_use / logical, 1)
                                       if logical else 0.0),
        )
        return out

    def prefix_stats(self) -> Optional[Dict[str, int]]:
        """Prefix-cache counters plus the instantaneous shared-page count
        (pages referenced by more than one block table); None when prefix
        caching is off."""
        if self.prefix_cache is None:
            return None
        s = self.prefix_cache.stats()
        s["shared_pages"] = int((self._page_refs > 1).sum())
        return s

    def check_pool_invariants(self, *, device: bool = True):
        """Audit the page-allocator partition (test hook; ``device=True``
        also syncs the device block table against the host mirror).

        Every pool page must be exactly one of:
        - free (on the free list, unreferenced, not cached),
        - live (referenced by >= 1 block tables, refcount == the number of
          table references; uniquely owned when 1, shared when > 1),
        - cache-retained (registered, zero references, parked in the LRU).

        In particular a freed page can never still be referenced from any
        table — the no-use-after-free half of the COW/refcount contract.
        """
        assert self.paged, "invariant audit is for paged engines"
        refs: Dict[int, int] = {}
        for s in range(self.max_batch):
            blocks = self._slot_blocks[s]
            for i, pg in enumerate(blocks):
                assert 0 <= pg < self.kv_pool_blocks, (s, i, pg)
                assert self._table[s, i] == pg, \
                    f"host table desync at slot {s} page {i}"
                refs[pg] = refs.get(pg, 0) + 1
            assert (self._table[s, len(blocks):]
                    == self.kv_pool_blocks).all(), \
                f"slot {s} table not sentinel past its allocation"
        free = set(self._free_pool)
        assert len(free) == len(self._free_pool), "double-freed page"
        assert not free & set(refs), \
            f"freed pages still referenced: {sorted(free & set(refs))}"
        if self.prefix_cache is not None:
            for pg in range(self.kv_pool_blocks):
                assert int(self._page_refs[pg]) == refs.get(pg, 0), \
                    (f"page {pg} refcount {int(self._page_refs[pg])} != "
                     f"{refs.get(pg, 0)} table references")
            lru = set(self.prefix_cache.unreferenced_pages())
            cached = set(self.prefix_cache.cached_pages())
            assert lru <= cached
            assert not lru & free and not lru & set(refs)
            # a registered page with no references must be evictable
            assert cached - set(refs) == lru, \
                "unreferenced cached page missing from the LRU"
            covered = free | set(refs) | lru
        else:
            covered = free | set(refs)
        assert covered == set(range(self.kv_pool_blocks)), \
            f"leaked pages: {sorted(set(range(self.kv_pool_blocks)) - covered)}"
        if device:
            dev = np.asarray(self._cache["block_table"])
            assert (dev == self._table).all(), "device table desync"

    def insert_request(self, prompt: List[int], slot: int,
                       extra: Optional[Dict[str, Any]] = None) -> jax.Array:
        """Prefill ``prompt`` into ``slot``; returns the first generated
        token as an *unforced* device scalar (greedy argmax over the prefill
        logits, computed on device). Callers defer the host read to their
        next sync point — admission never stalls the decode loop.

        Two spans cover it: ``max.engine.prefill_prep`` (padding, pages,
        the prompt's copy to the device) and ``max.engine.prefill_dispatch``
        (the enqueue of the prefill, insert and first-token programs);
        ``inserts`` / ``insert_host_s`` count them."""
        assert not self._active[slot], f"slot {slot} busy"
        if _bucket(len(prompt)) > self.max_seq:
            raise ValueError(
                f"prompt {len(prompt)} exceeds max_seq {self.max_seq}")
        t0 = _now()
        # prefix-cached admission applies only to requests whose KV is a
        # pure function of the token ids: anything carrying extra inputs
        # (image embeds, audio frames) takes the plain paged path and its
        # pages are never registered
        if (self.prefix_cache is not None and not extra
                and not self.extra_inputs):
            first = self._insert_cached(list(prompt), slot)
        else:
            first = self._insert_plain(prompt, slot, extra)
        self.inserts += 1
        self.insert_host_s += _now() - t0
        return first

    def _insert_plain(self, prompt: List[int], slot: int,
                      extra: Optional[Dict[str, Any]]) -> jax.Array:
        """Bucketed B=1 prefill, then the insert into the batch cache."""
        with _span("max.engine.prefill_prep"):
            bucket = _bucket(len(prompt))
            if self.prefix_cache is not None:
                self._slot_cacheable[slot] = False
            if bucket not in self._prefill_jit:
                self._prefill_jit[bucket] = jax.jit(self._prefill_impl)
            # Ring-cache families (sliding-window / hybrid local attention)
            # need contiguous positions, so their prompts are LEFT-padded
            # and pads are treated as context. Linear caches RIGHT-pad;
            # causal masking keeps pads out of real-token attention and
            # decode masks by true length. (SSM states are cumulative too,
            # so stateful families all left-pad.)
            ring = self._ring
            padded = np.zeros((1, bucket), np.int32)
            if ring:
                padded[0, bucket - len(prompt):] = prompt
                true_len = bucket
            else:
                padded[0, :len(prompt)] = prompt
                true_len = len(prompt)
            batch = {"tokens": jnp.asarray(padded),
                     "prompt_lengths": jnp.asarray([true_len], np.int32)}
            for k, v in (extra or self.extra_inputs).items():
                batch[k] = v
            if self.paged:
                # allocate the prefill's pages — plus the page the FIRST
                # decode write lands in, so a fresh admission can never be
                # starved by co-tenants before its first chunk — BEFORE
                # dispatching compute; the scheduler gates admission on
                # can_admit so this only trips for direct callers
                # outrunning the pool. blocks_for_prompt is the ONE
                # statement of this reservation rule: the admission gate
                # and the allocator must never diverge
                need = self.blocks_for_prompt(len(prompt))
                if not self._alloc_blocks(slot, need):
                    raise RuntimeError(
                        f"KV pool exhausted: prompt needs {need} pages, "
                        f"{len(self._free_pool)} of {self.kv_pool_blocks} "
                        "free")
            # host mirrors flip BEFORE the (possibly compiling) prefill
            # dispatch: stats readers on other threads must never observe
            # allocated pages without an owner
            self._lengths[slot] = true_len
            self._prompt_lens[slot] = len(prompt)
            self._prefill_lens[slot] = true_len
            self._active[slot] = True
        with _span("max.engine.prefill_dispatch"):
            try:
                logits, one_cache = self._prefill_jit[bucket](self.params,
                                                              batch)
                if self.paged:
                    self._cache = self._insert(
                        self._cache, one_cache,
                        jnp.asarray(self._table[slot]),
                        jnp.asarray(slot, jnp.int32))
                else:
                    self._cache = self._insert(self._cache, one_cache,
                                               jnp.asarray(slot, jnp.int32))
                first, self._next_tok = self._first_tok(
                    logits, self._next_tok, jnp.asarray(slot, jnp.int32))
            except Exception:
                self.release_slot(slot)   # no orphaned slot or leaked pages
                raise
        # host-side admission summary for observability (the scheduler's
        # tracer reads it right after insert — never a device value)
        self.last_admission = {
            "prompt_tokens": len(prompt), "cached_hit_tokens": 0,
            "pages_allocated": need if self.paged else 0, "cow": False}
        return first

    def _insert_cached(self, prompt: List[int], slot: int) -> jax.Array:
        """Prefix-cached admission. The prompt splits at page boundaries:

        - ``[0, hit_len)`` — the longest cached prefix: those pool pages
          are installed into the slot's block table with a refcount bump
          and NO compute (the prefill the cache absorbed);
        - ``[hit_len, n)`` — the miss region: on a cold miss the aligned
          part comes from the regular bucketed prefill, then the tail (a
          partial-hit miss region decode-fills entirely — prefill cannot
          start mid-sequence) is force-fed through the fused decode scan
          (:meth:`_fill`), which also yields the first generated token.

        Cold and warm admissions share the fill path for everything past
        the prefix boundary, so a warm replay of a seen prompt is token-
        identical to its cold run by construction (property-tested). When
        the WHOLE prompt is cached (page-aligned), the last prompt token
        is replayed for its logits; its KV write targets the final shared
        page, which copy-on-writes first — cached bytes never mutate.
        Freshly computed full prompt pages register immediately, so
        co-batched duplicates admitted later the same tick already hit.
        """
        with _span("max.engine.prefill_prep"):
            n = len(prompt)
            P = self.page_size
            cache = self.prefix_cache
            total = -(-(n + 1) // P)      # prompt pages + first decode write
            hits = cache.match(prompt)
            hit_len = len(hits) * P
            assert not self._slot_blocks[slot], f"slot {slot} holds pages"
            for i, pg in enumerate(hits):
                self._slot_blocks[slot].append(pg)
                self._table[slot, i] = pg
                self._page_refs[pg] += 1
                cache.ref_page(pg)
            if not self._alloc_blocks(slot, total - len(hits)):
                self.release_slot(slot)   # drop the shared refs taken above
                raise RuntimeError(
                    f"KV pool exhausted: prompt needs {total - len(hits)} "
                    f"new pages, {self.available_blocks()} of "
                    f"{self.kv_pool_blocks} claimable")
            self._push_table_row(slot)
            # host mirrors flip BEFORE the dispatches, same rule as the
            # plain path (paged prompts are linear: logical == physical)
            self._lengths[slot] = n
            self._prompt_lens[slot] = n
            self._prefill_lens[slot] = n
            self._active[slot] = True
            self._slot_cacheable[slot] = True
        with _span("max.engine.prefill_dispatch"):
            try:
                if hit_len >= n:          # full hit: replay the last token
                    start = n - 1
                    if not self._make_writable(slot, start):
                        raise RuntimeError(
                            "KV pool exhausted: no page for the replay "
                            "copy-on-write")
                elif not hits and n - 1 >= P:
                    # cold miss: aligned prefix through the regular prefill
                    start = ((n - 1) // P) * P
                    pb = _bucket(start)
                    if pb not in self._prefill_jit:
                        self._prefill_jit[pb] = jax.jit(self._prefill_impl)
                    padded = np.zeros((1, pb), np.int32)
                    padded[0, :start] = prompt[:start]
                    batch = {"tokens": jnp.asarray(padded),
                             "prompt_lengths": jnp.asarray([start],
                                                           np.int32)}
                    _, one_cache = self._prefill_jit[pb](self.params, batch)
                    self._cache = self._insert(
                        self._cache, one_cache,
                        jnp.asarray(self._table[slot]),
                        jnp.asarray(slot, jnp.int32))
                else:                     # partial hit (or tiny prompt)
                    start = hit_len
                first = self._fill(prompt[start:], start, slot)
                keys = cache.chain_keys(prompt)
                for i in range(len(hits), n // P):
                    cache.register(keys[i], self._slot_blocks[slot][i])
            except Exception:
                self.release_slot(slot)   # no orphaned slot or leaked pages
                raise
        # warm-vs-cold is distinguishable here: hit tokens were installed
        # by reference, only the remainder paid pages/compute
        self.last_admission = {
            "prompt_tokens": n, "cached_hit_tokens": min(hit_len, n),
            "pages_allocated": total - len(hits), "cow": hit_len >= n}
        return first

    def release_slot(self, slot: int, tokens: Optional[List[int]] = None):
        """Retire ``slot`` and return its KV pages.

        ``tokens`` (prompt + generated, as fed) lets a prefix-cached
        engine register the slot's fully-decoded pages before the
        references drop — multi-turn continuations then hit the whole
        previous exchange, not just the original prompt. Pages whose
        chain key is already cached (e.g. the shared prefix itself)
        simply skip. On the last reference, cache-registered pages park
        in the LRU free-candidate list; everything else frees."""
        self._active[slot] = False
        if not (self.paged and self._slot_blocks[slot]):
            return
        if self.prefix_cache is None:
            # free-on-retire: every page returns to the shared pool. The
            # sentinel row must reach the DEVICE table too: an inactive
            # slot still executes (masked) decode writes, and a stale row
            # would alias pages that now belong to another slot.
            self._free_pool.extend(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
            self._table[slot, :] = self.kv_pool_blocks
            self._push_table_row(slot)
            return
        if tokens is not None and self._slot_cacheable[slot]:
            # cache-eligible: pages fully covered by KV actually written
            # (positions [0, length)), keyed by the tokens that fed them
            full = min(int(self._lengths[slot]), len(tokens)) \
                // self.page_size
            keys = self.prefix_cache.chain_keys(
                tokens[:full * self.page_size])
            for i, key in enumerate(keys):
                self.prefix_cache.register(key, self._slot_blocks[slot][i])
        for pg in self._slot_blocks[slot]:
            self._decref(pg)
        self._slot_blocks[slot] = []
        self._slot_cacheable[slot] = False
        self._table[slot, :] = self.kv_pool_blocks
        self._push_table_row(slot)

    def reset(self):
        """Rebuild every piece of mutable serving state from scratch —
        the supervision layer's recovery hammer after repeated engine
        faults or a dead worker, when device caches, the page pool, and
        compiled programs are all suspect.

        Reconstructs the KV cache (and pool/table/refcounts on paged
        engines), clears the prefix cache, zeroes the host length/active
        mirrors, and drops every jitted callable so programs recompile
        clean. Weights (``params``) are immutable and survive. All active
        slots are abandoned: callers quarantine their requests first
        (``ContinuousBatchingScheduler.quarantine_active``); queued work
        never touched the engine and rides through untouched."""
        max_batch, max_seq = self.max_batch, self.max_seq
        if self.paged:
            self._free_pool = list(range(self.kv_pool_blocks))
            self._slot_blocks = [[] for _ in range(max_batch)]
            self._table = np.full((max_batch, self._pages_per_slot),
                                  self.kv_pool_blocks, np.int32)
            self._cache = self.model.init_cache(
                max_batch, max_seq,
                paged=(self.kv_pool_blocks, self.page_size))
            self._insert = jax.jit(self._insert_paged_impl,
                                   donate_argnums=(0,))
        else:
            self._free_pool = []
            self._slot_blocks = [[] for _ in range(max_batch)]
            self._cache = self.model.init_cache(max_batch, max_seq)
            self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
        if self.prefix_cache is not None:
            self.prefix_cache = PrefixCache(
                self.page_size,
                max_unreferenced=self.prefix_cache.max_unreferenced)
            self._page_refs = np.zeros((self.kv_pool_blocks,), np.int32)
            self._slot_cacheable = [False] * max_batch
            self._fill_jit = {}
            self._copy_page = jax.jit(self._copy_page_impl,
                                      donate_argnums=(0,))
        self._lengths = np.zeros((max_batch,), np.int32)
        self._active = np.zeros((max_batch,), bool)
        self._prompt_lens = np.zeros((max_batch,), np.int32)
        self._prefill_lens = np.zeros((max_batch,), np.int32)
        self._next_tok = jnp.zeros((max_batch,), jnp.int32)
        if self._device is not None:
            self._cache = jax.device_put(self._cache, self._device)
            self._next_tok = jax.device_put(self._next_tok, self._device)
        self._prefill_jit = {}
        self._decode = jax.jit(self._decode_impl)
        self._chunk_jit = {}
        self._first_tok = jax.jit(self._first_tok_impl)
        self.last_admission = None

    def step(self, tokens: np.ndarray, rng, temperature=0.0):
        """One decode step for the whole batch. tokens [max_batch] int32;
        ``temperature`` is a scalar (applied to every slot) or a per-slot
        [max_batch] vector. Slots whose cache is full (length == max_seq,
        or — paged — no page obtainable for the next write) are masked:
        they emit 0 and do not advance — lengths never grow past the
        writable cache."""
        writable = self._active & (self._lengths < self.max_seq)
        if self.paged:
            for i in np.flatnonzero(writable):
                if self.ensure_capacity(int(i), 1) < 1:
                    writable[i] = False
        active = jnp.asarray(writable)
        temps = np.broadcast_to(np.asarray(temperature, np.float32),
                                (self.max_batch,))
        nxt, self._cache = self._decode(
            self.params, self._cache, jnp.asarray(tokens, jnp.int32), rng,
            jnp.asarray(temps, F32), active)
        self._lengths[writable] += 1
        return np.asarray(nxt)

    def step_chunk(self, rng, temperature, budgets, k: Optional[int] = None
                   ) -> Tuple[jax.Array, jax.Array]:
        """Dispatch one fused chunk of ``k`` (default ``decode_chunk``)
        decode steps.

        ``budgets`` [max_batch] int32 = tokens each slot may still emit
        (0 for free slots). Input tokens come from the device-resident
        ``_next_tok`` buffer (written by ``insert_request`` and the
        previous chunk), so no host state crosses to the device. Callers
        (the scheduler) pass ``k = min(decode_chunk, earliest remaining
        budget)`` so a chunk never runs masked steps past the first
        completion — short requests sync at per-token cadence, long
        co-batches amortize the full chunk.

        Returns unforced device arrays ``(tokens [B, k], emitted [B, k])``;
        the caller reads both in ONE host sync and then calls
        :meth:`commit_chunk` with the per-slot emission counts.
        """
        # k is the caller's explicit choice (the scheduler budget-aligns
        # it); decode_chunk is only the default
        k = self.decode_chunk if k is None else max(1, int(k))
        if k not in self._chunk_jit:
            self._chunk_jit[k] = jax.jit(
                _named("decode_chunk", self._chunk_impl, k))
        if self.paged:
            # every budgeted write this chunk needs an allocated page
            # BEFORE dispatch (the device cannot allocate); clamping the
            # budget to the secured headroom freezes a starved slot at a
            # page boundary exactly like a max_seq-full one. The scheduler
            # pre-ensures and retires starved requests — this second call
            # is an idempotent no-op there and a guard for direct callers.
            budgets = np.asarray(budgets, np.int32).copy()
            for i in np.flatnonzero(self._active & (budgets > 0)):
                budgets[i] = self.ensure_capacity(int(i),
                                                  min(k, int(budgets[i])))
        temps = np.broadcast_to(np.asarray(temperature, np.float32),
                                (self.max_batch,))
        self._cache, self._next_tok, toks, emitted = self._chunk_jit[k](
            self.params, self._cache, self._next_tok, rng,
            jnp.asarray(temps, F32), jnp.asarray(budgets, jnp.int32),
            jnp.asarray(self._active))
        return toks, emitted

    def commit_chunk(self, emitted_counts: np.ndarray):
        """Fold a chunk's per-slot emission counts into the host-side
        length mirror (each emitted token wrote exactly one KV/state entry)."""
        self._lengths += np.asarray(emitted_counts, np.int32)

    # -- convenience: synchronous batch generation ------------------------------

    def generate(self, prompts: List[List[int]], *, max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 extras: Optional[List[Dict[str, Any]]] = None,
                 ) -> List[GenerationResult]:
        """Generate for up to ``max_batch`` prompts at once (convenience path;
        the scheduler drives the slot API directly for continuous batching)."""
        assert len(prompts) <= self.max_batch
        t0 = _now()
        rng = jax.random.PRNGKey(seed)
        last_tok = np.zeros((self.max_batch,), np.int32)
        outs: List[List[int]] = [[] for _ in prompts]
        try:
            firsts = [self.insert_request(p, i,
                                          extra=extras[i] if extras else None)
                      for i, p in enumerate(prompts)]
        except Exception:
            # a failed insert (e.g. pool exhausted mid-batch) must not
            # strand the prompts already inserted: their slots would stay
            # active with their pages allocated forever
            for i in range(len(prompts)):
                self.release_slot(i)
            raise
        for i, f in enumerate(firsts):            # one deferred sync point
            first = int(f)
            outs[i].append(first)
            last_tok[i] = first
        t_first = _now() - t0                     # all prefills + first toks
        done = [False] * len(prompts)
        capped = [False] * len(prompts)
        for step in range(max_new_tokens - 1):
            # a slot at cache capacity cannot decode another token — stop
            # rather than collect the masked 0s step() emits for it (the
            # scheduler path retires the same condition as MAX_SEQ_EXCEEDED;
            # here the result reports finished=False)
            for i in range(len(prompts)):
                if not done[i] and self.capacity_left(i) <= 0:
                    done[i] = capped[i] = True
                    self.release_slot(i)
            if all(done):
                break
            rng, sub = jax.random.split(rng)
            nxt = self.step(last_tok, sub, temperature)
            for i in range(len(prompts)):
                if done[i]:
                    continue
                tok = int(nxt[i])
                outs[i].append(tok)
                last_tok[i] = tok
                if self.eos_id is not None and tok == self.eos_id:
                    # release NOW, not at the end of the batch: a done slot
                    # left active keeps decoding (wasted compute) and keeps
                    # advancing its cache length — drifting vs the
                    # scheduler path's chunk-boundary retire
                    done[i] = True
                    self.release_slot(i)
            if all(done):
                break
        dt = _now() - t0
        results = []
        for i, p in enumerate(prompts):
            finished = bool(done[i]) if self.eos_id is not None else True
            results.append(GenerationResult(
                tokens=outs[i], prompt_len=len(p), steps=len(outs[i]),
                finished=finished and not capped[i],   # capacity-truncated
                latency_s=dt, first_token_s=t_first))
            self.release_slot(i)
        return results
