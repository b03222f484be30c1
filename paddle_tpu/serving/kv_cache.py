"""Paged KV-cache pool + block allocator for the serving engine.

Reference: the reference's block_multihead_attention serving path
(python/paddle/incubate/nn/functional/block_multihead_attention.py) keys
decode attention by a per-sequence block table into a shared page pool;
the allocator above it (PaddleNLP llm serving / fastdeploy cache manager)
hands out fixed-size pages from a free list so sequences of any length
share one HBM reservation.

Layout matches ops/pallas/paged_attention.py exactly: per layer a
(k_pool, v_pool) pair of [num_blocks, block_size, n_kv_heads, head_dim]
arrays, block tables of int32 page ids. Page 0 is RESERVED as scratch:
dead batch slots and padded prefill positions write there, so the
allocator never hands it out and no live sequence ever reads it.

ISSUE 3 adds page sharing (vLLM/SGLang-style prefix caching): pages are
refcounted, and a PrefixCache keeps FULL, immutable pages indexed by a
hash chain over their token content. A new request maps the longest
cached page-aligned prefix of its context straight into its block table
(incref, no recompute); any write that would land on a shared page is
copy-on-write forked first, so a shared page is never mutated in place.
Cached pages the cache alone still references (refcount 1) are evictable
in LRU order when the free list runs dry.

ISSUE 10 adds a HOST tier under the device pool: ``HostKVTier`` keeps
pinned numpy page buffers mirroring the device layout (one buffer per
layer per pool array — int8 code + scale pages ride along unchanged, so
offload composes with ISSUE 9). Two spill paths feed it: youngest-first
preemption spills the victim's exclusively-owned pages instead of
dropping them (``Request.phase = "offloaded"``; restore becomes an
O(bytes) copy instead of an O(prefill) recompute), and PrefixCache LRU
eviction DEMOTES full cached pages through ``evict_hook`` before the
device page is reclaimed (a later prefix match can then hit the
host-resident page and page it back in). Spilled bytes are exactly the
device bytes — page-in restores them bit-identically — so the engine's
token streams are untouched by construction, and any miss (eviction
hole, tier-cap overflow, crash) falls back to the existing
recompute-on-resume path.

ISSUE 9 adds quantized pools: ``KVCachePool(kv_dtype="int8")`` stores
K/V pages as int8 codes plus a parallel SCALE pool — one fp32 scale per
page per kv-head, the exact granularity the ragged kernel dequantizes
at inside its page walk. Each layer entry becomes a 4-tuple
``(k_codes, v_codes, k_scale, v_scale)`` instead of the fp32 ``(k, v)``
pair; everything host-side treats pages as opaque blocks, so the
allocator, block tables, PrefixCache, COW forking (`copy_page` copies
the scale row with the codes), truncate/rollback, and snapshot/restore
are all quantization-blind. `quantized_page_write` is the jit-pure
append: incoming K/V rows grow the per-page running-max scale (a write
landing on slot 0 RESTARTS the page's scale — page lifecycle begins
there), already-resident codes are requantized to the grown scale, and
the new rows are quantized at it — so one (page, head) scale always
dequantizes every live code in the page. Default stays "fp32": those
pools are byte-identical to the pre-ISSUE-9 layout.

ISSUE 15 extends the ladder one rung down: ``kv_dtype="fp8"`` stores
pages as native ``float8_e4m3fn`` — appends are a scale-free
per-element cast (``fp8_page_write``), so there are NO scale pools and
NO requant-on-grow, and the layer tuples stay plain ``(k, v)`` pairs
at 1 byte/element (4x vs fp32, measured by ``page_bytes``). And
``kv_dtype="mixed"`` serves mixed-precision TENANTS from one pool
geometry: fp32 storage plus a per-page TAG PLANE in each layer tuple
(``(k, v, tag)``); pages are tagged at alloc with their owner
request's effective kv_dtype (``SequenceKV.kv_tag`` from
``SamplingParams.kv_dtype``), fp8-tagged pages are written through the
fp8 round-trip cast (bit-identical values to a native fp8 pool), and
non-default tags seed DISJOINT prefix-hash chains so tenants of
different precision can never share pages. The auditor pins the tag
bijection (device plane == allocator tag map == owner requests'
dtypes).
"""

from __future__ import annotations

import heapq
import threading
from bisect import insort
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from paddle_tpu import profiler as _prof

SCRATCH_PAGE = 0

# int8 symmetric quantization range of the quantized KV pools (ISSUE 9)
KV_QMAX = 127.0

# pool storage rungs of the quantization ladder. "fp8" (ISSUE 15) is
# native float8_e4m3fn pages — a scale-free per-element cast at append,
# no scale pools, no requant-on-grow. "mixed" serves MIXED-PRECISION
# TENANTS from one pool geometry: fp32 storage plus a per-page tag
# plane; pages tagged "fp8" (per-request SamplingParams.kv_dtype) are
# written through the fp8 round-trip cast, so an fp8 tenant's values
# are bit-identical to a native fp8 pool while fp32 tenants stay
# bit-exact.
KV_DTYPES = ("fp32", "int8", "fp8", "mixed")


def fp8_supported() -> bool:
    """Whether this jax/ml_dtypes build carries float8_e4m3fn."""
    return hasattr(jnp, "float8_e4m3fn")


def require_fp8(context: str) -> None:
    """Loud gate for the fp8 rung (ISSUE 15 satellite): fp8 pools need
    float8_e4m3fn in jax (native fp8 hardware, or XLA's emulation on
    CPU/older TPUs) — never a silent fp32 fallback."""
    if not fp8_supported():
        raise RuntimeError(
            f"{context}: this jax/ml_dtypes build has no float8_e4m3fn "
            "support, so fp8 KV pages cannot be stored (or emulated) — "
            "upgrade jax (>= 0.4.14 ships fp8 dtypes) or serve with "
            "kv_dtype='int8' instead")


def fp8_round(x):
    """Round-trip through float8_e4m3fn: the exact value a native fp8
    page stores, represented at the input dtype — the mixed-pool write
    path (per-element, scale-free)."""
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def fp8_page_write(pool, write_page, write_off, x):
    """Append fp rows into a NATIVE fp8 page pool (ISSUE 15): a pure
    per-element cast — no scales to grow, no resident codes to
    requantize (the int8 path's whole lifecycle machinery evaporates).
    Deterministic and idempotent like `quantized_page_write`, so step
    retries stay exact."""
    return pool.at[write_page, write_off].set(x.astype(pool.dtype))


def quantized_page_write(codes, scales, write_page, write_off, x):
    """Append fp K/V rows into an int8 page pool, jit-pure (ISSUE 9).

    codes: [num_blocks, page_size, n_kv, d] int8; scales: [num_blocks,
    n_kv] fp32 (one scale per page per kv-head); write_page/write_off:
    [B, T] int32; x: [B, T, n_kv, d] float. Returns (codes, scales).

    Scale lifecycle: a write that lands on slot 0 of a page RESTARTS
    that page's scale (page occupancy begins there — a page recycled
    from the free list must not inherit its previous tenant's range),
    otherwise the scale is the running abs-max over everything written
    to the page so far. When a write grows a page's scale, the codes
    already resident in that page are requantized to the new scale
    (round(code * old/new)) so ONE (page, head) scale dequantizes every
    live code; pages whose scale is unchanged keep their codes
    bit-identical (ratio is exactly 1.0). Deterministic and idempotent:
    re-running the same write on the same pools produces the same pools,
    which is what makes engine step retries exact on the int8 path."""
    P, _, H, _ = codes.shape
    pages = write_page.reshape(-1)                          # [N]
    offs = write_off.reshape(-1)                            # [N]
    amax = jnp.max(jnp.abs(x), axis=-1)                     # [B, T, H]
    amax = amax.reshape(-1, H).astype(jnp.float32)          # [N, H]
    # slot-0 writes restart the page's scale (int32 scatter-max: bool
    # scatter-max is not universally supported)
    starts = jnp.zeros((P,), jnp.int32).at[pages].max(
        (offs == 0).astype(jnp.int32))
    base = jnp.where(starts[:, None] > 0, 0.0, scales)      # [P, H]
    contrib = jnp.zeros_like(scales).at[pages].max(amax / KV_QMAX)
    new_scales = jnp.maximum(base, contrib)
    # requantize the touched pages' resident codes to the grown scale
    # (ratio == 1 exactly where nothing grew -> codes unchanged; a
    # restarted page's stale codes go to 0 and are rewritten/dead)
    ratio = jnp.where(new_scales > 0.0,
                      base / jnp.maximum(new_scales, 1e-30), 1.0)
    resc = jnp.round(codes[pages].astype(jnp.float32)
                     * ratio[pages][:, None, :, None])
    codes = codes.at[pages].set(resc.astype(jnp.int8))
    # quantize the incoming rows at the new scale and write them through
    s = new_scales[write_page]                              # [B, T, H]
    q = jnp.round(x.astype(jnp.float32)
                  / jnp.maximum(s, 1e-30)[..., None])
    q = jnp.clip(q, -KV_QMAX, KV_QMAX).astype(jnp.int8)
    return codes.at[write_page, write_off].set(q), new_scales

# seed of the per-page content hash chain (any fixed int; the chain makes
# page i's key depend on every token in pages 0..i, so equal hash ==
# equal token prefix — the property prefix matching leans on)
_CHAIN_SEED = 0x5EED


def page_content_hash(prev_hash: int, page_tokens: Sequence[int]) -> int:
    """Hash key of one FULL page given its tokens and the previous page's
    chain hash. Tuple-of-int hashing is deterministic in CPython (ints
    hash to themselves), so equal prefixes always collide on purpose."""
    return hash((prev_hash,) + tuple(int(t) for t in page_tokens))


class BlockAllocator:
    """Deterministic refcounted free-list page allocator.

    Pages are handed out lowest-id-first (sorted free list) so a given
    request trace always produces the same block tables — the property the
    token-for-token equivalence test leans on. Page 0 (scratch) is never
    allocatable.

    Refcounts (ISSUE 3): `alloc` hands a page out at refcount 1;
    prefix-shared pages are `incref`ed per additional user (including the
    PrefixCache itself, which holds one reference per registered page) and
    `decref`ed on release — a page returns to the free list only when its
    count hits zero. `free(pages)` is decref-each, so exclusive pages
    behave exactly as before the cache existed.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("pool needs >= 2 pages (page 0 is scratch)")
        self.num_blocks = num_blocks
        self._free = list(range(1, num_blocks))  # ascending
        self._ref: Dict[int, int] = {}           # page -> refcount (>= 1)
        # per-page kv-dtype tags (ISSUE 15): stamped by SequenceKV at
        # alloc time with the owning request's effective kv_dtype,
        # cleared when the page's refcount hits zero — the auditor's
        # tag-bijection invariant reads this map
        self._tags: Dict[int, str] = {}
        self.evictor: Optional["PrefixCache"] = None

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_usable(self) -> int:
        """Total allocatable pages (excludes the scratch page)."""
        return self.num_blocks - 1

    @property
    def num_evictable(self) -> int:
        """Cached pages only the prefix cache still references — they can
        be reclaimed on demand, so admission treats them as free."""
        return self.evictor.evictable_count() if self.evictor else 0

    @property
    def allocated_pages(self) -> frozenset:
        """Read-only view of the live pages (resilience.audit_engine)."""
        return frozenset(self._ref)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free) + self.num_evictable

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free) and self.evictor is not None:
            self.evictor.evict(n - len(self._free))
        if n > len(self._free):
            raise MemoryError(
                f"KV pool exhausted: need {n} pages, {len(self._free)} free")
        pages, self._free = self._free[:n], self._free[n:]
        for p in pages:
            self._ref[p] = 1
        return pages

    def incref(self, page: int) -> int:
        if page not in self._ref:
            raise ValueError(f"incref of unallocated page {page}")
        self._ref[page] += 1
        return self._ref[page]

    def decref(self, page: int) -> int:
        """Drop one reference; a page whose count reaches zero returns to
        the (sorted) free list. Raises on over-release — the double-free
        guard the leak tests lean on."""
        if page not in self._ref:
            raise ValueError(f"double free of page {page}")
        self._ref[page] -= 1
        rc = self._ref[page]
        if rc == 0:
            del self._ref[page]
            self._tags.pop(page, None)   # tag dies with the last ref
            insort(self._free, page)   # keep sorted: allocation stays
        return rc                      # deterministic

    def free(self, pages: List[int]) -> None:
        for p in pages:
            self.decref(p)

    def check_no_leaks(self) -> bool:
        return not self._ref and len(self._free) == self.num_usable


class PrefixCache:
    """Hash-indexed cache of FULL, immutable KV pages (ISSUE 3 tentpole).

    Keys are content-chain hashes: page i of a sequence is keyed by
    hash(chain(pages 0..i-1), tokens of page i), so a hit on page i
    certifies the entire token prefix matches — exactly the vLLM /
    SGLang automatic-prefix-caching contract, restricted to page
    granularity.

    The cache holds ONE allocator reference per registered page, so a
    registered page survives its owning sequence (preemption, finish,
    crash-restore recompute) at refcount 1 — "cached free". Those pages
    are evictable in LRU order (a deterministic logical tick, never wall
    time) when the allocator runs dry; acquiring a page for a new match
    increfs it back above 1, which pins it.

    Immutability is enforced by copy-on-write at the write path
    (SequenceKV.ensure_writable): any page with refcount > 1 — shared
    with another sequence or with this cache — is forked before a write,
    so cached content is never mutated in place.
    """

    def __init__(self, pool: "KVCachePool"):
        self.pool = pool
        self.block_size = pool.block_size
        self._index: Dict[int, int] = {}        # chain hash -> page id
        self._page_hash: Dict[int, int] = {}    # page id -> chain hash
        self._page_tick: Dict[int, int] = {}    # page id -> last-use tick
        self._tick = 0
        self.hit_pages = 0
        self.miss_pages = 0
        self.evictions = 0
        # demotion intercept (ISSUE 10 satellite): called as
        # hook(page, chain_hash, reason) for EVERY page leaving the
        # index — reason "evict" on LRU reclaim, "clear" on clear() —
        # while the page is still allocated and its content intact, so
        # a host tier can copy it out without subclassing. clear() fires
        # it too on purpose: a hook that only saw evictions would leak
        # host-tier bookkeeping for every page dropped at teardown.
        self.evict_hook: Optional[Callable[[int, int, str], None]] = None

    def __len__(self) -> int:
        return len(self._index)

    def pages(self) -> frozenset:
        return frozenset(self._page_hash)

    def _touch(self, page: int) -> None:
        self._tick += 1
        self._page_tick[page] = self._tick

    # ---------------------------------------------------------- matching

    def match(self, tokens: Sequence[int],
              tag: Optional[str] = None) -> List[Tuple[int, int]]:
        """Longest cached page-aligned prefix of `tokens`, as a list of
        (chain_hash, page) pairs. Capped STRICTLY below len(tokens): at
        least one token is always left to compute, so admission always
        produces the logits it must sample from. `tag` is the
        requesting tenant's effective kv_dtype (ISSUE 15): non-default
        tags seed a DISJOINT hash chain, so mixed-precision tenants
        can never share each other's pages."""
        limit = (len(tokens) - 1) // self.block_size
        out: List[Tuple[int, int]] = []
        prev = self.pool.chain_seed(tag)
        for i in range(limit):
            h = page_content_hash(
                prev, tokens[i * self.block_size:(i + 1) * self.block_size])
            page = self._index.get(h)
            if page is None:
                self.miss_pages += 1
                break
            out.append((h, page))
            prev = h
        self.hit_pages += len(out)
        return out

    def match_tiered(self, tokens: Sequence[int],
                     tag: Optional[str] = None
                     ) -> Tuple[List[Tuple[int, int]], List[int]]:
        """match() extended into the host tier (ISSUE 10): after the
        device index misses, the chain continues against the tier's
        demoted-prefix index. Returns (device_matches, host_hashes) —
        device matches are (hash, page) pairs exactly like match();
        host hashes name host-resident pages the scheduler must fund a
        fresh device page for and the engine must page in before the
        step that reads them. Same strict cap as match(): the combined
        prefix always leaves at least one token to compute."""
        matched = self.match(tokens, tag)
        tier = self.pool.host_tier
        host: List[int] = []
        if tier is not None and tier.prefix_count:
            limit = (len(tokens) - 1) // self.block_size
            prev = (matched[-1][0] if matched
                    else self.pool.chain_seed(tag))
            for i in range(len(matched), limit):
                h = page_content_hash(
                    prev,
                    tokens[i * self.block_size:(i + 1) * self.block_size])
                if not tier.has_prefix(h):
                    break
                host.append(h)
                prev = h
        return matched, host

    def acquire(self, matched: List[Tuple[int, int]]) -> None:
        """Pin a match() result for a sequence: one incref per page (and
        an LRU touch). Must run before any further allocation so eviction
        cannot reclaim the matched pages out from under the admit."""
        for _, page in matched:
            self.pool.allocator.incref(page)
            self._touch(page)

    def unacquire(self, matched: List[Tuple[int, int]]) -> None:
        """Roll acquire() back (admission decided not to take the seat)."""
        for _, page in matched:
            self.pool.allocator.decref(page)

    # ------------------------------------------------------ registration

    def register_seq(self, kv: "SequenceKV", tokens: Sequence[int]) -> int:
        """Register every newly-FULL page of `kv` (tokens = the owning
        request's context). Pages whose content hash is already cached are
        skipped (first writer wins; the duplicate page stays private to
        its sequence). Returns the number of pages newly registered."""
        full = kv.num_tokens // self.block_size
        added = 0
        while kv.registered_pages < full:
            i = kv.registered_pages
            prev = (kv.hash_chain[i - 1] if i
                    else self.pool.chain_seed(kv.kv_tag))
            h = page_content_hash(
                prev, tokens[i * self.block_size:(i + 1) * self.block_size])
            page = kv.pages[i]
            if h not in self._index:
                self._index[h] = page
                self._page_hash[page] = h
                self.pool.allocator.incref(page)   # the cache's own ref
                self._touch(page)
                self._drop_host_duplicate(h)
            kv.hash_chain.append(h)
            kv.registered_pages += 1
            added += 1
        return added

    def register_page(self, page: int, h: int) -> bool:
        """Re-index an already-restored page under its chain hash — the
        host-tier PROMOTION re-entry (ISSUE 10): a fresh device page
        whose content the engine pages in from a demoted host copy joins
        the index exactly as if its first writer had registered it.
        First-writer-wins like register_seq; returns False if the hash
        is already indexed (the page then stays private). Marked as a
        PROMOTION to the tier: with a shared store (ISSUE 14) the
        resident copy is the source this page was restored from and
        stays indexed for every sibling replica."""
        if h in self._index:
            return False
        self._index[h] = page
        self._page_hash[page] = h
        self.pool.allocator.incref(page)       # the cache's own ref
        self._touch(page)
        self._drop_host_duplicate(h, promoted=True)
        return True

    def _drop_host_duplicate(self, h: int, promoted: bool = False) -> None:
        """Keep chain hashes device-live XOR host-resident (the
        auditor's per-engine tier invariant): when a RECOMPUTED
        sequence registers a hash the host tier still mirrors — its
        page was demoted AFTER this sequence's admission match, or sat
        past match()'s strict cap — the freshly computed device page
        wins and the redundant host copy is dropped. With a shared
        store the drop is TIER-WIDE (the ISSUE 14 satellite: decref
        the stale store copy, not just a local index entry), while a
        `promoted` registration keeps the store copy — it IS the bytes
        this page was just restored from, and the siblings still want
        it."""
        tier = self.pool.host_tier
        if tier is not None:
            tier.drop_stale_prefix(h, promoted=promoted)

    # ---------------------------------------------------------- eviction

    def evictable_count(self) -> int:
        alloc = self.pool.allocator
        return sum(1 for p in self._page_hash if alloc.refcount(p) == 1)

    def evict(self, n: int) -> int:
        """Reclaim up to n cached-free pages (refcount 1 = only the cache
        holds them), least-recently-used first — the tick order is a
        logical counter, so eviction is deterministic."""
        alloc = self.pool.allocator
        victims = sorted((p for p in self._page_hash
                          if alloc.refcount(p) == 1),
                         key=lambda p: self._page_tick[p])[:n]
        for page in victims:
            if self.evict_hook is not None:
                # demotion intercept fires BEFORE the decref: the page is
                # still allocated and its content intact, so the host
                # tier can copy it out (ISSUE 10)
                self.evict_hook(page, self._page_hash[page], "evict")
            self._unregister(page)
            alloc.decref(page)         # rc 1 -> 0: back to the free list
            self.evictions += 1
        return len(victims)

    def _unregister(self, page: int) -> None:
        h = self._page_hash.pop(page)
        del self._index[h]
        del self._page_tick[page]

    def clear(self) -> int:
        """Drop the whole index (the cache's references with it). Pages
        still mapped by running sequences stay live; cached-free pages
        return to the free list. Used by snapshot/teardown paths.

        Fires evict_hook(page, hash, "clear") for every dropped page —
        the same intercept evict() fires (ISSUE 10 satellite): a host
        tier that only saw LRU demotions would silently leak its
        bookkeeping for pages dropped wholesale here."""
        pages = list(self._page_hash)
        for page in pages:
            if self.evict_hook is not None:
                self.evict_hook(page, self._page_hash[page], "clear")
            self._unregister(page)
            self.pool.allocator.decref(page)
        return len(pages)


@dataclass
class OffloadRecord:
    """One preempted sequence's host-resident KV state (ISSUE 10).

    `slots[j]` holds the host copy of the sequence's page index
    `start_page + j`; token positions [0, covered_tokens) are restorable
    from (prefix-cache pages for [0, start_page)) + (these slots). The
    record rides `Request.offload` while the request waits with
    phase="offloaded"; admission either connects it back to a matching
    prefix (page-in resume) or drops it (recompute fallback). With a
    store-backed tier (ISSUE 14) the slots name SharedKVStore slots the
    owning engine holds references on — same lifecycle, tier-wide
    scope."""

    start_page: int                        # first page index the slots cover
    covered_tokens: int                    # positions [0, covered) restorable
    slots: List[int] = field(default_factory=list)


def _open_shm(name: str, tracked: bool = False):
    """Attach an existing shared_memory segment. `tracked=False` (the
    replica-child path) keeps the attaching process's resource tracker
    OUT of it — an attached segment must never be unlinked by a child's
    exit; only the owning router unlinks. `tracked=True` (the recovery
    path: this process WILL own and later unlink the segment) leaves
    the default tracking in place so unlink's unregister stays
    balanced. `track=` exists from python 3.13; older versions need the
    explicit unregister."""
    from multiprocessing import shared_memory

    if tracked:
        return shared_memory.SharedMemory(name=name)
    try:
        return shared_memory.SharedMemory(name=name, track=False)
    except TypeError:                      # python < 3.13
        seg = shared_memory.SharedMemory(name=name)
        try:
            from multiprocessing import resource_tracker

            resource_tracker.unregister(seg._name,  # noqa: SLF001
                                        "shared_memory")
        except Exception:                  # pragma: no cover
            pass
        return seg


class SharedKVStore:
    """Host-wide content-addressed KV page store (ISSUE 14 tentpole).

    ONE store per host replaces N private `HostKVTier` buffer sets: the
    router owns it, every engine replica's tier is a thin facade over
    it (`HostKVTier(store=...)`), and the page BYTES live either in
    plain numpy buffers (thread backend — every engine shares the
    router's address space) or in `multiprocessing.shared_memory`
    segments (`use_shm=True`, the process backend) that replica
    children map directly, so page bytes never cross a socket between
    processes on the same host.

    Two reference classes keep every slot alive, audited tier-wide
    (resilience.audit_store):

      owner refs   per-(slot, owner) counts. An owner is one engine
                   incarnation (e.g. "r0o3" / the launcher key) holding
                   the slot inside an OffloadRecord or a pending
                   page-in, or a transfer tag ("xfer:<rid>") while a
                   handoff's ownership is mid-flight between two
                   engines. `reap_owner` releases everything a dead
                   replica held — slots are reclaimed by refcount,
                   never leaked and never yanked from under a live
                   sibling.
      index ref    the content index's own single ref per indexed
                   slot: `index_prefix(chain_hash, slot)` publishes a
                   full page under its token-chain hash, tier-wide.
                   A second publication of the same chain is a DEDUP
                   (no copy, no slot); `acquire_prefix` hands any
                   engine a reference to the one resident copy — the
                   "page in once per host" property. The index entry
                   outlives every engine that used it; LRU eviction
                   (deterministic tick order) reclaims index-only
                   slots when the free list runs dry.

    A slot returns to the free list only when BOTH classes drop to
    zero; its generation then bumps, so staged transfers and stale
    handoff references self-invalidate (`generation`). Content hashes
    are CRC-accumulated at publish (stable across processes) and
    re-checked by the auditor's rotating spot check and at every
    handoff adoption, so corrupted segment bytes are caught, never
    served.
    """

    def __init__(self, layout, max_pages: int, *, use_shm: bool = False,
                 _attach: Optional[dict] = None):
        if max_pages < 1:
            raise ValueError("SharedKVStore needs max_pages >= 1")
        # layout: per layer, a tuple of (page_shape, dtype_str) per pool
        # array — the shape ONE page occupies in the host mirror
        self.layout = [tuple((tuple(int(d) for d in shape), str(dt))
                             for shape, dt in layer) for layer in layout]
        self.max_pages = int(max_pages)
        self.use_shm = bool(use_shm)
        self._segments: List = []          # SharedMemory handles
        self._segment_names: List[str] = []
        self._owns_segments = _attach is None
        self.bufs = self._map_buffers(_attach)
        self._lock = threading.RLock()
        self._free: List[int] = list(range(self.max_pages))
        # slot -> {owner: count}; empty/missing dict = no owner refs
        self._owners: Dict[int, Dict[str, int]] = {}
        self._indexed: set = set()         # slots the prefix index pins
        self._hash: Dict[int, Optional[int]] = {}
        self._gen: Dict[int, int] = {}
        self._prefix: Dict[int, int] = {}        # chain hash -> slot
        self._prefix_slot: Dict[int, int] = {}   # slot -> chain hash
        self._tick = 0
        self._slot_tick: Dict[int, int] = {}
        # cumulative tier-wide accounting (stats()/audit/bench)
        self.published_pages = 0           # fresh pages indexed
        self.dedup_pages = 0               # publications skipped: resident
        self.prefix_hits = 0               # acquire_prefix successes
        self.evictions = 0                 # LRU index-only reclaims
        self.reaped_slots = 0              # freed by dead-owner reaping
        self.dropped_pages = 0             # allocs a full store refused

    # ------------------------------------------------------ construction

    @classmethod
    def layout_for(cls, num_layers: int, block_size: int, n_kv_heads=None,
                   head_dim=None, dtype="float32",
                   kv_dtype: str = "fp32", page_layout=None) -> list:
        """The host-mirror page layout for a pool geometry — exactly
        the per-page slices of KVCachePool's layer tuples (`page_arrays`;
        `page_layout` as KVCachePool takes it from the runner, else the
        (k, v) pair of `[n_kv_heads, head_dim]`)."""
        layer = tuple(
            (shape, str(np.dtype(dt))) for _, shape, dt, _ in page_arrays(
                block_size,
                page_layout or kv_pair_layout(n_kv_heads, head_dim, dtype),
                kv_dtype))
        return [layer for _ in range(num_layers)]

    @classmethod
    def for_runner(cls, runner, max_pages: int, *, use_shm: bool = False
                   ) -> "SharedKVStore":
        """Build a store sized for a PagedModelRunner's pool geometry
        (the thread-backend router path: one runner is enough — every
        replica must share the model config, which attach-time shape
        validation enforces loudly)."""
        return cls(cls.layout_for(
            runner.num_layers, runner.block_size,
            kv_dtype=getattr(runner, "kv_dtype", "fp32"),
            page_layout=_page_layout_of(runner)),
            max_pages, use_shm=use_shm)

    @classmethod
    def for_geometry(cls, geometry: dict, max_pages: int, *,
                     use_shm: bool = False) -> "SharedKVStore":
        """Build from a JSON-able geometry dict (the process-backend
        router path, where no runner exists in the router process):
        {num_layers, block_size, n_kv_heads, head_dim, dtype?,
        kv_dtype?}."""
        return cls(cls.layout_for(
            int(geometry["num_layers"]), int(geometry["block_size"]),
            int(geometry["n_kv_heads"]), int(geometry["head_dim"]),
            geometry.get("dtype", "float32"),
            geometry.get("kv_dtype", "fp32")), max_pages, use_shm=use_shm)

    def _map_buffers(self, attach: Optional[dict]):
        bufs = []
        names = iter(attach["segments"]) if attach is not None else None
        for layer in self.layout:
            arrs = []
            for shape, dt in layer:
                full = (self.max_pages,) + shape
                if attach is not None:
                    # reattach = this process takes ownership (it will
                    # unlink at shutdown): keep tracking balanced
                    seg = _open_shm(next(names), tracked=True)
                    self._segments.append(seg)
                    self._segment_names.append(seg.name)
                    arr = np.ndarray(full, dtype=np.dtype(dt),
                                     buffer=seg.buf)
                elif self.use_shm:
                    from multiprocessing import shared_memory

                    nbytes = int(np.prod(full, dtype=np.int64)
                                 * np.dtype(dt).itemsize)
                    seg = shared_memory.SharedMemory(create=True,
                                                     size=max(1, nbytes))
                    self._segments.append(seg)
                    self._segment_names.append(seg.name)
                    arr = np.ndarray(full, dtype=np.dtype(dt),
                                     buffer=seg.buf)
                    arr[...] = 0
                else:
                    arr = np.zeros(full, np.dtype(dt))
                arrs.append(arr)
            bufs.append(tuple(arrs))
        return bufs

    def attach_spec(self) -> Optional[dict]:
        """JSON-able description a replica child (or a recovering
        router) needs to map the SAME segment bytes: segment names in
        layout order plus the layout itself. None without shm — plain
        numpy buffers cannot cross a process boundary."""
        if not self.use_shm:
            return None
        return {"max_pages": self.max_pages,
                "layout": [[[list(shape), dt] for shape, dt in layer]
                           for layer in self.layout],
                "segments": list(self._segment_names)}

    @classmethod
    def reattach(cls, spec: dict) -> "SharedKVStore":
        """Map an existing store's segments (router recovery, ISSUE 14:
        shared-memory segments survive a router SIGKILL until unlinked)
        with EMPTY metadata — restore_index() then revives the content
        index entries whose bytes still CRC-verify."""
        layout = [tuple((tuple(shape), dt) for shape, dt in layer)
                  for layer in spec["layout"]]
        store = cls(layout, int(spec["max_pages"]), use_shm=True,
                    _attach=spec)
        store._owns_segments = True        # the recovered router owns them
        return store

    @staticmethod
    def unlink_spec(spec: Optional[dict]) -> int:
        """Best-effort unlink of a dead store's segments (recovery
        decided not to reattach). Returns segments unlinked."""
        if not spec:
            return 0
        n = 0
        for name in spec.get("segments", ()):
            try:
                seg = _open_shm(name, tracked=True)
                seg.close()
                seg.unlink()
                n += 1
            except FileNotFoundError:
                pass
            except Exception:              # pragma: no cover
                pass
        return n

    def close(self, unlink: Optional[bool] = None) -> None:
        """Release the segment mappings; the creating (or recovered)
        router also unlinks, so host RAM is returned when the tier
        shuts down."""
        if unlink is None:
            unlink = self._owns_segments
        self.bufs = []
        for seg in self._segments:
            try:
                seg.close()
            except Exception:              # pragma: no cover
                pass
            if unlink:
                try:
                    seg.unlink()
                except Exception:          # pragma: no cover
                    pass
        self._segments = []

    # ------------------------------------------------------- accounting

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.max_pages - len(self._free)

    @property
    def prefix_count(self) -> int:
        return len(self._prefix)

    def page_bytes(self) -> int:
        return sum(int(np.prod(shape, dtype=np.int64)
                       * np.dtype(dt).itemsize)
                   for layer in self.layout for shape, dt in layer)

    @property
    def bytes_used(self) -> int:
        return self.used_count * self.page_bytes()

    def refcount(self, slot: int) -> int:
        with self._lock:
            return (sum(self._owners.get(slot, {}).values())
                    + (1 if slot in self._indexed else 0))

    def owner_count(self, slot: int, owner: str) -> int:
        with self._lock:
            return self._owners.get(slot, {}).get(owner, 0)

    def owners_snapshot(self) -> Dict[int, Dict[str, int]]:
        with self._lock:
            return {s: dict(o) for s, o in self._owners.items() if o}

    def generation(self, slot: int) -> int:
        with self._lock:
            return self._gen.get(slot, 0)

    def slot_hash(self, slot: int) -> Optional[int]:
        with self._lock:
            return self._hash.get(slot)

    def set_hash(self, slot: int, h: int) -> None:
        with self._lock:
            self._hash[slot] = int(h)

    def stats(self) -> Dict[str, float]:
        with self._lock:
            return {
                "store_max_pages": float(self.max_pages),
                "store_free": float(len(self._free)),
                "store_used": float(self.max_pages - len(self._free)),
                "store_prefix_pages": float(len(self._prefix)),
                "store_published_pages": float(self.published_pages),
                "store_dedup_pages": float(self.dedup_pages),
                "store_prefix_hits": float(self.prefix_hits),
                "store_evictions": float(self.evictions),
                "store_reaped_slots": float(self.reaped_slots),
                "store_dropped_pages": float(self.dropped_pages),
                "store_bytes_used": float(self.bytes_used),
            }

    # ------------------------------------------------------- slot refs

    def _touch_locked(self, slot: int) -> None:
        self._tick += 1
        self._slot_tick[slot] = self._tick

    def alloc(self, n: int, owner: str) -> List[int]:
        """Hand out up to n slots at one `owner` ref each (lowest-id
        first — spill traces stay deterministic). A dry free list first
        evicts LRU index-only slots; whatever still cannot be funded is
        dropped and counted, never an error — exactly the private
        tier's cap-pressure contract."""
        with self._lock:
            if n > len(self._free):
                self._evict_locked(n - len(self._free))
            take = min(n, len(self._free))
            if take < n:
                self.dropped_pages += n - take
            slots, self._free = self._free[:take], self._free[take:]
            for s in slots:
                self._owners[s] = {owner: 1}
                self._hash[s] = None
                self._touch_locked(s)
            return slots

    def incref(self, slots: Sequence[int], owner: str) -> None:
        with self._lock:
            for s in slots:
                own = self._owners.setdefault(s, {})
                if not own and s not in self._indexed:
                    raise ValueError(f"incref of free store slot {s}")
                own[owner] = own.get(owner, 0) + 1

    def release(self, slots: Sequence[int], owner: str) -> None:
        """Drop one `owner` ref per listed slot; a slot with no owner
        refs and no index ref returns to the free list (generation
        bumps). Over-release raises — the tier-wide double-free
        guard."""
        with self._lock:
            for s in slots:
                own = self._owners.get(s)
                if not own or own.get(owner, 0) <= 0:
                    raise ValueError(
                        f"release of store slot {s} not held by "
                        f"{owner!r}")
                own[owner] -= 1
                if own[owner] == 0:
                    del own[owner]
                self._maybe_free_locked(s)

    def retag(self, slots: Sequence[int], old_owner: str,
              new_owner: str) -> None:
        """Atomically move one ref per slot from `old_owner` to
        `new_owner` — the slot-reference handoff's ownership transfer
        (prefill engine -> "xfer:<rid>" -> decode engine): the bytes
        never move, only the tag does."""
        with self._lock:
            for s in slots:
                own = self._owners.get(s)
                if not own or own.get(old_owner, 0) <= 0:
                    raise ValueError(
                        f"retag of store slot {s}: no ref held by "
                        f"{old_owner!r}")
                own[old_owner] -= 1
                if own[old_owner] == 0:
                    del own[old_owner]
                own[new_owner] = own.get(new_owner, 0) + 1

    def _maybe_free_locked(self, s: int) -> bool:
        if self._owners.get(s) or s in self._indexed:
            return False
        self._owners.pop(s, None)
        self._hash.pop(s, None)
        self._slot_tick.pop(s, None)
        self._gen[s] = self._gen.get(s, 0) + 1
        insort(self._free, s)
        return True

    def reap_owner(self, owner: str) -> int:
        """Release EVERY ref a dead owner held (supervisor recovery,
        drain residue, abandoned transfer tags). Slots another engine
        or the index still references survive untouched; the rest are
        reclaimed by refcount — a dead replica can never leak store
        RAM. Returns slots actually freed."""
        with self._lock:
            freed = 0
            for s in list(self._owners):
                own = self._owners.get(s)
                if own and owner in own:
                    del own[owner]
                    if self._maybe_free_locked(s):
                        freed += 1
            self.reaped_slots += freed
            return freed

    # ---------------------------------------------------- content index

    def has_prefix(self, h: int) -> bool:
        with self._lock:
            return h in self._prefix

    def index_prefix(self, h: int, slot: int) -> bool:
        """Publish a written slot under its token-chain hash. The index
        takes its OWN ref (on top of whatever owner refs exist), so the
        content outlives the publishing engine. False = the chain is
        already resident (dedup — caller keeps/releases its slot; the
        FIRST publication wins, the PrefixCache registration rule
        stretched tier-wide)."""
        with self._lock:
            if h in self._prefix:
                self.dedup_pages += 1
                return False
            self._prefix[h] = slot
            self._prefix_slot[slot] = h
            self._indexed.add(slot)
            self.published_pages += 1
            self._touch_locked(slot)
            return True

    def acquire_prefix(self, h: int, owner: str) -> Optional[int]:
        """Take one `owner` ref on the chain's resident slot for a
        page-in (the hash STAYS indexed — the same bytes keep serving
        every sibling, which is the whole point). None on a miss (the
        entry raced away: recompute fallback applies)."""
        with self._lock:
            slot = self._prefix.get(h)
            if slot is None:
                return None
            own = self._owners.setdefault(slot, {})
            own[owner] = own.get(owner, 0) + 1
            self.prefix_hits += 1
            self._touch_locked(slot)
            return slot

    def drop_prefix(self, h: int) -> bool:
        """Remove a chain from the index and drop the index's ref (the
        store analogue of PR 10's device-XOR-host fix, ISSUE 14
        satellite: a recomputed device registration supersedes the
        store copy TIER-WIDE). Engines holding page-in refs keep the
        bytes alive until their fences release — refcounts make the
        race benign."""
        with self._lock:
            slot = self._prefix.pop(h, None)
            if slot is None:
                return False
            del self._prefix_slot[slot]
            self._indexed.discard(slot)
            self._maybe_free_locked(slot)
            return True

    def _evict_locked(self, n: int) -> int:
        """Reclaim up to n index-only slots (no owner refs), least-
        recently-used first by the deterministic tick."""
        victims = sorted((s for s in self._indexed
                          if not self._owners.get(s)),
                         key=lambda s: self._slot_tick.get(s, 0))[:n]
        for s in victims:
            h = self._prefix_slot.pop(s)
            del self._prefix[h]
            self._indexed.discard(s)
            self._maybe_free_locked(s)
            self.evictions += 1
        return len(victims)

    # ------------------------------------------------------ byte access

    def read_slot(self, slot: int) -> List[Tuple[np.ndarray, ...]]:
        return [tuple(np.array(buf[slot]) for buf in layer)
                for layer in self.bufs]

    def export_slots(self, slots: Sequence[int]
                     ) -> List[Tuple[np.ndarray, ...]]:
        return [tuple(np.stack([buf[s] for s in slots]) for buf in layer)
                for layer in self.bufs]

    def content_hash(self, slot: int) -> int:
        """CRC-accumulated hash over the slot's bytes across every
        layer buffer — the same math HostKVTier records, stable across
        processes (the audit spot check and handoff adoption both
        re-verify against it)."""
        import zlib

        h = 0x9E3779B9
        for layer in self.bufs:
            for buf in layer:
                h = zlib.crc32(np.ascontiguousarray(buf[slot]).tobytes(),
                               h)
        return h

    def scrub(self) -> int:
        """Re-CRC every indexed slot and DROP the entries whose segment
        bytes no longer match their recorded hash — the operator-grade
        response to a failed spot check: corrupted content falls back
        to recompute instead of ever serving (in-flight refs keep their
        bytes alive but the chain stops matching). Returns entries
        dropped."""
        with self._lock:
            entries = list(self._prefix.items())
        dropped = 0
        for h, s in entries:
            rec = self.slot_hash(s)
            if rec is not None and self.content_hash(s) != rec:
                if self.drop_prefix(h):
                    dropped += 1
        return dropped

    # ------------------------------------------------ journal round trip

    def journal_state(self) -> dict:
        """The content index as a JSON-able record — journaled beside
        replica snapshots so ServingRouter.recover can revive the index
        over segments that survived a router SIGKILL. Only INDEXED
        slots ride along: owner refs belong to engines that died with
        the router."""
        with self._lock:
            return {"prefix": [
                [int(h), int(s), int(self._gen.get(s, 0)),
                 int(self._hash.get(s) or 0)]
                for h, s in self._prefix.items()]}

    def restore_index(self, state: Optional[dict]) -> int:
        """Revive journaled index entries onto a reattached store.
        Every entry is CRC-verified against the segment bytes it names
        before it re-enters the index — a slot whose bytes did not
        survive (torn write, recycled segment) is silently skipped and
        its content recomputes on demand. Returns entries restored."""
        if not state:
            return 0
        restored = 0
        for h, s, g, crc in state.get("prefix", ()):
            s = int(s)
            if not 0 <= s < self.max_pages:
                continue
            if self.content_hash(s) != int(crc):
                continue                   # corrupt/stale: recompute wins
            with self._lock:
                if int(h) in self._prefix or s not in self._free:
                    continue
                self._free.remove(s)
                self._prefix[int(h)] = s
                self._prefix_slot[s] = int(h)
                self._indexed.add(s)
                self._hash[s] = int(crc)
                self._gen[s] = int(g)
                self._touch_locked(s)
            restored += 1
        return restored


class HostKVTier:
    """Host-RAM page tier under the device pool (ISSUE 10 tentpole).

    Pinned numpy buffers mirror the device pool layout exactly: one
    buffer per layer per pool array — fp32 pools spill (k, v) pages,
    int8 pools spill (k_codes, v_codes, k_scale, v_scale) including the
    scale rows, so a page-in is bit-identical to the spilled page on
    either dtype (offload composes with ISSUE 9 by construction). Slots
    are handed out lowest-id-first from a sorted free list, mirroring
    the device BlockAllocator, so spill traces are deterministic.

    ISSUE 14 adds the CLUSTER-WIDE mode: constructed with a
    `SharedKVStore` (and this engine's `owner` tag) the tier keeps its
    whole engine-facing surface but becomes a facade over the host-wide
    store — buffers alias the store's (possibly shared-memory)
    segments, slots are store slots refcounted under `owner`, the
    prefix index is tier-wide (dedup on publish, references on
    acquire), and handoffs move slot references instead of bytes. The
    private-buffer semantics below describe the store mode too, with
    "free" meaning "this engine's reference released".

    Two populations share the buffers, each owned by exactly one party
    (the auditor pins it):

      offload slots  owned by one waiting request's OffloadRecord —
                     preemption spilled its exclusively-owned pages;
      prefix slots   owned by the tier's own hash index — PrefixCache
                     LRU eviction / clear demoted a full cached page
                     through `evict_hook`; a later tiered prefix match
                     promotes it back onto a fresh device page.

    A full tier never blocks anything: spill_pages copies as many pages
    as fit and DROPS the rest (`host_tier_drops`), which degrades the
    affected resume back to the existing recompute path — exactness is
    therefore untouched by the cap. Every spilled slot records a
    content hash over its bytes; the auditor spot-checks a rotating
    sample so silent host-buffer corruption is caught, not served.
    """

    def __init__(self, pool: "KVCachePool", max_pages: int, metrics=None,
                 async_spill: bool = False, store=None,
                 owner: str = "engine"):
        if store is None and max_pages < 1:
            raise ValueError("host tier needs max_pages >= 1 (omit the "
                             "tier entirely to disable offload)")
        self.pool = pool
        # cluster-wide mode (ISSUE 14): `store` is a SharedKVStore (or
        # a process-backend SharedKVStoreClient) — this tier becomes a
        # per-engine FACADE over the host-wide store: page bytes live
        # in the store's buffers (possibly shared-memory segments),
        # slots are refcounted under this engine's `owner` tag, and the
        # prefix index is TIER-WIDE (a page demoted by any replica
        # serves every replica's admission). All engine-facing
        # semantics (spill/page-in/free, drop-on-overflow, async spill
        # worker) are unchanged.
        self.store = store
        self.owner = str(owner)
        if store is not None:
            self._validate_store_layout(pool, store)
            max_pages = store.max_pages
        self.max_pages = int(max_pages)
        self.metrics = metrics             # optional EngineMetrics mirror
        # threaded spill I/O (ISSUE 11 satellite): with async_spill the
        # device->host copy of a spill runs on a single worker thread
        # instead of blocking the engine loop on one np.asarray per
        # page. Safe by construction: the worker copies from the
        # FUNCTIONAL pool snapshot captured at spill time (jax arrays
        # are immutable — later launches produce new arrays, so page
        # reuse can never race the copy), and every consumer of a
        # slot's bytes (read_slot, free_slots, slot_hash, the auditor's
        # content spot check via sync()) joins the pending copy first.
        # Slot ALLOCATION and all accounting stay synchronous on the
        # loop thread, so spill traces are as deterministic as before.
        self.async_spill = bool(async_spill)
        self._executor = None
        self._pending: Dict[int, object] = {}     # slot -> Future
        if store is not None:
            # the store's buffers ARE this tier's buffers (same host
            # bytes for every engine on the host — shared-memory-backed
            # under the process backend)
            self._bufs = store.bufs
            self._free = None
            self._hash = None
            self._gen = None
            self._prefix = None
            self._prefix_slot = None
        else:
            # pinned host mirrors of the device pool layout, one buffer
            # per (layer, pool-array): [max_pages, *page_shape] at the
            # pool dtype
            self._bufs: List[Tuple[np.ndarray, ...]] = [
                tuple(np.zeros((self.max_pages,) + tuple(a.shape[1:]),
                               np.dtype(str(a.dtype))) for a in layer)
                for layer in pool.page_pools]
            self._free: List[int] = list(range(self.max_pages))  # asc.
            self._hash: Dict[int, int] = {}   # slot -> content hash
            self._gen: Dict[int, int] = {}    # slot -> reuse generation
            self._prefix: Dict[int, int] = {}   # chain hash -> slot
            self._prefix_slot: Dict[int, int] = {}  # slot -> chain hash
        # cumulative accounting (authoritative; the engine mirrors them
        # into EngineMetrics when `metrics` is set)
        self.spilled_pages = 0
        self.paged_in_pages = 0
        self.dropped_pages = 0              # spills a full tier refused
        self.resumes = 0                    # page-in resumes served
        self.fallbacks = 0                  # offload records dropped to
        #                                     the recompute path
        # store-mode accounting (ISSUE 14)
        self.store_hits = 0                 # pages acquired from the index
        self.store_dedups = 0               # copies skipped: chain resident
        self.store_published = 0            # pages this engine indexed
        # satellite observability (ISSUE 14): spills that read the
        # device SYNCHRONOUSLY on the calling thread, and _wait_slot
        # joins that actually blocked on an unfinished worker copy —
        # the counting-stub pin for the async preempt-spill path
        self.sync_spill_reads = 0
        self.blocking_joins = 0

    @staticmethod
    def _validate_store_layout(pool: "KVCachePool", store) -> None:
        """A store only serves pools with the EXACT page geometry it
        was built for — a replica with a different model config mapping
        the same segments would corrupt every sibling. Loud, at attach
        time."""
        want = [tuple((tuple(a.shape[1:]), str(np.dtype(str(a.dtype))))
                      for a in layer) for layer in pool.page_pools]
        have = [tuple((tuple(shape), str(np.dtype(dt)))
                      for shape, dt in layer) for layer in store.layout]
        if want != have:
            raise ValueError(
                "SharedKVStore layout mismatch: pool pages are "
                f"{want[0] if want else '?'} x {len(want)} layers but "
                f"the store was built for "
                f"{have[0] if have else '?'} x {len(have)} layers — "
                "every replica sharing a store must run the same model "
                "geometry and kv_dtype")

    # ------------------------------------------------------- accounting

    @property
    def free_count(self) -> int:
        if self.store is not None:
            return self.store.free_count
        return len(self._free)

    @property
    def used_count(self) -> int:
        if self.store is not None:
            return self.store.used_count
        return len(self._hash)

    @property
    def prefix_count(self) -> int:
        if self.store is not None:
            return self.store.prefix_count
        return len(self._prefix)

    @property
    def bytes_used(self) -> int:
        """Host bytes the used slots pin — same per-page cost as the
        device pool (code + scale bytes on int8, ISSUE 9 honesty)."""
        return self.used_count * self.pool.page_bytes()

    @property
    def capacity_bytes(self) -> int:
        return self.max_pages * self.pool.page_bytes()

    def generation(self, slot: int) -> int:
        """Reuse generation of a slot — bumped on every free, so a
        staged device_put keyed by (slot, generation) can never serve a
        later tenant's bytes."""
        if self.store is not None:
            return self.store.generation(slot)
        return self._gen.get(slot, 0)

    def slot_hash(self, slot: int) -> int:
        self._wait_slot(slot)
        if self.store is not None:
            return self.store.slot_hash(slot)
        return self._hash[slot]

    def _set_hash(self, slot: int, h: Optional[int]) -> None:
        if self.store is not None:
            if h is not None:
                self.store.set_hash(slot, h)
        else:
            self._hash[slot] = h

    # ------------------------------------------ async spill worker plumbing

    def _ensure_executor(self):
        if self._executor is None:
            from concurrent.futures import ThreadPoolExecutor

            self._executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="kv-spill")
        return self._executor

    def _wait_slot(self, slot: int) -> None:
        """Join the pending spill copy covering one slot (no-op when the
        slot has none). A future may cover several slots; popping one
        leaves the rest mapped — result() is idempotent.
        `blocking_joins` counts the joins that actually waited — the
        observable the async-preempt-spill pin reads (ISSUE 14
        satellite): a spill itself must never add one on the engine
        loop; only a consumer racing its own copy legitimately can."""
        fut = self._pending.pop(slot, None)
        if fut is not None:
            if not fut.done():
                self.blocking_joins += 1
            fut.result()

    def sync(self) -> None:
        """Join EVERY pending async spill copy — the fence the auditor
        (and any bulk reader) runs before trusting slot contents or
        content hashes."""
        pending, self._pending = self._pending, {}
        for fut in {id(f): f for f in pending.values()}.values():
            fut.result()

    def _spill_job(self, slots: List[int], arrs, gens=None,
                   publish=()) -> None:
        """Worker-thread half of an async spill: materialize the device
        gather (np.asarray blocks HERE, not on the engine loop) into the
        pinned buffers and record the content hashes. Store mode guards
        each write by slot generation (a crashed engine's reaped slot
        must never be scribbled by its orphaned worker job) and then
        publishes any registered-page chain hashes into the tier-wide
        index — publication happens strictly AFTER the bytes land, so a
        sibling can never page in a half-written slot."""
        if gens is not None:
            live = [i for i, s in enumerate(slots)
                    if self.store.generation(s) == gens[i]]
            if len(live) < len(slots):
                slots = [slots[i] for i in live]
                publish = [p for p in publish if p[0] in set(slots)]
                idx = np.asarray(live, np.int64)
            else:
                idx = None
            if not slots:
                return
            for layer_bufs, layer_data in zip(self._bufs, arrs):
                for buf, arr in zip(layer_bufs, layer_data):
                    host = np.asarray(arr)
                    buf[slots] = host if idx is None else host[idx]
        else:
            for layer_bufs, layer_data in zip(self._bufs, arrs):
                for buf, arr in zip(layer_bufs, layer_data):
                    buf[slots] = np.asarray(arr)
        for s in slots:
            self._set_hash(s, self.content_hash(s))
        for s, h in publish:
            if self.store.index_prefix(h, s):
                self.store_published += 1

    def _finish_spill(self, slots: List[int], publish=()) -> None:
        """Synchronous-path epilogue: record hashes, then publish any
        chain hashes into the tier-wide index (store mode)."""
        for s in slots:
            self._set_hash(s, self.content_hash(s))
        for s, h in publish:
            if self.store.index_prefix(h, s):
                self.store_published += 1

    def content_hash(self, slot: int) -> int:
        """Deterministic hash over the slot's bytes across every layer
        buffer — recorded at spill time, re-checked by the auditor.
        CRC-accumulated (not python hash()) so it is stable ACROSS
        PROCESSES: the prefill->decode handoff (ISSUE 12) sends these
        hashes over the wire and the receiving replica re-verifies them
        against the bytes it wrote — a salted per-process hash could
        never catch a transfer corruption."""
        import zlib

        h = 0x9E3779B9
        for layer in self._bufs:
            for buf in layer:
                h = zlib.crc32(buf[slot].tobytes(), h)
        return h

    # ------------------------------------------------------------ spill

    def spill_pages(self, device_pages: Sequence[int],
                    publish: Sequence[Tuple[int, int]] = ()) -> List[int]:
        """Copy device pages into host slots (device->host sync copy —
        the cost preemption pays ONCE instead of a full re-prefill
        later). Takes as many as fit; the overflow is dropped and
        counted, never an error. Returns the slots, aligned with the
        leading device_pages they hold.

        `publish` (store mode) maps positions in `device_pages` to
        chain hashes to index tier-wide once the bytes land — the
        handoff/demotion publication path; positions past the fitted
        prefix are dropped with their pages."""
        if self.store is not None:
            slots = self.store.alloc(len(device_pages), self.owner)
            n = len(slots)
        else:
            n = min(len(device_pages), len(self._free))
            slots = self._free[:n]
            del self._free[:n]
        dropped = len(device_pages) - n
        if dropped:
            self.dropped_pages += dropped
            if self.metrics is not None:
                self.metrics.host_tier_drops.inc(dropped)
        if n == 0:
            return []
        pub = [(slots[i], h) for i, h in publish if i < n]
        if self.async_spill:
            # dispatch the device-side gather now (async, immutable
            # functional snapshot) and hand the blocking np.asarray +
            # buffer write + hashing + index publication to the worker;
            # the slot is "used" immediately (placeholder hash) so
            # accounting stays synchronous and deterministic — the
            # engine loop never blocks on a spill's np.asarray
            # (the ISSUE 14 satellite pin)
            arrs = self.pool.gather_pages(list(device_pages)[:n])
            for s in slots:
                self._set_hash(s, None)
            gens = ([self.store.generation(s) for s in slots]
                    if self.store is not None else None)
            fut = self._ensure_executor().submit(self._spill_job, slots,
                                                 arrs, gens, pub)
            for s in slots:
                self._pending[s] = fut
        else:
            self.sync_spill_reads += 1
            data = self.pool.read_pages(list(device_pages)[:n])
            for layer_bufs, layer_data in zip(self._bufs, data):
                for buf, arr in zip(layer_bufs, layer_data):
                    buf[slots] = arr
            self._finish_spill(slots, pub)
        self.spilled_pages += n
        if self.metrics is not None:
            self.metrics.offload_spill_pages.inc(n)
        return slots

    def spill_sequence(self, kv: "SequenceKV", covered_tokens: int,
                       include_registered: bool = False
                       ) -> Optional[OffloadRecord]:
        """Spill a preemption victim's exclusively-owned pages (the ones
        release() would send back to the free list) covering token
        positions [registered_pages * bs, covered_tokens). Leading
        registered pages stay on device inside the PrefixCache at
        refcount 1 — they re-match at re-admission (or get demoted
        through evict_hook and re-match from the host index). Returns
        None when nothing spillable exists (then the existing recompute
        path simply applies); a partial fit trims covered_tokens down
        to the spilled page boundary.

        `include_registered=True` (the prefill->decode handoff, ISSUE
        12) spills the WHOLE page range from page 0, shared pages
        included: the spill only READS the pages, and the receiving
        replica owns its own pool, so refcounts are irrelevant — what
        matters is that the record is self-contained (start_page=0)
        and connects on a sibling whose prefix cache may hold none of
        the sender's pages.

        STORE mode (ISSUE 14) adds content-addressed dedup on fp32
        pools: a registered page whose chain hash is already resident
        tier-wide contributes a REFERENCE (refcount bump on the one
        resident copy) instead of a copy, and freshly spilled
        registered pages are PUBLISHED into the index once their bytes
        land — so the host materializes a hot shared prefix once, no
        matter how many requests or replicas hand it around. Int8
        pools skip the dedup/publish (codes are chunk-history-
        dependent, so equal chains do not guarantee equal bytes; the
        record must carry THIS sequence's exact codes for the
        continuation to stay pinned) but still ride store slots."""
        bs = self.pool.block_size
        covered = min(int(covered_tokens), kv.num_tokens)
        start = 0 if include_registered else kv.registered_pages
        end = -(-covered // bs) if covered > 0 else 0
        if end <= start:
            return None
        cand = kv.pages[start:end]
        if not include_registered:
            alloc = self.pool.allocator
            if any(alloc.refcount(p) != 1 for p in cand):
                # a shared page past the registered range would break the
                # record's contiguity — never expected (COW keeps writes
                # private), so decline loudly-by-metrics rather than
                # corrupt
                self.fallbacks += 1
                if self.metrics is not None:
                    self.metrics.offload_recompute_fallbacks.inc()
                return None
        dedup_ok = (self.store is not None
                    and self.pool.kv_dtype == "fp32")
        if not dedup_ok:
            slots = self.spill_pages(cand)
            if not slots:
                return None
            if len(slots) < len(cand):
                covered = (start + len(slots)) * bs
            return OffloadRecord(start_page=start, covered_tokens=covered,
                                 slots=slots)
        # store-mode dedup/publish: registered pages are chain-hashed
        slots: List[Optional[int]] = [None] * len(cand)
        fresh_pages: List[int] = []
        fresh_pos: List[int] = []
        publish: List[Tuple[int, int]] = []   # (fresh_pages idx, hash)
        for j, page in enumerate(cand):
            idx = start + j
            h = (kv.hash_chain[idx] if idx < kv.registered_pages
                 else None)
            if h is not None:
                s = self.store.acquire_prefix(h, self.owner)
                if s is not None:
                    self._wait_slot(s)    # never reference a half-copy
                    slots[j] = s
                    self.store_dedups += 1
                    if self.metrics is not None:
                        self.metrics.store_dedup_pages.inc()
                    continue
                publish.append((len(fresh_pages), h))
            fresh_pages.append(page)
            fresh_pos.append(j)
        fresh_slots = self.spill_pages(fresh_pages, publish=publish)
        for j, s in zip(fresh_pos, fresh_slots):
            slots[j] = s
        # a partial fit truncates at the first hole so the record stays
        # contiguous. Holes are dropped FRESH pages, and fresh slots
        # are assigned in ascending position, so everything past the
        # first hole that still holds a slot is a dedup reference —
        # release those refs (the resident copies stay indexed)
        k = 0
        while k < len(slots) and slots[k] is not None:
            k += 1
        tail_refs = [s for s in slots[k:] if s is not None]
        if tail_refs:
            self.store.release(tail_refs, self.owner)
        slots = slots[:k]
        if not slots:
            return None
        if len(slots) < len(cand):
            covered = (start + len(slots)) * bs
        return OffloadRecord(start_page=start, covered_tokens=covered,
                             slots=list(slots))

    # -------------------------------------------- prefix demotion (hook)

    def on_evict(self, page: int, chain_hash: int, reason: str) -> bool:
        """PrefixCache.evict_hook target: demote a full cached page to
        the host before the device page is reclaimed. Fires for both
        LRU eviction and clear() — the clear-path hook is what keeps
        teardown from silently leaking tier bookkeeping.

        Store mode: demotion PUBLISHES tier-wide. A chain already
        resident (any sibling demoted it first, or a handoff published
        it) is a pure dedup — no copy, the device page just dies while
        the content stays reachable from every replica; otherwise the
        page spills into a fresh slot that the index alone then owns
        (publication rides the spill worker under async_spill, so a
        sibling can never acquire a half-written slot)."""
        if self.store is not None:
            if self.store.has_prefix(chain_hash):
                self.store_dedups += 1
                if self.metrics is not None:
                    self.metrics.store_dedup_pages.inc()
                return True                # content already host-resident
            slots = self.spill_pages([page], publish=[(0, chain_hash)])
            if not slots:
                return False               # store full: the page dies
            # the spill allocated under this engine's owner tag; the
            # published page must end INDEX-owned only, so the content
            # outlives this engine. On the async path the release is a
            # SECOND job on the same single-thread executor: FIFO
            # ordering runs it strictly after the copy+publish job, and
            # re-mapping the pending future makes every joiner
            # (sync()/_wait_slot, the leak checks) wait through it.
            if self.async_spill:
                s = slots[0]
                fut1 = self._pending.get(s)

                def _release(s=s, fut1=fut1):
                    if fut1 is not None:
                        fut1.result()      # surface copy-job failures
                    try:
                        self.store.release([s], self.owner)
                    except ValueError:     # pragma: no cover — reaped
                        pass
                self._pending[s] = self._ensure_executor().submit(_release)
            else:
                self.store.release([slots[0]], self.owner)
            return True
        if chain_hash in self._prefix:      # pragma: no cover — the
            return False                    # index is hash-unique
        slots = self.spill_pages([page])
        if not slots:
            return False                    # tier full: the page just dies
        self._prefix[chain_hash] = slots[0]
        self._prefix_slot[slots[0]] = chain_hash
        return True

    def has_prefix(self, h: int) -> bool:
        if self.store is not None:
            return self.store.has_prefix(h)
        return h in self._prefix

    def promote(self, h: int) -> Optional[int]:
        """Claim a demoted prefix page for re-promotion. Private tier:
        the hash LEAVES the host index (device-live XOR host-resident —
        the single-ownership invariant) and the slot stays pinned until
        the engine's fence pages it in and frees it. Store mode: the
        hash STAYS indexed (the same bytes keep serving every sibling —
        "page in once per host"); this engine just takes a reference
        for the duration of its page-in. Returns None when the entry
        raced away tier-wide (another replica's recomputed registration
        dropped it) — the caller then falls back to recompute."""
        if self.store is not None:
            slot = self.store.acquire_prefix(h, self.owner)
            if slot is not None:
                self.store_hits += 1
                if self.metrics is not None:
                    self.metrics.store_hit_pages.inc()
            return slot
        slot = self._prefix.pop(h)
        del self._prefix_slot[slot]
        return slot

    def drop_stale_prefix(self, h: int, promoted: bool = False) -> None:
        """Registration-time reconciliation (the device-XOR-host fix of
        PR 10 and its STORE analogue, ISSUE 14 satellite). `promoted`
        marks a registration that just paged the content IN from this
        tier — the resident copy is the source of truth and must stay
        (store mode) / is already gone (private promote removed it).
        A RECOMPUTED registration (promoted=False) supersedes the tier
        copy: private mode frees the slot, store mode drops the index
        entry TIER-WIDE — in-flight sibling page-ins keep the bytes
        alive through their own refs, so the decref can never corrupt
        them."""
        if self.store is not None:
            if not promoted and self.store.has_prefix(h):
                self.store.drop_prefix(h)
            return
        if self.has_prefix(h):
            self.free_slots([self.promote(h)])

    # ---------------------------------------------------------- page-in

    def read_slot(self, slot: int) -> List[Tuple[np.ndarray, ...]]:
        """One slot's per-layer page arrays, COPIED (a device_put may
        alias host memory on CPU backends; the copy makes slot reuse
        safe while a staged transfer is still in flight). Joins any
        pending async spill of the slot first."""
        self._wait_slot(slot)
        return [tuple(np.array(buf[slot]) for buf in layer)
                for layer in self._bufs]

    def export_slots(self, slots: Sequence[int]
                     ) -> List[Tuple[np.ndarray, ...]]:
        """Stacked host copies of several slots, in pool-array layout:
        per layer a tuple of [len(slots), *page_shape] arrays — the
        prefill->decode handoff's wire payload (ISSUE 12). Raw page
        bytes plus scale rows in pool order; any pending async spill of
        a slot is joined first."""
        for s in slots:
            self._wait_slot(s)
        return [tuple(np.stack([buf[s] for s in slots]) for buf in layer)
                for layer in self._bufs]

    def import_slots(self, layer_data, hashes: Sequence[int]
                     ) -> Optional[List[int]]:
        """Write wire-received page payloads into fresh slots — the
        receiving half of the prefill->decode handoff (ISSUE 12).
        `layer_data` mirrors export_slots' layout; `hashes` are the
        sender's per-slot content hashes, RE-VERIFIED here against the
        bytes actually written (content_hash is CRC-based, stable
        across processes) — a mismatch frees everything and raises
        ValueError rather than ever serving corrupted KV. Returns None
        when the tier cannot hold the whole payload (the caller then
        degrades to the recompute path: partial imports would leave an
        unconnectable record). The cross-host path — same-host
        transfers use adopt_slots (slot references, zero byte
        copies)."""
        n = len(hashes)
        if n == 0:
            return []
        if self.store is not None:
            slots = self.store.alloc(n, self.owner)
            if len(slots) < n:
                if slots:
                    self.store.release(slots, self.owner)
                self.dropped_pages += n
                if self.metrics is not None:
                    self.metrics.host_tier_drops.inc(n)
                return None
        else:
            if n > len(self._free):
                self.dropped_pages += n
                if self.metrics is not None:
                    self.metrics.host_tier_drops.inc(n)
                return None
            slots = self._free[:n]
            del self._free[:n]
        for layer_bufs, data in zip(self._bufs, layer_data):
            for buf, arr in zip(layer_bufs, data):
                buf[slots] = np.asarray(arr).astype(buf.dtype, copy=False)
        bad = []
        for j, s in enumerate(slots):
            h = self.content_hash(s)
            self._set_hash(s, h)
            if h != int(hashes[j]):
                bad.append(s)
        if bad:
            self.free_slots(slots)
            raise ValueError(
                f"handoff content-hash mismatch on {len(bad)} of {n} "
                f"pages (slots {bad}) — page bytes corrupted in "
                "transfer; refusing to serve them")
        self.spilled_pages += n
        if self.metrics is not None:
            self.metrics.offload_spill_pages.inc(n)
        return slots

    # --------------------------------- slot-reference transfer (ISSUE 14)

    def retag_out(self, slots: Sequence[int], to_owner: str) -> None:
        """Hand this engine's refs on `slots` to a transfer tag (the
        slot-reference handoff's extract half): pending spill copies
        are joined first so the reference never names half-written
        bytes, then ownership moves atomically in the store — no bytes
        touched."""
        for s in slots:
            self._wait_slot(s)
        self.store.retag(list(slots), self.owner, to_owner)

    def adopt_slots(self, slots: Sequence[int], gens: Sequence[int],
                    hashes: Sequence[int], from_owner: str
                    ) -> Optional[List[int]]:
        """Accept a slot-reference handoff: verify each slot's
        generation is current (a stale reference names recycled bytes —
        degrade to recompute, never serve) and RE-VERIFY the CRC
        content hash against the segment bytes (the import-verify
        contract of ISSUE 12, kept: corruption raises loudly), then
        move the refs from the transfer tag to this engine. ZERO page
        bytes move — the transfer is bookkeeping."""
        slots = [int(s) for s in slots]
        stale = [s for s, g in zip(slots, gens)
                 if self.store.generation(s) != int(g)]
        if stale:
            self.store.release(slots, from_owner)
            self.fallbacks += 1
            if self.metrics is not None:
                self.metrics.offload_recompute_fallbacks.inc()
            return None
        bad = [s for s, h in zip(slots, hashes)
               if self.content_hash(s) != int(h)]
        if bad:
            self.store.release(slots, from_owner)
            raise ValueError(
                f"handoff content-hash mismatch on {len(bad)} of "
                f"{len(slots)} store slots ({bad}) — segment bytes "
                "corrupted; refusing to serve them")
        self.store.retag(slots, from_owner, self.owner)
        return slots

    def free_slots(self, slots: Sequence[int]) -> None:
        """Return slots to the tier, bumping each slot's generation so
        stale staged transfers can never resolve. A slot with a spill
        copy still in flight is joined first — a freed (and possibly
        re-spilled) slot must never be written by a worker job from its
        previous tenancy. Store mode releases this engine's REFS: the
        slot is actually reclaimed only when no sibling, transfer, or
        index reference remains."""
        if self.store is not None:
            for s in slots:
                self._wait_slot(s)
            if slots:
                self.store.release(list(slots), self.owner)
            return
        for s in slots:
            self._wait_slot(s)
            if s not in self._hash:
                raise ValueError(f"double free of host slot {s}")
            del self._hash[s]
            h = self._prefix_slot.pop(s, None)
            if h is not None:               # dropped without promotion
                del self._prefix[h]
            self._gen[s] = self._gen.get(s, 0) + 1
            insort(self._free, s)

    def note_resume(self) -> None:
        self.resumes += 1
        if self.metrics is not None:
            self.metrics.offload_resumes.inc()

    def note_fallback(self) -> None:
        self.fallbacks += 1
        if self.metrics is not None:
            self.metrics.offload_recompute_fallbacks.inc()


def kv_pair_layout(n_kv_heads: int, head_dim: int, dtype) -> list:
    """What a dense-attention layer's page holds, as a runner names it
    to KVCachePool: K and V, each `[n_kv_heads, head_dim]` a token."""
    return [((int(n_kv_heads), int(head_dim)), dtype)] * 2


def _page_layout_of(runner) -> list:
    """A runner's page layout: its own word (`page_layout()`), else the
    (k, v) pair of its head geometry (duck-typed runners need not know
    the question)."""
    ask = getattr(runner, "page_layout", None)
    return ask() if ask is not None else kv_pair_layout(
        runner.n_kv_heads, runner.head_dim, runner.dtype)


def page_arrays(block_size: int, page_layout, kv_dtype: str = "fp32",
                model_axis: str = "model", row_pages: bool = False) -> list:
    """ONE page of one layer as a pool stores it, `[(what, shape, dtype,
    PartitionSpec of the pool's array), ...]`: the one description that
    KVCachePool allocates from (a leading `[num_blocks]` on each shape),
    that `page_bytes` counts, SharedKVStore mirrors and the auditor
    checks. `page_layout` is the runner's word: `[(trailing shape,
    dtype), ...]`, each array `[block_size, *trailing]` a page. Arrays
    of `[heads, head_dim]` split over `model_axis` in whole heads and
    come in every `kv_dtype` rung (int8 codes with one float32 scale per
    page per head, float8 pages, float32 pages with a per-page tag
    plane); any other layout (a latent layer's one array) comes in the
    stated dtype on one device only. `row_pages`: a (k, v) page is kept
    as its rows, `[block_size * heads, head_dim]` (key-major), which is
    whole tiles for any count of heads where `[block_size, heads,
    head_dim]` pads the heads to the tile (10 heads of 128 lanes would be
    allocated as 16); in the stated dtype or float8, on one device."""
    layout = [(tuple(int(n) for n in t), jnp.dtype(d))
              for t, d in page_layout]
    per_head = all(len(t) == 2 for t, _ in layout)
    if row_pages:
        if not (per_head and len(layout) == 2) or kv_dtype not in ("fp32",
                                                                   "fp8"):
            raise ValueError(
                "row pages are (k, v) pages of [heads, head_dim] in the "
                f"stated dtype or fp8; got {layout} in {kv_dtype!r}")
        store = jnp.dtype(jnp.float8_e4m3fn) if kv_dtype == "fp8" else None
        return [(what + " rows", (block_size * t[0], t[1]), store or d,
                 PartitionSpec()) for what, (t, d) in zip("kv", layout)]
    if kv_dtype != "fp32" and not (per_head and len(layout) == 2):
        raise ValueError(
            f"kv_dtype={kv_dtype!r} is a rung of (k, v) pages of [heads, "
            f"head_dim]; the page layout {layout} comes in the runner's "
            "stated dtype only")
    names = ("k", "v") if per_head and len(layout) == 2 else tuple(
        f"page array {j}" for j in range(len(layout)))
    store = {"int8": jnp.dtype(jnp.int8),
             "fp8": jnp.dtype(jnp.float8_e4m3fn)}.get(kv_dtype)
    pages = PartitionSpec(None, None, model_axis, None)
    arrays = [(what, (block_size,) + t, store or d,
               pages if per_head else PartitionSpec())
              for what, (t, d) in zip(names, layout)]
    if kv_dtype == "int8":
        # the scale pool shares the pool's page geometry and shards
        # along the SAME kv-head axis: each model shard dequantizes
        # its own head slice with its own scales
        arrays += [(f"{what}-scale: one scale per page per kv-head",
                    (t[0],), jnp.dtype(jnp.float32),
                    PartitionSpec(None, model_axis))
                   for what, (t, _) in zip(names, layout)]
    elif kv_dtype == "mixed":
        # mixed-precision tenants (ISSUE 15): fp32 storage + a
        # per-page tag plane steering the write path — one plane
        # per layer tuple so the pools stay a uniform pytree
        # through every jitted step (the planes are kept identical;
        # tag_pages updates all of them). The tag plane has no head
        # axis — replicated per shard
        arrays.append(("tag plane", (), jnp.dtype(bool), PartitionSpec()))
    return arrays


class WindowGroup:
    """The host side of a page GROUP whose layers keep only a sequence's
    last `window` positions (sliding-window attention): its own pages
    (page 0 is its scratch), its own free list, and per decode slot the
    pages that hold the positions still inside the window, oldest first.

    The group is sized by the program, not by an option: every one of
    `slots` sequences may hold the pages of `window - 1 + span` consecutive
    positions (`span`: the most positions one launch writes, 1 for a plain
    decode step), which touch at most `pages_per_seq = ceil((window - 1 +
    span) / block_size) + 1` pages. So `cover` never fails and admission
    has nothing to count here: a slot is a sequence's whole claim.

    `cover(slot, lo, hi)` makes the slot hold exactly the pages of
    positions [lo, hi): pages that fell behind `lo` go back to the free
    list (the next `cover` of any slot may hand them out), pages up to
    `hi` are taken. `row(slot)` is what the device sees: the live pages
    only, then the index of the first one's page in the sequence, so that
    a kernel's positions are the table's (`position - base * block_size`)
    and its lower bound is a position inside the first live page."""

    def __init__(self, layers: int, window: int, block_size: int,
                 slots: int, span: int = 1):
        self.layers, self.window = int(layers), int(window)
        self.block_size = int(block_size)
        self.pages_per_seq = -(-(self.window - 1 + max(1, int(span)))
                               // self.block_size) + 1
        self.num_blocks = 1 + int(slots) * self.pages_per_seq
        self._free = list(range(1, self.num_blocks))   # a heap: lowest first
        self._held: Dict[int, Tuple[int, List[int]]] = {}
        self.pages_taken = 0          # ever handed out
        self.pages_returned = 0       # ever given back
        # a slot's whole ring: taken by a slot that held nothing (a new
        # request's first pages), released with the slot at its request's end
        self.rings_taken = 0
        self.rings_released = 0
        # summed over the rows of every launch `extend_tables` built: the
        # pages a layer held for the row, and what a cache of its whole
        # context would have held
        self.held_page_rows = 0
        self.whole_context_page_rows = 0

    @property
    def width(self) -> int:
        """Columns a row adds to a block table: the pages, then the base."""
        return self.pages_per_seq + 1

    @property
    def num_held(self) -> int:
        return self.num_blocks - 1 - len(self._free)

    def held(self, slot: int) -> int:
        return len(self._held.get(slot, (0, ()))[1])

    def cover(self, slot: int, lo: int, hi: int) -> None:
        bs = self.block_size
        first, last = max(0, lo) // bs, (hi - 1) // bs
        if slot not in self._held:
            self.rings_taken += 1
        base, pages = self._held.get(slot, (first, []))
        if pages and not base <= first <= base + len(pages):
            # not a continuation (a slot's new holder, a restart): all go
            self._give_back(pages)
            base, pages = first, []
        drop = first - base
        self._give_back(pages[:drop])
        pages = pages[drop:]
        need = last + 1 - first - len(pages)
        if need > 0:
            if len(pages) + need > self.pages_per_seq:
                raise ValueError(
                    f"positions [{lo}, {hi}) span more pages than a window "
                    f"sequence holds ({self.pages_per_seq})")
            pages = pages + [heapq.heappop(self._free) for _ in range(need)]
            self.pages_taken += need
        self._held[slot] = (first, pages)

    def _give_back(self, pages) -> None:
        for page in pages:
            heapq.heappush(self._free, page)
        self.pages_returned += len(pages)

    def release(self, slot: int) -> None:
        """Everything the slot holds goes back (its request ended or was
        preempted)."""
        if slot in self._held:
            self.rings_released += 1
        self._give_back(self._held.pop(slot, (0, []))[1])

    def row(self, slot: int) -> np.ndarray:
        """[width] int32: the slot's live pages, scratch after them, then
        the base (all scratch and 0 for a slot that holds nothing)."""
        out = np.zeros((self.width,), np.int32)
        if slot in self._held:
            base, pages = self._held[slot]
            out[:len(pages)] = pages
            out[-1] = base
        return out

    def advance(self, slot: int, end: int) -> Tuple[np.ndarray, np.ndarray]:
        """A prefill chunk that ends at position `end`: the slot's row as
        the chunk finds it, and as it leaves it, holding the last `window -
        1` positions before `end` (pages behind them are given back here,
        before the launch that stops reading them: it reads `before`
        first)."""
        before = self.row(slot)
        self.cover(slot, end - (self.window - 1), end)
        return before, self.row(slot)

    def extend_tables(self, tables: np.ndarray, rows) -> np.ndarray:
        """A launch's block tables with this group's columns appended.
        `rows`: (slot, start, end) of each live row, which feeds positions
        [start, end): the slot is made to cover what those rows read and
        write, [start - (window - 1), end). Idempotent (a retried launch
        builds its batch again)."""
        out = np.zeros((tables.shape[0], tables.shape[1] + self.width),
                       np.int32)
        out[:, :tables.shape[1]] = tables
        for slot, start, end in rows:
            self.cover(slot, start - (self.window - 1), end)
            out[slot, tables.shape[1]:] = self.row(slot)
            self.held_page_rows += self.held(slot)
            self.whole_context_page_rows += -(-end // self.block_size)
        return out

    def gauges(self) -> Dict[str, float]:
        """Sums over launches, so a window's delta divides: the pages a
        layer held for the rows of every decode launch, the pages a cache
        of the whole context would have held for the same rows, the pages
        given back, and the slots' whole rings taken and released."""
        return {"window_pages_held": self.held_page_rows,
                "window_pages_whole_context": self.whole_context_page_rows,
                "window_pages_returned": self.pages_returned,
                "window_rings_taken": self.rings_taken,
                "window_rings_released": self.rings_released}

    def check_no_leaks(self) -> bool:
        return not self._held and len(self._free) == self.num_blocks - 1


class KVCachePool:
    """The device-side page pool: per-layer (k, v) pools + the allocator.

    `pools` are plain jnp arrays threaded through the jitted model steps
    (functional update: the runner returns new pools, the engine writes
    them back here). Block tables live host-side as python lists per
    sequence; `pad_table` builds the fixed-shape device operand.

    With a `mesh` (ISSUE 7) the pools are BORN sharded along the kv-head
    axis over the mesh's model axis: each model shard holds every page's
    slice of n_kv_heads/tp heads, so per-shard pool HBM is the single-
    device pool / tp — the capacity win TP serving exists for. The
    allocator, block tables, and PrefixCache are deliberately mesh-blind:
    one page id names the same page on every shard, so all refcount /
    COW / eviction logic is identical to the single-device engine.
    """

    def __init__(self, num_layers: int, num_blocks: int, block_size: int,
                 n_kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None, dtype=jnp.float32,
                 mesh=None, model_axis: str = "model",
                 kv_dtype: str = "fp32", page_layout=None,
                 state_layout=None, state_slots: int = 0,
                 row_pages: bool = False, window=None):
        """`page_layout` is the runner's word on what a layer's page
        holds: a list of `(trailing shape, dtype)`, one per array, the
        same for every layer; each array is `[num_blocks, block_size,
        *trailing]`. Left out, it is the (k, v) pair of `[n_kv_heads,
        head_dim]` (`kv_pair_layout`). `page_arrays` says what the pool
        stores for a layout in the rung `kv_dtype` names, and which
        layouts come in which rungs.

        `state_layout` is its word on the layers that keep a fixed
        RECURRENT STATE per sequence and no pages: `(layers, [(trailing
        shape, dtype), ...])`; each array is `[state_slots, *trailing]`,
        indexed by the decode slot the scheduler hands a request (the
        engine asks for `max_batch_size`: a dead row of a decode batch
        writes back what it read at its own row, so no slot is scratch).
        `num_layers` then counts the layers that page. With states,
        `pools` (what the runner's steps take and return) is the pair
        `(pages, states)`.

        `window`, `(layers, window length, span)`, is its word on a second
        page GROUP (`WindowGroup`): that many layers keep only a
        sequence's last `window` positions, in pages of this pool's layout
        with their own table columns and free list, sized here for
        `state_slots` sequences (with or without state layers beside it).
        `num_layers` and `num_blocks` are then
        the "full" group's, whose pages grow with the context under the
        allocator as ever; `pools` is the triple `(pages, states,
        window pages)`. `row_pages`: see `page_arrays`."""
        self.num_layers = num_layers
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.dtype = dtype
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f"kv_dtype={kv_dtype!r}; expected one of "
                             f"{KV_DTYPES}")
        if kv_dtype in ("fp8", "mixed"):
            require_fp8(f"KVCachePool(kv_dtype={kv_dtype!r})")
        self.kv_dtype = kv_dtype
        self.mesh = mesh
        self.model_axis = model_axis
        self.tp_size = 1
        self.allocator = BlockAllocator(num_blocks)
        self.prefix_cache: Optional[PrefixCache] = None
        self.host_tier: Optional[HostKVTier] = None
        self.page_layout = [(tuple(t), jnp.dtype(d)) for t, d in (
            page_layout or kv_pair_layout(n_kv_heads, head_dim, dtype))]
        self.page_arrays = page_arrays(block_size, self.page_layout,
                                       kv_dtype, model_axis,
                                       row_pages=row_pages)
        # the head geometry of (k, v) pages, None for any other layout
        per_head = all(len(t) == 2 for t, _ in self.page_layout)
        self.n_kv_heads, self.head_dim = (
            self.page_layout[0][0] if per_head else (None, None))
        layer = [((num_blocks,) + shape, dt, spec)
                 for _, shape, dt, spec in self.page_arrays]
        if mesh is not None:
            self.tp_size = int(mesh.shape[model_axis])
            if self.n_kv_heads is None:
                raise ValueError(
                    f"the page layout {self.page_layout} has no head axis "
                    "to split over a mesh")
            if self.n_kv_heads % self.tp_size:
                raise ValueError(
                    f"n_kv_heads={self.n_kv_heads} is not divisible by the "
                    f"model-axis degree {self.tp_size}: the paged pools "
                    "shard in whole kv-heads (GQA rule)")

            def zeros(shp, dt, spec):
                return jax.device_put(jnp.zeros(shp, dt),
                                      NamedSharding(mesh, spec))
        else:
            def zeros(shp, dt, spec):
                return jnp.zeros(shp, dt)

        self.state_layers, self.state_arrays = 0, []
        if state_layout is not None:
            if mesh is not None:
                raise ValueError("recurrent state over a mesh is not built")
            self.state_layers = int(state_layout[0])
            self.state_arrays = [(tuple(int(n) for n in t), jnp.dtype(d))
                                 for t, d in state_layout[1]]
        # slots: a row of every state array, a ring of the window group
        self.state_slots = int(state_slots) if (
            self.state_layers or window is not None) else 0
        # the pages themselves: zero fills dispatched here, which finish
        # on the device behind whatever comes next
        with _prof.always_span("kv_pool.alloc", num_blocks=num_blocks):
            self.page_pools = [tuple(zeros(*a) for a in layer)
                               for _ in range(num_layers)]
            self.state_pools = []
            if self.state_layers:
                with _prof.always_span("state_pool.alloc",
                                       slots=self.state_slots):
                    self.state_pools = [
                        tuple(jnp.zeros((self.state_slots,) + t, d)
                              for t, d in self.state_arrays)
                        for _ in range(self.state_layers)]
            self.window: Optional[WindowGroup] = None
            self.window_pools = []
            if window is not None:
                if mesh is not None:
                    raise ValueError("a window group is built for one "
                                     "device")
                layers, length, span = window
                self.window = WindowGroup(layers, length, block_size,
                                          self.state_slots, span)
                with _prof.always_span("window_pool.alloc",
                                       num_blocks=self.window.num_blocks):
                    self.window_pools = [
                        tuple(jnp.zeros((self.window.num_blocks,) + shape, dt)
                              for _, shape, dt, _ in self.page_arrays)
                        for _ in range(self.window.layers)]

    @property
    def pools(self):
        """What the runner's steps take and return: the layers' page
        arrays, or with recurrent state the pair (pages, states), or with
        a window group besides the triple (pages, states, window pages)."""
        if self.window is not None:
            return (self.page_pools, self.state_pools, self.window_pools)
        if self.state_layers:
            return (self.page_pools, self.state_pools)
        return self.page_pools

    @pools.setter
    def pools(self, value):
        if self.window is not None:
            pages, states, ring = value
            self.page_pools, self.state_pools, self.window_pools = (
                list(pages), list(states), list(ring))
        elif self.state_layers:
            pages, states = value
            self.page_pools, self.state_pools = list(pages), list(states)
        else:
            self.page_pools = value

    @classmethod
    def for_runner(cls, runner, num_blocks: int, mesh=None,
                   model_axis: str = "model", state_slots: int = 1,
                   window_span: int = 1) -> "KVCachePool":
        """The pool a runner's steps read and write: the geometry is the
        runner's (the page layout it names, in its kv_dtype rung; where
        it names a state layout too, `state_slots` slots of it for the
        layers that keep a state, and pages for the others only). A
        runner that names page GROUPS (`page_groups()`: `{"full": layers,
        "window": (layers, window length)}`) gets `num_blocks` pages for
        the layers that keep their whole context and a `WindowGroup` for
        `state_slots` sequences that write `window_span` positions a
        launch; one that names none gets one table for every paged layer,
        as ever."""
        ask = getattr(runner, "state_layout", None)
        states = ask() if ask is not None else None
        ask = getattr(runner, "page_groups", None)
        groups = ask() if ask is not None else None
        if groups is None:
            paged = runner.num_layers - (states[0] if states else 0)
            window = None
        else:
            paged = groups["full"]
            window = (*groups["window"], window_span)
        return cls(paged, num_blocks, runner.block_size,
                   dtype=runner.dtype, mesh=mesh, model_axis=model_axis,
                   kv_dtype=getattr(runner, "kv_dtype", "fp32"),
                   page_layout=_page_layout_of(runner),
                   state_layout=states, state_slots=state_slots,
                   row_pages=bool(getattr(runner, "ROW_PAGES", False)),
                   window=window)

    # -------------------------------- per-request kv-dtype tags (ISSUE 15)

    def native_kv_tag(self) -> str:
        """The kv_dtype a request gets when it does not override: the
        pool's own storage rung, except "mixed" pools default to fp32
        (their storage width — fp8 is the opt-in tenant override)."""
        return "fp32" if self.kv_dtype == "mixed" else self.kv_dtype

    def chain_seed(self, tag: Optional[str]) -> int:
        """Prefix-chain seed for a tenant's kv-dtype tag: the default
        tag keeps the historical seed (host-tier indexes, journals and
        handoffs stay compatible); any OTHER tag folds itself in, so
        two tenants of different precision can NEVER share a prefix
        page — their KV bytes for equal tokens differ."""
        if tag is None or tag == self.native_kv_tag():
            return _CHAIN_SEED
        return hash((_CHAIN_SEED, tag))

    def tag_pages(self, pages: Sequence[int], tag: str) -> None:
        """Stamp freshly-allocated pages with their owner's effective
        kv_dtype (the auditor's bijection invariant reads the tags).
        On a "mixed" pool this also flips the device-side tag plane
        every layer tuple carries, which is what steers the jitted
        write path — fp8-tagged pages get the fp8 round-trip cast."""
        if not pages:
            return
        for p in pages:
            self.allocator._tags[p] = tag
        if self.kv_dtype == "mixed":
            idx = jnp.asarray(list(pages), jnp.int32)
            flag = tag == "fp8"
            self.page_pools = [(k, v, t.at[idx].set(flag))
                               for (k, v, t) in self.page_pools]

    def page_tag(self, page: int) -> Optional[str]:
        return self.allocator._tags.get(page)

    def enable_prefix_cache(self) -> PrefixCache:
        """Turn on shared-prefix page caching (idempotent)."""
        if self.prefix_cache is None:
            self.prefix_cache = PrefixCache(self)
            self.allocator.evictor = self.prefix_cache
            if self.host_tier is not None:
                self.prefix_cache.evict_hook = self.host_tier.on_evict
        return self.prefix_cache

    def enable_host_tier(self, max_pages: int, metrics=None,
                         async_spill: bool = False, store=None,
                         owner: str = "engine") -> HostKVTier:
        """Turn on the host-RAM offload tier (ISSUE 10, idempotent):
        preemption spills exclusively-owned pages to pinned host
        buffers, and prefix-cache eviction demotes cached pages through
        evict_hook instead of dropping them. `async_spill` (ISSUE 11
        satellite) moves the blocking device->host copy of each spill
        onto a worker thread. `store` (ISSUE 14) backs the tier with a
        host-wide SharedKVStore under this engine's `owner` tag instead
        of private buffers — spills publish tier-wide, admission
        matches against every replica's demotions, and handoffs move
        slot references instead of bytes."""
        if self.host_tier is None:
            self.host_tier = HostKVTier(self, max_pages, metrics=metrics,
                                        async_spill=async_spill,
                                        store=store, owner=owner)
            if self.prefix_cache is not None:
                self.prefix_cache.evict_hook = self.host_tier.on_evict
        return self.host_tier

    def gather_pages(self, pages: Sequence[int]) -> List[Tuple]:
        """DEVICE-side gather of the named pages across every layer's
        pool arrays — dispatches asynchronously and returns the jnp
        arrays without materializing them. The arrays are a functional
        snapshot: later pool writes produce new arrays, so a worker
        thread can np.asarray these at leisure even after the pages are
        freed and reused (the threaded-spill foundation, ISSUE 11)."""
        idx = jnp.asarray(list(pages), jnp.int32)
        return [tuple(a[idx] for a in layer) for layer in self.page_pools]

    def read_pages(self, pages: Sequence[int]
                   ) -> List[Tuple[np.ndarray, ...]]:
        """Host copies of the named device pages across every layer's
        pool arrays — the device->host half of a spill. One gather per
        pool array (sharded pools gather per shard under GSPMD), then
        one blocking transfer."""
        return [tuple(np.asarray(a) for a in layer)
                for layer in self.gather_pages(pages)]

    def write_pages(self, pages: Sequence[int], layer_data) -> None:
        """Scatter staged page contents into the named device pages —
        the fence half of a page-in (ISSUE 10). `layer_data` mirrors
        `pools`: per layer a tuple of [len(pages), *page_shape] arrays
        (device-staged by the engine via runner.stage_host_pages, or
        plain host arrays). Functional update like every other pool
        write: jax dispatches the scatters asynchronously, so the call
        itself never blocks."""
        idx = jnp.asarray(list(pages), jnp.int32)
        self.page_pools = [
            tuple(a.at[idx].set(jnp.asarray(d).astype(a.dtype))
                  for a, d in zip(layer, data))
            for layer, data in zip(self.page_pools, layer_data)]

    def blocks_for_tokens(self, n_tokens: int) -> int:
        """Pages needed to hold n_tokens KV entries."""
        return max(1, -(-n_tokens // self.block_size))

    def pad_table(self, pages: List[int], max_pages: int) -> List[int]:
        """Fixed-width table row; unused entries point at the scratch page
        (their keys are masked by pos, never read)."""
        if len(pages) > max_pages:
            raise ValueError(f"sequence needs {len(pages)} pages > "
                             f"max_pages_per_seq={max_pages}")
        return list(pages) + [SCRATCH_PAGE] * (max_pages - len(pages))

    def copy_page(self, src: int, dst: int) -> None:
        """Device-side page copy across every layer's pools — the data
        move behind a copy-on-write fork. Pages are copied as OPAQUE
        blocks: on an int8 pool the layer tuples carry the scale pools
        too ([num_blocks, n_kv] — page-indexed like the code pools), so
        a fork carries its source's quantization state verbatim."""
        self.page_pools = [tuple(a.at[dst].set(a[src]) for a in layer)
                           for layer in self.page_pools]

    def utilization(self) -> float:
        a = self.allocator
        return 1.0 - a.num_free / a.num_usable

    def gauges(self, running: int) -> Dict[str, float]:
        """What this pool's cache mechanisms show at a step's end, under
        the names an engine's snapshot gives them (the engine declares
        whatever is here; a pool of pages alone has nothing to add).
        `running`: the sequences that run, each of which holds its slot,
        and so its state."""
        out: Dict[str, float] = {}
        if self.state_layers:
            out["state_slots_live"] = running
        if self.window is not None:
            out.update(self.window.gauges())
        return out

    def page_bytes(self) -> int:
        """HBM bytes ONE page actually occupies across all layers and
        all of a layer's arrays, as allocated — quantized code bytes
        PLUS scale bytes on an int8 pool (ISSUE 9: the byte accounting
        is honest, not derived from the logical dtype's itemsize), a
        mixed pool's tag plane, a latent page's lanes padded to whole
        tiles."""
        return self.num_layers * sum(
            int(np.prod(shape)) * dt.itemsize
            for _, shape, dt, _ in self.page_arrays)

    def unquantized_page_bytes(self) -> int:
        """What the same page would cost stored at the layout's own
        dtypes — the denominator of the quantization win."""
        return self.num_layers * self.block_size * sum(
            int(np.prod(t)) * dt.itemsize for t, dt in self.page_layout)

    def kv_bytes_reduction_x(self) -> float:
        """Per-page byte reduction vs the unquantized pool, scale bytes
        counted (1.0 on fp32 pools). Because page count is fixed, this
        is also the factor by which a fixed HBM budget holds more pages
        — i.e. more concurrent sessions per pool."""
        return self.unquantized_page_bytes() / self.page_bytes()

    def memory_bytes(self) -> int:
        """Total logical pool bytes across the whole mesh (the single-
        device number — sharding never changes it). Counts what the
        pools actually store: int8 code bytes + scale bytes on a
        quantized pool. Recurrent state is counted by `state_bytes`."""
        return self.num_blocks * self.page_bytes()

    def state_bytes(self) -> int:
        """Bytes of the recurrent-state arrays, every slot and layer (0
        where every layer pages)."""
        return self.state_slots * self.state_layers * sum(
            int(np.prod(t)) * d.itemsize for t, d in self.state_arrays)

    def window_bytes(self) -> int:
        """Bytes of the window group's pages, every layer (0 without)."""
        if self.window is None:
            return 0
        return self.window.num_blocks * self.window.layers * sum(
            int(np.prod(shape)) * dt.itemsize
            for _, shape, dt, _ in self.page_arrays)

    def per_shard_memory_bytes(self) -> int:
        """Pool bytes ONE model shard holds: total / tp (each shard
        stores its n_kv/tp kv-head slice of every page AND of every
        scale row) — the ISSUE 7 capacity acceptance number."""
        return self.memory_bytes() // self.tp_size


class SequenceKV:
    """Host-side per-sequence cache state: the owned pages and how many
    token positions are live. Appending crosses page boundaries lazily —
    `pages_short()` reports the deficit the scheduler must fund (or
    preempt to fund) before the next decode step.

    With the prefix cache on, the leading pages may be SHARED (mapped
    from the cache at admission); `registered_pages`/`hash_chain` track
    how far this sequence's full pages have been pushed into the cache,
    and `ensure_writable` copy-on-write forks any shared page before the
    runner would write through it."""

    def __init__(self, pool: KVCachePool, kv_tag: Optional[str] = None):
        self.pool = pool
        # effective kv_dtype of this sequence's pages (ISSUE 15):
        # every page this sequence allocates is stamped with it — the
        # per-request override on "mixed" pools, the pool's own rung
        # otherwise
        self.kv_tag = kv_tag or pool.native_kv_tag()
        self.pages: List[int] = []
        self.num_tokens = 0
        self.registered_pages = 0          # leading pages already cached
        self.hash_chain: List[int] = []    # chain hash per registered page
        # `pages` as int32 for the step's block table (`pages_array`):
        # the list mirrored, how much of it, and `_rewrites` then; every
        # change to `pages` but an append counts a rewrite
        self._rewrites = 0
        self._row = np.zeros((0,), np.int32)
        self._row_of = (None, 0, 0)

    def pages_array(self) -> np.ndarray:
        """`pages` as an int32 array (a view, valid until the next call):
        a step copies it into its block table. Only the pages appended
        since the last call are converted; a 1000-page list costs 30 us
        to convert whole, a full batch of them more than the rest of a
        step's host work."""
        pages, n = self.pages, len(self.pages)
        of, have, rewrites = self._row_of
        if of is not pages or rewrites != self._rewrites or n < have:
            have = 0
        if n > len(self._row):
            row = np.zeros((max(2 * n, 64),), np.int32)
            row[:have] = self._row[:have]
            self._row = row
        if n > have:
            self._row[have:n] = pages[have:n]
        self._row_of = (pages, n, self._rewrites)
        return self._row[:n]

    def adopt_prefix(self, matched: List[Tuple[int, int]],
                     block_size: int) -> None:
        """Map an ALREADY-ACQUIRED PrefixCache match as this sequence's
        leading pages: their KV is live, so prefill starts after them."""
        self.pages = [page for _, page in matched]
        self.hash_chain = [h for h, _ in matched]
        self.registered_pages = len(matched)
        self.num_tokens = len(matched) * block_size

    def pages_short(self, upcoming_tokens: int = 1) -> int:
        need = self.pool.blocks_for_tokens(self.num_tokens + upcoming_tokens)
        return max(0, need - len(self.pages))

    def grow(self, upcoming_tokens: int = 1) -> None:
        short = self.pages_short(upcoming_tokens)
        if short:
            fresh = self.pool.allocator.alloc(short)
            self.pages.extend(fresh)
            self.pool.tag_pages(fresh, self.kv_tag)   # tagged at alloc

    def truncate(self, num_tokens: int) -> int:
        """Roll back over-committed tail state (ISSUE 5 + 6): keep only
        the pages needed to cover ``num_tokens`` live positions and
        decref the rest. Two callers grow a sequence past its accepted
        context up front and return the unused tail here: the
        speculative verify step (pages grown for a rejected `k+1`-token
        span — a speculated page must never outlive its rejection) and
        the multi-step decode horizon (pages pre-committed for `s`
        future tokens, rolled back when non-finite logits cut the
        horizon short; a request that merely STOPS mid-horizon instead
        releases everything through the normal finish path). The
        auditor's over-provision check pins both. Dropped pages are
        always private (freshly grown for the span, never registered or
        shared), so the decref sends them straight back to the free
        list. Returns the number of pages dropped."""
        keep = self.pool.blocks_for_tokens(max(num_tokens, 1))
        if keep < self.registered_pages:
            raise ValueError(
                f"truncate({num_tokens}) would drop registered page "
                f"{keep} < {self.registered_pages} — cached pages cannot "
                "be speculative")
        dropped = self.pages[keep:]
        if dropped:
            del self.pages[keep:]
            self._rewrites += 1
            self.pool.allocator.free(dropped)   # decref each
        self.num_tokens = num_tokens
        return len(dropped)

    def ensure_writable(self, start_tok: int, end_tok: int) -> int:
        """Copy-on-write guard for a write covering token positions
        [start_tok, end_tok): any touched page with refcount > 1 (shared
        with another sequence or pinned by the prefix cache) is forked —
        fresh page, KV contents copied, block-table entry swapped, old
        reference dropped. Returns the number of pages forked."""
        if end_tok <= start_tok:
            return 0
        alloc = self.pool.allocator
        bs = self.pool.block_size
        forked = 0
        for idx in range(start_tok // bs, (end_tok - 1) // bs + 1):
            page = self.pages[idx]
            if alloc.refcount(page) > 1:
                new = alloc.alloc(1)[0]
                self.pool.copy_page(page, new)
                self.pool.tag_pages([new], self.kv_tag)
                alloc.decref(page)
                self.pages[idx] = new
                self._rewrites += 1
                # the fork is private and its content will diverge: it is
                # no longer covered by this sequence's registered chain
                if idx < self.registered_pages:
                    self.registered_pages = idx
                    del self.hash_chain[idx:]
                forked += 1
        return forked

    def release(self) -> None:
        if self.pages:
            self.pool.allocator.free(self.pages)   # decref each
        self.pages = []
        self.num_tokens = 0
        self.registered_pages = 0
        self.hash_chain = []
