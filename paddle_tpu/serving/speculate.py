"""Draft proposal for speculative decoding (ISSUE 5 / ISSUE 18).

Reference: the serving-side speculation line in PAPERS.md — SpecInfer's
draft-and-verify loop and vLLM's n-gram "prompt lookup" speculator. A
second draft model is the classic proposer, but for a serving stack the
zero-cost variant is to mine the request's OWN token stream: if the
current suffix n-gram occurred earlier in the context (prompt or
generated output), propose the tokens that followed it. On
repetition-heavy workloads — extraction, code, templated answers, any
model that quotes its prompt — the proposals hit often enough that one
fused verify launch (scoring all k+1 positions at once) replaces
several per-token decode launches.

ISSUE 18 adds the rest of the ladder:

* ``NgramProposer`` keeps an **incremental suffix index** per request
  (n-gram -> most recent start), so the per-step cost is O(new tokens)
  instead of the old O(len(ctx) * n) right-to-left rescan — long
  repetition-heavy streams stop paying quadratic host time. A bounded
  ``scan_window`` knob covers the stateless path.
* ``propose_chain``: an optimistic s*(k+1)-1 token continuation the
  fused verify-in-scan slices per horizon step (engine
  ``_launch_spec_horizon``).
* ``AdaptiveK``: per-request EWMA over accepted/proposed, mapping the
  acceptance rate into k in [0, num_speculative_tokens] — cold requests
  stop paying dead verify positions.
* ``DraftModelProposer``: the model-based rung — a small runner (or an
  int8 "shadow" of the target via ``shadow_runner``) with its own paged
  pool of the same geometry, proposing by catch-up prefill + one greedy
  ``decode_multi`` chain (two host syncs per proposal, not one per
  token).

Every proposer is draft-only: token-exactness vs ``naive_generate``
never depends on WHAT is proposed, only that verify accepts exactly the
tokens the target model would have produced.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple


class NgramProposer:
    """Prompt-lookup draft proposer: match the context's trailing n-gram
    against its own history and propose the continuation.

    proposer = NgramProposer(max_ngram=3, min_ngram=1)
    draft = proposer.propose(context_tokens, max_k)   # [] when no match

    Matching tries the LONGEST suffix n-gram first (more context = higher
    -precision proposals) and, per length, the MOST RECENT earlier
    occurrence (recency beats frequency for self-repetitive streams).

    With a ``request_id`` the proposer maintains an incremental suffix
    index (n-gram tuple -> latest start position) that grows by the
    tokens appended since the last call — O(appended * n_grams) per
    step. The index is advisory: a stale entry (the engine rolled a
    request back behind our spot-check) can only degrade proposal
    quality, never correctness, because verify re-derives every accepted
    token from the target model. Without a ``request_id`` the original
    stateless scan runs, bounded by ``scan_window`` when set.
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1,
                 scan_window: Optional[int] = None):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(
                f"need 1 <= min_ngram({min_ngram}) <= max_ngram({max_ngram})")
        if scan_window is not None and scan_window < 1:
            raise ValueError(f"scan_window must be >= 1, got {scan_window}")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram
        self.scan_window = scan_window
        # request_id -> {"len": indexed prefix length, "tail": last few
        # indexed tokens (divergence spot-check), "maps": {n: {gram: j}}}
        self._index: Dict[str, dict] = {}

    # ------------------------------------------------ incremental index

    def _state(self, request_id: str) -> dict:
        st = self._index.get(request_id)
        if st is None:
            st = {"len": 0, "tail": [],
                  "maps": {n: {} for n in
                           range(self.min_ngram, self.max_ngram + 1)}}
            self._index[request_id] = st
        return st

    def _extend_index(self, st: dict, ctx: List[int]) -> None:
        """Index every n-gram occurrence that a suffix lookup at context
        length len(ctx) may use: starts j with j + n <= len(ctx) - 1
        (strictly before the final position, so the trailing suffix
        never matches itself). Overwriting keeps the most recent j."""
        L = len(ctx)
        if L < st["len"] or st["tail"] != ctx[max(0, st["len"] - 8):
                                              st["len"]]:
            # rollback / divergence (NaN truncation, restore): rebuild
            st["len"] = 0
            for m in st["maps"].values():
                m.clear()
        for n, grams in st["maps"].items():
            lo = max(0, st["len"] - n)      # starts not yet indexed
            for j in range(lo, L - n):
                grams[tuple(ctx[j:j + n])] = j
        st["len"] = L
        st["tail"] = ctx[max(0, L - 8):L]

    def release(self, request_id: str) -> None:
        """Drop a finished request's suffix index."""
        self._index.pop(request_id, None)

    # ---------------------------------------------------------- propose

    def propose(self, context: Sequence[int], max_k: int,
                request_id: Optional[str] = None) -> List[int]:
        """Up to ``max_k`` draft tokens continuing ``context``, or []."""
        if max_k <= 0:
            return []
        ctx = list(map(int, context))
        n_hi = min(self.max_ngram, len(ctx) - 1)
        if request_id is not None:
            st = self._state(request_id)
            self._extend_index(st, ctx)
            for n in range(n_hi, self.min_ngram - 1, -1):
                j = st["maps"][n].get(tuple(ctx[-n:]))
                if j is not None:
                    return ctx[j + n:j + n + max_k]
            return []
        lo_bound = (0 if self.scan_window is None
                    else max(0, len(ctx) - self.scan_window))
        for n in range(n_hi, self.min_ngram - 1, -1):
            suffix = ctx[-n:]
            # most recent earlier occurrence: scan right-to-left, ending
            # strictly before the suffix itself
            for j in range(len(ctx) - n - 1, lo_bound - 1, -1):
                if ctx[j:j + n] == suffix:
                    return ctx[j + n:j + n + max_k]
        return []

    def propose_chain(self, context: Sequence[int], length: int,
                      request_id: Optional[str] = None) -> List[int]:
        """An optimistic continuation of up to ``length`` tokens for the
        fused verify-in-scan (sliced per horizon step). A single lookup
        ends at the context's edge (the mined run can't be longer than
        what follows the match), so the chain SELF-EXTENDS: re-match the
        suffix of context + drafts-so-far until the horizon is covered
        or the stream stops repeating. On a truly periodic stream this
        fills the whole horizon; the extension lookups run the stateless
        scan so the per-request index never learns virtual tokens."""
        if length <= 0:
            return []
        ctx = list(map(int, context))
        out = self.propose(ctx, length, request_id=request_id)
        while out and len(out) < length:
            more = self.propose(ctx + out, length - len(out))
            if not more:
                break
            out.extend(more)
        return out[:length]


class AdaptiveK:
    """Per-request acceptance-rate-adaptive draft length (ISSUE 18).

    k(req) = clamp(round(ewma_accept_rate * k_max), 0, k_max), where the
    EWMA folds each verify outcome accepted/proposed in with weight
    ``alpha``. Starts optimistic (rate 1.0 -> k_max) so warm streams pay
    nothing; a run of rejections drives k monotonically to 0, and dead
    verify positions stop being proposed at all. Draft-only state: it
    shapes proposals, never accepted tokens.
    """

    def __init__(self, k_max: int, alpha: float = 0.5):
        if k_max < 0:
            raise ValueError(f"k_max must be >= 0, got {k_max}")
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1], got {alpha}")
        self.k_max = k_max
        self.alpha = alpha
        self._ewma: Dict[str, float] = {}

    def k_for(self, request_id: str) -> int:
        rate = self._ewma.get(request_id, 1.0)
        return max(0, min(self.k_max, int(round(rate * self.k_max))))

    def update(self, request_id: str, proposed: int, accepted: int) -> None:
        """Fold one verify outcome in. No-op when nothing was proposed
        (a zero-draft step says nothing about acceptance)."""
        if proposed <= 0:
            return
        rate = min(1.0, max(0.0, accepted / proposed))
        prev = self._ewma.get(request_id, 1.0)
        self._ewma[request_id] = (1.0 - self.alpha) * prev \
            + self.alpha * rate

    def release(self, request_id: str) -> None:
        self._ewma.pop(request_id, None)


def shadow_runner(target, weight_dtype: str = "int8"):
    """A weight-quantized shadow of ``target`` for the draft rung: same
    weights, same paged-pool geometry, own params dict and jit cache.
    Quantizes every 2-D non-embedding ``.weight`` down the ISSUE 19
    weight ladder — int8 per-channel, int4 packed + group scales, or
    fp8 native — with the dequant in the matmul epilogue; embeddings
    and norms stay floating, exactly like the subclass constructors.
    The shadow is draft-only, so quantization noise costs acceptance
    rate, never exactness."""
    import copy
    from collections import OrderedDict

    from .model_runner import WEIGHT_DTYPES

    if weight_dtype not in WEIGHT_DTYPES:
        raise ValueError(f"unsupported shadow weight_dtype {weight_dtype!r}"
                         f"; expected one of {WEIGHT_DTYPES}")
    if weight_dtype == "fp8":
        from .kv_cache import require_fp8

        require_fp8(f"shadow_runner(weight_dtype={weight_dtype!r})")
    r = copy.copy(target)
    r.params = dict(target.params)
    r._jit_cache = OrderedDict()
    r._impl_logged = set()
    if weight_dtype != "fp32" and getattr(target, "weight_dtype",
                                          "fp32") == "fp32":
        import numpy as np

        skip = ("embed", "wte", "wpe", "norm", "ln_")
        names = []
        for name, val in r.params.items():
            arr = np.asarray(val)
            if (name.endswith(".weight") and arr.ndim == 2
                    and np.issubdtype(arr.dtype, np.floating)
                    and not any(s in name for s in skip)):
                names.append(name)
        r.weight_dtype = weight_dtype
        r._quantize_weights(names)
    return r


class DraftModelProposer:
    """Model-based draft rung (ISSUE 18): a small runner — or an int8
    shadow of the target — with its OWN paged pool of the target's
    geometry, proposing greedy continuations.

    Per proposal: catch-up ``prefill_chunk`` over the tokens appended
    since the last call (one sync), then one greedy ``decode_multi``
    chain for the remaining tokens (one more sync) — the chain KV is
    rolled back immediately so the next catch-up always starts from the
    request's real context. Pool pressure evicts the least recently
    proposed request's draft KV; when pages still don't fit, the
    proposer returns [] (speculation gracefully off for that step).
    """

    def __init__(self, runner, *, num_blocks: Optional[int] = None,
                 max_model_len: Optional[int] = None):
        from .kv_cache import KVCachePool

        self.runner = runner
        self.max_model_len = max_model_len or runner.max_model_len
        self.max_pages = -(-self.max_model_len // runner.block_size)
        self.pool = KVCachePool.for_runner(
            runner, num_blocks or 4 * (self.max_pages + 1))
        # request_id -> [tokens covered by draft KV, pages, pools-ref ok]
        self._seqs: Dict[str, dict] = {}
        self._lru: List[str] = []       # least recently proposed first

    # --------------------------------------------------- pool plumbing

    def _touch(self, request_id: str) -> None:
        if request_id in self._lru:
            self._lru.remove(request_id)
        self._lru.append(request_id)

    def release(self, request_id: str) -> None:
        st = self._seqs.pop(request_id, None)
        if st is not None and st["pages"]:
            self.pool.allocator.free(st["pages"])
        if request_id in self._lru:
            self._lru.remove(request_id)

    def _ensure_pages(self, st: dict, tokens: int,
                      request_id: str) -> bool:
        """Grow st["pages"] to cover ``tokens``; evict colder draft
        sequences under pressure. False when it still doesn't fit."""
        need = -(-tokens // self.runner.block_size) - len(st["pages"])
        if need <= 0:
            return True
        while not self.pool.allocator.can_alloc(need):
            victim = next((rid for rid in self._lru if rid != request_id),
                          None)
            if victim is None:
                return False
            self.release(victim)
        fresh = self.pool.allocator.alloc(need)
        self.pool.tag_pages(fresh, self.pool.native_kv_tag())
        st["pages"].extend(fresh)
        return True

    def _truncate(self, st: dict, num_tokens: int) -> None:
        """Roll draft KV coverage back to ``num_tokens`` (chain writes /
        diverged suffixes): free whole pages past the boundary."""
        keep = -(-num_tokens // self.runner.block_size)
        if len(st["pages"]) > keep:
            self.pool.allocator.free(st["pages"][keep:])
            del st["pages"][keep:]
        del st["tokens"][num_tokens:]

    # ---------------------------------------------------------- propose

    def propose(self, context: Sequence[int], max_k: int,
                request_id: Optional[str] = None) -> List[int]:
        return self.propose_chain(context, max_k, request_id=request_id)

    def propose_chain(self, context: Sequence[int], length: int,
                      request_id: Optional[str] = None) -> List[int]:
        import numpy as np

        if length <= 0 or not context:
            return []
        rid = request_id or "_anon"
        ctx = list(map(int, context))
        length = min(length, self.max_model_len - len(ctx))
        if length <= 0:
            return []
        st = self._seqs.get(rid)
        if st is None:
            st = self._seqs[rid] = {"tokens": [], "pages": []}
        self._touch(rid)
        # catch-up: longest common prefix of draft KV and the context
        common = 0
        for a, b in zip(st["tokens"], ctx):
            if a != b:
                break
            common += 1
        # always leave >= 1 uncovered token: the catch-up chunk's last
        # position is where the chain's first logits come from
        common = min(common, len(ctx) - 1)
        if common < len(st["tokens"]):
            self._truncate(st, common)
        # fund context + chain writes up front; chain rolls back after
        if not self._ensure_pages(st, len(ctx) + length, rid):
            return []
        table = self.pool.pad_table(st["pages"], self.max_pages)
        pools = self.pool.pools
        covered = len(st["tokens"])
        try:
            try:
                logits, pools = self.runner.prefill_chunk(
                    ctx[covered:], covered, table, pools)
                st["tokens"] = list(ctx)
                chain = [int(np.argmax(np.asarray(logits)))]
                if length > 1:
                    tables = np.asarray(table, np.int32)[None]
                    packed, pools = self.runner.decode_multi(
                        np.asarray([chain[0]], np.int32), tables,
                        np.asarray([len(ctx)], np.int32), pools,
                        num_steps=length - 1)
                    chain.extend(int(t) for t in np.asarray(packed)[0, 0])
            finally:
                self.pool.pools = pools
                # drop the chain's KV (and its last page-tail) so the
                # next catch-up prefill always reflects the request's
                # REAL tokens
                self._truncate(st, len(st["tokens"]))
        except Exception:
            # a failing draft model must never fail the TARGET stream
            # (the shadow may sit behind the same fault injector as the
            # target, with none of the engine's retry machinery): drop
            # this request's draft KV — its write state is unknown —
            # and propose nothing; speculation degrades, serving holds
            self.release(rid)
            return []
        return chain
