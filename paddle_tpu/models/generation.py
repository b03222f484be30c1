"""Autoregressive generation with a static KV cache.

Reference: the reference's LLM serving path — block_multihead_attention
(paged KV cache, python/paddle/incubate/nn/functional/) + PaddleNLP
generation loops over masked_multihead_attention.

TPU-native: the KV cache is a preallocated [b, max_len, h, d] buffer per
layer updated with lax.dynamic_update_slice, so prefill + every decode step
are TWO fixed-shape compiled programs (no recompilation as length grows —
XLA requirement). Decode attends over the full cache with a position mask;
the cache buffers are donated between steps (true in-place update in HBM).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.gpt import GPT, GPTConfig


def _block_params(all_params, i):
    pre = f"blocks.{i}."
    return {k[len(pre):]: v for k, v in all_params.items()
            if k.startswith(pre)}


def _layer_norm(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def masked_cache_attention(q, k_cache, v_cache, pos, scale=None):
    """Causal attention of [b, t, h, d] queries at offset `pos` over a
    [b, L, h, d] cache — the single attention core shared by the dense
    cache, the paged cache, and incubate.masked_multihead_attention.
    `pos` may be a scalar offset or per-sequence [b] offsets.
    Returns [b, t, h*d]."""
    b, t, h, d = q.shape
    L = k_cache.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qT = jnp.swapaxes(q, 1, 2).astype(jnp.float32)        # [b,h,t,d]
    kT = jnp.swapaxes(k_cache, 1, 2).astype(jnp.float32)  # [b,h,L,d]
    vT = jnp.swapaxes(v_cache, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhtd,bhLd->bhtL", qT, kT) * scale
    pos_arr = jnp.asarray(pos)
    q_pos = pos_arr.reshape(-1, 1, 1) + jnp.arange(t)[None, :, None]
    mask = jnp.arange(L)[None, None, :] <= q_pos          # [b|1, t, L]
    s = jnp.where(mask[:, None], s, -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhtL,bhLd->bhtd", probs, vT).astype(q.dtype)
    return jnp.swapaxes(out, 1, 2).reshape(b, t, h * d)


def _attn_with_cache(p, x, k_cache, v_cache, pos, n_heads):
    """x: [b, t, H]; caches: [b, L, h, d]; pos: current write offset."""
    b, t, hdim = x.shape
    d = hdim // n_heads
    qkv = x @ p["attn.qkv.weight"] + p["attn.qkv.bias"]
    qkv = qkv.reshape(b, t, 3, n_heads, d)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, pos, 0, 0))
    out = masked_cache_attention(q, k_cache, v_cache, pos)
    return out @ p["attn.out.weight"] + p["attn.out.bias"], k_cache, v_cache


def _mlp(p, x):
    if "mlp.gate" in p:  # switch-MoE block: same routing math as training
        from paddle_tpu.parallel.moe import _switch_moe

        b, t, hdim = x.shape
        n_experts = p["mlp.gate"].shape[1]
        # capacity_factor = E makes capacity >= token count: serving must
        # not drop tokens (decode batches are tiny, so the training-time
        # capacity formula would zero out colliding tokens' MLP output)
        y, _aux = _switch_moe(x.reshape(-1, hdim), p["mlp.gate"],
                              p["mlp.w1"], p["mlp.b1"], p["mlp.w2"],
                              p["mlp.b2"],
                              capacity_factor=float(n_experts))
        return y.reshape(b, t, hdim)
    h = jax.nn.gelu(x @ p["mlp.fc1.weight"] + p["mlp.fc1.bias"],
                    approximate=True)
    return h @ p["mlp.fc2.weight"] + p["mlp.fc2.bias"]


def _forward_with_cache(params, cfg: GPTConfig, tokens, caches, pos):
    """tokens: [b, t]; caches: list of (k, v); returns logits [b, t, V]."""
    b, t = tokens.shape
    x = (jnp.take(params["wte.weight"], tokens, axis=0)
         + jnp.take(params["wpe.weight"], pos + jnp.arange(t), axis=0))
    new_caches = []
    for i in range(cfg.num_layers):
        p = _block_params(params, i)
        h = _layer_norm(x, p["ln1.weight"], p["ln1.bias"])
        a, kc, vc = _attn_with_cache(p, h, caches[i][0], caches[i][1], pos,
                                     cfg.num_heads)
        x = x + a
        h = _layer_norm(x, p["ln2.weight"], p["ln2.bias"])
        x = x + _mlp(p, h)
        new_caches.append((kc, vc))
    x = _layer_norm(x, params["ln_f.weight"], params["ln_f.bias"])
    if "lm_head.weight" in params:  # untied head (tie_embeddings=False)
        logits = jnp.einsum("bth,hv->btv", x, params["lm_head.weight"])
    else:
        logits = jnp.einsum("bth,vh->btv", x, params["wte.weight"])
    return logits, new_caches


def _sample(logits, key, temperature, top_k, top_p):
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_l, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


class GPTGenerator:
    """Compiled prefill + decode loop.

    gen = GPTGenerator(model); out = gen.generate(input_ids, max_new_tokens=...)
    """

    def __init__(self, model: GPT, max_len: Optional[int] = None):
        from paddle_tpu.jit.functionalize import functionalize

        from paddle_tpu.parallel.mesh import current_mesh

        self.model = model
        self.cfg = model.cfg
        self.max_len = max_len or self.cfg.max_seq_len
        self.func = functionalize(model)
        self.params = self.func.param_values()
        cfg = self.cfg
        # sharded serving: with an active mesh, params keep their tp/ep
        # shardings (mp layers set PartitionSpecs; GSPMD inserts the same
        # collectives the reference's sharded masked-MHA path runs by hand)
        # and the KV caches shard over heads on 'tp'. sequence_parallel
        # affects training activation sharding only — the cached decode path
        # computes the identical function without the sp constraints.
        self.mesh = current_mesh()
        self._cache_spec = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            shardings = self.func.param_shardings()
            self.params = {
                k: jax.device_put(
                    v, NamedSharding(self.mesh, shardings.get(k) or P()))
                for k, v in self.params.items()
            }
            if "tp" in self.mesh.axis_names and cfg.num_heads % \
                    self.mesh.shape["tp"] == 0:
                self._cache_spec = NamedSharding(
                    self.mesh, P(None, None, "tp", None))

        @jax.jit
        def prefill(params, tokens, caches):
            logits, caches = _forward_with_cache(params, cfg, tokens, caches, 0)
            return logits[:, -1], caches

        @partial(jax.jit, donate_argnums=(2,),
                 static_argnames=("temperature", "top_k", "top_p"))
        def decode(params, token, caches, pos, key, temperature=1.0,
                   top_k=None, top_p=None):
            logits, caches = _forward_with_cache(
                params, cfg, token[:, None], caches, pos)
            nxt = _sample(logits[:, -1], key, temperature, top_k, top_p)
            return nxt, caches

        @partial(jax.jit, donate_argnums=(2,))
        def decode_logits(params, token, caches, pos):
            logits, caches = _forward_with_cache(
                params, cfg, token[:, None], caches, pos)
            return logits[:, -1], caches

        self._prefill = prefill
        self._decode = decode
        self._decode_logits = decode_logits

    def _to_mesh(self, v):
        """Replicate host values onto the mesh (params live there)."""
        if self.mesh is None:
            return v
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(v, NamedSharding(self.mesh, P()))

    def _empty_caches(self, batch):
        cfg = self.cfg
        d = cfg.hidden_size // cfg.num_heads
        shape = (batch, self.max_len, cfg.num_heads, d)
        dt = self.params["wte.weight"].dtype

        def z():
            buf = jnp.zeros(shape, dt)
            if self._cache_spec is not None:
                buf = jax.device_put(buf, self._cache_spec)
            return buf

        return [(z(), z()) for _ in range(cfg.num_layers)]

    def _make_state(self, batch):
        return self._empty_caches(batch)

    def _prefill_call(self, ids, state):
        last_logits, state = self._prefill(self.params, ids, state)
        return last_logits, state

    def _decode_call(self, tok, state, pos, key, temperature, top_k, top_p):
        return self._decode(self.params, tok, state, pos, key,
                            temperature=temperature, top_k=top_k,
                            top_p=top_p)

    def _decode_logits_call(self, tok, state, pos):
        return self._decode_logits(self.params, tok, state, pos)

    def _expand_state(self, state, b, k):
        """Tile the post-prefill state from b rows to b*k beam rows."""
        return jax.tree_util.tree_map(
            lambda x: jnp.repeat(x, k, axis=0), state)

    def _gather_state(self, state, idx):
        """Reorder every state leaf's leading (batch*beam) axis by idx —
        the beam-reorder step (reference beam_search op's cache gather)."""
        return jax.tree_util.tree_map(lambda x: jnp.take(x, idx, axis=0),
                                      state)

    def _beam_search(self, ids, max_new_tokens, num_beams, length_penalty,
                     eos_token_id):
        """Beam search over the compiled decode path (reference
        generation `decode_strategy='beam_search'`,
        python/paddle/fluid/operators beam_search op semantics): beams
        fold into the batch axis so every step is one [b*k] decode, and
        the cache reorder is a leading-axis gather AFTER the step (the
        row that produced a beam's logits also wrote that row's cache)."""
        b, t = ids.shape
        k = num_beams
        v = self.cfg.vocab_size
        neg = jnp.float32(-1e9)
        state = self._make_state(b)
        last_logits, state = self._prefill_call(ids, state)
        logp = jax.nn.log_softmax(last_logits.astype(jnp.float32), axis=-1)
        scores, tok0 = jax.lax.top_k(logp, k)            # [b, k]
        state = self._expand_state(state, b, k)          # beams ride batch
        tokens = tok0.reshape(b * k).astype(jnp.int32)
        seqs = tokens[:, None]
        finished = (tokens == eos_token_id) if eos_token_id is not None \
            else jnp.zeros((b * k,), bool)
        pos = t
        for _ in range(max_new_tokens - 1):
            logits, state = self._decode_logits_call(
                tokens, state, self._to_mesh(jnp.asarray(pos, jnp.int32)))
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            if eos_token_id is not None:
                # finished beams: only eos continues, at zero added score
                eos_row = jnp.full((v,), neg).at[eos_token_id].set(0.0)
                logp = jnp.where(finished[:, None], eos_row[None], logp)
            total = scores.reshape(b * k, 1) + logp
            scores, idx = jax.lax.top_k(total.reshape(b, k * v), k)
            beam = idx // v                               # [b, k]
            tokval = (idx % v).astype(jnp.int32)
            gather = (jnp.arange(b)[:, None] * k + beam).reshape(-1)
            state = self._gather_state(state, gather)
            seqs = jnp.take(seqs, gather, axis=0)
            finished = jnp.take(finished, gather, axis=0)
            tokens = tokval.reshape(-1)
            if eos_token_id is not None:
                finished = finished | (tokens == eos_token_id)
            seqs = jnp.concatenate([seqs, tokens[:, None]], axis=1)
            pos += 1
            if eos_token_id is not None and bool(finished.all()):
                break
        # pick the best beam per batch row under GNMT length penalty
        gen_len = seqs.shape[1]
        if eos_token_id is not None:
            lengths = jnp.argmax(seqs == eos_token_id, axis=1) + 1
            lengths = jnp.where((seqs == eos_token_id).any(axis=1),
                                lengths, gen_len)
        else:
            lengths = jnp.full((b * k,), gen_len)
        norm = scores.reshape(-1) / (lengths.astype(jnp.float32)
                                     ** length_penalty)
        best = jnp.argmax(norm.reshape(b, k), axis=1)
        pick = jnp.arange(b) * k + best
        return Tensor._wrap(jnp.concatenate(
            [ids, jnp.take(seqs, pick, axis=0)], axis=1))

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=None, top_p=None, eos_token_id=None, seed=None,
                 num_beams=1, length_penalty=1.0):
        """Shared prefill + sample + decode loop; subclasses supply the
        cache state and the prefill/decode callables (template method —
        the eos/padding contract lives in exactly one place).
        num_beams > 1 switches to beam search (greedy within beams)."""
        from paddle_tpu.core.random import default_generator

        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None]
        ids = self._to_mesh(ids)
        b, t = ids.shape
        assert t + max_new_tokens <= self.max_len
        if num_beams > 1:
            return self._beam_search(ids, max_new_tokens, num_beams,
                                     length_penalty, eos_token_id)
        state = self._make_state(b)
        last_logits, state = self._prefill_call(ids, state)
        key = self._to_mesh(jax.random.key(seed) if seed is not None
                            else default_generator.next_key())
        tok = _sample(last_logits, key, temperature, top_k, top_p)
        finished = jnp.zeros((b,), bool)
        if eos_token_id is not None:
            finished = tok == eos_token_id
        outs = [tok]
        pos = t
        for i in range(max_new_tokens - 1):
            key = jax.random.fold_in(key, i)
            tok, state = self._decode_call(
                tok, state, self._to_mesh(jnp.asarray(pos, jnp.int32)),
                key, temperature, top_k, top_p)
            if eos_token_id is not None:
                # rows already finished keep emitting eos (pad), like the
                # reference/HF contract
                tok = jnp.where(finished, eos_token_id, tok)
                finished = finished | (tok == eos_token_id)
            outs.append(tok)
            pos += 1
            if eos_token_id is not None and bool(finished.all()):
                break
        gen = jnp.stack(outs, axis=1)
        return Tensor._wrap(jnp.concatenate([ids, gen], axis=1))


# ===================================================================== paged KV

class PagedKVCache:
    """Block-table KV cache — the reference's block_multihead_attention
    layout (python/paddle/incubate/nn/functional/block_multihead_attention.py:
    paged KV pools indexed by a per-sequence block table).

    Pools: [num_blocks, block_size, h, d]; block_table: [b, blocks_per_seq]
    int32 ids into the pool. This static allocator assigns each sequence a
    contiguous run of blocks; the indirection (gather pages by table) is the
    serving-framework contract that lets a dynamic allocator reuse and share
    blocks without touching the attention kernel.
    """

    def __init__(self, batch, max_len, n_heads, head_dim, n_layers, dtype,
                 block_size=64, sharding=None):
        assert max_len % block_size == 0
        self.block_size = block_size
        self.blocks_per_seq = max_len // block_size
        num_blocks = batch * self.blocks_per_seq
        self.block_table = jnp.arange(num_blocks, dtype=jnp.int32).reshape(
            batch, self.blocks_per_seq)
        shape = (num_blocks, block_size, n_heads, head_dim)

        def z():
            buf = jnp.zeros(shape, dtype)
            if sharding is not None:
                buf = jax.device_put(buf, sharding)
            return buf

        self.pools = [(z(), z()) for _ in range(n_layers)]


def paged_write_prefill(pool, block_table, kv, block_size):
    """Write [b, t, h, d] prefill keys/values through the block table."""
    b, t = kv.shape[:2]
    n_full, rem = divmod(t, block_size)
    for j in range(n_full):
        chunk = kv[:, j * block_size:(j + 1) * block_size]
        pool = pool.at[block_table[:, j]].set(chunk)
    if rem:
        chunk = kv[:, n_full * block_size:]
        pool = pool.at[block_table[:, n_full], :rem].set(chunk)
    return pool


def paged_write_token(pool, block_table, kv_tok, pos, block_size):
    """Write one [b, h, d] token at position `pos` (traced scalar, or
    per-sequence [b] positions — the ragged continuous-batching case the
    serving engine drives)."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        blk = jnp.take(block_table, pos // block_size, axis=1)     # [b]
        return pool.at[blk, pos % block_size].set(kv_tok)
    blk = jnp.take_along_axis(block_table, (pos // block_size)[:, None],
                              axis=1)[:, 0]                        # [b]
    return pool.at[blk, pos % block_size].set(kv_tok)


def paged_gather(pool, block_table):
    """[num_blocks, bs, h, d] gathered to [b, max_len, h, d]."""
    pages = pool[block_table]                 # [b, bps, bs, h, d]
    b, bps, bs = pages.shape[:3]
    return pages.reshape(b, bps * bs, *pages.shape[3:])


_PAGED_FALLBACK_WARNED: set = set()


def _warn_paged_fallback(head_dim):
    """Warn once per head dim when decode declines the paged kernel and
    pays the full [b, max_len, h, d] gather instead (VERDICT-r4 #10)."""
    if head_dim in _PAGED_FALLBACK_WARNED:
        return
    _PAGED_FALLBACK_WARNED.add(head_dim)
    import warnings

    warnings.warn(
        f"paged decode: head dim {head_dim} not 8-aligned — falling back "
        "to the gathered dense-cache path (full pool gather per step)",
        stacklevel=3)


def block_multihead_attention(q, k_pool, v_pool, block_table, pos,
                              scale=None):
    """Decode-step attention over a paged KV cache (reference
    incubate/nn/functional/block_multihead_attention.py analogue).
    q: [b, t, h, d]; returns [b, t, h*d].

    t == 1 (decode) runs the ragged paged kernel at q_len 1: pages are
    DMA'd straight from the pool via scalar-prefetch block indexing, so
    the full [b, max_len, h, d] cache is never materialized. Prefill
    (t > 1) and non-tiling head dims use the gather + dense-mask path."""
    b, t, h, d = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    if t == 1:
        from paddle_tpu.ops.pallas.ragged_paged_attention import (
            ragged_attention_ok, ragged_paged_attention)

        if ragged_attention_ok(d, h, k_pool.shape[2]):
            out = ragged_paged_attention(q, k_pool, v_pool, block_table,
                                         pos, 1, scale=scale)
            return out.reshape(b, 1, h * d)
        _warn_paged_fallback(d)
    k = paged_gather(k_pool, block_table)
    v = paged_gather(v_pool, block_table)
    return masked_cache_attention(q, k, v, pos, scale=scale)


def _attn_paged(p, x, k_pool, v_pool, block_table, pos, n_heads,
                block_size):
    b, t, hdim = x.shape
    d = hdim // n_heads
    qkv = x @ p["attn.qkv.weight"] + p["attn.qkv.bias"]
    qkv = qkv.reshape(b, t, 3, n_heads, d)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if t == 1:
        k_pool = paged_write_token(k_pool, block_table, k[:, 0], pos,
                                   block_size)
        v_pool = paged_write_token(v_pool, block_table, v[:, 0], pos,
                                   block_size)
    else:
        k_pool = paged_write_prefill(k_pool, block_table, k, block_size)
        v_pool = paged_write_prefill(v_pool, block_table, v, block_size)
    out = block_multihead_attention(q, k_pool, v_pool, block_table, pos)
    return out @ p["attn.out.weight"] + p["attn.out.bias"], k_pool, v_pool


def _forward_paged(params, cfg: GPTConfig, tokens, cache: "PagedKVCache",
                   pos):
    b, t = tokens.shape
    x = (jnp.take(params["wte.weight"], tokens, axis=0)
         + jnp.take(params["wpe.weight"], pos + jnp.arange(t), axis=0))
    new_pools = []
    for i in range(cfg.num_layers):
        p = _block_params(params, i)
        h = _layer_norm(x, p["ln1.weight"], p["ln1.bias"])
        a, kp, vp = _attn_paged(p, h, cache.pools[i][0], cache.pools[i][1],
                                cache.block_table, pos, cfg.num_heads,
                                cache.block_size)
        x = x + a
        h = _layer_norm(x, p["ln2.weight"], p["ln2.bias"])
        x = x + _mlp(p, h)
        new_pools.append((kp, vp))
    cache.pools = new_pools
    x = _layer_norm(x, params["ln_f.weight"], params["ln_f.bias"])
    if "lm_head.weight" in params:
        return jnp.einsum("bth,hv->btv", x, params["lm_head.weight"]), cache
    return jnp.einsum("bth,vh->btv", x, params["wte.weight"]), cache


class PagedGPTGenerator(GPTGenerator):
    """GPTGenerator over the paged block-table KV cache. Same contract;
    the cache is a PagedKVCache and the attention runs through
    block_multihead_attention."""

    def __init__(self, model: GPT, max_len: Optional[int] = None,
                 block_size: int = 64):
        super().__init__(model, max_len=max_len)
        bs = min(block_size, self.max_len)
        while self.max_len % bs:   # largest divisor <= requested
            bs -= 1
        self.block_size = bs
        cfg = self.cfg

        def prefill(params, tokens, pools, table):
            cache = _CacheView(pools, table, self.block_size)
            logits, cache = _forward_paged(params, cfg, tokens, cache, 0)
            return logits[:, -1], cache.pools

        def decode(params, token, pools, table, pos, key, temperature=1.0,
                   top_k=None, top_p=None):
            cache = _CacheView(pools, table, self.block_size)
            logits, cache = _forward_paged(params, cfg, token[:, None],
                                           cache, pos)
            nxt = _sample(logits[:, -1], key, temperature, top_k, top_p)
            return nxt, cache.pools

        def decode_logits(params, token, pools, table, pos):
            cache = _CacheView(pools, table, self.block_size)
            logits, cache = _forward_paged(params, cfg, token[:, None],
                                           cache, pos)
            return logits[:, -1], cache.pools

        self._prefill_paged = jax.jit(prefill)
        self._decode_paged = jax.jit(
            decode, donate_argnums=(2,),
            static_argnames=("temperature", "top_k", "top_p"))
        self._decode_logits_paged = jax.jit(decode_logits,
                                            donate_argnums=(2,))

    def _make_state(self, batch):
        cfg = self.cfg
        cache = PagedKVCache(batch, self.max_len, cfg.num_heads,
                             cfg.hidden_size // cfg.num_heads,
                             cfg.num_layers,
                             self.params["wte.weight"].dtype,
                             block_size=self.block_size,
                             sharding=self._cache_spec)
        return (cache.pools, self._to_mesh(cache.block_table))

    def _prefill_call(self, ids, state):
        pools, table = state
        last_logits, pools = self._prefill_paged(self.params, ids, pools,
                                                 table)
        return last_logits, (pools, table)

    def _decode_call(self, tok, state, pos, key, temperature, top_k, top_p):
        pools, table = state
        tok, pools = self._decode_paged(self.params, tok, pools, table,
                                        pos, key, temperature=temperature,
                                        top_k=top_k, top_p=top_p)
        return tok, (pools, table)

    def _decode_logits_call(self, tok, state, pos):
        pools, table = state
        logits, pools = self._decode_logits_paged(self.params, tok, pools,
                                                  table, pos)
        return logits, (pools, table)

    # Beam hooks: pool axis 0 is BLOCK index (batch*blocks_per_seq), not
    # batch — beam row ops must translate to block-row ops. The static
    # allocator keeps row r owning blocks [r*bps, (r+1)*bps), so a beam
    # gather of rows is a gather of each row's whole block run; the
    # block_table stays the identity mapping.

    def _row_to_block_idx(self, row_idx):
        bps = self.max_len // self.block_size
        return (row_idx[:, None] * bps
                + jnp.arange(bps)[None, :]).reshape(-1)

    def _expand_state(self, state, b, k):
        pools, _ = state
        rows = jnp.repeat(jnp.arange(b), k)
        blocks = self._row_to_block_idx(rows)
        new_pools = [(jnp.take(kp, blocks, axis=0),
                      jnp.take(vp, blocks, axis=0)) for kp, vp in pools]
        bps = self.max_len // self.block_size
        new_table = jnp.arange(b * k * bps, dtype=jnp.int32).reshape(
            b * k, bps)
        return new_pools, self._to_mesh(new_table)

    def _gather_state(self, state, idx):
        pools, table = state
        blocks = self._row_to_block_idx(idx)
        new_pools = [(jnp.take(kp, blocks, axis=0),
                      jnp.take(vp, blocks, axis=0)) for kp, vp in pools]
        return new_pools, table


class _CacheView:
    """Lightweight pools+table holder used inside the jitted fns."""

    def __init__(self, pools, block_table, block_size):
        self.pools = list(pools)
        self.block_table = block_table
        self.block_size = block_size
