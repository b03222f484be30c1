"""LagunaRunner: models.LagunaForCausalLM served through the paged chassis,
whole-context pages for its full-attention layers beside a window group's
ring for its sliding ones, every routed expert held."""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import profiler as _prof
from paddle_tpu.models import laguna as _lag
from paddle_tpu.models.deepseek_v3 import rms_norm
from paddle_tpu.serving.kv_cache import SCRATCH_PAGE
from paddle_tpu.serving.model_runner import (
    SCALE_SUFFIX, PagedModelRunner, bucket_len,
)


class LagunaRunner(PagedModelRunner):
    """Paged-step adapter for models.LagunaForCausalLM (models/laguna.py has
    the equations and the functions; this class is their caching). It names
    page GROUPS to the pool (`page_groups`), and `pools` is the triple
    (pages, states, ring) with no state:

    pages   the full-attention layers' keys and values, whole context,
            through the block table as any dense runner's (the "full"
            group: `num_blocks` counts its pages).
    ring    the sliding layers' pages (the "window" group, `WindowGroup`):
            only a sequence's last `sliding_window` positions. Its table
            columns ride behind the full group's in the one block table a
            step takes, `[pages | ring pages | ring base]`; positions there
            are the ring's own (less `base * block_size`), and the kernel is
            given the first position still inside the window. A request
            that ends gives its ring back with its slot, and the next
            request's prefill takes pages for its own.

    Both groups keep (k, v) pages of `num_key_value_heads` heads; the ONE
    ragged kernel reads them at two query head counts (a full layer's 48, a
    sliding layer's 64 at the published widths), with a lower bound on the
    sliding layers and none on the full ones.

    A decode step (one token a row) runs every layer. A prefill, or a chunk
    of one (one sequence), runs in pieces of PREFILL_SPAN rows (program
    `_prefill_piece`; a chunk no longer than that is one piece of its
    power-of-two bucket): a full layer writes the piece's keys and values
    to its pages and attends through the kernel over everything the table
    holds; a sliding layer's attention is dense over the piece's own keys
    and the `window - 1` before them, which the pieces hand on as an array:
    loaded from the ring before the first piece (`ring=(before, after)`, the
    group's rows as the engine found and left them), stored into it after
    the last. Only the chunk's last row passes the final norm and the head
    (program `_piece_head`). The expert layers count on the device
    (`COUNTS`: every launch's pairs, experts touched, row blocks walked and
    rows multiplied, and a decode launch's own beside them), the page reads
    of the two groups on the host (`GAUGES`).

    Precision: weights and pages in the model's dtype (pages in fp8 under
    `kv_dtype="fp8"`); router scores, rotary, the softmax, the gate and the
    norms' statistics float32. `weight_dtype` ("int8", "fp8") converts
    every matrix but the router, the experts' stacks among them (97 % of the
    weights: a control that left them floating would test little): a stack
    keeps int8 codes with a scale an expert and output channel, and a launch
    widens the stack it multiplies by, whole (a control's path, never a
    cell's). What needs a copy or a
    rollback of the ring is not built: spans of several rows for several
    sequences raise here, and ServingEngine refuses the options by name."""

    COUNTS = ("moe_tokens_routed", "moe_local_pairs", "moe_experts_touched",
              "moe_blocks_walked", "moe_rows_multiplied",
              "moe_decode_pairs", "moe_decode_experts_touched",
              "moe_decode_rows_multiplied")
    # pages the decode launches' attention walked, by group, summed over
    # the group's layers
    GAUGES = PagedModelRunner.GAUGES + ("attn_full_page_reads",
                                        "attn_window_page_reads")
    PREFILL_SPAN = 2048    # rows of one piece of a prefill
    WINDOW_ROWS = 512      # query rows of one block of a piece's window
    EXTRA_STEPS = {"laguna_piece": ("_prefill_piece", 6, ()),
                   "laguna_head": ("_piece_head", None, ()),
                   "laguna_ring_load": ("_ring_load", None, ()),
                   "laguna_ring_store": ("_ring_store", 0, ())}

    def __init__(self, model, block_size: int = 16,
                 max_model_len: int | None = None, attn_impl: str = "auto",
                 **quant):
        from paddle_tpu.jit.functionalize import functionalize

        cfg = model.cfg
        if quant.get("weight_dtype") == "int4":
            raise ValueError("weight_dtype='int4' is not wired for the "
                             "Laguna runner (int8 and fp8 are)")
        if quant.get("kv_dtype", "fp32") not in ("fp32", "fp8"):
            raise ValueError(
                f"kv_dtype={quant['kv_dtype']!r}: the two page groups come "
                "in the model's dtype or in fp8")
        params = functionalize(model).param_values()
        if cfg.init == "deferred":
            # the Layer was the weights' way in: they live here now
            model.release_weights()
        super().__init__(params, block_size,
                         max_model_len or cfg.max_seq_len, attn_impl,
                         **quant)
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        # the geometry the chassis asks about: the full layers' heads
        self.n_heads = cfg.num_attention_heads
        self.n_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.vocab_size = cfg.vocab_size
        self.kinds = [cfg.kind(i) for i in range(self.num_layers)]
        self.table_pages = -(-self.max_model_len // block_size)
        self._rope = {kind: _lag.rope_tables(cfg, kind, self.max_model_len)
                      for kind in set(self.kinds)}
        self._window_fold = None     # the sliding layers' pages a block
        if self.weight_dtype != "fp32":
            names = ["lm_head.weight"]
            for i in range(self.num_layers):
                pre = f"layers.{i}."
                names += [pre + "self_attn." + n + ".weight" for n in (
                    "q_proj", "k_proj", "v_proj", "g_proj", "o_proj")]
                mlp = pre + ("mlp." if cfg.is_dense(i)
                             else "mlp.shared_experts.")
                names += [mlp + n + ".weight" for n in (
                    "gate_proj", "up_proj", "down_proj")]
            self._quantize_weights(names)
            self._quantize_experts()

    def _quantize_experts(self) -> None:
        """The experts' stacks [G, in, out] on this runner's weight rung:
        int8 codes with a float32 scale an expert and output channel
        (`name::scale` [G, out]), or an fp8 cast."""
        for i in range(self.num_layers):
            if self.cfg.is_dense(i):
                continue
            for leaf in ("gate_proj", "up_proj", "down_proj"):
                name = f"layers.{i}.mlp.experts.{leaf}"
                w = self.params[name]
                if self.weight_dtype == "fp8":
                    self.params[name] = w.astype(jnp.float8_e4m3fn)
                    continue
                scale = jnp.maximum(jnp.max(jnp.abs(w.astype(jnp.float32)),
                                            axis=1), 1e-12) / 127.0
                self.params[name] = jnp.clip(jnp.round(
                    w.astype(jnp.float32) / scale[:, None, :]), -127, 127
                ).astype(jnp.int8)
                self.params[name + SCALE_SUFFIX] = scale

    def _stack(self, params, name):
        """A named stack of experts' matrices as its floating self."""
        w, s = params[name], params.get(name + SCALE_SUFFIX)
        dt = params["embed_tokens.weight"].dtype
        if s is not None:
            return w.astype(dt) * s[:, None, :].astype(dt)
        return w if w.dtype == dt else w.astype(dt)

    def page_groups(self):
        """The pool's page groups by name: layers that keep their whole
        context, and (layers, window) that keep a window of it."""
        return {"full": self.kinds.count(_lag.FULL),
                "window": (self.kinds.count(_lag.SLIDING),
                           self.cfg.sliding_window)}

    def _param_specs(self, layout):
        raise NotImplementedError(
            "LagunaRunner serves one chip; splitting the page groups and "
            "exchanging experts over a mesh is not built")

    def _kv_itemsize(self) -> int:
        return 1 if self.kv_dtype == "fp8" else np.dtype(self.dtype).itemsize

    def _layer_page_bytes(self) -> int:
        """K and V of one page in one layer."""
        return (2 * self.block_size * self.n_kv_heads * self.head_dim
                * self._kv_itemsize())

    def _kv_page_bytes(self) -> int:
        """Bytes a page of the FULL group costs a step's attention: every
        full layer reads it."""
        return self.kinds.count(_lag.FULL) * self._layer_page_bytes()

    def _account_decode(self, pos, tables) -> None:
        """The full group's walk as any runner's, then the window group's:
        the ring's own positions from its base (the table's last column),
        bounded where the window begins, as `_forward` has it, folded at a
        sliding layer's head count."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import (
            few_rows_block_pages, ragged_block_counts,
        )

        super()._account_decode(pos, tables[:, :self.table_pages])
        bs, sliding = self.block_size, self.kinds.count(_lag.SLIDING)
        rel = pos - tables[:, -1] * bs
        lower = np.maximum(rel - (self.cfg.sliding_window - 1), 0)
        live = tables[:, 0] != SCRATCH_PAGE     # a dead slot's is scratch
        ring_pages = int(((rel // bs + 1 - lower // bs) * live).sum())
        self.attn_full_page_reads += int(((pos // bs + 1) * live).sum()) \
            * self.kinds.count(_lag.FULL)
        self.attn_window_page_reads += ring_pages * sliding
        if self._attn_impl_for(1) != "ragged" or not sliding:
            return
        self.attn_kv_bytes_read += (ring_pages * sliding
                                    * self._layer_page_bytes())
        if self._window_fold is None:
            itemsize = np.dtype(self.dtype).itemsize
            self._window_fold = few_rows_block_pages(
                1, self.cfg.heads(self.kinds.index(_lag.SLIDING)), itemsize,
                bs, self.n_kv_heads, self.head_dim, self._kv_itemsize())
        if self._window_fold:
            blocks, edges = ragged_block_counts(
                rel, np.ones_like(pos), bs, self._window_fold, lower)
            self.ragged_blocks += int(blocks.sum())
            self.ragged_edge_blocks += int(edges.sum())

    # ----------------------------------------------------- cache plumbing

    def _split_tables(self, tables):
        """[.., pages | ring pages | ring base] -> the three."""
        P = self.table_pages
        if tables.shape[-1] < P + 2:
            raise ValueError(
                f"a block table of {tables.shape[-1]} columns holds no "
                f"window group behind {P} pages (max_model_len "
                f"{self.max_model_len}): build it with "
                "WindowGroup.extend_tables")
        return tables[..., :P], tables[..., P:-1], tables[..., -1]

    @staticmethod
    def _write(layer_pools, page, off, k, v):
        """A layer's (k, v) pools with tokens' rows written at (page,
        off), in the pools' own type (fp8 pages: a cast)."""
        kp, vp = layer_pools
        return (kp.at[page, off].set(k.astype(kp.dtype)),
                vp.at[page, off].set(v.astype(vp.dtype)))

    def _attend(self, q, layer_pools, table, pos, q_len, lower=None):
        """q [B, T, heads, d] at `pos` [B] of the table's own positions ->
        [B, T, heads, d]: the ragged kernel, or its gather reference."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import (
            ragged_paged_attention, ragged_reference,
        )

        fn = (ragged_paged_attention
              if self._attn_impl_for(q.shape[1]) == "ragged"
              else ragged_reference)
        return fn(q, *layer_pools, table, pos, q_len, lower=lower)

    def _ring_at(self, row, end):
        """(page, offset) of the positions [end - (W - 1), end) through a
        window group's `row`; positions before 0 go to the scratch page."""
        W, bs = self.cfg.sliding_window, self.block_size
        pos = end - (W - 1) + jnp.arange(W - 1, dtype=jnp.int32)
        at = jnp.clip(pos // bs - row[-1], 0, row.shape[0] - 2)
        return jnp.where(pos >= 0, row[at], SCRATCH_PAGE), pos % bs

    def _ring_load(self, ring, row, start):
        """The sliding layers' keys and values of positions [start - (W -
        1), start) as arrays ([layers, W - 1, kv, d] each; rows of
        positions before 0 are whatever the scratch page holds, and
        masked)."""
        page, off = self._ring_at(row, start)
        take = lambda pool: pool[page, off].astype(self.dtype)
        return (jnp.stack([take(k) for k, _ in ring]),
                jnp.stack([take(v) for _, v in ring]))

    def _ring_store(self, ring, tail, row, end):
        """The ring with the positions [end - (W - 1), end) of `tail`
        written through `row` (the group's row after the chunk)."""
        page, off = self._ring_at(row, end)
        return [self._write(layer, page, off, tail[0][i], tail[1][i])
                for i, layer in enumerate(ring)]

    # ------------------------------------------------------------ layers

    def _rope_rows(self, positions):
        """kind -> (cos, sin) [..., rot] at `positions`."""
        return {kind: (jnp.take(cos, positions, axis=0),
                       jnp.take(sin, positions, axis=0))
                for kind, (cos, sin) in self._rope.items()}

    def _window_prefill(self, q, k, v, tail, start, real_len):
        """Dense window attention of one sequence's rows: q [T, heads, d];
        k, v [T, kv, d] its own; `tail` (k, v) [W - 1, kv, d] of the
        positions before `start`. Returns (o [T, heads, d], the tail after
        the rows)."""
        cfg = self.cfg
        T, H, d = q.shape
        W, kv = cfg.sliding_window, cfg.num_key_value_heads
        ks = jnp.concatenate([tail[0], k], 0)          # index = W - 1 + t
        vs = jnp.concatenate([tail[1], v], 0)
        rows = min(T, self.WINDOW_ROWS)
        S = rows + W - 1

        def block(t0):
            qb = jax.lax.dynamic_slice_in_dim(q, t0, rows, 0)
            kb = jax.lax.dynamic_slice_in_dim(ks, t0, S, 0)
            vb = jax.lax.dynamic_slice_in_dim(vs, t0, S, 0)
            s = jnp.einsum("tgrd,sgd->grts",
                           qb.reshape(rows, kv, H // kv, d), kb,
                           preferred_element_type=jnp.float32) * d ** -0.5
            t = t0 + jnp.arange(rows)[:, None]
            idx = t0 + jnp.arange(S)[None, :]
            # row t sees indices [t, t + W - 1] at positions >= 0
            seen = (idx >= t) & (idx <= t + W - 1) & (
                start - (W - 1) + idx >= 0)
            p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            o = jnp.einsum("grts,sgd->tgrd", p.astype(vb.dtype), vb,
                           preferred_element_type=jnp.float32)
            return o.reshape(rows, H, d).astype(q.dtype)

        out = jax.lax.map(block, jnp.arange(0, T, rows, dtype=jnp.int32))
        keep = lambda a: jax.lax.dynamic_slice_in_dim(a, real_len, W - 1, 0)
        return out.reshape(T, H, d), (keep(ks), keep(vs))

    def _layer(self, params, i, x, valid, attend, rope):
        """h = x + Attn(RMSNorm(x)); y = h + FFN(RMSNorm(h)) on x [B, T,
        hidden]; `attend(q, k, v)` is the layer's own cache and kernel.
        Returns (y, the feed-forward's counts)."""
        cfg, m, pre = self.cfg, _lag, f"layers.{i}."
        B, T = x.shape[:2]
        with jax.named_scope("block/attn/" + (
                "full" if self.kinds[i] == m.FULL else "window")):
            u = rms_norm(x, params[pre + "input_layernorm.weight"],
                         cfg.rms_norm_eps)
            q, k, v, g = m.attention_qkvg(
                cfg, params, pre + "self_attn.", i, u, *rope[self.kinds[i]],
                mm=self._mm)
            x = x + m.gated_output(params, pre + "self_attn.",
                                   attend(q, k, v), g, x.dtype, self._mm)
        h = rms_norm(x, params[pre + "post_attention_layernorm.weight"],
                     cfg.rms_norm_eps).reshape(B * T, -1)
        f, counts = m.ffn(cfg, params, i, h, valid.reshape(B * T), self._mm,
                          self._stack)
        return x + f.reshape(B, T, -1), counts

    def _head(self, params, x):
        with jax.named_scope("final_norm"):
            x = rms_norm(x, params["norm.weight"], self.cfg.rms_norm_eps)
        with jax.named_scope("lm_head"):
            return self._mm(params, "lm_head.weight", x)

    # ------------------------------------------------------------- steps

    def _forward(self, params, tokens, positions, write_page, write_off,
                 tables, pos_q, q_lens, pools):
        """A decode step: one token a row, every layer."""
        cfg, m = self.cfg, _lag
        B, T = tokens.shape
        if T != 1:
            raise NotImplementedError(
                "spans of several rows for several sequences at once (the "
                "fused ragged step, speculative verify spans) are not "
                "built for this runner; a prefill goes through "
                "prefill_chunk")
        pages, _, ring = pools
        full_tab, ring_tab, ring_base = self._split_tables(tables)
        valid = write_page != SCRATCH_PAGE                          # [B, 1]
        live = valid[:, 0]
        n_live = live.astype(jnp.int32)
        bs, W = self.block_size, cfg.sliding_window
        # the ring's own positions: its table's last column holds `base`
        rel = pos_q - ring_base * bs
        ring_page = jnp.where(live, jnp.take_along_axis(
            ring_tab, jnp.clip(rel // bs, 0, ring_tab.shape[1] - 1)[:, None],
            axis=1)[:, 0], SCRATCH_PAGE)
        lower = jnp.maximum(rel - (W - 1), 0)
        rope = self._rope_rows(positions)
        x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
        new_pages, new_ring = [], []
        experts = jnp.zeros((5,), jnp.int32)
        for i in range(cfg.num_hidden_layers):
            if self.kinds[i] == m.SLIDING:
                def attend(q, k, v):
                    layer = self._write(ring[len(new_ring)], ring_page,
                                        rel % bs, k[:, 0], v[:, 0])
                    new_ring.append(layer)
                    return self._attend(q, layer, ring_tab, rel, n_live,
                                        lower)
            else:
                def attend(q, k, v):
                    layer = self._write(pages[len(new_pages)],
                                        write_page[:, 0], write_off[:, 0],
                                        k[:, 0], v[:, 0])
                    new_pages.append(layer)
                    return self._attend(q, layer, full_tab, pos_q, n_live)
            x, c = self._layer(params, i, x, valid, attend, rope)
            experts = experts + c
        logits = self._head(params, x)
        counts = jnp.concatenate([experts, experts[jnp.asarray([1, 2, 4])]])
        return logits, (new_pages, [], new_ring), counts

    def _prefill_piece(self, params, tokens, table, real_len, start, tail,
                       cache):
        """A piece's rows through every layer: tokens [1, T] of ONE
        sequence at positions start.. , `real_len` of them real; `tail` the
        sliding layers' keys and values of the `window - 1` positions
        before it; `cache` the full group's pages. Returns (pages, the tail
        after the piece, counts, the last real row's residual stream [1,
        1, hidden])."""
        cfg, m = self.cfg, _lag
        T = tokens.shape[1]
        offs = jnp.arange(T, dtype=jnp.int32)[None, :]
        valid = offs < real_len
        positions = jnp.where(valid, start + offs, 0)
        full_tab = table[None, :self.table_pages]
        page, off = self._write_indices(positions, full_tab, valid)
        rope = self._rope_rows(positions)
        start1, len1 = jnp.reshape(start, (1,)), jnp.reshape(real_len, (1,))
        x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
        new_pages, tail_k, tail_v = [], [], []
        experts = jnp.zeros((5,), jnp.int32)
        for i in range(cfg.num_hidden_layers):
            if self.kinds[i] == m.SLIDING:
                def attend(q, k, v):
                    n = len(tail_k)
                    o, (tk, tv) = self._window_prefill(
                        q[0], k[0], v[0], (tail[0][n], tail[1][n]), start,
                        real_len)
                    tail_k.append(tk)
                    tail_v.append(tv)
                    return o[None]
            else:
                def attend(q, k, v):
                    layer = self._write(cache[len(new_pages)], page, off, k,
                                        v)
                    new_pages.append(layer)
                    return self._attend(q, layer, full_tab, start1, len1)
            x, c = self._layer(params, i, x, valid, attend, rope)
            experts = experts + c
        last = jnp.reshape(real_len - 1, (1, 1, 1))
        counts = jnp.concatenate([experts, jnp.zeros((3,), jnp.int32)])
        return (new_pages, (jnp.stack(tail_k), jnp.stack(tail_v)), counts,
                jnp.take_along_axis(x, last, axis=1))

    def _piece_head(self, params, last_row):
        """A chunk's LAST real row (what `_prefill_piece` handed on) through
        the final norm and the head: logits [vocab]."""
        return self._head(params, last_row)[0, 0]

    def _piece_rows(self, t: int) -> int:
        """Rows of the pieces a chunk of t tokens runs in (the last one is
        padded to it): a chunk up to PREFILL_SPAN is one piece of its
        power-of-two bucket, as every runner's prefill is; a longer one
        runs in pieces of PREFILL_SPAN rows whatever is left for the last,
        so that long prompts of any length share ONE program."""
        return min(bucket_len(t), self.PREFILL_SPAN)

    def prefill_chunk(self, tokens: List[int], start_pos: int,
                      table_row: List[int], pools, slot=None, ring=None):
        """The chassis's entry, in pieces of PREFILL_SPAN rows (the head of
        this class). `ring`: the window group's row for this sequence
        before and after the chunk (`WindowGroup.advance`); `table_row` may
        carry the group's columns behind the pages (they are not read);
        `slot` is not needed (no state lives at it)."""
        if ring is None:
            raise ValueError(
                "LagunaRunner.prefill_chunk needs ring=(before, after), "
                "the window group's rows for this sequence around the "
                "chunk (ServingEngine and naive_generate pass them)")
        with _prof.span("runner.launch") as launch:
            pages, _, win = pools
            t, span = len(tokens), self._piece_rows(len(tokens))
            launch.set(kind="prefill", key=span)
            table = np.asarray(table_row, np.int32)[:self.table_pages]
            tail = self._jitted("laguna_ring_load", 0)(
                win, np.asarray(ring[0], np.int32), np.int32(start_pos))
            piece = self._jitted("laguna_piece", span)
            for lo in range(0, t, span):
                part = tokens[lo:lo + span]
                padded = np.zeros((1, span), np.int32)
                padded[0, :len(part)] = part
                with _prof.span("runner.dispatch"):
                    pages, tail, counts, last_row = piece(
                        self.params, padded, table, np.int32(len(part)),
                        np.int32(start_pos + lo), tail, pages)
                self._hand_over(counts)
                with _prof.span("runner.account"):
                    self._account_attn(
                        self._attn_impl_for(span),
                        np.asarray([start_pos + lo]),
                        np.asarray([len(part)]), len(table), span=span)
            with _prof.span("runner.dispatch"):
                logits = self._jitted("laguna_head", 0)(self.params,
                                                        last_row)
            win = self._jitted("laguna_ring_store", 0)(
                win, tail, np.asarray(ring[1], np.int32),
                np.int32(start_pos + t))
            return logits, (pages, [], win)
