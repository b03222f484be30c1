"""Phi4FlashRunner: models.Phi4FlashForCausalLM served through the paged
chassis, a cache per layer kind (pages, a window group's ring, state
slots)."""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import profiler as _prof
from paddle_tpu.models import phi4flash as _phi
from paddle_tpu.serving.kv_cache import SCRATCH_PAGE, kv_pair_layout
from paddle_tpu.serving.model_runner import PagedModelRunner, bucket_len


class Phi4FlashRunner(PagedModelRunner):
    """Paged-step adapter for models.Phi4FlashForCausalLM, whose layers keep
    a cache of four kinds (models/phi4flash.py has the equations and the
    functions; this class is their caching). It names page GROUPS to the
    pool (`page_groups`), and `pools` is the triple (pages, states, ring):

    pages   the ONE full-attention layer's keys and values, whole context,
            through the block table as any dense runner's (the "full"
            group: `num_blocks` counts its pages). The cross-attention
            layers of the cross-decoder own no cache: they read these pages
            with the same kernel and write nothing.
    ring    the window layers' pages (the "window" group, `WindowGroup`):
            only a sequence's last `sliding_window` positions. Its table
            columns ride behind the full group's in the one block table a
            step takes, `[pages | ring pages | ring base]`; positions
            there are the ring's own (less `base * block_size`), and the
            kernel is given the first position still inside the window.
    states  per Mamba layer `(state [slots, d_state, d_inner] float32, conv
            [slots, (taps - 1) * d_inner])`, a sequence's row its decode
            slot, as OlmoHybridRunner keeps its delta rule's.

    A page is kept as ROWS, `[block_size * pairs, 2 head_dim]` (10 pairs of
    128 lanes at the published widths: whole tiles, where `[16, 10, 128]`
    would be allocated as 16 pairs). The differential pairing costs no
    second walk: a query head padded to its pair's width scores its own key
    head against the pair (`models.phi4flash.pair_queries`), so ONE pass of
    the ragged kernel over pair heads gives both softmaxes' products.

    A decode step (one token a row) runs every layer; the gated memory
    units read the memory layer's scan output of the same step. A prefill,
    or a chunk of one (one sequence, `slot`), runs in pieces of
    PREFILL_SPAN rows: layers up to the full layer's key/value write for
    EVERY row, the full layer's attention and the whole cross-decoder for
    the chunk's LAST row only (nothing after that write keeps anything of
    an earlier row, so this is exact: tests hold it equal to the unskipped
    forward). Its window attention is dense over the chunk's own keys and
    the `window - 1` before them, which the pieces hand on as an array:
    loaded from the ring before the first piece (`ring=(before, after)`,
    the group's rows as the engine found and left them), stored into it
    after the last. The steps count on the device (`COUNTS`).

    Precision: weights, pages and convolution rows in the model's dtype;
    the scan state, dt, exp(dt A), the softmax, lambda and both norms'
    statistics float32. What needs a copy or a rollback of a state or of
    the ring is not built: spans of several rows for several sequences
    raise here, and ServingEngine refuses the options by name."""

    COUNTS = ("ssm_decode_seq_steps", "ssm_prefill_tokens",
              "cross_rows_skipped", "state_slot_resets")
    HEAD_ROWS = True
    ROW_PAGES = True
    PREFILL_SPAN = 2048    # rows of one piece of a prefill
    SHORT_CHUNK = 256      # a chunk up to this long is one piece of its bucket
    WINDOW_ROWS = 512      # query rows of one block of its window attention
    EXTRA_STEPS = {"phi_body": ("_piece_body", 6, ()),
                   "phi_head": ("_piece_head", None, ()),
                   "phi_ring_load": ("_ring_load", None, ()),
                   "phi_ring_store": ("_ring_store", 0, ())}

    def __init__(self, model, block_size: int = 16,
                 max_model_len: int | None = None, attn_impl: str = "auto",
                 **quant):
        from paddle_tpu.jit.functionalize import functionalize

        cfg = model.cfg
        if quant.get("weight_dtype") == "int4":
            raise ValueError("weight_dtype='int4' is not wired for the "
                             "Phi-4-flash runner (int8 and fp8 are)")
        if quant.get("kv_dtype", "fp32") not in ("fp32", "fp8"):
            raise ValueError(
                f"kv_dtype={quant['kv_dtype']!r}: row pages come in the "
                "model's dtype or in fp8")
        params = functionalize(model).param_values()
        if cfg.init == "deferred":
            # the Layer was the weights' way in: they live here now
            model.release_weights()
        super().__init__(params, block_size,
                         max_model_len or cfg.max_seq_len, attn_impl,
                         **quant)
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        # the geometry the attention kernel sees: PAIR heads
        self.n_heads = cfg.num_attention_heads
        self.n_kv_heads = cfg.kv_pairs
        self.head_dim = 2 * cfg.head_dim
        self.vocab_size = cfg.vocab_size
        self.kinds = [cfg.kind(i) for i in range(self.num_layers)]
        self.table_pages = -(-self.max_model_len // block_size)
        if self.weight_dtype != "fp32":
            per_kind = {
                "mamba": ["mamba." + n for n in ("in_proj", "x_proj",
                                                 "dt_proj", "out_proj")],
                "gmu": ["gmu.in_proj", "gmu.out_proj"],
                "cross": ["attn.q_proj", "attn.o_proj"]}
            names = []
            for i, kind in enumerate(self.kinds):
                names += [f"layers.{i}.{n}.weight" for n in per_kind.get(
                    kind, ["attn.qkv_proj", "attn.o_proj"])
                    + ["mlp.gate_up_proj", "mlp.down_proj"]]
            self._quantize_weights(names)

    def page_layout(self):
        return kv_pair_layout(self.n_kv_heads, self.head_dim, self.dtype)

    def page_groups(self):
        """The pool's page groups by name: layers that keep their whole
        context, and (layers, window) that keep a window of it."""
        return {"full": self.kinds.count("full"),
                "window": (self.kinds.count("window"),
                           self.cfg.sliding_window)}

    def state_layout(self):
        cfg = self.cfg
        return (self.kinds.count("mamba"), [
            ((cfg.mamba_d_state, cfg.d_inner), jnp.float32),
            (((cfg.mamba_d_conv - 1) * cfg.d_inner,), self.dtype)])

    def _param_specs(self, layout):
        raise NotImplementedError(
            "Phi4FlashRunner serves one chip; splitting state slots and "
            "page groups over a mesh is not built")

    def _kv_itemsize(self) -> int:
        return 1 if self.kv_dtype == "fp8" else np.dtype(self.dtype).itemsize

    def _kv_page_bytes(self) -> int:
        """Bytes a page of the full group costs a step's attention: the
        full layer and every cross layer read it."""
        readers = self.kinds.count("full") + self.kinds.count("cross")
        return (2 * readers * self.block_size * self.n_kv_heads
                * self.head_dim * self._kv_itemsize())

    def _account_decode(self, pos, tables) -> None:
        """The full group's walk as any runner's, then the window
        group's: the ring's own positions from its base (the table's last
        column), bounded where the window begins, as `_forward` has it."""
        super()._account_decode(pos, tables)
        if self._attn_impl_for(1) == "ragged":
            rel = pos - tables[:, -1] * self.block_size
            self._account_blocks(
                rel, np.ones_like(pos), 1,
                np.maximum(rel - (self.cfg.sliding_window - 1), 0))

    def _scan_kernel(self) -> bool:
        return self.attn_impl == "ragged" or (
            self.attn_impl == "auto" and jax.default_backend() == "tpu")

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

    def _write_rows(self, pool, page, off, new):
        """page, off [...]; new [..., pairs, lanes] -> the row pool with
        those tokens' rows written: a page's rows are key-major, so a
        token's pairs are ONE window of consecutive rows."""
        n = self.n_kv_heads
        at = jnp.stack([page, off * n], -1).reshape(-1, 2)
        return jax.lax.scatter(
            pool, at, new.astype(pool.dtype).reshape(-1, n, new.shape[-1]),
            jax.lax.ScatterDimensionNumbers(
                update_window_dims=(1, 2), inserted_window_dims=(0,),
                scatter_dims_to_operand_dims=(0, 1)))

    def _take_rows(self, pool, page, off):
        """page, off [n] -> those tokens' rows [n, pairs, lanes]."""
        n = self.n_kv_heads
        return jax.lax.gather(
            pool, jnp.stack([page, off * n], -1),
            jax.lax.GatherDimensionNumbers(
                offset_dims=(1, 2), collapsed_slice_dims=(0,),
                start_index_map=(0, 1)),
            slice_sizes=(1, n, pool.shape[-1]))

    def _attend(self, q, layer_pools, table, pos, q_len, lower=None):
        """q [B, heads, head_dim]: one row a sequence, at `pos` of the
        table's own positions -> [B, heads, 2 head_dim]: each head's
        softmax applied to its pair's values."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import (
            ragged_paged_attention, ragged_reference,
        )

        fn = (ragged_paged_attention if self._attn_impl_for(1) == "ragged"
              else ragged_reference)
        return fn(_phi.pair_queries(q)[:, None], *layer_pools, table, pos,
                  q_len, scale=self.cfg.head_dim ** -0.5, lower=lower,
                  kv_heads=self.n_kv_heads)[:, 0]

    def _ring_at(self, row, end):
        """(page, offset) of the positions [end - (W - 1), end) through a
        window group's `row`; positions before 0 go to the scratch page."""
        W, bs = self.cfg.sliding_window, self.block_size
        pos = end - (W - 1) + jnp.arange(W - 1, dtype=jnp.int32)
        at = jnp.clip(pos // bs - row[-1], 0, row.shape[0] - 2)
        return jnp.where(pos >= 0, row[at], SCRATCH_PAGE), pos % bs

    def _ring_load(self, ring, row, start):
        """The window layers' keys and values of positions [start - (W -
        1), start) as arrays ([layers, W - 1, pairs, lanes] each; rows of
        positions before 0 are whatever the scratch page holds, and
        masked)."""
        page, off = self._ring_at(row, start)
        take = lambda pool: self._take_rows(pool, page, off).astype(
            self.dtype)
        return (jnp.stack([take(k) for k, _ in ring]),
                jnp.stack([take(v) for _, v in ring]))

    def _ring_store(self, ring, tail, row, end):
        """The ring with the positions [end - (W - 1), end) of `tail`
        written through `row` (the group's row after the chunk)."""
        page, off = self._ring_at(row, end)
        return [(self._write_rows(k, page, off, tail[0][i]),
                 self._write_rows(v, page, off, tail[1][i]))
                for i, (k, v) in enumerate(ring)]

    # ------------------------------------------------------------ layers

    def _mamba(self, params, pre, u, valid, fresh, slots, layer_states):
        """One Mamba mixer on u [B, T, hidden] against its state arrays.
        T == 1: a decode step, row b at slot b. T > 1: one sequence (B ==
        1) at `slots[0]`. Returns (out, the memory y float32, states)."""
        from paddle_tpu.ops import selective_scan as ss
        from paddle_tpu.ops.pallas.selective_scan_decode import \
            selective_scan_decode

        cfg, m = self.cfg, _phi
        B, T = u.shape[:2]
        taps, c = cfg.mamba_d_conv, cfg.d_inner
        state, conv = layer_states
        xin, z = m.mamba_inputs(params, pre, u, self._mm)
        if T == 1:
            live = valid[:, 0]
            before = conv[:B].reshape(B, taps - 1, c)
            rows = jnp.concatenate([before, xin.astype(conv.dtype)], 1)
            xc = m.conv_silu(params, pre, rows)[:, 0]            # [B, c]
            dt, Bm, Cm, A = m.ssm_inputs(cfg, params, pre, xc, u.dtype,
                                         self._mm)
            conv = jax.lax.dynamic_update_slice(conv, jnp.where(
                live[:, None], rows[:, 1:].reshape(B, -1), conv[:B]), (0, 0))
            with jax.named_scope("block/ssm/scan"):
                if self._scan_kernel():
                    s, state = selective_scan_decode(state, xc, dt, A, Bm,
                                                     Cm, live)
                else:
                    s, new = ss.selective_scan_step(
                        state[:B], xc, jnp.where(live[:, None], dt, 0.0), A,
                        Bm, Cm)
                    state = jax.lax.dynamic_update_slice(state, new,
                                                         (0, 0, 0))
            y = m.mamba_memory(params, pre, s, xc)
            return (m.mamba_output(params, pre, y, z[:, 0], self._mm)[:, None],
                    y[:, None], (state, conv))
        if B != 1:
            raise NotImplementedError(
                "spans of several rows for several sequences at once (the "
                "fused ragged step, speculative verify spans) are not "
                "built for recurrent state")
        slot = slots[0]
        before = jnp.where(fresh, 0, conv[slot]).reshape(taps - 1, c)
        rows = jnp.concatenate([before, xin[0].astype(conv.dtype)], 0)
        xc = m.conv_silu(params, pre, rows)                      # [T, c]
        dt, Bm, Cm, A = m.ssm_inputs(cfg, params, pre, xc, u.dtype, self._mm)
        with jax.named_scope("block/ssm/scan"):
            s, new = ss.selective_scan_chunked(
                xc, jnp.where(valid[0][:, None], dt, 0.0), A, Bm, Cm,
                jnp.where(fresh, 0.0, state[slot]))
        n_real = jnp.sum(valid[0].astype(jnp.int32))
        # what the next token's convolution reads: the last real rows
        kept = jax.lax.dynamic_slice_in_dim(rows, n_real, taps - 1, 0)
        state = jax.lax.dynamic_update_index_in_dim(state, new, slot, 0)
        conv = jax.lax.dynamic_update_index_in_dim(conv, kept.reshape(-1),
                                                   slot, 0)
        y = m.mamba_memory(params, pre, s, xc)
        return (m.mamba_output(params, pre, y, z[0], self._mm)[None],
                y[None], (state, conv))

    def _window_prefill(self, q, k, v, tail, start, real_len):
        """Dense window attention of one sequence's rows: q [T, heads,
        d]; k, v [T, pairs, 2d] its own; `tail` (k, v) [W - 1, pairs, 2d]
        of the positions before `start`. Returns (o [T, heads, 2d], the
        tail after the rows)."""
        cfg = self.cfg
        T, W, d = q.shape[0], cfg.sliding_window, cfg.head_dim
        g, rep = cfg.kv_pairs, cfg.num_attention_heads // cfg.kv_pairs // 2
        ks = jnp.concatenate([tail[0], k], 0)          # index = W - 1 + t
        vs = jnp.concatenate([tail[1], v], 0)
        # [pairs', rep, 2, T, d]: query pair p = p' * rep + r
        qh = q.reshape(T, g, rep, 2, d).transpose(1, 2, 3, 0, 4)
        rows = min(T, self.WINDOW_ROWS)
        out = []
        for t0 in range(0, T, rows):
            S = rows + W - 1
            kb = ks[t0:t0 + S].reshape(S, g, 2, d).transpose(1, 2, 0, 3)
            s = jnp.einsum("grjtd,gjsd->grjts", qh[:, :, :, t0:t0 + rows],
                           kb, preferred_element_type=jnp.float32
                           ) * d ** -0.5
            t = t0 + jnp.arange(rows)[:, None]
            idx = t0 + jnp.arange(S)[None, :]
            # row t sees indices [t, t + W - 1] at positions >= 0
            seen = (idx >= t) & (idx <= t + W - 1) & (
                start - (W - 1) + idx >= 0)
            p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            o = jnp.einsum("grjts,sgd->tgrjd", p.astype(vs.dtype),
                           vs[t0:t0 + S],
                           preferred_element_type=jnp.float32)
            out.append(o.reshape(rows, cfg.num_attention_heads, 2 * d))
        keep = lambda a: jax.lax.dynamic_slice_in_dim(a, real_len, W - 1, 0)
        return (out[0] if len(out) == 1 else jnp.concatenate(out, 0),
                (keep(ks), keep(vs)))

    def _block(self, params, i, x, mixer):
        """h = x + Mixer(LN(x)); y = h + MLP(LN(h)); mixer(u) -> (m,
        extra)."""
        cfg, m, pre = self.cfg, _phi, f"layers.{i}."
        mix, extra = mixer(
            m.block_norm(cfg, params, pre + "input_layernorm", x))
        x = x + mix
        with jax.named_scope("block/mlp"):
            x = x + m.mlp(params, pre + "mlp.", m.block_norm(
                cfg, params, pre + "post_attention_layernorm", x), self._mm)
        return x, extra

    def _cross_decoder(self, params, x, memory, pages, table, pos, q_len):
        """The layers after the full layer on x [B, 1, hidden]: gated
        memory units on `memory` [B, 1, d_inner], cross-attention to the
        full layer's pages (no write)."""
        cfg, m = self.cfg, _phi
        for i in range(cfg.split, cfg.num_hidden_layers):
            pre = f"layers.{i}."
            if self.kinds[i] == "gmu":
                def mixer(u, pre=pre):
                    with jax.named_scope("block/gmu"):
                        return m.gmu(params, pre + "gmu.", u, memory,
                                     self._mm), None
            else:
                def mixer(u, pre=pre, i=i):
                    with jax.named_scope("block/attn/shared"):
                        q = m.cross_q(cfg, params, pre + "attn.", u, self._mm)
                        o = self._attend(q[:, 0], pages, table, pos, q_len)
                        return m.differential_output(
                            cfg, params, pre + "attn.", i, o[:, None],
                            u.dtype, self._mm), None
            x, _ = self._block(params, i, x, mixer)
        return x

    def _head(self, params, x):
        with jax.named_scope("final_norm"):
            x = _phi.block_norm(self.cfg, params, "final_layernorm", x)
        with jax.named_scope("lm_head"):
            return x @ params["embed_tokens.weight"].T

    # ------------------------------------------------------------- steps

    def _forward(self, params, tokens, positions, write_page, write_off,
                 tables, pos_q, q_lens, pools, head_rows=None):
        """A decode step: one token a row, every layer."""
        cfg, m = self.cfg, _phi
        B, T = tokens.shape
        if T != 1:
            raise NotImplementedError(
                "spans of several rows for several sequences at once (the "
                "fused ragged step, speculative verify spans) are not "
                "built for this runner; a prefill goes through "
                "prefill_chunk")
        pages, states, ring = pools
        full_tab, ring_tab, ring_base = self._split_tables(tables)
        valid = write_page != SCRATCH_PAGE                          # [B, 1]
        live = valid[:, 0]
        n_live = live.astype(jnp.int32)
        bs, W = self.block_size, cfg.sliding_window
        # the ring's own positions: its table's column 0 holds `base`
        rel = pos_q - ring_base * bs
        ring_page = jnp.where(live, jnp.take_along_axis(
            ring_tab, jnp.clip(rel // bs, 0, ring_tab.shape[1] - 1)[:, None],
            axis=1)[:, 0], SCRATCH_PAGE)
        lower = jnp.maximum(rel - (W - 1), 0)
        x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
        new_states, new_ring, new_pages, memory = [], [], list(pages), None
        for i in range(cfg.split):
            pre, kind = f"layers.{i}.", self.kinds[i]
            if kind == "mamba":
                def mixer(u, pre=pre):
                    out, y, layer = self._mamba(
                        params, pre + "mamba.", u, valid, None, None,
                        states[len(new_states)])
                    new_states.append(layer)
                    return out, y
                x, y = self._block(params, i, x, mixer)
                if i == cfg.memory_layer:
                    memory = y
                continue

            def mixer(u, pre=pre, i=i, kind=kind):
                a = pre + "attn."
                q, k, v = m.attention_qkv(cfg, params, a, u, self._mm)
                if kind == "window":
                    with jax.named_scope("block/attn/window"):
                        kp, vp = ring[len(new_ring)]
                        layer = (self._write_rows(kp, ring_page, rel % bs,
                                                  k[:, 0]),
                                 self._write_rows(vp, ring_page, rel % bs,
                                                  v[:, 0]))
                        new_ring.append(layer)
                        o = self._attend(q[:, 0], layer, ring_tab, rel,
                                         n_live, lower)
                else:
                    with jax.named_scope("block/attn/shared"):
                        kp, vp = pages[0]
                        layer = (self._write_rows(kp, write_page[:, 0],
                                                  write_off[:, 0], k[:, 0]),
                                 self._write_rows(vp, write_page[:, 0],
                                                  write_off[:, 0], v[:, 0]))
                        new_pages[0] = layer
                        o = self._attend(q[:, 0], layer, full_tab, pos_q,
                                         n_live)
                return m.differential_output(cfg, params, a, i, o[:, None],
                                             u.dtype, self._mm), None
            x, _ = self._block(params, i, x, mixer)
        x = self._cross_decoder(params, x, memory, new_pages[0], full_tab,
                                pos_q, n_live)
        logits = self._head(params, x)
        zero = jnp.int32(0)
        counts = jnp.stack([jnp.sum(n_live) * len(states), zero, zero, zero])
        return logits, (new_pages, new_states, new_ring), counts

    def _self_decoder(self, params, tokens, table, real_len, start_slot,
                      tail, cache):
        """A prefill piece's rows through the layers before the full one,
        and the full layer's key/value write: tokens [1, T] of ONE
        sequence at positions start.. . Returns (x [1, T, hidden] before
        the full layer, the memory, the full layer's (q, written pages),
        the states, the tail after the piece, valid)."""
        cfg, m = self.cfg, _phi
        pages, states = cache
        T = tokens.shape[1]
        start, slots = start_slot[0], start_slot[1:]
        offs = jnp.arange(T, dtype=jnp.int32)[None, :]
        valid = offs < real_len
        positions = jnp.where(valid, start + offs, 0)
        page, off = self._write_indices(positions, table[None, :self.table_pages],
                                        valid)
        fresh = start == 0
        x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
        new_states, tail_k, tail_v, memory = [], [], [], None
        for i in range(cfg.split - 1):
            pre = f"layers.{i}."
            if self.kinds[i] == "mamba":
                def mixer(u, pre=pre):
                    out, y, layer = self._mamba(
                        params, pre + "mamba.", u, valid, fresh, slots,
                        states[len(new_states)])
                    new_states.append(layer)
                    return out, y
                x, y = self._block(params, i, x, mixer)
                if i == cfg.memory_layer:
                    memory = y
                continue

            def mixer(u, pre=pre, i=i):
                a, n = pre + "attn.", len(tail_k)
                with jax.named_scope("block/attn/window"):
                    q, k, v = m.attention_qkv(cfg, params, a, u, self._mm)
                    o, (tk, tv) = self._window_prefill(
                        q[0], k[0], v[0], (tail[0][n], tail[1][n]), start,
                        real_len)
                    tail_k.append(tk)
                    tail_v.append(tv)
                return m.differential_output(
                    cfg, params, a, i, o[None].astype(u.dtype), u.dtype,
                    self._mm), None
            x, _ = self._block(params, i, x, mixer)
        # the full layer: keys and values of every row go to its pages
        i = cfg.split - 1
        a = f"layers.{i}.attn."
        with jax.named_scope("block/attn/shared"):
            u = m.block_norm(cfg, params, f"layers.{i}.input_layernorm", x)
            q, k, v = m.attention_qkv(cfg, params, a, u, self._mm)
            kp, vp = pages[0]
            written = (self._write_rows(kp, page[0], off[0], k[0]),
                       self._write_rows(vp, page[0], off[0], v[0]))
        return (x, memory, q, written, new_states,
                (jnp.stack(tail_k), jnp.stack(tail_v)), valid)

    def _piece_body(self, params, tokens, table, real_len, start_slot, tail,
                    cache):
        """A piece's rows through the layers before the full one and the
        full layer's key/value write; `start_slot` is (start, slot, whether
        the piece is its chunk's last). No row of it reaches the full
        layer's attention or the cross-decoder here: of its LAST real row
        it hands on what `_piece_head` takes there (the stream before the
        full layer, the memory, the full layer's query)."""
        x, memory, q, written, states, tail, valid = self._self_decoder(
            params, tokens, table, real_len, start_slot[:2], tail, cache)
        last = jnp.reshape(real_len - 1, (1,))
        row = lambda a: jnp.take_along_axis(a, last[:, None, None], axis=1)
        q_row = jnp.take_along_axis(q, last[:, None, None, None],
                                    axis=1)[:, 0]
        real = jnp.sum(valid.astype(jnp.int32))
        counts = jnp.stack([jnp.int32(0), real, real - start_slot[2],
                            (start_slot[0] == 0).astype(jnp.int32)])
        return ([written], states), tail, counts, (row(x), row(memory), q_row)

    def _piece_head(self, params, last_row, table, pos, pages):
        """A chunk's LAST real row (what `_piece_body` handed on, at
        position `pos` [1]) through the full layer's attention and the
        cross-decoder to the logits [vocab]. Reads the full group's pages,
        writes nothing."""
        cfg, m = self.cfg, _phi
        x, memory, q = last_row
        one = jnp.ones((1,), jnp.int32)
        full_tab = table[None, :self.table_pages]
        i = cfg.split - 1

        def mixer(u):
            # u is the last row's norm again: the same numbers
            with jax.named_scope("block/attn/shared"):
                o = self._attend(q, pages[0], full_tab, pos, one)
                return m.differential_output(
                    cfg, params, f"layers.{i}.attn.", i, o[:, None], u.dtype,
                    self._mm), None
        x, _ = self._block(params, i, x, mixer)
        x = self._cross_decoder(params, x, memory, pages[0], full_tab, pos,
                                one)
        return self._head(params, x)[0, 0]

    def _piece_rows(self, t: int) -> int:
        """Rows of the pieces a chunk of t tokens runs in (the last one is
        padded to it): a short chunk is one piece of its power-of-two
        bucket, as every runner's prefill is; a longer one runs in pieces
        of PREFILL_SPAN rows whatever is left for the last, so that long
        prompts of any length share ONE program of the 17 layers (a quarter
        of a minute to compile; a piece reads every weight once, 9.4 ms of
        a v5e's memory at the published sizes, whatever its rows)."""
        return bucket_len(t) if t <= min(self.SHORT_CHUNK,
                                         self.PREFILL_SPAN) \
            else self.PREFILL_SPAN

    def prefill_chunk(self, tokens: List[int], start_pos: int,
                      table_row: List[int], pools, slot=None, ring=None):
        """The chassis's entry, in pieces of PREFILL_SPAN rows (the head of
        this class). `ring`: the window group's row for this sequence
        before and after the chunk (`WindowGroup.row`); `table_row` may
        carry the group's columns behind the pages (they are not read)."""
        if ring is None:
            raise ValueError(
                "Phi4FlashRunner.prefill_chunk needs ring=(before, after), "
                "the window group's rows for this sequence around the "
                "chunk (ServingEngine and naive_generate pass them)")
        with _prof.span("runner.launch") as launch:
            pages, states, win = pools
            t, span = len(tokens), self._piece_rows(len(tokens))
            launch.set(kind="prefill", key=span)
            table = np.asarray(table_row, np.int32)[:self.table_pages]
            slot = 0 if slot is None else slot
            tail = self._jitted("phi_ring_load", 0)(
                win, np.asarray(ring[0], np.int32), np.int32(start_pos))
            body = self._jitted("phi_body", span)
            for lo in range(0, t, span):
                piece = tokens[lo:lo + span]
                padded = np.zeros((1, span), np.int32)
                padded[0, :len(piece)] = piece
                with _prof.span("runner.dispatch"):
                    (pages, states), tail, counts, last_row = body(
                        self.params, padded, table, np.int32(len(piece)),
                        np.asarray([start_pos + lo, slot, lo + span >= t],
                                   np.int32), tail, (pages, states))
                self._hand_over(counts)
            with _prof.span("runner.dispatch"):
                logits = self._jitted("phi_head", 0)(
                    self.params, last_row, table,
                    np.asarray([start_pos + t - 1], np.int32), pages)
            win = self._jitted("phi_ring_store", 0)(
                win, tail, np.asarray(ring[1], np.int32),
                np.int32(start_pos + t))
            return logits, (pages, states, win)
