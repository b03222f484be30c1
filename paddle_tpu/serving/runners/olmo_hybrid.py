"""OlmoHybridRunner: models.OlmoHybridForCausalLM served through the paged
chassis, a state slot a sequence beside the full layers' pages."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import olmo_hybrid as _olmo
from paddle_tpu.serving.kv_cache import SCRATCH_PAGE, kv_pair_layout
from paddle_tpu.serving.model_runner import PagedModelRunner, paged_attend


class OlmoHybridRunner(PagedModelRunner):
    """Paged-step adapter for models.OlmoHybridForCausalLM: pages for the
    full-attention layers, a STATE SLOT per sequence for the Gated
    DeltaNet layers (models/olmo_hybrid.py has the equations and the
    functions; this class is their caching).

    `pools` is the pair (pages, states). pages: the (k, v) arrays of the
    full layers only, through `paged_attend` like any dense runner's,
    their heads rounded up to what the chip copies as whole tiles (30 ->
    32 below 32 bits: allocated so, never padded per call); a span longer
    than ATTN_SPAN rows attends in pieces, its keys written first. states:
    per linear layer `(state [slots, d_k, H * d_v] float32, conv [slots,
    (taps - 1) * conv_dim])`, a sequence's row its decode slot. A decode
    step (one token a row) advances rows 0..B-1 in place where the row is
    LIVE, which is read off the write indices: a dead slot's all-scratch
    table and a horizon's frozen row (`write_mask`) both write to the
    scratch page. The update is the Pallas kernel where `attn_impl`
    resolves to "ragged" (a TPU, or forced), plain jnp elsewhere. A
    prefill or a chunk of one (one sequence, `slot`) runs the chunked form
    from the slot's state, or from zeros where it starts at position 0:
    the program that first writes a slot resets it; padding rows change
    nothing (beta = 0, no decay). The steps count on the device
    (`COUNTS`): live rows x linear layers a decode step advanced, a
    prefill's real tokens and computed positions, slots reset.

    What needs a copy or a rollback of a state is not built: spans of
    several rows for several sequences (`ragged_step`, speculation) raise
    here, and ServingEngine refuses the options that need them by name."""

    COUNTS = ("delta_decode_seq_steps", "delta_prefill_tokens",
              "delta_prefill_positions", "state_slot_resets")
    HEAD_ROWS = True
    ATTN_SPAN = 128      # query rows of one call of the attention kernel

    def __init__(self, model, block_size: int = 16,
                 max_model_len: int | None = None, attn_impl: str = "auto",
                 **quant):
        from paddle_tpu.jit.functionalize import functionalize
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            _page_copy_heads

        cfg = model.cfg
        if quant.get("weight_dtype") == "int4":
            raise ValueError("weight_dtype='int4' is not wired for the "
                             "hybrid runner (int8 and fp8 are)")
        if quant.get("kv_dtype", "fp32") not in ("fp32", "fp8"):
            raise ValueError(
                f"kv_dtype={quant['kv_dtype']!r}: the hybrid runner's paged "
                "layers come in the model's dtype or in fp8 (a long span "
                "writes its keys once and attends in pieces, which the "
                "int8 and mixed write paths are not built for)")
        params = functionalize(model).param_values()
        if cfg.init == "deferred":
            # the Layer was the weights' way in: they live here now
            model.release_weights()
        super().__init__(params, block_size,
                         max_model_len or cfg.max_seq_len, attn_impl,
                         **quant)
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.linear_layers = [i for i in range(self.num_layers)
                              if cfg.is_linear(i)]
        self.n_heads = self.n_kv_heads = cfg.num_attention_heads
        self.head_dim = cfg.head_dim
        self.vocab_size = cfg.vocab_size
        # heads of a page: what the chip copies as whole tiles
        self.page_heads = _page_copy_heads(self.n_heads,
                                           self._kv_itemsize())
        self._rope = _olmo.rope_tables(cfg, self.max_model_len)
        if self.weight_dtype != "fp32":
            names = ["lm_head.weight"]
            for i in range(self.num_layers):
                pre = f"layers.{i}."
                mixer = ("linear_attn.", "qkvgo") if cfg.is_linear(i) \
                    else ("self_attn.", "qkvo")
                names += [pre + mixer[0] + n + "_proj.weight"
                          for n in mixer[1]]
                names += [pre + "mlp." + n + "_proj.weight"
                          for n in ("gate", "up", "down")]
            self._quantize_weights(names)

    def page_layout(self):
        return kv_pair_layout(self.page_heads, self.head_dim, self.dtype)

    def state_layout(self):
        cfg = self.cfg
        return (len(self.linear_layers), [
            ((cfg.linear_key_head_dim, cfg.linear_num_value_heads
              * cfg.linear_value_head_dim), jnp.float32),
            (((cfg.linear_conv_kernel_dim - 1) * cfg.conv_dim,),
             self.dtype)])

    def _param_specs(self, layout):
        raise NotImplementedError(
            "OlmoHybridRunner serves one chip; splitting state slots over "
            "a mesh is not built")

    def _kv_page_bytes(self) -> int:
        """Bytes a page costs the attention of the layers that page."""
        full = self.num_layers - len(self.linear_layers)
        return (2 * full * self.block_size * self.page_heads * self.head_dim
                * self._kv_itemsize())

    def _kv_itemsize(self) -> int:
        """Bytes of a cached value: fp8 pages, or the model's dtype."""
        return 1 if self.kv_dtype == "fp8" else np.dtype(self.dtype).itemsize

    @staticmethod
    def _starts_fresh(pos_q):
        """A span that starts at position 0 starts from a zero state, not
        from what the slot's last holder left."""
        return pos_q[0] == 0

    def _delta_kernel(self) -> bool:
        return self.attn_impl == "ragged" or (
            self.attn_impl == "auto" and jax.default_backend() == "tpu")

    # ------------------------------------------------------------- steps

    def _prefill_step(self, params, tokens, table, real_len, start_slot,
                      pools):
        """The chassis's prefill with the sequence's state slot beside its
        start position (`prefill_chunk(..., slot=)`; slot 0 where the
        caller named none: the oracle's private pool)."""
        start_slot = jnp.reshape(start_slot, (-1,))
        slot = start_slot[1:] if start_slot.shape[0] > 1 \
            else jnp.zeros((1,), jnp.int32)
        return super()._prefill_step(params, tokens, table, real_len,
                                     start_slot[0], pools, slots=slot)

    def _attend(self, q, k, v, layer_pools, tables, write_page, write_off,
                pos_q, q_lens, impl):
        """One full layer's attention through the pages: the span's keys
        written once, then its query rows ATTN_SPAN at a time."""
        B, T = q.shape[:2]
        pad = ((0, 0), (0, 0), (0, self.page_heads - self.n_heads), (0, 0))
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
        span = min(T, self.ATTN_SPAN) if impl == "ragged" else T
        out = []
        for lo in range(0, T, span):
            wrote = lo > 0            # later pieces write nothing
            cut = lambda a: a[:, :0] if wrote else a
            o, layer_pools = paged_attend(
                q[:, lo:lo + span], cut(k), cut(v), layer_pools, tables,
                cut(write_page), cut(write_off), pos_q + lo,
                jnp.clip(q_lens - lo, 0, span), 1, impl)
            out.append(o)
        o = out[0] if len(out) == 1 else jnp.concatenate(out, 1)
        o = o.reshape(B, T, self.page_heads, self.head_dim)
        return o[:, :, :self.n_heads].reshape(B, T, -1), layer_pools

    def _linear(self, params, pre, x, valid, fresh, slots, layer_states):
        """One Gated DeltaNet mixer on x [B, T, hidden] against its state
        arrays. T == 1: a decode step, row b at slot b. T > 1: one
        sequence (B == 1) at `slots[0]`."""
        from paddle_tpu.ops import gated_delta as gd
        from paddle_tpu.ops.pallas import gated_delta_decode as gk

        cfg = self.cfg
        B, T = x.shape[:2]
        H, taps = cfg.linear_num_value_heads, cfg.linear_conv_kernel_dim
        state, conv = layer_states
        rows = _olmo.conv_inputs(params, pre, x, self._mm)     # [B, T, C]
        w = _olmo.conv_weights(params, pre)
        if T == 1:
            live = valid[:, 0]
            before = conv[:B].reshape(B, taps - 1, -1)
            rows = jnp.concatenate([before, rows.astype(conv.dtype)], 1)
            q, k, v, g, beta = _olmo.delta_inputs(
                cfg, params, pre, x[:, 0], _olmo.conv_silu(rows, w)[:, 0],
                self._mm)
            conv = jax.lax.dynamic_update_slice(conv, jnp.where(
                live[:, None], rows[:, 1:].reshape(B, -1), conv[:B]), (0, 0))
            if self._delta_kernel():
                o, state = gk.gated_delta_decode(state, q, k, v, g, beta,
                                                 live)
            else:
                on = live[:, None]
                o, new = gd.gated_delta_step(
                    gk.head_form(state[:B], H), q, k, v,
                    jnp.where(on, g, 0.0), jnp.where(on, beta, 0.0))
                state = jax.lax.dynamic_update_slice(
                    state, gk.pool_form(new), (0, 0, 0))
            return _olmo.gated_output(cfg, params, pre, x[:, 0], o,
                                      self._mm)[:, None], (state, conv)
        if B != 1:
            raise NotImplementedError(
                "spans of several rows for several sequences at once (the "
                "fused ragged step, speculative verify spans) are not "
                "built for recurrent state")
        slot = slots[0]
        before = jnp.where(fresh, 0, conv[slot]).reshape(taps - 1, -1)
        rows = jnp.concatenate([before, rows[0].astype(conv.dtype)], 0)
        q, k, v, g, beta = _olmo.delta_inputs(
            cfg, params, pre, x[0], _olmo.conv_silu(rows, w), self._mm)
        on = valid[0][:, None]
        o, new = gd.gated_delta_chunked(
            q, k, v, jnp.where(on, g, 0.0), jnp.where(on, beta, 0.0),
            jnp.where(fresh, 0.0, gk.head_form(state[slot], H)))
        n_real = jnp.sum(valid[0].astype(jnp.int32))
        # what the next token's convolution reads: the last real rows
        kept = jax.lax.dynamic_slice_in_dim(rows, n_real, taps - 1, 0)
        state = jax.lax.dynamic_update_index_in_dim(
            state, gk.pool_form(new), slot, 0)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, kept.reshape(-1), slot, 0)
        return _olmo.gated_output(cfg, params, pre, x[0], o,
                                  self._mm)[None], (state, conv)

    def _forward(self, params, tokens, positions, write_page, write_off,
                 tables, pos_q, q_lens, pools, head_rows=None, slots=None):
        cfg, m = self.cfg, _olmo
        B, T = tokens.shape
        impl = self._attn_impl_for(T)
        pages, states = pools
        # a position is real where its write lands on a page of its own
        valid = write_page != SCRATCH_PAGE                          # [B, T]
        fresh = self._starts_fresh(pos_q)
        if slots is None:
            slots = jnp.arange(B, dtype=jnp.int32)
        x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
        cos_sin = None if self._rope is None else tuple(
            jnp.take(t, positions, axis=0) for t in self._rope)
        new_pages, new_states = [], []
        for i in range(cfg.num_hidden_layers):
            pre = f"layers.{i}."
            if cfg.is_linear(i):
                with jax.named_scope("block/delta"):
                    mix, layer = self._linear(
                        params, pre + "linear_attn.", x, valid, fresh, slots,
                        states[len(new_states)])
                new_states.append(layer)
            else:
                with jax.named_scope("block/attention"):
                    a = pre + "self_attn."
                    q, k, v = m.attention_qkv(cfg, params, a, x, cos_sin,
                                              self._mm)
                    o, layer = self._attend(
                        q, k, v, pages[len(new_pages)], tables, write_page,
                        write_off, pos_q, q_lens, impl)
                    mix = self._mm(params, a + "o_proj.weight", o)
                new_pages.append(layer)
            x = x + m.rms_norm(
                mix, params[pre + "post_attention_layernorm.weight"],
                cfg.rms_norm_eps)
            with jax.named_scope("block/mlp"):
                f = m.swiglu(params, pre + "mlp.", x, self._mm)
            x = x + m.rms_norm(
                f, params[pre + "post_feedforward_layernorm.weight"],
                cfg.rms_norm_eps)
        with jax.named_scope("final_norm"):
            x = m.rms_norm(x, params["norm.weight"], cfg.rms_norm_eps)
            if head_rows is not None:
                x = jnp.take_along_axis(x, head_rows[:, None, None], axis=1)
        with jax.named_scope("lm_head"):
            logits = self._mm(params, "lm_head.weight", x)
        real = jnp.sum(valid.astype(jnp.int32))
        zero = jnp.int32(0)
        counts = jnp.stack(
            [real * len(self.linear_layers), zero, zero, zero] if T == 1
            else [zero, real, jnp.int32(B * T), fresh.astype(jnp.int32)])
        return logits, (new_pages, new_states), counts
