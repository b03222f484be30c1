"""LlamaRunner: models.Llama served through the paged chassis."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.models.llama import _rope_tables
from paddle_tpu.serving.model_runner import PagedModelRunner, paged_attend


class LlamaRunner(PagedModelRunner):
    """Paged-step adapter for models.Llama (RMSNorm + RoPE + GQA + SwiGLU).

    Params come from jit.functionalize, so the runner serves exactly the
    weights of the Layer it was built from."""

    def __init__(self, model, block_size: int = 16,
                 max_model_len: int | None = None, attn_impl: str = "auto",
                 **quant):
        from paddle_tpu.jit.functionalize import functionalize

        cfg = model.cfg
        params = functionalize(model).param_values()
        super().__init__(params, block_size,
                         max_model_len or cfg.max_seq_len, attn_impl,
                         **quant)
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        self.n_heads = cfg.num_heads
        self.n_kv_heads = cfg.num_kv_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.vocab_size = cfg.vocab_size
        cos, sin = _rope_tables(self.max_model_len, self.head_dim,
                                cfg.rope_theta)
        self._rope_cos, self._rope_sin = cos, sin      # [L, d] fp32
        if self.weight_dtype != "fp32":
            names = []
            for i in range(self.num_layers):
                pre = f"layers.{i}."
                names += [pre + n for n in (
                    "self_attn.q_proj.weight", "self_attn.k_proj.weight",
                    "self_attn.v_proj.weight", "self_attn.o_proj.weight",
                    "mlp.gate_proj.weight", "mlp.up_proj.weight",
                    "mlp.down_proj.weight")]
            if "lm_head.weight" in self.params:
                names.append("lm_head.weight")
            # embeddings stay floating (lookup table; tied heads reuse it)
            self._quantize_weights(names)

    def _param_specs(self, layout):
        """Megatron placements for the Llama block (ISSUE 7): column-
        wise Q/K/V and gate/up (each shard computes its own head /
        hidden slice), row-wise o_proj/down_proj (allreduce on the row
        output), vocab-sharded embeddings; norms replicated (default)."""
        col, row = layout.column_parallel(), layout.row_parallel()
        specs = {"embed_tokens.weight": layout.embeddings()}
        for i in range(self.num_layers):
            pre = f"layers.{i}."
            specs[pre + "self_attn.q_proj.weight"] = col
            specs[pre + "self_attn.k_proj.weight"] = col
            specs[pre + "self_attn.v_proj.weight"] = col
            specs[pre + "self_attn.o_proj.weight"] = row
            specs[pre + "mlp.gate_proj.weight"] = col
            specs[pre + "mlp.up_proj.weight"] = col
            specs[pre + "mlp.down_proj.weight"] = row
        if "lm_head.weight" in self.params:        # [H, V]: column-wise
            specs["lm_head.weight"] = col
        return specs

    def _rope(self, x, cos, sin):
        # same rotate-half convention as ops.rotary_embedding
        x1, x2 = jnp.split(x, 2, axis=-1)
        rot = jnp.concatenate([-x2, x1], axis=-1)
        return (x * cos[:, :, None, :] + rot * sin[:, :, None, :]
                ).astype(x.dtype)

    def _rms(self, x, w, eps):
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w

    def _forward(self, params, tokens, positions, write_page, write_off,
                 tables, pos_q, q_lens, pools):
        cfg = self.cfg
        B, T = tokens.shape
        d = self.head_dim
        impl = self._attn_impl_for(T)
        x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
        cos = jnp.take(self._rope_cos, positions, axis=0)   # [B, T, d]
        sin = jnp.take(self._rope_sin, positions, axis=0)
        new_pools = []
        for i in range(cfg.num_layers):
            pre = f"layers.{i}."
            h = self._rms(x, params[pre + "input_layernorm.weight"],
                          cfg.rms_eps)
            q = self._mm(params, pre + "self_attn.q_proj.weight", h
                         ).reshape(B, T, self.n_heads, d)
            k = self._mm(params, pre + "self_attn.k_proj.weight", h
                         ).reshape(B, T, self.n_kv_heads, d)
            v = self._mm(params, pre + "self_attn.v_proj.weight", h
                         ).reshape(B, T, self.n_kv_heads, d)
            q = self._rope(q, cos, sin)
            k = self._rope(k, cos, sin)
            q, k, v = self._constrain_heads(q, k, v)
            out, layer = paged_attend(
                q, k, v, pools[i], tables, write_page,
                write_off, pos_q, q_lens, self.n_rep, impl,
                shard_ctx=self._shard_ctx)
            x = x + self._mm(params, pre + "self_attn.o_proj.weight", out)
            h = self._rms(x, params[pre + "post_attention_layernorm.weight"],
                          cfg.rms_eps)
            gate = self._mm(params, pre + "mlp.gate_proj.weight", h)
            up = self._mm(params, pre + "mlp.up_proj.weight", h)
            x = x + self._mm(params, pre + "mlp.down_proj.weight",
                             jax.nn.silu(gate) * up)
            new_pools.append(layer)
        x = self._rms(x, params["norm.weight"], cfg.rms_eps)
        if cfg.tie_embeddings:
            logits = x @ params["embed_tokens.weight"].T
        else:
            logits = self._mm(params, "lm_head.weight", x)
        return logits, new_pools
