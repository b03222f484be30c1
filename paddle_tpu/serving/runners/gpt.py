"""GPTRunner: models.GPT served through the paged chassis."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.models.generation import _block_params, _layer_norm, _mlp
from paddle_tpu.serving.model_runner import (
    SCALE_SUFFIX, PagedModelRunner, paged_attend,
)


class GPTRunner(PagedModelRunner):
    """Paged-step adapter for models.GPT — reuses the functional block
    helpers the dense-cache generator already runs."""

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
        self.n_kv_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.vocab_size = cfg.vocab_size
        if self.weight_dtype != "fp32":
            # GPT stores the fused QKV weight FLAT as [hidden, 3*nh*d]
            # (column order (3, nh, d)), so per-output-channel/group
            # abs-max quantization is exact per fused column; the
            # quantizers reject a raw (3, nh, d) tensor loudly (ISSUE 9
            # satellite, generalized to int4 in ISSUE 19) rather than
            # silently scaling over the qkv axis.
            # MoE blocks (mlp.gate present) keep their expert weights
            # floating — only dense matmul matrices quantize.
            names = []
            for i in range(self.num_layers):
                pre = f"blocks.{i}."
                names += [pre + "attn.qkv.weight", pre + "attn.out.weight"]
                if pre + "mlp.fc1.weight" in self.params:
                    names += [pre + "mlp.fc1.weight", pre + "mlp.fc2.weight"]
            if "lm_head.weight" in self.params:
                names.append("lm_head.weight")
            self._quantize_weights(names)

    def _param_specs(self, layout):
        """GPT placements (ISSUE 7). The fused attn.qkv weight keeps its
        (3, n_heads, d) column layout — a flat column shard would split
        across the q/k/v boundary — so it stays replicated and the
        sharded K/V POOLS carry the attention split instead (the head-
        sharded pool makes the whole attention block compute per-shard;
        out-proj then reduces row-wise). MLP and the vocab matrices
        shard the standard Megatron way."""
        col, row = layout.column_parallel(), layout.row_parallel()
        specs = {"wte.weight": layout.embeddings()}
        for i in range(self.num_layers):
            pre = f"blocks.{i}."
            specs[pre + "attn.out.weight"] = row
            specs[pre + "mlp.fc1.weight"] = col
            specs[pre + "mlp.fc1.bias"] = layout.bias_column()
            specs[pre + "mlp.fc2.weight"] = row
        if "lm_head.weight" in self.params:        # [H, V]: column-wise
            specs["lm_head.weight"] = col
        return specs

    def _forward(self, params, tokens, positions, write_page, write_off,
                 tables, pos_q, q_lens, pools):
        cfg = self.cfg
        B, T = tokens.shape
        d = self.head_dim
        impl = self._attn_impl_for(T)
        # scope names reach the device trace: embed, block/attn, block/mlp,
        # final_norm, lm_head (the same as models/gpt.py gives training)
        with jax.named_scope("embed"):
            x = (jnp.take(params["wte.weight"], tokens, axis=0)
                 + jnp.take(params["wpe.weight"], positions, axis=0))
        new_pools = []
        for i in range(cfg.num_layers):
            p = _block_params(params, i)
            with jax.named_scope("block/attn"):
                h = _layer_norm(x, p["ln1.weight"], p["ln1.bias"])
                qkv = (self._mm(p, "attn.qkv.weight", h)
                       + p["attn.qkv.bias"]
                       ).reshape(B, T, 3, self.n_heads, d)
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
                q, k, v = self._constrain_heads(q, k, v)
                out, layer = paged_attend(
                    q, k, v, pools[i], tables, write_page,
                    write_off, pos_q, q_lens, 1, impl,
                    shard_ctx=self._shard_ctx)
                x = x + (self._mm(p, "attn.out.weight", out)
                         + p["attn.out.bias"])
            with jax.named_scope("block/mlp"):
                h = _layer_norm(x, p["ln2.weight"], p["ln2.bias"])
                fc1 = p.get("mlp.fc1.weight")
                if fc1 is not None and (
                        "mlp.fc1.weight" + SCALE_SUFFIX in p
                        or str(fc1.dtype).startswith("float8")):
                    # dense MLP with quantized weights (scale-carrying
                    # int8/int4 or scale-free fp8 — keyed on both, since
                    # fp8 has no scale entry): same gelu(fc1)+fc2 math,
                    # matmuls through the dequant epilogue (_mlp stays
                    # the untouched fp32 path so the default is
                    # bit-identical)
                    hm = jax.nn.gelu(self._mm(p, "mlp.fc1.weight", h)
                                     + p["mlp.fc1.bias"], approximate=True)
                    x = (x + self._mm(p, "mlp.fc2.weight", hm)
                         + p["mlp.fc2.bias"])
                else:
                    x = x + _mlp(p, h)
            new_pools.append(layer)
        with jax.named_scope("final_norm"):
            x = _layer_norm(x, params["ln_f.weight"], params["ln_f.bias"])
        with jax.named_scope("lm_head"):
            if "lm_head.weight" in params and (
                    "lm_head.weight" + SCALE_SUFFIX in params
                    or str(params["lm_head.weight"].dtype
                           ).startswith("float8")
                    or (self.comm_dtype != "fp32"
                        and "lm_head.weight" in self._gather_names)):
                # quantized head, or a head whose gather is routed through
                # the explicit quantized collective (ISSUE 19)
                logits = self._mm(params, "lm_head.weight", x)
            elif "lm_head.weight" in params:
                logits = jnp.einsum("bth,hv->btv", x,
                                    params["lm_head.weight"])
            else:
                logits = jnp.einsum("bth,vh->btv", x, params["wte.weight"])
        return logits, new_pools
