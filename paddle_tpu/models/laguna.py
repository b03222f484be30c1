"""Laguna (poolside; published `model_type: laguna`, Laguna-XS.2 "33B-A3B"):
grouped-query attention whose layers alternate by `layer_types` between the
whole context and a sliding window, with a head count and a rotary form a
layer type and a sigmoid gate a head; a dense first feed-forward, then 256
small routed experts (top 8, sigmoid scores) beside one shared expert.

Served, not trained: the `Layer` holds the weights and its eager `forward`
is the plain form (dense masks, no cache), with no autograd tape.
`serving/runners/laguna.py` serves it from the functions below: the
allocator's pages for the full layers, a ring of pages for the sliding ones.

The configuration carries the published per-layer lists whole
(`layer_types`, `num_attention_heads_per_layer`, `mlp_layer_types`: a model
of `num_hidden_layers` layers reads their first entries) and
`rope_parameters` by layer type; nothing is spelled a second time.

The equations (x [T, hidden]; RMSNorm at `rms_norm_eps`, float32
statistics; linears [in, out], no bias). What the published config does not
say is marked ASSUMED, one named constant or function each, and listed in
bench/configs/laguna-xs.2.json under `assumed`:
  block   h = x + Attn_l(RMSNorm(x)); y = h + FFN_l(RMSNorm(h)) (pre-norm on
          both sublayers, ASSUMED); a final RMSNorm; an untied head.
  Attn_l  u the sublayer's normed input. q = u W_q -> H_l heads of
          `head_dim` (`num_attention_heads_per_layer[l]`: 48 on full
          layers, 64 on sliding ones), k, v = u W_k, u W_v ->
          `num_key_value_heads` heads; query head j reads key/value head j
          // (H_l / kv). No QK-norm (ASSUMED: no key for one). Rotary by
          layer type (`rope_tables`) on the first `partial_rotary_factor *
          head_dim` values of each head, pairs (x[i], x[i + rot / 2])
          (rotate-half, ASSUMED). Causal softmax at head_dim^-1/2; on a
          sliding layer query i sees keys j with 0 <= i - j <
          `sliding_window` (`window_mask`, ASSUMED). `gating`: g = sigmoid(u
          W_g), W_g [hidden, H_l], one gate a head, times that head's
          attention output before W_o (`head_gate`, ASSUMED form).
  rotary  full layers: YaRN, inv_freq blends theta^(-2i/rot) and that over
          `factor` by the linear ramp between the correction dims of
          beta_fast / beta_slow, cos and sin times `attention_factor`;
          sliding layers: the default form, theta^(-2i/rot).
  FFN_l   `mlp_layer_types[l]` "dense": SwiGLU(intermediate_size). "sparse":
          s = sigmoid(u W_r) in float32 (`ROUTER_SCORE`, ASSUMED), the
          `num_experts_per_tok` largest of `num_experts`, no selection bias
          and no groups (ASSUMED), weights s_e / sum of the selected s x
          `moe_routed_scaling_factor` (ASSUMED normalisation) on the
          experts' OUTPUTS, each expert a SwiGLU(moe_intermediate_size);
          plus one shared SwiGLU(shared_expert_intermediate_size) added
          ungated (ASSUMED: no gate key). Every expert is held here
          (`parallel.moe.held_experts_ffn` with all of them).

Precision, as served: weights and pages in the model's dtype (bfloat16);
router scores, rotary, the softmax, the gate's sigmoid and the norms'
statistics float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import profiler as _prof
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.deepseek_v3 import (
    _Experts, _MLP, _Normal, _Weight, dense_ffn, plain_mm, rms_norm,
    rope_half, yarn_correction_range,
)
from paddle_tpu.models.olmo_hybrid import _OnHost
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer import Layer, LayerList
from paddle_tpu.parallel.moe import held_experts_ffn, sigmoid_topk_route

FULL, SLIDING = "full_attention", "sliding_attention"
PERIOD = 4                     # one full layer, then three sliding ones


def _default_rope() -> dict:
    return {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}


@dataclass
class LagunaConfig:
    """The published keys, then `max_seq_len` (rotary tables and the
    serving context), the parameters' `dtype`, and `init`: "normal" draws
    the weights, "deferred" makes the Layer a vessel for weights that
    arrive through `set_state_dict` and leave it for the first runner built
    from it. The three per-layer lists left out are made by the published
    pattern (a full layer every fourth, 48 heads on it and 64 between, a
    dense first feed-forward)."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    gating: bool = True
    sliding_window: int = 512
    rope_parameters: dict = field(default_factory=_default_rope)
    layer_types: Optional[List[str]] = None
    mlp_layer_types: Optional[List[str]] = None
    num_attention_heads_per_layer: Optional[List[int]] = None
    moe_routed_scaling_factor: float = 2.5
    moe_apply_router_weight_on_input: bool = False
    max_seq_len: int = 4096
    dtype: str = "float32"
    init: str = "normal"

    def __post_init__(self):
        L = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = [SLIDING if i % PERIOD else FULL
                                for i in range(L)]
        if self.mlp_layer_types is None:
            self.mlp_layer_types = ["sparse" if i else "dense"
                                    for i in range(L)]
        if self.num_attention_heads_per_layer is None:
            wide = self.num_attention_heads * 4 // 3
            self.num_attention_heads_per_layer = [
                self.num_attention_heads if t == FULL else wide
                for t in self.layer_types[:L]]
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            if len(getattr(self, name)) < L:
                raise ValueError(f"{name} has {len(getattr(self, name))} "
                                 f"entries for {L} layers")
        for i in range(L):
            if self.layer_types[i] not in (FULL, SLIDING) \
                    or self.mlp_layer_types[i] not in ("dense", "sparse"):
                raise ValueError(
                    f"layer {i}: {self.layer_types[i]!r} / "
                    f"{self.mlp_layer_types[i]!r} is no kind of layer here")
            if self.heads(i) % self.num_key_value_heads:
                raise ValueError(
                    f"layer {i}: {self.heads(i)} query heads over "
                    f"{self.num_key_value_heads} key/value heads")
            rot = self.rotary_dim(self.layer_types[i])
            if rot % 2 or not 0 < rot <= self.head_dim:
                raise ValueError(f"layer {i}: a rotary part of {rot}")
        if not self.gating:
            raise ValueError("gating=False is not built")
        if self.moe_apply_router_weight_on_input:
            raise ValueError("router weights on the experts' input are not "
                             "built (the published value is false)")
        if self.init not in ("normal", "deferred"):
            raise ValueError(f"init={self.init!r}; expected 'normal' or "
                             "'deferred'")

    def kind(self, layer: int) -> str:
        return self.layer_types[layer]

    def heads(self, layer: int) -> int:
        return self.num_attention_heads_per_layer[layer]

    def is_dense(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == "dense"

    def layers_of(self, kind: str) -> list:
        return [i for i in range(self.num_hidden_layers)
                if self.kind(i) == kind]

    def rotary_dim(self, kind: str) -> int:
        return int(self.head_dim
                   * self.rope_parameters[kind]["partial_rotary_factor"])

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim


# ------------------------------------------- functions (Layer and runner)


def rope_tables(cfg: LagunaConfig, kind: str, n: int):
    """cos, sin [n, rot] float32 of positions 0..n-1 for a layer type: the
    half-width angles twice over (rotate-half)."""
    rp, rot = cfg.rope_parameters[kind], cfg.rotary_dim(kind)
    inv = 1.0 / rp["rope_theta"] ** (
        jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    scale = 1.0
    if rp["rope_type"] == "yarn":
        low, high = yarn_correction_range(
            rp["beta_fast"], rp["beta_slow"], rot, rp["rope_theta"],
            rp["original_max_position_embeddings"])
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        inv = inv / rp["factor"] * ramp + inv * (1.0 - ramp)
        scale = rp["attention_factor"]
    elif rp["rope_type"] != "default":
        raise ValueError(f"rope_type {rp['rope_type']!r} is not built")
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def apply_rope(x, cos, sin):
    """x [..., heads, head_dim]; cos, sin [..., rot]: the first `rot` values
    of each head rotated (rotate-half pairing), the rest as they are."""
    rot = cos.shape[-1]
    turned = rope_half(x[..., :rot], cos[..., None, :], sin[..., None, :])
    return turned if rot == x.shape[-1] else jnp.concatenate(
        [turned, x[..., rot:]], axis=-1)


def head_gate(params, pre: str, u, mm=plain_mm):
    """g [..., H_l] float32: one sigmoid gate a query head, from the
    sublayer's normed input."""
    return jax.nn.sigmoid(mm(params, pre + "g_proj.weight", u
                             ).astype(jnp.float32))


def window_mask(cfg: LagunaConfig, q_pos, k_pos):
    """[q, k] bool: key j is seen by query i where 0 <= i - j < window."""
    d = q_pos[:, None] - k_pos[None, :]
    return (d >= 0) & (d < cfg.sliding_window)


def attention_qkvg(cfg, params, pre: str, layer: int, u, cos, sin,
                   mm=plain_mm):
    """From the normed input u [..., hidden] at the positions whose rotary
    rows are cos / sin [..., rot]: q [..., H_l, d] and k [..., kv, d] with
    rotary applied, v [..., kv, d], g [..., H_l] float32."""
    lead, d = u.shape[:-1], cfg.head_dim
    q = mm(params, pre + "q_proj.weight", u).reshape(*lead, cfg.heads(layer),
                                                     d)
    k = mm(params, pre + "k_proj.weight", u).reshape(
        *lead, cfg.num_key_value_heads, d)
    v = mm(params, pre + "v_proj.weight", u).reshape(
        *lead, cfg.num_key_value_heads, d)
    return (apply_rope(q, cos, sin), apply_rope(k, cos, sin), v,
            head_gate(params, pre, u, mm))


def gated_output(params, pre: str, o, g, dtype, mm=plain_mm):
    """o [..., H_l, d] (a head's attention output), g [..., H_l] -> the
    sublayer's output [..., hidden] in `dtype`."""
    y = (o.astype(jnp.float32) * g[..., None]).astype(dtype)
    return mm(params, pre + "o_proj.weight",
              y.reshape(*y.shape[:-2], -1))


def dense_attention(cfg, q, k, v, mask):
    """q [T, H, d]; k, v [S, kv, d]; mask [T, S] bool -> [T, H, d] float32:
    the plain form, every score made."""
    T, H, d = q.shape
    kv = k.shape[1]
    qg = q.astype(jnp.float32).reshape(T, kv, H // kv, d)
    s = jnp.einsum("tgrd,sgd->grts", qg, k.astype(jnp.float32)) * d ** -0.5
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("grts,sgd->tgrd", p, v.astype(jnp.float32)
                      ).reshape(T, H, d)


def plain_stack(params, name):
    return params[name]


def moe_ffn(cfg, params, pre: str, h, valid=None, mm=plain_mm,
            stack=plain_stack):
    """One expert layer on h [N, hidden]: (y [N, hidden], counts int32[5])
    with counts = (tokens routed, token-expert pairs, experts touched, row
    blocks walked, rows multiplied). `stack(params, name)` is the named
    stack of experts' matrices (a runner passes its own, which may
    dequantize)."""
    with jax.named_scope("block/moe/router"):
        idx, w = sigmoid_topk_route(
            h, params[pre + "gate.weight"], None, cfg.num_experts_per_tok,
            norm_topk_prob=True, scale=cfg.moe_routed_scaling_factor)
    with jax.named_scope("block/moe/experts"):
        y, pairs, touched, blocks, rows = held_experts_ffn(
            h, idx, w, stack(params, pre + "experts.gate_proj"),
            stack(params, pre + "experts.up_proj"),
            stack(params, pre + "experts.down_proj"), 0, valid, walk=True)
    with jax.named_scope("block/moe/shared"):
        y = y + dense_ffn(params, pre + "shared_experts.", h, mm
                          ).astype(jnp.float32)
    routed = (jnp.sum(valid.astype(jnp.int32)) if valid is not None
              else jnp.int32(h.shape[0]))
    return y.astype(h.dtype), jnp.stack([
        routed, *(c.astype(jnp.int32) for c in (pairs, touched, blocks,
                                                rows))])


def ffn(cfg, params, layer: int, h, valid=None, mm=plain_mm,
        stack=plain_stack):
    """Layer `layer`'s feed-forward on h [N, hidden]: (y, counts int32[5]
    as `moe_ffn` gives them, zeros for a dense layer)."""
    pre = f"layers.{layer}.mlp."
    if cfg.is_dense(layer):
        with jax.named_scope("block/mlp"):
            return dense_ffn(params, pre, h, mm), jnp.zeros((5,), jnp.int32)
    return moe_ffn(cfg, params, pre, h, valid, mm, stack)


def forward_plain(cfg: LagunaConfig, params: dict, tokens):
    """Logits [b, s, vocab] of whole sequences from position 0: dense
    masks, every layer on every row, no cache."""
    T = tokens.shape[1]
    pos = jnp.arange(T)
    tables = {k: rope_tables(cfg, k, T) for k in (FULL, SLIDING)}
    masks = {FULL: pos[None, :] <= pos[:, None],
             SLIDING: window_mask(cfg, pos, pos)}

    def one(ids):
        x = jnp.take(params["embed_tokens.weight"], ids, axis=0)
        for i in range(cfg.num_hidden_layers):
            pre, kind = f"layers.{i}.", cfg.kind(i)
            u = rms_norm(x, params[pre + "input_layernorm.weight"],
                         cfg.rms_norm_eps)
            q, k, v, g = attention_qkvg(cfg, params, pre + "self_attn.", i,
                                        u, *tables[kind])
            o = dense_attention(cfg, q, k, v, masks[kind])
            x = x + gated_output(params, pre + "self_attn.", o, g, x.dtype)
            h = rms_norm(x, params[pre + "post_attention_layernorm.weight"],
                         cfg.rms_norm_eps)
            x = x + ffn(cfg, params, i, h)[0]
        x = rms_norm(x, params["norm.weight"], cfg.rms_norm_eps)
        return x @ params["lm_head.weight"]

    return jnp.stack([one(ids) for ids in tokens])


# ----------------------------------------------------------------- Layer


class _Inits:
    def __init__(self, cfg):
        if cfg.init == "deferred":
            self.w_in = self.w_out = self.one = _OnHost()
        else:
            self.w_in = _Normal(0.02)
            self.w_out = _Normal(0.02 / math.sqrt(2 * cfg.num_hidden_layers))
            self.one = I.Constant(1.0)


class _Gate(Layer):
    def __init__(self, hidden, n_experts, init, dtype):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter([hidden, n_experts],
                                            default_initializer=init)


class _MoE(Layer):
    def __init__(self, cfg, init):
        super().__init__(dtype=cfg.dtype)
        h, dt = cfg.hidden_size, cfg.dtype
        self.gate = _Gate(h, cfg.num_experts, init.w_in, dt)
        self.experts = _Experts(cfg.num_experts, h,
                                cfg.moe_intermediate_size, init.w_in,
                                init.w_out, dt)
        self.shared_experts = _MLP(h, cfg.shared_expert_intermediate_size,
                                   init.w_in, init.w_out, dt)


class _Attention(Layer):
    def __init__(self, cfg, layer: int, init):
        super().__init__(dtype=cfg.dtype)
        h, dt = cfg.hidden_size, cfg.dtype
        qw = cfg.heads(layer) * cfg.head_dim
        self.q_proj = _Weight((h, qw), init.w_in, dt)
        self.k_proj = _Weight((h, cfg.kv_width), init.w_in, dt)
        self.v_proj = _Weight((h, cfg.kv_width), init.w_in, dt)
        self.g_proj = _Weight((h, cfg.heads(layer)), init.w_in, dt)
        self.o_proj = _Weight((qw, h), init.w_out, dt)


class _Block(Layer):
    def __init__(self, cfg, layer: int):
        super().__init__(dtype=cfg.dtype)
        init, h = _Inits(cfg), cfg.hidden_size
        self.input_layernorm = _Weight((h,), init.one, cfg.dtype)
        self.self_attn = _Attention(cfg, layer, init)
        self.post_attention_layernorm = _Weight((h,), init.one, cfg.dtype)
        self.mlp = (_MLP(h, cfg.intermediate_size, init.w_in, init.w_out,
                         cfg.dtype) if cfg.is_dense(layer)
                    else _MoE(cfg, init))


class LagunaForCausalLM(Layer):
    """The decoder. Every parameter is made in `cfg.dtype` directly, so a
    bfloat16 model never has a float32 copy beside it."""

    def __init__(self, cfg: LagunaConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        with _prof.always_span("model.build", model="LagunaForCausalLM",
                               layers=cfg.num_hidden_layers):
            init = _Inits(cfg)
            self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size),
                                        init.w_in, cfg.dtype)
            self.layers = LayerList([_Block(cfg, i) for i in
                                     range(cfg.num_hidden_layers)])
            self.norm = _Weight((cfg.hidden_size,), init.one, cfg.dtype)
            self.lm_head = _Weight((cfg.hidden_size, cfg.vocab_size),
                                   init.w_in, cfg.dtype)

    def release_weights(self) -> None:
        """Put the host placeholders back (`init="deferred"`: the weights
        have gone on to a runner, and the device holds them once)."""
        for _, p in self.named_parameters():
            p._value = np.zeros(p._value.shape, p._value.dtype)

    def forward(self, input_ids):
        """Logits [b, s, vocab] (plain form, inference only)."""
        params = {k: p._value for k, p in self.named_parameters()}
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        return Tensor._wrap(forward_plain(self.cfg, params, ids))
