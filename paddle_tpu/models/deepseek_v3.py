"""The DeepSeek-V3 block (published class `DeepseekV3ForCausalLM`; also
`model_type: kimi_k2`): latent attention (MLA) with YaRN rotary tables, a
leading dense SwiGLU layer, then routed + shared expert layers.

Served, one chip's share; not trained: the `Layer` holds ONE rank's part of
an expert-parallel deployment (`experts_held` of the `n_routed_experts`,
from `first_expert`; attention, router and shared expert whole) and its
eager `forward` is the expanded form of the attention in plain ops, with
no autograd tape. `serving.model_runner.DeepseekV3Runner` serves it through
latent pages from the same functions below.

The equations (x [T, hidden]; RMSNorm in float32; linears [in, out], no bias):
  MLA   c_q = RMSNorm(x W_qa); q = c_q W_qb -> heads x [q_nope | q_rope];
        [c_kv | k_r] = x W_kva; c_kv = RMSNorm(c_kv); k_r one vector a token.
        RoPE on q_rope and k_r, pairs taken interleaved, then rotate-half.
        expanded: [k_nope_h | v_h] = c_kv W_kvb per head, causal softmax of
        (q_nope_h . k_nope_h + q_rope_h . k_r) * scale over v_h.
        absorbed (the same function): q~_h = q_nope_h (W_kvb^K_h)^T, scores
        (q~_h . c_kv + q_rope_h . k_r) * scale, o_h = (sum p c_kv) W_kvb^V_h:
        the cache holds c_kv | k_r, kv_lora_rank + qk_rope_head_dim values.
  YaRN  inv_freq blends theta^(-2i/d) and theta^(-2i/d) / factor by the
        linear ramp between the correction dims of beta_fast / beta_slow;
        scale = (nope + rope)^(-1/2) * m(mscale_all_dim)^2, m(x) = 0.1 x
        ln(factor) + 1; cos/sin times m(mscale) / m(mscale_all_dim).
  FFN   dense layers: SwiGLU(intermediate_size). Expert layers:
        parallel.moe.sigmoid_topk_route + held_experts_ffn + a shared SwiGLU.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu import profiler as _prof
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer import Layer, LayerList
from paddle_tpu.parallel.moe import held_experts_ffn, sigmoid_topk_route


@dataclass
class DeepseekV3Config:
    """The published keys, plus the share (`experts_held`, `first_expert`),
    `max_seq_len` (rotary tables and the serving context) and the
    parameters' `dtype`."""
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=lambda: {
        "type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0})
    experts_held: Optional[int] = None     # None = all of them
    first_expert: int = 0
    max_seq_len: int = 4096
    dtype: str = "float32"

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        if not (0 <= self.first_expert and self.first_expert
                + self.experts_held <= self.n_routed_experts):
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert} + "
                f"{self.experts_held}) do not lie in the router's "
                f"{self.n_routed_experts}")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values a cached token holds per layer: c_kv | k_r."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace


# ---------------------------------------------------------------- rotary


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(beta_fast, beta_slow, dim, base, original_max):
    """DeepSeek's yarn_find_correction_range."""
    def dim_of(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    return (max(math.floor(dim_of(beta_fast)), 0),
            min(math.ceil(dim_of(beta_slow)), dim - 1))


def yarn_inv_freq(cfg: DeepseekV3Config):
    """[rope/2] float32; plain theta^(-2i/d) without a `rope_scaling`."""
    d, rs = cfg.qk_rope_head_dim, cfg.rope_scaling
    extra = 1.0 / cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32)
                                     / d)
    if not rs or rs.get("factor", 1) <= 1:
        return extra
    low, high = yarn_correction_range(
        rs["beta_fast"], rs["beta_slow"], d, cfg.rope_theta,
        rs["original_max_position_embeddings"])
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / rs["factor"] * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg: DeepseekV3Config) -> float:
    rs = cfg.rope_scaling or {}
    m = yarn_mscale(rs.get("factor", 1), rs.get("mscale_all_dim", 0))
    return cfg.qk_head_dim ** -0.5 * m * m


def rope_tables(cfg: DeepseekV3Config, n: int):
    """cos, sin [n, rope] float32 for positions 0..n-1."""
    rs = cfg.rope_scaling or {}
    ang = (jnp.arange(n, dtype=jnp.float32)[:, None]
           * yarn_inv_freq(cfg)[None, :])
    ang = jnp.concatenate([ang, ang], axis=-1)
    m = (yarn_mscale(rs.get("factor", 1), rs.get("mscale", 0))
         / yarn_mscale(rs.get("factor", 1), rs.get("mscale_all_dim", 0)))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def rope_interleaved(x, cos, sin):
    """x [..., rope], cos/sin broadcastable: pairs (x[2i], x[2i+1]) into two
    halves as the published model does, then rotate-half; float32 inside."""
    xf = x.astype(jnp.float32)
    xf = jnp.concatenate([xf[..., 0::2], xf[..., 1::2]], axis=-1)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return (xf * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
            ).astype(x.dtype)


# ---------------------------------------------------------------- pieces
# functions of a flat params dict (`layers.<i>.<leaf>`, the Layer's own
# names) and `mm(params, name, x)`, the matmul against a named weight (a
# runner passes its own, which may dequantize)


def plain_mm(params, name, x):
    return x @ params[name]


def rms_norm(x, w, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            ).astype(x.dtype)


def mla_project(cfg, params, pre: str, h, cos, sin, mm=plain_mm):
    """h [..., hidden] (normed), cos/sin [..., rope] at its positions ->
    q_nope [..., nh, nope], q_rope [..., nh, rope] (rotated), latent
    [..., kv_lora_rank + rope]: c_kv after its norm | k_r rotated, what
    the cache holds."""
    nh, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    c_q = rms_norm(mm(params, pre + "self_attn.q_a_proj.weight", h),
                   params[pre + "self_attn.q_a_layernorm.weight"],
                   cfg.rms_norm_eps)
    q = mm(params, pre + "self_attn.q_b_proj.weight", c_q)
    q = q.reshape(*q.shape[:-1], nh, cfg.qk_head_dim)
    kv = mm(params, pre + "self_attn.kv_a_proj_with_mqa.weight", h)
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank],
                    params[pre + "self_attn.kv_a_layernorm.weight"],
                    cfg.rms_norm_eps)
    k_r = rope_interleaved(kv[..., cfg.kv_lora_rank:], cos, sin)
    q_r = rope_interleaved(q[..., nope:], cos[..., None, :],
                           sin[..., None, :])
    return q[..., :nope], q_r, jnp.concatenate([c_kv, k_r], axis=-1)


def kv_b_heads(cfg, w_kvb):
    """kv_b_proj [kv_lora_rank, nh * (nope + v)] as (W^K [c, nh, nope],
    W^V [c, nh, v])."""
    w = w_kvb.reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                      cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


# key rows of one block of the blocked expanded attention, and the query
# rows; under DENSE_PAIRS query x key pairs the scores exist whole
Q_BLOCK, K_BLOCK, DENSE_PAIRS = 512, 1024, 1 << 20


def expanded_attention(cfg, q_nope, q_rope, latent, w_kvb, start, n_live):
    """The expanded form for ONE sequence: q_* [T, nh, .] are the queries
    at context positions start .. start+T-1 (rows from n_live on are
    padding), latent [L, >= latent_dim] the cache rows of positions 0..L-1
    (garbage past the context: masked by position). Per-head keys and
    values are rebuilt from the latent (bf16 on the MXU where the inputs
    are), scores and softmax in float32. Returns [T, nh * v]. Long spans
    are walked in blocks of query rows, each over the key blocks its rows
    can see (dynamic trip counts: padding rows and unseen keys cost
    nothing); the scores of a block pair are all that exists."""
    T, nh, _ = q_nope.shape
    L = latent.shape[0]
    scale = softmax_scale(cfg)
    w_k, w_v = kv_b_heads(cfg, w_kvb)
    c_kv = latent[:, :cfg.kv_lora_rank]
    k_r = latent[:, cfg.kv_lora_rank:cfg.latent_dim]           # [L, rope]
    k_n = jnp.einsum("lc,chd->hld", c_kv, w_k)                  # [nh, L, nope]
    v = jnp.einsum("lc,chd->hld", c_kv, w_v)                    # [nh, L, v]
    qn = jnp.swapaxes(q_nope, 0, 1)                             # [nh, T, nope]
    qr = jnp.swapaxes(q_rope, 0, 1)

    def scores(qn_b, qr_b, kn_b, kr_b, q_pos, k_pos):
        s = (jnp.einsum("hqd,hkd->hqk", qn_b, kn_b,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("hqd,kd->hqk", qr_b, kr_b,
                          preferred_element_type=jnp.float32)) * scale
        return jnp.where(k_pos[None, None, :] <= q_pos[None, :, None], s,
                         -1e30)

    if T * L <= DENSE_PAIRS or T % Q_BLOCK or L % K_BLOCK:
        s = scores(qn, qr, k_n, k_r, start + jnp.arange(T), jnp.arange(L))
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("hqk,hkd->qhd", p, v)
        return o.reshape(T, nh * cfg.v_head_dim)

    def q_block(i, out):
        q0 = i * Q_BLOCK
        qn_b = jax.lax.dynamic_slice_in_dim(qn, q0, Q_BLOCK, 1)
        qr_b = jax.lax.dynamic_slice_in_dim(qr, q0, Q_BLOCK, 1)
        q_pos = start + q0 + jnp.arange(Q_BLOCK)

        def k_block(j, carry):
            m, l, acc = carry
            k0 = j * K_BLOCK
            s = scores(qn_b, qr_b,
                       jax.lax.dynamic_slice_in_dim(k_n, k0, K_BLOCK, 1),
                       jax.lax.dynamic_slice_in_dim(k_r, k0, K_BLOCK, 0),
                       q_pos, k0 + jnp.arange(K_BLOCK))
            new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - new_m)
            corr = jnp.exp(m - new_m)
            pv = jnp.einsum(
                "hqk,hkd->hqd", p.astype(v.dtype),
                jax.lax.dynamic_slice_in_dim(v, k0, K_BLOCK, 1),
                preferred_element_type=jnp.float32)
            return (new_m, l * corr + jnp.sum(p, axis=-1, keepdims=True),
                    acc * corr + pv)

        n_k = jnp.minimum((start + q0 + Q_BLOCK + K_BLOCK - 1) // K_BLOCK,
                          L // K_BLOCK)
        m, l, acc = jax.lax.fori_loop(0, n_k, k_block, (
            jnp.full((nh, Q_BLOCK, 1), -1e30, jnp.float32),
            jnp.zeros((nh, Q_BLOCK, 1), jnp.float32),
            jnp.zeros((nh, Q_BLOCK, cfg.v_head_dim), jnp.float32)))
        o = (acc / jnp.maximum(l, 1e-30)).astype(out.dtype)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.swapaxes(o, 0, 1), q0, 0)

    out = jax.lax.fori_loop(
        0, (n_live + Q_BLOCK - 1) // Q_BLOCK, q_block,
        jnp.zeros((T, nh, cfg.v_head_dim), q_nope.dtype))
    return out.reshape(T, nh * cfg.v_head_dim)


def absorb_queries(cfg, q_nope, q_rope, w_kvb, lanes: int):
    """Queries of the absorbed form, [..., nh, lanes]: q_nope through
    (W^K_h)^T | q_rope | zeros up to a page's lane count."""
    w_k, _ = kv_b_heads(cfg, w_kvb)
    q_abs = jnp.einsum("...hd,chd->...hc", q_nope, w_k)
    pad = jnp.zeros((*q_abs.shape[:-1], lanes - cfg.latent_dim), q_abs.dtype)
    return jnp.concatenate([q_abs, q_rope.astype(q_abs.dtype), pad], axis=-1)


def absorb_outputs(cfg, o_latent, w_kvb):
    """[..., nh, kv_lora_rank] sums of p c_kv -> [..., nh * v] through
    W^V_h."""
    _, w_v = kv_b_heads(cfg, w_kvb)
    o = jnp.einsum("...hc,chd->...hd", o_latent, w_v)
    return o.reshape(*o.shape[:-2], -1)


# rows of one block of an MLP over a long span
ROW_BLOCK = 2048


def _by_rows(fn, x):
    n = x.shape[0]
    if n <= ROW_BLOCK or n % ROW_BLOCK:
        return fn(x)
    out = jax.lax.map(fn, x.reshape(n // ROW_BLOCK, ROW_BLOCK, x.shape[1]))
    return out.reshape(n, out.shape[-1])


def dense_ffn(params, pre: str, h, mm=plain_mm):
    """SwiGLU through the named weights, h [N, hidden], blocked over rows
    so that a long prefill's [N, intermediate] products never exist whole."""
    def rows(hb):
        g = mm(params, pre + "gate_proj.weight", hb).astype(jnp.float32)
        u = mm(params, pre + "up_proj.weight", hb).astype(jnp.float32)
        return mm(params, pre + "down_proj.weight",
                  (jax.nn.silu(g) * u).astype(hb.dtype))

    return _by_rows(rows, h)


def moe_ffn(cfg, params, pre: str, h, valid=None, mm=plain_mm):
    """One expert layer on h [N, hidden]: (y [N, hidden], counts int32[3])
    with counts = (tokens routed, token-expert pairs computed here, held
    experts touched)."""
    with jax.named_scope("block/moe/router"):
        idx, w = sigmoid_topk_route(
            h, params[pre + "gate.weight"],
            params[pre + "gate.e_score_correction_bias"],
            cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor)
    with jax.named_scope("block/moe/experts"):
        y, pairs, touched = held_experts_ffn(
            h, idx, w, params[pre + "experts.gate_proj"],
            params[pre + "experts.up_proj"],
            params[pre + "experts.down_proj"], cfg.first_expert, valid)
    with jax.named_scope("block/moe/shared"):
        y = y + dense_ffn(params, pre + "shared_experts.", h, mm
                          ).astype(jnp.float32)
    routed = (jnp.sum(valid.astype(jnp.int32)) if valid is not None
              else jnp.int32(h.shape[0]))
    return y.astype(h.dtype), jnp.stack(
        [routed, pairs.astype(jnp.int32), touched.astype(jnp.int32)])


def forward_expanded(cfg: DeepseekV3Config, params: dict, tokens):
    """Logits [b, s, vocab] of tokens [b, s]: every block in the expanded
    form, no cache."""
    b, s = tokens.shape
    cos, sin = rope_tables(cfg, s)
    x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        with jax.named_scope("block/mla"):
            h = rms_norm(x, params[pre + "input_layernorm.weight"],
                         cfg.rms_norm_eps)
            qn, qr, lat = mla_project(cfg, params, pre, h, cos, sin)
            w_kvb = params[pre + "self_attn.kv_b_proj.weight"]
            o = jax.vmap(lambda a, c, d: expanded_attention(
                cfg, a, c, d, w_kvb, 0, s))(qn, qr, lat)
            x = x + o @ params[pre + "self_attn.o_proj.weight"]
        h = rms_norm(x, params[pre + "post_attention_layernorm.weight"],
                     cfg.rms_norm_eps).reshape(b * s, -1)
        if cfg.is_dense(i):
            with jax.named_scope("block/mlp"):
                f = dense_ffn(params, pre + "mlp.", h)
        else:
            f, _ = moe_ffn(cfg, params, pre + "mlp.", h)
        x = x + f.reshape(b, s, -1)
    x = rms_norm(x, params["norm.weight"], cfg.rms_norm_eps)
    return x @ params["lm_head.weight"]


# ----------------------------------------------------------------- Layer


class _Weight(Layer):
    """One `weight` parameter, drawn in the model's dtype."""

    def __init__(self, shape, init, dtype):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(list(shape),
                                            default_initializer=init)


class _Gate(Layer):
    def __init__(self, hidden, n_experts, dtype):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(
            [hidden, n_experts], default_initializer=I.Normal(0.0, 0.02))
        self.e_score_correction_bias = self.create_parameter(
            [n_experts], default_initializer=I.Constant(0.0))


class _Experts(Layer):
    """The held experts' matrices, stacked on a leading axis."""

    def __init__(self, n, hidden, width, w_in, w_out, dtype):
        super().__init__(dtype=dtype)
        self.gate_proj = self.create_parameter([n, hidden, width],
                                               default_initializer=w_in)
        self.up_proj = self.create_parameter([n, hidden, width],
                                             default_initializer=w_in)
        self.down_proj = self.create_parameter([n, width, hidden],
                                               default_initializer=w_out)


class _MLP(Layer):
    def __init__(self, hidden, width, w_in, w_out, dtype):
        super().__init__(dtype=dtype)
        self.gate_proj = _Weight((hidden, width), w_in, dtype)
        self.up_proj = _Weight((hidden, width), w_in, dtype)
        self.down_proj = _Weight((width, hidden), w_out, dtype)


class _MoE(Layer):
    def __init__(self, cfg, w_in, w_out):
        super().__init__(dtype=cfg.dtype)
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        self.gate = _Gate(h, cfg.n_routed_experts, cfg.dtype)
        self.experts = _Experts(cfg.experts_held, h, f, w_in, w_out,
                                cfg.dtype)
        self.shared_experts = _MLP(h, f * cfg.n_shared_experts, w_in, w_out,
                                   cfg.dtype)


class _Attention(Layer):
    def __init__(self, cfg, w_in, w_out):
        super().__init__(dtype=cfg.dtype)
        h, nh, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
        one = I.Constant(1.0)
        self.q_a_proj = _Weight((h, cfg.q_lora_rank), w_in, dt)
        self.q_a_layernorm = _Weight((cfg.q_lora_rank,), one, dt)
        self.q_b_proj = _Weight((cfg.q_lora_rank, nh * cfg.qk_head_dim),
                                w_in, dt)
        self.kv_a_proj_with_mqa = _Weight((h, cfg.latent_dim), w_in, dt)
        self.kv_a_layernorm = _Weight((cfg.kv_lora_rank,), one, dt)
        self.kv_b_proj = _Weight(
            (cfg.kv_lora_rank,
             nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)), w_in, dt)
        self.o_proj = _Weight((nh * cfg.v_head_dim, h), w_out, dt)


class _Block(Layer):
    def __init__(self, cfg, layer: int):
        super().__init__(dtype=cfg.dtype)
        w_in = I.Normal(0.0, 0.02)
        w_out = I.Normal(0.0, 0.02 / math.sqrt(2 * cfg.num_hidden_layers))
        one = I.Constant(1.0)
        self.input_layernorm = _Weight((cfg.hidden_size,), one, cfg.dtype)
        self.self_attn = _Attention(cfg, w_in, w_out)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,), one,
                                                cfg.dtype)
        self.mlp = (_MLP(cfg.hidden_size, cfg.intermediate_size, w_in, w_out,
                         cfg.dtype) if cfg.is_dense(layer)
                    else _MoE(cfg, w_in, w_out))


class DeepseekV3ForCausalLM(Layer):
    """One rank's share of the decoder. Every parameter is drawn in
    `cfg.dtype` directly (a leaf's float32 draw is the largest temporary),
    so a bfloat16 model of 7 GB never has a float32 copy beside it."""

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        with _prof.always_span("model.build", model="DeepseekV3ForCausalLM",
                               layers=cfg.num_hidden_layers):
            w = I.Normal(0.0, 0.02)
            self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size), w,
                                        cfg.dtype)
            self.layers = LayerList([_Block(cfg, i) for i in
                                     range(cfg.num_hidden_layers)])
            self.norm = _Weight((cfg.hidden_size,), I.Constant(1.0),
                                cfg.dtype)
            self.lm_head = _Weight((cfg.hidden_size, cfg.vocab_size), w,
                                   cfg.dtype)

    def forward(self, input_ids):
        """Logits [b, s, vocab] (expanded form, inference only)."""
        params = {k: p._value for k, p in self.named_parameters()}
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        return Tensor._wrap(forward_expanded(self.cfg, params, ids))
