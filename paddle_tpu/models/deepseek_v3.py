"""The DeepSeek-V3 block (published class `DeepseekV3ForCausalLM`; also
`model_type: kimi_k2` and `deepseek_v32`): latent attention (MLA) with YaRN
rotary tables, leading dense SwiGLU layers, then routed + shared expert
layers. With `index_topk` set it is the DeepSeek-V3.2 block: a lightning
indexer scores every earlier token for each query, the `index_topk` best
are selected, and the latent attention runs over those alone (DeepSeek
Sparse Attention); with `n_group` > 1 the router is group-limited; with
`num_nextn_predict_layers` > 0 a multi-token-prediction module is built
(written and tested, not served). None of the three set: Kimi's block and
Kimi's programs.

Served, one chip's share; not trained: the `Layer` holds ONE rank's part of
an expert-parallel deployment (`experts_held` of the `n_routed_experts`,
from `first_expert`; attention, router and shared expert whole) and its
eager `forward` is the expanded form of the attention in plain ops, with
no autograd tape. `serving/runners/deepseek_v3.py` serves it through
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
        parallel.moe.sigmoid_topk_route (group-limited where n_group > 1)
        + held_experts_ffn + a shared SwiGLU.
  DSA   (index_topk set; every layer) q^I = c_q W^I_qb -> index_n_heads x
        index_head_dim; k^I = LayerNorm(x W^I_k) (gain and bias, eps 1e-6),
        one vector a token; RoPE on the FIRST qk_rope_head_dim values of
        each q^I_j and of k^I, from MLA's YaRN table, pairs NOT interleaved
        (rotate-half over the rotary part as it lies); both times the
        normalised Hadamard matrix; w = (x W^I_w) * heads^(-1/2) *
        head_dim^(-1/2). I(t, s) = sum_j w_j(t) ReLU(q^I_j(t) . k^I(s)) for
        s <= t, float32. S(t) = positions of the min(index_topk, t + 1)
        largest I(t, .), ties to the lower position; MLA's softmax and
        values run over s in S(t) only, one set for all heads. The cache
        holds k^I (after norm, RoPE and rotation) beside c_kv | k_r.
  MTP   (num_nextn_predict_layers > 0) h' = Block_L(W_eh [RMSNorm(h_t) |
        RMSNorm(Emb(x_{t+1}))]), h_t the residual stream after the last
        block; logits for token t + 2 = Head(RMSNorm(h')), embedding and
        head the model's own.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu import profiler as _prof
from paddle_tpu.core import dtype as dtype_mod
from paddle_tpu.core.random import default_generator
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
    # the router's group limit ("noaux_tc"): one group = none
    n_group: int = 1
    topk_group: int = 1
    # the lightning indexer and its selection; index_topk None = no indexer
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: Optional[int] = None
    # multi-token-prediction modules after the last block (0 or 1)
    num_nextn_predict_layers: int = 0

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
        if (self.n_routed_experts % self.n_group
                or not 1 <= self.topk_group <= self.n_group):
            raise ValueError(
                f"{self.n_routed_experts} experts in {self.n_group} groups, "
                f"{self.topk_group} kept: groups are equal and kept <= all")
        if self.index_topk is not None and (
                self.index_head_dim < self.qk_rope_head_dim
                or self.index_head_dim & (self.index_head_dim - 1)):
            raise ValueError(
                f"index_head_dim={self.index_head_dim}: a power of two (the "
                "Hadamard rotation) that holds the rotary part")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one multi-token-prediction module at most")

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


def rope_half(x, cos, sin):
    """x [..., rope], cos/sin broadcastable: rotate-half over the values as
    they lie (pairs (x[i], x[i + rope/2]), NOT interleaved): the indexer's
    pairing; float32 inside."""
    xf = x.astype(jnp.float32)
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


def mla_queries(cfg, c_q, w_qb, cos, sin):
    """c_q [..., q_lora_rank] through `w_qb` [q_lora_rank, heads * (nope +
    rope)] (all heads or a group of them) -> q_nope [..., heads, nope],
    q_rope [..., heads, rope] (rotated)."""
    q = c_q @ w_qb
    q = q.reshape(*q.shape[:-1], -1, cfg.qk_head_dim)
    q_r = rope_interleaved(q[..., cfg.qk_nope_head_dim:], cos[..., None, :],
                           sin[..., None, :])
    return q[..., :cfg.qk_nope_head_dim], q_r


def mla_project(cfg, params, pre: str, h, cos, sin, mm=plain_mm):
    """h [..., hidden] (normed), cos/sin [..., rope] at its positions ->
    q_nope [..., nh, nope], q_rope [..., nh, rope] (rotated), latent
    [..., kv_lora_rank + rope]: c_kv after its norm | k_r rotated, what
    the cache holds, and c_q [..., q_lora_rank] after its norm (the
    indexer's queries come from it too)."""
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
    return q[..., :nope], q_r, jnp.concatenate([c_kv, k_r], axis=-1), c_q


def kv_b_heads(cfg, w_kvb):
    """kv_b_proj [kv_lora_rank, heads * (nope + v)] (all heads or a group
    of them) as (W^K [c, heads, nope], W^V [c, heads, v])."""
    w = w_kvb.reshape(cfg.kv_lora_rank, -1,
                      cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


# ------------------------------------------------ the indexer (DSA)


def layer_norm(x, w, b, eps: float):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def hadamard(n: int):
    """The n x n Sylvester-Hadamard matrix of +-1 (n a power of two),
    float32, NOT normalised: +-1 is exact in every type."""
    h = jnp.ones((1, 1), jnp.float32)
    while h.shape[0] < n:
        h = jnp.block([[h, h], [h, -h]])
    return h


def _rotate(x):
    """x [..., d] times the normalised Hadamard matrix: the +-1 product
    accumulated in float32, scaled there, rounded once."""
    d = x.shape[-1]
    y = jnp.matmul(x, hadamard(d).astype(x.dtype),
                   preferred_element_type=jnp.float32)
    return (y * d ** -0.5).astype(x.dtype)


INDEX_NORM_EPS = 1e-6


def index_project(cfg, params, pre: str, h, c_q, cos, sin, mm=plain_mm):
    """The indexer's view of h [..., hidden] (normed) and c_q: queries
    q^I [..., heads, d] and the token's key k^I [..., d] (what the index
    cache holds), both after RoPE on their first `qk_rope_head_dim` values
    and the rotation, and the heads' weights w [..., heads] float32."""
    nh, d, rd = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    ipre = pre + "self_attn.indexer."
    q = mm(params, ipre + "wq_b.weight", c_q)
    q = q.reshape(*q.shape[:-1], nh, d)
    k = layer_norm(mm(params, ipre + "wk.weight", h),
                   params[ipre + "k_norm.weight"],
                   params[ipre + "k_norm.bias"], INDEX_NORM_EPS)
    q = jnp.concatenate([rope_half(q[..., :rd], cos[..., None, :],
                                   sin[..., None, :]), q[..., rd:]], axis=-1)
    k = jnp.concatenate([rope_half(k[..., :rd], cos, sin), k[..., rd:]],
                        axis=-1)
    w = (mm(params, ipre + "weights_proj.weight", h).astype(jnp.float32)
         * (nh ** -0.5 * d ** -0.5))
    return _rotate(q), _rotate(k), w


def index_scores(q_i, w_i, k_i):
    """I [T, L] float32 = sum_j w_j(t) ReLU(q^I_j(t) . k^I(s)): q_i [T,
    heads, d], w_i [T, heads] float32, k_i [L, d]."""
    s = jnp.einsum("thd,ld->thl", q_i, k_i,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w_i[:, :, None], axis=1)


def topk_threshold(scores, k: int):
    """Where the k largest of each row of scores [R, L] float32 (L > k; no
    NaN) end, ties to the lower index: (value [R] float32, the k-th
    largest; last [R] int32, the position of the last entry EQUAL to it
    that still belongs). Row r's k largest are the entries above value[r]
    and those equal to it at positions <= last[r] (`within_topk`). Exact,
    and no sort: the value is found bit by bit, 32 counting passes over
    the row's order-preserving integer keys, then the position by a second
    search."""
    R, L = scores.shape
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.int32)
    # unsigned keys in float order: a negative's bits flipped, the sign bit
    # of the rest set
    sign = jnp.int32(-2 ** 31)
    u = jax.lax.bitcast_convert_type(
        jnp.where(bits < 0, ~bits, bits ^ sign), jnp.uint32)

    def value_bit(i, v):
        cand = v | (jnp.uint32(1) << jnp.asarray(31 - i, jnp.uint32))
        enough = jnp.sum(u >= cand[:, None], axis=1) >= k
        return jnp.where(enough, cand, v)

    kth = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((R,), jnp.uint32))
    owed = k - jnp.sum(u > kth[:, None], axis=1)         # >= 1 ties to take
    tie = u == kth[:, None]
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    n_bits = max(1, (L - 1).bit_length())

    def pos_bit(i, p):
        # the largest p with fewer than `owed` ties before it
        cand = p | (jnp.int32(1) << (n_bits - 1 - i))
        few = jnp.sum(tie & (pos < cand[:, None]), axis=1) < owed
        return jnp.where(few, cand, p)

    last = jax.lax.fori_loop(0, n_bits, pos_bit, jnp.zeros((R,), jnp.int32))
    kth = jax.lax.bitcast_convert_type(kth, jnp.int32)
    value = jax.lax.bitcast_convert_type(
        jnp.where(kth < 0, kth ^ sign, ~kth), jnp.float32)
    return value, last


def within_topk(scores, pos, value, last):
    """The entries of `scores` (any shape, at positions `pos`) inside the
    selection `topk_threshold` found: `value` and `last` broadcast."""
    x = scores + 0.0                                     # -0.0 counts as 0.0
    return (x > value) | ((x == value) & (pos <= last))


def topk_mask(scores, k: int):
    """bool mask of the k largest of each row of scores [R, L] float32,
    ties to the lower index (everything where L <= k)."""
    R, L = scores.shape
    if L <= k:
        return jnp.ones((R, L), bool)
    value, last = topk_threshold(scores, k)
    return within_topk(scores, jnp.arange(L, dtype=jnp.int32)[None, :],
                       value[:, None], last[:, None])


def selection_mask(cfg, q_i, w_i, k_i, start, n_live):
    """S(t) of ONE sequence as a mask [T, L] bool: row t is the query at
    context position start + t (rows from n_live on are padding), column s
    the key at position s (k_i [L, d], garbage past the context: masked by
    position). Long spans are walked in blocks of query rows, each over
    the key blocks its rows can see; a block's scores [Q_BLOCK, L] are all
    that exists."""
    T, L = q_i.shape[0], k_i.shape[0]
    k_pos = jnp.arange(L, dtype=jnp.int32)

    def select(scores, q_pos):
        visible = k_pos[None, :] <= q_pos[:, None]
        return visible & topk_mask(jnp.where(visible, scores, -jnp.inf),
                                   cfg.index_topk)

    if T * L <= DENSE_PAIRS or T % Q_BLOCK or L % K_BLOCK:
        return select(index_scores(q_i, w_i, k_i), start + jnp.arange(T))

    def q_block(i, out):
        q0 = i * Q_BLOCK
        q_b = jax.lax.dynamic_slice_in_dim(q_i, q0, Q_BLOCK, 0)
        w_b = jax.lax.dynamic_slice_in_dim(w_i, q0, Q_BLOCK, 0)

        def k_block(j, scores):
            k0 = j * K_BLOCK
            return jax.lax.dynamic_update_slice_in_dim(
                scores, index_scores(q_b, w_b, jax.lax.dynamic_slice_in_dim(
                    k_i, k0, K_BLOCK, 0)), k0, 1)

        n_k = jnp.minimum((start + q0 + Q_BLOCK + K_BLOCK - 1) // K_BLOCK,
                          L // K_BLOCK)
        scores = jax.lax.fori_loop(
            0, n_k, k_block, jnp.zeros((Q_BLOCK, L), jnp.float32))
        return jax.lax.dynamic_update_slice_in_dim(
            out, select(scores, start + q0 + jnp.arange(Q_BLOCK)), q0, 0)

    return jax.lax.fori_loop(0, (n_live + Q_BLOCK - 1) // Q_BLOCK, q_block,
                             jnp.zeros((T, L), bool))


# key rows of one block of the blocked expanded attention, and the query
# rows; under DENSE_PAIRS query x key pairs the scores exist whole
Q_BLOCK, K_BLOCK, DENSE_PAIRS = 512, 1024, 1 << 20


def expanded_attention(cfg, q_nope, q_rope, latent, w_kvb, start, n_live,
                       select=None):
    """The expanded form for ONE sequence: q_* [T, nh, .] are the queries
    at context positions start .. start+T-1 (rows from n_live on are
    padding), latent [L, >= latent_dim] the cache rows of positions 0..L-1
    (garbage past the context: masked by position). Per-head keys and
    values are rebuilt from the latent (bf16 on the MXU where the inputs
    are), scores and softmax in float32. Returns [T, nh * v]. Long spans
    are walked in blocks of query rows, each over the key blocks its rows
    can see (dynamic trip counts: padding rows and unseen keys cost
    nothing); the scores of a block pair are all that exists. `select`
    [T, L] bool (`selection_mask`) restricts each query row to its chosen
    keys; nh may be a group of the heads, with its columns of `w_kvb`."""
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

    def scores(qn_b, qr_b, kn_b, kr_b, q_pos, k_pos, chosen=None):
        s = (jnp.einsum("hqd,hkd->hqk", qn_b, kn_b,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("hqd,kd->hqk", qr_b, kr_b,
                          preferred_element_type=jnp.float32)) * scale
        seen = k_pos[None, None, :] <= q_pos[None, :, None]
        if chosen is not None:
            seen = seen & chosen[None]
        return jnp.where(seen, s, -1e30)

    if T * L <= DENSE_PAIRS or T % Q_BLOCK or L % K_BLOCK:
        s = scores(qn, qr, k_n, k_r, start + jnp.arange(T), jnp.arange(L),
                   select)
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
                       q_pos, k0 + jnp.arange(K_BLOCK),
                       None if select is None else jax.lax.dynamic_slice(
                           select, (q0, k0), (Q_BLOCK, K_BLOCK)))
            new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - new_m)
            if select is not None:
                # a block may hold none of a row's chosen keys: exp(-1e30 -
                # -1e30) is 1 there, and counts for nothing
                p = jnp.where(s > -1e29, p, 0.0)
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


# heads of one pass of the expanded attention under a selection
HEAD_GROUP = 32


def sparse_expanded_attention(cfg, c_q, cos, sin, latent, select, w_qb,
                              w_kvb, w_o, start, n_live):
    """The expanded form under a selection for ONE sequence, through the
    output projection: [T, hidden]. c_q [T, q_lora_rank]; `select` [T, L]
    (`selection_mask`); w_qb, w_kvb, w_o the layer's q_b_proj, kv_b_proj
    and o_proj as floating matrices. The heads are taken HEAD_GROUP at a
    time, each group's queries, keys and values made, used and dropped, and
    its part of the output projection added in float32: at 128 heads and
    16 k rows the whole heads' queries, keys and values are 3 GB that a
    served chip does not have beside its weights and pages."""
    nh, qk = cfg.num_attention_heads, cfg.qk_head_dim
    kv, v = cfg.qk_nope_head_dim + cfg.v_head_dim, cfg.v_head_dim
    g = HEAD_GROUP if nh % HEAD_GROUP == 0 else nh
    groups = (
        jnp.moveaxis(w_qb.reshape(-1, nh // g, g * qk), 1, 0),
        jnp.moveaxis(w_kvb.reshape(-1, nh // g, g * kv), 1, 0),
        w_o.reshape(nh // g, g * v, -1))

    def one(acc, ws):
        qb, kvb, ob = ws
        qn, qr = mla_queries(cfg, c_q, qb, cos, sin)
        o = expanded_attention(cfg, qn, qr, latent, kvb, start, n_live,
                               select)
        return acc + jnp.matmul(o, ob, preferred_element_type=jnp.float32
                                ), None

    out, _ = jax.lax.scan(
        one, jnp.zeros((c_q.shape[0], w_o.shape[1]), jnp.float32), groups)
    return out.astype(c_q.dtype)


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
            scale=cfg.routed_scaling_factor, n_group=cfg.n_group,
            topk_group=cfg.topk_group)
    with jax.named_scope("block/moe/experts"):
        y, pairs, touched = held_experts_ffn(
            h, idx, w, params[pre + "experts.gate_proj"],
            params[pre + "experts.up_proj"],
            params[pre + "experts.down_proj"], cfg.first_expert, valid,
            n_routed=cfg.n_routed_experts)
    with jax.named_scope("block/moe/shared"):
        y = y + dense_ffn(params, pre + "shared_experts.", h, mm
                          ).astype(jnp.float32)
    routed = (jnp.sum(valid.astype(jnp.int32)) if valid is not None
              else jnp.int32(h.shape[0]))
    return y.astype(h.dtype), jnp.stack(
        [routed, pairs.astype(jnp.int32), touched.astype(jnp.int32)])


def block_expanded(cfg, params, pre: str, x, cos, sin, dense: bool):
    """One block on x [b, s, hidden], every sequence from position 0: the
    expanded attention (under the indexer's selection where the
    configuration has one), then the layer's FFN."""
    b, s, _ = x.shape
    with jax.named_scope("block/mla"):
        h = rms_norm(x, params[pre + "input_layernorm.weight"],
                     cfg.rms_norm_eps)
        qn, qr, lat, c_q = mla_project(cfg, params, pre, h, cos, sin)
        w_kvb = params[pre + "self_attn.kv_b_proj.weight"]
        if cfg.index_topk is None:
            o = jax.vmap(lambda a, c, d: expanded_attention(
                cfg, a, c, d, w_kvb, 0, s))(qn, qr, lat)
        else:
            q_i, k_i, w_i = index_project(cfg, params, pre, h, c_q, cos, sin)
            o = jax.vmap(lambda a, c, d, qi, ki, wi: expanded_attention(
                cfg, a, c, d, w_kvb, 0, s,
                selection_mask(cfg, qi, wi, ki, 0, s)))(
                    qn, qr, lat, q_i, k_i, w_i)
        x = x + o @ params[pre + "self_attn.o_proj.weight"]
    h = rms_norm(x, params[pre + "post_attention_layernorm.weight"],
                 cfg.rms_norm_eps).reshape(b * s, -1)
    if dense:
        with jax.named_scope("block/mlp"):
            f = dense_ffn(params, pre + "mlp.", h)
    else:
        f, _ = moe_ffn(cfg, params, pre + "mlp.", h)
    return x + f.reshape(b, s, -1)


def hidden_expanded(cfg: DeepseekV3Config, params: dict, tokens):
    """The residual stream [b, s, hidden] after the last block, before the
    final norm: every block in the expanded form, no cache."""
    cos, sin = rope_tables(cfg, tokens.shape[1])
    x = jnp.take(params["embed_tokens.weight"], tokens, axis=0)
    for i in range(cfg.num_hidden_layers):
        x = block_expanded(cfg, params, f"layers.{i}.", x, cos, sin,
                           cfg.is_dense(i))
    return x


def forward_expanded(cfg: DeepseekV3Config, params: dict, tokens):
    """Logits [b, s, vocab] of tokens [b, s]."""
    x = rms_norm(hidden_expanded(cfg, params, tokens), params["norm.weight"],
                 cfg.rms_norm_eps)
    return x @ params["lm_head.weight"]


def mtp_expanded(cfg: DeepseekV3Config, params: dict, tokens):
    """The multi-token-prediction module on tokens [b, s]: logits [b, s -
    1, vocab], row t for token t + 2, from the main model's residual stream
    at t and the embedding of token t + 1 (the module's parameters lie
    under `layers.<num_hidden_layers>.`, embedding and head are the
    model's)."""
    pre = f"layers.{cfg.num_hidden_layers}."
    eps = cfg.rms_norm_eps
    h = hidden_expanded(cfg, params, tokens)[:, :-1]
    e = jnp.take(params["embed_tokens.weight"], tokens[:, 1:], axis=0)
    x = jnp.concatenate([rms_norm(h, params[pre + "hnorm.weight"], eps),
                         rms_norm(e, params[pre + "enorm.weight"], eps)],
                        axis=-1) @ params[pre + "eh_proj.weight"]
    cos, sin = rope_tables(cfg, x.shape[1])
    x = block_expanded(cfg, params, pre, x, cos, sin, dense=False)
    x = rms_norm(x, params[pre + "shared_head.norm.weight"], eps)
    return x @ params["lm_head.weight"]


# ----------------------------------------------------------------- Layer


# rows one pass of a draw's loop makes: a whole bfloat16 tile
DRAW_ROWS = 16


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    rows, cols = math.prod(shape[:-1]), shape[-1]
    block = math.gcd(rows, DRAW_ROWS)

    def some(k):
        return (jax.random.normal(k, (block, cols), jnp.float32) * std
                ).astype(dtype)

    return jax.lax.map(some, jax.random.split(key, rows // block)
                       ).reshape(shape)


class _Normal(I.Initializer):
    """N(0, std) from the framework's generator, written for the chip's
    compiler as much as for the chip: ONE program a shape (draw, scale and
    cast together, so no float32 copy of a leaf is ever whole) and the draw
    inside it in blocks of rows through one loop body. The compiler's time
    over a draw grows with the draw (3 s for the 470 M values of an expert
    stack as a matrix, 17 s as a stack, 0.5 s as this loop), and a served
    model's first weights are replaced by its checkpoint's, but they are
    drawn: set-up pays for them."""

    def __init__(self, std):
        self.std = std

    def __call__(self, shape, dtype="float32"):
        return _draw(default_generator.next_key(), tuple(shape), self.std,
                     dtype_mod.to_jax_dtype(dtype))


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
            [hidden, n_experts], default_initializer=_Normal(0.02))
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
        if cfg.index_topk is not None:
            self.indexer = _Indexer(cfg, w_in)


class _LayerNorm(Layer):
    def __init__(self, width, dtype):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(
            [width], default_initializer=I.Constant(1.0))
        self.bias = self.create_parameter(
            [width], default_initializer=I.Constant(0.0))


class _Indexer(Layer):
    def __init__(self, cfg, w_in):
        super().__init__(dtype=cfg.dtype)
        nh, d, dt = cfg.index_n_heads, cfg.index_head_dim, cfg.dtype
        self.wq_b = _Weight((cfg.q_lora_rank, nh * d), w_in, dt)
        self.wk = _Weight((cfg.hidden_size, d), w_in, dt)
        self.k_norm = _LayerNorm(d, dt)
        self.weights_proj = _Weight((cfg.hidden_size, nh), w_in, dt)


class _Block(Layer):
    def __init__(self, cfg, layer: int):
        super().__init__(dtype=cfg.dtype)
        w_in = _Normal(0.02)
        w_out = _Normal(0.02 / math.sqrt(2 * cfg.num_hidden_layers))
        one = I.Constant(1.0)
        self.input_layernorm = _Weight((cfg.hidden_size,), one, cfg.dtype)
        self.self_attn = _Attention(cfg, w_in, w_out)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,), one,
                                                cfg.dtype)
        self.mlp = (_MLP(cfg.hidden_size, cfg.intermediate_size, w_in, w_out,
                         cfg.dtype) if cfg.is_dense(layer)
                    else _MoE(cfg, w_in, w_out))


class _SharedHead(Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.norm = _Weight((cfg.hidden_size,), I.Constant(1.0), cfg.dtype)


class _MTPModule(_Block):
    """A block (an expert layer) behind the projection that joins the main
    model's residual stream to the next token's embedding."""

    def __init__(self, cfg):
        super().__init__(cfg, cfg.num_hidden_layers)
        one = I.Constant(1.0)
        self.enorm = _Weight((cfg.hidden_size,), one, cfg.dtype)
        self.hnorm = _Weight((cfg.hidden_size,), one, cfg.dtype)
        self.eh_proj = _Weight((2 * cfg.hidden_size, cfg.hidden_size),
                               _Normal(0.02), cfg.dtype)
        self.shared_head = _SharedHead(cfg)


class DeepseekV3ForCausalLM(Layer):
    """One rank's share of the decoder. Every parameter is drawn in
    `cfg.dtype` directly (`_Normal`: a block of rows in float32 is the
    largest temporary), so a bfloat16 model of 7 GB never has a float32
    copy beside it."""

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        with _prof.always_span("model.build", model="DeepseekV3ForCausalLM",
                               layers=cfg.num_hidden_layers):
            w = _Normal(0.02)
            self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size), w,
                                        cfg.dtype)
            self.layers = LayerList(
                [_Block(cfg, i) for i in range(cfg.num_hidden_layers)]
                + [_MTPModule(cfg)] * cfg.num_nextn_predict_layers)
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

    def mtp_forward(self, input_ids):
        """The multi-token-prediction module's logits [b, s - 1, vocab]
        (`mtp_expanded`; inference only)."""
        params = {k: p._value for k, p in self.named_parameters()}
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        return Tensor._wrap(mtp_expanded(self.cfg, params, ids))
