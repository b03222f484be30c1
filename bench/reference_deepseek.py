"""The plain reference of the DeepSeek-V3 block (`model_type: kimi_k2`, the
published class `DeepseekV3ForCausalLM`): latent attention (MLA) and a routed
+ shared expert layer, in straightforward jax.numpy.

Float32 with every matmul at "highest" precision, the EXPANDED attention form
only (per-head keys and values rebuilt from the latent), no cache, no kernel,
every expert a dense masked sum over the experts held. It imports nothing of
paddle_tpu and takes nothing the program has made: the weights are drawn here
from the seed, and serve.py hands the same arrays to the program through its
public `set_state_dict`. The weights' names are the program's own
(`program_names` is the identity), one leaf per layer and matrix, the held
experts of a layer stacked on a leading axis: no single float32 temporary of
`init_weights` is larger than one layer's experts of one kind (0.7 GB at the
published widths with 12 held).

The layer equations (x [T, hidden]; RMSNorm eps `rms_norm_eps`, float32
statistics; linears without bias, weights [in, out]):
  block l: h = x + MLA(RMSNorm(x)); y = h + FFN_l(RMSNorm(h)); final RMSNorm;
  logits = y W_head (untied).
  MLA: c_q = RMSNorm(x W_qa); q = c_q W_qb -> heads x [q_nope | q_rope];
  [c_kv | k_r] = x W_kva; c_kv = RMSNorm(c_kv); k_r is ONE vector a token,
  shared by all heads. RoPE on q_rope and k_r: pairs taken interleaved
  (x[2i], x[2i+1]) as the published model does, then rotate-half.
  [k_nope_h | v_h] = c_kv W_kvb per head. score_h(t, s) = (q_nope_h(t) .
  k_nope_h(s) + q_rope_h(t) . k_r(s)) * scale, causal, softmax in float32,
  out = concat_h(sum_s p_h v_h(s)) W_o. YaRN: `yarn_inv_freq` blends
  theta^(-2i/d) and theta^(-2i/d)/factor by DeepSeek's linear ramp between the
  correction dims of beta_fast / beta_slow; cos/sin are multiplied by
  m(mscale)/m(mscale_all_dim); scale = (nope + rope)^(-1/2) * m(mscale_all_dim)^2
  with m(x) = 0.1 x ln(factor) + 1.
  FFN, the first `first_k_dense_replace` layers: SwiGLU of `intermediate_size`.
  FFN, expert layers: s = sigmoid(x W_r) in float32; selection: the top_k
  largest of s + b (n_group = topk_group = 1: no group limit); weights w_e =
  s_e / (sum of the selected s + 1e-20) * routed_scaling_factor (the bias does
  not enter the weights); y = sum_{e selected} w_e E_e(x) + E_shared(x), each
  expert a SwiGLU of `moe_intermediate_size`.

Departures from the published model, all of them the configuration's:
  * the share: this rank holds experts [first_expert, first_expert +
    experts_held); the routed sum runs over selected AND held, the
    normalisation over all selected; what the absent experts would add is
    left out and the partial result goes on to the next layer;
  * the slice: the vocabulary is its first `vocab_size` rows (embedding and
    head alike);
  * `e_score_correction_bias` is drawn from the seed, N(0, 0.005): non-zero
    so that selection and weighting differ, and of the size of the gaps
    between a token's best scores, as a bias learned to balance load is
    (N(0, 0.1) outweighs the scores: every token then picks the same few
    experts, up to 12 times an even share, and how many of them this rank
    holds changes with the seed);
  * norms' gains are 1 + N(0, 0.02), as reference_gpt draws them.

`stored` names the type in which a served model keeps its activations
("bfloat16": every value a block hands on is rounded to it, the arithmetic
stays float32): the reference AT the precision the configuration states.
`round_weights(..., "bfloat16")` keeps the rounded weights in bfloat16
STORAGE (7 GB at the cell's size, where float32 would not fit beside the
activations); every use widens one matrix.

Training cells call `leaf_norms` and `train_readings`: this configuration is
served, not trained, and both raise.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 2048          # rows of one block of the MLPs
QUERY_BLOCK = 128         # query rows whose scores exist at once


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any non-negative seed (the driver's pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


# ------------------------------------------------------------------ shapes


def _shapes(cfg: dict) -> dict:
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, ql, kl = cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    g = cfg["experts_held"]
    out = {"embed_tokens.weight": (cfg["vocab_size"], h),
           "norm.weight": (h,), "lm_head.weight": (h, cfg["vocab_size"])}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        out.update({
            p + "input_layernorm.weight": (h,),
            p + "self_attn.q_a_proj.weight": (h, ql),
            p + "self_attn.q_a_layernorm.weight": (ql,),
            p + "self_attn.q_b_proj.weight": (ql, nh * (nope + rope)),
            p + "self_attn.kv_a_proj_with_mqa.weight": (h, kl + rope),
            p + "self_attn.kv_a_layernorm.weight": (kl,),
            p + "self_attn.kv_b_proj.weight": (kl, nh * (nope + vd)),
            p + "self_attn.o_proj.weight": (nh * vd, h),
            p + "post_attention_layernorm.weight": (h,)})
        if i < cfg["first_k_dense_replace"]:
            out.update({p + "mlp.gate_proj.weight": (h, f),
                        p + "mlp.up_proj.weight": (h, f),
                        p + "mlp.down_proj.weight": (f, h)})
        else:
            fs = fe * cfg["n_shared_experts"]
            out.update({
                p + "mlp.gate.weight": (h, cfg["n_routed_experts"]),
                p + "mlp.gate.e_score_correction_bias":
                    (cfg["n_routed_experts"],),
                p + "mlp.experts.gate_proj": (g, h, fe),
                p + "mlp.experts.up_proj": (g, h, fe),
                p + "mlp.experts.down_proj": (g, fe, h),
                p + "mlp.shared_experts.gate_proj.weight": (h, fs),
                p + "mlp.shared_experts.up_proj.weight": (h, fs),
                p + "mlp.shared_experts.down_proj.weight": (fs, h)})
    return out


def init_weights(cfg: dict, key) -> dict:
    """Every weight from `key`, float32. Matrices and embeddings N(0, 0.02),
    the projections back into the residual (o_proj, down_proj) scaled by
    1/sqrt(2L); norms' gains 1 + N(0, 0.02); the router's selection bias
    N(0, 0.005). Pure: jit it (serve.py does, in one call)."""
    out = {}
    L = cfg["num_hidden_layers"]
    for n, (name, shape) in enumerate(sorted(_shapes(cfg).items())):
        std = 0.02
        if name.endswith(("o_proj.weight", "down_proj.weight", "down_proj")):
            std = 0.02 / math.sqrt(2 * L)
        elif name.endswith("e_score_correction_bias"):
            std = 0.005
        w = std * jax.random.normal(jax.random.fold_in(key, n), shape,
                                    jnp.float32)
        if name.endswith(("layernorm.weight", "norm.weight")):
            w = 1.0 + w
        out[name] = w
    return out


def program_names(weights: dict) -> dict:
    """The weights under the names the program gives its parameters: the
    reference draws them under those names already."""
    return dict(weights)


def round_weights(weights: dict, precision: str) -> dict:
    """The weights as a configuration of that precision holds them:
    "bfloat16" rounds AND keeps the bfloat16 storage (see the head)."""
    if precision == "float32":
        return weights
    return {k: jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7
                                        ).astype(jnp.bfloat16)
            for k, v in weights.items()}


def leaf_norms(tree: dict) -> dict:
    raise NotImplementedError(
        "reference_deepseek: only training cells read leaf norms; this "
        "configuration is served, not trained")


def train_readings(*args, **kwargs):
    raise NotImplementedError(
        "reference_deepseek: this configuration is served, not trained "
        "(16 bytes a parameter fit no cut of it on one chip)")


# -------------------------------------------------------------------- YaRN


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek's yarn_get_mscale: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(beta_fast, beta_slow, dim, base, original_max):
    """DeepSeek's yarn_find_correction_range: the rotary dims between which
    the ramp runs (floor / ceil of the dim that makes `beta` rotations over
    the original context, clamped to [0, dim - 1])."""
    def dim_of(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    return (max(math.floor(dim_of(beta_fast)), 0),
            min(math.ceil(dim_of(beta_slow)), dim - 1))


def yarn_inv_freq(cfg: dict):
    """inv_freq [rope/2], float32: theta^(-2i/d) where the ramp is 0 (fast
    dims, kept), theta^(-2i/d)/factor where it is 1 (slow dims,
    interpolated), blended linearly between."""
    d, rs = cfg["qk_rope_head_dim"], cfg["rope_scaling"]
    i = jnp.arange(0, d, 2, dtype=jnp.float32)
    extra = 1.0 / cfg["rope_theta"] ** (i / d)
    low, high = yarn_correction_range(
        rs["beta_fast"], rs["beta_slow"], d, cfg["rope_theta"],
        rs["original_max_position_embeddings"])
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / rs["factor"] * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope_tables(cfg: dict, n: int):
    """cos, sin [n, rope], float32, for positions 0..n-1: the half-width
    angles twice over (rotate-half), times m(mscale)/m(mscale_all_dim)."""
    rs = cfg["rope_scaling"]
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)[None]
    ang = jnp.concatenate([ang, ang], -1)
    m = (yarn_mscale(rs["factor"], rs["mscale"])
         / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def rope(x, cos, sin):
    """x [..., rope] with cos/sin broadcastable to it: pairs taken
    interleaved (x[2i], x[2i+1]) into two halves, then rotate-half."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# ------------------------------------------------------------------ layers


def _keep_for(stored: str):
    """Rounding of every value a block hands on, in float32: an explicit
    reduce_precision, which XLA may not drop as it may a convert pair."""
    if stored == "float32":
        return lambda x: x
    return lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                              mantissa_bits=7)


def _mm(spec, x, w):
    return jnp.einsum(spec, x, w.astype(jnp.float32), precision="highest")


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _by_rows(fn, x, block: int):
    """fn over blocks of x's rows, one block's temporaries live at a time."""
    n = x.shape[0]
    if n <= block or n % block:
        return fn(x)
    out = jax.lax.map(fn, x.reshape(n // block, block, *x.shape[1:]))
    return out.reshape(n, *out.shape[2:])


def _swiglu(keep, x, wg, wu, wd):
    def rows(xb):
        a = keep(jax.nn.silu(keep(_mm("th,hf->tf", xb, wg)))
                 * keep(_mm("th,hf->tf", xb, wu)))
        return _mm("tf,fh->th", a, wd)

    return _by_rows(rows, x, ROW_BLOCK)


def mla(cfg, keep, x, p, cos, sin):
    """Latent attention on x [T, hidden], expanded form, causal."""
    T = x.shape[0]
    nh, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rd, vd, kl = cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    c_q = keep(_rms(keep(_mm("th,hr->tr", x, p["self_attn.q_a_proj.weight"])),
                    p["self_attn.q_a_layernorm.weight"], eps))
    q = keep(_mm("tr,rk->tk", c_q, p["self_attn.q_b_proj.weight"])
             ).reshape(T, nh, nope + rd)
    kv = keep(_mm("th,hk->tk", x, p["self_attn.kv_a_proj_with_mqa.weight"]))
    c_kv = keep(_rms(kv[:, :kl], p["self_attn.kv_a_layernorm.weight"], eps))
    k_r = keep(rope(kv[:, kl:], cos, sin))                       # [T, rd]
    q_r = keep(rope(q[..., nope:], cos[:, None], sin[:, None]))  # [T, nh, rd]
    kvb = keep(_mm("tc,ck->tk", c_kv, p["self_attn.kv_b_proj.weight"])
               ).reshape(T, nh, nope + vd)
    k_n, v = kvb[..., :nope], kvb[..., nope:]
    scale = softmax_scale(cfg)
    k_pos = jnp.arange(T)

    def rows(args):
        qn, qr, q_pos = args                   # [B, nh, nope], [B, nh, rd]
        s = (jnp.einsum("qhd,khd->hqk", qn, k_n, precision="highest")
             + jnp.einsum("qhd,kd->hqk", qr, k_r, precision="highest")
             ) * scale
        s = jnp.where(k_pos[None, None, :] <= q_pos[None, :, None], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision="highest")

    blk = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    split = lambda a: a.reshape(T // blk, blk, *a.shape[1:])
    o = jax.lax.map(rows, (split(q[..., :nope]), split(q_r), split(k_pos)))
    o = keep(o.reshape(T, nh * vd))
    return _mm("tk,kh->th", o, p["self_attn.o_proj.weight"])


def route(cfg, x, w_r, bias):
    """(indices [T, top_k], weights [T, top_k]) of the experts each token
    selects, over ALL `n_routed_experts`: float32 sigmoid scores, selection
    by score + bias, weights from the scores alone."""
    s = jax.nn.sigmoid(_mm("th,he->te", x, w_r))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32)[None],
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def moe(cfg, keep, x, p):
    """Routed part over the experts held (a dense masked sum: every held
    expert on every token, times the token's weight for it or 0) plus the
    shared expert whole."""
    idx, w = route(cfg, x, p["mlp.gate.weight"],
                   p["mlp.gate.e_score_correction_bias"])
    first = cfg["first_expert"]

    def one(y, ex):
        e, wg, wu, wd = ex
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)   # [T]
        return y + w_e[:, None] * _swiglu(keep, x, wg, wu, wd), None

    g = cfg["experts_held"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(g), p["mlp.experts.gate_proj"], p["mlp.experts.up_proj"],
        p["mlp.experts.down_proj"]))
    return y + _swiglu(keep, x, p["mlp.shared_experts.gate_proj.weight"],
                       p["mlp.shared_experts.up_proj.weight"],
                       p["mlp.shared_experts.down_proj.weight"])


def _layer(weights: dict, i: int) -> dict:
    pre = f"layers.{i}."
    return {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}


def hidden(cfg: dict, weights: dict, tokens, stored: str = "float32"):
    """Final-RMSNorm output [T, hidden] for ONE sequence tokens [T]."""
    keep = _keep_for(stored)
    eps = cfg["rms_norm_eps"]
    cos, sin = rope_tables(cfg, tokens.shape[0])
    x = keep(weights["embed_tokens.weight"][tokens].astype(jnp.float32))
    for i in range(cfg["num_hidden_layers"]):
        p = _layer(weights, i)
        x = keep(x + mla(cfg, keep, keep(_rms(
            x, p["input_layernorm.weight"], eps)), p, cos, sin))
        y = keep(_rms(x, p["post_attention_layernorm.weight"], eps))
        if i < cfg["first_k_dense_replace"]:
            f = _swiglu(keep, y, p["mlp.gate_proj.weight"],
                        p["mlp.up_proj.weight"], p["mlp.down_proj.weight"])
        else:
            f = moe(cfg, keep, y, p)
        x = keep(x + f)
    return keep(_rms(x, weights["norm.weight"], eps))


def logits_at(cfg, weights, tokens, first: int, count: int,
              stored: str = "float32"):
    """Logits [count, vocab] of ONE sequence tokens [T] at positions
    first .. first+count-1 (the position that predicts token i+1 is i)."""
    x = hidden(cfg, weights, tokens, stored=stored)
    x = jax.lax.dynamic_slice_in_dim(x, first, count, 0)
    return _keep_for(stored)(_mm("th,hv->tv", x, weights["lm_head.weight"]))
