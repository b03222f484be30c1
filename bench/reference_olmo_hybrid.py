"""The plain reference of the Olmo-Hybrid block (`model_type: olmo_hybrid`):
three Gated DeltaNet layers (a gated delta rule over a fixed state per head,
arXiv:2412.06464, with negative eigenvalues allowed, arXiv:2411.12537) and
one full softmax-attention layer in every period of four, SwiGLU MLPs, the
Olmo 2 / Olmo 3 placement of the norms, in straightforward jax.numpy.

Float32 with every matmul at "highest" precision; the recurrence is a plain
`lax.scan` over tokens, one token a step, no chunking, no cache, no kernel.
It imports nothing of paddle_tpu and takes nothing the program has made: the
weights are drawn here from the seed, and serve.py hands the same arrays to
the program through its public `set_state_dict`. The weights' names are the
program's own (`program_names` is the identity), one leaf per layer and
matrix.

The equations (x [T, hidden]; RMSNorm eps `rms_norm_eps`, float32
statistics; linears without bias, weights [in, out]; H heads):
  block l: h = x + RMSNorm(Mixer_l(x)); y = h + RMSNorm(MLP(h)): the norm
  sits on each branch's OUTPUT. MLP(h) = W_down(SiLU(W_gate h) * (W_up h)).
  A final RMSNorm, then logits = y W_head (untied).
  `layer_types[l] == "linear_attention"` (d_k = linear_key_head_dim, d_v =
  linear_value_head_dim):
    q~ = x W_q, k~ = x W_k (H d_k each), v~ = x W_v (H d_v); each through a
    causal depthwise convolution of `linear_conv_kernel_dim` taps, no bias,
    then SiLU: q'_t[c] = SiLU(sum_j w_q[c, j] q~_{t-(K-1)+j}[c]), zeros
    before the sequence. Per head q_t = q'_t / |q'_t| / sqrt(d_k), k_t =
    k'_t / |k'_t| (|x| = sqrt(sum x^2 + 1e-6)). beta_t = sigmoid(x_t W_b),
    times 2 where `linear_allow_neg_eigval`; g_t = -exp(A_log) *
    softplus(x_t W_a + dt_bias), a_t = exp(g_t). State S [d_k, d_v] per
    head, zero at the start:
        S_t = a_t S_{t-1} + beta_t k_t (v_t - (a_t S_{t-1})^T k_t)^T
        o_t = S_t^T q_t
    y_t = RMSNorm_{d_v}(o_t) * SiLU(x_t W_g) per head (one gain vector of
    d_v for all heads), Mixer(x)_t = concat_h(y_t) W_o.
  `full_attention`: q = RMSNorm(x W_q), k = RMSNorm(x W_k), each over the
    WHOLE projection before the split into heads of hidden / H; v = x W_v;
    causal softmax of q . k / sqrt(head_dim) in float32, then W_o. Rotary
    embedding only where `rope_parameters.rope_theta` is a number
    (rotate-half over the whole head, that base): the published config
    gives null, read as it stands: no rotation, the linear layers carry
    position.

What the configuration assumes, and so does this file: the norm placement
and the QK-norm (the family's), no rotary embedding, the state and the
decay's arithmetic in float32, the initialisation of `A_log` (log of U(1,
16)) and `dt_bias` (inverse softplus of a step log-uniform in 0.001..0.1) as
the public Gated DeltaNet code draws them; norms' gains are 1 + N(0, 0.02)
as reference_gpt draws them; all else N(0, 0.02), the projections back into
the residual scaled by 1/sqrt(2L).

`stored` names the type in which a served model keeps its activations
("bfloat16": every value a block hands on is rounded to it, the arithmetic
stays float32; the recurrent state is NOT a value handed on: it stays
float32, as the configuration states). `round_weights(..., "bfloat16")`
keeps the rounded weights in bfloat16 STORAGE (8.2 GB at the cell's size);
every use widens one matrix.

Training cells call `leaf_norms` and `train_readings`: this configuration is
served, not trained, and both raise.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 2048          # rows of one block of the MLPs
L2_EPS = 1e-6             # under the root of q's and k's norms


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any non-negative seed (the driver's pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


# ------------------------------------------------------------------ shapes


def is_linear(cfg: dict, layer: int) -> bool:
    return cfg["layer_types"][layer] == "linear_attention"


def _shapes(cfg: dict) -> dict:
    h, f, nh = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_attention_heads"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    assert hk == hv, "grouped key heads are not written down here"
    out = {"embed_tokens.weight": (cfg["vocab_size"], h),
           "norm.weight": (h,), "lm_head.weight": (h, cfg["vocab_size"])}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        out.update({
            p + "post_attention_layernorm.weight": (h,),
            p + "post_feedforward_layernorm.weight": (h,),
            p + "mlp.gate_proj.weight": (h, f),
            p + "mlp.up_proj.weight": (h, f),
            p + "mlp.down_proj.weight": (f, h)})
        if is_linear(cfg, i):
            a = p + "linear_attn."
            out.update({
                a + "q_proj.weight": (h, hk * dk),
                a + "k_proj.weight": (h, hk * dk),
                a + "v_proj.weight": (h, hv * dv),
                a + "q_conv.weight": (hk * dk, taps),
                a + "k_conv.weight": (hk * dk, taps),
                a + "v_conv.weight": (hv * dv, taps),
                a + "a_proj.weight": (h, hv), a + "b_proj.weight": (h, hv),
                a + "A_log": (hv,), a + "dt_bias": (hv,),
                a + "g_proj.weight": (h, hv * dv),
                a + "o_norm.weight": (dv,),
                a + "o_proj.weight": (hv * dv, h)})
        else:
            a = p + "self_attn."
            out.update({
                a + "q_proj.weight": (h, h), a + "k_proj.weight": (h, h),
                a + "v_proj.weight": (h, h), a + "o_proj.weight": (h, h),
                a + "q_norm.weight": (h,), a + "k_norm.weight": (h,)})
    assert h % nh == 0
    return out


def init_weights(cfg: dict, key) -> dict:
    """Every weight from `key`, float32 (the head of this file says how
    each kind is drawn). Pure: jit it (serve.py does, in one call)."""
    out = {}
    L = cfg["num_hidden_layers"]
    for n, (name, shape) in enumerate(sorted(_shapes(cfg).items())):
        k = jax.random.fold_in(key, n)
        if name.endswith("A_log"):
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name.endswith("dt_bias"):
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(0.001), math.log(0.1)))
            w = dt + jnp.log(-jnp.expm1(-dt))          # softplus(w) == dt
        else:
            std = 0.02
            if name.endswith(("o_proj.weight", "down_proj.weight")):
                std = 0.02 / math.sqrt(2 * L)
            w = std * jax.random.normal(k, shape, jnp.float32)
            if name.endswith(("norm.weight",)):
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
        "reference_olmo_hybrid: only training cells read leaf norms; this "
        "configuration is served, not trained")


def train_readings(*args, **kwargs):
    raise NotImplementedError(
        "reference_olmo_hybrid: this configuration is served, not trained "
        "(16 bytes a parameter leave no room on one chip for one period "
        "and an eighth of the vocabulary)")


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


def causal_conv_silu(x, w):
    """x [T, C], w [C, K]: y_t[c] = SiLU(sum_j w[c, j] x_{t-(K-1)+j}[c]),
    zeros before the sequence."""
    T, K = x.shape[0], w.shape[1]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], 0)
    y = sum(xp[j:j + T] * w[:, j].astype(jnp.float32)[None] for j in range(K))
    return jax.nn.silu(y)


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def delta_rule_scan(q, k, v, g, beta):
    """The gated delta rule, token by token. q, k [T, H, d_k]; v [T, H,
    d_v]; g (log-decay) and beta [T, H]; state zero at the start. Returns
    o [T, H, d_v]."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[:, None, None]
        u = v_t - jnp.einsum("hkv,hk->hv", S, k_t, precision="highest")
        S = S + (b_t[:, None] * k_t)[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision="highest")

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    return o


def linear_attention(cfg, keep, x, p):
    """A Gated DeltaNet mixer on x [T, hidden]."""
    T = x.shape[0]
    H = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    eps = cfg["rms_norm_eps"]

    def branch(name, d):
        y = keep(_mm("th,hc->tc", x, p[name + "_proj.weight"]))
        return keep(causal_conv_silu(y, p[name + "_conv.weight"])
                    ).reshape(T, H, d)

    q = l2_normalize(branch("q", dk)) * dk ** -0.5
    k = l2_normalize(branch("k", dk))
    v = branch("v", dv)
    beta = jax.nn.sigmoid(_mm("th,hn->tn", x, p["b_proj.weight"]))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        _mm("th,hn->tn", x, p["a_proj.weight"])
        + p["dt_bias"].astype(jnp.float32))
    o = keep(delta_rule_scan(q, k, v, g, beta))
    gate = keep(_mm("th,hc->tc", x, p["g_proj.weight"])).reshape(T, H, dv)
    y = keep(_rms(o, p["o_norm.weight"], eps) * jax.nn.silu(gate))
    return _mm("tc,ch->th", y.reshape(T, H * dv), p["o_proj.weight"])


def rope_tables(head_dim: int, theta: float, n: int):
    """cos, sin [n, head_dim], float32: the half-width angles twice over."""
    inv = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                          / head_dim)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin):
    """Rotate-half on x [T, heads, head_dim]."""
    x1, x2 = jnp.split(x, 2, -1)
    return (x * cos[:, None] + jnp.concatenate([-x2, x1], -1) * sin[:, None])


def full_attention(cfg, keep, x, p):
    """Causal softmax attention on x [T, hidden], QK-norm over the whole
    projection, rotary embedding only where `rope_theta` is a number."""
    T, nh = x.shape[0], cfg["num_attention_heads"]
    hd, eps = cfg["hidden_size"] // nh, cfg["rms_norm_eps"]
    q = keep(_rms(keep(_mm("th,hc->tc", x, p["q_proj.weight"])),
                  p["q_norm.weight"], eps)).reshape(T, nh, hd)
    k = keep(_rms(keep(_mm("th,hc->tc", x, p["k_proj.weight"])),
                  p["k_norm.weight"], eps)).reshape(T, nh, hd)
    v = keep(_mm("th,hc->tc", x, p["v_proj.weight"])).reshape(T, nh, hd)
    theta = (cfg.get("rope_parameters") or {}).get("rope_theta")
    if theta is not None:
        cos, sin = rope_tables(hd, float(theta), T)
        q, k = keep(rope(q, cos, sin)), keep(rope(k, cos, sin))
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") * hd ** -0.5
    pos = jnp.arange(T)
    s = jnp.where(pos[None, None, :] <= pos[None, :, None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision="highest")
    return _mm("tc,ch->th", keep(o.reshape(T, nh * hd)), p["o_proj.weight"])


def _layer(weights: dict, i: int, group: str) -> dict:
    pre = f"layers.{i}."
    out = {}
    for k, v in weights.items():
        if k.startswith(pre):
            k = k[len(pre):]
            out[k[len(group):] if k.startswith(group) else k] = v
    return out


def hidden(cfg: dict, weights: dict, tokens, stored: str = "float32"):
    """Final-RMSNorm output [T, hidden] for ONE sequence tokens [T]."""
    keep = _keep_for(stored)
    eps = cfg["rms_norm_eps"]
    x = keep(weights["embed_tokens.weight"][tokens].astype(jnp.float32))
    for i in range(cfg["num_hidden_layers"]):
        if is_linear(cfg, i):
            m = linear_attention(cfg, keep, x, _layer(weights, i,
                                                      "linear_attn."))
        else:
            m = full_attention(cfg, keep, x, _layer(weights, i, "self_attn."))
        p = _layer(weights, i, "mlp.")
        x = keep(x + keep(_rms(keep(m), p["post_attention_layernorm.weight"],
                               eps)))
        f = _swiglu(keep, x, p["gate_proj.weight"], p["up_proj.weight"],
                    p["down_proj.weight"])
        x = keep(x + keep(_rms(keep(f),
                               p["post_feedforward_layernorm.weight"], eps)))
    return keep(_rms(x, weights["norm.weight"], eps))


def logits_at(cfg, weights, tokens, first: int, count: int,
              stored: str = "float32"):
    """Logits [count, vocab] of ONE sequence tokens [T] at positions
    first .. first+count-1 (the position that predicts token i+1 is i)."""
    x = hidden(cfg, weights, tokens, stored=stored)
    x = jax.lax.dynamic_slice_in_dim(x, first, count, 0)
    return _keep_for(stored)(_mm("th,hv->tv", x, weights["lm_head.weight"]))
