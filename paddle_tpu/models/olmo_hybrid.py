"""The Olmo-Hybrid block (published class `OlmoHybridForCausalLM`,
`model_type: olmo_hybrid`): in every period of four layers three Gated
DeltaNet mixers (a gated delta rule over a fixed state per head,
ops/gated_delta.py) and one full softmax-attention layer, SwiGLU MLPs, the
Olmo 2 / Olmo 3 placement of the norms.

Served, not trained: the `Layer` holds the weights and its eager `forward`
is the plain form (the recurrence token by token, dense causal attention),
with no autograd tape. `serving/runners/olmo_hybrid.py` serves it
from the functions below: pages for the full layers, a state slot per
sequence for the linear ones.

The equations (x [T, hidden]; RMSNorm in float32; linears [in, out], no bias):
  block   h = x + RMSNorm(Mixer(x)); y = h + RMSNorm(MLP(h)): the norm sits
          on each branch's output. A final RMSNorm, an untied head.
  linear  q~, k~, v~ = x W_q, x W_k, x W_v; each through a causal depthwise
          convolution of `linear_conv_kernel_dim` taps, then SiLU. Per head
          q = q' / |q'| / sqrt(d_k), k = k' / |k'|; beta = sigmoid(x W_b)
          (times 2 with `linear_allow_neg_eigval`); g = -exp(A_log) *
          softplus(x W_a + dt_bias). The gated delta rule gives o; y =
          RMSNorm_{d_v}(o) * SiLU(x W_g); Mixer = concat_h(y) W_o. What a
          sequence keeps of a layer is the state [H, d_k, d_v] in float32
          and the last `taps - 1` rows of (q~ | k~ | v~).
  full    q = RMSNorm(x W_q), k = RMSNorm(x W_k) over the whole projection,
          heads of hidden / H, causal softmax at 1 / sqrt(head_dim), W_o.
          Rotary embedding (rotate-half, base `rope_theta`) only where
          `rope_theta` is a number: the published config gives null.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import profiler as _prof
from paddle_tpu.core import dtype as dtype_mod
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.deepseek_v3 import (  # noqa: F401  (runner uses them)
    _MLP, _Weight, plain_mm, rms_norm,
)
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer import Layer, LayerList
from paddle_tpu.ops.gated_delta import gated_delta_recurrence

L2_EPS = 1e-6             # under the root of q's and k's norms


@dataclass
class OlmoHybridConfig:
    """The published keys (`layer_types` may be the published pattern
    whole: a model cut in depth runs its first `num_hidden_layers` kinds;
    `rope_parameters` as published, `{"rope_theta": null}`: no rotation),
    plus `max_seq_len` (the serving context), the parameters' `dtype` and
    `init`: "normal" draws the weights; "deferred"
    makes the Layer a vessel for weights that arrive through
    `set_state_dict` (placeholders on the host until then) and that leave
    it for the first runner built from it, so that a 7 B model is on the
    device once, not twice while it loads and again while it is served."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    rope_parameters: Optional[dict] = None
    max_seq_len: int = 65536
    dtype: str = "float32"
    init: str = "normal"

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                "full_attention" if i % 4 == 3 else "linear_attention"
                for i in range(self.num_hidden_layers))
        kinds = set(self.layer_types) - {"linear_attention",
                                         "full_attention"}
        if kinds or len(self.layer_types) < self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers for "
                f"{self.num_hidden_layers} (unknown kinds: {sorted(kinds)})")
        self.layer_types = tuple(self.layer_types)[:self.num_hidden_layers]
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("grouped key heads in the linear layers are "
                             "not built")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide into the heads")
        if self.init not in ("normal", "deferred"):
            raise ValueError(f"init={self.init!r}; expected 'normal' or "
                             "'deferred'")

    def is_linear(self, layer: int) -> bool:
        return self.layer_types[layer] == "linear_attention"

    @property
    def rope_theta(self) -> Optional[float]:
        """The rotary base of the full layers, None for no rotation: the
        ONE key, `rope_parameters.rope_theta`, that says so."""
        return (self.rope_parameters or {}).get("rope_theta")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: (q~ | k~ | v~)."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    def state_bytes_per_sequence(self, itemsize: int) -> int:
        """What one sequence keeps of the linear layers: the float32 state
        and the convolution's rows in the served dtype."""
        n = sum(map(self.is_linear, range(self.num_hidden_layers)))
        state = (self.linear_num_value_heads * self.linear_key_head_dim
                 * self.linear_value_head_dim * 4)
        conv = (self.linear_conv_kernel_dim - 1) * self.conv_dim * itemsize
        return n * (state + conv)


# ------------------------------------------- functions (Layer and runner)


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def conv_weights(params, pre: str):
    """[conv_dim, taps] float32: the three depthwise filters as one."""
    return jnp.concatenate([params[pre + n + "_conv.weight"] for n in "qkv"],
                           0).astype(jnp.float32)


def conv_inputs(params, pre: str, x, mm=plain_mm):
    """(q~ | k~ | v~) [..., conv_dim] in x's dtype: what the convolution
    reads, and what a sequence keeps the last rows of."""
    return jnp.concatenate([mm(params, pre + n + "_proj.weight", x)
                            for n in "qkv"], -1)


def conv_silu(rows, w):
    """rows [..., taps - 1 + T, C] (the rows before the span, then the
    span's) -> SiLU of the causal depthwise convolution [..., T, C],
    float32."""
    taps = w.shape[1]
    T = rows.shape[-2] - (taps - 1)
    r = rows.astype(jnp.float32)
    return jax.nn.silu(sum(r[..., j:j + T, :] * w[:, j] for j in range(taps)))


def delta_inputs(cfg, params, pre: str, x, conved, mm=plain_mm):
    """From x [..., hidden] and the convolution's output [..., conv_dim]:
    (q, k [..., H, d_k], v [..., H, d_v], g, beta [..., H]), float32."""
    H, dk = cfg.linear_num_value_heads, cfg.linear_key_head_dim
    dv = cfg.linear_value_head_dim
    lead = conved.shape[:-1]
    q, k, v = jnp.split(conved, [H * dk, 2 * H * dk], -1)
    q = l2_normalize(q.reshape(*lead, H, dk)) * dk ** -0.5
    k = l2_normalize(k.reshape(*lead, H, dk))
    v = v.reshape(*lead, H, dv)
    f32 = lambda n: params[pre + n].astype(jnp.float32)
    beta = jax.nn.sigmoid(mm(params, pre + "b_proj.weight", x)
                          .astype(jnp.float32))
    if cfg.linear_allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(f32("A_log")) * jax.nn.softplus(
        mm(params, pre + "a_proj.weight", x).astype(jnp.float32)
        + f32("dt_bias"))
    return q, k, v, g, beta


def gated_output(cfg, params, pre: str, x, o, mm=plain_mm):
    """o [..., H, d_v] float32 -> the mixer's output [..., hidden]."""
    H, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    gate = mm(params, pre + "g_proj.weight", x).astype(jnp.float32)
    y = rms_norm(o, params[pre + "o_norm.weight"], cfg.rms_norm_eps) \
        * jax.nn.silu(gate.reshape(*o.shape[:-2], H, dv))
    return mm(params, pre + "o_proj.weight",
              y.reshape(*o.shape[:-2], H * dv).astype(x.dtype))


def rope_tables(cfg, n: int):
    """cos, sin [n, head_dim] float32, or None where the configuration
    gives no rotary base."""
    if cfg.rope_theta is None:
        return None
    d = cfg.head_dim
    inv = 1.0 / float(cfg.rope_theta) ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin):
    """Rotate-half on x [..., T, heads, head_dim]; cos, sin [..., T,
    head_dim]."""
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, -1)
    out = (xf * cos[..., None, :]
           + jnp.concatenate([-x2, x1], -1) * sin[..., None, :])
    return out.astype(x.dtype)


def attention_qkv(cfg, params, pre: str, x, cos_sin=None, mm=plain_mm):
    """q, k, v [..., heads, head_dim] of a full-attention layer: QK-norm
    over the whole projection, rotation where the configuration has one
    (`cos_sin`: the tables' rows at x's positions)."""
    nh, hd, eps = cfg.num_attention_heads, cfg.head_dim, cfg.rms_norm_eps
    heads = lambda y: y.reshape(*y.shape[:-1], nh, hd)
    q = heads(rms_norm(mm(params, pre + "q_proj.weight", x),
                       params[pre + "q_norm.weight"], eps))
    k = heads(rms_norm(mm(params, pre + "k_proj.weight", x),
                       params[pre + "k_norm.weight"], eps))
    v = heads(mm(params, pre + "v_proj.weight", x))
    if cos_sin is not None:
        q, k = rope(q, *cos_sin), rope(k, *cos_sin)
    return q, k, v


def swiglu(params, pre: str, h, mm=plain_mm):
    a = jax.nn.silu(mm(params, pre + "gate_proj.weight", h)) \
        * mm(params, pre + "up_proj.weight", h)
    return mm(params, pre + "down_proj.weight", a)


def forward_plain(cfg: OlmoHybridConfig, params: dict, tokens):
    """Logits [b, s, vocab] of whole sequences from position 0: the
    recurrence token by token, dense causal attention."""
    eps = cfg.rms_norm_eps

    def one(ids):
        T = ids.shape[0]
        x = jnp.take(params["embed_tokens.weight"], ids, axis=0)
        tables = rope_tables(cfg, T)
        causal = jnp.tril(jnp.ones((T, T), bool))
        for i in range(cfg.num_hidden_layers):
            pre = f"layers.{i}."
            if cfg.is_linear(i):
                a = pre + "linear_attn."
                rows = conv_inputs(params, a, x)
                rows = jnp.concatenate([jnp.zeros(
                    (cfg.linear_conv_kernel_dim - 1, rows.shape[1]),
                    rows.dtype), rows], 0)
                q, k, v, g, beta = delta_inputs(
                    cfg, params, a, x, conv_silu(rows, conv_weights(params,
                                                                    a)))
                o, _ = gated_delta_recurrence(
                    q, k, v, g, beta, jnp.zeros(
                        (q.shape[1], q.shape[2], v.shape[2]), jnp.float32))
                m = gated_output(cfg, params, a, x, o)
            else:
                a = pre + "self_attn."
                q, k, v = attention_qkv(cfg, params, a, x, tables)
                s = jnp.einsum("qhd,khd->hqk", q, k,
                               preferred_element_type=jnp.float32
                               ) * cfg.head_dim ** -0.5
                p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
                o = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v)
                m = plain_mm(params, a + "o_proj.weight", o.reshape(T, -1))
            x = x + rms_norm(m, params[pre + "post_attention_layernorm"
                                       ".weight"], eps)
            f = swiglu(params, pre + "mlp.", x)
            x = x + rms_norm(f, params[pre + "post_feedforward_layernorm"
                                       ".weight"], eps)
        x = rms_norm(x, params["norm.weight"], eps)
        return x @ params["lm_head.weight"]

    return jnp.stack([one(ids) for ids in tokens])


# ----------------------------------------------------------------- Layer


class _OnHost:
    """`init="deferred"`: a placeholder on the host (zero pages the system
    hands out untouched), for a weight that `set_state_dict` brings."""

    def __call__(self, shape, dtype="float32"):
        return np.zeros(tuple(shape), dtype_mod.to_jax_dtype(dtype))


class _DecayInit(I.Initializer):
    """`A_log`: the log of a value uniform in (1, 16); `dt_bias`: the
    inverse softplus of a step log-uniform in 0.001..0.1 (the public Gated
    DeltaNet initialisation: every head its own decay)."""

    def __init__(self, what: str):
        self.what = what

    def __call__(self, shape, dtype="float32"):
        u = I.Uniform(0.0, 1.0)(shape, "float32")
        if self.what == "A_log":
            w = jnp.log(1.0 + 15.0 * u)
        else:
            dt = jnp.exp(math.log(0.001) + u * math.log(100.0))
            w = dt + jnp.log(-jnp.expm1(-dt))
        return w.astype(dtype_mod.to_jax_dtype(dtype))


class _LinearAttention(Layer):
    def __init__(self, cfg, w_in, w_out, one, decay):
        super().__init__(dtype=cfg.dtype)
        h, dt = cfg.hidden_size, cfg.dtype
        H, dk = cfg.linear_num_value_heads, cfg.linear_key_head_dim
        dv, taps = cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim
        for name, width in (("q", H * dk), ("k", H * dk), ("v", H * dv)):
            setattr(self, name + "_proj", _Weight((h, width), w_in, dt))
            setattr(self, name + "_conv", _Weight((width, taps), w_in, dt))
        self.a_proj = _Weight((h, H), w_in, dt)
        self.b_proj = _Weight((h, H), w_in, dt)
        self.A_log = self.create_parameter(
            [H], default_initializer=decay("A_log"))
        self.dt_bias = self.create_parameter(
            [H], default_initializer=decay("dt_bias"))
        self.g_proj = _Weight((h, H * dv), w_in, dt)
        self.o_norm = _Weight((dv,), one, dt)
        self.o_proj = _Weight((H * dv, h), w_out, dt)


class _FullAttention(Layer):
    def __init__(self, cfg, w_in, w_out, one):
        super().__init__(dtype=cfg.dtype)
        h, dt = cfg.hidden_size, cfg.dtype
        for name in ("q", "k", "v"):
            setattr(self, name + "_proj", _Weight((h, h), w_in, dt))
        self.o_proj = _Weight((h, h), w_out, dt)
        self.q_norm = _Weight((h,), one, dt)
        self.k_norm = _Weight((h,), one, dt)


class _Block(Layer):
    def __init__(self, cfg, layer: int):
        super().__init__(dtype=cfg.dtype)
        if cfg.init == "deferred":
            w_in = w_out = one = _OnHost()
            decay = lambda what: _OnHost()
        else:
            w_in = I.Normal(0.0, 0.02)
            w_out = I.Normal(0.0,
                             0.02 / math.sqrt(2 * cfg.num_hidden_layers))
            one, decay = I.Constant(1.0), _DecayInit
        if cfg.is_linear(layer):
            self.linear_attn = _LinearAttention(cfg, w_in, w_out, one, decay)
        else:
            self.self_attn = _FullAttention(cfg, w_in, w_out, one)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,), one,
                                                cfg.dtype)
        self.mlp = _MLP(cfg.hidden_size, cfg.intermediate_size, w_in, w_out,
                        cfg.dtype)
        self.post_feedforward_layernorm = _Weight((cfg.hidden_size,), one,
                                                  cfg.dtype)


class OlmoHybridForCausalLM(Layer):
    """The decoder. Every parameter is made in `cfg.dtype` directly, so a
    bfloat16 model never has a float32 copy beside it."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        with _prof.always_span("model.build", model="OlmoHybridForCausalLM",
                               layers=cfg.num_hidden_layers):
            deferred = cfg.init == "deferred"
            w = _OnHost() if deferred else I.Normal(0.0, 0.02)
            self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size), w,
                                        cfg.dtype)
            self.layers = LayerList([_Block(cfg, i) for i in
                                     range(cfg.num_hidden_layers)])
            self.norm = _Weight((cfg.hidden_size,),
                                _OnHost() if deferred else I.Constant(1.0),
                                cfg.dtype)
            self.lm_head = _Weight((cfg.hidden_size, cfg.vocab_size), w,
                                   cfg.dtype)

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
