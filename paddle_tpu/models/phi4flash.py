"""Phi-4-mini-flash-reasoning (published class `Phi4FlashForCausalLM`,
`model_type: phi4flash`; the architecture is SambaY, arXiv:2507.06607): a
self-decoder of Mamba-1 layers alternating with differential attention over
a sliding window, ended by ONE full-attention layer, and a cross-decoder
that owns no cache: its gated memory units read the last Mamba layer's scan
output of the same step, its cross-attention layers read the full layer's
keys and values.

Served, not trained: the `Layer` holds the weights and its eager `forward`
is the plain form (the scan token by token, dense masks), with no autograd
tape. `serving/runners/phi4flash.py` serves it from the functions
below: a state slot per sequence for the Mamba layers, a ring of pages for
the window layers, whole-context pages for the one full layer, nothing for
the cross-decoder.

Layer kinds by index l of L (`mb_per_layer` 2; split = L / 2 + 2, which is
18 of 32): l < split: l even -> "mamba", l odd -> "window", but l = split
- 1 -> "full"; l >= split: l even -> "gmu", l odd -> "cross". The memory
is layer split - 2's (the last Mamba layer's).

The equations (x [T, hidden]; linears [in, out]):
  block   h = x + Mixer(LN(x)); y = h + MLP(LN(h)); LN a LayerNorm with gain
          and bias at `layer_norm_eps`, float32 statistics. MLP(u) =
          W_down(SiLU(g) * v), (g, v) = u W_gate_up, no bias. A final
          LayerNorm, logits through the transposed embedding. No positional
          encoding anywhere: the scans carry position.
  mamba   (x, z) = u W_in; x = SiLU(conv_causal(x) + b_conv), depthwise over
          `d_conv` taps; (r, B, C) = x W_x (dt_rank + d_state + d_state);
          dt = softplus(r W_dt + b_dt); A = -exp(A_log); the selective scan
          (ops/selective_scan.py) gives s_t = sum_n h_t C_t; y_t = s_t + D
          x_t; Mixer = (y * SiLU(z)) W_out. The memory a later layer reads
          is m_t = y_t, before the gate. What a sequence keeps of a layer
          is the state [d_state, d_inner] in float32 and the last `d_conv -
          1` rows of x before the convolution.
  gmu     Mixer = (m_t * SiLU(u_t W_in_g)) W_out_g; no cache.
  attention, differential (window, full, cross): the query heads and the
          key/value heads of `head_dim` are taken in adjacent pairs. For
          query pair p on key/value pair p' = p // (pairs of queries per
          pair of keys): A1 = softmax(q_{p,0} k_{p',0}^T / sqrt(head_dim)),
          A2 = softmax(q_{p,1} k_{p',1}^T / sqrt(head_dim)) under the
          layer's mask, v = [v_{p',0}, v_{p',1}] (2 head_dim wide), o_p =
          (1 - lambda_init) RMSNorm_{2 head_dim}(A1 v - lambda A2 v), lambda
          = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, lambda_init = 0.8
          - 0.6 exp(-0.3 l); then W_o with bias. Window mask: key j is seen
          by query i where i - (W - 1) <= j <= i. A cross layer projects
          queries only and uses the full layer's keys and values of
          positions <= i.

`pair_queries` is how one ordinary attention over PAIR heads gives both
maps: a query head padded with zeros to 2 head_dim (its own half filled)
dotted with a key pair [k_{p',0} | k_{p',1}] is its own head's score, and
the values are the pair as it lies. So keys and values are kept as the
projection leaves them, `[pairs, 2 head_dim]` a token, and one walk over
them serves both softmaxes.

Precision, as served: weights, pages and convolution rows in the model's
dtype (bfloat16); the scan state, dt, exp(dt A), the softmax, lambda and
both norms' statistics float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import profiler as _prof
from paddle_tpu.core import dtype as dtype_mod
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.deepseek_v3 import _Weight, plain_mm, rms_norm
from paddle_tpu.models.olmo_hybrid import _OnHost
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer import Layer, LayerList
from paddle_tpu.ops.selective_scan import selective_scan_recurrence

SUBLN_EPS = 1e-5          # under the root of the differential RMSNorm


@dataclass
class Phi4FlashConfig:
    """The published keys, then what the published config does not give
    (bench/configs/phi-4-mini-flash.json lists each under `assumed` with
    its source): the Mamba-1 sizes, `max_seq_len` (the serving context),
    the parameters' `dtype`, and `init`: "normal" draws the weights,
    "deferred" makes the Layer a vessel for weights that arrive through
    `set_state_dict` and leave it for the first runner built from it."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None
    max_seq_len: int = 262144
    dtype: str = "float32"
    init: str = "normal"

    def __post_init__(self):
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = math.ceil(self.hidden_size / 16)
        L = self.num_hidden_layers
        if self.mb_per_layer != 2 or L % 2 or L < 6:
            raise ValueError(
                "the layer map is written for mb_per_layer 2 and an even "
                f"depth of at least 6 (mb_per_layer {self.mb_per_layer}, "
                f"{L} layers)")
        if not self.tie_word_embeddings:
            raise ValueError("an untied head is not built")
        nq, nkv = self.num_attention_heads, self.num_key_value_heads
        if self.hidden_size % nq or nq % 2 or nkv % 2 or nq % nkv:
            raise ValueError(
                f"heads are taken in pairs: {nq} query and {nkv} key/value "
                f"heads over a hidden size of {self.hidden_size}")
        if self.init not in ("normal", "deferred"):
            raise ValueError(f"init={self.init!r}; expected 'normal' or "
                             "'deferred'")

    @property
    def split(self) -> int:
        """The first layer of the cross-decoder."""
        return self.num_hidden_layers // 2 + 2

    @property
    def memory_layer(self) -> int:
        """The Mamba layer whose scan output the gated memory units read."""
        return self.split - 2

    def kind(self, layer: int) -> str:
        if layer >= self.split:
            return "cross" if layer % 2 else "gmu"
        if layer == self.split - 1:
            return "full"
        return "window" if layer % 2 else "mamba"

    def layers_of(self, kind: str) -> list:
        return [i for i in range(self.num_hidden_layers)
                if self.kind(i) == kind]

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def q_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def kv_pairs(self) -> int:
        return self.num_key_value_heads // 2

    def lambda_init(self, layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * layer)

    def state_bytes_per_sequence(self, itemsize: int) -> int:
        """What one sequence keeps of the Mamba layers: the float32 state
        and the convolution's rows in the served dtype."""
        return len(self.layers_of("mamba")) * self.d_inner * (
            self.mamba_d_state * 4 + (self.mamba_d_conv - 1) * itemsize)


# ------------------------------------------- functions (Layer and runner)


def layer_norm(x, w, b, eps: float):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def block_norm(cfg, params, name: str, x):
    return layer_norm(x, params[name + ".weight"], params[name + ".bias"],
                      cfg.layer_norm_eps)


def mlp(params, pre: str, h, mm=plain_mm):
    g, v = jnp.split(mm(params, pre + "gate_up_proj.weight", h), 2, -1)
    return mm(params, pre + "down_proj.weight", jax.nn.silu(g) * v)


def mamba_inputs(params, pre: str, u, mm=plain_mm):
    """(x [..., d_inner] before the convolution, z the gate's input), in
    u's dtype: x's last rows are what a sequence keeps."""
    return jnp.split(mm(params, pre + "in_proj.weight", u), 2, -1)


def conv_silu(params, pre: str, rows):
    """rows [..., taps - 1 + T, d_inner] (the rows before the span, then
    the span's) -> SiLU of the causal depthwise convolution plus its bias
    [..., T, d_inner], float32."""
    w = params[pre + "conv.weight"].astype(jnp.float32)       # [c, taps]
    taps = w.shape[1]
    T = rows.shape[-2] - (taps - 1)
    r = rows.astype(jnp.float32)
    y = sum(r[..., j:j + T, :] * w[:, j] for j in range(taps))
    return jax.nn.silu(y + params[pre + "conv.bias"].astype(jnp.float32))


def ssm_inputs(cfg, params, pre: str, xc, dtype, mm=plain_mm):
    """From the convolution's output xc [..., d_inner] (float32; the
    projections read it in `dtype`, the activations' own): (dt [...,
    d_inner], B, C [..., d_state]) float32, and A [d_state, d_inner]."""
    n, r = cfg.mamba_d_state, cfg.mamba_dt_rank
    proj = mm(params, pre + "x_proj.weight", xc.astype(dtype))
    rk, B, C = jnp.split(proj, [r, r + n], -1)
    B, C = B.astype(jnp.float32), C.astype(jnp.float32)
    dt = jax.nn.softplus(
        mm(params, pre + "dt_proj.weight", rk).astype(jnp.float32)
        + params[pre + "dt_proj.bias"].astype(jnp.float32))
    A = -jnp.exp(params[pre + "A_log"].astype(jnp.float32)).T
    return dt, B, C, A


def mamba_memory(params, pre: str, scanned, xc):
    """y = s + D x, float32: what the gate multiplies, and the memory."""
    return scanned + params[pre + "D"].astype(jnp.float32) * xc


def mamba_output(params, pre: str, y, z, mm=plain_mm):
    gated = y * jax.nn.silu(z.astype(jnp.float32))
    return mm(params, pre + "out_proj.weight", gated.astype(z.dtype))


def gmu(params, pre: str, u, memory, mm=plain_mm):
    """memory [..., d_inner] float32 (the memory layer's y at the same
    positions)."""
    gate = mm(params, pre + "in_proj.weight", u)
    return mm(params, pre + "out_proj.weight",
              (memory * jax.nn.silu(gate.astype(jnp.float32))
               ).astype(u.dtype))


def attention_qkv(cfg, params, pre: str, x, mm=plain_mm):
    """q [..., heads, head_dim]; k, v [..., pairs, 2 head_dim]: the keys
    and values of a pair of heads side by side, as the projection leaves
    them and as a page keeps them."""
    qkv = mm(params, pre + "qkv_proj.weight", x) \
        + params[pre + "qkv_proj.bias"].astype(x.dtype)
    # whole columns first, the heads after: nothing is laid out again
    q, k, v = jnp.split(qkv, [cfg.q_width, cfg.q_width + cfg.kv_width], -1)
    lead = x.shape[:-1]
    return (q.reshape(*lead, cfg.num_attention_heads, cfg.head_dim),
            k.reshape(*lead, cfg.kv_pairs, 2 * cfg.head_dim),
            v.reshape(*lead, cfg.kv_pairs, 2 * cfg.head_dim))


def cross_q(cfg, params, pre: str, x, mm=plain_mm):
    q = mm(params, pre + "q_proj.weight", x) \
        + params[pre + "q_proj.bias"].astype(x.dtype)
    return q.reshape(*x.shape[:-1], cfg.num_attention_heads, cfg.head_dim)


def pair_queries(q):
    """q [..., heads, d] -> [..., heads, 2 d]: head 2p in the first half of
    its row, head 2p + 1 in the second, zeros in the other half, so that
    against a key pair [k_0 | k_1] each scores its own key head."""
    zero = jnp.zeros_like(q)
    even = jnp.concatenate([q, zero], -1)
    odd = jnp.concatenate([zero, q], -1)
    first = (jnp.arange(q.shape[-2]) % 2 == 0)[:, None]
    return jnp.where(first, even, odd)


def differential_output(cfg, params, pre: str, layer: int, o, dtype,
                        mm=plain_mm):
    """o [..., heads, 2 head_dim]: head h's softmax applied to its pair's
    values -> the mixer's output [..., hidden] in `dtype`, the
    activations' own."""
    f32 = lambda n: params[pre + n].astype(jnp.float32)
    lam0 = cfg.lambda_init(layer)
    lam = (jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1")))
           - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + lam0)
    of = o.astype(jnp.float32)
    lead = o.shape[:-2]
    of = of.reshape(*lead, cfg.num_attention_heads // 2, 2, 2 * cfg.head_dim)
    d = of[..., 0, :] - lam * of[..., 1, :]
    d = (1.0 - lam0) * rms_norm(d, params[pre + "subln.weight"], SUBLN_EPS)
    y = d.reshape(*lead, cfg.q_width).astype(dtype)
    return mm(params, pre + "o_proj.weight", y) \
        + params[pre + "o_proj.bias"].astype(dtype)


def dense_pair_attention(cfg, q, k, v, mask):
    """q [T, heads, d]; k, v [S, pairs, 2 d]; mask [T, S] bool -> [T,
    heads, 2 d] float32: the plain form, every score made."""
    n_rep = cfg.num_attention_heads // cfg.kv_pairs
    qp = pair_queries(q).astype(jnp.float32)
    qp = qp.reshape(q.shape[0], cfg.kv_pairs, n_rep, -1)
    s = jnp.einsum("tgrd,sgd->grts", qp, k.astype(jnp.float32)
                   ) * cfg.head_dim ** -0.5
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("grts,sgd->tgrd", p, v.astype(jnp.float32))
    return o.reshape(q.shape[0], cfg.num_attention_heads, -1)


def forward_plain(cfg: Phi4FlashConfig, params: dict, tokens):
    """Logits [b, s, vocab] of whole sequences from position 0: the scan
    token by token, dense masks, every layer on every row."""
    def one(ids):
        T = ids.shape[0]
        x = jnp.take(params["embed_tokens.weight"], ids, axis=0)
        pos = jnp.arange(T)
        causal = pos[None, :] <= pos[:, None]
        window = causal & (pos[None, :] > pos[:, None] - cfg.sliding_window)
        memory = shared = None
        for i in range(cfg.num_hidden_layers):
            pre, kind = f"layers.{i}.", cfg.kind(i)
            u = block_norm(cfg, params, pre + "input_layernorm", x)
            if kind == "mamba":
                a = pre + "mamba."
                xin, z = mamba_inputs(params, a, u)
                rows = jnp.concatenate([jnp.zeros(
                    (cfg.mamba_d_conv - 1, xin.shape[1]), xin.dtype), xin], 0)
                xc = conv_silu(params, a, rows)
                dt, B, C, A = ssm_inputs(cfg, params, a, xc, x.dtype)
                s, _ = selective_scan_recurrence(
                    xc, dt, A, B, C, jnp.zeros(A.shape, jnp.float32))
                y = mamba_memory(params, a, s, xc)
                if i == cfg.memory_layer:
                    memory = y
                m = mamba_output(params, a, y, z)
            elif kind == "gmu":
                m = gmu(params, pre + "gmu.", u, memory)
            else:
                a = pre + "attn."
                if kind == "cross":
                    q, (k, v) = cross_q(cfg, params, a, u), shared
                else:
                    q, k, v = attention_qkv(cfg, params, a, u)
                    if kind == "full":
                        shared = (k, v)
                o = dense_pair_attention(
                    cfg, q, k, v, window if kind == "window" else causal)
                m = differential_output(cfg, params, a, i, o, x.dtype)
            x = x + m
            x = x + mlp(params, pre + "mlp.", block_norm(
                cfg, params, pre + "post_attention_layernorm", x))
        x = block_norm(cfg, params, "final_layernorm", x)
        return x @ params["embed_tokens.weight"].T

    return jnp.stack([one(ids) for ids in tokens])


# ----------------------------------------------------------------- Layer


class _Affine(Layer):
    """A `weight` and a `bias`, drawn in the model's dtype."""

    def __init__(self, shape, w_init, b_init, dtype, bias_width=None):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(list(shape),
                                            default_initializer=w_init)
        self.bias = self.create_parameter([bias_width or shape[-1]],
                                          default_initializer=b_init)


class _MambaInit(I.Initializer):
    """As the public Mamba code draws them: `A_log` the log of 1..d_state
    in every channel; `dt_bias` the inverse softplus of a step log-uniform
    in 0.001..0.1; `dt_w` uniform within dt_rank^-1/2; `conv` uniform
    within taps^-1/2 (a depthwise Conv1d's default)."""

    def __init__(self, what: str):
        self.what = what

    def __call__(self, shape, dtype="float32"):
        if self.what == "A_log":
            w = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=jnp.float32)), shape)
        else:
            u = I.Uniform(0.0, 1.0)(shape, "float32")
            if self.what == "dt_bias":
                dt = jnp.exp(math.log(0.001) + u * math.log(100.0))
                w = dt + jnp.log(-jnp.expm1(-dt))
            else:
                bound = (shape[0] if self.what == "dt_w" else shape[1]) ** -0.5
                w = (2.0 * u - 1.0) * bound
        return w.astype(dtype_mod.to_jax_dtype(dtype))


class _Inits:
    def __init__(self, cfg):
        if cfg.init == "deferred":
            host = _OnHost()
            self.w_in = self.w_out = self.one = self.zero = host
            self.lam = host
            self.mamba = lambda what: host
        else:
            self.w_in = I.Normal(0.0, 0.02)
            self.w_out = I.Normal(
                0.0, 0.02 / math.sqrt(2 * cfg.num_hidden_layers))
            self.one, self.zero = I.Constant(1.0), I.Constant(0.0)
            self.lam = I.Normal(0.0, 0.1)
            self.mamba = _MambaInit


class _Mamba(Layer):
    def __init__(self, cfg, init):
        super().__init__(dtype=cfg.dtype)
        h, c, dt = cfg.hidden_size, cfg.d_inner, cfg.dtype
        n, r = cfg.mamba_d_state, cfg.mamba_dt_rank
        self.in_proj = _Weight((h, 2 * c), init.w_in, dt)
        # a depthwise filter [channels, taps] and a bias per channel
        self.conv = _Affine((c, cfg.mamba_d_conv), init.mamba("conv"),
                            init.zero, dt, bias_width=c)
        self.x_proj = _Weight((c, r + 2 * n), init.w_in, dt)
        self.dt_proj = _Affine((r, c), init.mamba("dt_w"),
                               init.mamba("dt_bias"), dt)
        self.A_log = self.create_parameter(
            [c, n], default_initializer=init.mamba("A_log"))
        self.D = self.create_parameter([c], default_initializer=init.one)
        self.out_proj = _Weight((c, h), init.w_out, dt)


class _GMU(Layer):
    def __init__(self, cfg, init):
        super().__init__(dtype=cfg.dtype)
        self.in_proj = _Weight((cfg.hidden_size, cfg.d_inner), init.w_in,
                               cfg.dtype)
        self.out_proj = _Weight((cfg.d_inner, cfg.hidden_size), init.w_out,
                                cfg.dtype)


class _Attention(Layer):
    def __init__(self, cfg, init, cross: bool):
        super().__init__(dtype=cfg.dtype)
        h, dt = cfg.hidden_size, cfg.dtype
        if cross:
            self.q_proj = _Affine((h, cfg.q_width), init.w_in, init.zero, dt)
        else:
            self.qkv_proj = _Affine((h, cfg.q_width + 2 * cfg.kv_width),
                                    init.w_in, init.zero, dt)
        self.o_proj = _Affine((cfg.q_width, h), init.w_out, init.zero, dt)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                [cfg.head_dim], default_initializer=init.lam))
        self.subln = _Weight((2 * cfg.head_dim,), init.one, dt)


class _MLP(Layer):
    def __init__(self, cfg, init):
        super().__init__(dtype=cfg.dtype)
        h, f = cfg.hidden_size, cfg.intermediate_size
        self.gate_up_proj = _Weight((h, 2 * f), init.w_in, cfg.dtype)
        self.down_proj = _Weight((f, h), init.w_out, cfg.dtype)


class _Block(Layer):
    def __init__(self, cfg, layer: int):
        super().__init__(dtype=cfg.dtype)
        init, kind, h = _Inits(cfg), cfg.kind(layer), cfg.hidden_size
        self.input_layernorm = _Affine((h,), init.one, init.zero, cfg.dtype)
        if kind == "mamba":
            self.mamba = _Mamba(cfg, init)
        elif kind == "gmu":
            self.gmu = _GMU(cfg, init)
        else:
            self.attn = _Attention(cfg, init, cross=kind == "cross")
        self.post_attention_layernorm = _Affine((h,), init.one, init.zero,
                                                cfg.dtype)
        self.mlp = _MLP(cfg, init)


class Phi4FlashForCausalLM(Layer):
    """The decoder. Every parameter is made in `cfg.dtype` directly, so a
    bfloat16 model never has a float32 copy beside it."""

    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        with _prof.always_span("model.build", model="Phi4FlashForCausalLM",
                               layers=cfg.num_hidden_layers):
            init = _Inits(cfg)
            self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size),
                                        init.w_in, cfg.dtype)
            self.layers = LayerList([_Block(cfg, i) for i in
                                     range(cfg.num_hidden_layers)])
            self.final_layernorm = _Affine((cfg.hidden_size,), init.one,
                                           init.zero, cfg.dtype)

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
