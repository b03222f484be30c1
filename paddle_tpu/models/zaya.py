"""The ZAYA1 block (published `model_type: zaya`): attention in a compressed
latent with convolutions (CCA), a top-1 router that is an MLP fed by the
layer before, dropless experts. Trained: `jit.TrainStep(model, zaya_loss_fn,
opt)` as the GPT models are.

The `Layer` holds ONE rank's part of an expert-parallel deployment
(`experts_held` of the router's `num_experts`, from `first_expert`;
attention, router and norms whole; `vocab_size` is the slice of the
vocabulary held here, embedding and tied head alike). Routing runs over all
the experts; the products over the held ones (`parallel.moe.
held_experts_ffn_train`, dropless); a token whose expert is absent gets
nothing from the expert sublayer. On one chip the layer runs without its
exchange.

The equations are those of `bench/reference_zaya.py`'s head (the plain
reference; each assumption marked there). Precision under autocast O1
(`amp.state`'s lists): projections, convolutions (`cca_conv`), attention and
the grouped products (`grouped_matmul`) take bfloat16 operands; norms (a
head's L2 normalisation among them), the whole router (float32 products at
"highest"), the residual stream and the loss are float32 in the code itself.
Without autocast everything is the parameters' float32.

`forward` returns (logits, balance): the balance term is zero in value and
its gradient on each layer's selection bias is that layer's load error, so
the one optimizer balances the experts (arXiv:2408.15664's rule with the
optimizer's normalisation in place of the sign). `zaya_loss_fn` adds it to
the float32 cross-entropy. The step counts what it routed into buffers
that are outputs of its program (`step_counts`; `profiler.step_counters()`
reads them for a `TrainStep`, touching the device only when called): sums
since the model was built, and the pairs of each of the last PAIRS_RING
steps, so that a reader can set a span's kernels against those steps' own
work. They are not persistent and never in a state dict.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu import profiler as _prof
from paddle_tpu.amp.state import current_cast_dtype
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import deepseek_v3
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer import Layer, LayerList
from paddle_tpu.ops.impl import scaled_dot_product_attention
from paddle_tpu.parallel.moe import held_experts_ffn_train, softmax_topk_route

RESIDUALS = ("stream_scale", "stream_bias", "out_scale", "out_bias")
# what a step counts, in the order of the `moe_counts` buffer
COUNTS = ("moe_train_tokens", "moe_train_pairs", "moe_train_rows_padded",
          "moe_train_load_max")
PAIRS_RING = 128    # steps whose own pairs the `moe_pairs_ring` buffer keeps
# the selection is argmax(score + SELECTION_BIAS_SCALE * bias): the optimizer
# moves the bias by about its learning rate a step, the selection by this many
# times that in score units (the balance rule's own rate; the reference's)
SELECTION_BIAS_SCALE = 128.0


@dataclass
class ZayaConfig:
    """The published keys (`num_experts` is the router's), plus the share
    (`experts_held`, `first_expert`; `vocab_size` the rows held)."""
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    experts_held: Optional[int] = None     # None = all of them
    first_expert: int = 0

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts
        if not (0 <= self.first_expert and self.first_expert
                + self.experts_held <= self.num_experts):
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert} + "
                f"{self.experts_held}) do not lie in the router's "
                f"{self.num_experts}")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.num_key_value_heads % 2:
            raise ValueError("query heads are shared by an even number of "
                             "key/value heads (half of the values shift)")


# ------------------------------------------------------------------ pieces
# functions of a flat params dict under the Layer's own names


def _low(x, op: str):
    """x in the dtype autocast gives the inputs of `op` (amp.state's lists);
    as it is without autocast."""
    dt = current_cast_dtype(op)
    if dt is None or not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    return x.astype(dt)


def _mm(x, w):
    return jnp.matmul(_low(x, "matmul"), _low(w, "matmul"))


def _mm32(x, w):
    """A float32 product that is one on the chip too (the default there
    rounds float32 operands to bfloat16)."""
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision="highest")


def rms_norm(x, w, eps: float):
    """Float32 inside; `w` None is the L2 normalisation of a head scaled by
    sqrt(d)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return y if w is None else y * w.astype(jnp.float32)


def _shift(x, j: int):
    """x [b, s, ...] moved j positions later, zeros before position 0."""
    if j == 0:
        return x
    pad = [(0, 0), (j, 0)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def rope(x, cfg: ZayaConfig):
    """Rotate-half RoPE on the first `partial_rotary_factor` of every
    head's channels; x [b, s, heads, d] float32, positions 0..s-1."""
    rot = int(cfg.head_dim * cfg.partial_rotary_factor)
    inv = 1.0 / cfg.rope_theta ** (
        jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    xr = x[..., :rot]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    return jnp.concatenate(
        [xr * cos + jnp.concatenate([-x2, x1], -1) * sin, x[..., rot:]], -1)


def cca_qkv(cfg: ZayaConfig, params: dict, pre: str, u):
    """q [b, s, n_q, d], k, v [b, s, n_kv, d] of the normed stream u: the
    two causal convolutions over [q~; k~], the mean of queries and keys,
    the norms, the temperature, RoPE, and the values with their shift."""
    b, s, _ = u.shape
    nq, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q0 = _mm(u, params[pre + "q_proj.weight"])
    k0 = _mm(u, params[pre + "k_proj.weight"])
    with jax.named_scope("conv"):
        c = _low(jnp.concatenate([q0, k0], -1), "cca_conv")
        a = _low(params[pre + "conv0.weight"], "cca_conv")
        c1 = sum(_shift(c, j) * a[j] for j in range(cfg.cca_time0))
        c1 = c1.reshape(b, s, nq + nkv, d)
        w = _low(params[pre + "conv1.weight"], "cca_conv")
        c2 = sum(jnp.einsum("bsid,ide->bsie", _shift(c1, j), w[j])
                 for j in range(cfg.cca_time1)).astype(jnp.float32)
    q0 = q0.astype(jnp.float32).reshape(b, s, nkv, nq // nkv, d)
    k0 = k0.astype(jnp.float32).reshape(b, s, nkv, 1, d)
    m = (q0 + k0) / 2
    q = c2[:, :, :nq] + m.reshape(b, s, nq, d)
    k = c2[:, :, nq:] + jnp.mean(m, axis=3)
    q = rope(rms_norm(q, None, cfg.rms_norm_eps), cfg)
    k = rope(rms_norm(k, None, cfg.rms_norm_eps)
             * params[pre + "k_temp"].astype(jnp.float32)[:, None], cfg)
    v = jnp.concatenate(
        [_mm(u, params[pre + "v_proj.weight"]),
         _mm(_shift(u, 1), params[pre + "v_shift_proj.weight"])], -1)
    return q, k, v.reshape(b, s, nkv, d)


def cca_attention(cfg: ZayaConfig, params: dict, pre: str, u):
    """The attention sublayer's output [b, s, hidden] for the normed
    stream u [b, s, hidden]."""
    b, s, _ = u.shape
    q, k, v = cca_qkv(cfg, params, pre, u)
    with jax.named_scope("attn"):
        sdpa = "scaled_dot_product_attention"
        # a key/value head stays one head: each group of query heads reads
        # it where it lies
        o = scaled_dot_product_attention(
            _low(q, sdpa), _low(k, sdpa), _low(v, sdpa), is_causal=True)
    return _mm(o.reshape(b, s, -1), params[pre + "o_proj.weight"])


def route(cfg: ZayaConfig, params: dict, pre: str, u, r_prev):
    """(idx [T, 1], weights [T, 1], scores [T, E], r [T, router width]) for
    the normed stream u [T, hidden] and the router stream of the layer
    before; float32 throughout."""
    r = (_mm32(u, params[pre + "down.weight"])
         + params[pre + "gamma"].astype(jnp.float32) * r_prev)
    z = rms_norm(r, params[pre + "norm.weight"], cfg.rms_norm_eps)
    for leaf in ("w1.weight", "w2.weight"):
        z = jax.nn.gelu(_mm32(z, params[pre + leaf]), approximate=True)
    idx, w, s = softmax_topk_route(
        _mm32(z, params[pre + "w3.weight"]),
        SELECTION_BIAS_SCALE * params[pre + "bias"],
        cfg.num_experts_per_tok)
    return idx, w, s, r


def balance_term(idx, bias, n_experts: int):
    """(term, load): sum_e stopgrad(load_e - 1/E) (b_e - stopgrad(b_e)),
    zero in value, its gradient on the bias the load error; load [E] the
    share of the pairs each of ALL the experts was chosen for."""
    load = jnp.mean(jax.nn.one_hot(idx.reshape(-1), n_experts,
                                   dtype=jnp.float32), axis=0)
    bias = bias.astype(jnp.float32)
    term = jnp.sum(jax.lax.stop_gradient(load - 1.0 / n_experts)
                   * (bias - jax.lax.stop_gradient(bias)))
    return term, load


def _merge(x, y, params, pre: str):
    g = lambda leaf: params[pre + leaf].astype(jnp.float32)
    return (x * g("stream_scale") + g("stream_bias")
            + y.astype(jnp.float32) * g("out_scale") + g("out_bias"))


def hidden(cfg: ZayaConfig, params: dict, tokens):
    """(the stream after the last block [b, s, hidden] float32, balance,
    counts int32 [len(COUNTS)], the largest |selection bias|) for tokens
    [b, s]."""
    b, s = tokens.shape
    T, eps = b * s, cfg.rms_norm_eps
    x = jnp.take(params["embed.weight"], tokens, axis=0).astype(jnp.float32)
    r = jnp.zeros((T, cfg.router_hidden_size), jnp.float32)
    balance = jnp.float32(0)
    counts = jnp.zeros((len(COUNTS),), jnp.int32)
    bias_max = jnp.float32(0)
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        with jax.named_scope("block"):
            with jax.named_scope("cca"):
                u = rms_norm(x, params[pre + "input_norm.weight"], eps)
                x = _merge(x, cca_attention(cfg, params, pre + "attn.", u),
                           params, pre + "attn_res.")
            with jax.named_scope("moe"):
                u = rms_norm(x, params[pre + "post_norm.weight"], eps
                             ).reshape(T, -1)
                bias = params[pre + "router.bias"]
                with jax.named_scope("route"):
                    idx, w, _, r = route(cfg, params, pre + "router.", u, r)
                    term, load = balance_term(idx, bias, cfg.num_experts)
                with jax.named_scope("experts"):
                    y, pairs, rows = held_experts_ffn_train(
                        _low(u, "grouped_matmul"), idx, w,
                        params[pre + "experts.gate_proj"],
                        params[pre + "experts.up_proj"],
                        params[pre + "experts.down_proj"], cfg.first_expert)
                x = _merge(x, y.reshape(b, s, -1), params, pre + "moe_res.")
        balance = balance + term
        counts = counts + jnp.stack([
            jnp.int32(T), pairs, rows,
            jnp.round(jnp.max(load) * idx.size).astype(jnp.int32)])
        bias_max = jnp.maximum(bias_max, jnp.max(jnp.abs(bias)))
    return x, balance, counts, bias_max.astype(jnp.float32)


def forward(cfg: ZayaConfig, params: dict, tokens):
    """`hidden` with logits [b, s, vocab] (final norm, the tied head) in
    the stream's place."""
    x, *rest = hidden(cfg, params, tokens)
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm.weight"], cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        logits = jnp.matmul(_low(x, "matmul"),
                            _low(params["embed.weight"], "matmul").T)
    return (logits, *rest)


# ------------------------------------------------------------------- Layer

_Weight = functools.partial(deepseek_v3._Weight, dtype="float32")


class _Attention(Layer):
    def __init__(self, cfg: ZayaConfig, w_in, w_out):
        super().__init__()
        h, d = cfg.hidden_size, cfg.head_dim
        nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        self.q_proj = _Weight((h, nq * d), w_in)
        self.k_proj = _Weight((h, nkv * d), w_in)
        self.v_proj = _Weight((h, nkv * d // 2), w_in)
        self.v_shift_proj = _Weight((h, nkv * d // 2), w_in)
        self.conv0 = _Weight((cfg.cca_time0, (nq + nkv) * d),
                             I.Normal(0.0, 1 / math.sqrt(cfg.cca_time0)))
        self.conv1 = _Weight((cfg.cca_time1, nq + nkv, d, d),
                             I.Normal(0.0, 1 / math.sqrt(cfg.cca_time1 * d)))
        # sharp heads, as trained ones are (the reference's K_TEMP)
        self.k_temp = self.create_parameter(
            [nkv], default_initializer=I.Constant(4.0))
        self.o_proj = _Weight((nq * d, h), w_out)


class _Residual(Layer):
    """x <- (x * stream_scale + stream_bias) + (y * out_scale + out_bias)."""

    def __init__(self, hidden):
        super().__init__()
        for leaf in RESIDUALS:
            setattr(self, leaf, self.create_parameter(
                [hidden], default_initializer=I.Constant(
                    1.0 if leaf.endswith("scale") else 0.0)))


class _Router(Layer):
    def __init__(self, cfg: ZayaConfig):
        super().__init__()
        h, r, E = cfg.hidden_size, cfg.router_hidden_size, cfg.num_experts
        wide = I.Normal(0.0, 1 / math.sqrt(r))
        self.down = _Weight((h, r), I.Normal(0.0, 0.02))
        self.gamma = self.create_parameter(
            [r], default_initializer=I.Normal(0.0, 0.02))
        self.norm = _Weight((r,), I.Constant(1.0))
        self.w1 = _Weight((r, r), wide)
        self.w2 = _Weight((r, r), wide)
        self.w3 = _Weight((r, E), wide)
        # the selection bias: a parameter, moved by the balance term alone
        self.bias = self.create_parameter(
            [E], default_initializer=I.Constant(0.0))


class _Experts(Layer):
    """The held experts' matrices, stacked on a leading axis."""

    def __init__(self, cfg: ZayaConfig, w_in, w_out):
        super().__init__()
        n, h, f = (cfg.experts_held, cfg.hidden_size,
                   cfg.moe_intermediate_size)
        self.gate_proj = self.create_parameter([n, h, f],
                                               default_initializer=w_in)
        self.up_proj = self.create_parameter([n, h, f],
                                             default_initializer=w_in)
        self.down_proj = self.create_parameter([n, f, h],
                                               default_initializer=w_out)


class _Block(Layer):
    def __init__(self, cfg: ZayaConfig):
        super().__init__()
        w_in = I.Normal(0.0, 0.02)
        w_out = I.Normal(0.0, 0.02 / math.sqrt(2 * cfg.num_hidden_layers))
        one = I.Constant(1.0)
        self.input_norm = _Weight((cfg.hidden_size,), one)
        self.attn = _Attention(cfg, w_in, w_out)
        self.attn_res = _Residual(cfg.hidden_size)
        self.post_norm = _Weight((cfg.hidden_size,), one)
        self.router = _Router(cfg)
        self.experts = _Experts(cfg, w_in, w_out)
        self.moe_res = _Residual(cfg.hidden_size)


class ZayaForCausalLM(Layer):
    """One rank's share of the decoder; float32 parameters."""

    def __init__(self, cfg: ZayaConfig):
        super().__init__()
        self.cfg = cfg
        with _prof.always_span("model.build", model="ZayaForCausalLM",
                               layers=cfg.num_hidden_layers):
            self.embed = _Weight((cfg.vocab_size, cfg.hidden_size),
                                 I.Normal(0.0, 0.02))
            self.layers = LayerList([_Block(cfg) for _ in
                                     range(cfg.num_hidden_layers)])
            self.final_norm = _Weight((cfg.hidden_size,), I.Constant(1.0))
            self.register_buffer(
                "moe_counts", Tensor._wrap(jnp.zeros((len(COUNTS),),
                                                     jnp.int32)),
                persistable=False)
            self.register_buffer(
                "moe_pairs_ring", Tensor._wrap(jnp.zeros((PAIRS_RING,),
                                                         jnp.int32)),
                persistable=False)
            self.register_buffer(
                "moe_bias_abs_max", Tensor._wrap(jnp.float32(0)),
                persistable=False)

    def forward(self, input_ids):
        """(logits [b, s, vocab], the balance term)."""
        params = {k: p._value for k, p in self.named_parameters()}
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        logits, balance, counts, bias_max = forward(self.cfg, params, ids)
        self.moe_counts._value = self.moe_counts._value + counts
        self.moe_pairs_ring._value = jnp.concatenate(
            [self.moe_pairs_ring._value[1:], counts[1:2]])
        self.moe_bias_abs_max._value = bias_max
        return Tensor._wrap(logits), Tensor._wrap(balance)

    def step_counts(self, buffers: dict) -> dict:
        """The counters by name, from the buffers of a step's program:
        sums over layer-steps since the step was built, the gauge
        `moe_bias_abs_max` of the last step, and `moe_train_pairs_by_step`,
        the pairs (all layers) of each of the last PAIRS_RING steps, the
        newest last, zeros before the first. `moe_train_load_mean` is the
        sum of each layer-step's mean load over all the experts."""
        out = dict(zip(COUNTS, buffers["moe_counts"]))
        out["moe_train_pairs_by_step"] = buffers["moe_pairs_ring"]
        out["moe_train_load_mean"] = (
            out["moe_train_tokens"] * self.cfg.num_experts_per_tok
            / self.cfg.num_experts)
        out["moe_bias_abs_max"] = buffers["moe_bias_abs_max"]
        return out


def zaya_loss_fn(out, labels):
    """Float32 next-token cross-entropy over the slice (labels already
    shifted) plus the zero-valued balance term."""
    logits, balance = out
    v = logits.shape[-1]
    return F.cross_entropy(logits.reshape([-1, v]),
                           labels.reshape([-1])) + balance
