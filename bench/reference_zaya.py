"""The plain reference of the ZAYA1 block (`model_type: zaya`): attention in
a compressed latent with convolutions (CCA), a top-1 router that is an MLP
fed by the layer before, dropless experts. Straightforward jax.numpy, float32
with every matmul at "highest" precision, no kernels; trained, so it carries
loss, gradients (jax.grad) and AdamW (reference_gpt's). It imports nothing of
paddle_tpu; the weights are drawn here from the seed and run.py hands the same
arrays to the program through `set_state_dict`.

One chip's share (`experts_held` of the router's `num_experts`, from
`first_expert`; `vocab_size` rows of the vocabulary): routing runs over ALL
the experts, the products DENSE over the held ones (every held expert on
every token, masked by the route); a token whose expert is absent gets
nothing from the expert sublayer. Nothing is dropped.

The equations, for token t of a sequence, x the stream, u = RMSNorm(x).
Sizes come from the published config; each † is an assumption the
configuration file lists under `assumed` (the papers, arXiv:2510.04476 and
arXiv:2511.17127, were not at hand):

  attention  q~ = u W_Q (n_q heads of d), k~ = u W_K (n_kv heads of d);
      c = [q~; k~] through two causal convolutions along the sequence, zeros
      before position 0: depthwise, c1_t = sum_j a_j * c_{t-j} (`cca_time0`
      taps a channel), then grouped by head, c2_t[i] = sum_j c1_{t-j}[i]
      B_{i,j} (`cca_time1` taps of d x d a head); split into q^, k^.
      † m = (q~ + rep(k~)) / 2 (each key head repeated over its n_q / n_kv
      query heads); q = q^ + m; k = k^ + mean of m over a key head's query
      heads. † q and k L2-normalised a head and scaled by sqrt(d) (an RMS
      norm without a gain); k times a learned temperature a key head; RoPE
      (rotate-half) on the first `partial_rotary_factor` d channels; softmax
      scale 1/sqrt(d). † Values with a shift: the first half of the value
      heads from u_t, the second half from u_{t-1} (u_{-1} = 0). Causal
      softmax attention of n_q heads on n_kv, then W_O.
  router     r = u' W_down (u' the expert sublayer's norm of the stream);
      † r += gamma * r of the layer before (zero at the first layer);
      † s = softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(r)))) over the experts
      (tanh GELU); e = argmax(s + k b), the bias b moving the selection and
      never the weight, k = SELECTION_BIAS_SCALE (the optimizer moves b by
      about its learning rate a step, so the selection moves k times that in
      score units: the rule's own rate); y = s[e] SwiGLU_e(u').
  residual   † x' = (a1 x + b1) + (a2 Attn + b2), x'' = (a3 x' + b3) + (a4
      MoE + b4): learned per-channel scale and offset on the stream and on
      the sublayer's output.
  balance    † the loss gains, a layer, sum_e stopgrad(load_e - 1/E) (b_e -
      stopgrad(b_e)): zero in value, its gradient on b the load error, so
      the one optimizer moves an overloaded expert's bias down. load_e is
      the share of the batch's tokens that chose e, over ALL E experts.

`init_weights` draws b and then runs the sign form of that rule to rest on a
seeded calibration batch, layer by layer, so that a run starts balanced as a
trained model is. Block leaves are stacked on a leading layer axis and run
under lax.scan; attention runs by blocks of query rows, the experts one at
a time, each block rematerialised in the backward pass.

`precision`: "float32" (the reference) or "bfloat16" (master weights,
optimizer state and compute), used ONLY by the control the limits were set
against. Functions offered: seed_key, init_weights, program_names,
leaf_norms, train_readings.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from reference_gpt import _mm_for, _store, adamw, seed_key  # noqa: F401

# a checkout from before the model has nothing to compare with: say so at
# once, before the minutes the reference's own steps take
if not os.path.exists(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "paddle_tpu", "models", "zaya.py")):
    raise SystemExit("this checkout has no paddle_tpu/models/zaya.py: the "
                     "zaya1-8b cells cannot run on it")

Q_BLOCK = 1024                 # query rows of one block of attention
K_TEMP = 4.0                   # the keys' temperature as it is seeded
SELECTION_BIAS_SCALE = 128.0   # the program's (models/zaya.py)
CALIBRATION_BATCH = (2, 8192)  # sequences, tokens each: init_weights' batch
CALIBRATION_STEPS = 400        # of the balance rule on it
RESIDUALS = ("stream_scale", "stream_bias", "out_scale", "out_bias")


def _sizes(cfg: dict):
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return nq, nkv, d, nq // nkv


def _shapes(cfg: dict) -> dict:
    h, f, L = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_hidden_layers"])
    nq, nkv, d, _ = _sizes(cfg)
    r, E, G = (cfg["router_hidden_size"], cfg["num_experts"],
               cfg["experts_held"])
    block = {
        "input_norm.weight": (h,), "post_norm.weight": (h,),
        "attn.q_proj.weight": (h, nq * d), "attn.k_proj.weight": (h, nkv * d),
        "attn.v_proj.weight": (h, nkv * d // 2),
        "attn.v_shift_proj.weight": (h, nkv * d // 2),
        "attn.conv0.weight": (cfg["cca_time0"], (nq + nkv) * d),
        "attn.conv1.weight": (cfg["cca_time1"], nq + nkv, d, d),
        "attn.k_temp": (nkv,), "attn.o_proj.weight": (nq * d, h),
        "router.down.weight": (h, r), "router.gamma": (r,),
        "router.norm.weight": (r,), "router.w1.weight": (r, r),
        "router.w2.weight": (r, r), "router.w3.weight": (r, E),
        "router.bias": (E,),
        "experts.gate_proj": (G, h, f), "experts.up_proj": (G, h, f),
        "experts.down_proj": (G, f, h),
    }
    for sub in ("attn_res", "moe_res"):
        block.update({f"{sub}.{leaf}": (h,) for leaf in RESIDUALS})
    out = {"embed.weight": (cfg["vocab_size"], h), "final_norm.weight": (h,)}
    out.update({"layers." + k: (L,) + v for k, v in block.items()})
    return out


def _draw(cfg: dict, key) -> dict:
    """Every leaf from `key`, float32. Matrices N(0, 0.02), the projections
    back into the stream (W_O, the experts' down) scaled by 1/sqrt(2L);
    gains and residual scales 1 + N(0, 0.02); gamma N(0, 0.02) and the
    residual offsets N(0, 0.002), a tenth of the embedding's spread (a
    vector that every token shares is what attention hands on whole while
    it averages the rest away); the keys' temperature K_TEMP + N(0, 0.02):
    SHARP heads, as trained ones are, where a temperature of 1 spreads every
    query over thousands of random keys and four layers of that leave one
    vector in every token's stream (and one expert for all of them); the
    router's three square layers N(0, 1/sqrt(fan-in)), so that scores
    spread as a trained router's do; the convolutions' taps N(0,
    1/sqrt(taps)) a channel and N(0, 1/sqrt(taps * d)) a head; the bias
    N(0, 0.005) in score units before its calibration."""
    out = {}
    d, L = cfg["head_dim"], cfg["num_hidden_layers"]
    for n, (name, shape) in enumerate(sorted(_shapes(cfg).items())):
        std, mean = 0.02, 0.0
        if name.endswith(("o_proj.weight", "experts.down_proj")):
            std = 0.02 / math.sqrt(2 * L)
        elif name.endswith("k_temp"):
            mean = K_TEMP
        elif name.endswith(("norm.weight", "_scale")):
            mean = 1.0
        elif name.endswith(("router.w1.weight", "router.w2.weight",
                            "router.w3.weight")):
            std = 1.0 / math.sqrt(shape[-2])
        elif name.endswith("conv0.weight"):
            std = 1.0 / math.sqrt(shape[1])
        elif name.endswith("conv1.weight"):
            std = 1.0 / math.sqrt(shape[1] * d)
        elif name.endswith("router.bias"):
            std = 0.005 / SELECTION_BIAS_SCALE
        elif name.endswith("_bias"):
            std = 0.002
        out[name] = mean + std * jax.random.normal(
            jax.random.fold_in(key, n), shape, jnp.float32)
    return out


def init_weights(cfg: dict, key) -> dict:
    """The drawn leaves with every layer's selection bias run to rest on a
    seeded calibration batch (CALIBRATION_BATCH), layer by layer: a layer's
    scores do not depend on its own bias,
    so a layer is one forward and CALIBRATION_STEPS steps of k b -= step *
    sign(load - 1/E), the step shrinking from 0.01 of score. Pure: jit
    it."""
    w = _draw(cfg, key)
    cb, cs = CALIBRATION_BATCH
    tokens = jax.random.randint(jax.random.fold_in(key, 1 << 20), (cb, cs),
                                0, cfg["vocab_size"], jnp.int32)
    mm = _mm_for("float32")
    E, scale = cfg["num_experts"], SELECTION_BIAS_SCALE
    x = w["embed.weight"][tokens]
    r = jnp.zeros((cb, cs, cfg["router_hidden_size"]), jnp.float32)
    biases = []
    for i in range(cfg["num_hidden_layers"]):
        p = {k[7:]: v[i] for k, v in w.items() if k.startswith("layers.")}
        x1, u, scores, r = _until_route(cfg, mm, x, r, p)
        flat = scores.reshape(-1, E)

        def rule(k, b):
            load = jnp.mean(jax.nn.one_hot(
                jnp.argmax(flat + scale * b, -1), E), 0)
            return b - 0.01 / scale * 0.97 ** k * jnp.sign(load - 1.0 / E)

        b = jax.lax.fori_loop(0, CALIBRATION_STEPS, rule, p["router.bias"])
        biases.append(b)
        x, _ = _experts_and_merge(cfg, mm, x1, u, scores,
                                  dict(p, **{"router.bias": b}))
    w["layers.router.bias"] = jnp.stack(biases)
    return w


def program_names(weights: dict) -> dict:
    """The stacked weights under the names paddle_tpu's model gives its
    parameters (`layers.<i>.<leaf>`); slices, no arithmetic."""
    out = {}
    for k, v in weights.items():
        if k.startswith("layers."):
            for i in range(v.shape[0]):
                out[f"layers.{i}.{k[7:]}"] = v[i]
        else:
            out[k] = v
    return out


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf under its program name."""
    out = {}
    for k, v in tree.items():
        v = v.astype(jnp.float32)
        if k.startswith("layers."):
            n = jnp.sqrt(jnp.sum(v * v, axis=tuple(range(1, v.ndim))))
            for i in range(v.shape[0]):
                out[f"layers.{i}.{k[7:]}"] = n[i]
        else:
            out[k] = jnp.sqrt(jnp.sum(v * v))
    return out


# ------------------------------------------------------------------ block


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y if w is None else y * w.astype(jnp.float32)).astype(x.dtype)


def _shift(x, j: int):
    """x [b, s, ...] moved j positions later along the sequence, zeros
    before position 0."""
    if j == 0:
        return x
    pad = [(0, 0), (j, 0)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def _rope(x, cfg):
    """Rotate-half RoPE on the first `partial_rotary_factor` of every
    head's channels, x [b, s, heads, d], positions 0..s-1; float32."""
    rot = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    inv = 1.0 / cfg["rope_theta"] ** (
        jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = jnp.split(xr, 2, axis=-1)
    xr = xr * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([xr.astype(x.dtype), x[..., rot:]], -1)


def cca_qkv(cfg, mm, u, p):
    """q [b, s, n_q, d], k, v [b, s, n_kv, d] of the normed stream u."""
    b, s, _ = u.shape
    nq, nkv, d, rep = _sizes(cfg)
    eps = cfg["rms_norm_eps"]
    q0 = mm("bsh,hc->bsc", u, p["attn.q_proj.weight"])
    k0 = mm("bsh,hc->bsc", u, p["attn.k_proj.weight"])
    c = jnp.concatenate([q0, k0], -1)
    c1 = sum(_shift(c, j) * p["attn.conv0.weight"][j]
             for j in range(cfg["cca_time0"]))
    c1 = c1.reshape(b, s, nq + nkv, d)
    c2 = sum(mm("bsid,ide->bsie", _shift(c1, j), p["attn.conv1.weight"][j])
             for j in range(cfg["cca_time1"]))
    q0 = q0.reshape(b, s, nkv, rep, d)
    k0 = k0.reshape(b, s, nkv, 1, d)
    m = (q0 + k0) / 2
    q = c2[:, :, :nq] + m.reshape(b, s, nq, d)
    k = c2[:, :, nq:] + jnp.mean(m, axis=3)
    q = _rope(_rms(q, None, eps), cfg)
    k = _rope(_rms(k, None, eps) * p["attn.k_temp"][:, None].astype(k.dtype),
              cfg)
    v = jnp.concatenate(
        [mm("bsh,hc->bsc", u, p["attn.v_proj.weight"]),
         mm("bsh,hc->bsc", _shift(u, 1), p["attn.v_shift_proj.weight"])], -1)
    return q, k, v.reshape(b, s, nkv, d)


def _attention(cfg, mm, q, k, v):
    """Causal softmax attention of n_q heads on n_kv, by blocks of Q_BLOCK
    query rows; softmax in float32. Returns [b, s, n_q * d]."""
    b, s, nq, d = q.shape
    nkv, rep = k.shape[2], nq // k.shape[2]
    qg = q.reshape(b, s, nkv, rep, d)

    def rows(q_blk, first):
        att = mm("bqgrd,bkgd->bgrqk", q_blk, k) / math.sqrt(d)
        seen = (jnp.arange(s)[None, :]
                <= first + jnp.arange(q_blk.shape[1])[:, None])
        att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf).astype(
            jnp.float32), axis=-1).astype(q.dtype)
        return mm("bgrqk,bkgd->bqgrd", att, v)

    if s <= Q_BLOCK or s % Q_BLOCK:
        o = rows(qg, 0)
    else:
        n = s // Q_BLOCK
        blocks = jnp.moveaxis(qg.reshape(b, n, Q_BLOCK, nkv, rep, d), 1, 0)
        o = jax.lax.map(lambda a: jax.checkpoint(rows)(a[0], a[1]),
                        (blocks, jnp.arange(n) * Q_BLOCK))
        o = jnp.moveaxis(o, 0, 1)
    return o.reshape(b, s, nq * d)


def _merge(x, y, p, sub: str):
    g = lambda leaf: p[f"{sub}.{leaf}"].astype(x.dtype)
    return (x * g("stream_scale") + g("stream_bias")
            + y * g("out_scale") + g("out_bias"))


def _until_route(cfg, mm, x, r_prev, p):
    """Attention sublayer and the router: (x', u', scores [b, s, E]
    float32, r); r_prev is the router stream of the layer before, zeros at
    the first."""
    eps = cfg["rms_norm_eps"]
    q, k, v = cca_qkv(cfg, mm, _rms(x, p["input_norm.weight"], eps), p)
    att = mm("bsc,ch->bsh", _attention(cfg, mm, q, k, v),
             p["attn.o_proj.weight"])
    x1 = _merge(x, att, p, "attn_res")
    u = _rms(x1, p["post_norm.weight"], eps)
    r = (mm("bsh,hr->bsr", u, p["router.down.weight"])
         + p["router.gamma"].astype(x.dtype) * r_prev)
    z = _rms(r, p["router.norm.weight"], eps)
    for leaf in ("router.w1.weight", "router.w2.weight"):
        z = jax.nn.gelu(mm("bsr,rq->bsq", z, p[leaf]), approximate=True)
    logits = mm("bsr,re->bse", z, p["router.w3.weight"])
    return x1, u, jax.nn.softmax(logits.astype(jnp.float32), -1), r


def _experts_and_merge(cfg, mm, x1, u, scores, p):
    """Top-1 over all the experts, the held ones dense, the expert
    sublayer's merge: (x'', the balance term of this layer)."""
    E, G, first = (cfg["num_experts"], cfg["experts_held"],
                   cfg["first_expert"])
    bias = p["router.bias"].astype(jnp.float32)
    e = jnp.argmax(scores + SELECTION_BIAS_SCALE
                   * jax.lax.stop_gradient(bias), -1)              # [b, s]
    weight = jnp.take_along_axis(scores, e[..., None], -1)          # [b,s,1]
    load = jnp.mean(jax.nn.one_hot(e, E, dtype=jnp.float32), axis=(0, 1))
    balance = jnp.sum(jax.lax.stop_gradient(load - 1.0 / E)
                      * (bias - jax.lax.stop_gradient(bias)))

    @jax.checkpoint
    def one(y, xs):
        g, wg, wu, wd = xs
        a = (jax.nn.silu(mm("bsh,hf->bsf", u, wg))
             * mm("bsh,hf->bsf", u, wu))
        o = mm("bsf,fh->bsh", a, wd)
        return y + jnp.where((e == first + g)[..., None],
                             o * weight.astype(o.dtype), 0), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(G), p["experts.gate_proj"], p["experts.up_proj"],
        p["experts.down_proj"]))
    return _merge(x1, y, p, "moe_res"), balance


def forward(cfg: dict, weights: dict, tokens, precision: str = "float32",
            remat: bool = False):
    """(logits [b, s, vocab], the balance term summed over layers) for
    tokens [b, s]."""
    mm = _mm_for(precision)
    stacked = {k[7:]: v for k, v in weights.items()
               if k.startswith("layers.")}

    def block(carry, p):
        x, r = carry
        x1, u, scores, r = _until_route(cfg, mm, x, r, p)
        x2, balance = _experts_and_merge(cfg, mm, x1, u, scores, p)
        return (x2, r), balance

    if remat:
        block = jax.checkpoint(block)
    x = weights["embed.weight"][tokens]
    r0 = jnp.zeros((*tokens.shape, cfg["router_hidden_size"]), x.dtype)
    (x, _), balance = jax.lax.scan(block, (x, r0), stacked)
    x = _rms(x, weights["final_norm.weight"], cfg["rms_norm_eps"])
    return mm("bsh,vh->bsv", x, weights["embed.weight"]), jnp.sum(balance)


def loss_fn(cfg, weights, tokens, labels, precision: str = "float32"):
    """Mean next-token cross-entropy over the slice, in float32, plus the
    zero-valued balance term."""
    logits, balance = forward(cfg, weights, tokens, precision, remat=True)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1)) \
        + balance.astype(jnp.float32)


def train_readings(cfg, weights, batches, opt: dict, rows_per_block: int,
                   precision: str = "float32"):
    """Follow len(batches) optimizer steps from `weights`. Returns the loss
    of each step, the per-leaf norm of the first gradient, and the per-leaf
    norm of the parameters' change after the last step. Pure and jittable;
    `batches` is a tuple of (tokens, labels). An expert's load is a share
    of the WHOLE batch, so the batch is one block of rows."""
    if any(t.shape[0] != rows_per_block for t, _ in batches):
        raise ValueError("reference_zaya takes a batch as one block of rows: "
                         "set reference_rows_per_block to the batch")
    if precision == "bfloat16":         # the control: bf16 master weights
        weights = {k: _store(x, jnp.bfloat16) for k, x in weights.items()}
    w0 = {k: x.astype(jnp.float32) for k, x in weights.items()}
    m = jax.tree_util.tree_map(jnp.zeros_like, weights)
    v = jax.tree_util.tree_map(jnp.zeros_like, weights)
    vg = jax.value_and_grad(functools.partial(loss_fn, cfg,
                                              precision=precision))
    losses, grad_norms = [], None
    for i, (tokens, labels) in enumerate(batches):
        loss, g = vg(weights, tokens, labels)
        if i == 0:
            grad_norms = leaf_norms(g)
        weights, m, v = adamw(weights, g, m, v, i + 1, opt)
        losses.append(loss.astype(jnp.float32))
    delta = {k: weights[k].astype(jnp.float32) - w0[k] for k in w0}
    return jnp.stack(losses), grad_norms, leaf_norms(delta)
