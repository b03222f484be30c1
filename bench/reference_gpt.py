"""The plain reference: a GPT decoder in straightforward jax.numpy.

Float32 with every matmul at "highest" precision, no kernels, no cache, no
batching tricks. It imports nothing of paddle_tpu and takes nothing the
program has made: the weights are drawn here from the seed, and run.py hands
the same arrays to the program through its public `set_state_dict`.

Architecture, as openai-community/gpt2 and arXiv:2005.14165 describe it:
learned token and position embeddings; pre-LayerNorm blocks (eps 1e-5) of
causal multi-head attention with a fused QKV projection whose columns are
ordered (q|k|v, head, head_dim), and a 4x MLP with tanh-GELU; final
LayerNorm; output head tied to the token embedding. Linear weights are
[in, out]. Block parameters are stacked on a leading layer axis and the
blocks run under lax.scan (one compiled block, not `num_layers` copies).

`precision` selects the arithmetic: "float32" (the reference) or "bfloat16"
(master weights, optimizer state and compute), used ONLY by the control that
the training limits were set against. `stored` names the type in which a
served model keeps its activations ("bfloat16": every value a block hands
on is rounded to it, the arithmetic stays float32): the reference AT the
precision the configuration states, whose own distance from the float32
reference is the unit the serving check measures the program's in.

A reference module of another family offers the same functions: seed_key,
init_weights, program_names, round_weights, leaf_norms, logits_at,
train_readings.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
BLOCK_LEAVES = ("ln1.weight", "ln1.bias", "attn.qkv.weight", "attn.qkv.bias",
                "attn.out.weight", "attn.out.bias", "ln2.weight", "ln2.bias",
                "mlp.fc1.weight", "mlp.fc1.bias", "mlp.fc2.weight",
                "mlp.fc2.bias")


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any non-negative seed (the driver's pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def _shapes(cfg: dict) -> dict:
    h, f, L = cfg["hidden_size"], cfg["ffn_hidden"], cfg["num_layers"]
    return {
        "wte.weight": (cfg["vocab_size"], h),
        "wpe.weight": (cfg["max_seq_len"], h),
        "ln_f.weight": (h,), "ln_f.bias": (h,),
        "blocks.ln1.weight": (L, h), "blocks.ln1.bias": (L, h),
        "blocks.attn.qkv.weight": (L, h, 3 * h),
        "blocks.attn.qkv.bias": (L, 3 * h),
        "blocks.attn.out.weight": (L, h, h), "blocks.attn.out.bias": (L, h),
        "blocks.ln2.weight": (L, h), "blocks.ln2.bias": (L, h),
        "blocks.mlp.fc1.weight": (L, h, f), "blocks.mlp.fc1.bias": (L, f),
        "blocks.mlp.fc2.weight": (L, f, h), "blocks.mlp.fc2.bias": (L, h),
    }


def init_weights(cfg: dict, key) -> dict:
    """Every weight from `key`, float32, block leaves stacked [L, ...].
    Matrices and embeddings N(0, 0.02) (output projections scaled by
    1/sqrt(2L), GPT-2's rule); LayerNorm gains 1 + N(0, 0.02); every bias
    N(0, 0.02), so that no leaf's gradient path is degenerate. Pure: jit it
    (run.py does, in one call)."""
    shapes = _shapes(cfg)
    out = {}
    for n, (name, shape) in enumerate(sorted(shapes.items())):
        std = 0.02
        if name.endswith(("attn.out.weight", "mlp.fc2.weight")):
            std = 0.02 / math.sqrt(2 * cfg["num_layers"])
        w = std * jax.random.normal(jax.random.fold_in(key, n), shape,
                                    jnp.float32)
        if name.endswith(("ln1.weight", "ln2.weight", "ln_f.weight")):
            w = 1.0 + w
        out[name] = w
    return out


def program_names(weights: dict) -> dict:
    """The stacked weights under the names paddle_tpu's GPT gives its
    parameters (`blocks.<i>.<leaf>`); slices, no arithmetic."""
    out = {k: v for k, v in weights.items() if not k.startswith("blocks.")}
    n_layers = weights["blocks.ln1.weight"].shape[0]
    for leaf in BLOCK_LEAVES:
        for i in range(n_layers):
            out[f"blocks.{i}.{leaf}"] = weights["blocks." + leaf][i]
    return out


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf under its program name (stacked leaves give
    one norm per layer)."""
    out = {}
    for k, v in tree.items():
        v = v.astype(jnp.float32)
        if k.startswith("blocks."):
            n = jnp.sqrt(jnp.sum(v * v, axis=tuple(range(1, v.ndim))))
            for i in range(v.shape[0]):
                out[f"blocks.{i}.{k[7:]}"] = n[i]
        else:
            out[k] = jnp.sqrt(jnp.sum(v * v))
    return out


def round_weights(weights: dict, precision: str) -> dict:
    """The weights as a configuration of that precision holds them, kept
    in float32 (bfloat16 for the serving cells)."""
    if precision == "float32":
        return weights
    return {k: _store(v, jnp.bfloat16).astype(jnp.float32)
            for k, v in weights.items()}


def _ln(x, w, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


def _block(cfg, mm, keep, x, p):
    """One pre-LN block on x [b, s, h]; `mm` is the matmul (einsum) at the
    chosen precision, `keep` rounds what the block stores."""
    b, s, h = x.shape
    nh = cfg["num_heads"]
    d = h // nh
    y = keep(_ln(x, p["ln1.weight"], p["ln1.bias"]))
    qkv = keep(mm("bsh,hk->bsk", y, p["attn.qkv.weight"])
               + p["attn.qkv.bias"])
    q, k, v = jnp.moveaxis(qkv.reshape(b, s, 3, nh, d), 2, 0)
    att = mm("bqnd,bknd->bnqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf).astype(jnp.float32),
                         axis=-1).astype(x.dtype)
    o = keep(mm("bnqk,bknd->bqnd", att, v)).reshape(b, s, h)
    x = keep(x + mm("bsh,hk->bsk", o, p["attn.out.weight"])
             + p["attn.out.bias"])
    y = keep(_ln(x, p["ln2.weight"], p["ln2.bias"]))
    y = keep(jax.nn.gelu(mm("bsh,hf->bsf", y, p["mlp.fc1.weight"])
                         + p["mlp.fc1.bias"], approximate=True))
    return keep(x + mm("bsf,fh->bsh", y, p["mlp.fc2.weight"])
                + p["mlp.fc2.bias"])


def _mm_for(precision: str):
    if precision == "float32":
        return functools.partial(jnp.einsum, precision="highest")
    return jnp.einsum                 # operands already in the low type


def _keep_for(stored: str):
    """Rounding of every value a block hands on, in float32: an explicit
    reduce_precision, which XLA may not drop as it may a convert pair."""
    if stored == "float32":
        return lambda x: x
    return lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                              mantissa_bits=7)


def hidden(cfg: dict, weights: dict, tokens, precision: str = "float32",
           remat: bool = False, stored: str = "float32"):
    """Final-LayerNorm output [b, s, h] for tokens [b, s]."""
    mm, keep = _mm_for(precision), _keep_for(stored)
    s = tokens.shape[1]
    x = keep(weights["wte.weight"][tokens] + weights["wpe.weight"][:s])
    stacked = {k[7:]: v for k, v in weights.items() if k.startswith("blocks.")}
    body = functools.partial(_block, cfg, mm, keep)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x, stacked)
    return keep(_ln(x, weights["ln_f.weight"], weights["ln_f.bias"]))


def logits_at(cfg, weights, tokens, first: int, count: int,
              stored: str = "float32"):
    """Logits [count, vocab] of ONE sequence tokens [s] at positions
    first .. first+count-1 (the position that predicts token i+1 is i)."""
    x = hidden(cfg, weights, tokens[None], stored=stored)[0]
    x = jax.lax.dynamic_slice_in_dim(x, first, count, 0)
    return _keep_for(stored)(
        _mm_for("float32")("sh,vh->sv", x, weights["wte.weight"]))


def loss_fn(cfg, weights, tokens, labels, precision: str = "float32"):
    """Sum (not mean) of next-token cross-entropy over tokens [b, s], so
    that row blocks add up; the caller divides by the token count."""
    x = hidden(cfg, weights, tokens, precision, remat=True)
    logits = _mm_for(precision)("bsh,vh->bsv", x, weights["wte.weight"])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


def loss_and_grads(cfg, weights, tokens, labels, rows_per_block: int,
                   precision: str = "float32"):
    """Mean loss and its gradient over the whole batch, accumulated over
    blocks of rows so that one block's activations are all that is live."""
    b, s = tokens.shape
    n_blocks = b // rows_per_block
    tb = tokens.reshape(n_blocks, rows_per_block, s)
    lb = labels.reshape(n_blocks, rows_per_block, s)
    vg = jax.value_and_grad(functools.partial(loss_fn, cfg,
                                              precision=precision))

    def step(carry, xs):
        l, g = vg(weights, xs[0], xs[1])
        return (carry[0] + l.astype(jnp.float32), jax.tree_util.tree_map(
            lambda a, c: a + c.astype(jnp.float32), carry[1], g)), None

    zeros = jax.tree_util.tree_map(
        lambda w: jnp.zeros(w.shape, jnp.float32), weights)
    (l, g), _ = jax.lax.scan(step, (jnp.float32(0), zeros), (tb, lb))
    n = b * s
    return l / n, jax.tree_util.tree_map(lambda x: x / n, g)


def _store(x, dtype):
    """x (float32) as a leaf of `dtype` holds it. The rounding to bfloat16
    is an explicit reduce_precision: a bare convert pair inside one jit is
    something XLA may drop (excess precision), and the control would then
    keep float32 master weights after all."""
    if dtype == jnp.bfloat16:
        x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x.astype(dtype)


def adamw(weights, grads, m, v, step: int, opt: dict):
    """Decoupled-decay Adam with bias correction, as arXiv:1711.05101:
    p <- p - lr * m_hat / (sqrt(v_hat) + eps) - lr * wd * p, every leaf
    decayed. State keeps the dtype it came in (float32, or the control's
    bfloat16)."""
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]
    new_w, new_m, new_v = {}, {}, {}
    for k, p in weights.items():
        g = grads[k].astype(jnp.float32)
        mk = b1 * m[k].astype(jnp.float32) + (1 - b1) * g
        vk = b2 * v[k].astype(jnp.float32) + (1 - b2) * g * g
        upd = (mk / (1 - b1 ** step)) / (jnp.sqrt(vk / (1 - b2 ** step)) + eps)
        p32 = p.astype(jnp.float32)
        new_w[k] = _store(p32 - lr * upd - lr * wd * p32, p.dtype)
        new_m[k], new_v[k] = _store(mk, m[k].dtype), _store(vk, v[k].dtype)
    return new_w, new_m, new_v


def train_readings(cfg, weights, batches, opt: dict, rows_per_block: int,
                   precision: str = "float32"):
    """Follow len(batches) optimizer steps from `weights`. Returns the
    loss of each step, the per-leaf norm of the first gradient, and the
    per-leaf norm of the parameters' change after the last step. Pure and
    jittable; `batches` is a tuple of (tokens, labels)."""
    if precision == "bfloat16":         # the control: bf16 master weights
        weights = {k: _store(x, jnp.bfloat16) for k, x in weights.items()}
    w0 = {k: x.astype(jnp.float32) for k, x in weights.items()}
    m = jax.tree_util.tree_map(jnp.zeros_like, weights)
    v = jax.tree_util.tree_map(jnp.zeros_like, weights)
    losses, grad_norms = [], None
    for i, (tokens, labels) in enumerate(batches):
        loss, g = loss_and_grads(cfg, weights, tokens, labels,
                                 rows_per_block, precision)
        if i == 0:
            grad_norms = leaf_norms(g)
        weights, m, v = adamw(weights, g, m, v, i + 1, opt)
        losses.append(loss)
    delta = {k: weights[k].astype(jnp.float32) - w0[k] for k in w0}
    return jnp.stack(losses), grad_norms, leaf_norms(delta)
