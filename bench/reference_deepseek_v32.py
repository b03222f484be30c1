"""The plain reference of the DeepSeek-V3.2 block (`model_type: deepseek_v32`):
the DeepSeek-V3 block of reference_deepseek.py (latent attention, routed +
shared experts, YaRN, the share and the slice: read its head first) with the
three things V3.2 adds, in straightforward jax.numpy.

Float32 with every matmul at "highest" precision, the EXPANDED attention form
under a dense selection mask, `lax.top_k` over whole rows of index scores, no
cache, no kernel. It imports pieces of reference_deepseek.py (the rotary
tables, the norms, the SwiGLU, the rounding of `stored`) and nothing of
paddle_tpu; the weights are drawn here from the seed under the program's own
parameter names.

What V3.2 adds (x [T, hidden], c_q as in MLA):
  Indexer, every layer: q^I = c_q W^I_qb -> index_n_heads x index_head_dim;
  k^I = LayerNorm(x W^I_k), gain and bias, eps 1e-6, ONE vector a token; the
  FIRST qk_rope_head_dim values of each q^I_j and of k^I take RoPE at the
  token's position from MLA's YaRN table, pairs NOT interleaved (rotate-half
  over the rotary part as it lies, where MLA de-interleaves first); both are
  multiplied by the normalised Hadamard matrix of index_head_dim; w = (x
  W^I_w) * heads^(-1/2) * head_dim^(-1/2).
  I(t, s) = sum_j w_j(t) ReLU(q^I_j(t) . k^I(s)) for s <= t.
  Selection: S(t) = the positions of the min(index_topk, t + 1) largest
  I(t, .), ties to the lower position (`lax.top_k`'s rule); one set a query
  token for all heads.
  Attention: MLA's scores and values with s restricted to S(t).
  Router: s = sigmoid(x W_r); the experts are n_group groups of consecutive
  ids; a group's score is the sum of its two largest s + b; the topk_group
  best groups stay; the num_experts_per_tok largest s + b among THEIR experts
  are selected; weights from s alone, as in V3.
  MTP module (`mtp_logits`; built where num_nextn_predict_layers > 0, its
  weights under `layers.<num_hidden_layers>.`): h' = Block(W_eh [RMSNorm(h_t)
  | RMSNorm(Emb(x_{t+1}))]) with h_t the residual stream after the last
  block (before the final norm), the block an expert layer with its own
  indexer, positions from 0; logits for token t + 2 = Head(RMSNorm(h')),
  embedding and head the model's.

`stored` = "bfloat16" rounds every value a block hands on, the indexer's
queries, keys and weights among them (the index cache holds bfloat16); index
scores, their ReLU and sum stay float32 in every precision.

Departures, all the configuration's (bench/configs/deepseek-v3.2.json,
`assumed`): the published indexer keeps its keys in FP8 with a scale per 128
values, here they are bfloat16 like the latent rows; LayerNorm's bias is
drawn N(0, 0.02) so that it is there; the rest as reference_deepseek.py says.
The attention runs HEAD_GROUP heads at a time (the sum over groups of each
group's part of the output projection is the same function): at 128 heads
and 20 k rows the float32 keys and values of all heads do not fit a chip.

Written for the compiler's time as much as for the chip's (a run meets three
to five padded lengths and compiles a program for each; PERF.md has what each
of these was worth). The weights are kept STACKED over the layers that share
a shape, `stacked.attn.<leaf>` [layers, ...], `stacked.dense.<leaf>` and
`stacked.moe.<leaf>`, each stack drawn in one call (`program_names` hands the
program its own per-layer names; `layer(weights, i)` gives one layer's
leaves under them); the dense layers and the expert layers are each ONE loop
body (`lax.scan` over the layer's index); and `logits_at` makes both
precisions of a sequence through that one body (`lax.map` over a flag that
says whether a block's values are rounded) and keeps the pair, so that
serve.py's second call on the same operands costs nothing.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import reference_deepseek as v3
from reference_deepseek import (  # noqa: F401  (serve.py calls these here)
    leaf_norms, round_weights, train_readings,
)

QUERY_BLOCK = 128         # query rows whose scores exist at once
HEAD_GROUP = 32           # heads whose keys and values exist at once
INDEX_NORM_EPS = 1e-6


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any non-negative seed (the driver's pass 2**31), of
    the "rbg" kind: the chip's own bit generator, which the compiler takes
    a second over where the default counter-based one costs it 15 s a draw
    of 470 M values."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


# ------------------------------------------------------------------ shapes


def _block_extra(cfg: dict, p: str) -> dict:
    nh, d = cfg["index_n_heads"], cfg["index_head_dim"]
    return {p + "self_attn.indexer.wq_b.weight": (cfg["q_lora_rank"], nh * d),
            p + "self_attn.indexer.wk.weight": (cfg["hidden_size"], d),
            p + "self_attn.indexer.k_norm.weight": (d,),
            p + "self_attn.indexer.k_norm.bias": (d,),
            p + "self_attn.indexer.weights_proj.weight":
                (cfg["hidden_size"], nh)}


def _shapes(cfg: dict) -> dict:
    L, h = cfg["num_hidden_layers"], cfg["hidden_size"]
    n_mtp = cfg.get("num_nextn_predict_layers", 0)
    # the module's block is one more expert layer after the last
    out = v3._shapes(dict(cfg, num_hidden_layers=L + n_mtp))
    for i in range(L + n_mtp):
        out.update(_block_extra(cfg, f"layers.{i}."))
    if n_mtp:
        p = f"layers.{L}."
        out.update({p + "enorm.weight": (h,), p + "hnorm.weight": (h,),
                    p + "eh_proj.weight": (2 * h, h),
                    p + "shared_head.norm.weight": (h,)})
    return out


def _group_of(cfg: dict, name: str):
    """(group, layers of it, leaf) of a per-layer parameter that is kept in
    a stack, None for the rest (top-level leaves, the MTP module's)."""
    L, k = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    if not name.startswith("layers."):
        return None
    i, leaf = name[len("layers."):].split(".", 1)
    if int(i) >= L:
        return None
    if not leaf.startswith("mlp."):
        return "attn", range(L), leaf
    return ("dense", range(k), leaf) if int(i) < k else (
        "moe", range(k, L), leaf)


def init_weights(cfg: dict, key) -> dict:
    """Every weight from `key`, float32, as reference_deepseek draws them
    (matrices and embeddings N(0, 0.02), projections back into the residual
    scaled by 1/sqrt(2L), norms' gains 1 + N(0, 0.02), the router's
    selection bias N(0, 0.005); LayerNorm's bias N(0, 0.02)), a STACK of
    the layers that share a leaf in one draw (see the head). The standard
    normal is drawn at bfloat16's grain and scaled in float32: the
    generator's bits for a stack of experts are then 0.9 GB and not 1.9
    beside the engine's own first weights and the ones made here."""
    shapes, L = {}, cfg["num_hidden_layers"]
    for name, shape in _shapes(cfg).items():
        g = _group_of(cfg, name)
        if g is None:
            shapes[name] = shape
        else:
            shapes[f"stacked.{g[0]}.{g[2]}"] = (len(g[1]),) + tuple(shape)
    out = {}
    for n, (name, shape) in enumerate(sorted(shapes.items())):
        std = 0.02
        if name.endswith(("o_proj.weight", "down_proj.weight", "down_proj")):
            std = 0.02 / math.sqrt(2 * L)
        elif name.endswith("e_score_correction_bias"):
            std = 0.005
        w = std * jax.random.normal(jax.random.fold_in(key, n), shape,
                                    jnp.bfloat16).astype(jnp.float32)
        if name.endswith("norm.weight"):
            w = 1.0 + w
        out[name] = w
    return out


def program_names(weights: dict) -> dict:
    """The weights under the names the program gives its parameters:
    `layers.<i>.<leaf>` for every layer of every stack."""
    out = {}
    for name, w in weights.items():
        if not name.startswith("stacked."):
            out[name] = w
    for i in range(weights["stacked.attn.input_layernorm.weight"].shape[0]):
        out.update({f"layers.{i}.{k}": v
                    for k, v in layer(weights, i).items()})
    return out


def _take(weights: dict, group: str, j) -> dict:
    """Entry j (an index, traced or not) of every leaf of a stack."""
    pre = f"stacked.{group}."
    return {k[len(pre):]: w[j] for k, w in weights.items()
            if k.startswith(pre)}


def _n_dense(weights: dict) -> int:
    w = weights.get("stacked.dense.mlp.gate_proj.weight")
    return 0 if w is None else w.shape[0]


def layer(weights: dict, i: int) -> dict:
    """Layer i's leaves under the program's names for them."""
    k = _n_dense(weights)
    return {**_take(weights, "attn", i),
            **(_take(weights, "dense", i) if i < k
               else _take(weights, "moe", i - k))}


# ------------------------------------------------------------------ layers


def hadamard(n: int):
    """The normalised n x n Sylvester-Hadamard matrix, float32."""
    h = jnp.ones((1, 1), jnp.float32)
    while h.shape[0] < n:
        h = jnp.block([[h, h], [h, -h]])
    return h * n ** -0.5


def rope_half(x, cos, sin):
    """Rotate-half over x [..., rope] as it lies (no de-interleave)."""
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer_norm(x, w, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + INDEX_NORM_EPS)
            * w.astype(jnp.float32) + b.astype(jnp.float32))


def indexer(cfg, keep, x, c_q, p, cos, sin):
    """(q^I [T, heads, d], k^I [T, d], w [T, heads]) of x [T, hidden]."""
    T = x.shape[0]
    nh, d, rd = (cfg["index_n_heads"], cfg["index_head_dim"],
                 cfg["qk_rope_head_dim"])
    q = keep(v3._mm("tr,rk->tk", c_q, p["self_attn.indexer.wq_b.weight"])
             ).reshape(T, nh, d)
    k = keep(_layer_norm(
        keep(v3._mm("th,hd->td", x, p["self_attn.indexer.wk.weight"])),
        p["self_attn.indexer.k_norm.weight"],
        p["self_attn.indexer.k_norm.bias"]))
    q = keep(jnp.concatenate([rope_half(q[..., :rd], cos[:, None],
                                        sin[:, None]), q[..., rd:]], -1))
    k = keep(jnp.concatenate([rope_half(k[..., :rd], cos, sin), k[..., rd:]],
                             -1))
    had = hadamard(d)
    q = keep(jnp.einsum("thd,de->the", q, had, precision="highest"))
    k = keep(jnp.einsum("td,de->te", k, had, precision="highest"))
    w = keep(v3._mm("th,hj->tj", x, p["self_attn.indexer.weights_proj.weight"]
                    )) * (nh ** -0.5 * d ** -0.5)
    return q, k, w


def selection(cfg, q_i, k_i, w_i):
    """S(t) for every row of ONE sequence as a dense mask [T, T] bool:
    `lax.top_k` over whole rows of index scores, QUERY_BLOCK rows at a
    time. A row's set is what top_k returns for it: the values above the
    k-th largest and, of those equal to it, the earliest as many as are
    still owed."""
    T = q_i.shape[0]
    k = min(cfg["index_topk"], T)
    k_pos = jnp.arange(T)

    def rows(args):
        q, w, q_pos = args                     # [B, heads, d], [B, heads]
        s = jnp.einsum("qhd,kd->qhk", q, k_i, precision="highest")
        # (+ 0.0: a sum of -0.0 terms counts as 0.0 in the order)
        scores = jnp.sum(jax.nn.relu(s) * w[:, :, None], 1) + 0.0   # [B, T]
        visible = k_pos[None, :] <= q_pos[:, None]
        scores = jnp.where(visible, scores, -jnp.inf)
        kth = jax.lax.top_k(scores, k)[0][:, -1:]
        above, tie = scores > kth, scores == kth
        owed = k - jnp.sum(above, -1, keepdims=True)
        return visible & (above | (tie & (jnp.cumsum(tie, -1) <= owed)))

    blk = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    split = lambda a: a.reshape(T // blk, blk, *a.shape[1:])
    return jax.lax.map(rows, (split(q_i), split(w_i), split(k_pos))
                       ).reshape(T, T)


def attention(cfg, keep, x, p, cos, sin):
    """Latent attention on x [T, hidden] under the indexer's selection,
    expanded form."""
    T = x.shape[0]
    nh, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rd, vd, kl = cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    c_q = keep(v3._rms(
        keep(v3._mm("th,hr->tr", x, p["self_attn.q_a_proj.weight"])),
        p["self_attn.q_a_layernorm.weight"], eps))
    kv = keep(v3._mm("th,hk->tk", x, p["self_attn.kv_a_proj_with_mqa.weight"]))
    c_kv = keep(v3._rms(kv[:, :kl], p["self_attn.kv_a_layernorm.weight"], eps))
    k_r = keep(v3.rope(kv[:, kl:], cos, sin))                    # [T, rd]
    chosen = selection(cfg, *indexer(cfg, keep, x, c_q, p, cos, sin))
    scale = v3.softmax_scale(cfg)
    blk = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    split = lambda a: a.reshape(T // blk, blk, *a.shape[1:])
    g = HEAD_GROUP if nh % HEAD_GROUP == 0 else nh

    def heads(out, ws):
        w_qb, w_kvb, w_o = ws                  # one group's columns / rows
        q = keep(v3._mm("tr,rk->tk", c_q, w_qb)).reshape(T, g, nope + rd)
        q_r = keep(v3.rope(q[..., nope:], cos[:, None], sin[:, None]))
        kvb = keep(v3._mm("tc,ck->tk", c_kv, w_kvb)).reshape(T, g, nope + vd)
        k_n, v = kvb[..., :nope], kvb[..., nope:]

        def rows(args):
            qn, qr, sel = args                 # [B, g, nope], [B, g, rd]
            s = (jnp.einsum("qhd,khd->hqk", qn, k_n, precision="highest")
                 + jnp.einsum("qhd,kd->hqk", qr, k_r, precision="highest")
                 ) * scale
            s = jnp.where(sel[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                              precision="highest")

        o = jax.lax.map(rows, (split(q[..., :nope]), split(q_r),
                               split(chosen)))
        return out + v3._mm("tk,kh->th", keep(o.reshape(T, g * vd)), w_o), None

    n = nh // g
    out, _ = jax.lax.scan(heads, jnp.zeros_like(x), (
        jnp.moveaxis(p["self_attn.q_b_proj.weight"].reshape(
            -1, n, g * (nope + rd)), 1, 0),
        jnp.moveaxis(p["self_attn.kv_b_proj.weight"].reshape(
            kl, n, g * (nope + vd)), 1, 0),
        p["self_attn.o_proj.weight"].reshape(n, g * vd, -1)))
    return out


def route(cfg, x, w_r, bias):
    """(indices [T, top_k], weights [T, top_k]) over ALL `n_routed_experts`
    under the group limit: float32 sigmoid scores; selection by score +
    bias inside the topk_group best groups (a group's score: its two
    largest score + bias); weights from the scores alone."""
    s = jax.nn.sigmoid(v3._mm("th,he->te", x, w_r))
    choice = s + bias.astype(jnp.float32)[None]
    T, E = choice.shape
    n_group = cfg.get("n_group", 1)
    if n_group > 1:
        grouped = choice.reshape(T, n_group, E // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)
        _, best = jax.lax.top_k(group_score, cfg["topk_group"])
        kept = jnp.zeros((T, n_group), bool).at[
            jnp.arange(T)[:, None], best].set(True)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(T, E)
    _, idx = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def moe(cfg, keep, x, p):
    """Routed part over the experts held (a dense masked sum) plus the
    shared expert whole, as reference_deepseek.moe under this router."""
    idx, w = route(cfg, x, p["mlp.gate.weight"],
                   p["mlp.gate.e_score_correction_bias"])
    first = cfg["first_expert"]

    def one(y, ex):
        e, wg, wu, wd = ex
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)   # [T]
        return y + w_e[:, None] * v3._swiglu(keep, x, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(cfg["experts_held"]), p["mlp.experts.gate_proj"],
        p["mlp.experts.up_proj"], p["mlp.experts.down_proj"]))
    return y + v3._swiglu(keep, x, p["mlp.shared_experts.gate_proj.weight"],
                          p["mlp.shared_experts.up_proj.weight"],
                          p["mlp.shared_experts.down_proj.weight"])


def block(cfg, keep, x, p, cos, sin, ffn):
    """One block on x [T, hidden]; `ffn(y)` is the layer's own."""
    eps = cfg["rms_norm_eps"]
    x = keep(x + attention(cfg, keep, keep(v3._rms(
        x, p["input_layernorm.weight"], eps)), p, cos, sin))
    return keep(x + ffn(keep(v3._rms(
        x, p["post_attention_layernorm.weight"], eps))))


def dense_ffn(keep, y, p):
    return v3._swiglu(keep, y, p["mlp.gate_proj.weight"],
                      p["mlp.up_proj.weight"], p["mlp.down_proj.weight"])


def _keep_if(rounded):
    """`v3._keep_for` by a flag that may be traced: the values a block
    hands on rounded to bfloat16 where it is set."""
    return lambda x: jnp.where(rounded, v3._keep_for("bfloat16")(x), x)


def _residual(cfg: dict, weights: dict, tokens, keep):
    """Every layer through ONE loop body: the attention is the same in all
    of them, the FFN the layer's own by a `cond` on its index."""
    cos, sin = v3.rope_tables(cfg, tokens.shape[0])
    x = keep(weights["embed_tokens.weight"][tokens].astype(jnp.float32))
    L, k = cfg["num_hidden_layers"], _n_dense(weights)

    def one(x, i):
        dense = lambda y: dense_ffn(keep, y, _take(
            weights, "dense", jnp.minimum(i, k - 1)))
        routed = lambda y: moe(cfg, keep, y, _take(
            weights, "moe", jnp.maximum(i - k, 0)))
        ffn = routed if k == 0 else dense if k == L else (
            lambda y: jax.lax.cond(i < k, dense, routed, y))
        return block(cfg, keep, x, _take(weights, "attn", i), cos, sin,
                     ffn), None

    return jax.lax.scan(one, x, jnp.arange(L))[0]


def residual(cfg: dict, weights: dict, tokens, stored: str = "float32"):
    """The residual stream [T, hidden] after the last block, before the
    final norm, for ONE sequence tokens [T]."""
    return _residual(cfg, weights, tokens, v3._keep_for(stored))


_pair = None       # (weights, tokens, first, count, logits of both streams)


def logits_at(cfg, weights, tokens, first: int, count: int,
              stored: str = "float32"):
    """Logits [count, vocab] of ONE sequence tokens [T] at positions
    first .. first+count-1 (the position that predicts token i+1 is i).
    Both precisions are made at once, through one loop body, and kept: a
    second call on the same operands (serve.py asks for "float32", then for
    "bfloat16") takes the other of the pair."""
    global _pair
    if not (_pair and all(a is b for a, b in zip(
            _pair[:4], (weights, tokens, first, count)))):
        def stream(rounded):
            keep = _keep_if(rounded)
            x = keep(v3._rms(_residual(cfg, weights, tokens, keep),
                             weights["norm.weight"], cfg["rms_norm_eps"]))
            x = jax.lax.dynamic_slice_in_dim(x, first, count, 0)
            return keep(v3._mm("th,hv->tv", x, weights["lm_head.weight"]))

        _pair = (weights, tokens, first, count,
                 jax.lax.map(stream, jnp.asarray([False, True])))
    return _pair[4][("float32", "bfloat16").index(stored)]


def mtp_logits(cfg, weights, tokens, stored: str = "float32"):
    """The multi-token-prediction module on ONE sequence tokens [T]: logits
    [T - 1, vocab], row t for token t + 2."""
    keep = v3._keep_for(stored)
    eps = cfg["rms_norm_eps"]
    p = v3._layer(weights, cfg["num_hidden_layers"])
    h = residual(cfg, weights, tokens, stored)[:-1]
    e = keep(weights["embed_tokens.weight"][tokens[1:]].astype(jnp.float32))
    x = keep(v3._mm("tk,kh->th", jnp.concatenate(
        [keep(v3._rms(h, p["hnorm.weight"], eps)),
         keep(v3._rms(e, p["enorm.weight"], eps))], -1), p["eh_proj.weight"]))
    cos, sin = v3.rope_tables(cfg, x.shape[0])
    x = block(cfg, keep, x, p, cos, sin, lambda y: moe(cfg, keep, y, p))
    x = keep(v3._rms(x, p["shared_head.norm.weight"], eps))
    return keep(v3._mm("th,hv->tv", x, weights["lm_head.weight"]))
