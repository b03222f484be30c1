"""The plain reference of the Laguna block (`model_type: laguna`, Laguna-XS.2):
grouped-query attention, full-context and sliding-window layers mixed by
`layer_types`, a head count and a rotary form a layer type, a sigmoid gate a
head, a dense first feed-forward and then routed + shared experts, in
straightforward jax.numpy.

Float32 with every matmul at "highest" precision, dense masks, no cache, no
kernel, no batching; every expert applied in a per-expert loop over the rows
routed to it. It imports nothing of paddle_tpu and takes nothing the program
has made: the weights are drawn here from the seed, and serve.py hands the
same arrays to the program through its public `set_state_dict`.

The layer equations (x [T, hidden]; RMSNorm eps `rms_norm_eps`, float32
statistics; linears without bias, weights [in, out]). What the published
config does not give is ASSUMED, each one named constant or function here
(and one in paddle_tpu/models/laguna.py), listed in
bench/configs/laguna-xs.2.json under `assumed`:
  block l: h = x + Attn_l(RMSNorm(x)); y = h + FFN_l(RMSNorm(h)) (PRE_NORM:
  both sublayers normed on the way in, ASSUMED); final RMSNorm; logits = y
  W_head (untied).
  Attn_l, u its normed input: q = u W_q -> H_l heads of head_dim with H_l =
  num_attention_heads_per_layer[l]; k, v = u W_k, u W_v ->
  num_key_value_heads heads, query head j on key/value head j // (H_l / kv);
  no QK-norm (ASSUMED: the config has no key for one). Rotary on the first
  partial_rotary_factor * head_dim values of each head of q and k, pairs
  (x[i], x[i + rot/2]) (`rope_half`, ASSUMED pairing), by layer type
  (`rope_tables`): "yarn" blends theta^(-2i/rot) and that over `factor` by
  the linear ramp between the correction dims of beta_fast / beta_slow and
  multiplies cos and sin by `attention_factor`; "default" is theta^(-2i/rot).
  Causal softmax at head_dim^-1/2 in float32; on a sliding layer query i
  sees keys j with 0 <= i - j < sliding_window (`window_seen`, ASSUMED
  convention). g = sigmoid(u W_g), W_g [hidden, H_l]: one gate a head on
  that head's attention output before W_o (`head_gate`, ASSUMED form of
  `gating: true`).
  FFN_l "dense": SwiGLU of intermediate_size. "sparse": s = sigmoid(u W_r)
  in float32 (`ROUTER_SCORE`, ASSUMED), the num_experts_per_tok largest of
  num_experts, no selection bias and no groups (ASSUMED), weights s_e / (sum
  of the selected s + 1e-20) * moe_routed_scaling_factor (`NORM_TOPK`,
  ASSUMED) on the experts' outputs; y = sum_e w_e E_e(u) + E_shared(u)
  (`SHARED_GATED` False: the shared expert is added ungated, ASSUMED), each
  expert a SwiGLU of moe_intermediate_size.

Nothing is cut but depth: every expert, head and vocabulary row is here.

`stored` names the type in which a served model keeps its activations
("bfloat16": every value a block hands on is rounded to it, the arithmetic
stays float32): the reference AT the precision the configuration states.
`round_weights(..., "bfloat16")` keeps the rounded weights in bfloat16
STORAGE (7.7 GB at the cell's size); every use widens one matrix.

Written for the compiler's time as much as for the chip's (a run meets some
eight padded lengths and compiles a program for each). The weights are kept
STACKED over the layers that share a shape (`stacked.full_0_4.<leaf>`,
`stacked.sliding_1_2_3.<leaf>`: a layer type's attention, the stack's name
listing its layers; `stacked.dense_0.<leaf>`, `stacked.sparse_1_2_3_4.<leaf>`:
a kind of feed-forward; `stacked.norm_0_1_2_3_4.<leaf>`), each stack drawn in
one call (`program_names` hands the program its own per-layer names); EVERY
layer is one `lax.scan` body whose attention and feed-forward are a `cond` on
the layer's kind, so each mechanism is compiled once whatever the depth; and `logits_at` makes both precisions of a sequence
through that one body (`lax.map` over a flag) and keeps the pair, so that
serve.py's second call on the same operands costs nothing.

Training cells call `leaf_norms` and `train_readings`: this configuration is
served, not trained, and both raise.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

FULL, SLIDING = "full_attention", "sliding_attention"
# the assumed items that are a choice and not a formula (see the head)
PRE_NORM = True           # both sublayers normed on the way in
ROUTER_SCORE = jax.nn.sigmoid
NORM_TOPK = True          # weights over the sum of the selected scores
SHARED_GATED = False      # the shared expert is added as it is

ROW_BLOCK = 2048          # rows of one block of the MLPs
QUERY_BLOCK = 128         # query rows whose scores exist at once
EXPERT_ROWS = 128         # rows of one pass of an expert over its tokens


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any non-negative seed (the driver's pass 2**31), of
    the "rbg" kind: the chip's own bit generator, which its compiler takes
    a second over where the counter-based default costs it a quarter of a
    minute a draw of an expert stack."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


# ------------------------------------------------------------------ shapes


def _groups(cfg: dict) -> dict:
    """stack -> the layers it holds, in order."""
    L = cfg["num_hidden_layers"]
    kinds, mlps = cfg["layer_types"][:L], cfg["mlp_layer_types"][:L]
    return {FULL: [i for i in range(L) if kinds[i] == FULL],
            SLIDING: [i for i in range(L) if kinds[i] == SLIDING],
            "dense": [i for i in range(L) if mlps[i] == "dense"],
            "sparse": [i for i in range(L) if mlps[i] == "sparse"],
            "norm": list(range(L))}


_SHORT = {FULL: "full", SLIDING: "sliding"}


def _stack(cfg: dict, group: str) -> str:
    """A stack's name: its kind and the layers it holds, `full_0_4`, so
    that the weights say themselves which layer an entry is."""
    return "_".join([_SHORT.get(group, group)]
                    + [str(i) for i in _groups(cfg)[group]])


def _leaves(cfg: dict, group: str) -> dict:
    """leaf -> shape of ONE layer of a stack."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    if group in (FULL, SLIDING):
        heads = {cfg["num_attention_heads_per_layer"][i]
                 for i in _groups(cfg)[group]}
        if len(heads) != 1:
            raise ValueError(f"{group} layers of {sorted(heads)} heads: a "
                             "stack holds one shape")
        H, kv = heads.pop(), cfg["num_key_value_heads"]
        return {"self_attn.q_proj.weight": (h, H * d),
                "self_attn.k_proj.weight": (h, kv * d),
                "self_attn.v_proj.weight": (h, kv * d),
                "self_attn.g_proj.weight": (h, H),
                "self_attn.o_proj.weight": (H * d, h)}
    if group == "dense":
        f = cfg["intermediate_size"]
        return {"mlp.gate_proj.weight": (h, f), "mlp.up_proj.weight": (h, f),
                "mlp.down_proj.weight": (f, h)}
    if group == "sparse":
        E, fe = cfg["num_experts"], cfg["moe_intermediate_size"]
        fs = cfg["shared_expert_intermediate_size"]
        return {"mlp.gate.weight": (h, E),
                "mlp.experts.gate_proj": (E, h, fe),
                "mlp.experts.up_proj": (E, h, fe),
                "mlp.experts.down_proj": (E, fe, h),
                "mlp.shared_experts.gate_proj.weight": (h, fs),
                "mlp.shared_experts.up_proj.weight": (h, fs),
                "mlp.shared_experts.down_proj.weight": (fs, h)}
    return {"input_layernorm.weight": (h,),
            "post_attention_layernorm.weight": (h,)}


def _shapes(cfg: dict) -> dict:
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed_tokens.weight": (V, h), "norm.weight": (h,),
           "lm_head.weight": (h, V)}
    for group, layers in _groups(cfg).items():
        if layers:
            for leaf, shape in _leaves(cfg, group).items():
                out[f"stacked.{_stack(cfg, group)}.{leaf}"] = (
                    len(layers),) + shape
    return out


def parameter_count(cfg: dict) -> int:
    return sum(math.prod(s) for s in _shapes(cfg).values())


def init_weights(cfg: dict, key) -> dict:
    """Every weight from `key`, float32, a STACK of the layers that share a
    leaf, drawn a layer at a time. Matrices and embeddings N(0, 0.02), the projections
    back into the residual (o_proj, down_proj) scaled by 1/sqrt(2L); norms'
    gains 1 + N(0, 0.02). The standard normal is drawn at bfloat16's grain
    and scaled in float32, so the generator's bits for a stack of experts
    are half of what float32 draws would take beside the weights. Pure: jit
    it (serve.py does, in one call)."""
    out, L = {}, cfg["num_hidden_layers"]
    for n, (name, shape) in enumerate(sorted(_shapes(cfg).items())):
        std = 0.02
        if name.endswith(("o_proj.weight", "down_proj.weight", "down_proj")):
            std = 0.02 / math.sqrt(2 * L)
        draw = lambda k, shape: std * jax.random.normal(
            k, shape, jnp.bfloat16).astype(jnp.float32)
        k = jax.random.fold_in(key, n)
        # a stack a layer at a time: the generator's bits for the four
        # layers' experts at once are 3 GB that the runtime then keeps
        # reserved beside the engine for the whole run
        w = draw(k, shape) if not name.startswith("stacked.") else jnp.stack(
            [draw(jax.random.fold_in(k, j), shape[1:])
             for j in range(shape[0])])
        if name.endswith("norm.weight"):
            w = 1.0 + w
        out[name] = w
    return out


def program_names(weights: dict) -> dict:
    """The weights under the names the program gives its parameters:
    `layers.<i>.<leaf>` for every layer of every stack (a stack's name
    lists its layers)."""
    out = {k: w for k, w in weights.items() if not k.startswith("stacked.")}
    for name, w in weights.items():
        if name.startswith("stacked."):
            _, stack, leaf = name.split(".", 2)
            for j, i in enumerate(stack.split("_")[1:]):
                out[f"layers.{i}.{leaf}"] = w[j]
    return out


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
        "reference_laguna: only training cells read leaf norms; this "
        "configuration is served, not trained")


def train_readings(*args, **kwargs):
    raise NotImplementedError(
        "reference_laguna: this configuration is served, not trained (at "
        "16 bytes a parameter a cut inside the floors holds 16 or 32 of "
        "the 256 experts a layer)")


# ------------------------------------------------------------------ rotary


def yarn_correction_range(beta_fast, beta_slow, dim, base, original_max):
    """The rotary dims between which YaRN's ramp runs: floor / ceil of the
    dim that makes `beta` rotations over the original context, clamped to
    [0, dim - 1]."""
    def dim_of(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    return (max(math.floor(dim_of(beta_fast)), 0),
            min(math.ceil(dim_of(beta_slow)), dim - 1))


def rotary_dim(cfg: dict, kind: str) -> int:
    return int(cfg["head_dim"]
               * cfg["rope_parameters"][kind]["partial_rotary_factor"])


def rope_tables(cfg: dict, kind: str, n: int):
    """cos, sin [n, rot], float32, for positions 0..n-1 of a layer type: the
    half-width angles twice over (rotate-half)."""
    rp, rot = cfg["rope_parameters"][kind], rotary_dim(cfg, kind)
    i = jnp.arange(0, rot, 2, dtype=jnp.float32)
    inv, scale = 1.0 / rp["rope_theta"] ** (i / rot), 1.0
    if rp["rope_type"] == "yarn":
        low, high = yarn_correction_range(
            rp["beta_fast"], rp["beta_slow"], rot, rp["rope_theta"],
            rp["original_max_position_embeddings"])
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        inv = inv / rp["factor"] * ramp + inv * (1.0 - ramp)
        scale = rp["attention_factor"]
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rope_half(x, cos, sin):
    """x [T, heads, head_dim]; cos, sin [T, rot]: the first rot values of
    each head rotated, pairs (x[i], x[i + rot/2]); the rest as they are."""
    rot = cos.shape[-1]
    xr = x[..., :rot]
    x1, x2 = jnp.split(xr, 2, -1)
    turned = xr * cos[:, None] + jnp.concatenate([-x2, x1], -1) * sin[:, None]
    return jnp.concatenate([turned, x[..., rot:]], -1)


# ------------------------------------------------------------------ layers


def _keep_if(rounded):
    """Rounding of every value a block hands on, in float32, where the flag
    (which may be traced) is set: an explicit reduce_precision, which XLA
    may not drop as it may a convert pair."""
    return lambda x: jnp.where(rounded, jax.lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=7), x)


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


def _side_by_side(x, *ws):
    """x times each of `ws` ([hidden, n_i]) as ONE product against the
    matrices side by side, cut apart after it: the same sums, and one
    "highest" product for the chip's compiler where there were several (a
    second of its time each)."""
    out = _mm("th,hk->tk", x, jnp.concatenate(ws, axis=1))
    cuts, at = [], 0
    for w in ws[:-1]:
        at += w.shape[1]
        cuts.append(at)
    return jnp.split(out, cuts, axis=1)


def _swiglu(keep, x, wg, wu, wd):
    def rows(xb):
        g, u = _side_by_side(xb, wg, wu)
        a = keep(jax.nn.silu(keep(g)) * keep(u))
        return _mm("tf,fh->th", a, wd)

    return _by_rows(rows, x, ROW_BLOCK)


def head_gate(ug):
    """ug = u W_g [T, H] -> one sigmoid gate a query head."""
    return jax.nn.sigmoid(ug)


def window_seen(q_pos, k_pos, window: int):
    """[q, k] bool: 0 <= i - j < window."""
    d = q_pos[:, None] - k_pos[None, :]
    return (d >= 0) & (d < window)


def attention(cfg, keep, u, p, kind: str, cos, sin):
    """One layer type's attention on its normed input u [T, hidden]."""
    T, d = u.shape[0], cfg["head_dim"]
    kv, W = cfg["num_key_value_heads"], cfg["sliding_window"]
    H = p["self_attn.q_proj.weight"].shape[-1] // d
    q, k, v, g = (keep(a) for a in _side_by_side(u, *(
        p[f"self_attn.{n}_proj.weight"] for n in "qkvg")))
    q, k, v = q.reshape(T, H, d), k.reshape(T, kv, d), v.reshape(T, kv, d)
    q, k = keep(rope_half(q, cos, sin)), keep(rope_half(k, cos, sin))
    g = head_gate(g)
    q = q.reshape(T, kv, H // kv, d)
    blk = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    sliding = kind == SLIDING
    # a sliding layer's block of queries meets the keys of its own rows and
    # the window - 1 before them (zeros before position 0, masked)
    span = blk + W - 1 if sliding else T
    if sliding:
        front = jnp.zeros((W - 1, kv, d), jnp.float32)
        k, v = jnp.concatenate([front, k]), jnp.concatenate([front, v])

    def rows(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, blk, 0)
        q_pos = q0 + jnp.arange(blk)
        if sliding:
            kb = jax.lax.dynamic_slice_in_dim(k, q0, span, 0)
            vb = jax.lax.dynamic_slice_in_dim(v, q0, span, 0)
            k_pos = q0 - (W - 1) + jnp.arange(span)
            seen = window_seen(q_pos, k_pos, W) & (k_pos >= 0)[None, :]
        else:
            kb, vb, k_pos = k, v, jnp.arange(T)
            seen = k_pos[None, :] <= q_pos[:, None]
        s = jnp.einsum("qgrd,kgd->grqk", qb, kb, precision="highest"
                       ) * d ** -0.5
        pr = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return jnp.einsum("grqk,kgd->qgrd", pr, vb, precision="highest")

    o = jax.lax.map(rows, jnp.arange(0, T, blk)).reshape(T, H, d)
    o = keep(keep(o) * g[..., None]).reshape(T, H * d)
    return _mm("tk,kh->th", o, p["self_attn.o_proj.weight"])


def route(cfg, x, w_r):
    """(indices [T, top_k], weights [T, top_k]) of the experts each token
    selects among all of them: float32 scores, selection by the scores
    alone."""
    s = ROUTER_SCORE(_mm("th,he->te", x, w_r))
    _, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    if NORM_TOPK:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * cfg["moe_routed_scaling_factor"]


def moe(cfg, keep, x, p):
    """Routed experts plus the shared one on x [T, hidden]. The routed part
    is a loop over the experts, each over the rows that chose it: the
    token-expert pairs are laid out by expert, and an expert multiplies its
    own stretch of them EXPERT_ROWS rows a pass (rows past its stretch add
    nothing). Dropless: every pair is computed, by construction."""
    idx, w = route(cfg, x, p["mlp.gate.weight"])
    T, K = idx.shape
    E, R = cfg["num_experts"], EXPERT_ROWS
    # the pairs in the order (expert, token), by counting and not by a sort
    # (the chip's compiler takes nine seconds over a sort of 73728 keys): a
    # pair's place is its expert's first place plus the pairs of that
    # expert before it
    flat = idx.reshape(T * K)
    chose = (flat[:, None] == jnp.arange(E)[None, :]).astype(jnp.int32)
    before = jnp.cumsum(chose, 0) - chose                     # [T K, E]
    count = jnp.sum(chose, 0)
    first = jnp.cumsum(count) - count
    last = first + count
    place = first[flat] + jnp.take_along_axis(before, flat[:, None], 1)[:, 0]
    pair_tok = jnp.zeros((T * K + R,), jnp.int32).at[place].set(
        jnp.arange(T * K, dtype=jnp.int32) // K)
    pair_w = jnp.zeros((T * K + R,), jnp.float32).at[place].set(
        w.reshape(T * K))

    def expert(y, e):
        wg, wu, wd = (p["mlp.experts." + n][e]
                      for n in ("gate_proj", "up_proj", "down_proj"))

        def some(c, y):
            lo = first[e] + c * R
            tok = jax.lax.dynamic_slice_in_dim(pair_tok, lo, R)
            we = jnp.where(lo + jnp.arange(R) < last[e],
                           jax.lax.dynamic_slice_in_dim(pair_w, lo, R), 0.0)
            return y.at[tok].add(we[:, None]
                                 * _swiglu(keep, x[tok], wg, wu, wd))

        passes = (last[e] - first[e] + R - 1) // R
        return jax.lax.fori_loop(0, passes, some, y), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    shared = _swiglu(keep, x, p["mlp.shared_experts.gate_proj.weight"],
                     p["mlp.shared_experts.up_proj.weight"],
                     p["mlp.shared_experts.down_proj.weight"])
    if SHARED_GATED:
        raise NotImplementedError("no gate key in the published config")
    return y + shared


def dense_ffn(keep, y, p):
    return _swiglu(keep, y, p["mlp.gate_proj.weight"],
                   p["mlp.up_proj.weight"], p["mlp.down_proj.weight"])


def _take(weights: dict, group: str, j) -> dict:
    """Entry j (an index, traced or not) of every leaf of a stack."""
    pre = f"stacked.{group}."
    return {k[len(pre):]: w[j] for k, w in weights.items()
            if k.startswith(pre)}


def _either(flag, first, second, have_first: bool, have_second: bool, x):
    """first(x) where `flag`, else second(x); a kind no layer has is not
    traced."""
    if not have_second:
        return first(x)
    if not have_first:
        return second(x)
    return jax.lax.cond(flag, first, second, x)


def _residual(cfg: dict, weights: dict, tokens, keep):
    """Every layer through ONE loop body: its attention and its
    feed-forward the layer's own by a `cond` on its kind."""
    if not PRE_NORM:
        raise NotImplementedError("post-norm placement")
    groups = _groups(cfg)
    L, T, eps = cfg["num_hidden_layers"], tokens.shape[0], cfg["rms_norm_eps"]
    tables = {kind: rope_tables(cfg, kind, T) for kind in (FULL, SLIDING)
              if groups[kind]}
    within = lambda layers: jnp.asarray(
        [layers.index(i) if i in layers else 0 for i in range(L)])
    x = keep(weights["embed_tokens.weight"][tokens].astype(jnp.float32))

    def attn_of(kind, j):
        return lambda u: attention(cfg, keep, u, _take(
            weights, _stack(cfg, kind), j), kind, *tables[kind])

    def one(x, at):
        i, is_full, a, is_dense, m = at
        norms = _take(weights, _stack(cfg, "norm"), i)
        u = keep(_rms(x, norms["input_layernorm.weight"], eps))
        x = keep(x + _either(is_full, attn_of(FULL, a), attn_of(SLIDING, a),
                             bool(groups[FULL]), bool(groups[SLIDING]), u))
        y = keep(_rms(x, norms["post_attention_layernorm.weight"], eps))
        f = _either(
            is_dense,
            lambda y: dense_ffn(keep, y, _take(
                weights, _stack(cfg, "dense"), m)),
            lambda y: moe(cfg, keep, y, _take(
                weights, _stack(cfg, "sparse"), m)),
            bool(groups["dense"]), bool(groups["sparse"]), y)
        return keep(x + f), None

    full = jnp.asarray([i in groups[FULL] for i in range(L)])
    dense = jnp.asarray([i in groups["dense"] for i in range(L)])
    at = (jnp.arange(L), full,
          jnp.where(full, within(groups[FULL]), within(groups[SLIDING])),
          dense,
          jnp.where(dense, within(groups["dense"]), within(groups["sparse"])))
    return jax.lax.scan(one, x, at)[0]


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
            x = keep(_rms(_residual(cfg, weights, tokens, keep),
                          weights["norm.weight"], cfg["rms_norm_eps"]))
            x = jax.lax.dynamic_slice_in_dim(x, first, count, 0)
            return keep(_mm("th,hv->tv", x, weights["lm_head.weight"]))

        _pair = (weights, tokens, first, count,
                 jax.lax.map(stream, jnp.asarray([False, True])))
    return _pair[4][("float32", "bfloat16").index(stored)]

