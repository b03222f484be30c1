"""The plain reference of Phi-4-mini-flash-reasoning (`model_type:
phi4flash`, the SambaY architecture, arXiv:2507.06607): a self-decoder of
Mamba-1 layers alternating with differential attention over a sliding
window and ended by one full-attention layer, then a cross-decoder of gated
memory units (which read the last Mamba layer's scan output) alternating
with cross-attention to the full layer's keys and values; in straightforward
jax.numpy.

Float32, every product exact to float32 rounding; the selective scan is a
plain `lax.scan` over tokens, one token a step; every mask is dense; no
cache and no kernel. It imports nothing of
paddle_tpu and takes nothing the program has made: the weights are drawn
here from the seed, and serve.py hands the same arrays to the program
through its public `set_state_dict`. The weights' names are the program's
own (`program_names` drops only this file's stacked copies, below).

Layer kinds by index l of L (`mb_per_layer` 2; split = L / 2 + 2): l <
split: l even "mamba", l odd "window", l = split - 1 "full"; l >= split: l
even "gmu", l odd "cross". The memory is layer split - 2's.

The equations (x [T, hidden]; linears [in, out]):
  block   h = x + Mixer(LN(x)); y = h + MLP(LN(h)); LN a LayerNorm with gain
          and bias, eps `layer_norm_eps`. MLP(u) = W_down(SiLU(g) * v), (g,
          v) = u W_gate_up. A final LayerNorm; logits = y E^T (tied). No
          positional encoding.
  mamba   (x, z) = u W_in; x_t = SiLU(sum_j w[:, j] x_{t-(K-1)+j} + b_conv),
          zeros before the sequence; (r, B, C) = x W_x; dt = softplus(r W_dt
          + b_dt); A = -exp(A_log) [d_inner, d_state]; h_t = exp(dt_t A) *
          h_{t-1} + (dt_t x_t) (outer) B_t, h_0 = 0; y_t = h_t C_t + D x_t;
          Mixer = (y * SiLU(z)) W_out. The memory is m_t = y_t.
  gmu     Mixer = (m_t * SiLU(u_t W_in_g)) W_out_g.
  attention, differential: query heads and key/value heads of head_dim in
          adjacent pairs; for query pair p on key/value pair p' = p // (query
          pairs per key pair): A1 = softmax(q_{p,0} k_{p',0}^T / sqrt(d)),
          A2 = softmax(q_{p,1} k_{p',1}^T / sqrt(d)) under the layer's mask,
          v = [v_{p',0}, v_{p',1}], o_p = (1 - lambda_init) RMSNorm_{2d}(A1 v
          - lambda A2 v) (one gain of 2d a layer, eps 1e-5), lambda = exp(lq1
          . lk1) - exp(lq2 . lk2) + lambda_init, lambda_init = 0.8 - 0.6
          exp(-0.3 l); then W_o + b_o. Projections with bias. Window: key j
          is seen by query i where i - (W - 1) <= j <= i; full and cross: j
          <= i. A cross layer projects queries only (W_q, b_q) and uses the
          full layer's k and v.

What the configuration assumes, and so does this file (its `assumed` list
has the sources): the Mamba-1 sizes, the layer map, the differential form,
which array is the memory, the biases, the window's convention; `A_log` the
log of 1..d_state in every channel, `dt_proj.bias` the inverse softplus of
a step log-uniform in 0.001..0.1, `dt_proj.weight` uniform within
dt_rank^-1/2, the convolution's filter uniform within taps^-1/2, as the
public Mamba code draws them; gains (norms, `D`) 1 + N(0, 0.02); the four
lambda vectors N(0, 0.1); all else N(0, 0.02), the projections back into
the residual scaled by 1/sqrt(2L).

A run of the benchmark has to end inside the driver's time limit, and
serve.py compiles one reference program for every padded length it meets
(some eight a run) and asks ten forwards of up to 16384 rows: the rest of
this head is what that forced.

TWO STREAMS, ONE LOOP BODY. `stored` names the type in which a served model
keeps its activations: "float32", or "bfloat16" (every value a block hands
on is rounded to it, the arithmetic stays float32; the scan's state is NOT
a value handed on). serve.py asks for both of one sequence: `hidden` maps
the two over ONE loop body (`_stream`, whose `rounds` is traced: stream 0
"float32", stream 1 "bfloat16", `PRECISIONS`), one after the other, so
that the program is compiled once for both and holds one stream's
activations at a time. `logits_at` gives one stream of the pair and
remembers the pair it made last (`_pair`): the second call on the same
operands, which is what serve.py's one jitted function makes, costs
nothing. Stream 1's logits come back AS bfloat16 (what it hands on).

WEIGHTS. Every leaf is drawn from its own key, `fold_in(fold_in(key,
layer), crc32(name))`, the layers of one leaf set in ONE loop
(`init_weights`: four loop bodies to compile, where 430 separate draws
took the chip's compiler two minutes), and the stacked leaves are kept
beside the layers' own under `STACK`: the scans below walk them as they
are. `round_weights(.., "bfloat16")` hands on the stacks and the top
leaves AS bfloat16 arrays: 7.7 GB at the cell's size, which with 4.9 GB of
logits and 2.7 GB of temporaries is 15.3 of the chip's 16.9 GB (compiled
for a described v5e at 16384 rows).

PRODUCTS. The product of a float32 activation and a bfloat16 weight is
made EXACTLY, and not by `precision="highest"`, which spends six bfloat16
passes, three of them on the zero low parts of the weight, and which the
chip's compiler takes 2 to 5 s to compile, each: the activation is split
into three bfloat16 pieces whose sum it is (`_pieces`; 3 x 8 mantissa
bits), each piece times the weight is exact in the float32 the unit
accumulates in, and the three partial products are added; the pieces ride
one matmul as rows (`_mm_rows`). Where both sides are float32: the scores
are the six piece products that "highest" makes, side by side along the
contraction (`_six`), the probabilities times the values all nine, as
blocks of one result (`_three`, `_nine`). Float32 weights (`init_weights`' own
return: tests) go through `precision="highest"` as they are; tests hold
the two equal to float32 rounding.

ROWS. Every layer up to the full one runs on every row. The layers after
it run on the rows that are asked for (`first`, `count`): none of them
hands anything from one row to another, so the other rows are read by
nothing. `hidden(cfg, weights, tokens)` is every layer on every row, and
tests hold the rows asked for equal to its rows.

Memory and time. The layers run as two scans over PERIODS of two layers
(mamba + attention, the last period's attention full and not windowed: a
`cond` on the layer; then gmu + cross), the products by blocks of 512 rows
(serve.py pads to that; the Mamba mixer's blocks hand on the scan's state
and the convolution's last inputs) and attention by blocks of query rows,
so that one period's temporaries are live at a time. A block of query rows
makes, and masks densely, every score one of its rows may see; a window
layer's block takes the keys from its first row's window to its last row
(a slice of Q + W - 1 keys), a causal layer's all keys.

Training cells call `leaf_norms` and `train_readings`: this configuration is
served, not trained, and both raise.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

ROW_BLOCK = 512           # rows of one block of a product with a weight
HEAD_BLOCK = 128          # rows of one block of the logits
QUERY_BLOCK = 64          # query rows of one block of window attention
CAUSAL_BLOCK = 32         # and of causal attention, whose block sees every key
SUBLN_EPS = 1e-5
PRECISIONS = ("float32", "bfloat16")     # stream 0, stream 1
STACK = "stacked."        # + <group>.<leaf>: that leaf of the group's layers


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any non-negative seed (the driver's pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


# ------------------------------------------------------------------ shapes


def split(cfg: dict) -> int:
    return cfg["num_hidden_layers"] // 2 + 2


def kind(cfg: dict, layer: int) -> str:
    if layer >= split(cfg):
        return "cross" if layer % 2 else "gmu"
    if layer == split(cfg) - 1:
        return "full"
    return "window" if layer % 2 else "mamba"


def _sizes(cfg: dict):
    h = cfg["hidden_size"]
    d = h // cfg["num_attention_heads"]
    c = cfg["mamba_expand"] * h
    r = cfg.get("mamba_dt_rank") or math.ceil(h / 16)
    return h, d, c, r


def layer_shapes(cfg: dict, what: str) -> dict:
    """Leaf name (after `layers.<l>.`) -> shape, for a layer of that kind."""
    h, d, c, r = _sizes(cfg)
    f, n, taps = cfg["intermediate_size"], cfg["mamba_d_state"], \
        cfg["mamba_d_conv"]
    qw, kvw = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    out = {"input_layernorm.weight": (h,), "input_layernorm.bias": (h,),
           "post_attention_layernorm.weight": (h,),
           "post_attention_layernorm.bias": (h,),
           "mlp.gate_up_proj.weight": (h, 2 * f),
           "mlp.down_proj.weight": (f, h)}
    if what == "mamba":
        out.update({
            "mamba.in_proj.weight": (h, 2 * c),
            "mamba.conv.weight": (c, taps), "mamba.conv.bias": (c,),
            "mamba.x_proj.weight": (c, r + 2 * n),
            "mamba.dt_proj.weight": (r, c), "mamba.dt_proj.bias": (c,),
            "mamba.A_log": (c, n), "mamba.D": (c,),
            "mamba.out_proj.weight": (c, h)})
    elif what == "gmu":
        out.update({"gmu.in_proj.weight": (h, c),
                    "gmu.out_proj.weight": (c, h)})
    else:
        if what == "cross":
            out.update({"attn.q_proj.weight": (h, qw),
                        "attn.q_proj.bias": (qw,)})
        else:
            out.update({"attn.qkv_proj.weight": (h, qw + 2 * kvw),
                        "attn.qkv_proj.bias": (qw + 2 * kvw,)})
        out.update({"attn.o_proj.weight": (qw, h), "attn.o_proj.bias": (h,),
                    "attn.subln.weight": (2 * d,)})
        out.update({"attn.lambda_" + x: (d,)
                    for x in ("q1", "k1", "q2", "k2")})
    return out


def top_shapes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    return {"embed_tokens.weight": (cfg["vocab_size"], h),
            "final_layernorm.weight": (h,), "final_layernorm.bias": (h,)}


def draw_leaf(cfg: dict, key, layer, name: str, shape):
    """One leaf, float32. `layer` (0 for the leaves outside the layers, l +
    1 for layer l) may be traced; the name's part of the key is static."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer),
                           zlib.crc32(name.encode()) & 0x7FFFFFFF)
    normal = lambda std: std * jax.random.normal(k, shape, jnp.float32)
    if name.endswith("A_log"):
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[1] + 1, dtype=jnp.float32)), shape)
    if name.endswith("dt_proj.bias"):
        dt = jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(0.001), math.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))           # softplus(w) == dt
    if name.endswith(("dt_proj.weight", "conv.weight")):
        bound = (shape[0] if name.endswith("dt_proj.weight")
                 else shape[1]) ** -0.5
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    if name.endswith(("layernorm.weight", "subln.weight", ".D")):
        return 1.0 + normal(0.02)
    if ".lambda_" in name:
        return normal(0.1)
    if name.endswith(("o_proj.weight", "down_proj.weight",
                      "out_proj.weight")):
        return normal(0.02 / math.sqrt(2 * cfg["num_hidden_layers"]))
    return normal(0.02)


def draw_layer(cfg: dict, key, layer, what: str) -> dict:
    return {name: draw_leaf(cfg, key, layer + 1, name, shape)
            for name, shape in layer_shapes(cfg, what).items()}


def draw_top(cfg: dict, key) -> dict:
    return {name: draw_leaf(cfg, key, 0, name, shape)
            for name, shape in top_shapes(cfg).items()}


def groups(cfg: dict) -> dict:
    """Group -> (the leaf set's kind, its layers): the layers a scan walks
    together. The full layer has a window layer's leaves."""
    S, L = split(cfg), cfg["num_hidden_layers"]
    return {"mamba": ("mamba", list(range(0, S, 2))),
            "attn": ("window", list(range(1, S, 2))),
            "gmu": ("gmu", list(range(S, L, 2))),
            "cross": ("cross", list(range(S + 1, L, 2)))}


def init_weights(cfg: dict, key) -> dict:
    """Every weight from `key`, float32, under the program's names, and a
    group's leaves stacked under `STACK` (the same numbers: a layer's leaf
    is its row of the stack). Pure: jit it (serve.py does, in one call). A
    group's layers are drawn in ONE loop (four loop bodies to compile, not
    32 layers' draws: the chip's compiler takes a second for every large
    draw)."""
    out = draw_top(cfg, key)
    for group, (what, layers) in groups(cfg).items():
        stacked = jax.lax.map(lambda l, what=what: draw_layer(cfg, key, l, what),
                              jnp.asarray(layers, jnp.int32))
        for n, w in stacked.items():
            out[f"{STACK}{group}.{n}"] = w
            out.update({f"layers.{l}.{n}": w[j]
                        for j, l in enumerate(layers)})
    return out


def program_names(weights: dict) -> dict:
    """The weights under the names the program gives its parameters: the
    reference draws them under those names already; the stacks are its
    own."""
    return {k: v for k, v in weights.items() if not k.startswith(STACK)}


def _round(w):
    return jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)


def round_weights(weights: dict, precision: str) -> dict:
    """The weights as a configuration of that precision holds them, and of
    them what the forward reads: "bfloat16" gives the stacks and the top
    leaves as bfloat16 ARRAYS (see the head)."""
    if precision == "float32":
        return weights
    return {k: v.astype(jnp.bfloat16) for k, v in weights.items()
            if not k.startswith("layers.")}


def leaf_norms(tree: dict) -> dict:
    raise NotImplementedError(
        "reference_phi4flash: only training cells read leaf norms; this "
        "configuration is served, not trained")


def train_readings(*args, **kwargs):
    raise NotImplementedError(
        "reference_phi4flash: this configuration is served, not trained "
        "(16 bytes a parameter make the whole model 61.6 GB)")


# ---------------------------------------------------------------- products


def _pieces(x):
    """Float32 x as three bfloat16 arrays whose sum is x."""
    out = []
    for _ in range(3):
        out.append(x.astype(jnp.bfloat16))
        x = x - out[-1].astype(jnp.float32)
    return out


def _mm_rows(x, w, transposed: bool = False):
    """x [R, k] times w [k, n] ([n, k] if `transposed`) in float32. A
    bfloat16 w: x's three bfloat16 pieces ride one matmul as rows and
    their products are added, the smallest first."""
    spec = "rk,nk->rn" if transposed else "rk,kn->rn"
    if w.dtype != jnp.bfloat16:
        return jnp.einsum(spec, x, w, precision="highest")
    R = x.shape[0]
    out = jnp.einsum(spec, jnp.concatenate(_pieces(x), 0), w,
                     preferred_element_type=jnp.float32)
    return out[2 * R:] + out[R:2 * R] + out[:R]


def _by_rows(fn, *xs, block=None):
    """fn over blocks of rows of arrays [T, ..], one block's temporaries
    live at a time."""
    n, block = xs[0].shape[0], block or ROW_BLOCK
    if n <= block or n % block:
        return fn(*xs)
    out = jax.lax.map(lambda b: fn(*b), tuple(
        x.reshape(n // block, block, *x.shape[1:]) for x in xs))
    return jax.tree_util.tree_map(
        lambda o: o.reshape(n, *o.shape[2:]), out)


def _mm(x, w):
    """x [T, k] times w [k, n] -> [T, n], by blocks of rows."""
    return _by_rows(lambda xb: _mm_rows(xb, w), x)


def _six(x, axis: int, right: bool):
    """One side of a float32 product to float32 rounding as ONE matmul:
    the six products of bfloat16 pieces that `precision="highest"` makes
    (hi hi, hi mid, mid hi, hi lo, lo hi, mid mid) lie side by side along
    the contracted `axis`. The left side is hi hi mid hi lo mid, the right
    hi mid hi lo hi mid."""
    x0, x1, x2 = _pieces(x)
    return jnp.concatenate([x0, x1, x0, x2, x0, x1] if right else
                           [x0, x0, x1, x0, x2, x1], axis)


def _three(x, axis: int):
    """One side of a float32 product whose contracted axis is long: the
    three bfloat16 pieces side by side along a FREE `axis`; with both
    sides so, one matmul makes all nine piece products as blocks of its
    result (`_nine` adds them)."""
    return jnp.concatenate(_pieces(x), axis)


def _nine(out):
    """[g, r, j, 3 q, 3 d] -> [g, r, j, q, d]: the nine blocks added."""
    g, r, j, q, d = out.shape
    return out.reshape(g, r, j, 3, q // 3, 3, d // 3).sum((3, 5))


# ------------------------------------------------------------------ layers


def _ln(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _mlp(keep, x, p):
    def rows(xb):
        g, v = jnp.split(keep(_mm_rows(xb, p["mlp.gate_up_proj.weight"])),
                         2, -1)
        return _mm_rows(keep(jax.nn.silu(g) * v), p["mlp.down_proj.weight"])

    return _by_rows(rows, x)


def selective_scan(x, dt, A, B, C, h=None):
    """Token by token. x, dt [T, c]; A [c, n]; B, C [T, n]; the state h
    [c, n], zero where none is given. Returns (sum_n h_t C_t [T, c], the
    state after the last token)."""
    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * A) * h \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], axis=1)

    if h is None:
        h = jnp.zeros(A.shape, jnp.float32)
    h, y = jax.lax.scan(step, h, (x, dt, B, C))
    return y, h


def mamba(cfg, keep, u, p):
    """(the mixer's output [T, hidden], the memory y [T, d_inner]). By
    blocks of rows, which hand on the scan's state and the convolution's
    last taps - 1 inputs."""
    T = u.shape[0]
    _, _, c, r = _sizes(cfg)
    n, taps = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    R = ROW_BLOCK if T % ROW_BLOCK == 0 else T
    w, A = p["mamba.conv.weight"], -jnp.exp(p["mamba.A_log"])

    def rows(carry, ub):
        h, before = carry
        x, z = jnp.split(keep(_mm_rows(ub, p["mamba.in_proj.weight"])), 2, -1)
        xp = jnp.concatenate([before, x], 0)
        x = jax.nn.silu(sum(xp[j:j + R] * w[:, j][None] for j in range(taps))
                        + p["mamba.conv.bias"])
        rk, B, C = jnp.split(keep(_mm_rows(keep(x), p["mamba.x_proj.weight"])),
                             [r, r + n], -1)
        dt = jax.nn.softplus(keep(_mm_rows(rk, p["mamba.dt_proj.weight"]))
                             + p["mamba.dt_proj.bias"])
        y, h = selective_scan(x, dt, A, B, C, h)
        y = y + p["mamba.D"] * x
        return (h, xp[R:]), (
            _mm_rows(keep(y * jax.nn.silu(z)), p["mamba.out_proj.weight"]), y)

    start = (jnp.zeros((c, n), jnp.float32),
             jnp.zeros((taps - 1, c), jnp.float32))
    _, (out, y) = jax.lax.scan(rows, start, u.reshape(T // R, R, -1))
    return out.reshape(T, -1), y.reshape(T, c)


def gated_memory(keep, u, memory, p):
    def rows(ub, mb):
        gate = keep(_mm_rows(ub, p["gmu.in_proj.weight"]))
        return _mm_rows(keep(mb * jax.nn.silu(gate)),
                        p["gmu.out_proj.weight"])

    return _by_rows(rows, u, memory)


def differential_attention(cfg, keep, q, k, v, layer, window: bool, p,
                           first=0):
    """q [R, heads * d], the rows at positions first .. first+R-1; k, v [T,
    kv_heads * d]; `layer` (traced or not) gives lambda_init; `window`: the
    sliding-window mask, else causal. Returns [R, hidden].

    Every score a row may see is made and masked densely: a window layer's
    block of query rows takes the keys from its first row's window to its
    last row (a slice of Q + W - 1 keys), a causal layer's every key."""
    R, T = q.shape[0], k.shape[0]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, W = q.shape[1] // nq, cfg["sliding_window"]
    rep = nq // nkv                       # query pairs a key/value pair
    # [pairs', rep, 2, R, d]: query pair p = p' * rep + r, its two heads
    qh = q.reshape(R, nkv // 2, rep, 2, d).transpose(1, 2, 3, 0, 4)
    kh = k.reshape(T, nkv // 2, 2, d).transpose(1, 2, 0, 3)  # [p', 2, T, d]
    vp = v.reshape(T, nkv // 2, 2 * d).transpose(1, 0, 2)    # [p', T, 2d]
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))
    lam = (jnp.exp(jnp.sum(p["attn.lambda_q1"] * p["attn.lambda_k1"]))
           - jnp.exp(jnp.sum(p["attn.lambda_q2"] * p["attn.lambda_k2"]))
           + lam0)
    Q = QUERY_BLOCK if window else CAUSAL_BLOCK
    Q = Q if R % Q == 0 else R

    bf16 = lambda spec, a, b: jnp.einsum(
        spec, a, b, preferred_element_type=jnp.float32)

    def attend(qb, pos, k6, v3, kpos):
        """qb [p', rep, 2, Q, d] at positions pos [Q] against the keys k6
        [p', 2, S, 6d] and values v3 [p', S, 3 * 2d] (pieces: below) at
        positions kpos [S]."""
        s = bf16("grjqd,gjsd->grjqs", _six(qb, 4, False), k6) * d ** -0.5
        seen = (kpos[None, :] <= pos[:, None]) & (kpos[None, :] >= 0)
        if window:
            seen &= kpos[None, :] > pos[:, None] - W
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = _nine(bf16("grjqs,gsd->grjqd", _three(a, 3), v3))
        o = o[:, :, 0] - lam * o[:, :, 1]                    # [p', rep, Q, 2d]
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + SUBLN_EPS) * p["attn.subln.weight"]
        return ((1.0 - lam0) * o).transpose(2, 0, 1, 3).reshape(
            qb.shape[3], -1)

    n = R // Q
    blocks = (qh.reshape(*qh.shape[:3], n, Q, d).transpose(3, 0, 1, 2, 4, 5),
              first + jnp.arange(R).reshape(n, Q))
    if window:
        # keys W - 1 before the sequence stand at negative positions
        pad = lambda a, axis: jnp.pad(a, [(W - 1, 0) if i == axis else (0, 0)
                                          for i in range(a.ndim)])
        kh, vp = pad(kh, 2), pad(vp, 1)
    # the keys' and values' pieces are made ONCE, not a block of rows
    k6, v3 = _six(kh, 3, True), _three(vp, 2)
    if window:
        S = Q + W - 1

        def rows(args):
            qb, pos = args
            return attend(
                qb, pos, jax.lax.dynamic_slice_in_dim(k6, pos[0], S, 2),
                jax.lax.dynamic_slice_in_dim(v3, pos[0], S, 1),
                pos[0] - (W - 1) + jnp.arange(S))
    else:
        rows = lambda args: attend(*args, k6, v3, jnp.arange(T))
    out = jax.lax.map(rows, blocks).reshape(R, nq * d)
    return _mm(keep(out), p["attn.o_proj.weight"]) + p["attn.o_proj.bias"]


def _stream(cfg: dict, weights: dict, tokens, first, count: int, rounds):
    """Final-LayerNorm output [count, hidden] at the rows first ..
    first+count-1 of ONE sequence tokens [T] in one stream: `rounds`
    (traced) says whether every value a block hands on is rounded to
    bfloat16.

    Every layer up to the full one runs on every row. The layers after it
    run on the rows asked for: none of them hands anything from one row to
    another (a gated memory unit reads the memory at its own position, a
    cross layer the full layer's keys and values), so a row that is not
    asked for is read by nothing (`hidden` of every row is the same
    function; tests hold the two equal)."""
    keep = lambda x: jnp.where(rounds, _round(x), x)
    eps, h = cfg["layer_norm_eps"], cfg["hidden_size"]
    d = h // cfg["num_attention_heads"]
    qw, kvw = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d

    def leaves(group, j):
        """The leaves of the group's j-th layer (j traced). A product's
        weight stays as it is held; every other leaf is float32."""
        out = {n: weights[f"{STACK}{group}.{n}"][j]
               for n in layer_shapes(cfg, groups(cfg)[group][0])}
        return {n: w if n.endswith("proj.weight") else w.astype(jnp.float32)
                for n, w in out.items()}

    def block(x, p, mixer):
        """x + Mixer(LN(x)), then + MLP(LN(.)); mixer(u) -> (m, extra)."""
        u = keep(_ln(x, p["input_layernorm.weight"],
                     p["input_layernorm.bias"], eps))
        m, extra = mixer(u)
        x = keep(x + keep(m))
        u = keep(_ln(x, p["post_attention_layernorm.weight"],
                     p["post_attention_layernorm.bias"], eps))
        return keep(x + keep(_mlp(keep, u, p))), extra

    x = keep(weights["embed_tokens.weight"][tokens].astype(jnp.float32))
    S, T = split(cfg), tokens.shape[0]
    c = cfg["mamba_expand"] * h
    attn_layers = jnp.asarray(groups(cfg)["attn"][1], jnp.int32)
    cross_layers = jnp.asarray(groups(cfg)["cross"][1], jnp.int32)

    def self_period(carry, j):
        pm, pa, la = leaves("mamba", j), leaves("attn", j), attn_layers[j]
        x, memory = block(carry[0], pm, lambda u: mamba(cfg, keep, u, pm))
        # of the memory only the rows asked for are read (below)
        memory = jax.lax.dynamic_slice_in_dim(memory, first, count, 0)

        def attend(u):
            qkv = keep(_mm(u, pa["attn.qkv_proj.weight"])
                       + pa["attn.qkv_proj.bias"])
            q, k, v = jnp.split(qkv, [qw, qw + kvw], -1)
            # the two masks are two programs: the period's layer chooses
            o = jax.lax.cond(
                la == S - 1,
                lambda: differential_attention(cfg, keep, q, k, v, la, False,
                                               pa),
                lambda: differential_attention(cfg, keep, q, k, v, la, True,
                                               pa))
            return o, (k, v)

        x, (k, v) = block(x, pa, attend)
        return (x, memory, k, v), None

    init = (x, jnp.zeros((count, c), jnp.float32),
            jnp.zeros((T, kvw), jnp.float32), jnp.zeros((T, kvw), jnp.float32))
    (x, memory, k, v), _ = jax.lax.scan(self_period, init,
                                        jnp.arange(len(attn_layers)))
    x = jax.lax.dynamic_slice_in_dim(x, first, count, 0)

    def cross_period(x, j):
        pg, pc, lc = leaves("gmu", j), leaves("cross", j), cross_layers[j]
        x, _ = block(x, pg, lambda u: (gated_memory(keep, u, memory, pg),
                                       None))

        def attend(u):
            q = keep(_mm(u, pc["attn.q_proj.weight"])
                     + pc["attn.q_proj.bias"])
            return differential_attention(cfg, keep, q, k, v, lc, False, pc,
                                          first), None

        x, _ = block(x, pc, attend)
        return x, None

    x, _ = jax.lax.scan(cross_period, x, jnp.arange(len(cross_layers)))
    return keep(_ln(x, weights["final_layernorm.weight"].astype(jnp.float32),
                    weights["final_layernorm.bias"].astype(jnp.float32), eps))


def hidden(cfg: dict, weights: dict, tokens, first=0, count=None):
    """Final-LayerNorm output [2, count, hidden] at the rows first ..
    first+count-1 (every row where none is named) of ONE sequence tokens
    [T]: stream 0 "float32", stream 1 "bfloat16", one after the other
    through the same loop."""
    count = tokens.shape[0] if count is None else count
    return jax.lax.map(
        lambda rounds: _stream(cfg, weights, tokens, first, count, rounds),
        jnp.arange(2) == 1)


_PAIR = []      # [(weights, tokens, first, count), both streams' logits]


def _pair(cfg, weights, tokens, first, count):
    """Both streams' logits [count, vocab] of ONE sequence, and the pair
    made last is kept: the same operands (the same objects: one trace of
    serve.py's jitted function asks for stream 0, then for stream 1) get
    it again."""
    args = (weights, tokens, first, count)
    if _PAIR and all(a is b for a, b in zip(_PAIR[0], args)):
        return _PAIR[1]
    x = hidden(cfg, weights, tokens, first, count)
    emb = weights["embed_tokens.weight"]
    if emb.dtype != jnp.bfloat16:
        out = tuple(_mm_rows(x[i], emb, transposed=True) for i in range(2))
    else:
        def rows(x0, x1):
            """Stream 0's three pieces and stream 1's rows (bfloat16
            values: one piece) ride one matmul."""
            R = x0.shape[0]
            o = jnp.einsum("rk,nk->rn", jnp.concatenate(
                _pieces(x0) + [x1.astype(jnp.bfloat16)], 0), emb,
                preferred_element_type=jnp.float32)
            return o[2 * R:3 * R] + o[R:2 * R] + o[:R], o[3 * R:]

        out = _by_rows(rows, x[0], x[1], block=HEAD_BLOCK)
    # stream 1 hands its logits on as it does every value: in bfloat16
    _PAIR[:] = [args, (out[0], out[1].astype(jnp.bfloat16))]
    return _PAIR[1]


def logits_at(cfg, weights, tokens, first: int, count: int,
              stored: str = "float32"):
    """Logits [count, vocab] of ONE sequence tokens [T] at positions
    first .. first+count-1 (the position that predicts token i+1 is i)."""
    return _pair(cfg, weights, tokens, first, count)[PRECISIONS.index(stored)]
