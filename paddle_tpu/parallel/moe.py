"""Mixture-of-Experts with expert parallelism ('ep' mesh axis).

Reference: python/paddle/incubate/distributed/models/moe/moe_layer.py
(MoELayer:261 with MoEScatter:97/MoEGather:147 PyLayers over
global_scatter/global_gather all-to-all kernels,
phi/kernels/gpu/global_scatter_kernel.cu) and gates in moe/gate/ (gshard,
switch).

TPU-native: the classic one-hot dispatch/combine einsum formulation (GShard).
Expert weights carry a leading expert axis sharded over 'ep'; the dispatch
einsum contracts tokens against a [tokens, experts, capacity] mask, and GSPMD
lowers the resharding to the same all-to-all the reference calls explicitly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer import Layer
from paddle_tpu.ops.registry import OPS, OpDef
from paddle_tpu.parallel.api import sharding_constraint


def _switch_moe(x, gate_w, w1, b1, w2, b2, capacity_factor=1.25,
                activation="gelu"):
    """Pure kernel: top-1 (switch) routing with capacity, dense dispatch.
    x: [tokens, d]; gate_w: [d, E]; w1: [E, d, f]; w2: [E, f, d]."""
    s, d = x.shape
    e = gate_w.shape[1]
    c = max(int(capacity_factor * s / e), 1)

    logits = jnp.matmul(x.astype(jnp.float32), gate_w.astype(jnp.float32))
    probs = _stable_softmax(logits)
    expert_idx = jnp.argmax(probs, axis=-1)                     # [s]
    expert_prob = jnp.max(probs, axis=-1)                       # [s]
    onehot = jnp.eye(e, dtype=jnp.float32)[expert_idx]          # [s, e]
    # position of each token within its expert queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot          # [s, e]
    pos_in_e = jnp.sum(pos, axis=-1)                            # [s]
    keep = pos_in_e < c
    pos_oh = jnp.eye(c, dtype=jnp.float32)[
        jnp.clip(pos_in_e, 0, c - 1).astype(jnp.int32)]         # [s, c]
    dispatch = (onehot * keep[:, None])[:, :, None] * pos_oh[:, None, :]
    combine = dispatch * expert_prob[:, None, None]

    xin = jnp.einsum("sec,sd->ecd", dispatch.astype(x.dtype), x)
    h = jnp.einsum("ecd,edf->ecf", xin, w1) + b1[:, None, :]
    h = _act(h, activation)
    out_e = jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]
    y = jnp.einsum("sec,ecd->sd", combine.astype(x.dtype), out_e)

    # switch aux load-balancing loss (Fedus et al.)
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return y, aux.astype(x.dtype)


def _stable_softmax(logits):
    """Max-subtracted softmax: fp32 gate logits past ~88 overflow a bare
    exp() to inf and poison routing with NaNs (reference gates normalize the
    same way)."""
    import jax

    return jax.nn.softmax(logits, axis=-1)


def _act(h, name):
    import jax

    return {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
            "silu": jax.nn.silu}[name](h)


def _gshard_moe(x, gate_w, w1, b1, w2, b2, capacity_factor=1.25,
                activation="gelu", key=None, jitter=0.0):
    """Top-2 (GShard) routing with capacity and renormalized gates.

    Reference: incubate/distributed/models/moe/gate/gshard_gate.py (top-2 +
    aux load-balance loss + optional logit jitter) over the mesh-tf/GShard
    slot-claim order: top-1 claims expert slots first, top-2 claims the
    remainder; a choice that overflows capacity is dropped (its combine
    weight zeroes, so an overflowed token degrades to its other expert or
    to a pure residual — the published no-token-left-behind=False
    behavior). Gates of the surviving pair renormalize to sum 1.
    x: [tokens, d]; gate_w: [d, E]; w1: [E, d, f]; w2: [E, f, d]."""
    s, d = x.shape
    e = gate_w.shape[1]
    # top-2 routing makes 2s assignments, so capacity doubles relative to
    # the switch gate (the reference GShard C = 2 * cf * s / E) — without
    # the 2x even a perfectly balanced batch overflows at cf < 2
    c = max(int(2 * capacity_factor * s / e), 1)

    logits = jnp.matmul(x.astype(jnp.float32), gate_w.astype(jnp.float32))
    if key is not None and jitter > 0.0:
        import jax

        logits = logits + jax.random.normal(key, logits.shape) * jitter
    probs = _stable_softmax(logits)
    idx1 = jnp.argmax(probs, axis=-1)                           # [s]
    p1 = jnp.max(probs, axis=-1)
    oh1 = jnp.eye(e, dtype=jnp.float32)[idx1]                   # [s, e]
    probs2 = probs * (1.0 - oh1)
    idx2 = jnp.argmax(probs2, axis=-1)
    p2 = jnp.max(probs2, axis=-1)
    oh2 = jnp.eye(e, dtype=jnp.float32)[idx2]

    # slot claiming: all top-1 choices first, then top-2 choices on top
    pos1 = jnp.cumsum(oh1, axis=0) * oh1 - oh1                  # [s, e]
    count1 = jnp.sum(oh1, axis=0, keepdims=True)                # [1, e]
    pos2 = (jnp.cumsum(oh2, axis=0) + count1) * oh2 - oh2
    pos1_t = jnp.sum(pos1, axis=-1)                             # [s]
    pos2_t = jnp.sum(pos2, axis=-1)
    keep1 = pos1_t < c
    keep2 = pos2_t < c

    def disp(onehot, pos_t, keep):
        pos_oh = jnp.eye(c, dtype=jnp.float32)[
            jnp.clip(pos_t, 0, c - 1).astype(jnp.int32)]        # [s, c]
        return (onehot * keep[:, None])[:, :, None] * pos_oh[:, None, :]

    d1 = disp(oh1, pos1_t, keep1)                               # [s, e, c]
    d2 = disp(oh2, pos2_t, keep2)
    dispatch = jnp.minimum(d1 + d2, 1.0)

    # renormalize the surviving pair's gates to sum 1
    g1 = p1 * keep1.astype(jnp.float32)
    g2 = p2 * keep2.astype(jnp.float32)
    denom = jnp.maximum(g1 + g2, 1e-9)
    combine = d1 * (g1 / denom)[:, None, None] + \
        d2 * (g2 / denom)[:, None, None]

    xin = jnp.einsum("sec,sd->ecd", dispatch.astype(x.dtype), x)
    h = jnp.einsum("ecd,edf->ecf", xin, w1) + b1[:, None, :]
    h = _act(h, activation)
    out_e = jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]
    y = jnp.einsum("sec,ecd->sd", combine.astype(x.dtype), out_e)

    # GShard aux loss: E * sum_e(mean_prob_e * frac_top1_tokens_e)
    frac_tokens = jnp.mean(oh1, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return y, aux.astype(x.dtype)


def _naive_moe(x, gate_w, w1, b1, w2, b2, top_k=2, activation="gelu"):
    """Naive top-k gate (reference moe/gate/naive_gate.py): every token
    reaches all its top-k experts — no capacity, no drops, no aux loss.
    Dense-compute formulation: every expert runs on every token and the
    top-k softmax weights select; exact (reference semantics) but O(E)
    compute — the testing/small-E gate, as in the reference."""
    e = gate_w.shape[1]
    top_k = min(max(int(top_k), 1), e)
    logits = jnp.matmul(x.astype(jnp.float32), gate_w.astype(jnp.float32))
    probs = _stable_softmax(logits)
    # select exactly top_k experts by index (a >=kth threshold would route
    # tie-at-kth tokens to more than top_k experts with diluted weights)
    import jax

    _, top_idx = jax.lax.top_k(probs, top_k)                    # [s, k]
    sel = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], top_idx].set(1.0)  # [s, e]
    w = probs * sel
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)         # [s, e]
    h = jnp.einsum("sd,edf->esf", x, w1) + b1[:, None, :]
    h = _act(h, activation)
    out_e = jnp.einsum("esf,efd->esd", h, w2) + b2[:, None, :]
    y = jnp.einsum("se,esd->sd", w.astype(x.dtype), out_e)
    return y, jnp.zeros((), x.dtype)


def _gshard_moe_rng(x, key, gate_w, w1, b1, w2, b2, capacity_factor=1.25,
                    activation="gelu", jitter=0.0):
    """rng=True dispatch variant: the registry injects the PRNG key as the
    second positional arg (traced, so the per-op jit cache stays warm —
    passing the key through attrs would make them unhashable and silently
    disable compilation)."""
    return _gshard_moe(x, gate_w, w1, b1, w2, b2,
                       capacity_factor=capacity_factor,
                       activation=activation, key=key, jitter=jitter)


OPS["switch_moe"] = OpDef("switch_moe", _switch_moe, diff=True, method=False)
OPS["gshard_moe"] = OpDef("gshard_moe", _gshard_moe, diff=True, method=False)
OPS["gshard_moe_jitter"] = OpDef("gshard_moe_jitter", _gshard_moe_rng,
                                 diff=True, rng=True, method=False)
OPS["naive_moe"] = OpDef("naive_moe", _naive_moe, diff=True, method=False)


class MoELayer(Layer):
    """MoE FFN block; expert weights sharded over 'ep'.

    gate: 'switch' (top-1, reference switch_gate), 'gshard' (top-2 with
    renormalized gates + jitter, reference gshard_gate), or 'naive'
    (top-k, no capacity, reference naive_gate)."""

    def __init__(self, d_model, d_ffn, num_experts, capacity_factor=1.25,
                 activation="gelu", gate="switch", top_k=2, jitter=0.0,
                 name=None):
        super().__init__()
        if gate not in ("switch", "gshard", "naive"):
            raise ValueError(f"unknown MoE gate {gate!r}")
        if not 1 <= int(top_k) <= num_experts:
            raise ValueError(
                f"top_k={top_k} out of range for {num_experts} experts")
        self.gate_type = gate
        self.top_k = top_k
        self.jitter = jitter
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.gate = self.create_parameter(
            [d_model, num_experts], default_initializer=I.Normal(0.0, 0.02))
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_ffn],
            default_initializer=I.Normal(0.0, 0.02),
            attr={"sharding": P("ep", None, None)})
        self.b1 = self.create_parameter(
            [num_experts, d_ffn], is_bias=True,
            attr={"sharding": P("ep", None)})
        self.w2 = self.create_parameter(
            [num_experts, d_ffn, d_model],
            default_initializer=I.Normal(0.0, 0.02),
            attr={"sharding": P("ep", None, None)})
        self.b2 = self.create_parameter(
            [num_experts, d_model], is_bias=True,
            attr={"sharding": P("ep", None)})
        self.aux_loss = None

    def forward(self, x):
        from paddle_tpu.ops.registry import dispatch

        shape = x.shape
        flat = x.reshape([-1, shape[-1]])
        args = (flat, self.gate, self.w1, self.b1, self.w2, self.b2)
        if self.gate_type == "gshard":
            attrs = {"capacity_factor": self.capacity_factor,
                     "activation": self.activation}
            if self.jitter and self.training:
                # rng=True op: the dispatcher injects the key positionally
                attrs["jitter"] = self.jitter
                y, aux = dispatch("gshard_moe_jitter", args, attrs)
            else:
                y, aux = dispatch("gshard_moe", args, attrs)
        elif self.gate_type == "naive":
            y, aux = dispatch("naive_moe", args,
                              {"top_k": self.top_k,
                               "activation": self.activation})
        else:
            y, aux = dispatch("switch_moe", args,
                              {"capacity_factor": self.capacity_factor,
                               "activation": self.activation})
        self.aux_loss = aux
        return y.reshape(shape)


# ------------------------------------------------------------------------
# A dropless top-k expert layer that is told which experts it holds: one
# rank's share of an expert-parallel deployment. Routing runs over ALL the
# experts the router has; the products run over the experts held here.
# Pure functions on jax arrays: a model's eager forward and a serving
# runner's jitted step both call them. On one chip the layer runs without
# its exchange: what the absent experts would add is left out.


def sigmoid_topk_route(x, gate_w, bias, top_k: int, *,
                       norm_topk_prob: bool = True, scale: float = 1.0,
                       n_group: int = 1, topk_group: int = 1):
    """Sigmoid scores with a selection-only bias ("noaux_tc"): x [T, d],
    gate_w [d, E], bias [E] or None (a router without one selects by the
    scores alone) -> (indices [T, top_k] of the top_k largest of
    score + bias, weights [T, top_k] float32). With `n_group` > 1 the
    selection is group-limited: the E experts are `n_group` groups of
    consecutive ids, a group's score is the sum of its two largest score +
    bias, only the `topk_group` best groups stay, and the top_k are taken
    among their experts (one group: no limit, the same program as before
    the option). The bias moves the selection and never the weights: w_e =
    s_e / (sum of the selected s + 1e-20) * scale, the sum over all top_k
    selected whoever holds them."""
    import jax

    s = jax.nn.sigmoid(jnp.matmul(x, gate_w,
                                  preferred_element_type=jnp.float32))
    choice = s if bias is None else s + bias.astype(jnp.float32)[None, :]
    if n_group > 1:
        T, E = choice.shape
        grouped = choice.reshape(T, n_group, E // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, best = jax.lax.top_k(group_score, topk_group)       # [T, kept]
        kept = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None],
                       axis=1)                                  # [T, groups]
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf
                           ).reshape(T, E)
    _, idx = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * scale


def _swiglu(x, w_gate, w_up, w_down, out_dtype=None):
    """(silu(x W_g) * x W_u) W_d; the two products come out in x's type,
    their activation is taken in float32 and rounded once."""
    import jax

    g = jnp.matmul(x, w_gate).astype(jnp.float32)
    u = jnp.matmul(x, w_up).astype(jnp.float32)
    a = (jax.nn.silu(g) * u).astype(x.dtype)
    return jnp.matmul(a, w_down,
                      preferred_element_type=out_dtype or x.dtype)


def expert_layout(idx, first_expert: int, n_held: int, block_rows: int,
                  valid=None):
    """The token-expert pairs whose expert lies in [first_expert,
    first_expert + n_held), sorted by expert into rows where every expert's
    group starts at a multiple of `block_rows`. idx [T, K] int; valid [T]
    bool (rows that are padding route nowhere). Returns
    (counts [n_held], row_pair [R], p_end [n_held], block_expert
    [R / block_rows]): pairs per held expert; for each row of the layout
    the flat pair t * K + k it holds, T * K where it is padding; where each
    group's padded rows end (the last entry is the rows that hold
    anything); the expert of each row block. R is static: every pair held
    (a token picks min(K, n_held) held experts at most) and under a block
    of padding an expert."""
    T, K = idx.shape
    G, bm, N = n_held, block_rows, T * K
    local = (idx >= first_expert) & (idx < first_expert + G)
    if valid is not None:
        local = local & valid[:, None]
    e = jnp.where(local, idx - first_expert, G).reshape(N).astype(jnp.int32)
    counts = jnp.zeros((G + 1,), jnp.int32).at[e].add(1)[:G]
    order = jnp.argsort(e, stable=True).astype(jnp.int32)  # held pairs first
    padded = (counts + bm - 1) // bm * bm
    p_end = jnp.cumsum(padded)
    p_start, start = p_end - padded, jnp.cumsum(counts) - counts
    R = -(-T * min(K, G) // bm) * bm + G * bm
    rank = jnp.arange(N, dtype=jnp.int32)
    e_sorted = jnp.minimum(e[order], G - 1)
    dest = jnp.where(e[order] < G,
                     p_start[e_sorted] + rank - start[e_sorted], R)
    row_pair = jnp.full((R,), N, jnp.int32).at[dest].set(order, mode="drop")
    block_expert = jnp.minimum(jnp.searchsorted(
        p_end, jnp.arange(R // bm, dtype=jnp.int32) * bm, side="right"),
        G - 1).astype(jnp.int32)
    return counts, row_pair, p_end, block_expert


# fewest held experts at which the grouped product takes the serving form's
# walk: below it a loop iteration an expert block is few iterations
GROUPED_MIN_EXPERTS = 64


def block_rows(n_pairs: int, n_routed: int) -> int:
    """Rows of one block of `expert_layout` for the serving form: the power
    of two at or under the pairs an expert can expect (`n_pairs / n_routed`:
    the launch's token-expert pairs over ALL the experts the router chooses
    among, held here or not), between 16 (a whole bfloat16 tile) and 256. A
    decode step of 64 rows x 8 on 256 experts walks blocks of 16, a prefill
    piece of 2048 rows on them blocks of 64, a prompt of 16384 rows on 384
    experts blocks of 256: padding an expert's group to a block then costs
    a fraction of its rows, where a block chosen from the pairs alone (256
    past 4096 of them) multiplied four times the rows of an expert that
    gets 64."""
    per = n_pairs // max(n_routed, 1)
    bm = 16
    while bm * 2 <= min(per, 256):
        bm *= 2
    return bm


def grouped_walk(w_gate, w_down, bm: int) -> bool:
    """Whether the serving form walks its blocks inside
    `ops.pallas.grouped_matmul` rather than in a loop of its own: MANY
    experts held, each SMALL. Many: `GROUPED_MIN_EXPERTS` or more. Small:
    an expert's matrix is one weight tile of the grouped product
    (`grouped_matmul.one_tile`), so a launch's grid is its row blocks alone
    and block i + 1's matrix is fetched while block i multiplies. A loop
    iteration a block fetches nothing ahead: at 6 MB an expert it waits for
    its matrices as long as it multiplies, at 88 MB (12 or 8 held experts of
    7168 x 2048) the fetch IS the block and the loop reads at the memory's
    rate (PERF.md, S8)."""
    from paddle_tpu.ops.pallas.grouped_matmul import one_tile

    G, d, f = w_gate.shape
    return G >= GROUPED_MIN_EXPERTS and one_tile(
        bm, d, f, w_gate.dtype) and one_tile(bm, f, d, w_down.dtype, 4)


def held_experts_ffn(x, idx, weights, w_gate, w_up, w_down,
                     first_expert: int, valid=None, walk: bool = False,
                     n_routed: int = None):
    """The routed part of a top-k expert layer over the experts held here,
    dropless: y[t] = sum over the selected experts e of token t that lie in
    [first_expert, first_expert + G) of weights[t, e] * SwiGLU_e(x[t]).

    The SERVING form: it has no reverse mode and raises by name under
    `jax.grad`. A training step calls `held_experts_ffn_train`, the same
    layer with a gradient.

    x [T, d]; idx / weights [T, K] as `sigmoid_topk_route` gives them;
    w_gate / w_up [G, d, f], w_down [G, f, d]: the held experts, stacked;
    valid [T] bool (rows that are padding route nowhere); `n_routed`: the
    experts the router chooses among (left out: the G held). Returns
    (y [T, d] float32, pairs, touched): the token-expert pairs computed
    here and the held experts that got at least one; with `walk`, also
    (blocks, rows): the row blocks the layer walked and the rows it
    multiplied (whole blocks).

    No [tokens, experts, capacity] mask and no capacity: the local pairs
    are sorted by expert into a layout where every expert's group starts
    at a multiple of a row block (`expert_layout`; `block_rows` chooses the
    block from the pairs an expert can expect), and only the blocks that
    exist are multiplied: each gathers its tokens' rows, multiplies them by
    its one expert's matrices and adds the weighted result onto its tokens.
    An expert no token chose costs nothing (its matrices are not read); if
    every token chooses the same expert the walk is longer, nothing is
    dropped. Two walks of the same blocks, chosen from shapes
    (`grouped_walk`): a loop with a DYNAMIC trip count, an expert block an
    iteration, or the three products of the launch as
    `ops.pallas.grouped_matmul`'s forward."""
    G = w_gate.shape[0]
    bm = block_rows(idx.size, n_routed or G)
    y, counts, blocks = _walk(grouped_walk(w_gate, w_down, bm), bm, x, idx,
                              weights, w_gate, w_up, w_down, first_expert,
                              valid)
    out = (y, jnp.sum(counts), jnp.sum((counts > 0).astype(jnp.int32)))
    return out + (blocks, blocks * bm) if walk else out


def _walk(grouped: bool, bm: int, x, idx, weights, w_gate, w_up, w_down,
          first_expert: int, valid=None):
    """One of the two walks over `expert_layout` in blocks of `bm` rows:
    (y [T, d] float32, counts [G], the blocks that hold anything)."""
    K, N = idx.shape[1], idx.size
    counts, row_pair, p_end, block_expert = expert_layout(
        idx, first_expert, w_gate.shape[0], bm, valid)
    if grouped:
        blocks = p_end[-1] // bm
        return _walk_grouped(bm, blocks, x, weights, row_pair, block_expert,
                             w_gate, w_up, w_down), counts, blocks
    real = row_pair < N
    row_tok = jnp.where(real, row_pair // K, 0)
    row_w = jnp.where(real, weights.reshape(N)[jnp.minimum(row_pair, N - 1)],
                      0.0).astype(jnp.float32)
    blocks = p_end[-1] // bm
    return _walk_blocks(bm, blocks, x, row_tok, row_w, block_expert, w_gate,
                        w_up, w_down), counts, blocks


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _walk_blocks(bm, n_blocks, x, row_tok, row_w, block_expert,
                 w_gate, w_up, w_down):
    def block(b, y):
        rows = jax.lax.dynamic_slice_in_dim(row_tok, b * bm, bm)
        w = jax.lax.dynamic_slice_in_dim(row_w, b * bm, bm)
        ex = block_expert[b]
        out = _swiglu(x[rows], w_gate[ex], w_up[ex], w_down[ex],
                      out_dtype=jnp.float32)
        # a block's rows are distinct tokens (a token picks an expert
        # once); its padding rows add zero onto token 0
        return y.at[rows].add(out * w[:, None])

    return jax.lax.fori_loop(0, n_blocks, block,
                             jnp.zeros(x.shape, jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _walk_grouped(bm, n_blocks, x, weights, row_pair, block_expert,
                  w_gate, w_up, w_down):
    """The launch's three products over the layout's rows, then each pair's
    row of the result weighted onto its token. Rows and pairs are two views
    of one one-to-one map (`row_pair` [R]: the flat pair t * K + k a row
    holds, T * K where it is padding), so both directions are GATHERS: a
    scatter-add of the rows onto their tokens costs the chip several times
    as much. A pair whose expert is absent, or whose token is padding, has
    no row and adds nothing."""
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

    (T, K), R = weights.shape, row_pair.shape[0]
    product = functools.partial(
        grouped_matmul, block_group=block_expert, n_live=n_blocks,
        block_rows=bm)
    pair_row = jnp.full((T * K + 1,), R, jnp.int32).at[row_pair].set(
        jnp.arange(R, dtype=jnp.int32))[:T * K]
    rows = x.at[row_pair // K].get(mode="fill", fill_value=0)
    g = product(rows, w_gate).astype(jnp.float32)
    u = product(rows, w_up).astype(jnp.float32)
    out = product((jax.nn.silu(g) * u).astype(x.dtype), w_down,
                  out_dtype=jnp.float32)
    picked = out.at[pair_row].get(mode="fill", fill_value=0)
    return jnp.sum(picked.reshape(T, K, -1)
                   * weights.astype(jnp.float32)[..., None], axis=1)


def _no_gradient(*_):
    raise TypeError(
        "parallel.moe.held_experts_ffn is the serving form of the held-"
        "experts layer: its walk over the blocks that exist has a dynamic "
        "trip count, which has no reverse mode. Differentiate "
        "parallel.moe.held_experts_ffn_train, the same layer over "
        "ops.pallas.grouped_matmul")


_walk_blocks.defvjp(_no_gradient, _no_gradient)
_walk_grouped.defvjp(_no_gradient, _no_gradient)


# ------------------------------------------------------------------------
# The training form: the same share of the same layer, every step of it
# differentiable. Top-k of softmax scores, the pairs sorted by expert, the
# three products as ops.pallas.grouped_matmul, the results weighted and
# gathered back onto their tokens. Nothing is dropped and no capacity
# exists.


def softmax_topk_route(logits, bias, top_k: int):
    """Softmax scores with a selection-only bias: logits [T, E], bias [E]
    -> (indices [T, top_k] of the top_k largest of score + bias, weights
    [T, top_k] = the selected experts' own scores, scores [T, E]), float32.
    The bias moves the selection and never a weight, and no gradient
    reaches it through this function."""
    s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, idx = jax.lax.top_k(
        s + jax.lax.stop_gradient(bias.astype(jnp.float32))[None, :], top_k)
    return idx, jnp.take_along_axis(s, idx, axis=-1), s


def held_experts_ffn_train(x, idx, weights, w_gate, w_up, w_down,
                           first_expert: int, block_rows: int = None):
    """`held_experts_ffn` for a training step: y[t] = sum over the selected
    experts e of token t held here of weights[t, e] * SwiGLU_e(x[t]), with
    a gradient in x, weights and the three stacks of matrices. A token whose
    experts are all absent gets zeros.

    x [T, d] (bfloat16 under autocast, and the products then run in it);
    idx / weights [T, K]; w_gate / w_up [G, d, f], w_down [G, f, d].
    Returns (y [T, d] float32, pairs, rows_padded): the pairs computed here
    and the rows the grouped product multiplied (whole blocks of
    `block_rows`: 256 where there are pairs enough, 16 below)."""
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

    T, d = x.shape
    K, G = idx.shape[1], w_gate.shape[0]
    N = T * K
    bm = block_rows or (256 if N >= 2048 else 16)
    counts, row_pair, p_end, block_expert = expert_layout(
        idx, first_expert, G, bm)
    R = row_pair.shape[0]
    product = functools.partial(
        grouped_matmul, block_group=block_expert, n_live=p_end[-1] // bm,
        block_rows=bm)
    # the row of each pair, R for a pair whose expert is absent; the layout
    # holds each held pair in exactly one row, so rows and pairs are two
    # views of one one-to-one map and both directions of it are gathers
    pair_row = jnp.full((N + 1,), R, jnp.int32).at[row_pair].set(
        jnp.arange(R, dtype=jnp.int32))[:N]
    rows = _take_rows(jnp.repeat(x, K, axis=0) if K > 1 else x,
                      row_pair, pair_row)
    g = product(rows, w_gate).astype(jnp.float32)
    u = product(rows, w_up).astype(jnp.float32)
    out = product((jax.nn.silu(g) * u).astype(x.dtype), w_down,
                  out_dtype=jnp.float32)
    picked = _take_rows(out, pair_row, row_pair).reshape(T, K, d)
    y = jnp.sum(picked * weights.astype(jnp.float32)[..., None], axis=1)
    return y, jnp.sum(counts), p_end[-1]


@jax.custom_vjp
def _take_rows(x, index, inverse):
    """x[index], zeros where index is out of range. `inverse` is the same
    one-to-one map read the other way (inverse[index[i]] == i wherever
    index[i] is in range), so the gradient is a gather too: a scatter-add
    costs the chip several times as much."""
    return x.at[index].get(mode="fill", fill_value=0)


def _take_rows_fwd(x, index, inverse):
    return _take_rows(x, index, inverse), (index, inverse)


def _take_rows_bwd(res, dy):
    index, inverse = res
    return _take_rows(dy, inverse, index), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)
