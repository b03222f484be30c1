"""The gated delta rule's single-token update over a POOL of states, in place
(ops/gated_delta.py has the rule; this is `gated_delta_step` as a kernel).

A layer's states live in one array `[slots, d_k, H * d_v]` float32: slot s
holds its sequence's state with the key dimension on sublanes and (head,
value) on lanes, so that a slot is whole (8, 128) tiles at the published
widths (96 x 5760) and costs the algorithm's bytes, H * d_k * d_v * 4, and
not a d_v of 192 padded to 256 lanes. A decode step updates rows 0..B-1 (a
request's slot is its row of the batch); the array is aliased to the
output, rows the grid does not visit (the scratch slot) are not touched,
and each visited state is read once and written once: the update is bound
by those bytes.

Grid (B, H / heads_per_block). A step holds `[d_k, heads_per_block * d_v]`
of one sequence and walks it two heads at a time (384 lanes at d_v = 192:
whole lane tiles wherever it is cut). With S the tile, a / beta / v rows
over lanes and k, q columns over sublanes, all of it elementwise work and
reductions over sublanes, no matmul:

    S <- a * S;  u = beta * (v - sum_k S * K);  S <- S + K * u;
    o = sum_k S * Q

A row that is not live (a dead slot of the batch, a frozen row of a horizon)
is given a = 1, beta = 0 and k = 0 by the wrapper: it writes back what it
read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # pragma: no cover - absent on pure-CPU builds
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

# what one block of a state may hold: in and out, double-buffered, four of
# them live in the chip's 16 MiB of scoped VMEM
BLOCK_BYTES = 1 << 20


def heads_per_block(n_heads: int, d_k: int, d_v: int) -> int:
    """Heads of one grid step: the most (an even count that divides H, in
    whole 128-lane tiles unless it is all of them) within BLOCK_BYTES."""
    if n_heads % 2:
        raise ValueError(f"the kernel walks heads in pairs; {n_heads} heads")
    fits = [n for n in range(2, n_heads + 1, 2)
            if n_heads % n == 0 and (n == n_heads or (n * d_v) % 128 == 0)
            and n * d_k * d_v * 4 <= BLOCK_BYTES]
    return max(fits) if fits else n_heads


def _kernel(qk_ref, rows_ref, s_ref, o_ref, s_out_ref, *, heads: int,
            d_v: int):
    """qk [1, 1, d_k, 2 * heads] (this block's q columns, then its k
    columns); rows [1, 3, lanes] (a, beta * v, beta, each over (head,
    value)); s, s_out [1, d_k, lanes]; o [1, 1, lanes]."""
    d_k = s_ref.shape[1]
    pair = 2 * d_v
    first = jax.lax.broadcasted_iota(jnp.int32, (d_k, pair), 1) < d_v

    def column(c):
        return jnp.broadcast_to(qk_ref[0, 0, :, c:c + 1], (d_k, pair))

    for p in range(heads // 2):
        lanes = slice(p * pair, (p + 1) * pair)
        Q = jnp.where(first, column(2 * p), column(2 * p + 1))
        K = jnp.where(first, column(heads + 2 * p),
                      column(heads + 2 * p + 1))
        S = s_ref[0, :, lanes] * rows_ref[0, 0:1, lanes]
        kS = jnp.sum(S * K, axis=0, keepdims=True)
        u = rows_ref[0, 1:2, lanes] - rows_ref[0, 2:3, lanes] * kS
        S = S + K * u
        s_out_ref[0, :, lanes] = S
        o_ref[0, :, lanes] = jnp.sum(S * Q, axis=0, keepdims=True)


def gated_delta_decode(state, q, k, v, g, beta, live=None,
                       interpret: bool | None = None):
    """One token of B sequences against the pool. state [slots, d_k, H *
    d_v] float32, slots >= B; q, k [B, H, d_k]; v [B, H, d_v]; g, beta [B,
    H]; live [B] bool (None: every row). Returns (o [B, H, d_v] float32,
    the pool with rows 0..B-1 advanced where live)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _call(state, q, k, v, g, beta,
                 jnp.ones(q.shape[:1], bool) if live is None else live,
                 interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(state, q, k, v, g, beta, live, *, interpret: bool):
    B, H, d_k = q.shape
    d_v = v.shape[-1]
    hb = heads_per_block(H, d_k, d_v)
    nb, lanes = H // hb, hb * d_v
    f32 = lambda x: x.astype(jnp.float32)
    on = live[:, None]
    a = jnp.where(on, jnp.exp(f32(g)), 1.0)
    b = jnp.where(on, f32(beta), 0.0)
    over_lanes = lambda x: jnp.repeat(x, d_v, axis=-1)       # [B, H * d_v]
    rows = jnp.stack([over_lanes(a), (b[..., None] * f32(v)).reshape(B, -1),
                      over_lanes(b)], axis=1)                # [B, 3, H d_v]
    # [B, H, d_k] -> [B, nb, d_k, hb]: a block's heads as columns
    cols = lambda x: jnp.swapaxes(f32(x).reshape(B, nb, hb, d_k), 2, 3)
    qk = jnp.concatenate([cols(q), cols(jnp.where(on[..., None], k, 0))], -1)
    kw = {}
    if not interpret and pltpu is not None:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    o, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb, d_v=d_v),
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, 1, d_k, 2 * hb), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, 3, lanes), lambda b, j: (b, 0, j)),
            pl.BlockSpec((1, d_k, lanes), lambda b, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, lanes), lambda b, j: (b, 0, j)),
            pl.BlockSpec((1, d_k, lanes), lambda b, j: (b, 0, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, 1, H * d_v), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={2: 1},
        interpret=interpret,
        name="gated_delta_decode",
        **kw,
    )(qk, rows, state)
    return o.reshape(B, H, d_v), state


def pool_form(state):
    """[..., H, d_k, d_v] -> the pool's [..., d_k, H * d_v]."""
    *lead, H, d_k, d_v = state.shape
    return jnp.swapaxes(state, -3, -2).reshape(*lead, d_k, H * d_v)


def head_form(state, n_heads: int):
    """The pool's [..., d_k, H * d_v] -> [..., H, d_k, d_v]."""
    *lead, d_k, lanes = state.shape
    return jnp.swapaxes(
        state.reshape(*lead, d_k, n_heads, lanes // n_heads), -3, -2)
