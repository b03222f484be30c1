"""A grouped matrix product as Pallas TPU kernels, with its gradient.

`grouped_matmul(x, w, block_group, n_live, block_rows=bm)`: x [R, k] holds
rows sorted by group in a layout whose groups start at a multiple of `bm`
rows (`parallel.moe.expert_layout` builds it); row block i belongs to group
`block_group[i]` and only the first `n_live` blocks hold rows. The result
[R, n] is x's block i times `w[block_group[i]]` ([G, k, n]) for the live
blocks and zeros for the rest: an expert layer's product over the token-
expert pairs sorted by expert, dropless, with no capacity.

Three kernels behind one `custom_vjp` (operands in the dtype they arrive in,
bfloat16 under autocast O1; products and accumulators float32):

  * forward, grid (n / bn, R / bm), row blocks innermost: consecutive blocks
    of one group name the same weight tile, which is then fetched once; a
    dead block's index maps clamp to the last live block (it copies nothing)
    and it writes zeros;
  * dx = dy w^T: the same kernel on the transposed tile (contracting n);
  * dw[g] = x_g^T dy_g, grid (k / bk, n / bn, R / bm): a float32 accumulator
    in VMEM rides a group's blocks and is stored at the group's last one.
    x arrives transposed ([k, R], one XLA transpose), so every matmul in the
    kernels is plain or transposed-right.

A group no row chose has no block: none of the kernels reads its matrices
and its dw is zero (set outside the kernel, which never visits it). One
group may take every row. Interpret mode on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # TPU-specific memory spaces (absent on pure-CPU builds)
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

LANES = 128
# what one grid step may hold of the chip's 16 MiB of scoped VMEM: its
# blocks twice for the pipeline, the accumulator once
VMEM_BUDGET = 12 * 2 ** 20


def _tile(n: int, fits) -> int:
    """The largest multiple of 128 dividing n that `fits`; n itself where
    it has no such divisor (toy widths) or none fits."""
    ok = [t for t in range(LANES, n + 1, LANES) if n % t == 0 and fits(t)]
    return max(ok) if ok else n


def _fits(bm: int, c: int, isz: int, wsz: int, osz: int):
    """Whether a grid step of `_rows_call` with a weight tile of t columns
    fits: its three blocks, twice each for the pipeline."""
    return lambda t: 2 * (bm * c * isz + c * t * wsz
                          + bm * t * osz) <= VMEM_BUDGET


def one_tile(bm: int, c: int, n: int, dtype, out_itemsize: int = None
             ) -> bool:
    """Whether the forward's product of [bm, c] row blocks with [c, n]
    matrices of `dtype` takes a group's matrix as ONE tile: the grid is then
    the row blocks alone, a matrix is fetched once a run of its group's
    blocks, and the pipeline fetches the next block's while this one
    multiplies."""
    isz = jnp.dtype(dtype).itemsize
    return _fits(bm, c, isz, isz, out_itemsize or isz)(n)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _nt(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _live_block(i, n_live):
    """Block i's own index while it is live, the last live block's after."""
    return jnp.maximum(jnp.minimum(i, n_live[0] - 1), 0)


def _rows_kernel(group_ref, n_live_ref, x_ref, w_ref, o_ref, *,
                 transposed: bool):
    i = pl.program_id(1)

    @pl.when(i < n_live_ref[0])
    def _():
        dot = _nt if transposed else _nn
        o_ref[...] = dot(x_ref[...], w_ref[0]).astype(o_ref.dtype)

    @pl.when(i >= n_live_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _rows_call(x, w, block_group, n_live, bm: int, transposed: bool,
               out_dtype, interpret: bool):
    """x [R, c] times w[g] ([c, n], or [n, c] `transposed`) by row block."""
    R, c = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    isz, osz = x.dtype.itemsize, jnp.dtype(out_dtype).itemsize
    bn = _tile(n, _fits(bm, c, isz, w.dtype.itemsize, osz))
    w_block = (1, bn, c) if transposed else (1, c, bn)

    def w_index(j, i, group, n_live):
        g = group[_live_block(i, n_live)]
        return (g, j, 0) if transposed else (g, 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n // bn, R // bm),
        in_specs=[pl.BlockSpec((bm, c), lambda j, i, group, n_live:
                               (_live_block(i, n_live), 0)),
                  pl.BlockSpec(w_block, w_index)],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, *_: (i, j)))
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_rows_kernel, transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((R, n), out_dtype),
        grid_spec=grid_spec, interpret=interpret,
        name="grouped_dx" if transposed else "grouped_fwd", **kw,
    )(block_group, n_live, x, w)


def _group_kernel(group_ref, n_live_ref, xt_ref, dy_ref, o_ref, acc_ref):
    i, n_live = pl.program_id(2), n_live_ref[0]
    last_block = pl.num_programs(2) - 1
    g = group_ref[i]
    first = (i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != g)
    last = (i == n_live - 1) | (group_ref[jnp.minimum(i + 1, last_block)]
                                != g)

    @pl.when(i < n_live)
    def _():
        part = _nn(xt_ref[...], dy_ref[...])

        @pl.when(first)
        def _():
            acc_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _():
            acc_ref[...] += part

        @pl.when(last)
        def _():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _group_call(x, dy, block_group, n_live, bm: int, n_groups: int,
                out_dtype, interpret: bool):
    """dw [G, k, n]: x_g^T dy_g over the live blocks of each group that has
    one; the rest is zero."""
    R, k = x.shape
    n = dy.shape[1]
    isz, osz = x.dtype.itemsize, jnp.dtype(out_dtype).itemsize
    bn = _tile(n, lambda t: t <= 1024)
    bk = _tile(k, lambda t: (2 * (bm * (t + bn) * isz + t * bn * osz)
                             + t * bn * 4) <= VMEM_BUDGET and t <= 1024)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(k // bk, n // bn, R // bm),
        in_specs=[pl.BlockSpec((bk, bm), lambda a, j, i, group, n_live:
                               (a, _live_block(i, n_live))),
                  pl.BlockSpec((bm, bn), lambda a, j, i, group, n_live:
                               (_live_block(i, n_live), j))],
        out_specs=pl.BlockSpec((1, bk, bn), lambda a, j, i, group, n_live:
                               (group[_live_block(i, n_live)], a, j)),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)])
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"))
    dw = pl.pallas_call(
        _group_kernel,
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), out_dtype),
        grid_spec=grid_spec, interpret=interpret, name="grouped_dw", **kw,
    )(block_group, n_live, x.T, dy)
    # a group with no live block was never visited: what its slice of the
    # output holds is whatever the memory held
    live = jnp.arange(block_group.shape[0]) < n_live[0]
    visited = jnp.zeros((n_groups,), bool).at[block_group].max(live)
    return jnp.where(visited[:, None, None], dw, jnp.zeros_like(dw))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _grouped(x, w, block_group, n_live, bm, out_dtype, interpret):
    return _rows_call(x, w, block_group, n_live, bm, False, out_dtype,
                      interpret)


def _grouped_fwd(x, w, block_group, n_live, bm, out_dtype, interpret):
    return (_grouped(x, w, block_group, n_live, bm, out_dtype, interpret),
            (x, w, block_group, n_live))


def _grouped_bwd(bm, out_dtype, interpret, res, dy):
    x, w, block_group, n_live = res
    dy = dy.astype(x.dtype)
    dw = _group_call(x, dy, block_group, n_live, bm, w.shape[0], w.dtype,
                     interpret)
    dx = _rows_call(dy, w, block_group, n_live, bm, True, x.dtype, interpret)
    return dx, dw, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x, w, block_group, n_live, *, block_rows: int,
                   out_dtype=None, interpret: bool | None = None):
    """x [R, k] (R a multiple of `block_rows`) times w [G, k, n] by row
    block: block i by `w[block_group[i]]` for i < n_live, zeros after.
    `block_group` int32 [R / block_rows], non-decreasing over the live
    blocks; `n_live` an int32 scalar. Differentiable in x and w."""
    R, k = x.shape
    if R % block_rows or w.ndim != 3 or w.shape[1] != k:
        raise ValueError(f"grouped_matmul: x {x.shape} in blocks of "
                         f"{block_rows} rows against w {w.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _grouped(x, w.astype(x.dtype), block_group.astype(jnp.int32),
                    jnp.asarray(n_live, jnp.int32).reshape(1), block_rows,
                    jnp.dtype(out_dtype or x.dtype), bool(interpret))
