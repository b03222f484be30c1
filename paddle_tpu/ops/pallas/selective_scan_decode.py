"""The selective scan's single-token update over a POOL of states, in place
(ops/selective_scan.py has the scan; this is `selective_scan_step` as a
kernel).

A layer's states live in one array `[slots, d_state, d_inner]` float32, the
channels on lanes. A decode step updates rows 0..B-1 (a request's slot is
its row of the batch); the array is aliased to the output, rows the grid
does not visit (the scratch slot) are not touched, and each visited state is
read once and written once: the update is bound by those bytes.

Grid (B,): a step holds one sequence's whole state (16 x 5120 float32 =
320 KiB at the published widths) and does elementwise work and one
reduction over sublanes:

    h <- exp(dt * A) * h + (dt * x) * B;   y = sum_n h * C

A row that is not live (a dead slot of the batch, a frozen row of a horizon)
is given dt = 0 by the wrapper: it writes back what it read.
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


def _kernel(rows_ref, bc_ref, a_ref, s_ref, y_ref, s_out_ref):
    """rows [1, 2, c] (dt, dt * x); bc [1, n, 2] (B and C as columns); a
    [n, c]; s, s_out [1, n, c]; y [1, 1, c]."""
    shape = s_ref.shape[1:]
    dt, wrote = rows_ref[0, 0:1, :], rows_ref[0, 1:2, :]
    b = jnp.broadcast_to(bc_ref[0, :, 0:1], shape)
    c = jnp.broadcast_to(bc_ref[0, :, 1:2], shape)
    h = jnp.exp(dt * a_ref[...]) * s_ref[0] + wrote * b
    s_out_ref[0] = h
    y_ref[0] = jnp.sum(h * c, axis=0, keepdims=True)


def selective_scan_decode(state, x, dt, A, B, C, live=None,
                          interpret: bool | None = None):
    """One token of b sequences against the pool. state [slots, n, c]
    float32, slots >= b; x, dt [b, c]; A [n, c]; B, C [b, n]; live [b] bool
    (None: every row). Returns (y [b, c] float32, the pool with rows
    0..b-1 advanced where live)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _call(state, x, dt, A, B, C,
                 jnp.ones(x.shape[:1], bool) if live is None else live,
                 interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(state, x, dt, A, B, C, live, *, interpret: bool):
    b, c = x.shape
    n = A.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    dt = jnp.where(live[:, None], f32(dt), 0.0)
    rows = jnp.stack([dt, dt * f32(x)], axis=1)               # [b, 2, c]
    bc = jnp.stack([f32(B), f32(C)], axis=-1)                 # [b, n, 2]
    kw = {}
    if not interpret and pltpu is not None:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    y, state = pl.pallas_call(
        _kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 2, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, 2), lambda i: (i, 0, 0)),
            pl.BlockSpec((n, c), lambda i: (0, 0)),
            pl.BlockSpec((1, n, c), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, c), lambda i: (i, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={3: 1},
        interpret=interpret,
        name="selective_scan_decode",
        **kw,
    )(rows, bc, f32(A), state)
    return y[:, 0], state
