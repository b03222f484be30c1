"""The selective scan of a Mamba-1 layer (arXiv:2312.00752).

Per channel c of `d_inner` a state of `d_state` numbers, and per token an
input x_t[c], a step dt_t[c] > 0, and two vectors B_t, C_t [d_state] that
all channels share:

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[n, c] C_t[n]

with A [d_state, d_inner] negative. The skip `D * x` and the gate are the
caller's. Everything here is float32, and the state lies `[d_state,
d_inner]`: the channels along the chip's lanes, so that a state is whole
tiles (16 x 5120 at the published widths) and costs its own bytes.

Three forms of it, which tests/test_phi4flash.py holds equal:

`selective_scan_recurrence`  the definition: a `lax.scan`, one token a step.
`selective_scan_step`        ONE token for a batch of sequences: what a
                             decode step runs, each state read once and
                             written once (`ops/pallas/selective_scan_decode`
                             is the same update as a kernel over the state
                             pool, in place).
`selective_scan_chunked`     a prompt, BLOCK tokens at a time: the
                             discretisation (the exponentials and the outer
                             products of a block) is made for the whole block
                             at once, the recurrence inside the block is
                             unrolled, and a scan carries the state across
                             blocks. A token whose dt is 0 leaves the state
                             as it was (padding rows are given that).

The decay differs per (channel, state) pair, so there is no form of it in
matmuls as the gated delta rule has: a block is elementwise work.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 16


def selective_scan_step(state, x, dt, A, B, C):
    """state [b, n, c]; x, dt [b, c]; A [n, c]; B, C [b, n]. Returns (y
    [b, c], the states after the token), float32."""
    f32 = lambda a: a.astype(jnp.float32)
    x, dt = f32(x)[:, None, :], f32(dt)[:, None, :]
    state = jnp.exp(dt * f32(A)) * f32(state) + (dt * x) * f32(B)[:, :, None]
    return jnp.sum(state * f32(C)[:, :, None], axis=1), state


def selective_scan_recurrence(x, dt, A, B, C, state):
    """x, dt [T, c]; A [n, c]; B, C [T, n]; state [n, c]. Returns (y [T,
    c], the state after the last token), float32."""
    def step(h, xs):
        y, h = selective_scan_step(h[None], *(a[None] for a in xs[:2]), A,
                                   *(a[None] for a in xs[2:]))
        return h[0], y[0]

    f32 = lambda a: a.astype(jnp.float32)
    h, y = jax.lax.scan(step, f32(state), tuple(map(f32, (x, dt, B, C))))
    return y, h


def selective_scan_chunked(x, dt, A, B, C, state, block: int = BLOCK):
    """The same, `block` tokens a scan step (T is padded to a multiple of
    it with dt = 0 rows, which change nothing and are cut off)."""
    f32 = lambda a: a.astype(jnp.float32)
    T = x.shape[0]
    pad = -T % block
    x, dt, B, C = (jnp.pad(f32(a), ((0, pad), (0, 0))) for a in (x, dt, B, C))
    A = f32(A)
    blocks = lambda a: a.reshape(-1, block, a.shape[-1])

    def step(h, xs):
        xb, dtb, Bb, Cb = xs
        decay = jnp.exp(dtb[:, None, :] * A)                  # [L, n, c]
        wrote = (dtb * xb)[:, None, :] * Bb[:, :, None]
        ys = []
        for i in range(block):
            h = decay[i] * h + wrote[i]
            ys.append(jnp.sum(h * Cb[i][:, None], axis=0))
        return h, jnp.stack(ys)

    h, y = jax.lax.scan(step, f32(state), tuple(map(blocks, (x, dt, B, C))))
    return y.reshape(-1, y.shape[-1])[:T], h
