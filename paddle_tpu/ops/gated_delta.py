"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464; with beta in
(0, 2) its transition has negative eigenvalues, arXiv:2411.12537).

Per head a state S [d_k, d_v], and per token a query and a key [d_k]
(normalised by the caller), a value [d_v], a log-decay g <= 0 and a write
strength beta:

    S_t = a_t S_{t-1} + beta_t k_t (v_t - (a_t S_{t-1})^T k_t)^T,  a_t = e^{g_t}
    o_t = S_t^T q_t

Three forms of it, which tests/test_olmo_hybrid.py holds equal:

`gated_delta_recurrence`  the definition, a `lax.scan` over tokens.
`gated_delta_step`        ONE token for a batch of sequences: what a decode
                          step runs. Memory-bound: each state is read once
                          and written once. `ops/pallas/gated_delta_decode`
                          is the same update as a kernel over the state
                          pool, in place.
`gated_delta_chunked`     a prompt, `chunk` tokens at a time: inside a chunk
                          everything is matmuls, across chunks a scan carries
                          the state. With G_i = exp(sum_{j<=i} g_j) the
                          cumulative decay inside a chunk (float32, as all of
                          this is) and u_i the value token i really writes,
                              (I + A) U = diag(beta) (V - (G . K) S_0),
                              A_ij = beta_i (G_i / G_j) (k_i . k_j), j < i
                              O = (G . Q) S_0 + (M . Q K^T) U,
                              M_ij = G_i / G_j for j <= i
                              S_C = G_C S_0 + ((G_C / G) . K)^T U.
                          A is strictly lower triangular, so (I + A)^-1 =
                          (I - A)(I + A^2)(I + A^4)... is exact after
                          log2(chunk) factors: matmuls only, nothing solved
                          row by row. A padding token has beta = 0 and g = 0
                          and leaves the state as it was.

CHUNK = 64 tokens: the chunk's [64, 64] matrices fill half an MXU tile and
the product above has six factors; at 128 the inverse's entries, which can
grow with the chunk where keys repeat, cost float32 digits the tests see.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def gated_delta_recurrence(q, k, v, g, beta, state):
    """q, k [T, H, d_k]; v [T, H, d_v]; g, beta [T, H]; state [H, d_k,
    d_v]. Returns (o [T, H, d_v], state after the last token), float32."""
    def step(S, xs):
        o, S = gated_delta_step(S[None], *(x[None] for x in xs))
        return S[0], o[0]

    f32 = lambda x: x.astype(jnp.float32)
    S, o = jax.lax.scan(step, f32(state),
                        tuple(map(f32, (q, k, v, g, beta))))
    return o, S


def gated_delta_step(state, q, k, v, g, beta):
    """One token of B sequences. state [B, H, d_k, d_v]; q, k [B, H, d_k];
    v [B, H, d_v]; g, beta [B, H]. Returns (o [B, H, d_v], new state)."""
    S = state * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k,
                                          precision=_HI))
    S = S + k[..., :, None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", S, q, precision=_HI), S


def _unit_lower_inverse(A):
    """(I + A)^-1 for strictly lower triangular A [..., C, C], C a power of
    two: (I - A)(I + A^2)(I + A^4)... (A is nilpotent)."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=A.dtype)
    inv, X = eye - A, A
    n = 2
    while n < C:
        X = jnp.matmul(X, X, precision=_HI)
        inv = jnp.matmul(inv, eye + X, precision=_HI)
        n *= 2
    return inv


def gated_delta_chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The recurrence over T tokens, `chunk` at a time (T a multiple of the
    chunk, or shorter than one; both powers of two, as the runner's
    prefill buckets are). Shapes as `gated_delta_recurrence`."""
    T, H, dk = q.shape
    C = min(chunk, T)
    if T % C or C & (C - 1):
        raise ValueError(f"{T} tokens do not split into chunks of {C}")
    f32 = lambda x: x.astype(jnp.float32)
    # [n, H, C, *]: a chunk's rows of one head are one matrix
    split = lambda x: jnp.moveaxis(
        f32(x).reshape(T // C, C, H, *x.shape[2:]), 2, 1)
    i = jnp.arange(C)
    lower = i[:, None] > i[None, :]
    lower_eq = i[:, None] >= i[None, :]

    def one(S, xs):
        qc, kc, vc, gc, bc = xs              # [H, C, d], [H, C]
        cum = jnp.cumsum(gc, -1)             # log G_i
        # log(G_i / G_j), only where j <= i (elsewhere it may be large)
        ratio = jnp.exp(jnp.where(lower_eq, cum[:, :, None]
                                  - cum[:, None, :], 0.0))
        G = jnp.exp(cum)[..., None]          # [H, C, 1]
        kk = jnp.einsum("hik,hjk->hij", kc, kc, precision=_HI)
        A = jnp.where(lower, bc[..., None] * ratio * kk, 0.0)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "hck,hkv->hcv", G * kc, S, precision=_HI))
        U = jnp.matmul(_unit_lower_inverse(A), rhs, precision=_HI)
        qk = jnp.where(lower_eq, ratio * jnp.einsum(
            "hik,hjk->hij", qc, kc, precision=_HI), 0.0)
        o = (jnp.einsum("hck,hkv->hcv", G * qc, S, precision=_HI)
             + jnp.matmul(qk, U, precision=_HI))
        last = cum[:, -1]                    # log G_C
        S = (jnp.exp(last)[:, None, None] * S + jnp.einsum(
            "hck,hcv->hkv", jnp.exp(last[:, None] - cum)[..., None] * kc, U,
            precision=_HI))
        return S, o

    S, o = jax.lax.scan(one, f32(state), (
        split(q), split(k), split(v), split(g), split(beta)))
    # [n, H, C, d_v] -> [T, H, d_v]
    return jnp.moveaxis(o, 1, 2).reshape(T, H, -1), S
