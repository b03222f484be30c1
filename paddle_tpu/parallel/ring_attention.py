"""Ring attention: exact attention over sequence shards on a mesh axis.

The reference has NO ring-attention/context-parallel implementation
(SURVEY.md §5.7 — verified absent from the snapshot; its long-context story
is the 'sep' axis + flash kernels). This exceeds it: exact causal attention
for sequences sharded across the 'sp' mesh axis, with K/V blocks rotated
around the ring via lax.ppermute (ICI collective_permute on TPU) and a
flash-style online-softmax accumulator so no rank ever materializes the full
attention matrix. Autodiff through scan+ppermute yields the backward ring
pass automatically.

Layout [batch, seq, heads, head_dim] (the flash-attention convention,
reference nn/functional/flash_attention.py:358), seq sharded over `axis`.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.parallel.pipeline import manual_shard_map


def _ring_attention_local(q, k, v, axis: str, causal: bool, scale):
    """Per-device body. q/k/v: [b, s_local, h, d] local shards."""
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    b, sq, h, d = q.shape
    scale = scale or (1.0 / math.sqrt(d))

    qT = jnp.swapaxes(q, 1, 2).astype(jnp.float32)  # [b,h,sq,d]
    perm = [(j, (j + 1) % n) for j in range(n)]

    q_pos = idx * sq + jnp.arange(sq)  # global positions of local queries

    def step(carry, i):
        k_cur, v_cur, m, l, o = carry
        src = (idx - i) % n  # rank whose block we currently hold
        kT = jnp.swapaxes(k_cur, 1, 2).astype(jnp.float32)
        vT = jnp.swapaxes(v_cur, 1, 2).astype(jnp.float32)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qT, kT) * scale
        if causal:
            k_pos = src * k_cur.shape[1] + jnp.arange(k_cur.shape[1])
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        blk_max = jnp.max(scores, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        # -inf rows (no visible keys yet) must not poison the accumulator
        safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        corr = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m - safe_m))
        corr = jnp.where(jnp.isneginf(m), 0.0, corr)
        p = jnp.exp(scores - safe_m[..., None])
        p = jnp.where(jnp.isneginf(scores), 0.0, p)
        new_l = l * corr + jnp.sum(p, axis=-1)
        new_o = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vT)
        k_nxt = lax.ppermute(k_cur, axis, perm)
        v_nxt = lax.ppermute(v_cur, axis, perm)
        return (k_nxt, v_nxt, new_m, new_l, new_o), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (_, _, m, l, o), _ = lax.scan(step, (k, v, m0, l0, o0), jnp.arange(n))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = True, scale=None):
    """Exact attention with seq sharded over `axis`. Call on jax arrays
    (inside or outside jit); other mesh axes stay GSPMD-auto.

    q/k/v: [batch, seq, heads, head_dim], seq divisible by mesh.shape[axis].
    """
    body = partial(_ring_attention_local, axis=axis, causal=causal,
                   scale=scale)
    spec = P(None, axis, None, None)
    mapped = manual_shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=frozenset({axis}),
    )
    return mapped(q, k, v)


class RingAttention:
    """Layer-ish wrapper for use inside models (no parameters)."""

    def __init__(self, mesh=None, axis="sp", causal=True):
        self.mesh = mesh
        self.axis = axis
        self.causal = causal

    def __call__(self, q, k, v):
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.parallel.mesh import current_mesh

        mesh = self.mesh or current_mesh()
        unwrap = lambda t: t._value if isinstance(t, Tensor) else t
        out = ring_attention(unwrap(q), unwrap(k), unwrap(v), mesh,
                             axis=self.axis, causal=self.causal)
        return Tensor._wrap(out) if isinstance(q, Tensor) else out
