"""Single-token decode through the ragged paged-attention kernel (its
q_len == 1 case) vs the gather+dense oracle.

The PagedGPTGenerator greedy-identical tests (test_parallel_generation)
are the end-to-end oracle; these pin the kernel itself: shuffled block
tables (real indirection), page-boundary positions, per-sequence pos."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.generation import (
    masked_cache_attention, paged_gather,
)
from _helpers import paged_decode_attention
from paddle_tpu.ops.pallas.ragged_paged_attention import ragged_attention_ok

rng = np.random.default_rng(3)


def _pools(b=2, h=4, d=64, bs=64, npg=4):
    nb = b * npg
    kp = jnp.asarray(rng.standard_normal((nb, bs, h, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, bs, h, d)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(nb).reshape(b, npg).astype(np.int32))
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    return q, kp, vp, tbl


@pytest.mark.parametrize("pos", [0, 63, 64, 130, 255])
def test_matches_oracle_at_page_boundaries(pos):
    q, kp, vp, tbl = _pools()
    out = paged_decode_attention(q, kp, vp, tbl, pos, interpret=True)
    ref = masked_cache_attention(
        q[:, None], paged_gather(kp, tbl), paged_gather(vp, tbl), pos
    ).reshape(q.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_per_sequence_positions():
    q, kp, vp, tbl = _pools()
    pos = jnp.asarray([17, 200], jnp.int32)
    out = paged_decode_attention(q, kp, vp, tbl, pos, interpret=True)
    ref = masked_cache_attention(
        q[:, None], paged_gather(kp, tbl), paged_gather(vp, tbl), pos
    ).reshape(q.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_shared_pages_across_sequences():
    """Two sequences pointing at the SAME pages (prefix sharing — the
    serving feature the block-table indirection exists for)."""
    q, kp, vp, tbl = _pools(b=2, npg=4)
    shared = jnp.broadcast_to(tbl[0], tbl.shape)
    out = paged_decode_attention(q, kp, vp, shared, 100, interpret=True)
    ref = masked_cache_attention(
        q[:, None], paged_gather(kp, shared), paged_gather(vp, shared), 100
    ).reshape(q.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_tiling_gate():
    assert ragged_attention_ok(64, 4, 4) and ragged_attention_ok(8, 4, 4)
    assert not ragged_attention_ok(65, 4, 4)


def test_block_mha_routes_to_kernel(monkeypatch):
    """block_multihead_attention must take the kernel path for t=1."""
    import importlib

    import paddle_tpu.models.generation as gen

    # the package re-exports the function under the module's own name
    pa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")

    called = {}
    orig = pa.ragged_paged_attention

    def spy(*a, **kw):
        called["yes"] = True
        kw["interpret"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(pa, "ragged_paged_attention", spy)
    q, kp, vp, tbl = _pools()
    out = gen.block_multihead_attention(q[:, None], kp, vp, tbl, 10)
    assert called.get("yes"), "paged kernel not dispatched for t=1"
    assert out.shape == (2, 1, 4 * 64)


def test_dead_pages_do_not_change_output():
    """Pool-size invariance of the in-kernel page walk: the same
    sequence content in a 4x pool (extra dead pages past pos) gives a
    bit-identical result — the walk stops at the last live page under
    any table width."""
    rng = np.random.default_rng(5)
    b, h, d, bs = 2, 4, 64, 8
    pos = jnp.asarray([9, 21], jnp.int32)
    n_live = 3                            # ceil((21+1)/8)
    kv = rng.standard_normal((b, n_live * bs, h, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((b, h, d)), np.float32)

    def run(n_pages):
        nb = b * n_pages
        kp = np.zeros((nb, bs, h, d), np.float32)
        vp = np.zeros((nb, bs, h, d), np.float32)
        table = np.arange(nb, dtype=np.int32).reshape(b, n_pages)
        for i in range(b):
            for j in range(n_live):
                kp[table[i, j]] = kv[i, j * bs:(j + 1) * bs]
                vp[table[i, j]] = kv[i, j * bs:(j + 1) * bs] * 0.5
        return paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                      jnp.asarray(table), pos,
                                      interpret=True)

    tight = run(n_live)
    huge = run(4 * n_live)                # 9 dead pages per sequence
    np.testing.assert_array_equal(np.asarray(tight), np.asarray(huge))
