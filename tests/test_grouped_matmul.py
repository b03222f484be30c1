"""ops/pallas/grouped_matmul.py in interpret mode against a per-group einsum:
forward and both gradients, with an empty group and with one group taking
every row; and the held-experts layer built on it against the dense form."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
from paddle_tpu.parallel.moe import (
    expert_layout, held_experts_ffn_train, softmax_topk_route)

G, K, N, BM = 4, 32, 48, 8
CASES = {"spread": [3, 1, 2, 0, 1, 3, 2, 1, 0, 2, 3, 3] * 4,
         "an empty group": [0, 3, 3, 1, 0, 1, 3, 0] * 5,
         "one group takes every row": [2] * 40}


def layout(experts):
    idx = jnp.asarray(experts, jnp.int32)[:, None]
    counts, row_pair, p_end, block_group = expert_layout(idx, 0, G, BM)
    return idx, counts, row_pair, p_end, block_group


def per_group(x, w, row_group):
    """Row r times w[row_group[r]]; zeros where row_group is G."""
    out = jnp.einsum("rk,rkn->rn", x, jnp.concatenate(
        [w, jnp.zeros_like(w[:1])])[row_group])
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_forward_and_both_gradients(case):
    idx, counts, row_pair, p_end, block_group = layout(CASES[case])
    T = idx.shape[0]
    R = row_pair.shape[0]
    real = row_pair < T
    row_group = jnp.where(real, idx[jnp.minimum(row_pair, T - 1), 0], G)
    kx, kw, kc = jax.random.split(jax.random.key(0), 3)
    x = jnp.where(real[:, None], jax.random.normal(kx, (R, K)), 0.0)
    w = jax.random.normal(kw, (G, K, N))
    cot = jax.random.normal(kc, (R, N))

    def kernel(x, w):
        return grouped_matmul(x, w, block_group, p_end[-1] // BM,
                              block_rows=BM)

    got, vjp = jax.vjp(kernel, x, w)
    want, ref_vjp = jax.vjp(lambda x, w: per_group(x, w, row_group), x, w)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # rows past the live blocks are zero, whatever x holds there
    assert not np.any(np.asarray(got)[int(p_end[-1]):])
    (dx, dw), (rdx, rdw) = vjp(cot), ref_vjp(cot)
    # a padding row of a live block takes its block's expert: the layer
    # drops what the kernel hands it there; past the live blocks it is zero
    np.testing.assert_allclose(jnp.where(real[:, None], dx, 0), rdx,
                               atol=1e-5)
    assert not np.any(np.asarray(dx)[int(p_end[-1]):])
    np.testing.assert_allclose(dw, rdw, atol=1e-4)
    for g in range(G):                 # an empty group's gradient is zero
        if int(counts[g]) == 0:
            assert not np.any(np.asarray(dw[g]))


def test_bfloat16_operands_float32_products():
    idx, _, row_pair, p_end, block_group = layout(CASES["spread"])
    R = row_pair.shape[0]
    x = jax.random.normal(jax.random.key(1), (R, K)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.key(2), (G, K, N)).astype(jnp.bfloat16)
    out = grouped_matmul(x, w, block_group, p_end[-1] // BM, block_rows=BM,
                         out_dtype=jnp.float32)
    assert out.dtype == jnp.float32
    b0 = int(block_group[0])
    np.testing.assert_allclose(
        out[:BM], x[:BM].astype(jnp.float32) @ w[b0].astype(jnp.float32),
        rtol=1e-5, atol=1e-5)


def test_rows_must_be_whole_blocks():
    with pytest.raises(ValueError, match="blocks of"):
        grouped_matmul(jnp.zeros((10, K)), jnp.zeros((G, K, N)),
                       jnp.zeros((2,), jnp.int32), 1, block_rows=BM)


@pytest.mark.parametrize("first", [0, 1, 2])
def test_held_experts_layer_against_the_dense_form(first):
    """Top-1 over 4 experts, 2 held from `first`: output and the gradient of
    every operand against every held expert run on every token and masked;
    nothing dropped, a token whose expert is absent gets zeros."""
    T, d, f, E, held = 64, 32, 48, 4, 2
    ks = jax.random.split(jax.random.key(7), 5)
    x = jax.random.normal(ks[0], (T, d))
    logits = jax.random.normal(ks[1], (T, E))
    mats = [0.2 * jax.random.normal(k, s) for k, s in zip(
        ks[2:], [(held, d, f), (held, d, f), (held, f, d)])]

    def dense(x, logits, wg, wu, wd):
        s = jax.nn.softmax(logits)
        e, w = jnp.argmax(s, -1), jnp.max(s, -1)
        y = 0
        for g in range(held):
            o = (jax.nn.silu(x @ wg[g]) * (x @ wu[g])) @ wd[g]
            y = y + jnp.where((e == g + first)[:, None], o * w[:, None], 0)
        return y

    def layer(x, logits, wg, wu, wd):
        idx, w, _ = softmax_topk_route(logits, jnp.zeros(E), 1)
        return held_experts_ffn_train(x, idx, w, wg, wu, wd, first,
                                      block_rows=8)

    y, pairs, rows = layer(x, logits, *mats)
    np.testing.assert_allclose(y, dense(x, logits, *mats), atol=1e-5)
    e = jnp.argmax(logits, -1)
    assert int(pairs) == int(jnp.sum((e >= first) & (e < first + held)))
    assert int(rows) % 8 == 0 and int(pairs) <= int(rows) < int(pairs) + 16
    ga = jax.grad(lambda *a: jnp.sum(jnp.sin(layer(*a)[0])),
                  argnums=range(5))(x, logits, *mats)
    gb = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a))),
                  argnums=range(5))(x, logits, *mats)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(a, b, atol=2e-5)
