"""The ZAYA1 block (models/zaya.py) against its plain reference
(bench/reference_zaya.py) on seeded weights at toy width: 3 layers, 4 query
heads on 2 key/value heads of 16, 2 of 4 experts held. Logits, loss and every
leaf's gradient in float32; three `TrainStep` steps under autocast O1 against
the reference's steps; the share test of the model-configs guide; the pieces
(causality of the convolutions and of the value shift, the selection bias,
the balance term, the counters) one by one."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))

import paddle_tpu as paddle  # noqa: E402
import reference_zaya as ref  # noqa: E402
from paddle_tpu import profiler  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.jit.functionalize import functionalize  # noqa: E402
from paddle_tpu.models import zaya  # noqa: E402
from paddle_tpu.models.zaya import (  # noqa: E402
    ZayaConfig, ZayaForCausalLM, zaya_loss_fn)

CFG = dict(vocab_size=96, hidden_size=64, num_hidden_layers=3,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
           rope_theta=5e6, num_experts=4, num_experts_per_tok=1,
           moe_intermediate_size=48, router_hidden_size=32,
           rms_norm_eps=1e-5, experts_held=2, first_expert=1)
OPT = dict(learning_rate=3e-4, beta1=0.9, beta2=0.999, epsilon=1e-8,
           weight_decay=0.01)
BLOCK_LEAVES = sorted(k[7:] for k in ref._shapes(CFG) if k.startswith("layers."))


def batch(i, b=2, s=32):
    t = jax.random.randint(jax.random.key(100 + i), (b, s + 1), 0,
                           CFG["vocab_size"])
    return t[:, :-1], t[:, 1:]


def build(cfg, weights):
    model = ZayaForCausalLM(ZayaConfig(**cfg))
    missing, unexpected = model.set_state_dict(
        {k: Tensor(v) for k, v in ref.program_names(weights).items()})
    assert not missing and not unexpected
    return model


@pytest.fixture(scope="module", autouse=True)
def toy_calibration():
    """The balance rests on 8 x 32 tokens here, not on the cell's 2 x 8192."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ref, "CALIBRATION_BATCH", (8, 32))
        yield


@pytest.fixture(scope="module")
def weights():
    return jax.jit(lambda k: ref.init_weights(CFG, k))(ref.seed_key(5))


@pytest.fixture(scope="module")
def both_gradients(weights):
    """(program's loss, its gradients, the reference's loss, its gradients
    under the program's names), float32, no autocast."""
    tokens, labels = batch(0)
    func = functionalize(build(CFG, weights))

    def program_loss(p):
        out, _ = func.apply(p, func.buffer_values(), None, True, tokens)
        return zaya_loss_fn(tuple(Tensor._wrap(v) for v in out),
                            Tensor._wrap(labels))._value

    lp, gp = jax.value_and_grad(program_loss)(func.param_values())
    lr, gr = jax.value_and_grad(
        lambda w: ref.loss_fn(CFG, w, tokens, labels))(weights)
    return lp, gp, lr, ref.program_names(gr)


def test_logits_match_the_reference(weights):
    tokens, _ = batch(0)
    want, balance = ref.forward(CFG, weights, tokens)
    logits, term = build(CFG, weights)(Tensor(tokens))
    # float32 on both sides; the orders of summation differ
    np.testing.assert_allclose(logits._value, want, atol=2e-6)
    assert float(term._value) == 0.0 == float(balance)


def test_loss_matches_the_reference(both_gradients):
    lp, _, lr, _ = both_gradients
    assert abs(float(lp) - float(lr)) < 1e-5


@pytest.mark.parametrize("leaf", ["embed.weight", "final_norm.weight"]
                         + ["layers.*." + k for k in BLOCK_LEAVES])
def test_gradient_of_every_leaf(both_gradients, leaf):
    """Each leaf's gradient against jax.grad of the reference: float32 on
    both sides, so what is left is the order of summation, 1e-5 of the
    leaf's largest entry (1e-9 absolute for a leaf whose gradient is tiny)."""
    _, gp, _, gr = both_gradients
    names = ([leaf] if "*" not in leaf else
             [leaf.replace("*", str(i)) for i in range(3)])
    for name in names:
        scale = float(jnp.max(jnp.abs(gr[name])))
        np.testing.assert_allclose(gp[name], gr[name], rtol=0,
                                   atol=1e-5 * scale + 1e-9, err_msg=name)
        # no leaf is idle but the first layer's gamma (no layer before it)
        assert scale > 0 or name == "layers.0.router.gamma", name


def test_train_step_o1_follows_the_reference(weights):
    """Three steps of AdamW under autocast O1 against the float32
    reference's: losses within a bfloat16 ulp of the logits' scale, every
    leaf's change within a fifth of its norm or of the median leaf's
    (bfloat16 products; a top-1 choice may flip for a token or two)."""
    batches = tuple(batch(i) for i in range(3))
    losses, _, delta = jax.jit(lambda w, b: ref.train_readings(
        CFG, w, b, OPT, 2))(weights, batches)
    model = build(CFG, weights)
    opt = paddle.optimizer.AdamW(parameters=model.parameters(), **OPT)
    step = paddle.jit.TrainStep(model, zaya_loss_fn, opt, amp_level="O1")
    got = [float(step(Tensor(a), Tensor(b))._value) for a, b in batches]
    np.testing.assert_allclose(got, losses, atol=0.04)
    named = ref.program_names(weights)
    # the bench's own measure: the norms' gap over the larger of the leaf's
    # norm and the median leaf's (a leaf of two numbers moves by the sign of
    # a tiny gradient)
    median = float(np.median([float(v) for v in delta.values()]))
    for k, want in delta.items():
        change = float(jnp.linalg.norm((step.params[k] - named[k]).ravel()))
        assert abs(change - float(want)) <= 0.2 * max(float(want), median), k
    # the step's counters: an output of its program, read when asked
    c = profiler.step_counters()
    assert c["moe_train_tokens"] == 3 * 3 * 64
    assert 0 < c["moe_train_pairs"] <= c["moe_train_rows_padded"]
    assert c["moe_train_load_mean"] == 3 * 3 * 64 / 4
    assert c["moe_train_load_max"] >= c["moe_train_load_mean"]
    assert c["moe_bias_abs_max"] > 0
    # each step's own pairs, the newest last, zeros before the first
    by_step = c["moe_train_pairs_by_step"]
    assert len(by_step) == zaya.PAIRS_RING and by_step[-4] == 0
    assert all(by_step[-3:]) and sum(by_step) == c["moe_train_pairs"]
    assert not set(dict(model.named_buffers())) & set(model.state_dict())


def test_the_two_halves_add_up_to_the_uncut_layer():
    """Guide section 4: the expert parts that the two shares give (experts
    0-1 and 2-3), with what every chip computes alike (attention, router,
    the stream's own term) counted once, are the uncut reference's layer."""
    full = dict(CFG, num_hidden_layers=1, experts_held=4, first_expert=0)
    w = ref._draw(full, ref.seed_key(9))
    tokens, _ = batch(1)
    mm = ref._mm_for("float32")
    p = {k[7:]: v[0] for k, v in w.items() if k.startswith("layers.")}
    zero_r = jnp.zeros((*tokens.shape, full["router_hidden_size"]))
    x1, u, scores, _ = ref._until_route(full, mm, w["embed.weight"][tokens],
                                        zero_r, p)
    want, _ = ref._experts_and_merge(full, mm, x1, u, scores, p)
    alike = ref._merge(x1, jnp.zeros_like(x1), p, "moe_res")
    parts = []
    for first in (0, 2):
        half = dict(full, experts_held=2, first_expert=first)
        cut = {k: (v[:, first:first + 2] if ".experts." in k else v)
               for k, v in w.items()}
        params = {k: t._value for k, t in build(half, cut).named_parameters()}
        parts.append(zaya.hidden(ZayaConfig(**half), params, tokens)[0]
                     - alike)
    np.testing.assert_allclose(alike + parts[0] + parts[1], want, atol=2e-6)
    assert float(jnp.max(jnp.abs(parts[0]))) > 0 < float(
        jnp.max(jnp.abs(parts[1])))


def _attn_params(weights, layer=0):
    pre = f"layers.{layer}.attn."
    return {k: v for k, v in ref.program_names(weights).items()
            if k.startswith(pre)}, pre


def test_convolutions_and_value_shift_are_causal(weights):
    """A changed token changes nothing before it, through both
    convolutions and the shift; position 0's shifted value head is zero."""
    cfg = ZayaConfig(**CFG)
    params, pre = _attn_params(weights)
    u = jax.random.normal(jax.random.key(2), (1, 12, CFG["hidden_size"]))
    q, k, v = zaya.cca_qkv(cfg, params, pre, u)
    q2, k2, v2 = zaya.cca_qkv(cfg, params, pre, u.at[0, 7].add(1.0))
    for a, b in ((q, q2), (k, k2), (v, v2)):
        np.testing.assert_array_equal(a[:, :7], b[:, :7])
        assert float(jnp.max(jnp.abs(a[:, 7] - b[:, 7]))) > 0
    # two taps and a shift of one: position 8 sees position 7, 9 does too
    # through the second convolution's tap on the first's, 10 nothing
    assert float(jnp.max(jnp.abs(q[:, 9] - q2[:, 9]))) > 0
    np.testing.assert_array_equal(q[:, 10:], q2[:, 10:])
    np.testing.assert_array_equal(v[:, 9:], v2[:, 9:])
    assert float(jnp.max(jnp.abs(v[:, 8, 1] - v2[:, 8, 1]))) > 0
    np.testing.assert_array_equal(v[:, 0, 1], jnp.zeros_like(v[:, 0, 1]))
    assert float(jnp.max(jnp.abs(v[:, 0, 0]))) > 0


def test_bias_moves_the_selection_and_never_a_weight():
    from paddle_tpu.parallel.moe import softmax_topk_route

    logits = jax.random.normal(jax.random.key(3), (64, 4))
    idx0, w0, s0 = softmax_topk_route(logits, jnp.zeros(4), 1)
    bias = jnp.array([0.0, 0.0, 5.0, 0.0])
    idx1, w1, s1 = softmax_topk_route(logits, bias, 1)
    assert bool(jnp.all(idx1 == 2)) and not bool(jnp.all(idx0 == 2))
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_array_equal(w1[:, 0], s0[:, 2])
    # and no gradient reaches the bias through the route
    g = jax.grad(lambda b: jnp.sum(softmax_topk_route(logits, b, 1)[1]))(bias)
    np.testing.assert_array_equal(g, jnp.zeros(4))


def test_balance_terms_gradient_is_the_load_error():
    idx = jnp.array([0, 0, 0, 1, 2, 0, 3, 0])[:, None]
    bias = jnp.array([0.3, -0.1, 0.0, 0.2])
    (term, load), g = jax.value_and_grad(
        lambda b: zaya.balance_term(idx, b, 4), has_aux=True)(bias)
    assert float(term) == 0.0
    np.testing.assert_allclose(load, [5 / 8, 1 / 8, 1 / 8, 1 / 8])
    np.testing.assert_allclose(g, load - 0.25)


def test_serving_form_names_the_training_form_under_grad():
    from paddle_tpu.parallel.moe import held_experts_ffn, softmax_topk_route

    x = jax.random.normal(jax.random.key(4), (16, 8))
    idx, w, _ = softmax_topk_route(jax.random.normal(jax.random.key(5),
                                                     (16, 4)), jnp.zeros(4), 1)
    mats = [jax.random.normal(jax.random.key(6 + i), s)
            for i, s in enumerate([(2, 8, 12), (2, 8, 12), (2, 12, 8)])]
    y, pairs, _ = held_experts_ffn(x, idx, w, *mats, 1)     # serving: fine
    assert y.shape == x.shape and int(pairs) >= 0
    with pytest.raises(TypeError, match="held_experts_ffn_train"):
        jax.grad(lambda x: jnp.sum(held_experts_ffn(x, idx, w, *mats, 1)[0]))(x)


def test_autocast_lists_name_what_is_new():
    from paddle_tpu import amp
    from paddle_tpu.amp.state import current_cast_dtype

    with amp.auto_cast(level="O1"):
        assert current_cast_dtype("grouped_matmul") == jnp.bfloat16
        assert current_cast_dtype("cca_conv") == jnp.bfloat16
        assert current_cast_dtype("softmax") == np.float32
        assert current_cast_dtype("rms_norm") == np.float32
    assert current_cast_dtype("grouped_matmul") is None


def test_a_buffer_no_state_dict_keeps_is_not_reported_missing(weights):
    model = build(CFG, weights)          # build() asserts nothing is missing
    assert "moe_counts" in dict(model.named_buffers())
    missing, _ = model.set_state_dict({})
    assert "moe_counts" not in missing and "embed.weight" in missing
