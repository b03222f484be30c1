"""TPU hardware tier: the `tpu`-marked tests, run on the chip.

One command:  PADDLE_TPU_TESTS=1 python -m pytest -m tpu tests/test_tpu_hw.py -v

Everything here runs compiled on the real chip: the Pallas kernels through
Mosaic (interpret mode on the CPU cannot show what the compiler refuses), a
donated-buffer TrainStep (donation is honored on TPU, a no-op on CPU, so
the CPU suite can't catch aliasing bugs), and a profiler trace. chip_smoke.py
is the quicker proof; this tier goes wider. Keep each test small.
"""

import json

import numpy as np
import pytest

from _helpers import paged_decode_attention

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def tpu_backend():
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip(f"not on tpu (backend={jax.default_backend()})")
    from paddle_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    return jax.default_backend()


def test_flash_attention_fwd_bwd_on_hw(tpu_backend):
    """Pallas FA-2 kernels (Mosaic-compiled, interpret=False) vs XLA ref —
    the validator chip_smoke.py's kernel phase shares."""
    from paddle_tpu.ops.pallas.flash_attention import \
        validate_against_reference

    res = validate_against_reference(interpret=False)
    assert res["pass"], res


def test_trainstep_donation_smoke(tpu_backend):
    """Donated-buffer step + sync-then-keep-training on real HBM."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn

    paddle.seed(0)
    gpt = GPT(GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64))
    opt = paddle.optimizer.AdamW(parameters=gpt.parameters(),
                                 learning_rate=1e-3)
    step = paddle.jit.TrainStep(gpt, gpt_loss_fn, opt)
    tok = paddle.to_tensor(np.random.default_rng(0).integers(0, 256, (2, 64)))
    l1 = float(step(tok, tok))
    step.sync()  # must hand back copies, not donated aliases
    sd = {k: np.asarray(v._value) for k, v in gpt.state_dict().items()}
    l2 = float(step(tok, tok))  # donates again; state_dict stays readable
    assert np.isfinite(l1) and np.isfinite(l2)
    for k, v in sd.items():
        assert np.isfinite(v).all()


def test_eager_optimizer_detach_alias_on_hw(tpu_backend):
    """Param buffers must survive opt.step() for detached views (TPU-only
    failure mode: donation is a no-op on CPU)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    model = nn.Linear(8, 4)
    view = model.weight.detach()
    before = np.asarray(view._value).copy()
    opt = paddle.optimizer.Adam(learning_rate=0.1,
                                parameters=model.parameters())
    x = paddle.to_tensor(np.ones((2, 8), "float32"))
    model(x).sum().backward()
    opt.step()
    np.testing.assert_allclose(np.asarray(view._value), before)


def test_profiler_device_trace(tpu_backend, tmp_path):
    import paddle_tpu as paddle
    from paddle_tpu import profiler

    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                             on_trace_ready=None,
                             trace_dir=str(tmp_path / "xplane"))
    prof.start()
    x = paddle.to_tensor(np.ones((256, 256), "float32"))
    (x @ x).numpy()
    prof.stop()
    out = tmp_path / "trace.json"
    prof.export_chrome_tracing(str(out))
    data = json.loads(out.read_text())
    assert "traceEvents" in data


def test_masked_flash_attention_on_hw(tpu_backend):
    """Round-4 kernels on real Mosaic: kv-bias padding mask + segment-id
    varlen parity against the XLA path (interpret=False)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import (
        NEG_INF, _reference, flash_attention,
    )

    rng = np.random.default_rng(5)
    b, s, h, d = 2, 256, 4, 64
    q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
               for _ in range(3))
    valid = jnp.arange(s) < 192
    mask = jnp.where(valid, 0.0, NEG_INF)[None, None, None, :]
    mask = jnp.broadcast_to(mask, (b, 1, 1, s)).astype(jnp.float32)
    out = flash_attention(q, k, v, causal=False, mask=mask,
                          interpret=False)
    ref = _reference(q, k, v, False, 1 / np.sqrt(d),
                     kbias=jnp.where(valid, 0.0, NEG_INF)[None, :]
                     .repeat(b, 0).astype(jnp.float32))
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-2
    segs = jnp.broadcast_to((jnp.arange(s) * 4) // s, (b, s)
                            ).astype(jnp.int32)
    out = flash_attention(q, k, v, causal=True, segment_ids=segs,
                          interpret=False)
    ref = _reference(q, k, v, True, 1 / np.sqrt(d), qseg=segs, kseg=segs)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-2


def test_paged_decode_kernel_on_hw(tpu_backend):
    """Scalar-prefetch paged decode (the ragged kernel's q_len == 1 case)
    vs the gather oracle on real HBM."""
    import jax.numpy as jnp

    from paddle_tpu.models.generation import (
        masked_cache_attention, paged_gather,
    )

    rng = np.random.default_rng(6)
    b, h, d, bs, npg = 2, 4, 64, 64, 4
    nb = b * npg
    kp = jnp.asarray(rng.standard_normal((nb, bs, h, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, bs, h, d)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(nb).reshape(b, npg).astype(np.int32))
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    pos = jnp.asarray([100, 250], jnp.int32)
    out = paged_decode_attention(q, kp, vp, tbl, pos, interpret=False)
    ref = masked_cache_attention(q[:, None], paged_gather(kp, tbl),
                                 paged_gather(vp, tbl), pos
                                 ).reshape(q.shape)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-2


def test_int8_matmul_mxu_probe(tpu_backend):
    """Does int8 dot_general run natively (int32 accumulation) rather
    than silently upcasting? Checks the compiled HLO for a convert-to-f32
    on the operands and the result dtype (VERDICT r2/r3 Weak #5)."""
    import jax
    import jax.numpy as jnp

    a = jnp.ones((256, 256), jnp.int8)
    b = jnp.ones((256, 256), jnp.int8)

    @jax.jit
    def mm(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    out = mm(a, b)
    assert out.dtype == jnp.int32 and int(out[0, 0]) == 256
    txt = mm.lower(a, b).compile().as_text()
    # record the finding either way; fail only if the result is wrong
    upcast = "convert" in txt and "f32" in txt
    print(f"int8 matmul compiled; f32-convert present in HLO: {upcast}")


def test_gradscaler_found_inf_on_hw(tpu_backend):
    """AMP GradScaler skips the update and shrinks the scale on inf."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    model = nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    scaler = paddle.amp.GradScaler(init_loss_scaling=2.0 ** 15)
    before = np.asarray(model.weight._value).copy()
    x = paddle.to_tensor(np.full((2, 8), 1e38, "float32"))
    loss = (model(x) * 1e38).sum()
    scaled = scaler.scale(loss)
    scaled.backward()
    scaler.step(opt)
    scaler.update()
    after = np.asarray(model.weight._value)
    np.testing.assert_allclose(after, before)  # inf grads -> skipped step
    assert float(scaler._scale._value if hasattr(scaler._scale, "_value")
                 else scaler._scale) < 2.0 ** 15


def test_donation_chain_train_loop(tpu_backend):
    """A chain of donated TrainStep calls: per-step time must not grow
    (donation means no buffer churn) and the loss stays finite."""
    import time

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn

    paddle.seed(0)
    gpt = GPT(GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64))
    opt = paddle.optimizer.AdamW(parameters=gpt.parameters(),
                                 learning_rate=1e-3)
    step = paddle.jit.TrainStep(gpt, gpt_loss_fn, opt)
    tok = paddle.to_tensor(
        np.random.default_rng(0).integers(0, 256, (2, 64)))
    float(step(tok, tok))  # compile
    t0 = time.time()
    losses = [float(step(tok, tok)) for _ in range(10)]
    dt = time.time() - t0
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    print(f"10 donated steps in {dt * 1000:.1f} ms")


def test_one_chip_pipeline_schedule(tpu_backend):
    """pp=1 mesh on the single chip: the pipeline scan machinery (incl.
    zbh1's lax.switch tables) compiles and runs on real hardware."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from paddle_tpu.models.gpt import GPTConfig, build_pipeline_train_step

    devs = np.asarray(jax.devices()[:1]).reshape(1, 1, 1)
    mesh = Mesh(devs, ("dp", "pp", "tp"))
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=16)
    for schedule in ("1f1b", "zbh1"):
        step, state = build_pipeline_train_step(cfg, mesh, num_micro=2,
                                                schedule=schedule)
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, 128, (2, 2, 16)))
        state, loss = step(state, toks, toks)
        assert np.isfinite(float(loss)), schedule


def test_bf16_matmul_throughput_probe(tpu_backend):
    """One large bf16 matmul, timed up to block_until_ready — prints
    achieved TFLOP/s as a smoke reading (no hard floor)."""
    import time

    import jax
    import jax.numpy as jnp

    n = 4096
    a = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def mm(a):
        return a @ a

    mm(a).block_until_ready()               # compile + warm
    t0 = time.perf_counter()
    iters = 8
    r = a
    for _ in range(iters):
        r = mm(r)
    r.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    tflops = 2 * n ** 3 / dt / 1e12
    print(f"bf16 {n}x{n} matmul: {tflops:.1f} TFLOP/s")
    assert np.isfinite(tflops) and tflops > 0


def test_paged_decode_dead_pages_on_hw(tpu_backend):
    """The in-kernel walk: dead pages past pos must not change the output
    on real hardware."""
    import jax.numpy as jnp

    rng = np.random.default_rng(15)
    b, h, d, bs = 2, 4, 64, 64
    pos = jnp.asarray([70, 120], jnp.int32)
    n_live = 2
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)

    def run(npg):
        nb = b * npg
        kp = np.zeros((nb, bs, h, d), np.float32)
        vp = np.zeros((nb, bs, h, d), np.float32)
        tbl = np.arange(nb, dtype=np.int32).reshape(b, npg)
        fill = rng.standard_normal((b, n_live * bs, h, d)).astype(np.float32)
        for i in range(b):
            for j in range(n_live):
                kp[tbl[i, j]] = fill[i, j * bs:(j + 1) * bs]
                vp[tbl[i, j]] = fill[i, j * bs:(j + 1) * bs] * 0.5
        return paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                      jnp.asarray(tbl), pos,
                                      interpret=False)

    rng = np.random.default_rng(15)   # same fill both runs
    tight = run(n_live)
    rng = np.random.default_rng(15)
    huge = run(8 * n_live)
    assert float(jnp.max(jnp.abs(tight - huge))) == 0.0


def test_sdpa_pad_rescue_on_hw(tpu_backend, monkeypatch):
    """Round-5 pad-to-128 rescue: a seq-500 SDPA runs the Mosaic-compiled
    kernel at 512 and matches the dense path on hardware."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.ops.impl as impl_mod

    monkeypatch.setattr(impl_mod, "_flash_enabled", lambda: True)
    rng = np.random.default_rng(16)
    q = paddle.to_tensor(rng.standard_normal(
        (2, 500, 4, 64)).astype(np.float32))
    out = F.scaled_dot_product_attention(q, q, q, is_causal=True)
    monkeypatch.setattr(impl_mod, "_flash_enabled", lambda: False)
    ref = F.scaled_dot_product_attention(q, q, q, is_causal=True)
    assert float(np.abs(out.numpy() - ref.numpy()).max()) < 3e-2


def test_segment_replay_on_hw(tpu_backend):
    """Round-5 tape segments: a broken function's compiled segments
    execute on the real chip, grads intact."""
    import warnings

    import paddle_tpu as paddle
    from paddle_tpu.jit import segments

    @paddle.jit.to_static
    def f(x, w):
        h = paddle.tanh(paddle.matmul(x, w))
        s = h.sum().item()
        return (h * (1.0 if s > 0 else 2.0)).sum()

    rng = np.random.default_rng(17)
    x = paddle.to_tensor(rng.standard_normal((8, 16)).astype(np.float32),
                         stop_gradient=False)
    w = paddle.to_tensor(rng.standard_normal((16, 16)).astype(np.float32),
                         stop_gradient=False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f(x, w)
    segments.reset_stats()
    out = f(x, w)
    assert segments.STATS["flushes"] >= 1
    out.backward()
    assert np.isfinite(w.grad.numpy()).all()
