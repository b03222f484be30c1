"""chip_smoke.py: the quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py             one TPU chip: device, kernels, train, serve
    python chip_smoke.py --chips 4   four chips: the dp2 x tp2 training step
                                     and the tensor-parallel engine, each
                                     against its one-device twin, nothing else

One process, no child, no fallback: anything but a TPU is an error, and a
phase that fails raises, so no later phase runs and no result line is
printed. GPT-2 124M at its published widths (GPTConfig's defaults), weights
and data from SEED. The last line of stdout is the result object

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Every time printed here is a smoke reading (one cold run, no repeats), not a
benchmark.

The phases are importable functions that take their sizes as arguments:
tests/test_chip_smoke.py rehearses them at toy width on the CPU; this script
itself has no option for size.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import statistics
import tempfile
import time
import warnings

import numpy as np

SEED = 0

# flash attention fwd / fwd+bwd vs the XLA reference, compiled: both sides
# round fp32 dots to bf16 passes on the MXU (validate_against_reference)
FLASH_TOL_OUT = 2e-2
FLASH_TOL_GRAD = 1e-1
# ragged paged attention vs ragged_reference computed at "highest" matmul
# precision: what is left is the kernel's own rounding of unit-variance
# q, k, v over up to 1024 keys
RAGGED_TOL = 2e-2
# AdamW with no warm-up: at 1e-3 the first chip run's third loss jumped
# above the first (11.0, 10.69, 12.38, 10.06, 9.69)
LEARNING_RATE = 3e-4
# first loss of a freshly initialised model vs ln(vocab)
FIRST_LOSS_TOL = 0.3
# dp2 x tp2 losses vs the one-device losses, same seed and batch. Under
# bf16 autocast the loss itself is a bf16 value (one ulp is 0.0625 between
# 8 and 16) and the two programs reduce in different orders: two ulps
SHARDED_LOSS_TOL = 0.13
# tensor-parallel vs one-device token streams: where they first part, the
# one-device logits of the two tokens. fp32 logits of a 50304-way head
# computed through bf16-pass matmuls agree to about 1e-3 between the two
# programs
NEAR_TIE_TOL = 5e-3


class SmokeFailure(RuntimeError):
    """A check of this script did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------- device


def phase_device(platform: str = "tpu", count: int = 1) -> dict:
    """The device as JAX reports it; anything but `count` devices of
    `platform` is an error."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say("device", json.dumps(dev))
    check(dev["platform"] == platform,
          f"need platform {platform!r}, JAX reports {dev['platform']!r}")
    check(dev["count"] == count,
          f"need {count} device(s), JAX reports {dev['count']}")
    return dev


# --------------------------------------------------------------- kernels


def phase_kernels(n_heads: int, head_dim: int, seq: int, batch: int,
                  page_size: int = 16, interpret: bool = False) -> None:
    """Flash attention fwd+bwd and ragged paged attention against their
    references, at the model's head layout."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import \
        validate_against_reference
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention, ragged_reference)

    res = validate_against_reference(
        shapes=((batch, seq, n_heads, head_dim),), interpret=interpret,
        tol_out=FLASH_TOL_OUT, tol_grad=FLASH_TOL_GRAD, seed=SEED)
    for b, s, h, d, mode, err_o, err_g in res["shapes"]:
        say("kernels", f"flash [{b},{s},{h},{d}] {mode}: max|out err|="
                       f"{err_o:.3e} max|grad err|={err_g:.3e}")
    check(res["pass"], f"flash attention off its reference: {res}")

    rng = np.random.default_rng(SEED)
    pages_per_seq = seq // page_size
    # (name, B, T, start_pos, q_len): a decode batch, one whole-prompt
    # prefill in the widest bucket, and offset chunks with padding
    cases = [
        ("decode", batch, 1,
         rng.integers(0, seq - 1, batch), np.ones(batch, np.int64)),
        ("prefill", 1, seq // 2, np.zeros(1, np.int64),
         np.asarray([seq // 2 - 3])),
        ("chunks", 2, seq // 8,
         np.asarray([seq // 8, seq // 2]), np.asarray([seq // 8, 5])),
    ]
    for name, B, T, starts, qlens in cases:
        n_pages = 1 + B * pages_per_seq
        q = jnp.asarray(rng.standard_normal((B, T, n_heads, head_dim)),
                        jnp.float32)
        kp, vp = (jnp.asarray(rng.standard_normal(
            (n_pages, page_size, n_heads, head_dim)), jnp.float32)
            for _ in range(2))
        table = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(
            B, pages_per_seq).astype(np.int32))
        starts = jnp.asarray(starts, jnp.int32)
        qlens = jnp.asarray(qlens, jnp.int32)
        out = jax.jit(functools.partial(
            ragged_paged_attention, interpret=interpret))(
                q, kp, vp, table, starts, qlens)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(ragged_reference)(q, kp, vp, table, starts, qlens)
        err = float(jnp.max(jnp.abs(out - ref)))
        say("kernels", f"ragged {name} B={B} T={T}: max|err|={err:.3e} "
                       f"(tol {RAGGED_TOL})")
        check(np.isfinite(err) and err < RAGGED_TOL,
              f"ragged paged attention ({name}) off its reference: {err}")


# ----------------------------------------------------------------- train


def _token_batch(vocab: int, batch: int, seq: int):
    rng = np.random.default_rng(SEED)
    tokens = rng.integers(0, vocab, (batch, seq + 1))
    return tokens[:, :-1], tokens[:, 1:]


def _train_steps(model, batch: int, seq: int, steps: int, phase: str):
    """`steps` TrainStep calls on one repeated batch, each timed up to
    block_until_ready (the first one compiles). Returns the step object
    and the losses."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt_loss_fn

    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=LEARNING_RATE)
    step = paddle.jit.TrainStep(model, gpt_loss_fn, opt, amp_level="O1")
    tokens, labels = _token_batch(model.cfg.vocab_size, batch, seq)
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = step(tokens, labels)
        loss._value.block_until_ready()
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss))
    say(phase, "losses=" + json.dumps([round(x, 4) for x in losses]))
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    say(phase, f"smoke reading, not a benchmark: first call (compile + "
               f"step) {secs[0]:.2f} s; median of {len(secs) - 1} later "
               f"steps {statistics.median(secs[1:]):.4f} s")
    return step, losses


def phase_train(model, batch: int, seq: int, steps: int = 5,
                kernels_required: bool = True) -> None:
    """TrainStep with bf16 autocast on one repeated batch, then
    sync / save / load of the trained values."""
    import paddle_tpu as paddle
    from paddle_tpu.ops import impl as ops_impl

    if kernels_required:
        check(ops_impl._flash_enabled(),
              "SDPA's flash gate is closed on this backend")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        step, losses = _train_steps(model, batch, seq, steps, "train")
    # flash_attention's and SDPA's give-way warnings
    fell_back = [str(w.message) for w in caught
                 if "O(seq^2) XLA reference" in str(w.message)
                 or "falls back to the O(s^2)" in str(w.message)]
    check(not fell_back, f"flash attention fell back: {fell_back}")
    ln_v = math.log(model.cfg.vocab_size)
    check(abs(losses[0] - ln_v) < FIRST_LOSS_TOL,
          f"first loss {losses[0]:.4f} not within {FIRST_LOSS_TOL} of "
          f"ln(vocab)={ln_v:.4f}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")

    # the donated-buffer case: the step owns (and donates) its own param
    # copies, so sync() must hand the model values that survive the next
    # step, and a save / load must round-trip exactly those values
    step.sync()
    trained = {k: np.asarray(v) for k, v in step.params.items()}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "gpt.pdparams")
        paddle.save(model.state_dict(), path)
        step(*_token_batch(model.cfg.vocab_size, batch, seq))  # donates again
        loaded = paddle.load(path)
    state = {k: np.asarray(v._value) for k, v in model.state_dict().items()}
    check(set(trained) <= set(loaded), "saved state lacks trained params")
    for k, want in trained.items():
        check(np.array_equal(np.asarray(loaded[k]._value), want),
              f"save/load changed {k}")
        check(np.array_equal(state[k], want),
              f"model value of {k} did not survive the step after sync()")
    say("train", f"sync/save/load round-trip exact for {len(trained)} "
                 "parameters, model values intact after a further step")


# ----------------------------------------------------------------- serve


def _prompts(vocab: int, n_requests: int, prompt_range) -> list:
    rng = np.random.default_rng(SEED)
    lo, hi = prompt_range
    return [rng.integers(0, vocab, int(rng.integers(lo, hi + 1))).tolist()
            for _ in range(n_requests)]


def _serve(phase: str, model, prompts, max_tokens: int, num_blocks: int,
           **engine_kw):
    """create_serving_engine with its defaults (+ audit, a pool that holds
    the traffic), run to completion through engine.step(). Checks all that
    one engine can show alone; returns it and its token streams."""
    from paddle_tpu.inference import create_serving_engine
    from paddle_tpu.serving import SamplingParams

    eng = create_serving_engine(model, num_blocks=num_blocks, audit=True,
                                **engine_kw)
    sp = SamplingParams(max_tokens=max_tokens)
    t0 = time.perf_counter()
    ids = [eng.add_request(p, sp) for p in prompts]
    n_steps = 0
    while eng.has_work():
        eng.step()
        n_steps += 1
    outs = eng.outputs()
    secs = time.perf_counter() - t0
    reasons = [outs[i].finish_reason for i in ids]
    streams = [outs[i].output_tokens for i in ids]
    n_tokens = sum(map(len, streams))
    retries = eng.metrics.snapshot()["step_retries"]
    impls = sorted(eng.runner._impl_logged)
    say(phase, f"prompt lengths={[len(p) for p in prompts]}")
    say(phase, f"{n_tokens} tokens from {len(prompts)} requests in "
               f"{n_steps} engine steps; finish reasons={reasons}; "
               f"step_retries={retries}")
    say(phase, f"attention impl per q_len bucket: {impls}")
    say(phase, f"smoke reading, not a benchmark: {secs:.2f} s for the "
               "whole serve, compilation included")
    check(all(r == "length" for r in reasons),
          f"not every request finished for length: {reasons}")
    check(n_tokens == len(prompts) * max_tokens,
          f"{n_tokens} tokens, expected {len(prompts) * max_tokens}")
    check(retries == 0, f"step_retries={retries}")
    check(impls and all(impl != "reference" for _, impl in impls),
          f"attention gave way to the reference: {impls}")
    check(eng.pool.allocator.check_no_leaks(), "KV pages leaked")
    return eng, streams


def _first_difference(a, b):
    return next((k for k, (x, y) in enumerate(zip(a, b)) if x != y), None)


def phase_serve(model, n_requests: int = 8, prompt_range=(128, 512),
                max_tokens: int = 64, num_blocks: int = 1024,
                **engine_kw) -> None:
    """The engine's token streams against naive_generate on the same
    runner: token-exact, which is what the first chip run showed."""
    from paddle_tpu.serving import SamplingParams, naive_generate

    model.eval()
    prompts = _prompts(model.cfg.vocab_size, n_requests, prompt_range)
    eng, streams = _serve("serve", model, prompts, max_tokens, num_blocks,
                          **engine_kw)
    sp = SamplingParams(max_tokens=max_tokens)
    for n, (p, got) in enumerate(zip(prompts, streams)):
        ref = naive_generate(eng.runner, p, sp)
        check(got == ref,
              f"request {n}: engine tokens differ from naive_generate at "
              f"index {_first_difference(got, ref)}: {got} vs {ref}")
    say("serve", f"token streams equal naive_generate for all "
                 f"{n_requests} requests")


# --------------------------------------------------------------- sharded


def phase_sharded(make_model, batch: int, seq: int, steps: int = 3) -> None:
    """The README's hybrid-parallel step on a dp2 x tp2 mesh against the
    one-device step at the same seed and batch. `make_model(tp)` builds
    the model, tensor-parallel or not, from SEED."""
    import jax

    from paddle_tpu import parallel as dist
    from paddle_tpu.parallel.mesh import set_mesh

    _, single = _train_steps(make_model(False), batch, seq, steps,
                             "sharded/one-device")
    dist.init_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    try:
        step, sharded = _train_steps(make_model(True), batch, seq, steps,
                                     "sharded/dp2xtp2")
    finally:
        set_mesh(None)
    diffs = [abs(a - b) for a, b in zip(single, sharded)]
    say("sharded", f"max |loss difference| = {max(diffs):.3e} "
                   f"(tol {SHARDED_LOSS_TOL})")
    check(max(diffs) < SHARDED_LOSS_TOL,
          f"dp2 x tp2 losses {sharded} differ from one-device {single}")

    total = sum(v.nbytes for v in step.params.values())
    per_dev: dict = {}
    for name, v in step.params.items():
        shards = v.addressable_shards
        check(len({s.device for s in shards}) == 4,
              f"{name} lives on {len({s.device for s in shards})} devices")
        for s in shards:
            per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
    say("sharded", f"parameter bytes: unsharded total {total}, per device "
                   f"{sorted(per_dev.values())}")
    check(len(per_dev) == 4 and all(b < total for b in per_dev.values()),
          f"parameters are not spread: {per_dev} of {total}")


def phase_sharded_serve(model, n_requests: int = 8,
                        prompt_range=(128, 512), max_tokens: int = 64,
                        num_blocks: int = 1024, **engine_kw) -> None:
    """The tensor-parallel engine (weights and KV pools split over four
    devices) against the one-device engine on the same prompts.

    Splitting a contraction over shards changes the order of its fp32
    sums, so the two engines are not one program and a near-tie between
    two logits may resolve differently. The comparison that holds: the
    streams are equal, or, where one first differs, the one-device
    runner's own logits for that position rate the two tokens within
    NEAR_TIE_TOL of each other."""
    import jax

    from paddle_tpu.parallel.mesh import serving_mesh
    from paddle_tpu.serving.kv_cache import KVCachePool

    model.eval()
    prompts = _prompts(model.cfg.vocab_size, n_requests, prompt_range)
    one, want = _serve("sharded/serve-one-device", model, prompts,
                       max_tokens, num_blocks, **engine_kw)
    tp, got = _serve("sharded/serve-tp4", model, prompts, max_tokens,
                     num_blocks, mesh=serving_mesh(data=1, model=4),
                     **engine_kw)
    k_pool = tp.pool.pools[0][0]
    check(len({s.device for s in k_pool.addressable_shards}) == 4
          and k_pool.addressable_shards[0].data.nbytes * 4 == k_pool.nbytes,
          "the tensor-parallel engine's KV pool is not split four ways")
    runner = one.runner
    max_pages = -(-runner.max_model_len // runner.block_size)
    near_ties = 0
    for n, (p, a, b) in enumerate(zip(prompts, got, want)):
        k = _first_difference(a, b)
        if k is None:
            continue
        pool = KVCachePool(runner.num_layers, max_pages + 1,
                           runner.block_size, runner.n_kv_heads,
                           runner.head_dim, runner.dtype)
        table = pool.pad_table(pool.allocator.alloc(max_pages), max_pages)
        logits, _ = runner.prefill(p + b[:k], table, pool.pools)
        logits = np.asarray(jax.device_get(logits), np.float32)
        gap = abs(float(logits[a[k]]) - float(logits[b[k]]))
        say("sharded/serve", f"request {n} differs first at index {k}: "
                             f"tokens {a[k]} vs {b[k]}, logit gap {gap:.3e} "
                             f"(logits' std {float(logits.std()):.3e})")
        check(gap < NEAR_TIE_TOL,
              f"request {n}: tensor-parallel stream leaves the one-device "
              f"stream at index {k} on a logit gap of {gap}")
        near_ties += 1
    say("sharded/serve", f"{n_requests - near_ties} of {n_requests} token "
                         f"streams equal, {near_ties} part at a near-tie "
                         f"(tol {NEAR_TIE_TOL})")


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths and their one-device twins")
    args = ap.parse_args(argv)

    dev = phase_device("tpu", args.chips)

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.utils.compile_cache import place_compile_cache

    say("device", f"compile cache: {place_compile_cache()}")
    cfg = GPTConfig()                    # GPT-2 124M, published widths
    batch, seq = 8, cfg.max_seq_len

    def make_model(tensor_parallel: bool = False):
        paddle.seed(SEED)
        return GPT(GPTConfig(tensor_parallel=tensor_parallel))

    if args.chips == 4:
        phase_sharded(make_model, batch, seq)
        phase_sharded_serve(make_model())
    else:
        phase_kernels(cfg.num_heads, cfg.hidden_size // cfg.num_heads,
                      seq, batch, interpret=False)
        model = make_model()
        phase_train(model, batch, seq)
        phase_serve(model)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
