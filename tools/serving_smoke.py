"""Serving-engine smoke driver: stream tokens from a tiny LLaMA.

Usage (CPU-safe, no TPU needed):

    JAX_PLATFORMS=cpu python tools/serving_smoke.py
    JAX_PLATFORMS=cpu python tools/serving_smoke.py --requests 12 \
        --num-blocks 12 --max-model-len 64 --max-batch 4   # tight pool:
                                                           # preemptions

Submits a batch of random-token prompts with mixed lengths and sampling
params, streams tokens per engine step, then prints the metrics snapshot
and verifies the engine against the naive sequential oracle.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=32)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-model-len", type=int, default=96)
    ap.add_argument("--max-tokens", type=int, default=8)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--no-verify", action="store_true",
                    help="skip the naive-oracle equivalence check")
    args = ap.parse_args()

    from paddle_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.serving import (
        LlamaRunner, SamplingParams, ServingEngine, naive_generate,
    )

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=251, hidden_size=args.hidden,
                      num_layers=args.layers,
                      num_heads=max(2, args.hidden // 32),
                      max_seq_len=args.max_model_len, dropout=0.0)
    model = Llama(cfg)
    model.eval()
    runner = LlamaRunner(model, block_size=args.block_size,
                         max_model_len=args.max_model_len)
    engine = ServingEngine(runner, num_blocks=args.num_blocks,
                           max_batch_size=args.max_batch,
                           max_model_len=args.max_model_len)

    rng = np.random.default_rng(0)
    prompts, ids = [], []
    for i in range(args.requests):
        prompt = list(rng.integers(1, cfg.vocab_size,
                                   int(rng.integers(4, 24))))
        sp = SamplingParams(max_tokens=args.max_tokens,
                            temperature=args.temperature, seed=i)
        prompts.append((prompt, sp))
        ids.append(engine.add_request(prompt, sp))
        print(f"submit {ids[-1]}: prompt_len={len(prompt)}")

    step = 0
    while engine.has_work():
        events = engine.step()
        step += 1
        line = " ".join(f"{e.request_id}:{e.token}"
                        + ("*" if e.finished else "") for e in events)
        print(f"step {step:3d} | {line}")

    print("\nmetrics:",
          json.dumps({k: round(v, 4)
                      for k, v in engine.metrics.snapshot().items()},
                     indent=1))
    leaks_ok = engine.pool.allocator.check_no_leaks()
    print(f"pool pages all returned: {leaks_ok}")

    verify_ok = True
    if not args.no_verify:
        outs = engine.outputs()
        for rid, (prompt, sp) in zip(ids, prompts):
            ref = naive_generate(runner, prompt, sp,
                                 max_model_len=args.max_model_len)
            if outs[rid].output_tokens != ref:
                verify_ok = False
                print(f"MISMATCH {rid}: engine={outs[rid].output_tokens} "
                      f"naive={ref}")
        print(f"naive-oracle token equivalence: {verify_ok}")
    return 0 if (leaks_ok and verify_ok) else 1


if __name__ == "__main__":
    raise SystemExit(main())
