"""The serving form of the held-experts layer alone (`parallel.moe.
held_experts_ffn`'s walk), both of its walks at one shape: the loop of an
expert block an iteration and the three products inside
`ops.pallas.grouped_matmul`, timed by the host's clock around `--runs` calls
that end in `block_until_ready`, beside the least time the touched experts'
bytes take at the HBM peak. One JSON line a shape and walk, also appended to
`chiprun_out/expert_layer_alone.jsonl`. Needs the chip; no cell runs it and
no test depends on it (PERF.md §5-§6 cite its numbers since PR 49).

    python tools/expert_layer_alone.py [--shapes laguna-decode,...]

shapes (T rows, K a token, G held of E routed, d, f): `laguna-decode` 64 x 8
on 256 of 256 of 2048 x 512; `laguna-piece` 2048 rows of the same;
`kimi-decode` 48 x 8 on 12 of 384 of 7168 x 2048; `dsv32-decode` 36 x 8 on 8
of 256 of 7168 x 2048. `--blocks` overrides the row block (the rule's choice
first), so that a shape can be read at the block it had before PR 49.
"""
import argparse
import json
import os
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--shapes", default="laguna-decode,laguna-piece,kimi-decode,"
                                    "dsv32-decode")
ap.add_argument("--blocks", default="")
ap.add_argument("--runs", type=int, default=20)
args = ap.parse_args()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import moe

SHAPES = {"laguna-decode": (64, 8, 256, 256, 2048, 512),
          "laguna-piece": (2048, 8, 256, 256, 2048, 512),
          "kimi-decode": (48, 8, 12, 384, 7168, 2048),
          "dsv32-decode": (36, 8, 8, 256, 7168, 2048)}
HBM = 819e9


def walk(grouped: bool, bm: int, G: int, K: int):
    def fn(x, idx, w, wg, wu, wd):
        y, counts, blocks = moe._walk(grouped, bm, x, idx, w, wg, wu, wd, 0)
        return y, jnp.sum((counts > 0).astype(jnp.int32)), blocks * bm

    return jax.jit(fn)


def main():
    out = []
    for name in args.shapes.split(","):
        T, K, G, E, d, f = SHAPES[name]
        ks = jax.random.split(jax.random.key(0), 5)
        bf = jnp.bfloat16
        x = jax.random.normal(ks[0], (T, d), bf)
        gate = jax.random.normal(ks[1], (d, E), bf) * 0.02
        idx, w = moe.sigmoid_topk_route(x, gate, None, K, scale=2.5)
        wg = jax.random.normal(ks[2], (G, d, f), bf) * 0.02
        wu = jax.random.normal(ks[3], (G, d, f), bf) * 0.02
        wd = jax.random.normal(ks[4], (G, f, d), bf) * 0.02
        rule = moe.block_rows(T * K, E)
        blocks = [rule] + [int(b) for b in args.blocks.split(",") if b]
        ref = None
        for bm in dict.fromkeys(blocks):
            for grouped in (False, True):
                fn = walk(grouped, bm, G, K)
                y, touched, rows = jax.block_until_ready(
                    fn(x, idx, w, wg, wu, wd))
                ref = y if ref is None else ref
                t0 = time.perf_counter()
                for _ in range(args.runs):
                    r = fn(x, idx, w, wg, wu, wd)
                jax.block_until_ready(r)
                ms = 1e3 * (time.perf_counter() - t0) / args.runs
                least = 1e3 * int(touched) * 3 * d * f * 2 / HBM
                line = {"shape": name, "walk": "grouped" if grouped
                        else "loop", "block_rows": bm,
                        "rule": bm == rule and grouped == moe.grouped_walk(
                            wg, wd, bm),
                        "ms": ms, "touched": int(touched),
                        "rows_multiplied": int(rows),
                        "weights_least_ms": least,
                        "share_of_least": least / ms,
                        "max_abs_diff": float(jnp.max(jnp.abs(y - ref))),
                        "device": jax.devices()[0].device_kind}
                print(json.dumps(line), flush=True)
                out.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/expert_layer_alone.jsonl", "a") as fh:
        for line in out:
            fh.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
