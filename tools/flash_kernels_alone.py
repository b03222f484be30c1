"""Flash attention's kernels alone at the three training cells' shapes: one
layer's forward and backward, a Mosaic call at a time, from a device trace
(medians over `--runs` calls), and by the host's clock around the whole
backward. One JSON line a variant, also appended to
`chiprun_out/flash_kernels_alone.jsonl`. Needs the chip; no cell runs it and
no test depends on it (PERF.md §5-§6 cite its numbers since PR 47; PR 35's
`flash_bench.py`, PR 38's `kern38.py` and PR 46's `kern46.py` were its
unkept forerunners, ROADMAP D16).

    python tools/flash_kernels_alone.py --tree <checkout> --variant NAME[,NAME...]

variants: `rule` (what `schedule()` gives the shape); `two` (the two-kernel
backward forced); `k<rows>` (the fused backward with that many rows of k a
grid step beside all of sq); `q<rows>` (a k tile a step beside that q span,
FUSED_VMEM_LIMIT asked where the count passes VMEM_BUDGET). A tree from
before PR 47 knows `rule` alone. Gradients are compared with `_reference`'s
(with the two-kernel backward's at 8192 keys and more, where the reference's
score matrix is too large), the worst element a gradient.
"""
import argparse
import glob
import json
import os
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--tree", default=".")
ap.add_argument("--variant", default="rule")
ap.add_argument("--shapes", default="gpt2,gpt3,zaya")
ap.add_argument("--runs", type=int, default=10)
args = ap.parse_args()
sys.path.insert(0, os.path.abspath(args.tree))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.pallas import flash_attention as fa

SHAPES = {"gpt2": ((28, 1024, 12, 64), 12), "gpt3": ((4, 1024, 8, 128), 8),
          "zaya": ((1, 8192, 8, 128), 2), "16k": ((1, 16384, 8, 128), 8)}


def program(shape, hk, variant):
    b, s, h, d = shape
    sch = fa.schedule(shape, (b, s, hk, d), jnp.bfloat16, True)
    if variant == "two":
        sch = sch._replace(bwd_span_q=0, bwd_span_k=0)
    elif variant[0] in "kq":
        rows = int(variant[1:])
        q_rows, k_rows = (s, rows) if variant[0] == "k" else (rows, 512)
        need = fa._fused_vmem_bytes(q_rows, k_rows, s, sch.block_q,
                                    sch.block_k, d, 2, 0, sch.heads)
        sch = sch._replace(
            bwd_span_q=q_rows, bwd_span_k=k_rows,
            bwd_vmem_limit=None if need <= fa.VMEM_BUDGET
            else fa.FUSED_VMEM_LIMIT)
    scale = d ** -0.5

    def fwd(q, k, v):
        return fa._flash_forward(q, k, v, None, None, None, None, None, True,
                                 scale, sch, False, with_lse=True)

    def bwd(q, k, v, o, do, lse):
        return fa._flash_backward(q, k, v, o, do, lse, None, None, None,
                                  None, None, True, scale, sch, False)

    return sch, jax.jit(fwd), jax.jit(bwd)


def mosaic_events(trace_dir):
    """Per program run on the first device, the ops' durations in order:
    [(module, [(op, ns), ...]), ...]."""
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        mods, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                mods = [(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
            elif line.name == "XLA Ops":
                ops = [(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
        out = []
        for name, t0, dur in sorted(mods, key=lambda m: m[1]):
            inside = sorted((o for o in ops if t0 <= o[1] < t0 + dur),
                            key=lambda o: o[1])
            out.append((name.split("(")[0], [
                (o[0].split(" =")[0], o[2]) for o in inside]))
        return out
    return []


def main():
    for variant in args.variant.split(","):
        rec = {"tree": args.tree, "variant": variant,
               "device": str(jax.devices()[0])}
        for key in args.shapes.split(","):
            if variant.startswith("q") and key != "zaya":
                continue
            shape, hk = SHAPES[key]
            b, s, h, d = shape
            sch, fwd, bwd = program(shape, hk, variant)
            row = {"schedule": {k: getattr(sch, k) for k in sch._fields}}
            rng = np.random.default_rng(0)
            q = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
            k, v = (jnp.asarray(rng.standard_normal((b, s, hk, d)),
                                jnp.bfloat16) for _ in range(2))
            do = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
            t0 = time.perf_counter()
            o, lse = fwd(q, k, v)
            grads = bwd(q, k, v, o, do, lse)
            jax.block_until_ready(grads)
            row["first_s"] = round(time.perf_counter() - t0, 2)
            ref = jax.jit(lambda q, k, v: jax.vjp(
                lambda *a: fa._reference(*a, True, d ** -0.5), q, k, v)[1](do))
            if key not in ("zaya", "16k"):
                want = ref(q, k, v)
                row["grad_err"] = [float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - r.astype(jnp.float32))))
                    for a, r in zip(grads, want)]
            else:   # against the two-kernel backward of the same tree
                two = sch._replace(bwd_span_q=0, bwd_span_k=0) if hasattr(
                    sch, "bwd_span_q") else sch
                want = jax.jit(lambda *a: fa._flash_backward(
                    *a, None, None, None, None, None, True, d ** -0.5, two,
                    False))(q, k, v, o, do, lse)
                row["grad_err_vs_two"] = [float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - r.astype(jnp.float32))))
                    for a, r in zip(grads, want)]
            for name, f, a in (("fwd", fwd, (q, k, v)),
                               ("bwd", bwd, (q, k, v, o, do, lse))):
                jax.block_until_ready(f(*a))
                t0 = time.perf_counter()
                for _ in range(args.runs):
                    out = f(*a)
                jax.block_until_ready(out)
                row[name + "_wall_us"] = round(
                    (time.perf_counter() - t0) / args.runs * 1e6, 1)
            tdir = f"/tmp/flash_alone_{key}_{variant}_{os.getpid()}"
            with jax.profiler.trace(tdir):
                for _ in range(args.runs):
                    jax.block_until_ready(bwd(q, k, v, o, do, lse))
            by_mod = {}
            for mod, evs in mosaic_events(tdir):
                by_mod.setdefault(mod, []).append(evs)
            for mod, runs in by_mod.items():
                names = [n for n, _ in runs[0]]
                med = [float(np.median([r[i][1] for r in runs
                                        if len(r) == len(names)])) / 1e3
                       for i in range(len(names))]
                row["trace_us:" + mod] = [[n, round(m, 1)]
                                          for n, m in zip(names, med)
                                          if m > 20]
            rec[key] = row
            print(variant, key, json.dumps(row), flush=True)
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/flash_kernels_alone.jsonl", "a") as f:
            f.write(json.dumps(rec) + "\n")


main()
