"""What a served decode step's launch costs the host, part by part: one
process builds a serving cell's engine from the seed and fills it exactly as
`bench/run.py` does (the benchmark's own `build_engine`, `Driver` and
closed-loop fill), takes the batch the engine would launch next, and then
calls the runner's compiled decode program itself, `--steps` times a variant,
each call timed on the host from its enter to its return (`call_ms`) and to
the logits being ready (`wall_ms`: dispatch, the program, the wake-up):

    as_passed    tokens, tables, pos as host arrays, as `runner.decode` passes
    on_device    the three put on the device before the call
    packed       the three in ONE host int32 array [B, 1 + P + 1], sliced
                 apart inside the program (a second compile of the program)
    packed_dev   that array put on the device before the call

and, to tell the runtime's floor from the operands', three programs that do
nothing (`x + 1` on one int32), timed the same way:

    nop          one device operand
    nop_leaves   the runner's parameters and pools passed beside it, unused
    nop_host3    the batch's three host arrays passed beside it, unused

The program's device time is the same in the four decode variants, so the
differences of their `wall_ms` are differences of the launch alone;
`program_ms` is that device time (the median run of the largest program in a
short profiler trace of `as_passed`), so `wall_ms - program_ms` is dispatch
plus wake-up. Prints ONE JSON line. Needs the chip; no cell runs it and no test depends on it
(ISSUE 41).

    python tools/dispatch_floor.py --root <checkout> --workload <cell> --seed <n>
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time


def timed(call, ready, steps: int, warm: int = 5) -> dict:
    """Medians over `steps` calls of `call() -> out`: the call alone, and
    the call until `ready(out)` is on the host's side of a wait."""
    import jax

    calls, walls = [], []
    for i in range(warm + steps):
        t0 = time.perf_counter_ns()
        out = call()
        t1 = time.perf_counter_ns()
        jax.block_until_ready(ready(out))
        t2 = time.perf_counter_ns()
        if i >= warm:
            calls.append(t1 - t0)
            walls.append(t2 - t0)
    q = statistics.quantiles(walls, n=4)
    return {"call_ms": statistics.median(calls) / 1e6,
            "wall_ms": statistics.median(walls) / 1e6,
            "wall_iqr_ms": (q[2] - q[0]) / 1e6, "n": steps}


def program_ms(call, ready, runs: int = 12):
    """The device's time in one run of the program `call()` launches: the
    median duration of the longest-running module on the first device's
    plane, from a profiler trace of `runs` calls; None off the chip."""
    import jax

    tmp = tempfile.mkdtemp(prefix="dispatch_floor_")
    with jax.profiler.trace(tmp):
        for _ in range(runs):
            jax.block_until_ready(ready(call()))
    by_name = {}
    for path in glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        by_name.setdefault(e.name, []).append(e.duration_ns)
    if not by_name:
        return None
    longest = max(by_name.values(), key=sum)
    return statistics.median(longest) / 1e6


def measure(eng, steps: int) -> dict:
    """The variants above on `eng`'s runner, from the batch `eng` would
    launch next. The pools are threaded through the calls (the program
    donates them); every call writes the same positions again."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    runner = eng.runner
    eng._reserve_decode()          # the pages the next token is written to
    tokens, tables, pos = eng._build_batch(eng._decode_rows())
    toks = np.asarray(tokens, np.int32)[:, None]
    tabs, pos = np.asarray(tables, np.int32), np.asarray(pos, np.int32)
    B, P = tabs.shape
    fn = runner._jitted("decode", B)
    state = {"pools": eng.pool.pools}

    def decode(*ops):
        out = fn(runner.params, *ops, state["pools"])
        state["pools"] = out[1]
        return out

    out = {"workload_batch": int(B), "table_width": int(P),
           "leaves": len(jax.tree_util.tree_leaves(
               (runner.params, state["pools"])))}
    logits = lambda o: o[0]
    out["as_passed"] = timed(lambda: decode(toks, tabs, pos), logits, steps)
    out["program_ms"] = program_ms(lambda: decode(toks, tabs, pos), logits)
    dev = jax.block_until_ready(jax.device_put((toks, tabs, pos)))
    out["on_device"] = timed(lambda: decode(*dev), logits, steps)

    donate = (2,) if jax.default_backend() == "tpu" else ()
    packed_fn = jax.jit(
        lambda params, packed, pools: runner._decode_step(
            params, packed[:, :1], packed[:, 1:-1], packed[:, -1], pools),
        donate_argnums=donate)

    def decode_packed(packed):
        o = packed_fn(runner.params, packed, state["pools"])
        state["pools"] = o[1]
        return o

    packed = np.concatenate([toks, tabs, pos[:, None]], axis=1)
    jax.block_until_ready(decode_packed(packed)[0])          # compiles
    out["packed"] = timed(lambda: decode_packed(packed), logits, steps)
    packed_dev = jax.block_until_ready(jax.device_put(packed))
    out["packed_dev"] = timed(lambda: decode_packed(packed_dev), logits,
                              steps)
    eng.pool.pools = state["pools"]

    one = jax.block_until_ready(jnp.zeros((), jnp.int32))
    # `keep_unused`: the operands reach the executable, as a step's do
    nop = jax.jit(lambda x: x + 1)
    nop_leaves = jax.jit(lambda x, params, pools: x + 1, keep_unused=True)
    nop_host3 = jax.jit(lambda x, a, b, c: x + 1, keep_unused=True)
    same = lambda o: o
    out["nop"] = timed(lambda: nop(one), same, steps)
    out["nop_leaves"] = timed(
        lambda: nop_leaves(one, runner.params, state["pools"]), same, steps)
    out["nop_host3"] = timed(lambda: nop_host3(one, toks, tabs, pos), same,
                             steps)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from setup_compile_spans import cell_run, filled

    run = cell_run(args.root, args.workload, args.seed)
    drv, _ = filled(run)
    import jax

    print(json.dumps(dict(measure(drv.eng, args.steps),
                          workload=args.workload, seed=args.seed,
                          root=os.path.abspath(args.root),
                          device=jax.devices()[0].device_kind)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
