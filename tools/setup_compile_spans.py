"""Set-up of a serving cell, span by span: one process builds the cell's
engine from the seed and fills it exactly as `bench/run.py` does (the
benchmark's own `build_engine`, `Driver` and closed-loop fill, a window of
half a second), then prints ONE JSON line: `setup_s`, every `runner.compile`
span that ended before the window with its `kind`, `key` and seconds, the
other set-up spans by name, and how many programs the backend compiled.

    python tools/setup_compile_spans.py --root <checkout> --workload <cell> --seed <n>

`--root` is the checkout to measure (its `bench/` and its `paddle_tpu`), so a
`git archive` of the parent commit runs through the same file. Needs the
chip. ISSUE 40: two commits whose lists differ in a `(kind, key)` do not
compile the same programs, whatever their `setup_s` says.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def cell_run(root: str, workload: str, seed: int):
    """`bench/run.py`'s `Run` of one cell of the checkout `root`, as its
    `main()` sets it up: the checkout's `bench/` and `paddle_tpu` on the
    path, the devices found, every program cached. Needs the chip."""
    root = os.path.abspath(root)
    os.chdir(root)
    sys.path[:0] = [os.path.join(root, "bench"), root]

    import run as R                 # stamps T_START: the process is young

    run = R.Run(R.parse(["--workload", workload, "--seed", str(seed),
                         "--seconds", "0.5"]),
                R.load_json(root, "BENCHMARK.json"))
    run.find_devices()
    import jax
    from paddle_tpu.utils.compile_cache import place_compile_cache

    # as bench/run.py's main(): every program is cached, the small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    run.cache = place_compile_cache()
    return run


def filled(run):
    """(driver, index of the window's first step): the cell's engine built
    from the seed and filled by the benchmark's own closed loop, then a
    window of `run.seconds`."""
    import serve
    import traffic_gen

    drv = serve.Driver(serve.build_engine(run))
    clients = traffic_gen.closed_clients(run.traffic,
                                         run.config["vocab_size"], run.seed)
    n0, _, _ = serve._window_closed(run, drv, clients)
    return drv, n0


def measure(run, label: str = "") -> dict:
    """Build and fill `run`'s cell as the benchmark does and read the ring.
    `run` has its devices; `bench/` and the checkout are on `sys.path`."""
    import jax

    import program_spans as ps

    backend = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: backend.append(secs)
        if name.endswith("backend_compile_duration") else None)
    drv, n0 = filled(run)
    first = drv.steps[n0][0] * 1e9
    ring = [s for s in ps.ring() if s[ps.T1] <= first]
    secs = lambda s: (s[ps.T1] - s[ps.T0]) / 1e9
    compiles = [{"kind": s[ps.ATTRS]["kind"], "key": s[ps.ATTRS]["key"],
                 "s": secs(s)} for s in ring if s[ps.NAME] == "runner.compile"]
    other = {}
    for s in ring:
        if s[ps.NAME] != "runner.compile":
            other[s[ps.NAME]] = other.get(s[ps.NAME], 0.0) + secs(s)
    return {"label": label, "workload": run.cell["name"], "seed": run.seed,
            "setup_s": run.setup_s,
            "setup_compile_s": sum(c["s"] for c in compiles),
            "compiles": compiles, "spans_s": other,
            "backend_compiles": len(backend),
            "backend_compile_s": sum(backend), "fill_steps": n0,
            "device": jax.devices()[0].device_kind}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--label", default="")
    args = ap.parse_args(argv)
    run = cell_run(args.root, args.workload, args.seed)
    print(json.dumps(dict(measure(run, args.label),
                          root=os.path.abspath(args.root),
                          cache=str(run.cache))), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
