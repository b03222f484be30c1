"""Read a cell's correctness numbers on the chip for many seeds in ONE
process (set-up is most of a run): sound runs of the program and its
controls, a short window each at the cell's own load. The limits in
bench/limits/<cell>.json were set from what this prints (PERF.md, section 2).

    python3 bench/tests/read_limits.py <cell> <seconds> <seed>[:<probe>] ...
"""
import gc
import json
import os
import sys
import traceback

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run as R      # noqa: E402


def main(cell, seconds, *runs):
    import jax
    from paddle_tpu.utils.compile_cache import place_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    place_compile_cache()
    manifest = R.load_json(R.ROOT, "BENCHMARK.json")
    for spec in runs:
        seed, _, probe = spec.partition(":")
        argv = ["--workload", cell, "--seed", seed, "--seconds", seconds]
        run = R.Run(R.parse(argv + (["--probe", probe] if probe else [])),
                    manifest)
        run.find_devices()
        try:
            out = R.run_cell(run)
            print("READ", json.dumps({
                "seed": run.seed, "probe": probe or None,
                "correct": out["correct"],
                "checks": {c["name"]: c["value"] for c in out["checks"]},
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "memory_peak_bytes": out["device"]["memory_peak_bytes"]}),
                flush=True)
        except BaseException:
            traceback.print_exc()
            print("READ", json.dumps({"seed": seed, "probe": probe or None,
                                      "crashed": True}), flush=True)
        del run
        gc.collect()


if __name__ == "__main__":
    main(*sys.argv[1:])
