"""One traced benchmark run from a tree, as bench/run.py makes it, that also
writes what the per-layer readers were given (the clipped trace table, the
bench's step records, the traced span, the program's ring) to
chiprun_out/dump_<workload>_<seed>.json.gz, so that a reader can be run
again off the chip.   python tools/traced_dump.py <tree> <workload> <seed>"""
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
tree, workload, seed = sys.argv[1:4]
top = ROOT if tree == "." else os.path.join(ROOT, tree)
os.chdir(top)
sys.path[:0] = [os.path.join(top, "bench"), top]
import run as R  # noqa: E402

sys.modules["run"] = R
_layer_metrics = R.Run.layer_metrics


def layer_metrics(self, ctx):
    import program_spans as ps

    tr = ctx["trace"]
    lo = ctx["trace_span"][0] * 1e9 - 2e9
    out = {"ops": {str(d): e for d, e in tr.ops.items()},
           "modules": {str(d): e for d, e in tr.modules.items()},
           "host": tr.host, "span": tr.span, "steps": ctx["steps"],
           "trace_span": ctx["trace_span"],
           "ring": [list(s[:7]) + [s[7] if s[7] is None else
                                   {k: str(v) for k, v in s[7].items()}]
                    for s in ps.ring() if s[ps.T0] >= lo]}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with gzip.open(os.path.join(ROOT, "chiprun_out",
                                f"dump_{workload}_{seed}.json.gz"),
                   "wt") as f:
        json.dump(out, f)
    return _layer_metrics(self, ctx)


R.Run.layer_metrics = layer_metrics
raise SystemExit(R.main(["--workload", workload, "--seed", seed,
                         "--trace", "1"]))
