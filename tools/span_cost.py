"""What the new spans cost a traced step, in ONE process: a cell's engine,
filled as the bench fills it, stepped under a live profiler session with the
seven spans PR 40 adds recording and not (`profiler.span` handing back NO_SPAN
for their names, as the parent's step has it) in alternating blocks of three
steps.   python tools/span_cost.py <workload> <seed> <steps>"""
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "bench"), ROOT]
import run as R  # noqa: E402

workload, seed, n = sys.argv[1], sys.argv[2], int(sys.argv[3])
args = R.parse(["--workload", workload, "--seed", seed, "--seconds", "3"])
run = R.Run(args, R.load_json(ROOT, "BENCHMARK.json"))
run.find_devices()
import jax  # noqa: E402
from paddle_tpu.utils.compile_cache import place_compile_cache  # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
place_compile_cache()
sys.modules.setdefault("run", R)
import serve  # noqa: E402
import traffic_gen  # noqa: E402
from paddle_tpu import profiler as prof  # noqa: E402

run.watch_compiles()
drv = serve.Driver(serve.build_engine(run))
clients = traffic_gen.closed_clients(run.traffic, run.config["vocab_size"],
                                     run.seed)
_, _, more = serve._window_closed(run, drv, clients)
NEW = {"runner.account", "runner.stage", "runner.dispatch", "drain.enqueue",
       "drain.fetch"}
real_span, on = prof.span, [True]


def span(name, request_id=None, **attrs):
    if not on[0] and name in NEW:
        return prof.NO_SPAN
    return real_span(name, request_id, **attrs)


prof.span = span
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 2
d = tempfile.mkdtemp(prefix="oncost_")
for _ in range(20):
    more()
jax.profiler.start_trace(d, profiler_options=opts)
times = {"on": [], "off": []}
for i in range(n):
    which = "on" if (i // 3) % 2 == 0 else "off"
    on[0] = which == "on"
    t0 = time.perf_counter()
    more()
    times[which].append(1e3 * (time.perf_counter() - t0))
jax.profiler.stop_trace()
prof.span = real_span
out = {"workload": workload}
for k, v in times.items():
    med = statistics.median(v)
    body = [x for x in v if x < 1.15 * med]      # steps without a prefill
    out[k] = {"n": len(v), "p50_ms": med, "decode_only_n": len(body),
              "decode_only_mean_ms": sum(body) / len(body),
              "q1_q3_ms": statistics.quantiles(v, n=4)[::2]}
out["on_less_off_p50_us"] = 1e3 * (out["on"]["p50_ms"] - out["off"]["p50_ms"])
out["on_less_off_mean_us"] = 1e3 * (
    out["on"]["decode_only_mean_ms"] - out["off"]["decode_only_mean_ms"])
print("[span_cost]", json.dumps(out), flush=True)
