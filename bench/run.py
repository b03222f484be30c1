"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run = one new process: find the cell's files by name -> device check (no
TPU, or fewer chips than the cell asks for: non-zero exit, no result line)
-> compile cache -> build from the seed -> warm this cell's shapes -> the
window -> `correct` against the configuration's plain reference -> the last
line of stdout, one JSON object (README.md says what is in it). `--trace 0` reports the
cell's end-to-end metrics, `--trace 1` its per-layer metrics.

`--probe <name>` runs a cell with one of the program's own lower-precision
paths switched on, or the reference in a lower precision in the program's
place (the controls the correctness limits were set against: they must come
out not correct); it is not a cell.
"""

from __future__ import annotations

import time

T_START = time.time()          # process start, as nearly as Python can tell

import argparse
import gc
import glob
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_SECONDS = 4.0            # the profiler is on for the window's last part


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def say(msg: str) -> None:
    print(f"[bench +{time.time() - T_START:7.2f}s] {msg}", flush=True)


class Run:
    """What one run knows: the cell and its files, the devices, the clock
    of set-up, the compile counter and the profiler."""

    def __init__(self, args, manifest, root=ROOT, files=BENCH):
        """`root` holds the manifest's paths, `files` the traffic/ and
        limits/ directories (tests point both at a toy benchmark)."""
        self.args = args
        self.manifest = manifest
        self.files = files
        cells = {w["name"]: w for w in manifest["workloads"]}
        if args.workload not in cells:
            raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
        self.cell = cells[args.workload]
        cfg_entry = next(c for c in manifest["configs"]
                         if c["name"] == self.cell["config"])
        self.config = load_json(root, cfg_entry["file"])
        self.traffic = load_json(files, "traffic",
                                 self.cell["traffic"] + ".json")
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.probe = args.probe
        self.excluded_s = 0.0      # reference time inside set-up
        self.compiles = 0
        self.counting = False
        self.trace_dir = None
        self.trace_span = None     # (start, end) of the traced part
        self.trace_requested = None  # when the bench asked for the profiler
        # seconds of the window inside start_trace and stop_trace: a reader
        # that takes a rate from a traced run leaves them out
        self.profiler_stall_s = 0.0
        self.checks = []           # (name, value, limit, ok)

    # ------------------------------------------------------------ device

    def find_devices(self):
        import jax

        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise SystemExit(f"need a TPU, JAX reports "
                             f"{devs[0].platform!r}: no result")
        if len(devs) < self.cell["chips"]:
            raise SystemExit(f"cell needs {self.cell['chips']} chip(s), JAX "
                             f"reports {len(devs)}: no result")
        self.use_devices(devs[:self.cell["chips"]])

    def use_devices(self, devices):
        self.devices = list(devices)
        peaks = load_json(BENCH, "peaks.json")
        kind = self.devices[0].device_kind
        if kind not in peaks:
            raise SystemExit(f"device_kind {kind!r} is not in bench/peaks.json"
                             ": add its published peaks with their source")
        self.peaks = peaks[kind]
        say(f"device: {self.devices[0].platform} {kind!r} x{len(self.devices)}")

    def model_cfg(self) -> dict:
        """The model's constructor arguments: the configuration file's
        `program.args` maps each to the key of the file that holds it."""
        return {arg: self.config[key]
                for arg, key in self.config["program"]["args"].items()}

    def program(self, what: str):
        """An object of the program by the dotted name the configuration
        file gives it under `program` (`model`, `config`, `loss`)."""
        module, _, name = self.config["program"][what].rpartition(".")
        return getattr(importlib.import_module(module), name)

    @property
    def reference(self):
        """The configuration's plain reference: the module under bench/
        that its file names (`program.reference`)."""
        return importlib.import_module(self.config["program"]["reference"])

    # ---------------------------------------------------------- compiles

    def watch_compiles(self):
        import jax.monitoring

        def on_event(name, _secs, **_kw):
            if self.counting and name.endswith("backend_compile_duration"):
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    # ------------------------------------------------------------ window

    def setup_seconds(self) -> float:
        return time.time() - T_START - self.excluded_s

    def open_window(self):
        """Everything set-up made that is garbage goes now, and what stays
        is frozen out of the collector's reach, so that no full collection
        lands inside the window."""
        gc.collect()
        gc.freeze()
        self.setup_s = self.setup_seconds()
        self.counting = True
        self.t_open = time.perf_counter()
        say(f"window opens: setup_s={self.setup_s:.3f}")

    def tick(self):
        """Called between steps. A traced run has the profiler on for
        TRACE_SECONDS. By default those are the window's LAST: this only
        ever STARTS the profiler then, and `close_window()` stops it, once
        the driver has drained the device and read its clock for the run's
        own rate, so that neither a step in flight nor the seconds
        `stop_trace` takes are inside the window. A traffic file whose work
        changes through the window (a closed loop whose contexts grow) asks
        for the MIDDLE with `"trace_at": "middle"`: there the first tick
        TRACE_SECONDS past the start stops it, the stall only pauses the
        loop, and the slice stands for the whole window."""
        if not self.trace or self.trace_span is not None:
            return
        now = time.perf_counter()
        middle = self.traffic.get("trace_at") == "middle"
        start = (self.seconds - TRACE_SECONDS) / (2 if middle else 1)
        if self.trace_dir is not None:
            if middle and now - self.trace_t0 >= TRACE_SECONDS:
                self.stop_trace()
        elif now - self.t_open >= start:
            import jax.profiler

            # host spans (TraceAnnotation) yes, the Python tracer no: it
            # would record every call of the engine's host code
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.trace_requested = now
            self.trace_t0 = time.perf_counter()
            self.profiler_stall_s += self.trace_t0 - now

    def stop_trace(self):
        """The traced span is [trace_t0, t1] on the bench's clock: after
        `start_trace` returned, before `stop_trace` is called."""
        import jax.profiler

        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.trace_span = (self.trace_t0, t1)
        if self.counting:           # a middle trace: the window is open
            self.profiler_stall_s += time.perf_counter() - t1

    def close_window(self):
        """The driver calls this with the device drained (train.py blocks on
        its last step; every `engine.step()` of serve.py ends in the
        engine's own drain)."""
        self.counting = False
        if self.trace_dir is not None and self.trace_span is None:
            self.stop_trace()
        gc.unfreeze()

    def reduced_trace(self, ctx):
        """The profiler's table cut to the traced span, which the anchors
        (program_spans) move from the bench's clock onto the trace's: one
        span on one clock for `busy_s`, `window_s` and every reader. Device
        events with no anchor have no clock to stand on: no result."""
        import program_spans
        import trace_reduce

        if self.trace_dir is None:
            raise SystemExit("the window closed before the profiler was "
                             "due to start: no result")
        paths = glob.glob(os.path.join(self.trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        ctx["trace"] = raw = trace_reduce.load(paths[0], len(self.devices))
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        anchors = program_spans.of(ctx).align()
        if raw.ops and anchors is None:
            raise SystemExit(
                "the trace holds device events but none of the bench's own "
                "steps (no bench.* anchor inside the traced span), so the "
                "span cannot be moved onto the trace's clock: no result")
        offset = anchors[0] if anchors else 0
        lo, hi = (round(t * 1e9 + offset) for t in self.trace_span)
        return trace_reduce.clip(raw, lo, hi)

    # ----------------------------------------------------------- results

    def check(self, name: str, value: float, limit: float) -> bool:
        ok = bool(value <= limit)
        self.checks.append((name, float(value), float(limit), ok))
        say(f"check {name}: {value:.6g} (limit {limit:.6g}) "
            f"{'ok' if ok else 'NOT CORRECT'}")
        return ok

    def memory_peak_bytes(self) -> int:
        """Peak on the fullest chip, read when the window has closed: the
        larger of the allocator's own peak of live buffers and what the chip
        holds now, live buffers plus the runtime's reservation for the
        loaded programs' temporaries (on this TPU runtime
        `peak_bytes_in_use` leaves those out: a training step's
        activations live there)."""
        peak = 0
        for d in self.devices:
            s = d.memory_stats() or {}
            peak = max(peak, int(s.get("peak_bytes_in_use", 0)),
                       int(s.get("bytes_in_use", 0))
                       + int(s.get("bytes_reserved", 0)))
        return peak

    def layer_metrics(self, ctx: dict) -> dict:
        """Each per-layer metric that lists this cell, through its own
        reader bench/layer_metrics/<name>.py: read(ctx) -> number or None."""
        out = {}
        for m in self.manifest["per_layer"]:
            if self.cell["name"] not in m.get("workloads",
                                              [self.cell["name"]]):
                continue
            path = os.path.join(BENCH, "layer_metrics", m["name"] + ".py")
            spec = importlib.util.spec_from_file_location(
                "layer_metric_" + m["name"].replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            value = mod.read(ctx)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def result(self, correct: bool, attempted: int, failed: int,
               e2e: dict, ctx: dict) -> dict:
        """The result object. `e2e` holds this cell's end-to-end values
        (setup_s is added here); ctx is what the per-layer readers get."""
        e2e = dict(e2e, setup_s=self.setup_s)
        units = {m["name"]: m["unit"] for m in self.manifest["end_to_end"]}
        device = {"platform": self.devices[0].platform,
                  "kind": self.devices[0].device_kind,
                  "count": len(self.devices),
                  "memory_peak_bytes": ctx["memory_peak_bytes"]}
        out = {"correct": bool(correct), "attempted": int(attempted),
               "failed": int(failed)}
        if self.trace:
            import trace_reduce

            ctx.update(e2e=e2e, config=self.model_cfg(),
                       traffic=self.traffic, peaks=self.peaks,
                       chips=len(self.devices), trace_span=self.trace_span,
                       trace_requested=self.trace_requested,
                       profiler_stall_s=self.profiler_stall_s)
            tr = ctx["trace"] = self.reduced_trace(ctx)
            out["metrics"] = self.layer_metrics(ctx)
            device["busy_s"] = tr.busy_s
            device["window_s"] = tr.window_s
            out["breakdown"] = trace_reduce.breakdown(tr)
        else:
            out["metrics"] = {k: {"value": float(v), "unit": units[k]}
                              for k, v in e2e.items()}
        out["device"] = device
        out["compiles_in_window"] = self.compiles
        if self.trace:
            out["profiler_stall_s"] = self.profiler_stall_s
        out["workload"], out["seed"] = self.cell["name"], self.seed
        out["probe"] = self.probe
        # every number compared beside its limit: the line's last key
        out["checks"] = [{"name": n, "value": v, "limit": l, "ok": ok}
                         for n, v, l, ok in self.checks]
        return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--probe", default=None,
                    help="a control: see serve.PROBES / train.PROBES")
    return ap.parse_args(argv)


def run_cell(run: Run) -> dict:
    """Everything after the look for a chip (tests enter here)."""
    for p in (BENCH, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    # serve.py and train.py import this module by name, also when it is
    # the script
    sys.modules.setdefault("run", sys.modules[__name__])
    run.watch_compiles()
    # the traffic file names the module under bench/ that drives its kind
    return importlib.import_module(run.traffic["driver"]).drive(run)


def main(argv=None) -> int:
    args = parse(argv)
    manifest = load_json(ROOT, "BENCHMARK.json")
    if args.seconds is None:
        args.seconds = manifest["run_seconds"]
    run = Run(args, manifest)
    sys.path.insert(0, ROOT)
    run.find_devices()
    import jax
    from paddle_tpu.utils.compile_cache import place_compile_cache

    # every program is cached, the small ones too: a warm set-up then
    # compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say(f"compile cache: {place_compile_cache()}")
    result = run_cell(run)
    print(json.dumps(result), flush=True)
    for c in result["checks"]:      # and the last lines of standard error
        print(f"check {c['name']} {c['value']:.6g} limit {c['limit']:.6g} "
              f"{'ok' if c['ok'] else 'NOT CORRECT'}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
