"""From the profiler's trace to numbers: the one reduction every PR shares.

`load()` reads a jax.profiler `.xplane.pb` into an event table (per device:
the operations and the programs that ran, each with start and duration in
nanoseconds; plus the benchmark's own `bench.*` host spans on the same
clock). `clip()` cuts that table to the traced span, given on the trace's own
clock: the one table every reader sees, so that no number counts time outside
the span and `busy_s <= window_s` whatever the profiler recorded around it.
Everything else here is arithmetic on that table, so tests check it on small
tables (tests/data/trace_events.json is a recorded one) without a chip.

A run of a program (an `XLA Modules` event) is a step only where it is WHOLE:
inside the span, with another run recorded before it and another after it on
its device. The profiler arms and disarms the device some milliseconds apart
from the calls that ask it to, and prints a run it cut there as a shorter run,
inside the span or not; `whole_runs()` leaves the first and the last out, and
every per-step number is built on it.

Names: an operation is named by what the trace prints for it with its
numeric suffix dropped (`fusion.123` -> `fusion`); a Mosaic (Pallas) kernel
by `mosaic:<program>` of the program it ran in, because XLA prints every
such custom call alike.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)       # device -> [(name, t0, dur)]
    modules: dict = field(default_factory=dict)   # device -> [(name, t0, dur)]
    host: list = field(default_factory=list)      # [(name, t0, dur)] bench.*
    stats: dict = field(default_factory=dict)     # op name -> first event's stats
    # (lo, hi) in ns on the trace's clock: the traced span, which clip() cut
    # `ops` to (`modules` stay as recorded); None on a table as load() read it
    span: tuple = None

    @property
    def window_s(self) -> float:
        """Length of the traced span, on the clock `busy_s` is measured on."""
        return (self.span[1] - self.span[0]) / 1e9 if self.span else 0.0

    @property
    def busy_s(self) -> float:
        """Seconds of the span in which an operation ran, averaged over the
        devices."""
        if not self.ops:
            return 0.0
        return sum(union_ns(evs) for evs in self.ops.values()) / len(
            self.ops) / 1e9


def op_name(raw: str) -> str:
    """`%fusion.750 = bf16[32,50304]{...} fusion(...)` -> `fusion`."""
    head = raw.split(" =")[0].split("(")[0].strip().lstrip("%")
    return re.sub(r"[.\-_]?\d+$", "", head) or head


def module_name(raw: str) -> str:
    """`jit__decode_step(1234567)` -> `_decode_step`."""
    return re.sub(r"^jit_", "", raw.split("(")[0].strip())


def load(path: str, n_devices: int) -> Trace:
    """The table as the profiler recorded it; `clip()` makes it a reader's."""
    import jax.profiler

    pd = jax.profiler.ProfileData.from_file(path)
    raw, modules, host, stats = {}, {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1].split()[0])
            if dev >= n_devices:
                continue
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[dev] = [
                        (module_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
                elif line.name == OPS_LINE:
                    evs = []
                    for e in line.events:
                        evs.append((e.name, int(e.start_ns),
                                    int(e.duration_ns)))
                        if e.name not in stats:
                            stats[e.name] = {k: str(v)[:200]
                                             for k, v in e.stats}
                    raw[dev] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.name, int(e.start_ns), int(e.duration_ns))
                         for e in line.events if e.name.startswith("bench.")]
    ops = {dev: _name_ops(evs, modules.get(dev, []), stats)
           for dev, evs in raw.items()}
    return Trace(ops=ops, modules=modules, stats=stats,
                 host=sorted(host, key=lambda e: e[1]))


def clip(tr: Trace, lo: int, hi: int) -> Trace:
    """`tr` cut to the span [lo, hi) ns of its own clock: every operation
    trimmed to its part inside (one wholly outside is dropped). Programs'
    runs stay as recorded, for `whole_runs()` to tell a cut run by."""
    ops = {dev: [(n, max(t0, lo), min(t0 + d, hi) - max(t0, lo))
                 for n, t0, d in evs if t0 < hi and t0 + d > lo]
           for dev, evs in tr.ops.items()}
    return Trace(ops=ops, modules=tr.modules, host=tr.host, stats=tr.stats,
                 span=(lo, hi))


def _name_ops(ops, modules, stats):
    """Reduce raw op names; a Mosaic custom call takes its program's name."""
    mods = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for raw, t0, dur in sorted(ops, key=lambda e: e[1]):
        name = op_name(raw)
        if is_mosaic(raw, stats.get(raw, {})):
            while j + 1 < len(mods) and mods[j + 1][1] <= t0:
                j += 1
            prog = mods[j][0] if mods and mods[j][1] <= t0 else "?"
            name = "mosaic:" + prog
        out.append((name, t0, dur))
    return out


def is_mosaic(raw: str, stats: dict) -> bool:
    text = (raw + " " + " ".join(stats.values())).lower()
    return "custom-call" in raw and ("mosaic" in text or "tpu_custom_call"
                                     in text or "pallas" in text)


# ------------------------------------------------------------ arithmetic


def merged(events) -> list:
    """Disjoint [start, end) intervals covering the events, in order."""
    out = []
    for _, t0, dur in sorted(events, key=lambda e: e[1]):
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t0 + dur)
        else:
            out.append([t0, t0 + dur])
    return out


def union_ns(events) -> int:
    return sum(b - a for a, b in merged(events))


def op_seconds(tr: Trace, match) -> float:
    """Summed device time of the operations whose name `match` accepts,
    averaged over the devices."""
    if not tr.ops:
        return 0.0
    return sum(dur for evs in tr.ops.values() for n, _, dur in evs
               if match(n)) / len(tr.ops) / 1e9


def whole_runs(tr: Trace, match, dev: int) -> list:
    """(t0, dur, ops) of each WHOLE run on device `dev` of a program whose
    name `match` accepts, in order, `ops` being the operations that began
    inside it. Whole: inside the span, and neither the first nor the last
    run of any program that the profiler recorded on the device (the head of
    this file says why). Every number per step is taken from these and from
    nothing else."""
    lo, hi = tr.span or (float("-inf"), float("inf"))
    ops = sorted(tr.ops.get(dev, []), key=lambda e: e[1])
    out, j = [], 0
    for name, t0, dur in sorted(tr.modules.get(dev, []),
                                key=lambda m: m[1])[1:-1]:
        if not match(name) or t0 < lo or t0 + dur > hi:
            continue
        while j < len(ops) and ops[j][1] < t0:
            j += 1
        k = j
        while k < len(ops) and ops[k][1] < t0 + dur:
            k += 1
        out.append((t0, dur, ops[j:k]))
    return out


def inside_whole_runs(tr: Trace, match) -> tuple:
    """(table, n): `tr` with only the operations inside whole runs of the
    program `match` accepts, and the mean number of such runs a device. A
    reader sets `op_seconds` or `exposed_seconds` of that table against the
    work of n steps, so that time and work are of the same steps."""
    runs = {dev: whole_runs(tr, match, dev) for dev in tr.ops}
    ops = {dev: [e for _, _, evs in rs for e in evs]
           for dev, rs in runs.items()}
    return (Trace(ops=ops, stats=tr.stats, span=tr.span),
            sum(map(len, runs.values())) / len(runs) if runs else 0)


def module_ms(tr: Trace, match) -> list:
    """Busy milliseconds inside each whole run of a program whose name
    `match` accepts (the union of its operations, so gaps inside it do not
    count), on the first device."""
    if not tr.modules:
        return []
    return [union_ns(ops) / 1e6
            for _, _, ops in whole_runs(tr, match, min(tr.modules))]


def exposed_seconds(tr: Trace, match) -> float:
    """Time of the operations `match` accepts during which no OTHER
    operation runs on the same device, averaged over the devices."""
    if not tr.ops:
        return 0.0
    total = 0
    for evs in tr.ops.values():
        mine = merged([e for e in evs if match(e[0])])
        rest = merged([e for e in evs if not match(e[0])])
        covered, j = 0, 0
        for a, b in mine:
            while j < len(rest) and rest[j][1] <= a:
                j += 1
            k = j
            while k < len(rest) and rest[k][0] < b:
                covered += min(rest[k][1], b) - max(rest[k][0], a)
                k += 1
        total += sum(b - a for a, b in mine) - covered
    return total / len(tr.ops) / 1e9


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def is_kernel(name: str) -> bool:
    """A Mosaic (Pallas) kernel, as `load()` names it."""
    return name.startswith("mosaic:")


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The ten operations that took most device time in the span (first
    device), and the device's idle gaps there summed by the bench span the
    host was in. What lies idle between the span's edges and its first and
    last operation is `_span_edge_` (the profiler arms the device some
    milliseconds after `start_trace` returns: idle by this count, though
    nobody watched). The gaps sum to the span less the busy time."""
    if not tr.ops:
        return {"device_ops": [], "idle_gaps": []}
    dev = min(tr.ops)
    by_name = {}
    for n, _, dur in tr.ops[dev]:
        by_name[n] = by_name.get(n, 0) + dur
    gaps = {}
    ivs = merged(tr.ops[dev])
    spans = sorted(tr.host, key=lambda e: e[1])
    j = 0
    for (_, a_end), (b_start, _) in zip(ivs, ivs[1:]):
        mid = (a_end + b_start) // 2
        while j + 1 < len(spans) and spans[j][1] + spans[j][2] < mid:
            j += 1
        inside = spans and spans[j][1] <= mid <= spans[j][1] + spans[j][2]
        key = spans[j][0] if inside else "_none_"
        gaps[key] = gaps.get(key, 0) + (b_start - a_end)
    if tr.span and ivs:
        gaps["_span_edge_"] = (ivs[0][0] - tr.span[0]) + (tr.span[1]
                                                          - ivs[-1][1])
    rank = lambda d: [[k, v / 1e9] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_name), "idle_gaps": rank(gaps)}
