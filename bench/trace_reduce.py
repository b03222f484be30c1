"""From the profiler's trace to numbers: the one reduction every PR shares.

`load()` reads a jax.profiler `.xplane.pb` into an event table (per device:
the operations and the programs that ran, each with start and duration in
nanoseconds; plus the benchmark's own `bench.*` host spans on the same
clock). Everything else here is arithmetic on that table, so tests check it
on a small recorded table (tests/data/trace_events.json) without a chip.

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
    window_s: float = 0.0
    ops: dict = field(default_factory=dict)       # device -> [(name, t0, dur)]
    modules: dict = field(default_factory=dict)   # device -> [(name, t0, dur)]
    host: list = field(default_factory=list)      # [(name, t0, dur)] bench.*
    stats: dict = field(default_factory=dict)     # op name -> first event's stats

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
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


def load(path: str, n_devices: int, window_s: float = 0.0) -> Trace:
    import jax.profiler

    pd = jax.profiler.ProfileData.from_file(path)
    tr = Trace(window_s=window_s)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1].split()[0])
            if dev >= n_devices:
                continue
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    tr.modules[dev] = [
                        (module_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
                elif line.name == OPS_LINE:
                    evs = []
                    for e in line.events:
                        evs.append((e.name, int(e.start_ns),
                                    int(e.duration_ns)))
                        if e.name not in tr.stats:
                            tr.stats[e.name] = {k: str(v)[:200]
                                                for k, v in e.stats}
                    tr.ops[dev] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                tr.host += [(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith("bench.")]
    for dev, evs in tr.ops.items():
        tr.ops[dev] = _name_ops(evs, tr.modules.get(dev, []), tr.stats)
    tr.host.sort(key=lambda e: e[1])
    return tr


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


def module_ms(tr: Trace, match) -> list:
    """Busy milliseconds inside each run of a program whose name `match`
    accepts (the union of its operations, so gaps inside it do not count),
    on the first device."""
    if not tr.modules:
        return []
    dev = min(tr.modules)
    ivs = merged(tr.ops.get(dev, []))
    out, j = [], 0
    for name, t0, dur in sorted(tr.modules[dev], key=lambda m: m[1]):
        if not match(name):
            continue
        while j < len(ivs) and ivs[j][1] <= t0:
            j += 1
        busy, k = 0, j
        while k < len(ivs) and ivs[k][0] < t0 + dur:
            busy += min(ivs[k][1], t0 + dur) - max(ivs[k][0], t0)
            k += 1
        out.append(busy / 1e6)
    return out


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


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The ten operations that took most device time (first device), and
    the device's idle gaps summed by the bench span the host was in."""
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
    rank = lambda d: [[k, v / 1e9] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_name), "idle_gaps": rank(gaps)}
