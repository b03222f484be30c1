"""The served step's idle device, put down to what the host was doing.

A synchronous engine step launches a program, waits for it, reads its tokens
and only then prepares the next launch, so the device rests from the end of
one step's last event to the start of the next step's program. This module
splits that rest (README-idle.md defines every term) from three records of
one traced run, and launches nothing to do so:

  - `ctx["trace"]`: the device plane, on the trace's clock. Per step k,
    `s_k` = the start of the first runner program the step launched (a whole
    run, `trace_reduce.whole_runs`) and `e_k` = the end of the last device
    event before `s_{k+1}` (the argmax pass belongs to step k);
  - the program's spans, on the bench's clock: `d_k` = the enter of the
    step's first `runner.dispatch`, `r_k` = the exit of its last
    `drain.fetch` that brought tokens (the counts' fetch comes after it);
  - `ctx["steps"]`, through `program_spans.align()`: the anchors, which say
    which device runs are which step's (to a millisecond, which is enough to
    tell steps apart and too coarse to time anything).

Nothing starts on the device before the host enters the call that dispatches
it, and the host holds no value before the device has made it. So the ONE
offset `delta` with device time = bench time + `delta` obeys

    max_k (e_k - r_k)  <=  delta  <=  min_k (s_k - d_k)

over the decode-only steps: two causal fences. The device's idle time a step
and the host's turnaround `d_{k+1} - r_k` each live on one clock and do not
depend on `delta`; what is left of the rest, `s_{k+1} - e_k` less the
turnaround, is the launch's latency plus the fetch's, and only how it splits
between the two carries the fence's width.

A program without `runner.dispatch` / `drain.fetch` spans (an older commit)
gives `device_idle_per_step_ms` alone; without `runner.launch` spans, nothing.
"""

from __future__ import annotations

import bisect
import statistics

import program_spans as ps
import trace_reduce

NAME, T0, T1, SID, PARENT, STEP, ATTRS = (ps.NAME, ps.T0, ps.T1, ps.SID,
                                          ps.PARENT, ps.STEP, ps.ATTRS)
ROOT, LAUNCH, DISPATCH, FETCH = ("engine.step", "runner.launch",
                                 "runner.dispatch", "drain.fetch")
METRICS = ("device_idle_per_step_ms", "host_turnaround_ms",
           "launch_dispatch_ms", "drain_fetch_ms", "clock_fence_width_us")
# the host's turnaround by part: the span whose own time it is -> its name
# in the printed table (any other span keeps its own name)
PARTS = {"engine.commit": "commit", "engine.step": "step_tail",
         "engine.plan": "plan", "engine.build_batch": "build_batch",
         "runner.account": "account", "runner.stage": "stage",
         "runner.launch": "launch_other", "engine.drain": "drain_tail",
         "drain.fetch": "counts_fetch"}
BETWEEN = "between_steps"


def is_runner_program(name: str) -> bool:
    """A program a `runner.launch` dispatches (`model_runner._jitted`), as
    the trace names it; the drain's eager passes are not."""
    return name.endswith("_step") or name == "_ragged_core"


class Busy:
    """One device's busy intervals, for the union inside any [a, b)."""

    def __init__(self, events):
        ivs = trace_reduce.merged(events)
        self.starts = [a for a, _ in ivs]
        self.ends = [b for _, b in ivs]
        self.before = [0]               # busy ns before interval i
        for a, b in ivs:
            self.before.append(self.before[-1] + b - a)

    def inside(self, a, b) -> int:
        """Busy ns inside [a, b)."""
        return self._upto(b) - self._upto(a)

    def _upto(self, t) -> int:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0
        return self.before[i - 1] + min(self.ends[i - 1], t) - self.starts[i - 1]

    def last_end_before(self, t, floor):
        """The end of the last busy interval that starts in [floor, t), cut
        to t; None where none does."""
        i = bisect.bisect_left(self.starts, t)
        if i == 0 or self.starts[i - 1] < floor:
            return None
        return min(self.ends[i - 1], t)


def own_time(spans, a, b) -> dict:
    """Of [a, b), how long each of `spans` was the innermost open one, summed
    by name (`PARTS`); `spans` hold their own parents, as a step's do."""
    cut = {s[SID]: max(0, min(s[T1], b) - max(s[T0], a)) for s in spans}
    own = dict(cut)
    for s in spans:
        if s[PARENT] in own:
            own[s[PARENT]] -= cut[s[SID]]
    out = {}
    for s in spans:
        if own[s[SID]] > 0:
            key = PARTS.get(s[NAME], s[NAME])
            out[key] = out.get(key, 0) + own[s[SID]]
    return out


def steps_of(ctx) -> list:
    """The traced engine steps that launched something, in order: dicts with
    the root span, its spans, whether every launch was a decode, `d` and `r`
    (ns, bench clock; None where the program has no such span) and `s` (ns,
    trace clock: the start of the first program the step launched; None
    where its runs are not whole or do not match its launches)."""
    sp, tr = ps.of(ctx), ctx.get("trace")
    al = sp.align()
    if tr is None or not tr.modules or al is None:
        return []
    mine = {}
    for s in sp.traced:
        mine.setdefault(sp.root(s)[SID], []).append(s)
    out = []
    for root in sp.steps(ROOT):
        spans = mine[root[SID]]
        launches = [s for s in spans if s[NAME] == LAUNCH]
        if not launches:
            continue
        named = lambda name: [s for s in spans if s[NAME] == name]
        fetched = [s[T1] for s in named(FETCH)
                   if (s[ATTRS] or {}).get("what") != "counts"]
        out.append({
            "root": root, "spans": spans, "launches": len(launches),
            "decode_only": all(str((s[ATTRS] or {}).get("kind", "")
                                   ).startswith("decode") for s in launches),
            "d": min((s[T0] for s in named(DISPATCH)), default=None),
            "r": max(fetched, default=None), "s": None})
    dev = min(tr.modules)
    held = ps.runs_held(
        trace_reduce.whole_runs(tr, is_runner_program, dev),
        [(st["root"][T0] + al[0], st["root"][T1] + al[0]) for st in out])
    for st, runs in zip(out, held):
        if len(runs) == st["launches"]:
            st["s"] = runs[0][0]
    return out


def gaps_of(ctx, steps) -> list:
    """One dict for each pair of consecutive steps k, k+1 that both have an
    `s`: the device's time from `s_k` to `s_{k+1}` (all on the trace's
    clock) and, where the spans are there, the host's from `r_k` to
    `d_{k+1}` with its parts (bench clock)."""
    tr = ctx["trace"]
    busy = Busy(tr.ops.get(min(tr.modules), []))
    out = []
    for a, b in zip(steps, steps[1:]):
        if a["s"] is None or b["s"] is None or \
                b["root"][STEP] != a["root"][STEP] + 1:
            continue
        e = busy.last_end_before(b["s"], a["s"])
        if e is None:
            continue
        worked = busy.inside(a["s"], b["s"])
        gap = {"this": a, "next": b, "e": e,
               "decode_only": a["decode_only"] and b["decode_only"],
               "idle": b["s"] - a["s"] - worked,
               "in_step_idle": e - a["s"] - worked,
               "turnaround": None, "parts": {}}
        if a["r"] is not None and b["d"] is not None:
            gap["turnaround"] = b["d"] - a["r"]
            parts = own_time(a["spans"] + b["spans"], a["r"], b["d"])
            parts[BETWEEN] = b["root"][T0] - a["root"][T1]
            gap["parts"] = parts
        out.append(gap)
    return out


def fence(steps, gaps):
    """(lo, hi, lo_step, hi_step): `delta`'s interval in ns from the
    decode-only steps, and the numbers of the steps that set its edges; None
    where no step gives an edge."""
    highs = [(st["s"] - st["d"], st["root"][STEP]) for st in steps
             if st["decode_only"] and st["s"] is not None
             and st["d"] is not None]
    lows = [(g["e"] - g["this"]["r"], g["this"]["root"][STEP]) for g in gaps
            if g["this"]["decode_only"] and g["this"]["r"] is not None]
    if not highs or not lows:
        return None
    (lo, lo_step), (hi, hi_step) = max(lows), min(highs)
    return lo, hi, lo_step, hi_step


def _fence_report(ctx, steps, gaps):
    """(what the printed line says of the fence, why there is no `delta` or
    None, `delta` in ns or None)."""
    fen = fence(steps, gaps)
    if fen is None:
        return None, ("no runner.dispatch / drain.fetch span in a "
                      "decode-only step: the program records none"), None
    lo, hi, lo_step, hi_step = fen
    anchors = ps.of(ctx).align()[0]
    said = {"lo_ns": lo, "hi_ns": hi, "width_us": (hi - lo) / 1e3,
            "lo_step": lo_step, "hi_step": hi_step,
            "anchor_offset_ns": anchors,
            "anchor_inside": bool(lo <= anchors <= hi)}
    if lo > hi:
        return said, (
            f"empty interval: step {lo_step}'s fetch returned "
            f"{(lo - hi) / 1e3:.1f} us before step {hi_step}'s dispatch "
            "allows; a device event after a step's fetch, or steps and runs "
            "mismatched"), None
    said["delta_ns"] = (lo + hi) / 2
    return said, None, said["delta_ns"]


def _table(rows, delta, half_width_ms):
    """The medians over `rows` (gaps of one kind); None where there are
    none. What needs the spans is left out where they are absent, what
    needs `delta` where it is None."""
    if not rows:
        return None
    med_ms = lambda values: statistics.median(values) / 1e6
    t = {"gaps": len(rows),
         "device_idle_per_step_ms": med_ms(g["idle"] for g in rows),
         "in_step_idle_ms": med_ms(g["in_step_idle"] for g in rows),
         "rest_ms": med_ms(g["next"]["s"] - g["e"] for g in rows)}
    timed = [g for g in rows if g["turnaround"] is not None]
    if not timed:
        return t
    t["host_turnaround_ms"] = med_ms(g["turnaround"] for g in timed)
    t["turnaround_parts_ms"] = {
        n: med_ms(g["parts"].get(n, 0) for g in timed)
        for n in sorted({n for g in timed for n in g["parts"]})}
    if delta is None:
        return t
    t["launch_dispatch_ms"] = med_ms(
        g["next"]["s"] - g["next"]["d"] - delta for g in timed)
    t["drain_fetch_ms"] = med_ms(
        g["this"]["r"] + delta - g["e"] for g in timed)
    t["plus_minus_ms"] = half_width_ms
    # the identity, in medians: 0 but for what a median of sums differs
    # from the sum of medians by
    t["identity_residual_ms"] = (
        t["device_idle_per_step_ms"] - t["in_step_idle_ms"]
        - t["host_turnaround_ms"] - t["launch_dispatch_ms"]
        - t["drain_fetch_ms"])
    return t


def attribute(ctx):
    """Everything the five readers take, or None where there is nothing to
    read (no trace, no anchors, no launches). Computed once a run, and said
    then: one line on stdout, before the result line."""
    if "_idle_attribution" in ctx:
        return ctx["_idle_attribution"]
    steps = steps_of(ctx)
    gaps = gaps_of(ctx, steps) if steps else []
    out = None
    if gaps:
        said, why, delta = _fence_report(ctx, steps, gaps)
        half = said["width_us"] / 2e3 if said else None
        out = {"steps": len(steps),
               "steps_matched": sum(st["s"] is not None for st in steps),
               "fence": said, "why_no_fence": why,
               "decode_only": _table([g for g in gaps if g["decode_only"]],
                                     delta, half),
               "with_prefill": _table(
                   [g for g in gaps if not g["decode_only"]], delta, half),
               # against the run's own breakdown: all idle between whole
               # steps
               "idle_sum_ms": sum(g["idle"] for g in gaps) / 1e6,
               "idle_gaps_ms": 1e3 * sum(
                   secs for name, secs in trace_reduce.breakdown(
                       ctx["trace"])["idle_gaps"] if name != "_span_edge_")}
        print(f"[idle_attribution] {out}", flush=True)
    ctx["_idle_attribution"] = out
    return out


def metric(ctx, name: str):
    """One of `METRICS` over the decode-only steps, or None."""
    got = attribute(ctx)
    if got is None:
        return None
    if name == "clock_fence_width_us":
        fen = got["fence"]
        return fen["width_us"] if fen and "delta_ns" in fen else None
    return (got["decode_only"] or {}).get(name)
