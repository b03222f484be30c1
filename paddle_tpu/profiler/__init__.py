"""Profiler: one span primitive, on the clock the device trace can be
matched to, and Paddle's `paddle.profiler` surface over it.

Reference three-tier design (SURVEY.md §5.1): host events (RecordEvent,
event_tracing.h), device events (CUPTI tracer), aggregation
(python/paddle/profiler/profiler.py:358). TPU-native: the device side is
`jax.profiler` (XLA's xplane); the host side is ONE recorder here.

A span is kept twice. In memory, in a bounded ring of tuples

    (name, t0_ns, t1_ns, span_id, parent_id, step_id, request_id, attrs)

stamped with `time.perf_counter_ns()` (the clock a caller timing
`engine.step()` from outside reads, so the two compare without
conversion); parent = the span open on this thread when this one opened.
And as a `jax.profiler.TraceAnnotation`, so the same span lies in the
`.xplane.pb` of any live `jax.profiler` session, on the device trace's
clock.

Two classes of site, no switch and no environment variable:

  - per-step sites (`span`) record only while a profiler session is live.
    That is decided once per step, by `step_span` at the top of
    `ServingEngine.step` and `TrainStep.__call__`
    (`TraceAnnotation.is_enabled()`), and held in the module-level
    `recording` that every site tests. Not recording, `span()` returns
    the one shared `NO_SPAN`: no allocation, no clock read.
  - once-per-program sites (`always_span`, `record`): a compile, a build,
    a load, the import. At most once per compiled program or constructed
    object, milliseconds to seconds: they always record.

`Profiler` is the operator's handle (scheduler states, chrome-trace
export, a self-time table); it starts and stops a `jax.profiler` session
and reads the same ring.
"""

from __future__ import annotations

import time

# the package's import span starts here: before jax, which this module
# needs and `paddle_tpu/__init__.py` imports next
LOADED_NS = time.perf_counter_ns()

import collections
import itertools
import json
import os
import tempfile
import threading
from enum import Enum
from typing import Callable, Optional

import jax
from jax.profiler import TraceAnnotation

RING_SPANS = 1 << 16           # spans kept; the oldest go first

_ring: collections.deque = collections.deque(maxlen=RING_SPANS)
_tls = threading.local()
_next_id = itertools.count(1).__next__
_now = time.perf_counter_ns

recording = False              # per-step sites: decided by step_span()


def _stack() -> list:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        return _tls.stack


class _NoSpan:
    """What a per-step site gets when nothing records."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    begin = __enter__

    def end(self) -> None:
        pass

    def set(self, **attrs) -> None:
        pass


NO_SPAN = _NoSpan()


class Span:
    """One open span: a context manager, or begin()/end() where the
    spanned lines sit in a loop body that a `with` would have to indent
    (a span left open by an exception goes when its parent closes). A
    child opened without a step or request number takes its parent's."""

    __slots__ = ("name", "step_id", "request_id", "attrs", "span_id",
                 "parent_id", "t0", "_depth", "_ann")

    def __init__(self, name: str, step_id=None, request_id=None,
                 attrs: Optional[dict] = None):
        self.name = name
        self.step_id = step_id
        self.request_id = request_id
        self.attrs = attrs or None

    def __enter__(self):
        stack = _stack()
        self.parent_id = None
        if stack:
            parent = stack[-1]
            self.parent_id = parent.span_id
            if self.step_id is None:
                self.step_id = parent.step_id
            if self.request_id is None:
                self.request_id = parent.request_id
        self.span_id = _next_id()
        self._depth = len(stack)
        stack.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, *exc):
        t1 = _now()
        self._ann.__exit__(*exc)
        # a child that an exception left open goes with its parent
        del _stack()[self._depth:]
        _ring.append((self.name, self.t0, t1, self.span_id, self.parent_id,
                      self.step_id, self.request_id, self.attrs))
        return False

    begin = __enter__

    def end(self) -> None:
        self.__exit__(None, None, None)

    def set(self, **attrs) -> None:
        """Attributes known only after the span opened (a shape key)."""
        self.attrs = {**self.attrs, **attrs} if self.attrs else attrs


def step_span(name: str, step_id):
    """The root span of one engine or train step. Decides, once for the
    step, whether its per-step sites record: they do while a
    `jax.profiler` session is live."""
    global recording
    recording = TraceAnnotation.is_enabled()
    return Span(name, step_id) if recording else NO_SPAN


def span(name: str, request_id=None, **attrs):
    """A per-step site."""
    if not recording:
        return NO_SPAN
    return Span(name, None, request_id, attrs)


def always_span(name: str, **attrs) -> Span:
    """A once-per-program site: a compile, a build, a load."""
    return Span(name, None, None, attrs)


def stamp() -> int:
    """An instant on the spans' clock, for a span that opens in one call
    and closes in another (a request's wait in the queue)."""
    return _now()


def record(name: str, t0_ns: int, t1_ns: Optional[int] = None,
           request_id=None, **attrs) -> None:
    """A span given by its instants after the fact (`t1_ns` None: now).
    It began outside whatever is open now, so it has no parent; it takes
    the open span's step number. The profiler's own trace cannot be
    given a span of the past: such a span is in the ring only."""
    stack = _stack()
    _ring.append((name, t0_ns, _now() if t1_ns is None else t1_ns,
                  _next_id(), None, stack[-1].step_id if stack else None,
                  request_id, attrs or None))


def spans() -> list:
    """The ring's spans, oldest first: tuples (name, t0_ns, t1_ns,
    span_id, parent_id, step_id, request_id, attrs), closing order."""
    return list(_ring)


def clear() -> None:
    _ring.clear()


# A training step's counters are outputs of its program, accumulated on
# the device in the model's buffers. The newest TrainStep whose model counts
# publishes a way to read them; nothing touches the device until someone asks.
_read_step_counters: Optional[Callable[[], dict]] = None


def publish_step_counters(read: Optional[Callable[[], dict]]) -> None:
    """`read() -> {name: device scalar}`, or None where the step that
    published is gone; the process has one such read, the newest."""
    global _read_step_counters
    _read_step_counters = read


def step_counters() -> dict:
    """{name: float, or a list of floats for a count kept step by step} of
    the published step's counters, fetched from the device in one transfer
    now; empty where no step counts."""
    counts = _read_step_counters() if _read_step_counters else None
    if not counts:
        return {}
    return {k: float(v) if v.ndim == 0 else v.astype(float).tolist()
            for k, v in jax.device_get(counts).items()}


# What a program's TRACE chose, counted where the choice is made (a custom
# VJP's backward, a dispatch on shapes): Python integers bumped once a
# compile. No output of any program, nothing read from the device, nothing
# a step.
_traced: collections.Counter = collections.Counter()


def count_traced(name: str) -> None:
    _traced[name] += 1


def traced_counts() -> dict:
    """{name: times a trace took that path} since the process started."""
    return dict(_traced)


def self_ns(recorded=None) -> dict:
    """span_id -> the span's duration less what its children cover."""
    recorded = spans() if recorded is None else recorded
    own = {s[3]: s[2] - s[1] for s in recorded}
    for s in recorded:
        if s[4] in own:
            own[s[4]] -= s[2] - s[1]
    return own


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class RecordEvent:
    """A user's span (reference event_tracing.h RecordEvent): a context
    manager, or begin()/end(). Like the reference's it records while a
    profiler is on, which here is any live `jax.profiler` session."""

    def __init__(self, name: str, event_type: str = "UserDefined"):
        self.name = name
        self.event_type = event_type
        self._span = None

    def begin(self):
        if self._span is None and TraceAnnotation.is_enabled():
            self._span = Span(self.name, None, None,
                              {"type": self.event_type}).begin()

    def end(self):
        if self._span is not None:
            self._span.end()
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(closed: int = 0, ready: int = 0, record: int = 1,
                   repeat: int = 0, skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Reference: profiler.py make_scheduler — step-indexed state machine."""
    cycle = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


class Profiler:
    """Reference: python/paddle/profiler/profiler.py:358. While its
    scheduler says RECORD a `jax.profiler` session is live, written under
    `trace_dir` (a new directory under the system's temporary one when
    None): host spans and device operations in one `.xplane.pb`, and the
    program's spans in the ring. `timer_only` starts no session, so
    nothing records."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, trace_dir: Optional[str] = None):
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        if scheduler is None:
            self.scheduler = lambda step: ProfilerState.RECORD
        elif isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self.scheduler = lambda step: (
                ProfilerState.RECORD if lo <= step < hi else ProfilerState.CLOSED)
        else:
            self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.trace_dir = trace_dir
        self.step_num = 0
        self.state = ProfilerState.CLOSED
        self._session = False
        self._t_start = _now()

    # -------------------------------------------------------------- control

    def start(self):
        self._t_start = _now()
        self.state = self.scheduler(self.step_num)
        self._follow(self.state)

    def stop(self):
        self._follow(ProfilerState.CLOSED)
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def step(self):
        self.step_num += 1
        self.state = self.scheduler(self.step_num)
        self._follow(self.state)

    def _follow(self, state):
        """The session is live exactly while the scheduler says record.
        What `jax.profiler` raises (a session already live, a directory
        that cannot be written) reaches the caller."""
        want = not self.timer_only and state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if want and not self._session:
            if self.trace_dir is None:
                self.trace_dir = tempfile.mkdtemp(prefix="paddle_tpu_trace_")
            jax.profiler.start_trace(self.trace_dir)
            self._session = True
        elif not want and self._session:
            self._session = False
            jax.profiler.stop_trace()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -------------------------------------------------------------- export

    def spans(self) -> list:
        """The ring's spans that opened since start()."""
        return [s for s in spans() if s[1] >= self._t_start]

    def export_chrome_tracing(self, path: str):
        """The program's spans since start() as a chrome trace (reference
        chrometracing_logger.cc), microseconds of `perf_counter_ns`. The
        session's own `.xplane.pb`, device operations included, is under
        `trace_dir`."""
        events = [{
            "name": name, "ph": "X", "ts": t0 / 1e3, "dur": (t1 - t0) / 1e3,
            "pid": 0, "tid": 0,
            "args": {"span_id": sid, "parent_id": parent, "step_id": step,
                     "request_id": request, **(attrs or {})},
        } for name, t0, t1, sid, parent, step, request, attrs in self.spans()]
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f, default=str)
        return path

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Per span name: calls, total, and SELF time (a span's duration
        less what its children cover; reference profiler_statistic.py),
        largest self time first."""
        recorded = self.spans()
        own = self_ns(recorded)
        agg = {}
        for name, t0, t1, sid, *_ in recorded:
            a = agg.setdefault(name, [0, 0, 0])
            a[0] += 1
            a[1] += t1 - t0
            a[2] += own[sid]
        lines = [f"{'Name':<32}{'Calls':>8}{'Total(ms)':>12}{'Self(ms)':>12}"
                 f"{'Avg(ms)':>12}"]
        for name, (n, tot, slf) in sorted(agg.items(),
                                          key=lambda kv: -kv[1][2]):
            lines.append(f"{name:<32}{n:>8}{tot / 1e6:>12.3f}"
                         f"{slf / 1e6:>12.3f}{tot / n / 1e6:>12.3f}")
        table = "\n".join(lines)
        print(table)
        return table


def export_chrome_tracing(dir_name: str, worker_name: str = None):
    """on_trace_ready factory (reference profiler.py export_chrome_tracing)."""

    def handler(prof: Profiler):
        os.makedirs(dir_name, exist_ok=True)
        fname = f"{worker_name or 'worker'}_{int(time.time())}.json"
        prof.export_chrome_tracing(os.path.join(dir_name, fname))

    return handler


class SortedKeys(Enum):
    """Reference profiler SortedKeys — summary table sort orders."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(Enum):
    """Reference profiler SummaryView — which summary tables to show."""

    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(profiler_result, path):
    """Persist a profiler result (reference export_protobuf). The chrome
    trace JSON is the wire format here (one-compiler design: XLA's
    profiler speaks chrome-trace natively); the file is self-describing
    and load_profiler_result round-trips it."""
    data = (profiler_result if isinstance(profiler_result, dict)
            else getattr(profiler_result, "trace", profiler_result))
    with open(path, "w") as f:
        json.dump(data, f)


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)
