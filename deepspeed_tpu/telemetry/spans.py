"""Host spans that land in ALL views of the system.

``profiling/trace.py`` ``annotate`` puts a named range into the xplane /
Perfetto timeline (the deep per-capture view); the registry histograms
are the always-on aggregate view; and when a request trace is active
(telemetry/tracing.py ``current_span``), the same block becomes a child
span of that request's tree. ``span`` is the one spelling that feeds all
three, so instrumenting a code path once buys the profiler range, the
p50/p90/p99, AND the per-request attribution without a second
decoration pass.

The span log
------------
Every closed span also lands in one process-wide, bounded, in-memory
:class:`SpanLog` (next to ``get_registry()`` / ``get_event_ring()``):
a plain tuple ``(name, start, end, parent id, span id, key, attrs)``
per span, oldest dropped first, ``dropped`` counted. ``key`` is the
step number or request id that the spans of one unit share; times are
on the recorder's clock (``time.perf_counter`` unless the feeder was
handed another: the serving loop writes its own clock's readings, so a
fake-clock test sees fake times). :func:`span` writes to it; hot loops
that already hold both ends of an interval (``StepProfiler`` marks, the
server's request stamps, the engine's step timers, the compile watch)
call :meth:`SpanLog.record` directly: one tuple append, no lock (a
``deque`` append is atomic; a reader copies and retries). The names are
in docs/observability.md "Spans".

The same spans reach the profiler's ``/host:CPU`` plane as
``TraceMe`` events while a profiler session is running
(:func:`annotation`); with none running that costs one
``TraceMe.is_enabled()`` read.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from deepspeed_tpu.telemetry.events import SPAN_LOG_OVERFLOW, record_event
from deepspeed_tpu.telemetry.registry import (MetricRegistry, get_registry,
                                              sanitize_metric_name)
from deepspeed_tpu.telemetry.tracing import TraceSpan, current_span

SPAN_HISTOGRAM = "span_duration_seconds"

# positions in a span record
NAME, START, END, PARENT, ID, KEY, ATTRS = range(7)
SpanRecord = Tuple[str, float, float, int, int, object, Optional[dict]]


class SpanLog:
    """Bounded log of closed spans (see the module docstring).

    ``capacity`` defaults to 524,288 records, five traced runs of the
    fastest cell: a traced chat run leaves 101,296 records (my chip run,
    PR 40: 10,969 ``serve:step`` spans, worked steps and the first poll
    of each lull, over the lead-in, the 50 s window and the traced
    second schedule, each with up to 9 phase spans, plus 290 requests x
    4), where the 65,536 of before had lost 35,688 and with them the
    window's first 16 s. The deque grows only as records arrive (about
    0.15 GB of host memory when full). The first record dropped leaves
    one ``span_log_overflow`` event in the flight-recorder ring. One
    writer thread per feeder is assumed (the serving loop, the train
    loop); ids come from one atomic counter, so feeders on different
    threads never collide."""

    def __init__(self, capacity: int = 524288,
                 clock: Callable[[], float] = time.perf_counter):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.clock = clock
        self._buf: deque = deque(maxlen=self.capacity)
        self._ids = itertools.count(1)
        self.dropped = 0

    def next_id(self) -> int:
        """An id for a span that is still open (its children record
        ``parent=<this id>`` before it closes)."""
        return next(self._ids)

    def record(self, name: str, start: float, end: float, parent: int = 0,
               key=None, attrs: Optional[dict] = None,
               span_id: int = 0) -> int:
        """Append one closed span; returns its id."""
        sid = span_id or next(self._ids)
        buf = self._buf
        if len(buf) == self.capacity:
            self._drop(1)
        buf.append((name, start, end, parent, sid, key, attrs))
        return sid

    def _drop(self, n: int) -> None:
        if not self.dropped:
            record_event(SPAN_LOG_OVERFLOW, capacity=self.capacity)
        self.dropped += n

    def extend(self, records: List[SpanRecord]) -> None:
        """Append records the caller built in this log's layout (ids
        from :meth:`next_id`)."""
        buf = self._buf
        over = len(buf) + len(records) - self.capacity
        if over > 0:
            self._drop(over)
        buf.extend(records)

    def __len__(self) -> int:
        return len(self._buf)

    def snapshot(self, prefix: Optional[str] = None,
                 since: Optional[float] = None) -> List[SpanRecord]:
        """A copy, oldest first, optionally cut to names starting with
        ``prefix`` and to spans that ended at or after ``since``."""
        while True:
            try:
                out = list(self._buf)
                break
            except RuntimeError:      # a writer appended mid-copy
                continue
        if prefix is not None:
            out = [r for r in out if r[NAME].startswith(prefix)]
        if since is not None:
            out = [r for r in out if r[END] >= since]
        return out

    def clear(self) -> None:
        self._buf.clear()
        self.dropped = 0

    def stats(self) -> dict:
        return {"capacity": self.capacity, "records": len(self._buf),
                "dropped": self.dropped}


_log = SpanLog()


def get_span_log() -> SpanLog:
    """The process-wide span log."""
    return _log


def set_span_log(log: SpanLog) -> SpanLog:
    """Swap the process log (tests); returns the previous one."""
    global _log
    prev, _log = _log, log
    return prev


_TraceMe = None


def _traceme():
    """jax's ``TraceMe`` class, or False where jax has none."""
    global _TraceMe
    if _TraceMe is None:
        try:
            from jax._src.lib import _profiler
            _TraceMe = _profiler.TraceMe
        except Exception:  # noqa: BLE001 — spans must work without jax
            _TraceMe = False
    return _TraceMe


def annotation(name: str, **metadata):
    """An ENTERED profiler range named ``name`` when a profiler session
    is running, else None. The caller closes it with
    ``a.__exit__(None, None, None)`` (after an optional
    ``a.set_metadata(...)``)."""
    tm = _traceme()
    if not tm or not tm.is_enabled():
        return None
    a = tm(name, **metadata)
    a.__enter__()
    return a


# the innermost span() open in this context (its id in the span log)
_OPEN: "contextvars.ContextVar[int]" = contextvars.ContextVar(
    "ds_open_span", default=0)


@contextlib.contextmanager
def span(name: str, registry: Optional[MetricRegistry] = None,
         labels: Optional[Dict[str, str]] = None,
         parent: Optional[TraceSpan] = None, key=None):
    """``with span("prefill"): ...`` — profiler annotation + histogram
    (+ a child of the active request trace, when one exists).

    The profiler annotation is best-effort: span timing must survive
    environments where jax (or its profiler) is unavailable, because the
    histograms are the production signal and the trace is the debugging
    one. An exception inside the block is recorded on the trace span as
    an ``error`` attribute, the span still closes (no leaked profiler
    annotation or half-open tree), and the exception propagates.

    ``parent`` overrides the context-propagated anchor — pass an
    explicit :class:`TraceSpan` to nest under a span other than the
    innermost active one. Yields the trace child span (None when no
    trace is active) so the caller can ``.set()`` attributes on it.

    The closed span also lands in the process span log, parented under
    the innermost ``span()`` open in this context, with ``key`` (the
    step or request the span belongs to) and ``labels`` as attributes.
    """
    reg = registry or get_registry()
    hist = reg.histogram(
        SPAN_HISTOGRAM,
        help="host span wall time, by span name (see telemetry.spans)",
        labels={"span": name, **(labels or {})})
    ctx = contextlib.nullcontext()
    try:
        from deepspeed_tpu.profiling.trace import annotate
        ctx = annotate(name)
    except Exception:  # noqa: BLE001 — profiler optional, histogram is not
        pass
    anchor = parent if parent is not None else current_span()
    tspan = None
    if anchor is not None:
        tspan = anchor.trace.begin(name, parent=anchor)
    log = _log
    sid = log.next_id()
    outer = _OPEN.get()
    token = _OPEN.set(sid)
    t0 = log.clock()
    try:
        with ctx:
            if tspan is None:
                yield tspan
            else:
                # advance the context anchor: a span() nested inside
                # this block must parent under THIS span, not attach as
                # its sibling
                with tspan.trace.activate(tspan):
                    yield tspan
    except BaseException as e:  # noqa: BLE001 — recorded, then re-raised
        if tspan is not None:
            tspan.set("error", type(e).__name__)
        raise
    finally:
        t1 = log.clock()
        _OPEN.reset(token)
        log.record(name, t0, t1, parent=outer, key=key, attrs=labels,
                   span_id=sid)
        hist.observe(t1 - t0)
        if tspan is not None:
            anchor.trace.end_span(tspan)


def timed(fn: Optional[Callable] = None, *, name: Optional[str] = None,
          registry: Optional[MetricRegistry] = None):
    """``@timed`` / ``@timed(name="phase")`` — function-scoped ``span``
    (the ``instrument`` decorator's metrics-aware sibling)."""
    def deco(f):
        span_name = sanitize_metric_name(
            name or getattr(f, "__qualname__", f.__name__))

        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            with span(span_name, registry=registry):
                return f(*args, **kwargs)
        return wrapper

    return deco(fn) if fn is not None else deco
