"""Serving step observatory: per-step phase goodput accounting.

The training engine already answers "where did the step go" (PR 4's
:mod:`telemetry.goodput` splits every train step into data-wait /
device / host buckets that sum to wall by construction). The serving
loop had no such decomposition: ``ContinuousBatchingServer.step()``
ran admission, chunk selection, speculation proposal, device dispatch,
the sync wait, and commit/detokenize as one opaque wall interval —
exactly the measurement the async-serving-loop refactor (ROADMAP item
5) needs as its A/B baseline. :class:`StepProfiler` fills that gap
with the same discipline:

* **Phases sum to wall by construction.** A step is profiled as a
  chain of clock marks: every interval between two consecutive marks
  is attributed to exactly one named phase, and the tail between the
  last mark and ``finish()`` lands in ``other`` — so
  ``sum(phases) == wall`` is an identity, not an aspiration
  (tests/test_step_profile.py pins it on a fake clock).
* **Zero new device syncs.** Marks are monotonic-clock reads at
  boundaries the serving loop already crosses (the fetch that closes a
  decode step IS the existing ``np.asarray`` sync). With the profiler
  ON the decode/verify programs, their trace counts, and greedy output
  are untouched; OFF, the loop holds a no-op handle and records
  nothing.
* **Dispatch-gap detector.** The device is idle from the moment step
  N's result fetch completes until step N+1's program is dispatched —
  the host tax ROADMAP item 5's overlap refactor exists to remove.
  Every dispatch boundary (decode, verify, prefill, chunk) observes
  ``now - last_fetch`` into ``serve_dispatch_gap_seconds``; the
  cumulative gap is the exact wall-time budget an async loop can win
  back.
* **Commit lag awareness.** A serving step that runs at a commit lag
  above 0 (``inference.async_loop``, a step with no host state change
  to make) dispatches step N+1 BEFORE fetching step N, so a naive
  fetch→dispatch pairing would charge the lag-1 commit+publish work as
  device idle even though the device moved straight from N to N+1.
  The profiler counts dispatches outstanding (dispatched, not yet
  fetched): a dispatch issued while another program is still in flight
  observes a **zero** gap (the device had queued work — it never
  idled), and a fetch that leaves work outstanding does NOT open an
  idle span. Gaps are therefore always measured against the fetch
  that actually drained the device — the correct step's fetch, at any
  commit lag. A step the loop marks ``pipelined(since=...)`` credits
  device time for the whole window the device verifiably had work in
  flight (clamped to the step wall), keeping
  ``serve_goodput_fraction`` meaningful when dispatch/sync_wait host
  slivers no longer bound device activity.

Phase vocabulary (docs/observability.md "Serving goodput & KV-pool
accounting"):

``admission``       deadline reap, shedding, queue admission, the
                    preemption ladder (monolithic prefill compute runs
                    inside this phase; its device interval is still
                    device-attributed via :meth:`device_interval`)
``prefill_chunk``   chunk selection + one chunked-prefill program
``propose``         building the decode token batch; under speculation,
                    the per-slot prompt-lookup proposal scan
``dispatch``        host interval of the decode/verify program call
                    (JAX async dispatch returns before the device
                    finishes)
``sync_wait``       blocking on the step's tokens — the existing fetch
                    boundary, where the device actually computes
``commit``          accept/commit bookkeeping, EOS checks, retirement
``publish``         metric observations, ring events, SLO evaluation
``other``           the residual (finish tail) — near-zero by design

``serve_goodput_fraction`` is cumulative device-attributed time
(``dispatch`` + ``sync_wait`` + prefill/chunk device intervals) over
cumulative wall — the serving sibling of ``train_goodput_fraction``;
``1 - fraction`` is the host tax.

Every worked step also leaves its spans in the process span log
(:mod:`telemetry.spans`): one ``serve:step`` record and, parented under
it, one ``serve:<phase>`` record per mark interval, so the phase spans
of a step tile it exactly (the sum identity, on spans). ``dispatch``
spans carry the dispatch gap and chain depth observed at their opening
boundary, ``dispatch`` / ``sync_wait`` the watched program's name.
``Tracer.dump_timeline`` renders them as the "server host" track. While
a profiler session runs, the same intervals are ``TraceMe`` events on
``/host:CPU``: ``serve:step`` and, because a mark names an interval
only when it closes, ``serve:phase`` with the phase as its ``phase``
stat. A worked step slower than ``max(SLOW_STEP_S, SLOW_STEP_FACTOR x
running median)`` leaves one ``slow_step`` event in the flight-recorder
ring with its phases, chain depth and the program it waited on.

Beside all of that, and read by none of it, every device program the
loop launches leaves one ``serve:program`` record when the fetch that
proves it finished returns (:meth:`StepProfiler.program_launched`,
:meth:`StepProfiler.program_fetched`; docs/observability.md "Spans").

Host-pure: no jax import (the annotations go through
``telemetry.spans.annotation``). Config-gated by
``telemetry.step_profile`` (default ON — the cost is a handful of clock
reads, tuple appends and histogram observes per step).
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from statistics import median
from typing import Callable, Deque, Dict, List, Optional

from deepspeed_tpu.telemetry.registry import MetricRegistry, get_registry
from deepspeed_tpu.telemetry.spans import SpanLog, annotation, get_span_log

# THE phase vocabulary (module docstring); every span name derives from it
PHASES = ("admission", "prefill_chunk", "propose", "dispatch", "sync_wait",
          "commit", "publish", "other")
STEP_SPAN = "serve:step"
FLUSH_SPAN = "serve:flush"
PHASE_ANNOTATION = "serve:phase"
PROGRAM_SPAN = "serve:program"
# the request lifecycle the server records from its own stamps: the
# three phases tile the request, each requeue its own queue_wait
REQUEST_SPAN = "serve:request"
QUEUE_SPAN = "serve:queue_wait"
PREFILL_SPAN = "serve:prefill"
DECODE_SPAN = "serve:decode"
_PHASE_SPAN = {p: "serve:" + p for p in PHASES}

_UIDS = itertools.count(1)

# a worked step is slow when its wall passes both (flight-recorder
# ``slow_step`` event); the median runs over the last SLOW_STEP_WINDOW
# worked steps and needs SLOW_STEP_MIN_HISTORY of them
SLOW_STEP_S = 0.4
SLOW_STEP_FACTOR = 8.0
SLOW_STEP_WINDOW = 64
SLOW_STEP_MIN_HISTORY = 8

# phases whose whole interval is device-attributed (the program runs /
# the host blocks on it); prefill intervals attribute via
# device_interval() because they nest inside the admission phase
DEVICE_PHASES = frozenset({"dispatch", "sync_wait"})

# launched programs the FIFO behind ``serve:program`` holds at most (a
# chained prefill of a 33k-token prompt launches ~130 chunks in a step)
MAX_LAUNCHED = 4096


def _hist_p50(hist: Dict[int, int]) -> int:
    """Weighted median of an {value: count} histogram (0 when empty) —
    the observed-chain-depth p50 the commit-lag snapshot reports."""
    total = sum(hist.values())
    if not total:
        return 0
    half = (total + 1) // 2
    seen = 0
    for value in sorted(hist):
        seen += hist[value]
        if seen >= half:
            return value
    return max(hist)


class _NullStepHandle:
    """No-op handle the serving loop holds when profiling is off — the
    hot path keeps one shape (mark/finish calls) whether or not the
    profiler exists, and OFF costs a few no-op method calls per step."""

    __slots__ = ()

    def mark(self, phase: str, now: Optional[float] = None,
             dispatch: bool = False, fetch: bool = False,
             program: Optional[str] = None,
             note: Optional[dict] = None) -> None:
        return None

    def flush_span(self, t0: float, reason: str, programs: int) -> None:
        return None

    def device_interval(self, t0: float, t1: float,
                        note_dispatch: bool = True) -> None:
        return None

    def note_dispatch(self, now: float) -> None:
        return None

    def pipelined(self, since: Optional[float] = None) -> None:
        return None

    def pipelined_mode(self) -> None:
        return None

    def program_fetched(self, ticket: int, now: float,
                        since: Optional[float] = None) -> None:
        return None

    def finish(self, live: bool = True, slots: Optional[int] = None,
               admitted: Optional[int] = None, rider: bool = False) -> None:
        return None


NULL_STEP_HANDLE = _NullStepHandle()


class _StepHandle:
    """One step's phase accounting (reused across steps — ``begin()``
    resets it; the serving loop is single-threaded per server)."""

    __slots__ = ("_prof", "_t0", "_last", "acc", "device", "spans",
                 "seq", "span_id", "worked", "_pipelined_since",
                 "_pipelined_mode", "_dispatch_attrs", "program",
                 "_ann_step", "_ann_phase")

    def __init__(self, prof: "StepProfiler"):
        self._prof = prof
        self._t0 = 0.0
        self._last = 0.0
        self.acc: Dict[str, float] = {}
        self.device = 0.0
        # this step's closed span records (telemetry.spans layout),
        # written to the log at finish() — only then is it known
        # whether the step worked
        self.spans: List[tuple] = []
        self.seq = 0          # the step number: the spans' shared key
        self.span_id = 0      # id of this step's serve:step record
        # gap/depth observed at a dispatch boundary, for the dispatch
        # span that opens there; the program last dispatched or fetched
        self._dispatch_attrs: Optional[dict] = None
        self.program: Optional[str] = None
        self._ann_step = None
        self._ann_phase = None
        # did this step engage the device at all (decode/verify/prefill
        # dispatch)? A workless idle poll must not accumulate into the
        # goodput fraction — it would track traffic pattern, not host
        # tax (see StepProfiler._record)
        self.worked = False
        # async-loop device credit (see pipelined()): None = sync step
        self._pipelined_since: Optional[float] = None
        self._pipelined_mode = False

    def _reset(self, now: float, seq: int, span_id: int) -> None:
        self._t0 = now
        self._last = now
        self.acc = {}
        self.device = 0.0
        self.spans = []
        self.seq = seq
        self.span_id = span_id
        self.worked = False
        self._pipelined_since = None
        self._pipelined_mode = False
        self._dispatch_attrs = None
        self.program = None
        self._ann_step = annotation(STEP_SPAN, step=seq)
        self._ann_phase = annotation(PHASE_ANNOTATION)

    def _span(self, phase: str, t0: float, t1: float,
              attrs: Optional[dict]) -> None:
        """One closed phase interval: a span record for finish() to
        write, and the profiler range that was opened at ``t0``."""
        if t1 > t0:
            self.spans.append((
                _PHASE_SPAN.get(phase) or "serve:" + phase, t0, t1,
                self.span_id, self._prof.span_log.next_id(), self.seq,
                attrs))
        a = self._ann_phase
        if a is not None:
            a.set_metadata(phase=phase, **{
                k: v for k, v in (attrs or {}).items() if v is not None})
            a.__exit__(None, None, None)
            self._ann_phase = None

    def mark(self, phase: str, now: Optional[float] = None,
             dispatch: bool = False, fetch: bool = False,
             program: Optional[str] = None,
             note: Optional[dict] = None) -> float:
        """Close the interval since the previous mark and attribute it
        to ``phase``. ``dispatch=True`` flags this boundary as a device
        program dispatch (the dispatch gap is observed against the last
        fetch); ``fetch=True`` flags it as a result-fetch completion
        (the device went idle here). ``program`` names the watched
        program the interval dispatched or waited on; ``note`` is what
        else the interval's span says of it. Returns the boundary time
        so the caller can reuse the clock read."""
        prof = self._prof
        if now is None:
            now = prof.clock()
        last = self._last
        dt = now - last
        if dt < 0.0:            # clock weirdness must not corrupt sums
            dt = 0.0
            now = last
        self._last = now
        self.acc[phase] = self.acc.get(phase, 0.0) + dt
        if phase in DEVICE_PHASES and not self._pipelined_mode:
            # under pipelining the dispatch/sync_wait host slivers sit
            # INSIDE the explicitly-credited busy windows — crediting
            # both would double count
            self.device += dt
        attrs = None
        if program is not None:
            self.program = program
            attrs = dict(note or (), program=program)
        if phase == "dispatch" and self._dispatch_attrs is not None:
            attrs = dict(self._dispatch_attrs, **(attrs or {}))
            self._dispatch_attrs = None
        self._span(phase, last, now, attrs)
        if dispatch:
            self.worked = True
            self._dispatch_attrs = prof._note_dispatch(now)
        if fetch:
            prof._note_fetch(now)
        self._ann_phase = annotation(PHASE_ANNOTATION)
        return now

    def flush_span(self, t0: float, reason: str, programs: int) -> None:
        """A pipeline flush that ran inside this step, from ``t0`` to
        now: a ``serve:flush`` record beside the phase spans (it covers
        the ``sync_wait`` / ``commit`` intervals of the programs it
        fetched)."""
        self.spans.append((
            FLUSH_SPAN, t0, self._prof.clock(), self.span_id,
            self._prof.span_log.next_id(), self.seq,
            {"reason": reason, "programs": programs}))

    def device_interval(self, t0: float, t1: float,
                        note_dispatch: bool = True) -> None:
        """Attribute an already-measured device interval (prefill /
        chunk program: dispatch at ``t0``, fetch complete at ``t1``)
        that nests inside a host phase. Counts toward the goodput
        fraction and advances the dispatch-gap boundary — the device
        was busy, not idle, across it. ``note_dispatch=False`` realizes
        a span whose dispatch boundary was already noted at dispatch
        time (the deferred chunked-prefill attribution: the chunk no
        longer forces its own fetch, so its device span closes at the
        NEXT real fetch — which may be in a later step; the credit is
        clamped to this step's window so cumulative device time can
        never outrun cumulative wall)."""
        self.worked = True
        self.device += max(t1 - max(t0, self._t0), 0.0)
        if note_dispatch:
            self._prof._note_dispatch(t0)
        self._prof._note_fetch(t1)

    def note_dispatch(self, now: float) -> None:
        """A device program left the host at ``now`` with its fetch
        deferred (async chunk dispatch): the gap detector advances, the
        device-time credit waits for :meth:`device_interval` with
        ``note_dispatch=False``."""
        self.worked = True
        self._prof._note_dispatch(now)

    def pipelined(self, since: Optional[float] = None) -> None:
        """Mark this step as running with the async loop's commit lag:
        the device verifiably had work in flight from ``since`` (default
        the step's begin — an in-flight program from the previous step)
        through the step's end, so ``finish()`` credits that window as
        device time (clamped to the step wall). Implies
        :meth:`pipelined_mode`: the dispatch/sync_wait host slivers no
        longer bound device activity under pipelining — crediting them
        would double count, and NOT crediting the busy window would
        collapse the goodput fraction exactly when the loop gets good."""
        self.worked = True
        self._pipelined_mode = True
        self._pipelined_since = self._t0 if since is None else since

    def pipelined_mode(self) -> None:
        """Suppress the DEVICE_PHASES sliver credit without arming a
        finish-time busy window — for rounds whose device credit is
        carried entirely by explicit :meth:`device_interval` spans plus
        a later :meth:`pipelined` tail (the async verify round)."""
        self._pipelined_mode = True

    def program_fetched(self, ticket: int, now: float,
                        since: Optional[float] = None) -> None:
        """The fetch that returned at ``now`` proves program ``ticket``
        finished (:meth:`StepProfiler.program_fetched`); the host blocked
        in it from ``since``, by default this step's last mark: call it
        BEFORE the mark that closes the wait."""
        self._prof.program_fetched(
            ticket, now, max(now - (self._last if since is None
                                    else since), 0.0))

    def finish(self, live: bool = True, slots: Optional[int] = None,
               admitted: Optional[int] = None, rider: bool = False) -> None:
        """Close the step: the tail since the last mark becomes the
        ``other`` residual, and ``wall == sum(phases)`` exactly.
        ``slots`` (resident after the step) and ``admitted`` (into a
        slot during it) ride on the ``serve:step`` span, and ``rider``
        where the step's decode program prefilled an admitted prompt.

        ``live=False`` (no sequences resident after this step) resets
        the dispatch-gap baseline: with nothing to decode the device is
        idle because there is no WORK, not because the host is in the
        way — a traffic lull must never read as a multi-second
        dispatch gap (it would dominate the p90 the async-loop A/B is
        judged on, keyed to load pattern instead of host tax)."""
        end = max(self._prof.clock(), self._last)
        tail = end - self._last
        self.acc["other"] = self.acc.get("other", 0.0) + tail
        self._span("other", self._last, end, None)
        wall = max(end - self._t0, 0.0)
        if self._pipelined_since is not None:
            # additive, then clamped: phase slivers in DEVICE_PHASES may
            # overlap the pipelined window — the clamp keeps the
            # per-step device credit a true fraction of wall
            self.device += max(end - max(self._pipelined_since,
                                         self._t0), 0.0)
        if self.device > wall:
            self.device = wall
        if not live:
            self._prof._last_fetch = None
        self._prof._record(wall, self, end, slots, admitted, rider)
        a = self._ann_step
        if a is not None:
            self._ann_step = None
            a.__exit__(None, None, None)


class StepProfiler:
    """Factory + aggregate store for per-step serving phase profiles.

    ``clock`` defaults to ``time.perf_counter`` and should be the
    SERVER's clock so fake-clock chaos tests drive the profiler
    coherently with deadlines and SLO windows; the spans it writes to
    ``span_log`` (default: the process log) carry that clock's readings.
    Thread-safety: the serving loop writes, the scrape endpoint reads
    ``snapshot()``.
    """

    def __init__(self, registry: Optional[MetricRegistry] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 source: str = "serve",
                 span_log: Optional[SpanLog] = None):
        self.registry = registry if registry is not None else get_registry()
        self.clock = clock
        self.source = source
        self.span_log = span_log if span_log is not None else get_span_log()
        # which profiler wrote a serve:step span (several servers share
        # the process log and may share a source name)
        self.uid = next(_UIDS)
        self._lock = threading.Lock()
        self._seq = 0            # step() calls seen: the spans' key
        self._idle_run = 0       # consecutive workless polls
        self._recent_walls: Deque[float] = deque(maxlen=SLOW_STEP_WINDOW)
        self.slow_steps = 0
        self.steps = 0
        self.wall_total = 0.0
        self.device_total = 0.0
        # workless polls (no dispatch, no device interval): counted
        # apart so a traffic lull's pure-host steps never drag the
        # goodput fraction toward 0 — the fraction measures host tax
        # WHILE SERVING, the number the regression gate keys on
        self.idle_steps = 0
        self.idle_wall_total = 0.0
        self.phase_totals: Dict[str, float] = {}
        # dispatch-gap accounting (device idle between fetch N and
        # dispatch N+1 — the async-loop refactor's target)
        self._last_fetch: Optional[float] = None
        self.gap_count = 0
        self.gap_total = 0.0
        self.gap_max = 0.0
        # commit-lag accounting: programs dispatched but not yet
        # fetched. A dispatch that overlaps outstanding work observes a
        # ZERO gap (the device had queued work — see module docstring);
        # a fetch that leaves work outstanding opens no idle span.
        self.outstanding = 0
        self.pipelined_dispatches = 0   # dispatches issued into a busy device
        self.pipelined_steps = 0        # steps credited via pipelined()
        # chain-depth accounting (lag-N dispatch chains): at each
        # dispatch, the depth the chain reaches (outstanding AFTER the
        # increment) and the dispatch gap attributed to that depth —
        # depth-1 dispatches carry the real idle gaps (the device had
        # drained), depth>=2 are 0-gap by construction, so the per-depth
        # split shows exactly where lag-N closed gaps lag-1 could not
        self.depth_hist: Dict[int, int] = {}
        self.depth_gap_total: Dict[int, float] = {}
        # rolling window of the most recent gap observations (pipelined
        # 0-gaps included) — the cheap "how host-bound is this server
        # RIGHT NOW" signal the disaggregated frontend's telemetry
        # routing reads per admission (recomputing a histogram quantile
        # per routing decision would not be)
        self._recent_gaps: Deque[float] = deque(maxlen=32)
        # cost-accounting tap (telemetry/accounting.py RequestLedger):
        # called with each WORKED step's device-attributed seconds,
        # right after they enter device_total — the ledger splits
        # exactly what the profiler recorded, so per-request
        # device-seconds sum to the profiler's device total by
        # construction. None (default) costs one attribute read.
        self.on_step_device: Optional[Callable[[float], None]] = None
        self._handle = _StepHandle(self)
        # serve:program records: the programs launched and not yet
        # proven finished by a fetch, oldest first, and where the newest
        # record ended. BESIDE the accounting above, which never reads it
        self._launched: Deque[tuple] = deque()
        self._tickets = 0
        self._program_end = 0.0
        reg = self.registry
        self._h_wall = reg.histogram(
            "serve_step_wall_seconds",
            help="one whole server step() wall interval (phases sum to "
                 "it by construction)")
        self._h_gap = reg.histogram(
            "serve_dispatch_gap_seconds",
            help="device idle between a step's result fetch and the "
                 "next program dispatch — the host tax the async "
                 "serving loop (ROADMAP item 5) targets")
        self._g_goodput = reg.gauge(
            "serve_goodput_fraction",
            help="cumulative device-attributed share of serve step "
                 "wall time (dispatch + sync-wait + prefill device "
                 "intervals; 1.0 = the device never waits on the host)")
        self._h_depth = reg.histogram(
            "serve_commit_lag_depth",
            help="dispatch-chain depth observed at each program "
                 "dispatch (outstanding programs after the dispatch; "
                 "1 = the device had drained, >= 2 = lag-N pipelining "
                 "— ds_report compares this against the configured "
                 "async_loop max_commit_lag)",
            buckets=[float(i) for i in range(1, 17)])
        self._phase_hist: Dict[str, object] = {}
        self._c_span_errors = {
            site: reg.counter(
                "serve_program_span_errors_total",
                help="faults in the serve:program bookkeeping (a launch "
                     "or a fetch that raised, a FIFO nothing proved): "
                     "the record is dropped, the step goes on",
                labels={"site": site})
            for site in ("launch", "fetch")}

    # ------------------------------------------------------------ steps

    def begin(self) -> _StepHandle:
        """Start profiling one ``step()`` call; returns the handle the
        loop marks phase boundaries on. A handle must be ``finish()``ed
        before the next ``begin()`` (single-threaded serving loop)."""
        self._seq += 1
        self._handle._reset(self.clock(), self._seq,
                            self.span_log.next_id())
        return self._handle

    def _note_dispatch(self, now: float) -> dict:
        """Observe the dispatch boundary at ``now``; returns the gap and
        chain depth seen there (``gap_s`` None: no fetch to measure
        against), for the ``serve:dispatch`` span."""
        if self.outstanding > 0:
            # another program is still in flight: the device moves
            # straight from it to this one — zero idle by construction.
            # Observed (not skipped) so the gap histogram's count keeps
            # meaning "one observation per dispatch boundary" and the
            # p90 the async A/B gates on reflects the closed gaps.
            self.outstanding += 1
            depth = self.outstanding
            self._h_gap.observe(0.0)
            self._h_depth.observe(float(depth))
            with self._lock:
                self.gap_count += 1
                self.pipelined_dispatches += 1
                self._recent_gaps.append(0.0)
                self.depth_hist[depth] = self.depth_hist.get(depth, 0) + 1
            # zero by construction: countable as such on the span
            return {"gap_s": 0.0, "depth": depth, "busy": True}
        self.outstanding = 1
        self._h_depth.observe(1.0)
        if self._last_fetch is None:
            with self._lock:
                self.depth_hist[1] = self.depth_hist.get(1, 0) + 1
            return {"gap_s": None, "depth": 1, "busy": False}
        gap = max(now - self._last_fetch, 0.0)
        self._last_fetch = None      # one gap per idle span
        self._h_gap.observe(gap)
        with self._lock:
            self.gap_count += 1
            self.gap_total += gap
            self.gap_max = max(self.gap_max, gap)
            self._recent_gaps.append(gap)
            self.depth_hist[1] = self.depth_hist.get(1, 0) + 1
            self.depth_gap_total[1] = \
                self.depth_gap_total.get(1, 0.0) + gap
        return {"gap_s": gap, "depth": 1, "busy": False}

    def _note_fetch(self, now: float) -> None:
        self.outstanding = max(self.outstanding - 1, 0)
        if self.outstanding == 0:
            # the device actually drained here — idle begins
            self._last_fetch = now

    def note_fetch(self, now: float) -> None:
        """Out-of-step fetch boundary (a pipeline flush from ``cancel``
        or ``drain`` between ``step()`` calls): keeps the
        outstanding-dispatch pairing exact when no step handle is
        live."""
        self._note_fetch(now)

    # ------------------------------------------------- serve:program

    def program_launched(self, program: str, now: Optional[float],
                         bucket: Optional[int] = None, rows: int = 0,
                         prompt_tokens: int = 0) -> int:
        """A device program left the host at ``now`` (None: no clock
        read of its own, it opens where the program before it closed).
        Returns the ticket its fetch names, 0 where nothing was noted; a
        program no fetch waits for on its own (a non-final chunk, a
        draft forward) is proven by the next ticket's. Never raises."""
        try:
            q = self._launched
            if len(q) >= MAX_LAUNCHED:
                q.clear()
                raise RuntimeError(
                    f"{MAX_LAUNCHED} programs launched and none proven")
            self._tickets += 1
            q.append((self._tickets, program,
                      float("-inf") if now is None else now, bucket, rows,
                      prompt_tokens, self._seq, len(q) + 1))
            return self._tickets
        except Exception:  # noqa: BLE001: tracing must not end a run
            self._program_span_error("launch")
            return 0

    def program_fetched(self, ticket: int, now: float,
                        waited: Optional[float] = None) -> None:
        """The fetch that returned at ``now`` proves program ``ticket``
        finished, and with it every program launched before it (the
        device runs one stream in dispatch order): each leaves its
        ``serve:program`` record, ``start = max(its own dispatch, the
        end of the record before it)``. ``ticket``'s ends at ``now``
        with ``waited`` (seconds the host blocked in the fetch; None for
        a fetch between steps, which has no mark before it); one that no
        fetch named closes where the next one opens, ``waited`` None. A
        ticket the FIFO does not hold (0, or proven already) proves
        nothing. Never raises."""
        try:
            q = self._launched
            end = self._program_end
            while q and q[0][0] <= ticket:
                (tk, program, t, bucket, rows, prompt_tokens, seq,
                 depth) = q.popleft()
                start = max(t, end)
                end = max(now, start)
                if tk != ticket and q:    # no fetch of its own
                    end = min(max(q[0][2], start), end)
                self.span_log.record(PROGRAM_SPAN, start, end, key=seq, attrs={
                    "program": program, "bucket": bucket, "rows": rows,
                    "prompt_tokens": prompt_tokens, "dispatched_in": seq,
                    "fetched_in": self._seq, "depth": depth,
                    "waited": waited if tk == ticket else None})
                self._program_end = end
        except Exception:  # noqa: BLE001: tracing must not end a run
            self._program_span_error("fetch")

    def _program_span_error(self, site: str) -> None:
        """Counted, logged once a site, never raised."""
        try:
            counter = self._c_span_errors[site]
            counter.inc()
            if counter.value == 1:
                from deepspeed_tpu.utils.logging import logger
                logger.warning("serve:program bookkeeping failed at its "
                               "%s (counted, logged once)", site,
                               exc_info=True)
        except Exception:  # noqa: BLE001
            pass

    def recent_gap_s(self) -> float:
        """Mean of the last ≤32 dispatch-gap observations (0.0 with no
        history) — the per-replica host-bound signal the disaggregated
        frontend ranks decode replicas by (telemetry-routed admission:
        docs/serving.md 'Disaggregated prefill/decode')."""
        with self._lock:
            if not self._recent_gaps:
                return 0.0
            return sum(self._recent_gaps) / len(self._recent_gaps)

    def _phase_h(self, phase: str):
        h = self._phase_hist.get(phase)
        if h is None:
            h = self.registry.histogram(
                "serve_step_phase_seconds",
                help="per-step host time by serving phase (admission / "
                     "prefill_chunk / propose / dispatch / sync_wait / "
                     "commit / publish / other; phases sum to "
                     "serve_step_wall_seconds by construction)",
                labels={"phase": phase})
            self._phase_hist[phase] = h
        return h

    def _write_spans(self, handle: _StepHandle, end: float,
                     attrs: dict) -> None:
        log = self.span_log
        log.extend(handle.spans)
        log.record(STEP_SPAN, handle._t0, end, key=handle.seq, attrs=attrs,
                   span_id=handle.span_id)

    def _record(self, wall: float, handle: _StepHandle, end: float,
                slots: Optional[int], admitted: Optional[int],
                rider: bool = False) -> None:
        if not handle.worked:
            # idle poll: nothing dispatched, no device interval — the
            # step is counted for visibility but kept OUT of the
            # wall/phase/goodput accumulators (a lull's workless steps
            # are load pattern, not host tax). Only the FIRST poll of a
            # lull leaves spans: a caller spinning on an empty server
            # must not push the worked steps out of the bounded log.
            with self._lock:
                self.idle_steps += 1
                self.idle_wall_total += wall
            self._idle_run += 1
            if self._idle_run == 1:
                self._write_spans(handle, end, {
                    "source": self.source, "profiler": self.uid,
                    "idle": True, "slots": slots})
            return
        self._idle_run = 0
        self._write_spans(handle, end, {
            "source": self.source, "profiler": self.uid, "slots": slots,
            "admitted": admitted, "rider": rider,
            "depth": self.outstanding, "device_s": handle.device,
            "pipelined": handle._pipelined_mode})
        recent = self._recent_walls
        if wall > SLOW_STEP_S and len(recent) >= SLOW_STEP_MIN_HISTORY:
            typical = median(recent)
            if wall > SLOW_STEP_FACTOR * typical:
                self._slow_step(wall, handle, typical)
        recent.append(wall)
        with self._lock:
            self.steps += 1
            if handle._pipelined_mode:
                self.pipelined_steps += 1
            self.wall_total += wall
            self.device_total += handle.device
            for phase, dt in handle.acc.items():
                self.phase_totals[phase] = \
                    self.phase_totals.get(phase, 0.0) + dt
            fraction = (self.device_total / self.wall_total
                        if self.wall_total > 0 else 0.0)
        if self.on_step_device is not None:
            self.on_step_device(handle.device)
        self._h_wall.observe(wall)
        for phase, dt in handle.acc.items():
            self._phase_h(phase).observe(dt)
        self._g_goodput.set(fraction)

    def _slow_step(self, wall: float, handle: _StepHandle,
                   typical: float) -> None:
        """One ``slow_step`` ring event: which phase of a stalled step
        was blocked, how deep the chain was, which program it waited
        on (PERF.md "Stalls")."""
        from deepspeed_tpu.telemetry.events import SLOW_STEP, record_event
        self.slow_steps += 1
        record_event(
            SLOW_STEP, source=self.source, step=handle.seq,
            start=handle._t0, wall=wall, median_wall=typical,
            phases=[[r[0], r[2] - r[1]] for r in handle.spans],
            depth=self.outstanding, program=handle.program,
            pipelined=handle._pipelined_mode)

    # --------------------------------------------------------- snapshot

    def snapshot(self) -> dict:
        """JSON-able totals for ``/debug/goodput`` and
        ``server.stats``."""
        with self._lock:
            wall = self.wall_total
            device = self.device_total
            fraction = device / wall if wall > 0 else 0.0
            return {
                "enabled": True,
                "source": self.source,
                "steps": self.steps,
                "idle_steps": self.idle_steps,
                "idle_wall_s": self.idle_wall_total,
                "wall_s": wall,
                "device_s": device,
                "goodput_fraction": fraction,
                "host_fraction": 1.0 - fraction if wall > 0 else 0.0,
                "phases_s": dict(self.phase_totals),
                "dispatch_gap": {
                    "count": self.gap_count,
                    "total_s": self.gap_total,
                    "max_s": self.gap_max,
                    "mean_s": (self.gap_total / self.gap_count
                               if self.gap_count else 0.0),
                },
                # async-loop commit-lag view (docs/serving.md "Async
                # dispatch loop"): how deep the pipeline currently is,
                # how many dispatches landed on a busy device (gap 0),
                # and how many steps were credited via pipelined()
                "commit_lag": {
                    "outstanding": self.outstanding,
                    "pipelined_dispatches": self.pipelined_dispatches,
                    "pipelined_steps": self.pipelined_steps,
                    # observed chain-depth distribution (lag-N): keys
                    # are the depth each dispatch landed at; p50/max
                    # summarize it, gap_s_by_depth attributes the idle
                    # gaps (all at depth 1 by construction — deeper
                    # dispatches land on a busy device)
                    "depth_hist": {str(d): n for d, n in
                                   sorted(self.depth_hist.items())},
                    "depth_p50": _hist_p50(self.depth_hist),
                    "depth_max": max(self.depth_hist) if self.depth_hist
                    else 0,
                    "gap_s_by_depth": {str(d): t for d, t in
                                       sorted(self.depth_gap_total
                                              .items())},
                },
                "slow_steps": self.slow_steps,
            }
