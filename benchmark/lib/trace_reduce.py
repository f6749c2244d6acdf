"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read. One module, read with ``jax.profiler.ProfileData`` alone.

What a TPU trace holds (looked at by hand, ``tools/dump_trace.py``):
one plane per chip, ``/device:TPU:<n>``, with the lines ``XLA Modules``
(one event per execution of a compiled program, named
``jit_<fn>(<hash>)``), ``XLA Ops`` (one event per HLO instruction that
ran on the core, named by the instruction's whole text, ``%copy-done.29
= f32[...] copy-done(...)``; a ``while`` holds its body's events nested
inside it) and ``Async XLA Ops`` (the start-to-done span of each
asynchronous copy or collective). The host is the plane ``/host:CPU``,
one line per thread; ``jax.profiler.TraceAnnotation`` spans appear on
the thread that opened them under their own name: the benchmark's
``bench:*`` and, since the program annotates itself, ``serve:step``,
``train:step`` and ``serve:phase`` (one name for every phase of a server
step, the phase in its ``phase`` stat: read here as ``serve:<phase>``).
All planes share one clock, in nanoseconds.

Everything below works on plain tuples, so that it can be checked
against a recorded trace without a chip (``benchmark/testdata``).
"""
from __future__ import annotations

import collections
import functools
import glob
import heapq
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # start, end (seconds)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIXES = ("bench:", "serve:", "train:")
PHASE_SPAN = "serve:phase"
WINDOW_SPAN = "bench:window"
CONTAINERS = ("while", "conditional", "call")
COPY_OPS = ("copy-start", "copy-done")
# by opcode, or by the instruction's name where XLA wraps a collective in
# a generic async pair (``%async-collective-done.3 = ... async-done(...)``)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast",
               "async-collective")
_NAME = re.compile(r"^%?([^\s=]+)")
# executions of one named program inside ``bench:window`` that the
# readers walk; the window is cut where the first program passes it, so
# what a traced run costs after its window does not grow with the
# server's speed. Today's windows hold 64-139 (PERF.md, section 3).
MAX_EXECUTIONS = 160


# an instruction's text comes back once per execution: parsed once
_once = functools.lru_cache(maxsize=1 << 16)


@_once
def op_name(text: str) -> str:
    """``%fusion.4266.remat = ...`` -> ``fusion.remat``: the
    instruction's name without the numbers XLA appends."""
    m = _NAME.match(text)
    name = m.group(1) if m else text
    return ".".join(p for p in name.split(".") if not p.isdigit()) or name


@_once
def op_kind(text: str) -> str:
    """The HLO opcode of an instruction's text (``copy-done``,
    ``fusion``, ``custom-call``...): the word before the first ``(``
    after the ``=``; for a bare name, the name itself."""
    if " = " in text:
        rhs = text.split(" = ", 1)[1]
        depth, i = 0, 0
        # skip the result shape, which may be a parenthesised tuple
        while i < len(rhs):
            c = rhs[i]
            if c == "(":
                if depth == 0 and i > 0 and rhs[i - 1] not in " ,(":
                    word = rhs[:i].rsplit(" ", 1)[-1]
                    if re.fullmatch(r"[a-z][a-z0-9\-_]*", word):
                        return word
                depth += 1
            elif c == ")":
                depth -= 1
            i += 1
    return op_name(text).split(".")[0]


@_once
def is_kernel(text: str) -> bool:
    """A Pallas (Mosaic) kernel call."""
    return op_kind(text) == "custom-call" and (
        "tpu_custom_call" in text or "custom_call_target" not in text)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of a merged busy list inside ``[lo, hi]``."""
    out, at = [], lo
    for s, e in clip(busy, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float, float, float]]:
    """``(text, start, end)`` events of ONE line, possibly nested, to
    ``(text, start, end, self_seconds)``: an event's own time is its
    duration less the time its direct children cover."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [events[i][2] - events[i][1] for i in range(len(events))]
    stack: List[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= (min(e, events[stack[-1]][2]) - s)
        stack.append(i)
    return [(events[i][0], events[i][1], events[i][2], max(own[i], 0.0))
            for i in range(len(events))]


class Device:
    """One chip's part of a trace, cut to the window."""

    def __init__(self, ordinal: int, ops, modules, lo: float, hi: float):
        self.ordinal = ordinal
        self.lo, self.hi = lo, hi
        keep = [(t, s, e) for t, s, e in ops if e > lo and s < hi]
        self.ops = self_times(keep)
        self.modules = [(n, s, e) for n, s, e in modules
                        if s >= lo and e <= hi]
        self.busy = merge(clip(((s, e) for _, s, e in keep), lo, hi))

    @property
    def busy_s(self) -> float:
        return total(self.busy)

    def by_op(self) -> Dict[str, float]:
        """Own time by instruction name, containers left out (their
        bodies' events carry the time)."""
        out: Dict[str, float] = collections.defaultdict(float)
        for text, s, e, own in self.ops:
            if op_kind(text) in CONTAINERS:
                continue
            out[op_name(text)] += own * _share(s, e, self.lo, self.hi)
        return dict(out)

    def kind_seconds(self, kinds: Sequence[str]) -> float:
        """Own time of the instructions whose opcode or name starts with
        one of ``kinds``: time the core spent IN them, so no compute
        ran."""
        kinds = tuple(kinds)
        return sum(own * _share(s, e, self.lo, self.hi)
                   for text, s, e, own in self.ops
                   if op_kind(text).startswith(kinds)
                   or op_name(text).startswith(kinds))

    def kernels(self) -> List[Tuple[str, float, float]]:
        return [(t, s, e) for t, s, e, _ in self.ops if is_kernel(t)]


def _share(s: float, e: float, lo: float, hi: float) -> float:
    """The part of ``[s, e]`` inside the window, as a share of it."""
    return (min(e, hi) - max(s, lo)) / (e - s) if e > s else 0.0


def capped(modules: Sequence[Tuple[str, float, float]], window: Interval,
           programs: Sequence[str], limit: int = MAX_EXECUTIONS) -> Interval:
    """``window``, or the part of it that ends with execution number
    ``limit`` of the first of ``programs`` (``serve_decode`` for the
    module ``jit_serve_decode(<id>)``) to run that often wholly inside
    it on this chip."""
    lo, hi = window
    names = {"jit_" + p for p in programs}
    seen: Dict[str, int] = collections.Counter()
    for n, s, e in sorted(modules, key=lambda m: m[1]):
        n = n.split("(")[0]
        if n in names and s >= lo and e <= hi:
            seen[n] += 1
            if seen[n] == limit:
                # the next one is wholly inside no longer
                return lo, min(hi, e + 1e-9)
    return lo, hi


class Reduced:
    """A trace cut to its window: the devices, the host's spans, and the
    reductions over them. ``cap_programs`` names the programs whose
    executions in the window are held to ``MAX_EXECUTIONS``
    (:func:`capped`); ``cut_s`` is what that took off the window."""

    def __init__(self, device_events: Dict[int, dict],
                 host_spans: List[Tuple[str, float, float]],
                 window: Optional[Interval] = None,
                 cap_programs: Sequence[str] = ()):
        spans = [(n, s, e) for n, s, e in host_spans if n != WINDOW_SPAN]
        win = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
        if window is None and win:
            window = win[0]
        if window is None:
            pts = [(s, e) for d in device_events.values()
                   for _, s, e in d["ops"]]
            window = (min(s for s, _ in pts), max(e for _, e in pts))
        first = device_events[min(device_events)]["modules"]
        self.lo, self.hi = capped(first, window, cap_programs)
        self.cut_s = window[1] - self.hi
        self.window_s = self.hi - self.lo
        self.host_spans = spans
        # what the file held, before the cut to the window
        self.events = {
            "device_ops": sum(len(d["ops"]) for d in device_events.values()),
            "modules": sum(len(d["modules"])
                           for d in device_events.values()),
            "host_spans": len(host_spans)}
        self.profiled = collections.Counter(
            n.split("(")[0] for n, _, _ in first)
        self.devices = [Device(k, d["ops"], d["modules"], self.lo, self.hi)
                        for k, d in sorted(device_events.items())]

    # ---- device time
    @property
    def busy_s(self) -> float:
        """Seconds an operation ran on the device, mean over chips."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def exposed_pct(self, kinds: Sequence[str]) -> float:
        """Share of the window the worst chip's core sat in operations
        of these opcodes (copies or collectives it had to wait for)."""
        return 100.0 * max(d.kind_seconds(kinds)
                           for d in self.devices) / self.window_s

    def device_ops(self, top: int = 10) -> List[List]:
        acc: Dict[str, float] = collections.defaultdict(float)
        for d in self.devices:
            for k, v in d.by_op().items():
                acc[k] += v / len(self.devices)
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:top]]

    # ---- idle gaps by what the host was doing
    def idle_gaps(self, top: int = 10) -> List[List]:
        """Idle time of chip 0 by the innermost host span (the
        benchmark's or the program's) that covers each gap's middle:
        the shortest span open there. One sweep over the gaps and the
        spans in time order (a gap per instruction boundary times a
        span per step would be quadratic in the server's speed)."""
        acc: Dict[str, float] = collections.defaultdict(float)
        spans = sorted((ss, se, n) for n, ss, se in self.host_spans)
        open_: List[Tuple[float, float, str]] = []     # (end, length, name)
        k = 0
        for s, e in gaps(self.devices[0].busy, self.lo, self.hi):
            mid = (s + e) / 2
            while k < len(spans) and spans[k][0] <= mid:
                ss, se, n = spans[k]
                heapq.heappush(open_, (se, se - ss, n))
                k += 1
            while open_ and open_[0][0] < mid:
                heapq.heappop(open_)
            acc[min((d, n) for _, d, n in open_)[1] if open_
                else "_no_host_span_"] += e - s
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:top]]

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(),
                "idle_gaps": self.idle_gaps()}

    def traced_executions(self) -> dict:
        """Per program on chip 0: executions under the profiler, and of
        those the ones wholly inside ``bench:window``."""
        inside = collections.Counter(
            n.split("(")[0] for n, _, _ in self.devices[0].modules)
        return {n: [k, inside.get(n, 0)]
                for n, k in self.profiled.most_common()}

    def summary(self, top: int = 12) -> dict:
        """For an earlier line of a traced run: the programs that ran
        and the kernel calls seen, so that a reader that found nothing
        can be put right from the run's own output."""
        dev = self.devices[0]
        progs: Dict[str, list] = collections.defaultdict(list)
        for n, s, e in dev.modules:
            progs[n].append(e - s)
        kern: Dict[str, list] = collections.defaultdict(list)
        sample: Dict[str, str] = {}
        for t, s, e in dev.kernels():
            kern[op_name(t)].append(e - s)
            sample.setdefault(op_name(t), t[:240])
        rank = lambda d: sorted(  # noqa: E731
            ([k, len(v), sum(v)] for k, v in d.items()),
            key=lambda r: -r[2])[:top]
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "chips": len(self.devices), "programs": rank(progs),
                "kernels": rank(kern), "kernel_samples": sample,
                "host_spans": sorted({n for n, _, _ in self.host_spans})}


def span_name(ev) -> str:
    """A host event's name; a ``serve:phase`` event is named by its
    ``phase`` stat (``serve:dispatch``, ``serve:sync_wait``...)."""
    if ev.name == PHASE_SPAN:
        for key, value in ev.stats:
            if key == "phase" and value:
                return "serve:" + str(value)
    return ev.name


def read(path: str, cap_programs: Sequence[str] = ()) -> Reduced:
    """The newest ``*.xplane.pb`` under ``path`` (or the file itself).
    The host's plane is read first: it has ``bench:window``, and an
    instruction that ran wholly outside it (a lead-in under the
    profiler, what ``cap_programs`` cuts off) is counted and never made
    into a tuple."""
    from jax.profiler import ProfileData
    if not os.path.isfile(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no *.xplane.pb under {path}")
        path = found[-1]
    planes = list(ProfileData.from_file(path).planes)
    spans: List[Tuple[str, float, float]] = []
    host_events = 0
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                host_events += 1
                if ev.name.startswith(SPAN_PREFIXES):
                    s = ev.start_ns * 1e-9
                    spans.append((span_name(ev), s,
                                  s + ev.duration_ns * 1e-9))
    window = next(((s, e) for n, s, e in spans if n == WINDOW_SPAN), None)
    devices: Dict[int, dict] = {}
    op_lines = []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dev["modules"].append((ev.name, s,
                                           s + ev.duration_ns * 1e-9))
            elif line.name == "XLA Ops":
                op_lines.append((dev["ops"], line))
    if not devices:
        raise ValueError(f"{path} holds no /device:TPU plane")
    lo, hi = (capped(devices[min(devices)]["modules"], window, cap_programs)
              if window else (float("-inf"), float("inf")))
    device_ops = 0
    for dst, line in op_lines:
        for ev in line.events:
            device_ops += 1
            s = ev.start_ns * 1e-9
            e = s + ev.duration_ns * 1e-9
            if e > lo and s < hi:
                dst.append((ev.name, s, e))
    red = Reduced(devices, spans, cap_programs=cap_programs)
    red.events.update(device_ops=device_ops, host_events=host_events,
                      file_bytes=os.path.getsize(path))
    return red
