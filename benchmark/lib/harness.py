"""The harness: finds a cell's files by the names ``BENCHMARK.json``
gives, runs the cell through the runner of its configuration's ``kind``,
reads each metric with the reader file of its name, and prints the
contract's last line. Nothing here knows a cell, a configuration, a
traffic mix or a metric by name.

  cell      -> ``BENCHMARK.json`` ``workloads[name]``
  config    -> ``benchmark/configs/<config>.json``   (``kind``: train | serve)
  traffic   -> ``benchmark/traffic/<traffic>.json``  (``kind``: train_job |
               backlog | open_loop)
  model     -> ``benchmark/models/<model.family>.py``
  metric    -> ``benchmark/metrics/<name>.py``: ``read(run, trace)`` ->
               number or None (None: nothing to read, metric left out)
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)
OUT_DIR = os.path.join(ROOT, "out")


def log(obj) -> None:
    """An earlier line of standard output (never the last)."""
    print(json.dumps(obj, default=float), flush=True)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_contract(path: Optional[str] = None) -> dict:
    return load_json(path or os.path.join(REPO, "BENCHMARK.json"))


def find(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} (known: "
                   f"{[it['name'] for it in items]})")


def resolve_cell(contract: dict, workload: str, repo: str = REPO) -> dict:
    """A cell with its configuration and traffic loaded, the benchmark
    directory its files live in (the one that holds the configuration's
    ``configs/`` directory), and the names of the metrics it reports in
    each mode."""
    cell = find(contract["workloads"], workload, "workload")
    cfg_file = os.path.join(
        repo, find(contract["configs"], cell["config"], "config")["file"])
    root = os.path.dirname(os.path.dirname(cfg_file))

    def mine(metrics):
        return [m["name"] for m in metrics
                if "workloads" not in m or workload in m["workloads"]]
    return {"cell": cell, "root": root, "config": load_json(cfg_file),
            "traffic": load_json(os.path.join(
                root, "traffic", cell["traffic"] + ".json")),
            "end_to_end": mine(contract["end_to_end"]),
            "per_layer": mine(contract["per_layer"])}


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: str = ROOT) -> Callable:
    """``read(run, trace)`` of ``metrics/<name>.py``."""
    path = os.path.join(root, "metrics", name + ".py")
    return load_module(path, "benchmark_metric_" + name.replace(
        ".", "_").replace("-", "_")).read


def load_family(name: str, root: str = ROOT):
    return load_module(os.path.join(root, "models", name + ".py"),
                       "benchmark_model_" + name.replace("-", "_"))


def read_metrics(names: List[str], run: dict, trace, units: Dict[str, str],
                 root: str = ROOT) -> dict:
    out = {}
    for name in names:
        with TAIL.timed("metrics_read", name):
            value = load_reader(name, root)(run, trace)
        if value is not None:
            out[name] = {"value": float(value), "unit": units[name]}
    return out


def units_of(contract: dict) -> Dict[str, str]:
    return {m["name"]: m["unit"]
            for m in contract["end_to_end"] + contract["per_layer"]}


class Marks:
    """Where set-up went: seconds since the process started at each
    named point, printed on an earlier line."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.at = []

    def mark(self, label: str, ago: float = 0.0) -> None:
        """``ago``: seconds before now at which the point was passed."""
        self.at.append([label, round(time.time() - self.t_start - ago, 3)])


class TailMarks(Marks):
    """Where a run's time goes once its window has closed (a traced run
    spends more there than a cold set-up leaves it): seconds since the
    process started at each named point, seconds spent inside named
    pieces of work (``profiler_stopped`` is the time inside
    ``jax.profiler.stop_trace()``; ``tables_parsed`` and
    ``metrics_read`` hold one entry per program and per reader), and
    what the trace held. Printed on an earlier line, ``tail_marks``, in
    traced and untraced runs alike."""

    def __init__(self):
        self.begin(time.time())

    def begin(self, t_start: float) -> None:
        Marks.__init__(self, t_start)
        self.seconds: Dict[str, object] = {}
        self.counts: Dict[str, object] = {}

    @contextlib.contextmanager
    def timed(self, label: str, key: Optional[str] = None):
        """Seconds inside the block, under ``label`` (summed) or under
        ``label[key]``."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = round(time.perf_counter() - t0, 4)
            if key is None:
                self.seconds[label] = round(
                    self.seconds.get(label, 0.0) + dt, 4)
            else:
                self.seconds.setdefault(label, {})[key] = dt

    def line(self) -> dict:
        return {"tail_marks": self.at, "tail_seconds": self.seconds,
                **self.counts}


TAIL = TailMarks()


# ------------------------------------------------------------- the device

class NoDevice(RuntimeError):
    pass


class RunCeiling(RuntimeError):
    """A loop of a runner passed a ceiling of its own
    (``serve_cell.ServerStalled``, ``DrainCeiling``): the run failed,
    with its reason, well inside the time a run is given."""


def require_tpu(chips: int) -> dict:
    """The device stamp, or :class:`NoDevice`: a cell runs on exactly
    the chips it asks for and never anywhere else."""
    import jax
    from benchmark.lib.peaks import device_stamp, peaks_for
    try:
        stamp = device_stamp()
    except RuntimeError as e:      # jax found no backend at all
        raise NoDevice(f"jax found no device: {e}") from None
    if stamp["platform"] != "tpu" or stamp["count"] != chips:
        raise NoDevice(
            f"this cell needs {chips} TPU chip(s); jax reports "
            f"{stamp['count']} x {stamp['platform']} ({stamp['kind']}). "
            "The benchmark has no CPU fallback.")
    peaks_for(stamp["kind"])       # unknown kind: an error, not a default
    return stamp


# ----------------------------------------------------- compiles and traces

class CompileCounter:
    """Counts every backend compilation jax reports (watched programs
    and the small unwatched jits alike) so that a window can be shown to
    hold none."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def watched_compile_seconds() -> float:
    """Seconds the program's compile watch recorded for its programs."""
    from deepspeed_tpu.telemetry.compile_watch import all_watched
    return sum(rec.compile_seconds for w in all_watched()
               for rec in w.executables)


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's own trace (no cost when no trace
    is being taken beyond a TraceMe check)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class Tracer:
    """Takes the device trace of a sub-window: ``start()`` at a step
    boundary, ``stop()`` at a later one. Python-level tracing is off (it
    writes millions of events and slows the host it measures)."""

    def __init__(self, enabled: bool, workload: str):
        self.enabled = enabled
        self.dir = os.path.join(OUT_DIR, "trace", workload)
        self.active = False
        self.t0 = self.t1 = None
        self._win = None

    def start(self, window: bool = True):
        """Start the profiler (this takes seconds: do it where a stall
        harms nothing) and, unless told to wait, open the window."""
        if not self.enabled or self.active:
            return
        import shutil

        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        with TAIL.timed("profiler_started"):
            jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True
        self._on_at = time.perf_counter()
        if window:
            self.open_window()

    def open_window(self):
        """The traced window opens here (a ``bench:window`` span)."""
        if not self.active or self._win is not None:
            return
        import jax
        self._win = jax.profiler.TraceAnnotation("bench:window")
        self._win.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        if not self.active:
            return
        import jax
        if self._win is not None:
            self.t1 = time.perf_counter()
            self._win.__exit__(None, None, None)
        TAIL.seconds["profiled_s"] = round(
            time.perf_counter() - self._on_at, 3)
        with TAIL.timed("profiler_stopped"):
            jax.profiler.stop_trace()
        TAIL.mark("profiler_stopped")
        self.active = False

    def reduced(self):
        if not self.enabled or self.t1 is None:
            return None
        from benchmark.lib import trace_reduce
        import shutil
        from benchmark.lib.program_spans import watched_programs
        with TAIL.timed("trace_read"):
            red = trace_reduce.read(self.dir, watched_programs())
        TAIL.mark("trace_read")
        TAIL.counts.update(traced_executions=red.traced_executions(),
                           trace_events=red.events,
                           trace_window_cut_s=red.cut_s)
        if red.cut_s > 0:     # the host's records follow the cut
            self.t1 = self.t0 + red.window_s
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


RUNNERS = {"train": "benchmark.lib.train_cell",
           "serve": "benchmark.lib.serve_cell"}


def run_cell(cell: dict, args, t_start: float, devices, device_kind: str):
    """Run a resolved cell through the runner of its configuration's
    ``kind`` on ``devices``; returns the runner's record, completed with
    what every reader may use, and the tracer."""
    import importlib

    from benchmark.lib.peaks import peaks_for
    family = load_family(cell["config"]["model"]["family"], cell["root"])
    runner = importlib.import_module(RUNNERS[cell["config"]["kind"]])
    run = runner.run(cell, args, t_start, family, devices)
    tracer = run.pop("tracer")
    run.update(chips=len(devices), peaks=peaks_for(device_kind),
               shapes=family.shapes(cell["config"]["model"]),
               config=cell["config"], traffic=cell["traffic"],
               trace_t0=tracer.t0, trace_t1=tracer.t1)
    return run, tracer


# ------------------------------------------------------------ the last line

def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown: Optional[dict] = None,
                reason: Optional[str] = None,
                compared: Optional[dict] = None) -> str:
    """``compared``: each number the run's ``correct`` was decided from
    as ``name: [number, limit]``; it comes last on the line."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    if reason:
        line["reason"] = reason
    if compared:
        line["compared"] = compared
    return json.dumps(line, default=float)
