"""Runner for configurations of ``kind: serve``: the program's
``InferenceEngine`` + ``ContinuousBatchingServer`` answer requests made
by ``benchmark/lib/traffic.py``, under traffic of ``kind: backlog``
(everything queued at time zero; the window opens once every slot is
resident and decoding) or ``kind: open_loop`` (requests submitted when
they are due, a lead-in before the window so that occupancy is steady).

One process, one thread: the loop submits what is due, calls
``server.step()``, and reads from the scheduler's slots how many tokens
each resident request has now. All stamps are this file's own, taken at
step boundaries: a token is stamped when the step that committed it has
returned, which is when a caller polling the server could first see it.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from benchmark.lib import harness, traffic as traffic_lib
from benchmark.lib.trace_reduce import MAX_EXECUTIONS

OK_REASONS = ("length", "eos")
# ceilings a run keeps by itself, so that a server that never finishes
# a request is a failed run with a reason and not the driver's time-out
# (360 s for a whole run). STALL_S: seconds without a new token or a
# finished request while the server is busy (a step that compiles a
# 24-layer program takes up to a minute, in set-up only). DRAIN_S:
# seconds an open loop may stay busy after its last request was due
# (today about one).
STALL_S = 150.0
DRAIN_S = 90.0
# the profiler is stopped after ``MAX_EXECUTIONS`` steps of the traced
# window even where ``trace_seconds`` have not passed (a step runs the decode
# program at most once): stopping it costs ~40 us for every instruction
# it saw (PERF.md, section 3), so what it sees may not grow with the
# server's speed. An open loop's traced schedule starts it this long
# before its window opens (starting takes 0.04 s, in which no step
# runs), and no earlier: nothing reads the lead-in.
PROFILE_LEAD_S = 0.25


class ServerStalled(harness.RunCeiling):
    """The server was busy and nothing moved for ``STALL_S`` seconds."""


class DrainCeiling(harness.RunCeiling):
    """An open loop was still busy ``DRAIN_S`` seconds after its last
    request was due."""


class Tracked:
    """One request as the benchmark sees it. Times are on the
    benchmark's clock; ``due`` is relative to the window's opening until
    the schedule starts, absolute after."""
    __slots__ = ("rid", "prompt", "out", "due", "counted", "submitted",
                 "admitted", "token_times", "done", "reason", "refused",
                 "tokens")

    def __init__(self, rid, prompt, out, due, counted):
        self.rid, self.prompt, self.out = rid, prompt, out
        self.due, self.counted = due, counted
        self.submitted = self.admitted = self.done = None
        self.token_times: List[float] = []
        self.reason = self.tokens = None
        self.refused = False

    @property
    def failed(self) -> bool:
        return (self.refused or self.reason not in OK_REASONS
                or len(self.token_times) != self.out)


class Session:
    """The server with the benchmark's stamps around it."""

    def __init__(self, server):
        self.server = server
        self.clock = time.perf_counter
        self.reqs: Dict[int, Tracked] = {}
        self.steps: List[tuple] = []   # (t_start, t_end, live, live_tokens)
        self.moved_at = self.clock()   # the last new token or finish
        self.admissions: List[tuple] = []   # (t_step_start, prompt_len)
        # when a run reads far off, these say why: steps that took over
        # SLOW_STEP_S (with the CPU seconds this process used in them: a
        # stall with none was spent blocked, not computing) and every
        # collection of the oldest generation
        self.slow_steps: List[dict] = []
        self.gc_pauses: List[tuple] = []
        self._gc_t0 = None
        gc.callbacks.append(self._on_gc)

    SLOW_STEP_S = 0.4

    def _on_gc(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._gc_t0 = self.clock()
        elif self._gc_t0 is not None:
            self.gc_pauses.append((self._gc_t0, self.clock() - self._gc_t0))

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self.server.close()

    def submit(self, r: Tracked) -> None:
        with harness.span("bench:submit"):
            try:
                self.server.submit(r.prompt, max_new_tokens=r.out,
                                   eos_token_id=None, request_id=r.rid)
            except (ValueError, RuntimeError):   # refused: it failed
                r.refused = True
        r.submitted = self.clock()
        self.reqs[r.rid] = r

    @property
    def busy(self) -> bool:
        return not self.server.scheduler.idle

    def step(self) -> None:
        t_start, cpu0 = self.clock(), time.process_time()
        with harness.span("bench:step"):
            finished = self.server.step()
        t = self.clock()
        if t - t_start > self.SLOW_STEP_S:
            self.slow_steps.append({
                "at": t_start, "seconds": t - t_start,
                "cpu_seconds": time.process_time() - cpu0,
                "finished": len(finished),
                "queued": self.server.scheduler.pending_requests,
                "live": len(self.server.scheduler.slots)})
        with harness.span("bench:stamp"):
            live = live_tokens = 0
            for st in self.server.scheduler.slots.values():
                r = self.reqs[st.request.request_id]
                n = len(st.generated)
                self._seen(r, n, t, t_start)
                live += 1
                live_tokens += len(r.prompt) + n
            for rid in finished:
                r = self.reqs[rid]
                r.tokens = self.server.result(rid)[len(r.prompt):]
                self._seen(r, len(r.tokens), t, t_start)
                r.done = self.moved_at = t
                r.reason = self.server.finish_reason(rid)
                self.server.forget(rid)
            self.steps.append((t_start, t, live, live_tokens))

    def _seen(self, r: Tracked, n: int, t: float, t_start: float) -> None:
        if r.admitted is None:
            r.admitted = t_start       # it waited at least until this step
            self.admissions.append((t_start, len(r.prompt)))
        new = n - len(r.token_times)
        if new > 0:
            r.token_times.extend([t] * new)
            self.moved_at = t

    def alive(self, stall_s: float = STALL_S) -> None:
        """Raises :class:`ServerStalled` where the server has been busy
        for ``stall_s`` seconds with no new token and no finished
        request (called between steps by every loop here)."""
        idle = self.clock() - self.moved_at
        if idle > stall_s:
            sched = self.server.scheduler
            raise ServerStalled(
                f"the server is busy and nothing has moved for {idle:.0f} s "
                f"(limit {stall_s:.0f}): {len(self.steps)} steps taken, "
                f"{len(sched.slots)} slots resident, "
                f"{sched.pending_requests} queued")

    def drain(self, stall_s: float = STALL_S) -> None:
        self.moved_at = self.clock()
        while self.busy:
            self.step()
            self.alive(stall_s)


def build(config: dict, seed: int, family):
    from deepspeed_tpu.inference import (ContinuousBatchingServer,
                                         DeepSpeedInferenceConfig,
                                         InferenceEngine)
    cfg, params = family.serve_model(config["model"], seed)
    engine = InferenceEngine((cfg, params),
                             DeepSpeedInferenceConfig(**config["engine"]))
    return cfg, engine, ContinuousBatchingServer(engine)


def prefill_bucket(n: int, block: int, cap: int) -> int:
    """The padded length the server prefills an ``n``-token prompt at
    (``inference/engine.py`` ``_bucket``: 128 * 2**k, floored at the
    block size, capped at a slot's span). Used only to choose which
    prompts warm which program."""
    b = 128
    while b < n:
        b *= 2
    return min(max(b, block), cap)


def warm_and_check(sess: Session, cfg, engine, family, requests: list,
                   check: dict, seed: int) -> dict:
    """Serves seeded sample requests, one set for every prefill bucket
    this run's prompts touch (so every program the window uses is
    compiled and has run, the pipelined decode path included), then
    holds the served tokens to the plain float32 reference: it runs the
    full forward over prompt + served tokens, and every served token
    must be the reference's top choice or sit within ``tie_tolerance``
    (relative to the top logit's size) of it. Random weights make near
    ties common, so tokens are compared through the logits, never by
    equality. Prefill is checked by each sample's first token, decode
    through the paged cache by the rest."""
    block = engine.config.block_size
    cap = sess.server.max_blocks_per_slot * block
    n_out = int(check["output_tokens"])
    longest: Dict[int, int] = {}
    for r in requests:
        n = min(len(r["prompt"]), cap - n_out)
        b = prefill_bucket(n, block, cap)
        longest[b] = max(longest.get(b, 0), n)
    rng = np.random.default_rng(seed + 7)
    samples = []
    for b, n in sorted(longest.items()):
        for j in range(int(check.get("per_bucket", 1))):
            samples.append(Tracked(
                10 ** 9 + len(samples),
                traffic_lib.token_ids(rng, max(n - 3 * j, 1),
                                      cfg.vocab_size), n_out, 0.0, False))
    for r in samples:
        sess.submit(r)
    sess.drain()
    if any(r.failed for r in samples):
        return {"ok": False, "why": "a sample request failed: " + str(
            [(r.reason, len(r.token_times)) for r in samples])}
    T = max(len(r.prompt) for r in samples) + n_out - 1
    ids = np.zeros((len(samples), T), np.int32)
    pos = np.zeros((len(samples), n_out), np.int32)
    served = np.zeros((len(samples), n_out), np.int32)
    for i, r in enumerate(samples):
        full = r.prompt + list(r.tokens)
        ids[i, :len(full) - 1] = full[:-1]     # causal: padding is inert
        pos[i] = np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + n_out)
        served[i] = r.tokens
    weights = family.reference_from_serve(cfg, engine.params)
    lg = np.asarray(family.reference.logits_at(weights, ids, pos))
    lg = lg[..., :cfg.vocab_size]
    top = lg.max(-1)
    chosen = np.take_along_axis(lg, served[..., None], axis=-1)[..., 0]
    gap = (top - chosen) / np.maximum(1.0, np.abs(top))
    tol = float(check["tie_tolerance"])
    return {"ok": bool((gap <= tol).all()), "max_gap": float(gap.max()),
            "exact": int((gap == 0).sum()), "tokens": int(gap.size),
            "first_token_max_gap": float(gap[:, 0].max()),
            "buckets": sorted(longest), "samples": len(samples),
            "tolerance": tol}


def make_tracked(requests: list, base: int = 0,
                 counted: bool = True) -> List[Tracked]:
    """The generator's requests as tracked ones, ids from ``base``;
    ``counted=False`` marks them all as served but not counted."""
    return [Tracked(base + i, r["prompt"], r["out"], r["due"],
                    counted and r["counted"])
            for i, r in enumerate(requests)]


def ttft_ms(reqs: List[Tracked]) -> List[float]:
    """Due time to first visible token, per request; a request with no
    token (failed or refused) counts as the worst."""
    return [(r.token_times[0] - r.due) * 1e3 if r.token_times
            else float("inf") for r in reqs]


def itl_gaps_ms(reqs: List[Tracked]) -> List[float]:
    """Every gap between consecutive tokens of one request, pooled."""
    return [(b - a) * 1e3 for r in reqs
            for a, b in zip(r.token_times, r.token_times[1:])]


def run_backlog(sess: Session, reqs: List[Tracked], ring, seconds: float,
                tracer, trace_seconds: float,
                stall_s: float = STALL_S) -> dict:
    """``reqs`` (lap 0 of the backlog's ring) is queued at time zero
    (set-up); the window opens at the first step boundary at which every
    slot is resident and no prefill is pending, and closes at the first
    step boundary at or after ``seconds``. Output tokens stamped inside
    it count, whether or not their request finished in it.

    After every step the queue is topped up from ``ring`` (``(number,
    request)`` pairs without an end) to the depth it had at time zero,
    between two calls of ``server.step()`` and never inside one, so the
    server finds the same depth of queue however fast it empties its
    slots. The requests topped up are appended to ``reqs``. A refused
    top-up is a failed request, and ends that top-up."""
    depth = len(reqs)
    for r in reqs:
        sess.submit(r)
    sched = sess.server.scheduler
    slots = sess.server.num_slots
    top = {"requests": 0, "seconds": 0.0, "lowest_queue": depth}

    def step() -> bool:
        """One step and its top-up; whether the step ended with a free
        slot and nothing queued (the next step admits what this one's
        retirements made room for, so a free slot alone is not dry)."""
        sess.step()
        queued = sched.pending_requests
        top["lowest_queue"] = min(top["lowest_queue"], queued)
        if queued < depth:
            t = sess.clock()
            while sched.pending_requests < depth:
                number, r = next(ring)
                extra = Tracked(number, r["prompt"], r["out"], r["due"],
                                r["counted"])
                reqs.append(extra)
                sess.submit(extra)
                top["requests"] += 1
                if extra.refused:
                    break
            top["seconds"] += sess.clock() - t
        return sess.steps[-1][2] < slots and not queued

    sess.moved_at = sess.clock()
    while len(sched.slots) < slots and sess.busy:
        step()
        sess.alive(stall_s)     # slots that never fill: ServerStalled
    step()                             # one step with every slot decoding
    gc.collect()
    gc.freeze()
    tracer.start()
    t0 = sess.clock()
    first_step = len(sess.steps)
    top.update(requests=0, seconds=0.0, lowest_queue=depth)
    dry = False
    while True:
        dry = step() or dry
        now = sess.clock()
        if tracer.active and (now - t0 >= trace_seconds or len(sess.steps)
                              - first_step >= MAX_EXECUTIONS):
            tracer.stop()
        if now - t0 >= seconds:
            break
    return {"t0": t0, "t1": now, "first_step": first_step, "ran_dry": dry,
            "top_up": top}


def run_open_loop(sess: Session, reqs: List[Tracked], seconds: float,
                  lead_in: float, tracer, trace_seconds: float,
                  stop_after=None, stall_s: float = STALL_S,
                  drain_s: float = DRAIN_S,
                  leave_when_traced: bool = False) -> dict:
    """Requests are submitted when they are due (never earlier; how much
    later is the generator's lateness). The schedule starts ``lead_in``
    seconds before the window opens. After the window closes the
    requests that were due in it are drained and still count. An
    enabled tracer has its profiler started ``PROFILE_LEAD_S`` before
    this schedule's window opens (starting it takes tenths of a second,
    in which no step runs: not at the window's edge), its window opened
    with this schedule's, and is stopped ``trace_seconds`` or
    ``MAX_EXECUTIONS`` steps later, whichever comes first; with
    ``leave_when_traced`` the loop then ends, with whatever is still in
    flight (nothing reads it).
    A server that stays busy with nothing moving for ``stall_s``
    seconds, or stays busy ``drain_s`` seconds after the last request
    was due, ends the run with :class:`ServerStalled` /
    :class:`DrainCeiling`."""
    gc.collect()
    gc.freeze()
    begin = sess.moved_at = sess.clock()
    t0 = begin + lead_in
    for r in reqs:
        r.due += t0
    last_due = max([t0 + seconds] + [r.due for r in reqs])
    first_step = None
    i, n = 0, len(reqs)
    while True:
        now = sess.clock()
        if tracer.enabled and not tracer.active and tracer.t1 is None \
                and now >= t0 - PROFILE_LEAD_S:
            tracer.start(window=False)
            now = sess.clock()
        if first_step is None and now >= t0:
            first_step = len(sess.steps)
            tracer.open_window()
        if tracer.active and (now - t0 >= trace_seconds or (
                first_step is not None
                and len(sess.steps) - first_step >= MAX_EXECUTIONS)):
            tracer.stop()
            if leave_when_traced:
                break
        while i < n and reqs[i].due <= now:
            sess.submit(reqs[i])
            i += 1
        if sess.busy:
            sess.step()
            sess.alive(stall_s)
            if now - last_due > drain_s:
                raise DrainCeiling(
                    f"the server is still busy {now - last_due:.0f} s after "
                    f"the last request was due (limit {drain_s:.0f}): "
                    f"{sum(1 for r in reqs if r.done is None)} of {n} "
                    "requests unfinished")
        elif i >= n:
            break
        else:
            sess.moved_at = now        # idle, not stalled
            with harness.span("bench:wait"):
                time.sleep(min(max(reqs[i].due - sess.clock(), 0.0), 0.002))
        if stop_after is not None and now - t0 >= stop_after:
            break
    return {"t0": t0, "t1": t0 + seconds,
            "first_step": first_step if first_step is not None
            else len(sess.steps), "ran_dry": False}


def run(cell: dict, args, t_start: float, family, devices) -> dict:
    config, traffic = cell["config"], cell["traffic"]
    compiles = harness.CompileCounter()
    marks = harness.Marks(t_start)
    marks.mark("imports")
    made = traffic_lib.build_requests(traffic, args.seconds, args.seed,
                                      config["model"]["vocab_size"])
    harness.log({"traffic_totals": made["totals"]})
    marks.mark("traffic")
    cfg, engine, server = build(config, args.seed, family)
    marks.mark("weights_engine_pool")
    sess = Session(server)
    tracer = harness.Tracer(bool(args.trace), cell["cell"]["name"])
    try:
        check = warm_and_check(sess, cfg, engine, family, made["requests"],
                               traffic["check"], args.seed)
        harness.log({"reference_check": check})
        marks.mark("warmup_and_reference")
        reqs = make_tracked(made["requests"])
        compiled_before = compiles.count
        trace_seconds = float(traffic.get("trace_seconds", 4.0))
        if traffic["kind"] == "backlog":
            ring = traffic_lib.backlog_ring(
                traffic, args.seed, config["model"]["vocab_size"],
                first_lap=1)
            win = run_backlog(sess, reqs, ring, args.seconds, tracer,
                              trace_seconds)
        else:
            lead_in = float(traffic.get("lead_in_s", 0.0))
            win = run_open_loop(sess, reqs, args.seconds, lead_in,
                                harness.Tracer(False, ""), 0.0)
        tail = harness.TAIL
        tail.mark("window_closed", ago=sess.clock() - win["t1"])
        tail.mark("drained")
        # set-up ends where the measured window opens: the lead-in and
        # the filling of the slots are set-up that the traffic needs
        setup_s = (time.time() - t_start) - (sess.clock() - win["t0"])
        marks.at.append(["window_opens", round(setup_s, 3)])
        harness.log({"setup_marks": marks.at})
        compiles_in_window = compiles.count - compiled_before
        from benchmark.lib.peaks import memory_peak_bytes
        mem = memory_peak_bytes(devices)
        if tracer.enabled and traffic["kind"] == "open_loop":
            # the trace is taken AFTER the measured window has drained:
            # stopping the profiler stalls the loop for seconds, which
            # inside the window would be read as queue wait. A second,
            # short schedule of the same mix (lead-in, then
            # ``trace_seconds``) is traced; its requests are served and
            # not counted, and it ends where its trace does.
            extra = make_tracked(traffic_lib.build_requests(
                traffic, trace_seconds, args.seed + 1,
                config["model"]["vocab_size"])["requests"],
                base=2 * 10 ** 9, counted=False)
            run_open_loop(sess, extra, trace_seconds, lead_in, tracer,
                          trace_seconds, leave_when_traced=True)
            tail.mark("trace_schedule_done")
    finally:
        tracer.stop()
        sess.close()
        harness.TAIL.mark("server_closed")
    t0, t1 = win["t0"], win["t1"]
    harness.log({"slow_steps": [dict(s, at=s["at"] - t0)
                                for s in sess.slow_steps],
                 "gen2_collections": [[a - t0, d]
                                      for a, d in sess.gc_pauses]})
    counted = [r for r in reqs if r.counted and r.submitted is not None]
    if traffic["kind"] == "backlog":
        # the offline job's requests that the window touched, and any
        # the server refused
        touched = [r for r in counted if r.refused or (
            r.token_times and r.token_times[-1] > t0
            and r.token_times[0] <= t1)]
        failed = [r for r in touched if r.refused or (
            r.done is not None and r.reason not in OK_REASONS)]
        per_lap = made["totals"]["requests"]
        top = win["top_up"]
        made["totals"].update(
            requests_offered=len(counted),
            laps_touched=1 + max((r.rid for r in touched),
                                 default=0) // per_lap)
        harness.log({"backlog": dict(
            made["totals"], topped_up_in_window=top["requests"],
            lowest_queue_in_window=top["lowest_queue"],
            top_up_seconds_in_window=top["seconds"],
            top_up_share_of_window_pct=100.0 * top["seconds"] / (t1 - t0))})
    else:
        touched = counted
        failed = [r for r in touched if r.failed]
    window_tokens = sum(1 for r in reqs for t in r.token_times
                        if t0 < t <= t1)
    gaps = itl_gaps_ms(touched)
    harness.log({"sample_counts": {
        "requests_behind_ttft": len(touched), "gaps_behind_itl": len(gaps),
        "tokens_in_window": window_tokens,
        "steps_in_window": sum(1 for st in sess.steps if t0 < st[1] <= t1),
        "failed": len(failed)}})
    if traffic["kind"] == "open_loop" and len(gaps) > 1:
        from statistics import mean, quantiles

        def summary(v):
            q = quantiles(v, n=100, method="inclusive")
            return {"mean": mean(v), "p50": q[49], "p90": q[89],
                    "p99": q[98], "max": max(v)}
        harness.log({"ttft_ms": summary(ttft_ms(touched)),
                     "itl_ms": summary(gaps)})
    checks = {"matches_reference": check["ok"],
              "no_compile_in_window": compiles_in_window == 0,
              "backlog_never_dry": not win["ran_dry"],
              "nothing_failed": not failed}
    return {
        "kind": "serve", "traffic_kind": traffic["kind"],
        "setup_s": setup_s, "window_s": t1 - t0, "t0": t0, "t1": t1,
        "requests": reqs, "counted": touched, "steps": sess.steps,
        "first_step": win["first_step"], "admissions": sess.admissions,
        "window_tokens": window_tokens, "totals": made["totals"],
        "num_slots": server.num_slots,
        "reference_check": check,
        "compile_s": harness.watched_compile_seconds(),
        "jax_compile_s": compiles.seconds,
        "compiles_in_window": compiles_in_window,
        "memory_peak_bytes": mem, "checks": checks,
        # each number compared, beside its limit
        "compared": {
            "max_gap": [check.get("max_gap"), check.get("tolerance")],
            "compiles_in_window": [compiles_in_window, 0],
            "failed_requests": [len(failed), 0],
            "steps_run_dry": [int(win["ran_dry"]), 0]},
        "attempted": len(touched), "failed": len(failed), "tracer": tracer,
    }
