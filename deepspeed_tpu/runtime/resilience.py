"""Fault-tolerant training: the supervised train loop.

PR 13 gave serving a supervised replica pool; this is the training
mirror (docs/training.md "Fault-tolerant training & verified
checkpoints"). On preemptible TPU pods the dominant real-world failure
is a mid-step or mid-save kill — and the bare ``train_batch`` loop dies
wholesale on any of them. :class:`TrainingSupervisor` wraps the loop and
guarantees **forward progress or a loud terminal ``failed`` — never a
hang**:

* every fault class is caught at its site — a step that raises (crash /
  seeded preemption), a NaN storm surfaced through the PR-4 numerics
  watch or a non-finite loss, a dataloader stall past the configured
  timeout, a checkpoint write that dies mid-publication;
* recovery rolls back to the last **verified** checkpoint
  (runtime/checkpointing.py's fallback ladder skips corrupted tags),
  which restores params/optimizer/loss-scale/step **and the PRNG
  stream**, then replays forward — so a recovered run's loss trajectory
  and final params are bit-identical to the undisturbed one (the
  headline oracle, pinned in tests/test_resilience.py);
* restarts are bounded (``resilience.max_restarts``) with exponential
  backoff between attempts; an exhausted budget ends the run with
  ``status="failed"`` and the fault chain attached.

Determinism contract: the caller supplies ``batch_fn(step) -> batch`` —
a pure function of the global step (the seeded-dataloader idiom), so a
replayed step consumes the same bytes. Clock and sleep are injectable:
the chaos suite drives everything on a fake clock with zero real
sleeps.
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, List, Optional

from deepspeed_tpu.telemetry import events as _ev
from deepspeed_tpu.telemetry.faultinject import (CkptWriteFault, DataStall,
                                                 FaultInjector, StepCrash,
                                                 TrainingPreempted)
from deepspeed_tpu.utils.logging import logger


class TrainingFailed(RuntimeError):
    """Terminal supervisor outcome: the restart budget is exhausted (or
    recovery itself is impossible). Raised only with
    ``run(raise_on_failure=True)``; the default is a returned record
    with ``status="failed"`` so harnesses can inspect the fault chain."""


class _NanBurst(RuntimeError):
    """Internal fault token for a detected non-finite step (loss or
    numerics-watch provenance) — never escapes the supervisor."""


# fault-exception -> restart-counter kind label (telemetry/faultinject.py
# kind constants; anything unlisted counts as a generic step_crash)
_FAULT_KINDS = (
    (TrainingPreempted, "preempt_step"),
    (StepCrash, "step_crash"),
    (DataStall, "data_stall"),
    (CkptWriteFault, "ckpt_write_failure"),
    (_NanBurst, "nan_burst"),
)


def _classify(exc: BaseException) -> str:
    # walk the cause chain: an async finalize failure resurfaces as
    # `RuntimeError(...) from CkptWriteFault` at the next save's join —
    # the restart counter must still say ckpt_write_failure
    seen = set()
    cur: Optional[BaseException] = exc
    while cur is not None and id(cur) not in seen:
        seen.add(id(cur))
        for etype, kind in _FAULT_KINDS:
            if isinstance(cur, etype):
                return kind
        cur = cur.__cause__
    return "step_crash"


class TrainingSupervisor:
    """Supervise ``engine.train_batch`` to ``target`` steps under faults.

    Parameters
    ----------
    engine : DeepSpeedEngine
        The live training engine (its ``global_steps`` is the loop
        cursor — a supervisor can resume a half-done run).
    save_dir : str
        Checkpoint root. An initial verified checkpoint is written
        before the first supervised step so rollback always has a rung.
    batch_fn : Callable[[int], batch]
        Deterministic batch source keyed by global step.
    config : ResilienceConfig, optional
        Defaults to ``engine.config.resilience``.
    clock / sleep : injectable time sources (chaos tests pass a fake
        clock and a recording sleep — zero real waiting).
    injector : FaultInjector, optional
        Defaults to ``engine.fault_injector`` (built from
        ``telemetry.fault_injection``); present = its training-scoped
        arms are consulted every step.
    """

    _LOSS_KEEP = 100_000   # newest loss entries retained for the record

    def __init__(self, engine, save_dir: str,
                 batch_fn: Callable[[int], Any],
                 config=None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Optional[Callable[[float], None]] = None,
                 injector: Optional[FaultInjector] = None):
        self.engine = engine
        self.save_dir = str(save_dir)
        self.batch_fn = batch_fn
        self.config = config if config is not None \
            else engine.config.resilience
        self._clock = clock
        self._sleep = sleep if sleep is not None else time.sleep
        self.injector = injector if injector is not None \
            else getattr(engine, "fault_injector", None)
        self._replaced_engine_injector = False
        self._prev_engine_injector = None
        if injector is not None and \
                getattr(engine, "fault_injector", None) is not injector:
            # the checkpoint write site consults engine.fault_injector —
            # a supervisor-scoped injector must reach it too (replacing
            # a config-built one: split-brain arms would mean the
            # supervisor consults one injector and the checkpoint layer
            # another, and armed ckpt_write_failure faults would
            # silently never fire)
            if getattr(engine, "fault_injector", None) is not None:
                logger.warning(
                    "TrainingSupervisor injector replaces the engine's "
                    "config-built fault injector (one injector serves "
                    "both the step and checkpoint-write sites)")
            self._prev_engine_injector = getattr(
                engine, "fault_injector", None)
            self._replaced_engine_injector = True
            engine.fault_injector = injector
        self.registry = engine.telemetry
        self.status = "idle"
        self.restarts = 0
        self.checkpoints_saved = 0
        self.last_tag: Optional[str] = None
        self.recovery_s_total = 0.0
        self.faults: List[dict] = []
        self._target: Optional[int] = None
        self._losses: Dict[int, float] = {}
        # single persistent fetch worker (lazy; only with a real
        # data_stall_timeout_s): batch_fn must never be entered by two
        # threads at once — see _fetch_batch
        self._fetch_req = None
        self._fetch_resp = None
        self._fetch_seq = 0
        # numerics-watch high-water mark: the watch's state is NOT
        # rolled back with the engine, so a stale non-finite record
        # must never re-trigger against a clean replayed step — only a
        # GROWING non-finite total is a fresh burst
        self._nonfinite_seen = self._watch_nonfinite_total()
        register_supervisor(self)

    def _watch_nonfinite_total(self) -> int:
        watch = getattr(self.engine, "numerics", None)
        if watch is None:
            return 0
        try:
            return int(watch.snapshot()["nonfinite"]["steps_total"])
        except Exception:  # noqa: BLE001
            return 0

    # ----------------------------------------------------------- plumbing

    def _observe_recovery(self, seconds: float) -> None:
        self.recovery_s_total += seconds
        self.registry.histogram(
            "train_recovery_seconds",
            help="fault detection to rollback-complete, per restart "
                 "(runtime/resilience.py TrainingSupervisor; includes "
                 "the backoff wait)").observe(seconds)

    def _count_restart(self, kind: str) -> None:
        self.restarts += 1
        self.registry.counter(
            "train_restarts_total",
            help="supervised training restarts, by fault kind "
                 "(runtime/resilience.py; bounded by "
                 "resilience.max_restarts)",
            labels={"kind": kind}).inc()

    def _heartbeat(self) -> None:
        wd = getattr(self.engine, "watchdog", None)
        if wd is not None:
            wd.notify_progress()

    def _suspended(self):
        """Watchdog suspension around checkpoint save/rollback — real
        seconds without step progress that must not read as a hang."""
        wd = getattr(self.engine, "watchdog", None)
        if wd is not None:
            return wd.suspend()
        import contextlib
        return contextlib.nullcontext()

    # -------------------------------------------------------- fault sites

    def _fetch_batch(self, step: int):
        if self.injector is not None:
            self.injector.check_data(step)
        timeout = self.config.data_stall_timeout_s
        t0 = self._clock()
        if timeout is None:
            return self.batch_fn(step)
        # a batch_fn that never returns must not hang the supervisor
        # (the "forward progress or a loud failed" contract): fetch on
        # ONE persistent worker thread with a REAL-time bound. A single
        # worker means batch_fn is never entered by two threads at once
        # — a timed-out fetch stays outstanding ON that worker, so the
        # replay after rollback queues BEHIND it instead of re-entering
        # a shared iterator/pipeline concurrently. A transient stall
        # that clears lets the worker drain the stale fetch (its result
        # is dropped by sequence number) and serve the replay; a dead
        # source stalls every replay and exhausts max_restarts into a
        # loud `failed`. The injectable-clock check below still covers
        # slow-but-returning fetches, which is what the fake-clock
        # chaos tests drive.
        batch = self._fetch_via_worker(step, timeout)
        waited = self._clock() - t0
        if waited > timeout:
            raise DataStall(
                f"batch fetch for step {step} took {waited:.3f}s "
                f"(> data_stall_timeout_s={timeout})")
        return batch

    def _fetch_via_worker(self, step: int, timeout: float):
        import queue
        import threading
        if self._fetch_req is None:
            self._fetch_req = queue.Queue()
            self._fetch_resp = queue.Queue()
            # the loop must not strongly capture self: a supervisor
            # dropped without close() would otherwise be pinned forever
            # (with the engine and its params) by a thread blocked in
            # queue.get()
            import weakref
            req, resp = self._fetch_req, self._fetch_resp
            owner_ref = weakref.ref(self)

            def _loop():
                while True:
                    item = req.get()
                    if item is None:
                        return
                    seq, s = item
                    owner = owner_ref()
                    if owner is None:
                        return
                    try:
                        resp.put((seq, "ok", owner.batch_fn(s)))
                    except BaseException as e:  # noqa: BLE001
                        resp.put((seq, "error", e))
                    finally:
                        del owner

            threading.Thread(target=_loop, daemon=True,
                             name="ds-batch-fetch").start()
        self._fetch_seq += 1
        seq = self._fetch_seq
        self._fetch_req.put((seq, step))
        deadline = time.monotonic() + timeout
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise DataStall(
                    f"batch fetch for step {step} still blocked after "
                    f"data_stall_timeout_s={timeout}s (fetch left "
                    "outstanding on the worker)")
            try:
                rseq, kind, payload = self._fetch_resp.get(
                    timeout=remaining)
            except queue.Empty:
                raise DataStall(
                    f"batch fetch for step {step} still blocked after "
                    f"data_stall_timeout_s={timeout}s (fetch left "
                    "outstanding on the worker)")
            if rseq != seq:
                continue  # stale result from an abandoned fetch
            if kind == "error":
                raise payload
            return payload

    def _poison_params(self) -> None:
        """Inject the armed NaN burst into the live params — the storm
        then flows through the real step, the real numerics watch, and
        the real detection below; nothing is simulated."""
        import jax
        import jax.numpy as jnp
        leaves, treedef = jax.tree_util.tree_flatten(
            self.engine.state.params)
        leaves = list(leaves)
        leaves[0] = jnp.full_like(leaves[0], jnp.nan)
        self.engine.state = self.engine.state.replace(
            params=jax.tree_util.tree_unflatten(treedef, leaves))

    def _check_numerics(self, step: int, loss: float) -> None:
        if not self.config.restart_on_nan:
            return
        if not math.isfinite(loss):
            raise _NanBurst(f"non-finite loss at step {step}: {loss}")
        watch = getattr(self.engine, "numerics", None)
        if watch is None:
            return
        total = self._watch_nonfinite_total()
        if total > self._nonfinite_seen:
            self._nonfinite_seen = total
            last = watch.snapshot().get("nonfinite", {}).get("last") or {}
            raise _NanBurst(
                f"numerics watch flagged non-finite grads at step "
                f"{step} (block {last.get('block')!r})")

    # ----------------------------------------------------------- recovery

    def _save(self) -> None:
        with self._suspended():
            path = self.engine.save_checkpoint(self.save_dir)
        self.checkpoints_saved += 1
        import os
        self.last_tag = os.path.basename(path)

    def _join_finalize(self) -> None:
        """Block until an in-flight async checkpoint finalize lands (a
        sync engine has nothing pending). A failure raises — with the
        original :class:`CkptWriteFault` in the cause chain, so
        ``_classify`` still counts it as ``ckpt_write_failure``."""
        from deepspeed_tpu.runtime.checkpointing import (
            _join_pending_finalize)
        with self._suspended():
            _join_pending_finalize(self.engine)

    def _recover(self, step: int, exc: BaseException, kind: str) -> None:
        """Roll back to the last verified checkpoint after backoff.
        Raises :class:`TrainingFailed` when the budget is exhausted or
        rollback itself is impossible — the loop exits, never spins."""
        t0 = self._clock()
        # the budget-exhausting fault is NOT a restart: no rollback
        # happens for it, so neither self.restarts nor
        # train_restarts_total tick — the counter stays bounded by
        # max_restarts exactly as its help text and the docs promise
        exhausted = self.restarts + 1 > self.config.max_restarts
        if not exhausted:
            self._count_restart(kind)
        attempt = self.restarts + (1 if exhausted else 0)
        self.faults.append({"step": step, "kind": kind,
                            "error": f"{type(exc).__name__}: {exc}",
                            "restart": attempt})
        _ev.record_event(_ev.TRAIN_FAULT, step=step, fault=kind,
                         restart=attempt,
                         max_restarts=self.config.max_restarts,
                         error=str(exc))
        logger.error(
            f"training fault at step {step} ({kind}): {exc!r} — restart "
            f"{attempt}/{self.config.max_restarts}")
        if exhausted:
            raise TrainingFailed(
                f"restart budget exhausted ({self.config.max_restarts}) "
                f"after {kind} at step {step}") from exc
        backoff = min(
            self.config.backoff_base_s * (2.0 ** (self.restarts - 1)),
            self.config.backoff_max_s)
        if backoff > 0:
            self._sleep(backoff)
        self._heartbeat()
        try:
            with self._suspended():
                # an async finalize from a save that later failed must
                # not poison the reload: surface + clear it first. It
                # is RECORDED (fault list + ring), not silently dropped
                # — a genuine commit failure discovered here would
                # otherwise leave no trace beyond a thread log line —
                # but it does not consume a second restart: this
                # recovery is already paying for a counted fault.
                from deepspeed_tpu.runtime.checkpointing import (
                    _join_pending_finalize)
                try:
                    _join_pending_finalize(self.engine)
                except RuntimeError as e:
                    k2 = _classify(e)
                    self.faults.append(
                        {"step": step, "kind": k2,
                         "error": f"{type(e).__name__}: {e}",
                         "restart": self.restarts,
                         "during_recovery": True})
                    _ev.record_event(
                        _ev.TRAIN_FAULT, step=step, fault=k2,
                        restart=self.restarts, during_recovery=True,
                        error=str(e))
                    logger.error(
                        f"pending checkpoint finalize failed during "
                        f"recovery ({k2}): {e!r} — rolling back past it")
                path, _ = self.engine.load_checkpoint(self.save_dir)
        except Exception as e:
            raise TrainingFailed(
                f"rollback after {kind} at step {step} found no loadable "
                f"checkpoint: {e}") from e
        if path is None:
            raise TrainingFailed(
                f"rollback after {kind} at step {step}: no checkpoint "
                f"under {self.save_dir!r}")
        import os
        # the last DURABLE tag is the one we just restored — a failed
        # save's name must not linger here, or the terminal-save
        # dedup would skip re-publishing it after recovery
        self.last_tag = os.path.basename(path)
        # resync the numerics high-water mark: whatever non-finite steps
        # the watch counted BEFORE the rollback belong to the timeline
        # we just discarded, not to the replay
        self._nonfinite_seen = self._watch_nonfinite_total()
        seconds = self._clock() - t0
        self._observe_recovery(seconds)
        self._heartbeat()
        _ev.record_event(_ev.TRAIN_RESUME, from_step=step,
                         resumed_step=self.engine.global_steps,
                         restart=self.restarts,
                         recovery_seconds=round(seconds, 6),
                         backoff_seconds=backoff, checkpoint=path)
        logger.warning(
            f"resumed from step {self.engine.global_steps} after {kind} "
            f"at step {step} ({seconds:.3f}s recovery, "
            f"{backoff:.3f}s backoff)")

    # --------------------------------------------------------------- run

    def run(self, target: int,
            raise_on_failure: bool = False) -> Dict[str, Any]:
        """Supervise until ``engine.global_steps == target``. Returns a
        JSON-able record; ``status`` is ``"completed"`` or ``"failed"``
        (with the fault chain in ``faults``) — this method returns or
        raises, it never hangs."""
        engine = self.engine
        if target <= engine.global_steps:
            raise ValueError(
                f"target {target} must exceed the engine's current "
                f"global_steps {engine.global_steps}")
        self.status = "running"
        self._target = target
        t_wall = self._clock()
        failure: Optional[str] = None
        fault_exc: Optional[BaseException] = None
        try:
            if not self.checkpoints_saved:
                # rung zero: rollback must always have somewhere to land
                # — a failure HERE is terminal (there is nothing to roll
                # back to), not a restartable fault
                try:
                    self._save()
                except Exception as e:  # noqa: BLE001
                    raise TrainingFailed(
                        f"initial checkpoint under {self.save_dir!r} "
                        f"failed: {e}") from e
            while True:
                step = engine.global_steps
                try:
                    if step >= target:
                        # terminal checkpoint: the finished run is
                        # durable (inside the fault envelope — a
                        # mid-save kill here recovers like any other).
                        # An async engine's finalize is JOINED before
                        # "completed" is claimed: the status must never
                        # get ahead of the bytes on disk.
                        if self.last_tag != f"global_step{step}":
                            self._save()
                        self._join_finalize()
                        break
                    if self.injector is not None:
                        self.injector.check_train_step(step)
                        if self.injector.nan_burst_due(step):
                            self._poison_params()
                    batch = self._fetch_batch(step)
                    metrics = engine.train_batch(batch)
                    loss = float(metrics["loss"])
                    self._check_numerics(step, loss)
                    self._losses[step] = loss
                    # bounded retention: the returned trajectory keeps
                    # the newest _LOSS_KEEP entries — a multi-month
                    # supervised run must not grow host memory one
                    # float per step forever (parity oracles compare
                    # runs far shorter than the cap)
                    while len(self._losses) > self._LOSS_KEEP:
                        del self._losses[next(iter(self._losses))]
                    self._heartbeat()
                    if engine.global_steps < target and \
                            engine.global_steps % \
                            self.config.checkpoint_every == 0:
                        self._save()
                except Exception as e:  # noqa: BLE001 — the whole point
                    self._recover(step, e, _classify(e))
            self.status = "completed"
        except TrainingFailed as e:
            self.status = "failed"
            failure = str(e)
            fault_exc = e
            logger.error(f"supervised training FAILED: {e}")
        wall = self._clock() - t_wall
        record = self.snapshot()
        record.update({
            "wall_s": round(wall, 6),
            "losses": [self._losses[s] for s in sorted(self._losses)],
            "goodput_under_chaos": round(
                1.0 - min(self.recovery_s_total, wall) / wall, 6)
            if wall > 0 else 1.0,
        })
        if failure is not None:
            record["failure"] = failure
            if raise_on_failure:
                raise fault_exc
        return record

    # ----------------------------------------------------------- surface

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able supervisor state — ``GET /debug/resilience`` reads
        this."""
        out = {
            "status": self.status,
            "step": int(self.engine.global_steps),
            "target": self._target,
            "restarts": self.restarts,
            "max_restarts": self.config.max_restarts,
            "faults": list(self.faults[-16:]),
            "recovery_s_total": round(self.recovery_s_total, 6),
            "checkpoints_saved": self.checkpoints_saved,
            "checkpoint_every": self.config.checkpoint_every,
            "last_tag": self.last_tag,
            "backoff": {"base_s": self.config.backoff_base_s,
                        "max_s": self.config.backoff_max_s},
        }
        try:
            from deepspeed_tpu.runtime.checkpointing import (
                checkpoint_integrity_report)
            out["checkpoint_integrity"] = checkpoint_integrity_report(
                self.save_dir)
        except Exception as e:  # noqa: BLE001 — surface must not throw
            out["checkpoint_integrity"] = {"error": str(e)}
        if self.injector is not None:
            out["fault_injection"] = self.injector.snapshot()
        return out

    def close(self) -> None:
        # a supervisor-scoped injector must not outlive the supervisor:
        # its chaos arms (every-Nth-save write failures, seeded crashes)
        # would keep firing on the bare engine with no recovery path
        if self._replaced_engine_injector and \
                getattr(self.engine, "fault_injector", None) \
                is self.injector:
            self.engine.fault_injector = self._prev_engine_injector
        if self._fetch_req is not None:
            self._fetch_req.put(None)  # worker shutdown; never joined —
            # a wedged batch_fn must not hang close()
        unregister_supervisor(self)


# ---------------------------------------------------------------- registry
# process-wide supervisor registry: /debug/resilience and dstpu_report
# read whatever supervisors are alive without holding them alive

_supervisors: list = []


def register_supervisor(sup: TrainingSupervisor) -> None:
    import weakref
    _supervisors.append(weakref.ref(sup))


def unregister_supervisor(sup: TrainingSupervisor) -> None:
    _supervisors[:] = [r for r in _supervisors
                       if r() is not None and r() is not sup]


def resilience_snapshot() -> dict:
    """Every live supervisor's snapshot — the ``/debug/resilience``
    payload (self-describing when none is armed)."""
    alive = []
    for ref in list(_supervisors):
        sup = ref()
        if sup is not None:
            try:
                alive.append(sup.snapshot())
            except Exception as e:  # noqa: BLE001
                alive.append({"error": str(e)})
    _supervisors[:] = [r for r in _supervisors if r() is not None]
    if not alive:
        return {"enabled": False,
                "hint": "no TrainingSupervisor armed (wrap the train "
                        "loop with runtime/resilience.py — docs/"
                        "training.md 'Fault-tolerant training & "
                        "verified checkpoints')"}
    return {"enabled": True, "supervisors": alive}


def supervise(engine, save_dir: str, batch_fn: Callable[[int], Any],
              target: int, **kwargs) -> Dict[str, Any]:
    """One-call spelling: build a supervisor from the engine's
    ``resilience`` config and run to ``target`` steps."""
    sup = TrainingSupervisor(engine, save_dir, batch_fn, **kwargs)
    try:
        return sup.run(target)
    finally:
        sup.close()
