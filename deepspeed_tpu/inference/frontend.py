"""Replicated serving frontend: a supervised pool of server replicas.

The robustness half of ROADMAP item 3 (docs/serving.md "Replicated
serving & failover"): however hardened ONE ``ContinuousBatchingServer``
is — lifecycle, fault injection, watchdog — it still dies wholesale with
its process/thread: one wedged or killed server loses every queued and
in-flight request. :class:`ServingFrontend` owns N in-process replicas
(each with its own paged pool, scheduler, and traced programs over the
SHARED engine weights; the process-per-replica jump with per-replica
meshes is item 3 proper) behind one ``submit()/step()/drain()/result()``
surface, built on three pillars:

* **Health-checked routing** — a per-replica state machine (healthy →
  degraded → dead) driven by step-completion heartbeats riding the
  existing watchdog plumbing: every replica gets an (unstarted)
  :class:`~deepspeed_tpu.telemetry.watchdog.Watchdog` installed on the
  server's ``watchdog`` seam, so every site that already notifies
  progress (decode, prefill chunk, lifecycle action, idle poll) feeds
  the frontend's heartbeat for free. Admission is least-loaded (queue
  depth + residents, ties to the most free blocks) over HEALTHY
  replicas; a degraded replica trips the breaker — no new routing, its
  residents keep decoding — and recovers when its beats return. The
  breaker fails OPEN: with zero healthy replicas, degraded ones accept
  work rather than deadlocking the pool.

* **Mid-flight failover** — a replica whose step raises, or whose
  heartbeat goes stale past ``replication.heartbeat_dead_s``, is
  declared DEAD (permanent in-process; item 3's supervisor restarts
  processes): every request it held — queued, mid-prefill, or
  mid-decode — folds its committed tokens into the prompt
  (``Request.committed → sched_prompt``, the PR-7 recompute-preemption
  idiom) and resubmits to a survivor after a bounded exponential
  backoff. Greedy output is token-identical to an uninterrupted
  one-shot ``generate()`` through a mid-decode kill, because only
  COMMITTED tokens replay and greedy continuation from a replayed
  prefix is exact (the preempt→requeue oracle, now across replicas).
  Retries exhausted → finish reason ``failed``, never a hang.

* **Rolling drain** — :meth:`drain_replica` steers traffic away
  (unroutable), re-routes its QUEUED work to peers immediately
  (``server.reclaim`` — cancel-and-forget, so the id stays
  resubmittable), lets residents finish in place (their prefix cache
  stays warm), and re-admits the replica once idle: a config reload or
  rolling restart loses zero requests.

* **Disaggregated prefill/decode** (``replication.roles`` —
  docs/serving.md "Disaggregated prefill/decode"): DistServe/Splitwise-
  style phase separation over the same supervision substrate. A request
  routes first to a ``prefill``-role replica with a ONE-token budget:
  it chunk-prefills, commits the first token, retires — and its
  block-aligned KV (payload + int8 scale tiles, all layers, via
  ``paged_read_block``) publishes into a shared
  :class:`~deepspeed_tpu.inference.disagg.HandoffTier` keyed by the
  prefix chain hash. The request then resubmits (committed token
  folded into the prompt) to a ``decode``-role replica picked by
  TELEMETRY — load, then the step observatory's recent dispatch-gap
  mean, then free blocks — whose admission warms every published
  block back in through the existing ``match_prefix`` →
  ``paged_swap_in`` machinery (one jitted donated scatter per block,
  zero new executables) and recomputes only the sub-block tail as one
  short chunk. Chunked prefill thus never steals a device program
  from resident decoders, which is the entire point. Every failure
  mode degrades to the recompute idiom above (a dead prefill replica
  mid-publish, an expired bounded tier, a wrong-role last-resort
  route) — greedy output is token-identical to a single mixed server
  through every path, and a terminal finish abandons any unconsumed
  publication so the bounded tier never strands an entry.

Determinism contract (the chaos suite depends on it): replicas step in
index order on the caller's thread by default, every clock read goes
through the injectable frontend clock, and the replica-scoped fault
kinds (kill / wedge / heartbeat-loss / slow-step —
telemetry/faultinject.py) are consulted at fixed points of ``step()``.
``replication.threaded_step`` moves each replica's step onto its own
dedicated worker thread with a barrier at the end of the frontend step —
device programs overlap across replicas, while every health/routing
decision still happens on the owner thread against joined results.
"""
from __future__ import annotations

import json
import threading
import time
import weakref
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

from deepspeed_tpu.inference.disagg import (DECODE, MIXED, PREFILL,
                                            HandoffTier)
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.kv_cache import prefix_block_hashes
from deepspeed_tpu.inference.server import (_LIFECYCLE_EVENTS,
                                            ContinuousBatchingServer,
                                            check_drain_timeout,
                                            submit_rejection)
from deepspeed_tpu.telemetry import (CANARY_TENANT, AlertEngine,
                                     CanaryProber, FaultInjector,
                                     IncidentRecorder, MetricRegistry,
                                     ReplicaKilled, TenantMeter, Tracer,
                                     Watchdog, config_fingerprint,
                                     get_event_ring, get_registry,
                                     merge_cost_legs, new_cost_record,
                                     register_cost_histograms,
                                     rollup_capacity, start_http_server)
from deepspeed_tpu.telemetry import events as telemetry_events
from deepspeed_tpu.telemetry.memory import get_memory_monitor
from deepspeed_tpu.telemetry.spans import get_span_log
from deepspeed_tpu.telemetry.tracing import (ring_timeline_events,
                                             span_events_from_dict)

# hop causes (the bounded label set of serve_trace_hops_total): why a
# request's NEXT leg opened — first routing, the prefill->decode
# handoff, a failover off a dead replica, or a rolling-drain re-route
HOP_CAUSES = ("submit", "handoff", "failover", "drain_reroute")

# replica health states (the serve_replica_healthy gauge is 1 only for a
# healthy, non-draining — i.e. routable — replica)
HEALTHY = "healthy"
DEGRADED = "degraded"
DEAD = "dead"


def _entries_nbytes(entries) -> int:
    """Host bytes of one handoff publication: ``[(hash, payload)]``
    where payload is a dict of numpy arrays (k/v, optional scales).
    Computed from the payloads themselves — the publishing prefill
    replica has no host tier to ask for a per-block size."""
    return sum(int(a.nbytes) for _h, payload in entries
               for a in payload.values())



class _FrontRequest:
    """Frontend-side record of one request across replica lifetimes."""

    __slots__ = ("request_id", "prompt", "max_new_tokens", "eos_token_id",
                 "priority", "deadline_ts", "submit_ts", "replica",
                 "committed", "failovers", "retry_at_tick",
                 "prefill_only", "replay", "imported", "trace", "hop",
                 "hops", "next_cause", "tenant", "cost_legs")

    def __init__(self, request_id: int, prompt: List[int],
                 max_new_tokens: int, eos_token_id: Optional[int],
                 priority: int, deadline_ts: Optional[float],
                 submit_ts: float):
        self.request_id = request_id
        self.prompt = list(prompt)       # the ORIGINAL prompt, immutable
        self.max_new_tokens = max_new_tokens
        self.eos_token_id = eos_token_id
        self.priority = priority
        self.deadline_ts = deadline_ts   # absolute, frontend clock
        self.submit_ts = submit_ts
        self.replica: Optional[int] = None   # resident replica, or None
        # tokens recovered from dead/drained replicas (and folded by
        # the prefill->decode handoff): they fold into the resubmitted
        # prompt (the recompute-replay prefix)
        self.committed: List[int] = []
        self.failovers = 0
        self.retry_at_tick = 0           # frontend tick gating resubmit
        # disaggregation (docs/serving.md "Disaggregated prefill/
        # decode"): True while the current residency is the prefill-
        # only leg (budget one token on a prefill-role replica) — its
        # "length" finish is the handoff point, not a real finish
        self.prefill_only = False
        # True when the NEXT successful routing replays recomputed
        # tokens (failover / drain re-route — counted into the replay
        # overhead metric; a handoff's by-design one-token fold is not
        # failure replay and stays out of it)
        self.replay = False
        # (replica index, [chain hashes]) per consumed handoff: the
        # terminal finish purges still-parked payloads from those
        # replicas' import tiers — a request that dies QUEUED (cancel/
        # deadline/failed) never runs the admission that would consume
        # them, and an unpurged import-only tier leaks host RAM
        self.imported: List[tuple] = []
        # cross-replica trace stitching (docs/observability.md "Fleet
        # observability"): the frontend-owned logical trace, the
        # currently-open hop span (one per replica leg), the hop count,
        # and the cause the NEXT leg will carry
        self.trace = None
        self.hop = None
        self.hops = 0
        self.next_cause = "submit"
        # cost accounting (docs/observability.md "Cost accounting &
        # capacity"): the metering label the request was submitted
        # under, and the per-replica cost legs harvested at each leg
        # boundary — _finalize merges them into ONE bill
        self.tenant: Optional[str] = None
        self.cost_legs: List[dict] = []


class _Replica:
    """One supervised replica: the server plus its health bookkeeping."""

    __slots__ = ("index", "server", "watchdog", "health", "draining",
                 "dead_reason", "missed_beats", "last_beat_ts",
                 "last_step_s", "routed", "failovers",
                 "steps", "gauge", "stepped", "role", "failover_rids")

    def __init__(self, index: int, server: ContinuousBatchingServer,
                 watchdog: Watchdog, now: float, gauge,
                 role: str = MIXED):
        self.index = index
        self.server = server
        self.role = role
        self.watchdog = watchdog
        self.health = HEALTHY
        self.draining = False
        self.dead_reason: Optional[str] = None
        # beat bookkeeping: `missed_beats` counts consecutive frontend
        # steps with no observed beat — requiring missed >= 1 alongside
        # the wall threshold means a PAUSED frontend (nobody calling
        # step() for a while) never mass-declares its replicas dead on
        # resume: the first step back beats everyone before the sweep
        self.missed_beats = 0
        self.last_beat_ts = now
        self.last_step_s: Optional[float] = None
        self.routed = 0          # requests ever routed here
        self.failovers = 0       # requests failed over AWAY from here
        self.steps = 0
        self.gauge = gauge       # serve_replica_healthy{replica=index}
        self.stepped = False     # did this frontend tick step it?
        # requests failed over off this replica at death — once none
        # is still outstanding, the pool has RECOVERED from the loss
        # (the availability SLO signal's resolve condition)
        self.failover_rids: set = set()

    @property
    def routable(self) -> bool:
        return self.health == HEALTHY and not self.draining

    def load(self) -> tuple:
        """Least-loaded admission key: fewest queued+resident requests,
        ties to the most free pool blocks, then index (deterministic)."""
        sched = self.server.scheduler
        return (sched.pending_requests + sched.active_slots,
                -sched.allocator.free_blocks, self.index)

    def gap_s(self) -> float:
        """Recent mean dispatch gap from this replica's own step
        observatory (0.0 when telemetry.step_profile is off) — how
        host-bound the replica is right now."""
        prof = self.server._profiler
        return prof.recent_gap_s() if prof is not None else 0.0

    def decode_load(self) -> tuple:
        """Telemetry-routed decode admission key (docs/serving.md
        'Disaggregated prefill/decode'): queue+residents first (an
        empty replica always beats a loaded one), then the step
        observatory's recent dispatch-gap mean (the replica whose
        device is waiting on its host LEAST takes the next decoder),
        then free blocks, then index — richer than queue depth, still
        deterministic under a fake clock."""
        sched = self.server.scheduler
        return (sched.pending_requests + sched.active_slots,
                self.gap_s(), -sched.allocator.free_blocks, self.index)


class ServingFrontend:
    """N supervised ``ContinuousBatchingServer`` replicas behind one
    ``submit()/step()/drain()/result()`` surface (see module doc).

    ``engine`` is shared: replicas reuse its weights and mesh but build
    their own paged pools and jits. ``clock`` (injectable) is the basis
    for heartbeats, deadlines, and the drain timeout — the chaos tests
    drive the whole health state machine with a fake clock and zero
    real sleeps. ``fault_injector`` (or the config section) arms both
    the per-server chaos sites and the replica-scoped kinds; ONE
    injector is shared by the frontend and every replica so a seeded
    chaos schedule is pool-level. With ``replication.replicas == 1``
    the frontend is a pass-through: greedy output is byte-identical to
    a bare server (test-pinned)."""

    def __init__(self, engine: InferenceEngine,
                 registry: Optional[MetricRegistry] = None,
                 clock: Optional[Callable[[], float]] = None,
                 fault_injector: Optional[FaultInjector] = None):
        cfg = engine.config
        rcfg = cfg.replication
        self.engine = engine
        self._clock = clock if clock is not None else time.perf_counter
        self._degraded_s = rcfg.heartbeat_degraded_s
        self._dead_s = rcfg.heartbeat_dead_s
        self._degraded_step_s = rcfg.degraded_step_s
        self.max_failovers = rcfg.max_failovers
        self._backoff = rcfg.failover_backoff_steps
        self._max_pending = cfg.max_queued_requests
        tcfg = getattr(cfg, "telemetry", None)
        enabled = tcfg is None or tcfg.enabled
        self.telemetry = registry or (get_registry() if enabled
                                      else MetricRegistry())
        self._fi = fault_injector
        if self._fi is None and tcfg is not None and enabled:
            self._fi = FaultInjector.from_config(
                tcfg.fault_injection, registry=self.telemetry)
        reg = self.telemetry
        # disaggregated prefill/decode (docs/serving.md "Disaggregated
        # prefill/decode"): per-replica roles + the shared handoff
        # tier. No roles (or all-mixed) = self._handoff is None and
        # every routing/collection seam below short-circuits — the
        # pool is byte-identical to one without this layer (pinned).
        self._roles = (list(rcfg.roles) if rcfg.roles
                       else [MIXED] * rcfg.replicas)
        self._disagg = any(r != MIXED for r in self._roles)
        self._handoff = (HandoffTier(rcfg.handoff_blocks)
                         if self._disagg else None)
        self._handoffs = 0            # prefill->decode transitions
        if self._disagg:
            self._c_handoff_pub = reg.counter(
                "serve_handoff_published_total",
                help="prefix blocks published into the prefill->decode "
                     "handoff tier (payload + int8 scale tiles, all "
                     "layers, keyed by chain hash — docs/serving.md "
                     "'Disaggregated prefill/decode')")
            self._c_handoff_con = reg.counter(
                "serve_handoff_consumed_total",
                help="handoff blocks imported into a decode replica at "
                     "routing (its admission warms them via "
                     "match_prefix -> paged_swap_in, one jitted donated "
                     "scatter per block)")
            self._c_handoff_exp = reg.counter(
                "serve_handoff_expired_total",
                help="handoff blocks dropped unconsumed: capacity-"
                     "expired (bounded tier, oldest publication first) "
                     "or abandoned at a terminal finish — either way "
                     "the decode side recomputes, and nothing strands")
            self._g_handoff_blocks = reg.gauge(
                "serve_handoff_blocks",
                help="blocks currently parked in the prefill->decode "
                     "handoff tier awaiting a decode replica")
            self._h_handoff = reg.histogram(
                "serve_handoff_seconds",
                help="publish-to-consume latency of one request's KV "
                     "handoff (prefill replica finished -> decode "
                     "replica imported)")
        self._c_failovers = reg.counter(
            "serve_failovers_total",
            help="requests failed over off a dead replica (committed "
                 "tokens fold into the replayed prompt — docs/serving.md "
                 "'Replicated serving & failover')")
        self._c_replay = reg.counter(
            "serve_failover_replay_tokens_total",
            help="previously-committed tokens re-prefilled by failover "
                 "and drain re-route resubmissions (the replay-compute "
                 "overhead of surviving a replica death)")
        self._h_retries = reg.histogram(
            "serve_request_failovers",
            help="failover count per finished request (0 for the "
                 "undisturbed majority; the tail is the retry story)")
        # finish-reason counters for finishes the FRONTEND decides
        # (pending-queue deadline/cancel, retries exhausted, stranded
        # work) — the same families every server-side equivalent
        # ticks, so pool-level dashboards see the same lifecycle story
        # a bare server would tell
        self._c_finish = {
            "cancelled": reg.counter(
                "serve_cancelled_total",
                help="requests finished by cancel() or a bounded drain "
                     "(finish reason 'cancelled'; partial output "
                     "returned)"),
            "deadline": reg.counter(
                "serve_deadline_expired_total",
                help="requests reaped past their deadline_s (finish "
                     "reason 'deadline'; queued expiries are never "
                     "admitted)"),
            "failed": reg.counter(
                "serve_requests_failed_total",
                help="requests failed by the frontend: failover "
                     "retries exhausted, or every replica dead "
                     "(finish reason 'failed')"),
        }
        # fleet observability plane (docs/observability.md "Fleet
        # observability"): the frontend-owned stitched tracer (same
        # arming condition and knobs as a replica's own — the stitched
        # layer costs nothing when tracing is off), the hop counter by
        # cause, the federated-scrape wall histogram, and the
        # per-replica snapshot cache every fleet surface reads
        self.tracer = None
        if tcfg is not None and enabled and tcfg.trace_sample_rate > 0:
            self.tracer = Tracer(
                sample_rate=tcfg.trace_sample_rate,
                ring_capacity=tcfg.trace_ring_capacity,
                seed=tcfg.trace_seed,
                slow_threshold_s=tcfg.trace_slow_threshold_s,
                registry=reg)
        self._c_hops = {cause: reg.counter(
            "serve_trace_hops_total",
            help="replica legs routed, by cause (submit/handoff/"
                 "failover/drain_reroute) — each is one hop span on "
                 "the stitched frontend trace",
            labels={"cause": cause}) for cause in HOP_CAUSES}
        self._h_fleet_scrape = reg.histogram(
            "serve_fleet_scrape_seconds",
            help="wall time of one federated fleet scrape: refresh + "
                 "merge of every replica's registry snapshot into the "
                 "frontend's /metrics view")
        # request-level cost accounting at the pool boundary (docs/
        # observability.md "Cost accounting & capacity"): each replica
        # runs its own RequestLedger; the frontend harvests one cost
        # LEG per replica residency (finish, handoff, failover, drain
        # re-route) and merges them into one bill per request at
        # _finalize. The frontend-level tenant meter counts REQUESTS
        # (replica-level tenant series count legs — recompute is real
        # work and bills where it ran).
        self._acct = tcfg is None or tcfg.accounting.enabled
        self._costs: Dict[int, dict] = {}     # rid -> merged bill
        self._tenants: Optional[TenantMeter] = None
        if self._acct:
            self._tenants = TenantMeter(
                registry=reg,
                max_tenants=(tcfg.accounting.max_tenants
                             if tcfg is not None else 32))
            (self._h_cost_device, self._h_cost_blocks,
             self._h_cost_queued) = register_cost_histograms(reg)
        # per-replica observability snapshots, ALWAYS round-tripped
        # through json bytes (no cross-replica object sharing — the
        # process-per-replica transport ships the same bytes): index ->
        # (state dict, capture ts on the frontend clock). Dead and
        # draining replicas keep serving their last snapshot; the age
        # gauge is the staleness mark.
        self._obs_lock = threading.Lock()
        self._obs_cache: Dict[int, tuple] = {}
        self._g_scrape_age: Dict[int, object] = {}
        self._mem_components: List[tuple] = []
        # replicas: each gets its own private registry (per-replica
        # serving histograms must not merge into one family) and an
        # UNSTARTED heartbeat watchdog installed on the server's seam —
        # every existing notify_progress site now beats the frontend
        self.replicas: List[_Replica] = []
        now = self._clock()
        for i in range(rcfg.replicas):
            role = self._roles[i]
            srv = ContinuousBatchingServer(
                engine, registry=MetricRegistry(), clock=self._clock,
                fault_injector=self._fi, supervised=True, role=role,
                # decode-capable replicas in a role-split pool receive
                # handoffs — they need the import tier the admission
                # swap-in reads from; prefill replicas never do
                handoff_import=self._disagg and role != PREFILL,
                # tag the replica's step-profile ring events so the
                # merged fleet timeline can partition the SHARED event
                # ring into per-replica host-phase tracks
                profile_source=f"replica{i}")
            wd = Watchdog(self._dead_s, registry=reg, clock=self._clock,
                          name=f"serve_replica{i}")
            srv.watchdog = wd
            gauge = reg.gauge(
                "serve_replica_healthy",
                help="1 = replica is routable (healthy, not draining); "
                     "0 = breaker open (degraded/draining) or dead",
                labels={"replica": str(i)})
            gauge.set(1.0)
            self._g_scrape_age[i] = reg.gauge(
                "serve_replica_scrape_age_seconds",
                help="age of the replica's last observability snapshot "
                     "on the frontend clock — the staleness mark on a "
                     "dead/draining/wedged replica's federated series",
                labels={"replica": f"r{i}"})
            # each replica's private registry is host RAM the memory
            # monitor would otherwise never see (the PR-15 import-tier
            # leak-blindness class): a weakref getter on the REGISTRY
            # (it outlives server.close(), so a dead replica's last
            # snapshot stays accounted) under /debug/memory
            mem_name = f"replica{i}_telemetry"
            reg_ref = weakref.ref(srv.telemetry)

            def _reg_bytes(ref=reg_ref):
                r = ref()
                return 0 if r is None else r.approx_bytes()

            get_memory_monitor().register_host_component(
                mem_name, _reg_bytes)
            self._mem_components.append((mem_name, _reg_bytes))
            self.replicas.append(_Replica(i, srv, wd, now, gauge, role))
        if self._fi is not None:
            # seeded kill schedule: pick the victim now that the pool
            # size is known (telemetry.fault_injection.replica_kill_step)
            self._fi.schedule_replica_kill(len(self.replicas))
        # dedicated per-replica step threads (replication.threaded_step):
        # single-worker executors so each replica's steps always run on
        # ITS thread; the frontend joins the barrier before any health
        # or routing decision
        self._pools = None
        if rcfg.threaded_step:
            from concurrent.futures import ThreadPoolExecutor
            self._pools = [
                ThreadPoolExecutor(1, thread_name_prefix=f"serve-rep{i}")
                for i in range(rcfg.replicas)]
        self._pending: Deque[_FrontRequest] = deque()
        self._requests: Dict[int, _FrontRequest] = {}  # outstanding
        self._results: Dict[int, List[int]] = {}
        self.finish_reasons: Dict[int, str] = {}
        self._deferred_finished: List[int] = []
        self._next_id = 0
        self._tick = 0
        self._failovers = 0
        self._replay_tokens = 0
        self._drain_reroutes = 0
        self._closed = False
        # SLO burn-rate alerting + canary probes + incident bundles at
        # the POOL boundary (docs/observability.md "SLOs, alerting &
        # incidents"): the frontend is the availability authority (its
        # replica health state machine), its canary crosses the
        # prefill->decode handoff on a role-split pool, and its bundles
        # carry the replica rows + stitched traces. All default OFF —
        # a default-config pool builds none of these and registers zero
        # new instruments (byte-identity pinned).
        self.alerts = None
        self.canary = None
        self.incidents = None
        if tcfg is not None and enabled:
            if tcfg.incident.enabled:
                self.incidents = IncidentRecorder(
                    tcfg.incident, collect=self._incident_collect,
                    registry=reg, clock=self._clock,
                    fingerprint=config_fingerprint(cfg),
                    name="pool_incidents")
                for rep in self.replicas:
                    # unify each replica's heartbeat-watchdog stall dump
                    # with the pool's incident recorder (same episode
                    # machinery as an alert firing)
                    rep.watchdog.set_on_dump(
                        lambda dump, idx=rep.index:
                        self.incidents.capture(
                            "watchdog",
                            info={"replica": idx,
                                  "watchdog": dump.get("watchdog"),
                                  "idle_seconds":
                                      dump.get("idle_seconds")}))
            if tcfg.slo.enabled and tcfg.slo.objectives:
                # same master switch as the server: slo.enabled=false
                # arms no engine whatever the objectives say
                self.alerts = AlertEngine(
                    tcfg.slo, registry=reg, clock=self._clock,
                    sources={"availability": self._availability,
                             "goodput": self._pool_goodput},
                    on_fire=self._on_alert_fire,
                    on_resolve=self._on_alert_resolve)
            if tcfg.canary.enabled:
                self.canary = CanaryProber(
                    tcfg.canary, submit=self.submit, result=self.result,
                    finish_reason=self.finish_reason,
                    cancel=self.cancel, registry=reg,
                    clock=self._clock,
                    vocab_size=getattr(engine.model_config,
                                       "vocab_size", None))
        self.http_server = None
        if tcfg is not None and enabled and tcfg.http_port is not None:
            self.http_server = start_http_server(
                tcfg.http_port, host=tcfg.http_host, registry=reg,
                replicas=self._debug_snapshot, tracer=self.tracer,
                fleet=self._fleet_snapshot,
                metrics_view=self._fleet_registry,
                capacity=self._capacity_snapshot,
                incidents=self.incidents_snapshot)

    # ------------------------------------------------------------ API

    def submit(self, prompt: List[int], max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               request_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               priority: int = 0,
               tenant: Optional[str] = None) -> int:
        """Queue one request with the server's submit contract (same
        validation, same finish-reason vocabulary); the frontend routes
        it to the least-loaded healthy replica, holding it in a bounded
        frontend queue only when no replica can take it right now.

        ``tenant`` threads through to every replica leg (docs/
        observability.md "Cost accounting & capacity"): the frontend's
        tenant series count requests, each replica's count its own
        legs, and the merged cost bill carries the label."""
        rej = submit_rejection(prompt, max_new_tokens,
                               max(1, self.engine.config.min_out_tokens),
                               deadline_s)
        if rej is not None:
            self._count_rejection(rej[0], request_id, tenant=tenant)
            raise ValueError(rej[1])
        if request_id is None:
            request_id = self._next_id
        elif request_id in self._requests or request_id in self._results:
            self._count_rejection("duplicate_id", request_id,
                                  tenant=tenant)
            raise ValueError(
                f"request_id {request_id} is already outstanding or "
                "finished — a duplicate would silently overwrite its "
                "output")
        self._next_id = max(self._next_id, request_id) + 1
        now = self._clock()
        fr = _FrontRequest(
            request_id, prompt, max_new_tokens, eos_token_id, priority,
            None if deadline_s is None else now + deadline_s, now)
        fr.tenant = tenant
        if self.tracer is not None:
            # the STITCHED trace is born at the pool boundary: every
            # replica leg the request ever runs becomes a hop span
            # under this one root, whatever replicas it crosses
            fr.trace = self.tracer.start_trace(
                "request", trace_id=request_id,
                prompt_tokens=len(prompt),
                max_new_tokens=max_new_tokens)
        self._requests[request_id] = fr
        try:
            routed = self._route(fr)
        except ValueError as e:
            # permanent refusal (span/pool/...): identical on every
            # replica — the frontend has nothing to hold
            del self._requests[request_id]
            if self._tenants is not None:
                self._tenants.count_rejection(tenant)
            if fr.trace is not None:
                fr.trace.root.set("error", str(e))
                self.tracer.finish(fr.trace, status="rejected")
            raise
        if not routed:
            if all(r.health == DEAD for r in self.replicas):
                del self._requests[request_id]
                self._count_rejection("replicas_dead", request_id,
                                      trace=fr.trace, tenant=tenant)
                raise RuntimeError(
                    "every replica is dead — the pool can never serve "
                    "this request (restart the frontend)")
            if len(self._pending) >= self._max_pending:
                del self._requests[request_id]
                self._count_rejection("queue_full", request_id,
                                      trace=fr.trace, tenant=tenant)
                raise RuntimeError(
                    f"frontend queue is full ({self._max_pending}); "
                    "step() the pool before submitting more, or raise "
                    "max_queued_requests")
            self._pending.append(fr)
        if self._tenants is not None and tenant is not None:
            # the frontend meters accepted REQUESTS once, at the pool
            # boundary (replica series meter legs); fold() returns None
            # for the unmetered canary tenant
            label = self._tenants.fold(tenant)
            if label is not None:
                self._tenants.count_request(label, len(prompt))
        return request_id

    def _count_rejection(self, reason: str,
                         request_id: Optional[int] = None,
                         trace=None,
                         tenant: Optional[str] = None) -> None:
        """Pool-level refusals mirror the server's accounting (same
        counter family, same ring event, same always-kept error trace)
        so a frontend rejection is as visible as a bare server's."""
        self.telemetry.counter(
            "serve_admission_rejections_total",
            help="refused submit() calls, by reason",
            labels={"reason": reason}).inc()
        if self._tenants is not None:
            self._tenants.count_rejection(tenant)
        get_event_ring().record(telemetry_events.ADMISSION_REJECT,
                                reason=reason, source="frontend")
        if self.tracer is not None:
            if trace is not None:
                # the refusal happened AFTER the stitched trace opened
                # (replicas_dead / queue_full): close that trace as the
                # error record rather than minting a second one
                trace.root.set("error", reason)
                self.tracer.finish(trace, status="rejected")
            else:
                attrs = ({} if request_id is None
                         else {"request_id": request_id})
                self.tracer.record_rejected("request", reason, **attrs)

    # ------------------------------------------- trace-stitching hops

    def _open_hop(self, fr: _FrontRequest, rep: _Replica,
                  cause: str) -> None:
        """One replica leg = one hop span on the stitched trace,
        carrying replica/role/cause; the hop counter ticks even with
        tracing off (leg routing is load-bearing fleet telemetry)."""
        self._c_hops[cause].inc()
        if fr.trace is None:
            return
        self._close_hop(fr)      # invariant: at most one open hop
        fr.hop = fr.trace.begin(
            "hop", replica=rep.index, role=rep.role, cause=cause,
            hop=fr.hops, committed=len(fr.committed))
        fr.hops += 1

    def _close_hop(self, fr: _FrontRequest, **attrs) -> None:
        if fr.hop is None:
            return
        for k, v in attrs.items():
            fr.hop.set(k, v)
        fr.trace.end_span(fr.hop)
        fr.hop = None

    def result(self, request_id: int) -> Optional[List[int]]:
        """Finished output (prompt + generated) or None — the same
        contract as the server's, whatever replica (or replicas) the
        request lived on."""
        return self._results.get(request_id)

    def finish_reason(self, request_id: int) -> Optional[str]:
        return self.finish_reasons.get(request_id)

    @property
    def idle(self) -> bool:
        return not self._requests

    def cancel(self, request_id: int) -> bool:
        """Cancel one request wherever it lives — frontend-queued or
        resident on any replica. False when finished or unknown."""
        fr = self._requests.get(request_id)
        if fr is None:
            return False
        if fr.replica is None:
            try:
                self._pending.remove(fr)
            except ValueError:
                pass
            self._finalize(fr, list(fr.prompt) + list(fr.committed),
                           "cancelled", self._deferred_finished,
                           frontend_decided=True)
            return True
        rep = self.replicas[fr.replica]
        if not rep.server.cancel(request_id):
            # the replica already finished it — e.g. a pipeline flush
            # inside an EARLIER cancel committed this request's final
            # token server-side before the frontend's next step could
            # collect it. Collect that finish NOW: returning False
            # while leaving the record outstanding would strand a
            # computed result forever (drain(timeout_s)'s cancel-all
            # straggler loop would drop it on the floor).
            why = rep.server.finish_reason(request_id)
            if why is not None:
                tokens = rep.server.result(request_id)
                self._harvest_leg(rep, fr)
                if self._handoff_point(fr, why, tokens):
                    # the replica finished only the prefill-only LEG —
                    # pool-wise the request is still mid-flight, so
                    # the cancel wins: partial out, no handoff
                    self._finalize(fr, tokens, "cancelled",
                                   self._deferred_finished,
                                   frontend_decided=True)
                    return True
                self._finalize(fr, tokens, why,
                               self._deferred_finished)
            return False
        self._harvest_leg(rep, fr)
        self._finalize(fr, rep.server.result(request_id), "cancelled",
                       self._deferred_finished)
        return True

    # ------------------------------------------------------------ step

    def step(self) -> List[int]:
        """One supervision round: reap frontend-held deadline expiries,
        route eligible pending work (failover resubmits past their
        backoff included), step every non-dead replica (skipping
        injected wedges — no step, no heartbeat), collect finishes,
        run the health state machine (breaker transitions, heartbeat
        deadlines → failover), and complete any finished drains.
        Returns the frontend request ids that got a result this round."""
        finished: List[int] = []
        if self._deferred_finished:
            finished.extend(self._deferred_finished)
            self._deferred_finished.clear()
        self._tick += 1
        # canary probes self-inject through the REAL submit path ahead
        # of routing (the probe rides this very round's dispatch, and
        # on a role-split pool crosses the prefill->decode handoff);
        # alert evaluation is cadence-gated internally — at the top so
        # an idle pool still evaluates (silence is a signal)
        if self.canary is not None:
            self.canary.tick()
        if self.alerts is not None:
            self.alerts.maybe_evaluate()
        now = self._clock()
        self._reap_pending_deadlines(finished, now)
        self._route_pending(finished)
        self._step_replicas(finished)
        self._health_sweep(finished)
        self._finish_drains()
        self._fail_stranded(finished)
        return finished

    def _step_replicas(self, finished: List[int]) -> None:
        """Step every live replica, inline (index order) or fanned out
        to the dedicated per-replica threads with a join barrier.
        Injected kills are checked on the owner thread BEFORE the step
        dispatch; a step that raises — injected or real — declares the
        replica dead and fails its work over."""
        live: List[_Replica] = []
        for rep in self.replicas:
            rep.stepped = False
            if rep.health == DEAD:
                continue
            if self._fi is not None \
                    and self._fi.is_replica_wedged(rep.index):
                continue          # no step, no beat — deadline will see
            try:
                if self._fi is not None:
                    self._fi.check_replica_step(rep.index, self._tick)
            except ReplicaKilled as e:
                self._kill_replica(rep, str(e), finished)
                continue
            live.append(rep)
        if self._pools is None:
            results = [(rep, self._timed_step(rep)) for rep in live]
        else:
            futs = [(rep, self._pools[rep.index].submit(
                self._timed_step, rep)) for rep in live]
            results = [(rep, f.result()) for rep, f in futs]
        for rep, res in results:
            err, dt, done = res
            if err is not None:
                self._kill_replica(rep, f"step raised: {err!r}", finished)
                continue
            rep.stepped = True
            rep.steps += 1
            rep.last_step_s = dt + (
                self._fi.replica_step_latency(rep.index)
                if self._fi is not None else 0.0)
            self._collect(rep, done, finished)

    def _timed_step(self, rep: _Replica):
        """(error, seconds, finished ids) for one replica step — the
        exception is CAPTURED (threaded mode must deliver it to the
        owner thread, not kill the worker)."""
        t0 = self._clock()
        try:
            done = rep.server.step()
        except Exception as e:  # noqa: BLE001 — any step death is final
            return e, self._clock() - t0, []
        return None, self._clock() - t0, done

    def _collect(self, rep: _Replica, done: List[int],
                 finished: List[int]) -> None:
        for rid in done:
            fr = self._requests.get(rid)
            if fr is None:
                continue          # already finalized (e.g. via cancel)
            why = rep.server.finish_reason(rid)
            if why is None:
                # no terminal record left server-side: this finish was
                # already collected through another path in THIS round
                # (a mid-collect _kill_replica sweeps the dying
                # replica's uncollected finishes, and a handoff's
                # forget() wipes the record while the request lives on
                # mid-flight) — finalizing from the stale `done` entry
                # would pass tokens=None into _finalize and crash the
                # whole frontend step
                continue
            self._collect_finish(rep, fr, rep.server.result(rid), why,
                                 finished)

    @staticmethod
    def _handoff_point(fr: _FrontRequest, reason: str,
                       tokens: List[int]) -> bool:
        """True when a replica-side finish is the prefill→decode
        handoff point: the prefill-only leg ran out its one-token
        budget with output still owed. ONE predicate for
        :meth:`_collect_finish` and :meth:`cancel` — the two sites
        must never drift on what counts as a real finish."""
        return (fr.prefill_only and reason == "length"
                and len(tokens) < len(fr.prompt) + fr.max_new_tokens)

    def _harvest_leg(self, rep: _Replica, fr: _FrontRequest):
        """Pop the replica-side cost record for one finished (or
        abandoned) leg and stash it on the frontend request; the merged
        bill lands at :meth:`_finalize`. Returns the harvested leg (or
        None) so the handoff path can top up its bytes. Best-effort:
        a replica mid-death may refuse the scrape — the merged bill
        then simply misses that leg's device time (the abandon path in
        :meth:`_kill_replica` covers the common death shape)."""
        if not self._acct:
            return None
        try:
            leg = rep.server.pop_request_cost(fr.request_id)
        except Exception:  # noqa: BLE001 — billing never blocks serving
            return None
        if leg is not None:
            fr.cost_legs.append(leg)
        return leg

    def _collect_finish(self, rep: _Replica, fr: _FrontRequest,
                        tokens: List[int], reason: str,
                        finished: List[int]) -> None:
        """One replica-side finish, phase-aware: a prefill-only leg
        that ran out its one-token budget with output still owed is
        the HANDOFF point, not a finish — everything else (real
        finishes, a first-token EOS, lifecycle terminations, and a
        prefill leg that already satisfied the whole request) finalizes
        as before."""
        # harvest the leg's cost NOW — both downstream paths destroy
        # the replica-side record (_handoff_request forgets it, a
        # finalize leaves it to reclaim()/forget())
        self._harvest_leg(rep, fr)
        if self._handoff_point(fr, reason, tokens):
            self._handoff_request(rep, fr, tokens, finished)
            return
        self._finalize(fr, tokens, reason, finished)

    def _handoff_request(self, rep: _Replica, fr: _FrontRequest,
                         tokens: List[int], finished: List[int]) -> None:
        """The disaggregation seam (docs/serving.md "Disaggregated
        prefill/decode"): the prefill-only leg finished, so fold its
        committed token(s) into the scheduling prompt, publish the
        prompt's block-aligned KV into the shared handoff tier under
        its prefix chain hashes (the blocks ``commit_prefix``
        registered at the final chunk, read out block by block via
        ``paged_read_block``), and resubmit toward a decode replica —
        whose admission warms every published block back in through
        ``match_prefix`` → ``paged_swap_in`` and recomputes only the
        sub-block tail as one short chunk (the "prompt capped one
        token short" idiom). A publish that dies partway (the
        injected mid-publish replica kill, or a real export death)
        publishes NOTHING — the decode replica falls back to
        recomputing the whole prefix from the folded prompt, exact by
        the PR-7/PR-13 recompute oracle."""
        rid = fr.request_id
        fr.committed = list(tokens)[len(fr.prompt):]
        fr.replica = None
        fr.prefill_only = False
        # the prefill leg's hop closes HERE; the decode leg's hop opens
        # at its routing, carrying the explicit handoff cause
        self._close_hop(fr, outcome="handoff",
                        committed_out=len(fr.committed))
        fr.next_cause = "handoff"
        self._handoffs += 1
        # the prefill leg's terminal record must not block the id's
        # decode-leg resubmission — which on a role-degraded pool can
        # land back on this very replica (last-resort colocation)
        rep.server.forget(rid)
        bs = self.engine.config.block_size
        sched_prompt = list(fr.prompt) + list(fr.committed)
        # cap one token short of the decode-side scheduling prompt —
        # exactly the blocks its admission can take by hash (the tail
        # must re-run through the chunk program to produce logits)
        reusable = (len(sched_prompt) - 1) // bs
        hashes = prefix_block_hashes(sched_prompt, bs)[:reusable]
        entries: List[tuple] = []
        killed = None
        warm = 0
        t0 = self._clock()
        if hashes:
            # leading chain blocks already warm on EVERY live decode-
            # capable replica (device-registered, or parked in its
            # import tier) need no handoff at all: whichever replica
            # the request routes to, its admission walk hits them
            # before ever reaching the published tail — the shared-
            # system-prompt prefix is read off the prefill device
            # ONCE, then never again while it stays warm
            targets = [r for r in self.replicas
                       if r.role != PREFILL and r.health != DEAD
                       and r.server.host_tier is not None]
            for h in hashes:
                if targets and all(
                        r.server.scheduler.allocator.lookup_prefix(h)
                        is not None or r.server.host_tier.has(h)
                        for r in targets):
                    warm += 1
                else:
                    break
            hashes = hashes[warm:]
        if hashes:
            # identical leading chains another request already parked
            # need no device read — reuse the tier's payload objects
            # and export only the cold tail of the chain
            cached = self._handoff.payloads_for(hashes)
            rest = hashes[len(cached):]
            on_block = None
            if self._fi is not None:
                fi = self._fi
                on_block = (lambda i, n:
                            fi.check_handoff_block(rid, i, n))
            try:
                entries = cached + (
                    rep.server.export_prefix(rest, on_block=on_block)
                    if rest else [])
            except Exception as e:  # noqa: BLE001 — export death IS
                killed, entries = e, []   # replica death (mid-publish)
        if killed is None and self._fi is not None:
            try:
                self._fi.check_handoff_published(rid)
            except ReplicaKilled as e:
                # publish COMPLETED before the death: the payloads are
                # host-durable numpy — the handoff outlives its
                # publisher, only the replica dies
                killed = e
        if entries:
            if self._acct and fr.cost_legs:
                # bill the published bytes to the prefill leg that just
                # produced them (harvested in _collect_finish, so it is
                # the newest leg) — payload nbytes, not a tier estimate
                fr.cost_legs[-1]["handoff_bytes"] += \
                    _entries_nbytes(entries)
            expired = self._handoff.publish(rid, entries, t0)
            self._c_handoff_pub.inc(len(entries))
            if expired:
                self._c_handoff_exp.inc(expired)
            self._g_handoff_blocks.set(self._handoff.blocks)
            get_event_ring().record(
                telemetry_events.KV_HANDOFF, stage="published",
                request_id=rid, replica=rep.index,
                blocks=len(entries), warm_skipped=warm,
                expired=expired)
        elif killed is not None:
            # the export died mid-publish: the decode side recomputes
            # the prefix from the folded prompt — slower, never wrong
            get_event_ring().record(
                telemetry_events.KV_HANDOFF, stage="fallback",
                request_id=rid, replica=rep.index, cause=repr(killed))
        else:
            # nothing left to publish: the whole chain is already warm
            # on every decode-capable replica, or the prompt has no
            # full block — either way the decode side's own admission
            # serves it (warm hit / short recompute)
            get_event_ring().record(
                telemetry_events.KV_HANDOFF, stage="skipped",
                request_id=rid, replica=rep.index,
                cause="already_warm" if warm else "no_full_blocks")
        if killed is not None and rep.health != DEAD:
            self._kill_replica(
                rep, f"died during handoff publish: {killed!r}",
                finished)
        # route toward a decode replica NOW (no failure happened — no
        # backoff); an unroutable pool holds it pending, immediately
        # eligible
        if not self._route(fr, finished):
            fr.retry_at_tick = self._tick
            self._pending.append(fr)

    # ------------------------------------------------------- lifecycle

    def _finalize(self, fr: _FrontRequest, tokens: List[int],
                  reason: str, finished: List[int],
                  frontend_decided: bool = False) -> None:
        rid = fr.request_id
        # the budget-floor clamp on a resubmission can over-generate a
        # token or two past the request's true budget — truncate, so
        # the caller sees exactly prompt + <= max_new_tokens (and the
        # one-shot parity oracle compares like for like)
        limit = len(fr.prompt) + fr.max_new_tokens
        self._results[rid] = list(tokens)[:limit]
        self.finish_reasons[rid] = reason
        self._requests.pop(rid, None)
        finished.append(rid)
        if self._acct and fr.tenant == CANARY_TENANT:
            # synthetic probes are unmetered by design: no merged bill,
            # no cost histograms, no REQUEST_COST event. The harvested
            # legs drop here — their device time was already settled
            # exactly into OTHER requests' bills via the excluded
            # ledger records on the replica side.
            fr.cost_legs = []
        elif self._acct:
            # the merged bill: ONE cost record per request, summing
            # every harvested replica leg (prefill, decode, each
            # failover replay — recompute bills where it ran). A
            # request that never closed a leg (expired in the frontend
            # queue, every harvest refused) still bills an empty
            # synthesized record, so coverage is exactly one record
            # per finished request.
            folded = (self._tenants.fold(fr.tenant)
                      if self._tenants is not None else fr.tenant)
            legs = fr.cost_legs or [
                new_cost_record(rid, folded, len(fr.prompt))]
            rec = merge_cost_legs(legs)
            rec["finish_reason"] = reason
            # token totals come from the frontend's truth — an
            # abandoned leg reports tokens_out=0 and a replayed leg
            # re-counts its fold; device/KV/bytes columns still sum
            # across legs (the device really ran them)
            rec["tokens_in"] = len(fr.prompt)
            rec["tokens_out"] = max(
                0, len(self._results[rid]) - len(fr.prompt))
            rec["tenant"] = folded
            self._costs[rid] = rec
            fr.cost_legs = []
            self._h_cost_device.observe(rec["device_s"])
            self._h_cost_blocks.observe(rec["kv_block_s"])
            self._h_cost_queued.observe(rec["queued_s"])
            if self._tenants is not None and folded is not None:
                self._tenants.count_finish(folded, rec["tokens_out"],
                                           rec["device_s"])
            get_event_ring().record(
                telemetry_events.REQUEST_COST, source="frontend",
                **rec)
        if fr.trace is not None:
            # close the stitched trace: an eos/length finish is "ok"
            # (head-sampling decides retention); everything else —
            # frontend-decided included (stranded pools, retries
            # exhausted) — carries its reason as the status, which the
            # tracer always keeps (same contract as a replica's own
            # lifecycle finishes)
            self._close_hop(fr, outcome=reason)
            fr.trace.root.set("finish_reason", reason)
            fr.trace.root.set("failovers", fr.failovers)
            fr.trace.root.set("hops", fr.hops)
            fr.trace.root.set(
                "generated_tokens",
                max(0, len(self._results[rid]) - len(fr.prompt)))
            if frontend_decided:
                fr.trace.root.set("decided_by", "frontend")
            self.tracer.finish(
                fr.trace,
                status="ok" if reason in ("eos", "length") else reason)
            fr.trace = None
        if self._handoff is not None:
            # a terminal finish releases any unconsumed publication —
            # the invariant that keeps the bounded tier free of
            # stranded entries (chaos-pinned)
            n = self._handoff.abandon(rid)
            if n:
                self._c_handoff_exp.inc(n)
                self._g_handoff_blocks.set(self._handoff.blocks)
            # ...and any replica-side IMPORTS the request never lived
            # to consume at admission (a still-queued cancel/deadline/
            # failed death — the unbounded import tier would hold them
            # forever; already-swapped-in hashes are no-ops)
            for idx, hashes in fr.imported:
                rep = self.replicas[idx]
                if rep.health != DEAD:
                    rep.server.purge_import(hashes)
        self._h_retries.observe(fr.failovers)
        if frontend_decided:
            # a finish the FRONTEND itself decided (the request never
            # reached — or no longer has — a replica to count it):
            # tick the same lifecycle counter family and ring event a
            # bare server would, so chaos forensics stay
            # incident-identical at the pool level
            self._c_finish[reason].inc()
            get_event_ring().record(
                _LIFECYCLE_EVENTS[reason], request_id=rid,
                generated=len(tokens) - len(fr.prompt),
                preemptions=0, source="frontend")

    def _candidates(self, fr: _FrontRequest) -> List[tuple]:
        """``(replica, as_prefill)`` admission order. Without roles:
        least-loaded routable, breaker failing OPEN (degraded) only
        when nothing is healthy — unchanged from the replicated pool.
        With roles the request routes by PHASE: a request with no
        committed tokens wants a prefill replica (telemetry-blind
        least-loaded — prefill replicas are queue-bound), one with
        committed tokens wants a decode replica ranked by the
        telemetry key (load, recent dispatch gap, free blocks); mixed
        replicas back both phases colocated, and wrong-role replicas
        are the availability-over-purity last resort (a pool with
        every prefill-capable replica dead still serves, colocated).
        ``as_prefill`` is True only for a prefill-role target taking a
        prefill-phase request — THAT submission is the one-token
        prefill-only leg whose finish hands off."""
        if not self._disagg:
            cands = sorted((r for r in self.replicas if r.routable),
                           key=_Replica.load)
            if not cands:
                # breaker fail-open: a pool with zero healthy replicas
                # prefers a degraded one over deadlocking the queue
                cands = sorted(
                    (r for r in self.replicas
                     if r.health == DEGRADED and not r.draining),
                    key=_Replica.load)
            return [(r, False) for r in cands]
        want = DECODE if fr.committed else PREFILL
        prim_key = (_Replica.decode_load if want == DECODE
                    else _Replica.load)

        def tiers(pool: List[_Replica]) -> List[_Replica]:
            return (sorted((r for r in pool if r.role == want),
                           key=prim_key)
                    + sorted((r for r in pool if r.role == MIXED),
                             key=_Replica.load)
                    + sorted((r for r in pool
                              if r.role not in (want, MIXED)),
                             key=_Replica.load))

        pool = [r for r in self.replicas if r.routable]
        if not pool:
            pool = [r for r in self.replicas
                    if r.health == DEGRADED and not r.draining]
        return [(r, want == PREFILL and r.role == PREFILL)
                for r in tiers(pool)]

    def _route(self, fr: _FrontRequest,
               finished: Optional[List[int]] = None) -> bool:
        """Admission over the phase-aware candidate order (see
        :meth:`_candidates`). Returns True when the request was
        placed — or terminally handled (expired / permanently refused
        at re-route time)."""
        now = self._clock()
        if fr.deadline_ts is not None and now >= fr.deadline_ts:
            self._finalize(fr, list(fr.prompt) + list(fr.committed),
                           "deadline",
                           finished if finished is not None
                           else self._deferred_finished,
                           frontend_decided=True)
            return True
        floor = max(1, self.engine.config.min_out_tokens)
        for rep, as_prefill in self._candidates(fr):
            # the prefill-only leg budgets exactly the floor (one
            # token normally): the replica chunk-prefills, commits the
            # first token, and retires — the finish is the handoff
            budget = (floor if as_prefill
                      else max(fr.max_new_tokens - len(fr.committed),
                               floor))
            try:
                rep.server.submit(
                    list(fr.prompt) + list(fr.committed),
                    max_new_tokens=budget,
                    eos_token_id=fr.eos_token_id,
                    request_id=fr.request_id,
                    deadline_s=(None if fr.deadline_ts is None
                                else fr.deadline_ts - now),
                    priority=fr.priority,
                    # the propagated trace-context: the replica's own
                    # trace root records these as link_* attributes, so
                    # a replica-side tree names the stitched frontend
                    # tree (and leg) it belongs to — a plain dict, so
                    # it crosses a process boundary unchanged
                    trace_context=(None if fr.trace is None else
                                   {"trace_id": fr.trace.trace_id,
                                    "hop": fr.hops,
                                    "cause": fr.next_cause}),
                    tenant=fr.tenant)
            except RuntimeError:
                continue          # that queue is full — try the next
            except ValueError:
                if finished is None:
                    raise         # submit()-time: propagate to caller
                # re-route time: a refusal here is unexpected (config
                # is identical pool-wide) — fail loudly, never hang
                self._finalize(fr,
                               list(fr.prompt) + list(fr.committed),
                               "failed", finished,
                               frontend_decided=True)
                return True
            fr.replica = rep.index
            fr.prefill_only = as_prefill
            rep.routed += 1
            self._open_hop(fr, rep, fr.next_cause)
            if fr.replay and fr.committed:
                self._replay_tokens += len(fr.committed)
                self._c_replay.inc(len(fr.committed))
            fr.replay = False
            if (self._handoff is not None and fr.committed
                    and not as_prefill):
                self._consume_handoff(fr, rep)
            return True
        return False

    def _consume_handoff(self, fr: _FrontRequest, rep: _Replica) -> None:
        """Hand a routed decode-phase request its published KV: pop the
        publication and park it in the target replica's import tier,
        where the coming admission's ``match_prefix`` walk swaps each
        block in. A target without a tier (wrong-role last resort)
        leaves the publication parked — the terminal finish abandons
        it, and the replica simply recomputes (exact either way)."""
        if rep.server.host_tier is None:
            return
        got = self._handoff.consume(fr.request_id)
        if got is None:
            return                # never published / expired: cold
        entries, t_pub = got
        imported = rep.server.import_prefix(entries)
        fr.imported.append((rep.index, [h for h, _ in entries]))
        self._c_handoff_con.inc(len(entries))
        self._h_handoff.observe(self._clock() - t_pub)
        self._g_handoff_blocks.set(self._handoff.blocks)
        get_event_ring().record(
            telemetry_events.KV_HANDOFF, stage="consumed",
            request_id=fr.request_id, replica=rep.index,
            blocks=len(entries), imported=imported)

    def _route_pending(self, finished: List[int]) -> None:
        held: List[_FrontRequest] = []
        while self._pending:
            fr = self._pending.popleft()
            if fr.retry_at_tick > self._tick:
                held.append(fr)
                continue
            if not self._route(fr, finished):
                held.append(fr)
        self._pending.extend(held)

    def _reap_pending_deadlines(self, finished: List[int],
                                now: float) -> None:
        for fr in [f for f in self._pending
                   if f.deadline_ts is not None and now >= f.deadline_ts]:
            self._pending.remove(fr)
            self._finalize(fr, list(fr.prompt) + list(fr.committed),
                           "deadline", finished, frontend_decided=True)

    def _failover(self, fr: _FrontRequest, partial: List[int],
                  finished: List[int], cause: str) -> None:
        """One request off a dead replica: fold its committed tokens,
        bound the retries, and schedule the backed-off resubmission."""
        fr.committed = list(partial)[len(fr.prompt):]
        fr.replica = None
        fr.prefill_only = False
        # the dead leg's hop closes as an error; the replayed leg's
        # hop opens at resubmission with cause="failover"
        self._close_hop(fr, outcome="failover", error=cause,
                        committed_out=len(fr.committed))
        fr.next_cause = "failover"
        fr.replay = True          # the resubmission replays recompute
        fr.failovers += 1
        self._failovers += 1
        self._c_failovers.inc()
        get_event_ring().record(
            telemetry_events.REPLICA_FAILOVER,
            request_id=fr.request_id, committed=len(fr.committed),
            failovers=fr.failovers, cause=cause)
        if fr.failovers > self.max_failovers:
            self._finalize(fr, list(fr.prompt) + list(fr.committed),
                           "failed", finished, frontend_decided=True)
            return
        fr.retry_at_tick = self._tick + max(
            1, self._backoff * (2 ** (fr.failovers - 1)))
        self._pending.append(fr)

    def _kill_replica(self, rep: _Replica, reason: str,
                      finished: List[int]) -> None:
        """Declare one replica dead: transition + ring event, fail over
        everything it held (scheduler state is pure host data — safe to
        scrape even when the step just raised), close it best-effort."""
        self._transition(rep, DEAD, reason)
        rep.dead_reason = reason
        srv = rep.server
        moved: List[tuple] = []
        seen: set = set()
        for state in list(srv.scheduler.slots.values()):
            rid = state.request.request_id
            fr = self._requests.get(rid)
            if fr is None:
                continue
            # prompt here is the REPLICA's prompt (original + any
            # earlier-failover fold); generated starts pre-seeded with
            # any within-replica preemption fold — together they are
            # the full committed output so far
            moved.append((fr, list(state.request.prompt)
                          + list(state.generated)))
            seen.add(rid)
        for req in list(srv.scheduler.queue):
            fr = self._requests.get(req.request_id)
            if fr is None:
                continue
            moved.append((fr, list(req.prompt) + list(req.committed)))
            seen.add(req.request_id)
        # anything routed here the scheduler no longer holds: a finish
        # that never surfaced (collected now) or a request lost whole
        # (replayed from the frontend's last knowledge)
        for rid, fr in list(self._requests.items()):
            if fr.replica != rep.index or rid in seen:
                continue
            why = srv.finish_reasons.get(rid)
            if why is not None:
                # phase-aware: an uncollected prefill-only finish on
                # the dying replica still hands off (its KV is intact
                # in-process until close — publish before losing it)
                self._collect_finish(rep, fr, srv.result(rid), why,
                                     finished)
            else:
                moved.append((fr, list(fr.prompt) + list(fr.committed)))
        # the availability signal's resolve condition: this replica
        # counts against availability until every request it lost here
        # has left the in-flight table (failed over to completion)
        rep.failover_rids.update(fr.request_id for fr, _ in moved)
        for fr, partial in moved:
            rep.failovers += 1
            if self._acct:
                # the dead leg's charges still bill: force-close its
                # open ledger record and keep it for the merged bill
                # (replay recompute bills on the NEXT replica — the
                # device really does run those tokens twice)
                try:
                    leg = srv.abandon_cost(fr.request_id)
                except Exception:  # noqa: BLE001 — a dying replica may
                    leg = None     # refuse even the billing scrape
                if leg is not None:
                    fr.cost_legs.append(leg)
            self._failover(fr, partial, finished, cause=reason)
        # final observability capture BEFORE teardown: the dead
        # replica's last registry/trace state keeps serving from the
        # frontend's cache (with a growing staleness mark) instead of
        # vanishing from the fleet scrape
        self._capture_obs(rep)
        try:
            srv.close()
        except Exception:  # noqa: BLE001 — a dead replica's teardown
            pass           # must never take the supervisor with it

    def _health_sweep(self, finished: List[int]) -> None:
        """The state machine: beats come from steps the frontend itself
        observed (an injected heartbeat loss hides them); wall-clock
        staleness plus at least one MISSED beat drives degraded → dead,
        so a paused frontend never mass-kills healthy replicas, while
        the slow-step breaker can degrade a beating replica."""
        now = self._clock()
        for rep in self.replicas:
            if rep.health == DEAD:
                continue
            hb_lost = (self._fi is not None
                       and self._fi.replica_heartbeat_lost(rep.index))
            beat = rep.stepped and not hb_lost
            if beat:
                rep.missed_beats = 0
                rep.last_beat_ts = now
            else:
                rep.missed_beats += 1
            stale = now - rep.last_beat_ts
            slow = (self._degraded_step_s is not None
                    and rep.last_step_s is not None
                    and rep.last_step_s > self._degraded_step_s)
            if rep.missed_beats and stale > self._dead_s:
                # the installed watchdog fires the standard one-per-
                # stall forensic dump (ring + thread stacks) on the way
                # out — a replica death looks exactly like a server
                # stall in the flight recorder
                rep.watchdog.check()
                self._kill_replica(
                    rep, f"no heartbeat for {stale:.3f}s "
                         f"(heartbeat_dead_s={self._dead_s})", finished)
            elif (rep.missed_beats and stale > self._degraded_s) or slow:
                self._transition(
                    rep, DEGRADED,
                    "slow step" if slow and not rep.missed_beats
                    else f"heartbeat stale {stale:.3f}s")
            else:
                self._transition(rep, HEALTHY, "beats resumed")

    def _transition(self, rep: _Replica, to: str, reason: str) -> None:
        if rep.health == to:
            return
        get_event_ring().record(
            telemetry_events.REPLICA_HEALTH, replica=rep.index,
            frm=rep.health, to=to, reason=reason)
        rep.health = to
        rep.gauge.set(1.0 if rep.routable else 0.0)

    def _fail_stranded(self, finished: List[int]) -> None:
        """With every replica dead nothing pending can ever run — fail
        it loudly instead of letting drain() spin forever."""
        if not self._requests:
            return
        if any(r.health != DEAD for r in self.replicas):
            return
        for fr in list(self._requests.values()):
            try:
                self._pending.remove(fr)
            except ValueError:
                pass
            self._finalize(fr, list(fr.prompt) + list(fr.committed),
                           "failed", finished, frontend_decided=True)

    # ------------------------------- alerting / canary / incidents

    def _availability(self) -> float:
        """The ``availability`` SLO signal: alive replicas over the
        replicas the pool still OWES — a dead replica stops counting
        against availability once every request it lost has been failed
        over to completion (the pool recovered; in-process death is
        permanent, so `alive/total` would pin the alert firing
        forever). 2 replicas: a kill reads 0.5 while its work is
        re-running elsewhere, then 1.0 once the last failover finishes
        — the pending -> firing -> resolved arc the chaos suite pins."""
        total = len(self.replicas)
        alive = sum(1 for r in self.replicas if r.health != DEAD)
        recovered = sum(
            1 for r in self.replicas
            if r.health == DEAD
            and not (r.failover_rids & self._requests.keys()))
        return alive / max(total - recovered, 1)

    def _pool_goodput(self) -> Optional[float]:
        """The ``goodput`` SLO signal at the pool level: the capacity
        rollup's token-weighted goodput fraction (None before any
        replica reports one — no data holds the rule)."""
        try:
            return self._capacity_snapshot()["pool"].get(
                "goodput_fraction")
        except Exception:  # noqa: BLE001 — a dying source never pages
            return None

    def _on_alert_fire(self, rule: str, info: dict) -> None:
        if self.incidents is not None:
            self.incidents.capture("alert", rule=rule, info=info)

    def _on_alert_resolve(self, rule: str, info: dict) -> None:
        if self.incidents is not None:
            self.incidents.resolve(rule, info=info)

    def _incident_collect(self) -> dict:
        """The pool incident bundle's body: replica rows, capacity,
        kept (stitched) traces, recent ring events, and the live
        alert/canary rows — everything an operator re-assembles by
        hand in the first minutes of a page, captured at the instant
        of the transition."""
        return {
            "replicas": self._debug_snapshot(),
            "capacity": self._capacity_snapshot(),
            "events": get_event_ring().snapshot(),
            "traces": ([t.to_dict() for t in self.tracer.traces()]
                       if self.tracer is not None else []),
            "alerts": (self.alerts.snapshot()
                       if self.alerts is not None else None),
            "canary": (self.canary.snapshot()
                       if self.canary is not None else None),
            "availability": self._availability(),
        }

    def incidents_snapshot(self) -> dict:
        """``GET /debug/incidents`` payload (and ``stats`` rows): live
        alert/canary state beside the retained bundles."""
        if (self.incidents is None and self.alerts is None
                and self.canary is None):
            return {"enabled": False,
                    "hint": "no slo.objectives / canary / incident "
                            "knobs armed (docs/observability.md "
                            "'SLOs, alerting & incidents')"}
        return {
            "enabled": True,
            "alerts": (self.alerts.snapshot()
                       if self.alerts is not None else None),
            "canary": (self.canary.snapshot()
                       if self.canary is not None else None),
            "incidents": (self.incidents.snapshot()
                          if self.incidents is not None else None),
        }

    def dump_incident(self, path: Optional[str] = None) -> dict:
        """On-demand forensic bundle — exactly what an alert-fire
        capture grabs, never rate-limited. ``path`` defaults into
        ``telemetry.incident.dir``."""
        if self.incidents is None:
            raise RuntimeError(
                "incident capture is off — set telemetry.incident."
                "enabled (docs/observability.md 'SLOs, alerting & "
                "incidents')")
        if path is None:
            if not self.incidents.cfg.dir:
                raise ValueError(
                    "pass a path, or set telemetry.incident.dir for "
                    "the default location")
            import os
            path = os.path.join(
                self.incidents.cfg.dir,
                f"incident_manual_{self.incidents.captured_total + 1}"
                ".json")
        return self.incidents.dump(path)

    # ------------------------------------------- fleet observability

    def _capture_obs(self, rep: _Replica) -> None:
        """Refresh one replica's cached observability snapshot, ALWAYS
        round-tripped through json bytes: the fleet plane never holds a
        reference into a replica's live telemetry objects, so the
        process-per-replica split (ROADMAP item 1) ships the same bytes
        over a pipe and nothing above this line changes. A replica
        mid-teardown keeps its previous snapshot (last-known-good)."""
        try:
            blob = json.dumps(rep.server.observability_state(),
                              default=str).encode()
            state = json.loads(blob.decode())
        except Exception:  # noqa: BLE001 — dying replica: keep the last
            return
        with self._obs_lock:
            self._obs_cache[rep.index] = (state, self._clock())

    def _obs_age(self, rep: _Replica) -> Optional[float]:
        """Seconds since the replica's snapshot was captured (frontend
        clock); None before the first capture."""
        with self._obs_lock:
            ent = self._obs_cache.get(rep.index)
        if ent is None:
            return None
        return max(0.0, self._clock() - ent[1])

    def _fleet_states(self) -> List[tuple]:
        """(replica, snapshot state, staleness seconds) per replica with
        a snapshot. Live beating replicas refresh now; dead, draining,
        and beat-missing (wedged) replicas serve their LAST snapshot —
        its growing age, mirrored into the
        ``serve_replica_scrape_age_seconds`` gauge, is the staleness
        mark a dashboard sees before the breaker ever trips."""
        out = []
        for rep in self.replicas:
            if (rep.health != DEAD and not rep.draining
                    and rep.missed_beats == 0):
                self._capture_obs(rep)
            with self._obs_lock:
                ent = self._obs_cache.get(rep.index)
            if ent is None:
                continue
            state, ts = ent
            age = max(0.0, self._clock() - ts)
            self._g_scrape_age[rep.index].set(age)
            out.append((rep, state, age))
        return out

    def _fleet_registry(self) -> MetricRegistry:
        """The federated ``/metrics`` view, built fresh per scrape into
        a scratch registry (live registries are never mutated): the
        frontend's own instruments unlabeled, every replica's under
        ``replica="r<i>"``, and pool-merged totals (counters summed,
        histogram buckets summed; gauges stay per-source) under
        ``replica="pool"`` — label cardinality is replicas + 1, however
        big the pool's request volume. One scrape, the whole fleet."""
        t0 = self._clock()
        view = MetricRegistry()
        view.import_state(self.telemetry.export_state())
        for rep, state, _age in self._fleet_states():
            metrics = state.get("metrics") or {}
            view.import_state(metrics,
                              extra_labels={"replica": f"r{rep.index}"})
            pooled = {n: f for n, f in metrics.items()
                      if f.get("type") != "gauge"}
            view.import_state(pooled, extra_labels={"replica": "pool"})
        self._h_fleet_scrape.observe(max(0.0, self._clock() - t0))
        return view

    def _fleet_snapshot(self) -> dict:
        """``GET /debug/fleet``: health, roles, per-replica goodput and
        recent dispatch gap, scrape staleness, handoff gauges, and the
        trace-stitching state — the whole pool in one JSON."""
        rows = []
        for rep, state, age in self._fleet_states():
            rows.append({
                "replica": f"r{rep.index}",
                "role": rep.role,
                "health": rep.health,
                "draining": rep.draining,
                "goodput_fraction": state.get("goodput_fraction"),
                "recent_gap_ms": round(
                    (state.get("recent_gap_s") or 0.0) * 1e3, 3),
                "scrape_staleness_s": round(age, 6),
                "tracing": bool(state.get("tracing")),
                "kept_traces": len(state.get("traces") or ()),
            })
        return {
            "replicas": rows,
            "stitching": self.tracer is not None,
            "stitched_kept": (self.tracer.kept
                              if self.tracer is not None else 0),
            "hops_by_cause": {c: int(self._c_hops[c].value)
                              for c in HOP_CAUSES},
            "handoffs": self._handoffs,
            "handoff": (self._handoff.snapshot()
                        if self._handoff is not None else None),
            "failovers": self._failovers,
            "drain_reroutes": self._drain_reroutes,
            "tick": self._tick,
        }

    def dump_timeline(self, path: str) -> int:
        """One merged Perfetto file for the whole fleet: the stitched
        frontend traces (pid 1) with flow-arrows between consecutive
        hop spans, the shared device track (pid 2), and one process
        group per replica (pid 10+i) holding its step-phase track
        (partitioned out of the shared ring by profiler source) plus
        its own kept traces — rendered from the SERIALIZED snapshots,
        the same bytes a process-split replica would ship. Returns the
        event count."""
        if self.tracer is None:
            raise RuntimeError(
                "tracing is off (telemetry.trace_sample_rate == 0) — "
                "arm it to dump the fleet timeline")
        events = self.tracer.trace_events()
        for tr in self.tracer.traces():
            tid = tr.trace_id if isinstance(tr.trace_id, int) \
                else abs(hash(tr.trace_id)) % (1 << 31)
            hops = [sp for sp in tr.root.children if sp.name == "hop"]
            for a, b in zip(hops, hops[1:]):
                # flow-arrow from the end of one leg to the start of
                # the next — Perfetto draws the handoff/failover jump
                fid = f"{tr.trace_id}/h{a.attributes.get('hop')}"
                events.append({
                    "name": "hop", "ph": "s", "cat": "hop", "id": fid,
                    "pid": 1, "tid": tid,
                    "ts": round((a.end if a.end is not None
                                 else a.start) * 1e6, 3)})
                events.append({
                    "name": "hop", "ph": "f", "bp": "e", "cat": "hop",
                    "id": fid, "pid": 1, "tid": tid,
                    "ts": round(b.start * 1e6, 3)})
        profiler_pids: Dict[int, int] = {}
        for rep, state, _age in self._fleet_states():
            pid = 10 + rep.index
            prof = getattr(rep.server, "_profiler", None)
            if prof is not None:
                profiler_pids[prof.uid] = pid
            events.append({
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": f"replica r{rep.index} "
                                 f"({state.get('role', rep.role)}, "
                                 f"{rep.health})"}})
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": 1,
                "args": {"name": "step phases"}})
            for tdict in state.get("traces") or ():
                rid = tdict.get("trace_id")
                tid = 100 + (rid if isinstance(rid, int)
                             else abs(hash(str(rid))) % (1 << 20))
                events.append({
                    "name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid,
                    "args": {"name": f"request {rid} "
                                     f"[{tdict.get('keep_reason')}]"}})
                span_events_from_dict(
                    events, tdict["root"], pid, tid,
                    extra_args={"status": tdict.get("status"),
                                "keep_reason": tdict.get("keep_reason")})
        events.extend(ring_timeline_events(get_event_ring(),
                                           get_span_log(), profiler_pids))
        payload = {"traceEvents": events, "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(payload, f, default=str)
        return len(events)

    # ---------------------------------------------------- rolling drain

    def drain_replica(self, index: int) -> None:
        """Start a rolling drain of one replica: traffic steers away
        immediately, its QUEUED work re-routes to peers (reclaimed —
        cancel-and-forget, so the ids stay resubmittable anywhere),
        residents finish in place on their warm caches, and the replica
        re-admits itself once idle (watch ``stats['replicas']``). Zero
        requests are lost (test-pinned)."""
        rep = self.replicas[index]
        if rep.health == DEAD:
            raise ValueError(
                f"replica {index} is dead ({rep.dead_reason}) — there "
                "is nothing to drain")
        if rep.draining:
            return
        # drain freezes the replica's federated series at this snapshot
        # (staleness mark grows until drain completes and beats resume)
        self._capture_obs(rep)
        rep.draining = True
        rep.gauge.set(0.0)
        get_event_ring().record(
            telemetry_events.REPLICA_HEALTH, replica=index,
            frm=rep.health, to="draining", reason="drain_replica")
        for req in list(rep.server.scheduler.queue):
            fr = self._requests.get(req.request_id)
            if fr is None:
                continue
            partial = rep.server.reclaim(req.request_id)
            if partial is None:
                continue
            # reclaim leaves the leg's closed cost record harvestable
            # (queue-wait and any prefill charges bill where they ran)
            self._harvest_leg(rep, fr)
            fr.committed = list(partial)[len(fr.prompt):]
            fr.replica = None
            fr.prefill_only = False
            self._close_hop(fr, outcome="drain_reroute",
                            committed_out=len(fr.committed))
            fr.next_cause = "drain_reroute"
            fr.replay = True
            fr.retry_at_tick = self._tick   # immediately eligible
            self._drain_reroutes += 1
            self._pending.append(fr)

    def _finish_drains(self) -> None:
        for rep in self.replicas:
            if not rep.draining or rep.health == DEAD:
                continue
            if rep.server.scheduler.idle:
                rep.draining = False
                rep.gauge.set(1.0 if rep.routable else 0.0)
                get_event_ring().record(
                    telemetry_events.REPLICA_HEALTH, replica=rep.index,
                    frm="draining", to=rep.health,
                    reason="drain_complete")

    # ------------------------------------------------------------ drain

    def drain(self, timeout_s: Optional[float] = None
              ) -> Dict[int, List[int]]:
        """Step the pool until every outstanding request finished (any
        reason). ``timeout_s`` bounds the drain on the frontend clock:
        past it, stragglers are cancelled with their partials — one
        wedged REPLICA can no longer spin the pool forever (its work
        fails over and finishes; this bound covers pathological cases
        like every replica dead-and-beyond-retries)."""
        check_drain_timeout(timeout_s)
        deadline = None if timeout_s is None \
            else self._clock() + timeout_s
        while self._requests:
            if deadline is not None and self._clock() >= deadline:
                for rid in list(self._requests):
                    self.cancel(rid)
                break
            self.step()
        # flush each live replica's async remnant + publish worker so a
        # drained pool has no device work outstanding (a drain() on an
        # idle server is exactly that flush)
        for rep in self.replicas:
            if rep.health != DEAD:
                rep.server.drain()
        if self._deferred_finished:
            self._deferred_finished.clear()
        return dict(self._results)

    def close(self) -> None:
        """Release the scrape endpoint, the step threads, and every
        live replica (dead ones were closed at declaration)."""
        if self._closed:
            return
        self._closed = True
        if self.http_server is not None:
            self.http_server.close()
            self.http_server = None
        if self._pools is not None:
            for pool in self._pools:
                pool.shutdown(wait=True)
        for rep in self.replicas:
            if rep.health != DEAD:
                try:
                    rep.server.close()
                except Exception:  # noqa: BLE001 — arbitrary states
                    pass
            rep.watchdog.disarm()
        mon = get_memory_monitor()
        for name, getter in self._mem_components:
            mon.unregister_component(name, getter)
        self._mem_components.clear()

    # ------------------------------------------------------------ stats

    def _replica_row(self, rep: _Replica) -> dict:
        sched = rep.server.scheduler
        row = {
            "replica": rep.index,
            "role": rep.role,
            "health": rep.health,
            "draining": rep.draining,
            "routable": rep.routable,
            "routed": rep.routed,
            "failovers_from": rep.failovers,
            "steps": rep.steps,
            "dead_reason": rep.dead_reason,
            "last_step_s": rep.last_step_s,
            "heartbeat_idle_s": round(rep.watchdog.idle_seconds(), 6),
            "missed_beats": rep.missed_beats,
            # age of the last federated-metrics snapshot (None before
            # the first fleet scrape): a wedged replica's series going
            # stale is visible here before the breaker trips
            "scrape_staleness_s": (
                None if (age := self._obs_age(rep)) is None
                else round(age, 6)),
        }
        try:
            row.update({
                "queued": sched.pending_requests,
                "active_slots": sched.active_slots,
                "free_blocks": sched.allocator.free_blocks,
                "decode_steps": rep.server._step_clock,
            })
            if self._disagg:
                # per-replica host-tier view (handoff imports parked
                # for the next admission + swap-ins already warmed —
                # with kv_host_offload ALSO armed the same tier and
                # counter carry plain offload traffic too, hence the
                # neutral names) and the recent dispatch-gap mean the
                # decode router ranks by
                row.update({
                    "host_tier_blocks": (
                        len(rep.server.host_tier)
                        if rep.server.host_tier is not None else 0),
                    "host_tier_swap_ins": sched.allocator.swap_ins,
                    "recent_gap_ms": round(rep.gap_s() * 1e3, 3),
                })
        except Exception:  # noqa: BLE001 — a dead replica's books may
            pass           # be mid-teardown; health is the story then
        return row

    def _debug_snapshot(self) -> dict:
        """``GET /debug/replicas`` payload (scrape thread: host-side
        bookkeeping only, no device reads)."""
        return {
            "replicas": [self._replica_row(r) for r in self.replicas],
            "pending": len(self._pending),
            "outstanding": len(self._requests),
            "failovers": self._failovers,
            "failover_replay_tokens": self._replay_tokens,
            "drain_reroutes": self._drain_reroutes,
            "tick": self._tick,
            # disaggregation (docs/serving.md "Disaggregated prefill/
            # decode"): role topology + the shared handoff tier's view
            "roles": list(self._roles),
            "disaggregated": self._disagg,
            "handoffs": self._handoffs,
            "handoff": (self._handoff.snapshot()
                        if self._handoff is not None else None),
            # fleet observability: stitching state + leg routing by
            # cause (the serve_trace_hops_total counter's view)
            "stitching": self.tracer is not None,
            "hops_by_cause": {c: int(self._c_hops[c].value)
                              for c in HOP_CAUSES},
        }

    def cost(self, request_id: int) -> Optional[dict]:
        """The merged cost record for a finished request — every
        replica leg summed (docs/observability.md "Cost accounting &
        capacity"). None when accounting is off or the id never
        finished here."""
        return self._costs.get(request_id)

    def _capacity_snapshot(self) -> dict:
        """``GET /debug/capacity`` payload (and ``stats["capacity"]``):
        one row per live replica plus the pool rollup. Scrape-thread
        safe — each row is the replica's own host-side snapshot, and a
        replica mid-death that refuses the scrape is simply absent
        (the rollup covers whoever answered)."""
        rows = []
        for rep in self.replicas:
            if rep.health == DEAD:
                continue
            try:
                row = rep.server.capacity_snapshot()
            except Exception:  # noqa: BLE001 — a scrape never kills
                continue
            row["replica"] = rep.index
            row["role"] = rep.role
            rows.append(row)
        return {"replicas": rows, "pool": rollup_capacity(rows)}

    @property
    def stats(self) -> dict:
        """Pool-level supervision stats. ``replicas`` carries one row
        per replica (health, routing counts, failovers, heartbeat age);
        per-replica serving detail lives on each replica's own private
        registry/stats."""
        snap = self._debug_snapshot()
        snap.update({
            "healthy_replicas": sum(
                1 for r in self.replicas if r.health == HEALTHY),
            "dead_replicas": sum(
                1 for r in self.replicas if r.health == DEAD),
            "fault_injection": (self._fi.snapshot()
                                if self._fi is not None else None),
            "capacity": self._capacity_snapshot(),
            "accounting": {
                "enabled": self._acct,
                "requests_billed": len(self._costs),
                "tenants": (self._tenants.snapshot()
                            if self._tenants is not None else {}),
            },
            "alerts": (self.alerts.snapshot()
                       if self.alerts is not None else None),
            "canary": (self.canary.snapshot()
                       if self.canary is not None else None),
            "incidents": (self.incidents.snapshot()
                          if self.incidents is not None else None),
        })
        return snap
