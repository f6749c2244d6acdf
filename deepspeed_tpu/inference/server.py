"""Continuous-batching server over the paged KV cache.

The serving analog of vLLM's engine loop, built TPU-native: the decode
hot path is ONE jitted program over ``num_slots`` resident sequences and
a donated :class:`~deepspeed_tpu.inference.kv_cache.PagedKVCache` —
traced once per ``(num_slots, block_size)`` configuration, never per
request shape. Requests arrive asynchronously (``submit``), the host
scheduler admits them into freed slots between decode steps (``step``),
and an EOS'd sequence's blocks return to the pool immediately instead of
spinning as dead weight until the batch's slowest row finishes (the
one-shot ``generate`` head-of-line cost).

Request lifecycle (docs/serving.md "Request lifecycle & overload
behavior"): every request ends in exactly one finish reason — ``eos`` /
``length`` (normal), ``cancelled`` (``cancel()`` or a bounded
``drain(timeout_s=...)``), ``deadline`` (per-request ``deadline_s``
expired; reaped each ``step()`` and never admitted), ``shed``
(SLO-driven load shedding fast-failed it while queued), or ``failed``
(prefill died, or preemption retries exhausted). Preemption is the one
lifecycle edge that does NOT finish a request: under pool pressure a
higher-priority arrival preempts the lowest-priority newest resident,
whose committed tokens fold into its prompt and whose request requeues
with backoff (vLLM-style recompute preemption — greedy output after a
preempt→requeue round trip is token-identical to an uninterrupted run,
test-pinned). All of it is host bookkeeping: the traced decode/prefill
programs never change, so with no lifecycle action triggered the served
tokens are byte-identical to a server without this layer.

Tradeoff vs ``InferenceEngine.generate``: generate compiles the WHOLE
token loop as one ``lax.while_loop`` (one host sync per generation);
continuous batching needs the host scheduler between steps, so it pays
one small sync per decode step. That buys slot recycling + admission —
the throughput lever under sustained multi-request traffic — while
generate remains the latency king for a single fixed batch.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.inference.async_loop import InFlightStep, PublishWorker
from deepspeed_tpu.inference.engine import (InferenceEngine, _bucket,
                                            check_draft_compat)
from deepspeed_tpu.inference.kv_cache import (HostKVTier, PagedKVCache,
                                              init_latent_paged_cache,
                                              init_paged_cache,
                                              init_recurrent_state_cache,
                                              paged_read_block,
                                              paged_swap_in, pool_arrays)
from deepspeed_tpu.inference.scheduler import Request, Scheduler
from deepspeed_tpu.inference.speculation import (LookupIndex,
                                                 draft_propose,
                                                 greedy_accept_host)
from deepspeed_tpu.model_implementations.transformer import (
    model_family, paged_decode_step, paged_prefill, paged_prefill_chunk,
    paged_verify_step)
from deepspeed_tpu.telemetry import (NULL_STEP_HANDLE, AlertEngine,
                                     CanaryProber, CapacityModel,
                                     FaultInjector, IncidentRecorder,
                                     KVPoolAccountant, MetricRegistry,
                                     PrefillFault, ProfilerCapture,
                                     RequestLedger, SLOMonitor,
                                     StepProfiler, Tracer,
                                     config_fingerprint, get_event_ring,
                                     get_registry, start_http_server,
                                     watched_jit)
from deepspeed_tpu.telemetry import events as telemetry_events
from deepspeed_tpu.telemetry.step_profile import (DECODE_SPAN, FLUSH_SPAN,
                                                  PREFILL_SPAN, QUEUE_SPAN,
                                                  REQUEST_SPAN)

# finish reason -> event-ring kind (every lifecycle finish leaves a
# forensic entry; "eos"/"length" are the quiet normal path)
_LIFECYCLE_EVENTS = {
    "cancelled": telemetry_events.CANCEL,
    "deadline": telemetry_events.DEADLINE_EXPIRED,
    "shed": telemetry_events.SHED,
    "failed": telemetry_events.REQUEST_FAILED,
}


def submit_rejection(prompt, max_new_tokens: int, floor: int,
                     deadline_s) -> Optional[tuple]:
    """``(reason, message)`` when these submit() arguments can never be
    served, else None — ONE predicate for the server and the
    supervising :class:`~deepspeed_tpu.inference.frontend.
    ServingFrontend` (which promises the server's submit contract;
    sharing the check keeps that true by construction)."""
    if not prompt:
        return "empty_prompt", "empty prompt"
    if max_new_tokens < floor:
        return "budget_floor", (
            f"max_new_tokens={max_new_tokens} is below the "
            f"schedulable floor {floor} (min_out_tokens)")
    if deadline_s is not None and deadline_s <= 0:
        return "bad_deadline", (
            f"deadline_s must be > 0 seconds (or None for no "
            f"deadline), got {deadline_s}")
    return None


def check_drain_timeout(timeout_s) -> None:
    """Shared ``drain(timeout_s=...)`` validation (server + frontend)."""
    if timeout_s is not None and timeout_s < 0:
        raise ValueError(
            f"drain timeout_s must be >= 0 (or None for unbounded), "
            f"got {timeout_s}")


def _safe_cache_size(fn) -> int:
    """``_cache_size`` is private JAX API; a JAX upgrade must degrade the
    trace-count stat (-1), never crash step telemetry."""
    try:
        return int(fn._cache_size())
    except Exception:  # noqa: BLE001 — any private-API drift
        return -1


@jax.jit
def _set_row(table, slot, row):
    """``table.at[slot].set(row)`` as one small program: written out on
    the host, the indexed update costs 0.6-1.0 ms of Python a call, and
    a refill pays two with the device idle (PERF.md section 6, PR 45)."""
    return table.at[slot].set(row)


def _sample(logits):
    """Greedy choice, under the serving programs' ``sample`` scope."""
    with jax.named_scope("sample"):
        return jnp.argmax(logits, -1).astype(jnp.int32)


class _RequestTrace:
    """Host bookkeeping for one traced request (allocated only when
    tracing is armed — with ``telemetry.trace_sample_rate == 0`` the
    serving loop builds none of these, guarded by a test counting live
    trace objects)."""

    __slots__ = ("trace", "queue", "prefill", "decode", "steps", "tokens")

    def __init__(self, trace):
        self.trace = trace
        self.queue = None     # open queue_wait span (submit -> admission)
        self.prefill = None   # open prefill span (admission -> last chunk)
        self.decode = None    # open decode-residency span
        self.steps = 0        # decode steps this request participated in
        self.tokens = 0       # tokens committed by decode steps


@dataclasses.dataclass
class _Rider:
    """An admission handed to the step's decode round: its host part is
    done (``_admit``), its prompt rides the step's one program
    (``serve_decode_admit``) and its first token commits with that
    program's record."""
    slot: int
    state: object
    ids: np.ndarray        # the right-padded prompt, [1, bucket]
    t_admit: float


# ------------------------------------------------------ kinds of pool
# What the server does differently for each kind of cache a model's
# programs thread through (``model_config.cache_kind``: ``kv`` the
# generic decoder's K/V pool; ``kv_window`` and ``kv_state`` the same
# pool with rings / recurrent states a slot beside it under one
# allocator; ``latent``; ``state``): how the pool is
# built, whether its programs read a block table, and which switches it
# cannot honour (a model that counts on the device says so itself: its
# configuration has an ``aux_shape``). A
# switch's code reads or writes K/V pools ``[L, NB, BS, KH*D]`` (block
# copies, scale tiles, chunk / verify kernels); another kind of pool has
# none of those shapes, nothing falls back to K/V code, and each such
# switch is refused at construction by name.

def _rows_switches(int8, offload, prefix, chunk, chain, spec):
    """The switches whose code works on K/V rows, each with why this kind
    of pool refuses it: ``(switch, is it on, why not)`` over ``(config,
    draft_engine, handoff_import)``. The last two read the same for every
    kind without rows."""
    return (
        ("kv_cache_dtype", lambda c, d, h: c.kv_cache_dtype != "fp", int8),
        ("kv_host_offload", lambda c, d, h: c.kv_host_offload, offload),
        ("enable_prefix_caching",
         lambda c, d, h: c.enable_prefix_caching, prefix),
        ("prefill_chunk_tokens",
         lambda c, d, h: bool(c.prefill_chunk_tokens), chunk),
        ("prefill_chain", lambda c, d, h: c.prefill_chain, chain),
        ("speculation_tokens",
         lambda c, d, h: bool(c.speculation_tokens), spec),
        ("speculation_draft / draft_engine",
         lambda c, d, h: d is not None or c.speculation_draft is not None,
         "a draft pool mirrors K/V block tables"),
        ("handoff_import", lambda c, d, h: h,
         "handoff payloads are K/V slabs"))


@dataclasses.dataclass(frozen=True)
class _PoolKind:
    make_pool: str                 # the server's method: (num_blocks) -> pool
    block_tables: bool = True      # its programs read a block table
    what: str = ""                 # the model and its pool, in a refusal
    serves: str = ""               # what does serve it
    refuses: tuple = ()            # (switch, is it on, why not)
    # (switches, entry point): honoured for a model whose module has the
    # entry point, refused with their reason for one that has not
    by_entry_point: tuple = ((), "")

    def refuse(self, cfg, draft_engine, handoff_import, family=None) -> None:
        switches, entry = self.by_entry_point
        honoured = switches if entry and hasattr(family, entry) else ()
        on = [(name, why) for name, test, why in self.refuses
              if name not in honoured
              and test(cfg, draft_engine, handoff_import)]
        if on:
            raise NotImplementedError(
                f"{self.what} cannot be served with " + "; ".join(
                    f"{name} ({why})" for name, why in on)
                + f" — leave these at their defaults: {self.serves}")


_POOL_KINDS = {
    "kv": _PoolKind(make_pool="_make_kv_pool"),
    # the K/V pool with a layer-kind map (kv_cache.PagedKVCache
    # ``layer_map``): window layers keep a bounded ring a slot beside the
    # block pool of the full layers. Same rows, same pool class, same
    # block tables; what reads or writes EVERY layer through the tables
    # cannot see a ring
    "kv_window": _PoolKind(
        make_pool="_make_kv_pool",
        what="a model with window layers (a ring a slot beside the block "
             "pool)",
        serves="monolithic bucketed prefill and plain paged decode "
               "serve it",
        refuses=_rows_switches(
            "a ring has no int8 rows or scale tiles",
            "a block's payload is every layer's slab, and a ring layer "
            "has none in a block",
            "a cached block holds no window layer's rows, and the ring "
            "of the prompt that wrote it is gone",
            "no program carries a ring from one prompt chunk to the next",
            "it chains chunked prefill",
            "a rejected draft token's row cannot be taken back out of a "
            "ring")),
    # the K/V pool with state layers in its map: a state-space layer
    # keeps a recurrent state and a convolution tail a slot beside the
    # block pool of the attention layers. One pool class, one allocator,
    # one admission rule (a slot and the attention layers' blocks); what
    # works on EVERY layer's rows cannot see a state
    "kv_state": _PoolKind(
        make_pool="_make_kv_pool",
        what="a model with state layers (a recurrent state a slot beside "
             "the block pool)",
        serves="monolithic bucketed prefill (the chunked form inside one "
               "program) and paged decode over state updates serve it",
        refuses=_rows_switches(
            "a state is float32 and has no int8 rows or scale tiles",
            "a block's payload is every layer's slab, and a state layer "
            "has none in a block",
            "a cached block holds no state layer's state at its boundary; "
            "reusing a prefix needs a snapshot of the states there",
            "no program carries a state layer's state and convolution "
            "tail from one prompt chunk to the next",
            "it chains chunked prefill",
            "a rejected draft token cannot be taken back out of a "
            "state")),
    "latent": _PoolKind(
        make_pool="_make_latent_pool",
        what="a latent-attention model (latent paged cache)",
        serves="monolithic bucketed prefill and plain paged decode "
               "serve it, chunked prefill and prefix reuse too where the "
               "model's module has paged_prefill_chunk",
        by_entry_point=(("enable_prefix_caching", "prefill_chunk_tokens"),
                        "paged_prefill_chunk"),
        refuses=_rows_switches(
            "the latent pool has no int8 rows or scale tiles",
            "block payloads are read and swapped as K/V slabs",
            "a cache hit prefills its tail through a chunk program, and "
            "this model's module has no paged_prefill_chunk",
            "this model's module has no paged_prefill_chunk: no program "
            "of its attends a prompt chunk against the latent pool",
            "chunks chained on the device have no test over a latent "
            "pool",
            "the batched verify attends the pool with the K/V verify "
            "kernel")),
    "state": _PoolKind(
        make_pool="_make_state_pool", block_tables=False,
        what="a retention model (recurrent state pool)",
        serves="monolithic bucketed prefill (the chunked form inside "
               "one program) and state-update decode serve it",
        refuses=_rows_switches(
            "the state is float32 and has no rows or scale tiles",
            "a state has no blocks to demote to a host tier",
            "a state holds no per-token rows to share; reusing a prefix "
            "needs a snapshot of its state",
            "no program carries a slot's state from one prompt chunk to "
            "the next",
            "it chains chunked prefill",
            "a rejected draft token cannot be taken back out of a "
            "state")),
}


class ContinuousBatchingServer:
    """``submit() / step() / drain()`` serving loop over an
    :class:`InferenceEngine`'s weights.

    Greedy decoding only (the mode with an exact one-shot oracle:
    output is token-for-token identical to ``engine.generate``).
    Sampling per-request is a scheduler-policy follow-up, not a
    substrate change — temperatures would ride as a per-slot array.

    ``clock`` (injectable, default ``time.perf_counter``) is the basis
    for every latency observation, deadline, and the ``drain`` timeout —
    the chaos tests drive deadlines and wedged-slot reaping with a fake
    clock and zero real sleeps. ``fault_injector`` arms the chaos hooks
    (telemetry/faultinject.py); None (the default, and the default
    config) costs nothing per step.
    """

    def __init__(self, engine: InferenceEngine,
                 registry: Optional[MetricRegistry] = None,
                 clock: Optional[Callable[[], float]] = None,
                 fault_injector: Optional[FaultInjector] = None,
                 supervised: bool = False, role: str = "mixed",
                 handoff_import: bool = False,
                 profile_source: str = "serve",
                 draft_engine: Optional[InferenceEngine] = None):
        if engine.model_config.head == "none":
            raise ValueError("continuous batching needs an LM head — "
                             "encoder models have nothing to decode")
        if engine.model_config.seq_shard_kv:
            raise NotImplementedError(
                "continuous batching with a seq-sharded KV cache is "
                "unsupported — the paged pool is already the "
                "long-context memory lever")
        self.engine = engine
        # the kind of pool the model's programs thread through (K and V
        # per head; one latent row an attention; a recurrent state a
        # slot): what a kind cannot honour is refused here by name
        self._pool_kind = _POOL_KINDS[getattr(engine.model_config,
                                              "cache_kind", "kv")]
        self._pool_kind.refuse(engine.config, draft_engine, handoff_import,
                               model_family(engine.model_config))
        # supervised = this server is ONE REPLICA under a ServingFrontend
        # (inference/frontend.py): the frontend owns the scrape port and
        # installs its own heartbeat watchdog on self.watchdog, so the
        # config-armed endpoint and stall-dump thread stay off here —
        # everything else (tracing, SLO, step profile, fault sites) is
        # per-replica as usual
        self._supervised = supervised
        # disaggregated serving (docs/serving.md "Disaggregated
        # prefill/decode"): the ROLE is routing metadata owned by the
        # frontend — the server itself serves whatever it is handed
        # (a "prefill" replica just only ever receives one-token
        # budgets). handoff_import arms an import-only host tier on a
        # decode-capable replica so consumed handoff payloads park
        # where the next admission's match_prefix walk swaps them in.
        self.role = role
        self._closed = False
        cfg = engine.config
        mcfg = engine.model_config
        self.block_size = cfg.block_size
        self.num_slots = cfg.num_slots
        self._clock = clock if clock is not None else time.perf_counter
        # per-slot token budget reuses the engine's HBM accounting
        # (explicit max_out_tokens, or 'auto' free-memory sizing at
        # batch=num_slots — kv_cache.auto_max_tokens)
        per_slot = engine._max_out_budget(self.num_slots)
        if per_slot < self.block_size:
            raise ValueError(
                f"per-slot KV budget {per_slot} tokens is below one "
                f"block ({self.block_size}) — raise max_out_tokens or "
                "shrink block_size")
        self.max_blocks_per_slot = per_slot // self.block_size
        # prefix caching implies chunked prefill: a cache-hit admission
        # prefills only the tail, which needs the position-offset chunk
        # signature — when the knob is unset, one-block chunks keep the
        # skipped-compute win exact at block granularity
        self.prefix_caching = cfg.enable_prefix_caching
        self.chunk_tokens = cfg.prefill_chunk_tokens or (
            self.block_size if cfg.enable_prefix_caching else 0)
        # per-slot speculative decoding (docs/serving.md "Per-slot
        # speculative decoding"): K = chunk width of the batched verify
        # forward (pending token + up to K-1 proposals per active
        # slot — prompt-lookup by default, batched draft-model
        # forwards when a draft engine is wired). 0 = off — the decode
        # path is byte-identical to a server without this layer.
        self.spec_tokens = cfg.speculation_tokens
        self.draft = draft_engine if draft_engine is not None \
            else cfg.speculation_draft
        if self.draft is not None:
            if self.spec_tokens < 2:
                raise ValueError(
                    "draft_engine proposes speculation_tokens-1 "
                    "candidates per slot — it requires "
                    "speculation_tokens >= 2")
            check_draft_compat(engine, self.draft)
        # telemetry: registry recording is always on (dict lookup + float
        # add per event); telemetry.enabled=False swaps in a private
        # registry, so cost is identical but nothing reaches the process
        # scrape surface. The HTTP endpoint is opt-in via config.
        tcfg = getattr(cfg, "telemetry", None)
        enabled = tcfg is None or tcfg.enabled
        self.telemetry = registry or (get_registry() if enabled
                                      else MetricRegistry())
        # request-scoped tracing (telemetry/tracing.py): armed only when
        # the sample rate is nonzero — tracing fully off means the hot
        # path allocates NOTHING per request (no Tracer, no spans)
        self.tracer = None
        self._rt: Dict[int, _RequestTrace] = {}
        if tcfg is not None and enabled and tcfg.trace_sample_rate > 0:
            self.tracer = Tracer(
                sample_rate=tcfg.trace_sample_rate,
                ring_capacity=tcfg.trace_ring_capacity,
                seed=tcfg.trace_seed,
                slow_threshold_s=tcfg.trace_slow_threshold_s,
                registry=self.telemetry)
        # SLO gates (telemetry/slo.py): windowed objectives over the
        # serving histograms, re-evaluated at step cadence. Shares the
        # server clock so fake-clock tests drive violations coherently.
        self.slo = None
        if tcfg is not None and enabled and tcfg.slo.enabled:
            self.slo = SLOMonitor(tcfg.slo, registry=self.telemetry,
                                  clock=self._clock)
        # chaos hooks (telemetry/faultinject.py): explicit injector
        # beats config; both default to None = zero per-step cost
        self._fi = fault_injector
        if self._fi is None and tcfg is not None and enabled:
            self._fi = FaultInjector.from_config(
                tcfg.fault_injection, registry=self.telemetry)
        # SLO-driven load shedding (docs/serving.md "Request lifecycle
        # & overload behavior"): config error if armed without the
        # objective it consults — silently never shedding would defeat
        # the operator's intent at the worst possible moment
        self._shedding = cfg.enable_load_shedding
        if self._shedding and (self.slo is None
                               or "queue_wait_p90" not in self.slo.targets):
            raise ValueError(
                "enable_load_shedding consults the telemetry.slo "
                "queue_wait_p90_s objective — enable telemetry.slo and "
                "set queue_wait_p90_s (docs/serving.md 'Request "
                "lifecycle & overload behavior')")
        self.max_preemptions = cfg.max_preemptions
        self._backoff_steps = cfg.preemption_backoff_steps
        # serving step observatory (telemetry/step_profile.py) + KV-pool
        # accounting (telemetry/memory.py): ON by default — a handful
        # of monotonic-clock reads and histogram observes per step, NO
        # device syncs. OFF builds neither object: the loop holds the
        # shared no-op handle, the allocator hooks stay None, and none
        # of the serve_step_* / serve_kv_* families register.
        self._profiler = None
        self._pool_acct = None
        if tcfg is None or tcfg.step_profile:
            self._profiler = StepProfiler(
                registry=self.telemetry, clock=self._clock,
                source=profile_source)
            self._pool_acct = KVPoolAccountant(
                registry=self.telemetry, clock=self._clock)
        # request-level cost accounting + capacity model (telemetry/
        # accounting.py, telemetry/capacity.py — docs/observability.md
        # "Cost accounting & capacity"): the ledger splits each worked
        # step's device-attributed wall across resident slots by tokens
        # processed, so it arms only when the step profiler exists
        # (device attribution without one would be fiction) AND
        # accounting is enabled. OFF builds neither object, registers
        # none of the serve_request_*_seconds / serve_tenant_* families,
        # and leaves the serving loop byte-identical (every hook sits
        # behind a None check).
        self._ledger = None
        self._capacity = None
        acct_on = tcfg is None or tcfg.accounting.enabled
        if self._profiler is not None and acct_on:
            self._ledger = RequestLedger(
                registry=self.telemetry, clock=self._clock,
                max_tenants=(tcfg.accounting.max_tenants
                             if tcfg is not None else 32),
                source=profile_source)
            # the closure tap: each worked step's device attribution
            # settles across that step's per-request token weights the
            # moment the profiler records it
            self._profiler.on_step_device = self._ledger.settle_step
            self._capacity = CapacityModel(
                registry=self.telemetry, clock=self._clock,
                window_s=(tcfg.accounting.window_s
                          if tcfg is not None else 60.0),
                eval_interval_s=(tcfg.accounting.eval_interval_s
                                 if tcfg is not None else 5.0),
                levels=self._capacity_levels,
                goodput=self._capacity_goodput)
        # SLO burn-rate alerting + canary probes + incident bundles
        # (telemetry/alerts.py, canary.py, incident.py — docs/
        # observability.md "SLOs, alerting & incidents"): the closed
        # loop. All three default OFF (objectives={}, canary.enabled /
        # incident.enabled False) — a default-config server builds none
        # of these objects and registers zero new instruments, so the
        # serving path stays byte-identical.
        # A supervised replica builds NONE of them: the pool boundary
        # (ServingFrontend) owns the closed loop — a per-replica canary
        # would collide with the frontend's request-id namespace and
        # double-probe, and per-replica bundles would fragment the one
        # incident an operator needs.
        self.alerts = None
        self.canary = None
        self.incidents = None
        if tcfg is not None and enabled and not supervised:
            if tcfg.incident.enabled:
                self.incidents = IncidentRecorder(
                    tcfg.incident, collect=self._incident_collect,
                    registry=self.telemetry, clock=self._clock,
                    fingerprint=config_fingerprint(cfg),
                    name=f"{profile_source}_incidents")
            if tcfg.slo.enabled and tcfg.slo.objectives:
                # objectives ride under the slo.enabled master switch:
                # slo.enabled=false is byte-identical serving with zero
                # serve_alert* instruments, objectives or not (pinned)
                self.alerts = AlertEngine(
                    tcfg.slo, registry=self.telemetry,
                    clock=self._clock,
                    sources={"goodput": self._capacity_goodput},
                    on_fire=self._on_alert_fire,
                    on_resolve=self._on_alert_resolve)
            if tcfg.canary.enabled:
                self.canary = CanaryProber(
                    tcfg.canary, submit=self.submit,
                    result=self.result,
                    finish_reason=self.finish_reason,
                    cancel=self.cancel,
                    registry=self.telemetry, clock=self._clock,
                    vocab_size=getattr(engine.model_config,
                                       "vocab_size", None))
        self.http_server = None
        if (tcfg is not None and enabled and tcfg.http_port is not None
                and not supervised):
            self.http_server = start_http_server(
                tcfg.http_port, host=tcfg.http_host,
                registry=self.telemetry, tracer=self.tracer,
                goodput=self._goodput_snapshot,
                capacity=self.capacity_snapshot,
                incidents=self.incidents_snapshot)
        self.profiler_capture = ProfilerCapture()
        reg = self.telemetry
        self._h_queue_wait = reg.histogram(
            "serve_queue_wait_seconds", help="submit() to slot admission")
        self._h_ttft = reg.histogram(
            "serve_ttft_seconds", help="submit() to first token committed")
        self._h_request = reg.histogram(
            "serve_request_seconds", help="submit() to finished, end to end")
        self._h_decode_step = reg.histogram(
            "serve_decode_step_seconds",
            help="one decode step over all num_slots rows")
        self._h_token = reg.histogram(
            "serve_token_seconds",
            help="per-token decode latency (one committed token per live "
                 "slot per step)")
        self._c_submitted = reg.counter("serve_requests_submitted_total",
                                        help="accepted submit() calls")
        self._c_finished = reg.counter("serve_requests_finished_total",
                                       help="requests retired")
        self._c_prefills = reg.counter("serve_prefills_total",
                                       help="prefill programs executed")
        self._c_admissions = {
            path: reg.counter(
                "serve_admissions_total", labels={"path": path},
                help="admissions that reached their first token, by the "
                     "program that prefilled the prompt: rider (inside "
                     "a step's decode program, beside decoding rows), "
                     "alone (a program that carried no decoding row), "
                     "chunk (chunk programs)")
            for path in ("rider", "alone", "chunk")}
        self._admissions = dict.fromkeys(self._c_admissions, 0)
        self._c_decode_steps = reg.counter("serve_decode_steps_total",
                                           help="decode steps executed")
        self._c_tokens = reg.counter("serve_tokens_total",
                                     help="generated tokens committed")
        self._c_block_steps = reg.counter(
            "serve_kv_used_block_steps_total",
            help="pool blocks held by resident sequences, summed over "
                 "committed decode steps and verify rounds (over "
                 "serve_decode_steps_total x the pool's blocks: the "
                 "mean share of the pool in use)")
        self._c_blocked_steps = reg.counter(
            "serve_kv_admission_blocked_steps_total",
            help="committed decode steps that ended with a free slot "
                 "and an eligible queued request whose blocks the free "
                 "list could not cover")
        self._g_occupancy = reg.gauge(
            "serve_slot_occupancy",
            help="live/num_slots at the last decode step")
        self._h_prefill_chunk = reg.histogram(
            "serve_prefill_chunk_seconds",
            help="one chunked-prefill chunk (prefill_chunk_tokens "
                 "tokens through the paged trunk; non-final chunks "
                 "observe the dispatch interval — they no longer "
                 "force a fetch)")
        self._c_prompt_tokens = {
            source: reg.counter(
                "serve_prompt_tokens_total", labels={"source": source},
                help="prompt tokens of chunked admissions: served from "
                     "cached prefix blocks (no prefill compute) or "
                     "prefilled by chunk programs")
            for source in ("cached", "prefilled")}
        self._c_chunk_rows = reg.counter(
            "serve_prefill_chunk_rows_total",
            help="query rows of the chunk programs executed "
                 "(prefill_chunk_tokens a program, padding included); "
                 "serve_prefill_chunk_seconds counts the programs")
        self._c_tail_reclaimed = reg.counter(
            "serve_tail_blocks_reclaimed_total",
            help="reserved-but-never-written tail blocks returned to "
                 "the free list at retirement (budget the sequence "
                 "EOSed before reaching)")
        # lifecycle counters (docs/serving.md "Request lifecycle &
        # overload behavior"; docs/observability.md catalog)
        # one registry counter per terminal reason, keyed the way
        # _finalize receives it — adding a reason means adding it here,
        # in _LIFECYCLE_EVENTS, and in stats; a miss fails loudly at
        # finish time
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
            "shed": reg.counter(
                "serve_shed_total",
                help="queued requests fast-failed by SLO-driven load "
                     "shedding (finish reason 'shed')"),
            "failed": reg.counter(
                "serve_requests_failed_total",
                help="requests failed by the server: prefill fault, or "
                     "preemption retries exhausted (finish reason "
                     "'failed'; always-kept error trace)"),
        }
        self._c_preempted = reg.counter(
            "serve_preempted_total",
            help="slot preemptions (recompute-requeue): the victim's "
                 "committed tokens fold into its prompt and it waits "
                 "out a backoff before re-admission")
        # speculative decoding (docs/serving.md "Per-slot speculative
        # decoding"): proposal/acceptance volume plus the headline
        # number — committed tokens per target forward per slot
        self._c_spec_proposed = reg.counter(
            "serve_spec_proposed_total",
            help="prompt-lookup draft tokens submitted to the batched "
                 "verify forward ((speculation_tokens-1) per active "
                 "slot per step)")
        self._c_spec_accepted = reg.counter(
            "serve_spec_accepted_total",
            help="proposed draft tokens the target's argmax accepted "
                 "(acceptance rate = accepted / proposed)")
        self._h_spec_commit = reg.histogram(
            "serve_spec_committed_per_forward",
            help="tokens committed per active slot per verify forward "
                 "(1 = speculation wins nothing; up to "
                 "speculation_tokens on full acceptance)")
        # -------- KV tiering (docs/serving.md "KV quantization & host
        # tiering"): int8 pool storage and/or a host tier for demoted
        # prefix blocks. Both are DATA changes on the same traced
        # programs — the pool dtype and scale tiles ride the donated
        # cache pytree, tier membership lives in host bookkeeping.
        self.kv_dtype = cfg.kv_cache_dtype
        self.host_tier = (HostKVTier(cfg.kv_host_blocks)
                          if cfg.kv_host_offload else None)
        # import-only tier: holds handoff payloads the frontend parked
        # for this replica's next admission (import_prefix). Unbounded
        # — the frontend's HandoffTier is the bounded stage; entries
        # here are already committed to a specific routed request.
        # Demotion is NOT wired for an import-only tier (on_demote
        # stays None below), so this replica's LRU pops remain plain
        # evictions — byte-identical eviction behavior to a server
        # without the handoff layer.
        self._import_only_tier = False
        self._handoff_import = handoff_import
        if handoff_import and self.host_tier is None:
            if not self.prefix_caching:
                raise ValueError(
                    "handoff_import needs enable_prefix_caching — a "
                    "hashless block has no identity to import under")
            self.host_tier = HostKVTier(None)
            self._import_only_tier = True
        # swap-thrash detector: rolling window of per-step swap-in
        # counts (the allocator's counter, sampled at step cadence)
        self._swap_window: Deque[int] = deque(
            maxlen=self._SWAP_WINDOW_STEPS)
        self._swap_seen = 0
        self._swap_alarm = False
        self._host_mem_getter = None
        self._submit_ts: Dict[int, float] = {}
        # open request-lifecycle spans (see submit / _request_phase);
        # empty when the step profiler is off
        self._req_spans: Dict[int, list] = {}
        self._admitted_step = 0
        self._rode_step = False     # this step's program carried a rider
        # when the request last ENTERED the queue (submit or preemption
        # requeue) — the shed guard's notion of "how long has this
        # waiter actually been waiting"; _submit_ts must stay the
        # original birth time for TTFT/queue-wait/total-latency
        self._queued_ts: Dict[int, float] = {}
        # only requests WITH a deadline live here — the reap scan is
        # O(deadlined requests), zero when the feature is unused
        self._deadlines: Dict[int, float] = {}
        self.finish_reasons: Dict[int, str] = {}
        # +1: block 0 is the reserved null block idle slots write into.
        # ``kv_pool_blocks`` unset: every slot can hold a whole span at
        # once; set, the pool is that many blocks and admission waits on
        # the free list (Request.blocks_needed) with a slot free
        num_blocks = 1 + (cfg.kv_pool_blocks
                          or self.num_slots * self.max_blocks_per_slot)
        self.scheduler = Scheduler(
            num_slots=self.num_slots, num_blocks=num_blocks,
            block_size=self.block_size,
            max_blocks_per_slot=self.max_blocks_per_slot,
            max_queued_requests=cfg.max_queued_requests,
            registry=self.telemetry,
            enable_prefix_caching=self.prefix_caching,
            tracer=self.tracer,
            spec_margin=max(self.spec_tokens - 1, 0),
            pool_accountant=self._pool_acct,
            host_tier=self.host_tier,
            pool_has_blocks=self._pool_kind.block_tables)
        self._cache = getattr(self, self._pool_kind.make_pool)(num_blocks)
        if self.host_tier is not None:
            # the allocator decides WHEN to tier; the server owns the
            # device arrays, so the copies are its callbacks. Both run
            # only inside admission-time allocation — a lag-0 step,
            # after any pipeline flush — so a tier copy can never race
            # an in-flight donated step. An import-only tier wires the
            # swap-in side ONLY: handoff payloads swap in on prefix
            # hits, but this replica's own LRU pops stay plain
            # evictions (on_demote None — see _pop_free).
            alloc = self.scheduler.allocator
            if not self._import_only_tier:
                alloc.on_demote = self._demote_block
            alloc.on_swap_in = self._swap_in_block
            # /debug/memory accounts the tier's host-RAM bytes beside
            # the HBM buckets (weakref: a dropped server must not pin
            # its payloads through the process-wide monitor). Import-
            # only tiers skip it: N decode replicas would clobber one
            # process-wide getter, and their parked bytes are already
            # visible on the frontend's handoff gauge + /debug/replicas
            if not self._import_only_tier:
                import weakref

                from deepspeed_tpu.telemetry.memory import \
                    get_memory_monitor
                tier_ref = weakref.ref(self.host_tier)

                def _host_bytes():
                    tier = tier_ref()
                    return 0 if tier is None else tier.host_bytes

                self._host_mem_getter = _host_bytes
                get_memory_monitor().register_host_component(
                    "kv_host_tier", _host_bytes)
        # flight recorder (telemetry/compile_watch.py): the serving jits
        # are watched, so a prompt shape that defeats the geometric
        # buckets shows up as a `retrace` event naming the argument that
        # changed — with compile wall time and executable HBM footprint
        self._prefill_jit = watched_jit(
            functools.partial(self._prefill_fn, cfg=mcfg,
                              mesh=engine.mesh),
            name="serve_prefill", registry=self.telemetry,
            static_argnames=(), donate_argnames=("cache",))
        self._decode_jit = watched_jit(
            functools.partial(self._decode_fn, cfg=mcfg,
                              mesh=engine.mesh),
            name="serve_decode", registry=self.telemetry,
            donate_argnames=("cache",))
        # the decode program that also prefills one admitted prompt
        # (docs/serving.md "Async dispatch loop", the rider round): a
        # family that has the entry point, served monolithic and without
        # speculation, runs it for EVERY admission and never
        # ``serve_prefill``; one trace a prompt bucket, ``slot``,
        # ``length`` and ``active`` are data. With no slot decoding it
        # runs with no active row: an admission alone
        self._admit_jit = None
        self._rider: Optional[_Rider] = None
        if (hasattr(model_family(mcfg), "paged_decode_admit")
                and not self.chunk_tokens and not self.spec_tokens
                and self.draft is None):
            self._admit_jit = watched_jit(
                functools.partial(self._decode_admit_fn, cfg=mcfg,
                                  mesh=engine.mesh),
                name="serve_decode_admit", registry=self.telemetry,
                donate_argnames=("cache",))
            self._idle_rows = (jnp.zeros((self.num_slots,), jnp.int32),
                               jnp.zeros((self.num_slots,), bool))
        # the chunked-prefill program: ONE traced signature per
        # (prefill_chunk_tokens, num_slots, block_size) config — start/
        # slot/length ride as traced scalars, so neither prompt length
        # nor cached-prefix depth ever retraces
        self._chunk_jit = None
        if self.chunk_tokens:
            self._chunk_jit = watched_jit(
                functools.partial(self._chunk_fn, cfg=mcfg,
                                  mesh=engine.mesh),
                name="serve_prefill_chunk", registry=self.telemetry,
                static_argnames=(), donate_argnames=("cache",))
        # the batched speculative-verify program: ONE traced signature
        # per (speculation_tokens, num_slots, block_size) — per-slot
        # acceptance lengths ride in cache.lengths as traced data, so
        # varying acceptance NEVER retraces (PR-5 discipline)
        self._verify_jit = None
        if self.spec_tokens:
            self._verify_jit = watched_jit(
                functools.partial(self._verify_fn, cfg=mcfg,
                                  mesh=engine.mesh),
                name="serve_spec_verify", registry=self.telemetry,
                donate_argnames=("cache",))
        # draft-model speculation (docs/serving.md "Per-slot speculative
        # decoding", draft-model option): the draft keeps its OWN paged
        # pool with the target's geometry (same slots/blocks/block size)
        # and the draft model's dims. Its block tables MIRROR the
        # target's — copied per proposal round (tiny [S, MB] int32; a
        # shared buffer would be invalidated when the target cache is
        # donated) — so draft kv lands block-for-block beside the
        # target kv it shadows and every allocator decision (prefix
        # sharing, preemption, spec margin) covers both pools at once.
        # Proposals come from speculation_tokens sequential batched
        # draft decode steps (the last backfills the final proposal's
        # kv, mirroring the one-shot engine's draft scan) and feed the
        # SAME _verify_jit: the device-built [S, K] token block has the
        # host-built path's exact aval, so the target gains zero new
        # executables in draft mode.
        self._draft_cache = None
        self._draft_prefill_jit = None
        self._draft_decode_jit = None
        if self.draft is not None:
            dcfg = self.draft.model_config
            self._draft_cache = self._make_draft_pool(num_blocks)
            self._draft_prefill_jit = watched_jit(
                functools.partial(self._prefill_fn, cfg=dcfg,
                                  mesh=self.draft.mesh),
                name="serve_draft_prefill", registry=self.telemetry,
                static_argnames=(), donate_argnames=("cache",))
            self._draft_decode_jit = watched_jit(
                functools.partial(self._decode_fn, cfg=dcfg,
                                  mesh=self.draft.mesh),
                name="serve_draft_decode", registry=self.telemetry,
                donate_argnames=("cache",))
        self._results: Dict[int, List[int]] = {}
        # what a model of another family's programs accumulate on the
        # device (``cache.aux``) and the registry series its module
        # gives each cell of it
        self._aux_seen = self._aux_series = None
        if hasattr(mcfg, "aux_shape"):
            self._aux_seen = np.zeros(mcfg.aux_shape, np.int64)
            self._aux_series = model_family(mcfg).aux_series(
                mcfg, self.telemetry)
        self._next_id = 0
        self._step_clock = 0           # decode steps executed
        # scheduler tick: advances on EVERY step() call, decode or not —
        # requeue backoff counts against this clock, so a backing-off
        # queue head on an otherwise-idle server still becomes eligible
        # (keying backoff on decode steps would deadlock the drain loop:
        # no admittable work -> no decode -> no clock -> never ready)
        self._tick = 0
        self._active_slot_steps = 0    # sum of live slots per decode step
        self._prefills = 0
        self._prefill_chunks = 0       # chunk programs executed
        self._prefill_token_units = 0  # tokens run through prefill compute
        self._prefix_tokens_skipped = 0   # prompt tokens served from cache
        self._tail_reclaimed = 0
        # speculation host mirrors (stats without a snapshot round-trip)
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_committed = 0       # tokens committed by verify steps
        self._spec_steps = 0           # verify forwards executed
        self._spec_slot_steps = 0      # sum of active slots per verify
        # acceptance-collapse detector: rolling (proposed, accepted)
        # window; a sustained near-zero acceptance rate means the
        # workload stopped being lookup-friendly and every verify
        # forward is wasted width — ring-evented once per collapse,
        # re-armed on recovery
        self._spec_window: Deque[tuple] = deque(
            maxlen=self._SPEC_WINDOW_STEPS)
        self._spec_alarm = False
        # per-slot incremental lookup state (speculation.LookupIndex):
        # proposals cost O(1) per step instead of rescanning the whole
        # history; keyed by slot, identity-checked against the resident
        # SlotState so a recycled slot always rebuilds
        self._spec_hist: Dict[int, tuple] = {}
        # lifecycle host mirrors (stats without a snapshot round-trip),
        # keyed by finish reason + "preempted" (not a terminal state)
        self._lifecycle_counts = dict.fromkeys(
            ("cancelled", "deadline", "preempted", "shed", "failed"), 0)
        # chunked prefills in flight, FIFO; at most ONE chunk runs per
        # step() so a long prompt never stalls resident decoders
        self._prefilling: Deque[dict] = deque()
        self._mid_prefill: set = set()
        # ---- async dispatch loop (docs/serving.md "Async dispatch
        # loop"): every step picks its commit lag. At max_commit_lag,
        # that many decode programs chain device-side across step()
        # calls (each dispatched from the previous step's
        # device-resident tokens), committed FIFO; a step with a
        # host-driven state change to make runs at lag 0 and flushes
        # the whole chain first, so the scheduler only ever acts on
        # committed state. async_loop off = lag 0 in every step.
        self._async = cfg.async_loop
        self._max_lag = max(int(cfg.max_commit_lag), 1)
        self._inflight: Deque[InFlightStep] = deque()
        # chained chunked prefill (docs/serving.md "Async dispatch
        # loop"): dispatch ALL of the head prompt's non-final chunks as
        # one device-side chain per step instead of one chunk per step
        self._prefill_chain = cfg.prefill_chain and bool(self.chunk_tokens)
        # a lagged commit's metric publishing rides a worker thread
        # (drained at every flush / drain() / stats read); the thread
        # is lazy, so a server that only ever commits at lag 0
        # (async_loop off) never starts it
        self._worker = PublishWorker()
        # finishes discovered by an out-of-step flush (cancel/drain
        # between steps): returned by the NEXT step() call
        self._deferred_finished: List[int] = []
        # per-step publish records buffer locally and ship to the
        # worker in batches: a Queue.put + thread wakeup per step is a
        # measurable slice of a CPU decode step (and worse under core
        # contention — exactly when overlap matters); a tuple append
        # is not. Drained (buffer first, then worker) at every flush
        # point, so visibility is unchanged at every readable surface.
        self._pub_buf: List[tuple] = []
        # a chunk dispatched without its own fetch (the PR-10 satellite
        # removed the per-chunk host sync): earliest unrealized dispatch
        # time; its device span closes at the next real fetch
        self._chunk_pending_t0: Optional[float] = None
        self._async_stats = {
            "pipeline_starts": 0,    # dispatch-without-fetch entries
            "pipelined_steps": 0,    # lag-N commits (decode) / rounds (verify)
            "flushes": {},           # reason -> count
            # reason -> {chain depth at flush -> count}: which host
            # actions drain deep chains (satellite: flushes-by-reason
            # per depth)
            "flush_depths": {},
            "discarded_tokens": 0,   # in-flight garbage dropped at commit
            "garbage_steps": 0,      # in-flight steps with no survivor
        }
        self._init_flight_recorder(tcfg)

    # ------------------------------------------------------------ setup

    # decode-step ring events are SAMPLED (every Nth step + the first):
    # a TPU decode loop runs thousands of steps per second, and per-step
    # events would flush the compile/admission forensics out of the
    # bounded ring in seconds
    _EVENT_EVERY = 64

    # acceptance-collapse detector: over the last _SPEC_WINDOW_STEPS
    # verify steps (once at least _SPEC_MIN_PROPOSED proposals are in
    # the window), an acceptance rate below COLLAPSE fires one
    # spec_collapse ring event; the alarm re-arms above RECOVER
    _SPEC_WINDOW_STEPS = 64
    _SPEC_MIN_PROPOSED = 64
    _SPEC_COLLAPSE_RATE = 0.05
    _SPEC_RECOVER_RATE = 0.10

    # swap-thrash detector (host tiering): over the last
    # _SWAP_WINDOW_STEPS steps, a mean swap-in rate above
    # _KV_THRASH_SWAPS_PER_STEP fires one kv_swap_thrash ring event;
    # the alarm re-arms at or below _KV_THRASH_RECOVER
    _SWAP_WINDOW_STEPS = 32
    _KV_THRASH_SWAPS_PER_STEP = 0.5
    _KV_THRASH_RECOVER = 0.125

    def _init_flight_recorder(self, tcfg) -> None:
        """Arm the config-gated flight-recorder surfaces (see
        docs/observability.md "Flight recorder") via the shared
        telemetry helper. Components use a weak self-reference so a
        dropped (but not close()d) server never leaks its arrays
        through the process-wide monitor."""
        import weakref

        from deepspeed_tpu.telemetry.flight import arm_flight_recorder
        if (self._supervised and tcfg is not None
                and tcfg.watchdog_deadline_s is not None):
            # the supervising frontend's per-replica heartbeat watchdog
            # replaces the config-armed stall thread (it will be
            # installed on self.watchdog right after construction)
            tcfg = tcfg.model_copy(update={"watchdog_deadline_s": None})
        ref = weakref.ref(self)

        def _pool():
            srv = ref()
            if srv is None:
                return None
            # int8 pools carry their scale tiles in the same bucket —
            # the pool's HBM cost is payload + scales
            return pool_arrays(srv._cache)

        def _params():
            srv = ref()
            return None if srv is None else srv.engine.params

        # the pool and the weights are the serving process's two big
        # HBM residents
        self._flight = arm_flight_recorder(
            tcfg, self.telemetry, "serve_watchdog",
            [("kv_block_pool", _pool), ("params", _params)])
        self.watchdog = self._flight.watchdog
        if self.watchdog is not None and self.incidents is not None:
            # unify the stall-dump path with the incident recorder: a
            # watchdog dump is a forensic trigger like an alert firing
            # — same episode machinery, same once-per-episode limit
            self.watchdog.set_on_dump(self._on_watchdog_dump)

    def _goodput_snapshot(self) -> dict:
        """``GET /debug/goodput`` payload: the step observatory's phase
        totals + goodput fraction + dispatch-gap accounting beside the
        KV-pool lifetime/fragmentation view — one JSON answer to
        "where did the serving step go, and who holds the pool".

        Runs on the SCRAPE thread, so it reads only the accountant's
        own (lock-free but internally consistent) totals — it must
        never walk live allocator structures the serving loop is
        mutating (``free_ids`` iterates ``_free_set``; a concurrent
        ``allocate`` would raise mid-scrape) and must stay valid
        before ``__init__`` finishes (the listener opens a few lines
        before the scheduler exists). The fragmentation value is the
        last computed one — at most ``FRAG_EVERY`` transitions stale;
        :attr:`stats` (owner thread) refreshes it exactly."""
        astats = getattr(self, "_async_stats", None)
        return {
            "step_profile": (self._profiler.snapshot()
                             if self._profiler is not None
                             else {"enabled": False}),
            "kv_pool": (self._pool_acct.snapshot()
                        if self._pool_acct is not None
                        else {"enabled": False}),
            # lag-N chain forensics beside the profiler's depth
            # histogram: which host actions drain chains, and how deep
            # the chain was when they did (plain dict reads — safe on
            # the scrape thread)
            "async_loop": ({
                "max_commit_lag": self._max_lag,
                "flushes": dict(astats["flushes"]),
                "flush_depths": {
                    reason: {str(d): n
                             for d, n in sorted(depths.items())}
                    for reason, depths in sorted(
                        astats["flush_depths"].items())},
            } if astats is not None else {"enabled": False}),
        }

    def _capacity_levels(self):
        """CapacityModel ``levels`` callable: ``(active_slots,
        num_slots, free_blocks, usable_blocks)``. getattr-guarded for
        the window between the HTTP listener opening and ``__init__``
        building the scheduler — a scrape landing there reads an empty
        server, not an AttributeError."""
        sched = getattr(self, "scheduler", None)
        if sched is None:
            return (0, self.num_slots, 0, 0)
        alloc = sched.allocator
        return (sched.active_slots, self.num_slots,
                alloc.free_blocks, alloc.usable_blocks)

    def _capacity_goodput(self) -> Optional[float]:
        """CapacityModel ``goodput`` callable: lifetime device/wall
        fraction from the step observatory (None before any step —
        the model reports the field as null rather than inventing 1.0
        efficiency for an idle server)."""
        p = self._profiler
        if p is None:
            return None
        snap = p.snapshot()
        return snap.get("goodput_fraction")

    def capacity_snapshot(self) -> dict:
        """``GET /debug/capacity`` payload (and ``stats["capacity"]``):
        the live capacity model's latest row — windowed throughput,
        occupancy levels, goodput-derived sustainable token rate, and
        the admissible request rate at the current traffic mix. A
        supervising frontend calls this per replica and rolls the rows
        up with :func:`rollup_capacity`. Report-only: nothing in
        admission or scheduling reads it."""
        if self._capacity is None:
            return {"enabled": False,
                    "hint": "accounting disabled "
                            "(telemetry.accounting.enabled / "
                            "telemetry.step_profile)"}
        return self._capacity.snapshot()

    # ------------------------------- alerting / canary / incidents

    def _on_alert_fire(self, rule: str, info: dict) -> None:
        """AlertEngine ``on_fire`` hook: a rule entering firing is the
        incident recorder's capture trigger (rate-limited to one bundle
        per episode; a second rule joining the storm attaches)."""
        if self.incidents is not None:
            self.incidents.capture("alert", rule=rule, info=info)

    def _on_alert_resolve(self, rule: str, info: dict) -> None:
        """AlertEngine ``on_resolve`` hook: closes the open episode once
        every joined rule resolved (appending the post-recovery
        snapshot) and re-arms capture for the next incident."""
        if self.incidents is not None:
            self.incidents.resolve(rule, info=info)

    def _on_watchdog_dump(self, dump: dict) -> None:
        """Watchdog ``on_dump`` hook — the unified stall-forensics
        trigger (the bulky thread stacks stay in the watchdog's own
        dump; the bundle carries the stall coordinates)."""
        if self.incidents is not None:
            self.incidents.capture(
                "watchdog",
                info={"watchdog": dump.get("watchdog"),
                      "idle_seconds": dump.get("idle_seconds")})

    def _incident_collect(self) -> dict:
        """The incident bundle's body for a bare server (the frontend
        supplies its own pool-wide collect). Scrape-thread-safe on
        purpose — the watchdog trigger runs on the checker thread, so
        everything here reads lock-guarded telemetry structures, never
        live scheduler internals."""
        ring = get_event_ring()
        return {
            "observability": self.observability_state(),
            "events": ring.snapshot(),
            "capacity": self.capacity_snapshot(),
            "alerts": (self.alerts.snapshot()
                       if self.alerts is not None else None),
            "canary": (self.canary.snapshot()
                       if self.canary is not None else None),
        }

    def incidents_snapshot(self) -> dict:
        """``GET /debug/incidents`` payload (and ``stats["incidents"]``):
        the live alert/canary state beside the retained bundles."""
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

    def dump_incident(self, path: str) -> dict:
        """On-demand forensic bundle to ``path`` — the operator's
        manual pull of exactly what an alert-fire capture would have
        grabbed (never rate-limited). Requires ``telemetry.incident``
        to be armed."""
        if self.incidents is None:
            raise RuntimeError(
                "incident capture is off — set telemetry.incident."
                "enabled (docs/observability.md 'SLOs, alerting & "
                "incidents')")
        return self.incidents.dump(path)

    # ------------------------------------------------- cost accounting

    def request_cost(self, request_id: int) -> Optional[dict]:
        """The closed cost record for a finished request (docs/
        observability.md "Cost accounting & capacity"): device-seconds,
        KV block-seconds, queue wait, swap/handoff bytes, speculation
        counts, token totals. None when accounting is off or the id is
        unknown/still running. Non-destructive — the record stays until
        ``forget``/``pop_request_cost`` drops it."""
        if self._ledger is None:
            return None
        return self._ledger.cost(request_id)

    def pop_request_cost(self, request_id: int) -> Optional[dict]:
        """Harvest-and-drop a finished request's cost record — the
        frontend's per-leg collection path (each replica leg becomes
        one entry in the merged bill)."""
        if self._ledger is None:
            return None
        return self._ledger.pop_cost(request_id)

    def abandon_cost(self, request_id: int) -> Optional[dict]:
        """Force-close and harvest the cost record of a request this
        server will never finish — the supervising frontend declared
        the replica dead mid-flight and is failing the request over.
        The leg's charges so far still bill; recompute on the new
        replica charges there (the device really runs it twice)."""
        if self._ledger is None:
            return None
        self._ledger.abandon(request_id)
        return self._ledger.pop_cost(request_id)

    def observability_state(self) -> dict:
        """One replica's complete observability export: registry state
        (``MetricRegistry.export_state`` — the mergeable accumulator
        form), kept traces as serialized dicts, and the step
        observatory's goodput/dispatch-gap view. This is the fleet
        plane's ONLY read path into a replica — pure builtins, JSON
        round-trippable, and scrape-thread-safe (every piece reads
        lock-guarded telemetry structures, never scheduler internals),
        so ROADMAP item 1's process transport ships it verbatim."""
        prof = (self._profiler.snapshot() if self._profiler is not None
                else {"enabled": False})
        return {
            "role": self.role,
            "metrics": self.telemetry.export_state(),
            "traces": ([t.to_dict() for t in self.tracer.traces()]
                       if self.tracer is not None else []),
            "tracing": self.tracer is not None,
            "goodput_fraction": prof.get("goodput_fraction"),
            "recent_gap_s": (self._profiler.recent_gap_s()
                             if self._profiler is not None else None),
        }

    def _pool_snapshot(self) -> dict:
        """Fresh pool-accounting view for :attr:`stats` (OWNER-thread
        callers only — between steps, never from the scrape thread):
        the fragmentation scan on the transition path is rate-limited,
        so this recomputes it exactly (O(free log free), read
        cadence)."""
        self._pool_acct.update_fragmentation(
            self.scheduler.allocator.free_ids)
        return self._pool_acct.snapshot()

    @staticmethod
    def _prefill_fn(params, ids, length, cache, slot, *, cfg, mesh):
        logits, cache = paged_prefill(params, cfg, ids, length, cache,
                                      slot, mesh=mesh)
        return _sample(logits), cache

    @staticmethod
    def _decode_fn(params, tokens, cache, active, *, cfg, mesh):
        logits, cache = paged_decode_step(params, cfg, tokens, cache,
                                          active, mesh=mesh)
        return _sample(logits), cache

    @staticmethod
    def _decode_admit_fn(params, tokens, cache, active, ids, length, slot,
                         *, cfg, mesh):
        logits, cache = model_family(cfg).paged_decode_admit(
            params, cfg, tokens, cache, active, ids, length, slot,
            mesh=mesh)
        return _sample(logits), cache

    @staticmethod
    def _chunk_fn(params, ids, start, length, cache, slot, *, cfg, mesh):
        logits, cache = paged_prefill_chunk(params, cfg, ids, start,
                                            length, cache, slot,
                                            mesh=mesh)
        return _sample(logits), cache

    @staticmethod
    def _verify_fn(params, tokens, cache, *, cfg, mesh):
        logits, cache = paged_verify_step(params, cfg, tokens, cache,
                                          mesh=mesh)
        return _sample(logits), cache

    def _make_kv_pool(self, num_blocks: int) -> PagedKVCache:
        mcfg = self.engine.model_config
        cache = init_paged_cache(
            mcfg.n_layer, self.num_slots, num_blocks, self.block_size,
            self.max_blocks_per_slot, mcfg.kv_heads, mcfg.head_dim,
            dtype=self.engine._act_dtype,
            quantized=self.kv_dtype == "int8",
            window_layers=getattr(mcfg, "window_layers", None),
            window=getattr(mcfg, "sliding_window", 0) or 0,
            aux_shape=getattr(mcfg, "aux_shape", None),
            state_layers=getattr(mcfg, "state_layers", None),
            state_shapes=getattr(mcfg, "state_shapes", None),
            state_dtype=getattr(mcfg, "state_dtype", jnp.float32),
            v_head_dim=getattr(mcfg, "v_head_dim", None),
            ring_kv_heads=getattr(mcfg, "ring_kv_heads", None),
            cacheless_layers=getattr(mcfg, "cacheless_layers", None))
        if cache.state is not None:
            self.telemetry.gauge(
                "serve_kv_state_bytes",
                help="bytes of the state layers' states and convolution "
                     "tails (every slot): a fixed cost a slot, no block of "
                     "the pool"
            ).set(sum(a.nbytes for a in cache.state + cache.conv))
        if cache.ring_k is not None:
            self.telemetry.gauge(
                "serve_kv_ring_bytes",
                help="bytes of the window layers' rings (K and V, every "
                     "slot): a fixed cost a slot, no block of the pool"
            ).set(cache.ring_k.nbytes + cache.ring_v.nbytes)
        mesh = self.engine.mesh
        if mesh is not None:
            # kv heads shard over `tensor` exactly like the dense cache
            # (engine._make_cache): they are the major part of the
            # pool's [L, NB, BS, KH*D] lane dim. The block dim stays
            # replicated — every device owns the whole table, its heads
            # of every block
            sh = NamedSharding(mesh, P(None, None, None, "tensor"))
            cache = cache.replace(
                k=jax.device_put(cache.k, sh),
                v=jax.device_put(cache.v, sh))
            if cache.k_scale is not None:
                # scale tiles [L, NB, KH, BS]: head dim follows the pool
                ssh = NamedSharding(mesh, P(None, None, "tensor", None))
                cache = cache.replace(
                    k_scale=jax.device_put(cache.k_scale, ssh),
                    v_scale=jax.device_put(cache.v_scale, ssh))
        return cache

    def _make_latent_pool(self, num_blocks: int):
        # one buffer per attention sub-block: no program cuts a layer's
        # rows out of a stacked pool
        mcfg = self.engine.model_config
        return init_latent_paged_cache(
            mcfg.attentions, self.num_slots, num_blocks, self.block_size,
            self.max_blocks_per_slot, mcfg.latent_width,
            aux_shape=mcfg.aux_shape, dtype=self.engine._act_dtype)

    def _make_state_pool(self, num_blocks: int):
        # one buffer a layer for S and for z, slot-major: a prefill
        # writes one slot of a donated buffer in place. No blocks: the
        # scheduler's block budget is positions only (it never binds)
        mcfg = self.engine.model_config
        return init_recurrent_state_cache(
            mcfg.n_layer, self.num_slots, *mcfg.state_shapes,
            aux_shape=mcfg.aux_shape, dtype=mcfg.state_dtype)

    def _make_draft_pool(self, num_blocks: int) -> PagedKVCache:
        """Draft-model pool: the target pool's geometry (slots, blocks,
        block size) with the draft model's layer/head dims, so the
        target's block tables index it directly. Always fp storage —
        the draft is small by design, and int8 would buy little."""
        dcfg = self.draft.model_config
        cache = init_paged_cache(
            dcfg.n_layer, self.num_slots, num_blocks, self.block_size,
            self.max_blocks_per_slot, dcfg.kv_heads, dcfg.head_dim,
            dtype=self.draft._act_dtype, quantized=False)
        mesh = self.draft.mesh
        if mesh is not None:
            sh = NamedSharding(mesh, P(None, None, None, "tensor"))
            cache = cache.replace(k=jax.device_put(cache.k, sh),
                                  v=jax.device_put(cache.v, sh))
        return cache

    # -------------------------------------------------- host-tier copies

    def _demote_block(self, block: int, h: bytes) -> None:
        """Allocator demotion callback: copy one parked block's payload
        device→host (durable on return — ``np.asarray`` completes the
        fetch) and park it in the tier under its chain hash. Runs only
        inside admission-time allocation, which the step loop only
        reaches with no step in flight, so the read can never see a
        donated buffer."""
        t0 = self._clock()
        self.host_tier.put(h, paged_read_block(self._cache, block))
        if self._pool_acct is not None:
            self._pool_acct.observe_swap("out", self._clock() - t0,
                                         len(self.host_tier))

    def _swap_in_block(self, block: int, payload: dict) -> None:
        """Allocator swap-in callback: write the (already tier-popped —
        the allocator reserves it before its staging allocation can
        displace it) host payload back into a freshly allocated device
        block through the jitted, donated staging scatter (one
        executable per pool geometry — the block id is traced data).
        The dispatch is async; the decode program that next reads the
        block chains behind it."""
        t0 = self._clock()
        self._cache = paged_swap_in(self._cache, block, payload)
        if self._pool_acct is not None:
            self._pool_acct.observe_swap("in", self._clock() - t0,
                                         len(self.host_tier))

    def _check_swap_thrash(self) -> None:
        """Ring-event a swap-in storm ONCE per episode: over the rolling
        window, a sustained swap-in rate above the threshold means
        blocks are cycling device<->host faster than they serve — the
        device pool is undersized for the live working set and each
        admission is paying tier copies instead of cache hits. Re-arms
        after the rate recovers (same episode discipline as the
        speculation-collapse detector)."""
        if self.host_tier is None or self._handoff_import:
            # a handoff-importing replica swaps in BY DESIGN (one
            # handoff per routed request — that is traffic, not
            # thrash), and with kv_host_offload armed beside roles the
            # two streams share one allocator counter the detector
            # cannot tell apart: it stands down rather than latching a
            # false alarm on a healthy disaggregated pool
            return
        swaps = self.scheduler.allocator.swap_ins
        self._swap_window.append(swaps - self._swap_seen)
        self._swap_seen = swaps
        if len(self._swap_window) < self._SWAP_WINDOW_STEPS:
            return
        rate = sum(self._swap_window) / len(self._swap_window)
        if not self._swap_alarm and rate > self._KV_THRASH_SWAPS_PER_STEP:
            self._swap_alarm = True
            get_event_ring().record(
                telemetry_events.KV_SWAP_THRASH,
                swap_ins_per_step=round(rate, 4),
                window_steps=len(self._swap_window),
                host_blocks=len(self.host_tier),
                free_blocks=self.scheduler.allocator.free_blocks)
        elif self._swap_alarm and rate <= self._KV_THRASH_RECOVER:
            self._swap_alarm = False

    # ----------------------------------------------- prefill/decode handoff

    def export_prefix(self, hashes, on_block=None):
        """Read the payloads of the consecutively-registered prefix
        blocks under ``hashes`` (chain order): ``[(hash, payload),
        ...]``, stopping at the first unregistered hash — a deeper
        block is only valid under its whole chain. Each payload is one
        :func:`~deepspeed_tpu.inference.kv_cache.paged_read_block`
        result (k/v slabs + int8 scale tiles, all layers, host-durable
        numpy on return). The disaggregating frontend calls this right
        after a prefill-only request finishes: the blocks were
        registered by ``commit_prefix`` at the final chunk and parked
        in the LRU at retirement, content intact — and the read
        targets ``self._cache``, which chains after any in-flight
        dispatch, so it can never observe a donated buffer.
        ``on_block(index, total)`` is the chaos seam (it may raise —
        the mid-publish replica-kill injection)."""
        alloc = self.scheduler.allocator
        out = []
        total = len(hashes)
        for i, h in enumerate(hashes):
            b = alloc.lookup_prefix(h)
            if b is None:
                break
            if on_block is not None:
                on_block(i, total)
            out.append((h, paged_read_block(self._cache, b)))
        return out

    def import_prefix(self, entries) -> int:
        """Park handoff payloads in this replica's host tier so the
        next admission's ``match_prefix`` walk swaps them in (one
        jitted donated scatter per block — zero new executables).
        Hashes already warm here — device-registered, or already
        host-resident — are skipped: a hash must never be BOTH
        device-registered and host-resident (the register_prefix
        invariant), and the warmer copy wins anyway. Returns how many
        payloads were parked."""
        if self.host_tier is None:
            return 0
        alloc = self.scheduler.allocator
        n = 0
        for h, payload in entries:
            if alloc.lookup_prefix(h) is not None or self.host_tier.has(h):
                continue
            self.host_tier.put(h, payload)
            n += 1
        return n

    def purge_import(self, hashes) -> int:
        """Drop still-parked host-tier payloads under ``hashes`` — the
        frontend calls this when a request whose handoff it imported
        here reaches a TERMINAL finish without ever being admitted
        (cancelled / deadline-expired / failed while queued): nothing
        else would ever consume the entries, and an import-only tier
        is unbounded — without the purge they leak host RAM for the
        server's lifetime. Hashes already swapped in (gone from the
        tier) or re-registered device-side are no-ops; tier content is
        always recomputable, so an over-eager purge can only cost a
        recompute, never correctness. Returns how many were dropped."""
        if self.host_tier is None:
            return 0
        return sum(1 for h in hashes if self.host_tier.discard(h))

    # ------------------------------------------------------------ API

    def submit(self, prompt: List[int], max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               request_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               priority: int = 0,
               trace_context: Optional[dict] = None,
               tenant: Optional[str] = None) -> int:
        """Queue one request; returns its id. Raises when the request can
        never be scheduled (block span beyond a slot) or the queue is
        full — admission control instead of a silent deadlock.

        ``tenant`` labels the request for per-tenant metering (docs/
        observability.md "Cost accounting & capacity"): tokens, device
        seconds, requests, and rejections accumulate under a bounded
        label set (``telemetry.accounting.max_tenants``; overflow folds
        to ``tenant="other"``). ``None`` — the default — is unmetered
        and creates no series; scheduling NEVER reads the tenant.

        ``deadline_s`` bounds the request's WHOLE lifetime (queue wait
        included) on the server clock: an expired request is reaped with
        finish reason ``deadline`` — dequeued if still waiting, retired
        mid-prefill/decode with its partial output if resident — and is
        never admitted past its deadline. ``priority`` (higher wins)
        orders preemption and shedding victims; FIFO breaks ties.

        ``trace_context`` is the fleet-tracing link-back (docs/
        observability.md "Fleet observability"): a JSON-able dict of
        caller trace coordinates (``trace_id``/``hop``/``cause``) the
        frontend propagates per leg; it lands as ``link_*`` attributes
        on this replica's trace root, so a replica-side tree names the
        stitched frontend tree it belongs to even once replicas are
        separate processes."""
        floor = max(1, self.engine.config.min_out_tokens)
        rej = submit_rejection(prompt, max_new_tokens, floor, deadline_s)
        if rej is not None:
            self._count_rejection(rej[0], request_id, tenant=tenant)
            raise ValueError(rej[1])
        if request_id is None:
            request_id = self._next_id
        elif (request_id in self._results
              or any(s.request.request_id == request_id
                     for s in self.scheduler.slots.values())
              or any(r.request_id == request_id
                     for r in self.scheduler.queue)):
            self._count_rejection("duplicate_id", request_id,
                                  tenant=tenant)
            raise ValueError(
                f"request_id {request_id} is already queued, resident, "
                "or finished — a duplicate would silently overwrite its "
                "output")
        self._next_id = max(self._next_id, request_id) + 1
        now = self._clock()
        deadline_ts = None if deadline_s is None else now + deadline_s
        try:
            self.scheduler.submit(Request(
                request_id=request_id, prompt=list(prompt),
                max_new_tokens=max_new_tokens, eos_token_id=eos_token_id,
                priority=priority, deadline_ts=deadline_ts,
                tenant=tenant))
        except Exception:
            # scheduler-side refusals (span/pool/queue_full) count into
            # the same per-tenant rejection series as server-side ones
            if self._ledger is not None:
                self._ledger.tenants.count_rejection(tenant)
            raise
        if self._ledger is not None:
            self._ledger.open(request_id, tokens_in=len(prompt),
                              tenant=tenant)
        self._submit_ts[request_id] = now
        self._queued_ts[request_id] = now
        if self._profiler is not None:
            # request lifecycle in the span log: [span id, submit,
            # start of the open phase, the open phase's span name]
            self._req_spans[request_id] = [
                self._profiler.span_log.next_id(), now, now, QUEUE_SPAN]
        if deadline_ts is not None:
            self._deadlines[request_id] = deadline_ts
        if self.tracer is not None:
            # root span opens NOW (submit is the request's birth); the
            # queue_wait child stays open until admission into a slot
            tr = self.tracer.start_trace(
                "request", trace_id=request_id,
                prompt_tokens=len(prompt),
                max_new_tokens=max_new_tokens)
            if priority:
                tr.root.set("priority", priority)
            if deadline_s is not None:
                tr.root.set("deadline_s", deadline_s)
            if trace_context:
                for k, v in trace_context.items():
                    tr.root.set(f"link_{k}", v)
            rt = _RequestTrace(tr)
            rt.queue = tr.begin("queue_wait")
            self._rt[request_id] = rt
        self._c_submitted.inc()
        if self._fi is not None:
            self._fi.on_submit(request_id)
        return request_id

    def _request_phase(self, rid: int, now: float, opens: str,
                       attrs: Optional[dict] = None) -> None:
        """Close the request's open lifecycle phase at ``now`` (one
        ``serve:queue_wait`` / ``serve:prefill`` / ``serve:decode``
        record in the span log, from stamps the loop reads anyway) and
        open ``opens`` there, so the phases tile ``serve:request``."""
        st = self._req_spans.get(rid)
        if st is None:
            return
        self._profiler.span_log.record(st[3], st[2], now, parent=st[0],
                                       key=rid, attrs=attrs)
        st[2], st[3] = now, opens

    def _request_done(self, req: Request, now: float, tokens_out: int,
                      reason: str) -> None:
        st = self._req_spans.pop(req.request_id, None)
        if st is None:
            return
        log = self._profiler.span_log
        log.record(st[3], st[2], now, parent=st[0], key=req.request_id)
        log.record(REQUEST_SPAN, st[1], now, key=req.request_id,
                   span_id=st[0], attrs={
                       "prompt_tokens": len(req.prompt),
                       "output_tokens": tokens_out,
                       "finish_reason": reason,
                       "preemptions": req.preemptions})

    def _count_rejection(self, reason: str,
                         request_id: Optional[int] = None,
                         tenant: Optional[str] = None) -> None:
        """Server-side refusals; the scheduler counts its own (span/pool/
        queue_full) into the same family — one admission-failure metric."""
        self.telemetry.counter(
            "serve_admission_rejections_total",
            help="refused submit() calls, by reason",
            labels={"reason": reason}).inc()
        if self._ledger is not None:
            self._ledger.tenants.count_rejection(tenant)
        get_event_ring().record(telemetry_events.ADMISSION_REJECT,
                                reason=reason, source="server")
        if self.tracer is not None:
            # rejected requests are ALWAYS kept — the traces an operator
            # wants never lose the sampling coin flip. The request id
            # (when the caller supplied one) rides as an attribute, same
            # as the scheduler's rejection traces, so the operator can
            # tie the refusal back to client logs.
            attrs = {} if request_id is None else {"request_id": request_id}
            self.tracer.record_rejected("request", reason, **attrs)

    # ------------------------------------------------- lifecycle actions

    def _reset_slot_arrays(self, slot: int) -> None:
        """Host-side device-array reset for a vacated slot: length 0 and
        an all-null block table, so interleaved decode appends land in
        the null block until the next admission repopulates the row."""
        self._cache = self._cache.replace(
            lengths=_set_row(self._cache.lengths, slot, 0))
        self._set_block_row(slot, ())
        if self._draft_cache is not None:
            # the draft pool mirrors the target's tables at each use; a
            # vacated slot only needs its length zeroed so stale draft
            # KV can never be read as live context
            self._draft_cache = self._draft_cache.replace(
                lengths=_set_row(self._draft_cache.lengths, slot, 0))
        # every slot-vacating path (retire / cancel / preempt / fault)
        # runs through here — drop its lookup state with it
        self._spec_hist.pop(slot, None)

    def _set_block_row(self, slot: int, blocks) -> None:
        """``slot``'s row of the device block table: ``blocks`` first,
        the null block after. A pool without blocks (a recurrent state)
        has no table and nothing to write."""
        if not self._pool_kind.block_tables:
            return
        row = np.zeros((self.max_blocks_per_slot,), np.int32)
        row[:len(blocks)] = blocks
        self._cache = self._cache.replace(
            block_tables=_set_row(self._cache.block_tables, slot, row))

    def _drop_prefill_job(self, slot: int) -> None:
        """Forget any in-flight chunked prefill for a vacated slot."""
        if slot in self._mid_prefill:
            if (self._chunk_pending_t0 is not None and self._prefilling
                    and self._prefilling[0]["slot"] == slot):
                # the dropped slot owns the deferred chunk dispatch
                # (only the head job runs chunks): rebalance the
                # profiler's outstanding pairing NOW — leaving it would
                # force 0-gaps on every later dispatch and let the next
                # realize credit idle wall as device time. No span is
                # credited (conservative: the chunk did run, but its
                # fetch boundary is unobservable once the slot dies).
                if self._profiler is not None:
                    self._profiler.note_fetch(self._clock())
                self._chunk_pending_t0 = None
            self._mid_prefill.discard(slot)
            self._prefilling = deque(
                j for j in self._prefilling if j["slot"] != slot)

    def _teardown_slot(self, slot: int) -> None:
        """Vacate a resident slot mid-flight (cancel / injected prefill
        fault / retries-exhausted preemption): drop any in-flight chunk
        job, release the blocks through the refcount path, scrub the
        device-side slot state — in that order (the chunk job reads the
        block table; the array reset assumes the slot is off the
        scheduler's books)."""
        if self._ledger is not None:
            state = self.scheduler.slots.get(slot)
            if state is not None:
                self._ledger.close_residency(state.request.request_id)
        self._drop_prefill_job(slot)
        self.scheduler.release(slot)
        self._reset_slot_arrays(slot)

    def _finalize(self, req: Request, tokens: List[int], reason: str,
                  finished: Optional[list] = None) -> None:
        """Terminal lifecycle bookkeeping shared by cancel / deadline /
        shed / fail: record the (possibly partial) output + finish
        reason, tick the reason's counter and ring event, close the
        trace (always kept — a non-ok status never loses the sampling
        coin flip), and feed the watchdog (a server busy degrading is
        making progress, not hanging)."""
        rid = req.request_id
        self._results[rid] = tokens
        self.finish_reasons[rid] = reason
        if finished is not None:
            finished.append(rid)
        self._submit_ts.pop(rid, None)
        self._queued_ts.pop(rid, None)
        self._deadlines.pop(rid, None)
        self._request_done(req, self._clock(),
                           max(len(tokens) - len(req.prompt), 0), reason)
        if self._ledger is not None:
            # closes the record (and any still-open KV residency); the
            # finishing step's own device share still lands on it via
            # the pending-close window before it emits
            self._ledger.finish(
                rid, tokens_out=max(len(tokens) - len(req.prompt), 0),
                reason=reason)
        if self._pool_acct is not None:
            # high-water pool blocks across the request's residencies
            # (zero = never admitted; skipped inside the accountant)
            self._pool_acct.observe_request_peak(req.peak_blocks)
        self._c_finish[reason].inc()
        self._lifecycle_counts[reason] += 1
        get_event_ring().record(
            _LIFECYCLE_EVENTS[reason], request_id=rid,
            generated=len(tokens) - len(req.prompt),
            preemptions=req.preemptions)
        rt = (self._rt.pop(rid, None) if self.tracer is not None
              else None)
        if rt is not None:
            for sp in (rt.queue, rt.prefill, rt.decode):
                if sp is not None and sp.end is None:
                    rt.trace.end_span(sp)
            rt.trace.root.set("finish_reason", reason)
            rt.trace.root.set("generated_tokens",
                              len(tokens) - len(req.prompt))
            self.tracer.finish(rt.trace, status=reason)
        if self.watchdog is not None:
            self.watchdog.notify_progress()

    def cancel(self, request_id: int, reason: str = "cancelled") -> bool:
        """Cancel one request in ANY state: queued (dequeued, prompt
        returned as the partial result), mid-prefill or decoding (slot
        retired, blocks released through the refcount path, prompt +
        tokens-so-far returned). Returns False when the request is
        already finished or unknown. ``reason`` lands in
        ``finish_reasons`` ("cancelled" from callers, "deadline" from
        the reaper)."""
        if reason not in ("cancelled", "deadline"):
            raise ValueError(
                f"cancel reason must be 'cancelled' or 'deadline', "
                f"got {reason!r}")
        if request_id in self._results:
            return False
        req = self.scheduler.remove_queued(request_id)
        if req is not None:
            self._finalize(req, list(req.prompt) + list(req.committed),
                           reason)
            return True
        slot = self.scheduler.find_slot(request_id)
        if slot is None:
            return False
        if self._inflight:
            # cancel takes effect at the COMMITTED boundary the caller
            # observed: the target's in-flight tokens (the whole chain's
            # worth) are discarded (its slot arrays are about to be
            # reset anyway), everyone else's commit normally — no other
            # request loses a token to this cancellation. Collateral
            # finishes surface on the next step() (or via
            # results/finish_reasons immediately).
            self._flush_pipeline(self._deferred_finished,
                                 reason="cancel",
                                 discard_rid=request_id)
        state = self.scheduler.slots[slot]
        self._teardown_slot(slot)
        self._finalize(state.request,
                       list(state.request.prompt) + list(state.generated),
                       reason)
        return True

    def reclaim(self, request_id: int) -> Optional[List[int]]:
        """Take an UNFINISHED request away from this server without
        leaving a terminal record: cancel it (blocks release through
        the normal refcount path), then forget its result and finish
        reason so the SAME id can be resubmitted here later. The
        supervising frontend's rolling-drain re-route uses this — a
        plain ``cancel()`` would leave a ``cancelled`` entry that the
        duplicate-id guard treats as "already finished", blocking the
        id's return after the replica re-admits. Returns the partial
        output (prompt + committed tokens) the caller resubmits from,
        or None when the request is unknown or already finished (a
        finished request is a result, not reclaimable work). The
        cancellation still counts on this server's lifecycle books —
        from the replica's view it IS one; the supervisor's own
        accounting tells the re-route story."""
        if request_id in self._results:
            return None
        if not self.cancel(request_id):
            return None
        out = self._results.pop(request_id)
        self.finish_reasons.pop(request_id, None)
        # the cost record stays harvestable (pop_request_cost) — the
        # reclaiming frontend folds it into the request's merged bill
        return out

    def forget(self, request_id: int) -> None:
        """Drop a FINISHED request's terminal record so the same id is
        resubmittable HERE again. The disaggregating frontend calls
        this after collecting a prefill-only leg's finish: the id is
        about to resubmit for its decode leg, and on a role-degraded
        pool (every decode replica dead) the last-resort target can be
        this very server — whose duplicate-id guard would otherwise
        refuse the id it just served (the ``reclaim()`` forget step,
        for work that FINISHED its leg instead of being taken away)."""
        self._results.pop(request_id, None)
        self.finish_reasons.pop(request_id, None)
        if self._ledger is not None:
            # harvest-or-drop the leg's cost record too: a frontend
            # pops it BEFORE forgetting; anything left would shadow the
            # id's next leg on this server
            self._ledger.pop_cost(request_id)

    def _fail_request(self, req: Request, tokens: List[int],
                      error: str, finished: Optional[list]) -> None:
        """Server-side failure (injected prefill fault / preemption
        retries exhausted): finish reason ``failed`` + an always-kept
        error trace naming the cause."""
        rt = (self._rt.get(req.request_id)
              if self.tracer is not None else None)
        if rt is not None:
            rt.trace.root.set("error", error)
        self._finalize(req, tokens, "failed", finished)

    def _injected_prefill_fault(self, slot: int, state,
                                finished: list,
                                seeded: bool = True) -> bool:
        """Fault-injection prefill site, shared by the monolithic and
        chunked paths: when the injector kills this request's prefill,
        tear the slot down (drop the chunk job, release blocks, scrub
        device arrays) and fail the request. True = caller skips the
        prefill. ``seeded=False`` = targeted arms only (non-first
        chunks — the seeded coin is per REQUEST, not per chunk)."""
        if self._fi is None:
            return False
        req = state.request
        try:
            self._fi.check_prefill(req.request_id, seeded=seeded)
        except PrefillFault as e:
            self._teardown_slot(slot)
            self._fail_request(
                req, list(req.prompt) + list(state.generated),
                str(e), finished)
            return True
        return False

    def _reap_deadlines(self, finished: list) -> None:
        """Retire every request whose deadline passed — queued or
        resident — with finish reason ``deadline``. O(requests that HAVE
        deadlines); free when the feature is unused."""
        if not self._deadlines:
            return
        now = self._clock()
        expired = [rid for rid, ts in self._deadlines.items()
                   if now >= ts]
        for rid in expired:
            if self.cancel(rid, reason="deadline"):
                finished.append(rid)
            else:
                self._deadlines.pop(rid, None)

    def _maybe_shed(self, finished: list) -> None:
        """SLO-driven load shedding: while the queue-wait p90 objective
        is in violation, fast-fail the lowest-priority newest queued
        requests down to a floor of ``num_slots`` waiters — the queue
        stops growing faster than the machine drains it, so accepted
        requests keep meeting the objective instead of everyone
        missing it."""
        if not self._shedding or self.slo is None:
            return
        # refresh the verdict (rate-limited by eval_interval_s) and act
        # only on LIVE in-window evidence: a held verdict (no_data — the
        # window emptied while traffic paused) keeps the SLO red for
        # reporting but must not fast-fail a fresh burst whose queue
        # wait is ~0
        self.slo.maybe_evaluate()
        res = self.slo.last_results.get("queue_wait_p90")
        if not res or not res["violated"] or res.get("no_data"):
            return
        # live-pressure guard: the verdict can be stale (held across a
        # traffic pause, or a window baseline that predates an idle
        # gap) — only shed while some waiter has ACTUALLY aged past
        # the target since it last entered the queue (requeue time for
        # preempted work, not birth time — a once-preempted old request
        # must not keep the guard permanently satisfied); a fresh burst
        # with ~0 wait is never the victim of an old breach
        now = self._clock()
        target = self.slo.targets["queue_wait_p90"]
        if not any(now - self._queued_ts.get(r.request_id, now) > target
                   for r in self.scheduler.queue):
            return
        while self.scheduler.pending_requests > self.num_slots:
            victim = min(
                enumerate(self.scheduler.queue),
                key=lambda iv: (iv[1].priority, -iv[0]))[1]
            self.scheduler.remove_queued(victim.request_id)
            self._finalize(victim,
                           list(victim.prompt) + list(victim.committed),
                           "shed", finished)

    def _preempt_slot(self, slot: int, finished: list) -> None:
        """Preempt one resident (recompute-requeue), or fail it when its
        retry budget is spent."""
        state = self.scheduler.slots[slot]
        req = state.request
        if req.preemptions >= self.max_preemptions:
            # bounded retries: the pool keeps evicting this request —
            # failing it loudly (kept error trace) beats an unbounded
            # preempt/requeue livelock
            self._teardown_slot(slot)
            self._fail_request(
                req, list(req.prompt) + list(state.generated),
                f"preempted {req.preemptions}x (max_preemptions)",
                finished)
            return
        mid = slot in self._mid_prefill
        self._drop_prefill_job(slot)
        rt = (self._rt.get(req.request_id)
              if self.tracer is not None else None)
        if rt is not None:
            if rt.decode is not None:
                rt.decode.set("tokens_committed", rt.tokens)
                rt.decode.set("steps", rt.steps)
                rt.trace.end_span(rt.decode)
                rt.decode = None
            if rt.prefill is not None and rt.prefill.end is None:
                rt.prefill.set("preempted", True)
                rt.trace.end_span(rt.prefill)
            rt.prefill = None
        if self._ledger is not None:
            # residency pauses while the request waits off-pool; the
            # record stays OPEN — re-admission reopens it, and the
            # recompute prefill is charged like any other work (the
            # device really ran it)
            self._ledger.close_residency(req.request_id)
        self.scheduler.preempt(slot, self._tick,
                               self._backoff_steps,
                               register_extension=not mid)
        # requeue moment: the shed guard measures wait from HERE, not
        # from the original submit
        t_requeue = self._clock()
        self._queued_ts[req.request_id] = t_requeue
        self._request_phase(req.request_id, t_requeue, QUEUE_SPAN,
                            {"preempted": True})
        self._reset_slot_arrays(slot)
        self._c_preempted.inc()
        self._lifecycle_counts["preempted"] += 1
        get_event_ring().record(
            telemetry_events.PREEMPT, request_id=req.request_id,
            slot=slot, preemptions=req.preemptions,
            committed_tokens=len(req.committed),
            ready_at_step=req.ready_at_step)
        if rt is not None:
            # the requeue wait gets its own open span; the root carries
            # the running preemption count
            rt.trace.root.set("preemptions", req.preemptions)
            rt.queue = rt.trace.begin("queue_wait", requeue=True)
        if self.watchdog is not None:
            self.watchdog.notify_progress()

    def _preempt_for_head(self, finished: list) -> bool:
        """One degradation-ladder rung: when the first eligible queued
        request still isn't resident after admission (slots or blocks
        short — the allocator already evicted prefix-LRU blocks trying),
        preempt the lowest-priority newest resident IF it ranks strictly
        below the waiter. Equal priorities never preempt — plain FIFO
        traffic on a tight pool must queue, not thrash."""
        if self.max_preemptions <= 0:
            return False        # preemption disabled by config
        now = self._clock() if self._deadlines else None
        head = self.scheduler.next_ready(self._tick, now=now)
        if head is None:
            return False
        victim = self.scheduler.pick_preemption_victim()
        if victim is None:
            return False
        slot, state = victim
        if state.request.priority >= head.priority:
            return False
        if self._prefilling and self._prefilling[0]["slot"] != slot:
            # nobody moves in behind a prefill in flight (_admit): a
            # resident evicted now would free a slot the head cannot
            # take yet. Only the job itself may make way for it
            return False
        self._preempt_slot(slot, finished)
        return True

    def _admit(self, finished: list, sp=NULL_STEP_HANDLE) -> None:
        """Admit queued requests into free slots until blocks or slots
        run out. Monolithic mode prefills inline — one trace per prompt
        BUCKET (128·2^k, floored at block_size), shared by every slot
        (`slot` rides as a traced scalar). Chunked mode
        (prefill_chunk_tokens / prefix caching) only claims the slot and
        installs its block table here; the prefill itself runs one
        fixed-size chunk per ``step()`` via :meth:`_run_prefill_chunk`,
        so a long prompt never stalls the resident decoders. Chunks
        (chained or not) are the OLDEST job's, so a request admitted
        behind a prefill in flight would only hold its slot and blocks
        while it waits its turn: it stays queued instead, and where it
        shares a prefix with the job ahead it hits the blocks that job
        published (requests of one tenant queued together prefill their
        shared context once, not side by side).

        Where the model's family has ``paged_decode_admit`` (monolithic
        mode, no speculation) and slots are decoding when this is
        entered, ONE request is admitted and handed to the step's decode
        round as its rider (``_decode_round``): its prompt is prefilled
        inside the step's one program. Into a server with nothing
        decoding every request that fits is admitted here as before,
        each by that program with no active row."""
        riding = self._admit_jit is not None and bool(self.scheduler.slots)
        while True:
            if self._prefilling:
                return
            now = self._clock() if self._deadlines else None
            swaps0 = (self.scheduler.allocator.swap_ins
                      if self._ledger is not None else 0)
            adm = self.scheduler.admit_next(self._tick, now=now)
            if adm is None:
                return
            slot, state = adm
            req = state.request
            sched_prompt = req.sched_prompt
            t_admit = self._clock()
            self._admitted_step += 1
            self._request_phase(req.request_id, t_admit, PREFILL_SPAN)
            if not state.resumed:
                self._h_queue_wait.observe(
                    t_admit - self._submit_ts.get(req.request_id,
                                                  t_admit))
            if self._ledger is not None:
                # queue-wait charges EVERY admission (a preempted
                # request's requeue wait is real queueing, reset at the
                # preempt); block residency opens against the slot's
                # full allocated span — blocks are claimed up-front, so
                # the count is fixed for the whole residency
                self._ledger.note_queued(
                    req.request_id,
                    t_admit - self._queued_ts.get(req.request_id,
                                                  t_admit))
                self._ledger.open_residency(
                    req.request_id, len(state.blocks), now=t_admit)
                d_swaps = self.scheduler.allocator.swap_ins - swaps0
                if d_swaps and self.host_tier is not None:
                    self._ledger.note_swap_in_bytes(
                        req.request_id,
                        d_swaps * self.host_tier.block_nbytes)
            rt = (self._rt.get(req.request_id)
                  if self.tracer is not None else None)
            adm_span = None
            if rt is not None:
                rt.trace.end_span(rt.queue)
                adm_span = rt.trace.begin(
                    "admission", slot=slot,
                    resumed=state.resumed,
                    prefix_cache_hit=state.cached_blocks > 0,
                    blocks_reused=state.cached_blocks,
                    blocks_allocated=(len(state.blocks)
                                      - state.cached_blocks))
            # block table first — the prefill scatter reads it. Entries
            # beyond the allocated span stay 0 (null block), so bucket/
            # chunk padding past the span spills harmlessly.
            self._set_block_row(slot, state.blocks)
            if rt is not None:
                # admission work (slot pick, block table) is done —
                # close the span BEFORE the fault site, so an injected
                # failure's always-kept error trace has every child
                # closed
                rt.trace.end_span(adm_span)
            # fault-injection prefill site: admission is the ONE place
            # both prefill paths pass exactly once per FIRST admission,
            # so the seeded coin flips here — per-chunk flips would
            # compound the configured rate with prompt length, keying
            # on a chunk's start offset would skip warm-prefix requests
            # (their first chunk starts at cached_len, not 0), and
            # re-flipping at a preemption re-admission (resumed) would
            # compound the rate with preemption count
            if self._injected_prefill_fault(slot, state, finished,
                                            seeded=not state.resumed):
                continue
            if self.chunk_tokens:
                cached_len = state.cached_blocks * self.block_size
                self._prefix_tokens_skipped += cached_len
                self._c_prompt_tokens["cached"].inc(cached_len)
                self._c_prompt_tokens["prefilled"].inc(
                    len(sched_prompt) - cached_len)
                # pin the slot's live length at the cached boundary NOW:
                # decode steps that run before (or between) this slot's
                # chunks append their masked garbage token at
                # ``lengths[slot]`` — which must be the next PRIVATE
                # position the coming chunk overwrites, never offset 0
                # of a (possibly shared) prefix block
                self._cache = self._cache.replace(
                    lengths=_set_row(self._cache.lengths, slot, cached_len))
                self._prefilling.append(
                    {"slot": slot, "state": state, "start": cached_len})
                self._mid_prefill.add(slot)
                if rt is not None:
                    # the prefill span brackets the WHOLE chunked phase
                    # (chunk spans nest under it); step()-interleave gaps
                    # between chunks are inside it by design — that IS
                    # the Sarathi tradeoff made visible
                    rt.prefill = rt.trace.begin(
                        "prefill", chunked=True,
                        tokens=len(sched_prompt) - cached_len,
                        cached_tokens_skipped=cached_len)
                continue
            # ---------------- monolithic bucketed prefill (chunking off)
            T = min(max(_bucket(len(sched_prompt)), self.block_size),
                    self.max_blocks_per_slot * self.block_size)
            if rt is not None:
                rt.prefill = rt.trace.begin(
                    "prefill", chunked=False, tokens=len(sched_prompt),
                    bucket=T)
            ids = np.zeros((1, T), np.int32)
            ids[0, :len(sched_prompt)] = sched_prompt
            if self._ledger is not None:
                # weight = the PADDED bucket actually computed, so the
                # step's device split follows the work the device did
                self._ledger.add_weight(req.request_id, T)
            if riding:
                # slots are decoding: the prompt rides this step's decode
                # program, which reads the weights once for both, and the
                # first token commits with that program's record
                # (_decode_round). One rider a step
                self._rider = _Rider(slot, state, ids, t_admit)
                return
            t_pf = self._clock()
            plen = jnp.asarray([len(sched_prompt)], jnp.int32)
            if self._admit_jit is None:
                tok0, self._cache = self._prefill_jit(
                    self.engine.params, jnp.asarray(ids), plen, self._cache,
                    jnp.int32(slot))
                row = 0
            else:
                # nothing is decoding (an empty server filling up): the
                # same program with no active row, its token at ``slot``
                tokens, active = self._idle_rows
                tok0, self._cache = self._admit_jit(
                    self.engine.params, tokens, self._cache, active,
                    jnp.asarray(ids), plen, jnp.int32(slot))
                row = slot
            tok0 = int(self._fetch_tokens(tok0)[row])  # host sync: prefill done
            now_t = self._clock()
            # prefill compute runs inside the admission phase; its
            # dispatch->fetch interval is still device-attributed (and
            # advances the dispatch-gap boundary — the device was busy)
            sp.device_interval(t_pf, now_t)
            sp.program_fetched(self._launched(
                self._prefill_jit if self._admit_jit is None
                else self._admit_jit, t_pf, T,
                prompt_tokens=len(sched_prompt)), now_t, since=t_pf)
            self._prefilled(slot, state, T, t_admit, tok0, now_t, finished,
                            "alone")

    def _prefilled(self, slot: int, state, T: int, t_admit: float,
                   tok0: int, now_t: float, finished: list,
                   path: str) -> None:
        """A monolithic admission's first token is on the host (fetched
        with the program that prefilled its ``T``-row bucket, alone or as
        a step's rider)."""
        self._prefill_token_units += T
        # prefill latency by PADDED bucket (the traced shape, not the
        # raw prompt length — per-shape latency is what regressions
        # in the prefill program show up against)
        self.telemetry.histogram(
            "serve_prefill_seconds",
            help="prefill wall time, by padded prompt-bucket length",
            labels={"bucket": str(T)}).observe(now_t - t_admit)
        if self.watchdog is not None:
            # a prefill IS progress — a long admission burst must
            # not read as a decode stall
            self.watchdog.notify_progress()
        self._first_token(slot, state, tok0, now_t, finished, path)

    def _first_token(self, slot: int, state, tok0: int, now: float,
                     finished: list, path: str) -> None:
        """The prompt is resident and the request's next token real (a
        monolithic program's, a rider's, or the final chunk's): the
        request's lifecycle moves from prefill to decode."""
        req = state.request
        self._request_phase(req.request_id, now, DECODE_SPAN)
        if not state.generated:
            # TTFT is observed when the request's FIRST token ever
            # leaves (generated == committed until tok0 appends): a
            # resumed request that already emitted tokens skips it,
            # but one preempted mid-prefill still owes its first
            # token — hiding its (slow) TTFT would green an SLO
            # that is actually collapsing under preemption pressure
            self._h_ttft.observe(
                now - self._submit_ts.get(req.request_id, now))
        self._c_prefills.inc()
        self._c_tokens.inc()
        self._prefills += 1
        self._c_admissions[path].inc()
        self._admissions[path] += 1
        rt = (self._rt.get(req.request_id)
              if self.tracer is not None else None)
        if rt is not None:
            rt.trace.end_span(rt.prefill)
        self._draft_prefill_slot(slot, state)
        state.generated.append(tok0)
        state.pending = tok0
        if self._finished(state, tok0):
            self._retire(slot, state, finished)
        elif rt is not None:
            # decode residency: one span from "slot decodable" to
            # retirement, annotated at close with tokens/steps
            rt.decode = rt.trace.begin("decode", slot=slot)

    def _run_prefill_chunk(self, finished: list,
                           sp=NULL_STEP_HANDLE) -> None:
        """Run AT MOST one chunk of the oldest in-flight chunked
        prefill — the Sarathi-style interleave: each ``step()`` advances
        one prefill by ``prefill_chunk_tokens`` tokens and then decodes
        every active slot, so prefill latency is spread across steps
        instead of stalling all residents for a whole prompt.

        With ``prefill_chain`` the prompt's NON-FINAL chunks dispatch as
        one device-side chain in a single call (each chains on the
        previous chunk's donated cache — no host boundary, no per-chunk
        pipeline flush); only the final chunk, which fetches the first
        token, stays on its own step boundary."""
        if not self._prefilling:
            return
        job = self._prefilling[0]
        slot, state = job["slot"], job["state"]
        req = state.request
        sched_prompt = req.sched_prompt
        C = self.chunk_tokens
        start = job["start"]
        plen = len(sched_prompt)
        # targeted arms only (seeded=False): the per-request seeded
        # coin already flipped at this request's admission
        if self._injected_prefill_fault(slot, state, finished,
                                        seeded=False):
            return
        rt = (self._rt.get(req.request_id)
              if self.tracer is not None else None)
        while True:
            start = job["start"]
            ids = np.zeros((1, C), np.int32)
            valid = min(plen - start, C)
            ids[0, :valid] = sched_prompt[start:start + valid]
            ck = None
            if rt is not None:
                ck = rt.trace.begin("prefill_chunk", parent=rt.prefill,
                                    start_token=start, tokens=valid)
            t0 = self._clock()
            tok, self._cache = self._chunk_jit(
                self.engine.params, jnp.asarray(ids), jnp.int32(start),
                jnp.asarray([plen], jnp.int32), self._cache,
                jnp.int32(slot))
            self._prefill_chunks += 1
            self._prefill_token_units += C
            self._c_chunk_rows.inc(C)
            if self._ledger is not None:
                self._ledger.add_weight(req.request_id, C)
            job["start"] = start + C
            if job["start"] >= plen:
                break             # final chunk: fall through to fetch
            # NON-final chunk: its logits are chunk-tail garbage the
            # host never reads, so there is nothing to fetch — forcing
            # np.asarray here existed only for "honest per-chunk
            # timing" and stalled the whole pipeline once per chunk.
            # The dispatch boundary is noted NOW (gap accounting); the
            # chunk's device span closes at the next real fetch
            # (decode/verify/final-chunk — _realize_chunk_span), which
            # its compute provably precedes: the decode program chains
            # on this chunk's cache output.
            t1 = self._clock()
            self._h_prefill_chunk.observe(t1 - t0)   # dispatch interval
            self._launched(self._chunk_jit, t0, C, prompt_tokens=valid)
            if self._chunk_pending_t0 is None:
                # ONE dispatch note per pending chain: the whole chain
                # realizes through ONE fetch note (_realize_chunk_span),
                # so noting every chunk would leak the profiler's
                # outstanding counter and zero the gap metric forever
                self._chunk_pending_t0 = t0
                sp.note_dispatch(t0)
            if ck is not None:
                rt.trace.end_span(ck)
            if self.watchdog is not None:
                self.watchdog.notify_progress()   # a chunk IS progress
            if not self._prefill_chain:
                return            # more chunks, one per step()
            # prefill_chain: dispatch the prompt's REMAINING non-final
            # chunks device-side right now — each chains on the previous
            # chunk's donated cache, no host boundary between them. The
            # pending-chunk note machinery above is already one-note-
            # per-chain, so the whole chain realizes through the same
            # single fetch as one deferred chunk. The final chunk still
            # waits for the next step(): it fetches the first token, and
            # keeping it on the step boundary preserves the Sarathi
            # decode interleave exactly where the fetch cost lands.
            if job["start"] + C >= plen:
                return            # next chunk is final — next step's
        # final chunk: the prompt is resident, the first token is real —
        # this fetch is once per REQUEST (not per chunk) and the loop
        # needs the token to seed decoding
        tok = np.asarray(tok)     # host sync: prefill complete
        t1 = self._clock()
        self._h_prefill_chunk.observe(t1 - t0)
        sp.device_interval(self._chunk_pending_t0
                           if self._chunk_pending_t0 is not None
                           else t0, t1,
                           note_dispatch=self._chunk_pending_t0 is None)
        sp.program_fetched(self._launched(
            self._chunk_jit, t0, C, prompt_tokens=valid), t1, since=t0)
        self._chunk_pending_t0 = None
        if ck is not None:
            rt.trace.end_span(ck)
        if self.watchdog is not None:
            self.watchdog.notify_progress()   # a chunk IS progress
        self._prefilling.popleft()
        self._mid_prefill.discard(slot)
        if self.prefix_caching:
            # publish the cold tail's full prompt blocks — only now is
            # their content valid for another request to hit
            self.scheduler.commit_prefix(state)
        self._first_token(slot, state, int(tok[0]), self._clock(),
                          finished, "chunk")

    def _draft_prefill_slot(self, slot: int, state) -> None:
        """Admit one slot's FULL scheduled prompt into the draft pool
        (draft-model speculation). Runs once per admission, right after
        the target prefill completes. The draft always prefills from
        position 0, even under prefix caching or chunked prefill:
        shared prefix blocks are rewritten with identical content (same
        tokens, deterministic forward), so cross-slot sharing stays
        exact, and a preemption re-admission rebuilds the whole draft
        state the reset scrubbed. The mirrored tables are copied fresh
        first so the scatter lands in this slot's just-allocated
        blocks."""
        if self.draft is None:
            return
        sched_prompt = state.request.sched_prompt
        plen = len(sched_prompt)
        T = min(max(_bucket(plen), self.block_size),
                self.max_blocks_per_slot * self.block_size)
        ids = np.zeros((1, T), np.int32)
        ids[0, :plen] = sched_prompt
        self._draft_cache = self._draft_cache.replace(
            block_tables=jnp.copy(self._cache.block_tables))
        _, self._draft_cache = self._draft_prefill_jit(
            self.draft.params, jnp.asarray(ids),
            jnp.asarray([plen], jnp.int32), self._draft_cache,
            jnp.int32(slot))
        # behind the fetch of the target's prefill: no clock read of its own
        self._launched(self._draft_prefill_jit, None, T, prompt_tokens=plen)

    def _draft_propose(self, states: Dict[int, object]):
        """One draft proposal round for the given slot→state snapshot:
        re-mirror the target's block tables (the target jits donate the
        cache, so the draft must never hold an aliased buffer across a
        target dispatch), then run ``speculation.draft_propose`` — K
        chained draft decode forwards, all device-resident. Returns
        ``(verify_tokens [S, K] device, props [S, K-1] device)``; the
        verify input is built by device concatenation of the pending
        column and the proposals, so its aval matches the host-built
        prompt-lookup path exactly — the SAME target verify executable
        serves both."""
        K = self.spec_tokens
        S = self.num_slots
        pend = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        for slot, state in states.items():
            pend[slot] = state.pending
            active[slot] = True
        self._draft_cache = self._draft_cache.replace(
            block_tables=jnp.copy(self._cache.block_tables))
        props, self._draft_cache = draft_propose(
            self._draft_decode_jit, self.draft.params, self._draft_cache,
            jnp.asarray(pend), jnp.asarray(active), K)
        tokens = jnp.concatenate([jnp.asarray(pend)[:, None], props], 1)
        return tokens, props

    def _finished(self, state, tok: int) -> bool:
        req = state.request
        if self._fi is not None and self._fi.is_wedged(req.request_id):
            # injected wedge: neither EOS nor budget ever finishes this
            # request — it decodes until a deadline / cancel / bounded
            # drain reaps it. Appends past its allocated span spill
            # into the null block / clobber its own tail, and the reap
            # returns the whole over-budget token list as the partial
            # result: incoherent past the span, but deliberate — the
            # length itself is forensic evidence of how long the wedge
            # ran (the chaos tests pin len > budget)
            return False
        return (tok == req.eos_token_id
                or len(state.generated) >= req.max_new_tokens)

    def _retire(self, slot: int, state, finished: list) -> None:
        req = state.request
        rt = (self._rt.pop(req.request_id, None)
              if self.tracer is not None else None)
        fin = None
        if rt is not None:
            if rt.decode is not None:
                rt.decode.set("tokens_committed", rt.tokens)
                rt.decode.set("steps", rt.steps)
                rt.trace.end_span(rt.decode)
            fin = rt.trace.begin("finish")
        out = list(req.prompt) + state.generated
        self._results[req.request_id] = out
        reason = ("eos" if state.generated
                  and state.generated[-1] == req.eos_token_id
                  else "length")
        self.finish_reasons[req.request_id] = reason
        finished.append(req.request_id)
        ts = self._submit_ts.pop(req.request_id, None)
        self._queued_ts.pop(req.request_id, None)
        self._deadlines.pop(req.request_id, None)
        t_done = self._clock()
        if ts is not None:
            self._h_request.observe(t_done - ts)
        self._request_done(req, t_done, len(state.generated), reason)
        if self._ledger is not None:
            # moves the record to pending-close: the retiring step's
            # own device share still settles onto it before it emits
            self._ledger.finish(req.request_id,
                                tokens_out=len(state.generated),
                                reason=reason)
        if self._pool_acct is not None:
            self._pool_acct.observe_request_peak(req.peak_blocks)
        self._c_finished.inc()
        # reserved-tail accounting: blocks allocated for budget the
        # sequence EOSed before reaching were never written — they go
        # straight back to the free list here (never into the prefix
        # LRU: unwritten content is not cacheable), counted so early-EOS
        # traffic's reclaimed headroom is visible
        # cache holds prompt + all generated but the last (the final
        # token is committed without ever being appended)
        live = len(req.prompt) + max(len(state.generated) - 1, 0)
        tail = max(0, len(state.blocks) - (-(-live // self.block_size)))
        if tail:
            self._c_tail_reclaimed.inc(tail)
            self._tail_reclaimed += tail
        # slot + blocks recycle NOW: the freed span admits the next
        # queued request on the same step, without touching the trace.
        # The retired slot's length resets to 0 on the HOST array only —
        # the device sees it at the next decode call's lengths input.
        self.scheduler.release(slot)
        self._reset_slot_arrays(slot)
        if rt is not None:
            rt.trace.root.set("finish_reason", reason)
            rt.trace.root.set("generated_tokens", len(state.generated))
            rt.trace.end_span(fin)
            self.tracer.finish(rt.trace)

    def step(self) -> List[int]:
        """One scheduler round: reap expired deadlines, shed under SLO
        breach, admit from the queue into free slots (preempting
        lower-priority residents for a higher-priority waiter when the
        pool is short), run at most ONE chunk of any in-flight chunked
        prefill, then one decode step for all active resident slots.
        Returns the request ids that got a result this round — normal
        finishes AND lifecycle finishes (fetch outputs via ``result`` /
        ``drain``; ``finish_reasons`` tells them apart).

        Every step picks its COMMIT LAG — how many dispatched decode
        programs may stay unfetched when it returns (docs/serving.md
        "Async dispatch loop"). A step with a host-driven state change
        it can make now (:meth:`_host_can_act`: a chunked prefill in
        flight, or an eligible queued request with a free slot to take
        or a lower-priority resident to preempt) or with
        ``inference.async_loop`` off runs at lag 0: it commits whatever
        is in flight first, so admission, chunk scheduling, preemption,
        shedding and fault injection act on committed state, and it
        commits the program it dispatches before it returns (but for a
        step whose chunk finished a prefill and left nothing the next
        step could act on: its decode starts the next chain). Any other
        step — an empty queue, or a backlog waiting behind full slots —
        runs at ``max_commit_lag``: it dispatches step N+1 chained
        from step N's device-resident outputs and commits only the
        records beyond the lag, so finishes surface up to
        ``max_commit_lag`` ``step()`` calls after their device step
        (and the slot a finish frees is refilled by the step after)."""
        # step observatory (telemetry/step_profile.py): phase marks at
        # boundaries the loop already crosses — monotonic-clock reads
        # only, zero new device syncs; OFF = the shared no-op handle
        sp = (self._profiler.begin() if self._profiler is not None
              else NULL_STEP_HANDLE)
        finished: List[int] = []
        self._admitted_step = 0
        self._rode_step = False
        self._take_deferred(finished)
        self._tick += 1
        if self.canary is not None:
            # the prober self-injects through the REAL submit path ahead
            # of this round's admission, and scores its outstanding
            # probe; runs even on an otherwise-idle server — a wedged
            # loop that serves nobody is exactly what it detects
            self.canary.tick()
        if self.alerts is not None:
            # cadence-gated like slo/capacity; sits at the top so every
            # step shape (lag 0, lagged, idle early-return) evaluates
            self.alerts.maybe_evaluate()
        if self._fi is not None:
            self._fi.apply_famine(self.scheduler.allocator)
        self._reap_deadlines(finished)
        self._maybe_shed(finished)
        # an out-of-step flush inside a reap-triggered cancel defers its
        # collateral finishes — fold them into THIS round's return
        self._take_deferred(finished)
        # this step's commit lag: 0 whenever the host has a state
        # change it can make NOW (or async_loop is off), max_commit_lag
        # while the only host work is committing what the device
        # finished — an empty queue, or a backlog behind full slots
        lag = 0 if self._host_can_act() else self._max_lag
        if lag == 0:
            if self._inflight:
                # admission / chunk scheduling / the preemption ladder
                # ahead: commit the whole in-flight chain FIRST so
                # every decision below sees committed state
                self._flush_pipeline(finished, sp, reason="host_action")
            self._admit(finished, sp)
            # degradation ladder, rung 2 (rung 1, prefix-LRU eviction,
            # already ran inside the allocator during admission):
            # preempt strictly-lower-priority residents for the blocked
            # waiter, re-admitting after each victim frees its slot +
            # blocks
            # (never behind a rider, whichever _admit produced it: the
            # head it left queued waits for a step, not for a slot, and
            # the next step carries it or preempts for it)
            guard = self.num_slots
            while (guard > 0 and self._rider is None
                   and self._preempt_for_head(finished)):
                guard -= 1
                self._admit(finished, sp)
        if lag == 0 or self.scheduler.queue:
            # tier health: sample the admission round's swap-in traffic
            # into the thrash window (demotion/swap-in only ever runs
            # inside the admissions above; a lagged step with a backlog
            # behind full slots samples its zero, so the window stays
            # "steps with work waiting")
            self._check_swap_thrash()
        sp.mark("admission")     # at lag > 0: the reap/shed checks above
        before = self._prefill_chunks
        start = self._prefilling[0]["start"] if self._prefilling else None
        self._run_prefill_chunk(finished, sp)   # none in flight at lag > 0
        ran = self._prefill_chunks - before
        # a step that ran chunks names their program on the phase's
        # span, with the first chunk's position and how many ran
        if ran:
            sp.mark("prefill_chunk", program=self._chunk_jit.name,
                    note={"start": start, "chunks": ran,
                          "rows": self.chunk_tokens})
        else:
            sp.mark("prefill_chunk")
        if ran and lag == 0 and not self._host_can_act():
            # the prefill this step's chunk finished was its host
            # action, and nothing is left that the NEXT step could act
            # on (no prefill in flight, no free slot an eligible request
            # could take): the decode behind the final chunk starts the
            # chain that step would have started, and that step commits
            # it. The commit and the caller's work between two steps (a
            # 33k-token prompt queued and hashed) then run beside a
            # program in flight, not beside an idle device; a refill
            # with the prompt prefilled inside admission keeps its
            # committed round (docs/serving.md "Async dispatch loop")
            lag = self._max_lag
        if not self.scheduler.slots:
            if self._inflight:
                # every resident retired at the last commit; the steps
                # dispatched beside and after that commit are pure
                # garbage — fetch and discard them so their writes
                # complete before any future admission reuses the
                # released blocks
                self._flush_pipeline(finished, sp, reason="drain_tail")
            if self.watchdog is not None:
                # an IDLE server being polled is alive, not stalled —
                # without this heartbeat every traffic lull longer than
                # the deadline fires a spurious dump
                self.watchdog.notify_progress()
            # nothing resident: the device idles for lack of WORK, so
            # the dispatch-gap baseline resets (a lull is not host tax)
            self._finish_step(sp)
            return finished
        if self.spec_tokens:
            self._verify_round(finished, sp, lag)
        else:
            self._decode_round(finished, sp, lag)
        if self.slo is not None and not self._shedding:
            # with shedding armed, _maybe_shed already refreshed the
            # monitor this step — don't pay a second registry snapshot
            self.slo.maybe_evaluate()
        if self._capacity is not None:
            self._capacity.maybe_evaluate()
        sp.mark("publish")
        # live=False when this step retired the last resident: the gap
        # to the NEXT dispatch would measure traffic, not host tax
        self._finish_step(sp)
        return finished

    def _host_can_act(self) -> bool:
        """Whether this step has a host-driven state change it could
        make: ``async_loop`` is off (every step commits what it
        dispatched), a chunked prefill is in flight, or the queue holds
        an eligible request AND a slot is free or that request outranks
        a resident (``Scheduler.may_act``: conservative, a free slot
        answers yes whatever the pool holds). When every slot is
        resident and the eligible head outranks nobody, admission and
        the preemption ladder cannot change anything, however deep the
        queue: the step runs lagged, like one with an empty queue. The
        step after a retirement sees the free slot and flushes."""
        if not self._async or self._prefilling:
            return True
        if not self.scheduler.queue:
            return False
        now = self._clock() if self._deadlines else None
        return self.scheduler.may_act(
            self._tick, now, preemption=self.max_preemptions > 0)

    def _finish_step(self, sp) -> None:
        """Close the step's profile. ``live`` is false when nothing is
        resident after it (idle poll, or the step retired the last
        resident): the gap to the NEXT dispatch would measure traffic,
        not host tax."""
        slots = len(self.scheduler.slots)
        sp.finish(live=bool(slots), slots=slots,
                  admitted=self._admitted_step, rider=self._rode_step)

    # ------------------------------------------------ async dispatch loop

    def _take_deferred(self, finished: List[int]) -> None:
        """Fold finishes an out-of-step flush produced (cancel / drain
        between steps) into this round's return value."""
        if self._deferred_finished:
            finished.extend(self._deferred_finished)
            self._deferred_finished.clear()

    def _realize_chunk_span(self, sp, t1: float) -> None:
        """Close the device span of chunk dispatches whose fetch was
        deferred (the chunk program provably finished before whatever
        result just landed at ``t1`` — the later program chains on its
        cache output)."""
        if self._chunk_pending_t0 is None:
            return
        if sp is not NULL_STEP_HANDLE:
            sp.device_interval(self._chunk_pending_t0, t1,
                               note_dispatch=False)
        elif self._profiler is not None:
            # profiler armed but no step handle live (out-of-step
            # flush): keep the outstanding-dispatch pairing exact even
            # though the device credit has no step to land in
            self._profiler.note_fetch(t1)
        self._chunk_pending_t0 = None

    def _decode_round(self, finished: List[int], sp, lag: int) -> None:
        """One decode program for all active resident slots, committed
        ``lag`` steps late: dispatch, append the record to the in-flight
        chain, then commit the OLDEST record while the chain is deeper
        than ``lag``.

        Lag 0 (the chain is empty on entry — ``step`` flushed it) builds
        the inputs on the host and commits the step it just dispatched.
        Lag N dispatches step N+1 BEFORE fetching step N: N's greedy
        outputs are already a device array, so N+1's inputs chain from
        them with no host round trip (tokens feed back directly; lengths
        advanced in-graph by ``paged_decode_step``; the cache is the
        donated thread) and JAX async dispatch overlaps the device's
        compute with the host's commit of the record N steps back. A
        slot that turns out to have finished at step N already ran <= N
        garbage rows in the steps chained after it: each commit discards
        its row by state identity (advance-only rollback — the retire
        path reset its lengths/table, so the garbage KV sits masked in
        released blocks no one can reuse before the next flush fetches
        the chain)."""
        chain = self._inflight
        rec = chain[-1] if chain else None
        S = self.num_slots
        tokens = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        states: Dict[int, object] = {}
        # admitted by this step (_admit): its prompt rides the program,
        # its slot decodes from the next step on
        rider, self._rider = self._rider, None
        self._rode_step = rider is not None
        for slot, state in self.scheduler.slots.items():
            if slot in self._mid_prefill or (rider is not None
                                             and slot == rider.slot):
                continue   # resident but still prefilling: not decoded
            tokens[slot] = state.pending
            active[slot] = True
            states[slot] = state
        if not states and rider is None:
            # every resident slot is mid-prefill — the chunk above was
            # this step's progress; nothing to decode yet
            sp.mark("propose")
            return
        self.profiler_capture.step_begin()
        t0 = self._clock()
        if lag:
            # device-credit window: with a step already in flight the
            # device verifiably has work for this WHOLE step (N runs
            # until its fetch, N+1 from before that fetch onward); a
            # chain's first dispatch is busy from t0 to the step's end
            sp.pipelined(since=None if rec is not None else t0)
        else:
            # any deferred chunk span closes HERE: the device was busy
            # with the chunk from its dispatch until (at least) this
            # boundary, and the decode's own dispatch/sync_wait slivers
            # cover the rest — adjacent windows, no double count
            self._realize_chunk_span(sp, t0)
        # the propose phase ends HERE and the decode program dispatches:
        # the dispatch-gap detector measures this boundary against the
        # last fetch that drained the device
        sp.mark("propose", now=t0, dispatch=True)
        if rider is None:
            program = self._decode_jit
            nxt, self._cache = program(
                self.engine.params,
                # an empty chain starts from the host's pending tokens, a
                # live one from the newest record's device-resident
                # outputs
                jnp.asarray(tokens) if rec is None else rec.tokens,
                self._cache, jnp.asarray(active))
        else:
            # a lag-0 step (it admitted): the chain is empty, and the
            # record commits below with the rider's first token at its
            # slot of the sampled vector
            program = self._admit_jit
            nxt, self._cache = program(
                self.engine.params, jnp.asarray(tokens), self._cache,
                jnp.asarray(active), jnp.asarray(rider.ids),
                jnp.asarray([len(rider.state.request.sched_prompt)],
                            jnp.int32), jnp.int32(rider.slot))
        sp.mark("dispatch", program=program.name)
        ticket = self._launched(
            program, t0, None if rider is None else rider.ids.shape[1],
            len(states), 0 if rider is None
            else len(rider.state.request.sched_prompt))
        chain.append(InFlightStep("decode", nxt, states, t0, rider=rider,
                                  ticket=ticket))
        if lag:
            self._async_stats["pipeline_starts" if rec is None
                              else "pipelined_steps"] += 1
        if len(chain) > lag:
            # commit the OLDEST record and rethread the new-oldest one's
            # latency baseline to this fetch, so its eventual
            # fetch-to-fetch dt stays honest
            t1 = self._commit_decode_record(chain.popleft(), finished, sp,
                                            lagged=bool(lag))
            if chain:
                chain[0].prev_fetch = t1
        else:
            # deepening the chain: dispatch only — no fetch, no commit
            sp.mark("sync_wait")
            sp.mark("commit")
            if self.watchdog is not None:
                self.watchdog.notify_progress()   # a dispatch IS progress
        self.profiler_capture.step_end()

    def _commit_decode_record(self, rec: InFlightStep,
                              finished: List[int], sp=NULL_STEP_HANDLE,
                              discard_rid: Optional[int] = None,
                              lagged: bool = True) -> float:
        """Host commit of one in-flight decode step (the chain's
        oldest): fetch its tokens, append/EOS-check/retire for every
        slot whose SlotState is still the one that was resident at
        dispatch, and publish the step's metrics. ``lagged`` is false
        for a record committed by the lag-0 step that dispatched it:
        nothing ran after it, so the latent model's counters come over
        in the same fetch and the metrics publish inline; a lagged
        record's pool has been donated onward, and its publishing rides
        the worker thread. ``discard_rid`` drops one request's token on
        the floor (cancel/deadline teardown in progress: the caller
        observed the committed boundary, and the slot's arrays are
        about to be reset anyway). Returns the fetch timestamp."""
        in_step = sp is not NULL_STEP_HANDLE
        # host sync: the step completed
        nxt = (np.asarray(rec.tokens) if lagged
               else self._fetch_tokens(rec.tokens))
        t1 = self._clock()
        rider = rec.rider
        self._proven(sp, rec.ticket, t1)
        if in_step:
            sp.mark("sync_wait", now=t1, fetch=True,
                    program=(self._decode_jit if rider is None
                             else self._admit_jit).name)
        elif self._profiler is not None:
            self._profiler.note_fetch(t1)
        self._realize_chunk_span(sp, t1)
        # tokens are DELIVERED at fetches: the honest per-step latency
        # under pipelining is fetch-to-fetch (dispatch→fetch for the
        # pipeline's first step)
        dt = t1 - (rec.prev_fetch if rec.prev_fetch is not None
                   else rec.t_dispatch)
        if self._fi is not None:
            # injected latency is ACCOUNTED, never slept (see step())
            dt += self._fi.step_latency()
        n_live = 0
        # insertion order (scheduler.slots iteration at dispatch):
        # deterministic at any lag
        for slot, state in rec.states.items():
            if self.scheduler.slots.get(slot) is not state:
                # retired / torn down after this step dispatched: the
                # in-flight token is garbage (its KV was reset with the
                # slot)
                self._async_stats["discarded_tokens"] += 1
                continue
            if (discard_rid is not None
                    and state.request.request_id == discard_rid):
                self._async_stats["discarded_tokens"] += 1
                continue
            n_live += 1
            tok = int(nxt[slot])
            state.generated.append(tok)
            if self._ledger is not None:
                self._ledger.add_weight(state.request.request_id, 1)
            if self.tracer is not None:
                rt = self._rt.get(state.request.request_id)
                if rt is not None and rt.decode is not None:
                    rt.steps += 1
                    rt.tokens += 1
            if self._finished(state, tok):
                self._retire(slot, state, finished)
            else:
                state.pending = tok
        if (rider is not None and self.scheduler.slots.get(rider.slot)
                is rider.state
                and rider.state.request.request_id != discard_rid):
            # the prompt this program prefilled: its last live row's
            # token came over at its slot
            self._prefilled(rider.slot, rider.state, rider.ids.shape[1],
                            rider.t_admit, int(nxt[rider.slot]), t1,
                            finished, "rider")
        if in_step:
            sp.mark("commit")
        if n_live == 0:
            # pure garbage (every slot vanished between dispatch and
            # commit): the device step ran but served nothing — not a
            # decode step in any accounting
            if rider is None:
                self._async_stats["garbage_steps"] += 1
            return t1
        self._step_clock += 1
        self._active_slot_steps += n_live
        # every live slot committed one token this step, each costing
        # one step of wall time — THE per-token serving latency
        self._publish(self._publish_decode_step, lagged, dt, n_live,
                      n_live / self.num_slots, *self._pool_reading())
        if self.watchdog is not None:
            self.watchdog.notify_progress()
        if self._step_clock % self._EVENT_EVERY == 1:
            get_event_ring().record(
                telemetry_events.STEP_END, source="serve_decode",
                step=self._step_clock, live=n_live,
                seconds=round(dt, 6), pipelined=lagged,
                sampled_every=self._EVENT_EVERY)
        return t1

    def _pool_reading(self) -> tuple:
        """``(blocks held by residents, whether a free slot waits on
        blocks)`` at a step's commit, read on the owner thread."""
        sched = self.scheduler
        return (sched.allocator.live_blocks,
                sched.waits_on_blocks(self._tick))

    def _publish_pool(self, used_blocks: int, blocked: bool) -> None:
        self._c_block_steps.inc(used_blocks)
        if blocked:
            self._c_blocked_steps.inc()

    def _publish_decode_step(self, dt: float, n_live: int, occ: float,
                             used_blocks: int, blocked: bool) -> None:
        """Metric publish for one committed decode step (values computed
        on the owner thread — run on the worker, this never reads a
        clock or scheduler state)."""
        self._h_decode_step.observe(dt)
        self._h_token.observe(dt)
        self._c_decode_steps.inc()
        self._c_tokens.inc(n_live)
        self._g_occupancy.set(occ)
        self._publish_pool(used_blocks, blocked)

    def _verify_round(self, finished: List[int], sp, lag: int) -> None:
        """One speculative round for all active resident slots: each
        slot proposes up to K-1 tokens, ONE batched verify forward
        scores every slot's ``[pending, p_1..p_{K-1}]`` chunk through
        the block tables, and :meth:`_commit_verify_record` commits the
        accepted prefix — 1..K tokens per slot per round. At lag 0 the
        round commits before it returns; at any lag > 0 it returns with
        the verify in flight (its device compute overlaps the publish
        work on the worker thread, the inter-step host time and the
        next round's checks) and the NEXT round commits it first.

        The verify path deliberately commits BEFORE dispatching (the
        opposite ordering from :meth:`_decode_round`): prompt-lookup
        proposals are a host data structure over the *committed*
        history, so chaining N+1's inputs from N's un-fetched outputs
        would mean proposing from a history K tokens stale — acceptance
        (and with it the entire speculation win) collapses, trading the
        very tokens/s the async loop must not regress for a closed
        dispatch gap. Commit-then-dispatch keeps proposals fresh and
        acceptance intact; the dispatch gap shrinks to accept+propose
        because publishing rides the worker. It also means a verify
        round needs NO lag-N reconciliation: the active set is computed
        after commit, so no garbage rows are ever dispatched — and the
        chain never deepens past one verify round regardless of
        ``max_commit_lag`` (draft-model proposals would go equally
        stale: the draft pool only advances at commit).

        Proposals come from prompt lookup over the slot's own committed
        history or, with a draft engine, from
        ``speculation.draft_propose`` — K chained draft decode
        forwards over the mirrored draft pool, all device-resident, the
        [S, K] token block built by device concatenation. Same aval,
        SAME verify executable, and greedy acceptance keeps the output
        exactly greedy either way."""
        chain = self._inflight
        prev_fetch = None
        if lag:
            # device credit in this round rides explicit spans ([step
            # begin → fetch] at commit, [dispatch → step end] via
            # pipelined())
            sp.pipelined_mode()
        if chain:
            prev_fetch = self._commit_verify_record(
                chain.popleft(), finished, sp)     # depth <= 1
            self._async_stats["pipelined_steps"] += 1
        K = self.spec_tokens
        S = self.num_slots
        use_draft = self.draft is not None
        tokens = np.zeros((S, K), np.int32)
        props: Dict[int, List[int]] = {}
        states: Dict[int, object] = {}
        for slot, state in self.scheduler.slots.items():
            if slot in self._mid_prefill:
                continue   # resident but still prefilling: not decoded
            if not use_draft:
                # proposal source = committed history ONLY (prompt +
                # every generated token incl. pending) — never the
                # speculative garbage beyond it, so a preempted slot's
                # requeue prompt (prompt + committed) replays the same
                # proposals. The LookupIndex makes this O(1) per step:
                # full build at the slot's first verify, tail-sync
                # after.
                entry = self._spec_hist.get(slot)
                if entry is None or entry[0] is not state:
                    idx = LookupIndex(state.request.prompt)
                    idx.extend(state.generated)
                    self._spec_hist[slot] = (state, idx)
                else:
                    idx = entry[1]
                    grown = (len(state.request.prompt)
                             + len(state.generated) - len(idx.hist))
                    if grown > 0:
                        idx.extend(state.generated[-grown:])
                prop = idx.proposals(K - 1)
                tokens[slot, 1:] = prop
                props[slot] = prop
            tokens[slot, 0] = state.pending
            states[slot] = state
        if not states:
            # every resident is mid-prefill, or the commit above retired
            # them all — nothing to dispatch (the caller's live=False
            # finish resets the gap)
            sp.mark("propose")
            return
        self.profiler_capture.step_begin()
        t0 = self._clock()
        if not lag:
            self._realize_chunk_span(sp, t0)   # see _decode_round
        # proposal scan ends, the batched verify dispatches (the
        # dispatch-gap boundary — see _decode_round)
        sp.mark("propose", now=t0, dispatch=True)
        if use_draft:
            tok_arg, props = self._draft_propose(states)
        else:
            tok_arg = jnp.asarray(tokens)
        t_toks, self._cache = self._verify_jit(
            self.engine.params, tok_arg, self._cache)
        sp.mark("dispatch", program=self._verify_jit.name)
        for _ in range(K if use_draft else 0):
            self._launched(self._draft_decode_jit, t0, None, len(states))
        rec = InFlightStep("verify", t_toks, states, t0, props=props,
                           prev_fetch=prev_fetch, ticket=self._launched(
                               self._verify_jit, t0, K, len(states)))
        if lag:
            if prev_fetch is None:      # nothing was in flight: a start
                self._async_stats["pipeline_starts"] += 1
                if self.watchdog is not None:
                    self.watchdog.notify_progress()  # a dispatch IS progress
            # device busy from this dispatch through the step's end (the
            # [step-begin → fetch] half was credited at commit)
            sp.pipelined(since=t0)
            chain.append(rec)
        else:
            self._commit_verify_record(rec, finished, sp, lagged=False)
        self.profiler_capture.step_end()

    def _commit_verify_record(self, rec: InFlightStep,
                              finished: List[int], sp=NULL_STEP_HANDLE,
                              discard_rid: Optional[int] = None,
                              lagged: bool = True) -> float:
        """Commit one verify round: fetch the target argmaxes,
        greedy-accept against the proposals the round was scored with,
        append/EOS-check/retire per surviving slot, advance lengths over
        the accepted prefixes in ONE vectorized update, and publish the
        round's metrics (inline for the lag-0 round that just dispatched
        it, through the worker for a ``lagged`` one). The verify wrote K
        candidate positions past each slot's live length without
        advancing it; commit = advance the length over the accepted
        prefix only, so rejected KV is never rolled back, just left as
        masked garbage the next round overwrites (the
        garbage-beyond-lengths invariant)."""
        in_step = sp is not NULL_STEP_HANDLE
        K = self.spec_tokens
        S = self.num_slots
        t_np = np.asarray(rec.tokens)       # host sync: the verify ran
        # proposals: per-slot host lists (prompt lookup) or one [S, K-1]
        # device array (draft model) — realize the latter once; rows
        # index identically either way and greedy_accept_host
        # int()-converts every committed token
        props_src = (rec.props if isinstance(rec.props, dict)
                     else np.asarray(rec.props))
        t1 = self._clock()
        self._proven(sp, rec.ticket, t1)
        if in_step and getattr(sp, "_pipelined_mode", False):
            sp.mark("sync_wait", now=t1, program=self._verify_jit.name)
            # device busy from step begin (the round was in flight
            # across the call boundary) until this fetch; 0.0 clamps to
            # the handle's begin. note_dispatch=False: the dispatch was
            # noted when the round left the host.
            sp.device_interval(0.0, t1, note_dispatch=False)
        elif in_step:
            # a lag-0 step (its own round, or the flush ahead of a host
            # action): the plain fetch-wait attribution (mode off — the
            # sliver credit IS the span)
            sp.mark("sync_wait", now=t1, fetch=True,
                    program=self._verify_jit.name)
        elif self._profiler is not None:
            self._profiler.note_fetch(t1)
        self._realize_chunk_span(sp, t1)
        dt = t1 - (rec.prev_fetch if rec.prev_fetch is not None
                   else rec.t_dispatch)
        if self._fi is not None:
            dt += self._fi.step_latency()
        adv = np.zeros((S,), np.int32)
        committed_total = 0
        accepted_total = 0
        n_live = 0
        per_slot_commits: List[int] = []
        retire: List[int] = []
        for slot, state in rec.states.items():
            if self.scheduler.slots.get(slot) is not state:
                self._async_stats["discarded_tokens"] += 1
                continue
            if (discard_rid is not None
                    and state.request.request_id == discard_rid):
                self._async_stats["discarded_tokens"] += 1
                continue
            m, committed = greedy_accept_host(t_np[slot],
                                              props_src[slot])
            accepted_total += m
            n_live += 1
            rt = (self._rt.get(state.request.request_id)
                  if self.tracer is not None else None)
            if rt is not None and rt.decode is not None:
                rt.steps += 1
            done = False
            n_committed = 0
            for tok in committed:
                state.generated.append(tok)
                n_committed += 1
                if rt is not None and rt.decode is not None:
                    rt.tokens += 1
                if self._finished(state, tok):
                    done = True
                    break
            committed_total += n_committed
            # collected PER SLOT-FORWARD (not a cross-slot step mean):
            # the histogram's distribution must expose per-slot
            # acceptance skew — one lookup-friendly request carrying an
            # otherwise-collapsed batch shows as {K, 1, 1, 1}, not 1.75
            per_slot_commits.append(n_committed)
            # a continuing slot's cache gains [pending, p_1..p_m]; the
            # correction becomes the next pending (its KV, like any
            # pending token's, is written by the NEXT verify). A
            # retiring slot's lengths are reset right below, so its
            # adv value never matters.
            adv[slot] = n_committed
            if self._ledger is not None:
                rid_ = state.request.request_id
                self._ledger.add_weight(rid_, n_committed)
                self._ledger.note_spec(rid_, K - 1, m)
            if done:
                retire.append(slot)
            else:
                state.pending = committed[-1]
        self._cache = self._cache.replace(
            lengths=self._cache.lengths + jnp.asarray(adv))
        if self._draft_cache is not None:
            # the proposal round advanced the draft pool by K per active
            # slot in-graph; reconcile each surviving slot to the
            # committed prefix (adv - K <= 0) so both pools agree on
            # every live length. Discarded/identity-dead rows stay at
            # base+K until _reset_slot_arrays zeroes them — and this
            # runs BEFORE the retire loop, which does exactly that for
            # this round's finishers.
            d_adj = np.zeros((S,), np.int32)
            for slot, state in rec.states.items():
                if self.scheduler.slots.get(slot) is not state:
                    continue
                if (discard_rid is not None
                        and state.request.request_id == discard_rid):
                    continue
                d_adj[slot] = int(adv[slot]) - K
            self._draft_cache = self._draft_cache.replace(
                lengths=self._draft_cache.lengths + jnp.asarray(d_adj))
        for slot in retire:
            self._retire(slot, self.scheduler.slots[slot], finished)
        if in_step:
            sp.mark("commit")
        if n_live == 0:
            self._async_stats["garbage_steps"] += 1
            return t1
        self._step_clock += 1
        self._active_slot_steps += n_live
        proposed = n_live * (K - 1)
        self._spec_proposed += proposed
        self._spec_accepted += accepted_total
        self._spec_committed += committed_total
        self._spec_steps += 1
        self._spec_slot_steps += n_live
        self._maybe_spec_collapse(proposed, accepted_total)
        # per-token latency keeps meaning "wall per committed token per
        # slot" under speculation
        self._publish(self._publish_verify_step, lagged, dt, n_live,
                      committed_total, proposed, accepted_total,
                      per_slot_commits, *self._pool_reading())
        if self.watchdog is not None:
            self.watchdog.notify_progress()
        if self._step_clock % self._EVENT_EVERY == 1:
            get_event_ring().record(
                telemetry_events.STEP_END, source="serve_spec_verify",
                step=self._step_clock, live=n_live,
                committed=committed_total, accepted=accepted_total,
                seconds=round(dt, 6), pipelined=lagged,
                sampled_every=self._EVENT_EVERY)
        return t1

    def _publish_verify_step(self, dt: float, n_live: int,
                             committed_total: int, proposed: int,
                             accepted: int,
                             per_slot_commits: List[int],
                             used_blocks: int, blocked: bool) -> None:
        """Metric publish for one committed verify round (see
        :meth:`_publish_decode_step`)."""
        self._h_decode_step.observe(dt)
        self._h_token.observe(dt * n_live / max(committed_total, 1))
        self._c_decode_steps.inc()
        self._c_tokens.inc(committed_total)
        self._g_occupancy.set(n_live / self.num_slots)
        self._c_spec_proposed.inc(proposed)
        self._c_spec_accepted.inc(accepted)
        for n in per_slot_commits:
            self._h_spec_commit.observe(n)
        self._publish_pool(used_blocks, blocked)

    # one worker job per this many buffered step records (see _pub_buf)
    _PUBLISH_BATCH = 16

    def _publish(self, publisher, lagged: bool, *vals) -> None:
        """One committed step's metrics: inline for a lag-0 commit,
        buffered for the worker thread for a lagged one (the device is
        waiting on this thread's next dispatch)."""
        if not lagged:
            publisher(*vals)
            return
        self._pub_buf.append((publisher, vals))
        if len(self._pub_buf) >= self._PUBLISH_BATCH:
            self._ship_publish_buf()

    def _ship_publish_buf(self) -> None:
        """Hand the buffered step records to the worker as ONE job."""
        if not self._pub_buf:
            return
        buf, self._pub_buf = self._pub_buf, []

        def job():
            for publisher, vals in buf:
                publisher(*vals)

        self._worker.submit(job)

    def _drain_publishing(self) -> None:
        """Every buffered and queued publish lands in the registry —
        called at each flush point so no readable surface ever sees a
        half-published step."""
        self._ship_publish_buf()
        self._worker.drain()

    def _flush_pipeline(self, finished: List[int], sp=NULL_STEP_HANDLE,
                        reason: str = "",
                        discard_rid: Optional[int] = None) -> None:
        """Commit whatever is in flight and drain the publish worker —
        the bounded flush every host-driven state change pays so the
        scheduler (and anyone reading results/metrics afterwards) acts
        on committed state. Bounded by construction: the chain holds at
        most ``max_commit_lag`` in-flight steps, committed here oldest
        first (each commit rethreads the next record's prev_fetch so
        per-step gap attribution stays honest across the drain)."""
        if self._inflight:
            depth = len(self._inflight)
            t_flush = self._clock()
            while self._inflight:
                rec = self._inflight.popleft()
                if rec.kind == "decode":
                    t1 = self._commit_decode_record(
                        rec, finished, sp, discard_rid=discard_rid)
                else:
                    t1 = self._commit_verify_record(
                        rec, finished, sp, discard_rid=discard_rid)
                if self._inflight:
                    self._inflight[0].prev_fetch = t1
            fl = self._async_stats["flushes"]
            fl[reason] = fl.get(reason, 0) + 1
            fd = self._async_stats["flush_depths"].setdefault(reason, {})
            fd[depth] = fd.get(depth, 0) + 1
            if sp is not NULL_STEP_HANDLE:
                sp.flush_span(t_flush, reason, depth)
            elif self._profiler is not None:
                # out-of-step flush (cancel / drain / close between
                # steps): no step to parent it
                self._profiler.span_log.record(
                    FLUSH_SPAN, t_flush, self._clock(),
                    attrs={"reason": reason, "programs": depth})
        self._drain_publishing()

    def _launched(self, program, t0: Optional[float],
                  bucket: Optional[int] = None, rows: int = 0,
                  prompt_tokens: int = 0) -> int:
        """Note one launch of a watched program for its ``serve:program``
        record (``StepProfiler.program_launched``): the ticket its fetch
        names, 0 with the step profile off."""
        if self._profiler is None:
            return 0
        return self._profiler.program_launched(
            program.name, t0, bucket, rows, prompt_tokens)

    def _proven(self, sp, ticket: int, t1: float) -> None:
        """The fetch that returned at ``t1`` proves program ``ticket``
        finished; called BEFORE the mark that closes the wait. Between
        steps (no handle) how long the host blocked is not known."""
        if sp is not NULL_STEP_HANDLE:
            sp.program_fetched(ticket, t1)
        elif self._profiler is not None:
            self._profiler.program_fetched(ticket, t1)

    def _fetch_tokens(self, tokens) -> np.ndarray:
        """Fetch of a program's sampled tokens where no later program
        is in flight (a monolithic prefill, a lag-0 decode step).
        A model of another family's programs count on the device as
        they run (``cache.aux``, the model's own, cumulative); the array
        comes over in the same ``device_get`` as the tokens, after the
        same wait, and its growth goes to the registry."""
        if self._aux_series is None:
            return np.asarray(tokens)
        got, aux = jax.device_get((tokens, self._cache.aux))
        self._publish_aux(aux)
        return got

    def _publish_aux(self, aux=None) -> None:
        """Publish what the model's device counters grew by since the
        last call, each cell to the series its module named. Without
        ``aux`` the array is read from the pool: only where no program
        is in flight (``stats``, ``close``)."""
        if self._aux_series is None:
            return
        if aux is None:
            aux = np.asarray(self._cache.aux)
        grew = aux - self._aux_seen
        self._aux_seen = aux
        for p, col in zip(*np.nonzero(grew)):
            self._aux_series[p][col].inc(float(grew[p, col]))

    def _maybe_spec_collapse(self, proposed: int, accepted: int) -> None:
        """Ring-event an acceptance-rate collapse ONCE per episode: over
        the rolling window, enough proposal volume with near-zero
        acceptance means every verify forward is wasted width — the
        operator should turn speculation off (or the workload changed
        under them). Re-arms after the rate recovers."""
        self._spec_window.append((proposed, accepted))
        p = sum(w[0] for w in self._spec_window)
        if p < self._SPEC_MIN_PROPOSED:
            return
        rate = sum(w[1] for w in self._spec_window) / p
        if not self._spec_alarm and rate < self._SPEC_COLLAPSE_RATE:
            self._spec_alarm = True
            get_event_ring().record(
                telemetry_events.SPEC_COLLAPSE,
                acceptance_rate=round(rate, 4),
                window_steps=len(self._spec_window), proposed=p,
                k=self.spec_tokens)
        elif self._spec_alarm and rate >= self._SPEC_RECOVER_RATE:
            self._spec_alarm = False

    def result(self, request_id: int) -> Optional[List[int]]:
        """Finished output (prompt + generated, EOS included) or None.
        Lifecycle-terminated requests (``cancelled`` / ``deadline`` /
        ``shed`` / ``failed`` in ``finish_reasons``) return their
        partial output — prompt plus whatever was committed."""
        return self._results.get(request_id)

    def finish_reason(self, request_id: int) -> Optional[str]:
        """``eos`` / ``length`` / ``cancelled`` / ``deadline`` /
        ``shed`` / ``failed``, or None while unfinished."""
        return self.finish_reasons.get(request_id)

    def drain(self, timeout_s: Optional[float] = None
              ) -> Dict[int, List[int]]:
        """Run ``step`` until queue and slots are empty; returns all
        finished outputs keyed by request id.

        ``timeout_s`` bounds the drain on the server clock: past it,
        every still-unfinished request is cancelled (finish reason
        ``cancelled``, partial results returned) — a single wedged slot
        can no longer spin the process forever. ``timeout_s=0`` cancels
        immediately; None preserves the unbounded behavior."""
        check_drain_timeout(timeout_s)
        deadline = None if timeout_s is None \
            else self._clock() + timeout_s
        while not self.scheduler.idle:
            if deadline is not None and self._clock() >= deadline:
                get_event_ring().record(
                    telemetry_events.CANCEL, source="drain_timeout",
                    timeout_s=timeout_s,
                    stragglers=(self.scheduler.pending_requests
                                + self.scheduler.active_slots))
                for req in list(self.scheduler.queue):
                    self.cancel(req.request_id)
                for state in list(self.scheduler.slots.values()):
                    self.cancel(state.request.request_id)
                break
            self.step()
        # the drain loop exits the moment the scheduler empties, which
        # under the async loop can leave up to max_commit_lag garbage
        # steps in flight (dispatched beside the final commits): fetch +
        # discard them and drain the publish worker, so a drained server
        # has no device work outstanding and fully-published metrics
        self._flush_pipeline(self._deferred_finished, reason="drain")
        if self._ledger is not None:
            # drained = no further worked step is coming: emit every
            # pending-close cost record NOW so the histograms/ring a
            # post-drain reader scrapes are complete
            self._ledger.flush_pending()
        return dict(self._results)

    def dump_timeline(self, path: str) -> int:
        """Write the kept request traces plus the flight recorder's
        decode-step / compile events as Chrome trace-event JSON — load
        in Perfetto (ui.perfetto.dev) or chrome://tracing to see where
        each request's time went AND what the device was doing
        meanwhile. Returns the emitted event count."""
        if self.tracer is None:
            raise RuntimeError(
                "request tracing is off — set telemetry."
                "trace_sample_rate > 0 (docs/observability.md "
                "'Request tracing & SLOs')")
        prof = self._profiler
        return self.tracer.dump_timeline(
            path, event_ring=get_event_ring(),
            span_log=prof.span_log if prof is not None else None,
            profiler_pids={prof.uid: 3} if prof is not None else None)

    def capture_decode_steps(self, num_steps: int, logdir: str) -> None:
        """Arm an on-demand ``jax.profiler`` capture: the next
        ``num_steps`` decode steps are traced to ``logdir`` (view with
        TensorBoard's profile plugin or Perfetto). Host-side arming only
        — until the next ``step()`` nothing changes, and the serving loop
        never pays for an idle hook (see telemetry/capture.py)."""
        self.profiler_capture.arm(num_steps, logdir)

    def close(self) -> None:
        """Release the scrape endpoint, the watchdog thread, and the
        memory-monitor registrations (if config armed them). Idempotent,
        and safe on a server in ANY health state — a supervising
        frontend tears replicas down wedged, stalled, or mid-pipeline
        (docs/serving.md "Replicated serving & failover")."""
        if self._closed:
            return
        self._closed = True
        # detach + disarm the stall watchdog BEFORE the teardown flush:
        # committing the stale in-flight step below notifies progress,
        # which would RE-ARM a watchdog that already fired on this very
        # stall — its checker thread (alive until stopped) could then
        # dump the same stall's event ring a second time mid-teardown
        wd, self.watchdog = self.watchdog, None
        if wd is not None:
            wd.disarm()
            wd.stop()
        if self.http_server is not None:
            self.http_server.close()
            self.http_server = None
        if self._host_mem_getter is not None:
            from deepspeed_tpu.telemetry.memory import get_memory_monitor
            get_memory_monitor().unregister_component(
                "kv_host_tier", self._host_mem_getter)
            self._host_mem_getter = None
        # commit whatever is still in flight: a close() without a
        # drain() must not silently drop a pipelined step's committed
        # tokens, finishes, or metrics
        self._flush_pipeline(self._deferred_finished, reason="close")
        self._publish_aux()
        if self._ledger is not None:
            self._ledger.flush_pending()
        self._worker.close()
        self._flight.close()

    # ------------------------------------------------------------ stats

    @property
    def stats(self) -> dict:
        """Serving telemetry. ``decode_step_slot_units`` is the honest
        static-shape cost metric (every decode step computes all
        num_slots rows, live or idle); ``slot_occupancy`` is the fraction
        of those units that carried a live sequence — the number
        continuous batching exists to push toward 1.0."""
        # owner-thread read: flush buffered publishes + drain the
        # worker first so every registry instrument agrees with the
        # host mirrors below
        self._drain_publishing()
        self._publish_aux()
        units = self._step_clock * self.num_slots
        alloc = self.scheduler.allocator
        return {
            "decode_steps": self._step_clock,
            "prefills": self._prefills,
            "prefill_chunks": self._prefill_chunks,
            "prefill_token_units": self._prefill_token_units,
            "decode_step_slot_units": units,
            "active_slot_steps": self._active_slot_steps,
            "slot_occupancy": (self._active_slot_steps / units
                               if units else 0.0),
            "decode_traces": _safe_cache_size(self._decode_jit),
            "prefill_traces": _safe_cache_size(self._prefill_jit),
            # the decode program that also prefills an admitted prompt
            # (0 where the family has no paged_decode_admit or a mode
            # keeps it off), and how each admission was prefilled
            "decode_admit_traces": (_safe_cache_size(self._admit_jit)
                                    if self._admit_jit is not None else 0),
            "admissions": dict(self._admissions),
            "chunk_traces": (_safe_cache_size(self._chunk_jit)
                             if self._chunk_jit is not None else 0),
            "retraces": (
                len(getattr(self._decode_jit, "retraces", ()))
                + len(getattr(self._prefill_jit, "retraces", ()))
                + (len(getattr(self._admit_jit, "retraces", ()))
                   if self._admit_jit is not None else 0)
                + (len(getattr(self._chunk_jit, "retraces", ()))
                   if self._chunk_jit is not None else 0)
                + (len(getattr(self._verify_jit, "retraces", ()))
                   if self._verify_jit is not None else 0)),
            "num_slots": self.num_slots,
            "block_size": self.block_size,
            "role": self.role,
            "free_blocks": alloc.free_blocks,
            "queued": self.scheduler.pending_requests,
            "prefix_caching": self.prefix_caching,
            "prefill_chunk_tokens": self.chunk_tokens,
            "prefix_cache_hits": self.scheduler.prefix_hits,
            "prefix_cache_misses": self.scheduler.prefix_misses,
            "prefix_cached_blocks": alloc.cached_blocks,
            "prefix_cache_evictions": alloc.evictions,
            "prefix_tokens_skipped": self._prefix_tokens_skipped,
            "tail_blocks_reclaimed": self._tail_reclaimed,
            # lifecycle (docs/serving.md "Request lifecycle & overload
            # behavior")
            "cancelled": self._lifecycle_counts["cancelled"],
            "deadline_expired": self._lifecycle_counts["deadline"],
            "preempted": self._lifecycle_counts["preempted"],
            "shed": self._lifecycle_counts["shed"],
            "failed": self._lifecycle_counts["failed"],
            "requeue_depth": self.scheduler.requeue_depth,
            # speculation (docs/serving.md "Per-slot speculative
            # decoding"): tokens_per_forward is THE number that decides
            # whether the verify width pays for itself (1.0 = nothing
            # won; up to speculation_tokens on full acceptance)
            "speculation": {
                "k": self.spec_tokens,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "acceptance_rate": round(
                    self._spec_accepted / self._spec_proposed, 4)
                if self._spec_proposed else None,
                "verify_steps": self._spec_steps,
                "committed_tokens": self._spec_committed,
                "tokens_per_forward": round(
                    self._spec_committed / self._spec_slot_steps, 3)
                if self._spec_slot_steps else None,
                "verify_traces": (_safe_cache_size(self._verify_jit)
                                  if self._verify_jit is not None else 0),
                "draft": ("model" if self.draft is not None
                          else "prompt-lookup"),
                "draft_prefill_traces": (
                    _safe_cache_size(self._draft_prefill_jit)
                    if self._draft_prefill_jit is not None else 0),
                "draft_decode_traces": (
                    _safe_cache_size(self._draft_decode_jit)
                    if self._draft_decode_jit is not None else 0),
            },
            # KV tiering (docs/serving.md "KV quantization & host
            # tiering"): storage dtype, device pool bytes (scales
            # included), and the host tier's residency + swap traffic
            "kv_tier": {
                "kv_dtype": self.kv_dtype,
                "pool_bytes": int(sum(
                    a.nbytes for a in pool_arrays(self._cache))),
                "host_offload": (self.host_tier is not None
                                 and not self._import_only_tier),
                "host_blocks": (len(self.host_tier)
                                if self.host_tier is not None else 0),
                "host_bytes": (self.host_tier.host_bytes
                               if self.host_tier is not None else 0),
                "host_dropped": (self.host_tier.dropped
                                 if self.host_tier is not None else 0),
                "demotions": alloc.demotions,
                "swap_ins": alloc.swap_ins,
                "thrash_alarm": self._swap_alarm,
            },
            "fault_injection": (self._fi.snapshot()
                                if self._fi is not None else None),
            # async dispatch loop (docs/serving.md "Async dispatch
            # loop"): pipeline state, flush forensics by reason (and by
            # chain depth at the flush), lag-N reconciliation counters,
            # and the publish worker's queue
            "async_loop": {
                "enabled": self._async,
                "commit_lag": len(self._inflight),
                "max_commit_lag": self._max_lag,
                "prefill_chain": self._prefill_chain,
                "pipeline_starts": self._async_stats["pipeline_starts"],
                "pipelined_steps": self._async_stats["pipelined_steps"],
                "flushes": dict(self._async_stats["flushes"]),
                "flush_depths": {
                    reason: {str(d): n for d, n in sorted(depths.items())}
                    for reason, depths in sorted(
                        self._async_stats["flush_depths"].items())},
                "discarded_tokens":
                    self._async_stats["discarded_tokens"],
                "garbage_steps": self._async_stats["garbage_steps"],
                "worker": self._worker.snapshot(),
            },
            # serving step observatory + KV-pool accounting
            # (docs/observability.md "Serving goodput & KV-pool
            # accounting"); None = telemetry.step_profile off
            "step_profile": (self._profiler.snapshot()
                             if self._profiler is not None else None),
            "kv_pool": (self._pool_snapshot()
                        if self._pool_acct is not None else None),
            "traces_started": (self.tracer.started
                               if self.tracer is not None else 0),
            "traces_kept": (self.tracer.kept
                            if self.tracer is not None else 0),
            "slo_compliance": (self.slo.compliance_ratio
                               if self.slo is not None else None),
            # request-level cost accounting + live capacity model
            # (docs/observability.md "Cost accounting & capacity");
            # None = accounting off (report-only either way)
            "accounting": (self._ledger.snapshot()
                           if self._ledger is not None else None),
            "capacity": (self._capacity.snapshot()
                         if self._capacity is not None else None),
            # SLO alerting + canary + incident bundles (docs/
            # observability.md "SLOs, alerting & incidents"); None =
            # the closed loop is unarmed
            "alerts": (self.alerts.snapshot()
                       if self.alerts is not None else None),
            "canary": (self.canary.snapshot()
                       if self.canary is not None else None),
            "incidents": (self.incidents.snapshot()
                          if self.incidents is not None else None),
        }
