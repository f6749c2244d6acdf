#!/usr/bin/env python
"""Serve an HF checkpoint directory with TP / int8 / MoE knobs.

One-shot generation:

  python examples/serve_hf_model.py /path/to/gpt2-checkpoint \
      --dtype int8 --prompt-ids "1,2,3,4"

Continuous batching (asynchronous arrivals through the paged-KV
ContinuousBatchingServer — docs/serving.md "Continuous batching"):

  python examples/serve_hf_model.py /path/to/gpt2-checkpoint \
      --continuous 12 --num-slots 4 --max-new-tokens 32
"""
import argparse


def _tenant_cycle(args):
    if not getattr(args, "tenants", None):
        return None
    return [t.strip() for t in args.tenants.split(",") if t.strip()] \
        or None


def _print_cost(st):
    """Cost ledger + per-tenant metering table (docs/observability.md
    "Cost accounting & capacity") — works off either a server's stats
    (ledger snapshot) or a frontend's (merged-bill view)."""
    acct = st.get("accounting")
    if not acct or not acct.get("enabled"):
        return
    billed = acct.get("closed_records", acct.get("requests_billed", 0))
    head = f"cost ledger: {billed} bills"
    if acct.get("device_s_total") is not None:
        head += (f", {acct['device_s_total']:.3f} device-s attributed "
                 f"(unattributed carry "
                 f"{acct['residual_carry_s']:.2e} s)")
    print(head)
    ten = acct.get("tenants") or {}
    if ten:
        print(f"  {'tenant':<14}{'requests':>9}{'tok_in':>8}"
              f"{'tok_out':>9}{'device_s':>10}{'rejected':>9}")
        for name in sorted(ten):
            row = ten[name]
            dev = row.get("serve_tenant_device_seconds_total", 0.0)
            print(
                f"  {name:<14}"
                f"{int(row.get('serve_tenant_requests_total', 0)):>9}"
                f"{int(row.get('serve_tenant_tokens_in_total', 0)):>8}"
                f"{int(row.get('serve_tenant_tokens_out_total', 0)):>9}"
                f"{dev:>10.3f}"
                f"{int(row.get('serve_tenant_rejections_total', 0)):>9}")
    cap = st.get("capacity") or {}
    cap = cap.get("pool", cap)      # frontend nests the rollup
    if cap.get("enabled"):
        tps = cap.get("tokens_per_s")
        adm = cap.get("admissible_requests_per_s")
        print(f"capacity: occupancy {cap.get('slot_occupancy')}, "
              f"block utilization {cap.get('block_utilization')}, "
              f"{'-' if tps is None else round(tps, 1)} tok/s in "
              f"window, admissible "
              f"{'-' if adm is None else round(adm, 2)} req/s")


def _hook_alert_prints(owner):
    """Chain a live print in FRONT of the owner's own fire/resolve
    hooks (which capture incident bundles), so a --slo run narrates
    every rule transition the moment it happens."""
    al = getattr(owner, "alerts", None)
    if al is None:
        return

    def _noisy(label, chain):
        def cb(rule, info):
            print(f"  ALERT {label}: {rule} [{info.get('signal')}] "
                  f"fast {info.get('observed_fast')} / slow "
                  f"{info.get('observed_slow')} vs threshold "
                  f"{info.get('threshold')}")
            if chain is not None:
                chain(rule, info)
        return cb

    al._on_fire = _noisy("firing", al._on_fire)
    al._on_resolve = _noisy("resolved", al._on_resolve)


def _print_slo_loop(owner, args):
    """Post-drain closed-loop report + forensic bundle dump."""
    al = getattr(owner, "alerts", None)
    if al is None:
        return
    snap = al.snapshot()
    line = (f"  slo loop: {snap['fired_total']} alert(s) fired, "
            f"{snap['resolved_total']} resolved")
    canary = getattr(owner, "canary", None)
    if canary is not None:
        cs = canary.snapshot()
        line += (f"; canary {cs['probes']} probes, success "
                 f"{cs['success_ratio']}, p90 {cs['latency_p90_ms']} ms")
    print(line)
    if getattr(owner, "incidents", None) is not None:
        inc = owner.incidents.snapshot()
        print(f"  incidents: {inc['captured_total']} captured, "
              f"{inc['suppressed_total']} suppressed within episodes")
        bundle = owner.dump_incident("slo_incident_bundle.json")
        print(f"  forensic bundle ({len(bundle)} sections) -> "
              "slo_incident_bundle.json")


def run_replicated(eng, prompt, args):
    """Drive a --replicas N pool end-to-end through the ServingFrontend
    (docs/serving.md "Replicated serving & failover"): staggered
    arrivals, optional seeded chaos (a mid-decode replica kill plus the
    per-server wedge/prefill faults), bounded drain, and the per-replica
    health/routing/failover report."""
    from deepspeed_tpu.inference.frontend import ServingFrontend
    fi = None
    if args.chaos:
        # seeded pool-level chaos: one seeded-chosen replica is killed
        # mid-decode at frontend tick 6 (its work fails over and still
        # finishes exactly), every 5th request wedges, prefills
        # occasionally die — the pool degrades; nothing is lost
        from deepspeed_tpu.telemetry import FaultInjector
        fi = FaultInjector(seed=0, wedge_nth_request=5,
                           prefill_failure_rate=0.1, replica_kill_step=6)
    front = ServingFrontend(eng, fault_injector=fi)
    _hook_alert_prints(front)
    tenants = _tenant_cycle(args)
    ids = []
    for i in range(args.continuous):
        if args.roles:
            # disaggregation demo: full-length distinct prompts — the
            # handoff publishes FULL blocks (a sub-block prompt has
            # nothing block-aligned to hand off and recomputes on the
            # decode side, exact but unspectacular)
            p = [1 + (i + j) % 90 for j in range(len(prompt))]
        else:
            p = prompt[: 1 + i % len(prompt)]
        ids.append(front.submit(p, max_new_tokens=2 + args.max_new_tokens
                                * (i % 3) // 2,
                                deadline_s=args.deadline_s,
                                priority=i % 2 if args.chaos else 0,
                                tenant=(tenants[i % len(tenants)]
                                        if tenants else None)))
        front.step()
    out = front.drain(timeout_s=60.0 if args.chaos else None)
    for rid in ids:
        reason = front.finish_reason(rid)
        tag = "" if reason in ("eos", "length") else f"  [{reason}]"
        print(f"request {rid}: {out.get(rid)}{tag}")
    st = front.stats
    print(f"pool: {st['healthy_replicas']}/{len(st['replicas'])} "
          f"replicas healthy, {st['failovers']} failovers, "
          f"{st['failover_replay_tokens']} replay tokens, "
          f"{st['drain_reroutes']} drain re-routes")
    if st["disaggregated"]:
        hf = st["handoff"]
        print(f"  roles {st['roles']}: {st['handoffs']} handoffs, "
              f"{hf['published']} blocks published / {hf['consumed']} "
              f"consumed / {hf['expired']} expired, "
              f"{hf['blocks']} parked")
    for row in st["replicas"]:
        dead = (f" ({row['dead_reason']})"
                if row["dead_reason"] else "")
        extra = ""
        if st["disaggregated"]:
            extra = (f", swap-ins {row.get('host_tier_swap_ins', 0)}, "
                     f"gap {row.get('recent_gap_ms', 0.0)} ms")
        stale = row.get("scrape_staleness_s")
        print(f"  replica {row['replica']} [{row['role']}]: "
              f"{row['health']}{dead} — routed {row['routed']}, "
              f"steps {row['steps']}, "
              f"failovers-from {row['failovers_from']}{extra}"
              + (f", scrape stale {stale}s" if stale else ""))
    # fleet observability (docs/observability.md "Fleet observability"):
    # hop routing by cause plus the stitched-trace state; with
    # --trace-dump the merged fleet timeline lands next to the
    # per-server one — every replica as its own Perfetto process group,
    # flow arrows joining a request's legs across them
    hops = st["hops_by_cause"]
    print(f"  fleet: stitching {'on' if st['stitching'] else 'off'}, "
          f"hops " + ", ".join(f"{c}={n}" for c, n in hops.items()
                               if n or c == "submit"))
    _print_cost(st)
    _print_slo_loop(front, args)
    if args.trace_dump and st["stitching"]:
        path = args.trace_dump + ".fleet.json"
        n = front.dump_timeline(path)
        print(f"  fleet timeline: {n} events -> {path} "
              "(load in ui.perfetto.dev)")
    if front.http_server is not None:
        port = front.http_server.port
        input(f"pool state at http://127.0.0.1:{port}/debug/replicas, "
              f"fleet rollup at /debug/fleet, federated scrape at "
              f"/metrics — press Enter to exit")
    front.close()


def run_continuous(eng, prompt, args):
    """Replay --continuous staggered arrivals: submit a new request
    every other scheduler step, drain, report per-request outputs and
    the slot-recycling telemetry."""
    from deepspeed_tpu.inference.server import ContinuousBatchingServer
    fi = None
    if args.chaos:
        # deterministic chaos demo (telemetry/faultinject.py): every
        # 5th request wedges (reaped by --deadline-s or the bounded
        # drain below) and prefills occasionally die — the lifecycle
        # layer degrades; the process survives
        from deepspeed_tpu.telemetry import FaultInjector
        fi = FaultInjector(seed=0, wedge_nth_request=5,
                           prefill_failure_rate=0.1)
    srv = ContinuousBatchingServer(eng, fault_injector=fi)
    _hook_alert_prints(srv)
    tenants = _tenant_cycle(args)
    ids = []
    for i in range(args.continuous):
        if srv.prefix_caching:
            # shared-prefix workload: every request reuses the full
            # prompt as its system prefix + a tiny distinct tail, so
            # the prefix cache has something to hit after request 0
            p = prompt + [(i * 7 + t) % 90 + 1 for t in range(1 + i % 3)]
        else:
            # vary lengths so slots recycle at different times
            p = prompt[: 1 + i % len(prompt)]
        # mixed priorities only under --chaos: a plain demo run stays
        # pure-FIFO and lossless (no preemption, nothing ever 'failed')
        ids.append(srv.submit(p, max_new_tokens=2 + args.max_new_tokens
                              * (i % 3) // 2,
                              deadline_s=args.deadline_s,
                              priority=i % 2 if args.chaos else 0,
                              tenant=(tenants[i % len(tenants)]
                                      if tenants else None)))
        srv.step()   # arrivals interleave with decoding
    # chaos mode needs the bounded drain — a wedged slot would spin the
    # unbounded loop forever (docs/serving.md "Request lifecycle")
    out = srv.drain(timeout_s=60.0 if args.chaos else None)
    for rid in ids:
        reason = srv.finish_reason(rid)
        tag = "" if reason in ("eos", "length") else f"  [{reason}]"
        print(f"request {rid}: {out.get(rid)}{tag}")
    st = srv.stats
    if any(st[k] for k in ("cancelled", "deadline_expired", "preempted",
                           "shed", "failed")):
        print(f"lifecycle: {st['cancelled']} cancelled, "
              f"{st['deadline_expired']} deadline-expired, "
              f"{st['preempted']} preempted, {st['shed']} shed, "
              f"{st['failed']} failed")
    print(f"decode steps {st['decode_steps']}, occupancy "
          f"{st['slot_occupancy']:.2f}, traces {st['decode_traces']}")
    al = st["async_loop"]
    lag = al.get("max_commit_lag", 1) if al["enabled"] else 1
    print(f"async loop: {'on' if al['enabled'] else 'off (sync)'}"
          + (f" (lag {lag})" if lag > 1 else "") + " — "
          f"{al['pipelined_steps']} pipelined steps, "
          f"{sum(al['flushes'].values())} flushes, "
          f"{al['discarded_tokens']} in-flight tokens discarded, "
          f"worker published {al['worker']['published']}")
    if st["prefix_caching"]:
        print(f"prefix cache: {st['prefix_cache_hits']} hits / "
              f"{st['prefix_cache_misses']} misses, "
              f"{st['prefix_tokens_skipped']} prefill tokens skipped, "
              f"{st['prefix_cached_blocks']} blocks cached")
    if st["prefill_chunk_tokens"]:
        chained = al["enabled"] and al.get("prefill_chain")
        print(f"chunked prefill{' (chained)' if chained else ''}: "
              f"{st['prefill_chunks']} chunks of "
              f"{st['prefill_chunk_tokens']} tokens, "
              f"{st['chunk_traces']} trace(s)")
    kt = st["kv_tier"]
    if kt["kv_dtype"] != "fp" or kt["host_offload"]:
        print(f"kv tier: {kt['kv_dtype']} pool "
              f"({kt['pool_bytes'] / 2**20:.1f} MiB), host offload "
              f"{'on' if kt['host_offload'] else 'off'} — "
              f"{kt['demotions']} demoted / {kt['swap_ins']} swapped "
              f"in, {kt['host_blocks']} blocks "
              f"({kt['host_bytes'] / 2**20:.2f} MiB) on host"
              + (", THRASHING" if kt["thrash_alarm"] else ""))
    if args.step_profile and st["step_profile"] is not None:
        spf = st["step_profile"]
        wall = max(spf["wall_s"], 1e-12)
        print(f"step profile: {spf['steps']} steps, "
              f"goodput {spf['goodput_fraction']:.3f} "
              f"(host tax {spf['host_fraction']:.3f})")
        for ph, secs in sorted(spf["phases_s"].items(),
                               key=lambda kv: -kv[1]):
            print(f"  {ph:<14} {secs * 1e3:9.2f} ms  "
                  f"({secs / wall:6.1%} of wall)")
        gap = spf["dispatch_gap"]
        print(f"  dispatch gap: {gap['count']} gaps, total "
              f"{gap['total_s'] * 1e3:.2f} ms, max "
              f"{gap['max_s'] * 1e3:.2f} ms (device idle between "
              "fetch and next dispatch)")
        pool = st["kv_pool"]
        print(f"  kv pool: free-run ratio "
              f"{pool['free_longest_run_ratio']:.3f}, "
              f"{pool['famine_episodes']} famine episode(s)")
    sp = st["speculation"]
    if sp["k"]:
        print(f"speculation (K={sp['k']}, {sp['draft']}): "
              f"{sp['tokens_per_forward']} tokens/forward, acceptance "
              f"{sp['acceptance_rate']}, {sp['committed_tokens']} "
              f"tokens over {sp['verify_steps']} verify steps, "
              f"{sp['verify_traces']} trace(s)")
    _print_cost(st)
    # registry view of the same run (docs/observability.md)
    snap = srv.telemetry.snapshot()
    for h in ("serve_ttft_seconds", "serve_queue_wait_seconds",
              "serve_token_seconds"):
        s = snap[h]["series"][0]
        print(f"{h}: n={s['count']} p50={s['p50'] * 1e3:.2f}ms "
              f"p90={s['p90'] * 1e3:.2f}ms")
    if srv.tracer is not None:
        print(f"request tracing: {srv.tracer.kept}/"
              f"{srv.tracer.started} traces kept")
        if args.trace_dump:
            n = srv.dump_timeline(args.trace_dump)
            print(f"timeline: {n} trace events -> {args.trace_dump} "
                  "(load in ui.perfetto.dev or chrome://tracing)")
    if srv.slo is not None:
        res = srv.slo.evaluate()
        print(f"SLO compliance: {srv.slo.compliance_ratio:.2f}")
        for name, r in res.items():
            obs = ("n/a" if r["observed"] is None
                   else f"{r['observed']:.4f}")
            state = "VIOLATED" if r["violated"] else "ok"
            print(f"  {name}: observed {obs} vs target "
                  f"{r['target']} [{state}]")
    _print_slo_loop(srv, args)
    if srv.http_server is not None:
        port = srv.http_server.port
        input(f"scrape endpoint live at http://127.0.0.1:{port}/metrics "
              "— press Enter to exit")
        srv.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("path", help="HF checkpoint dir (config.json + "
                                 "safetensors/bin) or nothing to demo "
                                 "with a random tiny model")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--num-beams", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-p", type=float, default=0.0)
    ap.add_argument("--repetition-penalty", type=float, default=1.0)
    ap.add_argument("--prompt-ids", default="1,2,3,4",
                    help="comma-separated token ids (no tokenizer dep)")
    ap.add_argument("--continuous", type=int, default=0, metavar="N",
                    help="serve N staggered requests through the "
                         "continuous-batching server instead of one "
                         "one-shot generate (greedy)")
    ap.add_argument("--num-slots", type=int, default=None,
                    help="resident sequences per decode step "
                         "(continuous mode)")
    ap.add_argument("--block-size", type=int, default=None,
                    help="paged KV pool block size (continuous mode)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="open a Prometheus/JSON scrape endpoint on this "
                         "port (continuous mode; docs/observability.md)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="automatic prefix caching: shared block-aligned "
                         "prompt prefixes prefill once and are reused by "
                         "refcount (continuous mode; docs/serving.md)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    metavar="TOKENS",
                    help="chunked prefill: prefill prompts this many "
                         "tokens per scheduler step instead of one "
                         "monolithic pass (multiple of --block-size; "
                         "continuous mode)")
    ap.add_argument("--kv-dtype", default=None, choices=["fp", "int8"],
                    help="paged KV pool storage dtype (continuous "
                         "mode): int8 stores symmetric per-position-"
                         "per-head int8 with scale tiles beside the "
                         "pool — ~2x KV capacity at greedy parity "
                         "(docs/serving.md 'KV quantization & host "
                         "tiering')")
    ap.add_argument("--kv-host-offload", action="store_true",
                    help="tier cold prefix blocks to host RAM "
                         "(continuous mode; implies --prefix-cache): "
                         "LRU eviction becomes demotion, prefix hits "
                         "on demoted blocks swap back in")
    ap.add_argument("--replicas", type=int, default=0, metavar="N",
                    help="replicated serving: drive N supervised server "
                         "replicas through the ServingFrontend instead "
                         "of one bare server (continuous mode; combine "
                         "with --chaos for a seeded mid-decode replica "
                         "kill that fails over losslessly — "
                         "docs/serving.md 'Replicated serving & "
                         "failover')")
    ap.add_argument("--roles", default=None, metavar="R1,R2,...",
                    help="disaggregated prefill/decode serving: one "
                         "role per replica from {prefill,decode,mixed} "
                         "(e.g. 'prefill,decode' — implies --replicas "
                         "len(roles) and --prefix-cache). New requests "
                         "chunk-prefill on a prefill replica, hand "
                         "their KV off by chain hash, and decode on a "
                         "telemetry-picked decode replica "
                         "(docs/serving.md 'Disaggregated prefill/"
                         "decode')")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="per-slot speculative decoding: each active "
                         "slot proposes up to K-1 tokens per step by "
                         "prompt lookup over its own history, verified "
                         "in one batched forward — 1..K tokens per "
                         "slot per step, greedy output unchanged "
                         "(continuous mode; docs/serving.md 'Per-slot "
                         "speculative decoding')")
    ap.add_argument("--draft", default=None, metavar="PATH",
                    help="HF checkpoint dir for a draft model: "
                         "propose the K-1 tokens with its batched "
                         "forwards instead of prompt lookup, verified "
                         "by the same paged verify program (requires "
                         "--speculate; docs/serving.md 'Draft-model "
                         "proposals')")
    ap.add_argument("--commit-lag", type=int, default=None, metavar="N",
                    help="let the async loop dispatch up to N device "
                         "steps ahead of the host commit "
                         "(inference.max_commit_lag; default 1 = the "
                         "classic lag-1 pipeline — docs/serving.md "
                         "'Lag-N dispatch chains')")
    ap.add_argument("--prefill-chain", action="store_true",
                    help="dispatch all of a prompt's non-final prefill "
                         "chunks as one device-side chain instead of "
                         "one chunk per step (requires --prefill-chunk "
                         "or --prefix-cache; docs/serving.md 'Chunked "
                         "prefill')")
    ap.add_argument("--async-loop", dest="async_loop",
                    action="store_true", default=True,
                    help="pipelined dispatch with lag-1 host commit "
                         "(the default — docs/serving.md 'Async "
                         "dispatch loop'); see --sync-loop")
    ap.add_argument("--sync-loop", dest="async_loop",
                    action="store_false",
                    help="force the synchronous serving loop "
                         "(async_loop=false): dispatch, fetch, commit "
                         "every step — the A/B baseline")
    ap.add_argument("--step-profile", action="store_true",
                    help="print the rolling serving-step phase "
                         "breakdown (admission/propose/dispatch/"
                         "sync-wait/commit/publish, goodput fraction, "
                         "dispatch gaps) after the drain (every "
                         "step's phase spans are in the timeline: "
                         "combine with --trace-dump for the merged "
                         "Perfetto view; docs/observability.md "
                         "'Serving goodput & KV-pool accounting')")
    ap.add_argument("--trace-dump", default=None, metavar="PATH",
                    help="trace every request (telemetry.trace_sample_"
                         "rate=1.0) and write a Perfetto-loadable "
                         "Chrome trace timeline here after the drain "
                         "(continuous mode; docs/observability.md)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    metavar="SECONDS",
                    help="per-request deadline: a request still queued "
                         "or decoding past this many seconds after "
                         "submit is reaped with finish reason "
                         "'deadline' (continuous mode; docs/serving.md "
                         "'Request lifecycle & overload behavior')")
    ap.add_argument("--chaos", action="store_true",
                    help="seeded fault injection demo: wedge every 5th "
                         "request and fail ~10%% of prefills "
                         "(telemetry/faultinject.py) — watch the "
                         "lifecycle layer degrade gracefully under a "
                         "bounded drain (continuous mode)")
    ap.add_argument("--tenants", default=None, metavar="T1,T2,...",
                    help="cycle requests across these tenant labels "
                         "(continuous mode, plain or replicated) and "
                         "print the per-tenant metering table after "
                         "the drain — requests, tokens in/out, ledger-"
                         "attributed device-seconds, rejections "
                         "(docs/observability.md 'Cost accounting & "
                         "capacity')")
    ap.add_argument("--slo", action="store_true",
                    help="arm default SLO gates (TTFT p90 1s, per-token "
                         "p50 100ms, queue-wait p90 1s, error rate 5%%) "
                         "and print windowed compliance after the drain "
                         "(continuous mode); also arms the closed loop "
                         "— burn-rate alert rules, canary probes and "
                         "incident bundles — printing each rule "
                         "transition live and dumping a forensic "
                         "bundle after the drain (pair with --chaos)")
    args = ap.parse_args()

    import deepspeed_tpu
    knobs = dict(dtype=args.dtype, tp={"tp_size": args.tp})
    if args.num_slots:
        knobs["num_slots"] = args.num_slots
    if args.block_size:
        knobs["block_size"] = args.block_size
    telemetry = {}
    if args.metrics_port is not None:
        telemetry["http_port"] = args.metrics_port
    if args.trace_dump:
        telemetry["trace_sample_rate"] = 1.0
    if args.slo:
        # compliance gates PLUS the closed loop (docs/observability.md
        # "SLOs, alerting & incidents"): burn-rate alert rules, the
        # synthetic canary probing the real serving path, and one-shot
        # incident bundles on rule-fire; combine with --chaos to watch
        # a rule walk pending -> firing -> resolved live (availability
        # only observes a --replicas pool; error_rate works everywhere)
        telemetry["slo"] = {"enabled": True, "ttft_p90_s": 1.0,
                            "token_p50_s": 0.1, "queue_wait_p90_s": 1.0,
                            "error_rate": 0.05,
                            "eval_interval_s": 0.25,
                            "objectives": {
                                "availability": {
                                    "signal": "availability",
                                    "threshold": 0.99,
                                    "fast_window_s": 2.0,
                                    "slow_window_s": 10.0},
                                "errors": {
                                    "signal": "error_rate",
                                    "threshold": 0.05,
                                    "fast_window_s": 2.0,
                                    "slow_window_s": 10.0}}}
        telemetry["canary"] = {"enabled": True, "interval_s": 2.0}
        telemetry["incident"] = {"enabled": True}
    if telemetry:
        knobs["telemetry"] = telemetry
    if args.prefix_cache or args.kv_host_offload:
        knobs["enable_prefix_caching"] = True
    if args.kv_dtype:
        knobs["kv_cache_dtype"] = args.kv_dtype
    if args.kv_host_offload:
        knobs["kv_host_offload"] = True
    if args.prefill_chunk is not None:
        knobs["prefill_chunk_tokens"] = args.prefill_chunk
    if args.speculate:
        knobs["speculation_tokens"] = args.speculate
    if args.draft:
        # a second, smaller engine over the same tokenizer/vocab; the
        # config route reaches every replica of a replicated pool
        knobs["speculation_draft"] = deepspeed_tpu.init_inference(
            args.draft, dtype=args.dtype)
    if args.commit_lag is not None:
        knobs["max_commit_lag"] = args.commit_lag
    if args.prefill_chain:
        knobs["prefill_chain"] = True
    knobs["async_loop"] = args.async_loop
    roles = None
    if args.roles:
        roles = [r.strip() for r in args.roles.split(",") if r.strip()]
        knobs["replication"] = {"replicas": len(roles), "roles": roles}
        knobs["enable_prefix_caching"] = True   # the handoff identity
        args.replicas = len(roles)
    elif args.replicas and args.replicas > 1:
        knobs["replication"] = {"replicas": args.replicas}
    eng = deepspeed_tpu.init_inference(args.path, **knobs)
    prompt = [int(t) for t in args.prompt_ids.split(",")]
    if args.continuous:
        if args.replicas and args.replicas > 1:
            run_replicated(eng, prompt, args)
        else:
            run_continuous(eng, prompt, args)
        return
    out = eng.generate([prompt], max_new_tokens=args.max_new_tokens,
                       num_beams=args.num_beams,
                       temperature=args.temperature, top_p=args.top_p,
                       repetition_penalty=args.repetition_penalty)
    print("generated ids:", out[0])


if __name__ == "__main__":
    main()
