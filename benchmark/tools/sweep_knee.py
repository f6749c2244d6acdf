"""Find the knee of an open-loop cell once, on the chip: one process
that pays set-up once and steps through offered rates with the cell's
own mix. A rate is steady when the requests waiting for a slot (due and
not admitted) at the window's close are no more than at its middle, give
or take ``--slack``; the knee is the highest steady rate, and no higher
than the capacity the saturated rates show (completed output tokens a
second over the mix's mean output length). The cell's traffic file takes
0.8 of it as ``rate_per_s``.

    python3 benchmark/tools/sweep_knee.py --workload serve-gpt2-1.3b-chat-p80 \
        --rates 4,6,8,10,12 --seconds 30 [--seed 5]

One JSON line per rate; the last line names the knee. A later
``benchmark`` PR that changes the mix or the server's sizes sweeps again
and writes the new rate into a NEW traffic file.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from statistics import quantiles

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness, serve_cell, traffic as traffic_lib  # noqa: E402


def backlog(reqs, t):
    """Due and not finished (waiting or resident)."""
    return sum(1 for r in reqs if r.due <= t
               and (r.done is None or r.done > t))


def waiting(reqs, t):
    """Due and not yet admitted to a slot."""
    return sum(1 for r in reqs if r.due <= t
               and (r.admitted is None or r.admitted > t))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--slack", type=int, default=2)
    args = ap.parse_args()
    contract = harness.load_contract()
    cell = harness.resolve_cell(contract, args.workload)
    try:
        device = harness.require_tpu(int(cell["cell"]["chips"]))
    except harness.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from deepspeed_tpu.utils.logging import logger
    for handler in logger.handlers:
        handler.setStream(sys.stderr)
    enable_compile_cache()
    config, traffic = cell["config"], dict(cell["traffic"])
    family = harness.load_family(config["model"]["family"], cell["root"])
    vocab = config["model"]["vocab_size"]
    cfg, engine, server = serve_cell.build(config, args.seed, family)
    sess = serve_cell.Session(server)
    tracer = harness.Tracer(False, "sweep")
    knee, capacity = None, float("inf")
    try:
        traffic["rate_per_s"] = max(float(r) for r in args.rates.split(","))
        made = traffic_lib.build_requests(traffic, args.seconds, args.seed,
                                          vocab)
        harness.log({"device": device, "reference_check":
                     serve_cell.warm_and_check(
                         sess, cfg, engine, family, made["requests"],
                         traffic["check"], args.seed)})
        base = 0
        for rate in (float(r) for r in args.rates.split(",")):
            traffic["rate_per_s"] = rate
            made = traffic_lib.build_requests(traffic, args.seconds,
                                              args.seed, vocab)
            reqs = serve_cell.make_tracked(made["requests"], base=base)
            base += len(reqs)
            win = serve_cell.run_open_loop(
                sess, reqs, args.seconds, float(traffic["lead_in_s"]),
                tracer, 0.0, stop_after=args.seconds)
            t0 = win["t0"]
            mid, close = (backlog(reqs, t0 + args.seconds / 2),
                          backlog(reqs, t0 + args.seconds))
            t_drain = time.perf_counter()
            sess.drain()
            counted = [r for r in reqs if r.counted and r.token_times]
            ttft = serve_cell.ttft_ms(counted)
            gaps = serve_cell.itl_gaps_ms(counted)
            tokens = sum(1 for r in reqs for t in r.token_times
                         if t0 < t <= t0 + args.seconds)
            w_mid, w_close = (waiting(reqs, t0 + args.seconds / 2),
                              waiting(reqs, t0 + args.seconds))
            steady = w_close <= w_mid + args.slack
            if steady:
                knee = rate
            offered = made["totals"]["output_tokens"] / args.seconds
            if not steady:
                capacity = min(capacity, tokens / args.seconds / (
                    made["totals"]["output_tokens"]
                    / made["totals"]["requests"]))
            harness.log({
                "rate_per_s": rate, "requests": len(counted),
                "backlog_mid": mid, "backlog_close": close,
                "waiting_mid": w_mid, "waiting_close": w_close,
                "steady": steady, "offered_out_tokens_per_s": offered,
                "ttft_p50_ms": quantiles(ttft, n=10, method="inclusive")[4],
                "ttft_p90_ms": quantiles(ttft, n=10, method="inclusive")[8],
                "itl_p90_ms": quantiles(gaps, n=10, method="inclusive")[8],
                "out_tokens_per_s": tokens / args.seconds,
                "drain_s": time.perf_counter() - t_drain,
                "mean_live_slots": sum(s[2] for s in sess.steps[
                    win["first_step"]:]) / max(
                        1, len(sess.steps) - win["first_step"])})
    finally:
        sess.close()
    if knee is not None and capacity < float("inf"):
        knee = max(knee, capacity)    # the steady grid point is a floor
    harness.log({"knee_rate_per_s": knee,
                 "capacity_requests_per_s": capacity,
                 "rate_at_0.8": None if knee is None else 0.8 * knee})
    return 0


if __name__ == "__main__":
    sys.exit(main())
