"""How far a backlog cell's tokens/s follow the ORDER of its lap: the
runner's loop (``serve_cell.run_backlog``) and the server's admission
replayed in steps, with no device and no jax. A request admitted at step
``i`` with ``o`` outputs leaves at step ``i + o`` whatever the clock
reads, so a run is exact in steps; what a step costs in time is the
traffic file's ``order_model`` (fitted on the chip from a run's
``Session.steps`` / ``Session.admissions``; PERF.md section 6, PR 43):

    step_ms + ms_per_million_live_tokens x (live tokens / 1e6)
    + admission_ms[prefill bucket] for each request admitted in the step
    + after_admission_ms in a step that admitted (the pipeline catches up)

Prints the scatter (standard deviation / mean, %) of tokens/s over seeds
for ``order: permutation`` or over a ring's 256 rotations for ``order:
rotation``, and with ``--search N`` ranks ``order_seed`` 1..N. Not a
cell: no number it prints is a metric.

    python3 benchmark/tools/backlog_order.py --traffic laguna-mixed-context-batch \
        --slots 96 [--seconds 50] [--order-seed 796] [--block 8] [--search 600]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(ROOT))

from benchmark.lib import traffic as T  # noqa: E402


def laps(tr: dict, seed: int, count: int = 2) -> np.ndarray:
    """``(prompt_len, output_len)`` of the first ``count`` laps as the
    generator orders them for ``seed`` (no token ids drawn)."""
    n, block = int(tr["requests"]), int(tr.get("stratify_block", 16))
    pairs = T.multiset(n, tr["prompt_len"], tr["output_len"],
                       int(tr["max_total_tokens"]), block)
    out = []
    for lap in range(count):
        rng, order_rng, _ = T._lap_rngs(tr, seed, 2, lap)
        order = T.stratified_order(n, block, order_rng)
        if tr.get("order", "permutation") == "rotation":
            r = int(rng.integers(n))
            order = order[r:] + order[:r]
        out += [pairs[i] for i in order]
    return np.array(out)


def tokens_per_s(seqs: np.ndarray, model: dict, slots: int,
                 seconds: float) -> np.ndarray:
    """``seqs [R, L, 2]``: R queues served side by side. The window opens
    at the first step with every slot resident and nothing admitted."""
    R = len(seqs)
    P, O = seqs[:, :, 0].astype(float), seqs[:, :, 1].astype(int)
    buckets = np.array(sorted(int(b) for b in model["admission_ms"]))
    cost = np.array([model["admission_ms"][str(b)] for b in buckets])[
        np.minimum(np.searchsorted(buckets, P), len(buckets) - 1)]
    rows = np.arange(R)
    ctx = np.zeros((R, slots))
    left = np.zeros((R, slots), dtype=int)
    live = np.zeros((R, slots), dtype=bool)
    t, t0 = np.zeros(R), np.full(R, np.nan)
    tokens, taken = np.zeros(R), np.zeros(R, dtype=int)
    out = np.full(R, np.nan)
    while np.isnan(out).any():
        admitted = np.zeros(R, dtype=int)
        while not live.all():
            rr = rows[~live.all(axis=1)]
            s, k = np.argmin(live[rr], axis=1), taken[rr]
            t[rr] += cost[rr, k]
            ctx[rr, s], left[rr, s], live[rr, s] = P[rr, k], O[rr, k], True
            taken[rr] += 1
            admitted[rr] += 1
        t += np.where(admitted > 0, model["after_admission_ms"], 0.0)
        t += model["step_ms"] + model["ms_per_million_live_tokens"] * (
            ctx * live).sum(axis=1) / 1e6
        n = live.sum(axis=1)
        tokens += np.where(np.isnan(t0), 0, n)
        ctx += live
        left -= live
        live &= left > 0
        opening = np.isnan(t0) & (n == slots) & (admitted == 0)
        t0[opening], tokens[opening] = t[opening], 0
        done = np.isnan(out) & (t - t0 >= seconds * 1000)
        out[done] = tokens[done] / ((t[done] - t0[done]) / 1000.0)
    return out


def scatter(tr: dict, model: dict, slots: int, seconds: float,
            seeds: int = 256) -> float:
    """A rotation has ``requests`` outcomes (lap 0's turn; the window
    hardly reaches lap 1), a permutation one a seed."""
    n = int(tr["requests"])
    if tr.get("order", "permutation") == "rotation":
        ring = laps(tr, 0, 1)    # some turn of the ring: every turn follows
        # lap 1 turns on its own draw; any other turn stands in for it
        seqs = np.array([np.concatenate([np.roll(ring, -r, axis=0),
                                         np.roll(ring, -(r * 97 + 13) % n,
                                                 axis=0)])
                         for r in range(n)])
    else:
        seqs = np.array([laps(tr, 7000000 + 7919 * i) for i in range(seeds)])
    v = tokens_per_s(seqs, model, slots, seconds)
    return float(100.0 * v.std() / v.mean())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--block", type=int, default=None)
    ap.add_argument("--order-seed", type=int, default=None)
    ap.add_argument("--search", type=int, default=0)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "traffic", args.traffic + ".json")) as f:
        tr = json.load(f)
    model = tr["order_model"]
    if args.block:
        tr["stratify_block"] = args.block
    if args.order_seed:
        tr.update(order="rotation", order_seed=args.order_seed)
    print(json.dumps({"order": tr.get("order", "permutation"),
                      "order_seed": tr.get("order_seed"),
                      "stratify_block": tr.get("stratify_block", 16),
                      "scatter_pct": scatter(tr, model, args.slots,
                                             args.seconds)}))
    if args.search:
        perm = scatter(dict(tr, order="permutation"), model, args.slots,
                       args.seconds)
        found = sorted((scatter(dict(tr, order="rotation", order_seed=s),
                                model, args.slots, args.seconds), s)
                       for s in range(1, args.search + 1))
        print(json.dumps({"permutation_scatter_pct": perm,
                          "median_ring_pct": found[len(found) // 2][0],
                          "best": found[:5]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
