"""Traffic from a data file: one general generator.

A traffic file fixes the distributions of prompt and output length and
how many requests a second of window gets. The requests are NOT sampled:
the distributions' quantiles are taken on an even grid, so every seed
offers the same multiset of ``(prompt_len, output_len)`` pairs and the
same total tokens. What ``--seed`` changes is the order (``"order"``):

  ``permutation`` (default)  the seed permutes which arrival gets which
                  pair and draws the arrival times;
  ``rotation``    the order and the arrival gaps are drawn once, from
                  the file's ``order_seed``; the seed turns that ring to
                  another starting point. Every seed then offers the
                  same requests with the same neighbours at the same
                  gaps (the same bursts meeting the same long prompts),
                  begun elsewhere: for a tail over a few hundred
                  requests, which a different order moves by more than
                  a regression would.

Token ids and (elsewhere) weights always come from the seed.

A ``backlog`` is an endless ring of laps (:func:`backlog_ring`): lap 0
is the list :func:`build_requests` returns, lap ``k >= 1`` the same
multiset in another stratified order with other token ids, drawn from
``(seed, k)``. The runner queues lap 0 at time zero and tops the queue
up from the ring, so the queue is as deep at any speed of the server.

Length distributions (``{"dist": ...}``):
  ``loguniform``  lo, hi
  ``lognormal``   median, sigma, lo, hi   (clipped)
  ``fixed``       value

Arrival processes for an open loop (``{"process": ...}``), each
conditioned on its count so that the offered rate is exact:
  ``poisson``     N sorted uniform draws over the span
  ``exponential`` the N gaps are the exponential distribution's
                  quantiles on an even grid (every seed offers the same
                  multiset of gaps, bursts included) in a seeded order,
                  stratified like the lengths (``stratify_block``), and
                  scaled to fill the span
  ``gamma``       cv: gaps drawn gamma with that coefficient of
                  variation, scaled so that the N arrivals fill the span
"""
from __future__ import annotations

import itertools
import math
from statistics import NormalDist
from typing import Dict, Iterator, List, Tuple

import numpy as np

GOLDEN = 0.6180339887498949


def quantile(dist: dict, u: float) -> int:
    """The ``u`` quantile (0 < u < 1) of a length distribution, as a
    whole number of tokens."""
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    lo, hi = dist["lo"], dist["hi"]
    if kind == "loguniform":
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(x), lo), hi))


def _blocks(n: int, block: int) -> int:
    return max(1, math.ceil(n / max(block, 1)))


def multiset(n: int, prompt: dict, output: dict, max_total: int,
             block: int = 16) -> List[Tuple[int, int]]:
    """``n`` pairs, the same for every seed. Pair ``i`` takes the prompt
    quantile ``(i + 0.5) / n``. Its output quantile is even and
    uncorrelated with the prompt's BOTH over all pairs and inside each
    place-block of :func:`stratified_order` (block ``b`` holds the pairs
    ``b, b + nb, ...``): pair ``b + k nb`` takes the ``perm[k]``-th of
    ``K`` even output quantiles, rotated by ``b`` times the golden
    ratio, where ``perm`` is the fixed low-discrepancy order of ``K``
    places. A pair over ``max_total`` has its output cut to fit (the cut
    is part of the multiset, not of the seed)."""
    nb = _blocks(n, block)
    K = math.ceil(n / nb)
    perm = np.argsort(np.argsort([((j + 1) * GOLDEN) % 1.0
                                  for j in range(K)]))
    pairs = []
    for i in range(n):
        b, k = i % nb, i // nb
        p = quantile(prompt, (i + 0.5) / n)
        v = (b * GOLDEN + (perm[k] + 0.5) / K) % 1.0
        o = quantile(output, min(max(v, 0.5 / n), 1 - 0.5 / n))
        p = min(p, max_total - 1)
        pairs.append((p, max(1, min(o, max_total - p))))
    return pairs


def stratified_order(n: int, block: int, rng: np.random.Generator
                     ) -> List[int]:
    """A seed-dependent order of ``range(n)`` in which every run of
    ``block`` consecutive places holds indices spread evenly over the
    whole range: place-block ``b`` of ``nb`` takes the indices ``b,
    b + nb, b + 2 nb, ...``, shuffled; the blocks are shuffled too. So
    whatever prefix of the order a window serves is itself close to a
    stratified sample, and seeds differ only in the order."""
    nb = _blocks(n, block)
    blocks = []
    for b in rng.permutation(nb):
        idx = np.arange(b, n, nb)
        blocks.append(rng.permutation(idx))
    return [int(i) for i in np.concatenate(blocks)] if blocks else []


def arrivals(n: int, start: float, span: float, process: dict,
             rng: np.random.Generator, block: int = 16) -> np.ndarray:
    """``n`` arrival times in ``[start, start + span)``, sorted."""
    if n == 0:
        return np.zeros((0,))
    kind = process.get("process", "poisson")
    if kind == "poisson":
        t = np.sort(rng.uniform(0.0, span, size=n))
    elif kind == "exponential":
        gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
        gaps = gaps[stratified_order(n, block, rng)]
        # consecutive arrivals are exactly one gap apart; the first
        # comes half its gap after the span opens
        t = (np.cumsum(gaps) - 0.5 * gaps[0]) / gaps.sum() * span
    elif kind == "gamma":
        cv = float(process["cv"])
        gaps = rng.gamma(1.0 / (cv * cv), cv * cv, size=n + 1)
        t = np.cumsum(gaps)[:n] / gaps.sum() * span
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return start + t


def rotate_arrivals(t: np.ndarray, start: float, span: float, r: int
                    ) -> np.ndarray:
    """The same arrivals as a ring of gaps (the last gap wraps to the
    first arrival), begun ``r`` places later."""
    if len(t) == 0 or r == 0:
        return t
    gaps = np.append(np.diff(t), span - (t[-1] - t[0]))
    gaps = np.roll(gaps, -r)
    first = start + 0.5 * gaps[-1]       # half the gap that wraps around
    first = min(first, start + span - gaps[:-1].sum() - 1e-9)
    return first + np.concatenate(([0.0], np.cumsum(gaps[:-1])))


def token_ids(rng: np.random.Generator, length: int, vocab: int,
              shared: np.ndarray = None) -> List[int]:
    """Prompt of ``length`` ids in ``[1, vocab)``; with ``shared`` (a
    session's common prefix) the prompt starts with as much of it as
    fits and the rest is the request's own."""
    ids = rng.integers(1, vocab, size=length)
    if shared is not None and len(shared):
        k = min(len(shared), length)
        ids[:k] = shared[:k]
    return ids.tolist()


def _lap_rngs(traffic: dict, seed: int, vocab: int, lap: int = 0):
    """``(rng, order_rng, prefixes)`` of one lap. Lap 0 draws everything
    from ``seed``; a later lap draws from ``(seed, lap)`` and keeps lap
    0's shared prefixes."""
    rng = np.random.default_rng(seed)
    prefix_len = int(traffic.get("shared_prefix_tokens", 0))
    groups = int(traffic.get("shared_prefix_groups", 1))
    prefixes = [rng.integers(1, vocab, size=prefix_len)
                for _ in range(groups)] if prefix_len else None
    if lap:
        rng = np.random.default_rng((seed, lap))
    rotation = traffic.get("order", "permutation") == "rotation"
    order_rng = (np.random.default_rng(int(traffic["order_seed"]))
                 if rotation else rng)
    return rng, order_rng, prefixes


def _part(traffic: dict, n: int, start: float, span: float, counted: bool,
          vocab: int, rng, order_rng, prefixes
          ) -> Tuple[Iterator[dict], List[Tuple[int, int]]]:
    """``n`` requests due in ``[start, start + span)`` as an iterator
    (a request's token ids are drawn when it is taken, in due order) and
    the multiset they are made from."""
    kind = traffic["kind"]
    block = int(traffic.get("stratify_block", 16))
    pairs = multiset(n, traffic["prompt_len"], traffic["output_len"],
                     int(traffic["max_total_tokens"]), block)
    order = stratified_order(n, block, order_rng)
    if kind == "open_loop":
        due = arrivals(n, start, span, traffic.get("arrivals", {}),
                       order_rng, block)
    else:
        due = np.zeros((n,))
    if traffic.get("order", "permutation") == "rotation" and n:
        r = int(rng.integers(n))
        order = order[r:] + order[:r]
        due = rotate_arrivals(due, start, span, r)

    def requests():
        for k, i in enumerate(order):
            p, o = pairs[i]
            shared = (prefixes[int(rng.integers(len(prefixes)))]
                      if prefixes else None)
            yield {"due": float(due[k]),
                   "prompt": token_ids(rng, p, vocab, shared),
                   "out": o, "counted": counted}
    return requests(), pairs


def build_requests(traffic: dict, seconds: float, seed: int, vocab: int
                   ) -> Dict:
    """The requests of one run of a serving cell.

    ``backlog``: ``requests`` pairs, all due at time zero (lap 0 of
    :func:`backlog_ring`).
    ``open_loop``: ``round(rate * lead_in_s)`` lead-in requests (served,
    not counted) and ``round(rate * seconds)`` window requests, each
    part its own fixed multiset, arrival times relative to the opening
    of the window (lead-in times are negative).

    Returns ``{"requests": [...], "totals": {...}}``; a request is
    ``{"due", "prompt", "out", "counted"}`` in due order."""
    kind = traffic["kind"]
    draws = _lap_rngs(traffic, seed, vocab)

    def part(n, start, span, counted):
        reqs, pairs = _part(traffic, n, start, span, counted, vocab, *draws)
        return list(reqs), pairs

    if kind == "backlog":
        reqs, pairs = part(int(traffic["requests"]), 0.0, 0.0, True)
        lead_pairs = []
    elif kind == "open_loop":
        rate = float(traffic["rate_per_s"])
        lead = float(traffic.get("lead_in_s", 0.0))
        lead_reqs, lead_pairs = part(round(rate * lead), -lead, lead, False)
        win_reqs, pairs = part(round(rate * seconds), 0.0, seconds, True)
        reqs = lead_reqs + win_reqs
    else:
        raise ValueError(f"traffic kind {kind!r} makes no requests")
    totals = {"requests": len(pairs), "lead_in_requests": len(lead_pairs),
              "prompt_tokens": sum(p for p, _ in pairs),
              "output_tokens": sum(o for _, o in pairs),
              "longest_prompt": max(p for p, _ in pairs + lead_pairs),
              "shortest_prompt": min(p for p, _ in pairs + lead_pairs)}
    return {"requests": reqs, "totals": totals}


def backlog_ring(traffic: dict, seed: int, vocab: int, first_lap: int = 0
                 ) -> Iterator[Tuple[int, dict]]:
    """A backlog without an end: ``(request number, request)`` from lap
    ``first_lap`` on, numbers running on across laps (request ``i`` of
    lap ``k`` is ``k * requests + i``). Lap 0 is
    :func:`build_requests`' list, request for request; every later lap
    is the same multiset in a stratified order of its own. A request is
    drawn only when it is taken."""
    n = int(traffic["requests"])
    for lap in itertools.count(first_lap):
        reqs, _ = _part(traffic, n, 0.0, 0.0, True, vocab,
                        *_lap_rngs(traffic, seed, vocab, lap))
        for i, r in enumerate(reqs):
            yield lap * n + i, r
