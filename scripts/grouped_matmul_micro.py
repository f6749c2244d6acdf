"""The held experts' grouped matmul ALONE on the chip: the small-tile
Pallas kernel (``ops/pallas/grouped_matmul.py``) against
``jax.lax.ragged_dot`` at the geometry of every program that calls
``held_experts._experts`` (ISSUE 52; PERF.md section 6, PR 52 has what it
read).

    chiprun -- python3 scripts/grouped_matmul_micro.py [--only granite]
    JAX_PLATFORMS=cpu python3 scripts/grouped_matmul_micro.py --tiny

For each geometry: ``w_in [X, E, 2 Fe]`` alone, ``w_out [X, Fe, E]``
alone and the whole expert layer (both and the SwiGLU), each as a chain
of ``--chain`` calls under one ``jit``, the best of three; the weights of
the experts HIT over that time as GB/s; and the largest difference
between the two forms over the rows inside the groups. ``ragged_dot``
reads the rows packed end to end, the kernel the same rows laid out on
boundaries of its row tile (``grouped_matmul.aligned_starts``, the layout
``held_experts._align`` builds); ``row_tiles_walked`` over ``hit`` is the
times a hit expert's weight block goes through the MXU. ``--aligned-draw``
replaces the draw by groups of exactly one row tile (one pass a block:
the floor of that ratio; ISSUE 59's first chip call). One JSON line a
geometry, all of them in ``chiprun_out/grouped_matmul_micro.jsonl``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.model_implementations.held_experts import expert_row_tile
from deepspeed_tpu.ops.pallas import grouped_matmul as gm

# name: (rows, held experts, E, Fe, landed picks): the decode programs of
# the four cells, LongCat's rider, the exact fallbacks and the prefill
# buckets (PERF.md section 5 has the landed picks a step)
GEOMETRIES = {
    "granite-decode": (640, 36, 4096, 768, 500),
    "laguna-decode": (256, 32, 2048, 512, 90),
    "gigachat-decode": (128, 16, 7168, 2048, 19),
    "longcat-decode": (128, 16, 6144, 2048, 62),
    "longcat-rider": (256, 16, 6144, 2048, 110),
    "granite-fallback": (960, 36, 4096, 768, 700),
    "granite-prefill-512": (3200, 36, 4096, 768, 2600),
    "granite-prefill-4096": (25600, 36, 4096, 768, 20500),
    "laguna-prefill-8192": (8704, 32, 2048, 512, 8192),
    "gigachat-chunk-1024": (640, 16, 7168, 2048, 512),
    "longcat-fallback": (3072, 16, 6144, 2048, 300),
}
TINY = {"tiny-a": (128, 4, 256, 128, 90), "tiny-b": (64, 4, 128, 128, 20)}


def group_sizes(rng, X: int, landed: int):
    """``landed`` picks over ``X`` experts, the busiest near twice the
    mean (what the cells' routers give)."""
    p = rng.dirichlet(np.full(X, 4.0))
    return rng.multinomial(landed, p).astype(np.int32)


def laid_out(packed, sizes, tm: int):
    """``packed [R, K]`` (the groups end to end) on boundaries of ``tm``
    in the buffer any ``R`` rows fit, and each group row's place in it."""
    astart = np.asarray(gm.aligned_starts(jnp.asarray(sizes), tm)[0])
    at = np.concatenate([a + np.arange(n) for a, n in zip(astart, sizes)])
    rows = gm.aligned_rows(packed.shape[0], len(sizes), tm)
    return jnp.zeros((rows, packed.shape[1]), packed.dtype).at[at].set(
        packed[:at.size]), at


def draw(rng, a, R: int, X: int, landed: int, tm: int):
    """The group sizes of one geometry and the rows they are packed in:
    the cells' own spread, or under ``--aligned-draw`` every group exactly
    one row tile."""
    if a.aligned_draw:
        return np.full(X, tm, np.int32), max(R, X * tm)
    return group_sizes(rng, X, landed), R


def timed(fn, args, chain: int) -> float:
    """Milliseconds a call: ``chain`` calls in a loop under one jit, the
    best of 3."""
    def many(xs, *rest):
        def body(_, xs):
            y = fn(xs, *rest)
            cols = min(xs.shape[1], y.shape[1], 128)
            return xs.at[:, :cols].add((y[:, :cols] * 1e-3).astype(xs.dtype))
        return jax.lax.fori_loop(0, chain, body, xs)
    run = jax.jit(many)
    float(jnp.sum(run(*args)[:1].astype(jnp.float32)))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        float(jnp.sum(run(*args)[:1].astype(jnp.float32)))
        best = min(best, time.perf_counter() - t0)
    return 1e3 * best / chain


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--chain", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--aligned-draw", action="store_true")
    a = ap.parse_args()
    geometries = TINY if a.tiny else GEOMETRIES
    if a.only:
        geometries = {k: v for k, v in geometries.items()
                      if any(k.startswith(o) for o in a.only.split(","))}
    dtype = jnp.float32 if a.tiny else jnp.bfloat16
    device = jax.devices()[0]
    if not a.tiny and device.platform != "tpu":
        print("no TPU: a time from another device is not reported",
              file=sys.stderr)
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/grouped_matmul_micro.jsonl", "a")
    rng = np.random.default_rng(a.seed)
    for name, (R, X, E, Fe, landed) in geometries.items():
        itemsize = jnp.dtype(dtype).itemsize
        tm = expert_row_tile(R, X, E, Fe, itemsize)
        sizes, R = draw(rng, a, R, X, landed, tm)
        landed = int(sizes.sum())
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(a.seed), 3)
        packed = (jax.random.normal(k1, (R, E), dtype),
                  jax.random.normal(k1, (R, Fe), dtype))
        (xs, at), (hs, _) = (laid_out(p, sizes, tm) for p in packed)
        operands = {"ragged": packed, "tiled": (xs, hs)}
        w_in = jax.random.normal(k2, (X, E, 2 * Fe), dtype) / np.sqrt(E)
        w_out = jax.random.normal(k3, (X, Fe, E), dtype) / np.sqrt(Fe)
        gs = jnp.asarray(sizes)
        hit = int((sizes > 0).sum())
        walked = int((-(-sizes // tm)).sum())
        line = {"geometry": name, "rows": R, "aligned_rows": xs.shape[0],
                "experts": X, "E": E, "Fe": Fe, "landed": landed,
                "hit": hit, "largest_group": int(sizes.max()),
                "row_tiles_walked": walked,
                "passes_a_weight_block": round(walked / max(hit, 1), 3),
                "aligned_draw": a.aligned_draw,
                "device": device.device_kind,
                "tiles_in": [tm, gm.column_tile(E, 2 * Fe, itemsize)],
                "tiles_out": [tm, gm.column_tile(Fe, E, itemsize)]}
        forms = {"ragged": jax.lax.ragged_dot,
                 "tiled": functools.partial(gm.grouped_matmul, tm=tm)}
        outs = {}
        for label, mm in forms.items():
            xs, hs = operands[label]

            def layer(xs, w_in, w_out, gs, mm=mm):
                gu = mm(xs, w_in, gs)
                h = (jax.nn.silu(gu[:, :Fe].astype(jnp.float32))
                     * gu[:, Fe:].astype(jnp.float32))
                return mm(h.astype(xs.dtype), w_out, gs)
            out_ = np.asarray(jax.jit(layer)(xs, w_in, w_out, gs), np.float32)
            outs[label] = out_[at] if label == "tiled" else out_[:landed]
            ms_in = timed(mm, (xs, w_in, gs), a.chain)
            ms_out = timed(mm, (hs, w_out, gs), a.chain)
            ms = timed(layer, (xs, w_in, w_out, gs), a.chain)
            line[label] = {
                "w_in_ms": round(ms_in, 4), "w_out_ms": round(ms_out, 4),
                "layer_ms": round(ms, 4),
                "layer_hit_weights_gb_s": round(
                    hit * 3 * E * Fe * itemsize / ms / 1e6, 1)}
        first = next(iter(outs))
        line["max_gap_to_" + first] = {
            k: float(np.abs(v - outs[first]).max())
            for k, v in outs.items() if k != first}
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()
        del xs, hs, packed, operands, w_in, w_out
    return 0


if __name__ == "__main__":
    sys.exit(main())
