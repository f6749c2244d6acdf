"""The held UNGATED experts' grouped matmul ALONE on the chip at an expert
width that is no multiple of 128 lanes (ISSUE 58: 64 held relu² experts
1856 wide under a hidden size of 2688, 12 rows a group): the small-tile
Pallas kernel (``ops/pallas/grouped_matmul.py``) with the weights stored
PADDED to 1920 columns / rows of zeros, as the family stores them
(``nemotron_h.expert_stored_width``; the kernel refuses 1856 itself),
against the weight read that should bound it. PERF.md section 6, PR 58
has what it read, and what a kernel with a short last tile read at the
published storage before that path was taken out.

    chiprun -- python3 scripts/grouped_matmul_ungated_micro.py
    JAX_PLATFORMS=cpu python3 scripts/grouped_matmul_ungated_micro.py --tiny

``w_up`` alone, ``w_down`` alone and the whole expert (both and
``relu(.)^2``), each as a chain of ``--chain`` calls under one ``jit``,
the best of three; the weights of the experts HIT at the PUBLISHED width
over that time as GB/s (``flops_nemotron.expert_weight_bytes``: what the
roofline counts, whatever the storage); the largest difference to
``jax.lax.ragged_dot`` over the weights at their own width, over the rows
inside the groups (``ragged_dot`` reads them packed end to end, the kernel
laid out on boundaries of its row tile); ``row_tiles_walked`` over ``hit``,
the times a hit expert's weight block goes through the MXU, and
``--aligned-draw`` for groups of exactly one row tile
(``grouped_matmul_micro.py`` has both). One JSON line a geometry, all of
them in ``chiprun_out/grouped_matmul_ungated_micro.jsonl``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.flops_nemotron import expert_weight_bytes
from deepspeed_tpu.model_implementations.held_experts import (
    expert_row_tile, relu2)
from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from grouped_matmul_micro import draw, laid_out, timed

# name: (rows, held experts, E, Fe, landed picks): the decode program's
# buffer (256 slots x 6 picks, half of them held, LOAD_MARGIN 1.25), its
# exact fallback, and the 2048 bucket's prefill
GEOMETRIES = {
    "nemotron-decode": (1024, 64, 2688, 1856, 768),
    "nemotron-fallback": (1536, 64, 2688, 1856, 900),
    "nemotron-prefill-2048": (7680, 64, 2688, 1856, 6144),
}
TINY = {"tiny": (128, 4, 256, 200, 90)}
LANES = 128


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--chain", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--aligned-draw", action="store_true")
    a = ap.parse_args()
    geometries = TINY if a.tiny else GEOMETRIES
    dtype = jnp.float32 if a.tiny else jnp.bfloat16
    device = jax.devices()[0]
    if not a.tiny and device.platform != "tpu":
        print("no TPU: a time from another device is not reported",
              file=sys.stderr)
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/grouped_matmul_ungated_micro.jsonl", "a")
    rng = np.random.default_rng(a.seed)
    itemsize = jnp.dtype(dtype).itemsize
    for name, (R, X, E, Fe, landed) in geometries.items():
        pad = -Fe % LANES
        F = Fe + pad
        tm = expert_row_tile(R, X, E, F, itemsize)
        sizes, R = draw(rng, a, R, X, landed, tm)
        landed = int(sizes.sum())
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(a.seed), 3)
        packed = jax.random.normal(k1, (R, E), dtype)
        xs, at = laid_out(packed, sizes, tm)
        w_up = jax.random.normal(k2, (X, E, Fe), dtype) / np.sqrt(E)
        w_down = jax.random.normal(k3, (X, Fe, E), dtype) / np.sqrt(Fe)
        gs = jnp.asarray(sizes)
        hit = int((sizes > 0).sum())
        walked = int((-(-sizes // tm)).sum())
        up = jnp.pad(w_up, ((0, 0), (0, 0), (0, pad)))
        down = jnp.pad(w_down, ((0, 0), (0, pad), (0, 0)))

        def layer(xs, w_up, w_down, gs, mm):
            h = relu2(mm(xs, w_up, gs))
            return mm(h.astype(xs.dtype), w_down, gs)
        want = np.asarray(jax.jit(
            lambda *v: layer(*v, jax.lax.ragged_dot))(packed, w_up, w_down,
                                                      gs)[:landed],
            np.float32)
        hs = jax.random.normal(k1, (xs.shape[0], F), dtype)
        mm = functools.partial(gm.grouped_matmul, tm=tm)
        tiled = lambda *v: layer(*v, mm)  # noqa: E731
        got = np.asarray(jax.jit(tiled)(xs, up, down, gs), np.float32)[at]
        ms_up = timed(mm, (xs, up, gs), a.chain)
        ms_down = timed(mm, (hs, down, gs), a.chain)
        ms = timed(tiled, (xs, up, down, gs), a.chain)
        # MB of one matrix of the experts hit, at the published width
        one = hit * expert_weight_bytes(E, Fe, itemsize) / 2 / 1e6
        line = {"geometry": name, "rows": R, "aligned_rows": xs.shape[0],
                "experts": X, "E": E,
                "Fe": Fe, "stored_width": F, "landed": landed, "hit": hit,
                "largest_group": int(sizes.max()),
                "row_tiles_walked": walked,
                "passes_a_weight_block": round(walked / max(hit, 1), 3),
                "aligned_draw": a.aligned_draw,
                "device": device.device_kind,
                "tiles_up": [tm, gm.column_tile(E, F, itemsize)],
                "tiles_down": [tm, gm.column_tile(F, E, itemsize)],
                "w_up_ms": round(ms_up, 4),
                "w_down_ms": round(ms_down, 4),
                "layer_ms": round(ms, 4),
                "w_up_published_gb_s": round(one / ms_up, 1),
                "w_down_published_gb_s": round(one / ms_down, 1),
                "layer_published_gb_s": round(2 * one / ms, 1),
                "max_gap_to_ragged_dot": float(np.abs(got - want).max())}
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()
        del xs, hs, packed, w_up, w_down, up, down
    return 0


if __name__ == "__main__":
    sys.exit(main())
