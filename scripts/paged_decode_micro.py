#!/usr/bin/env python3
"""The K/V paged decode kernel alone (``ops/pallas/decode_attention.py``
``_paged_kernel``, one query token a slot) at the geometries of the
cells that serve through it, for each number of table entries a loop
iteration attends (``--entries``; 0: what the kernel's own rule gives
the shapes, reported as ``entries``), and, where ``--parent`` names a checkout, that tree's
kernel over the same operands.

    chiprun -- python3 scripts/paged_decode_micro.py --parent _parent
    JAX_PLATFORMS=cpu python3 scripts/paged_decode_micro.py --tiny

Blocks of 128 rows, bfloat16; slots, kv heads x query heads a kv head,
key / value lanes a head, pool blocks, table entries, context rows:

- ``mimo-full``: 96, 4 x 16, 192 / 128, 5400, 96, 1500-5000 (12-40 live
  blocks a slot); ``mimo-window``: 96, 8 x 8, 192 / 128, a ring of 2, a
  window of 128 and a sink;
- ``laguna-full``: 96, 8 x 6, 128, 3200, 80, 400-9000;
  ``laguna-window``: 96, 8 x 8, 128, a ring of 5, a window of 512;
- ``granite``: 96, 8 x 4, 128, 2400, 48, 400-5000;
- ``gpt2``: 32, 16 x 1, 128, 256, 8, 100-1000;
- ``gqa4x16`` (no cell's: a product a head over blocks of a quarter
  mebibyte, where the rule gives its most): 96, 4 x 16, 128, 3200, 80,
  400-9000.

A slot's context is drawn log-uniformly; every slot is live but each
sixteenth (idle); a pool table names scattered blocks. ``--calls``
chained calls under one ``jit`` over the layers of one pool in turn,
the lengths one longer each call, as decode steps would; host clock
over ``--reps`` repeats ended by a transfer, the median and the spread
(first to third quartile over the median). Every reading says whether
each call's output equals the one-entry walk's to the bit.

One JSON line a reading; a time from a CPU run (``--tiny``: toy sizes,
interpret mode) is not a device number."""
import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.lib.peaks import PEAKS  # noqa: E402
from deepspeed_tpu.ops.pallas import decode_attention as da  # noqa: E402
from deepspeed_tpu.telemetry.registry import get_registry  # noqa: E402

GEOMETRIES = {
    # slots, kv heads, group, D, Dv, layers, pool blocks, table entries,
    # context rows (lo, hi), window, sink
    "mimo-full": (96, 4, 16, 192, 128, 3, 5400, 96, (1500, 5000), 0, False),
    "mimo-window": (96, 8, 8, 192, 128, 9, 0, 2, (1500, 5000), 128, True),
    "laguna-full": (96, 8, 6, 128, 128, 3, 3200, 80, (400, 9000), 0, False),
    "laguna-window": (96, 8, 8, 128, 128, 9, 0, 5, (400, 9000), 512, False),
    "granite": (96, 8, 4, 128, 128, 1, 2400, 48, (400, 5000), 0, False),
    "gpt2": (32, 16, 1, 128, 128, 24, 256, 8, (100, 1000), 0, False),
    "gqa4x16": (96, 4, 16, 128, 128, 3, 3200, 80, (400, 9000), 0, False),
}
BS, TINY_BS = 128, 16


def say(**line):
    print(json.dumps(line), flush=True)


def operands(geometry, seed, tiny):
    (S, KH, rep, D, Dv, L, NB, MB, (lo, hi), window, sink
     ) = GEOMETRIES[geometry]
    bs = BS
    if tiny:    # the same head shapes and tables over toy blocks
        bs, S, L = TINY_BS, min(S, 9), min(L, 2)
        MB = min(MB, 7)
        NB = 0 if window else S * MB
        lo, hi, window = 3, MB * bs - 8, window // 8
    dtype = jnp.float32 if tiny else jnp.bfloat16
    rng = np.random.default_rng(seed)
    lengths = np.exp(rng.uniform(np.log(lo), np.log(hi), S)).astype(np.int64)
    live = np.arange(S) % 16 != 5
    if window:
        NB, tables = S * MB, np.asarray(da.ring_tables(S, MB))
    else:
        # a slot's live entries name scattered blocks of the pool, the
        # rest the null block, which the walk never reads
        entries = np.minimum(-(-(lengths + 64) // bs), MB)
        tables = np.zeros((S, MB), np.int32)
        ids = 1 + rng.permutation(NB)
        if entries.sum() > NB:
            raise SystemExit(f"{geometry}: the drawn contexts need "
                             f"{entries.sum()} blocks of a pool of {NB}")
        at = 0
        for s, n in enumerate(entries):
            tables[s, :n] = ids[at:at + n]
            at += n
        NB += 1
    kq, kk, kv, ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return dict(
        q=jax.random.normal(kq, (S, KH, rep, D), dtype),
        k=jax.random.normal(kk, (L, NB, bs, KH * D), dtype),
        v=jax.random.normal(kv, (L, NB, bs, KH * Dv), dtype),
        tables=jnp.asarray(tables, jnp.int32),
        lengths=jnp.asarray(np.where(live, lengths, 0), jnp.int32),
        sink=(jax.random.normal(ks, (KH * rep,), jnp.float32) if sink
              else None)), dict(rep=rep, window=window, MB=MB)


def chained(module, calls, interpret, name, rep, window, **pin):
    """``calls`` calls of ``module``'s kernel, layer ``i % L`` at lengths
    ``+ i``; every call's output, stacked."""
    def run(q, k, v, tables, lengths, sink):
        outs = []
        for i in range(calls):
            outs.append(module._paged_attention(
                q, k, v, tables, jnp.where(lengths > 0, lengths + i, 0) - 1,
                rep=rep, scale=None, interpret=interpret, name=name,
                layer=i % k.shape[0], window=window, sink=sink, **pin))
        return jnp.stack(outs)
    return jax.jit(run)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", default=None,
                    help="a checkout whose kernel is timed beside this one")
    ap.add_argument("--only", default=",".join(GEOMETRIES))
    ap.add_argument("--entries", default="1,2,3,4,0",
                    help="table entries an iteration; 0: the kernel's rule")
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes in interpret mode, for a CPU rehearsal")
    args = ap.parse_args(argv)
    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_decode_attention", os.path.join(
                args.parent, "deepspeed_tpu", "ops", "pallas",
                "decode_attention.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    kind = jax.devices()[0].device_kind
    # what the live blocks' bytes take at the chip's HBM rate (a device
    # with no peak on record: None)
    roof = PEAKS.get(kind, {}).get("hbm_bytes_per_s")
    say(device=kind, calls=args.calls, seed=args.seed)
    for geometry in args.only.split(","):
        ops, static = operands(geometry, args.seed, args.tiny)
        MB = static.pop("MB")
        name = ("paged_window_decode_attention" if static["window"]
                else "paged_decode_attention")
        S, KH, _, D = ops["q"].shape
        bs, Dv = ops["k"].shape[2], ops["v"].shape[3] // KH
        slab = bs * KH * (D + Dv) * ops["k"].dtype.itemsize
        forms = {}
        for G in (int(g) for g in args.entries.split(",")):
            if G <= MB:
                forms[f"entries={G}" if G else "rule"] = chained(
                    da, args.calls, args.tiny, name, **static,
                    entries=G or None)
        if parent:
            forms["parent"] = chained(parent, args.calls, args.tiny, name,
                                      **static)
        blocks = np.minimum(-(-np.asarray(ops["lengths"]) // bs), MB)
        # what the kernel says of the signature it built last
        walk = get_registry().gauge(
            "paged_decode_entries_per_iteration",
            labels={"kernel": name, "slab_bytes": str(slab)})
        one = None
        for form, fn in forms.items():
            out = np.asarray(fn(**ops).astype(jnp.float32))    # compiles
            if one is None and form in ("entries=1", "parent"):
                one = out
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                float(fn(**ops)[-1, 0, 0, 0, 0])
                times.append((time.perf_counter() - t0) * 1e6 / args.calls)
            q1, _, q3 = statistics.quantiles(times, n=4)
            us = statistics.median(times)
            say(geometry=geometry, form=form, us_per_call=us,
                entries=None if form == "parent" else walk.value,
                spread_pct=100 * (q3 - q1) / us, slots=S,
                slab_bytes=slab, live_blocks_mean=float(blocks.mean()),
                us_per_block=us / blocks.sum(),
                bytes_us_per_call=roof and blocks.sum() * slab / roof * 1e6,
                equal_to_one_entry=(None if one is None
                                    else bool((out == one).all())))


if __name__ == "__main__":
    main()
