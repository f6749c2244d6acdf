#!/usr/bin/env python3
"""The latent decode kernel alone at the two latent cells' geometries
(64 heads over rows of 512 + 64 values, blocks of 128, bfloat16): one
call appends a row a slot and attends (``ops/pallas/
latent_decode_attention.py``), and, where ``--parent`` names a checkout
that still has ``paged_latent_append`` (PR 53's tree or older), that
tree's append followed by its kernel over the same operands.

    chiprun -- python3 scripts/latent_decode_micro.py --parent _parent
    JAX_PLATFORMS=cpu python3 scripts/latent_decode_micro.py --tiny

- ``longcat``: 256 slots, a pool of 2049 blocks, tables of 8, contexts
  of 200-800 rows (2-7 live blocks a slot);
- ``gigachat``: 48 slots, a pool of 2561 blocks, tables of 268: a shared
  context of 256 whole blocks under every table and 600-1400 rows of a
  slot's own after it (~263 live blocks a slot).

Every slot is live but each sixteenth (idle). ``--calls`` chained calls
under one ``jit``, the pool threaded through them and the lengths one
longer each call, as decode steps would; host clock over ``--reps``
repeats ended by a transfer, the median and the spread. With a parent:
whether the outputs and the pools of the two forms are equal to the bit
(the null block apart: the parent's idle slots write there).

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

from deepspeed_tpu.ops.pallas import latent_decode_attention as lda  # noqa: E402

GEOMETRIES = {
    # slots, pool blocks, table entries, shared blocks, own rows (lo, hi)
    "longcat": (256, 2049, 8, 0, (200, 800)),
    "gigachat": (48, 2561, 268, 256, (600, 1400)),
}
TINY = {"longcat": (16, 129, 8, 0, (20, 100)),
        "gigachat": (6, 97, 20, 12, (10, 100))}


def say(**line):
    print(json.dumps(line), flush=True)


def operands(geometry, seed, tiny):
    S, NB, MB, shared, (lo, hi) = (TINY if tiny else GEOMETRIES)[geometry]
    H, W, V, BS = (8, 40, 32, 16) if tiny else (64, 576, 512, 128)
    dtype = jnp.float32 if tiny else jnp.bfloat16
    rng = np.random.default_rng(seed)
    own = MB - shared
    assert 1 + shared + S * own <= NB
    tables = np.zeros((S, MB), np.int32)
    tables[:, :shared] = 1 + np.arange(shared)
    tables[:, shared:] = 1 + shared + rng.permutation(S * own).reshape(S, own)
    lengths = shared * BS + rng.integers(lo, hi, size=S)
    active = np.arange(S) % 16 != 5
    key = jax.random.PRNGKey(seed)
    kq, kp, kr = jax.random.split(key, 3)
    return dict(
        q=jax.random.normal(kq, (S, H, W), dtype),
        pool=jax.random.normal(kp, (NB, W, BS), dtype),
        rows=jax.random.normal(kr, (S, W), dtype),
        tables=jnp.asarray(tables), lengths=jnp.asarray(lengths, jnp.int32),
        active=jnp.asarray(active), V=V, scale=0.07)


def fused(calls, interpret):
    def run(pool, q, rows, tables, lengths, active, V, scale):
        total = 0.0
        for i in range(calls):
            out, pool = lda.paged_latent_decode_attention(
                q[..., :V], q[..., V:], rows, pool, tables,
                jnp.where(active, lengths + i, -1), scale=scale,
                interpret=interpret)
            total = total + out.astype(jnp.float32)
        return total, pool
    return run


def parent_form(module, calls, interpret):
    def run(pool, q, rows, tables, lengths, active, V, scale):
        # the parent's idle slots: an all-null table and length 0
        tables = jnp.where(active[:, None], tables, 0)
        total = 0.0
        for i in range(calls):
            at = jnp.where(active, lengths + i, 0)
            pool = module.paged_latent_append(pool, rows, tables, at,
                                              interpret=interpret)
            out = module.paged_latent_decode_attention(
                q, pool, tables, at + 1, value_dim=V, scale=scale,
                interpret=interpret)
            total = total + jnp.where(active[:, None, None],
                                      out.astype(jnp.float32), 0.0)
        return total, pool
    return run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", default=None,
                    help="a checkout that has paged_latent_append")
    ap.add_argument("--only", default="longcat,gigachat")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes in interpret mode, for a CPU rehearsal")
    args = ap.parse_args(argv)
    forms = {"fused": fused(args.calls, args.tiny)}
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_latent_decode_attention", os.path.join(
                args.parent, "deepspeed_tpu", "ops", "pallas",
                "latent_decode_attention.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        forms["parent"] = parent_form(module, args.calls, args.tiny)
    say(device=jax.devices()[0].device_kind, calls=args.calls)
    for geometry in args.only.split(","):
        ops = operands(geometry, args.seed, args.tiny)
        static = dict(V=ops.pop("V"), scale=ops.pop("scale"))
        pool = ops.pop("pool")
        results = {}
        for name, form in forms.items():
            fn = jax.jit(lambda pool, ops, form=form: form(
                pool, **ops, **static), donate_argnums=(0,))
            out, after = fn(jnp.copy(pool), ops)        # compiles; parity
            results[name] = (np.asarray(out), np.asarray(after[1:],
                                                         np.float32))
            times = []
            for _ in range(args.reps):
                mine = jnp.copy(pool)
                float(mine[0, 0, 0])
                t0 = time.perf_counter()
                out, _ = fn(mine, ops)
                float(out[0, 0, 0])
                times.append((time.perf_counter() - t0) * 1e3 / args.calls)
            q1, _, q3 = statistics.quantiles(times, n=4)
            say(geometry=geometry, form=name,
                ms_per_call=statistics.median(times),
                spread_pct=100 * (q3 - q1) / statistics.median(times),
                slots=int(ops["active"].shape[0]),
                live_blocks_mean=float(np.mean(
                    -(-(np.asarray(ops["lengths"]) + 1) // pool.shape[2]))))
        if "parent" in results:
            (a, pa), (b, pb) = results["fused"], results["parent"]
            say(geometry=geometry, outputs_equal=bool((a == b).all()),
                pools_equal=bool((pa == pb).all()),
                max_out_gap=float(np.abs(a - b).max()))


if __name__ == "__main__":
    main()
