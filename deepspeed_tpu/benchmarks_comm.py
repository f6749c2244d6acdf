"""Collective micro-benchmarks — the ``ds_bench`` /
``benchmarks/communication/*`` analog: sweep message sizes over
all_reduce / all_gather / reduce_scatter / all_to_all / ppermute on the
live device set and report algorithmic bandwidth. On the virtual CPU mesh
the numbers are meaningless but the sweep validates every collective
lowers and runs; on real slices it measures ICI.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

COLLECTIVES = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
               "ppermute")


def _op(name: str, axis: str, n: int):
    """Dispatch through the project's comm facade (the reference ds_bench
    measures through deepspeed.comm, not the raw backend)."""
    from deepspeed_tpu.comm import comm as C
    if name == "all_reduce":
        return lambda x: C.all_reduce(x, axis_name=axis)
    if name == "all_gather":
        return lambda x: C.all_gather(x, axis_name=axis)
    if name == "reduce_scatter":
        return lambda x: C.reduce_scatter(x, axis_name=axis)
    if name == "all_to_all":
        return lambda x: C.all_to_all(x.reshape(n, -1), axis_name=axis,
                                      split_axis=0,
                                      concat_axis=0).reshape(-1)
    if name == "ppermute":
        perm = [(i, (i + 1) % n) for i in range(n)]
        return lambda x: C.ppermute(x, perm, axis_name=axis)
    raise ValueError(name)


def _bus_bytes(name: str, per_device_bytes: int, n: int) -> float:
    """Algorithmic bus bytes PER DEVICE from the per-device message size
    (ring conventions, the reference's bandwidth formulas)."""
    if name == "all_reduce":
        return 2 * per_device_bytes * (n - 1) / n
    if name in ("all_gather", "reduce_scatter"):
        return per_device_bytes * (n - 1) / n
    if name == "all_to_all":
        return per_device_bytes * (n - 1) / n
    return per_device_bytes  # ppermute: one hop


def run_sweep(sizes_mb=(1, 4, 16), trials: int = 5,
              collectives=COLLECTIVES, axis: str = "data",
              mesh: Mesh = None) -> List[Dict]:
    devs = jax.devices()
    n = len(devs)
    mesh = mesh or Mesh(np.asarray(devs), (axis,))
    results = []
    sync = jax.jit(lambda y: jnp.sum(y.reshape(-1)[:1]))
    for name in collectives:
        for mb in sizes_mb:
            elems = int(mb * (1 << 20)) // 4
            # per-device shards must themselves split n ways for
            # reduce_scatter/all_to_all → global size a multiple of n^2
            per_dev = max(n * n, elems // (n * n) * (n * n))
            x = jnp.ones((per_dev,), jnp.float32)
            fn = jax.jit(jax.shard_map(
                _op(name, axis, n), mesh=mesh, in_specs=P(axis),
                out_specs=P(axis) if name != "all_gather" else P(),
                check_vma=False))
            # warm up BOTH programs (the host transfer in sync() is
            # the barrier: it cannot return before the result exists)
            float(sync(fn(x)))
            t0 = time.perf_counter()
            for _ in range(trials):
                y = fn(x)
            float(sync(y))
            dt = (time.perf_counter() - t0) / trials
            nbytes = per_dev // n * 4  # per-device payload
            busbw = _bus_bytes(name, nbytes, n) / max(dt, 1e-9)
            results.append({
                "collective": name, "size_mb": mb, "devices": n,
                "latency_ms": round(dt * 1e3, 3),
                "busbw_GiBps": round(busbw / (1 << 30), 3)})
    return results


def main() -> None:
    import argparse
    import json
    ap = argparse.ArgumentParser(description="collective bandwidth sweep")
    ap.add_argument("--sizes-mb", default="1,4,16")
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--collectives", default=",".join(COLLECTIVES))
    args = ap.parse_args()
    out = run_sweep(tuple(float(s) for s in args.sizes_mb.split(",")),
                    args.trials, tuple(args.collectives.split(",")))
    for r in out:
        print(json.dumps(r))


if __name__ == "__main__":
    main()
