"""Calibration, not a cell: what one chip reaches on a large bf16 matmul
and on a plain elementwise pass, against the table's peaks. Run it once
when the table or the installation changes and record the figures in
PERF.md.

    chiprun -- python3 benchmark/tools/mxu_peak.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def timeit(fn, *args, n: int = 30) -> float:
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / n


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark.lib import harness
    from benchmark.lib.peaks import peaks_for
    try:
        device = harness.require_tpu(jax.device_count())
    except harness.NoDevice as e:
        print(f"mxu_peak: {e}", file=sys.stderr)
        return 2
    peaks = peaks_for(device["kind"])
    n = 8192
    a = jnp.ones((n, n), jnp.bfloat16)
    dt = timeit(jax.jit(lambda x, y: x @ y), a, a)
    flops = 2 * n ** 3 / dt
    big = jnp.ones((1 << 29,), jnp.bfloat16)          # 1 GiB
    dt = timeit(jax.jit(lambda x: x * 2 + 1), big, n=20)
    bw = 2 * big.size * 2 / dt                          # read + write
    print(json.dumps({
        "device": device, "matmul_8192_bf16_tflops": flops / 1e12,
        "share_of_peak_flops_pct": 100 * flops / peaks["bf16_flops"],
        "elementwise_1GiB_gb_per_s": bw / 1e9,
        "share_of_peak_bandwidth_pct": 100 * bw / peaks["hbm_bytes_per_s"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
