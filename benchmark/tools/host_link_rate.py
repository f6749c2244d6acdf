"""Calibration, not a cell: what the link between one chip and its
host's pinned memory carries, by direction. 256 MB float32 buffers go
through ``jax.device_put`` between the ``pinned_host`` and ``device``
memory kinds INSIDE one jitted program (as the offload stream's do):
host to device alone, device to host alone, and both in one program.
Record the three figures in PERF.md section 5, beside ``mxu_peak.py``'s;
``offload_link_gb_per_s`` is read against them.

    chiprun -- python3 benchmark/tools/host_link_rate.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

BUFFER_BYTES = 256 << 20
BUFFERS = 4            # per direction and program: 1 GiB each way


def timeit(fn, sources, into=None, n: int = 8) -> float:
    """Seconds a call. ``into``: host buffers the call writes over; they
    are donated and each call's results are the next call's, as the
    engine's streamed state is (a program that had to ALLOCATE a GiB of
    pinned host memory a call would be timing that: 0.9 GB/s)."""
    import jax

    def call(into):
        return fn(*sources) if into is None else fn(*sources, into)
    out = call(into)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = call(None if into is None else
                   (out[-1] if isinstance(out, tuple) else out))
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import harness
    try:
        device = harness.require_tpu(jax.device_count())
    except harness.NoDevice as e:
        print(f"host_link_rate: {e}", file=sys.stderr)
        return 2
    chip = jax.devices()[0]
    host = SingleDeviceSharding(chip, memory_kind="pinned_host")
    hbm = SingleDeviceSharding(chip, memory_kind="device")
    n = BUFFER_BYTES // 4

    def buffers(where, base):
        return [jax.device_put(jnp.full((n,), float(base + i), jnp.float32),
                               where) for i in range(BUFFERS)]
    on_host, on_chip = buffers(host, 0), buffers(hbm, 10)
    move = lambda xs, to: [jax.device_put(x, to) for x in xs]  # noqa: E731
    fetch = jax.jit(lambda hs: move(hs, hbm), out_shardings=[hbm] * BUFFERS)
    # ``into`` is only written over: kept (jit drops an unused argument)
    # so that the host results alias it
    store = jax.jit(lambda ds, into: move(ds, host), donate_argnums=(1,),
                    keep_unused=True, out_shardings=[host] * BUFFERS)
    both = jax.jit(lambda hs, ds, into: (move(hs, hbm), move(ds, host)),
                   donate_argnums=(2,), keep_unused=True,
                   out_shardings=([hbm] * BUFFERS, [host] * BUFFERS))
    gb = BUFFERS * BUFFER_BYTES / 1e9
    t_fetch = timeit(fetch, (on_host,))
    t_store = timeit(store, (on_chip,), buffers(host, 20))
    t_both = timeit(both, (on_host, on_chip), buffers(host, 30))
    print(json.dumps({
        "device": device, "buffer_mb": BUFFER_BYTES >> 20,
        "buffers_each_way": BUFFERS,
        "host_to_device_alone_gb_per_s": gb / t_fetch,
        "device_to_host_alone_gb_per_s": gb / t_store,
        # both directions in one program: each direction's bytes over
        # the program's time, and the two together
        "both_each_direction_gb_per_s": gb / t_both,
        "both_total_gb_per_s": 2 * gb / t_both,
        # the solo times' sum over the joint time: 1.0 = the directions
        # took turns, 2.0 = they ran together at their solo rates
        "overlap_ratio": (t_fetch + t_store) / t_both,
        "seconds": {"fetch": t_fetch, "store": t_store, "both": t_both}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
