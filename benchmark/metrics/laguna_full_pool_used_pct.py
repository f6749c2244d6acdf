"""Mean share of the full layers' block pool held by resident sequences
over the committed decode steps of the process (the server's
``serve_kv_used_block_steps_total`` over ``serve_decode_steps_total``
times the pool's blocks, the engine's ``kv_pool_blocks``). The pool is
smaller than slots x span: near 100 a free slot waits on blocks; the
earlier line (``laguna_cache_counters``) has the steps that did."""

from benchmark.lib import laguna_readers as lg


def read(run, trace):
    got = lg.counters()
    blocks = ((run.get("config") or {}).get("engine") or {}).get(
        "kv_pool_blocks")
    if not got or not blocks:
        return None
    return 100.0 * got["used_block_steps"] / (got["steps"] * blocks)
