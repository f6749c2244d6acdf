"""Operations of GigaChat3's prompt-chunk attention kernel
(``latent_chunk_attention``, materialised form), from shapes: the pairs
a causal chunk may see and the pool blocks that hold them, not what a
program happens to do (no masked half of a diagonal block, no dead table
entry), so a share of a peak computed from them cannot be raised by
computing more. K and V are rebuilt from the cached latents inside the
kernel, once a head a block: that is work the algorithm DOES in this
form (the cache holds no K or V), and it is counted. A FLOP is one
multiply or one add."""
from __future__ import annotations

import math


def chunk_seen_pairs(start: int, rows: int) -> float:
    """(query, key) pairs of a chunk of ``rows`` queries at positions
    ``start .. start + rows - 1``: query ``i`` sees ``start + i + 1``
    keys."""
    return rows * float(start) + rows * (rows + 1) / 2.0


def chunk_attention_flops(start: int, rows: int, heads: int, qk_dim: int,
                          v_dim: int) -> float:
    """Scores and weighted values of one attention's chunk: 2 x ``qk_dim
    + v_dim`` FLOPs a seen pair a head."""
    return 2.0 * (qk_dim + v_dim) * heads * chunk_seen_pairs(start, rows)


def chunk_rebuild_flops(start: int, rows: int, heads: int, kv_rank: int,
                        nope_dim: int, v_dim: int, block: int) -> float:
    """K's no-rope part and V of every cached row the chunk sees, rebuilt
    from its latent a head: 2 x ``kv_rank`` x ``nope_dim + v_dim`` FLOPs
    a row a head, whole pool blocks (a block is rebuilt whole)."""
    seen_rows = block * math.ceil((start + rows) / block)
    return 2.0 * kv_rank * (nope_dim + v_dim) * heads * seen_rows


def chunk_kernel_flops(start: int, rows: int, s: dict, block: int) -> float:
    """One call of the kernel (one attention, one chunk); ``s`` the
    family's ``shapes()``."""
    return (chunk_attention_flops(start, rows, s["heads"], s["qk_dim"],
                                  s["v_dim"])
            + chunk_rebuild_flops(start, rows, s["heads"], s["kv_rank"],
                                  s["nope_dim"], s["v_dim"], block))


def absorbed_chunk_flops(start: int, rows: int, heads: int, kv_rank: int,
                         rope_dim: int) -> float:
    """The same chunk in the ABSORBED form (queries carried into the
    latent space, whole rows attended, as decode does for its one row):
    2 x (2 ``kv_rank`` + ``rope_dim``) FLOPs a seen pair a head. Not
    what the program runs; PERF.md compares the two."""
    return 2.0 * (2 * kv_rank + rope_dim) * heads * chunk_seen_pairs(start,
                                                                     rows)
