"""Operations and bytes of power retention's two kernels, from shapes:
what the algorithm needs, not what a program happens to do. The state is
counted at its PACKED size, the ``d (d + 1) / 2`` = 8256 distinct pairs
of a 128-wide head (plus the normaliser's column), whatever layout a pool
stores (the program's 65 circulant diagonals hold 8320): a share of a
peak computed from these cannot be raised by storing or moving more. A
FLOP is one multiply or one add."""
from __future__ import annotations


def packed_rows(head_dim: int) -> int:
    """Distinct degree-2 features of one head: squares and pairs."""
    return head_dim * (head_dim + 1) // 2


def state_bytes(kv_heads: int, head_dim: int, itemsize: int = 4) -> int:
    """One sequence's state of one layer: ``S [KH, D, d]`` and ``z [KH,
    D]``, ``D`` packed."""
    return kv_heads * packed_rows(head_dim) * (head_dim + 1) * itemsize


def decode_bytes(live_slots: float, kv_heads: int, head_dim: int,
                 itemsize: int = 4) -> float:
    """Bytes ONE layer's state-update decode call must move: every live
    slot's state once in and once out (queries, keys, values and outputs
    are a few hundred KB and are left out)."""
    return 2.0 * live_slots * state_bytes(kv_heads, head_dim, itemsize)


def prefill_flops(tokens: float, chunk: int, heads: int, kv_heads: int,
                  head_dim: int) -> float:
    """One layer's chunked form over ``tokens`` live prompt tokens:
    inside a chunk the causal half of scores and weighted values (2 x 2
    x C/2 x d a query head a token), across chunks every query head's
    read of the packed state and every key/value head's update of it (2
    x D x d each a token); the first chunk reads an empty state."""
    D = packed_rows(head_dim)
    later = max(tokens - chunk, 0.0)
    return (tokens * heads * 2.0 * chunk * head_dim
            + later * heads * 2.0 * D * head_dim
            + tokens * kv_heads * 2.0 * D * head_dim)


def prefill_bytes(tokens: float, heads: int, kv_heads: int, head_dim: int,
                  itemsize: int = 2, state_itemsize: int = 4) -> float:
    """One layer's chunked form must read q, k, v and write y once a
    token, and write the final state once."""
    return (tokens * (2 * heads + 2 * kv_heads) * head_dim * itemsize
            + state_bytes(kv_heads, head_dim, state_itemsize))
