"""Operations and bytes of Laguna's attention kernels, from shapes: what
the algorithm needs, not what a program happens to do (no ring rows
outside the window, no dead table entries, no masked half of a diagonal
block), so a share of a peak computed from them cannot be raised by
reading or computing more. A FLOP is one multiply or one add."""
from __future__ import annotations


def row_bytes(kv_heads: int, head_dim: int, itemsize: int = 2) -> int:
    """One cached position of one layer: K and V of every kv head."""
    return 2 * kv_heads * head_dim * itemsize


def decode_read_bytes(rows: float, kv_heads: int, head_dim: int,
                      itemsize: int = 2) -> float:
    """Bytes ONE layer's decode-attention call must read: the cached
    rows its queries see, once (a kv head's whole query group shares
    them; queries and outputs are a few hundred KB and are left out).
    ``rows``: summed over the resident sequences, the whole context on a
    full layer and ``min(context, window)`` on a window layer, so a
    window layer that reads its ring's slack, or its whole context,
    reads LOW."""
    return float(rows) * row_bytes(kv_heads, head_dim, itemsize)


def seen_pairs(tokens: int, window: int | None = None) -> float:
    """(query, key) pairs a causal prefill of ``tokens`` positions sees:
    ``i + 1`` keys for query ``i``, at most ``window`` of them."""
    if window is None or window >= tokens:
        return tokens * (tokens + 1) / 2.0
    return window * (window + 1) / 2.0 + (tokens - window) * float(window)


def prefill_attention_flops(tokens: int, heads: int, head_dim: int,
                            window: int | None = None) -> float:
    """One layer's prefill attention: scores and weighted values, 2 x
    ``head_dim`` FLOPs each a seen pair a query head."""
    return 4.0 * head_dim * heads * seen_pairs(tokens, window)
