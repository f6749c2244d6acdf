"""Operations and bytes of MiMo-V2's attention kernels, from shapes: what
the algorithm needs, not what a program happens to do (no ring rows
outside the window, no dead table entries, no masked half of a diagonal
block, no zero of a block-diagonal operand), so a share of a peak
computed from them cannot be raised by reading or computing more. A key
head is ``head_dim`` wide and a value head ``v_head_dim``; a layer kind
has its own key/value head count. A FLOP is one multiply or one add."""
from __future__ import annotations


def row_bytes(kv_heads: int, head_dim: int, v_head_dim: int,
              itemsize: int = 2) -> int:
    """One cached position of one layer: K and V of every kv head of the
    layer's kind (768 + 512 lanes x 2 B = 2560 B on a full layer of the
    published model, 1536 + 1024 lanes = 5120 B on a window layer)."""
    return kv_heads * (head_dim + v_head_dim) * itemsize


def decode_read_bytes(rows: float, kv_heads: int, head_dim: int,
                      v_head_dim: int, itemsize: int = 2) -> float:
    """Bytes ONE layer's decode-attention call must read: the cached
    rows its queries see, once (a kv head's whole query group shares
    them; queries, sinks and outputs are a few hundred KB and are left
    out). ``rows``: summed over the resident sequences, the whole
    context on a full layer and ``min(context, window)`` on a window
    layer, so a window layer that reads its ring's slack block reads
    LOW."""
    return float(rows) * row_bytes(kv_heads, head_dim, v_head_dim, itemsize)


def seen_pairs(tokens: int, window: int | None = None) -> float:
    """(query, key) pairs a causal prefill of ``tokens`` positions sees:
    ``i + 1`` keys for query ``i``, at most ``window`` of them. The sink
    is a column of scalars, not a key: it adds no pair."""
    if window is None or window >= tokens:
        return tokens * (tokens + 1) / 2.0
    return window * (window + 1) / 2.0 + (tokens - window) * float(window)


def prefill_attention_flops(tokens: int, heads: int, head_dim: int,
                            v_head_dim: int,
                            window: int | None = None) -> float:
    """One layer's prefill attention: scores (2 x ``head_dim`` FLOPs a
    seen pair a query head) and weighted values (2 x ``v_head_dim``)."""
    return 2.0 * (head_dim + v_head_dim) * heads * seen_pairs(tokens, window)


def prefill_attention_bytes(tokens: int, heads: int, kv_heads: int,
                            head_dim: int, v_head_dim: int,
                            itemsize: int = 2) -> float:
    """One layer's prefill attention: q in and the output out once a
    query head, K and V in once a kv head."""
    return float(tokens) * itemsize * (
        heads * (head_dim + v_head_dim)
        + kv_heads * (head_dim + v_head_dim))
