"""Operations and bytes of the Granite hybrid's Mamba-2 layer, from
shapes: what the algorithm needs, not what a program happens to do (live
slots and live prompt tokens only, no padding of a bucket, the causal
half of a chunk, nothing read twice), so a share of a peak computed from
them cannot be raised by moving or computing more. A FLOP is one multiply
or one add."""
from __future__ import annotations


def state_update_bytes(live_slots: float, state_bytes: int) -> float:
    """Bytes ONE layer's decode state update must move: every live
    slot's state ``S [heads, d_head, d_state]`` once in and once out
    (the step's ``x``, ``B``, ``C``, ``dt`` and ``y`` are a few hundred
    KB and are left out; the convolution's tail is another scope's)."""
    return 2.0 * live_slots * state_bytes


def scan_flops(tokens: float, chunk: int, heads: int, d_head: int,
               d_state: int) -> float:
    """One layer's chunked (SSD) form over ``tokens`` live prompt tokens
    with one B/C group: inside a chunk the causal half of the scores ``C
    B^T`` (shared by the heads: 2 x d_state a pair) and of the weighted
    inputs (2 x d_head a pair a head); across chunks every token's read
    of the state before its chunk and its part of the state at the
    chunk's end (2 x d_state x d_inner each). The first chunk reads an
    empty state."""
    d_inner = heads * d_head
    later = max(tokens - chunk, 0.0)
    pairs = tokens * min(chunk, tokens) / 2.0
    return (pairs * 2.0 * (d_state + d_inner)
            + later * 2.0 * d_state * d_inner
            + tokens * 2.0 * d_state * d_inner)


def scan_bytes(tokens: float, heads: int, d_head: int, d_state: int,
               state_bytes: int, itemsize: int = 2) -> float:
    """One layer's chunked form must read ``x``, ``B``, ``C`` and ``dt``
    and write ``y`` once a token, and write the final state once."""
    d_inner = heads * d_head
    return (tokens * (2 * d_inner + 2 * d_state + heads) * itemsize
            + state_bytes)


def scan_seconds(tokens: float, s: dict, peaks: dict) -> float:
    """The least time one layer's chunked form over ``tokens`` live
    tokens could take: its FLOPs at peak or its bytes at the bandwidth,
    whichever is longer. ``s``: the family's ``shapes``."""
    return max(
        scan_flops(tokens, s["mamba_chunk"], s["mamba_heads"],
                   s["mamba_d_head"], s["mamba_d_state"])
        / peaks["bf16_flops"],
        scan_bytes(tokens, s["mamba_heads"], s["mamba_d_head"],
                   s["mamba_d_state"], s["state_bytes"], s["itemsize"])
        / peaks["hbm_bytes_per_s"])
