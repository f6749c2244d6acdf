"""Operations and bytes of the Nemotron-H expert layer's held experts,
from shapes: what the algorithm needs, not what a program happens to do
(no padding of the buffer or of a stored weight, no expert that got no
token), so a share of a peak computed from them cannot be raised by doing
more work. An expert is UNGATED: ``relu(u W_up)^2 W_down``, TWO matrices
(``lib/flops_longcat.py`` counts a SwiGLU expert's three). A FLOP is one
multiply or one add."""
from __future__ import annotations


def expert_weight_bytes(hidden: int, expert_ffn: int,
                        itemsize: int = 2) -> int:
    """One expert's two matrices (up, down) at the PUBLISHED width,
    whatever the storage or the kernel's tiles."""
    return 2 * hidden * expert_ffn * itemsize


def expert_flops_per_pick(hidden: int, expert_ffn: int) -> int:
    """One token through one ungated expert: two matmuls, 2 FLOPs a
    weight."""
    return 4 * hidden * expert_ffn


def experts_seconds(experts_hit: float, landed_picks: float, hidden: int,
                    expert_ffn: int, itemsize: int, peaks: dict) -> float:
    """The least time one execution of the held-experts grouped matmul
    could take: the weights of the experts that got a token read once
    each, or the landed picks' FLOPs at peak, whichever is longer."""
    return max(
        experts_hit * expert_weight_bytes(hidden, expert_ffn, itemsize)
        / peaks["hbm_bytes_per_s"],
        landed_picks * expert_flops_per_pick(hidden, expert_ffn)
        / peaks["bf16_flops"])
