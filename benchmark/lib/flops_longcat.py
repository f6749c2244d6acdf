"""Operations and bytes of LongCat-Flash's two new kernels, from shapes:
what the algorithm needs, not what a program happens to do (no padding,
no rows beyond the live ones, no expert that got no token), so a share
of a peak computed from them cannot be raised by doing more work.
A FLOP is one multiply or one add."""
from __future__ import annotations


def latent_decode_bytes(live_rows: float, latent_width: int,
                        itemsize: int = 2) -> float:
    """Bytes ONE attention's latent-decode kernel call must read: the
    cached row ``[c_kv ; k_rope]`` of every live position of every
    resident sequence, once (all query heads share it; queries and
    outputs are a few hundred KB and are left out)."""
    return float(live_rows) * latent_width * itemsize


def expert_weight_bytes(hidden: int, expert_ffn: int,
                        itemsize: int = 2) -> int:
    """One expert's three matrices (gate, up, down)."""
    return 3 * hidden * expert_ffn * itemsize


def expert_flops_per_pick(hidden: int, expert_ffn: int) -> int:
    """One token through one SwiGLU expert: three matmuls, 2 FLOPs a
    weight."""
    return 6 * hidden * expert_ffn


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
