"""Rotary tables by the published ``rope_parameters`` / ``rope_scaling``
group: plain and YaRN-scaled inverse frequencies. How a family pairs a
head's dims (half rotation, interleaved) and which dims it turns are its
own. Shared code: it imports no model."""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One layer kind's entry of the published ``rope_parameters``."""
    rope_theta: float
    partial_rotary_factor: float = 1.0
    rope_type: str = "default"
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise NotImplementedError(f"rope_type {self.rope_type!r}")


def rope_table(spec: RopeSpec, head_dim: int) -> Tuple[np.ndarray, float]:
    """``(inv_freq [rotary_dim / 2], what cos and sin are multiplied
    by)`` of one layer kind. YaRN (Peng et al. 2023, as the reference
    implementations compute it): each frequency is blended between the
    original and the one divided by ``factor`` by a linear ramp over the
    dimension index, from the dimension that turns ``beta_fast`` times
    within the original context (kept) to the one that turns
    ``beta_slow`` times (divided); cos and sin carry
    ``attention_factor`` (``0.1 ln(factor) + 1`` unless given)."""
    dim = int(head_dim * spec.partial_rotary_factor)
    base = float(spec.rope_theta)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if spec.rope_type == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0

    def turns_at(turns):
        return (dim * math.log(spec.original_max_position_embeddings
                               / (turns * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(turns_at(spec.beta_fast)), 0)
    high = min(math.ceil(turns_at(spec.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 - ramp) / pos_freqs + ramp / (spec.factor * pos_freqs)
    scale = (spec.attention_factor if spec.attention_factor is not None
             else 0.1 * math.log(spec.factor) + 1.0)
    return inv.astype(np.float32), float(scale)
