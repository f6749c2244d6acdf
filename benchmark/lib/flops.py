"""Operations and bytes from shapes. These are the yardstick's own
functions: what the algorithm needs, not what a program happens to do
(no recomputation, no padding), so a share of a peak computed from them
cannot be raised by doing more work.

All counts are for a decoder-only transformer with learned positions,
two-matrix MLP of width ``ffn`` and a tied LM head, which is GPT-2.
A FLOP is one multiply or one add (a multiply-add is two).
"""
from __future__ import annotations


def matmul_params(n_embd: int, n_layer: int, ffn: int) -> int:
    """Weights that take part in a matmul for every token, without the
    LM head: qkv (3E*E), attention out (E*E), MLP in and out (2*E*F)."""
    return n_layer * (4 * n_embd * n_embd + 2 * n_embd * ffn)


def forward_flops_per_token(n_embd: int, n_layer: int, ffn: int,
                            vocab: int, seq: int) -> float:
    """Forward FLOPs per token at sequence length ``seq``: 2 per weight
    of every matmul incl. the LM head (``vocab`` unpadded rows), plus
    causal attention: scores and values are 2*2*E FLOPs per (query, key)
    pair, and a query at position t sees t+1 keys, (seq+1)/2 on average.
    The position table and the embedding lookup do no FLOPs."""
    dense = 2.0 * (matmul_params(n_embd, n_layer, ffn) + n_embd * vocab)
    attn = n_layer * 4.0 * n_embd * (seq + 1) / 2.0
    return dense + attn


def train_flops_per_token(n_embd: int, n_layer: int, ffn: int, vocab: int,
                          seq: int) -> float:
    """Forward plus backward (twice the forward). Recomputation under
    remat does not count."""
    return 3.0 * forward_flops_per_token(n_embd, n_layer, ffn, vocab, seq)


def flash_flops(batch: int, heads: int, q_len: int, kv_len: int,
                head_dim: int, causal: bool = True,
                backward: bool = False) -> float:
    """FLOPs one attention call needs: QK^T and PV are 2*D FLOPs per
    (query, key) pair each; a causal call of q_len == kv_len has
    q*(q+1)/2 live pairs. Backward needs dV, dP, dQ and dK (four such
    products) beside the forward's two: 2.0x more, and the recompute of
    the scores inside a flash backward is not counted."""
    if causal:
        pairs = q_len * (q_len + 1) / 2.0 + q_len * (kv_len - q_len)
    else:
        pairs = float(q_len) * kv_len
    fwd = batch * heads * pairs * 4.0 * head_dim
    return fwd * 3.0 if backward else fwd


def flash_bytes(batch: int, heads: int, q_len: int, kv_len: int,
                head_dim: int, itemsize: int = 2,
                backward: bool = False) -> float:
    """Bytes one attention call must move: read Q, K, V and write O
    once. ``backward=True`` adds the backward call: it reads Q, K, V,
    O, dO and writes dQ, dK, dV."""
    q = batch * heads * q_len * head_dim * itemsize
    kv = batch * heads * kv_len * head_dim * itemsize
    fwd = 2 * q + 2 * kv
    return fwd + (3 * q + 2 * kv) + (q + 2 * kv) if backward else fwd


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def kv_bytes_per_token(n_layer: int, kv_heads: int, head_dim: int,
                       itemsize: int = 2) -> int:
    """K and V of one position over all layers."""
    return 2 * n_layer * kv_heads * head_dim * itemsize


def paged_decode_bytes(live_tokens: int, kv_heads: int, head_dim: int,
                       itemsize: int = 2) -> float:
    """Bytes ONE layer's paged-decode kernel call must read: K and V of
    every live position of every resident sequence (queries and outputs
    are a few KB and are left out)."""
    return 2.0 * live_tokens * kv_heads * head_dim * itemsize
