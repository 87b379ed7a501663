"""Operations and bytes that a training step NEEDS, from shapes alone.

Work the algorithm requires, not work the program executes: a forward
replayed under rematerialisation, padded positions and masked-out score
blocks are time, not work. All counts are multiply-adds x 2.
"""
from __future__ import annotations


def attention_pairs(lengths, causal: bool) -> float:
    """(query, key) pairs attention needs over a batch of sequences:
    len^2 per sequence, half of it where a causal mask hides the rest."""
    pairs = float(sum(int(n) * int(n) for n in lengths))
    return pairs / 2 if causal else pairs


def transformer_train_flops(*, n_layer: int, d: int, ffn: int,
                            tokens: int, pairs: float, head_rows: int,
                            head_params: int, extra_params_rows: float = 0.0
                            ) -> float:
    """Forward + backward of a transformer trunk and its output head.

    6 x parameters x rows for every weight matmul (2 forward, 4
    backward): the blocks' 4 d^2 + 2 d ffn on every real token, the
    head's `head_params` on `head_rows` rows (all tokens for a language
    model's tied head, the gathered positions for a masked LM).
    Attention scores and values: 4 d per pair forward, 12 d with the
    backward. `extra_params_rows` adds small per-sequence heads
    (parameters x rows)."""
    block = n_layer * (4 * d * d + 2 * d * ffn)
    return (6.0 * block * tokens + 6.0 * head_params * head_rows
            + 6.0 * extra_params_rows + 12.0 * n_layer * d * pairs)


def flash_attention_cost(*, n_layer: int, d: int, rows: int, pairs: float,
                         bytes_per_el: int = 2) -> dict:
    """What the attention kernels of one training step need: forward
    4 d flops per pair, backward 2.5 x that (dq, dk, dv and the score
    recomputation the flash backward cannot avoid), per layer; q, k, v,
    o read or written once forward, q, k, v, o, do, dq, dk, dv once
    backward (`rows` = batch x seq positions the tensors hold)."""
    fwd = 4.0 * d * pairs
    flops = n_layer * fwd * 3.5
    bytes_moved = n_layer * (4 + 8) * rows * d * bytes_per_el
    return {"flops": flops, "bytes": float(bytes_moved)}


def least_seconds(cost: dict, peak: dict) -> tuple:
    """Roofline: the larger of flops / peak and bytes / bandwidth, and
    which of the two it is."""
    t_f = cost["flops"] / peak["bf16_flops_per_s"]
    t_b = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
