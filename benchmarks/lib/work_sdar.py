"""Operations and bytes the ``sdar_moe`` layers need for a training step,
from shapes and counts alone (never from a trace): the block-diffusion
attention by the (query, key) pairs its mask ADMITS, and the held experts by
token-expert pairs.  Convention as ``lib/work.py``: model FLOPs, 2 m n k a
matrix product forward, three times that for forward plus the two backward
products; recomputation never counts.  The router, the attention's
projections and the head are dense 2-D leaves: ``work_lm.dense_kernel_shapes``
counts them from the program's tree (the backbone's at ``2 T`` positions,
the head's at ``T``)."""

from __future__ import annotations

import numpy as np


def admitted_pairs(segment: np.ndarray, block_length: int) -> int:
    """(query, key) pairs the block-diffusion mask admits over the ``[x_t |
    x_0]`` stream of the packed sequences ``segment`` [B, T] (or [T]): per
    document of ``n`` tokens in blocks ``j`` of ``s_j`` tokens, noisy to
    noisy ``s_j^2``, noisy to the clean earlier blocks ``s_j j L_b``, clean
    to the clean blocks up to its own ``s_j min((j + 1) L_b, n)``."""
    total = 0
    for row in np.atleast_2d(np.asarray(segment)):
        _, sizes = np.unique(row, return_counts=True)
        for n in sizes.tolist():
            starts = np.arange(0, n, block_length)
            s = np.minimum(starts + block_length, n) - starts
            total += int((s * s + s * starts
                          + s * np.minimum(starts + block_length, n)).sum())
    return total


def bd_attn_flops(pairs: float, heads: int, head_dim: int) -> float:
    """The two products (``q k^T``, ``p v``) over ``pairs`` admitted pairs
    of every query head, forward and backward."""
    return 3.0 * pairs * 4 * head_dim * heads


def bd_attn_bytes(positions: int, heads: int, key_value_heads: int,
                  head_dim: int, bytes_per_value: int = 2) -> float:
    """``q``, ``k``, ``v``, ``o`` and their gradients, once each, at the
    stream's ``positions`` (bfloat16): what one layer's attention must
    move whatever its tiles."""
    return 2.0 * positions * (2 * heads + 2 * key_value_heads) * head_dim \
        * bytes_per_value


def expert_flops(pairs: float, hidden: int, width: int) -> float:
    """``pairs`` token-expert pairs through ``(silu(x W_g) * x W_u) W_d``
    (W_g, W_u hidden x width, W_d width x hidden), forward and backward."""
    return 3.0 * pairs * 2 * 3.0 * hidden * width


def expected_pairs(positions: int, per_token: int, held: int,
                   routed: int) -> float:
    """Pairs a layer routes to the held experts under uniform routing."""
    return positions * per_token * held / routed


def sequence_other_flops(*, pairs: float, layers: int, heads: int,
                         head_dim: int, positions: int, hidden: int,
                         experts_per_token: int, experts_held: int,
                         routed_experts: int, expert_width: int) -> float:
    """FLOPs of one sequence that are not a dense 2-D leaf: what the
    configuration's ``work.interaction_flops_per_example`` states.  The
    attention at ``pairs`` admitted pairs a layer, the held experts at the
    EXPECTED pairs of ``positions`` a layer."""
    routed = expected_pairs(positions, experts_per_token, experts_held,
                            routed_experts)
    return layers * (bd_attn_flops(pairs, heads, head_dim)
                     + expert_flops(routed, hidden, expert_width))


def mean_admitted_pairs(traffic: dict, tokens: int, vocab: int,
                        block_length: int, seeds: int = 256) -> float:
    """The admitted pairs a sequence of the traffic mix holds on average
    over ``seeds`` sequences drawn as the driver draws them (seeds 0, 1,
    ...): the pairs behind ``interaction_flops_per_example``."""
    from benchmarks.drivers.lm_epoch import draw_sequences

    return float(np.mean([
        admitted_pairs(draw_sequences(s, 1, tokens, vocab, traffic)[1],
                       block_length)
        for s in range(seeds)]))
