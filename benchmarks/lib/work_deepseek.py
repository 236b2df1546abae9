"""Operations and bytes the ``deepseek_v3`` layers need for a training step,
from shapes and counts alone (never from a trace): latent attention by the
(query, key) pairs its document-causal mask ADMITS, and the held experts by
token-expert pairs.  Convention as ``lib/work.py``: model FLOPs, 2 m n k a
matrix product forward, three times that for forward plus the two backward
products; recomputation never counts.  MLA's projections (``W_q``,
``W_kv_a``, ``W_kv_b``, ``W_o``), the dense MLP, the router, the shared
experts and the head are dense 2-D leaves: ``work_lm.dense_kernel_shapes``
counts them from the program's tree."""

from __future__ import annotations

import numpy as np

from benchmarks.lib.work_sdar import expected_pairs, expert_flops


def admitted_pairs(segment: np.ndarray) -> int:
    """(query, key) pairs the causal mask admits within the documents of the
    packed sequences ``segment`` [B, T] (or [T]): a document of ``n`` tokens
    admits ``n (n + 1) / 2``."""
    total = 0
    for row in np.atleast_2d(np.asarray(segment)):
        _, sizes = np.unique(row, return_counts=True)
        total += int((sizes * (sizes + 1) // 2).sum())
    return total


def mla_attn_flops(pairs: float, heads: int, qk_head_dim: int,
                   v_head_dim: int) -> float:
    """The two products over ``pairs`` admitted pairs of every head, ``q
    k^T`` at the query/key width and ``p v`` at the value width, forward and
    backward."""
    return 3.0 * 2.0 * pairs * heads * (qk_head_dim + v_head_dim)


def mla_attn_bytes(positions: int, heads: int, qk_head_dim: int,
                   v_head_dim: int, bytes_per_value: int = 2) -> float:
    """``q``, ``k`` (query/key width), ``v``, ``o`` (value width) and their
    gradients, once each (bfloat16): what one layer's attention must move
    whatever its tiles."""
    return 2.0 * positions * heads * (2 * qk_head_dim + 2 * v_head_dim) \
        * bytes_per_value


def sequence_other_flops(*, pairs: float, layers: int, expert_layers: int,
                         heads: int, qk_head_dim: int, v_head_dim: int,
                         positions: int, hidden: int, experts_per_token: int,
                         experts_held: int, routed_experts: int,
                         expert_width: int) -> float:
    """FLOPs of one sequence that are not a dense 2-D leaf: what the
    configuration's ``work.interaction_flops_per_example`` states.  The
    attention at ``pairs`` admitted pairs in every layer, the held experts
    at the EXPECTED pairs of ``positions`` in every expert layer."""
    routed = expected_pairs(positions, experts_per_token, experts_held,
                            routed_experts)
    return (layers * mla_attn_flops(pairs, heads, qk_head_dim, v_head_dim)
            + expert_layers * expert_flops(routed, hidden, expert_width))


def mean_admitted_pairs(traffic: dict, tokens: int, vocab: int,
                        seeds: int = 256) -> float:
    """The admitted pairs a sequence of the traffic mix holds on average
    over ``seeds`` sequences drawn as the driver draws them (seeds 0, 1,
    ...): the pairs behind ``interaction_flops_per_example``."""
    from benchmarks.drivers.lm_epoch import draw_sequences

    return float(np.mean([
        admitted_pairs(draw_sequences(s, 1, tokens, vocab, traffic)[1])
        for s in range(seeds)]))
