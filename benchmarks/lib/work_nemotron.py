"""Operations and bytes the ``nemotron_h`` layers need for a training step,
from shapes and counts alone (never from a trace): the chunked Mamba-2 scan
(SSD) and the routed experts.  Convention as ``lib/work.py`` and
``lib/work_lm.py``: model FLOPs, 2 m n k a matrix product forward, three
times that for forward plus the two backward products; recomputation never
counts.  The router's product, the latent projections, the shared expert,
the mixers' projections and the head are dense 2-D leaves:
``work_lm.dense_kernel_shapes`` counts them from the program's tree.  The
routed experts (3-D leaves) are counted here, by token-expert PAIRS."""

from __future__ import annotations

from benchmarks.lib import work_lm


def ssd_chunk_flops(chunk: int, head_dim: int, state: int,
                    heads_per_group: int) -> float:
    """One chunk of one head, forward, in the chunked form: ``C B^T`` (2 C^2
    N, made once a group: a head's share is 1 / heads_per_group of it),
    ``(C B^T * L) (dt x)`` (2 C^2 P), the chunk's state ``(dt x)^T B`` and
    ``C S_0`` (2 C P N each)."""
    c = float(chunk)
    return (2 * c * c * state / heads_per_group + 2 * c * c * head_dim
            + 4 * c * head_dim * state)


def ssd_flops(tokens: int, heads: int, groups: int, head_dim: int, state: int,
              chunk: int) -> float:
    """The chunked scan of one layer over one sequence, forward and
    backward."""
    chunks = -(-tokens // chunk)
    return 3.0 * chunks * heads * ssd_chunk_flops(chunk, head_dim, state,
                                                  heads // groups)


def ssd_bytes(tokens: int, heads: int, groups: int, head_dim: int, state: int,
              bytes_per_value: int = 2) -> float:
    """What one layer's scan must move, forward and backward: x read and y
    written (H P a token each), B and C read (G N each) forward; those and
    their gradients backward; ``dt`` in float32."""
    values = tokens * (2 * heads * head_dim + 2 * groups * state)
    return 3.0 * values * bytes_per_value + 3.0 * tokens * heads * 4


def expert_flops(pairs: float, latent: int, width: int) -> float:
    """``pairs`` token-expert pairs through ``relu(x W1)^2 W2`` (W1 latent x
    width, W2 width x latent), forward and backward."""
    return 3.0 * pairs * 2 * 2.0 * latent * width


def expert_bytes(pairs: float, experts: int, latent: int, width: int) -> float:
    """The held experts' weights (two matrices an expert) read once forward
    and once backward as the products take them (2 bytes) and their float32
    gradient written; each pair's row read and written in the latent,
    forward, and with its gradient backward (2 bytes)."""
    weights = experts * 2.0 * latent * width
    return weights * (2 + 2 + 4) + 3.0 * pairs * 2 * latent * 2


def expected_pairs(tokens: int, per_token: int, held: int, routed: int) -> float:
    """Pairs a layer routes to the held experts under uniform routing."""
    return tokens * per_token * held / routed


def sequence_other_flops(*, tokens: int, pattern: str, attention_heads: int,
                         head_dim: int, mamba_heads: int, mamba_groups: int,
                         mamba_head_dim: int, state: int, chunk: int,
                         conv_width: int, experts_per_token: int,
                         experts_held: int, routed_experts: int, latent: int,
                         expert_width: int) -> float:
    """FLOPs of one sequence that are not a dense 2-D leaf: what the
    configuration's ``work.interaction_flops_per_example`` states.  The
    routed experts at the EXPECTED pairs a layer."""
    conv = mamba_heads * mamba_head_dim + 2 * mamba_groups * state
    pairs = expected_pairs(tokens, experts_per_token, experts_held,
                           routed_experts)
    return (pattern.count("*") * work_lm.attention_flops(
                tokens, attention_heads, head_dim)
            + pattern.count("M") * (
                ssd_flops(tokens, mamba_heads, mamba_groups, mamba_head_dim,
                          state, chunk)
                + work_lm.conv_flops(tokens, conv, conv_width))
            + pattern.count("E") * expert_flops(pairs, latent, expert_width))
