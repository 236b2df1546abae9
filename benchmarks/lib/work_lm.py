"""Operations and bytes a sequence model's training step needs, from shapes
alone (never from a trace): the dense kernels as ``train_step_mfu_pct``
counts them, causal attention, and the chunked gated delta rule.

Convention as ``lib/work.py``: model FLOPs, 2 m n per multiply-add matrix
product forward, three times that for forward plus the two backward products;
recomputation never counts.  ``T`` is the tokens of one sequence (one
example)."""

from __future__ import annotations


def dense_kernel_shapes(leaf_shapes: dict[str, tuple], tokens: int
                        ) -> list[tuple[int, int]]:
    """``[(tokens * m, n)]`` for every matrix-product kernel ``[m, n]``, so
    that ``work.dense_flops_per_example`` counts 2 * tokens * m * n forward a
    sequence.  The depthwise convolutions (``conv_*``: no matrix product)
    are counted by :func:`conv_flops`."""
    return [(tokens * s[0], s[1]) for path, s in sorted(leaf_shapes.items())
            if len(s) == 2 and not path.rsplit("/", 1)[-1].startswith("conv_")]


def attention_flops(tokens: int, heads: int, head_dim: int) -> float:
    """Causal self-attention of one sequence, forward and backward: the two
    products (``q k^T``, ``p v``) over HALF the square (keys at positions
    <= t; document masks skip more, which is not counted as less)."""
    forward = 2 * (2.0 * tokens * tokens * head_dim * heads) / 2
    return 3.0 * forward


def delta_chunk_flops(chunk: int, dk: int, dv: int) -> float:
    """One chunk of one head, forward, in the chunked form: ``K K^T`` and
    ``Q K^T`` (2 C^2 dk each), the unit-lower-triangular solve by forward
    substitution (C^3), ``U = T V`` (2 C^2 dv), ``W = T K`` (2 C^2 dk),
    ``W S_0``, ``Q S_0`` and ``K^T V'`` (2 C dk dv each), ``attn V'``
    (2 C^2 dv)."""
    c = float(chunk)
    return 2 * c * c * (3 * dk + 2 * dv) + c ** 3 + 6 * c * dk * dv


def delta_rule_flops(tokens: int, heads: int, dk: int, dv: int,
                     chunk: int) -> float:
    """The chunked gated delta rule of one layer over one sequence, forward
    and backward."""
    chunks = -(-tokens // chunk)
    return 3.0 * chunks * heads * delta_chunk_flops(chunk, dk, dv)


def delta_rule_bytes(tokens: int, heads: int, dk: int, dv: int,
                     bytes_per_value: int = 2) -> float:
    """What one layer's delta rule must move, forward and backward: q, k, v
    read and o written forward; those four and their gradients backward; the
    gates in float32."""
    values = tokens * heads * (2 * dk + 2 * dv)
    return 3.0 * values * bytes_per_value + 3.0 * tokens * heads * 2 * 4


def conv_flops(tokens: int, channels: int, width: int) -> float:
    """Depthwise causal convolutions of one layer, forward and backward."""
    return 3.0 * 2.0 * tokens * channels * width


def sequence_other_flops(*, tokens: int, layer_types: list[str],
                         full_heads: int, head_dim: int, linear_heads: int,
                         dk: int, dv: int, chunk: int, conv_width: int
                         ) -> float:
    """FLOPs of one sequence that are not a dense kernel: what a
    configuration's ``work.interaction_flops_per_example`` states."""
    n_full = sum(1 for k in layer_types if k == "full_attention")
    n_lin = len(layer_types) - n_full
    return (n_full * attention_flops(tokens, full_heads, head_dim)
            + n_lin * (delta_rule_flops(tokens, linear_heads, dk, dv, chunk)
                       + conv_flops(tokens, linear_heads * (2 * dk + dv),
                                    conv_width)))
