"""Operations and bytes the ALGORITHM needs for one training step, from
shapes and counts alone (never from a trace): the numerators of
``train_step_mfu_pct``, ``train_step_hbm_pct`` and ``table_update_roofline``.

FLOPs follow the usual model-FLOPs convention (``bench.py``
``dense_flops_per_example``, copied): 2 m n per dense kernel forward, three
times that for forward plus the two backward matmuls; recomputation never
counts.  What joins the towers is stated by the configuration file.
Embedding gathers contribute bytes, not FLOPs."""

from __future__ import annotations

SLOT_FLOATS_PER_ROW = {
    # optimizer state the update must read and write per touched row, in
    # floats: Adam keeps m and v per element, row-wise Adagrad one cell
    "adam": lambda d: 2 * d,
    "adagrad": lambda d: d,
    "rowwise_adagrad": lambda d: 1,
    "sgd": lambda d: 0,
}


def dense_flops_per_example(kernel_shapes: list[tuple[int, int]]) -> float:
    return 3.0 * sum(2.0 * m * n for m, n in kernel_shapes)


def step_flops(kernel_shapes, interaction_flops_per_example: float,
               batch: int) -> float:
    """``interaction_flops_per_example``: forward plus backward of what joins
    the towers, stated as data in the configuration file's ``work`` table (a
    DLRM's F x F pairwise products 3 * 2 * F * F * D, a two-tower's row-wise
    dot product 3 * 2 * D)."""
    per_example = (dense_flops_per_example(kernel_shapes)
                   + float(interaction_flops_per_example))
    return per_example * batch


def update_bytes(unique_rows: float, dim: int, kind: str,
                 bytes_per_float: int = 4) -> float:
    """The row-sparse update alone: each distinct row's parameters and
    optimizer state read once and written once."""
    floats = dim + SLOT_FLOATS_PER_ROW[kind](dim)
    return 2.0 * unique_rows * floats * bytes_per_float


def step_bytes(*, lookups: float, unique_rows: float, dim: int, kind: str,
               dense_param_count: int, batch_bytes: float) -> float:
    """The whole step: every looked-up row read once for the forward pass,
    the update's traffic, the dense parameters with AdamW's two moments read
    and written, and the batch itself."""
    forward = lookups * dim * 4
    dense = 2.0 * 3 * dense_param_count * 4
    return forward + update_bytes(unique_rows, dim, kind) + dense + batch_bytes
