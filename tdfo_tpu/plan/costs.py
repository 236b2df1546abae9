"""Measured v5e step-cost table — docs/BUDGET.md as an executable model.

Every constant in this module is a per-descriptor cost fitted to the
builders' round-4 IN-SITU ablations in ``docs/BUDGET.md`` (the cumulative
piece tables taken on the real chip by chain differencing, with a harness
since deleted; NOT isolated-op microbenchmarks — the fat-line kernel
measured 3x slower in situ than isolated, so isolated numbers are banned
here).  None is a ledger number; where a constant rests on an expectation
that was never measured, its comment says so.  This is the single
sanctioned home for numeric cost constants: ``tests/test_quality.py``
rejects ``*_NS``/``*_US``/``*_MS`` constants anywhere else in the tree, so
the measured numbers cannot fork.

Calibration contract (``tests/test_planner.py``): :func:`estimate_step_ms`
must reproduce BOTH BUDGET.md in-situ step budgets with the correct
plain-vs-fused ordering —

  * DLRM-Criteo (26 tables, 33.76M rows, d=16, B=8192, rowwise-adagrad,
    213k ids -> 102k touched rows -> 77k touched lines): plain-scatter
    22.4 ms, fused fat-line 29-32 ms (plain must win);
  * TwoTower DMP (7 tables, ~2.4M rows, d=64, B=8192, adam, ~8k touched
    rows): fused 1.40 ms, plain ~2.8 ms (fused must win).

The model is deliberately descriptor-count-based: BUDGET.md's core finding
is that sparse steps on v5e (no SparseCore) bottom out at per-descriptor
issue costs, not bandwidth — the roofline "floor" is meaningless there.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

__all__ = [
    "TableLoad",
    "FULL_SLOT_BUFFERS",
    "SCATTER_BUFFERS",
    "DEDUPE_NS_PER_ID",
    "ROW_GATHER_BASE_NS",
    "EXPAND_NS_PER_ID",
    "SEGSUM_NS_PER_TARGET",
    "SCATTER_NS_PER_SLOT_PER_BUFFER",
    "CACHE_SCATTER_NS_PER_SLOT_PER_BUFFER",
    "CACHE_ROUTE_NS_PER_ID",
    "RESHAPE_MS_PER_GB",
    "LINE_GATHER_BASE_NS",
    "LINE_DMA_BASE_NS_PER_DIR",
    "A2A_US_PER_TABLE",
    "DENSE_STEP_MS_AT_B8192",
    "in_situ_multiplier",
    "line_geometry",
    "expected_lines",
    "one_hot_update_ms",
    "dense_step_ms",
    "padded_lane_width",
    "table_hbm_bytes",
    "cache_hbm_bytes",
    "estimate_step_ms",
]


# --------------------------------------------------------------------------
# per-descriptor constants (ns), fitted to the BUDGET.md cumulative ablations
# --------------------------------------------------------------------------

# dedupe_ids 2-sort formulation: 0.6 ms for 213k ids (BUDGET.md Criteo row
# "dedupe sort (213k ids -> 102k slots)"); the 16k-scale measurement is
# 0.24 ms (CLAUDE.md), i.e. the cost is ~linear in the id count.
DEDUPE_NS_PER_ID = 2.8

# compact row gather, IN SITU at the Criteo scale: ~3.9 ms for 102k
# scattered 64 B rows from a 2.2 GB stack (BUDGET.md "+ compact row
# gather", the ~40 ns/row multi-GB floor).  The BASE here is the
# small-touch-count rate (~60-90 us for 8192 rows, CLAUDE.md); the in-situ
# multiplier below ramps it to the measured large-touch-count floor:
# 13.3 * 3.0 = ~40 ns/row at >= 65k step touches.
ROW_GATHER_BASE_NS = 13.3

# expand compact rows to [B, d]: ~1.0 ms for 213k gathers from the compact
# 6.5 MB block (BUDGET.md "+ expand to [B, d]", ~4 ns/row — cache-resident
# source, so no in-situ ramp applies).
EXPAND_NS_PER_ID = 4.7

# row segment-sum: cost scales with the TARGET segment count at fixed
# input (CLAUDE.md: 213k -> [102k, 16] ~4 ms, -> [310k, 16] ~10 ms;
# BUDGET.md Criteo row says ~4.5 ms).  39 ns/target reproduces the 4 ms
# fact; sorted/cumsum/one-hot alternatives all measured slower.
SEGSUM_NS_PER_TARGET = 39.0

# XLA scatter serialization floor: ~60-110 ns per touched slot per
# scattered buffer (BUDGET.md "+ table scatter + accum scatter": ~11 ms
# for 102k rows x 2 buffers under rowwise-adagrad).  54 * 2 buffers
# lands the measured 11 ms at the Criteo profile.
SCATTER_NS_PER_SLOT_PER_BUFFER = 54.0

# update-cache scatters target the small [C, d] cache arrays (MBs, not
# GBs) — never measured on the chip: the builders' expectation (round 4)
# brackets them 0.05-0.5 ms for ~3k rows x 2 buffers (8-80
# ns/slot/buffer, the open question being whether a cache-resident
# target beats the multi-GB floor).  27 = half the
# big-table floor is the bracket's middle; the planner only reaches for
# it on int8 plans, where the eager path's extra sidecar buffer and
# requantize RMW shift the break-even structurally (module docstring of
# plan/planner.py records the stance).
CACHE_SCATTER_NS_PER_SLOT_PER_BUFFER = 27.0

# cache directory route: `searchsorted method="sort"` of the deduped ids
# into the [C] sorted directory + the admission pair-sorts (builders'
# expectation, round 4, never measured on the chip: ~0.15-0.3 ms for 8k
# ids into 131k).
CACHE_ROUTE_NS_PER_ID = 25.0

# a trailing-dim retiling reshape MATERIALIZES the array on TPU:
# [L, 1, 128] -> [L*4, 32] of a 4.3 GB table measured ~10 ms/step
# (CLAUDE.md).  The int8 fat update goes through exactly that [L*R, W]
# byte view (ops/sparse._fat_apply_rows_int8) and pays it twice (view +
# write-back), so big fused-int8 tables carry a bytes-proportional term
# no descriptor count captures.
RESHAPE_MS_PER_GB = 2.3

# fat-line forward gather, IN SITU: ~10 ms for 77k x 512 B lines
# (BUDGET.md fused ablation "forward line gather + slot select" — the
# 512 B line granularity taxes the forward vs 64 B plain rows).  Base is
# the small-scale line-gather rate (~0.4 ms for ~8k 1 KB lines, BUDGET.md
# TwoTower "7 lookups" row); 45 * 3.0 = 135 ns/line at the Criteo scale.
LINE_GATHER_BASE_NS = 45.0

# in-place DMA update kernel: ~80-90 ns/line/direction IN SITU (BUDGET.md
# fused ablation "fused update kernel": ~14 ms for 77k lines read+write;
# the isolated 17-35 ns/row figure does NOT hold at that scale).  Base is
# the small-scale rate (TwoTower kernel ~0.5 ms for ~8k lines both
# directions); 30 * 2 dirs * 3.0 = 180 ns/line at the Criteo scale.
LINE_DMA_BASE_NS_PER_DIR = 30.0

# all-to-all launch allowance per sharded table per step (2 collectives
# per direction): on one chip the exchange is degenerate (PROGRAM
# OVERHEAD only) and multichip ICI is unmeasured (ROADMAP.md W2), so
# this is a nominal launch cost, not a measured ICI number, and no
# program in the tree reproduces it — it exists so replication wins tiny
# tables (no exchange) while row sharding wins big ones (descriptor
# work / n).
A2A_US_PER_TABLE = 20.0

# one-hot MXU segment-sum update for a replicated hot head / small table:
# ~100-350 us for vocabs 5k-16k (CLAUDE.md; XLA fuses the one-hot away).
# Modeled linear in the head size over that range with a floor — the
# CEILING end of that range, because the per-table updates serialize in
# situ (the fat-line 3x lesson); the hot/cold step itself was never
# measured on the chip.
ONE_HOT_BASE_US = 100.0
ONE_HOT_BASE_VOCAB = 5000
ONE_HOT_US_PER_ROW = (350.0 - 100.0) / (16384 - 5000)
ONE_HOT_FLOOR_US = 50.0

# dense fwd+bwd anchors at B=8192, bf16 MXU (BUDGET.md "+ model fwd+bwd"
# rows): DLRM bottom+top MLPs 1.5 ms, TwoTower towers 0.3 ms.  Scaled
# linearly in batch (MXU-bound at these widths).
DENSE_STEP_MS_AT_B8192 = {"dlrm": 1.5, "twotower": 0.3}

# in-situ descriptor-cost ramp: isolated/small-step descriptor rates hold
# up to ~16k touches per step; at the Criteo scale (~100k touches) every
# scattered-descriptor cost measured ~3x its small-scale rate (BUDGET.md
# fused-ablation finding: "the 17-35 ns/row figure from small-scale
# isolated runs does not hold at 77k lines"; custom calls serialize
# against the step).  Linear ramp between the two measured regimes,
# keyed on the STEP's total per-device touched rows — contention is a
# whole-step property, not a per-table one.
IN_SITU_RAMP_START = 16384
IN_SITU_RAMP_FULL = 65536
IN_SITU_MAX = 3.0

# optimizer state geometry (ops/sparse.py kinds): full table-shaped slot
# buffers, and the number of scattered buffers a plain update touches
# (table itself + full slots + the rowwise [V] accumulator cell-scatter).
FULL_SLOT_BUFFERS = {"sgd": 0, "adagrad": 1, "rowwise_adagrad": 0, "adam": 2}
SCATTER_BUFFERS = {"sgd": 1, "adagrad": 2, "rowwise_adagrad": 2, "adam": 3}


@dataclass(frozen=True)
class TableLoad:
    """One table's traffic + placement, as the estimator consumes it.

    ``ids_per_batch``/``unique_rows`` come from the ``table_stats.json``
    artifact (analytic estimates from preprocessing counts, optionally
    replaced by observed telemetry counters — ``plan/stats.py``).
    ``unique_lines`` is the observed fat-line touch count when telemetry
    recorded one; ``None`` falls back to the occupancy estimate
    (:func:`expected_lines`).  ``hot_mass`` is the lookup-mass fraction a
    ``hot_k``-row hot head absorbs (stats head-mass curve).
    ``flush_unique_rows`` is E[distinct rows touched across one
    ``cache_flush_every``-step interval] (``plan/stats.unique_rows_over``)
    — only read when the estimator prices the update cache; ``None``
    falls back to the no-reuse pessimum (``unique_rows`` per step, i.e.
    the cache never wins)."""

    name: str
    vocab: int
    dim: int
    ids_per_batch: float
    unique_rows: float
    unique_lines: float | None = None
    sharding: str = "row"  # "row" | "replicated" | "table"
    fused: bool = False
    dtype: str = "float32"
    hot_k: int = 0
    hot_mass: float = 0.0
    flush_unique_rows: float | None = None


def in_situ_multiplier(total_unique_rows: float) -> float:
    """Descriptor-cost multiplier for a step touching this many rows."""
    if total_unique_rows <= IN_SITU_RAMP_START:
        return 1.0
    if total_unique_rows >= IN_SITU_RAMP_FULL:
        return IN_SITU_MAX
    frac = (total_unique_rows - IN_SITU_RAMP_START) / (
        IN_SITU_RAMP_FULL - IN_SITU_RAMP_START)
    return 1.0 + (IN_SITU_MAX - 1.0) * frac


def _next_pow2(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


def line_geometry(dim: int, optimizer: str, dtype: str) -> tuple[int, int]:
    """Fat-line packing of one vocab row: ``(line_elems, rows_per_line)``.

    Mirrors ``ops/pallas_kernels.line_layout``: a row carries
    ``dim * (1 + full_slots)`` elements (+1 for the rowwise accumulator),
    padded to a power of two; rows pack into 128-lane f32 lines (256
    elements for bf16 — half the bytes per element, same 512 B line).

    ``dtype == "int8"`` is the BYTE-container line (elements are bytes):
    ``dim`` code bytes + 8 sidecar bytes (bitcast f32 scale, offset) + 4
    bytes per f32 state lane, padded to the next slot width from
    (8, 16, 32, 64, 128) or up to whole 128-byte tiles.  rowwise_adagrad
    is refused here exactly as ``ops/pallas_kernels.line_layout`` refuses
    it: its shared scalar accumulator has no per-row byte-container home.
    """
    if dtype == "int8":
        if optimizer == "rowwise_adagrad":
            raise ValueError(
                "fused int8 storage does not support rowwise_adagrad: the "
                "rowwise accumulator is a shared scalar per row with no "
                "byte-container slot in the fat line — keep the table on "
                "plain int8 storage (optionally cache-fronted) or switch "
                "the optimizer")
        need = dim + 8 + 4 * dim * FULL_SLOT_BUFFERS[optimizer]
        width = next((s for s in (8, 16, 32, 64, 128) if s >= need),
                     128 * math.ceil(need / 128))
        return width, max(1, 128 // width)
    elems = dim * (1 + FULL_SLOT_BUFFERS[optimizer])
    if optimizer == "rowwise_adagrad":
        elems += 1
    width = _next_pow2(elems)
    lane_elems = 128 if dtype == "float32" else 256
    rows_per_line = max(1, lane_elems // width)
    return width, rows_per_line


def expected_lines(unique_rows: float, vocab: int, rows_per_line: int) -> float:
    """Occupancy estimate of touched lines: ``unique_rows`` rows drawn over
    ``ceil(vocab / R)`` lines touch ``L * (1 - (1 - 1/L)^u)`` of them —
    saturated small tables compress ~R-fold, sparse big tables barely."""
    if unique_rows <= 0:
        return 0.0
    n_lines = math.ceil(vocab / max(1, rows_per_line))
    if n_lines <= 1:
        return 1.0
    return n_lines * -math.expm1(unique_rows * math.log1p(-1.0 / n_lines))


def one_hot_update_ms(hot_rows: int) -> float:
    """One replicated hot head's scatter-free one-hot MXU update."""
    us = ONE_HOT_BASE_US + (hot_rows - ONE_HOT_BASE_VOCAB) * ONE_HOT_US_PER_ROW
    return max(ONE_HOT_FLOOR_US, us) / 1000.0


def dense_step_ms(dense_model: str, batch_size: int) -> float:
    """Dense backbone fwd+bwd, scaled from the measured B=8192 anchors."""
    if dense_model not in DENSE_STEP_MS_AT_B8192:
        raise ValueError(f"no dense anchor for model {dense_model!r}")
    return DENSE_STEP_MS_AT_B8192[dense_model] * (batch_size / 8192.0)


# --------------------------------------------------------------------------
# HBM model (per-device bytes, undivided — the planner applies sharding)
# --------------------------------------------------------------------------

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def padded_lane_width(dim: int) -> int:
    """XLA's allocated trailing width: narrow dims (8/16) get narrow
    tiles, everything else lane-pads to a 128 multiple — a [V, 64] table
    allocates 2x its logical bytes (CLAUDE.md measured fact; same 2x for
    bf16, which is why bf16 saves exactly half, not more)."""
    if dim <= 16:
        return dim
    return 128 * math.ceil(dim / 128)


def table_hbm_bytes(
    vocab: int,
    dim: int,
    *,
    optimizer: str,
    dtype: str = "float32",
    slot_dtype: str = "float32",
    fused: bool = False,
    hot_k: int = 0,
) -> int:
    """Allocated bytes of one table + its optimizer state (whole table,
    before any sharding division).  ``hot_k`` adds the replicated dense
    head (always f32 + dense slot buffers — the head is small).

    int8 adds the per-row f32 (scale, offset) sidecar (8 B/row) and keeps
    the slot buffers at ``slot_dtype`` — so at NARROW dims the ratio vs
    f32 is bounded well under 4x (d=16 sgd: 64 B -> 16 + 8 = 24 B, 2.67x),
    while lane-padded dims approach it (d=64 sgd: 512 B -> 128 + 8 = 136 B,
    3.76x; the int8 codes lane-pad 128-wide exactly like f32).  Fused int8
    packs codes + sidecar + f32-byte state into the byte-container line
    (``line_geometry``), so slot-width padding can make it LARGER than
    plain int8 at some (dim, optimizer) — the planner prices both."""
    dsize = _DTYPE_BYTES[dtype]
    if fused:
        # int8 fat lines are byte containers: the (scale, offset) sidecar
        # and the f32-byte optimizer state ride IN-LINE, so the line
        # geometry already prices them (no separate sidecar/slot terms)
        width, rows_per_line = line_geometry(dim, optimizer, dtype)
        lane_elems = 256 if dtype == "bfloat16" else 128
        if rows_per_line > 1:
            body = math.ceil(vocab / rows_per_line) * lane_elems * dsize
        else:
            body = vocab * width * dsize
    else:
        padded = padded_lane_width(dim)
        body = vocab * padded * dsize
        body += FULL_SLOT_BUFFERS[optimizer] * vocab * padded * _DTYPE_BYTES[slot_dtype]
        if optimizer == "rowwise_adagrad":
            body += vocab * 4  # the EXACT_ROWWISE_ADAGRAD f32 accumulator
        if dtype == "int8":
            body += vocab * 2 * 4  # f32 (scale, offset) per row
    if hot_k > 0:
        k = min(hot_k, vocab)
        head = k * padded_lane_width(dim) * 4 * (1 + FULL_SLOT_BUFFERS[optimizer])
        if optimizer == "rowwise_adagrad":
            head += k * 4
        body += head
    return int(body)


def cache_hbm_bytes(
    dim: int,
    *,
    optimizer: str,
    dtype: str = "float32",
    cache_rows: int,
) -> int:
    """Replicated per-device bytes of ONE update cache
    (``ops/sparse.cache_init``): ``cache_rows`` rows at the table dtype,
    the f32 slot mirrors, the int8 (scale, offset) mirror, the rowwise
    accumulator cell, plus ~16 B/row of int32 directory bookkeeping
    (sorted ids + permutation + age/dirty).  Stacked arrays share a cache,
    so the planner charges one per plain storage GROUP."""
    c = int(cache_rows)
    if c <= 0:
        return 0
    padded = padded_lane_width(dim)
    row = padded * _DTYPE_BYTES[dtype]
    row += FULL_SLOT_BUFFERS[optimizer] * padded * 4
    if optimizer == "rowwise_adagrad":
        row += 4
    if dtype == "int8":
        row += 8
    row += 16
    return c * row


# --------------------------------------------------------------------------
# step-cost estimator
# --------------------------------------------------------------------------


def estimate_step_ms(
    loads: list[TableLoad],
    *,
    optimizer: str,
    dense_model: str,
    batch_size: int,
    n_devices: int = 1,
    cache_flush_every: int | None = None,
) -> dict:
    """Predicted per-device train-step milliseconds for a set of placed
    tables, assuming the measured-fastest formulation of each path:

      * plain tables stack per (dim, dtype, sharding) and run the
        dedup_lookup pipeline — one dedupe sort, compact row gather,
        expand, row segment-sum, then one scatter per optimizer buffer
        (the 22.4 ms Criteo formulation);
      * fused tables stack into fat-line arrays per (dim, dtype,
        sharding) — dedupe, line gather, segment-sum, in-place DMA kernel
        (the 1.40 ms TwoTower formulation).  Fused INT8 arrays update in
        ROW space instead (``ops/sparse._fat_apply_rows_int8``: byte-row
        gather + one packed scatter through the ``[L*R, W]`` view), so
        they pay row-gather + single-buffer-scatter descriptor costs plus
        the view's retiling materialization (``RESHAPE_MS_PER_GB``);
      * plain int8 tables pay one EXTRA scatter buffer (the f32
        (scale, offset) sidecar written alongside the requantized codes);
      * ``cache_flush_every`` (when not ``None``) prices every plain
        group as cache-fronted (``[embeddings] cache_rows``): per-step
        scatters move to the cache-resident arrays
        (``CACHE_SCATTER_NS_PER_SLOT_PER_BUFFER``), the deduped ids pay
        the directory route, and the big-table write-back (admission
        gather + coalesced flush scatter of the interval's
        ``flush_unique_rows``) amortizes over the interval.  Fused groups
        ignore it (the cache covers plain 2D arrays only —
        ``parallel/embedding.cached_array_names``);
      * a ``hot_k`` head removes ``hot_mass`` of the table's traffic from
        the scattered path and pays one one-hot MXU update per table
        (heads are per-table and serialize — ``ONE_HOT_*`` above).

    Row-sharded groups divide descriptor counts by ``n_devices`` (balanced
    shards) and pay the a2a launch allowance; replicated and table-wise
    groups do full-count work per device / on the owner.  Returns a
    breakdown dict with ``total_ms``, ``dense_ms``, ``hot_ms`` and a
    ``per_table`` attribution (group costs split by touched-row share).
    """
    if optimizer not in SCATTER_BUFFERS:
        raise ValueError(f"unknown sparse optimizer {optimizer!r}")
    f_every = int(cache_flush_every) if cache_flush_every else 0
    cold: list[dict] = []
    hot_ms = 0.0
    per_table = {ld.name: 0.0 for ld in loads}
    for ld in loads:
        ids, uniq = float(ld.ids_per_batch), float(ld.unique_rows)
        lines = ld.unique_lines
        # interval working set for the cache write-back; absent stats fall
        # back to the no-reuse pessimum (flush == uniq per step amortized,
        # so the cache never looks like a win without an occupancy curve)
        flush = ld.flush_unique_rows
        if flush is None and f_every:
            flush = min(float(ld.vocab), uniq * f_every)
        if ld.hot_k > 0:
            k = min(ld.hot_k, ld.vocab)
            mass = 1.0 if ld.hot_k >= ld.vocab else min(1.0, max(0.0, ld.hot_mass))
            head_ms = one_hot_update_ms(k)
            hot_ms += head_ms
            per_table[ld.name] += head_ms
            ids *= 1.0 - mass
            uniq *= 1.0 - mass
            lines = None if lines is None else lines * (1.0 - mass)
            flush = None if flush is None else flush * (1.0 - mass)
        cold.append(dict(load=ld, ids=ids, uniq=uniq, lines=lines,
                         flush=flush))

    # the in-situ ramp keys on the step's total per-device touched rows
    def _div(ld: TableLoad) -> float:
        return float(n_devices) if ld.sharding == "row" else 1.0

    total_touched = sum(c["uniq"] / _div(c["load"]) for c in cold)
    m = in_situ_multiplier(total_touched)

    groups: dict[tuple, list[dict]] = {}
    for c in cold:
        ld = c["load"]
        key = (ld.fused, ld.dim, ld.dtype, ld.sharding)
        groups.setdefault(key, []).append(c)

    sparse_ms = 0.0
    a2a_ms = 0.0
    for (fused, dim, dtype, sharding), members in sorted(
            groups.items(), key=lambda kv: repr(kv[0])):
        div = float(n_devices) if sharding == "row" else 1.0
        ids = sum(c["ids"] for c in members)
        uniq = sum(c["uniq"] for c in members) / div
        if fused:
            width, rpl = line_geometry(dim, optimizer, dtype)
            lines = sum(
                c["lines"] if c["lines"] is not None else expected_lines(
                    c["uniq"], c["load"].vocab, rpl)
                for c in members) / div
            if dtype == "int8":
                # row-space int8 fat update (no DMA kernel): forward line
                # gather stays, the update pays byte-row gather + ONE
                # packed-row scatter through the [L*R, W] view — which
                # retiles, so the whole fat array materializes twice per
                # step (free only when the view is a unit-dim collapse,
                # i.e. one 128-byte-slot row per line)
                table_gb = sum(
                    table_hbm_bytes(c["load"].vocab, dim,
                                    optimizer=optimizer, dtype=dtype,
                                    fused=True)
                    for c in members) / div / float(1 << 30)
                reshape_ms = (0.0 if (rpl == 1 and width == 128)
                              else 2.0 * RESHAPE_MS_PER_GB * table_gb)
                group_ms = (
                    ids * DEDUPE_NS_PER_ID
                    + lines * LINE_GATHER_BASE_NS * m
                    + uniq * SEGSUM_NS_PER_TARGET
                    + uniq * ROW_GATHER_BASE_NS * m
                    + uniq * SCATTER_NS_PER_SLOT_PER_BUFFER
                ) / 1e6 + reshape_ms
            else:
                group_ms = (
                    ids * DEDUPE_NS_PER_ID
                    + lines * LINE_GATHER_BASE_NS * m
                    + uniq * SEGSUM_NS_PER_TARGET
                    + lines * 2 * LINE_DMA_BASE_NS_PER_DIR * m
                ) / 1e6
        else:
            # plain int8 scatters the f32 (scale, offset) sidecar alongside
            # the requantized codes: one extra buffer
            buffers = SCATTER_BUFFERS[optimizer] + (1 if dtype == "int8"
                                                    else 0)
            common = (
                ids * DEDUPE_NS_PER_ID
                + uniq * ROW_GATHER_BASE_NS * m
                + ids * EXPAND_NS_PER_ID
                + uniq * SEGSUM_NS_PER_TARGET
            )
            if f_every:
                # cache-fronted: per-step scatters hit the small cache
                # arrays (incl. the int8 qs mirror — the per-step
                # requantize keeps bit-parity with the eager path), the
                # deduped ids pay the directory route, and the big-table
                # write-back (admission row gather + coalesced flush of
                # the interval's distinct rows) amortizes over the
                # interval
                flush_rows = sum(
                    min(c["flush"], float(c["load"].vocab))
                    for c in members) / div / float(f_every)
                group_ms = (
                    common
                    + uniq * CACHE_ROUTE_NS_PER_ID
                    + uniq * CACHE_SCATTER_NS_PER_SLOT_PER_BUFFER * buffers
                    + flush_rows * (ROW_GATHER_BASE_NS * m
                                    + SCATTER_NS_PER_SLOT_PER_BUFFER
                                    * buffers)
                ) / 1e6
            else:
                group_ms = (
                    common
                    # NO in-situ ramp on the scatter: the ~54 ns/slot floor
                    # IS the at-scale in-situ figure (BUDGET.md measured the
                    # 102k-row scatter in the full step; small-scale XLA
                    # scatters are ~170 ns/row, i.e. scatters do not get
                    # WORSE at scale)
                    + uniq * SCATTER_NS_PER_SLOT_PER_BUFFER * buffers
                ) / 1e6
        sparse_ms += group_ms
        if sharding in ("row", "table") and n_devices > 1:
            a2a_ms += len(members) * A2A_US_PER_TABLE / 1000.0
        g_uniq = sum(c["uniq"] for c in members)
        for c in members:
            share = (c["uniq"] / g_uniq) if g_uniq > 0 else 1.0 / len(members)
            per_table[c["load"].name] += group_ms * share

    dense = dense_step_ms(dense_model, batch_size)
    return {
        "total_ms": dense + sparse_ms + hot_ms + a2a_ms,
        "dense_ms": dense,
        "sparse_ms": sparse_ms,
        "hot_ms": hot_ms,
        "a2a_ms": a2a_ms,
        "in_situ_multiplier": m,
        "per_table": per_table,
    }
