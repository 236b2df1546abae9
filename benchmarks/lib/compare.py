"""The comparison that decides ``correct`` for a training cell.

Both sides — what the timed path left, and the plain reference fed the same
rows from the same start — are reduced to the same ``readings`` by the same
functions here, and then compared number by number, each against a limit of
its own (``benchmarks/configs/<name>.json`` ``limits``, set from chip
readings: PERF.md section 2).

Readings of one side:
  losses       the loss of each of the first steps
  grad_norm    per leaf, the norm of the FIRST gradient as the optimizer got
               it, worked out from the optimizer state one step leaves
               (zero before it): Adam / AdamW  m1 / (1 - b1); row-wise
               Adagrad  sqrt(D * sum(acc1))
  update_norm  per leaf, the norm of (parameters after the last step minus
               parameters at the start)

A leaf is one embedding table (its touched rows) or one dense array.

Gaps are taken "by the worst leaf": |program's norm - reference's norm| over
max(reference's norm of that leaf, reference's norm of the median leaf) —
the gap between norms, not the norm of a difference, and never divided by an
all-but-zero gradient.  Leaves whose reference gradient is under a thousandth
of the median leaf's move under Adam by round-off alone and are left out of
``update_norm_gap`` (by that rule, not by name)."""

from __future__ import annotations

import math
import statistics

import numpy as np


def sparse_grad_norm(kind: str, b1: float, dim: int, slots) -> float:
    if kind == "rowwise_adagrad":
        return math.sqrt(dim * float(np.sum(np.asarray(slots[0], np.float64))))
    if kind == "adam":
        m = np.asarray(slots[0], np.float64)
        return float(np.sqrt(np.sum(m * m))) / (1.0 - b1)
    raise ValueError(f"compare: no gradient reading for optimizer {kind!r}")


def l2(x) -> float:
    x = np.asarray(x, np.float64)
    return float(np.sqrt(np.sum(x * x)))


def readings(*, kind: str, sparse_b1: float, dense_b1: float, dim: int,
             losses, slots1: dict, rows0: dict, rows: dict, dense_m1: dict,
             dense0: dict, dense: dict) -> dict:
    """One side's readings.  Tables are keyed by column, dense leaves by
    path; every array is over the same rows on both sides."""
    grad, upd = {}, {}
    for c in rows:
        grad[f"table:{c}"] = sparse_grad_norm(kind, sparse_b1, dim, slots1[c])
        upd[f"table:{c}"] = l2(np.asarray(rows[c], np.float64)
                               - np.asarray(rows0[c], np.float64))
    for k in dense:
        grad[f"dense:{k}"] = l2(dense_m1[k]) / (1.0 - dense_b1)
        upd[f"dense:{k}"] = l2(np.asarray(dense[k], np.float64)
                               - np.asarray(dense0[k], np.float64))
    return {"losses": [float(x) for x in losses], "grad_norm": grad,
            "update_norm": upd}


def leaf_gaps(got: dict, want: dict) -> dict[str, float]:
    """Per leaf: |program's norm - reference's| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    floor = statistics.median(want.values())
    out = {}
    for leaf, w in want.items():
        gap = abs(got[leaf] - w) / max(w, floor, 1e-30)
        out[leaf] = gap if math.isfinite(gap) else math.inf
    return out


def worst_leaf_gap(got: dict, want: dict, skip=()) -> tuple[float, str]:
    gaps = {k: v for k, v in leaf_gaps(got, want).items() if k not in skip}
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def compare(program: dict, reference: dict, limits: dict,
            extra: dict | None = None) -> tuple[bool, dict]:
    """``(correct, compared)``; ``compared`` maps each short name to its
    number, its limit and, for a worst-leaf gap, the leaf.  A number the
    configuration gives no limit (it has no upper reading: PERF.md section 2)
    is read and printed with ``limit: null`` and decides nothing."""
    out: dict[str, dict] = {}
    for i, (p, r) in enumerate(zip(program["losses"], reference["losses"]), 1):
        gap = abs(p - r) / max(abs(r), 1e-30)
        out[f"loss{i}_gap"] = {"value": gap if math.isfinite(gap) else math.inf,
                               "limit": limits.get(f"loss{i}_gap")}
    g, at = worst_leaf_gap(program["grad_norm"], reference["grad_norm"])
    out["grad_norm_gap"] = {"value": g, "limit": limits["grad_norm_gap"],
                            "leaf": at}
    med = statistics.median(reference["grad_norm"].values())
    still = [k for k, v in reference["grad_norm"].items() if v < 1e-3 * med]
    u, at = worst_leaf_gap(program["update_norm"], reference["update_norm"],
                           skip=still)
    out["update_norm_gap"] = {"value": u, "limit": limits["update_norm_gap"],
                              "leaf": at}
    for name, value in (extra or {}).items():
        out[name] = {"value": value, "limit": limits.get(name, 0)}
    if len(program["losses"]) != len(reference["losses"]):
        out["steps_missing"] = {
            "value": abs(len(program["losses"]) - len(reference["losses"])),
            "limit": 0}
    return all(within(v) for v in out.values()), out


def within(v: dict) -> bool:
    return v["limit"] is None or v["value"] <= v["limit"]


def print_compared(compared: dict, stream) -> None:
    for name, v in compared.items():
        leaf = f"  ({v['leaf']})" if v.get("leaf") else ""
        if v["limit"] is None:
            print(f"observed {name}: {v['value']:.6g} (no limit: not compared)"
                  f"{leaf}", file=stream, flush=True)
            continue
        print(f"compared {name}: {v['value']:.6g} limit {v['limit']:.6g} "
              f"{'ok' if within(v) else 'OVER'}{leaf}", file=stream, flush=True)
