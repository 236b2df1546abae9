"""Per-table traffic statistics artifact (``table_stats.json``).

The planner (``plan/planner.py``) prices a placement from each table's
traffic profile: how many rows a batch touches, how concentrated the
lookup mass is, which ids form the head.  The preprocessing passes already
count per-id value frequencies for the hot/cold artifact
(``data/hot_ids.py``), so they can emit this summary for free, next to
``hot_ids.json``:

  * ``vocab`` / ``total_count`` — table size and total observed lookups;
  * ``unique_per_batch`` — E[distinct rows touched by a size-B batch]
    under the observed id distribution, at a fixed batch grid
    (sum_i 1 - (1 - p_i)^B — the occupancy expectation);
  * ``head_mass`` — lookup-mass fraction absorbed by the top-K
    frequency-ranked ids, at a fixed K grid (the hot-split payoff curve);
  * ``head_ids`` — the frequency-ranked id prefix itself (capped), so a
    chosen hot split can embed its exact id set in the plan artifact.

Counts are ESTIMATES from the training scan; the PR-7 telemetry counters
record the step's true touched/unique rows on-device.  The
:func:`refine_stats_from_metrics` adapter folds a run's ``metrics.jsonl``
counter means back into the artifact (an ``observed`` block per table), so
replanning after a real run prices from measured traffic.
"""

from __future__ import annotations

import hashlib
import json
import math
from pathlib import Path
from typing import Mapping

import numpy as np

__all__ = [
    "FORMAT_VERSION",
    "BATCH_GRID",
    "HEAD_K_GRID",
    "HEAD_IDS_CAP",
    "table_stats_from_counts",
    "write_table_stats",
    "load_table_stats",
    "table_stats_digest",
    "unique_rows_at",
    "unique_rows_over",
    "unique_lines_at",
    "head_mass_at",
    "head_ids_for",
    "refine_stats_from_metrics",
]

# Artifact schema version; bump on incompatible layout changes so a loader
# never silently misreads an old file.
FORMAT_VERSION = 1

_FILENAME = "table_stats.json"

# per-batch unique-row estimates are precomputed at these batch sizes; the
# planner interpolates between them (linear in B — the curve is smooth and
# concave, interpolation error is far below the cost model's tolerance).
# The flush-scale tail points (>= 131072) price the update cache's
# per-interval working set (``unique_rows_over`` at flush_every x B
# draws); artifacts written before they existed clamp at 32768.
BATCH_GRID = (1024, 2048, 4096, 8192, 16384, 32768,
              131072, 524288, 2097152)

# head-mass curve sample points (the planner's hot-split candidate sizes)
HEAD_K_GRID = (1024, 4096, 8192, 16384)

# largest hot head the planner may choose — matches the one-hot MXU update
# range the chip measurements cover (CLAUDE.md: vocabs 5k-16k)
HEAD_IDS_CAP = 16384

_TABLE_KEYS = {"vocab", "total_count", "unique_per_batch", "head_mass",
               "head_ids"}


def table_stats_from_counts(counts: np.ndarray) -> dict:
    """One table's stats entry from its per-id lookup counts
    (``counts[i]`` = lookups of id ``i``, the same array
    ``hot_ids_from_counts`` consumes).  Ties in the head ranking break
    toward lower ids (stable argsort on negated counts) so ``head_ids``
    prefixes equal the hot/cold artifact's sets for the same K."""
    counts = np.asarray(counts, dtype=np.float64)
    v = int(counts.shape[0])
    total = float(counts.sum())
    unique_per_batch = {}
    if total > 0:
        p = counts / total
        # E[unique rows touched] = sum_i 1 - (1 - p_i)^B, computed in log
        # space (p_i can be 1e-8 at Criteo vocabs); zero-count ids
        # contribute exactly 0, full-mass ids exactly 1.
        with np.errstate(divide="ignore"):
            log1mp = np.log1p(-np.minimum(p, 1.0))
        for b in BATCH_GRID:
            unique_per_batch[str(b)] = float(
                np.sum(-np.expm1(b * log1mp)))
    else:
        for b in BATCH_GRID:
            unique_per_batch[str(b)] = float(min(b, v))
    order = np.argsort(-counts, kind="stable")
    ranked = counts[order]
    cum = np.cumsum(ranked)
    head_mass = {}
    for k in HEAD_K_GRID:
        if total > 0:
            head_mass[str(k)] = float(cum[min(k, v) - 1] / total)
        else:
            head_mass[str(k)] = float(min(k, v) / v)
    return {
        "vocab": v,
        "total_count": total,
        "unique_per_batch": unique_per_batch,
        "head_mass": head_mass,
        "head_ids": order[: min(HEAD_IDS_CAP, v)].astype(np.int64).tolist(),
    }


def _canonical(obj):
    """Round floats so reruns on the same counts serialize byte-identically
    (the plan artifact inherits this determinism contract)."""
    if isinstance(obj, float):
        return round(obj, 6)
    if isinstance(obj, dict):
        return {k: _canonical(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_canonical(v) for v in obj]
    return obj


def _dumps(payload: dict) -> str:
    return json.dumps(_canonical(payload), sort_keys=True,
                      separators=(",", ":"))


def write_table_stats(
    data_dir: str | Path, per_table: Mapping[str, np.ndarray]
) -> Path:
    """Persist the artifact next to ``hot_ids.json`` / ``size_map.json``.
    ``per_table`` keys are the categorical COLUMN names; values are per-id
    count arrays (the same ones the hot/cold artifact is built from)."""
    data_dir = Path(data_dir)
    payload = {
        "format_version": FORMAT_VERSION,
        "tables": {
            name: table_stats_from_counts(counts)
            for name, counts in per_table.items()
        },
    }
    path = data_dir / _FILENAME
    path.write_text(_dumps(payload))
    return path


def load_table_stats(data_dir: str | Path) -> dict | None:
    """Read the artifact back as ``{column: stats entry}``; ``None`` when
    ``data_dir`` carries no artifact (the planner then raises with
    re-run-preprocessing guidance)."""
    path = Path(data_dir) / _FILENAME
    if not path.exists():
        return None
    payload = json.loads(path.read_text())
    version = payload.get("format_version")
    if version != FORMAT_VERSION:
        raise ValueError(
            f"{path} has table-stats artifact format_version {version!r}, "
            f"this build reads {FORMAT_VERSION}.  Re-run preprocessing to "
            "regenerate the artifact."
        )
    tables = payload.get("tables")
    if not isinstance(tables, dict):
        raise ValueError(f"{path}: missing 'tables' — the file is corrupt; "
                         "re-run preprocessing.")
    for name, entry in tables.items():
        missing = _TABLE_KEYS - set(entry)
        if missing:
            raise ValueError(
                f"{path}: table {name!r} is missing keys {sorted(missing)} "
                "— the file is corrupt; re-run preprocessing."
            )
        ids = np.asarray(entry["head_ids"], dtype=np.int64)
        if ids.ndim != 1 or (ids.size and (ids.min() < 0
                                           or ids.max() >= entry["vocab"])):
            raise ValueError(
                f"{path}: table {name!r} head_ids out of range — the file "
                "is corrupt; re-run preprocessing."
            )
    return tables


def table_stats_digest(tables: Mapping[str, dict]) -> str:
    """Artifact fingerprint for plan provenance: sha256 over the canonical
    serialization, truncated to 16 hex chars (the ``hot_ids_digest``
    idiom)."""
    payload = {"format_version": FORMAT_VERSION,
               "tables": {k: tables[k] for k in sorted(tables)}}
    return hashlib.sha256(_dumps(payload).encode()).hexdigest()[:16]


def _interp_grid(grid: dict[str, float], x: float) -> float:
    """Piecewise-linear read of a {str(x): y} sample dict, clamped at the
    ends (deterministic pure-float math — the plan must be reproducible)."""
    pts = sorted((int(k), float(v)) for k, v in grid.items())
    if not pts:
        raise ValueError("empty sample grid")
    if x <= pts[0][0]:
        return pts[0][1]
    for (x0, y0), (x1, y1) in zip(pts, pts[1:]):
        if x <= x1:
            return y0 + (y1 - y0) * (x - x0) / (x1 - x0)
    return pts[-1][1]


def unique_rows_at(entry: dict, batch_size: int) -> float:
    """Expected distinct rows a size-``batch_size`` batch touches.  Prefers
    the telemetry-observed mean when the run recorded one at this batch
    size; falls back to the analytic occupancy curve."""
    obs = entry.get("observed")
    if obs and int(obs.get("batch", -1)) == int(batch_size):
        return float(obs["unique_rows"])
    u = _interp_grid(entry["unique_per_batch"], float(batch_size))
    return min(u, float(entry["vocab"]), float(batch_size))


def unique_rows_over(entry: dict, batch_size: int, steps: int) -> float:
    """Expected DISTINCT rows touched across ``steps`` consecutive
    batches — the update cache's per-flush-interval working set (what the
    coalesced write-back scatters and what ``cache_rows`` must hold).
    Reads the same occupancy curve as :func:`unique_rows_at`, at
    ``steps * batch_size`` draws.  Artifacts written before the
    flush-scale grid points existed clamp at their largest sample — an
    UNDERestimate of the working set (optimistic toward the cache);
    regenerate ``table_stats.json`` for honest flush pricing.  Never
    returns less than the single-batch estimate."""
    n = float(int(steps) * int(batch_size))
    u = _interp_grid(entry["unique_per_batch"], n)
    u = min(u, float(entry["vocab"]), n)
    return max(u, unique_rows_at(entry, batch_size))


def unique_lines_at(entry: dict, batch_size: int) -> float | None:
    """Telemetry-observed fat-line touch count at this batch size, or
    ``None`` (the estimator then uses its occupancy model)."""
    obs = entry.get("observed")
    if obs and int(obs.get("batch", -1)) == int(batch_size):
        lines = obs.get("unique_lines")
        return None if lines is None else float(lines)
    return None


def head_mass_at(entry: dict, k: int) -> float:
    """Lookup-mass fraction of the top-``k`` frequency-ranked ids."""
    if k <= 0:
        return 0.0
    if k >= entry["vocab"]:
        return 1.0
    return min(1.0, _interp_grid(entry["head_mass"], float(k)))


def head_ids_for(entry: dict, k: int) -> list[int]:
    """The top-``k`` head as a SORTED id list (the hot/cold artifact's
    representation) — raises when the stats head is shorter than ``k``."""
    ids = entry["head_ids"]
    k = min(k, entry["vocab"])
    if len(ids) < k:
        raise ValueError(
            f"stats head_ids holds {len(ids)} ids but the plan wants a "
            f"{k}-row hot head — regenerate table_stats.json"
        )
    return sorted(int(i) for i in ids[:k])


def refine_stats_from_metrics(
    tables: Mapping[str, dict],
    metrics_path: str | Path,
    *,
    batch_size: int,
) -> dict:
    """Fold a run's telemetry counters back into the stats: for every table
    whose ``emb/<name>/touched_ids`` / ``unique_rows`` (and, on fused
    tables, ``unique_lines``) counters appear in ``metrics.jsonl``
    (PR-7 ``obs/counters.py``), attach an ``observed`` block carrying the
    per-step counter MEANS at the run's batch size.  Table names must match
    the counters' array names — i.e. the run should use unstacked tables
    (``stack_tables=false``), since stacked counters aggregate per stack.
    Returns a new stats dict; tables without counters pass through
    unchanged."""
    sums: dict[str, dict[str, float]] = {}
    ns: dict[str, dict[str, int]] = {}
    with open(metrics_path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            for key, val in rec.items():
                if not key.startswith("emb/"):
                    continue
                parts = key.split("/")
                if len(parts) != 3:
                    continue
                _, name, counter = parts
                if counter not in ("touched_ids", "unique_rows",
                                   "unique_lines"):
                    continue
                sums.setdefault(name, {}).setdefault(counter, 0.0)
                ns.setdefault(name, {}).setdefault(counter, 0)
                sums[name][counter] += float(val)
                ns[name][counter] += 1
    out = {}
    for name, entry in tables.items():
        entry = dict(entry)
        if name in sums and "unique_rows" in sums[name]:
            means = {c: sums[name][c] / ns[name][c] for c in sums[name]}
            obs = {
                "batch": int(batch_size),
                "touched_ids": means.get("touched_ids",
                                         float(batch_size)),
                "unique_rows": means["unique_rows"],
            }
            if "unique_lines" in means:
                obs["unique_lines"] = means["unique_lines"]
            entry["observed"] = _canonical(obs)
        out[name] = entry
    return out


def _expected_unique(vocab: int, batch: int) -> float:
    """Uniform-traffic occupancy (used by the tests' synthetic profiles):
    ``v * (1 - (1 - 1/v)^B``)."""
    if vocab <= 0:
        return 0.0
    return vocab * -math.expm1(batch * math.log1p(-1.0 / vocab))
