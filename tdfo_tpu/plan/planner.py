"""Cost-model-driven auto-sharding planner (torchrec
``EmbeddingShardingPlanner``/``EmbeddingEnumerator`` parity).

Enumerates per-table placement decisions — replicated / row-sharded /
table-wise, fused fat-line vs plain storage, f32 vs bf16 table dtype, and
hot-split size — prices every candidate with the measured v5e cost model
(``plan/costs.py``) against the table's traffic stats
(``plan/stats.py``), and greedily picks the plan minimizing predicted
per-device step time, optionally under a device HBM budget.  The result
is a versioned, deterministic ``sharding_plan.json`` the trainer consumes
as per-table spec overrides (``train/trainer.py``) and stamps into
checkpoints (the ``hot_ids_digest`` idiom).

Decision search: path choices couple through the step-level in-situ
descriptor ramp and through stacking (a table's scatter rides its
group's), so per-table independent pricing would mis-order plain vs fused
at exactly the Criteo profile the model is calibrated on.  The planner
instead runs coordinate descent over FULL-plan estimates: sweep tables in
deterministic order, re-pricing the whole step for each candidate, until
a sweep changes nothing.  Tables are few (dozens) and the estimator is
O(tables), so this is milliseconds of host work.

Deliberately conservative stances (none rests on a chip measurement):

  * bf16 storage is priced step-time-NEUTRAL — the fat-line bf16 ablation
    was never measured on the chip (the builders expected ~1.7x from the
    DMA-byte ratio; UNMEASURED), so dtype is chosen
    only as an HBM lever (it halves allocated bytes — that part IS
    measured) during budget demotion, never on predicted speed.
  * the update cache is considered ONLY for plans that carry plain int8
    storage.  For f32/bf16 the stance stays at the pessimistic end of
    the builders' round-4 expectation (break-even-to-loss: the cache
    moves scatters, it does not remove them), so pure-float plans keep
    emitting ``cache_rows: 0`` and an operator opts in by hand after
    measuring.  Plain int8 shifts the break-even structurally — the
    eager path pays an EXTRA sidecar scatter buffer plus a per-step
    requantize read-modify-write on the multi-GB table — so the
    post-pass prices the cache-fronted step at the bracket middle
    (``costs.CACHE_SCATTER_NS_PER_SLOT_PER_BUFFER``) with the honest
    flush cost (the interval working set from the stats occupancy
    curve), and emits ``cache_rows > 0`` IFF the model predicts a win
    AND the caches fit the HBM budget.  On no-reuse (uniform) traffic
    the working set equals ``flush_every x uniq`` and the cache correctly
    never wins; it takes Zipf-style reuse to tip it.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
from pathlib import Path
from typing import Mapping

from tdfo_tpu.plan.costs import (
    TableLoad,
    cache_hbm_bytes,
    estimate_step_ms,
    table_hbm_bytes,
)
from tdfo_tpu.plan.stats import (
    HEAD_IDS_CAP,
    HEAD_K_GRID,
    head_ids_for,
    head_mass_at,
    table_stats_digest,
    unique_lines_at,
    unique_rows_at,
    unique_rows_over,
)

__all__ = [
    "FORMAT_VERSION",
    "PLAN_FILENAME",
    "FUSED_MIN_VOCAB",
    "CACHE_FLUSH_EVERY",
    "plan_tables",
    "write_plan",
    "load_plan",
    "plan_digest",
    "format_plan",
    "apply_plan_to_specs",
]

# Plan schema version; bump on incompatible layout changes.
FORMAT_VERSION = 1

PLAN_FILENAME = "sharding_plan.json"

# Fat-line storage is only enumerated above this vocab — mirrors the
# config default ``fused_table_threshold`` (small tables ride the one-hot
# MXU tier / plain stacks; fat packing them was never measured).
FUSED_MIN_VOCAB = 16384

# Flush cadence a cache-carrying plan prices and emits — the
# ``[embeddings] flush_every`` config default, so a plan-driven cache
# behaves exactly like the hand-set knob it replaces.
CACHE_FLUSH_EVERY = 64

_SHARDINGS = ("row", "replicated", "table")
_DTYPES = ("float32", "bfloat16", "int8")


@dataclasses.dataclass(frozen=True)
class _Candidate:
    sharding: str
    fused: bool
    dtype: str
    hot_k: int  # effective head size (<= vocab); 0 = no split


def _candidates(name: str, entry: dict, optimizer: str,
                n_devices: int) -> list[_Candidate]:
    """Deterministic candidate order per table; index in this list is the
    final tie-break, so defaults (row, plain, f32, no hot) come first."""
    vocab = int(entry["vocab"])
    out = []
    shardings = _SHARDINGS if n_devices > 1 else ("row", "replicated")
    hot_ks = [0]
    for k in HEAD_K_GRID:
        k_eff = min(k, vocab)
        # the plan embeds the head's exact id set, so the stats head must
        # cover it; fully-hot tables need the whole vocab enumerated
        if k_eff not in hot_ks and len(entry["head_ids"]) >= k_eff:
            hot_ks.append(k_eff)
    for sharding in shardings:
        for fused in (False, True):
            if fused and (vocab <= FUSED_MIN_VOCAB
                          or sharding not in ("row", "replicated")):
                continue
            for dtype in _DTYPES:
                if fused and dtype != "float32" \
                        and optimizer == "rowwise_adagrad":
                    # the fat line packs the accumulator at the table
                    # dtype (bf16, PR 5) or cannot carry it at all (int8:
                    # the f32 per-row accumulator contract cannot ride a
                    # quantized line); EXACT_ROWWISE_ADAGRAD requires f32
                    # accum (refused at collection construction)
                    continue
                for hot_k in hot_ks:
                    if hot_k > 0 and (
                            fused or sharding not in ("row", "replicated")):
                        # hot heads require a plain, row/replicated base
                        # table (parallel/embedding.py hot_ids contract);
                        # int8 composes — the head stays f32, only the
                        # cold residual stores codes
                        continue
                    out.append(_Candidate(sharding, fused, dtype, hot_k))
    return out


def _loads(names, stats, decisions, *, dim, batch_size, flush_steps=None):
    loads = []
    for name in names:
        entry = stats[name]
        d = decisions[name]
        loads.append(TableLoad(
            name=name,
            vocab=int(entry["vocab"]),
            dim=dim,
            ids_per_batch=float(batch_size),
            unique_rows=unique_rows_at(entry, batch_size),
            unique_lines=unique_lines_at(entry, batch_size) if d.fused
            else None,
            sharding=d.sharding,
            fused=d.fused,
            dtype=d.dtype,
            hot_k=d.hot_k,
            hot_mass=head_mass_at(entry, d.hot_k),
            flush_unique_rows=(
                unique_rows_over(entry, batch_size, flush_steps)
                if flush_steps else None),
        ))
    return loads


def _device_loads(names, stats, decisions, *, dim, optimizer, slot_dtype,
                  n_devices):
    """Per-device HBM bytes under the current decisions.  Table-wise
    tables go to the least-loaded device (greedy, biggest-first,
    deterministic) — the assignment is recomputed from scratch so it is a
    pure function of the decisions."""
    loads = [0] * n_devices
    tablewise = []
    for name in names:
        d = decisions[name]
        b = table_hbm_bytes(
            int(stats[name]["vocab"]), dim, optimizer=optimizer,
            dtype=d.dtype, slot_dtype=slot_dtype, fused=d.fused,
            hot_k=d.hot_k)
        if d.sharding == "row":
            per = math.ceil(b / n_devices)
            for i in range(n_devices):
                loads[i] += per
        elif d.sharding == "replicated":
            for i in range(n_devices):
                loads[i] += b
        else:
            tablewise.append((b, name))
    assignment = {}
    for b, name in sorted(tablewise, key=lambda t: (-t[0], t[1])):
        dev = min(range(n_devices), key=lambda i: (loads[i], i))
        loads[dev] += b
        assignment[name] = dev
    return loads, assignment


def plan_tables(
    stats: Mapping[str, dict],
    *,
    dim: int,
    batch_size: int,
    optimizer: str,
    dense_model: str,
    n_devices: int = 1,
    hbm_gb: float = 0.0,
    slot_dtype: str = "float32",
) -> dict:
    """Choose a placement for every table in ``stats`` and return the plan
    payload (see :func:`write_plan`).  ``hbm_gb`` > 0 bounds per-device
    allocated bytes; an unsatisfiable budget raises ``ValueError``."""
    if not stats:
        raise ValueError("table stats are empty — nothing to plan")
    names = sorted(stats)
    cands = {n: _candidates(n, stats[n], optimizer, n_devices)
             for n in names}

    def total_ms(decisions, cache=False):
        flush = CACHE_FLUSH_EVERY if cache else None
        return estimate_step_ms(
            _loads(names, stats, decisions, dim=dim, batch_size=batch_size,
                   flush_steps=flush),
            optimizer=optimizer, dense_model=dense_model,
            batch_size=batch_size, n_devices=n_devices,
            cache_flush_every=flush)

    # start at the config-default placement: row-sharded plain f32 —
    # candidate 0 by construction
    decisions = {n: cands[n][0] for n in names}
    best = total_ms(decisions)["total_ms"]

    # coordinate descent over full-plan estimates (see module docstring)
    for _sweep in range(16):
        changed = False
        for name in names:
            cur = decisions[name]
            pick, pick_ms = cur, best
            for cand in cands[name]:
                if cand == cur:
                    continue
                trial = dict(decisions)
                trial[name] = cand
                ms = total_ms(trial)["total_ms"]
                if ms < pick_ms - 1e-9:
                    pick, pick_ms = cand, ms
            if pick != cur:
                decisions[name] = pick
                best = pick_ms
                changed = True
        if not changed:
            break

    # HBM budget repair: while the fullest device overflows, apply the
    # candidate swap with the best predicted-cost-per-byte-saved ratio
    # (bytes saved measured on the fullest device)
    budget = int(hbm_gb * (1 << 30))
    if budget > 0:
        for _ in range(1000):
            loads, _assign = _device_loads(
                names, stats, decisions, dim=dim, optimizer=optimizer,
                slot_dtype=slot_dtype, n_devices=n_devices)
            over = max(loads)
            if over <= budget:
                break
            pick = None
            for name in names:
                cur = decisions[name]
                for idx, cand in enumerate(cands[name]):
                    if cand == cur:
                        continue
                    trial = dict(decisions)
                    trial[name] = cand
                    t_loads, _ = _device_loads(
                        names, stats, trial, dim=dim, optimizer=optimizer,
                        slot_dtype=slot_dtype, n_devices=n_devices)
                    saved = over - max(t_loads)
                    if saved <= 0:
                        continue
                    dms = total_ms(trial)["total_ms"] - best
                    key = (dms / saved, round(dms, 9), name, idx)
                    if pick is None or key < pick[0]:
                        pick = (key, name, cand,
                                total_ms(trial)["total_ms"])
            if pick is None:
                raise ValueError(
                    f"planner cannot fit the tables under {hbm_gb} GB per "
                    f"device (fullest device needs {over / (1 << 30):.2f} "
                    "GB and no candidate swap reduces it) — raise "
                    "planner.hbm_gb or add devices"
                )
            _, name, cand, best = pick
            decisions[name] = cand
        else:
            raise ValueError("planner HBM repair did not converge")

    # update-cache post-pass (module docstring): only a plan carrying
    # plain int8 storage considers the cache — its eager path pays the
    # sidecar scatter buffer + per-step requantize on the big table, which
    # is what the cache-fronted pricing can beat on reuse-heavy traffic
    use_cache, cache_rows, cache_bytes = False, 0, 0
    if any(d.dtype == "int8" and not d.fused for d in decisions.values()):
        # size the cache to the biggest plain storage GROUP's interval
        # working set (stacked arrays share one cache; directories are
        # replicated, so no device division), next power of two with 2x
        # slack so retention never overflows mid-interval
        group_ws: dict[tuple, float] = {}
        for name in names:
            d = decisions[name]
            if d.fused:
                continue
            ws = unique_rows_over(stats[name], batch_size,
                                  CACHE_FLUSH_EVERY)
            if d.hot_k > 0:
                ws *= 1.0 - head_mass_at(stats[name], d.hot_k)
            key = (d.dtype, d.sharding)
            group_ws[key] = group_ws.get(key, 0.0) + ws
        c = 1024
        while c < 2.0 * max(group_ws.values()) and c < (1 << 21):
            c *= 2
        c_bytes = sum(
            cache_hbm_bytes(dim, optimizer=optimizer, dtype=dt,
                            cache_rows=c)
            for dt, _sh in sorted(group_ws))
        t_loads, _ = _device_loads(
            names, stats, decisions, dim=dim, optimizer=optimizer,
            slot_dtype=slot_dtype, n_devices=n_devices)
        fits = budget <= 0 or max(t_loads) + c_bytes <= budget
        cached_ms = total_ms(decisions, cache=True)["total_ms"]
        if fits and cached_ms < best - 1e-9:
            use_cache, cache_rows, cache_bytes = True, c, c_bytes
            best = cached_ms

    final = total_ms(decisions, cache=use_cache)
    loads, assignment = _device_loads(
        names, stats, decisions, dim=dim, optimizer=optimizer,
        slot_dtype=slot_dtype, n_devices=n_devices)

    # the all-defaults baseline the CLI compares against: what the
    # config defaults would build — row-sharded, fat-line storage above
    # the default fused_table_threshold, f32, no hot split
    defaults = {
        n: _Candidate("row", int(stats[n]["vocab"]) > FUSED_MIN_VOCAB,
                      "float32", 0)
        for n in names
    }
    default_ms = total_ms(defaults)["total_ms"]
    default_loads, _ = _device_loads(
        names, stats, defaults, dim=dim, optimizer=optimizer,
        slot_dtype=slot_dtype, n_devices=n_devices)

    tables = {}
    for name in names:
        d = decisions[name]
        entry = stats[name]
        tables[name] = {
            "vocab": int(entry["vocab"]),
            "dim": int(dim),
            "sharding": d.sharding,
            "fused": bool(d.fused),
            "dtype": d.dtype,
            "hot_k": int(d.hot_k),
            "hot_ids": head_ids_for(entry, d.hot_k) if d.hot_k > 0 else [],
            "device": assignment.get(name),
            "predicted_ms": round(final["per_table"][name], 6),
            "hbm_bytes": table_hbm_bytes(
                int(entry["vocab"]), dim, optimizer=optimizer,
                dtype=d.dtype, slot_dtype=slot_dtype, fused=d.fused,
                hot_k=d.hot_k),
        }
    return {
        "format_version": FORMAT_VERSION,
        "batch_size": int(batch_size),
        "n_devices": int(n_devices),
        "dim": int(dim),
        "optimizer": optimizer,
        "dense_model": dense_model,
        "hbm_gb": float(hbm_gb),
        "slot_dtype": slot_dtype,
        # update-cache decision (module docstring): > 0 only when a plain
        # int8 plan predicts a cache win that fits the budget; f32/bf16
        # plans keep the measured-pessimistic 0 (operator opt-in)
        "cache_rows": int(cache_rows),
        "cache_flush_every": CACHE_FLUSH_EVERY if use_cache else 0,
        "stats_digest": table_stats_digest(stats),
        "predicted_step_ms": round(final["total_ms"], 6),
        "predicted_default_ms": round(default_ms, 6),
        "predicted_dense_ms": round(final["dense_ms"], 6),
        "max_device_hbm_bytes": max(loads) + cache_bytes,
        "default_max_device_hbm_bytes": max(default_loads),
        "tables": tables,
    }


# --------------------------------------------------------------------------
# artifact I/O (deterministic: byte-identical across reruns on same stats)
# --------------------------------------------------------------------------


def _canonical(obj):
    if isinstance(obj, float):
        return round(obj, 6)
    if isinstance(obj, dict):
        return {k: _canonical(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_canonical(v) for v in obj]
    return obj


def _dumps(plan: dict) -> str:
    return json.dumps(_canonical(plan), sort_keys=True,
                      separators=(",", ":"))


def write_plan(path: str | Path, plan: dict) -> Path:
    path = Path(path)
    if path.is_dir():
        path = path / PLAN_FILENAME
    path.write_text(_dumps(plan))
    return path


def plan_digest(plan: dict) -> str:
    """Plan fingerprint for the checkpoint ``stamps`` sidecar: sha256 over
    the canonical serialization, truncated to 16 hex chars (the
    ``hot_ids_digest`` idiom) — any placement/dtype/hot-set change flips
    it, so a restore under a different plan refuses loudly."""
    return hashlib.sha256(_dumps(plan).encode()).hexdigest()[:16]


def load_plan(path: str | Path) -> dict:
    """Read and validate a plan artifact.  Raises on a missing file, a
    format-version mismatch, or a structurally corrupt table entry."""
    path = Path(path)
    if path.is_dir():
        path = path / PLAN_FILENAME
    if not path.exists():
        raise ValueError(
            f"no sharding plan at {path} — run `python -m tdfo_tpu.launch "
            "plan --config ...` to generate one from table_stats.json"
        )
    plan = json.loads(path.read_text())
    version = plan.get("format_version")
    if version != FORMAT_VERSION:
        raise ValueError(
            f"{path} has plan format_version {version!r}, this build reads "
            f"{FORMAT_VERSION}.  Re-run the planner."
        )
    tables = plan.get("tables")
    if not isinstance(tables, dict) or not tables:
        raise ValueError(f"{path}: missing 'tables' — the plan is corrupt; "
                         "re-run the planner.")
    for name, entry in tables.items():
        missing = {"sharding", "fused", "dtype", "hot_k",
                   "hot_ids"} - set(entry)
        if missing:
            raise ValueError(f"{path}: table {name!r} is missing "
                             f"{sorted(missing)} — re-run the planner.")
        if entry["sharding"] not in _SHARDINGS:
            raise ValueError(f"{path}: table {name!r} has unknown sharding "
                             f"{entry['sharding']!r}")
        if entry["dtype"] not in _DTYPES:
            raise ValueError(f"{path}: table {name!r} has unknown dtype "
                             f"{entry['dtype']!r}")
        ids = entry["hot_ids"]
        k = int(entry["hot_k"])
        if k > 0:
            if len(ids) != k or any(b <= a for a, b in zip(ids, ids[1:])) \
                    or (ids and ids[0] < 0):
                raise ValueError(
                    f"{path}: table {name!r} hot ids must be {k} sorted, "
                    "unique, non-negative ids — the plan is corrupt; "
                    "re-run the planner."
                )
    return plan


def format_plan(plan: dict) -> str:
    """Human-readable plan summary for the ``launch.py plan`` subcommand:
    one line per table (costliest first) plus the plan-vs-defaults
    predicted step times."""
    rows = sorted(plan["tables"].items(),
                  key=lambda kv: (-kv[1]["predicted_ms"], kv[0]))
    lines = [
        f"{'table':<24} {'vocab':>10} {'sharding':>10} {'store':>6} "
        f"{'dtype':>9} {'hot_k':>6} {'dev':>4} {'HBM':>9} {'pred ms':>8}"
    ]
    for name, e in rows:
        dev = "-" if e.get("device") is None else str(e["device"])
        hbm = e.get("hbm_bytes", 0) / (1 << 20)
        lines.append(
            f"{name:<24} {e['vocab']:>10} {e['sharding']:>10} "
            f"{'fused' if e['fused'] else 'plain':>6} {e['dtype']:>9} "
            f"{e['hot_k']:>6} {dev:>4} {hbm:>8.1f}M "
            f"{e['predicted_ms']:>8.3f}"
        )
    lines.append(
        f"predicted step: plan {plan['predicted_step_ms']:.3f} ms vs "
        f"all-defaults {plan['predicted_default_ms']:.3f} ms "
        f"(dense {plan['predicted_dense_ms']:.3f} ms, B="
        f"{plan['batch_size']}, {plan['n_devices']} device(s), "
        f"digest {plan_digest(plan)})"
    )
    if "default_max_device_hbm_bytes" in plan:
        cur = plan["max_device_hbm_bytes"] / (1 << 20)
        dflt = plan["default_max_device_hbm_bytes"] / (1 << 20)
        lines.append(
            f"per-device HBM: plan {cur:.1f} MB vs all-defaults "
            f"{dflt:.1f} MB ({dflt - cur:+.1f} MB saved)"
        )
    if plan.get("cache_rows"):
        lines.append(
            f"update cache: cache_rows {plan['cache_rows']} @ flush_every "
            f"{plan['cache_flush_every']} (int8 write-combining; cache HBM "
            "counted in the per-device total)"
        )
    return "\n".join(lines)


def apply_plan_to_specs(specs, plan: dict):
    """Rewrite embedding specs to the plan's per-table decisions.  Returns
    ``(new_specs, hot_ids)`` where ``hot_ids`` is the plan-embedded
    ``{table_key: sorted int32 ids}`` mapping (or ``None`` when no table
    is hot-split).  Plan entries match a spec by table name or by any of
    its feature names (stats artifacts key by column).  A served table
    with no plan entry is an error — a plan must place every table."""
    import jax.numpy as jnp
    import numpy as np

    tables = plan["tables"]
    new_specs, hot_ids, missing = [], {}, []
    for spec in specs:
        key = None
        if spec.name in tables:
            key = spec.name
        else:
            for f in spec.features:
                if f in tables:
                    key = f
                    break
        if key is None:
            missing.append(spec.name)
            continue
        entry = tables[key]
        if int(entry.get("vocab", spec.num_embeddings)) != spec.num_embeddings:
            raise ValueError(
                f"plan table {key!r} was built for vocab {entry['vocab']} "
                f"but the model serves {spec.num_embeddings} — the plan is "
                "stale; re-run the planner on current stats."
            )
        new_specs.append(dataclasses.replace(
            spec,
            sharding=entry["sharding"],
            fused=bool(entry["fused"]),
            dtype=jnp.dtype(entry["dtype"]),
        ))
        if int(entry["hot_k"]) > 0:
            hot_ids[key] = np.asarray(entry["hot_ids"], dtype=np.int32)
    if missing:
        raise ValueError(
            f"sharding plan has no entry for tables {sorted(missing)} — "
            "regenerate the plan from this model's table_stats.json"
        )
    return new_specs, (hot_ids or None)
