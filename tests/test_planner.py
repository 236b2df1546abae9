"""Cost-model-driven auto-sharding planner tests (``tdfo_tpu/plan``).

The calibration contract is the load-bearing piece: ``estimate_step_ms``
must reproduce BOTH docs/BUDGET.md in-situ step budgets (builders' round-4
readings, not ledger numbers) — DLRM-Criteo plain 22.4 ms vs fused 29-32 ms,
TwoTower fused 1.40 ms vs plain ~2.8 ms — with the correct plain-vs-fused
ORDERING on each profile, because that ordering is exactly the decision the
planner exists to make.  On top of that: the stats artifact round trip (preprocessing -> table_stats.json ->
planner), plan determinism/byte-identity, the HBM budget repair, the
telemetry-refinement round trip, and the trainer-level wiring (plan ->
actual spec/array placement, trajectory equivalence with hand-set knobs,
checkpoint plan-digest refusal).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tdfo_tpu.plan.costs import (
    TableLoad,
    cache_hbm_bytes,
    estimate_step_ms,
    expected_lines,
    in_situ_multiplier,
    line_geometry,
    padded_lane_width,
    table_hbm_bytes,
)
from tdfo_tpu.plan.planner import (
    CACHE_FLUSH_EVERY,
    FUSED_MIN_VOCAB,
    apply_plan_to_specs,
    format_plan,
    load_plan,
    plan_digest,
    plan_tables,
    write_plan,
)
from tdfo_tpu.plan.stats import (
    _expected_unique,
    head_ids_for,
    head_mass_at,
    load_table_stats,
    refine_stats_from_metrics,
    table_stats_digest,
    table_stats_from_counts,
    unique_rows_at,
    unique_rows_over,
    write_table_stats,
)

# ---------------------------------------------------- calibration profiles
#
# Pinned to the docs/BUDGET.md chip facts (the Criteo-Kaggle vocabularies,
# equal to chip_smoke.CRITEO_KAGGLE_VOCABS by tests/test_chip_smoke.py, +
# the measured per-step touch counts): 26 tables, 213k ids/step deduping to
# ~102k touched rows / ~77k touched fat lines at B=8192.  Uniques are the
# per-table occupancy expectations rescaled to land the MEASURED totals —
# the budget numbers are chip-observed truth, so the profile pins them
# rather than trusting the analytic estimate end to end.

CRITEO_VOCABS = (
    1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145, 5683,
    8351593, 3194, 27, 14992, 5461306, 10, 5652, 2173, 4, 7046547, 18, 15,
    286181, 105, 142572,
)
CRITEO_TOUCHED_ROWS = 102_000
CRITEO_TOUCHED_LINES = 77_000

# TwoTower profile (docs/BUDGET.md "TwoTower DMP" table): ~8k touched rows
# across the 7 tables at B=8192 under the power-law goodreads traffic.
TWOTOWER_PROFILE = {
    "user": (1_600_000, 4000.0),
    "item": (760_000, 3500.0),
    "language": (32, 32.0),
    "is_ebook": (2, 2.0),
    "format": (16, 16.0),
    "publisher": (5000, 630.0),
    "pub_decade": (16, 16.0),
}


def _criteo_loads(fused: bool) -> list[TableLoad]:
    raw = [_expected_unique(v, 8192) for v in CRITEO_VOCABS]
    scale = CRITEO_TOUCHED_ROWS / sum(raw)
    uniq = [u * scale for u in raw]
    lines = None
    if fused:
        _, rpl = line_geometry(16, "rowwise_adagrad", "float32")
        lraw = [expected_lines(u, v, rpl)
                for u, v in zip(uniq, CRITEO_VOCABS)]
        lscale = CRITEO_TOUCHED_LINES / sum(lraw)
        lines = [l * lscale for l in lraw]
    return [
        TableLoad(name=f"cat_{i}", vocab=v, dim=16, ids_per_batch=8192.0,
                  unique_rows=u, fused=fused,
                  unique_lines=lines[i] if fused else None)
        for i, (v, u) in enumerate(zip(CRITEO_VOCABS, uniq))
    ]


def _twotower_loads(fused: bool) -> list[TableLoad]:
    return [
        TableLoad(name=n, vocab=v, dim=64, ids_per_batch=8192.0,
                  unique_rows=u, fused=fused,
                  # d=64 adam packs 1 row/line: touched lines == rows
                  unique_lines=u if fused else None)
        for n, (v, u) in TWOTOWER_PROFILE.items()
    ]


def test_calibration_reproduces_budget_anchors():
    """The planner's license to operate: the estimator lands both measured
    step budgets within 30% AND orders plain-vs-fused correctly on both
    profiles (Criteo prefers plain, TwoTower prefers fused)."""
    crit_plain = estimate_step_ms(
        _criteo_loads(False), optimizer="rowwise_adagrad",
        dense_model="dlrm", batch_size=8192)
    crit_fused = estimate_step_ms(
        _criteo_loads(True), optimizer="rowwise_adagrad",
        dense_model="dlrm", batch_size=8192)
    assert abs(crit_plain["total_ms"] - 22.4) / 22.4 < 0.30, crit_plain
    assert abs(crit_fused["total_ms"] - 30.5) / 30.5 < 0.30, crit_fused
    assert crit_plain["total_ms"] < crit_fused["total_ms"]

    tt_fused = estimate_step_ms(
        _twotower_loads(True), optimizer="adam", dense_model="twotower",
        batch_size=8192)
    tt_plain = estimate_step_ms(
        _twotower_loads(False), optimizer="adam", dense_model="twotower",
        batch_size=8192)
    assert abs(tt_fused["total_ms"] - 1.40) / 1.40 < 0.30, tt_fused
    assert abs(tt_plain["total_ms"] - 2.8) / 2.8 < 0.30, tt_plain
    assert tt_fused["total_ms"] < tt_plain["total_ms"]

    # the Criteo step runs deep in the in-situ regime, TwoTower does not —
    # the contention ramp is what separates the two orderings
    assert crit_plain["in_situ_multiplier"] == 3.0
    assert tt_fused["in_situ_multiplier"] == 1.0


def test_cost_model_geometry():
    # d=16 rowwise-adagrad f32: 17 elems -> 32-wide row, 4 rows per line
    assert line_geometry(16, "rowwise_adagrad", "float32") == (32, 4)
    # d=64 adam f32: 192 elems -> 256-wide row, one row per (2-line) row
    assert line_geometry(64, "adam", "float32") == (256, 1)
    # occupancy: saturated small tables compress ~R-fold, and the
    # single-line guard never divides by zero
    assert expected_lines(0.0, 100, 4) == 0.0
    assert expected_lines(5.0, 3, 4) == 1.0
    assert 24.0 < expected_lines(100.0, 100, 4) <= 25.0
    # ramp endpoints
    assert in_situ_multiplier(1000) == 1.0
    assert in_situ_multiplier(1 << 20) == 3.0
    # XLA lane padding: [V, 64] allocates 128 lanes (2x), narrow dims do not
    assert padded_lane_width(64) == 128 and padded_lane_width(16) == 16
    v = 1000
    assert table_hbm_bytes(v, 64, optimizer="sgd") == v * 128 * 4
    assert table_hbm_bytes(v, 64, optimizer="sgd", dtype="bfloat16") \
        == v * 128 * 2
    # rowwise-adagrad plain: padded table + the f32 [V] accumulator
    assert table_hbm_bytes(v, 16, optimizer="rowwise_adagrad") \
        == v * 16 * 4 + v * 4


# ------------------------------------------------------- stats artifact


def test_table_stats_from_counts_basic():
    counts = np.array([40, 0, 10, 10, 40], np.int64)
    e = table_stats_from_counts(counts)
    assert e["vocab"] == 5 and e["total_count"] == 100.0
    # occupancy expectation is monotone in B and bounded by the support
    us = [e["unique_per_batch"][str(b)] for b in (1024, 8192, 32768)]
    assert us[0] <= us[1] <= us[2] <= 4.0 + 1e-9  # id 1 never appears
    # head ranking: stable ties toward lower ids -> 0, 4, 2, 3 (1 is last)
    assert e["head_ids"][:4] == [0, 4, 2, 3]
    assert head_mass_at(e, 5) == 1.0
    assert head_ids_for(e, 2) == [0, 4]
    with pytest.raises(ValueError, match="head"):
        head_ids_for({"vocab": 10, "head_ids": [1]}, 5)


def test_stats_roundtrip_digest_and_corruption(tmp_path):
    per = {"a": np.array([5, 1, 1], np.int64), "b": np.ones(64, np.int64)}
    write_table_stats(tmp_path, per)
    loaded = load_table_stats(tmp_path)
    assert set(loaded) == {"a", "b"}
    assert loaded["a"]["vocab"] == 3
    # digest: stable across a round trip, sensitive to the counts
    d1 = table_stats_digest(loaded)
    write_table_stats(tmp_path, per)
    assert table_stats_digest(load_table_stats(tmp_path)) == d1
    per2 = dict(per, b=np.arange(64, dtype=np.int64))
    write_table_stats(tmp_path, per2)
    assert table_stats_digest(load_table_stats(tmp_path)) != d1
    # absent and corrupt artifacts
    assert load_table_stats(tmp_path / "nope") is None
    p = tmp_path / "table_stats.json"
    payload = json.loads(p.read_text())
    payload["format_version"] = 99
    p.write_text(json.dumps(payload))
    with pytest.raises(ValueError, match="format_version"):
        load_table_stats(tmp_path)
    payload["format_version"] = 1
    payload["tables"]["a"]["head_ids"] = [0, 99]
    p.write_text(json.dumps(payload))
    with pytest.raises(ValueError, match="head_ids"):
        load_table_stats(tmp_path)


def test_unique_rows_interpolation_and_observed_priority():
    e = table_stats_from_counts(np.ones(100_000, np.int64))
    u4k = unique_rows_at(e, 4096)
    u6k = unique_rows_at(e, 6144)
    u8k = unique_rows_at(e, 8192)
    assert u4k < u6k < u8k <= 8192.0
    assert abs(u6k - (u4k + u8k) / 2) < 1e-6  # linear between grid points
    # a telemetry-observed mean at the SAME batch size wins outright
    e2 = dict(e, observed={"batch": 6144, "unique_rows": 1234.0})
    assert unique_rows_at(e2, 6144) == 1234.0
    assert unique_rows_at(e2, 8192) == u8k  # other batch sizes fall back


def test_criteo_preprocessing_emits_stats(tmp_path):
    """The ETL emits table_stats.json unconditionally, its head ranking is
    consistent with the hot/cold artifact (same stable ordering), and the
    occupancy estimates are sane."""
    from tdfo_tpu.data.criteo_preprocessing import (
        CRITEO_CATEGORICAL,
        run_criteo_preprocessing,
    )
    from tdfo_tpu.data.hot_ids import load_hot_ids
    from tdfo_tpu.data.synthetic import write_synthetic_criteo

    write_synthetic_criteo(tmp_path, n_rows=600, seed=0)
    size_map = run_criteo_preprocessing(tmp_path, hot_vocab=8,
                                        hot_fraction=0.8, min_freq=2)
    stats = load_table_stats(tmp_path)
    assert stats is not None and set(stats) == set(CRITEO_CATEGORICAL)
    hot = load_hot_ids(tmp_path)
    for c in CRITEO_CATEGORICAL:
        e = stats[c]
        assert e["vocab"] == size_map[c]
        assert e["total_count"] == 600.0  # one lookup per row per column
        u = unique_rows_at(e, 8192)
        assert 0 < u <= size_map[c]
        # hot/cold sets are prefixes of the SAME frequency ranking
        k = len(hot[c])
        np.testing.assert_array_equal(hot[c], head_ids_for(e, k))


def test_ctr_preprocessing_emits_stats(tmp_path):
    from tdfo_tpu.data.ctr_preprocessing import run_ctr_preprocessing
    from tdfo_tpu.data.synthetic import write_synthetic_goodreads

    write_synthetic_goodreads(tmp_path, n_users=60, n_books=90,
                              interactions_per_user=(10, 20), seed=3)
    size_map = run_ctr_preprocessing(tmp_path)
    stats = load_table_stats(tmp_path)
    assert set(stats) == {"user_id", "item_id", "language", "is_ebook",
                          "format", "publisher", "pub_decade"}
    assert stats["user_id"]["vocab"] == size_map["user"]
    assert stats["item_id"]["vocab"] == size_map["item"]
    # category traffic is the item traffic folded through book features:
    # same total lookup mass as the item table (train-split pairs)
    assert sum(stats[c]["total_count"] for c in ("language",)) > 0
    for c in ("language", "is_ebook", "format", "publisher", "pub_decade"):
        assert stats[c]["total_count"] == stats["item_id"]["total_count"]


# ------------------------------------------------------------- planner


def _uniform_stats(vocabs: dict[str, int]) -> dict:
    return {n: table_stats_from_counts(np.ones(v, np.int64))
            for n, v in vocabs.items()}


@pytest.fixture(scope="module")
def criteo_stats():
    return _uniform_stats(
        {f"cat_{i}": v for i, v in enumerate(CRITEO_VOCABS)})


def _criteo_plan(criteo_stats, **kw):
    kw.setdefault("dim", 16)
    kw.setdefault("batch_size", 8192)
    kw.setdefault("optimizer", "rowwise_adagrad")
    kw.setdefault("dense_model", "dlrm")
    return plan_tables(criteo_stats, **kw)


def test_planner_keeps_criteo_big_tables_plain(criteo_stats):
    """The docs/BUDGET.md "Fused fat-line findings" decision: at the Criteo
    profile every fused-eligible table stays on the plain-scatter path, and
    the plan beats the all-defaults (fused) baseline it reports."""
    plan = _criteo_plan(criteo_stats)
    big = {n: e for n, e in plan["tables"].items()
           if e["vocab"] > FUSED_MIN_VOCAB}
    assert len(big) == 8
    assert all(not e["fused"] for e in big.values()), big
    assert all(e["sharding"] == "row" for e in big.values())
    assert plan["predicted_step_ms"] < plan["predicted_default_ms"]
    # small tables ride the one-hot MXU tier (fully hot) — the hot/cold
    # subsystem's measured sweet spot, never fat-packed
    small = {n: e for n, e in plan["tables"].items()
             if e["vocab"] <= FUSED_MIN_VOCAB}
    assert all(not e["fused"] for e in small.values())


def test_planner_prefers_fused_on_twotower_profile():
    """The other half of the ordering: d=64 adam tables at ~8k touches
    choose the fused fat-line path (the 1.40 vs 2.8 ms measurement)."""
    stats = _uniform_stats({n: v for n, (v, _) in TWOTOWER_PROFILE.items()})
    plan = plan_tables(stats, dim=64, batch_size=8192, optimizer="adam",
                       dense_model="twotower")
    assert plan["tables"]["user"]["fused"]
    assert plan["tables"]["item"]["fused"]


def test_plan_deterministic_and_stamped(tmp_path, criteo_stats):
    plan1 = _criteo_plan(criteo_stats)
    plan2 = _criteo_plan(criteo_stats)
    assert plan1 == plan2
    p1 = write_plan(tmp_path / "a.json", plan1)
    p2 = write_plan(tmp_path / "b.json", plan2)
    assert p1.read_bytes() == p2.read_bytes()  # byte-identical artifact
    assert plan_digest(plan1) == plan_digest(load_plan(p1))
    assert plan1["stats_digest"] == table_stats_digest(criteo_stats)
    # a different traffic profile flips the digest
    other = _criteo_plan(criteo_stats, batch_size=16384)
    assert plan_digest(other) != plan_digest(plan1)
    # the human summary carries the decisions and the digest
    text = format_plan(plan1)
    assert "cat_2" in text and plan_digest(plan1) in text


def test_planner_hbm_budget_demotes_and_refuses(criteo_stats):
    free = _criteo_plan(criteo_stats, n_devices=8)
    budget = _criteo_plan(criteo_stats, n_devices=8, hbm_gb=2.0)
    assert free["max_device_hbm_bytes"] > 0
    assert budget["max_device_hbm_bytes"] <= 2.0 * (1 << 30)
    # demotion may not break plan validity
    for e in budget["tables"].values():
        assert e["sharding"] in ("row", "replicated", "table")
    with pytest.raises(ValueError, match="cannot fit"):
        _criteo_plan(criteo_stats, n_devices=8, hbm_gb=0.001)


def test_planner_demotes_to_int8_under_tight_budget(criteo_stats):
    """A budget bf16 cannot satisfy pushes big tables onto int8 storage
    (the 3.76x d=64 / 2.67x d=16 HBM lever) and the summary reports the
    per-device HBM saved vs all-defaults.  int8 now composes with the
    fused and hot/cold layouts, but on THIS profile neither wins: the
    Criteo optimizer is rowwise_adagrad (fused int8 is a retained
    refusal — no per-row second moment to byte-pack), and uniform
    traffic has no head for hot/cold and no reuse for the update cache,
    so the tight-budget plan stays plain int8 with cache_rows 0."""
    plan = _criteo_plan(criteo_stats, n_devices=8, hbm_gb=0.25)
    int8 = {n: e for n, e in plan["tables"].items()
            if e["dtype"] == "int8"}
    assert int8, plan["tables"]
    assert plan["max_device_hbm_bytes"] <= 0.25 * (1 << 30)
    assert plan["max_device_hbm_bytes"] \
        < plan["default_max_device_hbm_bytes"]
    for n, e in int8.items():
        # rowwise_adagrad keeps the fused-int8 refusal everywhere
        assert not e["fused"], n
        # uniform traffic never justifies a PARTIAL hot head on a big
        # demoted table; small tables may keep their fully-hot MXU tier
        # while demoting — that composition is exactly what this PR lifts
        if e["vocab"] > FUSED_MIN_VOCAB:
            assert e["hot_k"] == 0, n
        elif e["hot_k"]:
            assert e["hot_k"] == e["vocab"], n
    assert plan["cache_rows"] == 0  # no reuse -> cache cannot win
    assert plan["cache_flush_every"] == 0
    text = format_plan(plan)
    assert "per-device HBM" in text and "int8" in text


@pytest.fixture(scope="module")
def criteo_zipf_stats():
    """Zipf(1.2) traffic over the Criteo vocabs: heavy reuse inside a
    flush interval, the regime the update cache is meant for (builders'
    expectation, round 4; never measured on the chip)."""
    stats = {}
    for i, v in enumerate(CRITEO_VOCABS):
        p = np.arange(1, v + 1, dtype=np.float64) ** -1.2
        counts = np.floor(p / p.sum() * 10_000_000).astype(np.int64)
        counts[0] += 10_000_000 - counts.sum()
        stats[f"cat_{i}"] = table_stats_from_counts(counts)
    return stats


def test_planner_zipf_tight_budget_selects_int8_cache(criteo_zipf_stats):
    """The lifted composition actually gets SELECTED: under the same
    tight budget but zipf traffic (interval working set << touched rows
    x flush_every), the plan demotes to int8 AND fronts the plain-int8
    storage with the update cache, pricing the flush from the stats
    occupancy curve.  Deterministic and digest-stamped like every plan."""
    kw = dict(dim=16, batch_size=8192, optimizer="rowwise_adagrad",
              dense_model="dlrm", n_devices=8, hbm_gb=0.25)
    plan = plan_tables(criteo_zipf_stats, **kw)
    int8 = {n: e for n, e in plan["tables"].items()
            if e["dtype"] == "int8"}
    assert int8, plan["tables"]
    # the acceptance composition: at least one int8+fused table or a
    # cache-fronted int8 plan (rowwise_adagrad refuses fused int8, so
    # here it must be the cache)
    assert any(e["fused"] for e in int8.values()) \
        or plan["cache_rows"] > 0
    assert plan["cache_rows"] > 0
    assert plan["cache_flush_every"] == CACHE_FLUSH_EVERY
    # cache HBM is accounted inside the budget, not snuck past it
    assert plan["max_device_hbm_bytes"] <= 0.25 * (1 << 30)
    plan2 = plan_tables(criteo_zipf_stats, **kw)
    assert plan == plan2
    assert plan_digest(plan) == plan_digest(plan2)
    assert plan["stats_digest"] == table_stats_digest(criteo_zipf_stats)
    text = format_plan(plan)
    assert "update cache" in text and str(plan["cache_rows"]) in text


def test_unique_rows_over_and_cache_hbm():
    """Interval working set: monotone in steps, clamped by vocab and by
    total draws, and never below the single-batch unique count.  Cache
    HBM prices codes + slots + sidecars + directory per plain group."""
    p = np.arange(1, 100_001, dtype=np.float64) ** -1.2
    counts = np.floor(p / p.sum() * 1_000_000).astype(np.int64)
    counts[0] += 1_000_000 - counts.sum()
    e = table_stats_from_counts(counts)
    u1 = unique_rows_at(e, 8192)
    u64 = unique_rows_over(e, 8192, 64)
    assert u1 <= unique_rows_over(e, 8192, 1) + 1e-6
    assert u1 < u64 < 64 * u1  # reuse: sublinear growth
    assert u64 <= e["vocab"]
    assert unique_rows_over(e, 8192, 10**9) <= e["vocab"]
    # int8 rowwise cache row: 16 codes + 4 slot + 8 qscale + 16 directory
    c = cache_hbm_bytes(16, optimizer="rowwise_adagrad", dtype="int8",
                        cache_rows=1024)
    assert c == 1024 * (16 + 4 + 8 + 16)
    f = cache_hbm_bytes(16, optimizer="rowwise_adagrad", dtype="float32",
                        cache_rows=1024)
    assert f == 1024 * (16 * 4 + 4 + 16)  # d=16 keeps narrow tiles
    f64 = cache_hbm_bytes(64, optimizer="adam", dtype="float32",
                          cache_rows=1024)
    assert f64 == 1024 * (128 * 4 + 2 * 128 * 4 + 16)  # d=64 lane-pads


def test_load_plan_validation(tmp_path, criteo_stats):
    with pytest.raises(ValueError, match="launch"):
        load_plan(tmp_path / "missing.json")
    plan = _criteo_plan(criteo_stats)
    p = write_plan(tmp_path, plan)  # dir -> sharding_plan.json
    assert p.name == "sharding_plan.json"
    payload = json.loads(p.read_text())
    payload["format_version"] = 99
    p.write_text(json.dumps(payload))
    with pytest.raises(ValueError, match="format_version"):
        load_plan(p)
    payload["format_version"] = 1
    payload["tables"]["cat_0"]["hot_k"] = 2
    payload["tables"]["cat_0"]["hot_ids"] = [2, 1]
    p.write_text(json.dumps(payload))
    with pytest.raises(ValueError, match="sorted"):
        load_plan(p)


def test_apply_plan_to_specs():
    from tdfo_tpu.parallel.embedding import EmbeddingSpec

    specs = [EmbeddingSpec("a_embed", 40_000, 8, features=("a",)),
             EmbeddingSpec("b_embed", 50, 8, features=("b",))]
    plan = {"tables": {
        "a": {"vocab": 40_000, "sharding": "replicated", "fused": True,
              "dtype": "bfloat16", "hot_k": 0, "hot_ids": []},
        "b_embed": {"vocab": 50, "sharding": "row", "fused": False,
                    "dtype": "float32", "hot_k": 2, "hot_ids": [3, 7]},
    }}
    new, hot = apply_plan_to_specs(specs, plan)
    assert new[0].sharding == "replicated" and new[0].fused
    assert new[0].dtype == jnp.bfloat16
    assert new[1].sharding == "row" and not new[1].fused
    assert set(hot) == {"b_embed"}
    assert hot["b_embed"].dtype == np.int32
    np.testing.assert_array_equal(hot["b_embed"], [3, 7])
    # stale plan: vocab mismatch must refuse
    stale = {"tables": {**plan["tables"],
                        "a": dict(plan["tables"]["a"], vocab=999)}}
    with pytest.raises(ValueError, match="stale"):
        apply_plan_to_specs(specs, stale)
    # a served table missing from the plan must refuse
    with pytest.raises(ValueError, match="no entry"):
        apply_plan_to_specs(
            specs, {"tables": {"a": plan["tables"]["a"]}})


# ------------------------------------------- telemetry-refinement round trip


def test_plan_from_replayed_counters_matches_synthetic(tmp_path):
    """PR-7 feedback loop: replaying a run's counter means through
    ``refine_stats_from_metrics`` reproduces the plan the synthetic stats
    produce when the observed traffic MATCHES the analytic estimate — the
    adapter changes provenance, not decisions."""
    rng = np.random.default_rng(0)
    vocabs = {"big": 200_000, "mid": 30_000, "tiny": 500}
    stats = {}
    for n, v in vocabs.items():
        counts = rng.zipf(1.3, size=20_000) % v
        stats[n] = table_stats_from_counts(
            np.bincount(counts, minlength=v).astype(np.int64))
    batch = 8192
    metrics = tmp_path / "metrics.jsonl"
    with open(metrics, "w") as fh:
        for _ in range(3):  # several records: the adapter takes means
            rec = {}
            for n in vocabs:
                rec[f"emb/{n}/touched_ids"] = float(batch)
                rec[f"emb/{n}/unique_rows"] = unique_rows_at(stats[n], batch)
            fh.write(json.dumps(rec) + "\n")
    refined = refine_stats_from_metrics(stats, metrics, batch_size=batch)
    assert all("observed" in refined[n] for n in vocabs)

    kw = dict(dim=16, batch_size=batch, optimizer="rowwise_adagrad",
              dense_model="dlrm")
    plan_syn = plan_tables(stats, **kw)
    plan_obs = plan_tables(refined, **kw)
    for n in vocabs:
        for key in ("sharding", "fused", "dtype", "hot_k"):
            assert plan_obs["tables"][n][key] == plan_syn["tables"][n][key]
    assert plan_obs["predicted_step_ms"] == pytest.approx(
        plan_syn["predicted_step_ms"], rel=1e-3)


# --------------------------------------------------- trainer-level wiring


@pytest.fixture(scope="module")
def plan_data(tmp_path_factory):
    from tdfo_tpu.data.ctr_preprocessing import run_ctr_preprocessing
    from tdfo_tpu.data.synthetic import write_synthetic_goodreads

    d = tmp_path_factory.mktemp("gr_plan")
    write_synthetic_goodreads(d, n_users=80, n_books=120,
                              interactions_per_user=(15, 40), seed=7)
    ctr = run_ctr_preprocessing(d, hot_vocab=4, hot_fraction=0.8)
    return d, ctr


def _trainer_cfg(d, ctr, **kw):
    from tdfo_tpu.core.config import read_configs

    return read_configs(
        None, data_dir=d, model="twotower", model_parallel=True,
        mesh={"data": 4, "model": 2}, n_epochs=1, learning_rate=3e-3,
        embed_dim=8, per_device_train_batch_size=16,
        per_device_eval_batch_size=16, shuffle_buffer_size=500,
        log_every_n_steps=2, size_map=ctr,
        sparse_optimizer="rowwise_adagrad", **kw)


# twotower feature-column -> size_map vocab key
_COL_TO_VOCAB = {"user_id": "user", "item_id": "item", "language": "language",
                 "is_ebook": "is_ebook", "format": "format",
                 "publisher": "publisher", "pub_decade": "pub_decade"}


def _hand_plan(ctr, overrides=None):
    tables = {}
    for col, vkey in _COL_TO_VOCAB.items():
        tables[col] = {"vocab": int(ctr[vkey]), "sharding": "row",
                       "fused": False, "dtype": "float32",
                       "hot_k": 0, "hot_ids": []}
    for col, entry in (overrides or {}).items():
        tables[col].update(entry)
    return {"format_version": 1, "tables": tables}


def test_plan_placement_wiring(plan_data, tmp_path):
    """The plan's decisions become the ACTUAL placement: fused storage,
    storage dtype, replicated cold base + hot head, row sharding — read
    back off the trainer's specs and device arrays, and the plan digest is
    stamped for the checkpoint sidecar."""
    from jax.sharding import PartitionSpec as P

    from tdfo_tpu.train.trainer import Trainer

    d, ctr = plan_data
    plan = _hand_plan(ctr, {
        # two fused f32/row tables -> they share ONE __fatstack_ array
        "user_id": {"fused": True},
        "format": {"fused": True},
        "item_id": {"dtype": "bfloat16"},
        "language": {"sharding": "replicated", "hot_k": 2,
                     "hot_ids": [0, 1]},
        "publisher": {"sharding": "replicated"},
    })
    path = write_plan(tmp_path / "plan.json", plan)
    tr = Trainer(_trainer_cfg(d, ctr, stack_tables=False,
                              planner={"plan": str(path)}),
                 log_dir=tmp_path / "log")
    by_name = tr.coll.specs  # dict name -> (plan-replaced) spec
    assert by_name["user_embed"].fused and by_name["format_embed"].fused
    assert by_name["item_embed"].dtype == jnp.bfloat16
    assert by_name["language_embed"].sharding == "replicated"
    tables = tr.state.tables
    # the two fused tables stack into ONE fat-line 3D array
    fat = [n for n in tables if n.startswith("__fatstack_")]
    assert len(fat) == 1 and tables[fat[0]].ndim == 3
    assert "user_embed" not in tables and "format_embed" not in tables
    # plain bf16 storage, row-sharded over the model axis
    assert tables["item_embed"].dtype == jnp.bfloat16
    assert tables["item_embed"].sharding.spec[0] == "model"
    # replicated cold base + replicated hot head with the plan's id set
    assert tables["language_embed"].sharding.spec == P()
    assert tables["language_embed__hot"].shape == (2, 8)
    assert tr.coll.hot_count("language_embed") == 2
    # the checkpoint sidecar pins this placement
    assert tr._ckpt_stamps["sharding_plan"] == plan_digest(plan)
    # bf16 storage stamps ride along from the plan-replaced specs
    assert tr._ckpt_stamps["table_dtype"]["item_embed"] == "bfloat16"


def test_plan_trajectory_matches_hand_knobs(plan_data, tmp_path):
    """A plan expressing exactly the hand-set knobs (row/plain/f32 + the
    hot_ids.json head sets) trains the SAME trajectory as
    embeddings.hot_vocab — the plan is a routing change, not a math
    change."""
    from tdfo_tpu.data.hot_ids import load_hot_ids
    from tdfo_tpu.train.trainer import Trainer

    d, ctr = plan_data
    m_hand = Trainer(_trainer_cfg(d, ctr, embeddings={"hot_vocab": 4}),
                     log_dir=tmp_path / "hand").fit()
    hot = load_hot_ids(d)
    plan = _hand_plan(ctr, {
        col: {"hot_k": len(hot[col]),
              "hot_ids": [int(i) for i in hot[col]]}
        for col in ("user_id", "item_id")
    })
    path = write_plan(tmp_path / "plan.json", plan)
    m_plan = Trainer(_trainer_cfg(d, ctr, planner={"plan": str(path)}),
                     log_dir=tmp_path / "plan").fit()
    assert set(m_plan) == set(m_hand)
    for k in m_hand:
        assert m_plan[k] == m_hand[k], (k, m_plan[k], m_hand[k])


def test_launch_plan_subcommand(plan_data, tmp_path, capsys):
    from tdfo_tpu.launch import main

    d, _ = plan_data
    cfgp = tmp_path / "config.toml"
    cfgp.write_text(
        f"""
data_dir = "{d}"
model = "twotower"
model_parallel = true
embed_dim = 8
per_device_train_batch_size = 16

[planner]
n_devices = 2
"""
    )
    assert main(["plan", "--config", str(cfgp)]) == 0
    out = capsys.readouterr().out
    assert "predicted step" in out and "sharding_plan.json" in out
    plan = load_plan(d)
    assert set(plan["tables"]) == set(_COL_TO_VOCAB)
    assert plan["n_devices"] == 2
    # global batch = per-device x planned devices
    assert plan["batch_size"] == 32


def test_plan_stamp_refuses_mismatched_restore(tmp_path):
    """A plan-built checkpoint pairs state layout with the plan digest:
    restore under a different plan — or none — refuses, both directions;
    legacy stampless checkpoints restore into plan-less runs untouched."""
    from tdfo_tpu.train.checkpoint import CheckpointManager

    state = {"t": jnp.zeros((4, 8), jnp.float32)}
    stamp = {"sharding_plan": "aaaa000011112222"}
    mgr = CheckpointManager(tmp_path / "c")
    mgr.save(0, state, stamps=dict(stamp))
    step, _, _ = mgr.restore(state, stamps=dict(stamp))
    assert step == 0
    for bad in (None, {"sharding_plan": "ffff000011112222"}):
        with pytest.raises(ValueError, match="stamps"):
            mgr.restore(state, stamps=bad)
    mgr.close()
    mgr2 = CheckpointManager(tmp_path / "c2")
    mgr2.save(0, state)  # legacy, no stamps
    s, _, _ = mgr2.restore(state, stamps=None)
    assert s == 0
    with pytest.raises(ValueError, match="stamps"):
        mgr2.restore(state, stamps=dict(stamp))
    mgr2.close()
