"""How ``correct`` is decided, driven end to end at a tiny size on the CPU:
the plain reference agrees with the program's own ``Trainer.train_step``; the
control and every fault a one-chip training cell can have come out NOT
correct under the limits the cells commit."""

import jax
import numpy as np
import pytest

from benchmarks.drivers import train_epoch as D
from benchmarks.lib import compare as cmp, traffic as traffic_lib

from bench_examples import CELL, EXAMPLE

CELLS = [CELL, EXAMPLE]


@pytest.mark.parametrize("workload", CELLS)
def test_reference_agrees_with_the_trainer(run_tiny, workload):
    res = run_tiny(workload)
    assert res["correct"] is True, res["compared"]
    # float32 on both sides on the CPU: far inside every limit
    assert res["compared"]["grad_norm_gap"]["value"] < 1e-4
    assert res["compared"]["update_norm_gap"]["value"] < 1e-4
    assert list(res)[-1] == "compared"
    assert res["attempted"] == 12 and res["failed"] == 0   # one window epoch
    assert res["compared"]["window_steps_lost"] == {"value": 0, "limit": 0}
    assert set(res["metrics"]) == {"train_examples_per_s", "setup_s"}


def _break(monkeypatch, how):
    build = D.build_trainer

    def broken(cfg, devices):
        trainer = build(cfg, devices)
        inner = trainer.train_step
        if how == "frozen":     # a step that returns its state unchanged
            trainer.train_step = lambda state, *a: (state, *inner(state, *a)[1:])
        elif how == "half_batch":   # half the batch left out, mean over the rest
            trainer.train_step = lambda state, batch, *a: inner(
                state, {k: v[: v.shape[0] // 2] for k, v in batch.items()}, *a)
        return trainer

    monkeypatch.setattr(D, "build_trainer", broken)


@pytest.mark.parametrize("how", ["frozen", "half_batch"])
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_step_is_not_correct(run_tiny, monkeypatch, workload, how):
    _break(monkeypatch, how)
    res = run_tiny(workload)
    assert res["correct"] is False
    over = [k for k, v in res["compared"].items() if v["value"] > v["limit"]]
    assert set(over) - {"window_steps_lost"}, res["compared"]
    if how == "frozen":      # the window's own count sees it too
        assert res["failed"] == res["attempted"] and "window_steps_lost" in over


@pytest.mark.parametrize("workload", CELLS)
def test_the_bfloat16_control_is_not_correct(resolve, workload):
    from benchmarks.tools import readings

    _, config, traffic = resolve(workload)
    columns, batch = config["columns"], 512
    dim = int(config["program"]["embed_dim"])
    shapes = readings.dense_shapes(config)
    for seed in (3, 2**31 + 5):
        rows = traffic_lib.draw_rows(seed, 3 * batch, columns=columns, traffic=traffic)
        feed = [{k: v[i * batch:(i + 1) * batch] for k, v in rows.items()}
                for i in range(3)]
        tinfo = D.table_info(columns["categorical"], dim, seed)
        sound, *_ = D.reference_side(config, tinfo, columns["continuous"], feed, seed, shapes)
        again, *_ = D.reference_side(config, tinfo, columns["continuous"], feed, seed, shapes)
        control, *_ = D.reference_side(config, tinfo, columns["continuous"], feed, seed,
                                       shapes, compute="bfloat16")
        assert cmp.compare(again, sound, config["limits"])[0] is True
        ok, compared = cmp.compare(control, sound, config["limits"])
        assert ok is False
        assert compared["update_norm_gap"]["value"] > config["limits"]["update_norm_gap"]


def test_the_window_is_held_to_its_own_counts():
    sound = dict(due=24, fed=24, applied=24, losses=[0.69, 0.68])
    assert all(cmp.within(v) for v in D.window_numbers(sound, 0, {}).values())
    for broken in (dict(sound, fed=23, applied=23),     # a batch never fed
                   dict(sound, applied=12),             # a rollback, or a frozen step
                   dict(sound, losses=[0.69, float("nan")])):
        got = D.window_numbers(broken, 0, {})
        assert not cmp.within(got["window_steps_lost"]), broken
    assert not cmp.within(D.window_numbers(sound, 1, {})["window_compiles"])


def test_worst_leaf_rules():
    want = {"a": 1.0, "b": 1e-9, "c": 2.0}
    got = {"a": 1.1, "b": 0.5e-9, "c": 2.0}
    gaps = cmp.leaf_gaps(got, want)
    assert gaps["a"] == pytest.approx(0.1)
    assert gaps["b"] < 1e-9          # an all-but-zero leaf is held to the median leaf
    ref = {"losses": [1.0], "grad_norm": want, "update_norm": want}
    # leaf b's gradient is under a thousandth of the median: its change is not compared
    prog = {"losses": [1.0], "grad_norm": want,
            "update_norm": {"a": 1.0, "b": 7.0, "c": 2.0}}
    limits = {"grad_norm_gap": 0.01, "update_norm_gap": 0.01}
    ok, compared = cmp.compare(prog, ref, limits)
    assert ok and compared["loss1_gap"]["limit"] is None
    assert not cmp.compare({**prog, "losses": []}, ref, limits)[0]


def test_feed_rows_the_generator_never_wrote_are_counted(bench):
    rows = {"c0": np.arange(10, dtype=np.int32), "c1": np.arange(10, dtype=np.int32) % 3}
    keys = traffic_lib.row_keys(rows, ["c0", "c1"])
    assert len(np.unique(keys)) == 10
    other = traffic_lib.row_keys({"c0": rows["c0"] + 1, "c1": rows["c1"]}, ["c0", "c1"])
    assert not np.isin(other, keys).all()
