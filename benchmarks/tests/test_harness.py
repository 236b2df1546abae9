"""The harness itself: ``main`` demands the chip, ``BENCHMARK.json`` keeps to
the contract's shapes, the generator and the weights are functions of the
seed, and a later PR's cell is taken as data with no edit to this code."""

import json
import re
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmarks import run
from benchmarks.drivers import train_epoch as D
from benchmarks.lib import traffic as traffic_lib, weights
from benchmarks.lib.monitor import CompileClock

from bench_examples import CELL, dlrm_example

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_main_fails_on_the_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "needs a TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""     # no result line, ever


def test_unknown_workload_is_an_error():
    with pytest.raises(SystemExit):
        run.resolve_cell(run.load_benchmark(), "no-such.cell")


def test_benchmark_json_keeps_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = {w["name"] for w in bench["workloads"]}
    assert len(cells) == len(bench["workloads"])
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert (run.ROOT / bench["paths"][0] / "metrics" / f"{m['name']}.py").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and len(c["why"]) <= 200 and len(c["source"]) <= 200
        body = json.loads((run.ROOT / c["file"]).read_text())
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] == sorted(body["reduced_why"])
        assert "base_toml" not in body      # every deployment key is stated here
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
        run.resolve_cell(bench, w["name"])
        assert {m[0] for m in run.metric_readers(bench, w)} == {
            m["name"] for m in bench["per_layer"]
            if w["name"] in m.get("workloads", [w["name"]])}
    mfu = [m for m in bench["per_layer"] if "mfu" in re.split(r"[_.]", m["name"])]
    roof = [m for m in bench["per_layer"] if m["name"].endswith("_roofline")]
    assert mfu and roof and all(m["moves"] == mfu[0]["moves"] for m in roof)


def test_the_same_seed_gives_the_same_rows_and_every_seed_the_same_sizes(bench):
    _, config, traffic = run.resolve_cell(bench, CELL)
    a = traffic_lib.draw_rows(2**31 + 99, 4096, columns=config["columns"], traffic=traffic)
    b = traffic_lib.draw_rows(2**31 + 99, 4096, columns=config["columns"], traffic=traffic)
    c = traffic_lib.draw_rows(5, 4096, columns=config["columns"], traffic=traffic)
    assert all((a[k] == b[k]).all() for k in a)
    assert {k: (v.shape, v.dtype) for k, v in a.items()} == \
        {k: (v.shape, v.dtype) for k, v in c.items()}
    assert any((a[k] != c[k]).any() for k in a)
    for col, vocab in config["columns"]["categorical"].items():
        assert a[col].dtype == np.int32 and 0 <= a[col].min() and a[col].max() < vocab
    assert a["label"].dtype == np.int8 and 0.45 < a["label"].mean() < 0.55


def test_zipf_ids_are_skewed_and_in_range():
    rng = np.random.default_rng(0)
    ids = traffic_lib.draw_ids(rng, 50_000, 1_000_000,
                               {"distribution": "zipf", "exponent": 1.1})
    assert ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < 1_000_000
    _, counts = np.unique(ids, return_counts=True)
    uniform = traffic_lib.draw_ids(rng, 50_000, 1_000_000, {"distribution": "uniform"})
    assert counts.max() > 1000 and len(counts) < 0.8 * len(np.unique(uniform))
    with pytest.raises(ValueError):
        traffic_lib.draw_ids(rng, 1, 10, {"distribution": "pareto"})


def test_weights_are_the_same_function_on_host_and_device():
    import jax.numpy as jnp

    key = weights.table_key(2**31 + 7, "cat_2")
    rows = np.array([0, 1, 12345, 10_131_226, 2**31 - 1])
    a = weights.embedding_rows(np, key, rows, 16, 7.7e-4)
    b = np.asarray(weights.embedding_rows(jnp, key, jnp.asarray(rows), 16, 7.7e-4))
    assert a.dtype == np.float32 and (a == b).all()
    assert np.abs(a).max() <= 7.7e-4 and a.std() > 0.4 * 7.7e-4
    assert weights.table_key(1, "cat_2") != weights.table_key(2, "cat_2")
    whole = weights.embedding_rows(np, key, np.arange(4096), 16, 1.0)
    assert abs(whole.mean()) < 0.02 and abs(whole.std() - 3 ** -0.5) < 0.02
    d = weights.dense_params(3, {"a/kernel": (13, 64), "a/bias": (64,)})
    assert d["a/bias"].sum() == 0 and np.abs(d["a/kernel"]).max() <= (6 / 77) ** 0.5


def test_a_later_pr_adds_a_cell_as_data(tmp_path, bench):
    """Another model of the family with a zipf traffic mix and a per-layer
    metric of its own, a ``chips: 4`` deployment with a mesh, a ``bert4rec``
    configuration: new files and new entries only.  The code as it stands
    loads them all and RUNS the first (tiny, on the CPU) to a correct result
    with its per-layer metrics."""
    root = tmp_path
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(run.ROOT / "benchmarks" / sub, root / "benchmarks" / sub)
    base = dlrm_example()
    x4 = {**base, "name": "dlrm-example-x4", "chips": 4,
          "program": {**base["program"], "lookup_mode": "alltoall",
                      "dedup_lookup": False, "mesh": {"data": 1, "model": 4},
                      "embeddings": {"grouped_a2a": True}}}
    seq = {"name": "bert4rec-goodreads", "source": "https://arxiv.org/abs/1904.06690",
           "driver": "train_epoch", "program": {"model": "bert4rec"}, "reduced": [],
           "reference": {"module": "bert4rec"}}
    for c in (base, x4, seq):
        (root / f"benchmarks/configs/{c['name']}.json").write_text(json.dumps(c))
    (root / "benchmarks/traffic/train-zipf.json").write_text(json.dumps(
        {"name": "train-zipf", "ids": {"distribution": "zipf", "exponent": 1.05,
                                       "per_column": {"cat_8": {"distribution": "uniform"}}},
         "label": {"kind": "bernoulli", "rate": 0.25}, "files": 4, "epoch_steps": 12}))
    (root / "benchmarks/metrics/lookups_per_distinct_row.py").write_text(
        "def read(ctx):\n"
        "    return ctx['batch'] * ctx['n_columns'] / ctx['unique_rows_per_step']\n")
    more = json.loads(json.dumps(bench))
    more["configs"] += [
        {"name": c["name"], "source": c["source"], "reduced": [], "why": "x",
         "file": f"benchmarks/configs/{c['name']}.json"} for c in (base, x4, seq)]
    more["workloads"] += [
        {"name": "dlrm-example.train-zipf", "config": "dlrm-example",
         "traffic": "train-zipf", "chips": 1, "why": "x"},
        {"name": "dlrm-example-x4.train-zipf", "config": "dlrm-example-x4",
         "traffic": "train-zipf", "chips": 4, "why": "x"},
        {"name": "bert4rec-goodreads.train", "config": "bert4rec-goodreads",
         "traffic": "train-uniform", "chips": 1, "why": "x"}]
    more["per_layer"].append(
        {"name": "lookups_per_distinct_row", "unit": "x", "better": "higher",
         "source": "program_counter", "layer": "input",
         "moves": "train_examples_per_s",
         "workloads": ["dlrm-example.train-zipf", "dlrm-example-x4.train-zipf"]})

    cell, config, traffic = run.resolve_cell(more, "dlrm-example.train-zipf", root)
    rows = traffic_lib.draw_rows(1, 20_000, columns=config["columns"], traffic=traffic)
    assert len(np.unique(rows["cat_2"])) < len(np.unique(rows["cat_8"])) * 20_000 / 3
    assert 0.2 < rows["label"].mean() < 0.3
    readers = run.metric_readers(more, cell, root)
    go = lambda readers: D.run(
        cell=cell, config=config, traffic=traffic, seed=2**31 + 3, seconds=0.0,
        trace=True, devices=jax.devices()[:1], t_process_start=run.T_PROCESS_START,
        clock=CompileClock(), metric_readers=readers)
    with pytest.raises(SystemExit, match="no peaks for device_kind 'cpu'"):
        go(readers)     # a share of a peak never comes out of a CPU run
    res = go([m for m in readers if "mfu" not in m[0]])
    assert res["correct"] is True, res["compared"]
    # no device trace on the CPU: the readers that need one return nothing
    # and are left out; those that read counters report
    assert res["metrics"]["lookups_per_distinct_row"]["value"] > 1.0
    assert "step_device_ms" not in res["metrics"]

    cell, config, traffic = run.resolve_cell(more, "dlrm-example-x4.train-zipf", root)
    assert cell["chips"] == 4
    cfg = D.build_config(config, data_dir=tmp_path / "d", out_dir=tmp_path / "o",
                         seed=2**31 + 1, on_tpu=False)
    assert (cfg.mesh.model, cfg.lookup_mode, cfg.embeddings.grouped_a2a) == (4, "alltoall", True)
    assert 0 <= cfg.seed < 2**31

    cell, config, traffic = run.resolve_cell(more, "bert4rec-goodreads.train", root)
    assert config["driver"] == "train_epoch"
    cfg = D.build_config(config, data_dir=tmp_path / "d", out_dir=tmp_path / "o",
                         seed=3, on_tpu=False)
    assert cfg.model == "bert4rec"
    assert "lookups_per_distinct_row" not in [m[0] for m in run.metric_readers(more, cell, root)]
