"""The ``deepseek_v3`` cell: it resolves from ``BENCHMARK.json`` as data,
keeps the published widths and the catalog's numbers, its parameters are
those the configuration reckons, it runs tiny on a CPU device through
``drivers/deepseek_v3_epoch.run`` to a correct result, every fault of the
reference fails it, and ``lib/work_deepseek.py`` counts what the mask
admits as the program's counter does."""

import json
import os

import jax
import numpy as np
import pytest

from benchmarks import run
from benchmarks.drivers import deepseek_v3_epoch, lm_epoch
from benchmarks.lib import work_deepseek
from benchmarks.lib.monitor import CompileClock
from benchmarks.reference import deepseek_v3 as R

CELL = "moonlight-16b-a3b.train-packed-8k"
# the model-configs catalog (architectures.jsonl), where the environment
# names it
CATALOG = os.environ.get("MODEL_CONFIGS_CATALOG", "")
TINY = {
    "program": {"max_len": 64, "per_device_train_batch_size": 2,
                "lm": {"vocab_size": 64, "hidden_size": 64,
                       "num_hidden_layers": 3, "num_attention_heads": 4,
                       "kv_lora_rank": 16, "qk_nope_head_dim": 16,
                       "qk_rope_head_dim": 8, "v_head_dim": 16,
                       "intermediate_size": 96, "n_routed_experts": 8,
                       "num_experts_per_tok": 2, "moe_intermediate_size": 24,
                       "experts_held": 4, "first_expert_held": 2}},
    "epoch_steps": 4,
}
# on the CPU the program computes in float32, so a tiny run is held far
# tighter than the chip's limits
GAPS = {"loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
        "grad_norm_gap_attn", "grad_norm_gap_experts", "grad_norm_gap_median",
        "update_norm_gap"}
TIGHT = {**dict.fromkeys(GAPS, 5e-5), "attn_pairs_uncounted": 0,
         "moe_pairs_uncomputed": 0, "feed_rows_unknown": 0,
         "window_steps_lost": 0}


@pytest.fixture(scope="module")
def tiny(bench):
    """One traced tiny run, each fault of the reference handed to a further
    run of it over the same recorded steps."""
    cell, config, traffic = run.resolve_cell(bench, CELL)
    traffic = {**traffic, "documents": {**traffic["documents"], "min": 4},
               "trace_first_step": 2, "trace_steps": 2}
    readers = [m for m in run.metric_readers(bench, cell)
               if "mfu" not in m[0] and "roofline" not in m[0]]
    return deepseek_v3_epoch.run(
        cell=cell, config={**config, "limits": TIGHT}, traffic=traffic,
        seed=2**31 + 7, seconds=0.0, trace=True, devices=jax.devices()[:1],
        t_process_start=run.T_PROCESS_START, clock=CompileClock(), sizes=TINY,
        metric_readers=readers, faults=R.FAULTS[1:])


def test_the_cell_is_data_and_keeps_the_published_widths(bench):
    cell, config, traffic = run.resolve_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], config["driver"]) == (
        1, "train-packed-8k", "deepseek_v3_epoch")
    source, lm = config["source_values"], config["program"]["lm"]
    changed = {k for k, v in source.items() if config[k] != v}
    assert changed == set(config["reduced"]) == set(config["published"])
    assert all(config["published"][k] == source[k] for k in changed)
    widths = {"hidden_size", "intermediate_size", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "moe_intermediate_size", "num_experts_per_tok",
              "num_attention_heads", "n_shared_experts"}
    assert not widths & changed
    assert all(lm[k] == source[k] for k in widths)
    assert lm["n_routed_experts"] == source["n_routed_experts"] == 64
    assert lm["experts_held"] == config["n_routed_experts"] == 8
    assert lm["vocab_size"] * 8 == source["vocab_size"] == 163840
    assert lm["first_k_dense_replace"] == source["first_k_dense_replace"] == 1
    assert lm["q_lora_rank"] is source["q_lora_rank"] is None
    assert config["program"]["nonfinite_tolerance"] == 0
    assert config["program"]["fused_table_threshold"] > lm["vocab_size"]
    for key, held in deepseek_v3_epoch.HELD_KEYS.items():
        assert config[key] == lm[held], key
    assert set(config["limits_why"]) >= set(config["limits"]) - {
        "feed_rows_unknown", "window_steps_lost", "moe_pairs_uncomputed",
        "attn_pairs_uncounted"}
    assert config["work"]["interaction_flops_per_example"] == \
        work_deepseek.sequence_other_flops(
            pairs=work_deepseek.mean_admitted_pairs(traffic, 8192, 20480),
            layers=6, expert_layers=5, heads=16, qk_head_dim=192,
            v_head_dim=128, positions=8192, hidden=2048, experts_per_token=6,
            experts_held=8, routed_experts=64, expert_width=1408)
    names = [m[0] for m in run.metric_readers(bench, cell)]
    assert {"mla_attn_device_ms", "mla_attn_roofline", "mla_proj_device_ms",
            "table_update_roofline", "train_step_mfu_pct"} <= set(names)
    assert not {"moe_experts_roofline", "bd_attn_device_ms"} & set(names)


def test_the_file_holds_every_number_of_the_catalog_entry(bench):
    try:
        rows = [json.loads(x) for x in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog: set MODEL_CONFIGS_CATALOG")
    _, config, _ = run.resolve_cell(bench, CELL)
    row = next(r for r in rows if r["source_url"] == config["source"])
    assert config["source_values"] == row["config"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"])


def test_the_parameters_are_those_the_configuration_reckons(bench):
    """13,763,072 of MLA a layer; the dense layer 82,973,184, an expert
    layer 100,405,760 and its 64-wide selection bias; table, head and final
    norm 83,888,128; from the program's own leaves (shapes only)."""
    from tdfo_tpu.models import deepseek_v3 as M

    _, config, _ = run.resolve_cell(bench, CELL)
    cfg = M.LmConfig(**{k: v for k, v in config["program"]["lm"].items()
                        if k != "q_lora_rank"})
    count = lambda tree: sum(int(np.prod(s)) for s in
                             lm_epoch.base._paths(tree).values())
    shapes = M.param_shapes(cfg)
    mla = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    assert count(shapes["layer_0"]["attn"]) == mla == 13_763_072
    assert count(shapes["layer_0"]) == mla + 4096 + 3 * 2048 * 11264 == 82_973_184
    assert count(shapes["layer_1"]) - 64 == 100_405_760
    total = count(shapes) + 20480 * 2048 - 5 * 64
    assert total - 82_973_184 - 5 * 100_405_760 == 83_888_128
    assert total == 668_890_112


def test_the_cell_runs_tiny_to_a_correct_result(tiny):
    res = tiny
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["compared"]["attn_pairs_uncounted"] == {"value": 0, "limit": 0}
    assert res["compared"]["moe_pairs_uncomputed"] == {"value": 0, "limit": 0}
    assert res["compared"]["grad_norm_gap"]["value"] < 1e-4
    assert res["observed"]["moe_pairs_agree"] == 1.0
    # device readers find no device plane off the chip
    assert not {"mla_attn_device_ms", "mla_proj_device_ms",
                "step_device_ms"} & set(res["metrics"])
    assert res["metrics"]["compile_s"]["value"] > 0
    # the dense layer's 5 MLA leaves, 2 norms and 3 MLP leaves; two expert
    # layers' 5 + 2 and router, bias, w1, w2 and 3 shared; final norm,
    # head, table
    assert len(res["leaf_gaps"]["grad_norm"]) == 10 + 2 * 14 + 3


@pytest.mark.parametrize("fault", R.FAULTS[1:])
def test_every_fault_of_the_reference_fails_a_limit(tiny, fault):
    reading = tiny["fault_readings"][fault]
    over = {k for k, v in reading.items() if k in TIGHT and v > TIGHT[k]}
    assert over and over <= GAPS, over


def test_admitted_pairs_are_the_mask_counted():
    rng = np.random.default_rng(0)
    segment = np.cumsum(rng.random((2, 50)) < 0.1, axis=1)
    assert work_deepseek.admitted_pairs(segment) == int(R.admitted_pairs(segment))
    assert work_deepseek.admitted_pairs(np.zeros(8, int)) == 36
    assert work_deepseek.mla_attn_flops(36, 2, 24, 16) == 3 * 2 * 36 * 2 * 40
    assert work_deepseek.mla_attn_bytes(16, 2, 24, 16) == 2 * 16 * 2 * 80 * 2


def test_the_new_readers_read_nothing_where_there_is_nothing(bench):
    cell, _, _ = run.resolve_cell(bench, CELL)
    new = {"mla_attn_device_ms", "mla_attn_roofline", "mla_proj_device_ms"}
    readers = {n: r for n, _, r in run.metric_readers(bench, cell) if n in new}
    assert set(readers) == new
    for ctx in ({}, {"scope_ms": None}, {"scope_ms": {"mlp": 1.0}}):
        assert all(r(ctx) is None for r in readers.values())
    shape = dict(pairs_per_step=1e7, positions=8192, layers=6, heads=16,
                 qk_head_dim=192, v_head_dim=128)
    ctx = dict(scope_ms={"mla_attn": 100.0, "mla_proj": 20.0},
               deepseek_shape=shape, n_chips=1, device_kind="TPU v5 lite")
    assert readers["mla_attn_device_ms"](ctx) == 100.0
    assert readers["mla_proj_device_ms"](ctx) == 20.0
    # 6 layers of 3 * 2 * 1e7 * 16 * 320 FLOP at 197 TFLOP/s over 100 ms
    want = 6 * 3 * 2 * 1e7 * 16 * 320 / 197e12 / 0.1 * 100
    assert readers["mla_attn_roofline"](ctx) == pytest.approx(want, rel=1e-3)
