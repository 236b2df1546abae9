"""The ``sdar_moe`` cell: it resolves from ``BENCHMARK.json`` as data, keeps
the published widths and the catalog's numbers, its parameters are those the
issue reckons, it runs tiny on a CPU device through ``drivers/sdar_epoch.run``
to a correct result, the reference's faults of the stream fail it, and
``lib/work_sdar.py`` counts what the mask admits."""

import json

import jax
import numpy as np
import pytest

from benchmarks import run
from benchmarks.drivers import lm_epoch, sdar_epoch
from benchmarks.lib import work_sdar
from benchmarks.lib.monitor import CompileClock

CELL = "sdar-30b-a3b-chat.train-packed-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = {
    "program": {"max_len": 64, "per_device_train_batch_size": 2,
                "lm": {"vocab_size": 64, "hidden_size": 64,
                       "num_hidden_layers": 2, "num_attention_heads": 4,
                       "num_key_value_heads": 2, "head_dim": 16,
                       "num_experts": 8, "num_experts_per_tok": 2,
                       "moe_intermediate_size": 24, "experts_held": 4,
                       "first_expert_held": 2}},
    "epoch_steps": 4,
}
# on the CPU the program computes in float32, so a tiny run is held far
# tighter than the chip's limits
GAPS = {"loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
        "grad_norm_gap_attn", "grad_norm_gap_experts", "grad_norm_gap_median",
        "update_norm_gap"}
TIGHT = {**dict.fromkeys(GAPS, 5e-5), "bd_masked_uncounted": 0,
         "moe_pairs_uncomputed": 0, "feed_rows_unknown": 0,
         "window_steps_lost": 0}


@pytest.fixture(scope="module")
def cell(bench):
    cell, config, traffic = run.resolve_cell(bench, CELL)
    traffic = {**traffic, "documents": {**traffic["documents"], "min": 4},
               "trace_first_step": 2, "trace_steps": 2}
    return cell, {**config, "limits": TIGHT}, traffic


STREAM_FAULTS = ("leak_own_block", "shifted_noisy_positions",
                 "causal_in_block", "no_t_weight")


@pytest.fixture(scope="module")
def tiny(bench, cell):
    """One traced tiny run, each fault of the stream handed to a further
    run of the reference over the same recorded steps."""
    cell_, config, traffic = cell
    readers = [m for m in run.metric_readers(bench, cell_)
               if "mfu" not in m[0] and "roofline" not in m[0]]
    return sdar_epoch.run(
        cell=cell_, config=config, traffic=traffic, seed=2**31 + 5,
        seconds=0.0, trace=True, devices=jax.devices()[:1],
        t_process_start=run.T_PROCESS_START, clock=CompileClock(), sizes=TINY,
        metric_readers=readers, faults=STREAM_FAULTS)


def test_the_cell_is_data_and_keeps_the_published_widths(bench):
    cell, config, traffic = run.resolve_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], config["driver"]) == (
        1, "train-packed-8k", "sdar_epoch")
    source, lm = config["source_values"], config["program"]["lm"]
    changed = {k for k, v in source.items() if config[k] != v}
    assert changed == set(config["reduced"]) == set(config["published"])
    assert all(config["published"][k] == source[k] for k in changed)
    widths = {"hidden_size", "head_dim", "moe_intermediate_size",
              "intermediate_size", "num_experts_per_tok", "num_attention_heads",
              "num_key_value_heads"}
    assert not widths & changed
    assert all(lm[k] == source[k] for k in widths & set(lm))
    assert lm["num_experts"] == source["num_experts"] == 128
    assert lm["experts_held"] == config["num_experts"] == 16
    assert lm["vocab_size"] * 8 == source["vocab_size"] == 151936
    assert source["num_hidden_layers"] % lm["num_hidden_layers"] == 0
    assert lm["rope_theta"] == source["rope_theta"]
    assert config["program"]["nonfinite_tolerance"] == 0
    assert config["program"]["fused_table_threshold"] > lm["vocab_size"]
    for key, held in sdar_epoch.HELD_KEYS.items():
        assert config[key] == lm[held], key
    assert set(config["limits_why"]) >= set(config["limits"]) - {
        "feed_rows_unknown", "window_steps_lost", "moe_pairs_uncomputed",
        "bd_masked_uncounted"}
    assert config["work"]["interaction_flops_per_example"] == \
        work_sdar.sequence_other_flops(
            pairs=work_sdar.mean_admitted_pairs(traffic, 8192, 18991, 4),
            layers=lm["num_hidden_layers"], heads=32, head_dim=128,
            positions=16384, hidden=2048, experts_per_token=8,
            experts_held=16, routed_experts=128, expert_width=768)
    names = [m[0] for m in run.metric_readers(bench, cell)]
    assert {"bd_attn_device_ms", "bd_attn_roofline", "table_update_roofline",
            "train_step_mfu_pct"} <= set(names)
    assert not {"moe_experts_roofline", "full_attn_device_ms"} & set(names)


def test_the_file_holds_every_number_of_the_catalog_entry(bench):
    try:
        rows = [json.loads(x) for x in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog on this machine")
    _, config, _ = run.resolve_cell(bench, CELL)
    row = next(r for r in rows if r["source_url"] == config["source"])
    assert config["source_values"] == row["config"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"])


def test_the_parameters_are_those_the_issue_reckons(bench):
    """94,638,336 a layer; table, head and final norm 77,793,280; from the
    program's own leaves (shapes only: nothing is made)."""
    from tdfo_tpu.models import sdar_moe as M

    _, config, _ = run.resolve_cell(bench, CELL)
    cfg = M.LmConfig(**config["program"]["lm"])
    count = lambda tree: sum(int(np.prod(s)) for s in
                             lm_epoch.base._paths(tree).values())
    shapes = M.param_shapes(cfg)
    attn = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 128
    moe = 2048 * 128 + 2 * 2048 + 16 * 3 * 2048 * 768
    assert (attn, moe) == (18_874_624, 266_240 + 75_497_472)
    assert count(shapes["layer_0"]) == attn + moe == 94_638_336
    layers = cfg.num_hidden_layers
    total = count(shapes) + 18992 * 2048
    assert total - layers * 94_638_336 == 77_793_280
    assert total == {6: 645_623_296, 4: 456_346_624}[layers]


def test_the_cell_runs_tiny_to_a_correct_result(tiny):
    res = tiny
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["compared"]["bd_masked_uncounted"] == {"value": 0, "limit": 0}
    assert res["compared"]["moe_pairs_uncomputed"] == {"value": 0, "limit": 0}
    assert res["compared"]["grad_norm_gap"]["value"] < 1e-4
    assert res["observed"]["moe_pairs_agree"] == 1.0
    assert res["observed"]["bd_masked_tokens_per_step"] > 0
    # device readers find no device plane off the chip
    assert not {"bd_attn_device_ms", "step_device_ms"} & set(res["metrics"])
    assert res["metrics"]["compile_s"]["value"] > 0
    # 2 layers of 6 attention and 3 expert leaves and two norms, final norm,
    # head, table
    assert len(res["leaf_gaps"]["grad_norm"]) == 2 * 11 + 3


@pytest.mark.parametrize("fault", STREAM_FAULTS)
def test_a_fault_of_the_stream_fails_the_attention_limit(tiny, fault):
    reading = tiny["fault_readings"][fault]
    over = {k for k, v in reading.items() if k in TIGHT and v > TIGHT[k]}
    assert "grad_norm_gap_attn" in over and over <= GAPS, over


def test_admitted_pairs_are_the_rule_counted():
    from benchmarks.reference.sdar_moe import blocks, visible

    rng = np.random.default_rng(0)
    segment = np.cumsum(rng.random((2, 50)) < 0.1, axis=1)
    n = 100
    i, j = np.arange(n)[:, None], np.arange(n)[None, :]
    want = 0
    for row in segment:
        seg = np.concatenate([row, row])
        blk = np.tile(np.asarray(blocks(row, 4)), 2)
        noisy = np.arange(n) < 50
        ok = np.asarray(visible(i, j, noisy[i], noisy[j], blk[i], blk[j], None))
        want += int((ok & (seg[i] == seg[j])).sum())
    assert work_sdar.admitted_pairs(segment, 4) == want
    # one document of 8 tokens in blocks of 4: noisy 16 + 16, noisy to
    # clean 0 + 16, clean 16 + 32
    assert work_sdar.admitted_pairs(np.zeros(8, int), 4) == 96
    assert work_sdar.bd_attn_flops(96, 2, 8) == 3 * 96 * 4 * 8 * 2
    assert work_sdar.bd_attn_bytes(16, 4, 2, 8) == 2 * 16 * 12 * 8 * 2
    assert work_sdar.expert_flops(10, 4, 7) == 3 * 10 * 2 * 3 * 4 * 7
    assert work_sdar.expected_pairs(16384, 8, 16, 128) == 16384


def test_the_new_readers_read_nothing_where_there_is_nothing(bench):
    cell, _, _ = run.resolve_cell(bench, CELL)
    new = {"bd_attn_device_ms", "bd_attn_roofline"}
    readers = {n: r for n, _, r in run.metric_readers(bench, cell) if n in new}
    assert set(readers) == new
    for ctx in ({}, {"scope_ms": None}, {"scope_ms": {"mlp": 1.0}}):
        assert all(r(ctx) is None for r in readers.values())
    shape = dict(pairs_per_step=1e8, positions=16384, layers=4, heads=32,
                 key_value_heads=4, head_dim=128)
    ctx = dict(scope_ms={"bd_attn": 100.0}, sdar_shape=shape, n_chips=1,
               device_kind="TPU v5 lite")
    assert readers["bd_attn_device_ms"](ctx) == 100.0
    # 4 layers of 3 * 1e8 * 4 * 128 * 32 FLOP at 197 TFLOP/s over 100 ms
    want = 4 * 3 * 1e8 * 4 * 128 * 32 / 197e12 / 0.1 * 100
    assert readers["bd_attn_roofline"](ctx) == pytest.approx(want, rel=1e-3)
