"""The sequence-model cell: it resolves from ``BENCHMARK.json`` as data, runs
tiny on a CPU device through ``drivers/lm_epoch.run`` to a correct result, the
reference's faults come out as not correct, and ``lib/work_lm.py`` counts
what a hand count gives."""

import re

import jax
import numpy as np
import pytest

from benchmarks import run
from benchmarks.drivers import lm_epoch
from benchmarks.lib import scopes, work_lm
from benchmarks.lib.monitor import CompileClock

LM_CELL = "olmo-hybrid-7b.train-packed-8k"
TINY = {
    "program": {"max_len": 96, "per_device_train_batch_size": 2,
                "lm": {"vocab_size": 64, "hidden_size": 32,
                       "intermediate_size": 48, "num_attention_heads": 4,
                       "full_heads_held": 2,
                       "linear_heads_held": 2, "linear_key_head_dim": 6,
                       "linear_value_head_dim": 12,
                       "layer_types": ["linear_attention", "full_attention"]}},
    "epoch_steps": 4,
}
# on the CPU the program computes in float32 (``compute_dtype``), so a tiny
# run is held far tighter than the chip's limits
TIGHT = {"loss1_gap": 1e-5, "loss2_gap": 1e-5, "loss3_gap": 1e-5,
         "grad_norm_gap": 5e-4, "grad_norm_gap_full_attn": 5e-4,
         "grad_norm_gap_median": 5e-4,
         "update_norm_gap": 5e-4,
         "feed_rows_unknown": 0, "window_steps_lost": 0}


@pytest.fixture(scope="module")
def lm_cell(bench):
    cell, config, traffic = run.resolve_cell(bench, LM_CELL)
    traffic = {**traffic, "documents": {**traffic["documents"], "min": 4},
               "trace_first_step": 2, "trace_steps": 2}
    return cell, {**config, "limits": TIGHT}, traffic


def go(bench, lm_cell, *, trace=False, fault=None, seed=2**31 + 5):
    cell, config, traffic = lm_cell
    readers = [m for m in run.metric_readers(bench, cell)
               if "mfu" not in m[0] and "roofline" not in m[0]]
    return lm_epoch.run(
        cell=cell, config=config, traffic=traffic, seed=seed, seconds=0.0,
        trace=trace, devices=jax.devices()[:1],
        t_process_start=run.T_PROCESS_START, clock=CompileClock(), sizes=TINY,
        metric_readers=readers if trace else None, fault=fault)


def test_the_cell_is_data_and_keeps_the_published_widths(bench):
    cell, config, traffic = run.resolve_cell(bench, LM_CELL)
    assert (cell["chips"], config["driver"]) == (1, "lm_epoch")
    source, lm = config["source_values"], config["program"]["lm"]
    changed = {k for k, v in source.items() if config[k] != v}
    assert changed == set(config["reduced"])
    widths = {"hidden_size", "intermediate_size", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim"}
    assert not widths & changed and all(lm[k] == source[k] for k in widths)
    # published head counts (they set the head size) beside the heads held
    assert lm["num_attention_heads"] == source["num_attention_heads"] == 30
    assert lm["full_heads_held"] == config["num_attention_heads"] == 15
    assert lm["linear_heads_held"] == config["linear_num_value_heads"] == 15
    assert config["layer_types"] == source["layer_types"][:4]
    assert lm["vocab_size"] * 8 == source["vocab_size"]
    assert config["program"]["nonfinite_tolerance"] == 0
    assert set(config["limits_why"]) >= set(config["limits"]) - {
        "feed_rows_unknown", "window_steps_lost"}
    assert not any(k.startswith("loss") for k in config["limits"])
    shape = dict(tokens=8192, layer_types=config["layer_types"],
                 full_heads=15, head_dim=128, linear_heads=15, dk=96, dv=192,
                 chunk=64, conv_width=4)
    assert config["work"]["interaction_flops_per_example"] == \
        work_lm.sequence_other_flops(**shape)
    names = [m[0] for m in run.metric_readers(bench, cell)]
    assert "table_update_roofline" in names and len(names) == 19
    # the table's update by its output's shape; not the head's, not the lookup's
    update = re.compile(config["trace_patterns"]["table_update"])
    tile = "{1,0:T(8,128)}"
    assert update.search(f"%fusion.570 = (f32[12544,3840]{tile}, f32[12544,3840]"
                         f"{tile}, f32[12544,3840]{tile}) fusion(%copy-done.872)")
    assert not update.search(f"%fusion.12 = (f32[3840,12544]{tile}, f32[3840,"
                             f"12544]{tile}) fusion(%convolution.3)")
    assert not update.search("%fusion = bf16[8192,3840]{1,0:T(8,128)(2,1)} "
                             "fusion(f32[12544,3840]{1,0:T(8,128)} %p)")


def test_the_cell_runs_tiny_to_a_correct_result(bench, lm_cell):
    res = go(bench, lm_cell, trace=True)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["compared"]["window_compiles"]["value"] == 0
    assert res["compared"]["grad_norm_gap"]["value"] < 1e-4
    # counters report off the chip; device readers find no device plane
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert "deltanet_scan_device_ms" not in res["metrics"]
    assert "step_device_ms" not in res["metrics"]
    assert len(res["leaf_gaps"]["grad_norm"]) == 32   # 31 dense leaves and the table


@pytest.mark.parametrize("fault", ["bf16_state", "no_resets", "bf16_params"])
def test_a_fault_of_the_reference_is_not_correct(bench, lm_cell, fault):
    res = go(bench, lm_cell, fault=fault)
    assert res["correct"] is False
    over = [k for k, v in res["compared"].items() if v["value"] > v["limit"]]
    assert over and set(over) <= {"loss1_gap", "loss2_gap", "loss3_gap",
                                  "grad_norm_gap", "grad_norm_gap_full_attn",
                                  "grad_norm_gap_median",
                                  "update_norm_gap"}, over


def test_sequences_and_weights_are_functions_of_the_seed(bench):
    _, config, traffic = run.resolve_cell(bench, LM_CELL)
    a = lm_epoch.draw_sequences(2**31 + 9, 4, 8192, 12544, traffic)
    b = lm_epoch.draw_sequences(2**31 + 9, 4, 8192, 12544, traffic)
    c = lm_epoch.draw_sequences(5, 4, 8192, 12544, traffic)
    assert all((x == y).all() for x, y in zip(a, b)) and (a[0] != c[0]).any()
    token, segment = a
    assert token.dtype == segment.dtype == np.int32 and token.shape == (4, 8192)
    assert 0 <= token.min() and token.max() < 12544
    assert (segment[:, 0] == 0).all() and (np.diff(segment, axis=1) >= 0).all()
    docs = segment.max(axis=1) + 1
    assert 1 <= docs.min() and docs.mean() < 12
    assert len(set(lm_epoch.row_keys(token, segment))) == 4
    # one compiled program serves every seed: the key is an argument
    make = lm_epoch.jit_leaf_values()
    w1 = make(lm_epoch.leaf_key(1, "layer_0/mlp/up"), (64, 48), "proj")
    traced = make._cache_size()
    w2 = make(lm_epoch.leaf_key(2, "layer_0/mlp/up"), (64, 48), "proj")
    assert make._cache_size() == traced and float(abs(w1 - w2).max()) > 0
    assert abs(float(w1.std()) - 0.02) < 2e-3
    norm = make(lm_epoch.leaf_key(1, "final_norm"), (512,), "norm")
    assert 0.9 <= float(norm.min()) and float(norm.max()) <= 1.1
    a_log = make(lm_epoch.leaf_key(1, "layer_0/mixer/A_log"), (64,), "A_log")
    assert 0.0 <= float(a_log.min()) and float(a_log.max()) <= np.log(16.0)
    assert lm_epoch.leaf_kind("layer_3/mixer/q_norm") == "norm"
    assert lm_epoch.leaf_kind("layer_0/mixer/conv_k") == "conv"


def test_work_lm_against_hand_counts():
    # one head, one chunk of 4 tokens, dk = 2, dv = 3, forward:
    # K K^T and Q K^T 2*16*2 each (128 with W = T K), U 2*16*3, attn V' 2*16*3
    # -> 2 * 16 * (3*2 + 2*3) = 384; the solve 4^3 = 64; W S, Q S, K^T V'
    # 3 * 2*4*2*3 = 144
    assert work_lm.delta_chunk_flops(4, 2, 3) == 384 + 64 + 144
    assert work_lm.delta_rule_flops(8, 5, 2, 3, 4) == 3 * 2 * 5 * 592
    assert work_lm.delta_rule_flops(9, 5, 2, 3, 4) == 3 * 3 * 5 * 592
    # causal attention, T = 4, 2 heads of 3: q k^T and p v over half the square
    assert work_lm.attention_flops(4, 2, 3) == 3 * (2 * 2 * 16 * 3 * 2) / 2
    assert work_lm.conv_flops(10, 7, 4) == 3 * 2 * 10 * 7 * 4
    shapes = {"layer_0/mlp/up": (8, 16), "layer_0/mixer/conv_q": (4, 6),
              "final_norm": (8,), "head": (8, 32)}
    assert work_lm.dense_kernel_shapes(shapes, 10) == [(80, 32), (80, 16)]
    assert work_lm.delta_rule_bytes(8, 5, 2, 3) == 3 * 8 * 5 * 10 * 2 + 3 * 8 * 5 * 8
    # the cell's numbers, as ISSUE 31 reckons them: 0.77 and about 0.2 TFLOP
    assert round(work_lm.attention_flops(8192, 15, 128) / 1e12, 2) == 0.77
    assert round(3 * work_lm.delta_rule_flops(8192, 15, 96, 192, 64) / 1e12, 2) == 0.22


def test_scope_times_take_the_innermost_scope_once(tmp_path, monkeypatch):
    """``lib/scopes.py`` over a synthetic plane: exclusive times, the
    innermost of the asked-for scopes, per executed step."""
    meta = {1: ("step", {}), 2: ("%while", {"tf_op": "jit(step)/dense_fwd_bwd/deltanet_scan/while"}),
            3: ("%dot", {"tf_op": "jit(step)/dense_fwd_bwd/transpose(jvp(deltanet_scan))/while/body/dot"}),
            4: ("%exp", {"tf_op": "jit(step)/dense_fwd_bwd/full_attn/checkpoint/exp"}),
            5: ("%copy", {})}
    lines = {"XLA Modules": [(1, 0, 100), (1, 200, 100)],
             "XLA Ops": [(2, 0, 60), (3, 10, 20), (4, 60, 30), (5, 90, 10),
                         (2, 200, 60), (3, 210, 20), (4, 260, 30)]}
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(scopes.scope_dump, "planes",
                        lambda path: [("/host:CPU", {}, {}),
                                      ("/device:TPU:0", meta, lines)])
    got = scopes.scope_ms(tmp_path, ("deltanet_scan", "full_attn", "dense_fwd_bwd"))
    assert got == {"deltanet_scan": 60e-9, "full_attn": 30e-9}
    assert scopes.scope_ms(tmp_path / "none", ("x",)) is None
