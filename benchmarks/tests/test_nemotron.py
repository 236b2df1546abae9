"""The ``nemotron_h`` cell: it resolves from ``BENCHMARK.json`` as data, keeps
the published widths, runs tiny on a CPU device through
``drivers/nemotron_epoch.run`` to a correct result, each fault of the
reference fails the limit the configuration says it fails, and
``lib/work_nemotron.py`` counts what a hand count gives."""

import copy
import json
import re

import jax
import numpy as np
import pytest

from benchmarks import run
from benchmarks.drivers import lm_epoch, nemotron_epoch
from benchmarks.lib import work_nemotron
from benchmarks.lib.monitor import CompileClock

CELL = "nemotron-3-super-120b-a12b.train-packed-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = {
    "program": {"max_len": 96, "per_device_train_batch_size": 2,
                "lm": {"vocab_size": 64, "hidden_size": 32,
                       "hybrid_override_pattern": "ME*E",
                       "mamba_num_heads": 8, "mamba_head_dim": 4,
                       "ssm_state_size": 6, "n_groups": 4,
                       "num_attention_heads": 8, "num_key_value_heads": 2,
                       "head_dim": 8, "n_routed_experts": 16,
                       "num_experts_per_tok": 3, "moe_latent_size": 16,
                       "moe_intermediate_size": 24,
                       "moe_shared_expert_intermediate_size": 40,
                       "mamba_heads_held": 4, "attention_heads_held": 2,
                       "experts_held": 4, "first_expert_held": 4}},
    "epoch_steps": 4,
}
# on the CPU the program computes in float32 (``compute_dtype``), so a tiny
# run is held far tighter than the chip's limits
TIGHT = {"loss1_gap": 1e-5, "loss2_gap": 1e-5, "loss3_gap": 1e-5,
         "grad_norm_gap": 5e-5, "grad_norm_gap_attn": 5e-5,
         "grad_norm_gap_experts": 5e-5, "grad_norm_gap_median": 5e-5,
         "update_norm_gap": 5e-5, "moe_pairs_uncomputed": 0,
         "feed_rows_unknown": 0, "window_steps_lost": 0}
GAPS = {"loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
        "grad_norm_gap_attn", "grad_norm_gap_experts", "grad_norm_gap_median",
        "update_norm_gap"}


@pytest.fixture(scope="module")
def cell(bench):
    cell, config, traffic = run.resolve_cell(bench, CELL)
    traffic = {**traffic, "documents": {**traffic["documents"], "min": 4},
               "trace_first_step": 2, "trace_steps": 2}
    return cell, {**config, "limits": TIGHT}, traffic


def go(bench, cell, *, trace=False, fault=None, seed=2**31 + 5):
    cell_, config, traffic = cell
    readers = [m for m in run.metric_readers(bench, cell_)
               if "mfu" not in m[0] and "roofline" not in m[0]]
    return nemotron_epoch.run(
        cell=cell_, config=config, traffic=traffic, seed=seed, seconds=0.0,
        trace=trace, devices=jax.devices()[:1],
        t_process_start=run.T_PROCESS_START, clock=CompileClock(), sizes=TINY,
        metric_readers=readers if trace else None, fault=fault)


def test_the_cell_is_data_and_keeps_the_published_widths(bench):
    cell, config, traffic = run.resolve_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], config["driver"]) == (
        1, "train-packed-8k", "nemotron_epoch")
    source, lm = config["source_values"], config["program"]["lm"]
    changed = {k for k, v in source.items() if config[k] != v}
    assert changed == set(config["reduced"]) == set(config["published"])
    assert all(config["published"][k] == source[k] for k in changed)
    # no width is cut: what `reduced` may never name
    widths = {"hidden_size", "mamba_head_dim", "ssm_state_size", "head_dim",
              "moe_latent_size", "moe_intermediate_size", "expand",
              "moe_shared_expert_intermediate_size", "num_experts_per_tok",
              "intermediate_size", "conv_kernel", "chunk_size"}
    assert not widths & changed
    assert all(lm[k] == source[k] for k in widths & set(lm))
    # published counts (they set group and head sizes) beside what is held
    for key, held in (("mamba_num_heads", "mamba_heads_held"),
                      ("num_attention_heads", "attention_heads_held"),
                      ("n_routed_experts", "experts_held")):
        assert lm[key] == source[key] and lm[held] == config[key]
    assert lm["n_groups"] == source["n_groups"] == 8 and config["n_groups"] == 2
    assert lm["num_key_value_heads"] == source["num_key_value_heads"] == 2
    assert config["hybrid_override_pattern"] == \
        source["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    full = source["hybrid_override_pattern"]
    assert [full.count(k) for k in "ME*"] == [40, 40, 8]    # 5 : 5 : 1 held
    assert lm["vocab_size"] * 8 == source["vocab_size"]
    assert config["program"]["nonfinite_tolerance"] == 0
    for key, held in nemotron_epoch.HELD_KEYS.items():
        assert config[key] == lm[held], key
    assert set(config["limits_why"]) >= set(config["limits"]) - {
        "feed_rows_unknown", "window_steps_lost", "moe_pairs_uncomputed"}
    # the loss is compared from the second step on (limits_why: loss)
    assert {k for k in config["limits"] if k.startswith("loss")} == {
        "loss2_gap", "loss3_gap"}
    from tdfo_tpu.ops.ssd import CHUNK
    assert CHUNK == source["chunk_size"]
    assert config["work"]["interaction_flops_per_example"] == \
        work_nemotron.sequence_other_flops(
            tokens=8192, pattern=config["hybrid_override_pattern"],
            attention_heads=8, head_dim=128, mamba_heads=32, mamba_groups=2,
            mamba_head_dim=64, state=128, chunk=CHUNK, conv_width=4,
            experts_per_token=22, experts_held=8, routed_experts=512,
            latent=1024, expert_width=2688)
    names = [m[0] for m in run.metric_readers(bench, cell)]
    assert len(names) == 20 and {"ssd_scan_roofline", "moe_experts_roofline",
                                 "table_update_roofline"} <= set(names)
    update = re.compile(config["trace_patterns"]["table_update"])
    tile = "{1,0:T(8,128)}"
    assert update.search(f"%fusion.570 = (f32[16384,4096]{tile}, f32[16384,4096]"
                         f"{tile}, f32[16384,4096]{tile}) fusion(%copy-done.872)")
    assert not update.search(f"%fusion.12 = (f32[4096,16384]{tile}, f32[4096,"
                             f"16384]{tile}) fusion(%convolution.3)")


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
    """773.6 M parameters at the cell's size, from the program's own leaves
    (shapes only: nothing is made)."""
    from tdfo_tpu.models import nemotron_h as M

    _, config, _ = run.resolve_cell(bench, CELL)
    cfg = M.LmConfig(**config["program"]["lm"])
    count = lambda tree: sum(int(np.prod(s)) for s in lm_epoch.base._paths(tree).values())
    shapes = M.param_shapes(cfg)
    by_kind = {k: count(shapes[f"layer_{cfg.hybrid_override_pattern.index(k)}"])
               for k in "M*E"}
    assert by_kind == {"M": 4096 * 4640 + 2048 * 4096 + 5 * 2560 + 3 * 32 + 2048 + 4096,
                       "*": 2 * 4096 * 1024 + 2 * 4096 * 128 + 4096,
                       "E": 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
                       + 8 * 2 * 1024 * 2688 + 4096}
    total = count(shapes) + 16384 * 4096
    assert round(total / 1e6, 1) == 773.6
    assert round(total * 12 / 2**30, 2) == 8.65


def test_the_cell_runs_tiny_to_a_correct_result(bench, cell):
    res = go(bench, cell, trace=True)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["compared"]["window_compiles"]["value"] == 0
    assert res["compared"]["moe_pairs_uncomputed"] == {"value": 0, "limit": 0}
    assert res["compared"]["grad_norm_gap"]["value"] < 1e-4
    assert res["observed"]["moe_pairs_agree"] == 1.0
    assert res["observed"]["moe_load_max_over_mean"] > 1.0
    # which form each expert layer took: the program's count over all 12
    # steps, the first step's two layers as the reference routes them
    rows = res["observed"]["moe_sorted_rows"]
    by_layer = res["observed"]["moe_pairs_by_layer"]
    assert len(by_layer) == len(res["observed"]["moe_load_max_by_layer"]) == 2
    assert res["observed"]["moe_layers_dense_per_step"] == 0.0
    assert rows == 512 and max(by_layer) <= rows
    # device readers find no device plane off the chip
    assert not {"ssd_scan_device_ms", "moe_experts_device_ms",
                "moe_route_device_ms", "step_device_ms"} & set(res["metrics"])
    assert res["metrics"]["compile_s"]["value"] > 0
    # 4 layers' leaves (8 + 8 + 4 + 8 and a norm each), final norm, head, table
    assert len(res["leaf_gaps"]["grad_norm"]) == 8 + 8 + 4 + 8 + 4 + 3


@pytest.mark.parametrize("fault,fails", [
    ("drop_tokens", {"grad_norm_gap_experts"}),
    ("no_topk_norm", {"grad_norm_gap_experts"}),
    ("no_resets", {"grad_norm_gap_attn", "grad_norm_gap_median"}),
    ("bf16_state", {"update_norm_gap"}),
    ("bf16_params", {"update_norm_gap"}),
])
def test_a_fault_of_the_reference_fails_its_limit(bench, cell, fault, fails):
    res = go(bench, cell, fault=fault)
    assert res["correct"] is False
    over = {k for k, v in res["compared"].items() if v["value"] > v["limit"]}
    assert fails <= over <= GAPS, over


def test_no_topk_norm_fails_the_loss_limits_from_the_second_step(bench, cell):
    """The configuration compares ``loss2_gap`` and ``loss3_gap``: on the
    chip ``no_topk_norm`` reads 25-40 times the sound program there
    (``limits_why``).  At the tiny size the routed part is a sliver of the
    stream (3 experts a token: weights 1.5 times too large, not 11) and
    reaches the loss only once the steps are large, so this run takes them
    at a rate of 0.2: the sound program stays inside the tight limits, the
    fault passes both by more than ten times; the first loss sees neither."""
    cell_, config, traffic = cell
    reference = copy.deepcopy(config["reference"])
    for part in reference["optimizer"].values():
        part["lr"] = 0.2
    fast = (cell_, {**config, "reference": reference, "program": {
        **config["program"], "learning_rate": 0.2}}, traffic)
    loss = lambda res: {k: v["value"] for k, v in res["compared"].items()
                        if k.startswith("loss")}
    sound, faulty = loss(go(bench, fast)), loss(go(bench, fast, fault="no_topk_norm"))
    assert set(sound) == {"loss1_gap", "loss2_gap", "loss3_gap"}
    assert all(v <= TIGHT[k] for k, v in sound.items()), sound
    assert faulty["loss1_gap"] <= TIGHT["loss1_gap"]
    assert all(faulty[k] > 10 * TIGHT[k] for k in ("loss2_gap", "loss3_gap")), faulty


def test_weights_of_every_kind_are_functions_of_the_seed():
    make = jax.jit(nemotron_epoch.leaf_values, static_argnums=(1, 2))
    key = lambda path, seed=1: lm_epoch.leaf_key(seed, path)
    kinds = {p: nemotron_epoch.leaf_kind(p) for p in (
        "layer_1/part/w1", "layer_1/part/router_bias", "layer_0/part/conv_w",
        "layer_0/part/conv_bias", "layer_0/part/D", "layer_0/part/gate_norm",
        "layer_0/part/A_log", "layer_0/part/dt_bias", "layer_0/norm")}
    assert kinds == {
        "layer_1/part/w1": "proj", "layer_1/part/router_bias": "router_bias",
        "layer_0/part/conv_w": "conv", "layer_0/part/conv_bias": "conv_bias",
        "layer_0/part/D": "norm", "layer_0/part/gate_norm": "norm",
        "layer_0/part/A_log": "A_log", "layer_0/part/dt_bias": "dt_bias",
        "layer_0/norm": "norm"}
    w1 = make(key("layer_1/part/w1"), (4, 16, 24), "proj")
    assert w1.shape == (4, 16, 24) and abs(float(w1.std()) - 0.02) < 2e-3
    assert float(abs(w1[0] - w1[1]).max()) > 0     # an expert is not its neighbour
    again = make(key("layer_1/part/w1"), (4, 16, 24), "proj")
    other = make(key("layer_1/part/w1", 2), (4, 16, 24), "proj")
    assert (w1 == again).all() and float(abs(w1 - other).max()) > 0
    bias = make(key("layer_1/part/router_bias"), (512,), "router_bias")
    assert 0.09 < float(bias.max()) <= 0.1 and -0.1 <= float(bias.min()) < -0.09
    skip = make(key("layer_0/part/D"), (32,), "norm")
    assert 0.9 <= float(skip.min()) and float(skip.max()) <= 1.1


def test_work_nemotron_against_hand_counts():
    # one head of a group of 2, one chunk of 4 tokens, P = 3, N = 5, forward:
    # C B^T 2*16*5 shared by 2 heads -> 80; (CB*L) X 2*16*3 = 96; the chunk's
    # state and C S_0 2*4*3*5 each = 240
    assert work_nemotron.ssd_chunk_flops(4, 3, 5, 2) == 80 + 96 + 240
    assert work_nemotron.ssd_flops(8, 6, 3, 3, 5, 4) == 3 * 2 * 6 * 416
    assert work_nemotron.ssd_flops(9, 6, 3, 3, 5, 4) == 3 * 3 * 6 * 416
    # x and y 6*3 each, B and C 3*5 each, a token, in 2 bytes; dt in 4
    assert work_nemotron.ssd_bytes(8, 6, 3, 3, 5) == 3 * 8 * 66 * 2 + 3 * 8 * 6 * 4
    assert work_nemotron.expert_flops(10, 4, 7) == 3 * 10 * 2 * 2 * 4 * 7
    # 2 experts x 2 matrices of 4 x 7 at 2 + 2 + 4 bytes; 10 pairs' rows
    assert work_nemotron.expert_bytes(10, 2, 4, 7) == 112 * 8 + 3 * 10 * 2 * 4 * 2
    assert work_nemotron.expected_pairs(8192, 22, 8, 512) == 2816
    # the cell's numbers, as ISSUE 36 reckons them
    assert work_nemotron.expert_flops(2816, 1024, 2688) == 3 * 2816 * 2 * 2 * 1024 * 2688
    assert round(work_nemotron.ssd_flops(8192, 32, 2, 64, 128, 128) / 1e9, 1) == 40.3
    # on the v5e the scan is bound by its bytes (0.28 against 0.20 ms a
    # layer); the experts' two floors at the expected pairs are 0.472 ms both
    assert (work_nemotron.ssd_bytes(8192, 32, 2, 64, 128) / 819e9
            > 1.3 * work_nemotron.ssd_flops(8192, 32, 2, 64, 128, 128) / 197e12)
    assert (work_nemotron.expert_flops(2816, 1024, 2688) / 197e12
            == pytest.approx(work_nemotron.expert_bytes(2816, 8, 1024, 2688)
                             / 819e9, rel=2e-3))
    other = work_nemotron.sequence_other_flops(
        tokens=8, pattern="ME*", attention_heads=2, head_dim=3, mamba_heads=6,
        mamba_groups=3, mamba_head_dim=3, state=5, chunk=4, conv_width=4,
        experts_per_token=2, experts_held=4, routed_experts=16, latent=4,
        expert_width=7)
    assert other == (3 * (2 * 2 * 64 * 3 * 2) / 2 + 3 * 2 * 6 * 416
                     + 3 * 2 * 8 * (18 + 30) * 4
                     + work_nemotron.expert_flops(8 * 2 * 4 / 16, 4, 7))


def test_the_new_readers_read_nothing_where_there_is_nothing(bench):
    cell, _, _ = run.resolve_cell(bench, CELL)
    new = {"ssd_scan_device_ms", "ssd_scan_roofline", "moe_experts_device_ms",
           "moe_experts_roofline", "moe_route_device_ms"}
    readers = {n: r for n, _, r in run.metric_readers(bench, cell) if n in new}
    assert set(readers) == new
    for ctx in ({}, {"scope_ms": None}, {"scope_ms": {"mlp": 1.0}}):
        assert all(r(ctx) is None for r in readers.values())
    shape = dict(tokens=8192, chunk=128, mamba_layers=5, mamba_heads=32,
                 mamba_groups=2, mamba_head_dim=64, state=128, expert_layers=5,
                 experts=8, latent=1024, expert_width=2688)
    ctx = dict(scope_ms={"ssd_scan": 10.0, "moe_experts": 20.0, "moe_route": 3.0},
               nemotron_shape=shape, moe_pairs_per_step=5 * 2816.0, batch=1,
               n_chips=1, device_kind="TPU v5 lite")
    assert readers["ssd_scan_device_ms"](ctx) == 10.0
    assert readers["moe_route_device_ms"](ctx) == 3.0
    # five layers' bytes floor (0.28 ms each) over 10 ms
    assert 13.0 < readers["ssd_scan_roofline"](ctx) < 15.0
    # five layers' floor (0.47 ms each) over 20 ms
    assert 11.0 < readers["moe_experts_roofline"](ctx) < 12.5
