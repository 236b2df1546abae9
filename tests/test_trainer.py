"""Trainer end-to-end on the spoofed 8-device mesh: both workloads, resume.

Covers the loop capabilities of all five reference main()s (SURVEY.md §3):
epoch driving, padded eval, metric computation, checkpoint/resume with
optimizer state, and the CLI wiring.
"""

import json

import numpy as np
import pytest

from tdfo_tpu.core.config import read_configs
from tdfo_tpu.data.ctr_preprocessing import run_ctr_preprocessing
from tdfo_tpu.data.seq_preprocessing import run_seq_preprocessing
from tdfo_tpu.data.synthetic import write_synthetic_goodreads
from tdfo_tpu.obs import trace as obs_trace
from tdfo_tpu.train.trainer import Trainer, pad_batch


@pytest.fixture(scope="module")
def prepared_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("gr")
    write_synthetic_goodreads(d, n_users=100, n_books=150,
                              interactions_per_user=(15, 50), seed=3)
    ctr = run_ctr_preprocessing(d)
    seq = run_seq_preprocessing(d, max_len=12, sliding_step=6, seed=3)
    return d, ctr, seq


def test_pad_batch():
    b = {"x": np.arange(5, dtype=np.float32), "y": np.ones((5, 3))}
    padded, w = pad_batch(b, 8)
    assert padded["x"].shape == (8,) and padded["y"].shape == (8, 3)
    assert w.tolist() == [1] * 5 + [0] * 3
    same, w2 = pad_batch(b, 5)
    assert same is b or same["x"].shape == (5,)
    assert w2.sum() == 5


def test_twotower_trainer_fits_and_improves(prepared_dir, tmp_path):
    d, ctr, _ = prepared_dir
    cfg = read_configs(
        None,
        data_dir=d,
        model="twotower",
        n_epochs=2,
        learning_rate=3e-3,
        embed_dim=16,
        per_device_train_batch_size=16,
        per_device_eval_batch_size=16,
        shuffle_buffer_size=1000,
        log_every_n_steps=1000,
        size_map=ctr,
    )
    tr = Trainer(cfg, log_dir=tmp_path)
    metrics = tr.fit()
    assert 0.0 <= metrics["auc"] <= 1.0
    assert metrics["eval_loss"] > 0
    # metrics.jsonl written with epoch records
    lines = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert any("train_loss_epoch" in l for l in lines)
    assert any("auc" in l for l in lines)
    # every epoch line says where the host loop's time went (obs.trace phases)
    epochs = [l for l in lines if "train_loss_epoch" in l]
    assert [l["epoch"] for l in epochs] == [0, 1]
    records = obs_trace.epoch_history()[-2:]
    assert [r["epoch"] for r in records] == [0, 1]
    for l, record in zip(epochs, records):
        for name in ("epoch_open", "next_batch", "loader_next", "h2d_put",
                     "dispatch", "loss_sync", "epoch_close"):
            assert l[f"phase_{name}_s"] >= 0.0, name
        assert 0.0 < (l["phase_next_batch_s"] + l["phase_dispatch_s"]
                      + l["phase_loss_sync_s"]) <= l["loop_s"]
        assert l["phase_h2d_put_s"] <= (
            l["phase_next_batch_s"] + l["phase_epoch_open_s"])
        assert l["phase_next_batch_max_ms"] <= 1e3 * l["phase_next_batch_s"]
        # the decode runs beside the loop on prefetch_to_mesh's producer
        # thread, which joined the epoch: every batch decoded and put is
        # counted, and the line says how far ahead the producer kept
        _, decoded, _ = record["phases"]["loader_next"]
        _, put, _ = record["phases"]["h2d_put"]
        assert (decoded, put) == (l["steps"] + 1, l["steps"])  # + the end
        assert record["tallies"]["prefetch_depth"][1] == l["steps"] + 1
        assert 1 <= l["prefetch_empty_takes"] <= l["steps"] + 1
        assert 0.0 <= l["prefetch_depth_mean"] <= 4.0  # the queue's bound
        assert l["examples_per_sec"] == pytest.approx(
            l["steps"] * 16 * tr.mesh.shape["data"] / l["loop_s"])


def test_bert4rec_trainer_model_parallel(prepared_dir, tmp_path):
    d, _, seq = prepared_dir
    cfg = read_configs(
        None,
        data_dir=d,
        model="bert4rec",
        model_parallel=True,
        n_epochs=1,
        learning_rate=3e-3,
        embed_dim=16,
        n_heads=2,
        n_layers=1,
        max_len=12,
        sliding_step=6,
        per_device_train_batch_size=8,
        per_device_eval_batch_size=8,
        shuffle_buffer_size=1000,
        log_every_n_steps=1000,
        size_map={"n_items": seq["n_items"]},
    )
    tr = Trainer(cfg, log_dir=tmp_path)
    metrics = tr.fit()
    eval_keys = {"Recall@10", "Recall@20", "Recall@50",
                 "NDCG@10", "NDCG@20", "NDCG@50"}
    # fit() now also runs the final held-out TEST evaluation (the split the
    # reference computes and never consumes, torchrec/train.py:147-177)
    assert set(metrics) == eval_keys | {"test_" + k for k in eval_keys}
    for v in metrics.values():
        assert 0.0 <= v <= 1.0


def test_checkpoint_resume_roundtrip(prepared_dir, tmp_path):
    d, ctr, _ = prepared_dir
    common = dict(
        data_dir=d, model="twotower", learning_rate=3e-3, embed_dim=8,
        per_device_train_batch_size=16, per_device_eval_batch_size=16,
        shuffle_buffer_size=500, log_every_n_steps=1000, size_map=ctr,
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every_n_epochs=1,
    )
    m1 = Trainer(read_configs(None, n_epochs=1, **common)).fit()
    # second trainer resumes from epoch 0's checkpoint and trains one more;
    # checkpoint ids are global data steps, with the epoch recorded in the
    # cursor sidecar
    tr2 = Trainer(read_configs(None, n_epochs=2, **common))
    restored = tr2._ckpt.latest_step()
    assert restored is not None
    cursor = tr2._ckpt.read_cursor(restored)
    assert cursor["epoch"] == 0 and cursor["epoch_complete"]
    m2 = tr2.fit()
    assert m2["eval_loss"] <= m1["eval_loss"] * 1.1  # did not regress from scratch


def test_launch_cli_end_to_end(tmp_path, capsys):
    from tdfo_tpu.launch import main

    d = tmp_path / "data"
    cfgp = tmp_path / "config.toml"
    cfgp.write_text(
        f"""
data_dir = "{d}"
model = "twotower"
n_epochs = 1
learning_rate = 3e-3
embed_dim = 8
per_device_train_batch_size = 16
per_device_eval_batch_size = 16
shuffle_buffer_size = 500
log_every_n_steps = 1000
"""
    )
    assert main(["synth", "--config", str(cfgp)]) == 0
    assert main(["preprocess-ctr", "--config", str(cfgp)]) == 0
    assert (d / "size_map.json").exists()
    assert main(["train", "--config", str(cfgp), "--distributed", "never",
                 "--log-dir", str(tmp_path / "logs")]) == 0
    out = capsys.readouterr().out
    assert "auc" in out


def test_steps_per_execution_matches_single_step(prepared_dir, tmp_path):
    """The compiled multi-step loop must train identically to per-step
    dispatch (tensorflow2 steps_per_execution parity) — same data order,
    same math, just one dispatch per K steps."""
    d, ctr, _ = prepared_dir
    common = dict(
        data_dir=d, model="twotower", learning_rate=3e-3, embed_dim=8,
        per_device_train_batch_size=16, per_device_eval_batch_size=16,
        shuffle_buffer_size=500, log_every_n_steps=1000, size_map=ctr,
        n_epochs=1,
    )
    tr1 = Trainer(read_configs(None, **common))
    avg1 = tr1.train_epoch(0)
    tr4 = Trainer(read_configs(None, steps_per_execution=4, **common))
    avg4 = tr4.train_epoch(0)
    assert np.isclose(avg1, avg4, rtol=1e-4), (avg1, avg4)
    e1, e4 = tr1.evaluate(0), tr4.evaluate(0)
    assert np.isclose(e1["eval_loss"], e4["eval_loss"], rtol=1e-4)


def test_pipeline_overlap_matches_eager_grouped(prepared_dir, tmp_path):
    """train.pipeline_overlap (TrainPipelineSparseDist parity) trains the
    same batches with the same math one call later: epoch average, final
    tables and eval AUC all bit-identical to the eager grouped run, and the
    grouped run itself tracks the per-table baseline."""
    d, ctr, _ = prepared_dir
    common = dict(
        data_dir=d, model="twotower", model_parallel=True,
        mesh={"data": 4, "model": 2}, lookup_mode="alltoall",
        learning_rate=3e-3, embed_dim=8,
        per_device_train_batch_size=16, per_device_eval_batch_size=16,
        shuffle_buffer_size=500, log_every_n_steps=1000, size_map=ctr,
        n_epochs=1,
    )
    tr_g = Trainer(read_configs(None, embeddings={"grouped_a2a": True},
                                **common))
    avg_g = tr_g.train_epoch(0)
    tr_p = Trainer(read_configs(None, embeddings={"grouped_a2a": True},
                                train={"pipeline_overlap": True}, **common))
    avg_p = tr_p.train_epoch(0)
    assert avg_g == avg_p, (avg_g, avg_p)
    for a in tr_g.state.tables:
        np.testing.assert_array_equal(
            np.asarray(tr_g.state.tables[a]),
            np.asarray(tr_p.state.tables[a]), err_msg=a)
    assert tr_g.evaluate(0)["auc"] == tr_p.evaluate(0)["auc"]
    tr_0 = Trainer(read_configs(None, **common))
    assert np.isclose(avg_g, tr_0.train_epoch(0), rtol=1e-5)


def test_pipeline_overlap_bert4rec_matches_eager(prepared_dir, tmp_path):
    """The bert4rec pipelined branch (dropout rng threaded through
    prime/step/flush, jagged-free padded batches): same epoch average and
    final tables as the eager grouped run."""
    d, _, seq = prepared_dir
    common = dict(
        data_dir=d, model="bert4rec", model_parallel=True,
        mesh={"data": 4, "model": 2}, lookup_mode="alltoall",
        n_epochs=1, learning_rate=3e-3,
        embed_dim=16, n_heads=2, n_layers=1, max_len=12, sliding_step=6,
        per_device_train_batch_size=8, per_device_eval_batch_size=8,
        shuffle_buffer_size=1000, log_every_n_steps=1000,
        size_map={"n_items": seq["n_items"]},
        embeddings={"grouped_a2a": True},
    )
    tr_g = Trainer(read_configs(None, **common))
    avg_g = tr_g.train_epoch(0)
    tr_p = Trainer(read_configs(None, train={"pipeline_overlap": True},
                                **common))
    avg_p = tr_p.train_epoch(0)
    assert avg_g == avg_p, (avg_g, avg_p)
    for a in tr_g.state.tables:
        np.testing.assert_array_equal(
            np.asarray(tr_g.state.tables[a]),
            np.asarray(tr_p.state.tables[a]), err_msg=a)


def test_a2a_overflow_metric_logged_in_grouped_mode(prepared_dir, tmp_path):
    """alltoall + finite a2a_capacity_factor surfaces the dropped-id count
    in the periodic metrics stream (JSONL + the TB mirror) — including in
    grouped_a2a mode, where the counter measures the COMBINED per-group
    stream against the same bucket cap the real exchange uses."""
    d, ctr, _ = prepared_dir
    cfg = read_configs(
        None, data_dir=d, model="twotower", model_parallel=True,
        mesh={"data": 4, "model": 2}, lookup_mode="alltoall",
        a2a_capacity_factor=2.0, embeddings={"grouped_a2a": True},
        learning_rate=3e-3, embed_dim=8,
        per_device_train_batch_size=16, per_device_eval_batch_size=16,
        shuffle_buffer_size=500, log_every_n_steps=2, size_map=ctr,
        n_epochs=1,
    )
    tr = Trainer(cfg, log_dir=tmp_path)
    tr.train_epoch(0)
    recs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    vals = [r["a2a_overflow_ids"] for r in recs if "a2a_overflow_ids" in r]
    assert vals, recs  # the diagnostic reached the stream
    assert all(isinstance(v, int) and v >= 0 for v in vals)


def test_twotower_map_style_loader(prepared_dir, tmp_path):
    """config streaming=false -> in-memory map-style epochs (jax-flax
    train.py data_loader parity) through the same trainer."""
    d, ctr, _ = prepared_dir
    cfg = read_configs(
        None, data_dir=d, model="twotower", streaming=False, n_epochs=1,
        learning_rate=3e-3, embed_dim=8, per_device_train_batch_size=16,
        per_device_eval_batch_size=16, log_every_n_steps=1000, size_map=ctr,
    )
    tr = Trainer(cfg, log_dir=tmp_path)
    metrics = tr.fit()
    assert 0.0 <= metrics["auc"] <= 1.0


def test_bert4rec_config_wired_islands(prepared_dir, tmp_path):
    """attn/lookup_mode/fused_table_threshold/steps_per_execution are
    reachable from Config: flash attention (interpret on CPU), psum lookup
    program over a 2-shard model axis, fused fat-row sparse Adam (threshold
    forced low so the item table takes the fat tier), 2-step compiled loop."""
    d, _, seq = prepared_dir
    cfg = read_configs(
        None,
        data_dir=d,
        model="bert4rec",
        model_parallel=True,
        attn="flash",
        lookup_mode="psum",
        fused_table_threshold=8,
        steps_per_execution=2,
        mesh={"data": 4, "model": 2},
        n_epochs=1,
        learning_rate=3e-3,
        embed_dim=16,
        n_heads=2,
        n_layers=1,
        max_len=12,
        sliding_step=6,
        per_device_train_batch_size=8,
        per_device_eval_batch_size=8,
        shuffle_buffer_size=1000,
        log_every_n_steps=1000,
        size_map={"n_items": seq["n_items"]},
    )
    tr = Trainer(cfg, log_dir=tmp_path)
    metrics = tr.fit()
    for v in metrics.values():
        assert 0.0 <= v <= 1.0


def test_eval_template_synthesis_for_empty_host(prepared_dir, tmp_path):
    """A host with ZERO eval rows must synthesise zero-weight template
    batches from the schema and run the full lockstep budget (on a real pod
    one shard-starved host would otherwise kill eval for everyone)."""
    d, ctr, _ = prepared_dir
    cfg = read_configs(
        None, data_dir=d, model="twotower", n_epochs=1, learning_rate=3e-3,
        embed_dim=8, per_device_train_batch_size=16,
        per_device_eval_batch_size=16, shuffle_buffer_size=500,
        log_every_n_steps=1000, size_map=ctr,
    )
    tr = Trainer(cfg, log_dir=tmp_path)

    class EmptyStream:
        batch_size = 16

        def set_epoch(self, e):
            pass

        def max_batches_per_host(self):
            return 3  # other hosts have 3 batches; we must march in lockstep

        def __iter__(self):
            return iter(())

    tr._stream = lambda pattern, train: EmptyStream()
    batches = list(tr._eval_batches())
    assert len(batches) == 3
    for b in batches:
        assert float(b["_weight"].sum()) == 0.0  # pure padding
    # and the metric math over pure padding stays finite / neutral
    metrics = tr.evaluate(0)
    assert metrics["eval_loss"] == 0.0
    import math
    assert math.isnan(metrics["auc"])  # no rows -> undefined AUC, not a crash


def test_tensor_parallel_bert4rec(prepared_dir, tmp_path):
    """tensor_parallel=true shards the feed-forward and vocab-projection
    kernels over the model axis (Megatron split as sharding specs) and the
    metrics match the replicated run (GSPMD inserts the collectives; only
    reduction order differs)."""
    import jax

    d, _, seq = prepared_dir
    common = dict(
        data_dir=d, model="bert4rec", model_parallel=True,
        mesh={"data": 4, "model": 2}, n_epochs=1, learning_rate=3e-3,
        embed_dim=16, n_heads=2, n_layers=1, max_len=12, sliding_step=6,
        per_device_train_batch_size=8, per_device_eval_batch_size=8,
        shuffle_buffer_size=1000, log_every_n_steps=1000,
        size_map={"n_items": seq["n_items"]},
    )
    tr_tp = Trainer(read_configs(None, tensor_parallel=True, **common))
    sharded = {
        "/".join(str(getattr(k, "key", k)) for k in path)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tr_tp.state.dense_params)
        if any(ax is not None for ax in leaf.sharding.spec)
    }
    assert any("out_proj/kernel" in p for p in sharded), sharded
    assert any("fc1/kernel" in p for p in sharded)
    assert any("fc2/kernel" in p for p in sharded)
    # full Megatron: attention QKV column-parallel, out-proj row-parallel
    assert any("attn/qkv/kernel" in p for p in sharded), sharded
    assert any("attn/out/kernel" in p for p in sharded), sharded

    m_tp = tr_tp.fit()
    m_rep = Trainer(read_configs(None, **common)).fit()
    for k in m_rep:
        assert np.isclose(m_tp[k], m_rep[k], rtol=1e-3, atol=1e-5), (k, m_tp[k], m_rep[k])


def test_megatron_head_divisibility_guard():
    """A mesh whose model axis does not divide n_heads must be rejected at
    plan time, not silently resharded mid-layer (VERDICT r3 next #3)."""
    import jax
    import jax.numpy as jnp
    import pytest

    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh
    from tdfo_tpu.parallel.sharding import make_sharding_plan, megatron_tp_rule

    mesh = make_mesh(MeshSpec(data=4, model=2, seq=1))
    tree = {"block_0": {"attn": {"qkv": {"kernel": jnp.zeros((16, 48))}}}}
    with pytest.raises(ValueError, match="n_heads"):
        make_sharding_plan(tree, mesh, megatron_tp_rule(mesh, n_heads=3))
    # divisible heads shard; unknown heads leave attention replicated
    plan = make_sharding_plan(tree, mesh, megatron_tp_rule(mesh, n_heads=2))
    spec = plan["block_0"]["attn"]["qkv"]["kernel"].spec
    assert any(ax is not None for ax in spec), spec
    plan_unknown = make_sharding_plan(tree, mesh, megatron_tp_rule(mesh))
    assert all(ax is None for ax in plan_unknown["block_0"]["attn"]["qkv"]["kernel"].spec)


def test_train_auc_matches_exact(prepared_dir, tmp_path):
    """train_auc (streaming, device-side) must match binary_auc on the
    epoch's predictions.  lr=0 freezes the model, so recomputing logits after
    the epoch reproduces exactly what the steps saw (VERDICT r3 missing #1)."""
    d, ctr, _ = prepared_dir
    cfg = read_configs(
        None,
        data_dir=d,
        model="twotower",
        n_epochs=1,
        learning_rate=0.0,
        weight_decay=0.0,
        embed_dim=8,
        per_device_train_batch_size=16,
        per_device_eval_batch_size=16,
        shuffle_buffer_size=1000,
        log_every_n_steps=1000,
        size_map=ctr,
    )
    tr = Trainer(cfg, log_dir=tmp_path)
    tr.fit()
    lines = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    logged = [l["train_auc"] for l in lines if "train_auc" in l]
    assert logged, "train_auc missing from the epoch log"

    # recompute the exact AUC over every train row with the (frozen) model
    import jax.numpy as jnp

    from tdfo_tpu.train.metrics import binary_auc

    labels, scores = [], []
    for batch, _k in tr._train_batches(epoch=0):
        loss, logits = tr.eval_step(tr.state, batch)
        labels.append(np.asarray(batch["label"]).reshape(-1))
        scores.append(np.asarray(jnp.ravel(logits)))
    exact = binary_auc(np.concatenate(labels), 1 / (1 + np.exp(-np.concatenate(scores))))
    # 200-bin histogram quantisation bounds the streaming estimate's error
    assert abs(logged[-1] - exact) < 0.02, (logged[-1], exact)


def test_param_summary(prepared_dir, capsys):
    from tdfo_tpu.utils.summary import param_summary

    d, ctr, _ = prepared_dir
    cfg = read_configs(
        None, data_dir=d, model="twotower", model_parallel=True,
        embed_dim=8, size_map=ctr, shuffle_buffer_size=100,
    )
    tr = Trainer(cfg)
    out = capsys.readouterr().out
    assert "twotower parameters" in out and "total" in out
    # fat tables report TRUE param counts (vocab x dim), not storage size
    s = param_summary(tr.state.dense_params, tables=tr.state.tables, coll=tr.coll)
    assert "tables/" in s


def test_preempted_save_does_not_poison_resume(prepared_dir, tmp_path):
    """A kill DURING checkpoint save leaves an in-progress tmp dir; the
    manager must keep resuming from the last COMPLETE checkpoint (the
    BackupAndRestore failure-recovery contract, tensorflow2/train_ps.py:156)."""
    from tdfo_tpu.train.checkpoint import CheckpointManager

    d, ctr, _ = prepared_dir
    cfg = read_configs(
        None, data_dir=d, model="twotower", n_epochs=1, learning_rate=3e-3,
        embed_dim=8, per_device_train_batch_size=16,
        per_device_eval_batch_size=16, shuffle_buffer_size=500,
        log_every_n_steps=1000, size_map=ctr,
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every_n_epochs=1,
    )
    tr = Trainer(cfg)
    tr.fit()  # writes a complete checkpoint for epoch 0
    mgr = CheckpointManager(tmp_path / "ckpt")
    s0 = mgr.latest_step()
    assert s0 is not None
    assert mgr.read_cursor(s0)["epoch"] == 0
    mgr.close()
    # simulate a preemption mid-save of a later step: orbax-style in-progress
    # dir with no committed payload
    (tmp_path / "ckpt" / f"{s0 + 1}.orbax-checkpoint-tmp-1234567").mkdir()
    tr2 = Trainer(cfg.replace(n_epochs=2))
    assert tr2._ckpt.latest_step() == s0  # incomplete save ignored
    m = tr2.fit()  # resumes from epoch 0 and completes epoch 1
    assert 0.0 <= m["auc"] <= 1.0
    s1 = tr2._ckpt.latest_step()
    assert s1 > s0
    assert tr2._ckpt.read_cursor(s1)["epoch"] == 1


def test_checkpoint_layout_version_guard(tmp_path):
    """Restoring a checkpoint with a foreign (or missing) storage-layout
    stamp must REFUSE with a clear error: parameter layout changes (the
    round-4 fused-QKV reorder, the round-5 fat-line packing) restore
    without shape errors but scramble values — the exact silent-corruption
    hazard the stamp exists to block."""
    import jax.numpy as jnp
    import orbax.checkpoint as ocp
    import pytest

    from tdfo_tpu.train import checkpoint as ckpt_mod
    from tdfo_tpu.train.checkpoint import LAYOUT_VERSION, CheckpointManager

    state = {"w": jnp.arange(6.0).reshape(2, 3)}

    # roundtrip at the current version works and preserves values
    mgr = CheckpointManager(tmp_path / "ok")
    mgr.save(0, state)
    step, restored, cursor = mgr.restore(state)
    assert step == 0 and cursor is None  # no cursor saved with this step
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))
    mgr.close()

    # legacy checkpoint (no stamp — pre-versioning format): refused
    legacy = ocp.CheckpointManager(
        (tmp_path / "legacy").absolute(),
        options=ocp.CheckpointManagerOptions(create=True))
    legacy.save(0, args=ocp.args.StandardSave(state))
    legacy.wait_until_finished()
    legacy.close()
    mgr2 = CheckpointManager(tmp_path / "legacy")
    with pytest.raises(ValueError, match="layout_version"):
        mgr2.restore(state)
    mgr2.close()

    # foreign version stamp: refused with both versions named
    mgr3 = CheckpointManager(tmp_path / "old")
    try:
        ckpt_mod.LAYOUT_VERSION = LAYOUT_VERSION - 1
        mgr3.save(0, state)
    finally:
        ckpt_mod.LAYOUT_VERSION = LAYOUT_VERSION
    with pytest.raises(ValueError, match="layout version"):
        mgr3.restore(state)
    mgr3.close()


def test_checkpoint_unstamped_probe_failure_guidance(tmp_path):
    """When the item_metadata probe itself FAILS on a legacy unstamped
    checkpoint, the early refusal cannot fire and restore used to die with
    an opaque orbax structure mismatch (the abstract tree expects the
    layout_version leaf the legacy save never wrote).  That error must now
    arrive wrapped with the layout-version guidance."""
    import jax.numpy as jnp
    import orbax.checkpoint as ocp
    import pytest

    from tdfo_tpu.train.checkpoint import CheckpointManager

    state = {"w": jnp.arange(6.0).reshape(2, 3)}
    legacy = ocp.CheckpointManager(
        (tmp_path / "legacy").absolute(),
        options=ocp.CheckpointManagerOptions(create=True))
    legacy.save(0, args=ocp.args.StandardSave(state))
    legacy.wait_until_finished()
    legacy.close()

    mgr = CheckpointManager(tmp_path / "legacy")

    def broken_probe(step_id):
        raise ValueError("simulated metadata schema drift")

    mgr._mgr.item_metadata = broken_probe
    with pytest.raises(ValueError, match="layout_version"):
        mgr.restore(state)
    mgr.close()


def test_checkpoint_stamps_mismatch_refused(tmp_path):
    """The stamps sidecar must round-trip, and ANY asymmetry — different
    values, missing on either side — refuses the restore (the hot/cold
    hot-id digest contract: same shapes under a different hot set restore
    cleanly but pair every hot row with the wrong id)."""
    import jax.numpy as jnp
    import pytest

    from tdfo_tpu.train.checkpoint import CheckpointManager

    state = {"w": jnp.arange(4.0)}
    mgr = CheckpointManager(tmp_path / "ck")
    mgr.save(0, state, stamps={"hot_digest": {"item": "abc123"}})
    # matching stamps restore fine
    step, restored, _ = mgr.restore(
        state, stamps={"hot_digest": {"item": "abc123"}})
    assert step == 0
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))
    # wrong digest, missing expectation, or extra expectation: all refused
    for bad in ({"hot_digest": {"item": "zzz999"}}, None, {"other": 1}):
        with pytest.raises(ValueError, match="stamps"):
            mgr.restore(state, stamps=bad)
    mgr.close()
    # and the symmetric case: checkpoint without stamps, run expecting some
    mgr2 = CheckpointManager(tmp_path / "ck2")
    mgr2.save(0, state)
    with pytest.raises(ValueError, match="stamps"):
        mgr2.restore(state, stamps={"hot_digest": {"item": "abc123"}})
    mgr2.close()


def test_bert4rec_dedup_lookup_matches_default(prepared_dir):
    """dedup_lookup on the sequence family ([B, T] ids, fat item table,
    model-parallel mesh): same metrics as the default path."""
    d, _, seq = prepared_dir
    common = dict(
        data_dir=d, model="bert4rec", model_parallel=True,
        fused_table_threshold=8,  # fat item table
        n_epochs=1, learning_rate=3e-3, embed_dim=16, n_heads=2, n_layers=1,
        max_len=12, sliding_step=6, per_device_train_batch_size=8,
        per_device_eval_batch_size=8, shuffle_buffer_size=1000,
        log_every_n_steps=1000, size_map={"n_items": seq["n_items"]},
    )
    m_dd = Trainer(read_configs(None, dedup_lookup=True, **common)).fit()
    m_def = Trainer(read_configs(None, **common)).fit()
    for k in m_def:
        assert np.isclose(m_dd[k], m_def[k], rtol=1e-4, atol=1e-6), (k, m_dd, m_def)
