"""The committed cell at a size a CPU test can hold, and an example of what a
later PR could add as data."""

CELL = "twotower-goodreads.train-uniform"
EXAMPLE = "dlrm-example.train-uniform"

TINY = {
    CELL: {
        "categorical": {"user_id": 50000, "item_id": 30000, "language": 32,
                        "is_ebook": 2, "format": 16, "publisher": 5000,
                        "pub_decade": 16},
        "program": {"per_device_train_batch_size": 64},
        "epoch_steps": 12,
    },
    EXAMPLE: {"epoch_steps": 12},
}


def dlrm_example() -> dict:
    """What a later PR's configuration file could hold: another model of the
    family (DLRM over one stacked table, row-wise Adagrad, no fused lines),
    at vocabularies a CPU test can hold.  The harness takes it as data."""
    cats = {f"cat_{i}": v for i, v in enumerate(
        [100, 50, 40000, 30000, 20] + [7, 300] * 10 + [5000])}
    conts = [f"cont_{i}" for i in range(13)]
    d, f = 16, len(cats) + 1
    layers = {"bottom_0": (13, 64), "bottom_out": (64, d),
              "top_0": (d + f * (f - 1) // 2, 128), "top_1": (128, 64),
              "top_out": (64, 1)}
    shapes = {}
    for name, (a, b) in layers.items():
        shapes[f"{name}/kernel"], shapes[f"{name}/bias"] = [a, b], [b]
    adam = {"lr": 3e-4, "weight_decay": 1e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    return {
        "name": "dlrm-example", "source": "https://arxiv.org/abs/1906.00091",
        "chips": 1, "driver": "train_epoch", "reduced": [],
        "program": {
            "model": "dlrm", "embed_dim": d, "model_parallel": True,
            "embedding_sharding": "row", "fused_table_threshold": -1,
            "stack_tables": True, "dedup_lookup": True,
            "sparse_optimizer": "rowwise_adagrad", "learning_rate": 3e-4,
            "weight_decay": 1e-4, "per_device_train_batch_size": 64,
            "per_device_eval_batch_size": 64, "shuffle_buffer_size": 100000,
            "categorical_features": list(cats), "continuous_features": conts},
        "columns": {"categorical": cats, "continuous": conts},
        "work": {"interaction_flops_per_example": 6 * f * f * d},
        "reference": {
            "module": "ctr",
            "model": {"model": "dlrm", "bottom": [64], "top": [128, 64]},
            "dense_shapes": shapes,
            "optimizer": {"sparse": {"kind": "rowwise_adagrad", "lr": 3e-4,
                                     "weight_decay": 1e-4, "eps": 1e-8},
                          "dense": adam}},
        "limits": {"loss2_gap": 1e-4, "loss3_gap": 1e-4, "grad_norm_gap": 0.15,
                   "update_norm_gap": 0.02},
        "trace_patterns": {"table_update": "^%fusion"},
    }
