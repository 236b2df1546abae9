#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

``python chip_smoke.py`` (one TPU chip, no arguments) drives the main path
once through the entry points a user calls, in ONE process:

  data     seed -> the on-disk format ``launch preprocess-criteo`` writes
           (``data/criteo_preprocessing.py``), ids inside the Criteo-Kaggle
           vocabularies (26 tables, 33,762,577 rows)
  train    ``launch train`` on ``configs/dlrm-criteo.toml`` as committed
           (row-sharded DMP regime, rowwise_adagrad, stacked tables,
           dedup_lookup, embed_dim 16) at B = 8192: two epochs of a few dozen
           steps, eval after each, one checkpoint
  serve    ``launch serve``: restores THAT checkpoint (step > 0), exports
           the bundle, answers micro-batched scoring requests
  verify   every table / optimizer slot / served parameter on a ``tpu``
           device; served scores == the trainer's eval-step logits for the
           same rows
  kernels  the fused fat-line Pallas kernels (the quickstart default, which
           DLRM-Criteo opts out of): TwoTower sparse steps at d=64, B=8192
           with ``tpu_custom_call`` in the compiled text, kernel vs the XLA
           line formulation on the chip, flash attention at Bert4Rec's width

``--multichip`` (four chips; the builder runs it) runs ONLY the sharded path
and what it is compared with: the same DLRM-Criteo step on a ``model = 4``
mesh with ``lookup_mode = "alltoall"`` + ``grouped_a2a`` against a one-device
mesh, same config and seed.

Every check is fatal: nothing here catches an exception to carry on, and no
option waives the device check.  Sizes are arguments of the phase functions
so ``tests/test_chip_smoke.py`` drives the same code at a tiny size on CPU
devices; ``main`` always runs the full width and always demands the chip.
The LAST stdout line is the result object the driver reads.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.metadata
import json
import math
import shutil
import sys
import tempfile
import tomllib
from pathlib import Path

import numpy as np

from tdfo_tpu.obs import trace as obs_trace  # the repo's one host-clock site

DLRM_CRITEO_TOML = Path(__file__).resolve().parent / "configs" / "dlrm-criteo.toml"

# Criteo-Kaggle per-column vocabularies (the standard 26-table profile of the
# public DLRM benchmarks; tests/test_chip_smoke.py pins them equal to
# tests/test_planner.py's copy): 33,762,577 rows in 26 tables.
CRITEO_KAGGLE_VOCABS = (
    1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145, 5683,
    8351593, 3194, 27, 14992, 5461306, 10, 5652, 2173, 4, 7046547, 18, 15,
    286181, 105, 142572,
)
# TwoTower vocabularies of the kernels phase (pinned by literal in
# tests/test_chip_smoke.py): user and item sit above the default
# fused_table_threshold, the rest below it.
TWOTOWER_SIZE_MAP = {
    "user": 500_000, "item": 200_000, "language": 32, "is_ebook": 2,
    "format": 16, "publisher": 5_000, "pub_decade": 16,
}
N_CONT = 13
BATCH = 8192
STEPS_PER_EPOCH = 32  # x n_epochs = 2 of the committed config
EVAL_BATCHES = 2

# Tolerances, each stated where it is used:
SERVE_ATOL = 2e-2     # served score vs trainer eval logit (two XLA programs,
                      # f32 matmuls at the TPU's default bf16-pass precision)
KERNEL_ATOL = 1e-5    # fat-line kernel vs XLA line formulation, f32 Adam
KERNEL_RTOL = 1e-4
FLASH_ATOL = 3e-2     # flash kernel vs XLA attention, bf16 MXU operands
MULTICHIP_RTOL = 2e-3  # per-step loss, model=4 alltoall vs one device


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


# ------------------------------------------------------------ compile clock


class CompileClock:
    """Seconds jax spent in backend compiles (cache reads included) and the
    persistent cache's hits, from jax's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, report: dict):
    """Times one phase (wall + compile seconds).  Exceptions pass through."""
    say(f"--- {name}")
    t0 = obs_trace.clock()
    c0, n0, h0 = clock.snapshot()
    yield
    c1, n1, h1 = clock.snapshot()
    rec = {"seconds": round(obs_trace.elapsed_s(t0), 2),
           "compile_seconds": round(c1 - c0, 2), "compiles": n1 - n0,
           "cache_hits": h1 - h0}
    report[name] = rec
    say(f"phase {name}: {rec['seconds']} s, of which compile "
        f"{rec['compile_seconds']} s in {rec['compiles']} programs "
        f"({rec['cache_hits']} persistent-cache hits)")


def hbm_line(label: str) -> int | None:
    """Print live/peak device bytes; returns the peak (None off the chip,
    where the backend reports no memory stats)."""
    import jax

    gc.collect()
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if not stats:
            say(f"hbm after {label}: device {d.id} reports no memory stats")
            return None
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        say(f"hbm after {label}: device {d.id} in use "
            f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB, peak "
            f"{peaks[-1] / 2**30:.2f} GiB, limit "
            f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    return max(peaks)


# --------------------------------------------------------------------- data


def write_criteo_data(data_dir: Path, vocabs, *, n_train: int, n_eval: int,
                      seed: int, file_num: int = 8) -> dict[str, int]:
    """What ``launch preprocess-criteo`` leaves behind
    (``tdfo_tpu/data/criteo_preprocessing.py:62-175``), made from ``seed``:
    ``parquet/{train,eval}_part_<k>.parquet`` with columns ``label`` int8,
    ``cont_0..12`` float32 in [0, 1], ``cat_0..25`` int32 inside each
    vocabulary (uniform: the most distinct rows a batch can touch), train
    rows on a random shard, plus ``size_map.json``.  ``table_stats.json`` is
    the planner's input; the trainer never reads it, so it is not written.
    The label depends on two continuous columns and a small table, so a few
    dozen steps measurably lower the loss."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(seed)
    data_dir = Path(data_dir)
    (data_dir / "parquet").mkdir(parents=True, exist_ok=True)
    cats = [f"cat_{i}" for i in range(len(vocabs))]
    conts = [f"cont_{i}" for i in range(N_CONT)]
    size_map = {c: int(v) for c, v in zip(cats, vocabs)}
    (data_dir / "size_map.json").write_text(json.dumps(size_map, indent=4))

    def rows(n: int) -> dict[str, np.ndarray]:
        cols: dict[str, np.ndarray] = {}
        for c in conts:
            cols[c] = rng.random(n, dtype=np.float32)
        for c, v in size_map.items():
            cols[c] = rng.integers(0, v, n, dtype=np.int32)
        small = min(size_map, key=size_map.get)  # a table a few steps learn
        logit = (4.0 * (cols["cont_0"] - 0.5) + 3.0 * (cols["cont_1"] - 0.5)
                 + 1.5 * (cols[small] % 2) - 0.75)
        label = rng.random(n) < 1.0 / (1.0 + np.exp(-logit))
        return {"label": label.astype(np.int8), **cols}

    def write(name: str, cols: dict[str, np.ndarray], keep=None) -> None:
        if keep is not None:
            cols = {k: v[keep] for k, v in cols.items()}
        pq.write_table(pa.table(cols), data_dir / "parquet" / name)

    train = rows(n_train)
    shard_of = rng.integers(0, file_num, n_train)
    for s in range(file_num):
        write(f"train_part_{s}.parquet", train, shard_of == s)
    write("eval_part_0.parquet", rows(n_eval))
    return size_map


def _toml(cfg: dict) -> str:
    def val(v) -> str:
        if isinstance(v, bool):
            return "true" if v else "false"
        if isinstance(v, (int, float)):
            return repr(v)
        if isinstance(v, (list, tuple)):
            return "[" + ", ".join(val(x) for x in v) + "]"
        return json.dumps(str(v))

    top = [f"{k} = {val(v)}" for k, v in cfg.items() if not isinstance(v, dict)]
    for name, table in cfg.items():
        if isinstance(table, dict):
            top += ["", f"[{name}]"] + [f"{k} = {val(v)}"
                                        for k, v in table.items()]
    return "\n".join(top) + "\n"


def write_config(path: Path, *, data_dir: Path, checkpoint_dir: Path,
                 batch: int, use_tpu: bool, **overrides) -> Path:
    """``configs/dlrm-criteo.toml`` as committed, with only the run's
    directories, the batch size and ``use_tpu`` (the trainer's own refusal
    to run a TPU config elsewhere) replaced; ``overrides`` merge on top
    (sub-tables merge key-wise)."""
    cfg = tomllib.loads(DLRM_CRITEO_TOML.read_text())
    cfg.update(data_dir=str(data_dir), checkpoint_dir=str(checkpoint_dir),
               per_device_train_batch_size=batch,
               per_device_eval_batch_size=batch, use_tpu=use_tpu)
    for k, v in overrides.items():
        if isinstance(v, dict):
            cfg[k] = {**cfg.get(k, {}), **v}
        else:
            cfg[k] = v
    path.write_text(_toml(cfg))
    return path


def _metrics(checkpoint_dir: Path) -> list[dict]:
    lines = (Path(checkpoint_dir) / "metrics.jsonl").read_text().splitlines()
    return [json.loads(ln) for ln in lines]


# -------------------------------------------------------------------- train


def phase_train(config_path: Path, *, steps_per_epoch: int,
                n_epochs: int) -> dict:
    """``launch train``: Trainer.fit() takes the steps, evaluates after each
    epoch, writes the checkpoint.  Losses must be finite and the eval loss
    (a fixed set of batches) must fall from the first epoch to the last."""
    from tdfo_tpu import launch
    from tdfo_tpu.core.config import read_configs

    check(launch.main(["train", "--config", str(config_path)]) == 0,
          "launch train returned non-zero")
    cfg = read_configs(config_path)
    recs = _metrics(cfg.checkpoint_dir)
    step_losses = [r["train_loss"] for r in recs if "train_loss" in r]
    eval_losses = [r["eval_loss"] for r in recs if "eval_loss" in r]
    epochs = [r for r in recs if "train_loss_epoch" in r]
    check(len(step_losses) > 0 and all(map(math.isfinite, step_losses)),
          f"train losses not finite: {step_losses}")
    check(len(eval_losses) == n_epochs
          and all(map(math.isfinite, eval_losses)),
          f"expected {n_epochs} finite eval losses, got {eval_losses}")
    check([r["steps"] for r in epochs] == [steps_per_epoch] * n_epochs,
          f"expected {n_epochs} epochs of {steps_per_epoch} steps, "
          f"got {[r['steps'] for r in epochs]}")
    check(eval_losses[-1] < eval_losses[0],
          f"eval loss did not fall: {eval_losses}")
    say(f"train: {n_epochs} x {steps_per_epoch} steps at B = "
        f"{cfg.per_device_train_batch_size}, embed_dim {cfg.embed_dim}, "
        f"{sum(cfg.size_map.values()):,} rows in {len(cfg.size_map)} tables")
    say(f"train: step losses {', '.join(f'{x:.4f}' for x in step_losses)}")
    say(f"train: eval loss per epoch "
        f"{' -> '.join(f'{x:.4f}' for x in eval_losses)} (fell), "
        f"host-loop examples/s per epoch "
        f"{[round(r['examples_per_sec']) for r in epochs]} "
        "(first epoch includes compilation; set-up fact, not a metric)")
    return {"trained_steps": steps_per_epoch * n_epochs,
            "eval_losses": eval_losses}


# -------------------------------------------------------------------- serve


def phase_serve(config_path: Path, *, trained_steps: int) -> dict:
    """``launch serve`` (in-process frontend, replicas = 1): restore the
    checkpoint ``launch train`` wrote, export the bundle, answer the
    micro-batched request trace.  ``serve_from_config`` initialises fresh
    weights when it finds no checkpoint; the exported manifest's step proves
    that is not what ran."""
    from tdfo_tpu import launch
    from tdfo_tpu.core.config import read_configs

    check(launch.main(["serve", "--config", str(config_path)]) == 0,
          "launch serve returned non-zero")
    cfg = read_configs(config_path)
    bundle_dir = Path(cfg.checkpoint_dir) / "serving_bundle"
    manifest = json.loads((bundle_dir / "bundle.json").read_text())
    check(manifest["step"] == trained_steps and trained_steps > 0,
          f"serve exported step {manifest['step']}, the trainer saved "
          f"{trained_steps}: the checkpoint was not what was served")
    summary = [r for r in _metrics(cfg.checkpoint_dir)
               if r.get("event") == "serve_summary"][-1]
    check(summary["requests"] > 0 and summary["shed"] == 0,
          f"serve summary {summary}")
    say(f"serve: restored checkpoint step {manifest['step']} (> 0), bundle "
        f"digest {manifest['digest'][:12]}, answered {summary['requests']} "
        f"requests in {summary['batches']} micro-batches, p50 "
        f"{summary['p50_ms']:.1f} ms p99 {summary['p99_ms']:.1f} ms "
        "(first batch of each bucket compiles; set-up fact, not a metric)")
    return {"bundle_dir": bundle_dir, "step": manifest["step"]}


# ------------------------------------------------------------------- verify


def _leaf_platforms(tree) -> set[str]:
    import jax

    return {d.platform for leaf in jax.tree.leaves(tree)
            if isinstance(leaf, jax.Array) for d in leaf.devices()}


def phase_verify(config_path: Path, bundle_dir: Path, *, n_rows: int,
                 platform: str, atol: float = SERVE_ATOL) -> dict:
    """Where the state lives, and train/serve agreement: the trainer's
    eval-step logits for the first ``n_rows`` eval rows against the scores
    the exported bundle serves for the same rows through the micro-batcher."""
    import jax
    import pyarrow.parquet as pq

    from tdfo_tpu.core.config import read_configs
    from tdfo_tpu.serve.export import load_bundle
    from tdfo_tpu.serve.frontend import MicroBatcher
    from tdfo_tpu.serve.scoring import make_scorer
    from tdfo_tpu.train.trainer import Trainer

    cfg = read_configs(config_path)
    trainer = Trainer(cfg)
    step, state, _ = trainer._ckpt.restore(trainer.state,
                                           stamps=trainer._ckpt_stamps)
    trainer.logger.close()
    trainer._ckpt.close()
    where = {"tables": _leaf_platforms(state.tables),
             "optimizer slots": _leaf_platforms(state.slots),
             "dense params + optax state": _leaf_platforms(
                 (state.dense_params, state.opt_state))}

    tbl = pq.read_table(Path(cfg.data_dir) / "parquet" / "eval_part_0.parquet")
    rows = {c: tbl.column(c).to_numpy()[:n_rows] for c in tbl.column_names}
    _, logits = trainer.eval_step(state, rows)
    logits = np.asarray(jax.device_get(logits), np.float32)

    scorer = make_scorer(load_bundle(bundle_dir, verify=True),
                         mesh=trainer.mesh)
    where["served parameters"] = _leaf_platforms(scorer._params)
    for what, got in where.items():
        check(got == {platform}, f"{what} live on {got}, not {platform!r}")
    spec = cfg.serving
    mb = MicroBatcher(scorer.score, buckets=spec.buckets,
                      max_batch=spec.max_batch,
                      batch_deadline_ms=spec.batch_deadline_ms,
                      program_cache_size=scorer.score_cache_size)
    feats = {c: v for c, v in rows.items() if c != "label"}
    cuts = np.linspace(0, n_rows, 5).astype(int)  # four ragged requests
    results = mb.run([(i, {c: v[a:b] for c, v in feats.items()})
                      for i, (a, b) in enumerate(zip(cuts, cuts[1:]))])
    served = np.concatenate([results[i] for i in range(len(cuts) - 1)])
    check(served.shape == logits.shape and np.isfinite(served).all(),
          f"served scores shape {served.shape} vs logits {logits.shape}")
    diff = float(np.abs(served - logits).max())
    check(diff <= atol,
          f"served scores differ from trainer eval logits by {diff} > {atol}")
    say(f"verify: checkpoint step {step}; tables, optimizer slots, dense and "
        f"served parameters all on {platform!r} devices; served scores vs "
        f"trainer eval logits on {n_rows} rows: max |diff| {diff:.3e} "
        f"(tolerance {atol})")
    return {"max_abs_diff": diff}


# ------------------------------------------------------------------ kernels


def _twotower_batch(rng, size_map, b: int) -> dict[str, np.ndarray]:
    ints = {"user_id": "user", "item_id": "item", "language": "language",
            "is_ebook": "is_ebook", "format": "format",
            "publisher": "publisher", "pub_decade": "pub_decade"}
    out = {c: rng.integers(0, size_map[f], b, dtype=np.int32)
           for c, f in ints.items()}
    out["avg_rating"] = rng.random(b, dtype=np.float32)
    out["num_pages"] = rng.random(b, dtype=np.float32)
    out["label"] = rng.integers(0, 2, b).astype(np.int8)
    return out


def _twotower_steps(size_map, *, embed_dim: int, batch: int, steps: int,
                    seed: int, dedup_lookup: bool, platform: str):
    """A few TwoTower sparse train steps through the Trainer's own step
    (``make_sparse_train_step`` at the default ``fused_table_threshold``:
    user/item ride fused fat lines, Adam).  Returns (losses, final tables,
    compiled text)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tdfo_tpu.core.config import read_configs
    from tdfo_tpu.train.metrics import AUC
    from tdfo_tpu.train.trainer import Trainer

    cfg = read_configs(
        None, model="twotower", model_parallel=True, size_map=dict(size_map),
        embed_dim=embed_dim, per_device_train_batch_size=batch,
        dedup_lookup=dedup_lookup, seed=seed, use_tpu=platform == "tpu")
    trainer = Trainer(cfg)
    rng = np.random.default_rng(seed)
    put = lambda b: jax.device_put(b, NamedSharding(trainer.mesh, P("data")))
    batches = [put(_twotower_batch(rng, size_map, batch * trainer.mesh.size))
               for _ in range(steps)]
    # compiled once for its text; the jitted calls below then hit the
    # persistent cache instead of compiling the same program again
    text = trainer.train_step.lower(
        trainer.state, batches[0], AUC.empty()).compile().as_text()
    state, auc, losses = trainer.state, AUC.empty(), []
    for b in batches:
        state, loss, auc = trainer.train_step(state, b, auc)
        losses.append(float(loss))
    return losses, state.tables, text


def _fat_kernel_vs_xla(n_rows: int, *, embed_dim: int, batch: int, seed: int,
                       platform: str) -> float:
    """One fused Adam update of a fat-line table, same operands, through
    ``_fat_apply_lines`` (the Pallas kernel on TPU devices; the interpreted
    kernel on CPU devices, where only the test runs this) and through
    ``_fat_apply_lines_xla``.  Returns max |diff| over the whole table —
    touched lines must agree within tolerance, untouched ones exactly."""
    import jax
    import jax.numpy as jnp

    from tdfo_tpu.ops import sparse
    from tdfo_tpu.ops.pallas_kernels import fat_pack, line_layout

    layout = line_layout(embed_dim, "adam")
    hp = dict(lr=1e-2, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    k_t, k_m, k_i, k_g = jax.random.split(jax.random.key(seed), 4)
    table = jax.random.normal(k_t, (n_rows, embed_dim), jnp.float32)
    mom = 0.1 * jax.random.normal(k_m, (2, n_rows, embed_dim), jnp.float32)
    fat = fat_pack(table, mom[0], jnp.abs(mom[1]), kind="adam", layout=layout)
    ids = jax.random.randint(k_i, (batch,), 0, n_rows, jnp.int32)
    grads = jax.random.normal(k_g, (batch, embed_dim), jnp.float32)
    count = jnp.asarray(3, jnp.int32)

    def operands(ids, grads):
        ulines, seg, _ = sparse.dedupe_ids(
            ids, capacity=batch, vocab=n_rows, max_distinct=batch,
            rows_per_line=layout.r)
        c = ulines.shape[0]
        g_slots = jax.ops.segment_sum(grads, seg, num_segments=c * layout.r)
        touched = jax.ops.segment_sum(
            jnp.ones((batch,), jnp.float32), seg, num_segments=c * layout.r)
        return ulines, g_slots, touched

    @jax.jit
    def via_kernel(fat, ids, grads):
        ulines, g_slots, touched = operands(ids, grads)
        return sparse._fat_apply_lines(
            fat, (count,), ulines, g_slots, touched, layout=layout,
            interpret=True, platform=platform, **hp)[0]

    @jax.jit
    def via_xla(fat, ids, grads):
        ulines, g_slots, touched = operands(ids, grads)
        return sparse._fat_apply_lines_xla(
            fat, ulines, g_slots, (touched > 0).astype(jnp.float32),
            layout=layout, new_count=count + 1, **hp)

    a, b = via_kernel(fat, ids, grads), via_xla(fat, ids, grads)
    check(bool(jnp.any(a != fat)), "the fat-line update changed nothing")
    bound = KERNEL_ATOL + KERNEL_RTOL * jnp.abs(b)
    check(bool(jnp.all(jnp.abs(a - b) <= bound)),
          f"fat-line kernel vs XLA formulation: max |diff| "
          f"{float(jnp.abs(a - b).max())} beyond atol {KERNEL_ATOL} + rtol "
          f"{KERNEL_RTOL}")
    return float(jnp.abs(a - b).max())


def _flash_vs_xla(shape, *, seed: int, platform: str) -> float:
    """Flash attention (fwd) at Bert4Rec's own width against the XLA
    formulation, with a ragged key-padding mask."""
    import jax
    import jax.numpy as jnp

    from tdfo_tpu.ops.pallas_kernels import _xla_attention, flash_attention

    b, _, t, _ = shape
    kq, kk, kv, kl = jax.random.split(jax.random.key(seed), 4)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
               for key in (kq, kk, kv))
    lens = jax.random.randint(kl, (b,), 1, t + 1)
    valid = jnp.arange(t)[None, :] < lens[:, None]
    got = jax.jit(lambda q, k, v, m: flash_attention(
        q, k, v, m, interpret=platform == "cpu"))(q, k, v, valid)
    want = jax.jit(_xla_attention)(q, k, v, valid)
    diff = float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max())
    check(bool(jnp.isfinite(got.astype(jnp.float32)).all())
          and diff <= FLASH_ATOL,
          f"flash attention at {shape}: max |diff| {diff} > {FLASH_ATOL}")
    return diff


def phase_kernels(size_map, *, embed_dim: int, batch: int, steps: int,
                  seed: int, platform: str, flash_shape) -> dict:
    """The Pallas kernels the main path does not run."""
    from tdfo_tpu.core.mesh import PALLAS_CHOICES

    want = "kernel" if platform == "tpu" else "xla"
    runs = {}
    for op, dedup in (("fat_line_update", False),
                      ("fat_line_update_routed", True)):
        losses, tables, text = _twotower_steps(
            size_map, embed_dim=embed_dim, batch=batch, steps=steps,
            seed=seed, dedup_lookup=dedup, platform=platform)
        check(all(map(math.isfinite, losses)), f"{op}: losses {losses}")
        check(PALLAS_CHOICES[(op, want, platform)] > 0,
              f"{op} did not run as {want!r} on {platform!r} devices: "
              f"{dict(PALLAS_CHOICES)}")
        n_calls = text.count("tpu_custom_call")
        check((n_calls > 0) == (platform == "tpu"),
              f"{op}: {n_calls} tpu_custom_call in the compiled step on "
              f"{platform!r} devices")
        say(f"kernels: TwoTower d={embed_dim} B={batch} Adam, "
            f"dedup_lookup={str(dedup).lower()}: {op} -> {want}, "
            f"{n_calls} tpu_custom_call in the compiled step, losses "
            f"{', '.join(f'{x:.4f}' for x in losses)}")
        runs[op] = (losses, tables)
    # the two steps are the same math through different data movement
    (l_a, t_a), (l_b, t_b) = runs.values()
    check(np.allclose(l_a, l_b, rtol=1e-3, atol=1e-4),
          f"plain vs routed kernel losses diverge: {l_a} vs {l_b}")
    fat_names = [n for n, t in t_a.items() if t.ndim == 3]
    check(len(fat_names) > 0, f"no fused fat-line table among {list(t_a)}")
    import jax.numpy as jnp

    for n in fat_names:
        d = float(jnp.abs(t_a[n] - t_b[n]).max())
        check(d <= 1e-3, f"fat table {n}: plain vs routed differ by {d}")
    diff = _fat_kernel_vs_xla(size_map["item"], embed_dim=embed_dim,
                              batch=batch, seed=seed, platform=platform)
    say(f"kernels: fat_line_update vs _fat_apply_lines_xla on "
        f"[{size_map['item']}, {embed_dim}] Adam, {batch} ids: max |diff| "
        f"{diff:.3e} (atol {KERNEL_ATOL}, rtol {KERNEL_RTOL})")
    fdiff = _flash_vs_xla(flash_shape, seed=seed, platform=platform)
    say(f"kernels: flash_attention at B,H,T,dh = {tuple(flash_shape)} vs XLA "
        f"attention: max |diff| {fdiff:.3e} (tolerance {FLASH_ATOL})")
    return {"fat_max_abs_diff": diff, "flash_max_abs_diff": fdiff}


# ---------------------------------------------------------------- multichip


def phase_multichip(workdir: Path, vocabs, *, batch: int, steps: int,
                    seed: int, devices, platform: str,
                    rtol: float = MULTICHIP_RTOL) -> dict:
    """The DLRM-Criteo step with table rows sharded over ``model = n``
    (``lookup_mode = "alltoall"``, ``grouped_a2a``; ``dedup_lookup`` off, as
    that mode requires) against the same config and seed on a one-device
    mesh, through ``Trainer.train_epoch`` on the same data."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tdfo_tpu.core.config import read_configs
    from tdfo_tpu.train.metrics import AUC
    from tdfo_tpu.train.trainer import Trainer

    n = len(devices)
    data_dir = Path(workdir) / "data"
    write_criteo_data(data_dir, vocabs, n_train=steps * batch, n_eval=batch,
                      seed=seed)
    losses, shards, text = {}, {}, ""
    for name, devs, model_axis in (("sharded", devices, n),
                                   ("single", devices[:1], 1)):
        out = Path(workdir) / name
        cfg = read_configs(write_config(
            Path(workdir) / f"{name}.toml", data_dir=data_dir,
            checkpoint_dir=out, batch=batch, use_tpu=platform == "tpu",
            lookup_mode="alltoall", dedup_lookup=False, log_every_n_steps=1,
            embeddings={"grouped_a2a": True},
            mesh={"data": 1, "model": model_axis}))
        trainer = Trainer(cfg, devices=devs)
        trainer.train_epoch(0)
        trainer.logger.close()
        losses[name] = [r["train_loss"] for r in _metrics(out)
                        if "train_loss" in r]
        check(len(losses[name]) == steps
              and all(map(math.isfinite, losses[name])),
              f"{name}: expected {steps} finite losses, got {losses[name]}")
        big = {k: v for k, v in trainer.state.tables.items()
               if v.shape[0] >= 8 * n}
        for k, slot in trainer.state.slots.items():
            big.update({f"{k} slot {i}": v for i, v in enumerate(slot)
                        if v.ndim and v.shape[0] >= 8 * n})
        check(any(k in trainer.state.tables for k in big),
              f"{name}: no big table in {list(trainer.state.tables)}")
        shards[name] = {
            k: [(s.device.id, s.data.shape[0]) for s in v.addressable_shards]
            for k, v in big.items()}
        if name == "sharded":
            # persistent-cache hit after train_epoch's own compile
            b0 = jax.device_put(
                {k: np.zeros((batch,), dt)
                 for k, (dt, _) in trainer._eval_schema.items()},
                NamedSharding(trainer.mesh, P("data")))
            text = trainer.train_step.lower(
                trainer.state, b0, AUC.empty()).compile().as_text()
        del trainer
        hbm_line(f"multichip {name}")
    for k, per_dev in shards["sharded"].items():
        rows = sum(r for _, r in per_dev)
        check(len({d for d, _ in per_dev}) == n
              and all(abs(r - rows / n) <= 1 for _, r in per_dev),
              f"table {k}: shards {per_dev} are not ~1/{n} of {rows} rows "
              f"on {n} distinct devices")
        say(f"multichip: {k} [{rows} rows] -> "
            + ", ".join(f"device {d}: {r}" for d, r in per_dev))
    n_a2a = text.count("all-to-all")
    check(n_a2a > 0, "no all-to-all in the compiled sharded step")
    worst = max(abs(a - b) / max(abs(b), 1e-12)
                for a, b in zip(losses["sharded"], losses["single"]))
    check(worst <= rtol,
          f"sharded vs single losses differ by rel {worst} > {rtol}: "
          f"{losses}")
    say(f"multichip: model = {n} alltoall + grouped_a2a vs one device, "
        f"{steps} steps at B = {batch}: losses sharded "
        f"{', '.join(f'{x:.5f}' for x in losses['sharded'])} | single "
        f"{', '.join(f'{x:.5f}' for x in losses['single'])} | max rel diff "
        f"{worst:.2e} (tolerance {rtol}); {n_a2a} all-to-all in the compiled "
        "step text")
    return {"max_rel_loss_diff": worst, "all_to_all": n_a2a}


# --------------------------------------------------------------------- main


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--multichip", action="store_true",
                   help="four chips: run ONLY the model=4 sharded DLRM-Criteo "
                        "step and its one-device reference")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the generated data and weights")
    args = p.parse_args(argv)

    from tdfo_tpu.core.mesh import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but "
                         f"jax.devices()[0].platform is {dev.platform!r}")
    if args.multichip and len(devices) != 4:
        raise SystemExit(f"chip_smoke: --multichip needs four chips, found "
                         f"{len(devices)}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:  # a label, not a check
        libtpu = "unknown"
    say(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{libtpu}; {len(devices)} x {dev.device_kind} ({dev.platform}); "
        f"compile cache at {cache_dir}")

    clock = CompileClock()
    report: dict = {}
    t_all = obs_trace.clock()
    workdir = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    try:
        if args.multichip:
            with phase("multichip", clock, report):
                phase_multichip(workdir, CRITEO_KAGGLE_VOCABS, batch=BATCH,
                                steps=8, seed=args.seed, devices=devices,
                                platform="tpu")
        else:
            n_dev = len(devices)
            cfg_path = workdir / "dlrm-criteo-smoke.toml"
            with phase("data", clock, report):
                write_criteo_data(
                    workdir / "data", CRITEO_KAGGLE_VOCABS,
                    n_train=STEPS_PER_EPOCH * BATCH * n_dev,
                    n_eval=EVAL_BATCHES * BATCH * n_dev, seed=args.seed)
                write_config(cfg_path, data_dir=workdir / "data",
                             checkpoint_dir=workdir / "ckpt", batch=BATCH,
                             use_tpu=True, log_every_n_steps=8)
            with phase("train", clock, report):
                trained = phase_train(cfg_path,
                                      steps_per_epoch=STEPS_PER_EPOCH,
                                      n_epochs=2)
            hbm_line("train")
            with phase("serve", clock, report):
                served = phase_serve(cfg_path,
                                     trained_steps=trained["trained_steps"])
            hbm_line("serve")
            with phase("verify", clock, report):
                phase_verify(cfg_path, served["bundle_dir"], n_rows=1000,
                             platform="tpu")
            hbm_line("verify")
            with phase("kernels", clock, report):
                phase_kernels(TWOTOWER_SIZE_MAP, embed_dim=64, batch=BATCH,
                              steps=3, seed=args.seed, platform="tpu",
                              flash_shape=(256, 2, 20, 32))
        peak = hbm_line("all phases")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    secs, compiles, hits = clock.snapshot()
    say(f"total {obs_trace.elapsed_s(t_all):.1f} s; compile {secs:.1f} s in "
        f"{compiles} programs, {hits} persistent-cache hits; peak HBM "
        f"{'not reported' if peak is None else f'{peak / 2**30:.2f} GiB'}; "
        f"phases {json.dumps(report)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
