"""Epoch driver — train/eval loops, metrics, checkpoint/resume, profiling.

Unifies the reference's five ``main()`` loops (``jax-flax/train.py:95-164``,
``train_dp.py:144-247``, ``tensorflow2/train.py:22-57``, ``train_dp.py:107-190``,
``torchrec/train.py:147-273``) into one mesh-aware driver:

  * TwoTower CTR: streaming parquet epochs, BCE train loss, padded-final-batch
    eval (``pad_shard_unpad`` parity, ``jax-flax/train_dp.py:182-184,233-240``)
    with in-framework streaming AUC (replacing the borrowed keras metric).
  * Bert4Rec: masked-LM train epochs; sampled-candidate eval
    (Recall@K/NDCG@K, 1+100 protocol), pre-training validation as a sanity
    floor (``torchrec/train.py:159``).
  * checkpoint/resume every N epochs incl. optimizer state + mid-training
    restart (supersedes all three reference mechanisms, see
    ``tdfo_tpu/train/checkpoint.py``), JSONL metric logging (observability
    the reference lacks, SURVEY.md §5.5), optional ``jax.profiler`` traces
    (§5.1).

Fault tolerance: training survives preemption by construction — restart the
same command and the driver resumes from the newest checkpoint (the
``BackupAndRestore`` capability, ``tensorflow2/train_ps.py:156``), now at
STEP granularity: ``checkpoint_every_n_steps`` saves mid-epoch with a
data-stream cursor, and resume fast-forwards the stream to the exact batch.
A non-finite-loss guard keeps a bounded on-device snapshot and rolls back to
it (skipping the offending batch window) instead of training through NaNs;
checkpoint I/O retries with backoff (``tdfo_tpu/utils/retry.py``); the
``[faults]`` config section injects deterministic kills/NaNs/I/O failures so
all of this is testable (``tdfo_tpu/utils/faults.py``).
"""

from __future__ import annotations

import dataclasses
import json
import math
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from tdfo_tpu.core.config import Config
from tdfo_tpu.core.mesh import make_mesh, mesh_platform
from tdfo_tpu.obs import counters as obs_counters
from tdfo_tpu.obs import events as obs_events
from tdfo_tpu.obs import trace as obs_trace
from tdfo_tpu.data.loader import (
    MapStream,
    ParquetStream,
    prefetch_to_mesh,
    resolve_files,
)
from tdfo_tpu.train.metrics import AUC, recalls_and_ndcgs_for_ks
from tdfo_tpu.train.state import TrainState, make_adamw
from tdfo_tpu.train.step import make_eval_step, make_multi_step, make_train_step
from tdfo_tpu.utils import faults as _faults
from tdfo_tpu.utils import retry as _retry

__all__ = ["Trainer", "MetricLogger", "pad_batch"]


class MetricLogger:
    """stdout + JSONL metrics (the observability layer the reference lacks —
    its closest analogue is tqdm bars + prints, SURVEY.md §5.5)."""

    def __init__(self, log_dir: str | Path | None = None,
                 tensorboard: bool = False, rotate_bytes: int = 0):
        self._f = None
        self._tb = None
        self._n = 0
        # size-based rotation ([telemetry] log_rotate_bytes): a long-running
        # online loop must not grow metrics.jsonl without bound
        self._rotate_bytes = int(rotate_bytes)
        self._path: Path | None = None
        # telemetry norm scalars accumulate here and flush as ONE histogram
        # summary per tag at close() (run-wide distribution view)
        self._hist_buf: dict[str, list[float]] = {}
        if log_dir is not None and jax.process_index() == 0:
            Path(log_dir).mkdir(parents=True, exist_ok=True)
            self._path = Path(log_dir) / "metrics.jsonl"
            self._f = open(self._path, "a")
            if tensorboard:
                # TF-free tfevents mirror of every scalar (the PS recipe's
                # TensorBoard callback, tensorflow2/train_ps.py:154, made
                # framework-wide): `tensorboard --logdir` shows the curves
                from tdfo_tpu.utils.tensorboard import TBScalarWriter

                self._tb = TBScalarWriter(log_dir)

    def log(self, **record: Any) -> None:
        # numpy scalars (device fetches, np.float32 arithmetic) are not JSON
        # serialisable and dodge the float-format branch below — coerce at
        # the door so callers can pass fetched values straight through
        record = {
            k: (v.item() if isinstance(v, np.generic)
                or (isinstance(v, np.ndarray) and v.ndim == 0) else v)
            for k, v in record.items()
        }
        record.setdefault("time", time.time())
        if jax.process_index() == 0:
            msg = ", ".join(
                f"{k}={v:.5f}" if isinstance(v, float) else f"{k}={v}"
                for k, v in record.items() if k != "time"
            )
            print(msg, flush=True)
            if self._f is not None:
                self._f.write(json.dumps(record) + "\n")
                self._f.flush()
                if self._rotate_bytes:
                    from tdfo_tpu.utils.logrotate import maybe_rotate_file

                    self._f = maybe_rotate_file(
                        self._f, self._path, self._rotate_bytes)
            if self._tb is not None:
                scalars = {
                    k: float(v) for k, v in record.items()
                    if k not in ("time", "step", "epoch", "global_step")
                    and isinstance(v, (int, float))
                }
                # per-tag x-axis: run-global step when the caller provides
                # one (per-epoch `step` resets and would fold curves back),
                # else epoch, else a monotone event counter
                step = record.get(
                    "global_step", record.get("epoch", self._n))
                self._tb.scalars(int(step), scalars,
                                 wall_time=record["time"])
                for k in ("grad_norm", "param_norm"):
                    if k in scalars:
                        self._hist_buf.setdefault(k, []).append(scalars[k])
            self._n += 1

    def close(self) -> None:
        """Idempotent: ``fit`` closes in a ``finally`` block, and a caller
        logging afterwards falls back to stdout-only instead of crashing."""
        if self._f is not None:
            self._f.close()
            self._f = None
        if self._tb is not None:
            for tag, vals in self._hist_buf.items():
                self._tb.histogram(self._n, f"{tag}_dist", vals)
            self._hist_buf = {}
            self._tb.close()
            self._tb = None


def pad_batch(batch: dict[str, np.ndarray], size: int) -> tuple[dict[str, np.ndarray], np.ndarray]:
    """Pad a short final eval batch to ``size`` rows; returns (batch, weights)
    with 0-weight padding rows (``flax.jax_utils.pad_shard_unpad`` parity,
    ``jax-flax/train_dp.py:182-184``)."""
    n = len(next(iter(batch.values())))
    w = np.zeros((size,), np.float32)
    w[:n] = 1.0
    if n == size:
        return batch, w
    out = {}
    for k, v in batch.items():
        pad_width = [(0, size - n)] + [(0, 0)] * (v.ndim - 1)
        out[k] = np.pad(v, pad_width)
    return out, w


def _ctr_columns(cfg: Config) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """(categorical input columns, continuous columns) for the CTR family —
    the custom schema (``categorical_features``, e.g. Criteo's 26+13) or the
    Goodreads TwoTower default."""
    if cfg.categorical_features:
        return tuple(cfg.categorical_features), tuple(cfg.continuous_features)
    from tdfo_tpu.models.twotower import (
        TWOTOWER_CATEGORICAL,
        TWOTOWER_CONTINUOUS,
        _FEATURE_TO_INPUT,
    )

    return (tuple(_FEATURE_TO_INPUT[f] for f in TWOTOWER_CATEGORICAL),
            TWOTOWER_CONTINUOUS)


def _ctr_eval_schema(cat_columns: tuple[str, ...],
                     cont_columns: tuple[str, ...]) -> dict[str, tuple]:
    """Post-rename eval-batch schema for the CTR family: key ->
    (numpy dtype, trailing shape).  The authority for (a) restricting real
    batches so every host ships an identical pytree and (b) synthesising
    zero-weight template batches on hosts with no eval rows — dtypes match
    what the CTR preprocessing writes to parquet."""
    schema: dict[str, tuple] = {c: (np.int32, ()) for c in cat_columns}
    for c in cont_columns:
        schema[c] = (np.float32, ())
    schema["label"] = (np.int8, ())
    return schema


def _make_ctr_eval_accum(logits_fn: Callable):
    """Device-side eval accumulator for the CTR family.

    One jitted call per batch folds (weighted loss sum, weight sum, streaming
    AUC histograms) into a replicated accumulator pytree — the host only
    fetches floats ONCE at epoch end.  Under a multi-host mesh the reductions
    are global (GSPMD inserts the cross-host psums), replacing torchrec's
    ``all_gather_object`` metric aggregation (``torchrec/train.py:108-111``)
    and never touching non-addressable shards from the host.
    """

    @jax.jit
    def accum(state, batch, acc):
        w = batch["_weight"]
        logits = logits_fn(state, batch)
        labels = batch["label"].astype(jnp.float32)
        loss_vec = optax.sigmoid_binary_cross_entropy(logits, labels)
        # non-finite logits (mixed-precision overflow) must not fold a
        # backend-defined NaN->bin cast into the headline eval AUC
        ok = jnp.isfinite(logits)
        return {
            "loss_sum": acc["loss_sum"] + (loss_vec * w).sum(),
            "w_sum": acc["w_sum"] + w.sum(),
            "auc": acc["auc"].update(
                labels, jax.nn.sigmoid(jnp.where(ok, logits, 0.0)),
                w * ok.astype(jnp.float32)),
        }

    return accum


def _wrap_auc_step(inner, *, donate_state: bool = True,
                   counters: bool = False):
    """Fuse the train-side streaming-AUC fold INTO the step's single jitted
    program: ``(state, batch, acc) -> (state, loss, acc)``.

    One global program per step matters beyond speed: in multi-process runs a
    SEPARATE jitted fold interleaved with the loop's eager loss arithmetic
    deadlocked the cross-process dispatch rendezvous (two global programs
    racing for the mesh in different orders on different hosts).  ``inner``
    is an unjitted ``with_aux`` step returning ``(state, (loss, logits))``.

    ``counters=True`` opens a telemetry collector around the trace and
    appends the gathered dict as an extra return; ``False`` keeps the
    construction — and the jaxpr — exactly as without telemetry (the lazy
    ``emit`` thunks below add zero equations when no collector is open).
    """

    def _step(state, batch, acc: AUC):
        state, (loss, logits) = inner(state, batch)
        # mixed-precision overflow steps can emit non-finite logits; a
        # NaN->int32 histogram-bin cast is backend-defined, so weight those
        # samples out of the streaming AUC instead of folding garbage in
        ok = jnp.isfinite(logits)
        obs_counters.emit("nonfinite_logits", lambda: (~ok).sum())
        acc = acc.update(batch["label"].astype(jnp.float32),
                         jax.nn.sigmoid(jnp.where(ok, logits, 0.0)),
                         ok.astype(jnp.float32))
        return state, loss, acc

    if counters:
        def step(state, batch, acc: AUC):
            with obs_counters.collect() as c:
                out = _step(state, batch, acc)
            return (*out, dict(c))
    else:
        step = _step

    return jax.jit(step, donate_argnums=(0,) if donate_state else ())


def _wrap_auc_multi_step(inner, *, donate_state: bool = True,
                         counters: bool = False):
    """steps_per_execution twin of :func:`_wrap_auc_step`: scan the unjitted
    step over a stacked chunk, folding AUC in the scan carry.  With
    ``counters`` the collector opens INSIDE the scan body (a collector
    opened outside would capture body tracers and leak them through the
    scan boundary); counter dicts stack as scan outputs and the chunk
    reports the final step's values."""

    def _body(carry, batch):
        st, a = carry
        st, (loss, logits) = inner(st, batch)
        ok = jnp.isfinite(logits)  # see _wrap_auc_step
        obs_counters.emit("nonfinite_logits", lambda: (~ok).sum())
        a = a.update(batch["label"].astype(jnp.float32),
                     jax.nn.sigmoid(jnp.where(ok, logits, 0.0)),
                     ok.astype(jnp.float32))
        return (st, a), loss

    if counters:
        def multi(state, stack, acc: AUC):
            def body(carry, batch):
                with obs_counters.collect() as c:
                    carry, loss = _body(carry, batch)
                return carry, (loss, dict(c))

            (state, acc), (losses, cs) = jax.lax.scan(body, (state, acc), stack)
            return (state, losses.mean(), acc,
                    jax.tree.map(lambda x: x[-1], cs))
    else:
        def multi(state, stack, acc: AUC):
            (state, acc), losses = jax.lax.scan(_body, (state, acc), stack)
            return state, losses.mean(), acc

    return jax.jit(multi, donate_argnums=(0,) if donate_state else ())


def _wrap_auc_pipelined(pipe, *, donate_state: bool = False,
                        counters: bool = False):
    """Pipelined twin of :func:`_wrap_auc_step`: the step trains the CARRIED
    batch, so the AUC fold reads the carry's labels — folding the incoming
    batch's labels would pair them with the previous batch's logits.
    Returns jitted ``(prime, step, flush)``; ``counters`` appends the
    telemetry dict to step/flush returns (see :func:`_wrap_auc_step`)."""

    def _fold(acc: AUC, labels, logits):
        ok = jnp.isfinite(logits)  # see _wrap_auc_step
        obs_counters.emit("nonfinite_logits", lambda: (~ok).sum())
        return acc.update(labels.astype(jnp.float32),
                          jax.nn.sigmoid(jnp.where(ok, logits, 0.0)),
                          ok.astype(jnp.float32))

    def _step(state, batch, carry, acc: AUC):
        labels = carry[0]["label"]
        state, (loss, logits), carry = pipe.step(state, batch, carry)
        return state, loss, carry, _fold(acc, labels, logits)

    def _flush(state, carry, acc: AUC):
        labels = carry[0]["label"]
        state, (loss, logits) = pipe.flush(state, carry)
        return state, loss, _fold(acc, labels, logits)

    if counters:
        def step(state, batch, carry, acc: AUC):
            with obs_counters.collect() as c:
                out = _step(state, batch, carry, acc)
            return (*out, dict(c))

        def flush(state, carry, acc: AUC):
            with obs_counters.collect() as c:
                out = _flush(state, carry, acc)
            return (*out, dict(c))
    else:
        step, flush = _step, _flush

    d = (0,) if donate_state else ()
    return (jax.jit(pipe.prime), jax.jit(step, donate_argnums=d),
            jax.jit(flush, donate_argnums=d))


def _wrap_counters_step(fn, *, donate_state: bool = False):
    """Counter-collecting jit wrapper for steps WITHOUT an AUC fold
    (bert4rec): append the telemetry dict to ``fn``'s return tuple.  Only
    built when ``telemetry.counters`` is on — the off path keeps the
    original (wrapper-free) construction, so its jaxpr cannot drift."""

    def wrapped(*args):
        with obs_counters.collect() as c:
            out = fn(*args)
        out = out if isinstance(out, tuple) else (out,)
        return (*out, dict(c))

    return jax.jit(wrapped, donate_argnums=(0,) if donate_state else ())


def _wrap_counters_multi_step(step_fn, *, donate_state: bool = False):
    """steps_per_execution twin of :func:`_wrap_counters_step` (the
    counter-aware variant of ``step.make_multi_step``): collect inside the
    scan body, stack as scan outputs, report the final step's values."""

    def multi(state, stack, *rest):
        def body(st, batch):
            with obs_counters.collect() as c:
                st, loss = step_fn(st, batch, *rest)
            return st, (loss, dict(c))

        state, (losses, cs) = jax.lax.scan(body, state, stack)
        return state, losses.mean(), jax.tree.map(lambda x: x[-1], cs)

    return jax.jit(multi, donate_argnums=(0,) if donate_state else ())


def _commit_replicated(state, mesh):
    """Pin every uncommitted leaf of a state pytree to the mesh, replicated.

    Sharded leaves (embedding tables placed by the collection) keep their
    shardings; everything else (step counter, dense params, optax state,
    count slots) commits as replicated.  Without this, checkpoint restore
    materialises the uncommitted leaves on device 0 only and the next jitted
    step fails with incompatible-device errors against the sharded tables.
    """
    repl = NamedSharding(mesh, P())

    def commit(leaf):
        if isinstance(leaf, jax.Array) and leaf.committed:
            return leaf
        return jax.device_put(leaf, repl)

    return jax.tree.map(commit, state)


def _copy_tree(tree):
    """Deep-copy the array leaves of a pytree into FRESH device buffers
    (shardings preserved — the copy is an eager op and computation follows
    data).  Needed wherever a tree must survive donation: the dense train
    step donates its state, so a rollback snapshot aliasing live buffers
    would be invalidated by the very next step."""
    return jax.tree.map(
        lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, tree)


# second-moment decay of the decoder's AdamW and table Adam (betas 0.9, 0.95:
# the family's pre-training recipe)
_LM_ADAM_B2 = 0.95


def _count_lm_batches(stream, seq_len: int):
    """Packed-sequence batches (``token`` and ``segment`` of [B, T] int32) as
    the step takes them, counted on the way: ``lm_tokens`` goes to the open
    epoch's record (``obs.trace.tally``; this runs on ``prefetch_to_mesh``'s
    producer thread, which has joined the epoch) and from there to the
    epoch line."""
    for b in stream:
        token = np.ascontiguousarray(b["token"], np.int32)
        segment = np.ascontiguousarray(b["segment"], np.int32)
        if token.shape[1:] != (seq_len,) or segment.shape != token.shape:
            raise ValueError(
                f"causal decoder: the data holds sequences of {token.shape[1:]} "
                f"tokens (segment {segment.shape}), max_len says {seq_len}")
        obs_trace.tally("lm_tokens", token.size)
        yield {"token": token, "segment": segment}


def _tally_fields(tallies: dict) -> dict:
    """A phase record's tallies as fields of a metrics.jsonl line: counts
    as integers, seconds (a name ending ``_s``) as floats, and the five
    ``jit_*`` of ``obs.trace.JIT_KEYS`` always, 0 where jax compiled
    nothing."""
    out = {k: 0.0 if k.endswith("_s") else 0 for k in obs_trace.JIT_KEYS}
    for name, (total, _) in tallies.items():
        out[name] = float(total) if name.endswith("_s") else int(total)
    return out


def _check_cache_overflow(overflow: dict) -> None:
    """Fail LOUDLY on update-cache admission overflow: ids past the free
    capacity never entered the cache, so their updates were silently lost
    and the bit-exactness contract is already broken — continuing would
    train on corrupt tables."""
    bad = {a: int(v) for a, v in overflow.items() if int(v) > 0}
    if bad:
        raise RuntimeError(
            f"update-cache admission overflow (distinct ids whose updates "
            f"were LOST): {bad}.  embeddings.cache_rows is too small for "
            "the per-flush-interval working set — raise cache_rows (the "
            "retained half must cover the interval's distinct touched "
            "rows) or lower flush_every.")


class Trainer:
    """Config-driven trainer for both workload families."""

    def __init__(self, config: Config, *, log_dir: str | Path | None = None,
                 devices: Sequence[jax.Device] | None = None):
        """``devices``: build the mesh over these instead of all of
        ``jax.devices()`` (a one-device reference run on a multi-chip
        host).

        The construction is one set-up record (``obs.trace.setup_phases``,
        kept in ``setup_history()``): the seconds of its stages (``mesh``,
        ``checkpoint_init``, ``table_init``, ``dense_init``, ``opt_init``,
        ``step_build``, ``param_summary``) and what jax traced, lowered and
        compiled meanwhile, written as the ``event=setup`` line of
        metrics.jsonl."""
        with obs_trace.setup_phases() as setup:
            self._setup(config, log_dir, devices)
            timed = setup.close()
        self.logger.log(
            event="setup", build_s=timed["loop_s"],
            **{f"phase_{k}_s": v[0] for k, v in timed["phases"].items()},
            **_tally_fields(timed["tallies"]))

    def _setup(self, config: Config, log_dir: str | Path | None,
               devices: Sequence[jax.Device] | None) -> None:
        self.config = config
        with obs_trace.phase("mesh"):
            self.mesh = make_mesh(config.mesh, devices=devices)
        if config.use_tpu and mesh_platform(self.mesh) != "tpu":
            raise RuntimeError(
                f"use_tpu = true but the mesh's devices are "
                f"{mesh_platform(self.mesh)!r} (TPUStrategy-resolution "
                "parity: refuse to silently train a TPU config elsewhere)"
            )
        self.logger = MetricLogger(log_dir or config.checkpoint_dir,
                                   tensorboard=config.tensorboard,
                                   rotate_bytes=config.telemetry.log_rotate_bytes)
        self._ckpt = None
        self._ckpt_stamps = None  # compatibility stamps (hot/cold digests)
        self._logged_steps = 0  # run-global data-step counter (batches consumed)
        self._a2a_overflow = None  # alltoall dropped-id diagnostic (jitted)
        self._pipelined = False  # train.pipeline_overlap (prime/step/flush)
        # names of the counters a causal decoder's step returns beside the loss
        self._step_counters: tuple[str, ...] = ()
        self._cache_flush = None  # update-cache write-back program (jitted)
        self._flush_every = 0  # cache write-back cadence in train steps
        self._map_streams: dict = {}  # streaming=false table cache
        # retryable-I/O observability: failed attempts land next to
        # metrics.jsonl (process 0 only; set_failure_log is a no-op path-wise
        # on other processes because MetricLogger made the dir on p0)
        out_dir = log_dir or config.checkpoint_dir
        if out_dir and jax.process_index() == 0:
            _retry.set_failure_log(Path(out_dir) / "retries.jsonl",
                                   rotate_bytes=config.telemetry.log_rotate_bytes)
        # arm (or clear) the process-global deterministic fault injector from
        # THIS config — the kill marker lives in checkpoint_dir so "restart
        # the same command" converges instead of crash-looping
        _faults.configure(config.faults, config.checkpoint_dir or None)
        # [telemetry]: counters ride the step's return pytree and are fetched
        # at the existing log boundary (no extra host syncs); compile/memory
        # events stream to events.jsonl; the stall watchdog heartbeats to
        # heartbeat.jsonl from a daemon thread while fit() runs
        tele = config.telemetry
        self._counters_on = tele.counters
        self._flush_ctrs: dict = {}  # latest cache-flush counter fetch
        self._a2a_fill = None  # alltoall bucket-utilisation probe (jitted)
        self._watchdog = None
        if (tele.events or tele.stall_timeout_s > 0 or tele.trace) \
                and not out_dir:
            raise ValueError(
                "telemetry.events / telemetry.stall_timeout_s / "
                "telemetry.trace need a checkpoint_dir (or log_dir) to "
                "write events.jsonl / heartbeat.jsonl / trace-*.jsonl")
        if tele.events and jax.process_index() == 0:
            obs_events.configure(Path(out_dir) / "events.jsonl",
                                 rotate_bytes=tele.log_rotate_bytes)
        if tele.trace and jax.process_index() == 0:
            obs_trace.configure(Path(out_dir) / "trace",
                                rotate_bytes=tele.log_rotate_bytes)
        if tele.stall_timeout_s > 0 and jax.process_index() == 0:
            from tdfo_tpu.obs.watchdog import StallWatchdog

            self._watchdog = StallWatchdog(
                Path(out_dir) / "heartbeat.jsonl", tele.stall_timeout_s,
                rotate_bytes=tele.log_rotate_bytes)
        if config.checkpoint_dir:
            with obs_trace.phase("checkpoint_init"):  # imports orbax once
                from tdfo_tpu.train.checkpoint import CheckpointManager

                self._ckpt = CheckpointManager(config.checkpoint_dir)
        self._build()

    # ------------------------------------------------------------- building

    def _build(self) -> None:
        cfg = self.config
        if cfg.model in ("twotower", "dlrm"):
            self._build_ctr()
        elif cfg.model == "bert4rec":
            self._build_bert4rec()
        elif cfg.is_causal_lm:
            self._build_causal_lm()
        else:
            raise ValueError(f"unknown model {cfg.model!r}")
        # model.tabulate-equivalent observability (jax-flax/models.py:154-155)
        if jax.process_index() == 0:
            from tdfo_tpu.utils.summary import param_summary

            with obs_trace.phase("param_summary"):
                if hasattr(self.state, "dense_params"):  # sparse/DMP regime
                    summary = param_summary(
                        self.state.dense_params, tables=self.state.tables,
                        coll=self.coll, title=f"{cfg.model} parameters",
                    )
                else:
                    summary = param_summary(self.state.params,
                                            title=f"{cfg.model} parameters")
            print(summary, flush=True)

    def _set_ctr_streams(self) -> None:
        cfg = self.config
        if cfg.write_format == "tfrecord":
            from tdfo_tpu.data.loader import TFRecordStream

            self._stream_cls = TFRecordStream
            to_tfr = lambda pat: pat.replace(".parquet", ".tfrecord")
            self._train_pattern = str(Path("tfrecord") / to_tfr(cfg.train_data))
            self._eval_pattern = str(Path("tfrecord") / to_tfr(cfg.eval_data))
        else:
            self._stream_cls = ParquetStream
            self._train_pattern = str(Path("parquet") / cfg.train_data)
            self._eval_pattern = str(Path("parquet") / cfg.eval_data)

    def _build_ctr(self) -> None:
        """CTR family.  TwoTower without model_parallel keeps the reference's
        dense regime (nn.Embed tables, dense AdamW).  TwoTower with
        model_parallel — and DLRM always — run the DMP regime: tables in a
        ShardedEmbeddingCollection with the row-sparse in-backward optimizer
        (``torchrec/train.py:235-254`` parity, O(batch) optimizer traffic)."""
        cfg = self.config
        self._set_ctr_streams()
        if cfg.model == "twotower" and not cfg.model_parallel:
            self._build_twotower_dense()
        else:
            self._build_ctr_sparse()

    def _build_twotower_dense(self) -> None:
        from tdfo_tpu.core.precision import DynamicLossScale, compute_dtype
        from tdfo_tpu.models.twotower import init_twotower

        cfg = self.config
        dtype = compute_dtype(cfg.mixed_precision, mesh_platform(self.mesh))
        with obs_trace.phase("dense_init"):
            model, params = init_twotower(
                jax.random.key(cfg.seed), cfg.size_map, cfg.embed_dim,
                dtype=dtype)
        loss_scale = (
            DynamicLossScale.create()
            if cfg.mixed_precision and cfg.loss_scale == "dynamic"
            and dtype == jnp.float16
            else None
        )
        with obs_trace.phase("opt_init"):
            state = TrainState.create(
                apply_fn=model.apply,
                params=params,
                tx=make_adamw(cfg.learning_rate, cfg.weight_decay),
                loss_scale=loss_scale,
            )
            if cfg.ps_min_shard_bytes > 0:
                # PS-strategy parity (tensorflow2/train_ps.py:55-58):
                # partition any variable big enough that a shard stays >=
                # the threshold.  Under GSPMD "parameter servers" are just
                # sharded arrays; the optimizer state shards alongside each
                # variable automatically (the plan maps over the whole
                # state pytree).
                from tdfo_tpu.parallel.sharding import (
                    min_size_partitioner_rule,
                    shard_state,
                )

                self.state = shard_state(
                    state, self.mesh,
                    min_size_partitioner_rule(self.mesh,
                                              cfg.ps_min_shard_bytes),
                )
            else:
                self.state = jax.device_put(state,
                                            NamedSharding(self.mesh, P()))
        with obs_trace.phase("step_build"):
            inner = make_train_step(mesh=self.mesh, jit=False, with_aux=True)
            if cfg.steps_per_execution > 1:
                self.train_step = _wrap_auc_multi_step(
                    inner, counters=self._counters_on)
            else:
                self.train_step = _wrap_auc_step(inner,
                                                 counters=self._counters_on)
        self._train_auc_enabled = True
        self.eval_step = make_eval_step(mesh=self.mesh)
        self._eval_schema = _ctr_eval_schema(*_ctr_columns(cfg))
        self.eval_accum = _make_ctr_eval_accum(
            lambda state, batch: state.apply_fn({"params": state.params}, batch)
        )

    def _build_ctr_sparse(self) -> None:
        import optax as _optax

        from tdfo_tpu.core.precision import compute_dtype
        from tdfo_tpu.models.twotower import (
            TWOTOWER_CONTINUOUS,
            TwoTowerBackbone,
            ctr_embedding_specs,
        )
        from tdfo_tpu.ops.sparse import sparse_optimizer
        from tdfo_tpu.parallel.embedding import ShardedEmbeddingCollection
        from tdfo_tpu.train.ctr import ctr_sparse_forward, make_ctr_sparse_eval_step
        from tdfo_tpu.train.sparse_step import SparseTrainState, make_sparse_train_step

        from tdfo_tpu.models.twotower import TWOTOWER_CATEGORICAL

        cfg = self.config
        cat_cols, cont_cols = _ctr_columns(cfg)
        custom = bool(cfg.categorical_features)
        # every table's vocab must be present — a partial size_map should
        # fail with this message, not a KeyError downstream
        vocab_keys = cat_cols if custom else TWOTOWER_CATEGORICAL
        missing = [f for f in vocab_keys if f not in cfg.size_map]
        if missing:
            raise ValueError(
                f"{cfg.model} needs vocab sizes {missing} in size_map (run preprocessing)"
            )
        dtype = compute_dtype(cfg.mixed_precision, mesh_platform(self.mesh))
        sharding = cfg.embedding_sharding if cfg.model_parallel else "replicated"
        if custom:
            from tdfo_tpu.models.dlrm import generic_embedding_specs

            specs = generic_embedding_specs(
                cfg.size_map, cat_cols, cfg.embed_dim, sharding,
                fused_threshold=cfg.effective_fused_threshold)
        else:
            specs = ctr_embedding_specs(
                cfg.size_map, cfg.embed_dim, sharding,
                fused_threshold=cfg.effective_fused_threshold)
        # storage dtype is a per-table property of the spec; the collection,
        # kernels and optimizer all read it from spec.dtype downstream
        specs = [
            dataclasses.replace(
                s, dtype=jnp.dtype(cfg.embeddings.dtype_for(s.name))
            )
            for s in specs
        ]
        plan = None
        hot_ids = None
        # the plan owns the update-cache decision when present (config
        # validation refuses hand-set cache_rows alongside a plan)
        cache_rows_eff = cfg.embeddings.cache_rows
        flush_every_eff = cfg.embeddings.flush_every
        if cfg.planner.plan:
            from tdfo_tpu.plan.planner import apply_plan_to_specs, load_plan

            # cost-model-chosen per-table placement: the plan artifact
            # rewrites each spec's sharding / fused storage / dtype and
            # carries its own hot-split id sets (config validation refuses
            # hot_vocab / cache_rows / hand-set dtypes alongside a plan, so
            # the plan is the single owner of the per-table levers)
            plan = load_plan(cfg.planner.plan)
            specs, hot_ids = apply_plan_to_specs(specs, plan)
            cache_rows_eff = int(plan.get("cache_rows", 0) or 0)
            if cache_rows_eff > 0:
                flush_every_eff = int(plan.get("cache_flush_every") or
                                      cfg.embeddings.flush_every)
                # the config-time cache gates only see embeddings.cache_rows;
                # a plan-carried cache must honor the same contracts
                if cfg.steps_per_execution != 1 or cfg.train.pipeline_overlap:
                    raise ValueError(
                        "the sharding plan enables the update cache "
                        f"(cache_rows = {cache_rows_eff}), which requires "
                        "steps_per_execution = 1 and train.pipeline_overlap "
                        "= false — adjust the config or re-plan")
        if cfg.embeddings.hot_vocab > 0:
            from tdfo_tpu.data.hot_ids import load_hot_ids

            artifact = load_hot_ids(cfg.data_dir)
            if artifact is None:
                raise ValueError(
                    "embeddings.hot_vocab > 0 but no hot_ids.json under "
                    f"{cfg.data_dir!r} — re-run preprocessing with this "
                    "config to emit the hot/cold remap artifact"
                )
            # the artifact keys by feature/column name; keep only tables this
            # model actually serves (a schema subset is fine, the rest of the
            # artifact is simply unused)
            served = {f for s in specs for f in s.features} | {s.name for s in specs}
            hot_ids = {k: v for k, v in artifact.items() if k in served} or None
        coll = ShardedEmbeddingCollection(
            specs,
            mesh=self.mesh,
            a2a_capacity_factor=cfg.a2a_capacity_factor or None,
            stack_tables=cfg.stack_tables,
            fused_kind=cfg.sparse_optimizer,
            hot_ids=hot_ids,
            grouped_a2a=cfg.embeddings.grouped_a2a,
            cache_rows=cache_rows_eff,
        )
        # hot/cold checkpoints are only loadable under the SAME hot sets —
        # stamp the digests into the checkpoint sidecar so a mismatched
        # restore refuses instead of silently mis-routing rows.  Same for
        # storage dtypes: a bf16-stored table restored into an f32 run (or
        # vice versa) would silently change every subsequent update, so the
        # stamp pins them.  Defaults-only runs keep the stamp absent — their
        # sidecars stay byte-compatible with pre-dtype checkpoints.
        stamps: dict[str, Any] = {}
        if coll.hot_ids:
            stamps["hot_ids"] = coll.hot_digest()
        tstamp = {s.name: jnp.dtype(s.dtype).name for s in specs}
        if (any(v != "float32" for v in tstamp.values())
                or cfg.embeddings.slot_dtype != "float32"):
            stamps["table_dtype"] = tstamp
            stamps["slot_dtype"] = cfg.embeddings.slot_dtype
        if any(v == "int8" for v in tstamp.values()):
            # int8 state carries extra __qscale__/ arrays in state.tables;
            # stamp their layout so a restore into a run that would lay the
            # sidecar out differently (or not at all) refuses loudly
            from tdfo_tpu.ops.quant import QSCALE_LAYOUT

            stamps["qscale_layout"] = QSCALE_LAYOUT
            # fused int8 arrays pack the sidecar IN-LINE (byte-container fat
            # lines, no __qscale__/ entry): stamp per-array storage so a
            # legacy int8-unfused checkpoint refuses to restore into an
            # int8-fused run and vice versa.  Unfused int8 runs add no key,
            # keeping their sidecars byte-identical to pre-fused-int8 ones.
            fat_inline = {
                s.name: "fat-inline" for s in specs
                if jnp.dtype(s.dtype) == jnp.int8 and s.fused}
            if fat_inline:
                stamps["qscale_storage"] = fat_inline
        if cache_rows_eff > 0:
            # the cache arrays live in state.slots: a cached checkpoint
            # cannot restore into a cache-off run (or vice versa, or across
            # cache_rows), so stamp both knobs — flush_every too, so the
            # restored run's flush cadence matches what the operator (or
            # the plan) asked for rather than silently inheriting the
            # sidecar-less default
            stamps["update_cache"] = {
                "cache_rows": int(cache_rows_eff),
                "flush_every": int(flush_every_eff),
            }
        if plan is not None:
            from tdfo_tpu.plan.planner import plan_digest

            # a checkpoint written under a plan pairs the whole state
            # layout (shardings, fat lines, hot heads, dtypes) with that
            # placement; stamp the plan digest so a restore under a
            # different plan — or none — refuses instead of mis-routing
            stamps["sharding_plan"] = plan_digest(plan)
        self._ckpt_stamps = stamps or None
        k_tables, k_dense = jax.random.split(jax.random.key(cfg.seed))
        with obs_trace.phase("table_init"):
            tables = coll.init(k_tables)
        if cfg.model == "dlrm":
            from tdfo_tpu.models.dlrm import DLRMBackbone

            backbone = DLRMBackbone(embed_dim=cfg.embed_dim, dtype=dtype,
                                    cat_columns=cat_cols,
                                    cont_columns=cont_cols)
        else:
            backbone = TwoTowerBackbone(embed_dim=cfg.embed_dim, dtype=dtype)
        with obs_trace.phase("dense_init"):
            dummy_embs = {f: jnp.zeros((1, cfg.embed_dim), jnp.float32)
                          for f in coll.features()}
            dummy_cont = {c: jnp.zeros((1,), jnp.float32) for c in cont_cols}
            dense = backbone.init(k_dense, dummy_embs, dummy_cont)["params"]
        self.coll = coll
        with obs_trace.phase("opt_init"):
            self.state = _commit_replicated(SparseTrainState.create(
                dense_params=dense,
                tx=_optax.adamw(cfg.learning_rate,
                                weight_decay=cfg.weight_decay),
                tables=tables,
                # small_vocab_threshold stays at its own default: the
                # one-hot tier's viability is a fixed TPU property, while
                # fused_table_threshold is a storage-layout choice — one
                # knob must not drag the other
                sparse_opt=sparse_optimizer(
                    cfg.sparse_optimizer, lr=cfg.learning_rate,
                    weight_decay=cfg.weight_decay,
                    slot_dtype=cfg.embeddings.slot_dtype,
                ),
            ), self.mesh)
        if cache_rows_eff > 0:
            # device-resident update cache: empty caches ride state.slots
            # (kill/resume, NaN-rollback snapshots and donation all cover
            # them for free); the coalesced write-back runs as a SEPARATE
            # jitted program every flush_every steps + before checkpoint/
            # eval/export, so train-step jaxprs carry no big-table scatter
            from tdfo_tpu.train.sparse_step import make_cache_flush_fn

            caches = coll.init_caches(self.state.tables,
                                      self.state.sparse_opt)
            if caches:
                self.state = dataclasses.replace(
                    self.state, slots={**self.state.slots, **caches})
                self._cache_flush = make_cache_flush_fn(
                    mesh=coll.mesh, counters=self._counters_on)
                self._flush_every = flush_every_eff
        with obs_trace.phase("step_build"):
            if cfg.train.pipeline_overlap:
                # TrainPipelineSparseDist parity: batch N+1's input-dist issues
                # inside the jitted step ahead of batch N's fwd/bwd/update.  The
                # epoch loop primes on the first batch and flushes the last.
                from tdfo_tpu.train.sparse_step import (
                    make_pipelined_sparse_train_step,
                )

                if cfg.dedup_lookup:
                    raise ValueError(
                        "dedup_lookup (gspmd-only) does not compose with "
                        "train.pipeline_overlap")
                pipe = make_pipelined_sparse_train_step(
                    coll, ctr_sparse_forward(backbone, with_logits=True),
                    jit=False, with_aux=True,
                )
                self._pipelined = True
                self._prime_step, self.train_step, self._flush_step = (
                    _wrap_auc_pipelined(pipe, donate_state=False,
                                        counters=self._counters_on))
            else:
                inner = make_sparse_train_step(
                    coll, ctr_sparse_forward(backbone, with_logits=True),
                    mode=cfg.lookup_mode, jit=False, with_aux=True,
                    dedup_lookup=cfg.dedup_lookup,
                )
                if cfg.steps_per_execution > 1:
                    self.train_step = _wrap_auc_multi_step(
                        inner, donate_state=False, counters=self._counters_on)
                else:
                    self.train_step = _wrap_auc_step(
                        inner, donate_state=False, counters=self._counters_on)
        self._train_auc_enabled = True
        self.eval_step = make_ctr_sparse_eval_step(coll, backbone, mode=cfg.lookup_mode)
        self._eval_schema = _ctr_eval_schema(cat_cols, cont_cols)
        features, mode = list(coll.features()), cfg.lookup_mode
        if (mode == "alltoall" and cfg.a2a_capacity_factor
                and cfg.steps_per_execution == 1):
            # a finite capacity factor silently zeroes overflowed ids under
            # skew: surface the dropped-id count in the JSONL log
            # (steps_per_execution > 1 logs stacked chunks whose leading dim
            # is steps, not batch — skipped there)
            self._a2a_overflow = jax.jit(lambda st, bt: coll.a2a_overflow(
                st.tables, {f: bt[f] for f in features}))
        if (mode == "alltoall" and self._counters_on
                and cfg.steps_per_execution == 1):
            # telemetry companion of the capacity knob: bucket fill fraction
            # + dropped ids, logged alongside the step counters
            self._a2a_fill = jax.jit(lambda st, bt: coll.a2a_fill_stats(
                st.tables, {f: bt[f] for f in features}))

        def sparse_logits(state, batch):
            embs = coll.lookup(state.tables, {f: batch[f] for f in features}, mode=mode)
            return backbone.apply({"params": state.dense_params}, embs, batch)

        self.eval_accum = _make_ctr_eval_accum(sparse_logits)

    def _build_bert4rec(self) -> None:
        from tdfo_tpu.models.bert4rec import Bert4RecConfig, make_sharded_bert4rec
        from tdfo_tpu.ops.sparse import sparse_optimizer
        from tdfo_tpu.train.seq import bert4rec_sparse_forward
        from tdfo_tpu.train.sparse_step import SparseTrainState, make_sparse_train_step

        cfg = self.config
        n_items = int(cfg.size_map.get("n_items", cfg.size_map.get("item", 0)))
        if not n_items:
            raise ValueError("bert4rec needs n_items in size_map (run preprocessing)")
        self.model_cfg = Bert4RecConfig(
            n_items=n_items,
            max_len=cfg.max_len,
            embed_dim=cfg.embed_dim,
            n_heads=cfg.n_heads,
            n_layers=cfg.n_layers,
            dropout=cfg.dropout,
        )
        sharding = cfg.embedding_sharding if cfg.model_parallel else "replicated"
        self.coll, tables, self.backbone, dense = make_sharded_bert4rec(
            jax.random.key(cfg.seed), self.model_cfg, self.mesh,
            sharding=sharding, attn=cfg.attn,
            fused_threshold=cfg.effective_fused_threshold,
            fused_kind=cfg.sparse_optimizer,
            a2a_capacity_factor=cfg.a2a_capacity_factor or None,
            ring_block_k=cfg.ring_block_k or None,
            tp_heads=cfg.tensor_parallel and cfg.attn in ("ring", "ring_flash"),
            grouped_a2a=cfg.embeddings.grouped_a2a,
        )
        if cfg.tensor_parallel:
            from tdfo_tpu.parallel.sharding import megatron_tp_rule, shard_state

            # optax moments mirror the params and inherit these shardings;
            # n_heads licenses the attention (head-parallel) split and
            # rejects head-indivisible meshes at plan time.  attn="flash"
            # keeps attention replicated (n_heads=None): the Pallas kernel
            # has no GSPMD partitioning rule, so head-sharded params would
            # all-gather inside every layer.
            dense = shard_state(
                dense, self.mesh,
                megatron_tp_rule(
                    self.mesh,
                    n_heads=cfg.n_heads if cfg.attn != "flash" else None,
                ),
            )
        with obs_trace.phase("opt_init"):
            self.state = _commit_replicated(SparseTrainState.create(
                dense_params=dense,
                tx=optax.adamw(cfg.learning_rate, weight_decay=cfg.weight_decay),
                tables=tables,
                # small_vocab_threshold stays at its own default: the one-hot
                # tier's viability is a fixed TPU property, while
                # fused_table_threshold is a storage-layout choice — one knob
                # must not drag the other
                sparse_opt=sparse_optimizer(
                    cfg.sparse_optimizer, lr=cfg.learning_rate,
                    weight_decay=cfg.weight_decay,
                ),
            ), self.mesh)
        # jagged mode: batches arrive as (values, lengths) pairs packed per
        # host; jagged_to_dense runs INSIDE the jitted step (fbgemm
        # jagged_2d_to_dense parity, torchrec/models.py:168-172)
        transform = None
        if cfg.jagged:
            from tdfo_tpu.data.jagged import jagged_to_dense_per_host
            from tdfo_tpu.models.bert4rec import PAD_ID

            t_len, n_hosts = cfg.max_len, jax.process_count()

            def transform(batch):
                item = jagged_to_dense_per_host(
                    batch["item_values"], batch["item_lengths"], t_len,
                    PAD_ID, n_hosts)
                label = jagged_to_dense_per_host(
                    batch["label_values"], batch["item_lengths"], t_len,
                    PAD_ID, n_hosts)
                return {"item": item, "label": label}

        with obs_trace.phase("step_build"):
            if cfg.train.pipeline_overlap:
                from tdfo_tpu.train.sparse_step import (
                    make_pipelined_sparse_train_step,
                )

                if cfg.dedup_lookup:
                    raise ValueError(
                        "dedup_lookup (gspmd-only) does not compose with "
                        "train.pipeline_overlap")
                if self._counters_on:
                    # counter collection needs the UNJITTED prime/step/flush (a
                    # collector cannot reach across an inner jit boundary); the
                    # off path below keeps the original construction untouched
                    pipe = make_pipelined_sparse_train_step(
                        self.coll, bert4rec_sparse_forward(self.backbone),
                        jit=False, batch_transform=transform,
                    )
                    self._pipelined = True
                    self._prime_step = jax.jit(pipe.prime)
                    self.train_step = _wrap_counters_step(pipe.step)
                    self._flush_step = _wrap_counters_step(pipe.flush)
                else:
                    pipe = make_pipelined_sparse_train_step(
                        self.coll, bert4rec_sparse_forward(self.backbone),
                        donate=False, batch_transform=transform,
                    )
                    self._pipelined = True
                    self._prime_step = pipe.prime
                    self.train_step = pipe.step
                    self._flush_step = pipe.flush
            elif cfg.steps_per_execution > 1:
                inner = make_sparse_train_step(
                    self.coll, bert4rec_sparse_forward(self.backbone),
                    mode=cfg.lookup_mode, jit=False, batch_transform=transform,
                    dedup_lookup=cfg.dedup_lookup,
                )
                if self._counters_on:
                    self.train_step = _wrap_counters_multi_step(inner)
                else:
                    self.train_step = make_multi_step(inner, donate_state=False)
            elif self._counters_on:
                self.train_step = _wrap_counters_step(make_sparse_train_step(
                    self.coll, bert4rec_sparse_forward(self.backbone),
                    mode=cfg.lookup_mode, jit=False, batch_transform=transform,
                    dedup_lookup=cfg.dedup_lookup,
                ))
            else:
                self.train_step = make_sparse_train_step(
                    self.coll, bert4rec_sparse_forward(self.backbone),
                    mode=cfg.lookup_mode, donate=False, batch_transform=transform,
                    dedup_lookup=cfg.dedup_lookup,
                )
        self._train_auc_enabled = False  # AUC is a binary-CTR metric
        self._dropout_rng = jax.random.key(cfg.seed + 1)
        if (cfg.lookup_mode == "alltoall" and cfg.a2a_capacity_factor
                and not cfg.jagged and cfg.steps_per_execution == 1):
            # surface the capacity knob's silent failure mode (dropped ids
            # -> zero vectors) in the JSONL log
            seq_coll = self.coll
            self._a2a_overflow = jax.jit(lambda st, bt: seq_coll.a2a_overflow(
                st.tables, {"item": bt["item"]}))
        if (cfg.lookup_mode == "alltoall" and self._counters_on
                and not cfg.jagged and cfg.steps_per_execution == 1):
            fill_coll = self.coll
            self._a2a_fill = jax.jit(lambda st, bt: fill_coll.a2a_fill_stats(
                st.tables, {"item": bt["item"]}))
        self._stream_cls = ParquetStream  # seq ETL writes parquet only
        self._train_pattern = str(Path("parquet_bert4rec") / cfg.train_data)
        self._eval_pattern = str(Path("parquet_bert4rec") / cfg.eval_data)

        # eval accumulator built ONCE (a fresh jit closure per eval epoch
        # would recompile every time), honouring the configured lookup
        # program, and folding metrics on device — multihost-global by
        # construction (see _make_ctr_eval_accum's docstring).
        from tdfo_tpu.data.seq_preprocessing import EVAL_NEG_NUM
        from tdfo_tpu.models.bert4rec import key_padding_mask
        from tdfo_tpu.train.seq import score_candidates

        self._eval_schema = {
            "seqs": (np.int32, (cfg.max_len,)),
            "cands": (np.int32, (EVAL_NEG_NUM + 1,)),
        }
        coll, backbone, mode = self.coll, self.backbone, cfg.lookup_mode

        @jax.jit
        def eval_accum(state, batch, acc):
            w = batch["_weight"]
            embs = coll.lookup(state.tables, {"item": batch["seqs"]}, mode=mode)
            logits = backbone.apply(
                {"params": state.dense_params}, embs["item"],
                key_padding_mask(batch["seqs"]),
            )
            scores = score_candidates(logits, batch["cands"])
            labels = jnp.zeros_like(scores).at[:, 0].set(1.0)
            # ks from the same constant that seeds the accumulator dict
            m = recalls_and_ndcgs_for_ks(scores, labels, ks=self._METRIC_KS,
                                         row_weights=w)
            out = {"w_sum": acc["w_sum"] + w.sum()}
            for k, v in m.items():
                out[k] = acc[k] + v * w.sum()
            return out

        self.eval_accum = eval_accum

    def _build_causal_lm(self) -> None:
        """A decoder (``Config.is_causal_lm``: ``models/olmo_hybrid``,
        ``models/nemotron_h``, ``models/sdar_moe``, ``models/deepseek_v3``)
        in the DMP regime, wired
        as ``_build_bert4rec`` wires its model-parallel one: the vocabulary
        (slice) as one table of the collection (under the fused threshold it
        is a plain table: gather lookup, row-sparse Adam on touched rows),
        backbone and head as dense leaves under AdamW, one
        ``make_sparse_train_step`` whose forward is the family's
        ``forward_loss``, which owns its loss (next-token cross-entropy, or
        block diffusion's over masked positions).  The family's module
        supplies the configuration (``LmConfig``, from the ``[lm]`` keys of
        its fields' names), the initialiser (``init_params``),
        ``forward_loss`` (given the devices' ``platform``, by which a layer
        may pick a kernel: ``core/mesh.pallas_impl``), the counters its
        step returns beside the loss (``STEP_COUNTERS``: tallied into the
        epoch record where the loss is fetched), the leaves that are
        buffers (``BUFFERS``: no decay) and, where its loss needs more than
        the packed ids, ``prepare_batch``: run inside the step on the
        step's key before the lookup, it may replace the ids (block
        diffusion's noisy copy beside the clean one) and add arrays, which
        reach ``forward_loss`` by name.  The dense
        state is many GiB, so unlike the other builders this one DONATES
        the step's state (config requires ``nonfinite_tolerance = 0``: the
        guard's second copy has no room), rematerialises by layer (the
        family's ``backbone``) and computes in bfloat16 on TPU under
        ``mixed_precision`` with float32 parameters, optimizer state and
        softmax."""
        import importlib

        from tdfo_tpu.core.precision import compute_dtype
        from tdfo_tpu.ops.sparse import sparse_optimizer
        from tdfo_tpu.parallel.embedding import (
            EmbeddingSpec, ShardedEmbeddingCollection)
        from tdfo_tpu.train.sparse_step import (
            SparseTrainState, make_sparse_train_step)

        family = importlib.import_module(
            f"tdfo_tpu.models.{self.config.model}")
        cfg, lm = self.config, self.config.lm
        # the [lm] table's keys are the model configuration's own (its chunk
        # and block sizes are the model's constants, measured on the v5e)
        reads = {f.name for f in dataclasses.fields(family.LmConfig)}
        other = sorted(f.name for f in dataclasses.fields(lm)
                       if f.name not in reads
                       and getattr(lm, f.name) != f.default)
        if other:
            raise ValueError(
                f"model = \"{cfg.model}\" does not read [lm] {other}")
        self.model_cfg = family.LmConfig(**{k: getattr(lm, k) for k in reads})
        sharding = cfg.embedding_sharding if cfg.model_parallel else "replicated"
        fused_at = cfg.effective_fused_threshold
        self.coll = ShardedEmbeddingCollection(
            [EmbeddingSpec(
                "token_embedding", num_embeddings=lm.vocab_size,
                embedding_dim=lm.hidden_size, features=("token",),
                sharding=sharding, init_scale=0.02,
                fused=(fused_at is not None
                       and sharding in ("row", "replicated")
                       and lm.vocab_size > fused_at))],
            mesh=self.mesh, fused_kind=cfg.sparse_optimizer)
        k_table, k_dense = jax.random.split(jax.random.key(cfg.seed))
        with obs_trace.phase("table_init"):
            tables = self.coll.init(k_table)
        with obs_trace.phase("dense_init"):
            # initialised under jit so that every leaf is made where it lives
            dense = jax.jit(lambda k: family.init_params(k, self.model_cfg),
                            out_shardings=NamedSharding(self.mesh, P()))(k_dense)
        # a buffer (a router's selection bias) gets no gradient and no decay:
        # under AdamW it stands still.  A family without one keeps the
        # optimizer (and so its state's tree) as it was
        decayed = jax.tree_util.tree_map_with_path(
            lambda path, _: path[-1].key not in family.BUFFERS,
            dense) if family.BUFFERS else None
        with obs_trace.phase("opt_init"):
            self.state = _commit_replicated(SparseTrainState.create(
                dense_params=dense,
                tx=optax.adamw(cfg.learning_rate, b2=_LM_ADAM_B2,
                               weight_decay=cfg.weight_decay, mask=decayed),
                tables=tables,
                # the table takes Adam without decay (no decay on embeddings)
                sparse_opt=sparse_optimizer(
                    cfg.sparse_optimizer, lr=cfg.learning_rate,
                    weight_decay=0.0, b2=_LM_ADAM_B2),
            ), self.mesh)
        platform = mesh_platform(self.mesh)
        dtype = compute_dtype(cfg.mixed_precision, platform)
        model_cfg = self.model_cfg
        prepare = getattr(family, "prepare_batch", None)
        transform = None if prepare is None else (
            lambda batch, rng: prepare(batch, rng, model_cfg))

        def forward(dense_params, embs, batch):
            added = {k: v for k, v in batch.items()
                     if k not in ("token", "segment")}
            return family.forward_loss(
                dense_params, embs["token"], batch["token"], batch["segment"],
                model_cfg, dtype=dtype, platform=platform, **added)

        # with counters the forward returns (loss, counters) and the step
        # (state, (loss, counters)): _train_epoch takes them apart
        self._step_counters = tuple(family.STEP_COUNTERS)
        with obs_trace.phase("step_build"):
            step = make_sparse_train_step(
                self.coll, forward, mode=cfg.lookup_mode,
                jit=not self._counters_on, dedup_lookup=cfg.dedup_lookup,
                with_aux=bool(self._step_counters), batch_transform=transform)
            self.train_step = (_wrap_counters_step(step, donate_state=True)
                               if self._counters_on else step)
        self._train_auc_enabled = False
        self._dropout_rng = jax.random.key(cfg.seed + 1)  # the step's rng slot
        self._stream_cls = ParquetStream
        self._train_pattern = str(Path("parquet_lm") / cfg.train_data)
        self._eval_pattern = str(Path("parquet_lm") / cfg.eval_data)
        coll, mode, rng = self.coll, cfg.lookup_mode, self._dropout_rng

        @jax.jit
        def eval_loss(state, batch):
            if transform is not None:   # the noise the next step would draw
                batch = transform(batch, jax.random.fold_in(rng, state.step))
            embs = coll.lookup(state.tables, {"token": batch["token"]}, mode=mode)
            out = forward(state.dense_params, embs, batch)
            return out[0] if self._step_counters else out

        self._eval_loss = eval_loss

    # --------------------------------------------------------------- epochs

    def _stream(self, pattern: str, *, train: bool):
        cfg = self.config
        files = resolve_files(cfg.data_dir, pattern)
        # each host streams only its local slice of the global batch: the
        # data axis spans every host's devices, and prefetch_to_mesh
        # assembles the global array from per-process chunks.
        local_data = max(1, self.mesh.shape["data"] // jax.process_count())
        bsz = (cfg.per_device_train_batch_size if train
               else cfg.per_device_eval_batch_size) * local_data
        if not cfg.streaming:
            # map-style in-memory epochs (config streaming=false,
            # jax-flax/train.py:52-70 parity); table cached across epochs
            key = (pattern, bsz, train)
            if key not in self._map_streams:
                self._map_streams[key] = MapStream(
                    files, batch_size=bsz, shuffle=train, seed=cfg.seed,
                    drop_last=train,
                )
            return self._map_streams[key]
        return self._stream_cls(
            files,
            batch_size=bsz,
            shuffle=train,
            buffer_size=cfg.shuffle_buffer_size,
            seed=cfg.seed,
            drop_last=train,
            # eval shards are always fixed-length (padded seqs + candidate
            # lists); only the jagged TRAIN stream opts into object columns
            allow_ragged=train and cfg.model == "bert4rec" and cfg.jagged,
            num_workers=cfg.num_workers,
            max_bad_shards=cfg.max_bad_shards,
        )

    def _train_batches(self, epoch: int, skip: int = 0) -> Iterator[tuple[dict, int]]:
        """Yields ``(device_batch, n_steps_in_batch)``.

        With ``steps_per_execution > 1`` host batches are stacked into
        [K, B, ...] chunks and the whole chunk ships as one transfer feeding
        one compiled multi-step dispatch; a short tail chunk recompiles at
        most once per distinct K.

        ``skip`` resumes mid-epoch: the stream fast-forwards that many host
        batches (the checkpoint cursor's step count) before yielding, so the
        post-resume batch sequence is bit-identical to the uninterrupted
        epoch's tail.  With spe>1 the chunk BOUNDARIES shift relative to the
        uninterrupted run, but a chunk is a ``lax.scan`` of the same single
        step over the same ordered batches — state evolution is unchanged.
        """
        cfg = self.config
        stream = self._stream(self._train_pattern, train=True)
        stream.set_epoch(epoch)
        if skip:
            stream.load_state_dict({"seed": cfg.seed, "epoch": epoch,
                                    "batches_emitted": skip})
        if cfg.model == "bert4rec" and cfg.jagged:
            from tdfo_tpu.data.jagged import pack_rows

            cap = stream.batch_size * cfg.max_len  # static host capacity

            def pack(b):
                iv, il = pack_rows(list(b["train_interactions"]), cap)
                lv, ll = pack_rows(list(b["labels"]), cap)
                if (il != ll).any():  # data integrity, must survive python -O
                    raise ValueError(
                        "item/label window lengths diverged — mixed-version "
                        "or corrupted jagged shards"
                    )
                return {"item_values": iv, "item_lengths": il, "label_values": lv}

            renamed = (pack(b) for b in stream)
        elif cfg.model == "bert4rec":
            renamed = (
                {"item": b["train_interactions"], "label": b["labels"]} for b in stream
            )
        elif cfg.is_causal_lm:
            renamed = _count_lm_batches(stream, cfg.max_len)
        else:
            renamed = iter(stream)
        inj = _faults.active()
        if inj is not None and inj.spec.nan_at_step:
            # deterministic NaN injection keyed on run-global data position
            # (stable across resume and steps_per_execution regrouping);
            # _logged_steps still holds the epoch-start value here — the
            # epoch-end += happens after this generator is exhausted
            base, poison = self._logged_steps, inj.poison_batch

            def poisoned(gen, pos):
                for b in gen:
                    pos += 1
                    yield poison(b, base + pos)

            renamed = poisoned(renamed, skip)
        spe = cfg.steps_per_execution
        if spe <= 1:
            for batch in prefetch_to_mesh(renamed, self.mesh, P("data")):
                yield batch, 1
            return

        def stacked():
            chunk: list[dict] = []
            for b in renamed:
                chunk.append(b)
                if len(chunk) == spe:
                    yield {k: np.stack([c[k] for c in chunk]) for k in chunk[0]}
                    chunk = []
            if chunk:
                yield {k: np.stack([c[k] for c in chunk]) for k in chunk[0]}

        for stack in prefetch_to_mesh(stacked(), self.mesh, P(None, "data")):
            yield stack, int(next(iter(stack.values())).shape[0])

    def _jit_ctx(self):
        """jit_xla = false -> the loop runs under jax.disable_jit(): op-by-op
        eager execution for numerics debugging (TF jit_compile=False parity)."""
        import contextlib

        if self.config.jit_xla is False:
            return jax.disable_jit()
        return contextlib.nullcontext()

    def train_epoch(self, epoch: int, *, start_step: int = 0,
                    loss_sum: float = 0.0, contributed: int = 0) -> float:
        with self._jit_ctx(), obs_trace.epoch_phases(epoch) as phases:
            return self._train_epoch(epoch, phases, start_step=start_step,
                                     loss_sum=loss_sum, contributed=contributed)

    def _train_epoch(self, epoch: int, phases: obs_trace.epoch_phases, *,
                     start_step: int = 0, loss_sum: float = 0.0,
                     contributed: int = 0) -> float:
        """One training epoch, resumable at step granularity.

        ``start_step`` (plus the matching partial ``loss_sum``/``contributed``
        from the checkpoint cursor) restarts the epoch at an exact batch; the
        stream fast-forwards, so the tail is bit-identical to an
        uninterrupted epoch.  Device losses queue in a pending window and are
        fetched together at log/checkpoint boundaries — the same sync cadence
        as before (a per-step ``float()`` would serialise dispatch and defeat
        the double-buffered prefetch), so the non-finite guard below adds NO
        extra host round-trips.

        Non-finite guard: with ``nonfinite_tolerance`` = K > 0, a known-good
        (state, train-AUC, loss-sums) snapshot is kept ON DEVICE — refreshed
        every ``snapshot_every_n_steps`` once the window since the last
        snapshot verified finite — and K consecutive non-finite batch losses
        roll back to it, SKIPPING the offending batch window (data position
        stays monotone; ``state.step`` rewinds).  Each rollback emits a
        ``rollback`` record to metrics.jsonl.  The snapshot costs one extra
        state copy in device memory; set ``nonfinite_tolerance = 0`` to
        disable the guard (and the copy) on memory-tight runs.

        Host-loop time is kept by ``phases`` (``obs.trace.epoch_phases``,
        opened by ``train_epoch`` round this call): ``obs_trace.phase``
        names where the loop waits — ``epoch_open`` (entry to the first
        batch in hand), ``next_batch`` (inside ``prefetch_to_mesh``: the
        wait for its queue of host batches, and child ``h2d_put``),
        ``dispatch``, ``loss_sync`` (child ``guard_snapshot``),
        ``cache_flush``, ``checkpoint_save``, ``epoch_close`` — each also a
        ``tdfo:<name>`` span in a profiler trace; the epoch line of
        metrics.jsonl carries their seconds.  ``loader_next`` is on the same
        line but not of this thread: ``prefetch_to_mesh``'s producer thread
        decodes beside the loop and joins this epoch's record;
        ``prefetch_empty_takes`` and ``prefetch_depth_mean`` say whether it
        kept ahead.
        """
        cfg = self.config
        inj = _faults.active()
        phase = obs_trace.phase
        n_steps = start_step
        step_ctrs: dict = {}  # latest step's device counter pytree
        # update-cache write-back schedule: the periodic flush runs async
        # (overflow counters queue like the pending losses and are verified
        # at the same cadence — no extra host sync); checkpoint/eval/epoch
        # boundaries flush synchronously
        flush_n = self._flush_every if self._cache_flush is not None else 0
        next_flush = (n_steps // flush_n + 1) * flush_n if flush_n else None
        pending_over: list[dict] = []
        pending_counts: list[dict] = []  # a decoder step's device counters
        next_log = start_step + cfg.log_every_n_steps
        profiled = cfg.profile and epoch == 0 and jax.process_index() == 0
        train_auc = None
        # pipeline_overlap carry: (transformed batch, input-dist ctx) one
        # batch ahead of training.  Not persisted in cursors: n_steps counts
        # TRAINED batches, so a resume fast-forwards past exactly those and
        # re-primes on the batch the carry held — state evolution is
        # bit-identical to the uninterrupted run.
        carry = None
        tol = cfg.nonfinite_tolerance
        guard = tol > 0
        # pending: (device loss, steps in batch, global data step)
        pending: list[tuple[jax.Array, int, int]] = []
        pending_steps = 0
        flush_every = max(1, cfg.log_every_n_steps)
        consec_bad = 0
        snap = None  # (state, auc, loss_sum, contributed, global data step)
        steps_at_snap = n_steps

        def flush_checks() -> None:
            """Fetch queued losses: fold finite ones into the epoch sums,
            roll back on ``tol`` consecutive non-finite steps, refresh the
            snapshot after a clean window."""
            with phase("loss_sync"):  # the host blocked on the device
                _flush_checks()

        def _flush_checks() -> None:
            nonlocal loss_sum, contributed, consec_bad, snap, train_auc
            nonlocal steps_at_snap, pending_steps
            for over in pending_over:
                _check_cache_overflow(over)
            pending_over.clear()
            for counted in jax.device_get(pending_counts):
                for name, value in counted.items():
                    obs_trace.tally(name, int(value))
            pending_counts.clear()
            rolled = False
            for loss_dev, k, gstep in pending:
                v = float(loss_dev)
                if math.isfinite(v):
                    consec_bad = 0
                    loss_sum += v * k
                    contributed += k
                    continue
                consec_bad += k  # non-finite losses never fold into the sums
                if not guard or consec_bad < tol:
                    continue
                # bounded rollback: restore the last known-good snapshot
                # (device copy, no disk) and keep consuming data FORWARD —
                # the poisoned window is skipped, not retried
                state_c, auc_c, ls, ct, sg = snap
                with phase("guard_snapshot"):
                    # the snapshot must survive donation
                    self.state = _copy_tree(state_c)
                    train_auc = _copy_tree(auc_c)
                loss_sum, contributed = ls, ct
                consec_bad = 0
                rolled = True
                self.logger.log(
                    epoch=epoch, rollback=1, global_step=gstep,
                    restored_to_step=sg, skipped_steps=gstep - sg,
                    nonfinite_loss=v,
                )
                break  # later pending losses came from the poisoned lineage
            pending.clear()
            pending_steps = 0
            if (guard and not rolled and consec_bad == 0
                    and n_steps - steps_at_snap >= cfg.snapshot_every_n_steps):
                with phase("guard_snapshot"):
                    snap = (_copy_tree(self.state), _copy_tree(train_auc),
                            loss_sum, contributed,
                            self._logged_steps + n_steps)
                steps_at_snap = n_steps

        ckpt_n = cfg.checkpoint_every_n_steps if self._ckpt is not None else 0
        next_ckpt = (n_steps // ckpt_n + 1) * ckpt_n if ckpt_n else None
        loss = None
        with phase("epoch_open"):
            # train-side streaming AUC on this epoch's predictions, folded ON
            # DEVICE from the step's aux logits — no second forward pass
            # (jax-flax/train_dp.py:190,219-220 parity).  Not persisted in
            # the cursor (device histograms): after a mid-epoch resume the
            # epoch AUC covers post-resume steps only.  State evolution is
            # unaffected.
            if self._train_auc_enabled:
                train_auc = self._fresh_accumulator(AUC.empty())
            if guard:
                snap = (_copy_tree(self.state), _copy_tree(train_auc),
                        loss_sum, contributed, self._logged_steps + n_steps)
            # stream opened, pool filled, the first puts in flight
            batches = iter(self._train_batches(epoch, skip=start_step))
            item = next(batches, None)
        try:
            while item is not None:
                batch, k = item
                if profiled is True and n_steps >= 10:
                    # no Python tracer: it slows a host-bound loop by a
                    # large factor; the host side is the tdfo: spans
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    jax.profiler.start_trace(
                        str(Path(cfg.checkpoint_dir or ".") / "profile"),
                        profiler_options=options)
                    profiled = "tracing"
                with phase("dispatch"):
                    if self._pipelined and carry is None:
                        # pipeline prime: the first batch's input-dist only;
                        # training starts next iteration
                        carry = self._prime_step(batch)
                        out = None
                    elif self._pipelined:
                        if cfg.model == "bert4rec":
                            out = self.train_step(
                                self.state, batch, carry, self._dropout_rng)
                            self.state, loss, carry = out[:3]
                        else:
                            out = self.train_step(
                                self.state, batch, carry, train_auc)
                            self.state, loss, carry, train_auc = out[:4]
                    elif cfg.model == "bert4rec" or cfg.is_causal_lm:
                        out = self.train_step(
                            self.state, batch, self._dropout_rng)
                        self.state, loss = out[:2]
                        if self._step_counters:
                            loss, counted = loss
                            pending_counts.append(counted)
                    else:
                        out = self.train_step(self.state, batch, train_auc)
                        self.state, loss, train_auc = out[:3]
                if out is None:  # primed: nothing trained yet
                    with phase("next_batch"):
                        item = next(batches, None)
                    continue
                if self._counters_on:
                    # DEVICE dict (the step's extra return) — floats are
                    # pulled at the log boundary with the train_loss fetch
                    step_ctrs = out[-1]
                n_steps += k
                gstep = self._logged_steps + n_steps
                if self._watchdog is not None:
                    self._watchdog.beat(gstep)
                pending.append((loss, k, gstep))
                pending_steps += k
                if next_flush is not None and n_steps >= next_flush:
                    # coalesced cache write-back: the ONLY big-table scatter
                    # in the cadence — one per flush_every steps
                    with phase("cache_flush"):
                        pending_over.append(self._run_cache_flush())
                    next_flush = (n_steps // flush_n + 1) * flush_n
                if pending_steps >= flush_every:
                    flush_checks()
                if profiled == "tracing" and n_steps >= 20:
                    jax.block_until_ready(loss)
                    jax.profiler.stop_trace()
                    profiled = False
                if next_ckpt is not None and n_steps >= next_ckpt:
                    # never persist an unverified window: flushing first means
                    # a detected-NaN state rolls back BEFORE the save; force
                    # overwrites a step id a prior (crashed) run already wrote
                    flush_checks()
                    # cache flush BEFORE the save (post-rollback state):
                    # checkpoints always hold flushed tables, so restores
                    # and exports never depend on cache contents
                    self._flush_cache_sync()
                    with phase("checkpoint_save"):
                        self._ckpt.save(
                            gstep, self.state, force=True,
                            cursor={"epoch": epoch, "step": n_steps,
                                    "epoch_complete": False,
                                    "global_step": gstep,
                                    "loss_sum": loss_sum,
                                    "contributed": contributed},
                            stamps=self._ckpt_stamps,
                        )
                    next_ckpt = (n_steps // ckpt_n + 1) * ckpt_n
                if inj is not None:
                    inj.maybe_stall(gstep)  # host-side sleep (watchdog test)
                    inj.maybe_kill(gstep)  # after the save: ckpt is durable
                if n_steps >= next_log:
                    with phase("loss_sync"):
                        rec = self._step_record(epoch, n_steps, loss, batch,
                                                step_ctrs)
                    # TB charts need a run-global x (per-epoch `step` resets,
                    # which would fold multi-epoch curves back on themselves)
                    rec["global_step"] = gstep
                    self.logger.log(**rec)
                    # device-memory watermark at the log cadence (no-op on
                    # backends without memory_stats, e.g. spoofed CPU)
                    if obs_events.active():
                        obs_events.memory_snapshot()
                    # chunked counting can jump n_steps past several
                    # intervals; advance past n_steps so each interval logs
                    # at most once
                    next_log = n_steps + cfg.log_every_n_steps
                with phase("next_batch"):
                    item = next(batches, None)
            if self._pipelined and carry is not None:
                # drain the pipeline: the last carried batch trains here
                # (flush is prime's twin — together they shift every batch's
                # training one call later without changing its math)
                with phase("dispatch"):
                    if cfg.model == "bert4rec":
                        out = self._flush_step(
                            self.state, carry, self._dropout_rng)
                        self.state, loss = out[:2]
                    else:
                        out = self._flush_step(self.state, carry, train_auc)
                        self.state, loss, train_auc = out[:3]
                carry = None
                n_steps += 1
                pending.append((loss, 1, self._logged_steps + n_steps))
                pending_steps += 1
        finally:
            if profiled == "tracing":
                # epoch ended (or raised) inside the trace window: close the
                # trace so the next epoch/run can profile again
                if loss is not None:
                    jax.block_until_ready(loss)
                jax.profiler.stop_trace()
        extra: dict[str, float] = {}
        with phase("epoch_close"):
            flush_checks()
            self._flush_cache_sync()  # epoch boundary: leave the tables flushed
            if train_auc is not None and n_steps:
                extra["train_auc"] = float(train_auc.result())
        ran = n_steps - start_step  # steps actually executed THIS session
        # the epoch's clock stops before the line is written: the line
        # carries what the clock read
        timed = phases.close(ran)
        self._logged_steps += n_steps
        avg = loss_sum / contributed if contributed else 0.0
        for name, (seconds, _, longest) in timed["phases"].items():
            extra[f"phase_{name}_s"] = seconds
            if name == "next_batch":
                extra["phase_next_batch_max_ms"] = 1e3 * longest
        tallies = dict(timed["tallies"])
        depth_sum, takes = tallies.pop("prefetch_depth", (0.0, 0))
        empty = tallies.pop("prefetch_empty_takes", (0.0, 0))[1]
        if takes:
            extra["prefetch_depth_mean"] = depth_sum / takes
            extra["prefetch_empty_takes"] = empty
        extra.update(_tally_fields(tallies))  # lm_tokens, jit_*, ...
        self.logger.log(
            epoch=epoch, train_loss_epoch=avg, steps=n_steps,
            examples_per_sec=ran * cfg.per_device_train_batch_size
            * self.mesh.shape["data"] / max(timed["loop_s"], 1e-9),
            loop_s=timed["loop_s"], **extra,
        )
        return avg

    def _step_record(self, epoch: int, n_steps: int, loss, batch,
                     step_ctrs: dict) -> dict:
        """The log-cadence line's device values, fetched: the loss and,
        where they are on, the a2a and telemetry counters."""
        rec = dict(epoch=epoch, step=n_steps, train_loss=float(loss))
        if self._a2a_overflow is not None:
            # ids dropped by the finite a2a capacity THIS batch
            # (zero vectors under skew — watch for quality decay)
            rec["a2a_overflow_ids"] = int(
                self._a2a_overflow(self.state, batch))
        if self._counters_on:
            # ONE host fetch of the latest step's counter pytree
            # — the same boundary the train_loss float() above
            # already syncs on, so the cadence is unchanged
            for ck, cv in {**step_ctrs, **self._flush_ctrs}.items():
                rec[ck] = float(cv)
            for ck in [c for c in rec if c.endswith("cache_hit_rows")]:
                base = ck[: -len("hit_rows")]
                tot = rec[ck] + rec.get(base + "miss_rows", 0.0)
                if tot:
                    rec[base + "hit_rate"] = rec[ck] / tot
            if self._a2a_fill is not None:
                fill, dropped = self._a2a_fill(self.state, batch)
                rec["a2a_fill"] = float(fill)
                rec["a2a_dropped_ids"] = int(dropped)
        return rec

    def _fresh_accumulator(self, tree):
        """A zeroed on-device accumulator (train AUC, eval sums), placed as
        the step hands it back: replicated on the mesh.  Left uncommitted,
        the first call compiles one program for it and the second call —
        now fed the committed output — compiles the same step again."""
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def _run_cache_flush(self) -> dict:
        """One cache write-back dispatch.  With telemetry counters on, the
        flush program returns a third element (the flush-scoped counter
        dict) — stash it for the next log boundary.  Returns overflow."""
        if self._counters_on:
            self.state, over, self._flush_ctrs = self._cache_flush(self.state)
        else:
            self.state, over = self._cache_flush(self.state)
        return over

    def _flush_cache_sync(self) -> None:
        """Write the update cache back NOW and verify zero admission
        overflow — the synchronous flush used at checkpoint, eval, and
        epoch boundaries (no-op when the cache is off)."""
        if self._cache_flush is None:
            return
        with obs_trace.phase("cache_flush"):  # dispatch + the overflow fetch
            _check_cache_overflow(self._run_cache_flush())

    # ----------------------------------------------------------------- eval

    def evaluate(self, epoch: int) -> dict[str, float]:
        # the eval step reads state.tables directly; flush first so it
        # never sees values the cache holds (bit-equal to an eager run)
        self._flush_cache_sync()
        with self._jit_ctx():
            if self.config.model == "bert4rec":
                return self._evaluate_bert4rec(epoch)
            if self.config.is_causal_lm:
                return self._evaluate_lm(epoch)
            return self._evaluate_twotower(epoch)

    def _eval_batches(self, rename: Callable[[dict], dict] | None = None,
                      pattern: str | None = None) -> Iterator[dict]:
        """Padded, budgeted, mesh-sharded eval batches.

        Every host yields exactly ``max_batches_per_host()`` batches — short
        hosts (including hosts with NO eval rows at all) top up with
        zero-weight template batches synthesised from ``self._eval_schema``
        — so the jitted eval computation (a global-mesh program) runs in
        lockstep and never deadlocks (the drop_last=False twin of the
        train-loop invariant).  Real batches are restricted to the schema's
        keys so every host ships an identical pytree regardless of which
        extra columns its files carry.  Each batch has a ``_weight`` row
        mask.
        """
        stream = self._stream(pattern or self._eval_pattern, train=False)
        budget = stream.max_batches_per_host()
        bsz = stream.batch_size
        schema = self._eval_schema

        def template() -> dict[str, np.ndarray]:
            t = {k: np.zeros((bsz, *shape), dtype) for k, (dtype, shape) in schema.items()}
            t["_weight"] = np.zeros((bsz,), np.float32)
            return t

        def gen():
            n = 0
            for raw in stream:
                if rename is not None:
                    try:
                        raw = rename(raw)
                    except KeyError as e:
                        raise ValueError(
                            f"eval shard is missing column {e} "
                            f"(has {sorted(raw)}); it was likely written by "
                            "an older or mismatched preprocessing run — "
                            "re-run preprocessing for this data_dir"
                        ) from None
                # cast to the schema dtypes: loaders differ (tfrecord decodes
                # ints as int64, parquet as int32/int8) and real batches must
                # be aval-identical to synthesized templates on EVERY host
                missing = schema.keys() - raw.keys()
                if missing:
                    raise ValueError(
                        f"eval shard is missing columns {sorted(missing)} "
                        f"(has {sorted(raw)}); it was likely written by an "
                        "older or mismatched preprocessing run — re-run "
                        "preprocessing for this data_dir"
                    )
                real = {
                    k: np.asarray(raw[k]).astype(dtype, copy=False)
                    for k, (dtype, _) in schema.items()
                }
                batch, w = pad_batch(real, bsz)
                batch = dict(batch, _weight=w)
                n += 1
                yield batch
            while n < budget:
                yield template()
                n += 1

        yield from prefetch_to_mesh(gen(), self.mesh, P("data"))

    def _evaluate_twotower(self, epoch: int) -> dict[str, float]:
        """Eval metrics accumulate ON DEVICE as a replicated pytree; the host
        fetches floats once at the end.  Every reduction is global across the
        whole mesh (multi-host included), so this is the ``all_gather_object``
        capability (``torchrec/train.py:108-111``) with zero host collectives
        — and no per-batch ``float()`` sync stalling the eval pipeline."""
        acc = self._fresh_accumulator({
            "loss_sum": jnp.zeros(()),
            "w_sum": jnp.zeros(()),
            "auc": AUC.empty(),
        })
        for batch in self._eval_batches():
            acc = self.eval_accum(self.state, batch, acc)
            if self._watchdog is not None:  # eval batches count as liveness
                self._watchdog.beat(self._logged_steps)
        w = max(float(acc["w_sum"]), 1.0)
        metrics = {
            "eval_loss": float(acc["loss_sum"]) / w,
            "auc": float(acc["auc"].result()),
        }
        self.logger.log(epoch=epoch, **metrics)
        return metrics

    def _evaluate_lm(self, epoch: int) -> dict[str, float]:
        """Mean loss (the family's: next-token, or block diffusion's) over
        the eval shards' whole batches (a batch weighs the same whatever its
        labelled positions)."""
        stream = self._stream(self._eval_pattern, train=False)
        losses = []
        full = (b for b in _count_lm_batches(stream, self.config.max_len)
                if len(b["token"]) == stream.batch_size)
        for batch in prefetch_to_mesh(full, self.mesh, P("data")):
            losses.append(self._eval_loss(self.state, batch))
        if not losses:
            return {}
        metrics = {"eval_loss": float(jnp.mean(jnp.stack(losses)))}
        self.logger.log(epoch=epoch, **metrics)
        return metrics

    _METRIC_KS = (10, 20, 50)

    def _evaluate_bert4rec(self, epoch: int, pattern: str | None = None,
                           prefix: str = "") -> dict[str, float]:
        acc: dict[str, jax.Array] = {"w_sum": jnp.zeros(())}
        for k in self._METRIC_KS:
            acc[f"Recall@{k}"] = jnp.zeros(())
            acc[f"NDCG@{k}"] = jnp.zeros(())
        acc = self._fresh_accumulator(acc)
        rename = lambda raw: {"seqs": raw["eval_seqs"], "cands": raw["candidate_items"]}
        for batch in self._eval_batches(rename, pattern=pattern):
            acc = self.eval_accum(self.state, batch, acc)
            if self._watchdog is not None:  # eval batches count as liveness
                self._watchdog.beat(self._logged_steps)
        w = max(float(acc.pop("w_sum")), 1.0)
        metrics = {prefix + k: float(v) / w for k, v in acc.items()}
        self.logger.log(epoch=epoch, **metrics)
        return metrics

    def evaluate_test(self) -> dict[str, float]:
        """Final held-out TEST evaluation (bert4rec leave-last-one).

        Beats the reference's dead code: ``train_val_test`` never tests
        despite its name (``torchrec/train.py:147-177``).  Returns {} when
        the data dir has no test shards (older preprocessing runs) or the
        knob is disabled.  Runs the same lockstep-budgeted eval machinery,
        so multi-host meshes stay in step.
        """
        cfg = self.config
        if cfg.model != "bert4rec" or not cfg.test_data:
            return {}
        pattern = str(Path("parquet_bert4rec") / cfg.test_data)
        try:
            resolve_files(cfg.data_dir, pattern)
        except FileNotFoundError:
            self.logger.log(test_split="absent (re-run preprocess-seq to write it)")
            return {}
        with self._jit_ctx():
            return self._evaluate_bert4rec(
                epoch=self.config.n_epochs, pattern=pattern, prefix="test_"
            )

    # ------------------------------------------------------------------ fit

    def fit(self) -> dict[str, float]:
        """Train/eval until ``n_epochs``, resuming from the newest checkpoint.

        Resume is cursor-aware: a mid-epoch checkpoint (written every
        ``checkpoint_every_n_steps``) re-enters its epoch at the exact batch
        — the data stream fast-forwards, so a killed-and-restarted run
        replays the identical batch sequence and lands on bit-identical
        state.  Checkpoints without a cursor sidecar are the legacy
        epoch-indexed format and resume at the following epoch."""
        cfg = self.config
        if self._watchdog is not None:
            self._watchdog.start()
        start_epoch = 0
        resume = {"step": 0, "loss_sum": 0.0, "contributed": 0}
        if self._ckpt is not None:
            restored = self._ckpt.restore(self.state,
                                          stamps=self._ckpt_stamps)
            if restored is not None:
                step_id, self.state, cursor = restored
                if cursor is None:
                    # legacy epoch-indexed checkpoint: step_id IS the epoch
                    start_epoch = step_id + 1
                    self.logger.log(resumed_from_epoch=step_id)
                elif cursor.get("epoch_complete"):
                    start_epoch = int(cursor["epoch"]) + 1
                    self._logged_steps = int(cursor["global_step"])
                    self.logger.log(resumed_from_epoch=int(cursor["epoch"]),
                                    global_step=self._logged_steps)
                else:
                    start_epoch = int(cursor["epoch"])
                    resume = {"step": int(cursor["step"]),
                              "loss_sum": float(cursor.get("loss_sum", 0.0)),
                              "contributed": int(cursor.get("contributed", 0))}
                    self._logged_steps = (int(cursor["global_step"])
                                          - resume["step"])
                    self.logger.log(resumed_mid_epoch=start_epoch,
                                    step=resume["step"],
                                    global_step=int(cursor["global_step"]))
        metrics: dict[str, float] = {}
        try:
            if cfg.model == "bert4rec" and start_epoch == 0 and not resume["step"]:
                # pre-training validation sanity floor (torchrec/train.py:159)
                self.evaluate(epoch=-1)
            for epoch in range(start_epoch, cfg.n_epochs):
                self.train_epoch(epoch, start_step=resume["step"],
                                 loss_sum=resume["loss_sum"],
                                 contributed=resume["contributed"])
                resume = {"step": 0, "loss_sum": 0.0, "contributed": 0}
                metrics = self.evaluate(epoch)
                if epoch == start_epoch and obs_events.active():
                    # every program of the steady-state cadence (train step,
                    # cache flush, eval accum) has compiled by the end of the
                    # first epoch+eval cycle; later compiles are retraces
                    obs_events.mark_warmup()
                if self._ckpt is not None and (
                    (epoch + 1) % cfg.checkpoint_every_n_epochs == 0
                    or epoch == cfg.n_epochs - 1
                ):
                    # checkpoint ids live in the global data-step namespace
                    # (shared with mid-epoch saves); force overwrites a
                    # mid-epoch save that landed on the same step
                    gstep = self._logged_steps
                    self._ckpt.save(
                        gstep, self.state, force=True,
                        cursor={"epoch": epoch, "step": 0,
                                "epoch_complete": True, "global_step": gstep},
                        stamps=self._ckpt_stamps,
                    )
            # final held-out test evaluation (bert4rec; no-op elsewhere)
            metrics.update(self.evaluate_test())
        finally:
            # crash or success: release the JSONL/TB handles and the orbax
            # manager's background machinery (both leaked on error before),
            # stop the watchdog thread, and detach the compile-event handler
            # (with the run-peak device-memory watermark as its last record)
            if self._watchdog is not None:
                self._watchdog.stop()
            if obs_events.active():
                obs_events.record("run_summary",
                                  peak_bytes=obs_events.peak_memory())
                obs_events.configure(None)
            if obs_trace.active():
                obs_trace.configure(None)
            self.logger.close()
            if self._ckpt is not None:
                self._ckpt.close()
        return metrics
