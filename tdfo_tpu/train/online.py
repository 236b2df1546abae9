"""The online-learning supervisor: serve -> retrain -> delta-export -> swap.

Monolith (§3.3) keeps CTR models fresh by feeding served traffic back into
training and streaming parameter deltas to the serving fleet; torchrec's
streaming-retrain loop is the same shape.  This module closes that loop for
this repo: it tails the frontend's request log through the crash-safe
``ReplayConsumer`` (``data/replay.py``), trains ``steps_per_cycle``
incremental steps, persists the replay cursor as a checkpoint sidecar,
exports a delta bundle (``serve/export.py:export_delta``), publishes it to
the ``BundleStore`` and hot-swaps the in-process ``MicroBatcher`` — forever,
or until the log drains / ``max_cycles``.

Crash-safety is a single-durability-point design.  Each cycle runs stages

    replay -> train -> checkpoint -> export -> publish -> swap

and the CHECKPOINT is the only commit: state and replay cursor land
atomically in one ``CheckpointManager.save`` (plus a ``target_version``
claim for the store).  A kill before the checkpoint discards the cycle —
the restart re-reads the same records from the last durable cursor and
retrains them onto the matching restored state, so each record contributes
to the state lineage exactly once.  A kill after the checkpoint but before
the store caught up is repaired by ``_catch_up`` at startup: the store head
still names a version below ``target_version``, so the supervisor re-exports
the (deterministic) delta from the head to the checkpointed state and
publishes it before entering the loop.  Either way "restart the same
command" converges to the uninterrupted run's bundle, bit for bit — the
property ``tests/test_online.py`` asserts with real ``os._exit`` kills at
every stage boundary (``[faults] kill_between_stages`` /
``kill_during_replay`` / ``kill_during_swap``).

Stage boundaries consult ``FaultInjector.maybe_kill_stage`` so the kill
matrix is deterministic, and every cycle logs an ``online_cycle`` record —
consumed ``(seq, row_start, row_end)`` spans plus the ``replay/*`` counters
— through the trainer's ``metrics.jsonl`` (PR-7 telemetry path), which is
the record-id accounting the no-dup/no-loss test audits.

The GATED mode (``[online] canary_cycles > 0``, requires a multi-replica
``[serving] replicas`` fleet) puts a canary gatekeeper between training
and serving, the deployment discipline Monolith §3.3 describes for its
online models.  Cycle stages become

    replay -> train -> export -> publish -> canary -> verdict -> commit -> swap

with the VERDICT CHECKPOINT as the single durability point: (1) a shadow
slice of held-out replayed traffic (``ReplayConsumer.peek_batches`` —
rows PAST the committed cursor, which train only in a LATER cycle, i.e.
progressive validation) scores every candidate against the incumbent
before any pointer moves, refusing on AUC regression beyond ``[online]
max_auc_regression``; (2) survivors publish under the ``CANARY`` pointer,
picked up by only the first ``canary_fraction`` of the
``serve/fleet.ServingFleet`` replicas; (3) ``canary_cycles`` watch rounds
compare per-replica held-out-AUC heartbeats (latency recorded alongside)
canary-vs-stable — training/serving skew that byte-perfect bundles can't
reveal shows up here; (4) promote moves ``CURRENT`` and rollback deletes
the candidate, records it in ``rejections.json`` and digest-verifies that
every replica converges bitwise back onto the last good version.  A
rejected cycle still advances the replay cursor and the durable
``cycles_done`` counter (consumed-but-discarded, recorded in metrics), so
a persistently bad stream cannot wedge the loop, and the trained state is
restored from the previous verdict checkpoint — version numbers are
REUSED by the next candidate, keeping the delta chain strictly parent+1.
A kill anywhere before the verdict checkpoint redoes the whole cycle
deterministically (same records, bit-identical retrain, identical delta
digest, idempotent ``publish_canary``); a kill after it is repaired by
``_catch_up_gated`` replaying the recorded verdict onto the store.
"""

from __future__ import annotations

import shutil
from pathlib import Path
from typing import Any

import numpy as np

from tdfo_tpu.obs import trace as _trace
from tdfo_tpu.obs.aggregate import percentile as _percentile
from tdfo_tpu.utils import faults as _faults

__all__ = ["OnlineLoop", "online_from_config"]


def _stage(name: str) -> None:
    """A supervisor stage boundary: the deterministic kill-matrix hook.
    The named stage has NOT run yet when the injected kill fires."""
    inj = _faults.active()
    if inj is not None:
        inj.maybe_kill_stage(name)


class _StageTrace:
    """Per-cycle stage timer: ``mark(name)`` closes the previous stage's
    trace span and opens the next, so the assembled timeline gets a
    wall-clock breakdown of every stage the cycle actually crossed.  A
    killed stage simply never closes — its partial time is lost with the
    cycle (which redoes entirely anyway)."""

    def __init__(self, cycle: int):
        self.cycle = int(cycle)
        self._name: str | None = None
        self._t0 = 0.0

    def mark(self, name: str) -> None:
        self.close()
        self._name, self._t0 = name, _trace.clock()

    def close(self) -> None:
        if self._name is not None:
            _trace.emit("online", "stage", cycle=self.cycle,
                        stage=self._name,
                        dur_ms=round(_trace.elapsed_ms(self._t0), 3))
            self._name = None


class OnlineLoop:
    """One supervisor process: trainer + replay consumer + bundle store +
    serving batcher, advancing in checkpointed cycles.

    Restricted to the DMP/sparse regime (DLRM, TwoTower with
    model_parallel, or Bert4Rec): delta export diffs embedding tables, and
    online freshness is an embedding-dominated problem (Monolith §3.3).
    The seq family (``model_kind == "seq"``) replays eval-window records
    (``seqs``/``cands``, no label column), maps each to a last-position
    masked-LM step, and judges shadow/canary scores by ``ranking_auc``
    over the candidate panels instead of the labelled ``binary_auc``.
    """

    def __init__(self, config, *, log_dir: str | Path | None = None):
        import jax

        from tdfo_tpu.data.replay import ReplayConsumer, make_replay_consumer
        from tdfo_tpu.serve.swap import BundleStore
        from tdfo_tpu.train.trainer import Trainer

        if not config.online.request_log:
            raise ValueError(
                "the online loop needs [online] request_log — the directory "
                "a serving frontend (serve --serving.log_features) wrote")
        from tdfo_tpu.core.config import serving_model_kind

        # rejects unknown models with the actionable family map; bert4rec
        # joins as the "seq" family (replayed candidate panels, ranking_auc
        # gates, label-free heartbeats)
        self.model_kind = serving_model_kind(config)
        if jax.process_count() > 1:
            raise ValueError(
                "the online supervisor is single-process (one serving "
                "replica owns its request log and bundle store)")
        if config.steps_per_execution > 1:
            raise ValueError(
                "online requires steps_per_execution = 1: cycles are short "
                "and the cursor commits per cycle, not per scan chunk")

        self.config = config
        self.trainer = Trainer(config, log_dir=log_dir)
        if not hasattr(self.trainer.state, "tables"):
            raise ValueError(
                "online requires the DMP/sparse regime (dlrm, or twotower "
                "with model_parallel) — delta export diffs embedding tables")
        if self.trainer._pipelined:
            raise ValueError(
                "online does not support train.pipeline_overlap: the "
                "checkpoint stage needs the cycle's updates flushed")
        if self.trainer._ckpt is None:
            raise ValueError("online requires checkpoint_dir")

        self.workdir = Path(config.checkpoint_dir)
        self.store = BundleStore(self.workdir / "bundle_store",
                                 keep_versions=config.serving.keep_versions)
        self.store.recover()  # half-published strays from a killed publish
        self.chain = self.workdir / "delta_chain"
        self.chain.mkdir(parents=True, exist_ok=True)
        self.gated = config.online.canary_cycles > 0

        # restore: state + replay cursor land together, so a resumed process
        # continues at the exact record the durable state has seen
        self.gstep = 0
        cursor: dict[str, Any] | None = None
        if self.trainer._ckpt.latest_step() is not None:
            self.gstep, self.trainer.state, cursor = self.trainer._ckpt.restore(
                self.trainer.state, stamps=self.trainer._ckpt_stamps)
        replay_cursor = (cursor or {}).get("replay")
        self._claimed_version = int((cursor or {}).get("target_version") or 0)
        self.cycles_done = int((cursor or {}).get("cycles_done") or 0)
        self._pending_canary = (cursor or {}).get("canary")

        mesh = self.trainer.mesh
        # a multi-replica fleet writes one request log per replica
        # (<root>/replica-<k>); the factory folds them into one
        # exactly-once stream keyed (replica_id, seq)
        consumer_cls = (make_replay_consumer if config.serving.replicas > 1
                        else ReplayConsumer)
        self.consumer = consumer_cls(
            config.online.request_log,
            schema=self.trainer._eval_schema,
            batch_size=config.per_device_train_batch_size
            * mesh.shape["data"],
            max_bad_records=config.online.max_bad_records,
            max_lag_records=config.online.max_lag_records,
            lag_policy=config.online.lag_policy,
            cursor=replay_cursor,
        )
        self._bootstrap_store()
        if self.gated and self.trainer._ckpt.latest_step() is None:
            # rollback anchor: gated cycle 1 needs a last-good state to
            # restore on rejection, so the pristine state is durable BEFORE
            # any gated training
            self.trainer._ckpt.save(
                0, self.trainer.state, force=True,
                cursor={"online": True, "global_step": 0, "cycles_done": 0,
                        "replay": self.consumer.cursor(),
                        "target_version":
                        int(self.store.current_version() or 0)},
                stamps=self.trainer._ckpt_stamps)
        if self.gated:
            self._catch_up_gated()
        else:
            self._catch_up()
        self.fleet = None
        if config.serving.fleet_mode == "process":
            # out-of-process fleet: each replica is a real OS process behind
            # the socket ingress; same duck-typed surface as ServingFleet,
            # but mark_canary_watch can deliver a REAL SIGKILL and sync()
            # respawns/reconnects the victims (serve/supervisor.py)
            from tdfo_tpu.serve.supervisor import ProcessFleet

            self.fleet = ProcessFleet(self.store, config,
                                      workdir=self.workdir,
                                      logger=self.trainer.logger)
            self.fleet.sync()
            self.batcher = None
        elif config.serving.replicas > 1:
            from tdfo_tpu.serve.fleet import ServingFleet

            self.fleet = ServingFleet(self.store, config, mesh=mesh,
                                      logger=self.trainer.logger)
            self.fleet.sync()
            self.batcher = None
        else:
            self.batcher = self._make_batcher()
        self.cycles = 0

    # ----------------------------------------------------------- store side

    def _export_kwargs(self) -> dict[str, Any]:
        cfg = self.config
        state = self.trainer.state
        if self.model_kind == "seq":
            # seq bundles carry no CTR columns; the manifest's seq block is
            # the backbone geometry the scorer rebuilds (and the drift key
            # export_delta refuses on)
            cat_cols: tuple[str, ...] = ()
            cont_cols: tuple[str, ...] = ()
            seq = {"max_len": cfg.max_len, "n_heads": cfg.n_heads,
                   "n_layers": cfg.n_layers}
        else:
            from tdfo_tpu.train.trainer import _ctr_columns

            cat_cols, cont_cols = _ctr_columns(cfg)
            seq = None
        return dict(
            model=cfg.model, embed_dim=cfg.embed_dim, cat_columns=cat_cols,
            cont_columns=cont_cols, size_map=cfg.size_map, step=self.gstep,
            coll=self.trainer.coll, tables=state.tables,
            dense_params=state.dense_params,
            mixed_precision=cfg.mixed_precision, seq=seq,
        )

    def _bootstrap_store(self) -> None:
        """First launch: publish the current state as full bundle v0 so every
        later cycle is a delta on a verified base.  Idempotent — a restart
        that finds a store head skips this entirely."""
        from tdfo_tpu.serve.export import export_bundle
        from tdfo_tpu.serve.swap import _version_name

        if self.store.current_version() is not None:
            return
        v0 = self.chain / _version_name(0)
        if v0.exists():
            shutil.rmtree(v0)  # crashed between export and ingest: redo
        export_bundle(v0, version=0, **self._export_kwargs())
        self.store.ingest_full(v0)

    def _publish_state(self, target: int) -> None:
        """Export the delta from the store head to the CURRENT trainer state
        and publish it as ``target``.  Deterministic and redoable: a stale
        half-exported directory is discarded and rebuilt from the same
        state, and the store refuses to regress versions."""
        from tdfo_tpu.serve.export import export_delta
        from tdfo_tpu.serve.swap import _version_name

        _stage("export")
        delta_dir = self.chain / _version_name(target)
        if delta_dir.exists():
            shutil.rmtree(delta_dir)
        export_delta(delta_dir, self.store.current_dir(),
                     **self._export_kwargs())
        _stage("publish")
        self.store.apply_delta(delta_dir)  # kill_during_swap fires in here

    def _catch_up(self) -> None:
        """Repair a kill between checkpoint and publish: the checkpoint
        claimed ``target_version`` but the store head is still behind it, so
        the durable state has never reached serving.  Re-export + publish
        before the loop — without this, a drained log would strand the last
        trained cycle in the checkpoint forever."""
        if self._claimed_version <= int(self.store.current_version() or 0):
            return
        self._publish_state(self._claimed_version)

    def _catch_up_gated(self) -> None:
        """Repair a kill between the gated VERDICT checkpoint and the store
        commit: the checkpoint records the verdict durably; the store-side
        promote/rollback replays idempotently here.  Identity is the
        verdict's ``(version, digest)`` pair — version numbers are reused
        after a rollback, so a LATER cycle's pending canary carrying the
        same number (different bytes) must not be judged by an old
        verdict.  The gated mode never runs the non-gated ``_catch_up``:
        a claimed-but-unpromoted version already exists as the canary
        directory, so the repair is a pointer move, not a re-export."""
        pc = self._pending_canary
        if not pc:
            return
        verdict = pc.get("verdict")
        if verdict == "promote":
            if int(self.store.current_version() or 0) < int(pc["version"]):
                self.store.promote_canary()
        elif verdict == "rollback":
            ptr = self.store._read_pointer("CANARY")
            if ptr is not None and (ptr["version"], ptr["digest"]) == (
                    int(pc["version"]), pc["digest"]):
                self.store.rollback_canary(
                    str(pc.get("reason") or "auto-rollback (replayed)"))
        # "rejected" never published — nothing on the store side to redo

    def _make_batcher(self):
        from tdfo_tpu.serve.frontend import MicroBatcher

        spec = self.config.serving
        scorer = self._build_scorer(self.store.current_dir())
        buckets = ((spec.history_buckets or spec.buckets)
                   if self.model_kind == "seq" else spec.buckets)
        return MicroBatcher(
            scorer.score, buckets=buckets, max_batch=spec.max_batch,
            batch_deadline_ms=spec.batch_deadline_ms,
            logger=self.trainer.logger,
            program_cache_size=scorer.score_cache_size,
            max_queue=spec.max_queue, shed_policy=spec.shed_policy,
        )

    def _build_scorer(self, bundle_dir):
        from tdfo_tpu.serve.export import load_bundle
        from tdfo_tpu.serve.scoring import make_scorer

        return make_scorer(load_bundle(bundle_dir), mesh=self.trainer.mesh)

    # ------------------------------------------------------------ the cycle

    def _seq_train_batch(self, batch: dict[str, np.ndarray]
                         ) -> dict[str, np.ndarray]:
        """Replayed eval windows -> one masked-LM training batch.  The
        request's ``seqs`` already carry the appended MASK at the last
        position (``serve/seq_scoring.py:history_window``); the label sheet
        supervises ONLY that position with the panel's positive (column 0,
        the torchrec eval convention) — online next-item fine-tuning through
        the SAME ``bert4rec_sparse_forward`` step as offline fit
        (``masked_ce_loss`` ignores the ``PAD_ID`` sheet)."""
        from tdfo_tpu.models.bert4rec import PAD_ID

        item = np.asarray(batch["seqs"], np.int32)
        label = np.full_like(item, PAD_ID)
        label[:, -1] = np.asarray(batch["cands"], np.int32)[:, 0]
        return {"item": item, "label": label}

    def _train_cycle(self, batches: list[dict[str, np.ndarray]]) -> float:
        """Run one incremental step per replay batch.  Same step program as
        offline fit — [online] adds no graph edits (jaxpr-pinned by
        tests/test_online.py), so serving-loop configs never recompile."""
        from jax.sharding import PartitionSpec as P

        from tdfo_tpu.data.loader import prefetch_to_mesh
        from tdfo_tpu.train.metrics import AUC

        if self.model_kind == "seq":
            batches = [self._seq_train_batch(b) for b in batches]
        trainer, loss = self.trainer, 0.0
        auc = (trainer._fresh_accumulator(AUC.empty())
               if trainer._train_auc_enabled else None)
        for batch in prefetch_to_mesh(iter(batches), trainer.mesh, P("data")):
            if self.model_kind == "seq":
                # the bert4rec step signature (trainer.py fit loop): a fixed
                # dropout key folded with state.step — deterministic per
                # step, so rollback-restored state replays bit for bit
                out = trainer.train_step(trainer.state, batch,
                                         trainer._dropout_rng)
                trainer.state, step_loss = out[:2]
            else:
                out = trainer.train_step(trainer.state, batch, auc)
                trainer.state, step_loss, auc = out[:3]
            self.gstep += 1
            loss = float(step_loss)
        trainer._flush_cache_sync()  # update cache -> tables before export
        return loss

    def run_cycle(self) -> dict[str, Any] | None:
        """One full serve->retrain->swap cycle; ``None`` when the durable
        log has fewer than one batch of unread rows (drained)."""
        cfg = self.config
        st = _StageTrace(self.cycles)  # metrics rec numbers ungated cycles 0-based
        cycle_t0 = _trace.clock()
        step_begin = self.gstep
        _stage("replay")
        st.mark("replay")
        self.consumer.check_backpressure()
        batches, consumed = [], []
        while len(batches) < cfg.online.steps_per_cycle:
            out = self.consumer.next_batch()
            if out is None:
                break
            batches.append(out[0])
            consumed.extend(out[1])
        if not batches:
            return None

        _stage("train")
        st.mark("train")
        loss = self._train_cycle(batches)

        _stage("checkpoint")
        st.mark("checkpoint")
        target = int(self.store.current_version() or 0) + 1
        self.trainer._ckpt.save(
            self.gstep, self.trainer.state, force=True,
            cursor={"online": True, "global_step": self.gstep,
                    "replay": self.consumer.cursor(),
                    "target_version": target},
            stamps=self.trainer._ckpt_stamps)
        self._claimed_version = target
        # ungated cycles have no verdict; "published" marks the direct-to-
        # CURRENT path in the assembled timeline
        _trace.emit(
            "online", "online_cycle", cycle=self.cycles,
            verdict="published", version=target,
            step_begin=step_begin, step_end=self.gstep,
            dur_ms=round(_trace.elapsed_ms(cycle_t0), 3),
            consumed=[list(span) for span in consumed])
        rec = {
            "event": "online_cycle", "cycle": self.cycles,
            "global_step": self.gstep, "steps": len(batches),
            "loss": loss, "version": target,
            "consumed": [list(span) for span in consumed],
            **self.consumer.counters(),
        }
        self.trainer.logger.log(**rec)

        st.mark("publish")
        self._publish_state(target)  # stages: export -> publish

        _stage("swap")
        st.mark("swap")
        if self.fleet is not None:
            # ungated fleet: every replica follows the freshly-moved CURRENT
            self.fleet.sync()
        else:
            scorer = self._build_scorer(self.store.current_dir())
            self.batcher.swap(scorer.score, version=target,
                              program_cache_size=scorer.score_cache_size)
        st.close()
        self.cycles += 1
        return rec

    # ------------------------------------------------------- the gated cycle

    def _score_batches(self, scorer, batches: list[dict[str, np.ndarray]]
                       ) -> np.ndarray:
        """Score replay batches on a scorer, label-stripped.  The jitted
        score donates its inputs, so every call gets fresh arrays."""
        outs = []
        for b in batches:
            feats = {k: np.array(v) for k, v in b.items() if k != "label"}
            outs.append(np.asarray(scorer.score(feats)))
        return np.concatenate(outs)

    def _shadow_auc(self, labels, scores) -> float:
        """The gate metric for either family: labelled rows -> binary_auc
        (CTR); ``labels is None`` -> ranking_auc over [N, C] candidate
        panels with the positive in column 0 (seq)."""
        from tdfo_tpu.train.metrics import binary_auc, ranking_auc

        return (ranking_auc(scores) if labels is None
                else binary_auc(labels, scores))

    def _restore_last_good(self) -> None:
        """Discard the cycle's trained state: reload the last durable state
        (the previous verdict checkpoint, or the gated anchor).  ``gstep``
        is NOT rewound — checkpoint ids stay monotonic, and a restarted
        redo recomputes the identical ids from the identical records."""
        _, self.trainer.state, _ = self.trainer._ckpt.restore(
            self.trainer.state, stamps=self.trainer._ckpt_stamps)

    def _corrupt_candidate(self, delta_dir: Path) -> None:
        """The ``corrupt_candidate`` fault body: flip one payload byte of
        the ON-DISK delta (manifest digest left stale), so the gate's
        ``compose_delta`` digest check runs against real corruption."""
        from tdfo_tpu.serve.export import read_raw_bundle, write_raw_bundle

        manifest, arrays = read_raw_bundle(delta_dir)
        name = sorted(arrays)[0]
        arr = arrays[name]
        raw = bytearray(arr.tobytes())
        raw[len(raw) // 2] ^= 0xFF
        arrays[name] = np.frombuffer(bytes(raw),
                                     dtype=arr.dtype).reshape(arr.shape)
        shutil.rmtree(delta_dir)
        write_raw_bundle(delta_dir, manifest, arrays)

    def _run_cycle_gated(self) -> dict[str, Any] | None:
        """One gatekept cycle (see the module docstring for the contract):
        shadow-gate the candidate, canary it on the fleet's canary cohort,
        then promote or roll back — with the verdict checkpoint as the
        cycle's single durability point.  Returns ``None`` (nothing
        committed, nothing trained into the durable lineage) when the log
        lacks a full cycle of train rows plus the held-out shadow slice."""
        from tdfo_tpu.serve.export import bundle_from_raw, export_delta
        from tdfo_tpu.serve.scoring import make_scorer
        from tdfo_tpu.serve.swap import CorruptDeltaError, _version_name

        cfg = self.config
        inj = _faults.active()
        cycle_no = self.cycles_done + 1
        st = _StageTrace(cycle_no)
        cycle_t0 = _trace.clock()
        step_begin = self.gstep

        _stage("replay")
        st.mark("replay")
        self.consumer.check_backpressure()
        batches, consumed = [], []
        while len(batches) < cfg.online.steps_per_cycle:
            out = self.consumer.next_batch()
            if out is None:
                break
            batches.append(out[0])
            consumed.extend(out[1])
        if not batches:
            return None
        # the shadow-eval slice: held-out traffic PAST the cursor (it
        # trains in a later cycle, never this one — progressive validation)
        shadow = self.consumer.peek_batches(cfg.online.shadow_eval_batches)
        if len(shadow) < cfg.online.shadow_eval_batches:
            return None  # no commit: wait until the held-out slice fills
        if self.model_kind == "seq":
            # seq records carry no label column: candidate panels judge
            # themselves (column 0 is the positive), so the shadow labels
            # are None and every gate below routes through ranking_auc
            shadow_labels = None
            shadow_feats = {k: np.concatenate([b[k] for b in shadow])
                            for k in shadow[0]}
        else:
            shadow_labels = np.concatenate([b["label"] for b in shadow])
            shadow_feats = {k: np.concatenate([b[k] for b in shadow])
                            for k in shadow[0] if k != "label"}

        _stage("train")
        st.mark("train")
        loss = self._train_cycle(batches)

        _stage("export")
        st.mark("export")
        target = int(self.store.current_version() or 0) + 1
        delta_dir = self.chain / _version_name(target)
        if delta_dir.exists():
            shutil.rmtree(delta_dir)
        export_delta(delta_dir, self.store.current_dir(),
                     **self._export_kwargs())
        if inj is not None and inj.corrupt_candidate_due():
            self._corrupt_candidate(delta_dir)
        try:
            manifest, arrays = self.store.compose_delta(delta_dir)
        except CorruptDeltaError as err:
            # a corrupt candidate never reaches a pointer: re-export from
            # the in-memory state (deterministic) and re-verify — a second
            # failure means the corruption is upstream of the disk, so die
            self.trainer.logger.log(event="candidate_corrupt",
                                    cycle=cycle_no, version=target,
                                    error=str(err))
            shutil.rmtree(delta_dir)
            export_delta(delta_dir, self.store.current_dir(),
                         **self._export_kwargs())
            manifest, arrays = self.store.compose_delta(delta_dir)
        digest = manifest["digest"]

        # shadow gate: candidate vs incumbent on the same held-out rows
        candidate = make_scorer(
            bundle_from_raw(manifest, arrays, source=str(delta_dir)),
            mesh=self.trainer.mesh)
        incumbent = self._build_scorer(self.store.current_dir())
        auc_cand = self._shadow_auc(shadow_labels,
                                    self._score_batches(candidate, shadow))
        auc_base = self._shadow_auc(shadow_labels,
                                    self._score_batches(incumbent, shadow))

        verdict, reason = "promote", ""
        canary_auc = stable_auc = None
        canary_p99 = stable_p99 = None
        canary_ms: list[float] = []
        stable_ms: list[float] = []
        if auc_cand < auc_base - cfg.online.max_auc_regression:
            verdict = "rejected"
            reason = (f"shadow gate: candidate AUC {auc_cand:.4f} < "
                      f"incumbent {auc_base:.4f} - "
                      f"{cfg.online.max_auc_regression}")
        else:
            if inj is not None and inj.auc_regress_due(cycle_no):
                # training/serving skew: the BYTES are healthy (the shadow
                # gate scored them directly and passed) — only live serving
                # misbehaves, which is what the canary watch exists for
                self.fleet.set_score_skew(digest)
            if inj is not None and inj.slow_canary_due(cycle_no):
                # latency regression the AUC gate cannot see: only the
                # replicas serving this digest score slowly, so the p99
                # verdict term below has a differential signal
                self.fleet.set_score_slow(digest)
            _stage("publish")
            st.mark("publish")
            self.store.publish_canary(delta_dir, composed=(manifest, arrays))
            _stage("canary")
            st.mark("canary")
            self.fleet.sync()  # the canary cohort picks the candidate up
            for rnd in range(1, cfg.online.canary_cycles + 1):
                if inj is not None:
                    inj.maybe_kill_canary(rnd)
                self.fleet.mark_canary_watch()
                self.fleet.sync()
                hbs = self.fleet.heartbeat(shadow_feats, shadow_labels)
                for hb in hbs:
                    self.trainer.logger.log(event="canary_heartbeat",
                                            cycle=cycle_no, round=rnd, **hb)
                canaries = [h for h in hbs
                            if h["canary"] and h["version"] == target]
                stables = [h for h in hbs if not h["canary"]]
                if not canaries:
                    verdict, reason = "rollback", "no alive canary replica"
                    break
                canary_ms.extend(h["ms"] for h in canaries)
                stable_ms.extend(h["ms"] for h in stables)
                canary_auc = float(np.mean([h["auc"] for h in canaries]))
                stable_auc = (float(np.mean([h["auc"] for h in stables]))
                              if stables else auc_base)
                if canary_auc < stable_auc - cfg.online.max_auc_regression:
                    verdict = "rollback"
                    reason = (f"canary AUC {canary_auc:.4f} < stable "
                              f"{stable_auc:.4f} - "
                              f"{cfg.online.max_auc_regression} at watch "
                              f"round {rnd}")
                    break
            # latency verdict term ([online] max_p99_regression_ms): the
            # heartbeat-scoring p99s, canary cohort vs stable cohort, on
            # the SAME nearest-rank percentile launch.py obs reports — a
            # candidate that serves correct logits slowly rolls back
            # exactly like an AUC regression
            canary_p99 = _percentile(canary_ms, 99)
            stable_p99 = _percentile(stable_ms, 99)
            if (verdict == "promote" and cfg.online.max_p99_regression_ms > 0
                    and canary_p99 is not None and stable_p99 is not None
                    and canary_p99 > stable_p99
                    + cfg.online.max_p99_regression_ms):
                verdict = "rollback"
                reason = (f"canary p99 {canary_p99:.1f}ms > stable p99 "
                          f"{stable_p99:.1f}ms + "
                          f"{cfg.online.max_p99_regression_ms}ms budget")

        _stage("verdict")
        st.mark("verdict")
        if verdict != "promote":
            self._restore_last_good()
        canary_rec = {"verdict": verdict, "version": target,
                      "digest": digest, "reason": reason}
        self.trainer._ckpt.save(
            self.gstep, self.trainer.state, force=True,
            cursor={"online": True, "global_step": self.gstep,
                    "cycles_done": cycle_no,
                    "replay": self.consumer.cursor(),
                    "target_version": target if verdict == "promote"
                    else int(self.store.current_version() or 0),
                    "canary": canary_rec},
            stamps=self.trainer._ckpt_stamps)
        self._pending_canary = canary_rec
        # the cycle's trace span lands right AFTER its single durability
        # point: a kill before the verdict checkpoint redoes the cycle (and
        # emits then, once); a kill after it leaves the span already on
        # disk while _catch_up_gated replays the store side — either way
        # the assembled timeline carries exactly one record per durable
        # cycle (obs/aggregate.py dedups by cycle number, last wins)
        _trace.emit(
            "online", "online_cycle", cycle=cycle_no, verdict=verdict,
            reason=reason, version=target, digest=digest,
            step_begin=step_begin, step_end=self.gstep,
            canary_p99_ms=canary_p99, stable_p99_ms=stable_p99,
            dur_ms=round(_trace.elapsed_ms(cycle_t0), 3),
            consumed=[list(span) for span in consumed])

        _stage("commit")
        st.mark("commit")
        if verdict == "promote":
            self.store.promote_canary()
        elif verdict == "rollback":
            self.store.rollback_canary(reason)

        _stage("swap")
        st.mark("swap")
        self.fleet.sync()  # every replica converges on the verdict's head
        if cfg.online.keep_consumed_segments > 0:
            self.consumer.gc_consumed_segments(
                cfg.online.keep_consumed_segments)
        st.close()
        self.cycles_done = cycle_no
        self.cycles += 1
        rec = {
            "event": "online_cycle", "cycle": cycle_no, "gated": True,
            "global_step": self.gstep, "steps": len(batches), "loss": loss,
            "verdict": verdict, "reason": reason, "version": target,
            "shadow_auc": auc_cand, "shadow_auc_base": auc_base,
            "canary_auc": canary_auc, "stable_auc": stable_auc,
            "canary_p99_ms": canary_p99, "stable_p99_ms": stable_p99,
            "consumed": [list(span) for span in consumed],
            **self.consumer.counters(),
        }
        self.trainer.logger.log(**rec)
        return rec

    def run(self) -> dict[str, Any]:
        """Cycle until the log drains or ``max_cycles``; returns run stats.
        The gated loop counts DURABLE cycles (``cycles_done`` rides in the
        verdict checkpoint) so a restarted run finishes the budget instead
        of re-running it."""
        max_cycles = self.config.online.max_cycles
        if self.gated:
            while not max_cycles or self.cycles_done < max_cycles:
                if self._run_cycle_gated() is None:
                    break
        else:
            while not max_cycles or self.cycles < max_cycles:
                if self.run_cycle() is None:
                    break
        ctrs = self.consumer.counters()
        out = {
            "cycles": self.cycles,
            "global_step": self.gstep,
            "version": int(self.store.current_version() or 0),
            "bundle": str(self.store.current_dir()),
            **ctrs,
        }
        if self.gated:
            out["cycles_done"] = self.cycles_done
        return out

    def probe(self, requests) -> dict[Any, np.ndarray]:
        """Score a request trace through the live (post-swap) serving side —
        the served-logits fingerprint the bitwise-equality acceptance
        compares.  In fleet mode the trace round-robins over alive
        replicas (``fleet.probe_each`` gives the per-replica variant)."""
        if self.fleet is not None:
            return self.fleet.run(requests)
        return self.batcher.run(requests)

    def close(self) -> None:
        """Release the serving side.  Required for process fleets (child
        processes + sockets); a no-op-ish courtesy for the in-process
        kinds."""
        if self.fleet is not None:
            self.fleet.close()


def online_from_config(config, *, log_dir: str | Path | None = None
                       ) -> dict[str, Any]:
    """The ``python -m tdfo_tpu.launch online`` body."""
    loop = OnlineLoop(config, log_dir=log_dir)
    try:
        return loop.run()
    finally:
        loop.close()
