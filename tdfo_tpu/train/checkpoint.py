"""Sharded checkpoint / resume — orbax-backed, covering all reference regimes.

Supersedes the reference's three checkpoint mechanisms (SURVEY.md §5.4):
flax byte blobs written once at train end with no optimizer state
(``jax-flax/models.py:128-139``), ``torch.save(state_dict())`` every 10
epochs whose DMP shards live per-rank (``torchrec/train.py:172-177``), and
keras ``ModelCheckpoint``/``BackupAndRestore`` (``tensorflow2/train_ps.py:155-157``)
— the only reference path with preemption resume.

Here: ONE mechanism.  The full train state (params, optimizer state/slots,
step/epoch counters, loss-scale) is a pytree of (possibly sharded) arrays;
orbax writes each host's shards and restores onto the same mesh/sharding
layout, giving mid-training resume with optimizer state for every model
family and parallelism regime — the BackupAndRestore capability, generalised.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any

import jax
import numpy as np
import orbax.checkpoint as ocp

from tdfo_tpu.utils.retry import retry_call

__all__ = ["CheckpointManager", "LAYOUT_VERSION"]

# Storage-layout schema version, stamped into every checkpoint and verified
# on restore.  Bump whenever a parameter's in-memory LAYOUT changes in a way
# that restores without shape errors but scrambles values:
#   1: original layouts
#   2: fused-QKV feature order changed (qkv, head, dh) -> (head, qkv, dh)
#      (same shapes — silent q/k/v scramble on resume)
#   3: fat-line embedding storage (line_layout packing; adam d<64 moved from
#      stride-64 to d-contiguous component offsets, non-adam kinds gained
#      in-line state)
# A version mismatch (or a pre-stamping checkpoint) REFUSES to restore with
# a clear error instead of silently corrupting the resumed run.
LAYOUT_VERSION = 3

# No target size for OCDBT data files: orbax otherwise caps a chunk at
# tensorstore's 2 GiB default, and an array shard above that is split into
# chunks whose shape must DIVIDE the shard's.  The stacked Criteo-Kaggle
# table is [33,762,577, 16] f32 = 2.16 GB and 33,762,577 is prime, so the
# only "chunk" dividing its rows is one row: 33.7M chunks of 64 bytes, a save
# that had not finished after 15 minutes (full-size rehearsal, PR 23; a
# 16M-row table saved in 10 s).  With no target the shard stays ONE chunk, as
# every smaller array already is: 25 s to save, 8 s to restore.  This is a
# property of the files written, not of the state's layout — checkpoints
# written either way restore alike (no LAYOUT_VERSION bump).
_OCDBT_TARGET_DATA_FILE_SIZE = 0


class CheckpointManager:
    """Step-indexed save/restore of an arbitrary train-state pytree.

    ``save(step_id, state, cursor=...)`` / ``restore(state_like)`` ->
    (step_id, state, cursor) or None.  ``step_id`` is whatever monotone id
    the caller uses (the Trainer uses the run-global data step, so mid-epoch
    checkpoints and epoch-end checkpoints share one ordered namespace).
    ``state_like`` provides structure, shardings, and dtypes (use the freshly
    initialised state); restored arrays land with the same shardings.  Static
    leaves (``apply_fn``, ``tx``...) registered as dataclass static fields
    are not serialised — they come from ``state_like``.
    """

    def __init__(self, directory: str | Path, *, max_to_keep: int = 3):
        self._dir = Path(directory).absolute()
        self._dir.mkdir(parents=True, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True
            ),
            item_handlers=ocp.PyTreeCheckpointHandler(),
        )

    def save(
        self,
        step_id: int,
        state: Any,
        *,
        cursor: dict[str, Any] | None = None,
        stamps: dict[str, Any] | None = None,
        force: bool = False,
    ) -> None:
        """Write the state pytree (and an optional data-stream ``cursor``)
        under ``step_id``.  The cursor — epoch, batches consumed, shuffle-seed
        provenance — is a small JSON sidecar (``cursor_<step_id>.json``)
        written by process 0 only, AFTER the orbax write is durable, so a
        cursor file on disk always refers to a complete checkpoint.  Saves
        retry with backoff (``tdfo_tpu/utils/retry.py``): transient storage
        failures must not kill an otherwise-healthy run.

        ``stamps``: JSON-able compatibility fingerprints beyond the layout
        version (e.g. the hot/cold mode's per-table hot-id digests — same
        shapes under a DIFFERENT hot set would restore cleanly but pair
        every hot row with the wrong id).  Written as a
        ``stamps_<step_id>.json`` sidecar and VERIFIED on restore: a
        mismatch (or a missing side) refuses the resume."""
        payload = {
            "layout_version": np.asarray(LAYOUT_VERSION, np.int32),
            "state": state,
        }
        retry_call(
            self._mgr.save,
            step_id,
            args=ocp.args.PyTreeSave(
                payload,
                ocdbt_target_data_file_size=_OCDBT_TARGET_DATA_FILE_SIZE),
            force=force,
            description=f"ckpt_save:{step_id}",
        )
        self._mgr.wait_until_finished()
        if jax.process_index() == 0:
            cpath = self._cursor_path(step_id)
            if cursor is not None:
                retry_call(
                    cpath.write_text,
                    json.dumps(cursor),
                    description=f"cursor_save:{step_id}",
                )
            elif cpath.exists():
                cpath.unlink()  # force-overwrite must not keep a stale cursor
            spath = self._stamps_path(step_id)
            if stamps:
                retry_call(
                    spath.write_text,
                    json.dumps(stamps),
                    description=f"stamps_save:{step_id}",
                )
            elif spath.exists():
                spath.unlink()
            self._prune_cursors()

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def _cursor_path(self, step_id: int) -> Path:
        return self._dir / f"cursor_{step_id}.json"

    def _stamps_path(self, step_id: int) -> Path:
        return self._dir / f"stamps_{step_id}.json"

    def _prune_cursors(self) -> None:
        """Drop cursor/stamps sidecars whose checkpoint was garbage-collected
        by ``max_to_keep`` so the directory never accumulates orphans."""
        live = set(self._mgr.all_steps())
        for p in (*self._dir.glob("cursor_*.json"),
                  *self._dir.glob("stamps_*.json")):
            try:
                step = int(p.stem.split("_", 1)[1])
            except ValueError:
                continue
            if step not in live:
                p.unlink(missing_ok=True)

    def read_cursor(self, step_id: int) -> dict[str, Any] | None:
        """The data-stream cursor saved with ``step_id``, or None when absent
        (legacy epoch-indexed checkpoints have no cursor)."""
        cpath = self._cursor_path(step_id)
        if not cpath.exists():
            return None
        return json.loads(cpath.read_text())

    def restore(self, state_like: Any, step_id: int | None = None, *,
                stamps: dict[str, Any] | None = None):
        """Restore into the structure/shardings of ``state_like``.  Returns
        ``(step_id, state, cursor)`` or ``None`` when no checkpoint exists;
        ``cursor`` is the data-stream position saved alongside (None for
        legacy epoch-indexed checkpoints).  Refuses checkpoints whose
        storage-layout version differs from :data:`LAYOUT_VERSION` (same
        shapes, different value layout — a silent-corruption hazard, e.g. the
        round-4 fused-QKV reorder or the round-5 fat-line packing), and
        checkpoints whose ``stamps`` sidecar does not match the caller's
        ``stamps`` (e.g. a hot/cold run resumed under a different hot-id
        set: identical shapes, every hot row paired with the wrong id)."""
        step_id = self._mgr.latest_step() if step_id is None else step_id
        if step_id is None:
            return None
        spath = self._stamps_path(step_id)
        saved_stamps = json.loads(spath.read_text()) if spath.exists() else {}
        if (stamps or {}) != saved_stamps:
            raise ValueError(
                f"checkpoint step {step_id} in {self._dir} was saved with "
                f"compatibility stamps {saved_stamps!r}, but this run "
                f"expects {(stamps or {})!r}.  The state trees may restore "
                "cleanly anyway (identical shapes) with values paired to "
                "the WRONG ids — e.g. a hot/cold embedding run resumed "
                "under a different hot-id set — so resuming is refused.  "
                "Re-run with the matching artifacts (same data_dir "
                "hot_ids.json), or retrain."
            )
        # probe the SAVED tree's metadata for the stamp before restoring:
        # a missing stamp is the legacy (pre-versioning) format and must be
        # refused — without conflating genuine I/O or sharding errors from
        # the restore itself with layout incompatibility.  Only the probe's
        # expected failure modes are swallowed (absent/partial metadata,
        # schema drift across orbax versions); anything else propagates.
        try:
            meta = self._mgr.item_metadata(step_id)
        except (OSError, ValueError, KeyError, TypeError):
            meta = None
        meta_tree = getattr(meta, "tree", meta)
        if meta_tree is not None and "layout_version" not in meta_tree:
            raise ValueError(
                f"checkpoint step {step_id} in {self._dir} does not carry a "
                "layout_version stamp (it predates the versioned format).  "
                "Parameter LAYOUT changes (fused-QKV reorder, fat-line "
                "packing) restore without shape errors but scramble values, "
                "so resuming it is refused.  Retrain, or convert the "
                "checkpoint offline."
            )
        abstract = {
            "layout_version": jax.ShapeDtypeStruct((), np.int32),
            "state": jax.tree.map(ocp.utils.to_shape_dtype_struct, state_like),
        }
        try:
            restored = retry_call(
                self._mgr.restore,
                step_id,
                args=ocp.args.PyTreeRestore(
                    item=abstract,
                    restore_args=ocp.checkpoint_utils.construct_restore_args(
                        abstract)),
                description=f"ckpt_restore:{step_id}",
            )
        except (ValueError, KeyError, TypeError) as e:
            if meta_tree is not None:
                raise
            # the metadata probe failed (meta is None), so the legacy-format
            # refusal above could not fire — a pre-versioning checkpoint then
            # surfaces here as an opaque orbax structure mismatch (the
            # abstract tree expects a layout_version leaf the legacy save
            # never wrote).  Re-raise with the layout-version guidance
            # appended so the operator sees the real cause.
            raise ValueError(
                f"restoring checkpoint step {step_id} in {self._dir} failed "
                f"with: {e}.  Its metadata could not be probed, which "
                "together with this structure mismatch usually means the "
                "checkpoint predates the layout_version stamp "
                "(tdfo_tpu/train/checkpoint.py LAYOUT_VERSION).  Parameter "
                "LAYOUT changes restore without shape errors but scramble "
                "values, so unstamped checkpoints cannot be resumed.  "
                "Retrain, or convert the checkpoint offline."
            ) from e
        found = int(np.asarray(restored["layout_version"]))
        if found != LAYOUT_VERSION:
            raise ValueError(
                f"checkpoint step {step_id} in {self._dir} was written with "
                f"storage-layout version {found}, but this build uses "
                f"{LAYOUT_VERSION}.  The layouts are not value-compatible "
                "(see tdfo_tpu/train/checkpoint.py LAYOUT_VERSION history); "
                "resuming would silently scramble parameters, so it is "
                "refused.  Retrain, or convert the checkpoint offline."
            )
        return (
            step_id,
            _merge_static(state_like, restored["state"]),
            self.read_cursor(step_id),
        )

    def close(self) -> None:
        self._mgr.close()


def _merge_static(like: Any, restored: Any) -> Any:
    """Rebuild the full state: restored array leaves + static fields from
    ``like`` (tree structure carries them for registered dataclasses)."""
    leaves, treedef = jax.tree.flatten(restored)
    return jax.tree.unflatten(jax.tree.structure(like), leaves)
