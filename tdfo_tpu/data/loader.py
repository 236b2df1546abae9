"""Streaming data loading: parquet shards -> shuffled, host-sharded, device-fed
batches.

Unifies the reference's three loading stacks (HF iterable datasets with a 2M
shuffle buffer, ``jax-flax/train_dp.py:94-136``; ``tf.data`` with
shuffle/prefetch/AUTOTUNE, ``tensorflow2/data.py:134-210``; torchrec's
``split_dataset_by_node`` DataLoader, ``torchrec/data.py:13-49``) into one
pyarrow-native pipeline with no per-row Python:

  * :class:`ParquetStream` — record-batch streaming with a block shuffle
    buffer (each row emitted exactly once per epoch; mixing radius =
    ``buffer_size``), per-host sharding (files round-robin when there are
    enough files, else strided batch slices — ``split_dataset_by_node``
    parity), epoch reseeding (``set_epoch`` parity), and ``drop_last`` for
    static shapes (``jax-flax/train_dp.py:111-114`` rationale: ragged final
    batches would retrigger XLA compilation).
  * :func:`load_parquet_table` / :func:`permutation_batches` — the map-style
    full-permutation loader (``jax-flax/train.py:52-70`` parity).
  * :func:`prefetch_to_mesh` — double-buffered host->HBM transfer onto a
    named mesh (``flax.jax_utils.prefetch_to_device`` parity,
    ``jax-flax/train_dp.py:211``) fed by a producer thread that decodes a
    bounded queue of host batches ahead, multihost-aware via
    ``jax.make_array_from_process_local_data``.

List-typed columns (Bert4Rec windows) are stacked into dense [B, T] arrays at
the arrow level.
"""

from __future__ import annotations

import collections
import glob as _glob
import queue
import threading
import zlib
from pathlib import Path
from typing import Iterator, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from tdfo_tpu.utils.retry import retry_call

# failure modes a corrupted/truncated shard presents as: quarantinable when
# the stream was configured with max_bad_shards > 0
_BAD_SHARD_ERRORS = (OSError, EOFError, zlib.error, pa.ArrowException)

__all__ = [
    "ParquetStream",
    "TFRecordStream",
    "MapStream",
    "load_parquet_table",
    "permutation_batches",
    "prefetch_to_mesh",
]


def _to_numpy_columns(batch: pa.RecordBatch | pa.Table,
                      allow_ragged: bool = False) -> dict[str, np.ndarray]:
    """Arrow -> dict of numpy; fixed-width list columns become [B, T] arrays.

    With ``allow_ragged`` (the jagged training path), variable-length list
    columns become object arrays of per-row numpy arrays — the shuffle/slice
    machinery is row-indexed either way, and consumers pack them into
    (values, lengths) at batch emit (``tdfo_tpu/data/jagged.py``).  Without
    it, ragged data fails HERE with an actionable message instead of as an
    obscure object-dtype error at device transfer."""
    out: dict[str, np.ndarray] = {}
    for name, col in zip(batch.schema.names, batch.columns):
        if pa.types.is_list(col.type) or pa.types.is_large_list(col.type):
            arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
            flat = arr.flatten().to_numpy(zero_copy_only=False)
            offsets = arr.offsets.to_numpy(zero_copy_only=False)
            widths = np.diff(offsets)
            ragged = len(widths) and (widths != widths[0]).any()
            if ragged and not allow_ragged:
                raise ValueError(
                    f"list column {name!r} is ragged; these shards were "
                    "written for the jagged path (config jagged = true) "
                    "— or pad them in preprocessing"
                )
            if allow_ragged:
                # ALWAYS object rows under allow_ragged — an arrow batch
                # whose rows coincidentally share one length must not switch
                # representation mid-stream (the shuffle pool concatenates
                # across batches and mixed ndim crashes it)
                # flatten() is slice-aware but .offsets is absolute: rebase
                # so sliced arrays split correctly
                rel = offsets - offsets[0]
                rows = np.split(flat, rel[1:-1])
                obj = np.empty(len(arr), dtype=object)
                for i, r in enumerate(rows):
                    obj[i] = r
                out[name] = obj
                continue
            t = int(widths[0]) if len(widths) else 0
            out[name] = flat.reshape(len(arr), t)
        else:
            out[name] = col.to_numpy(zero_copy_only=False)
    return out


def _concat_rows(parts: list[dict[str, np.ndarray]]) -> dict[str, np.ndarray]:
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def _take(d: dict[str, np.ndarray], idx) -> dict[str, np.ndarray]:
    return {k: v[idx] for k, v in d.items()}


def _offer(q, item, stop) -> bool:
    """Put ``item`` on a bounded queue for a consumer that may have left:
    ``stop`` (a ``threading.Event``) is set when the consumer abandons its
    generator (exception mid-epoch, generator GC), and a producer must
    notice and leave instead of blocking on a full queue for ever, pinning
    open readers and decoded batches."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.2)
            return True
        except queue.Full:
            continue
    return False


def _drain(q) -> None:
    """Empty ``q`` so that a producer waiting on it full wakes at once."""
    while True:
        try:
            q.get_nowait()
        except queue.Empty:
            return


def resolve_files(data_dir: str | Path, pattern: str) -> list[str]:
    files = sorted(_glob.glob(str(Path(data_dir) / pattern)))
    if not files:
        raise FileNotFoundError(f"no parquet files match {pattern!r} in {data_dir}")
    return files


class ParquetStream:
    """Streaming shuffled batches from parquet shards.

    Each epoch yields every (host-local) row exactly once, in an order
    randomised by (seed, epoch): file order is permuted, then rows pass
    through a ``buffer_size``-row block shuffle.  With ``drop_last`` the
    ragged tail batch is dropped (train); otherwise it is emitted short
    (eval, to be padded by the caller).
    """

    def __init__(
        self,
        files: Sequence[str],
        batch_size: int,
        *,
        shuffle: bool = True,
        buffer_size: int = 2_000_000,  # jax-flax/train_dp.py:129 default
        seed: int = 42,
        drop_last: bool = True,
        process_index: int | None = None,
        process_count: int | None = None,
        columns: Sequence[str] | None = None,
        allow_ragged: bool = False,
        num_workers: int = 0,
        max_bad_shards: int = 0,
    ):
        import jax

        self.files = list(files)
        # corrupted-shard quarantine: files that failed to open/decode are
        # skipped (0 rows) with a warning; the (max_bad_shards+1)-th bad
        # shard is fatal.  0 keeps the historical any-failure-is-fatal
        # behaviour.
        self.max_bad_shards = int(max_bad_shards)
        self._bad_files: dict[str, str] = {}
        # resume support: _skip batches are fast-forwarded (decoded and
        # discarded) by the next __iter__; _emitted tracks this epoch's
        # position for state_dict().  One live iterator per stream.
        self._skip = 0
        self._emitted = 0
        self.allow_ragged = allow_ragged
        # >0: that many background threads read files ahead of the consumer
        # (order-preserving, so shuffles stay deterministic) — the
        # capability the reference gets from tf.data num_parallel_reads /
        # DataLoader num_workers; pyarrow/zlib release the GIL, so plain
        # threads pipeline decode behind device compute.
        self.num_workers = int(num_workers)
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.buffer_size = int(buffer_size)
        self.seed = seed
        self.drop_last = drop_last
        self.columns = list(columns) if columns is not None else None
        self._epoch = 0
        self.process_index = (
            jax.process_index() if process_index is None else process_index
        )
        self.process_count = (
            jax.process_count() if process_count is None else process_count
        )
        # split_dataset_by_node parity (torchrec/data.py:58): whole files per
        # host when they divide evenly, else strided row-block sharding.
        self._shard_by_file = (
            self.process_count > 1 and len(self.files) % self.process_count == 0
        )

    # ---- file-format hooks (overridden by TFRecordStream) ----

    def _file_row_count(self, path: str) -> int:
        return retry_call(
            lambda: pq.ParquetFile(path).metadata.num_rows,
            description=f"parquet_metadata:{Path(path).name}",
        )

    def _file_batches(self, path: str):
        pf = retry_call(pq.ParquetFile, path,
                        description=f"open_shard:{Path(path).name}")
        for rb in pf.iter_batches(batch_size=65536, columns=self.columns):
            yield _to_numpy_columns(rb, allow_ragged=self.allow_ragged)

    # ---- corrupted-shard quarantine ----

    def _quarantine(self, path: str, err: BaseException) -> None:
        """Record ``path`` as bad (skip + warn).  Raises once MORE than
        ``max_bad_shards`` distinct shards have failed — a data set that
        rotten is a pipeline bug, not a shard to shrug off."""
        if path not in self._bad_files:
            self._bad_files[path] = f"{type(err).__name__}: {err}"
            print(f"[loader] quarantined bad shard {path}: "
                  f"{self._bad_files[path]} "
                  f"({len(self._bad_files)}/{self.max_bad_shards} allowed)",
                  flush=True)
        if len(self._bad_files) > self.max_bad_shards:
            raise RuntimeError(
                f"{len(self._bad_files)} corrupted shard(s), more than "
                f"max_bad_shards={self.max_bad_shards} allows: "
                f"{self._bad_files}"
            ) from err

    def _row_count_safe(self, path: str) -> int:
        """Row count with quarantine: a shard whose footer/sidecar cannot be
        read counts 0 rows and is excluded from iteration — deterministic
        across hosts because EVERY host scans every footer for the budget."""
        if path in self._bad_files:
            return 0
        try:
            return self._file_row_count(path)
        except _BAD_SHARD_ERRORS as e:
            self._quarantine(path, e)
            return 0

    def _files_batches(self, files: Sequence[str]):
        """All batches across ``files`` in order; with ``num_workers`` > 0 a
        background thread per in-flight file decodes into a small BOUNDED
        queue (never a whole materialised file), up to ``num_workers`` files
        ahead of the consumer.  Order is preserved — determinism is part of
        the loader's contract — and host memory stays O(num_workers x a few
        arrow batches)."""
        files = [f for f in files if f not in self._bad_files]
        if self.num_workers <= 0:
            for f in files:
                try:
                    yield from self._file_batches(f)
                except _BAD_SHARD_ERRORS as e:
                    # mid-read corruption: rows already emitted from this
                    # shard stay emitted; the remainder is quarantined.  On
                    # multi-host meshes this can shrink one host's row count
                    # below the footer-derived budget — shared-storage
                    # corruption is visible to every host, but keep
                    # max_bad_shards=0 on pods unless shards replicate.
                    self._quarantine(f, e)
            return
        _END = object()
        stop = threading.Event()  # the consumer has left: see _offer

        def start_reader(path: str):
            q: queue.Queue = queue.Queue(maxsize=2)

            def worker():
                try:
                    for d in self._file_batches(path):
                        if not _offer(q, d, stop):
                            return
                    _offer(q, _END, stop)
                except BaseException as e:  # surfaced on the consumer side
                    _offer(q, e, stop)

            t = threading.Thread(target=worker, daemon=True)
            t.start()
            return q

        pending: collections.deque = collections.deque()
        it = iter(files)
        try:
            for _ in range(self.num_workers):
                f = next(it, None)
                if f is None:
                    break
                pending.append((f, start_reader(f)))
            while pending:
                path, q = pending.popleft()
                while True:
                    item = q.get()
                    if item is _END:
                        break
                    if isinstance(item, BaseException):
                        if isinstance(item, _BAD_SHARD_ERRORS):
                            self._quarantine(path, item)  # skip the rest
                            break
                        raise item
                    yield item
                f = next(it, None)
                if f is not None:
                    pending.append((f, start_reader(f)))
        finally:
            stop.set()
            for _, q in pending:  # unblock any waiting worker
                _drain(q)

    def _batches_per_host(self) -> int | None:
        """Cross-host batch budget from parquet metadata (no communication).

        Hosts MUST run the same number of batches per epoch or the first
        collective after the shortest host's last batch deadlocks the mesh
        (SURVEY.md §7 hard part #4).  Row counts come from file footers, so
        every host computes the same minimum independently."""
        if self.process_count <= 1:
            return None
        if self._shard_by_file:
            rows = [
                sum(
                    self._row_count_safe(f)
                    for f in self.files[r :: self.process_count]
                )
                for r in range(self.process_count)
            ]
            min_rows = min(rows)
        else:
            # strided: rank r owns global rows g with g % P == r_assigned;
            # the smallest share is floor(N / P).
            n = sum(self._row_count_safe(f) for f in self.files)
            min_rows = n // self.process_count
        return min_rows // self.batch_size

    def set_epoch(self, epoch: int) -> None:
        """Reshuffle order for a new epoch (HF ``set_epoch`` parity,
        ``jax-flax/train.py:143``).  Clears any pending resume fast-forward —
        call :meth:`load_state_dict` AFTER set_epoch to resume mid-epoch."""
        self._epoch = int(epoch)
        self._skip = 0

    # ---- step-granular resume (checkpoint cursor contract) ----

    def state_dict(self) -> dict[str, int]:
        """Position cursor: (seed, epoch, batches emitted this epoch).  The
        epoch's batch sequence is a pure function of (seed, epoch) — file
        permutation, block shuffle and batch assembly all derive from
        ``default_rng((seed, epoch))`` — so the cursor pins the exact batch.

        NOTE: counts batches handed to the CALLER of ``__iter__``.  Behind a
        prefetcher, count consumed batches yourself (the Trainer does) and
        build the cursor from that."""
        return {"seed": int(self.seed), "epoch": int(self._epoch),
                "batches_emitted": int(self._emitted)}

    def load_state_dict(self, state: dict[str, int]) -> None:
        """Resume: the next ``__iter__`` fast-forwards ``batches_emitted``
        batches (decode-and-discard — the shuffle pool must replay to
        reproduce the stream bit-exactly) and yields from there."""
        if int(state.get("seed", self.seed)) != self.seed:
            raise ValueError(
                f"stream cursor was recorded with seed "
                f"{state['seed']}, this stream uses {self.seed} — resuming "
                "would yield a different batch sequence"
            )
        self._epoch = int(state["epoch"])
        self._skip = int(state["batches_emitted"])

    def max_batches_per_host(self) -> int:
        """The LARGEST per-host batch count this epoch (ceil division, no
        drop_last) — the eval-loop budget: every host must run this many step
        calls, topping up with zero-weight padding batches, or the mesh
        deadlocks (same invariant as :meth:`_batches_per_host`, opposite
        rounding)."""
        counts = []
        for r in range(max(self.process_count, 1)):
            if self._shard_by_file:
                rows = sum(
                    self._row_count_safe(f)
                    for f in self.files[r :: self.process_count]
                )
            else:
                n = sum(self._row_count_safe(f) for f in self.files)
                p = max(self.process_count, 1)
                rows = (n - r + p - 1) // p
            counts.append(-(-rows // self.batch_size))
        return max(counts)

    def __iter__(self) -> Iterator[dict[str, np.ndarray]]:
        budget = self._batches_per_host() if self.drop_last else None
        skip, self._skip = self._skip, 0
        pos = 0
        self._emitted = 0
        for batch in self._iter_unbounded():
            if budget is not None and pos >= budget:
                return
            pos += 1
            self._emitted = pos
            if pos <= skip:
                continue  # resume fast-forward: already consumed pre-crash
            yield batch

    def _iter_unbounded(self) -> Iterator[dict[str, np.ndarray]]:
        rng = np.random.default_rng((self.seed, self._epoch))
        files = list(self.files)
        if self._shard_by_file:
            files = files[self.process_index :: self.process_count]
        if self.shuffle:
            rng.shuffle(files)

        def raw_batches():
            stride_pos = 0
            for d in self._files_batches(files):
                if not self._shard_by_file and self.process_count > 1:
                    # strided slice so every host sees a disjoint subset
                    n = len(next(iter(d.values())))
                    idx = np.arange(
                        (self.process_index - stride_pos) % self.process_count,
                        n,
                        self.process_count,
                    )
                    stride_pos = (stride_pos + n) % self.process_count
                    d = _take(d, idx)
                yield d

        pool: list[dict[str, np.ndarray]] = []
        pooled = 0
        pending: list[dict[str, np.ndarray]] = []
        pend_n = 0

        def emit(d):
            nonlocal pending, pend_n
            pending.append(d)
            pend_n += len(next(iter(d.values())))
            while pend_n >= self.batch_size:
                rows = _concat_rows(pending)
                n = len(next(iter(rows.values())))
                yield _take(rows, slice(0, self.batch_size))
                rest = _take(rows, slice(self.batch_size, n))
                pending = [rest]
                pend_n = n - self.batch_size

        for d in raw_batches():
            if not self.shuffle:
                yield from emit(d)
                continue
            pool.append(d)
            pooled += len(next(iter(d.values())))
            if pooled >= self.buffer_size:
                rows = _concat_rows(pool)
                perm = rng.permutation(pooled)
                half = pooled // 2  # emit half, keep half for further mixing
                yield from emit(_take(rows, perm[:half]))
                pool = [_take(rows, perm[half:])]
                pooled -= half
        if pool:
            rows = _concat_rows(pool)
            yield from emit(_take(rows, rng.permutation(pooled)))
        if pend_n and not self.drop_last:
            yield _concat_rows(pending)


class TFRecordStream(ParquetStream):
    """The same streaming pipeline over TFRecord shards
    (``tensorflow2/data.py:171-210`` capability — schema comes from the
    Example protos themselves instead of ``FixedLenFeature`` declarations).

    Row counts come from the ``{prefix}_data_size.json`` sidecar written at
    preprocessing time (``tensorflow2/data.py:83-84`` parity); scanning a
    gzip TFRecord just to count it would defeat streaming.
    """

    def __init__(self, files, batch_size, *, compression: str | None = "GZIP",
                 **kw):
        super().__init__(files, batch_size, **kw)
        self.compression = compression
        self._row_counts: dict[str, int] = {}

    def _file_row_count(self, path: str) -> int:
        from tdfo_tpu.data.tfrecord import read_shard_sizes, read_tfrecord_records

        if path not in self._row_counts:
            p = Path(path)
            prefix = p.name.split("_part_")[0]
            sizes = read_shard_sizes(p.parent, prefix)
            if sizes is not None and p.name in sizes:
                for name, n in sizes.items():
                    self._row_counts[str(p.parent / name)] = n
            else:
                # no per-shard sidecar: count by scanning once, then CACHE
                # the count to a sidecar so later epochs (and other runs /
                # hosts) never rescan the whole gzip stream again
                self._row_counts[path] = retry_call(
                    lambda: sum(
                        1 for _ in read_tfrecord_records(path, self.compression)
                    ),
                    description=f"scan_tfrecord:{p.name}",
                )
                from tdfo_tpu.data.tfrecord import write_shard_sizes_entry

                write_shard_sizes_entry(
                    p.parent, prefix, p.name, self._row_counts[path]
                )
        return self._row_counts[path]

    def _file_batches(self, path: str):
        from tdfo_tpu.data.tfrecord import (
            decode_example,
            read_tfrecord_records,
            stack_example_rows,
        )

        rows: list[dict[str, np.ndarray]] = []
        for payload in read_tfrecord_records(path, self.compression):
            rows.append(decode_example(payload))
            if len(rows) >= 8192:
                yield stack_example_rows(rows, self.columns)
                rows = []
        if rows:
            yield stack_example_rows(rows, self.columns)


def count_rows(files: Sequence[str]) -> int:
    """Total row count from parquet metadata without reading data
    (``get_data_size`` parity, ``jax-flax/utils.py:36-38``)."""
    return sum(pq.ParquetFile(f).metadata.num_rows for f in files)


def load_parquet_table(files: Sequence[str],
                       columns: Sequence[str] | None = None) -> dict[str, np.ndarray]:
    """Map-style: read everything into memory (``jax-flax/train.py:52-60``)."""
    tables = [pq.read_table(f, columns=list(columns) if columns else None) for f in files]
    return _to_numpy_columns(pa.concat_tables(tables).combine_chunks())


def permutation_batches(
    data: dict[str, np.ndarray],
    batch_size: int,
    *,
    shuffle: bool = True,
    seed: int = 42,
    epoch: int = 0,
    drop_last: bool = True,
) -> Iterator[dict[str, np.ndarray]]:
    """Full-permutation epoch over an in-memory table
    (``jax-flax/train.py:52-70`` parity)."""
    n = len(next(iter(data.values())))
    idx = np.arange(n)
    if shuffle:
        np.random.default_rng((seed, epoch)).shuffle(idx)
    end = n - n % batch_size if drop_last else n
    for i in range(0, end, batch_size):
        yield _take(data, idx[i : i + batch_size])


class MapStream:
    """Map-style epochs over an in-memory table, presenting the same
    interface as :class:`ParquetStream` (``config streaming = false``;
    ``jax-flax/train.py:52-70`` full-permutation loader parity).

    Single-process only: the whole table lives on this host, so multi-host
    budget logic does not apply (use the streaming loader on pods).
    """

    def __init__(self, files: Sequence[str], batch_size: int, *,
                 shuffle: bool = True, seed: int = 42, drop_last: bool = True,
                 columns: Sequence[str] | None = None):
        import jax

        if jax.process_count() > 1:
            raise ValueError(
                "streaming=false (map-style) loading is single-process only; "
                "multi-host runs need the streaming loader's per-host budgets"
            )
        self.table = load_parquet_table(files, columns)
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self._epoch = 0
        self._skip = 0
        self._emitted = 0
        self._n = len(next(iter(self.table.values())))

    def set_epoch(self, epoch: int) -> None:
        self._epoch = int(epoch)
        self._skip = 0

    def state_dict(self) -> dict[str, int]:
        """Same cursor contract as :meth:`ParquetStream.state_dict`."""
        return {"seed": int(self.seed), "epoch": int(self._epoch),
                "batches_emitted": int(self._emitted)}

    def load_state_dict(self, state: dict[str, int]) -> None:
        """Resume mid-epoch; map-style skip is O(1) (index arithmetic into
        the epoch permutation), no replay needed."""
        if int(state.get("seed", self.seed)) != self.seed:
            raise ValueError(
                f"stream cursor was recorded with seed "
                f"{state['seed']}, this stream uses {self.seed}"
            )
        self._epoch = int(state["epoch"])
        self._skip = int(state["batches_emitted"])

    def max_batches_per_host(self) -> int:
        # must mirror the __iter__ count exactly: drop_last floors, else ceils
        if self.drop_last:
            return self._n // self.batch_size
        return -(-self._n // self.batch_size)

    def __iter__(self) -> Iterator[dict[str, np.ndarray]]:
        skip, self._skip = self._skip, 0
        self._emitted = skip
        idx = np.arange(self._n)
        if self.shuffle:
            np.random.default_rng((self.seed, self._epoch)).shuffle(idx)
        end = self._n - self._n % self.batch_size if self.drop_last else self._n
        for i in range(skip * self.batch_size, end, self.batch_size):
            self._emitted += 1
            yield _take(self.table, idx[i : i + self.batch_size])


IN_FLIGHT = 2  # device batches put ahead of the one the consumer holds


def prefetch_to_mesh(it, mesh, pspec=None, *, size: int = 4):
    """Host batches of ``it`` onto a mesh: decoded ahead by ONE producer
    thread of this call's own, put ahead by the consumer's.

    The producer thread (``tdfo-prefetch``, daemon) alone advances ``it``
    (phase ``loader_next``: decode, shuffle pool, stacking) and hands the
    host batches over a FIFO queue bounded at ``size``: it is never more
    than ``size`` batches, and the one in its hand, ahead of what the
    consumer has taken.  The consumer's ``next()`` takes one host batch from
    the queue, puts it on the mesh (``h2d_put``) and yields the oldest of
    the ``IN_FLIGHT`` device batches it keeps ahead: jax dispatches
    transfers asynchronously, so compute overlaps the next batches' copies
    (``jax-flax/train_dp.py:210-211`` parity: shard +
    ``prefetch_to_device(2)``; the reference decodes inside ``next()`` too,
    that part is off the consumer's thread here).  Batch n out is batch n
    in.  An exception of the source is raised in the consumer where it
    happened, after the batches before it.  A consumer that leaves early
    (``break``, an epoch that raised, a generator that is collected) sets a
    stop flag, and the producer leaves instead of waiting on a full queue.
    Multihost: each host provides its local rows via
    ``make_array_from_process_local_data``.

    The put stays on the consumer's thread because it is device work: on
    the v5e a second thread's buffer allocations starve behind the step's
    own (each waits out a whole ``Execute``) and both run slower for it
    (PERF.md section 6, PR 30).  ``size`` 4 covers one row group decoded
    (16-22 ms there) at 6 ms a step.

    The producer joins the ``obs.trace`` epoch open on the thread that
    first advances this generator, so ``loader_next`` lands in that epoch's
    record; with none open it only annotates.  The consumer tallies there
    ``prefetch_depth`` (the queue's length at each take) and
    ``prefetch_empty_takes`` (takes that found it empty and waited).

    Jagged batches need no special casing: per-host-packed ``values`` and
    ``lengths`` both ship batch-sharded ``P("data")`` (each process provides
    exactly its local slice), and ``jagged_to_dense_per_host`` reads the
    host-segmented layout back inside the step.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tdfo_tpu.obs.trace import current_epoch, join_epoch, phase, tally

    sharding = NamedSharding(mesh, pspec if pspec is not None else P("data"))

    def put(batch):
        if jax.process_count() > 1:
            return {
                k: jax.make_array_from_process_local_data(sharding, v)
                for k, v in batch.items()
            }
        return jax.device_put(batch, sharding)

    _END = object()
    q: queue.Queue = queue.Queue(maxsize=size)
    stop = threading.Event()  # the consumer has left: see _offer
    epoch = current_epoch()

    def produce():
        try:
            with join_epoch(epoch):
                src = iter(it)
                while True:
                    with phase("loader_next"):
                        batch = next(src, _END)
                    if not _offer(q, batch, stop) or batch is _END:
                        return
        except BaseException as e:  # raised again on the consumer's side
            _offer(q, e, stop)

    ahead: collections.deque = collections.deque()
    ended = None  # what the stream ended in: _END or the source's exception

    def take_and_put() -> None:
        """One host batch off the queue and onto the mesh.  The phase
        closes before the caller yields."""
        nonlocal ended
        if ended is not None:
            return
        depth = q.qsize()
        tally("prefetch_depth", depth)
        if not depth:
            tally("prefetch_empty_takes")
        item = q.get()
        if item is _END or isinstance(item, BaseException):
            ended = item
            return
        with phase("h2d_put"):
            ahead.append(put(item))

    threading.Thread(target=produce, name="tdfo-prefetch", daemon=True).start()
    try:
        for _ in range(IN_FLIGHT):
            take_and_put()
        while ahead:
            b = ahead.popleft()
            take_and_put()
            yield b
        if ended is not _END:
            raise ended
    finally:
        stop.set()
        _drain(q)
