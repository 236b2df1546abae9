"""Data layer: ETL correctness, streaming loader semantics, device prefetch.

The test pyramid the reference lacks (SURVEY.md §4): synthetic raw goodreads
files -> both ETLs -> loaders -> mesh-sharded device batches.
"""

import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tdfo_tpu.data.ctr_preprocessing import (
    FINAL_COLUMNS,
    read_interactions,
    run_ctr_preprocessing,
    split_interactions,
    year_to_decade,
)
from tdfo_tpu.data.loader import (
    IN_FLIGHT,
    ParquetStream,
    count_rows,
    load_parquet_table,
    permutation_batches,
    prefetch_to_mesh,
    resolve_files,
)
from tdfo_tpu.data.seq_preprocessing import (
    EVAL_NEG_NUM,
    PAD_ID,
    run_seq_preprocessing,
)
from tdfo_tpu.data.synthetic import write_synthetic_goodreads


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("goodreads")
    write_synthetic_goodreads(d, n_users=80, n_books=200,
                              interactions_per_user=(5, 60), seed=0)
    return d


@pytest.fixture(scope="module")
def ctr_size_map(data_dir):
    return run_ctr_preprocessing(data_dir)


@pytest.fixture(scope="module")
def seq_stats(data_dir):
    return run_seq_preprocessing(data_dir, max_len=12, sliding_step=6,
                                 mask_prob=0.2, seed=42)


class TestCtrEtl:
    def test_interaction_filter_bounds(self, data_dir):
        df = read_interactions(data_dir)
        counts = df.groupby("user_id").size()
        assert counts.min() >= 10 and counts.max() <= 250
        assert set(df.columns) == {"user_id", "book_id", "is_read", "is_reviewed", "label"}
        assert set(df["label"].unique()) <= {0, 1}

    def test_items_sorted_per_user(self, data_dir):
        df = read_interactions(data_dir)
        for _, g in df.groupby("user_id"):
            assert (np.diff(g["book_id"].to_numpy()) >= 0).all()

    def test_split_ratio_and_disjoint(self, data_dir):
        df = read_interactions(data_dir)
        tr = split_interactions(df, True)
        ev = split_interactions(df, False)
        n = df.groupby("user_id").size()
        ntr = tr.groupby("user_id").size().reindex(n.index, fill_value=0)
        assert (ntr == np.ceil(n * 0.8)).all()
        assert len(tr) + len(ev) == len(df)

    def test_year_to_decade_boundaries(self):
        s = pd.Series(["1900", "1910", "1911", "1999", "2000", "2030", "2031",
                       "1899", "", "garbage"])
        out = year_to_decade(s).tolist()
        # inclusive is_between semantics: boundary years -> earlier decade
        assert out == ["1900s", "1900s", "1910s", "1990s", "1990s", "2020s",
                       "unknown", "unknown", "unknown", "unknown"]

    def test_size_map_and_final_columns(self, data_dir, ctr_size_map):
        assert set(ctr_size_map) == {"user", "item", "language", "is_ebook",
                                     "format", "publisher", "pub_decade"}
        files = resolve_files(data_dir, "parquet/train_part_*.parquet")
        assert len(files) == 8
        tbl = load_parquet_table(files[:1])
        assert list(tbl) == FINAL_COLUMNS
        # encoded categoricals within vocab bounds
        for col in ("language", "format", "publisher", "pub_decade"):
            assert tbl[col].max() < ctr_size_map[col]
        # continuous normalised to [0, 1]
        for col in ("avg_rating", "num_pages"):
            assert 0.0 <= tbl[col].min() and tbl[col].max() <= 1.0

    def test_train_eval_rows_cover_split(self, data_dir, ctr_size_map):
        n_train = count_rows(resolve_files(data_dir, "parquet/train_part_*.parquet"))
        n_eval = count_rows(resolve_files(data_dir, "parquet/eval_part_*.parquet"))
        df = read_interactions(data_dir)
        assert n_train + n_eval == len(df)


class TestSeqEtl:
    def test_size_map_and_mask_ratio(self, seq_stats):
        assert seq_stats["n_users"] > 0 and seq_stats["n_items"] > 0
        # mask_prob 0.2 + always-mask-last => ratio slightly above 0.2
        assert 0.15 < seq_stats["masked_ratio"] < 0.45

    def test_train_windows_shape_and_mask_semantics(self, data_dir, seq_stats):
        files = resolve_files(data_dir, "parquet_bert4rec/train_part_*.parquet")
        tbl = load_parquet_table(files)
        items, labels = tbl["train_interactions"], tbl["labels"]
        assert items.shape == labels.shape and items.shape[1] == 12
        mask_id = seq_stats["n_items"] + 1
        is_masked = items == mask_id
        # labels are real items exactly where input is masked, PAD elsewhere
        assert (labels[is_masked] != PAD_ID).all()
        assert (labels[~is_masked] == PAD_ID).all()
        assert items.max() <= mask_id and items.min() >= PAD_ID

    def test_eval_candidates(self, data_dir, seq_stats):
        files = resolve_files(data_dir, "parquet_bert4rec/eval_part_*.parquet")
        tbl = load_parquet_table(files)
        cands = tbl["candidate_items"]
        assert cands.shape[1] == 1 + EVAL_NEG_NUM
        # positive (col 0) never repeats among its negatives
        for row in cands:
            assert row[0] not in row[1:]
            assert len(np.unique(row[1:])) == EVAL_NEG_NUM  # unique negatives
        seqs = tbl["eval_seqs"]
        mask_id = seq_stats["n_items"] + 1
        # last position is always the MASK token; left-padded
        assert (seqs[:, -1] == mask_id).all()

    def test_test_split_candidates(self, data_dir, seq_stats):
        """The TEST split (reference computes it and never consumes it,
        torchrec/train.py:147-177) is written with eval-compatible columns,
        includes the eval item as known history, and never leaks the test
        item into its negatives."""
        files = resolve_files(data_dir, "parquet_bert4rec/test_part_*.parquet")
        tbl = load_parquet_table(files)
        cands = tbl["candidate_items"]
        assert cands.shape[1] == 1 + EVAL_NEG_NUM
        for row in cands:
            assert row[0] not in row[1:]
            assert len(np.unique(row[1:])) == EVAL_NEG_NUM
        seqs = tbl["eval_seqs"]
        mask_id = seq_stats["n_items"] + 1
        assert (seqs[:, -1] == mask_id).all()

        # cross-check vs eval shards: test input history = eval history + the
        # eval positive (leave-last-one protocol), per user
        efiles = resolve_files(data_dir, "parquet_bert4rec/eval_part_*.parquet")
        etbl = load_parquet_table(efiles)
        by_user = {u: (s, c) for u, s, c in
                   zip(etbl["user_id"], etbl["eval_seqs"], etbl["candidate_items"])}
        for u, s, c in zip(tbl["user_id"], seqs, cands):
            es, ec = by_user[u]
            eval_pos = ec[0]
            assert s[-2] == eval_pos  # last known item before MASK
            assert eval_pos not in c[1:]  # eval item is a positive: excluded


class TestParquetStream:
    def test_exactly_once_per_epoch(self, data_dir, ctr_size_map):
        files = resolve_files(data_dir, "parquet/train_part_*.parquet")
        total = count_rows(files)
        stream = ParquetStream(files, batch_size=64, buffer_size=500, seed=1,
                               drop_last=False, process_index=0, process_count=1)
        seen = []
        for b in stream:
            seen.append(np.stack([b["user_id"], b["item_id"]], 1))
        seen = np.concatenate(seen)
        assert len(seen) == total
        # same multiset of rows as the raw table
        raw = load_parquet_table(files, columns=["user_id", "item_id"])
        raw_rows = np.stack([raw["user_id"], raw["item_id"]], 1)
        assert sorted(map(tuple, seen)) == sorted(map(tuple, raw_rows))

    def test_epochs_differ_and_are_seeded(self, data_dir, ctr_size_map):
        files = resolve_files(data_dir, "parquet/train_part_*.parquet")
        s = ParquetStream(files, batch_size=32, buffer_size=200, seed=7,
                          process_index=0, process_count=1)
        first = next(iter(s))["user_id"].copy()
        again = next(iter(s))["user_id"].copy()
        np.testing.assert_array_equal(first, again)  # same epoch -> same order
        s.set_epoch(1)
        other = next(iter(s))["user_id"].copy()
        assert not np.array_equal(first, other)

    def test_drop_last_gives_static_shapes(self, data_dir, ctr_size_map):
        files = resolve_files(data_dir, "parquet/train_part_*.parquet")
        sizes = {len(b["user_id"]) for b in ParquetStream(
            files, batch_size=50, buffer_size=100, process_index=0, process_count=1)}
        assert sizes == {50}

    def test_host_sharding_partitions_rows(self, data_dir, ctr_size_map):
        files = resolve_files(data_dir, "parquet/train_part_*.parquet")
        total = count_rows(files)
        all_rows = []
        for rank in range(4):
            s = ParquetStream(files, batch_size=16, buffer_size=100, seed=3,
                              drop_last=False, process_index=rank, process_count=4)
            for b in s:
                all_rows.append(np.stack([b["user_id"], b["item_id"]], 1))
        rows = np.concatenate(all_rows)
        assert len(rows) == total  # disjoint and complete across ranks
        raw = load_parquet_table(files, columns=["user_id", "item_id"])
        raw_rows = np.stack([raw["user_id"], raw["item_id"]], 1)
        assert sorted(map(tuple, rows)) == sorted(map(tuple, raw_rows))

    def test_list_columns_stack(self, data_dir, seq_stats):
        files = resolve_files(data_dir, "parquet_bert4rec/train_part_*.parquet")
        b = next(iter(ParquetStream(files, batch_size=8, buffer_size=64,
                                    process_index=0, process_count=1)))
        assert b["train_interactions"].shape == (8, 12)
        assert b["labels"].dtype == np.int32


class TestMapStyle:
    def test_permutation_batches_cover_all(self):
        data = {"x": np.arange(103), "y": np.arange(103) * 2}
        out = np.concatenate([b["x"] for b in permutation_batches(
            data, 10, drop_last=False, seed=0)])
        assert sorted(out.tolist()) == list(range(103))
        dropped = list(permutation_batches(data, 10, drop_last=True, seed=0))
        assert all(len(b["x"]) == 10 for b in dropped) and len(dropped) == 10


class TestPrefetch:
    def test_prefetch_shards_on_mesh(self, data_dir, ctr_size_map, mesh_dp):
        files = resolve_files(data_dir, "parquet/train_part_*.parquet")
        stream = ParquetStream(files, batch_size=64, buffer_size=128,
                               process_index=0, process_count=1)
        n = 0
        for batch in prefetch_to_mesh(stream, mesh_dp, P("data")):
            assert batch["user_id"].sharding.spec == P("data")
            assert batch["user_id"].shape == (64,)
            n += 1
            if n >= 3:
                break
        assert n == 3

    def test_prefetch_exhausts_short_iterators(self, mesh_dp):
        batches = [{"x": np.ones((8,), np.float32) * i} for i in range(2)]
        out = list(prefetch_to_mesh(iter(batches), mesh_dp, P("data"), size=4))
        assert len(out) == 2
        assert float(out[1]["x"][0]) == 1.0

    # the producer thread: one per call, alone on the source, a bounded
    # FIFO of host batches ahead of the consumer, which puts IN_FLIGHT ahead

    @staticmethod
    def _producers():
        return [t for t in threading.enumerate() if t.name == "tdfo-prefetch"]

    @classmethod
    def _wait(cls, holds, seconds=20.0):
        """Poll ``holds()`` up to a bound; the producer is another thread."""
        end = time.monotonic() + seconds
        while not holds() and time.monotonic() < end:
            time.sleep(0.002)
        return holds()

    def test_prefetch_keeps_a_shuffled_epochs_order_and_bits(
            self, data_dir, ctr_size_map, mesh_dp):
        files = resolve_files(data_dir, "parquet/train_part_*.parquet")

        def stream():
            s = ParquetStream(files, batch_size=64, buffer_size=128, seed=3,
                              process_index=0, process_count=1)
            s.set_epoch(2)
            return s

        sharding = NamedSharding(mesh_dp, P("data"))
        by_hand = [jax.device_put(b, sharding) for b in stream()]
        threaded = list(prefetch_to_mesh(stream(), mesh_dp, P("data")))
        assert len(threaded) == len(by_hand) > 4
        # shuffled: the epoch is not the files' order
        assert not np.array_equal(
            np.concatenate([np.asarray(b["user_id"]) for b in by_hand]),
            np.concatenate([b["user_id"] for b in ParquetStream(
                files, batch_size=64, shuffle=False, process_index=0,
                process_count=1)]))
        for got, want in zip(threaded, by_hand):
            assert list(got) == list(want)
            for k in want:
                assert got[k].sharding == want[k].sharding
                assert got[k].dtype == want[k].dtype
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(want[k]))

    @pytest.mark.parametrize("k", [0, 1, 5])
    def test_prefetch_raises_the_sources_error_in_its_place(self, mesh_dp, k):
        boom = KeyError("shard 7")

        def source():
            for i in range(k):
                yield {"x": np.full((8,), i, np.float32)}
            raise boom

        got = []
        with pytest.raises(KeyError) as caught:
            for b in prefetch_to_mesh(source(), mesh_dp, P("data"), size=2):
                got.append(float(b["x"][0]))
        assert caught.value is boom
        assert got == [float(i) for i in range(k)]
        assert self._wait(lambda: not self._producers())

    def test_prefetch_producer_leaves_when_the_consumer_does(self, mesh_dp):
        assert self._wait(lambda: not self._producers())
        made = []

        def source():
            for i in range(10_000):
                made.append(i)
                yield {"x": np.full((8,), i, np.float32)}

        n = 0
        for b in prefetch_to_mesh(source(), mesh_dp, P("data"), size=2):
            n += 1
            if n == 3:
                assert len(self._producers()) == 1
                break
        # the loop let go of the generator: stop is set, the queue drained
        assert self._wait(lambda: not self._producers())
        assert len(made) <= 3 + IN_FLIGHT + 2 + 1 + 1  # + the drain's one
        # an abandoned generator that is only collected, never closed by hand
        g = prefetch_to_mesh(source(), mesh_dp, P("data"))
        next(g)
        del g
        assert self._wait(lambda: not self._producers())

    @pytest.mark.parametrize("size", [1, 2, 4])
    def test_prefetch_is_at_most_size_and_one_in_hand_ahead(self, mesh_dp,
                                                            size):
        made = []

        def source():
            for i in range(40):
                made.append(i)
                yield {"x": np.full((8,), i, np.float32)}

        # the consumer has taken what it yielded and what it put ahead
        taken = IN_FLIGHT
        for b in prefetch_to_mesh(source(), mesh_dp, P("data"), size=size):
            taken += 1
            assert float(b["x"][0]) == taken - IN_FLIGHT - 1
            if taken <= 30:
                # it does get ahead, as far as the bound and no further
                assert self._wait(lambda: len(made) == taken + size + 1)
            assert len(made) <= taken + size + 1
        assert len(made) == 40 and taken == 40 + IN_FLIGHT

    def test_prefetch_advances_the_source_from_one_thread_only(self, mesh_dp):
        idents = []

        def source():
            idents.append(threading.get_ident())  # the first fill
            for i in range(12):
                yield {"x": np.full((8,), i, np.float32)}
                idents.append(threading.get_ident())

        assert len(list(prefetch_to_mesh(source(), mesh_dp, P("data")))) == 12
        assert len(idents) == 13 and len(set(idents)) == 1
        assert idents[0] != threading.get_ident()


class TestMultihostBatchBudget:
    def test_equal_batch_counts_across_hosts(self, data_dir, ctr_size_map):
        # regression: unequal per-host batch counts would deadlock collectives
        files = resolve_files(data_dir, "parquet/train_part_*.parquet")
        for pc in (2, 3, 4):
            counts = []
            for rank in range(pc):
                s = ParquetStream(files, batch_size=37, buffer_size=100, seed=5,
                                  drop_last=True, process_index=rank,
                                  process_count=pc)
                counts.append(sum(1 for _ in s))
            assert len(set(counts)) == 1, f"pc={pc}: unequal counts {counts}"
            assert counts[0] > 0
