"""Pallas kernels in interpreter mode vs XLA references (CPU-exact).

The compiled path is checked for the chip by tests/test_chip_compile.py and
run there by chip_smoke.py's kernels phase; here the same kernel code
executes interpreted so the math is verified everywhere.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tdfo_tpu.ops.pallas_kernels import (
    fat_pack,
    fat_unpack,
    fat_view,
    flash_attention,
    line_layout,
)
from tdfo_tpu.ops.sparse import (
    dedupe_grads,
    fat_apply_unique,
    sparse_adagrad,
    sparse_adam,
    sparse_rowwise_adagrad,
    sparse_sgd,
)


def _qkv(key, b=2, h=2, t=128, dh=32):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, (b, h, t, dh)) for k in ks)


def _ref_attention(q, k, v, valid=None):
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) / (q.shape[-1] ** 0.5)
    if valid is not None:
        s = jnp.where(valid[:, None, None, :], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s.astype(jnp.float32), -1)
    return jnp.einsum("bhts,bhsd->bhtd", p.astype(v.dtype), v)


class TestFlashAttention:
    def test_matches_reference(self):
        q, k, v = _qkv(jax.random.key(0))
        out = flash_attention(q, k, v, None, 64, 64, True)
        ref = _ref_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_key_padding_mask(self):
        q, k, v = _qkv(jax.random.key(1))
        valid = jnp.asarray(np.random.default_rng(0).random((2, 128)) > 0.4)
        valid = valid.at[:, 0].set(True)
        out = flash_attention(q, k, v, valid, 64, 64, True)
        ref = _ref_attention(q, k, v, valid)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_fully_masked_rows_zero(self):
        q, k, v = _qkv(jax.random.key(2), b=1, t=64)
        valid = jnp.zeros((1, 64), bool)
        out = flash_attention(q, k, v, valid, 64, 64, True)
        assert not bool(jnp.isnan(out).any())
        np.testing.assert_allclose(np.asarray(out), 0.0)

    def test_uneven_seq_len_padded(self):
        q, k, v = _qkv(jax.random.key(3), t=100)
        out = flash_attention(q, k, v, None, 64, 64, True)
        ref = _ref_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_gradients_flow(self):
        q, k, v = _qkv(jax.random.key(4), b=1, h=1, t=64, dh=16)

        def loss(q, k, v):
            return (flash_attention(q, k, v, None, 64, 64, True) ** 2).sum()

        def ref_loss(q, k, v):
            return (_ref_attention(q, k, v) ** 2).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)

    def test_block_sizes_do_not_change_result(self):
        q, k, v = _qkv(jax.random.key(5), t=128)
        a = flash_attention(q, k, v, None, 128, 128, True)
        b = flash_attention(q, k, v, None, 32, 64, True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


class TestFatLayout:
    @pytest.mark.parametrize("d,kind,w,r,tiles", [
        (16, "rowwise_adagrad", 32, 4, 1),
        (16, "sgd", 16, 8, 1),
        (16, "adagrad", 32, 4, 1),
        (16, "adam", 64, 2, 1),
        (64, "rowwise_adagrad", 128, 1, 1),
        (64, "adam", 256, 1, 2),
        (8, "sgd", 8, 16, 1),
        (128, "adam", 384, 1, 3),
    ])
    def test_geometry(self, d, kind, w, r, tiles):
        lay = line_layout(d, kind)
        assert (lay.w, lay.r, lay.tiles) == (w, r, tiles)
        assert lay.r * lay.w == lay.tiles * 128  # contiguous-view invariant

    @pytest.mark.parametrize("d", [16, 42, 64, 96, 128, 200])
    def test_pack_unpack_roundtrip_adam(self, d):
        rng = np.random.default_rng(d)
        v = 24
        t, mu, nu = (jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
                     for _ in range(3))
        fat = fat_pack(t, mu, nu)
        lay = line_layout(d, "adam")
        assert fat.shape == (lay.n_lines(v), lay.tiles, 128)
        got = fat_unpack(fat, lay, rows=v)
        for a, b in zip(got, (t, mu, nu)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("kind", ["sgd", "rowwise_adagrad", "adagrad"])
    def test_pack_unpack_roundtrip_other_kinds(self, kind):
        rng = np.random.default_rng(11)
        v, d = 37, 16
        t = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
        state = ()
        if kind == "rowwise_adagrad":
            state = (jnp.asarray(rng.random(v).astype(np.float32)),)
        elif kind == "adagrad":
            state = (jnp.asarray(rng.random((v, d)).astype(np.float32)),)
        fat = fat_pack(t, *state, kind=kind)
        got = fat_unpack(fat, line_layout(d, kind), rows=v)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(t))
        for a, b in zip(got[1:], state):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_view_gather_matches_table(self):
        rng = np.random.default_rng(5)
        v, d = 100, 16
        lay = line_layout(d, "rowwise_adagrad")
        t = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
        view = fat_view(fat_pack(t, kind="rowwise_adagrad"), lay)
        ids = jnp.asarray(rng.integers(0, v, 33).astype(np.int32))
        np.testing.assert_array_equal(
            np.asarray(jnp.take(view, ids, axis=0)[:, :d]), np.asarray(t[ids])
        )


def _ref_update(kind, table, state, uids, g, valid, lr, wd):
    if kind == "sgd":
        return sparse_sgd(table, uids, g, valid, lr=lr, weight_decay=wd), ()
    if kind == "rowwise_adagrad":
        t, acc = sparse_rowwise_adagrad(table, state[0], uids, g, valid,
                                        lr=lr, eps=1e-8, weight_decay=wd)
        return t, (acc,)
    if kind == "adagrad":
        t, acc = sparse_adagrad(table, state[0], uids, g, valid, lr=lr,
                                eps=1e-8, weight_decay=wd)
        return t, (acc,)
    t, mu, nu, _ = sparse_adam(table, state[0], state[1],
                               jnp.asarray(0, jnp.int32), uids, g, valid,
                               lr=lr, weight_decay=wd)
    return t, (mu, nu)


def _zero_state(kind, v, d):
    if kind == "sgd":
        return ()
    if kind == "rowwise_adagrad":
        return (jnp.zeros((v,), jnp.float32),)
    if kind == "adagrad":
        return (jnp.zeros((v, d), jnp.float32),)
    return (jnp.zeros((v, d), jnp.float32), jnp.zeros((v, d), jnp.float32))


class TestFatLineUpdate:
    """The in-place DMA kernel (interpret mode) must reproduce the plain
    per-row XLA formulations for EVERY fused optimizer kind — fbgemm fused
    EmbOptimType parity (torchrec/train.py:187-195)."""

    def _setup(self, v=64, d=64, b=32, seed=0):
        rng = np.random.default_rng(seed)
        table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
        ids = jnp.asarray(rng.integers(0, v, b).astype(np.int32))
        grads = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
        return table, ids, grads

    @pytest.mark.parametrize("kind,d", [
        ("adam", 16), ("adam", 64),
        ("rowwise_adagrad", 16), ("rowwise_adagrad", 64),
        ("adagrad", 16), ("sgd", 16),
    ])
    def test_matches_xla_row_formulation(self, kind, d):
        table, ids, grads = self._setup(d=d)
        v = table.shape[0]
        uids, g, valid = dedupe_grads(ids, grads)
        state = _zero_state(kind, v, d)
        t_ref, s_ref = _ref_update(kind, table, state, uids, g, valid,
                                   lr=1e-2, wd=0.01)
        fat = fat_pack(table, kind=kind)
        slots = (jnp.zeros((), jnp.int32),) if kind == "adam" else ()
        fat_new, _ = fat_apply_unique(
            fat, slots, uids, g, valid, embedding_dim=d, kind=kind, lr=1e-2,
            weight_decay=0.01, interpret=True,
        )
        got = fat_unpack(fat_new, line_layout(d, kind), rows=v)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(t_ref),
                                   rtol=1e-5, atol=1e-6)
        for a, b in zip(got[1:], s_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_untouched_rows_unchanged(self):
        table, ids, grads = self._setup()
        uids, g, valid = dedupe_grads(ids, grads)
        fat = fat_pack(table, kind="adam")
        fat_new, _ = fat_apply_unique(
            fat, (jnp.zeros((), jnp.int32),), uids, g, valid,
            embedding_dim=table.shape[1], kind="adam", lr=1e-2, interpret=True,
        )
        touched = set(np.asarray(uids[np.asarray(valid)]).tolist())
        view, view_new = (np.asarray(fat_view(f, line_layout(64, "adam")))
                          for f in (fat, fat_new))
        for r in range(table.shape[0]):
            if r not in touched:
                np.testing.assert_array_equal(view_new[r], view[r])

    def test_padding_slots_are_noops(self):
        table, _, _ = self._setup(b=8)
        d = table.shape[1]
        sent = jnp.iinfo(jnp.int32).max
        uids = jnp.array([3, 7] + [sent] * 6, jnp.int32)
        g = jnp.ones((8, d), jnp.float32)
        g = g.at[2:].set(999.0)  # garbage grads on padding slots must not land
        fat = fat_pack(table, kind="adam")
        fat_new, _ = fat_apply_unique(
            fat, (jnp.zeros((), jnp.int32),), uids, g, None, embedding_dim=d,
            kind="adam", lr=1e-2, interpret=True,
        )
        t_pl = fat_unpack(fat_new, line_layout(d, "adam"))[0]
        assert not np.array_equal(np.asarray(t_pl[3]), np.asarray(table[3]))
        assert not np.array_equal(np.asarray(t_pl[7]), np.asarray(table[7]))
        np.testing.assert_array_equal(np.asarray(t_pl[0]), np.asarray(table[0]))

    def test_shared_line_slots_update_independently(self):
        """Two touched rows in the SAME packed line (R > 1) plus untouched
        neighbours: per-slot gating must keep neighbours bit-identical."""
        rng = np.random.default_rng(9)
        v, d, kind = 16, 16, "rowwise_adagrad"  # R = 4: rows 0-3 share line 0
        table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
        ids = jnp.asarray([0, 2, 0, 9], jnp.int32)
        grads = jnp.asarray(rng.normal(size=(4, d)).astype(np.float32))
        uids, g, valid = dedupe_grads(ids, grads)
        acc = jnp.zeros((v,), jnp.float32)
        t_ref, s_ref = _ref_update(kind, table, (acc,), uids, g, valid,
                                   lr=1e-2, wd=0.01)
        fat_new, _ = fat_apply_unique(
            fat_pack(table, kind=kind), (), uids, g, valid, embedding_dim=d,
            kind=kind, lr=1e-2, weight_decay=0.01, interpret=True,
        )
        got_t, got_acc = fat_unpack(fat_new, line_layout(d, kind), rows=v)
        np.testing.assert_allclose(np.asarray(got_t), np.asarray(t_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_acc), np.asarray(s_ref[0]),
                                   rtol=1e-5, atol=1e-6)
        # rows 1 and 3 share line 0 with touched rows 0/2 but must be intact
        np.testing.assert_array_equal(np.asarray(got_t[1]), np.asarray(table[1]))
        np.testing.assert_array_equal(np.asarray(got_t[3]), np.asarray(table[3]))


class TestSparseOptimizerTiers:
    """The three adam tiers (one-hot small-vocab, fat fused, plain) are one
    optimizer semantically: identical trajectories on identical data."""

    def _data(self, v, d, b=24, seed=3):
        rng = np.random.default_rng(seed)
        table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
        ids = jnp.asarray(rng.integers(0, v, b).astype(np.int32))
        grads = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
        return table, ids, grads

    def test_onehot_tier_matches_plain(self):
        from tdfo_tpu.ops.sparse import sparse_optimizer

        table, ids, grads = self._data(v=50, d=32)
        small = sparse_optimizer("adam", lr=1e-2, weight_decay=0.01)  # v<=thresh
        plain = sparse_optimizer("adam", lr=1e-2, weight_decay=0.01,
                                 small_vocab_threshold=0)
        t_a, s_a = small.update(table, small.init(table), ids, grads)
        t_b, s_b = plain.update(table, plain.init(table), ids, grads)
        np.testing.assert_allclose(np.asarray(t_a), np.asarray(t_b), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(s_a[0]), np.asarray(s_b[0]), rtol=1e-5, atol=1e-6)
        assert int(s_a[2]) == int(s_b[2]) == 1

    @pytest.mark.parametrize("kind,d", [
        ("adam", 64), ("adam", 200), ("rowwise_adagrad", 16), ("sgd", 16),
    ])
    def test_fat_tier_matches_plain(self, kind, d):
        from tdfo_tpu.ops.sparse import sparse_optimizer

        table, ids, grads = self._data(v=64, d=d)
        opt = sparse_optimizer(kind, lr=1e-2, weight_decay=0.01,
                               small_vocab_threshold=0)
        t_ref, _ = opt.update(table, opt.init(table), ids, grads)
        fat = fat_pack(table, kind=kind)
        fat_new, slots = opt.update(fat, opt.init(fat), ids, grads,
                                    embedding_dim=d)
        t_fat = fat_unpack(fat_new, line_layout(d, kind), rows=64)[0]
        np.testing.assert_allclose(np.asarray(t_fat), np.asarray(t_ref), rtol=1e-5, atol=1e-6)
        if kind == "adam":
            assert int(slots[0]) == 1


def test_bert4rec_flash_attn_matches_full(mesh8):
    from tdfo_tpu.models.bert4rec import Bert4RecConfig, key_padding_mask, make_sharded_bert4rec

    cfg = Bert4RecConfig(n_items=40, max_len=16, embed_dim=16, n_heads=2, n_layers=1)
    coll, tables, bb_full, dense = make_sharded_bert4rec(
        jax.random.key(0), cfg, None, sharding="replicated", attn="full"
    )
    _, _, bb_flash, _ = make_sharded_bert4rec(
        jax.random.key(0), cfg, None, sharding="replicated", attn="flash"
    )
    ids = jnp.array([[1, 2, 3, 4, 5, 41, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0]] * 2)
    embs = coll.lookup(tables, {"item": ids})
    lf = bb_full.apply({"params": dense}, embs["item"], key_padding_mask(ids))
    lfl = bb_flash.apply({"params": dense}, embs["item"], key_padding_mask(ids))
    np.testing.assert_allclose(np.asarray(lfl), np.asarray(lf), rtol=3e-5, atol=3e-5)


def test_flash_pads_non_multiple_seq_len():
    # T=200 is not a block multiple; pad-and-slice path must match reference
    q, k, v = _qkv(jax.random.key(7), b=1, h=2, t=200, dh=16)
    valid = jnp.asarray(np.random.default_rng(1).random((1, 200)) > 0.3)
    valid = valid.at[:, 0].set(True)
    out = flash_attention(q, k, v, valid, 128, 128, True)
    ref = _ref_attention(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_backward_with_mask_matches_reference():
    """The Pallas backward kernels under key-padding masks (incl. a fully
    masked row) must match the XLA attention VJP."""
    q, k, v = _qkv(jax.random.key(8), b=2, h=2, t=128, dh=32)
    valid = jnp.asarray(np.random.default_rng(2).random((2, 128)) > 0.35)
    valid = valid.at[:, 0].set(True)
    valid = valid.at[1, :].set(False)  # batch 1: every key masked

    def loss(q, k, v):
        return (flash_attention(q, k, v, valid, 64, 64, True) ** 2).sum()

    def ref_loss(q, k, v):
        s = jnp.einsum("bhtd,bhsd->bhts", q, k) / (q.shape[-1] ** 0.5)
        s = jnp.where(valid[:, None, None, :], s, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s.astype(jnp.float32), -1)
        p = jnp.where(valid.any(-1)[:, None, None, None], p, 0.0)
        return (jnp.einsum("bhts,bhsd->bhtd", p.astype(v.dtype), v) ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-4)


def test_flash_backward_padded_seq_len():
    """T not a block multiple: the backward pad-and-slice path must match."""
    q, k, v = _qkv(jax.random.key(9), b=1, h=2, t=100, dh=16)

    def loss(q, k, v):
        return (flash_attention(q, k, v, None, 64, 64, True) ** 2).sum()

    def ref_loss(q, k, v):
        return (_ref_attention(q, k, v) ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-4)


class TestFatRouted:
    """The routed fat-line path: dedupe_rows_and_lines + row-level
    segment-sum + fat_apply_routed (in-kernel operand routing reusing the
    forward's line gather) must reproduce the plain-table formulations for
    every kind, including padding ids, shared lines, and multi-block."""

    # rowwise_adagrad d=16 (the multi-row-per-line Criteo layout where
    # parity matters most) and the slot-free sgd stay tier-1; adam and
    # adagrad repeat the same routed plumbing at ~35 s of interpret-mode
    # time each on CPU and ride the slow tier to stay inside the tier-1
    # budget.
    @pytest.mark.parametrize("kind,d", [
        ("rowwise_adagrad", 16),
        pytest.param("adam", 64, marks=pytest.mark.slow),
        ("sgd", 8),
        pytest.param("adagrad", 16, marks=pytest.mark.slow),
    ])
    def test_matches_plain_path(self, kind, d):
        from tdfo_tpu.ops.sparse import (
            SparseOptimizer,
            dedupe_rows_and_lines,
            fat_apply_routed,
        )

        rng = np.random.default_rng(17)
        v, b = 530, 700  # > 128 lines at d=16 kinds -> multi-block
        lr, wd = 1e-2, 1e-3
        lay = line_layout(d, kind)
        table = jnp.asarray(rng.standard_normal((v, d)).astype(np.float32))
        ids = jnp.asarray(rng.integers(-1, v, b).astype(np.int32))
        grads = jnp.asarray(rng.standard_normal((b, d)).astype(np.float32))
        grads = jnp.where((ids >= 0)[:, None], grads, 0.0)
        opt = SparseOptimizer(kind=kind, lr=lr, weight_decay=wd,
                              small_vocab_threshold=0)
        t_ref, _ = opt.update(table, opt.init(table), ids, grads)

        seg, ulines, row_lidx, row_slot = dedupe_rows_and_lines(
            ids, capacity_rows=b, capacity_lines=b, rows_per_line=lay.r)
        fat = fat_pack(table, kind=kind)
        oob = jnp.iinfo(jnp.int32).max
        lines = jnp.take(fat, jnp.where(ulines < oob, ulines, 0), axis=0)
        # forward parity: expanded rows == table[ids] (negatives -> row 0)
        flat = lines.reshape(b, lay.tiles * 128)
        rows = jnp.take(flat, jnp.minimum(row_lidx, b - 1), axis=0)[:, :d]
        for s in range(1, lay.r):
            rl = jnp.take(flat, jnp.minimum(row_lidx, b - 1), axis=0)
            rows = jnp.where((row_slot == s)[:, None],
                             rl[:, s * lay.w: s * lay.w + d], rows)
        np.testing.assert_array_equal(
            np.asarray(jnp.take(rows, seg, axis=0)),
            np.asarray(jnp.take(table, jnp.maximum(ids, 0), axis=0)))

        g_u = jax.ops.segment_sum(grads.astype(jnp.float32), seg,
                                  num_segments=b)
        slots = (jnp.zeros((), jnp.int32),) if kind == "adam" else ()
        for interpret in (True, False):  # kernel (interpret) and XLA paths
            t_new, _ = fat_apply_routed(
                fat, slots, ulines, g_u, row_lidx, row_slot, lines,
                embedding_dim=d, kind=kind, lr=lr, weight_decay=wd,
                interpret=interpret)
            got = fat_unpack(t_new, lay, rows=v)[0]
            np.testing.assert_allclose(np.asarray(got), np.asarray(t_ref),
                                       rtol=1e-5, atol=1e-6)


    # one kind suffices: the drain skip is per-grid structure, not per-math
    # (the multi-kind parity matrix above covers the math); rowwise_adagrad
    # d=16 is the multi-row-per-line Criteo layout where parity matters most
    @pytest.mark.parametrize("kind,d", [("rowwise_adagrad", 16)])
    def test_one_block_grid(self, kind, d):
        """nblocks == 1 regression: the final drain used to construct
        write_copy for the off-parity block index -1, loading ids_ref at a
        negative SMEM index before the guard.  The drain must be statically
        skipped for one-block grids and still produce the plain-path
        result."""
        from tdfo_tpu.ops.sparse import (
            SparseOptimizer,
            dedupe_rows_and_lines,
            fat_apply_routed,
        )
        from tdfo_tpu.ops.pallas_kernels import routed_lines_per_step

        rng = np.random.default_rng(23)
        lay = line_layout(d, kind)
        lps = routed_lines_per_step(lay)
        v, b = 200, lps  # capacity_lines == lps -> exactly one grid block
        lr, wd = 1e-2, 1e-3
        table = jnp.asarray(rng.standard_normal((v, d)).astype(np.float32))
        ids = jnp.asarray(rng.integers(-1, v, b).astype(np.int32))
        grads = jnp.asarray(rng.standard_normal((b, d)).astype(np.float32))
        grads = jnp.where((ids >= 0)[:, None], grads, 0.0)
        opt = SparseOptimizer(kind=kind, lr=lr, weight_decay=wd,
                              small_vocab_threshold=0)
        t_ref, _ = opt.update(table, opt.init(table), ids, grads)

        seg, ulines, row_lidx, row_slot = dedupe_rows_and_lines(
            ids, capacity_rows=b, capacity_lines=lps, rows_per_line=lay.r)
        fat = fat_pack(table, kind=kind)
        oob = jnp.iinfo(jnp.int32).max
        lines = jnp.take(fat, jnp.where(ulines < oob, ulines, 0), axis=0)
        g_u = jax.ops.segment_sum(grads.astype(jnp.float32), seg,
                                  num_segments=b)
        slots = (jnp.zeros((), jnp.int32),) if kind == "adam" else ()
        for interpret in (True, False):
            t_new, _ = fat_apply_routed(
                fat, slots, ulines, g_u, row_lidx, row_slot, lines,
                embedding_dim=d, kind=kind, lr=lr, weight_decay=wd,
                interpret=interpret)
            got = fat_unpack(t_new, lay, rows=v)[0]
            np.testing.assert_allclose(np.asarray(got), np.asarray(t_ref),
                                       rtol=1e-5, atol=1e-6)


# u=129 (one line past a block) already forces the multi-block steady
# state; u=400 re-runs it at more grid steps for ~53 s of interpret-mode
# time and rides the slow tier.
@pytest.mark.parametrize("u", [129, pytest.param(400,
                                                 marks=pytest.mark.slow)])
def test_fat_multi_block_pipeline(u):
    """>128 touched lines forces multiple grid steps, exercising the
    double-buffered steady state (block i-1 write drain, block i+1 read
    prefetch, final-block drain) — not just the i==0 branch."""
    rng = np.random.default_rng(u)
    v, d = 512, 64
    table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
    mu = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32)) * 0.1
    nu = jnp.abs(jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))) * 0.1
    ids = jnp.asarray(rng.choice(v, size=u, replace=False).astype(np.int32))
    grads = jnp.asarray(rng.normal(size=(u, d)).astype(np.float32))
    uids, g, valid = dedupe_grads(ids, grads)
    count = jnp.asarray(4, jnp.int32)
    t_ref, mu_ref, nu_ref, _ = sparse_adam(
        table, mu, nu, count, uids, g, valid, lr=1e-2, weight_decay=0.01
    )
    fat_new, slots = fat_apply_unique(
        fat_pack(table, mu, nu), (count,), uids, g, valid, embedding_dim=d,
        kind="adam", lr=1e-2, weight_decay=0.01, interpret=True,
    )
    assert int(slots[0]) == 5
    t_pl, mu_pl, nu_pl = fat_unpack(fat_new, line_layout(d, "adam"), rows=v)
    np.testing.assert_allclose(np.asarray(t_pl), np.asarray(t_ref), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mu_pl), np.asarray(mu_ref), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(nu_pl), np.asarray(nu_ref), rtol=1e-5, atol=1e-6)


class TestQuantizedFatLine:
    """bf16 fat-line storage with in-kernel stochastic rounding: the packed
    lines live at bf16 (half the DMA bytes), the line math runs f32, and
    the writeback requantizes through the counter-hashed SR (fbgemm
    quantized-TBE intra-training parity).  Kernel (interpret) and XLA
    fallback are both exercised; they are NOT required bit-equal to each
    other — each path is deterministic per platform."""

    def _setup(self, v=64, d=16, b=32, seed=0):
        rng = np.random.default_rng(seed)
        table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
        ids = jnp.asarray(rng.integers(0, v, b).astype(np.int32))
        grads = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
        return table, ids, grads

    # interpret-mode runs execute the kernel python per block (~20-40 s on
    # CPU); they ride the slow tier to stay inside the tier-1 budget, same
    # as the test_hot_cold non-default-kind params.  The compiled variants
    # stay tier-1.
    @pytest.mark.parametrize("interpret", [
        pytest.param(True, marks=pytest.mark.slow), False])
    def test_bf16_sr_stays_close_to_f32_and_is_deterministic(self, interpret):
        table, ids, grads = self._setup()
        d = table.shape[1]
        uids, g, valid = dedupe_grads(ids, grads)
        slots = (jnp.zeros((), jnp.int32),)
        # f32 reference trajectory on the same fat geometry
        ref, _ = fat_apply_unique(
            fat_pack(table, kind="adam"), slots, uids, g, valid,
            embedding_dim=d, kind="adam", lr=1e-2, interpret=interpret)
        t_ref = fat_unpack(ref, line_layout(d, "adam"), rows=64)[0]
        fat16 = fat_pack(table, kind="adam", dtype=jnp.bfloat16)
        assert fat16.dtype == jnp.bfloat16
        key = jax.random.PRNGKey(11)
        out = []
        for _ in range(2):
            got, _ = fat_apply_unique(
                fat16, slots, uids, g, valid, embedding_dim=d, kind="adam",
                lr=1e-2, interpret=interpret, sr_key=key)
            assert got.dtype == jnp.bfloat16
            out.append(np.asarray(
                fat_unpack(got, line_layout(d, "adam"), rows=64)[0],
                dtype=np.float32))
        np.testing.assert_array_equal(out[0], out[1])  # same key -> same bits
        np.testing.assert_allclose(out[0], np.asarray(t_ref),
                                   rtol=2e-2, atol=2e-2)
        other, _ = fat_apply_unique(
            fat16, slots, uids, g, valid, embedding_dim=d, kind="adam",
            lr=1e-2, interpret=interpret, sr_key=jax.random.PRNGKey(12))
        o = np.asarray(fat_unpack(other, line_layout(d, "adam"), rows=64)[0],
                       dtype=np.float32)
        assert (o != out[0]).any()  # a different key flips some low bits

    @pytest.mark.parametrize("interpret", [True, False])
    def test_bf16_untouched_rows_bit_identical(self, interpret):
        """SR is the identity on already-representable values, so rows that
        ride a touched block without being touched keep their exact bits —
        including neighbours INSIDE a touched packed line (R > 1)."""
        rng = np.random.default_rng(9)
        v, d, kind = 16, 16, "adagrad"  # R = 4: rows 0-3 share line 0
        table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
        ids = jnp.asarray([0, 2, 9], jnp.int32)
        grads = jnp.asarray(rng.normal(size=(3, d)).astype(np.float32))
        uids, g, valid = dedupe_grads(ids, grads)
        fat16 = fat_pack(table, kind=kind, dtype=jnp.bfloat16)
        got, _ = fat_apply_unique(
            fat16, (), uids, g, valid, embedding_dim=d, kind=kind, lr=1e-2,
            interpret=interpret, sr_key=jax.random.PRNGKey(5))
        lay = line_layout(d, kind)
        before = np.asarray(fat_view(fat16, lay)).view(np.uint16)
        after = np.asarray(fat_view(got, lay)).view(np.uint16)
        touched = {0, 2, 9}
        for r in range(v):
            if r not in touched:
                np.testing.assert_array_equal(after[r], before[r],
                                              err_msg=f"row {r}")

    @pytest.mark.parametrize("interpret", [
        pytest.param(True, marks=pytest.mark.slow), False])
    def test_f32_fat_ignores_sr_key(self, interpret):
        """float32 fat storage must stay byte-identical with or without a
        key: the seed operand only exists for narrow storage, so the f32
        kernel call graph is the pre-quantization one."""
        table, ids, grads = self._setup(d=16)
        uids, g, valid = dedupe_grads(ids, grads)
        fat = fat_pack(table, kind="sgd")
        a, _ = fat_apply_unique(fat, (), uids, g, valid, embedding_dim=16,
                                kind="sgd", lr=1e-2, interpret=interpret)
        b, _ = fat_apply_unique(fat, (), uids, g, valid, embedding_dim=16,
                                kind="sgd", lr=1e-2, interpret=interpret,
                                sr_key=jax.random.PRNGKey(3))
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint32), np.asarray(b).view(np.uint32))
