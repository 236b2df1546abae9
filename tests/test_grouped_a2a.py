"""Grouped cross-table all-to-all + pipelined input-dist (torchrec
``KJTAllToAll`` / ``TrainPipelineSparseDist`` parity).

The collective-count win is assertable without a chip: the grouped forward
must carry exactly 2 ``all_to_all`` ops in its jaxpr for ANY number of
row-sharded tables (vs 2 per table in the per-table program), and the
grouped update at most 2.  Numerics: the stable owner sort delivers each
shard its owned contributions in global batch order, so the grouped update
is bit-identical to the SEQUENTIAL per-table reference (per-table updates
on replicated arrays) — the per-table GSPMD program's own numerics are
layout-dependent (XLA partitions its segment-sums per shard), so that is
the well-defined parity target.  Pipelining shifts every batch's training
one call later without touching its math, so pipelined == eager grouped
bit-identically, state included.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from closeness import assert_within_ulp

from tdfo_tpu.ops.sparse import sparse_optimizer
from tdfo_tpu.parallel.embedding import EmbeddingSpec, ShardedEmbeddingCollection
from tdfo_tpu.train.sparse_step import (
    SparseTrainState,
    make_pipelined_sparse_train_step,
    make_sparse_train_step,
)

B, D = 64, 8


def _specs(n_tables: int, dim: int = D):
    return [
        EmbeddingSpec(name=f"t{i}", num_embeddings=40 + 9 * i,
                      embedding_dim=dim, features=(f"f{i}",),
                      sharding="row", init_scale=0.1)
        for i in range(n_tables)
    ]


def _coll(mesh, n_tables=5, *, grouped=True, stack=False, cf=None):
    return ShardedEmbeddingCollection(
        _specs(n_tables), mesh=mesh, stack_tables=stack,
        fused_kind="rowwise_adagrad", grouped_a2a=grouped,
        a2a_capacity_factor=cf,
    )


def _feats(mesh, n_tables=5, b=B, key=1, with_pad=False):
    k = jax.random.PRNGKey(key)
    out = {}
    for i in range(n_tables):
        ids = jax.random.randint(jax.random.fold_in(k, i), (b,), 0, 40)
        if with_pad:
            ids = jnp.where(jnp.arange(b) % 7 == 0, -1, ids)
        out[f"f{i}"] = jax.device_put(ids, NamedSharding(mesh, P("model")))
    return out


def test_grouped_forward_jaxpr_exactly_two_alltoall_at_26_tables(mesh8):
    """The headline O(2·tables) -> O(1) collective claim, at the DLRM-Criteo
    table count: 26 row-sharded tables of one (dim, dtype) ride ONE id +
    ONE vector exchange; the per-table program issues 52."""
    n = 26
    grouped = _coll(mesh8, n, grouped=True)
    per_table = _coll(mesh8, n, grouped=False)
    tables = grouped.init(jax.random.PRNGKey(0))
    feats = _feats(mesh8, n, b=32)
    jg = str(jax.make_jaxpr(
        lambda t, f: grouped.lookup(t, f, mode="alltoall"))(tables, feats))
    jp = str(jax.make_jaxpr(
        lambda t, f: per_table.lookup(t, f, mode="alltoall"))(tables, feats))
    assert jg.count("all_to_all") == 2, jg.count("all_to_all")
    assert jp.count("all_to_all") == 2 * n


def test_grouped_update_jaxpr_at_most_two_alltoall_at_26_tables(mesh8):
    n = 26
    coll = _coll(mesh8, n, grouped=True)
    tables = coll.init(jax.random.PRNGKey(0))
    opt = sparse_optimizer("rowwise_adagrad", lr=0.05)
    slots = {a: opt.init(t) for a, t in tables.items()}
    feats = _feats(mesh8, n, b=32)
    grads = {f: jnp.ones((32, D)) for f in feats}
    j = str(jax.make_jaxpr(
        lambda t, s, i, g: coll.grouped_update(opt, t, s, i, g)
    )(tables, slots, feats, grads))
    assert j.count("all_to_all") <= 2, j.count("all_to_all")


@pytest.mark.parametrize("stack", [False, True])
def test_grouped_forward_matches_per_table_exactly(mesh8, stack):
    """Same gathers, same unpermute: grouped vectors == per-table vectors
    bitwise on real ids, and padding ids resolve to exact zero on the
    grouped path even inside a ``__tablestack_`` (where the per-table
    program's unconditional ``ids + offset`` aliases -1 onto the previous
    member's last row — pre-existing stacked-path behavior)."""
    grouped = _coll(mesh8, grouped=True, stack=stack)
    per_table = _coll(mesh8, grouped=False, stack=stack)
    tables = grouped.init(jax.random.PRNGKey(0))
    feats = _feats(mesh8, with_pad=True)
    lk_g = jax.jit(lambda t, f: grouped.lookup(t, f, mode="alltoall"))(
        tables, feats)
    lk_p = jax.jit(lambda t, f: per_table.lookup(t, f, mode="alltoall"))(
        tables, feats)
    for f in feats:
        pad = np.asarray(feats[f]) < 0
        np.testing.assert_array_equal(
            np.asarray(lk_g[f])[~pad], np.asarray(lk_p[f])[~pad], err_msg=f)
        assert (np.asarray(lk_g[f])[pad] == 0).all()
        if not stack:  # unstacked offsets are 0: both paths drop -1
            np.testing.assert_array_equal(
                np.asarray(lk_g[f]), np.asarray(lk_p[f]), err_msg=f)


@pytest.mark.parametrize("stack", [False, True])
def test_grouped_update_matches_sequential_reference(mesh8, stack):
    """Tables AND optimizer slots match the sequential per-table reference
    (opt.update per table on REPLICATED arrays, feature order): rows the
    step did not touch stay bit-identical to their initial values, touched
    rows agree within 4 ULP of the array's scale — the reference runs
    op-by-op eagerly, the grouped update as one jitted shard_map program,
    and two XLA programs do not share their last bit."""
    coll = _coll(mesh8, grouped=True, stack=stack)
    tables = coll.init(jax.random.PRNGKey(0))
    opt = sparse_optimizer("rowwise_adagrad", lr=0.05)
    slots = {a: opt.init(t) for a, t in tables.items()}
    feats = _feats(mesh8, with_pad=True)
    k = jax.random.PRNGKey(9)
    grads = {
        f: jax.device_put(
            jax.random.normal(jax.random.fold_in(k, i), (B, D)),
            NamedSharding(mesh8, P("model", None)))
        for i, f in enumerate(feats)
    }
    # sequential reference on replicated copies
    ref_t = {a: jnp.asarray(np.asarray(t)) for a, t in tables.items()}
    ref_s = {a: tuple(jnp.asarray(np.asarray(x)) for x in s)
             for a, s in slots.items()}
    for i, f in enumerate(feats):
        aname, spec, off = coll.resolve(f)
        ids = jnp.asarray(np.asarray(feats[f]))
        ids = jnp.where(ids >= 0, ids + off, -1)
        ref_t[aname], ref_s[aname] = opt.update(
            ref_t[aname], ref_s[aname], ids,
            jnp.asarray(np.asarray(grads[f])), embedding_dim=D)
    got_t, got_s = jax.jit(
        lambda t, s, i, g: coll.grouped_update(opt, t, s, i, g)
    )(tables, slots, feats, grads)
    touched_any = False
    for a in got_t:
        init, ref, got = (np.asarray(x) for x in
                          (tables[a], ref_t[a], got_t[a]))
        untouched = np.all(ref == init, axis=1)
        touched_any |= bool((~untouched).any())
        np.testing.assert_array_equal(got[untouched], init[untouched],
                                      err_msg=a)
        assert_within_ulp(got, ref, err_msg=a)
        for x, y in zip(ref_s[a], got_s[a]):
            assert_within_ulp(y, x, err_msg=a)
    assert touched_any


def _toy_forward(dense, embs, batch):
    h = sum(e.sum(-1) for e in embs.values()) * dense["w"]
    return jnp.mean((h - batch["label"]) ** 2)


def _toy_state(coll):
    return SparseTrainState.create(
        dense_params={"w": jnp.ones(())},
        tx=optax.adam(1e-2),
        tables=coll.init(jax.random.PRNGKey(0)),
        sparse_opt=sparse_optimizer("rowwise_adagrad", lr=0.05),
    )


def _toy_batches(n):
    key = jax.random.PRNGKey(3)
    out = []
    for s in range(n):
        b = {f"f{i}": jax.random.randint(
                jax.random.fold_in(key, 10 * s + i), (B,), 0, 40)
             for i in range(5)}
        b["label"] = jax.random.normal(jax.random.fold_in(key, 999 + s), (B,))
        out.append(b)
    return out


def test_grouped_step_losses_match_per_table(mesh8):
    """Grouped vs per-table eager: the FIRST loss (same initial tables,
    forward is bitwise-equal) must match exactly; later losses track to
    float32 resolution.  They cannot be required bit-identical multi-step:
    the per-table GSPMD update's own numerics are layout-dependent (XLA
    partitions its segment-sums per shard), which is why the bitwise update
    target above is the sequential reference instead."""
    bs = _toy_batches(6)
    losses = {}
    for grouped in (False, True):
        coll = _coll(mesh8, grouped=grouped)
        step = make_sparse_train_step(
            coll, _toy_forward, mode="alltoall", donate=False)
        st = _toy_state(coll)
        ls = []
        for b in bs:
            st, l = step(st, b)
            ls.append(float(l))
        losses[grouped] = ls
    assert losses[True][0] == losses[False][0], losses
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-6)


def test_pipelined_matches_eager_grouped_bitwise(mesh8):
    """prime/step/flush trains the same batches with the same math, one
    call later: losses, tables and slots all bit-identical to eager."""
    bs = _toy_batches(4)
    coll = _coll(mesh8, grouped=True)
    step = make_sparse_train_step(
        coll, _toy_forward, mode="alltoall", donate=False)
    st_e = _toy_state(coll)
    eager = []
    for b in bs:
        st_e, l = step(st_e, b)
        eager.append(float(l))

    pipe = make_pipelined_sparse_train_step(coll, _toy_forward, donate=False)
    st_p = _toy_state(coll)
    piped = []
    carry = pipe.prime(bs[0])
    for b in bs[1:]:
        st_p, l, carry = pipe.step(st_p, b, carry)
        piped.append(float(l))
    st_p, l = pipe.flush(st_p, carry)
    piped.append(float(l))

    assert piped == eager, (piped, eager)
    assert int(st_p.step) == int(st_e.step) == len(bs)
    for a in st_e.tables:
        np.testing.assert_array_equal(
            np.asarray(st_e.tables[a]), np.asarray(st_p.tables[a]), err_msg=a)
        for x, y in zip(st_e.slots[a], st_p.slots[a]):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_pipelined_step_jaxpr_single_grouped_exchange(mesh8):
    """One pipelined step = next batch's id dist (1) + carried batch's
    vector return (1) + grouped update (2): 4 all_to_all total, independent
    of table count."""
    coll = _coll(mesh8, grouped=True)
    pipe = make_pipelined_sparse_train_step(coll, _toy_forward, jit=False)
    st = _toy_state(coll)
    b = _toy_batches(1)[0]
    carry = pipe.prime(b)
    j = str(jax.make_jaxpr(pipe.step)(st, b, carry))
    assert j.count("all_to_all") == 4, j.count("all_to_all")
    assert str(jax.make_jaxpr(pipe.prime)(b)).count("all_to_all") == 1


def test_pipelined_requires_grouped_collection(mesh8):
    coll = _coll(mesh8, grouped=False)
    with pytest.raises(ValueError, match="grouped_a2a"):
        make_pipelined_sparse_train_step(coll, _toy_forward)


def test_grouped_a2a_overflow_counts_dropped_ids(mesh8):
    """The capacity knob's failure mode stays observable in grouped mode:
    a skewed batch (every id owned by shard 0) overflows the combined
    stream's bucket cap by a hand-computable amount."""
    m = 2  # model-axis shards in mesh8
    cf = 0.5
    coll = _coll(mesh8, n_tables=2, grouped=True, cf=cf)
    tables = coll.init(jax.random.PRNGKey(0))
    # every id < rows_per_shard -> owner 0 on every shard
    feats = {f"f{i}": jnp.zeros((B,), jnp.int32) for i in range(2)}
    got = int(jax.jit(lambda t, f: coll.a2a_overflow(t, f))(tables, feats))
    # per shard: combined stream n = 2 tables x B/m ids, cap per bucket =
    # round8(cf*n/m) (same _a2a_bucket_cap the real exchange sizes its send
    # buffers with); shard 0's bucket holds ALL n ids -> n - cap dropped,
    # summed over the m shards
    n_local = 2 * B // m
    cap = min(n_local, -(-int(cf * n_local / m) // 8) * 8)
    assert cap < n_local  # the scenario really overflows
    assert got == m * (n_local - cap), (got, n_local, cap)
    # uncapped collection reports zero
    coll0 = _coll(mesh8, n_tables=2, grouped=True, cf=None)
    assert int(jax.jit(
        lambda t, f: coll0.a2a_overflow(t, f))(tables, feats)) == 0


def test_grouped_capacity_drops_same_ids_forward_and_backward(mesh8):
    """Under a finite capacity factor the stable sort makes forward and
    update drop the SAME overflowed ids: training still moves every row
    whose forward vector was non-zero, and only those."""
    coll = _coll(mesh8, n_tables=1, grouped=True, cf=0.5)
    tables = coll.init(jax.random.PRNGKey(0))
    opt = sparse_optimizer("rowwise_adagrad", lr=0.05)
    slots = {a: opt.init(t) for a, t in tables.items()}
    feats = {"f0": jnp.zeros((B,), jnp.int32)}  # all ids -> shard 0: overflow
    grads = {"f0": jnp.ones((B, D))}
    vec = jax.jit(lambda t, f: coll.lookup(t, f, mode="alltoall"))(
        tables, feats)["f0"]
    kept_fwd = int((np.abs(np.asarray(vec)).sum(-1) > 0).sum())
    nt, _ = jax.jit(lambda t, s, i, g: coll.grouped_update(opt, t, s, i, g))(
        tables, slots, feats, grads)
    aname = coll.resolve("f0")[0]
    rows_touched = int((np.abs(np.asarray(nt[aname])
                               - np.asarray(tables[aname])).sum(-1) > 0).sum())
    assert kept_fwd < B  # the cap really dropped something
    # all kept ids are id 0 -> exactly one row updates iff anything was kept
    assert rows_touched == (1 if kept_fwd else 0)


def test_grouped_routes_around_replicated_tables(mesh8):
    """A mixed spec set (row-sharded + replicated) splits cleanly: grouped
    exchange for the sharded tables, plain gather for the replicated one,
    bitwise equal to the all-per-table program."""
    specs = _specs(3) + [
        EmbeddingSpec(name="r0", num_embeddings=16, embedding_dim=D,
                      features=("fr",), sharding="replicated",
                      init_scale=0.1)
    ]
    mk = lambda grouped: ShardedEmbeddingCollection(
        specs, mesh=mesh8, fused_kind="rowwise_adagrad", grouped_a2a=grouped)
    grouped, per_table = mk(True), mk(False)
    tables = grouped.init(jax.random.PRNGKey(0))
    feats = dict(_feats(mesh8, 3),
                 fr=jnp.arange(B, dtype=jnp.int32) % 16)
    lk_g = jax.jit(lambda t, f: grouped.lookup(t, f, mode="alltoall"))(
        tables, feats)
    lk_p = jax.jit(lambda t, f: per_table.lookup(t, f, mode="alltoall"))(
        tables, feats)
    for f in feats:
        np.testing.assert_array_equal(
            np.asarray(lk_g[f]), np.asarray(lk_p[f]), err_msg=f)
