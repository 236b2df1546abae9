"""Hybrid sparse/dense train step — the DMP + CombinedOptimizer equivalent.

torchrec splits parameters in two (``torchrec/train.py:235-254``): embedding
tables get a fused in-backward sparse optimizer (fbgemm), dense params get a
regular optimizer wrapped in ``CombinedOptimizer``.  The TPU-native
re-expression:

  * the step computes gradients w.r.t. the *gathered vectors* (an activation,
    shape [B, D]) instead of the dense [V, D] table — the jnp.take VJP that
    would materialise a dense table gradient is never taken;
  * each table then gets a row-sparse update (``tdfo_tpu/ops/sparse``) that
    touches O(unique ids) rows of table + optimizer slots;
  * dense params flow through optax exactly as in the dense step.

Under GSPMD with row-sharded tables the gather/scatter pair lowers to ICI
collectives; tables, slots and updates all stay sharded end to end.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import optax

from tdfo_tpu.obs import counters as obs_counters
from tdfo_tpu.ops.quant import bytes_to_f32, dequantize_rows
from tdfo_tpu.ops.quant import sr_key as _make_sr_key
from tdfo_tpu.ops.sparse import SparseOptimizer, cache_lookup_rows, dedupe_ids
from tdfo_tpu.ops.sparse import cache_overlay_rows
from tdfo_tpu.parallel.embedding import (
    CACHE_PREFIX, ShardedEmbeddingCollection, qscale_name)


def _array_is_narrow(state: "SparseTrainState", aname: str) -> bool:
    """True when ``aname``'s table or any optimizer slot is stored narrow
    (bf16 or int8): the signal that its update needs a stochastic-rounding
    key.  Static under jit (dtypes are trace-time constants), so f32 arrays
    keep a key-free — hence byte-identical — update graph."""
    if state.tables[aname].dtype in (jnp.bfloat16, jnp.int8):
        return True
    return any(leaf.dtype == jnp.bfloat16
               for leaf in jax.tree_util.tree_leaves(state.slots[aname]))


def _pin_replicated(mesh, tree):
    """Constrain every leaf of ``tree`` to a fully-replicated layout.

    The update cache is replicated state by contract (``init_caches``
    commits it at ``P()``), but inside a jitted program GSPMD's sharding
    PROPAGATION — not the committed input shardings — decides the layout
    of intermediates, and it is free to partition the [C] sorted-id
    directory over the batch axis (observed under the trainer's fused
    step+AUC program: a data-sharded directory breaks the searchsorted
    routing and silently drops every cache write).  Explicit constraints
    at the cache read and write boundaries make replication part of the
    program instead of a propagation accident.  No-op when ``mesh`` is
    None (single-device / eager tests)."""
    if mesh is None:
        return tree
    s = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(x, s), tree)


__all__ = [
    "SparseTrainState",
    "make_sparse_train_step",
    "make_cache_flush_fn",
    "PipelinedSparseStep",
    "make_pipelined_sparse_train_step",
]


@jax.tree_util.register_dataclass
@dataclass
class SparseTrainState:
    """Dense params under optax + embedding tables under sparse optimizers."""

    step: jax.Array
    dense_params: Any
    opt_state: Any
    tables: dict[str, jax.Array]
    slots: dict[str, Any]
    tx: optax.GradientTransformation = field(metadata=dict(static=True))
    sparse_opt: SparseOptimizer = field(metadata=dict(static=True))

    @classmethod
    def create(cls, *, dense_params, tx, tables, sparse_opt) -> "SparseTrainState":
        from tdfo_tpu.parallel.embedding import QSCALE_PREFIX

        return cls(
            step=jnp.zeros((), jnp.int32),
            dense_params=dense_params,
            opt_state=tx.init(dense_params),
            tables=dict(tables),
            # int8 (scale, offset) sidecars are storage, not optimized
            # parameters: they get no slot state (empty tuple keeps the
            # pytree structure table-keyed and checkpoint-stable)
            slots={n: (() if n.startswith(QSCALE_PREFIX)
                       else sparse_opt.init(t))
                   for n, t in tables.items()},
            tx=tx,
            sparse_opt=sparse_opt,
        )


def make_sparse_train_step(
    coll: ShardedEmbeddingCollection,
    forward: Callable,
    *,
    mode: str = "gspmd",
    donate: bool = True,
    jit: bool = True,
    batch_transform: Callable | None = None,
    with_aux: bool = False,
    dedup_lookup: bool = False,
):
    """Build the jitted hybrid step.

    ``forward(dense_params, embeddings, batch) -> scalar loss`` receives the
    gathered vectors ``{feature: [**ids_shape, D]}`` — the model under this
    step consumes embeddings as inputs (HistoryArch-style,
    ``torchrec/models.py:163-178``) rather than owning the tables.  A forward
    that also accepts a ``dropout_rng`` keyword gets a per-step key derived
    from the rng passed to the step (``step(state, batch, rng)``), enabling
    stochastic regularisation in this regime.

    ``batch`` must contain an id array for every feature the collection
    serves (same key names) — or, with ``batch_transform``, whatever the
    transform turns into one: the transform runs INSIDE the jitted step
    (e.g. ``jagged_to_dense`` materialising [B, T] ids from a
    (values, lengths) jagged batch, fbgemm ``jagged_2d_to_dense`` parity).
    A transform that accepts an ``rng`` keyword gets the step's key, as a
    ``dropout_rng`` forward does (block diffusion's noisy copy of the ids).

    ``with_aux=True``: ``forward`` must return ``(loss, aux)`` and the step
    returns ``(state, (loss, aux))`` — the hook for per-epoch TRAIN metrics
    (reference parity: train-side ROC-AUC, ``jax-flax/train_dp.py:219-220``).

    ``dedup_lookup=True`` (requires ``mode="gspmd"``, non-negative ids): the
    TBE unique-then-expand recipe.  Per table array, ONE sort deduplicates
    the step's ids; the forward gathers only the unique rows (a compact,
    cache-resident block — scattered gathers from a multi-GB table cost
    ~40 ns/row on v5e, expands from the compact block ~2 ns/row) and the
    backward segment-sums grads by the SAME mapping, feeding the optimizer
    directly — no second dedupe.  Embeddings and updates are bit-identical
    to the default path (same gather values, same segment construction);
    measured ~25%% off the DLRM-Criteo step.  Arrays whose update needs the
    explicit shard_map program (fused fat + real row sharding) keep the
    default update path.

    Grouped exchange (collection built with ``grouped_a2a=True``, requires
    ``mode="alltoall"``): every row/table-sharded feature's forward rides
    the collection's combined-stream lookup and the update half runs ONE
    :meth:`~ShardedEmbeddingCollection.grouped_update` over all of them —
    O(1) collectives per direction instead of O(tables).  Losses and
    tables are bit-identical to the sequential per-table reference (see
    ``grouped_update``'s docstring for the exact guarantee).

    Hot/cold collections (``ShardedEmbeddingCollection`` built with
    ``hot_ids``, requires ``mode="gspmd"``): each split table's ids route
    once per step into hot-head positions and residual cold ids.  The hot
    half updates via ONE one-hot MXU contraction + dense [K, D]
    read-modify-write per table (``SparseOptimizer.dense_update`` — no
    sort/dedupe/scatter for the power-law head, where most ids land); the
    cold half rides the unchanged machinery above with hot hits as -1
    (dropped by dedupe like padding).  Fully-hot tables skip the cold side
    statically, shrinking the cold distinct-row bound and scatter cost.

    Update cache (collection built with ``cache_rows > 0`` AND a state
    whose ``slots`` carry the ``coll.init_caches`` entries, requires
    ``mode="gspmd"``): every cached array's row update runs IN its cache
    (``SparseOptimizer.cache_update[_unique]`` — admit misses gather-only,
    update hits scatter-free, touch no big array), and forward gathers
    overlay the cached rows so nothing ever reads a stale big-table value.
    The step's jaxpr then contains NO scatter into any big table; the
    trainer pays the coalesced write-back via :func:`make_cache_flush_fn`
    once per ``flush_every`` interval.  Bit-identical to the eager path
    (see ``ops/sparse.py``'s cache section for why).  A state without
    cache entries — the default — traces the exact pre-cache graph.
    """
    import inspect

    if dedup_lookup and mode != "gspmd":
        raise ValueError("dedup_lookup composes with lookup mode 'gspmd' only")
    if coll.cache_rows > 0 and mode != "gspmd":
        raise ValueError(
            "the update cache (cache_rows > 0) composes with lookup mode "
            "'gspmd' only")
    features = list(coll.features())
    takes_rng = "dropout_rng" in inspect.signature(forward).parameters
    transform_rng = (batch_transform is not None and "rng" in
                     inspect.signature(batch_transform).parameters)
    # hot/cold (frequency-partitioned) tables: per-feature id routing splits
    # lookups into hot-head positions (updated scatter-free via one-hot MXU
    # contractions, no dedupe) and residual cold ids (riding the unchanged
    # machinery below — hot hits become -1 and the existing negative-id
    # padding semantics drop them everywhere).  All statics resolved here.
    hot_tables = coll.hot_tables()
    if hot_tables and mode != "gspmd":
        raise ValueError(
            "hot/cold tables compose with lookup mode 'gspmd' only")
    feat_table = {f: coll.resolve(f)[1].name for f in features}
    hot_by_table = {
        t: [f for f in features if feat_table[f] == t] for t in hot_tables
    }
    hot_feats = {f for t in hot_tables for f in hot_by_table[t]}
    # features of FULLY hot tables have no cold side at all: they skip the
    # cold concat/dedupe/gather/update statically (at the Criteo profile 18
    # of 26 tables fit under a 16k hot cap, shrinking the cold distinct-row
    # bound ~102k -> ~65k and the scatter cost with it)
    full_hot_feats = {f for f in hot_feats if coll.hot_full(feat_table[f])}
    # grouped cross-table exchange (torchrec KJTAllToAll parity): every
    # row/table-sharded feature rides ONE combined id all_to_all + ONE
    # vector all_to_all per direction instead of one pair per TABLE.
    # ``coll.lookup`` routes the forward internally; the update below
    # replaces these features' per-array loop with one grouped_update.
    use_grouped = (
        mode == "alltoall" and coll.grouped_a2a
        and coll.mesh is not None and coll.n_shards > 1)
    grouped_feats = tuple(
        f for f in features
        if coll.resolve(f)[1].sharding in ("row", "table")
    ) if use_grouped else ()
    grouped_arrays = tuple(sorted({coll.resolve(f)[0] for f in grouped_feats}))
    by_table_static: dict[str, list[str]] = {}
    for f in features:
        if f in full_hot_feats or f in grouped_feats:
            continue
        by_table_static.setdefault(coll.resolve(f)[0], []).append(f)

    def _concat_ids(feats, ids, rows_per_line: int = 1):
        id_list, sizes, bound = [], [], 0
        for f in feats:
            _, spec, offset = coll.resolve(f)
            # negative (padding or routed-to-hot) ids must stay negative:
            # adding the stack offset would alias them into the previous
            # member's rows and corrupt its update
            flat = jnp.where(ids[f] >= 0, ids[f] + offset, -1).reshape(-1)
            id_list.append(flat)
            sizes.append(flat.shape[0])
            # static per-feature distinct bound: a feature can touch at most
            # min(its id count, its member vocab) rows — minus the hot-head
            # rows for hot/cold tables (hot ids never reach the cold side) —
            # or, for fat-line arrays, that many LINES (+1: a member's row
            # range may straddle one extra line at each unaligned stack
            # offset)
            if rows_per_line == 1:
                cold_rows = spec.num_embeddings - coll.hot_count(spec.name)
                bound += min(flat.shape[0], cold_rows)
            else:
                bound += min(flat.shape[0],
                             -(-spec.num_embeddings // rows_per_line) + 1)
        return jnp.concatenate(id_list), sizes, bound

    def step(state: SparseTrainState, batch, rng=None) -> tuple[SparseTrainState, jax.Array]:
        step_rng = None
        if (takes_rng or transform_rng) and rng is not None:
            step_rng = jax.random.fold_in(rng, state.step)
        if transform_rng:
            batch = batch_transform(batch, rng=step_rng)
        elif batch_transform is not None:
            batch = batch_transform(batch)
        ids = {f: batch[f] for f in features}
        # update-cache coverage, static under jit: the presence of the
        # coll.init_caches entries in state.slots IS the enable signal, so
        # a cache-off state traces the exact pre-cache (byte-identical)
        # graph even on a cache_rows > 0 collection
        cached = {k[len(CACHE_PREFIX):] for k in state.slots
                  if k.startswith(CACHE_PREFIX)}

        # hot/cold routing: one remap per hot feature, shared by the
        # forward gather and both update halves.  cold_ids carries -1 at
        # hot hits (dropped by dedupe / clamped by gathers), hot_pos
        # carries -1 at cold hits (zeroed by the one-hot contraction).
        hot_pos: dict[str, jax.Array] = {}
        cold_ids = ids
        if hot_tables:
            cold_ids = dict(ids)
            for f in hot_feats:
                hp, ci = coll.route_ids(f, ids[f])
                hot_pos[f] = hp
                cold_ids[f] = ci

        def _merge_hot(f, cold_vec):
            """Select hot-head vectors at hot hits (identity off hot/cold)."""
            hp = hot_pos.get(f)
            if hp is None:
                return cold_vec
            hot = state.tables[coll.hot_array_name(feat_table[f])]
            hot_vec = jnp.take(
                hot, jnp.maximum(hp, 0), axis=0).astype(jnp.float32)
            if cold_vec is None:  # fully hot: there is no cold side
                return hot_vec
            return jnp.where((hp >= 0)[..., None], hot_vec, cold_vec)

        def _overlay_lookup(embs, feats):
            """Serve cached rows into ``coll.lookup`` outputs: between
            flushes the big tables are stale for dirty cached rows, so any
            position whose gather landed on a cached row must show the
            cache value — replicating each lookup path's own padding-clamp
            semantics so the overlaid vector equals the eager-path gather
            bit-for-bit."""
            for f in feats:
                aname, _, off = coll.resolve(f)
                # fully hot features never read their (dead) cold rows
                if aname not in cached or f in full_hot_feats:
                    continue
                cache = _pin_replicated(
                    coll.mesh, state.slots[CACHE_PREFIX + aname])
                hp = hot_pos.get(f)
                if hp is None:
                    # plain gspmd lookup: jnp.take clamps out-of-range ids
                    v = state.tables[aname].shape[0]
                    gid = jnp.clip(ids[f] + off, 0, v - 1)
                else:
                    # hot/cold lookup gathers cold at where(cold >= 0,
                    # cold + off, 0) and selects the hot head at hot hits —
                    # those positions must keep the (authoritative) hot vec
                    cold = cold_ids[f]
                    gid = jnp.where(cold >= 0, cold + off, 0)
                cur, hit = cache_lookup_rows(cache, gid, mesh=coll.mesh)
                if hp is not None:
                    hit = hit & (hp < 0)
                embs[f] = jnp.where(
                    hit[..., None], cur.astype(embs[f].dtype), embs[f])
            return embs

        # Gradients w.r.t. the gathered vectors, never the [V, D] table.
        def loss_from_embs(dense_params, embs):
            if takes_rng:
                return forward(dense_params, embs, batch, dropout_rng=step_rng)
            return forward(dense_params, embs, batch)

        dedup_ctx: dict[str, tuple] = {}
        with jax.named_scope("emb_lookup"):
            if dedup_lookup:
                embs = {}
                for tname, feats in by_table_static.items():
                    # column-sharded tables shard the EMBEDDING dim: the compact
                    # gather would drop the activation sharding the default
                    # lookup constrains — keep them on the default path (their
                    # update falls back too, since no ctx entry exists)
                    if (tname in coll.specs
                            and coll.specs[tname].sharding == "column"):
                        embs.update(_overlay_lookup(coll.lookup(
                            state.tables, {f: ids[f] for f in feats}, mode=mode),
                            feats))
                        continue
                    table = state.tables[tname]
                    d = coll.array_embedding_dim(tname)
                    fat = table.ndim == 3
                    all_ids, sizes, bound = _concat_ids(feats, cold_ids)
                    obs_counters.emit(f"emb/{tname}/touched_ids",
                                      lambda a=all_ids: (a >= 0).sum())
                    total = all_ids.shape[0]
                    # +1 slack: negative (padding) ids dedupe to ONE sentinel
                    # slot beyond the real-id bound; without it the expand would
                    # clamp the sentinel seg onto a real row's slot
                    cap = (-(-(bound + 1) // 8) * 8) if bound + 1 < total else None
                    if fat:
                        # routed fat-line flow: ONE sort yields the row-level
                        # expand key AND the line grouping.  Forward: gather
                        # whole packed LINES straight off the 3D array (the
                        # fast TPU gather — reshaping the table to a row view
                        # materialises a multi-GB copy), expand per distinct
                        # row from the SMALL gathered block, slot-select, then
                        # expand per batch position.  Sentinel rows resolve to
                        # line 0 slot 0 = row 0, the default lookup's clip.
                        from tdfo_tpu.ops.sparse import dedupe_rows_and_lines

                        lay = coll.fat_layout_for(tname)
                        _, _, bound_l = _concat_ids(feats, cold_ids,
                                                    rows_per_line=lay.r)
                        cap_r = cap if cap is not None else total
                        cap_l = min(cap_r, -(-(bound_l + 1) // 8) * 8)
                        seg, ulines, row_lidx, row_slot = dedupe_rows_and_lines(
                            all_ids.astype(jnp.int32), capacity_rows=cap_r,
                            capacity_lines=cap_l, rows_per_line=lay.r,
                        )
                        oob = jnp.iinfo(jnp.int32).max
                        lines = jnp.take(
                            table, jnp.where(ulines < oob, ulines, 0), axis=0)
                        flat = lines.reshape(cap_l, lay.tiles * 128)
                        rowlines = jnp.take(
                            flat, jnp.minimum(row_lidx, cap_l - 1), axis=0)
                        # int8 byte lines slot-select codes AND the adjacent 8
                        # sidecar bytes, then decode the small selected block
                        span = d + 8 if lay.dtype == "int8" else d
                        rows = rowlines[:, :span]
                        for s in range(1, lay.r):
                            rows = jnp.where(
                                (row_slot == s)[:, None],
                                rowlines[:, s * lay.w: s * lay.w + span], rows)
                        if lay.dtype == "int8":
                            rows = dequantize_rows(
                                rows[:, :d], bytes_to_f32(rows[:, d:span]))
                        dedup_ctx[tname] = ("routed", ulines, seg, row_lidx,
                                            row_slot, lines)
                        obs_counters.emit(f"emb/{tname}/unique_lines",
                                          lambda u=ulines: (u < oob).sum())
                    else:
                        uids, seg, valid = dedupe_ids(
                            all_ids.astype(jnp.int32), capacity=cap,
                            max_distinct=cap,
                        )
                        rows = jnp.take(table, jnp.where(valid, uids, 0), axis=0)
                        if coll.array_is_int8(tname):
                            # sidecar rides the same compact gather; dequantize
                            # the small block so downstream expand stays f32
                            rows = dequantize_rows(rows, jnp.take(
                                state.tables[qscale_name(tname)],
                                jnp.where(valid, uids, 0), axis=0))
                        if tname in cached:
                            # serve cached (authoritative) rows into the compact
                            # gather — sentinel slots clamp to row 0 exactly like
                            # the eager gather, so they overlay to row 0's
                            # authoritative value too
                            rows = cache_overlay_rows(
                                _pin_replicated(
                                    coll.mesh,
                                    state.slots[CACHE_PREFIX + tname]),
                                jnp.where(valid, uids, 0),
                                rows, mesh=coll.mesh)
                        dedup_ctx[tname] = ("rows", uids, seg, valid)
                        obs_counters.emit(f"emb/{tname}/unique_rows",
                                          lambda v=valid: v.sum())
                    off = 0
                    # dequantize after the compact gather (identity for f32):
                    # the model interface is f32 whatever the storage dtype
                    rows = rows.astype(jnp.float32)
                    for f, n_f in zip(feats, sizes):
                        e = jnp.take(rows, seg[off:off + n_f], axis=0)
                        e = e.reshape(*ids[f].shape, e.shape[-1])
                        embs[f] = _merge_hot(f, e)
                        off += n_f
                for f in full_hot_feats:  # no cold side: hot gather only
                    embs[f] = _merge_hot(f, None)
            else:
                # coll.lookup routes hot/cold internally (eval shares that path)
                embs = _overlay_lookup(
                    coll.lookup(state.tables, ids, mode=mode), features)
        with jax.named_scope("dense_fwd_bwd"):
            loss, (g_dense, g_embs) = jax.value_and_grad(
                loss_from_embs, argnums=(0, 1), has_aux=with_aux
            )(state.dense_params, embs)
        aux = None
        if with_aux:
            loss, aux = loss
        if obs_counters.enabled():
            # global norms over the dense half and the gathered-vector
            # grads (the table-side signal without a [V, D] reduction);
            # param_norm walks the full tables — one HBM pass, priced into
            # telemetry.counters = true only
            obs_counters.emit("grad_norm",
                              optax.global_norm((g_dense, g_embs)))
            obs_counters.emit("param_norm", optax.global_norm(
                (state.dense_params, state.tables)))

        # dense half: optax
        with jax.named_scope("dense_update"):
            updates, new_opt_state = state.tx.update(g_dense, state.opt_state, state.dense_params)
            new_dense = optax.apply_updates(state.dense_params, updates)

        # sparse half: group features by table, one row-sparse update each.
        # _sr_key: stochastic-rounding key per narrow-storage array, derived
        # from (state.step, array name) — bit-deterministic, resume-exact —
        # and None for f32 arrays (their update graph stays key-free)
        def _sr_key(aname):
            return (_make_sr_key(state.step, aname)
                    if _array_is_narrow(state, aname) else None)

        with jax.named_scope("emb_update"):
            new_tables = dict(state.tables)
            new_slots = dict(state.slots)
            if grouped_feats:
                # one grouped backward exchange for every row/table-sharded
                # feature: 2 collectives total (ids + grads) vs 2 per array.
                # One base key serves the whole exchange (grouped_update folds
                # per-array table ids itself)
                g_narrow = any(_array_is_narrow(state, a) for a in grouped_arrays)
                gt, gs = coll.grouped_update(
                    state.sparse_opt, state.tables, state.slots,
                    {f: ids[f] for f in grouped_feats},
                    {f: g_embs[f] for f in grouped_feats},
                    sr_key=(_make_sr_key(state.step, "__grouped_update__")
                            if g_narrow else None))
                new_tables.update(gt)
                new_slots.update(gs)
            for tname, feats in by_table_static.items():
                grad_list = [
                    g_embs[f].reshape(-1, g_embs[f].shape[-1]) for f in feats
                ]
                all_grads = jnp.concatenate(grad_list)
                # small-vocab adam tables keep the one-hot MXU tier (raw ids,
                # no scatter — ~10x the per-row scatter formulation update_unique
                # would fall back to)
                small_adam = (
                    state.sparse_opt.kind == "adam"
                    and state.tables[tname].ndim == 2
                    and state.tables[tname].shape[0]
                    <= state.sparse_opt.small_vocab_threshold
                )
                if (tname in dedup_ctx and not small_adam
                        and not coll.needs_shard_map_update(tname)):
                    # shared-dedupe fast path: segment-sum by the forward's seg
                    # and feed the optimizer tiers directly (no second sort)
                    ctx = dedup_ctx[tname]
                    d_t = coll.array_embedding_dim(tname)
                    if ctx[0] == "routed":
                        # row-level segment-sum (the cheap space) + in-kernel
                        # routing: the whole table update has no XLA scatter,
                        # and the kernel reuses the forward's line gather
                        _, ulines, seg, row_lidx, row_slot, lines = ctx
                        with jax.named_scope("segment_sum"):
                            g_u = jax.ops.segment_sum(
                                all_grads.astype(jnp.float32), seg,
                                num_segments=row_lidx.shape[0],
                            )
                        new_tables[tname], new_slots[tname] = (
                            state.sparse_opt.update_routed(
                                state.tables[tname], state.slots[tname], ulines,
                                g_u, row_lidx, row_slot, lines,
                                embedding_dim=d_t, sr_key=_sr_key(tname),
                                platform=coll.platform,
                            ))
                        continue
                    _, uids, seg, valid = ctx
                    with jax.named_scope("segment_sum"):
                        g_u = jax.ops.segment_sum(
                            all_grads, seg, num_segments=uids.shape[0]
                        )
                    g_u = jnp.where(valid[:, None], g_u, 0.0)
                    if tname in cached:
                        # cached tier: admit misses (gather-only), update in
                        # the cache — the big table and slot rows stay
                        # untouched until the coalesced flush.  All cache-math
                        # operands pin replicated (see _pin_replicated).
                        ck = CACHE_PREFIX + tname
                        u_r, g_r, v_r = _pin_replicated(
                            coll.mesh, (uids, g_u, valid))
                        qsc = (state.tables[qscale_name(tname)]
                               if coll.array_is_int8(tname) else None)
                        with obs_counters.scope(f"emb/{tname}/"):
                            new_cache, new_slots[tname] = (
                                state.sparse_opt.cache_update_unique(
                                    _pin_replicated(coll.mesh, state.slots[ck]),
                                    state.tables[tname],
                                    state.slots[tname], u_r, g_r, v_r,
                                    step=state.step, sr_key=_sr_key(tname),
                                    mesh=coll.mesh, qscale=qsc,
                                ))
                        new_slots[ck] = _pin_replicated(coll.mesh, new_cache)
                        continue
                    if (coll.array_is_int8(tname)
                            and state.tables[tname].ndim == 2):
                        # plain 2D int8: the (scale, offset) sidecar is a
                        # separate array; fat int8 carries it in-line and
                        # never threads qscale
                        qn = qscale_name(tname)
                        (new_tables[tname], new_slots[tname],
                         new_tables[qn]) = state.sparse_opt.update_unique(
                            state.tables[tname], state.slots[tname], uids, g_u,
                            valid, embedding_dim=d_t, sr_key=_sr_key(tname),
                            qscale=state.tables[qn], platform=coll.platform,
                        )
                    else:
                        new_tables[tname], new_slots[tname] = (
                            state.sparse_opt.update_unique(
                                state.tables[tname], state.slots[tname], uids,
                                g_u, valid, embedding_dim=d_t,
                                sr_key=_sr_key(tname), platform=coll.platform,
                            ))
                    continue
                all_ids, _, bound = _concat_ids(feats, cold_ids)
                obs_counters.emit(f"emb/{tname}/touched_ids",
                                  lambda a=all_ids: (a >= 0).sum())
                # dedupe capacity = the proven bound when it is tighter than the
                # id count: scatter cost scales with SLOTS, so stacked many-table
                # arrays (e.g. DLRM-Criteo, where small tables are fully covered
                # every step) save ~half the update cost
                total = all_ids.shape[0]
                md = -(-bound // 8) * 8 if bound < total else None
                if tname in cached and not small_adam:
                    # cached tier: the SAME dedupe (bit-identical summed grads)
                    # feeds the cache update; no big array is written.  All
                    # cache-math operands pin replicated (see _pin_replicated).
                    ck = CACHE_PREFIX + tname
                    i_r, g_r = _pin_replicated(
                        coll.mesh, (all_ids, all_grads))
                    qsc = (state.tables[qscale_name(tname)]
                           if coll.array_is_int8(tname) else None)
                    with obs_counters.scope(f"emb/{tname}/"):
                        new_cache, new_slots[tname] = (
                            state.sparse_opt.cache_update(
                                _pin_replicated(coll.mesh, state.slots[ck]),
                                state.tables[tname],
                                state.slots[tname], i_r, g_r,
                                step=state.step, capacity=md, max_distinct=md,
                                sr_key=_sr_key(tname), mesh=coll.mesh,
                                qscale=qsc,
                            ))
                    new_slots[ck] = _pin_replicated(coll.mesh, new_cache)
                    continue
                # sharding-aware routing: fused row-sharded tables update inside
                # an explicit shard_map (Pallas has no GSPMD partition rule)
                if (coll.array_is_int8(tname)
                        and state.tables[tname].ndim == 2):
                    # plain 2D int8 threads the separate qscale sidecar; fat
                    # int8 byte containers carry it in-line
                    qn = qscale_name(tname)
                    (new_tables[tname], new_slots[tname],
                     new_tables[qn]) = coll.sparse_update(
                        state.sparse_opt, tname,
                        state.tables[tname], state.slots[tname], all_ids,
                        all_grads, max_distinct=md, sr_key=_sr_key(tname),
                        qscale=state.tables[qn],
                    )
                else:
                    new_tables[tname], new_slots[tname] = coll.sparse_update(
                        state.sparse_opt, tname,
                        state.tables[tname], state.slots[tname], all_ids,
                        all_grads, max_distinct=md, sr_key=_sr_key(tname),
                    )

        # hot-head updates: per logical table, ONE one-hot MXU contraction
        # merges duplicates and a full dense [K, D] read-modify-write
        # applies the optimizer — no sort, no dedupe, no scatter (the
        # power-law head is where scatters hurt: most of the batch's ids
        # land here).  Cold hits carry hot_pos -1 and one-hot to zero rows.
        with jax.named_scope("hot_update"):
            for tname in hot_tables:
                hname = coll.hot_array_name(tname)
                feats = hot_by_table[tname]
                hp_all = jnp.concatenate(
                    [hot_pos[f].reshape(-1) for f in feats])
                obs_counters.emit(f"emb/{tname}/hot_ids",
                                  lambda h=hp_all: (h >= 0).sum())
                g_all = jnp.concatenate([
                    g_embs[f].reshape(-1, g_embs[f].shape[-1]) for f in feats
                ])
                new_tables[hname], new_slots[hname] = state.sparse_opt.dense_update(
                    state.tables[hname], state.slots[hname], hp_all, g_all,
                    sr_key=_sr_key(hname),
                )

        return (
            SparseTrainState(
                step=state.step + 1,
                dense_params=new_dense,
                opt_state=new_opt_state,
                tables=new_tables,
                slots=new_slots,
                tx=state.tx,
                sparse_opt=state.sparse_opt,
            ),
            (loss, aux) if with_aux else loss,
        )

    if not jit:
        return step
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_cache_flush_fn(*, donate: bool = True, jit: bool = True,
                        mesh=None, counters: bool = False):
    """Build the coalesced write-back program of the update cache:
    ``flush(state) -> (state, overflow)``.

    A SEPARATE jitted program from the train step — the trainer calls it
    every ``flush_every`` steps and unconditionally before checkpoint,
    eval, and serving export — so the big-table scatter cost is paid once
    per interval and non-flush step jaxprs carry no big-table scatter at
    all.  Per cached array it writes every dirty row + slot mirror back
    verbatim (``SparseOptimizer.cache_flush``), evicts down to the hottest
    half, and surfaces the interval's admission-overflow counters:
    ``overflow`` maps array name -> int32 count of distinct ids whose
    updates were LOST to a full cache.  Callers MUST fail on any non-zero
    entry — the bit-exactness contract is broken past that point.  A state
    without cache entries flushes to itself (empty overflow dict).  Pass
    the collection's ``mesh`` so the cache stays pinned replicated inside
    the jitted program (see ``_pin_replicated``).

    ``counters=True`` (``telemetry.counters``) collects the flush's
    in-graph diagnostics (``emb/<array>/cache_flushed_rows`` and resident
    counts, ``tdfo_tpu/obs/counters.py``) and returns ``(state, overflow,
    counters_dict)``; the default signature and graph are untouched."""

    def _body(state: SparseTrainState):
        new_tables = dict(state.tables)
        new_slots = dict(state.slots)
        overflow = {}
        for key in sorted(state.slots):
            if not key.startswith(CACHE_PREFIX):
                continue
            aname = key[len(CACHE_PREFIX):]
            qn = qscale_name(aname)
            with obs_counters.scope(f"emb/{aname}/"):
                if qn in state.tables:
                    # int8 array: flush bit-copies codes AND the per-row
                    # (scale, offset) grid back into the table + sidecar
                    cache, table, slots, qsc, over = (
                        state.sparse_opt.cache_flush(
                            _pin_replicated(mesh, state.slots[key]),
                            state.tables[aname], state.slots[aname],
                            qscale=state.tables[qn]))
                    new_tables[qn] = qsc
                else:
                    cache, table, slots, over = state.sparse_opt.cache_flush(
                        _pin_replicated(mesh, state.slots[key]),
                        state.tables[aname], state.slots[aname])
            new_tables[aname] = table
            new_slots[aname] = slots
            new_slots[key] = _pin_replicated(mesh, cache)
            overflow[aname] = over
        return SparseTrainState(
            step=state.step,
            dense_params=state.dense_params,
            opt_state=state.opt_state,
            tables=new_tables,
            slots=new_slots,
            tx=state.tx,
            sparse_opt=state.sparse_opt,
        ), overflow

    if counters:
        def flush(state: SparseTrainState):
            with obs_counters.collect() as ctrs:
                new_state, overflow = _body(state)
            return new_state, overflow, dict(ctrs)
    else:
        flush = _body

    if not jit:
        return flush
    return jax.jit(flush, donate_argnums=(0,) if donate else ())


@dataclass(frozen=True)
class PipelinedSparseStep:
    """The three entry points of the cross-batch pipelined sparse step.

    ``prime(batch) -> carry`` starts the pipeline on the epoch's first
    batch (input-dist only, no training).  ``step(state, batch, carry,
    rng=None) -> (state, out, carry)`` issues the NEW batch's input-dist
    and trains the CARRIED one.  ``flush(state, carry, rng=None) ->
    (state, out)`` trains the last carried batch at epoch end.  ``carry``
    is a plain ``(transformed_batch, ctx)`` pytree — checkpoint cursors
    need not persist it: on resume the stream re-yields the carried batch
    and ``prime`` rebuilds the ctx (pure function of the ids).
    """

    prime: Callable
    step: Callable
    flush: Callable


def make_pipelined_sparse_train_step(
    coll: ShardedEmbeddingCollection,
    forward: Callable,
    *,
    donate: bool = True,
    jit: bool = True,
    batch_transform: Callable | None = None,
    with_aux: bool = False,
):
    """Cross-batch input-dist pipelining over the grouped exchange —
    torchrec ``TrainPipelineSparseDist`` parity (``torchrec/train.py``'s
    pipeline overlaps batch N+1's ``KJTAllToAll`` with batch N's
    fwd/bwd/update on a side CUDA stream).

    The TPU-native re-expression: :meth:`grouped_input_dist` reads NO
    tables (owner/virtual-id arithmetic is pure spec-derived statics), so
    batch N+1's bucketing + id ``all_to_all`` is issued at the TOP of the
    jitted step, before batch N's dense fwd/bwd and table update — with no
    data dependency between them, the XLA scheduler is free to overlap the
    collective with the compute instead of serialising 2 exchange phases
    behind the step.

    Semantics: losses, rng folds (by ``state.step``, which counts TRAINED
    batches) and state evolution are bit-identical to the eager grouped
    step — outputs just surface one ``step`` call later, with ``flush``
    draining the final batch.  Requires a ``grouped_a2a`` collection on a
    multi-shard mesh; hot/cold tables and ``dedup_lookup`` (both
    gspmd-only) do not compose.  Features on replicated tables keep their
    plain lookup/update path inside the same jitted program.
    """
    import inspect

    if not (coll.grouped_a2a and coll.mesh is not None and coll.n_shards > 1):
        raise ValueError(
            "the pipelined sparse step requires a grouped_a2a collection on "
            "a multi-shard mesh ([embeddings] grouped_a2a = true with "
            "model_parallel)")
    if coll.hot_tables():
        raise ValueError(
            "hot/cold tables do not compose with the pipelined sparse step "
            "(they require lookup mode 'gspmd')")
    if coll.cache_rows > 0:
        raise ValueError(
            "the update cache (cache_rows > 0) does not compose with the "
            "pipelined sparse step (it requires lookup mode 'gspmd')")
    features = list(coll.features())
    takes_rng = "dropout_rng" in inspect.signature(forward).parameters
    grouped_feats = tuple(
        f for f in features if coll.resolve(f)[1].sharding in ("row", "table"))
    grouped_arrays = tuple(sorted({coll.resolve(f)[0] for f in grouped_feats}))
    rest_feats = tuple(f for f in features if f not in grouped_feats)
    by_table_rest: dict[str, list[str]] = {}
    for f in rest_feats:
        by_table_rest.setdefault(coll.resolve(f)[0], []).append(f)

    def input_dist(batch):
        if batch_transform is not None:
            batch = batch_transform(batch)
        ctx = coll.grouped_input_dist({f: batch[f] for f in grouped_feats})
        return batch, ctx

    def train_on(state, batch, ctx, rng):
        ids = {f: batch[f] for f in features}
        step_rng = None
        if takes_rng and rng is not None:
            # same fold as the eager step: state.step counts trained batches
            step_rng = jax.random.fold_in(rng, state.step)

        def loss_from_embs(dense_params, embs):
            if takes_rng:
                return forward(dense_params, embs, batch, dropout_rng=step_rng)
            return forward(dense_params, embs, batch)

        with jax.named_scope("emb_lookup"):
            embs = coll.grouped_lookup(
                state.tables, {f: ids[f] for f in grouped_feats}, ctx)
            if rest_feats:
                embs.update(coll.lookup(
                    state.tables, {f: ids[f] for f in rest_feats},
                    mode="alltoall"))
        with jax.named_scope("dense_fwd_bwd"):
            loss, (g_dense, g_embs) = jax.value_and_grad(
                loss_from_embs, argnums=(0, 1), has_aux=with_aux
            )(state.dense_params, embs)
        aux = None
        if with_aux:
            loss, aux = loss
        if obs_counters.enabled():
            # global norms over the dense half and the gathered-vector
            # grads (the table-side signal without a [V, D] reduction);
            # param_norm walks the full tables — one HBM pass, priced into
            # telemetry.counters = true only
            obs_counters.emit("grad_norm",
                              optax.global_norm((g_dense, g_embs)))
            obs_counters.emit("param_norm", optax.global_norm(
                (state.dense_params, state.tables)))

        with jax.named_scope("dense_update"):
            updates, new_opt_state = state.tx.update(
                g_dense, state.opt_state, state.dense_params)
            new_dense = optax.apply_updates(state.dense_params, updates)

        # same SR keying as the eager step: state.step counts trained
        # batches, so pipelining does not shift the key stream
        def _sr_key(aname):
            return (_make_sr_key(state.step, aname)
                    if _array_is_narrow(state, aname) else None)

        with jax.named_scope("emb_update"):
            new_tables = dict(state.tables)
            new_slots = dict(state.slots)
            g_narrow = any(_array_is_narrow(state, a) for a in grouped_arrays)
            gt, gs = coll.grouped_update(
                state.sparse_opt, state.tables, state.slots,
                {f: ids[f] for f in grouped_feats},
                {f: g_embs[f] for f in grouped_feats},
                sr_key=(_make_sr_key(state.step, "__grouped_update__")
                        if g_narrow else None))
            new_tables.update(gt)
            new_slots.update(gs)
            for tname, feats in by_table_rest.items():
                id_list, bound = [], 0
                for f in feats:
                    _, spec, off = coll.resolve(f)
                    flat = jnp.where(ids[f] >= 0, ids[f] + off, -1).reshape(-1)
                    id_list.append(flat)
                    bound += min(flat.shape[0], spec.num_embeddings)
                all_ids = jnp.concatenate(id_list)
                all_grads = jnp.concatenate([
                    g_embs[f].reshape(-1, g_embs[f].shape[-1]) for f in feats])
                md = -(-bound // 8) * 8 if bound < all_ids.shape[0] else None
                if (coll.array_is_int8(tname)
                        and state.tables[tname].ndim == 2):
                    # plain 2D int8 threads the separate qscale sidecar; fat
                    # int8 byte containers carry it in-line
                    qn = qscale_name(tname)
                    (new_tables[tname], new_slots[tname],
                     new_tables[qn]) = coll.sparse_update(
                        state.sparse_opt, tname,
                        state.tables[tname], state.slots[tname], all_ids,
                        all_grads, max_distinct=md, sr_key=_sr_key(tname),
                        qscale=state.tables[qn],
                    )
                else:
                    new_tables[tname], new_slots[tname] = coll.sparse_update(
                        state.sparse_opt, tname,
                        state.tables[tname], state.slots[tname], all_ids,
                        all_grads, max_distinct=md, sr_key=_sr_key(tname),
                    )

        new_state = SparseTrainState(
            step=state.step + 1,
            dense_params=new_dense,
            opt_state=new_opt_state,
            tables=new_tables,
            slots=new_slots,
            tx=state.tx,
            sparse_opt=state.sparse_opt,
        )
        return new_state, (loss, aux) if with_aux else loss

    def prime(batch):
        return input_dist(batch)

    def step(state, batch, carry, rng=None):
        # the NEW batch's dist first: no table dependency, so the scheduler
        # may overlap its id all_to_all with everything below
        new_carry = input_dist(batch)
        cur_batch, ctx = carry
        state, out = train_on(state, cur_batch, ctx, rng)
        return state, out, new_carry

    def flush(state, carry, rng=None):
        cur_batch, ctx = carry
        return train_on(state, cur_batch, ctx, rng)

    if jit:
        d = (0,) if donate else ()
        return PipelinedSparseStep(
            prime=jax.jit(prime),
            step=jax.jit(step, donate_argnums=d),
            flush=jax.jit(flush, donate_argnums=d),
        )
    return PipelinedSparseStep(prime=prime, step=step, flush=flush)
