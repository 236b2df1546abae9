"""Serving subsystem tests: export, scoring parity, corpus, retrieval.

The three contracts under test, in order of importance:

  * train/serve skew is ZERO — a scorer built from an exported bundle
    produces bitwise the same logits as the training eval step it mirrors
    (``train/ctr.py make_ctr_sparse_eval_step``), for both CTR regimes;
  * bundles are hot/cold-AGNOSTIC — the ``{name}__hot`` merge writes the
    live head rows over their dead cold duplicates, so a split and an
    unsplit run of the same state export byte-identical tables;
  * sharded exact retrieval is bitwise-equal (ids AND f32 scores) to the
    single-device stable-argsort reference, including tie-breaks, for
    k in {10, 100} and a corpus that does NOT divide the mesh evenly.
"""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from closeness import assert_same_topk, assert_within_ulp

from tdfo_tpu.models.twotower import (
    TWOTOWER_CATEGORICAL,
    TwoTower,
    TwoTowerBackbone,
    ctr_embedding_specs,
)
from tdfo_tpu.ops.sparse import sparse_optimizer
from tdfo_tpu.parallel.embedding import ShardedEmbeddingCollection
from tdfo_tpu.serve.corpus import build_corpus, synthetic_item_features
from tdfo_tpu.serve.export import (
    BUNDLE_VERSION,
    export_bundle,
    load_bundle,
    merged_tables,
)
from tdfo_tpu.serve.retrieval import (
    make_retrieval,
    mips_scores,
    retrieval_reference,
)
from tdfo_tpu.serve.scoring import make_scorer
from tdfo_tpu.train.ctr import make_ctr_sparse_eval_step
from tdfo_tpu.train.sparse_step import SparseTrainState

SIZE_MAP = {"user": 1000, "item": 800, "language": 8, "is_ebook": 2,
            "format": 8, "publisher": 64, "pub_decade": 16}
CAT_COLS = ("user_id", "item_id", "language", "is_ebook", "format",
            "publisher", "pub_decade")
CONT_COLS = ("avg_rating", "num_pages")


def _ctr_batch(rng, n, with_label=True):
    batch = {
        "user_id": rng.integers(0, SIZE_MAP["user"], n).astype(np.int32),
        "item_id": rng.integers(0, SIZE_MAP["item"], n).astype(np.int32),
        "language": rng.integers(0, 8, n).astype(np.int32),
        "is_ebook": rng.integers(0, 2, n).astype(np.int32),
        "format": rng.integers(0, 8, n).astype(np.int32),
        "publisher": rng.integers(0, 64, n).astype(np.int32),
        "pub_decade": rng.integers(0, 16, n).astype(np.int32),
        "avg_rating": rng.random(n).astype(np.float32),
        "num_pages": rng.random(n).astype(np.float32),
    }
    if with_label:
        batch["label"] = rng.integers(0, 2, n).astype(np.float32)
    return batch


def _twotower_sparse(mesh, hot_ids=None, seed=0):
    """ShardedEmbeddingCollection + TwoTowerBackbone + SparseTrainState,
    mirroring the trainer's ``_build_ctr_sparse`` at toy scale."""
    specs = ctr_embedding_specs(SIZE_MAP, 16, sharding="row",
                                fused_threshold=None)
    coll = ShardedEmbeddingCollection(specs, mesh=mesh, hot_ids=hot_ids)
    backbone = TwoTowerBackbone(embed_dim=16)
    tables = coll.init(jax.random.key(seed))
    dummy_e = {f: jnp.zeros((1, 16), jnp.float32) for f in coll.features()}
    dummy_c = {c: jnp.zeros((1,), jnp.float32) for c in CONT_COLS}
    state = SparseTrainState.create(
        dense_params=backbone.init(jax.random.key(seed + 1),
                                   dummy_e, dummy_c)["params"],
        tx=optax.adamw(1e-3), tables=tables,
        sparse_opt=sparse_optimizer("adam", lr=1e-3, weight_decay=0.0),
    )
    return coll, backbone, state


def _export_sparse(out_dir, coll, state, **kw):
    return export_bundle(
        out_dir, model="twotower", embed_dim=16, cat_columns=CAT_COLS,
        cont_columns=CONT_COLS, size_map=SIZE_MAP, coll=coll,
        tables=state.tables, dense_params=state.dense_params, **kw)


# ------------------------------------------------------- train/serve skew


def test_sparse_bundle_scores_match_eval_step(mesh8, tmp_path):
    """The zero-skew bar: serving logits from a round-tripped bundle are
    BITWISE equal to the training eval step's logits."""
    coll, backbone, state = _twotower_sparse(mesh8)
    batch = _ctr_batch(np.random.default_rng(7), 64)
    _, ref = make_ctr_sparse_eval_step(coll, backbone)(state, batch)

    scorer = make_scorer(
        load_bundle(_export_sparse(tmp_path / "b", coll, state)), mesh=mesh8)
    got = scorer.score({k: v for k, v in batch.items() if k != "label"})
    assert np.asarray(got).dtype == np.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_dlrm_bundle_scores_match_eval_step(mesh8, tmp_path):
    """Same zero-skew bar for the custom-schema DLRM regime (one table per
    categorical column, generic specs)."""
    from tdfo_tpu.models.dlrm import DLRMBackbone, generic_embedding_specs

    cats, conts = ("c0", "c1", "c2"), ("x0",)
    sizes = {"c0": 7, "c1": 50, "c2": 300}
    coll = ShardedEmbeddingCollection(
        generic_embedding_specs(sizes, cats, 8, "row", fused_threshold=None),
        mesh=mesh8)
    bb = DLRMBackbone(embed_dim=8, cat_columns=cats, cont_columns=conts)
    tables = coll.init(jax.random.key(0))
    dummy_e = {c: jnp.zeros((1, 8), jnp.float32) for c in cats}
    dummy_c = {c: jnp.zeros((1,), jnp.float32) for c in conts}
    state = SparseTrainState.create(
        dense_params=bb.init(jax.random.key(1), dummy_e, dummy_c)["params"],
        tx=optax.adam(1e-3), tables=tables,
        sparse_opt=sparse_optimizer("adam", lr=1e-3, weight_decay=0.0))
    rng = np.random.default_rng(3)
    batch = {c: rng.integers(0, sizes[c], 32).astype(np.int32) for c in cats}
    batch["x0"] = rng.random(32).astype(np.float32)
    batch["label"] = rng.integers(0, 2, 32).astype(np.float32)
    _, ref = make_ctr_sparse_eval_step(coll, bb)(state, batch)

    out = export_bundle(
        tmp_path / "b", model="dlrm", embed_dim=8, cat_columns=cats,
        cont_columns=conts, size_map=sizes, coll=coll, tables=state.tables,
        dense_params=state.dense_params)
    scorer = make_scorer(load_bundle(out), mesh=mesh8)
    got = scorer.score({k: v for k, v in batch.items() if k != "label"})
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    with pytest.raises(ValueError, match="no user tower"):
        scorer.user_embed(batch)


def test_dense_bundle_roundtrip(tmp_path):
    """Dense (replicated nn.Embed) regime: bundle scoring matches a direct
    model.apply bitwise; the tower methods factorize the dot."""
    sizes = {k: max(4, v // 10) for k, v in SIZE_MAP.items()}
    model = TwoTower(size_map=sizes, embed_dim=8)
    rng = np.random.default_rng(0)
    batch = {c: rng.integers(0, sizes[f], 16).astype(np.int32)
             for c, f in (("user_id", "user"), ("item_id", "item"),
                          ("language", "language"), ("is_ebook", "is_ebook"),
                          ("format", "format"), ("publisher", "publisher"),
                          ("pub_decade", "pub_decade"))}
    for c in CONT_COLS:
        batch[c] = rng.random(16).astype(np.float32)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    params = model.init(jax.random.key(0), jb)["params"]
    ref = np.asarray(model.apply({"params": params}, jb))

    out = export_bundle(
        tmp_path / "b", model="twotower", embed_dim=8, cat_columns=CAT_COLS,
        cont_columns=CONT_COLS, size_map=sizes, params=params)
    bundle = load_bundle(out)
    assert bundle.kind == "dense" and bundle.dtype == "float32"
    scorer = make_scorer(bundle)
    np.testing.assert_array_equal(np.asarray(scorer.score(dict(batch))), ref)
    u = np.asarray(scorer.user_embed(dict(batch)))
    it = np.asarray(scorer.item_embed(dict(batch)))
    np.testing.assert_allclose(np.sum(u * it, axis=-1), ref, atol=1e-5)


def test_sparse_towers_factorize_score(mesh8, tmp_path):
    """user_embed . item_embed reproduces score() for the sparse regime —
    the property that makes corpus-based retrieval score-consistent."""
    coll, _, state = _twotower_sparse(mesh8)
    scorer = make_scorer(
        load_bundle(_export_sparse(tmp_path / "b", coll, state)), mesh=mesh8)
    batch = _ctr_batch(np.random.default_rng(11), 32, with_label=False)
    s = np.asarray(scorer.score(dict(batch)))
    u = np.asarray(scorer.user_embed(dict(batch)))
    it = np.asarray(scorer.item_embed(dict(batch)))
    np.testing.assert_allclose(np.sum(u * it, axis=-1), s, atol=1e-5)


# --------------------------------------------------- hot/cold agnosticism


def test_hot_split_bundle_matches_unsplit(mesh8, tmp_path):
    """Satellite bar: a bundle exported from a hot-split collection is
    byte-identical to the unsplit equivalent — the merge takes the LIVE
    ``{name}__hot`` rows, not the dead cold duplicates."""
    hot = {"item_embed": np.array([0, 3, 97, 512], np.int32),
           "user_embed": np.arange(16, dtype=np.int32)}
    coll_b, _, state_b = _twotower_sparse(mesh8, hot_ids=None)
    coll_h, _, state_h = _twotower_sparse(mesh8, hot_ids=hot)

    # Same-seed init starts bit-identical (hot heads gather cold rows), so
    # perturb the LIVE storage the way training would: new values into the
    # hot heads (split run) == same values into the cold rows (unsplit run),
    # and poison the split run's dead cold duplicates to prove the merge
    # never reads them.
    tables_h = dict(state_h.tables)
    tables_b = dict(state_b.tables)
    for tname, hids in hot.items():
        aname, spec, off = coll_h.resolve_table(tname)
        fresh = np.random.default_rng(len(hids)).normal(
            size=(len(hids), spec.embedding_dim)).astype(np.float32)
        tables_h[coll_h.hot_array_name(tname)] = jnp.asarray(fresh)
        cold = np.asarray(tables_h[aname]).copy()
        cold[off + hids] = 7777.0  # dead storage; must never be exported
        tables_h[aname] = jnp.asarray(cold)
        base = np.asarray(tables_b[aname]).copy()
        base[off + hids] = fresh
        tables_b[aname] = jnp.asarray(base)

    merged_h = merged_tables(coll_h, tables_h)
    merged_b = merged_tables(coll_b, tables_b)
    for name in merged_b:
        np.testing.assert_array_equal(merged_h[name], merged_b[name])
        assert not np.any(merged_h[name] == 7777.0)

    export = lambda d, coll, tables, state: export_bundle(
        d, model="twotower", embed_dim=16, cat_columns=CAT_COLS,
        cont_columns=CONT_COLS, size_map=SIZE_MAP, coll=coll, tables=tables,
        dense_params=state.dense_params)
    sc_h = make_scorer(load_bundle(
        export(tmp_path / "hot", coll_h, tables_h, state_h)), mesh=mesh8)
    sc_b = make_scorer(load_bundle(
        export(tmp_path / "base", coll_b, tables_b, state_b)), mesh=mesh8)
    batch = _ctr_batch(np.random.default_rng(5), 64, with_label=False)
    np.testing.assert_array_equal(np.asarray(sc_h.score(dict(batch))),
                                  np.asarray(sc_b.score(dict(batch))))


def test_merged_tables_inverts_fused_storage(mesh8):
    """merged_tables must invert the fat-line fused layout and table
    stacking too: the exported rows equal what lookup() serves."""
    from tdfo_tpu.models.dlrm import generic_embedding_specs

    sizes = {"big": 40000, "small": 60}  # big > fused_threshold -> fat lines
    coll = ShardedEmbeddingCollection(
        generic_embedding_specs(sizes, ("big", "small"), 16, "row",
                                fused_threshold=16384),
        mesh=mesh8, stack_tables=True)
    tables = coll.init(jax.random.key(2))
    merged = merged_tables(coll, tables)
    for col, size in sizes.items():
        assert merged[f"{col}_embed"].shape == (size, 16)
        ids = np.random.default_rng(1).integers(0, size, 64).astype(np.int32)
        looked = coll.lookup(tables, {col: jnp.asarray(ids)}, mode="gspmd")
        np.testing.assert_array_equal(merged[f"{col}_embed"][ids],
                                      np.asarray(looked[col]))


# ----------------------------------------------------------- bundle refusals


def test_bundle_refusals(mesh8, tmp_path):
    import json

    coll, _, state = _twotower_sparse(mesh8)
    out = _export_sparse(tmp_path / "b", coll, state)

    with pytest.raises(ValueError, match="not a serving bundle"):
        load_bundle(tmp_path / "nope")

    manifest = json.loads((out / "bundle.json").read_text())
    stale = dict(manifest, bundle_version=BUNDLE_VERSION + 1)
    (out / "bundle.json").write_text(json.dumps(stale))
    with pytest.raises(ValueError, match="bundle_version"):
        load_bundle(out)

    torn = dict(manifest)
    torn["tables"] = dict(manifest["tables"], ghost=[4, 16])
    (out / "bundle.json").write_text(json.dumps(torn))
    with pytest.raises(ValueError, match="torn bundle"):
        load_bundle(out)

    torn = dict(manifest)
    torn["tables"] = dict(manifest["tables"], item_embed=[3, 3])
    (out / "bundle.json").write_text(json.dumps(torn))
    with pytest.raises(ValueError, match="torn bundle"):
        load_bundle(out)

    (out / "bundle.json").write_text(json.dumps(dict(manifest, kind="ann")))
    with pytest.raises(ValueError, match="unknown kind"):
        load_bundle(out)

    # a valid bundle whose tables do not cover the model's schema (here a
    # 2-table DLRM bundle re-labelled as a 1-column config) is refused by
    # make_scorer, not served with a missing table
    from tdfo_tpu.models.dlrm import generic_embedding_specs

    sizes = {"c0": 5, "c1": 6}
    coll2 = ShardedEmbeddingCollection(generic_embedding_specs(
        sizes, ("c0", "c1"), 4, "replicated", fused_threshold=None))
    out2 = export_bundle(
        tmp_path / "d", model="dlrm", embed_dim=4, cat_columns=("c0", "c1"),
        cont_columns=("x0",), size_map=sizes, coll=coll2,
        tables=coll2.init(jax.random.key(0)),
        dense_params={"w": np.zeros((4,), np.float32)})
    m2 = json.loads((out2 / "bundle.json").read_text())
    (out2 / "bundle.json").write_text(json.dumps(
        dict(m2, cat_columns=["c0"])))
    with pytest.raises(ValueError, match="do not match"):
        make_scorer(load_bundle(out2))

    with pytest.raises(ValueError, match="not both"):
        export_bundle(tmp_path / "x", model="twotower", embed_dim=16,
                      cat_columns=CAT_COLS, cont_columns=CONT_COLS,
                      size_map=SIZE_MAP)


def test_bf16_export_policy(mesh8, tmp_path):
    """mixed_precision=True on a TPU platform casts every floating array to
    bf16 (stored as uint16 bit patterns) and the loader views them back."""
    coll, _, state = _twotower_sparse(mesh8)
    out = _export_sparse(tmp_path / "b", coll, state,
                         mixed_precision=True, platform="tpu")
    bundle = load_bundle(out)
    assert bundle.dtype == "bfloat16"
    assert all(t.dtype == jnp.bfloat16 for t in bundle.tables.values())
    ref = merged_tables(coll, state.tables)
    np.testing.assert_array_equal(
        np.asarray(bundle.tables["item_embed"], np.float32),
        np.asarray(ref["item_embed"].astype(jnp.bfloat16), np.float32))
    # the default policy keeps f32 (the zero-skew guarantee)
    f32 = load_bundle(_export_sparse(tmp_path / "f", coll, state))
    assert f32.dtype == "float32"


# ------------------------------------------------------------------ corpus


def test_corpus_build_chunked(mesh8, tmp_path):
    """Chunked sweep == one-shot sweep; uneven catalogs pad with id -1 rows
    up to a shard multiple and land sharded over the data axis."""
    from jax.sharding import PartitionSpec as P

    coll, _, state = _twotower_sparse(mesh8)
    scorer = make_scorer(
        load_bundle(_export_sparse(tmp_path / "b", coll, state)), mesh=mesh8)
    n_items = 333  # does not divide the 4-way data axis
    feats = synthetic_item_features(SIZE_MAP, n_items, seed=3)
    corpus = build_corpus(scorer, feats, corpus_batch=128, mesh=mesh8)
    assert corpus.n_items == n_items
    assert corpus.vectors.shape == (336, 16)  # padded to a multiple of 4
    assert corpus.vectors.sharding.spec == P("data", None)
    ids = np.asarray(corpus.ids)
    np.testing.assert_array_equal(ids[:n_items], np.arange(n_items))
    np.testing.assert_array_equal(ids[n_items:], [-1, -1, -1])
    np.testing.assert_array_equal(np.asarray(corpus.vectors)[n_items:], 0.0)

    oneshot = build_corpus(scorer, feats, corpus_batch=n_items, mesh=mesh8)
    np.testing.assert_allclose(np.asarray(corpus.vectors),
                               np.asarray(oneshot.vectors),
                               rtol=1e-6, atol=1e-7)

    with pytest.raises(ValueError, match="align"):
        build_corpus(scorer, dict(feats, language=feats["language"][:-1]))
    with pytest.raises(ValueError, match="missing columns"):
        build_corpus(scorer, {"item_id": np.arange(4, dtype=np.int32)})


# --------------------------------------------------------------- retrieval


def test_sharded_retrieval_bitwise(mesh8, tmp_path):
    """THE acceptance bar: sharded top-k returns exactly the same ids as
    the single-device stable-argsort reference, and f32 scores within 4 ULP
    of the score scale (the sharded and the single-device scan are two XLA
    programs: their last bits belong to the compiler), for k in {10, 100},
    on a corpus that does not divide the 4-way data axis."""
    coll, _, state = _twotower_sparse(mesh8)
    scorer = make_scorer(
        load_bundle(_export_sparse(tmp_path / "b", coll, state)), mesh=mesh8)
    corpus = build_corpus(
        scorer, synthetic_item_features(SIZE_MAP, 333, seed=3),
        corpus_batch=128, mesh=mesh8)
    rng = np.random.default_rng(9)
    queries = scorer.user_embed(
        {"user_id": rng.integers(0, SIZE_MAP["user"], 16).astype(np.int32)})
    for k in (10, 100):
        s, i = make_retrieval(corpus, mesh=mesh8, top_k=k)(queries)
        s_ref, i_ref = retrieval_reference(queries, corpus, top_k=k)
        assert_same_topk(i, s, i_ref, s_ref)
        assert np.all(np.asarray(i) >= 0)  # padding rows never retrieved


def test_retrieval_ties_prefer_lower_id(mesh8):
    """Duplicate corpus vectors straddling shard boundaries: ties must
    resolve to the LOWER corpus id in both programs."""
    rng = np.random.default_rng(4)
    base = rng.normal(size=(5, 8)).astype(np.float32)
    vectors = jnp.asarray(np.tile(base, (8, 1)))  # 40 rows, every score x8
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tdfo_tpu.serve.corpus import Corpus
    c = Corpus(
        vectors=jax.device_put(vectors,
                               NamedSharding(mesh8, P("data", None))),
        ids=jax.device_put(jnp.arange(40, dtype=jnp.int32),
                           NamedSharding(mesh8, P("data"))),
        n_items=40)
    queries = jnp.asarray(rng.normal(size=(6, 8)).astype(np.float32))
    s, i = make_retrieval(c, mesh=mesh8, top_k=10)(queries)
    s_ref, i_ref = retrieval_reference(queries, c, top_k=10)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    # the winning duplicate of each clone group is its lowest id (< 5)
    assert np.all(np.asarray(i)[:, 0] < 5)


def test_retrieval_single_device_and_validation(mesh8, tmp_path):
    coll, _, state = _twotower_sparse(mesh8)
    scorer = make_scorer(
        load_bundle(_export_sparse(tmp_path / "b", coll, state)), mesh=mesh8)
    corpus = build_corpus(
        scorer, synthetic_item_features(SIZE_MAP, 50, seed=1),
        corpus_batch=64)  # no mesh: single-device layout
    queries = scorer.user_embed(
        {"user_id": np.arange(4, dtype=np.int32)})
    s, i = make_retrieval(corpus, top_k=10)(queries)
    s_ref, i_ref = retrieval_reference(queries, corpus, top_k=10)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))

    with pytest.raises(ValueError, match="top_k"):
        make_retrieval(corpus, top_k=0)
    with pytest.raises(ValueError, match="exceeds the corpus"):
        make_retrieval(corpus, top_k=51)


def test_mips_scores_formula():
    """The shared score formula: bf16 operands, f32 accumulation."""
    q = jnp.asarray(np.random.default_rng(0).normal(size=(3, 8)), jnp.float32)
    v = jnp.asarray(np.random.default_rng(1).normal(size=(5, 8)), jnp.float32)
    s = mips_scores(q, v)
    assert s.shape == (3, 5) and s.dtype == jnp.float32
    ref = np.asarray(q.astype(jnp.bfloat16), np.float32) @ \
        np.asarray(v.astype(jnp.bfloat16), np.float32).T
    np.testing.assert_allclose(np.asarray(s), ref, rtol=1e-2)


# -------------------------------------------- int8 corpora + two-stage


def _rand_corpus(mesh, n_items, dim=16, dtype="float32", seed=0):
    """Manually assembled corpus (no scorer sweep): padded to a shard
    multiple like ``build_corpus``, ids -1 on padding, quantized AFTER
    padding — the layout every retrieval program assumes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tdfo_tpu.ops.quant import quantize_rows
    from tdfo_tpu.serve.corpus import Corpus

    rng = np.random.default_rng(seed)
    n_shards = mesh.shape["data"] if mesh is not None else 1
    pad = (-n_items) % n_shards
    vecs = np.zeros((n_items + pad, dim), np.float32)
    vecs[:n_items] = rng.normal(size=(n_items, dim)).astype(np.float32)
    ids = np.concatenate([np.arange(n_items, dtype=np.int32),
                          np.full(pad, -1, np.int32)])
    v, qs = jnp.asarray(vecs), None
    if dtype == "int8":
        v, qs = quantize_rows(v)
    elif dtype == "bfloat16":
        v = v.astype(jnp.bfloat16)
    i = jnp.asarray(ids)
    if mesh is not None:
        v = jax.device_put(v, NamedSharding(mesh, P("data", None)))
        i = jax.device_put(i, NamedSharding(mesh, P("data")))
        if qs is not None:
            qs = jax.device_put(qs, NamedSharding(mesh, P("data", None)))
    return Corpus(vectors=v, ids=i, n_items=n_items, qscale=qs)


def _recall(ids, ids_ref):
    a, b = np.asarray(ids), np.asarray(ids_ref)
    return sum(len(set(r) & set(rr)) for r, rr in zip(a, b)) / b.size


def test_int8_corpus_build_and_exact_retrieval(mesh8, tmp_path):
    """``build_corpus(dtype="int8")`` stores codes + [N_pad, 2] f32 sidecar
    sharded with the rows, and the EXACT program over it (dequantize
    in-shard, then the usual scan) returns the reference's ids, with scores
    within 4 ULP of it — the reference itself scores the corpus as served
    (dequantized), not pre-quantization."""
    from jax.sharding import PartitionSpec as P

    coll, _, state = _twotower_sparse(mesh8)
    scorer = make_scorer(
        load_bundle(_export_sparse(tmp_path / "b", coll, state)), mesh=mesh8)
    feats = synthetic_item_features(SIZE_MAP, 333, seed=3)
    corpus = build_corpus(scorer, feats, corpus_batch=128, mesh=mesh8,
                          dtype="int8")
    assert corpus.vectors.dtype == jnp.int8
    assert corpus.qscale.shape == (336, 2)
    assert corpus.qscale.dtype == jnp.float32
    assert corpus.qscale.sharding.spec == P("data", None)

    rng = np.random.default_rng(9)
    queries = scorer.user_embed(
        {"user_id": rng.integers(0, SIZE_MAP["user"], 16).astype(np.int32)})
    s, i = make_retrieval(corpus, mesh=mesh8, top_k=10)(queries)
    s_ref, i_ref = retrieval_reference(queries, corpus, top_k=10)
    assert_same_topk(i, s, i_ref, s_ref)
    assert np.all(np.asarray(i) >= 0)

    # the quantized corpus still serves the same catalog: recall vs the
    # f32 corpus stays high (rowwise int8 at D=16 is a gentle grid)
    f32 = build_corpus(scorer, feats, corpus_batch=128, mesh=mesh8)
    _, i_f32 = retrieval_reference(queries, f32, top_k=10)
    assert _recall(i_ref, i_f32) >= 0.9

    with pytest.raises(ValueError, match="dtype"):
        build_corpus(scorer, feats, corpus_batch=128, dtype="int4")


def test_twostage_recall_floor_on_zipf_corpus(mesh8):
    """ISSUE acceptance: two-stage recall@10 >= 0.95 vs the exact
    reference at ``coarse_k = 4 * top_k`` on a zipf-queried synthetic
    corpus (popular items queried most, the serving skew)."""
    corpus = _rand_corpus(mesh8, 1234, dtype="int8", seed=11)
    rng = np.random.default_rng(12)
    pop = np.minimum(rng.zipf(1.5, size=32) - 1, 1233)
    base = np.asarray(jax.device_get(corpus.vectors), np.float32)[pop]
    queries = jnp.asarray(
        base + 0.3 * rng.normal(size=base.shape).astype(np.float32))
    s2, i2 = make_retrieval(
        corpus, mesh=mesh8, top_k=10, coarse_k=40)(queries)
    s_ref, i_ref = retrieval_reference(queries, corpus, top_k=10)
    assert _recall(i2, i_ref) >= 0.95
    assert np.all(np.asarray(i2) >= 0)
    del s2, s_ref  # exactness of survivor scores asserted below


def test_twostage_rerank_scores_are_exact_bits(mesh8):
    """Every surviving (query, id) pair's score is the exact scan's score
    for that pair to within 4 ULP (the re-rank multiplies gathered rows, the
    scan the whole corpus: two programs) — the re-rank stage adds NO
    approximation on top of storage quantization."""
    from tdfo_tpu.ops.quant import dequantize_rows

    corpus = _rand_corpus(mesh8, 200, dtype="int8", seed=21)
    rng = np.random.default_rng(22)
    queries = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    s2, i2 = make_retrieval(
        corpus, mesh=mesh8, top_k=10, coarse_k=40)(queries)
    vecs = dequantize_rows(
        jnp.asarray(jax.device_get(corpus.vectors))[:200],
        jnp.asarray(jax.device_get(corpus.qscale))[:200])
    full = np.asarray(mips_scores(queries, vecs))  # [B, N] exact scan
    want = np.take_along_axis(full, np.asarray(i2), axis=1)
    assert_within_ulp(s2, want)


def test_twostage_degenerate_routes_to_exact(mesh8):
    """``coarse_k >= n_items`` is statically the exact program: bitwise-
    equal ids AND scores (recall@k == 1.0 by construction)."""
    corpus = _rand_corpus(mesh8, 120, dtype="int8", seed=31)
    rng = np.random.default_rng(32)
    queries = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    s_exact, i_exact = make_retrieval(corpus, mesh=mesh8, top_k=10)(queries)
    s_deg, i_deg = make_retrieval(
        corpus, mesh=mesh8, top_k=10, coarse_k=120)(queries)
    np.testing.assert_array_equal(np.asarray(i_deg), np.asarray(i_exact))
    np.testing.assert_array_equal(
        np.asarray(s_deg).view(np.uint32),
        np.asarray(s_exact).view(np.uint32))
    s_ref, i_ref = retrieval_reference(queries, corpus, top_k=10)
    assert _recall(i_deg, i_ref) == 1.0


def test_twostage_tiny_ragged_corpus_clamps_coarse_k(mesh8):
    """13 items over 4 shards (4 rows/shard after padding): ``coarse_k``
    clamps to the shard row count, padding ids (-1) never survive the
    coarse stage, and the output still matches the reference."""
    corpus = _rand_corpus(mesh8, 13, dtype="int8", seed=41)
    rng = np.random.default_rng(42)
    queries = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    retrieve = make_retrieval(corpus, mesh=mesh8, top_k=5, coarse_k=12)
    s, i = retrieve(queries)
    ia = np.asarray(i)
    assert np.all(ia >= 0) and np.all(ia < 13)
    for row in ia:
        assert len(set(row.tolist())) == 5  # no duplicate survivors
    s_ref, i_ref = retrieval_reference(queries, corpus, top_k=5)
    assert_same_topk(ia, s, i_ref, s_ref)


def test_twostage_single_device_and_float_corpus(mesh8):
    """The meshless two-stage program and the f32-corpus two-stage program
    both reduce to the reference answer (coarse == exact scores when
    nothing is quantized)."""
    single = _rand_corpus(None, 100, dtype="int8", seed=51)
    rng = np.random.default_rng(52)
    queries = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    s, i = make_retrieval(single, top_k=10, coarse_k=40)(queries)
    s_ref, i_ref = retrieval_reference(queries, single, top_k=10)
    assert _recall(i, i_ref) >= 0.95

    f32 = _rand_corpus(mesh8, 100, dtype="float32", seed=53)
    s, i = make_retrieval(f32, mesh=mesh8, top_k=10, coarse_k=100 - 1)(
        queries)
    s_ref, i_ref = retrieval_reference(queries, f32, top_k=10)
    assert_same_topk(i, s, i_ref, s_ref)


def test_twostage_validation(mesh8):
    corpus = _rand_corpus(mesh8, 50, dtype="int8", seed=61)
    with pytest.raises(ValueError, match="coarse_k"):
        make_retrieval(corpus, mesh=mesh8, top_k=10, coarse_k=-1)
    with pytest.raises(ValueError, match="coarse_k"):
        make_retrieval(corpus, mesh=mesh8, top_k=10, coarse_k=5)


def test_corpus_store_roundtrip_and_refusals(mesh8, tmp_path):
    """``export_corpus``/``load_corpus``: int8 corpora round-trip bitwise
    (codes, sidecar, ids) and refuse a future qscale re-grid or a store
    predating the stamp — the same refuse-on-mismatch discipline as
    training restores."""
    import json

    from tdfo_tpu.serve.export import bundle_digest, export_corpus, load_corpus

    corpus = _rand_corpus(mesh8, 333, dtype="int8", seed=71)
    cdir = tmp_path / "corpus"
    export_corpus(cdir, corpus, step=7)
    back = load_corpus(cdir, mesh=mesh8)
    assert back.vectors.dtype == jnp.int8 and back.n_items == 333
    np.testing.assert_array_equal(np.asarray(back.vectors),
                                  np.asarray(corpus.vectors))
    np.testing.assert_array_equal(
        np.asarray(back.qscale).view(np.uint32),
        np.asarray(corpus.qscale).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(back.ids),
                                  np.asarray(corpus.ids))

    # a served answer from the reloaded corpus is bitwise the original's
    rng = np.random.default_rng(72)
    queries = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    s0, i0 = make_retrieval(corpus, mesh=mesh8, top_k=10,
                            coarse_k=40)(queries)
    s1, i1 = make_retrieval(back, mesh=mesh8, top_k=10,
                            coarse_k=40)(queries)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(
        np.asarray(s0).view(np.uint32), np.asarray(s1).view(np.uint32))

    manifest = cdir / "corpus.json"
    good = json.loads(manifest.read_text())
    with np.load(cdir / "corpus.npz") as z:
        arrays = {k: z[k] for k in z.files}

    def _restamp(m):  # a legitimately-stamped store from another build
        return dict(m, digest=bundle_digest(m, arrays))

    bad = _restamp(dict(good, qscale_layout="rowwise-f32-scale-offset-v2"))
    manifest.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="qscale_layout"):
        load_corpus(cdir, mesh=mesh8)
    bad = _restamp({k: v for k, v in good.items() if k != "qscale_layout"})
    manifest.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="qscale"):
        load_corpus(cdir, mesh=mesh8)
    # a plainly corrupted store (manifest edited, digest stale) also refuses
    manifest.write_text(json.dumps(dict(good, step=99)))
    with pytest.raises(ValueError, match="digest"):
        load_corpus(cdir, mesh=mesh8)
    manifest.write_text(json.dumps(good))

    # float corpora round-trip too (no sidecar on disk, none tolerated)
    f32 = _rand_corpus(mesh8, 50, dtype="float32", seed=73)
    export_corpus(tmp_path / "f32", f32)
    back32 = load_corpus(tmp_path / "f32", mesh=mesh8)
    assert back32.qscale is None
    np.testing.assert_array_equal(np.asarray(back32.vectors),
                                  np.asarray(f32.vectors))
