"""Sequence-serving subsystem tests: bert4rec bundles, masked-position
scoring, ragged-history windows, and item-table retrieval.

The contracts under test, in order of importance:

  * train/serve skew is ZERO for the seq family too — a ``SeqScorer`` built
    from an exported bert4rec bundle produces bitwise the same
    masked-position candidate scores as the trainer's seq eval chain
    (``train/trainer.py _build_bert4rec`` eval_accum);
  * ragged histories batch through the SAME bounded-jit-cache discipline as
    CTR traffic — ``history_window`` fixes the row shape, bucket padding
    fixes the batch shape, so compiled programs stay <= len(buckets);
  * next-item retrieval searches the OUTPUT head as the corpus
    (``item_corpus``: bias-folded out_proj columns — NOT the input item
    table, out_proj is untied) so MIPS ranks exactly like ``score()``, and
    inherits the retrieval contracts unchanged: exact-path bitwise equality
    to the stable-argsort reference, and the int8 two-stage path holding
    its recall floor;
  * request-log replay forms deterministic [B, width] panels from seq
    feature payloads and quarantines width drift (the multihost-lockstep
    guard of ``trainer._eval_schema`` extended to the serve->retrain loop).
"""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from closeness import assert_same_topk, assert_within_ulp

from tdfo_tpu.data.replay import ReplayConsumer, RequestLog
from tdfo_tpu.models.bert4rec import (
    PAD_ID,
    Bert4RecConfig,
    key_padding_mask,
    make_sharded_bert4rec,
)
from tdfo_tpu.ops.sparse import sparse_optimizer
from tdfo_tpu.serve.export import ServingBundle, export_bundle, export_delta, load_bundle
from tdfo_tpu.serve.frontend import MicroBatcher
from tdfo_tpu.serve.retrieval import make_retrieval, retrieval_reference
from tdfo_tpu.serve.scoring import make_scorer
from tdfo_tpu.serve.seq_scoring import (
    SeqScorer,
    history_window,
    item_corpus,
    make_seq_scorer,
)
from tdfo_tpu.train.seq import score_candidates
from tdfo_tpu.train.sparse_step import SparseTrainState

CFG = Bert4RecConfig(n_items=50, max_len=8, embed_dim=16, n_heads=2,
                     n_layers=2)
N_CANDS = 101  # EVAL_NEG_NUM + 1, the eval panel width


def _bert4rec_sparse(mesh, seed=0, cfg=CFG):
    """Item collection + transformer backbone + SparseTrainState, mirroring
    the trainer's ``_build_bert4rec`` at toy scale."""
    coll, tables, backbone, dense = make_sharded_bert4rec(
        jax.random.key(seed), cfg, mesh, sharding="row",
        fused_threshold=None)
    state = SparseTrainState.create(
        dense_params=dense, tx=optax.adamw(1e-3), tables=tables,
        sparse_opt=sparse_optimizer("adam", lr=1e-3, weight_decay=0.0))
    return coll, backbone, state


def _export_seq(out_dir, coll, state, cfg=CFG, **kw):
    return export_bundle(
        out_dir, model="bert4rec", embed_dim=cfg.embed_dim, cat_columns=(),
        cont_columns=(), size_map={"n_items": cfg.n_items}, coll=coll,
        tables=state.tables, dense_params=state.dense_params,
        seq={"max_len": cfg.max_len, "n_heads": cfg.n_heads,
             "n_layers": cfg.n_layers}, **kw)


def _seq_batch(rng, n, cfg=CFG):
    """Ragged histories -> the eval window schema (appended MASK, left pad)
    plus a candidate panel — exactly what a live request carries."""
    seqs = np.stack([
        history_window(
            rng.integers(1, cfg.n_items + 1,
                         size=int(rng.integers(1, 2 * cfg.max_len))),
            n_items=cfg.n_items, max_len=cfg.max_len)
        for _ in range(n)])
    cands = rng.integers(1, cfg.n_items + 1,
                         size=(n, N_CANDS)).astype(np.int32)
    return {"seqs": seqs, "cands": cands}


def _eval_chain(coll, backbone):
    """The trainer's seq eval forward (train/trainer.py eval_accum): the
    bitwise reference every served score must reproduce."""

    @jax.jit
    def scores(state, batch):
        embs = coll.lookup(state.tables, {"item": batch["seqs"]},
                           mode="gspmd")
        logits = backbone.apply(
            {"params": state.dense_params}, embs["item"],
            key_padding_mask(batch["seqs"]))
        return score_candidates(logits, batch["cands"])

    return scores


# ------------------------------------------------------- train/serve skew


def test_seq_bundle_scores_match_eval_step(mesh8, tmp_path):
    """The zero-skew bar for the second model family: served masked-position
    candidate scores from a round-tripped bundle are BITWISE equal to the
    trainer's seq eval chain."""
    coll, backbone, state = _bert4rec_sparse(mesh8)
    batch = _seq_batch(np.random.default_rng(7), 16)
    ref = np.asarray(_eval_chain(coll, backbone)(
        state, {k: jnp.asarray(v) for k, v in batch.items()}))

    scorer = make_seq_scorer(
        load_bundle(_export_seq(tmp_path / "b", coll, state), verify=True),
        mesh=mesh8)
    got = np.asarray(scorer.score(dict(batch)))
    assert got.dtype == np.float32 and got.shape == (16, N_CANDS)
    np.testing.assert_array_equal(got.view(np.uint32), ref.view(np.uint32))


def test_seq_scoring_never_materializes_the_logits_cube(mesh8, tmp_path):
    """XLA does not sink the last-position slice into the vocab matmul, so
    an eval-shaped serving program would materialize the full [B, T, V]
    logits (420 GB at the bench profile).  The scorer applies out_proj to
    the [B, d] row slice instead; pin that the compiled program's largest
    f32 tensor stays an order of magnitude under the cube."""
    import re

    # vocab must dwarf the legit intermediates (FF hidden is [B, T, 4d]) so
    # the cube/10 bound separates them cleanly
    cfg = Bert4RecConfig(n_items=5000, max_len=16, embed_dim=16, n_heads=2,
                         n_layers=2)
    coll, backbone, state = _bert4rec_sparse(mesh8, cfg=cfg)
    bundle = load_bundle(_export_seq(tmp_path / "b", coll, state, cfg=cfg))
    scorer = make_seq_scorer(bundle, mesh=mesh8)

    n = 32
    batch = _seq_batch(np.random.default_rng(3), n, cfg=cfg)
    hlo = scorer._score.lower(
        {k: jnp.asarray(v) for k, v in batch.items()},
        *scorer._params).compile().as_text()
    largest = max(
        int(np.prod([int(d) for d in dims.split(",")]))
        for dims in re.findall(r"f32\[([0-9,]+)\]", hlo))
    cube = n * cfg.max_len * cfg.vocab_size
    assert largest < cube / 10, (
        f"largest compiled f32 tensor has {largest} elements — the serving "
        f"program is materializing at [B, T, V] cube scale ({cube})")


def test_make_scorer_dispatches_seq_family(mesh8, tmp_path):
    """Pointer followers (fleet replicas, swap controllers) build scorers
    through ONE entry point; bert4rec bundles must come back as the seq
    scorer with an empty continuous-column set."""
    coll, _, state = _bert4rec_sparse(mesh8)
    bundle = load_bundle(_export_seq(tmp_path / "b", coll, state))
    scorer = make_scorer(bundle, mesh=mesh8)
    assert isinstance(scorer, SeqScorer)
    assert scorer.model == "bert4rec" and scorer.cont_columns == ()
    assert scorer.features == ("seqs", "cands")
    assert scorer.max_len == CFG.max_len and scorer.n_items == CFG.n_items
    assert scorer.mask_id == CFG.n_items + 1


def test_query_embed_is_the_retrieval_head_query(mesh8, tmp_path):
    """``query_embed`` must be ``[h, 1]`` — the hidden state FEEDING
    out_proj with the constant that picks up the bias column: pushing it
    through the bias-folded output head by hand reproduces the served
    candidate scores (the identity next-item retrieval relies on; out_proj
    is UNTIED, so the input table would be the wrong head)."""
    coll, backbone, state = _bert4rec_sparse(mesh8)
    batch = _seq_batch(np.random.default_rng(11), 8)
    bundle = load_bundle(_export_seq(tmp_path / "b", coll, state))
    scorer = make_seq_scorer(bundle, mesh=mesh8)

    q = np.asarray(scorer.query_embed(dict(batch)))
    assert q.shape == (8, CFG.embed_dim + 1) and q.dtype == np.float32
    np.testing.assert_array_equal(q[:, -1], 1.0)
    W = np.asarray(bundle.dense_params["out_proj"]["kernel"])
    b = np.asarray(bundle.dense_params["out_proj"]["bias"])
    head = np.concatenate([W, b[None, :]], axis=0)  # [d+1, V]
    manual = np.take_along_axis(q @ head, batch["cands"], axis=1)
    ref = np.asarray(scorer.score(dict(batch)))
    np.testing.assert_allclose(manual, ref, rtol=2e-5, atol=2e-5)


# --------------------------------------------------------- bundle refusals


def _toy_bundle(**over):
    vocab = CFG.n_items + 2
    kw = dict(
        kind="sparse", model="bert4rec", embed_dim=CFG.embed_dim,
        cat_columns=(), cont_columns=(),
        size_map={"n_items": CFG.n_items}, step=0, dtype="float32",
        tables={"item_embedding": np.zeros((vocab, CFG.embed_dim),
                                           np.float32)},
        dense_params={}, params=None,
        seq={"max_len": CFG.max_len, "n_heads": CFG.n_heads,
             "n_layers": CFG.n_layers})
    kw.update(over)
    return ServingBundle(**kw)


@pytest.mark.parametrize("over,msg", [
    ({"model": "twotower"}, "CTR family"),
    ({"kind": "dense", "tables": None, "dense_params": None, "params": {}},
     "sparse"),
    ({"seq": None}, "no seq hyperparameters"),
    ({"seq": {"max_len": CFG.max_len}}, "missing"),
    ({"size_map": {}}, "needs n_items"),
    ({"tables": {"wrong_table": np.zeros((52, 16), np.float32)}},
     "do not match"),
    ({"size_map": {"n_items": CFG.n_items - 3}}, "vocab drift"),
], ids=["ctr-family", "dense-kind", "no-seq", "missing-keys", "no-n-items",
        "wrong-tables", "vocab-drift"])
def test_seq_scorer_refusals(over, msg):
    with pytest.raises(ValueError, match=msg):
        make_seq_scorer(_toy_bundle(**over))


def test_delta_export_refuses_seq_geometry_drift(mesh8, tmp_path):
    """``seq`` is a frozen manifest field: a delta whose max_len drifted
    would silently mis-position the appended MASK, so the chain refuses."""
    coll, _, state = _bert4rec_sparse(mesh8)
    base = _export_seq(tmp_path / "base", coll, state)
    with pytest.raises(ValueError, match="schema drift on 'seq'"):
        export_delta(
            tmp_path / "d1", base, model="bert4rec",
            embed_dim=CFG.embed_dim, cat_columns=(), cont_columns=(),
            size_map={"n_items": CFG.n_items}, step=1, coll=coll,
            tables=state.tables, dense_params=state.dense_params,
            seq={"max_len": CFG.max_len + 1, "n_heads": CFG.n_heads,
                 "n_layers": CFG.n_layers})


# --------------------------------------------------------- history windows


class TestHistoryWindow:
    """torchrec/preprocessing.py:229-239 applied to a live request:
    truncate LEFT (keep newest), append MASK, LEFT-pad with PAD_ID."""

    def test_long_history_keeps_newest(self):
        w = history_window(range(1, 21), n_items=50, max_len=8)
        np.testing.assert_array_equal(w, [14, 15, 16, 17, 18, 19, 20, 51])

    def test_short_history_left_pads(self):
        w = history_window([5, 9], n_items=50, max_len=8)
        np.testing.assert_array_equal(
            w, [PAD_ID] * 5 + [5, 9, 51])

    def test_empty_history_is_all_pad_plus_mask(self):
        w = history_window([], n_items=50, max_len=8)
        np.testing.assert_array_equal(w, [PAD_ID] * 7 + [51])

    def test_max_history_caps_the_window(self):
        w = history_window(range(1, 21), n_items=50, max_len=8,
                           max_history=3)
        np.testing.assert_array_equal(
            w, [PAD_ID] * 4 + [18, 19, 20, 51])

    def test_reserved_ids_refused(self):
        with pytest.raises(ValueError, match="reserved"):
            history_window([0, 3], n_items=50, max_len=8)
        with pytest.raises(ValueError, match="outside the catalog"):
            history_window([51], n_items=50, max_len=8)


# ------------------------------------------------- ragged-history batching


def test_microbatcher_seq_panels_and_compile_pin(mesh8, tmp_path):
    """Ragged seq traffic through the frontend's bucket batcher: 2-D panel
    columns pad/unpad row-wise like CTR columns, per-request scores match
    the direct scorer — bitwise where the request fills its bucket (the
    same program on the same rows), within 16 ULP of the score scale where
    it was padded (a [bucket, T] and an [n, T] transformer forward are two
    XLA programs) — and the jit cache stays <= len(buckets): the
    bounded-compile contract that makes live serving viable."""
    coll, _, state = _bert4rec_sparse(mesh8)
    bundle = load_bundle(_export_seq(tmp_path / "b", coll, state))
    scorer = make_seq_scorer(bundle, mesh=mesh8)
    buckets = (2, 4, 8)
    mb = MicroBatcher(scorer.score, buckets=buckets, max_batch=8,
                      batch_deadline_ms=0.0,
                      program_cache_size=scorer.score_cache_size)
    rng = np.random.default_rng(23)
    requests = {f"r{i}": _seq_batch(rng, n)
                for i, n in enumerate([1, 3, 2, 5, 8, 4, 1, 7, 6, 2])}
    for rid, batch in requests.items():
        mb.submit(rid, batch)
        mb.poll()
    assert set(mb.results) == set(requests)
    assert scorer.score_cache_size() <= len(buckets)
    assert {p for _, p in mb.shipped} <= set(buckets)
    # reference scores through an INDEPENDENT scorer so the pinned cache
    # above only ever saw the batcher's bucketed shapes
    ref_scorer = make_seq_scorer(bundle, mesh=mesh8)
    for rid, batch in requests.items():
        ref = np.asarray(ref_scorer.score(dict(batch)))
        assert mb.results[rid].shape == ref.shape  # unpadded [n, C] panels
        if len(ref) in buckets:  # shipped alone, unpadded: same program
            np.testing.assert_array_equal(mb.results[rid], ref)
        else:
            assert_within_ulp(mb.results[rid], ref, max_ulp=16, err_msg=rid)


# ------------------------------------------------------ item-table corpus


def test_item_corpus_layout(mesh8, tmp_path):
    """Bias-folded out_proj columns 1..n_items (each row ``[W[:, v]; b_v]``,
    width d+1), 1-based catalog ids, PAD/MASK columns excluded, shard
    padding id -1 — ``build_corpus``'s alignment contract on the bundle's
    own output head."""
    coll, _, state = _bert4rec_sparse(mesh8)
    bundle = load_bundle(_export_seq(tmp_path / "b", coll, state))
    corpus = item_corpus(bundle, mesh=mesh8)
    assert corpus.n_items == CFG.n_items
    n_pad = -(-CFG.n_items // mesh8.shape["data"]) * mesh8.shape["data"]
    assert corpus.vectors.shape == (n_pad, CFG.embed_dim + 1)
    ids = np.asarray(corpus.ids)
    np.testing.assert_array_equal(ids[:CFG.n_items],
                                  np.arange(1, CFG.n_items + 1))
    assert (ids[CFG.n_items:] == -1).all()
    W = np.asarray(bundle.dense_params["out_proj"]["kernel"], np.float32)
    b = np.asarray(bundle.dense_params["out_proj"]["bias"], np.float32)
    head = np.concatenate([W.T, b[:, None]], axis=1)  # [V, d+1]
    np.testing.assert_array_equal(
        np.asarray(corpus.vectors)[:CFG.n_items],
        head[1:CFG.n_items + 1])
    with pytest.raises(ValueError, match="not in"):
        item_corpus(bundle, mesh=mesh8, dtype="int4")
    with pytest.raises(ValueError, match="no out_proj"):
        item_corpus(_toy_bundle())
    with pytest.raises(ValueError, match="head drift"):
        item_corpus(_toy_bundle(dense_params={"out_proj": {
            "kernel": np.zeros((CFG.embed_dim, CFG.n_items + 1), np.float32),
            "bias": np.zeros((CFG.n_items + 1,), np.float32)}}))


def test_item_retrieval_exact_matches_reference(mesh8, tmp_path):
    """Sharded exact MIPS over the item corpus, queried with the scorer's
    own last-position hidden states, returns exactly the ids of the
    single-device stable-argsort reference and f32 scores within 4 ULP of
    it (two XLA programs)."""
    coll, _, state = _bert4rec_sparse(mesh8)
    bundle = load_bundle(_export_seq(tmp_path / "b", coll, state))
    scorer = make_seq_scorer(bundle, mesh=mesh8)
    corpus = item_corpus(bundle, mesh=mesh8)
    q = scorer.query_embed(_seq_batch(np.random.default_rng(5), 16))
    for k in (1, 10):
        scores, ids = make_retrieval(corpus, mesh=mesh8, top_k=k)(q)
        ref_s, ref_i = retrieval_reference(q, corpus, top_k=k)
        assert_same_topk(ids, scores, ref_i, ref_s)


def test_item_retrieval_ranks_like_the_served_scores(mesh8, tmp_path):
    """THE identity the corpus exists for: MIPS top-k over ``item_corpus``
    agrees with the argsort of the SERVED full-catalog logits — ``score()``
    with every catalog item as a candidate.  out_proj is untied, so a
    corpus built from the input item table ranks by ``h @ e_v`` instead of
    ``h @ W[:, v] + b_v`` and fails this by a wide margin.  ``mips_scores``
    runs bf16 x bf16 -> f32 while ``score()`` is an f32 matmul, so adjacent
    ranks inside the bf16 rounding bound may legitimately swap: the
    retrieved items' exact logits must match the true top-k logits within
    that bound everywhere, and the id lists must agree exactly wherever the
    k-boundary gap exceeds it."""
    coll, _, state = _bert4rec_sparse(mesh8)
    bundle = load_bundle(_export_seq(tmp_path / "b", coll, state))
    scorer = make_seq_scorer(bundle, mesh=mesh8)
    corpus = item_corpus(bundle, mesh=mesh8)

    n = 16
    batch = _seq_batch(np.random.default_rng(13), n)
    catalog = np.arange(1, CFG.n_items + 1, dtype=np.int32)
    q = np.asarray(scorer.query_embed(dict(batch)))
    full = np.asarray(scorer.score(
        {"seqs": batch["seqs"], "cands": np.tile(catalog, (n, 1))}))
    # per-row bf16 dot-product error bound: sum_i |q_i||c_i| * 2^-7 covers
    # rounding both operands to bf16 (8-bit mantissa) before the f32 matmul
    head = np.asarray(jax.device_get(corpus.vectors))[:CFG.n_items]
    tol = (np.abs(q) @ np.abs(head).T).max(axis=1) * 2.0 ** -7  # [n]

    for k in (1, 10):
        _, ids_ret = make_retrieval(corpus, mesh=mesh8, top_k=k)(q)
        ids_ret = np.asarray(ids_ret)
        for row in range(n):
            order = np.argsort(-full[row], kind="stable")
            best = full[row, order[:k]]
            got = full[row, ids_ret[row] - 1]
            assert np.all(best - got <= tol[row]), (
                f"row {row} top-{k}: retrieved items' served logits trail "
                f"the true top-k by {(best - got).max()} > {tol[row]} — the "
                "corpus is not the output head")
            boundary_gap = full[row, order[k - 1]] - full[row, order[k]]
            if boundary_gap > 2 * tol[row]:
                assert set(map(int, ids_ret[row])) == \
                    set(map(int, catalog[order[:k]])), f"row {row} top-{k}"


def _recall(ids, ids_ref):
    hits = sum(len(set(map(int, a)) & set(map(int, b)))
               for a, b in zip(np.asarray(ids), np.asarray(ids_ref)))
    return hits / ids_ref.size


def test_item_corpus_int8_twostage_recall_floor(mesh8, tmp_path):
    """The PR-11 int8 two-stage path applies to the item corpus unchanged:
    coarse-over-codes + exact rerank at coarse_k = 4*top_k holds the same
    recall floor against the exact scan of the SAME int8 corpus."""
    coll, _, state = _bert4rec_sparse(mesh8)
    bundle = load_bundle(_export_seq(tmp_path / "b", coll, state))
    scorer = make_seq_scorer(bundle, mesh=mesh8)
    corpus = item_corpus(bundle, mesh=mesh8, dtype="int8")
    assert corpus.qscale is not None
    q = scorer.query_embed(_seq_batch(np.random.default_rng(9), 32))
    top_k = 10
    _, ids_two = make_retrieval(corpus, mesh=mesh8, top_k=top_k,
                                coarse_k=4 * top_k)(q)
    _, ids_ref = retrieval_reference(q, corpus, top_k=top_k)
    assert _recall(ids_two, np.asarray(ids_ref)) >= 0.95


# -------------------------------------------------------- replay seq panels


_REPLAY_SCHEMA = {"seqs": (np.int32, (CFG.max_len,)),
                  "cands": (np.int32, (5,))}


def _log_seq_records(root, rows_per_record, *, widths=None, cands_w=5):
    log = RequestLog(root)
    rng = np.random.default_rng(31)
    for r, n in enumerate(rows_per_record):
        w = CFG.max_len if widths is None else widths[r]
        log.append({
            "event": "serve_request", "request": f"q{r}", "rows": n,
            "outcome": "ok",
            "features": {
                "seqs": rng.integers(1, 51, (n, w)).astype(int).tolist(),
                "cands": rng.integers(1, 51, (n, cands_w)).astype(int).tolist(),
            },
        })
    log.seal_active()
    log.close()


def test_replay_forms_seq_panels(tmp_path):
    """Seq feature payloads (fixed-width per-row vectors) batch into
    deterministic [B, width] panels — the schema discipline that keeps every
    replayed batch shaped exactly like ``trainer._eval_schema``."""
    _log_seq_records(tmp_path, [4, 3, 5])
    con = ReplayConsumer(tmp_path, schema=_REPLAY_SCHEMA, batch_size=6)
    batch, consumed = con.next_batch()
    assert batch["seqs"].shape == (6, CFG.max_len)
    assert batch["cands"].shape == (6, 5)
    assert batch["seqs"].dtype == np.int32
    assert [(s, a, b) for s, a, b in consumed] == [(1, 0, 4), (2, 0, 2)]
    # 12 rows total: the second batch drains the log mid-record-free,
    # the third cannot fill and commits nothing (all-or-nothing)
    batch2, consumed2 = con.next_batch()
    assert batch2["seqs"].shape == (6, CFG.max_len)
    assert [(s, a, b) for s, a, b in consumed2] == [(2, 2, 3), (3, 0, 5)]
    assert con.next_batch() is None


def test_replay_quarantines_width_drift(tmp_path):
    """A record whose seq panel width drifted from the schema is BAD, not
    trainable — width drift would desync multihost lockstep downstream."""
    _log_seq_records(tmp_path, [3, 3, 3], widths=[8, 7, 8])
    con = ReplayConsumer(tmp_path, schema=_REPLAY_SCHEMA, batch_size=6,
                         max_bad_records=1)
    batch, consumed = con.next_batch()
    assert batch["seqs"].shape == (6, CFG.max_len)
    assert [s for s, _, _ in consumed] == [1, 3]  # record 2 quarantined
    assert con.counters()["replay/bad"] == 1.0


def test_replay_schema_rejects_ragged_and_high_rank():
    with pytest.raises(ValueError, match="fixed-width 1-D"):
        ReplayConsumer("/nonexistent",
                       schema={"seqs": (np.int32, (4, 4))}, batch_size=2)
    with pytest.raises(ValueError, match="fixed-width 1-D"):
        ReplayConsumer("/nonexistent",
                       schema={"seqs": (np.int32, (0,))}, batch_size=2)
