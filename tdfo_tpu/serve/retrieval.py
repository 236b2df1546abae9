"""Sharded top-k MIPS over the candidate corpus: exact scan + two-stage.

On v5e the measured cost model makes brute force the right first retrieval
subsystem (no ANN index): bf16 MXU matmuls run 100-350 us at Goodreads/
Criteo corpus scales and ``lax.top_k``/argsort ~16 us, so a corpus-sharded
scan saturates the chip — ScaNN's quantized search (Guo et al. 2020) only
pays once corpora outgrow HBM.

Exact program (one ``shard_map`` over the corpus shards, queries
replicated):

  1. per-shard ``[B, D] x [D, rows/shard]`` bf16 matmul with
     ``preferred_element_type=f32`` (CLAUDE.md: bf16 INPUTS, f32
     accumulation), padding rows (id -1) masked to -inf;
  2. per-shard ``lax.top_k`` -> k local (score, id) candidates;
  3. global merge: the ``k x n_shards`` candidates concatenate shard-major
     and one final ``lax.top_k`` picks the answer.

Returns the ids of :func:`retrieval_reference` (single-device stable
argsort) including tie-breaks: ``lax.top_k`` prefers lower indices, which
within a shard means lower corpus position, and the shard-major merge order
means lower shard — i.e. lower corpus position globally — exactly the stable
argsort's preference.  Scores pass through selection untouched, so they are
the per-shard matmul's f32 values: within a few ULP of the reference's, not
its bits — a per-shard and a whole-corpus matmul are two XLA programs, and
which order each sums a row's D products in is the compiler's choice
(``tests/closeness.py``).

Two-stage program (``coarse_k`` > 0, the ScaNN split for int8 corpora that
would not fit HBM at f32):

  1. COARSE: per-shard scan of the STORED rows.  For an int8 corpus the
     scores come from the quantized rows without materialising f32:
     ``dot(q, code_j * scale_j + offset_j) = scale_j * dot(q, code_j)
     + sum(q) * offset_j`` — one bf16 code matmul (int8 codes are exact in
     bf16: |code| <= 128 < 2^8) plus a rank-1 affine correction.  Top
     ``min(coarse_k, rows/shard)`` candidates per shard, shard-major merge,
     global top ``coarse_k`` by coarse score.
  2. RERANK: candidate corpus positions sort ascending (restoring the
     lower-position tie-break the coarse selection scrambled), full rows
     gather (CLAUDE.md: FULL-row gathers only) and dequantize, and
     ``lax.top_k`` over EXACT per-query :func:`mips_scores` values picks
     the final k: no approximation beyond storage quantization, and within
     a few ULP of the full-corpus matmul's score for the same pair.

``coarse_k >= n_items`` routes STATICALLY to the exact program (the coarse
stage could drop nothing), so the degenerate case IS the exact scan — the
same program, bitwise-equal by construction.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tdfo_tpu.core.mesh import DATA_AXIS, shard_map
from tdfo_tpu.ops.quant import dequantize_rows
from tdfo_tpu.serve.corpus import Corpus

__all__ = ["make_retrieval", "mips_scores", "retrieval_reference"]


def mips_scores(queries: jax.Array, vectors: jax.Array) -> jax.Array:
    """THE serving score formula: ``[B, D] x [N, D] -> [B, N]`` f32 inner
    products from bf16 operands.  One definition shared by the sharded
    program and the reference so the comparison is of identical arithmetic
    (the summation order inside each compiled matmul still differs)."""
    return jax.lax.dot_general(
        queries.astype(jnp.bfloat16),
        vectors.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _masked_top_k(scores: jax.Array, ids: jax.Array, k: int):
    """Top-k over one corpus block, padding rows (id -1) masked to -inf so
    shard-alignment padding can never be retrieved."""
    scores = jnp.where(ids >= 0, scores, -jnp.inf)
    s, pos = jax.lax.top_k(scores, k)
    return s, jnp.take(ids, pos)


def _coarse_scores(queries, block, qscale):
    """Approximate scores against STORED rows: exact :func:`mips_scores`
    for float blocks, the affine-corrected code matmul for int8 blocks
    (module docstring identity — nothing f32-dense materialises)."""
    if qscale is None:
        return mips_scores(queries, block)
    raw = mips_scores(queries, block)  # int8 codes are exact in bf16
    qsum = jnp.sum(
        queries.astype(jnp.bfloat16).astype(jnp.float32), axis=1)
    return raw * qscale[None, :, 0] + qsum[:, None] * qscale[None, :, 1]


def _gather_dequant(vectors, qscale, flat_pos):
    """FULL-row gather of candidate rows + f32 dequantize.  bf16 rows cast
    up exactly; :func:`mips_scores` casts back down, so rerank bits match
    the exact scan for every storage dtype."""
    rows = jnp.take(vectors, flat_pos, axis=0)
    if qscale is None:
        return rows.astype(jnp.float32)
    return dequantize_rows(rows, jnp.take(qscale, flat_pos, axis=0))


def _rerank_scores(queries, cand):
    """Exact re-rank: ``[B, D] x [B, m, D] -> [B, m]``, the
    :func:`mips_scores` of the full corpus at the candidate columns to
    within a few ULP.  Uses a per-query ``lax.map`` of the SAME dot_general,
    which stays closest to the full-corpus matmul's summation."""
    return jax.lax.map(
        lambda qc: mips_scores(qc[0][None, :], qc[1])[0], (queries, cand))


def make_retrieval(
    corpus: Corpus,
    *,
    mesh=None,
    axis: str = DATA_AXIS,
    top_k: int = 100,
    coarse_k: int = 0,
) -> Callable[[jax.Array], tuple[jax.Array, jax.Array]]:
    """Build the jitted retrieval program for one corpus.

    Returns ``retrieve(queries[B, D]) -> (scores[B, k] f32, ids[B, k]
    int32)``, candidates in descending score order.  The corpus rides as a
    jit ARGUMENT (bound here), never a closure constant (CLAUDE.md: big
    closed-over arrays serialize into the compile payload).  Without a mesh
    the program degenerates to the single-device scan.

    ``coarse_k`` = 0 runs the exact scan (int8 corpora dequantize in-shard
    first).  ``coarse_k`` >= ``top_k`` runs the two-stage program: coarse
    top-``coarse_k`` over stored rows, exact re-rank of the survivors.
    """
    if top_k < 1:
        raise ValueError("top_k must be >= 1")
    if top_k > corpus.n_items:
        raise ValueError(
            f"top_k ({top_k}) exceeds the corpus ({corpus.n_items} items)")
    if coarse_k < 0:
        raise ValueError("coarse_k must be >= 0 (0 = exact scan)")
    if coarse_k and coarse_k < top_k:
        raise ValueError(
            f"coarse_k ({coarse_k}) must be >= top_k ({top_k}) — the "
            "coarse stage must keep every row the final stage can return")
    if coarse_k >= corpus.n_items:
        coarse_k = 0  # static degenerate routing: nothing could be dropped
    n_shards = mesh.shape[axis] if mesh is not None else 1
    qs = corpus.qscale

    if coarse_k == 0 and n_shards == 1:
        if qs is None:
            @jax.jit
            def retrieve_single(queries, vectors, ids):
                return _masked_top_k(
                    mips_scores(queries, vectors), ids, top_k)
        else:
            @jax.jit
            def retrieve_single(queries, vectors, qscale, ids):
                vecs = dequantize_rows(vectors, qscale)
                return _masked_top_k(mips_scores(queries, vecs), ids, top_k)

        return _bind(retrieve_single, corpus)

    rows_per_shard = corpus.vectors.shape[0] // n_shards

    if coarse_k == 0:
        # a shard holds N_pad / n_shards rows; it can contribute at most
        # that many candidates (k_local < top_k only for tiny corpora,
        # where the merged k_local * n_shards >= N_pad >= top_k candidates
        # still suffice)
        k_local = min(top_k, rows_per_shard)

        if qs is None:
            def local(vec_shard, id_shard, queries):
                return _masked_top_k(
                    mips_scores(queries, vec_shard), id_shard, k_local)

            @jax.jit
            def retrieve_sharded(queries, vectors, ids):
                # out_specs concatenate the per-shard [B, k_local]
                # candidate blocks along dim 1 SHARD-MAJOR — the property
                # the tie-break proof needs
                cand_s, cand_i = shard_map(
                    local,
                    mesh=mesh,
                    in_specs=(P(axis, None), P(axis), P()),
                    out_specs=(P(None, axis), P(None, axis)),
                    check_vma=False,
                )(vectors, ids, queries)
                top_s, pos = jax.lax.top_k(cand_s, top_k)
                return top_s, jnp.take_along_axis(cand_i, pos, axis=1)
        else:
            def local_q(vec_shard, qs_shard, id_shard, queries):
                vecs = dequantize_rows(vec_shard, qs_shard)
                return _masked_top_k(
                    mips_scores(queries, vecs), id_shard, k_local)

            @jax.jit
            def retrieve_sharded(queries, vectors, qscale, ids):
                cand_s, cand_i = shard_map(
                    local_q,
                    mesh=mesh,
                    in_specs=(P(axis, None), P(axis, None), P(axis), P()),
                    out_specs=(P(None, axis), P(None, axis)),
                    check_vma=False,
                )(vectors, qscale, ids, queries)
                top_s, pos = jax.lax.top_k(cand_s, top_k)
                return top_s, jnp.take_along_axis(cand_i, pos, axis=1)

        return _bind(retrieve_sharded, corpus)

    # ------------------------------------------------ two-stage program
    # coarse_k clamps to what a shard can contribute; the merged pool
    # always holds >= top_k real rows (each shard surfaces its real rows
    # before any -inf padding, and sum_s min(k_local, real_s) >=
    # min(coarse_k, n_items) >= top_k)
    k_local = min(coarse_k, rows_per_shard)
    n_cand = min(coarse_k, k_local * n_shards)

    if n_shards == 1:
        @jax.jit
        def retrieve_two_single(queries, vectors, qscale, ids):
            coarse = _coarse_scores(queries, vectors, qscale)
            coarse = jnp.where(ids[None, :] >= 0, coarse, -jnp.inf)
            _, pos = jax.lax.top_k(coarse, n_cand)
            pos = jnp.sort(pos, axis=1)  # restore the position tie-break
            flat = pos.reshape(-1)
            cand = _gather_dequant(vectors, qscale, flat).reshape(
                *pos.shape, -1)
            cand_ids = jnp.take(ids, pos)
            rr = jnp.where(
                cand_ids >= 0, _rerank_scores(queries, cand), -jnp.inf)
            s, sel = jax.lax.top_k(rr, top_k)
            return s, jnp.take_along_axis(cand_ids, sel, axis=1)

        return _bind(retrieve_two_single, corpus, with_qscale=True)

    def coarse_local(vec_shard, id_shard, queries, *qs_ops):
        qs_shard = qs_ops[0] if qs_ops else None
        scores = _coarse_scores(queries, vec_shard, qs_shard)
        scores = jnp.where(id_shard[None, :] >= 0, scores, -jnp.inf)
        s, pos = jax.lax.top_k(scores, k_local)
        base = jax.lax.axis_index(axis) * rows_per_shard
        return s, pos + base  # GLOBAL corpus positions

    def gather_local(vec_shard, id_shard, pos, *qs_ops):
        # each position lives on exactly one shard: the owner contributes
        # the dequantized row (and id), everyone else exact f32 zeros, and
        # the psum is a pure select — candidate rows come out replicated
        qs_shard = qs_ops[0] if qs_ops else None
        base = jax.lax.axis_index(axis) * rows_per_shard
        loc = pos - base
        mine = (loc >= 0) & (loc < rows_per_shard)
        flat = jnp.clip(loc, 0, rows_per_shard - 1).reshape(-1)
        rows = _gather_dequant(vec_shard, qs_shard, flat).reshape(
            *pos.shape, -1)
        rows = jnp.where(mine[..., None], rows, 0.0)
        idv = jnp.where(mine, jnp.take(id_shard, flat).reshape(pos.shape), 0)
        return jax.lax.psum(rows, axis), jax.lax.psum(idv, axis)

    @jax.jit
    def retrieve_two_sharded(queries, vectors, qscale, ids):
        qs_ops = () if qscale is None else (qscale,)
        qs_specs = tuple(P(axis, None) for _ in qs_ops)
        cand_s, cand_pos = shard_map(
            coarse_local,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis), P(), *qs_specs),
            out_specs=(P(None, axis), P(None, axis)),
            check_vma=False,
        )(vectors, ids, queries, *qs_ops)
        _, sel = jax.lax.top_k(cand_s, n_cand)
        pos = jnp.sort(jnp.take_along_axis(cand_pos, sel, axis=1), axis=1)
        cand, cand_ids = shard_map(
            gather_local,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis), P(), *qs_specs),
            out_specs=(P(), P()),
            check_vma=False,
        )(vectors, ids, pos, *qs_ops)
        rr = jnp.where(
            cand_ids >= 0, _rerank_scores(queries, cand), -jnp.inf)
        s, sel2 = jax.lax.top_k(rr, top_k)
        return s, jnp.take_along_axis(cand_ids, sel2, axis=1)

    return _bind(retrieve_two_sharded, corpus, with_qscale=True)


def _bind(jitted, corpus: Corpus, *, with_qscale: bool | None = None):
    """Close the corpus over a jitted program as jit ARGUMENTS; ``.jitted``
    stays reachable for lowering inspection and compile-cache accounting
    (``tests/test_serve_frontend.py``).  Float exact programs keep
    the historical ``(queries, vectors, ids)`` signature; qscale-bearing
    programs take ``(queries, vectors, qscale, ids)`` (two-stage programs
    always do — ``qscale`` rides as ``None`` for float corpora)."""
    if with_qscale is None:
        with_qscale = corpus.qscale is not None

    if with_qscale:
        def retrieve(queries):
            return jitted(
                queries, corpus.vectors, corpus.qscale, corpus.ids)
    else:
        def retrieve(queries):
            return jitted(queries, corpus.vectors, corpus.ids)

    retrieve.jitted = jitted
    retrieve.corpus = corpus
    return retrieve


def retrieval_reference(
    queries, corpus: Corpus, *, top_k: int = 100
) -> tuple[jax.Array, jax.Array]:
    """Single-device exact reference: full matmul + STABLE argsort (ties ->
    lowest corpus position, the same preference ``lax.top_k`` encodes).
    The yardstick for :func:`make_retrieval`: identical ids, f32 scores
    within a few ULP (two XLA programs do not share their last bit).
    int8 corpora dequantize first: the reference scores the corpus as
    served, not the pre-quantization vectors."""
    vectors = jnp.asarray(jax.device_get(corpus.vectors))[:corpus.n_items]
    if corpus.qscale is not None:
        vectors = dequantize_rows(
            vectors,
            jnp.asarray(jax.device_get(corpus.qscale))[:corpus.n_items])
    ids = jnp.asarray(jax.device_get(corpus.ids))[:corpus.n_items]
    scores = mips_scores(jnp.asarray(queries), vectors)  # [B, N]
    order = jnp.argsort(-scores, axis=-1, stable=True)[:, :top_k]
    return jnp.take_along_axis(scores, order, axis=1), jnp.take(ids, order)
