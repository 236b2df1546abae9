"""Train-parity sequence scoring from a ``model="bert4rec"`` serving bundle.

The serving forward IS the trainer's seq eval forward (``train/trainer.py
_build_bert4rec`` eval_accum) re-pointed at the bundle's merged tables: the
same ``ShardedEmbeddingCollection`` lookup (replicated table, ``mode="gspmd"``
— plain row gathers), the same :class:`~tdfo_tpu.models.bert4rec.Bert4RecBackbone`
module rebuilt from the manifest's ``seq`` hyperparameters, and the
appended-MASK-position candidate slice of
:func:`~tdfo_tpu.train.seq.score_candidates` (``torchrec/train.py:44-58``)
— with ONE serving-only restructuring: ``out_proj`` is applied to the
last-position hidden state ``[B, d]`` instead of the full sequence, a row
slice of the Dense lhs that keeps every computed element bitwise equal to
the eval step's ``logits[:, -1, :]`` while never materializing the
``[B, T, V]`` logits cube (XLA does not sink the slice into the matmul —
at B=8192/V=200k that cube is 420 GB).  That chain is what makes served
masked-position logits bitwise-equal to the eval step for f32 bundles
(``tests/test_serve_seq.py``), the same contract ``serve/scoring.py``
establishes for the CTR family.

Request payloads are the eval schema's shapes (``trainer._eval_schema``):
``seqs`` [B, max_len] int32 eval windows (history truncated LEFT at
``max_len - 1``, appended MASK, LEFT-padded with ``PAD_ID`` —
``torchrec/preprocessing.py:229-239``, see :func:`history_window`) and
``cands`` [B, C] int32 candidate ids.  Scoring steps are jitted with the
request batch DONATED and take tables/params as ARGUMENTS, never closures
(a closure is baked into the compiled program, CLAUDE.md).

Next-item retrieval searches the OUTPUT HEAD as the corpus
(:func:`item_corpus`): Bert4Rec's ``out_proj`` is an UNTIED Dense
(``models/bert4rec.py`` — its own ``[d, V]`` kernel and bias, no weight
tying with the input item table), and it scores item ``v`` as
``h_last @ W_out[:, v] + b_out[v]``.  The corpus row for item ``v`` is
therefore the head column with the bias folded in, ``[W_out[:, v]; b_out[v]]``,
and the MIPS query is the last-position hidden state with a constant 1
appended (:meth:`SeqScorer.query_embed`) — every corpus inner product IS
the served logit, so retrieval ranks exactly like :meth:`SeqScorer.score`
(pinned by ``tests/test_serve_seq.py`` against the full-catalog argsort).
The input embedding table would rank by ``h @ e_v`` — a different function;
no separate corpus sweep is needed either way, the head already lives in
the bundle's dense params.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from tdfo_tpu.core.mesh import DATA_AXIS, replicated_sharding
from tdfo_tpu.models.bert4rec import (
    PAD_ID,
    Bert4RecBackbone,
    Bert4RecConfig,
    key_padding_mask,
)
from tdfo_tpu.ops.quant import STORAGE_DTYPES, quantize_rows
from tdfo_tpu.serve.corpus import Corpus
from tdfo_tpu.serve.export import ServingBundle
__all__ = ["SeqScorer", "make_seq_scorer", "history_window", "item_corpus"]

# the seq request schema: categorical-panel columns score() consumes
SEQ_FEATURES = ("seqs", "cands")


@dataclass
class SeqScorer:
    """Jitted sequence-serving programs bound to one bundle's parameters.

    ``score(batch) -> [B, C] f32`` ranks ``cands`` at the appended-MASK
    position (batch donated).  ``query_embed(batch) -> [B, D+1] f32`` is the
    last-position hidden state with a constant 1 appended — the MIPS query
    against the bias-folded output-head corpus of :func:`item_corpus`.
    ``cont_columns`` is empty (sequence requests carry no continuous
    features); fleet/frontend code must not assume a CTR column set.
    """

    model: str
    embed_dim: int
    max_len: int
    n_items: int
    features: tuple[str, ...]
    cont_columns: tuple[str, ...]
    _score: Callable = field(repr=False)
    _params: tuple = field(repr=False)  # trailing args for the jitted fns
    _query: Callable = field(repr=False)

    @property
    def mask_id(self) -> int:
        return self.n_items + 1

    def score(self, batch: Mapping[str, jax.Array]) -> jax.Array:
        return self._score(dict(batch), *self._params)

    def query_embed(self, batch: Mapping[str, jax.Array]) -> jax.Array:
        return self._query(dict(batch), *self._params)

    def score_cache_size(self) -> int:
        """Compiled-program count of the scoring step (one per padded batch
        shape) — the frontend's compile-count regression hook."""
        return self._score._cache_size()


def _device_tree(tree, mesh):
    put = (partial(jax.device_put, device=replicated_sharding(mesh))
           if mesh is not None else jnp.asarray)
    return jax.tree.map(put, tree)


def _check_seq_bundle(bundle: ServingBundle) -> tuple[int, dict]:
    """Schema refusals shared by the scorer and the corpus builder: wrong
    family, missing/incomplete seq hyperparameters, vocab drift."""
    if bundle.model != "bert4rec":
        raise ValueError(
            f"seq scorer got a {bundle.model!r} bundle — the CTR family "
            "(twotower/dlrm) is served by serve.scoring.make_scorer")
    if bundle.kind != "sparse":
        raise ValueError(
            "bert4rec bundles are sparse (item table + dense backbone split, "
            f"the DMP regime), got kind={bundle.kind!r}")
    seq = bundle.seq
    if not seq:
        raise ValueError(
            "bundle carries no seq hyperparameters — re-export with "
            "export_bundle(..., seq={'max_len': ..., 'n_heads': ..., "
            "'n_layers': ...}); a bundle without them cannot rebuild the "
            "backbone geometry")
    missing = [k for k in ("max_len", "n_heads", "n_layers") if k not in seq]
    if missing:
        raise ValueError(f"bundle seq hyperparameters missing {missing}")
    n_items = int(bundle.size_map.get(
        "n_items", bundle.size_map.get("item", 0)))
    if not n_items:
        raise ValueError("bert4rec bundle needs n_items in size_map")
    if set(bundle.tables) != {"item_embedding"}:
        raise ValueError(
            f"bundle tables {sorted(bundle.tables)} do not match the "
            "bert4rec schema ['item_embedding'] — wrong bundle for this "
            "model/config")
    vocab = n_items + 2  # PAD(0) + items(1..n) + MASK(n+1)
    rows, dim = bundle.tables["item_embedding"].shape
    if rows != vocab or dim != bundle.embed_dim:
        raise ValueError(
            f"item_embedding is [{rows}, {dim}] but size_map says n_items="
            f"{n_items} (vocab {vocab}) at embed_dim {bundle.embed_dim} — "
            "vocab drift; the bundle and the catalog disagree")
    return n_items, dict(seq)


def make_seq_scorer(bundle: ServingBundle, *, mesh=None) -> SeqScorer:
    """Bundle -> :class:`SeqScorer`.  ``mesh`` replicates the parameters
    over it (the table is replicated at serve time; retrieval shards the
    CORPUS, not the table — ``serve/retrieval.py``)."""
    from tdfo_tpu.parallel.embedding import (
        EmbeddingSpec,
        ShardedEmbeddingCollection,
    )

    n_items, seq = _check_seq_bundle(bundle)
    cfg = Bert4RecConfig(
        n_items=n_items,
        max_len=int(seq["max_len"]),
        embed_dim=bundle.embed_dim,
        n_heads=int(seq["n_heads"]),
        n_layers=int(seq["n_layers"]),
    )
    # replicated + non-fused: the single logical table keeps its own [V, d]
    # array under its own name, exactly the merged-bundle layout
    coll = ShardedEmbeddingCollection(
        [EmbeddingSpec("item_embedding", num_embeddings=cfg.vocab_size,
                       embedding_dim=cfg.embed_dim, features=("item",),
                       sharding="replicated", init_scale=1.0)],
        mesh=mesh,
    )
    backbone = Bert4RecBackbone(cfg=cfg, dtype=bundle.jax_dtype)
    tables = _device_tree(dict(bundle.tables), mesh)
    dense_params = _device_tree(bundle.dense_params, mesh)

    last_block = f"block_{cfg.n_layers - 1}"

    def last_hidden(tables, dense_params, seqs):
        # the hidden state FEEDING out_proj at the appended-MASK (last)
        # position — the last transformer block's output; flax intermediate
        # capture reads it without restructuring the module, and the unused
        # full [B, T, V] primal output is dead code XLA eliminates
        embs = coll.lookup(tables, {"item": seqs}, mode="gspmd")
        _, st = backbone.apply(
            {"params": dense_params}, embs["item"], key_padding_mask(seqs),
            capture_intermediates=lambda mdl, _: mdl.name == last_block,
            mutable=["intermediates"],
        )
        h = st["intermediates"][last_block]["__call__"][0]
        return h[:, -1, :]

    @partial(jax.jit, donate_argnums=(0,))
    def score(batch, tables, dense_params):
        # masked-position scoring: only the last position is ever served, so
        # out_proj runs on [B, d] — a row slice of the Dense lhs, bitwise
        # equal per computed element to the trainer eval's full-sequence
        # projection (trainer.py seq eval_accum) while the [B, T, V] logits
        # cube never materializes (XLA does NOT sink the slice into the
        # matmul: measured [B*T, V] live at bench scale, 420 GB at B=8192)
        h = last_hidden(tables, dense_params, batch["seqs"])
        op = dense_params["out_proj"]
        logits = (jnp.dot(h, jnp.asarray(op["kernel"], h.dtype))
                  + jnp.asarray(op["bias"], h.dtype))  # [B, V]
        return jnp.take_along_axis(logits, batch["cands"], axis=1)

    @jax.jit
    def query(batch, tables, dense_params):
        # the MIPS query against item_corpus: [h, 1] — the appended
        # constant picks up the head-bias column folded into every corpus
        # row, so dot(query, corpus[v]) = h @ W_out[:, v] + b_out[v], the
        # served logit itself
        h = last_hidden(tables, dense_params, batch["seqs"])
        h = h.astype(jnp.float32)
        return jnp.concatenate(
            [h, jnp.ones((h.shape[0], 1), jnp.float32)], axis=1)

    return SeqScorer(
        model=bundle.model, embed_dim=bundle.embed_dim, max_len=cfg.max_len,
        n_items=n_items, features=SEQ_FEATURES, cont_columns=(),
        _score=score, _params=(tables, dense_params), _query=query,
    )


def history_window(
    history: Sequence[int],
    *,
    n_items: int,
    max_len: int,
    max_history: int = 0,
) -> np.ndarray:
    """Ragged user history -> the fixed ``[max_len]`` eval window: truncate
    LEFT (keep the newest items), append the MASK token, LEFT-pad with
    ``PAD_ID`` so the tail stays right-aligned — the eval-sequence
    construction of ``torchrec/preprocessing.py:229-239`` applied to a live
    request.  ``max_history`` caps the kept raw items (0 = the protocol's
    full ``max_len - 1`` window)."""
    keep = max_len - 1
    if max_history > 0:
        keep = min(max_history, keep)
    hist = np.asarray(list(history), dtype=np.int64).reshape(-1)
    if hist.size and (hist.min() < 1 or hist.max() > n_items):
        bad = hist[(hist < 1) | (hist > n_items)]
        raise ValueError(
            f"history item id {int(bad[0])} outside the catalog [1, "
            f"{n_items}] — PAD({PAD_ID}) and MASK({n_items + 1}) are "
            "reserved ids, not items")
    tail = np.concatenate(
        [hist[-keep:] if keep else hist[:0], [n_items + 1]]).astype(np.int32)
    out = np.full((max_len,), PAD_ID, np.int32)
    out[-len(tail):] = tail
    return out


def item_corpus(
    bundle: ServingBundle,
    *,
    mesh=None,
    axis: str = DATA_AXIS,
    dtype: str = "float32",
) -> Corpus:
    """The bundle's trained OUTPUT-PROJECTION head as a retrieval
    :class:`~tdfo_tpu.serve.corpus.Corpus`: row ``v`` is the head column
    ``[W_out[:, v]; b_out[v]]`` (a ``[D+1]`` vector, bias folded in) for the
    catalog items ``v = 1..n_items`` (the PAD and MASK columns are reserved,
    never candidates), ids = the 1-based catalog item ids.  Queried with
    :meth:`SeqScorer.query_embed` (``[h, 1]``) every inner product is the
    served masked-position logit, so retrieval ranks exactly like
    ``SeqScorer.score`` — ``out_proj`` is untied from the input item table
    (``models/bert4rec.py``), which is why the table rows are NOT the
    corpus.  Shard-aligned exactly like ``build_corpus`` (zero rows,
    ids = -1) and storable through ``export_corpus`` / searchable by
    ``make_retrieval`` unchanged — including the int8 two-stage path."""
    if dtype not in STORAGE_DTYPES:
        raise ValueError(f"corpus dtype {dtype!r} not in {STORAGE_DTYPES}")
    n_items, _ = _check_seq_bundle(bundle)
    op = (bundle.dense_params or {}).get("out_proj")
    if not isinstance(op, Mapping) or "kernel" not in op or "bias" not in op:
        raise ValueError(
            "bundle dense params carry no out_proj kernel/bias — the "
            "retrieval corpus is the output head (out_proj is untied from "
            "the item table), so a headless bundle cannot retrieve")
    kernel = np.asarray(op["kernel"], dtype=np.float32)  # [d, V]
    bias = np.asarray(op["bias"], dtype=np.float32)  # [V]
    vocab = n_items + 2
    if kernel.shape != (bundle.embed_dim, vocab) or bias.shape != (vocab,):
        raise ValueError(
            f"out_proj geometry kernel{kernel.shape} bias{bias.shape} does "
            f"not match embed_dim {bundle.embed_dim} x vocab {vocab} — "
            "head drift; the bundle and the catalog disagree")
    head = np.concatenate([kernel.T, bias[:, None]], axis=1)  # [V, d+1]
    vectors = jnp.asarray(head[1:n_items + 1])
    ids = jnp.arange(1, n_items + 1, dtype=jnp.int32)

    n_shards = mesh.shape[axis] if mesh is not None else 1
    n_pad = -(-n_items // n_shards) * n_shards - n_items
    if n_pad:
        vectors = jnp.pad(vectors, [(0, n_pad), (0, 0)])
        ids = jnp.pad(ids, [(0, n_pad)], constant_values=-1)
    qscale = None
    if dtype == "bfloat16":
        vectors = vectors.astype(jnp.bfloat16)
    elif dtype == "int8":
        vectors, qscale = quantize_rows(vectors)
    if mesh is not None:
        vectors = jax.device_put(vectors, NamedSharding(mesh, P(axis, None)))
        ids = jax.device_put(ids, NamedSharding(mesh, P(axis)))
        if qscale is not None:
            qscale = jax.device_put(
                qscale, NamedSharding(mesh, P(axis, None)))
    return Corpus(vectors=vectors, ids=ids, n_items=n_items, qscale=qscale)
