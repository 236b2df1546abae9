"""Train-parity CTR scoring from a serving bundle.

The serving forward IS the training eval forward (``train/ctr.py
make_ctr_sparse_eval_step`` for the DMP regime, ``TwoTower.__call__`` for the
dense regime) re-pointed at the bundle's merged tables: same backbone module,
same lookup program (replicated tables, ``mode="gspmd"`` — plain row
gathers), same dtype policy.  That is what makes train/serve skew exactly
zero for f32 bundles (``tests/test_serve.py``), the property Monolith calls
out as the serving contract and the reference's eval forward
(``jax-flax/train_dp.py:233-240``) implies but never packages.

Scoring steps are jitted with the request batch DONATED (the batch is
per-request garbage the moment logits exist) and take tables/params as
ARGUMENTS, never closures — a big closed-over constant is baked into the
compiled program (its size, its compile time, its cache key; CLAUDE.md).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp

from tdfo_tpu.core.mesh import replicated_sharding
from tdfo_tpu.models.twotower import (
    TWOTOWER_CATEGORICAL,
    TWOTOWER_CONTINUOUS,
    TWOTOWER_ITEM_CATEGORICAL,
    _FEATURE_TO_INPUT,
    Tower,
    TwoTower,
    TwoTowerBackbone,
)
from tdfo_tpu.serve.export import ServingBundle

__all__ = ["Scorer", "make_scorer"]


@dataclass
class Scorer:
    """Jitted serving programs bound to one bundle's parameters.

    ``score(batch) -> [B] f32 logits`` is the CTR request path (batch
    donated).  ``user_embed`` / ``item_embed`` map a batch to its tower
    vectors — the retrieval query/corpus halves (TwoTower only; ``None``
    for DLRM, whose interaction head does not factorize into towers).
    """

    model: str
    embed_dim: int
    cont_columns: tuple[str, ...]
    features: tuple[str, ...]  # categorical input columns score() consumes
    _score: Callable = field(repr=False)
    _params: tuple = field(repr=False)  # trailing args for the jitted fns
    _user: Callable | None = field(repr=False, default=None)
    _item: Callable | None = field(repr=False, default=None)

    def score(self, batch: Mapping[str, jax.Array]) -> jax.Array:
        return self._score(dict(batch), *self._params)

    def user_embed(self, batch: Mapping[str, jax.Array]) -> jax.Array:
        if self._user is None:
            raise ValueError(f"{self.model!r} has no user tower")
        return self._user(dict(batch), *self._params)

    def item_embed(self, batch: Mapping[str, jax.Array]) -> jax.Array:
        if self._item is None:
            raise ValueError(f"{self.model!r} has no item tower")
        return self._item(dict(batch), *self._params)

    def score_cache_size(self) -> int:
        """Compiled-program count of the scoring step (one per padded batch
        shape) — the frontend's compile-count regression hook."""
        return self._score._cache_size()


def _device_tree(tree: Any, mesh) -> Any:
    put = (partial(jax.device_put, device=replicated_sharding(mesh))
           if mesh is not None else jnp.asarray)
    return jax.tree.map(put, tree)


def make_scorer(bundle: ServingBundle, *, mesh=None):
    """Bundle -> :class:`Scorer`.  ``mesh`` replicates the parameters over
    it (serving tables are replicated; retrieval shards the CORPUS, not the
    tables — ``serve/retrieval.py``).  Bert4rec bundles dispatch to the
    sequence scorer (``serve/seq_scoring.py``) so pointer followers — fleet
    replicas, swap controllers — serve either family through one builder."""
    if bundle.model == "bert4rec":
        from tdfo_tpu.serve.seq_scoring import make_seq_scorer

        return make_seq_scorer(bundle, mesh=mesh)
    if bundle.kind == "dense":
        return _dense_scorer(bundle, mesh)
    return _sparse_scorer(bundle, mesh)


def _dense_scorer(bundle: ServingBundle, mesh) -> Scorer:
    model = TwoTower(size_map=dict(bundle.size_map),
                     embed_dim=bundle.embed_dim, dtype=bundle.jax_dtype)
    params = _device_tree(bundle.params, mesh)

    @partial(jax.jit, donate_argnums=(0,))
    def score(batch, params):
        return model.apply({"params": params}, batch)

    @jax.jit
    def user(batch, params):
        return model.apply({"params": params}, batch,
                           method="user_embeddings")

    @jax.jit
    def item(batch, params):
        return model.apply({"params": params}, batch,
                           method="item_embeddings")

    return Scorer(
        model=bundle.model, embed_dim=bundle.embed_dim,
        cont_columns=tuple(TWOTOWER_CONTINUOUS),
        features=tuple(_FEATURE_TO_INPUT[f] for f in TWOTOWER_CATEGORICAL),
        _score=score, _params=(params,), _user=user, _item=item,
    )


def _sparse_scorer(bundle: ServingBundle, mesh) -> Scorer:
    from tdfo_tpu.models.dlrm import DLRMBackbone, generic_embedding_specs
    from tdfo_tpu.models.twotower import ctr_embedding_specs
    from tdfo_tpu.parallel.embedding import ShardedEmbeddingCollection

    dtype = bundle.jax_dtype
    twotower_names = {f"{f}_embed" for f in TWOTOWER_CATEGORICAL}
    if set(bundle.tables) == twotower_names:
        specs = ctr_embedding_specs(bundle.size_map, bundle.embed_dim,
                                    sharding="replicated",
                                    fused_threshold=None)
    else:
        specs = generic_embedding_specs(bundle.size_map, bundle.cat_columns,
                                        bundle.embed_dim,
                                        sharding="replicated",
                                        fused_threshold=None)
    # replicated + non-fused + unstacked: every logical table keeps its own
    # [V, d] array under its own name, exactly the merged-bundle layout
    coll = ShardedEmbeddingCollection(specs, mesh=mesh)
    if set(bundle.tables) != set(coll.specs):
        raise ValueError(
            f"bundle tables {sorted(bundle.tables)} do not match the "
            f"{bundle.model!r} schema {sorted(coll.specs)} — wrong bundle "
            "for this model/config")
    tables = _device_tree(dict(bundle.tables), mesh)
    dense_params = _device_tree(bundle.dense_params, mesh)
    features = tuple(coll.features())

    if bundle.model == "dlrm":
        backbone = DLRMBackbone(embed_dim=bundle.embed_dim, dtype=dtype,
                                cat_columns=tuple(bundle.cat_columns),
                                cont_columns=tuple(bundle.cont_columns))
    else:
        backbone = TwoTowerBackbone(embed_dim=bundle.embed_dim, dtype=dtype)

    @partial(jax.jit, donate_argnums=(0,))
    def score(batch, tables, dense_params):
        embs = coll.lookup(tables, {f: batch[f] for f in features},
                           mode="gspmd")
        return backbone.apply({"params": dense_params}, embs, batch)

    user = item = None
    if bundle.model == "twotower":
        item_cols = tuple(
            _FEATURE_TO_INPUT[f] for f in TWOTOWER_ITEM_CATEGORICAL)
        tower = Tower(bundle.embed_dim, dtype=dtype)

        @jax.jit
        def user(batch, tables, dense_params):
            embs = coll.lookup(tables, {"user_id": batch["user_id"]},
                               mode="gspmd")
            return tower.apply({"params": dense_params["user_tower"]},
                               embs["user_id"].astype(dtype))

        @jax.jit
        def item(batch, tables, dense_params):
            embs = coll.lookup(tables, {c: batch[c] for c in item_cols},
                               mode="gspmd")
            parts = [embs[c].astype(dtype) for c in item_cols]
            parts += [batch[c].astype(dtype)[:, None]
                      for c in TWOTOWER_CONTINUOUS]
            return tower.apply({"params": dense_params["item_tower"]},
                               jnp.concatenate(parts, axis=-1))

    return Scorer(
        model=bundle.model, embed_dim=bundle.embed_dim,
        cont_columns=tuple(bundle.cont_columns), features=features,
        _score=score, _params=(tables, dense_params), _user=user, _item=item,
    )
