"""Bert4Rec — masked-LM sequential recommender, flax + sharded embeddings.

Capability parity with the reference (``torchrec/models.py:132-223``):
``HistoryArch`` (item ``EmbeddingCollection`` + learned positional encoding +
LayerNorm/dropout) feeding N transformer blocks and a vocab-size output
projection; padding id 0, mask token ``n_items + 1``
(``torchrec/preprocessing.py:14-15``); attention mask = key-validity
broadcast to [B, 1, T, T] (``torchrec/models.py:214-219``).

Two usage modes mirror the reference's DMP/DDP split (``torchrec/train.py:235-260``):

  * :class:`Bert4Rec` owns its item table as a flax ``nn.Embed`` — the
    replicated/DDP-equivalent path; one module, one param tree.
  * :class:`Bert4RecBackbone` consumes *already gathered* item vectors, with
    the table living in a :class:`~tdfo_tpu.parallel.embedding.ShardedEmbeddingCollection`
    outside the module — the DMP-equivalent model-parallel path, used with
    ``make_sparse_train_step`` (in-backward sparse optimizer, tables sharded
    over the ``model`` mesh axis).  :func:`make_sharded_bert4rec` wires both
    halves.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from flax import linen as nn

from tdfo_tpu.models.transformer import TransformerBlock, dot_product_attention

__all__ = [
    "PAD_ID",
    "Bert4RecConfig",
    "Bert4RecBackbone",
    "Bert4Rec",
    "make_sharded_bert4rec",
    "init_bert4rec",
]

PAD_ID = 0  # torchrec/preprocessing.py:14


@dataclass(frozen=True)
class Bert4RecConfig:
    """Hyperparameters (``torchrec/utils.py:8-26`` + size_map handshake).

    ``vocab_size = n_items + 2``: PAD(0) + items(1..n) + MASK(n+1)
    (``torchrec/train.py:227-233``).
    """

    n_items: int
    max_len: int = 20
    embed_dim: int = 64
    n_heads: int = 2
    n_layers: int = 2
    ff_mult: int = 4
    dropout: float = 0.1

    @property
    def vocab_size(self) -> int:
        return self.n_items + 2

    @property
    def mask_id(self) -> int:
        return self.n_items + 1


def key_padding_mask(item_ids: jax.Array) -> jax.Array:
    """[B, T] ids -> [B, 1, T, T] attention mask (True = attend); keys at PAD
    are masked for every query (``torchrec/models.py:214-219``)."""
    valid = item_ids != PAD_ID  # [B, T]
    return valid[:, None, None, :]


class Bert4RecBackbone(nn.Module):
    """Everything after the embedding lookup: positional encoding, LN/dropout
    (HistoryArch tail, ``torchrec/models.py:144-146,177-178``), transformer
    stack, vocab projection (``torchrec/models.py:220-223``)."""

    cfg: Bert4RecConfig
    dtype: jnp.dtype = jnp.float32
    attn_fn: staticmethod = staticmethod(dot_product_attention)

    @nn.compact
    def __call__(self, item_embs: jax.Array, mask: jax.Array | None, *,
                 deterministic: bool = True) -> jax.Array:
        cfg = self.cfg
        b, t, d = item_embs.shape
        pos = self.param(
            "pos_embed",
            jax.nn.initializers.normal(0.02),
            (cfg.max_len, d),
            jnp.float32,
        )
        h = item_embs.astype(self.dtype) + pos[None, :t].astype(self.dtype)
        h = nn.LayerNorm(dtype=self.dtype, name="ln_in")(h)
        h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        for i in range(cfg.n_layers):
            h = TransformerBlock(
                n_heads=cfg.n_heads,
                ff_dim=cfg.ff_mult * d,
                dropout=cfg.dropout,
                dtype=self.dtype,
                attn_fn=self.attn_fn,
                name=f"block_{i}",
            )(h, mask, deterministic=deterministic)
        # [B, T, V] — the FLOPs peak; under a mesh the caller constrains the
        # vocab axis (column) sharding if desired.
        return nn.Dense(cfg.vocab_size, dtype=self.dtype, name="out_proj")(h)


class Bert4Rec(nn.Module):
    """Self-contained Bert4Rec (replicated item table — the DDP branch,
    ``torchrec/train.py:256-260``)."""

    cfg: Bert4RecConfig
    dtype: jnp.dtype = jnp.float32
    # same init as the DMP path's EmbeddingSpec(init_scale=1.0) — torchrec's
    # weight_init_min/max = -1/1 — so the two regimes are init-equivalent
    init_scale: float = 1.0

    @nn.compact
    def __call__(self, item_ids: jax.Array, *, deterministic: bool = True) -> jax.Array:
        scale = self.init_scale
        emb = nn.Embed(
            self.cfg.vocab_size,
            self.cfg.embed_dim,
            dtype=self.dtype,
            embedding_init=lambda key, shape, dtype: jax.random.uniform(
                key, shape, dtype, minval=-scale, maxval=scale
            ),
            name="item_embed",
        )
        h = emb(item_ids)
        return Bert4RecBackbone(self.cfg, self.dtype, name="backbone")(
            h, key_padding_mask(item_ids), deterministic=deterministic
        )


def init_bert4rec(rng: jax.Array, cfg: Bert4RecConfig, dtype=jnp.float32):
    model = Bert4Rec(cfg=cfg, dtype=dtype)
    dummy = jnp.zeros((1, cfg.max_len), jnp.int32)
    params = model.init(rng, dummy)["params"]
    return model, params


def make_sharded_bert4rec(
    rng: jax.Array,
    cfg: Bert4RecConfig,
    mesh,
    *,
    sharding: str = "row",
    dtype=jnp.float32,
    attn: str = "full",
    fused_threshold: int | None = 16384,
    fused_kind: str = "adam",
    a2a_capacity_factor: float | None = None,
    ring_block_k: int | None = None,
    tp_heads: bool = False,
    grouped_a2a: bool = False,
):
    """The DMP-equivalent wiring (``torchrec/train.py:235-254``): item table in
    a ShardedEmbeddingCollection (sharded over ``model``), dense transformer
    replicated.

    Returns ``(collection, tables, backbone, dense_params)``; feed a batch as
    ``{"item": [B, T] ids, ...}`` through ``collection.lookup`` then
    ``backbone.apply``.  Pairs with ``make_sparse_train_step``.
    """
    from tdfo_tpu.parallel.embedding import EmbeddingSpec, ShardedEmbeddingCollection

    coll = ShardedEmbeddingCollection(
        [
            EmbeddingSpec(
                "item_embedding",
                num_embeddings=cfg.vocab_size,
                embedding_dim=cfg.embed_dim,
                features=("item",),
                sharding=sharding,
                init_scale=1.0,  # torchrec weight_init_min/max = -1/1
                # big item catalogues get fused fat-row storage (in-place
                # DMA Adam, O(touched rows) updates)
                fused=(fused_threshold is not None
                       and sharding in ("row", "replicated")
                       and cfg.vocab_size > fused_threshold),
            )
        ],
        mesh=mesh,
        a2a_capacity_factor=a2a_capacity_factor,
        fused_kind=fused_kind,
        grouped_a2a=grouped_a2a,
    )
    k_table, k_dense = jax.random.split(rng)
    tables = coll.init(k_table)
    if attn in ("ring", "ring_flash"):
        # sequence parallelism: attention shards T over the "seq" mesh axis
        # (ring K/V rotation over ICI) — long-context capability beyond the
        # reference's full T×T attention.  ``tp_heads`` composes it with
        # Megatron attention TP (heads over the "model" axis — pair with
        # megatron_tp_rule(n_heads=...) on the dense params); the batch stays
        # sharded over "data" rather than gathering per layer.
        from tdfo_tpu.core.mesh import DATA_AXIS, MODEL_AXIS
        from tdfo_tpu.parallel.ring_attention import make_ring_attn_fn

        attn_fn = make_ring_attn_fn(
            mesh, block_k=ring_block_k,
            head_axis=MODEL_AXIS if tp_heads else None,
            batch_axis=DATA_AXIS,
            impl="flash" if attn == "ring_flash" else "xla",
        )
    elif attn == "flash":
        # single-device long-context path: Pallas blockwise online-softmax
        # kernel, O(T) memory (tdfo_tpu/ops/pallas_kernels.py)
        from tdfo_tpu.core.mesh import mesh_platform, pallas_impl
        from tdfo_tpu.ops.pallas_kernels import flash_attention

        def attn_fn(q, k, v, mask=None):
            key_valid = None if mask is None else mask[:, 0, 0, :]
            how = pallas_impl("flash_attention", mesh_platform(mesh),
                              off_chip="interpret")
            return flash_attention(q, k, v, key_valid,
                                   interpret=how == "interpret")
    elif attn == "full":
        attn_fn = dot_product_attention
    else:
        raise ValueError(f"unknown attn {attn!r}")
    backbone = Bert4RecBackbone(cfg=cfg, dtype=dtype, attn_fn=attn_fn)
    dummy = jnp.zeros((1, cfg.max_len, cfg.embed_dim), dtype)
    dense_params = backbone.init(k_dense, dummy, None)["params"]
    return coll, tables, backbone, dense_params
