"""``sdar_moe``: a decoder trained by block diffusion.  Every layer is
grouped-query attention with rotary positions and per-head q/k norms, then
a softmax-routed mixture of SwiGLU experts, each part pre-normed:
``h = x + W_o Attn(RMSNorm(x))``, ``x' = h + MoE(RMSNorm(h))``.

Source: https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json
(``model_type: sdar_moe``, whose key names :class:`LmConfig` keeps; the
layers are Qwen3-MoE's, ``decoder_sparse_step`` 1, no shared expert).  The
objective is SDAR's (arXiv:2510.06303) in the vectorised form of Block
Diffusion (Arriola et al., ICLR 2025).  The equations are written out in
``benchmarks/reference/sdar_moe.py``, the plain float32 reference the tests
hold this file to; what the config does not state is under ``assumed`` in
``benchmarks/configs/sdar-30b-a3b-chat.json``:

noise       each document is cut into blocks of ``block_length`` tokens from
            its first; a block draws ``t ~ U[noise_eps, 1]`` and each of its
            tokens is replaced by the mask token, the table's last row,
            with probability ``t``
            (:func:`block_noise`, from the step's key: a function of (seed,
            step)).
stream      the noisy copy ``x_t`` and the clean copy ``x_0`` side by side,
            ``2 L`` positions, a token's two copies at ONE rotary position.
attention   ``q = RoPE(RMSNorm_head(x W_q))``, ``k`` likewise, ``v = x W_v``;
            a noisy token sees its own noisy block both ways and the clean
            blocks before it; a clean token the clean blocks up to its own
            (``olmo_hybrid.causal_document_attention``'s block-diffusion
            predicate), all within its document.
experts     ``p = softmax(u W_r)`` over every published expert, the top
            ``k`` renormalised; the held experts' ``(silu(u W_g) * u W_u)
            W_d`` (``ops/moe.py``; no token dropped).
loss        the untied head on the noisy half; ``(1 / N) sum over masked
            positions of CE(logits, x_0) / t``, ``N`` the sequence's tokens.

The chip's share (``/opt/skills/guides/model-configs`` section 4): the
expert layer is told which experts it holds (``first_expert_held`` and
``experts_held`` of ``num_experts``); the router keeps its published width
and its experts per token.  What the absent experts would add is left out;
no code stands in for the absent chips.

Parameters are float32 and cast to ``dtype`` where they are used; router,
softmax, norms, rotary angles and the loss are float32.  The embedding
lives outside (a ``ShardedEmbeddingCollection`` table): :func:`prepare_batch`
makes the ids of both copies inside the step, before the lookup, and
:func:`forward_loss` takes the gathered vectors and returns the loss with
the step's counters.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from tdfo_tpu.models.olmo_hybrid import (
    causal_document_attention, cotangent_once, init_tree, made_once, proj,
    rms_norm)
from tdfo_tpu.ops import moe
from tdfo_tpu.ops.pallas_kernels import BD_RESIDUALS, bd_tile_count

__all__ = ["LmConfig", "init_params", "param_shapes", "prepare_batch",
           "forward_loss", "backbone", "attention_mixer", "expert_layer",
           "block_noise", "document_blocks", "rope",
           "STEP_COUNTERS", "BUFFERS", "LAYER_POLICY"]

# what the step returns beside the loss: the noisy positions that were
# masked, the attention kernel's (query tile, key tile) pairs summed over the
# layers (``ops/pallas_kernels.bd_tile_count``: what the kernel multiplies on
# the chip, counted alike off it), and ``nemotron_h``'s expert counters
# (summed over the layers but ``moe_load_max``, the largest held expert's
# pairs in one)
_EXPERT_COUNTERS = ("moe_pairs", "moe_pairs_computed", "moe_load_max",
                   "moe_layers_dense")
STEP_COUNTERS = ("bd_masked_tokens", "bd_attn_tiles") + _EXPERT_COUNTERS
BUFFERS: tuple[str, ...] = ()


@dataclass(frozen=True)
class LmConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    # published counts: they set the head size and the router's width
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    rope_theta: float
    block_length: int
    noise_eps: float = 1e-3
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    # the chip's share (0 = all published)
    experts_held: int = 0
    first_expert_held: int = 0

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must divide by "
                             "num_key_value_heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotate-half)")
        if min(self.experts_held, self.first_expert_held) < 0 or \
                self.first_expert_held + self.experts > self.num_experts:
            raise ValueError("first_expert_held + experts_held must lie in "
                             "[0, num_experts]")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok must be <= num_experts")
        if not 0.0 < self.noise_eps < 1.0:
            raise ValueError("noise_eps must lie in (0, 1)")

    @property
    def mask_token_id(self) -> int:
        """The mask token of the noisy copy: the table's last row."""
        return self.vocab_size - 1

    @property
    def experts(self) -> int:
        return self.experts_held or self.num_experts


# ------------------------------------------------------------- parameters


def param_shapes(cfg: LmConfig) -> dict:
    """The dense parameter tree's shapes (nested like the tree).  An
    expert's gate and up projections lie side by side in ``w1``."""
    d, dh, f = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size
    q, kv = cfg.num_attention_heads * dh, cfg.num_key_value_heads * dh
    layer = {"attn_norm": (d,),
             "attn": {"wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                      "wo": (q, d), "q_norm": (dh,), "k_norm": (dh,)},
             "moe_norm": (d,),
             "moe": {"router": (d, cfg.num_experts),
                     "w1": (cfg.experts, d, 2 * f), "w2": (cfg.experts, f, d)}}
    tree = {f"layer_{i}": layer for i in range(cfg.num_hidden_layers)}
    tree["final_norm"] = (d,)
    tree["head"] = (d, cfg.vocab_size)
    return tree


def init_leaf(rng: jax.Array, name: str, shape: tuple) -> jax.Array:
    """Norm weights one, every product normal(0, 0.02) (Qwen3-MoE's
    ``initializer_range``)."""
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    return 0.02 * jax.random.normal(rng, shape, jnp.float32)


def init_params(rng: jax.Array, cfg: LmConfig) -> dict:
    return init_tree(rng, param_shapes(cfg), init_leaf)


# ------------------------------------------------------------------- noise


def document_blocks(segment, block_length: int):
    """``(within, block)`` of [B, T] int32: a token's position in its
    document and its block there (blocks start at the document's first
    token)."""
    t = segment.shape[1]
    pos = jnp.arange(t, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones_like(segment[:, :1], bool),
                              segment[:, 1:] != segment[:, :-1]], axis=1)
    first = jax.lax.cummax(jnp.where(starts, pos, 0), axis=1)
    within = pos - first
    return within, within // block_length


def block_noise(key, segment, block_length: int, noise_eps: float):
    """``(masked, t)`` of [B, T]: which tokens the noisy copy masks, and
    each token's block's noise level ``t ~ U[noise_eps, 1]`` (one draw a
    block, read at the block's index in the sequence), from ``key`` alone.
    The plain reference draws the same from its definition and the same
    key (``benchmarks/reference/sdar_moe.noise``)."""
    within, _ = document_blocks(segment, block_length)
    opens = within % block_length == 0
    at = jnp.cumsum(opens, axis=1, dtype=jnp.int32) - 1     # block's index
    k_level, k_mask = jax.random.split(key)
    level = jax.random.uniform(k_level, segment.shape, jnp.float32,
                               noise_eps, 1.0)
    t = jnp.take_along_axis(level, at, axis=1)
    return jax.random.uniform(k_mask, segment.shape, jnp.float32) < t, t


def prepare_batch(batch: dict, rng, cfg: LmConfig) -> dict:
    """Inside the step, before the lookup: ``token`` [B, T] -> ``[x_t |
    x_0]`` [B, 2T], and ``weight`` [B, T], ``1 / t`` at a masked position
    and 0 elsewhere."""
    with jax.named_scope("bd_noise"):
        x0, segment = batch["token"], batch["segment"]
        masked, t = block_noise(rng, segment, cfg.block_length, cfg.noise_eps)
        x_t = jnp.where(masked, jnp.asarray(cfg.mask_token_id, x0.dtype), x0)
        return {"token": jnp.concatenate([x_t, x0], axis=1),
                "segment": segment,
                "weight": jnp.where(masked, 1.0 / t, 0.0)}


# ------------------------------------------------------------------ layers


def rope(x, pos, theta: float):
    """Rotate-half rotary embedding over the whole head: ``x`` [B, N, H,
    dh], ``pos`` [N]; angles ``pos * theta^(-2m / dh)`` in float32."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    half = jnp.concatenate([-x32[..., dh // 2:], x32[..., :dh // 2]], axis=-1)
    return (x32 * jnp.cos(ang) + half * jnp.sin(ang)).astype(x.dtype)


def attention_mixer(p, u, segment, block_of, cfg: LmConfig, *,
                    platform: str | None = None):
    """``u`` [B, 2T, d] (normed ``[x_t | x_0]``) -> ``W_o``'s output;
    ``platform`` (the devices') picks the attention's kernel."""
    b, n, _ = u.shape
    length, dh = n // 2, cfg.head_dim
    with jax.named_scope("attn_proj"):
        heads = lambda a: a.reshape(b, n, -1, dh)
        q = rms_norm(heads(proj(u, p["wq"])), p["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(heads(proj(u, p["wk"])), p["k_norm"], cfg.rms_norm_eps)
        v = heads(proj(u, p["wv"]))
        pos = jnp.tile(jnp.arange(length), 2)   # both copies: one position
        q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
    with jax.named_scope("bd_attn"):
        o = causal_document_attention(q, k, v, segment, block_of=block_of,
                                      block_length=cfg.block_length,
                                      platform=platform)
    with jax.named_scope("attn_proj"):
        return proj(o.reshape(b, n, -1), p["wo"])


def expert_layer(p, u, cfg: LmConfig):
    """``u`` [B, N, d] (normed) -> ``(out, counters)``: the held experts'
    part of the routed sum."""
    b, n, d = u.shape
    tokens = u.reshape(b * n, d)
    lo, e = cfg.first_expert_held, cfg.experts
    with jax.named_scope("moe_route"):
        weights, chosen = moe.route(
            tokens, p["router"], None, top_k=cfg.num_experts_per_tok,
            scale=1.0, normalise=cfg.norm_topk_prob, score="softmax")
        weights, chosen = weights[:, lo:lo + e], chosen[:, lo:lo + e]
    with jax.named_scope("moe_experts"):
        out, pairs, computed, load_max, fell_back = moe.held_experts(
            tokens, weights, chosen, p["w1"], p["w2"], act=moe.swiglu,
            rows=moe.sorted_rows(b * n, cfg.num_experts_per_tok, e,
                                 cfg.num_experts))
    counters = {"moe_pairs": pairs, "moe_pairs_computed": computed,
                "moe_load_max": load_max, "moe_layers_dense": fell_back}
    return out.reshape(b, n, d), counters


def _block(p, x, segment, block_of, cfg: LmConfig, platform=None):
    eps = cfg.rms_norm_eps
    u = made_once(rms_norm(x, p["attn_norm"], eps))
    h = x + attention_mixer(p["attn"], u, segment, block_of, cfg,
                            platform=platform)
    out, counters = expert_layer(
        p["moe"], made_once(rms_norm(h, p["moe_norm"], eps)), cfg)
    return h + out, counters


# what a layer keeps for the backward pass: its input, its projections'
# outputs (as ``models/olmo_hybrid.backbone``) and, on the chip, the
# attention kernel's output and log-sum-exp, so that the forward kernel runs
# once a layer and not again to remake them (off the chip nothing has those
# names)
LAYER_POLICY = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(*BD_RESIDUALS))


def backbone(params, x, segment, block_of, cfg: LmConfig, *,
             platform: str | None = None):
    """``x`` [B, 2T, d] through every layer, each rematerialised in the
    backward pass under :data:`LAYER_POLICY`; ``(x, counters)``."""
    total = dict.fromkeys(_EXPERT_COUNTERS, jnp.zeros((), jnp.int32))
    for i in range(cfg.num_hidden_layers):
        layer = jax.checkpoint(
            lambda p, x: _block(p, x, segment, block_of, cfg, platform),
            policy=LAYER_POLICY)
        x, c = layer(params[f"layer_{i}"], x)
        total = {k: (jnp.maximum(total[k], c[k]) if k == "moe_load_max"
                     else total[k] + c[k]) for k in total}
    return x, total


def diffusion_loss(params, x, label, weight, cfg: LmConfig):
    """``(1 / N) sum_i weight_i CE(head(x_i), label_i)`` over the [B, T]
    positions ``x`` [B, T, d] of the noisy copy, ``N = B T``.  The head's
    form is ``olmo_hybrid.next_token_loss``'s: float32 logits KEPT for the
    backward pass, the label's logit picked by a compare against the
    vocabulary's iota (no scatter in the transpose)."""

    def loss(final_norm, head, x):
        h = rms_norm(x, final_norm, cfg.rms_norm_eps)
        logits = cotangent_once(jnp.dot(h, head.astype(h.dtype),
                                         preferred_element_type=jnp.float32))
        vocab = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        picked = jnp.where(vocab == label[..., None], logits, 0.0).sum(-1)
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        return (weight * nll).sum() / weight.size

    keep = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    with jax.named_scope("lm_head_loss"):
        return jax.checkpoint(loss, policy=keep)(
            params["final_norm"], params["head"], x)


def forward_loss(params, embedded, token, segment, cfg: LmConfig, *,
                 weight, dtype=jnp.float32, platform: str | None = None):
    """The training forward over :func:`prepare_batch`'s arrays: gathered
    vectors ``embedded`` [B, 2T, d] of ``token`` = ``[x_t | x_0]``,
    ``segment`` and ``weight`` [B, T] -> ``(loss, {counter: int32})``;
    ``platform`` is the devices' (the attention is a kernel on TPU)."""
    length = segment.shape[1]
    with jax.named_scope("lm_embed"):
        x = embedded.astype(dtype)
    _, block_of = document_blocks(segment, cfg.block_length)
    x, counters = backbone(params, x, segment, block_of, cfg,
                           platform=platform)
    loss = diffusion_loss(params, x[:, :length], token[:, length:], weight, cfg)
    tiles = bd_tile_count(segment, block_of, cfg.block_length)
    return loss, {"bd_masked_tokens": (weight > 0).sum(dtype=jnp.int32),
                  "bd_attn_tiles": cfg.num_hidden_layers * tiles, **counters}
