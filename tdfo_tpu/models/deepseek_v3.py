"""``deepseek_v3``: a decoder whose every layer is multi-head latent
attention (MLA) and then a feed-forward part, each pre-normed:
``h = x + MLA(RMSNorm(x))``, ``x' = h + FFN(RMSNorm(h))``.  The first
``first_k_dense_replace`` layers' FFN is a dense SwiGLU MLP, every later
layer's a sigmoid-routed mixture of SwiGLU experts beside shared experts.

Source: https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json
(``model_type: deepseek_v3``, whose key names :class:`LmConfig` keeps; the
layers follow the published ``modeling_deepseek.py``: ``DeepseekV3Attention``
with ``q_lora_rank`` null, ``DeepseekV3MLP``, ``DeepseekV3MoE`` with
``MoEGate``'s ``noaux_tc`` selection at ``n_group = topk_group = 1``;
DeepSeek-V2 / V3, arXiv:2405.04434, 2412.19437).  The equations are written
out in ``benchmarks/reference/deepseek_v3.py``, the plain float32 reference
the tests hold this file to; what the config does not state is under
``assumed`` in ``benchmarks/configs/moonlight-16b-a3b.json``:

MLA       ``[q_nope | q_pe] = u W_q`` a head; ``[c_kv | k_pe] = u W_kv_a``,
          ``c_kv <- RMSNorm(c_kv)`` (``kv_a_layernorm``, eps 1e-6);
          ``[k_nope | v] = c_kv W_kv_b`` a head; RoPE on ``q_pe`` and on the
          ONE ``k_pe``, which every head reads, in the published pair layout
          (a head's ``d`` channels read as ``d / 2`` pairs, evens first,
          then rotate-half); ``q = [q_nope | q_pe]``, ``k = [k_nope | k_pe]``;
          causal softmax within documents at scale ``1 / sqrt(nope + rope)``
          over ``v`` (``v_head_dim`` wide); ``W_o``.  Positions are a
          token's place in its document.
experts   ``s = sigmoid(u W_r)`` over every published expert; the ``k``
          largest of ``s + b`` chosen (``b``, the published
          ``e_score_correction_bias``: a buffer); ``w = routed_scaling_factor
          * s / sum of the chosen s``; the held experts' ``(silu(u W_g) *
          u W_u) W_d`` (``ops/moe.py``; no token dropped) plus the
          ``n_shared_experts`` shared experts as ONE SwiGLU MLP of
          ``n_shared_experts * moe_intermediate_size``.

The chip's share (the deployment the cell's configuration states): the
expert layer is told which experts it holds (``first_expert_held`` and
``experts_held`` of ``n_routed_experts``); the router keeps its published
width and its experts per token, and the shared experts are held whole.
What the absent experts would add is left out; no code stands in for the
absent chips.

Parameters are float32 and cast to ``dtype`` where they are used; router,
sigmoid, norms, rotary angles, the softmax's statistics and the loss are
float32.  The embedding lives outside (a ``ShardedEmbeddingCollection``
table): :func:`forward_loss` takes the gathered vectors and returns the
next-token loss with the step's counters.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from tdfo_tpu.models.olmo_hybrid import (
    causal_document_attention, cotangent_once, init_tree, made_once,
    next_token_loss, proj, rms_norm)
from tdfo_tpu.models.sdar_moe import document_blocks
from tdfo_tpu.ops import moe

__all__ = ["LmConfig", "init_params", "param_shapes", "forward_loss",
           "backbone", "mla_mixer", "expert_layer", "rope_pairs",
           "attention_pairs", "STEP_COUNTERS", "BUFFERS"]

# what the step returns beside the loss: the (query, key) pairs the
# document-causal mask admits, summed over the layers, and ``nemotron_h``'s
# expert counters (summed over the expert layers but ``moe_load_max``, the
# largest held expert's pairs in one)
_EXPERT_COUNTERS = ("moe_pairs", "moe_pairs_computed", "moe_load_max",
                    "moe_layers_dense")
STEP_COUNTERS = ("attn_pairs",) + _EXPERT_COUNTERS
# leaves that are no parameters: no gradient, no decay (the published
# ``e_score_correction_bias``)
BUFFERS = ("router_bias",)
# ``kv_a_layernorm`` is built without the config's epsilon in the published
# code: the class default
LATENT_NORM_EPS = 1e-6


@dataclass(frozen=True)
class LmConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int            # the leading dense layers' MLP
    n_routed_experts: int             # the router's width
    num_experts_per_tok: int
    moe_intermediate_size: int
    rope_theta: float
    first_k_dense_replace: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    # the chip's share (0 = all published)
    experts_held: int = 0
    first_expert_held: int = 0

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace must lie in "
                             "[0, num_hidden_layers]")
        if min(self.experts_held, self.first_expert_held) < 0 or \
                self.first_expert_held + self.experts > self.n_routed_experts:
            raise ValueError("first_expert_held + experts_held must lie in "
                             "[0, n_routed_experts]")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok must be <= n_routed_experts")

    @property
    def experts(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


# ------------------------------------------------------------- parameters


def _mlp_shapes(d: int, f: int) -> dict[str, tuple]:
    return {"gate": (d, f), "up": (d, f), "down": (f, d)}


def param_shapes(cfg: LmConfig) -> dict:
    """The dense parameter tree's shapes (nested like the tree).  An
    expert's gate and up projections lie side by side in ``w1``."""
    d, h, f = cfg.hidden_size, cfg.num_attention_heads, cfg.moe_intermediate_size
    attn = {"wq": (d, h * cfg.qk_head_dim),
            "wkv_a": (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "kv_norm": (cfg.kv_lora_rank,),
            "wkv_b": (cfg.kv_lora_rank,
                      h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": (h * cfg.v_head_dim, d)}
    experts = {"router": (d, cfg.n_routed_experts),
               "router_bias": (cfg.n_routed_experts,),
               "w1": (cfg.experts, d, 2 * f), "w2": (cfg.experts, f, d)}
    if cfg.n_shared_experts:
        experts["shared"] = _mlp_shapes(d, cfg.n_shared_experts * f)
    tree = {f"layer_{i}": {
        "attn_norm": (d,), "attn": attn, "mlp_norm": (d,),
        "mlp": (_mlp_shapes(d, cfg.intermediate_size)
                if i < cfg.first_k_dense_replace else experts)}
        for i in range(cfg.num_hidden_layers)}
    tree["final_norm"] = (d,)
    tree["head"] = (d, cfg.vocab_size)
    return tree


def init_leaf(rng: jax.Array, name: str, shape: tuple) -> jax.Array:
    """Norm weights one, the selection bias zero, every product
    normal(0, 0.02) (``initializer_range``)."""
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    return 0.02 * jax.random.normal(rng, shape, jnp.float32)


def init_params(rng: jax.Array, cfg: LmConfig) -> dict:
    return init_tree(rng, param_shapes(cfg), init_leaf)


# ------------------------------------------------------------------ layers


def rope_pairs(x, pos, theta: float):
    """The published rotary embedding of ``x`` [B, T, H, d] at positions
    ``pos`` [B, T]: the channels read as ``d / 2`` pairs and laid out evens
    first, odds after (``view(d/2, 2).transpose``), then rotate-half at
    angles ``pos * theta^(-2m / d)``, in float32.  The output keeps that
    layout, as the published code's does."""
    d = x.shape[-1]
    x32 = x.astype(jnp.float32)
    x32 = jnp.swapaxes(x32.reshape(*x.shape[:-1], d // 2, 2), -1, -2
                       ).reshape(x.shape)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[:, :, None, :]
    half = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], axis=-1)
    return (x32 * jnp.cos(ang) + half * jnp.sin(ang)).astype(x.dtype)


def mla_mixer(p, u, segment, pos, cfg: LmConfig):
    """``u`` [B, T, d] (normed) -> ``W_o``'s output; ``pos`` [B, T] a
    token's position in its document."""
    b, t, _ = u.shape
    h, nope, rope = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
        cfg.qk_rope_head_dim
    with jax.named_scope("mla_proj"):
        q = proj(u, p["wq"]).reshape(b, t, h, nope + rope)
        kv_a = proj(u, p["wkv_a"])
        c_kv = rms_norm(kv_a[..., :cfg.kv_lora_rank], p["kv_norm"],
                        LATENT_NORM_EPS)
        kv = proj(c_kv, p["wkv_b"]).reshape(b, t, h, nope + cfg.v_head_dim)
    with jax.named_scope("mla_rope"):
        q_pe = rope_pairs(q[..., nope:], pos, cfg.rope_theta)
        k_pe = rope_pairs(kv_a[..., None, cfg.kv_lora_rank:], pos,
                          cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (b, t, h, rope))], axis=-1)
    with jax.named_scope("mla_attn"):
        o = causal_document_attention(q, k, kv[..., nope:], segment)
    with jax.named_scope("mla_proj"):
        return proj(o.reshape(b, t, -1), p["wo"])


def swiglu_mlp(p, u):
    """``(silu(u W_g) * u W_u) W_d`` with each product's cotangent made
    once (``olmo_hybrid``'s MLP)."""
    gate = cotangent_once(proj(u, p["gate"]))
    up = cotangent_once(proj(u, p["up"]))
    return cotangent_once(proj(jax.nn.silu(gate) * up, p["down"]))


def expert_layer(p, u, cfg: LmConfig):
    """``u`` [B, T, d] (normed) -> ``(out, counters)``: the held experts'
    part of the routed sum plus the shared experts."""
    b, t, d = u.shape
    tokens = u.reshape(b * t, d)
    lo, e = cfg.first_expert_held, cfg.experts
    with jax.named_scope("moe_route"):
        weights, chosen = moe.route(
            tokens, p["router"], p["router_bias"],
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
            normalise=cfg.norm_topk_prob, score="sigmoid")
        weights, chosen = weights[:, lo:lo + e], chosen[:, lo:lo + e]
    with jax.named_scope("moe_experts"):
        out, pairs, computed, load_max, fell_back = moe.held_experts(
            tokens, weights, chosen, p["w1"], p["w2"], act=moe.swiglu,
            rows=moe.sorted_rows(b * t, cfg.num_experts_per_tok, e,
                                 cfg.n_routed_experts))
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            out = out + swiglu_mlp(p["shared"], tokens)
    counters = {"moe_pairs": pairs, "moe_pairs_computed": computed,
                "moe_load_max": load_max, "moe_layers_dense": fell_back}
    return out.reshape(b, t, d), counters


def _block(p, x, segment, pos, dense: bool, cfg: LmConfig):
    eps = cfg.rms_norm_eps
    u = made_once(rms_norm(x, p["attn_norm"], eps))
    h = x + mla_mixer(p["attn"], u, segment, pos, cfg)
    u = made_once(rms_norm(h, p["mlp_norm"], eps))
    if dense:
        with jax.named_scope("dense_mlp"):
            return h + swiglu_mlp(p["mlp"], u), _no_counters()
    out, counters = expert_layer(p["mlp"], u, cfg)
    return h + out, counters


def _no_counters() -> dict:
    return dict.fromkeys(_EXPERT_COUNTERS, jnp.zeros((), jnp.int32))


def backbone(params, x, segment, cfg: LmConfig):
    """``x`` [B, T, d] through every layer, each rematerialised in the
    backward pass (it keeps its input and its projections' outputs, as
    ``models/olmo_hybrid.backbone``); ``(x, counters)``."""
    keep = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    pos, _ = document_blocks(segment, 1)
    total = _no_counters()
    for i in range(cfg.num_hidden_layers):
        layer = jax.checkpoint(
            lambda p, x, dense=i < cfg.first_k_dense_replace: _block(
                p, x, segment, pos, dense, cfg), policy=keep)
        x, c = layer(params[f"layer_{i}"], x)
        total = {k: (jnp.maximum(total[k], c[k]) if k == "moe_load_max"
                     else total[k] + c[k]) for k in total}
    return x, total


def attention_pairs(segment):
    """The (query, key) pairs the document-causal mask admits in ``segment``
    [B, T] (documents packed in order): ``sum over documents of L (L + 1) /
    2``, counted as each position's place in its document plus one."""
    within, _ = document_blocks(segment, 1)
    return (within + 1).sum(dtype=jnp.int32)


def forward_loss(params, embedded, token, segment, cfg: LmConfig, *,
                 dtype=jnp.float32, platform: str | None = None):
    """The training forward: gathered vectors ``embedded`` [B, T, d] ->
    ``(next-token loss, {counter: int32 scalar})``.  ``platform`` (the
    devices') picks no kernel in this family."""
    with jax.named_scope("lm_embed"):
        x = embedded.astype(dtype)
    x, counters = backbone(params, x, segment, cfg)
    loss = next_token_loss(params, x, token, segment, cfg)[0]
    pairs = cfg.num_hidden_layers * attention_pairs(segment)
    return loss, {"attn_pairs": pairs, **counters}
