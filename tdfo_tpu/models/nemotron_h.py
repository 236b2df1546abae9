"""``nemotron_h``: a decoder whose layer is, by ``hybrid_override_pattern``,
a Mamba-2 mixer (``M``), grouped-query causal attention (``*``) or a latent
mixture of experts (``E``) ALONE: ``x <- x + Part(RMSNorm(x))``.

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json
(``model_type: nemotron_h``, whose key names :class:`LmConfig`
keeps; the layers follow the published ``modeling_nemotron_h.py``:
``NemotronHMamba2Mixer``, ``NemotronHAttention`` (no rotary embedding),
``NemotronHMOE`` with ``NemotronHTopkRouter``).  The equations are written
out in ``benchmarks/reference/nemotron_h.py``, the plain float32 reference
the tests hold this file to.  What the config does not state is under
``assumed`` in ``benchmarks/configs/nemotron-3-super-120b-a12b.json``.

The chip's share (``/opt/skills/guides/model-configs`` section 4): a mixer
is told how many of the published heads it holds (``mamba_heads_held``, and
with them whole groups of ``B`` / ``C``; ``attention_heads_held`` on the
key/value heads they read), the expert layer which experts
(``first_expert_held`` and ``experts_held`` of ``n_routed_experts``).  The
router keeps its published width and its experts per token; the shared
expert is held whole.  What the absent heads and experts would add is left
out and the partial result goes on; no code stands in for the absent chips.

Parameters are float32 and cast to ``dtype`` where they are used; router,
softmax, norms, ``dt``, the decays and the SSM state are float32.  The
embedding lives outside (a ``ShardedEmbeddingCollection`` table):
:func:`forward_loss` takes the gathered vectors and returns the loss with
the step's expert counters.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from tdfo_tpu.models.olmo_hybrid import (
    causal_conv, causal_document_attention, cotangent_once, init_tree,
    made_once, next_token_loss, proj, rms_norm)
from tdfo_tpu.ops import moe
from tdfo_tpu.ops.ssd import chunk_ssd

__all__ = ["LmConfig", "init_params", "forward_loss",
           "backbone", "mamba2_mixer", "attention_mixer", "expert_layer",
           "STEP_COUNTERS", "BUFFERS", "LAYER_KINDS"]

LAYER_KINDS = ("M", "*", "E")
# what the step returns beside the loss (``forward_loss``), summed over the
# expert layers but ``moe_load_max``, the largest held expert's pairs in one;
# ``moe_layers_dense`` counts the expert layers that took the dense form
# (``ops/moe.py``: their pairs exceeded the sorted form's rows)
STEP_COUNTERS = ("moe_pairs", "moe_pairs_computed", "moe_load_max",
                 "moe_layers_dense")
# leaves that are no parameters: no gradient, no decay
BUFFERS = ("router_bias",)


@dataclass(frozen=True)
class LmConfig:
    vocab_size: int
    hidden_size: int
    hybrid_override_pattern: str
    # published counts: they set group and head sizes
    mamba_num_heads: int
    mamba_head_dim: int
    ssm_state_size: int
    n_groups: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_latent_size: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    conv_kernel: int = 4
    rms_norm_eps: float = 1e-5       # the config's ``layer_norm_epsilon``
    # the chip's share (0 = all published)
    mamba_heads_held: int = 0
    attention_heads_held: int = 0
    experts_held: int = 0
    first_expert_held: int = 0

    def __post_init__(self):
        bad = set(self.hybrid_override_pattern) - set(LAYER_KINDS)
        if bad or not self.hybrid_override_pattern:
            raise ValueError(f"hybrid_override_pattern must be of {LAYER_KINDS}"
                             f", got {sorted(bad) or 'none'}")
        per_group = self.mamba_num_heads // self.n_groups
        if self.mamba_num_heads % self.n_groups or self.mamba_heads % per_group \
                or not 0 <= self.mamba_heads_held <= self.mamba_num_heads:
            raise ValueError("mamba_heads_held must be whole groups of "
                             f"{per_group} heads, at most mamba_num_heads: "
                             f"got {self.mamba_heads_held}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must divide by "
                             "num_key_value_heads")
        if not 0 <= self.attention_heads_held <= self.num_attention_heads:
            raise ValueError("attention_heads_held must be in "
                             "[0, num_attention_heads]")
        if min(self.experts_held, self.first_expert_held) < 0 or \
                self.first_expert_held + self.experts > self.n_routed_experts:
            raise ValueError("first_expert_held + experts_held must lie in "
                             "[0, n_routed_experts]")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok must be <= n_routed_experts")
        if self.conv_kernel < 1:
            raise ValueError("conv_kernel must be >= 1")

    @property
    def mamba_heads(self) -> int:
        return self.mamba_heads_held or self.mamba_num_heads

    @property
    def mamba_groups(self) -> int:
        return self.mamba_heads // (self.mamba_num_heads // self.n_groups)

    @property
    def attention_heads(self) -> int:
        return self.attention_heads_held or self.num_attention_heads

    @property
    def key_value_heads(self) -> int:
        """The key/value heads the held query heads read (a query head
        reads one; part of a group still needs its whole head)."""
        per_kv = self.num_attention_heads // self.num_key_value_heads
        return -(-self.attention_heads // per_kv)

    @property
    def experts(self) -> int:
        return self.experts_held or self.n_routed_experts


# ------------------------------------------------------------- parameters


def _part_shapes(cfg: LmConfig, kind: str) -> dict[str, tuple]:
    d = cfg.hidden_size
    if kind == "M":
        inner = cfg.mamba_heads * cfg.mamba_head_dim
        conv = inner + 2 * cfg.mamba_groups * cfg.ssm_state_size
        h = cfg.mamba_heads
        return {"in_proj": (d, inner + conv + h), "conv_w": (cfg.conv_kernel, conv),
                "conv_bias": (conv,), "A_log": (h,), "dt_bias": (h,), "D": (h,),
                "gate_norm": (inner,), "out_proj": (inner, d)}
    if kind == "*":
        q = cfg.attention_heads * cfg.head_dim
        kv = cfg.key_value_heads * cfg.head_dim
        return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    e, l = cfg.experts, cfg.moe_latent_size
    f, s = cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size
    return {"router": (d, cfg.n_routed_experts),
            "router_bias": (cfg.n_routed_experts,),
            "down": (d, l), "up": (l, d), "w1": (e, l, f), "w2": (e, f, l),
            "shared_in": (d, s), "shared_out": (s, d)}


def param_shapes(cfg: LmConfig) -> dict:
    """The dense parameter tree's shapes (nested like the tree)."""
    tree: dict = {}
    for i, kind in enumerate(cfg.hybrid_override_pattern):
        tree[f"layer_{i}"] = {"norm": (cfg.hidden_size,),
                              "part": _part_shapes(cfg, kind)}
    tree["final_norm"] = (cfg.hidden_size,)
    tree["head"] = (cfg.hidden_size, cfg.vocab_size)
    return tree


def init_leaf(rng: jax.Array, name: str, shape: tuple) -> jax.Array:
    """One leaf's initial value, by its name: norm weights and ``D`` one;
    biases and the selection bias zero; projections normal(0, 0.02); the
    convolution uniform(+-1/sqrt(K)); ``A = 1..H`` (``A_log`` its log) and
    ``dt_bias`` the inverse softplus of ``dt`` log-uniform in [1e-3, 1e-1]
    (``time_step_min`` / ``time_step_max``), as the published
    ``NemotronHMamba2Mixer`` initialises them."""
    if name.endswith("norm") or name == "D":
        return jnp.ones(shape, jnp.float32)
    if name.endswith("bias") and name != "dt_bias":
        return jnp.zeros(shape, jnp.float32)
    if name == "conv_w":
        bound = 1.0 / math.sqrt(shape[0])
        return jax.random.uniform(rng, shape, jnp.float32, -bound, bound)
    if name == "A_log":
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(rng, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return 0.02 * jax.random.normal(rng, shape, jnp.float32)


def init_params(rng: jax.Array, cfg: LmConfig) -> dict:
    return init_tree(rng, param_shapes(cfg), init_leaf)


# ------------------------------------------------------------------ layers


def mamba2_mixer(p, u, segment, cfg: LmConfig):
    """``u`` [B, T, d] (normed) -> ``W_out``'s output over the heads held."""
    b, t, _ = u.shape
    h, hp, n = cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state_size
    g, inner = cfg.mamba_groups, cfg.mamba_heads * cfg.mamba_head_dim
    f32 = jnp.float32
    with jax.named_scope("ssd_proj"):
        zxbcdt = proj(u, p["in_proj"])
        z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner:-h]
        dt = jax.nn.softplus(zxbcdt[..., -h:].astype(f32) + p["dt_bias"])
    with jax.named_scope("ssd_conv"):
        xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], segment)
                          + p["conv_bias"].astype(xbc.dtype))
        xs = xbc[..., :inner].reshape(b, t, h, hp)
        b_mat = xbc[..., inner:inner + g * n].reshape(b, t, g, n)
        c_mat = xbc[..., inner + g * n:].reshape(b, t, g, n)
    with jax.named_scope("ssd_scan"):
        starts = jnp.concatenate(
            [jnp.ones((b, 1), bool), segment[:, 1:] != segment[:, :-1]], axis=1)
        y = chunk_ssd(xs, dt, -jnp.exp(p["A_log"]), b_mat, c_mat, starts)
        y = y + xs * p["D"].astype(xs.dtype)[:, None]
    with jax.named_scope("ssd_proj"):
        # the gated norm over a group's channels (H P / G: each held group
        # lies whole on this chip, so it needs no exchange)
        y = (y.reshape(b, t, inner) * jax.nn.silu(z)).reshape(b, t, g, -1)
        y = rms_norm(y, p["gate_norm"].reshape(g, -1), cfg.rms_norm_eps)
        return proj(y.reshape(b, t, inner), p["out_proj"])


def attention_mixer(p, u, segment, cfg: LmConfig):
    """``u`` [B, T, d] (normed) -> ``W_o``'s output over the query heads
    held, each on the key/value head of its group; no rotary embedding."""
    b, t, _ = u.shape
    with jax.named_scope("full_attn"):
        heads = lambda a: a.reshape(b, t, -1, cfg.head_dim)
        o = causal_document_attention(
            heads(proj(u, p["wq"])), heads(proj(u, p["wk"])),
            heads(proj(u, p["wv"])), segment)
        return proj(o.reshape(b, t, -1), p["wo"])


def expert_layer(p, u, cfg: LmConfig):
    """``u`` [B, T, d] (normed) -> ``(out, counters)``: the held experts'
    part of the routed sum, in the latent, projected up once, beside the
    shared expert."""
    b, t, d = u.shape
    tokens = u.reshape(b * t, d)
    lo, e = cfg.first_expert_held, cfg.experts
    with jax.named_scope("moe_route"):
        weights, chosen = moe.route(
            tokens, p["router"], p["router_bias"],
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
            normalise=cfg.norm_topk_prob)
        weights, chosen = weights[:, lo:lo + e], chosen[:, lo:lo + e]
    with jax.named_scope("moe_latent"):
        latent = proj(tokens, p["down"])
    with jax.named_scope("moe_experts"):
        routed, pairs, computed, load_max, fell_back = moe.held_experts(
            latent, weights, chosen, p["w1"], p["w2"],
            rows=moe.sorted_rows(b * t, cfg.num_experts_per_tok, e,
                                 cfg.n_routed_experts))
    with jax.named_scope("moe_latent"):
        out = proj(routed, p["up"])
    with jax.named_scope("moe_shared"):
        hidden = cotangent_once(proj(tokens, p["shared_in"]))
        out = out + cotangent_once(
            proj(jnp.square(jax.nn.relu(hidden)), p["shared_out"]))
    counters = {"moe_pairs": pairs, "moe_pairs_computed": computed,
                "moe_load_max": load_max, "moe_layers_dense": fell_back}
    return out.reshape(b, t, d), counters


def _no_counters() -> dict:
    return dict.fromkeys(STEP_COUNTERS, jnp.zeros((), jnp.int32))


def _block(p, x, segment, kind: str, cfg: LmConfig):
    u = made_once(rms_norm(x, p["norm"], cfg.rms_norm_eps))
    counters = _no_counters()
    if kind == "M":
        part = mamba2_mixer(p["part"], u, segment, cfg)
    elif kind == "*":
        part = attention_mixer(p["part"], u, segment, cfg)
    else:
        part, counters = expert_layer(p["part"], u, cfg)
    return x + part, counters


def backbone(params, x, segment, cfg: LmConfig):
    """``x`` [B, T, d] through every layer, each rematerialised in the
    backward pass (it keeps its input and its projections' outputs, as
    ``models/olmo_hybrid.backbone``); ``(x, counters)``."""
    keep = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    total = _no_counters()
    for i, kind in enumerate(cfg.hybrid_override_pattern):
        layer = jax.checkpoint(
            lambda p, x, kind=kind: _block(p, x, segment, kind, cfg),
            policy=keep)
        x, c = layer(params[f"layer_{i}"], x)
        total = {k: (jnp.maximum(total[k], c[k]) if k == "moe_load_max"
                     else total[k] + c[k]) for k in total}
    return x, total


def forward_loss(params, embedded, token, segment, cfg: LmConfig, *,
                 dtype=jnp.float32):
    """The training forward: gathered vectors ``embedded`` [B, T, d] ->
    ``(next-token loss, {counter: int32 scalar})``."""
    with jax.named_scope("lm_embed"):
        x = embedded.astype(dtype)
    x, counters = backbone(params, x, segment, cfg)
    return next_token_loss(params, x, token, segment, cfg)[0], counters
