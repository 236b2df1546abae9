"""``olmo_hybrid``: a decoder whose layers are, by ``layer_types``, either
Gated DeltaNet (``linear_attention``) or causal full attention
(``full_attention``), each followed by a SwiGLU MLP.

Source: https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json
(``model_type: olmo_hybrid``, whose key names :class:`LmConfig`
keeps); the linear-attention layer follows flash-linear-attention's
``GatedDeltaNet`` (Yang, Kautz, Hatamizadeh, arXiv:2412.06464).  The
equations are written out in ``benchmarks/reference/olmo_hybrid.py``, the
plain float32 reference the tests hold this file to.  What the config does
not state is the Olmo 2/3 family's convention: ``h = x + RMSNorm(Mixer(x))``,
``y = h + RMSNorm(MLP(h))``, RMSNorm over the whole projection of q and of k,
no rotary embedding (``rope_theta`` null).

Heads held apart from heads published (the expert-parallel analogue for
heads, ``/opt/skills/guides/model-configs`` section 4): a mixer is told how
many heads it holds (``full_heads_held`` / ``linear_heads_held``) and, under
a mesh, the axis they are shared over.  The Gated DeltaNet mixer is additive
head by head.  The full-attention mixer's q/k RMSNorm runs over every
chip's columns: with ``axis_name`` the mean square is summed over that axis
(one scalar a token), with ``None`` over the held columns alone.  What the
absent heads would add to ``W_o``'s output is left out and the partial
result goes on to the block's RMSNorm; no code stands in for the absent chip.

Parameters are float32 and cast to ``dtype`` where they are used; softmax,
norms, gates, ``gamma`` and the delta-rule state are float32.  The embedding
lives outside (a ``ShardedEmbeddingCollection`` table): :func:`forward_loss`
takes the gathered vectors.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from tdfo_tpu.ops.gated_delta import chunk_gated_delta_rule

__all__ = ["LmConfig", "init_params", "forward_loss", "backbone",
           "STEP_COUNTERS", "BUFFERS", "next_token_loss",
           "full_attention_mixer", "gated_delta_mixer", "LAYER_KINDS",
           # pieces another decoder family takes as they are
           "causal_document_attention", "rms_norm", "causal_conv", "proj",
           "made_once", "cotangent_once", "init_tree"]

LAYER_KINDS = ("linear_attention", "full_attention")
# attention: queries (and keys) a block; the tests pass smaller ones
QUERY_BLOCK = 1024


@dataclass(frozen=True)
class LmConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    layer_types: tuple[str, ...]
    num_attention_heads: int          # published count: sets the head size
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    # the chip's share: heads HELD here of each kind (0 = all published)
    full_heads_held: int = 0
    linear_heads_held: int = 0

    def __post_init__(self):
        bad = set(self.layer_types) - set(LAYER_KINDS)
        if bad or not self.layer_types:
            raise ValueError(f"layer_types must be of {LAYER_KINDS}, got "
                             f"{sorted(bad) or 'none'}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide by num_attention_heads")
        for key in ("full_heads_held", "linear_heads_held"):
            if not 0 <= getattr(self, key) <= self.num_attention_heads:
                raise ValueError(f"{key} must be in [0, num_attention_heads]")
        if self.linear_conv_kernel_dim < 1:
            raise ValueError("linear_conv_kernel_dim must be >= 1")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def full_heads(self) -> int:
        return self.full_heads_held or self.num_attention_heads

    @property
    def linear_heads(self) -> int:
        return self.linear_heads_held or self.num_attention_heads


# what ``Trainer``'s decoder builder reads of a family's module beside
# ``LmConfig`` (built from the ``[lm]`` table's keys of its fields' names),
# ``init_params`` and ``forward_loss``: the counters its step returns beside
# the loss, and the leaves that are buffers (no decay)
STEP_COUNTERS: tuple[str, ...] = ()
BUFFERS: tuple[str, ...] = ()


# ------------------------------------------------------------- parameters


def _mixer_shapes(cfg: LmConfig, kind: str) -> dict[str, tuple]:
    d = cfg.hidden_size
    if kind == "full_attention":
        w = cfg.full_heads * cfg.head_dim
        return {"wq": (d, w), "wk": (d, w), "wv": (d, w), "wo": (w, d),
                "q_norm": (w,), "k_norm": (w,)}
    h, kw = cfg.linear_heads, cfg.linear_conv_kernel_dim
    qk, vw = h * cfg.linear_key_head_dim, h * cfg.linear_value_head_dim
    return {"wq": (d, qk), "wk": (d, qk), "wv": (d, vw), "wg": (d, vw),
            "wo": (vw, d), "wa": (d, h), "wb": (d, h),
            "conv_q": (kw, qk), "conv_k": (kw, qk), "conv_v": (kw, vw),
            "A_log": (h,), "dt_bias": (h,),
            "o_norm": (cfg.linear_value_head_dim,)}


def param_shapes(cfg: LmConfig) -> dict:
    """The dense parameter tree's shapes (nested like the tree)."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    tree: dict = {}
    for i, kind in enumerate(cfg.layer_types):
        tree[f"layer_{i}"] = {
            "mixer": _mixer_shapes(cfg, kind), "mixer_norm": (d,),
            "mlp": {"gate": (d, f), "up": (d, f), "down": (f, d)},
            "mlp_norm": (d,)}
    tree["final_norm"] = (d,)
    tree["head"] = (d, cfg.vocab_size)
    return tree


def init_leaf(rng: jax.Array, name: str, shape: tuple) -> jax.Array:
    """One leaf's initial value, by its name: norm weights one; projections
    normal(0, 0.02) (the family's); the rest as flash-linear-attention's
    ``GatedDeltaNet``: depthwise convolutions uniform(+-1/sqrt(K)),
    ``A_log = log(uniform(0, 16))``, ``dt_bias`` the inverse softplus of
    ``dt`` log-uniform in [1e-3, 1e-1]."""
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name.startswith("conv_"):
        bound = 1.0 / math.sqrt(shape[0])
        return jax.random.uniform(rng, shape, jnp.float32, -bound, bound)
    if name == "A_log":
        return jnp.log(jax.random.uniform(rng, shape, jnp.float32, 1e-3, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(rng, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return 0.02 * jax.random.normal(rng, shape, jnp.float32)


def init_tree(rng: jax.Array, shapes: dict, leaf=init_leaf) -> dict:
    """A tree of ``shapes`` (nested dicts of tuples) initialised leaf by
    leaf, ``leaf(key, name, shape)`` by the leaf's own name."""
    leaves, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf(k, path[-1].key, shape)
        for k, (path, shape) in zip(keys, leaves)])


def init_params(rng: jax.Array, cfg: LmConfig) -> dict:
    return init_tree(rng, param_shapes(cfg))


# ------------------------------------------------------------------ pieces


def rms_norm(x, w, eps: float, *, axis_name: str | None = None):
    """RMSNorm in float32 over the last axis; with ``axis_name`` the mean
    square runs over every device's columns on that mesh axis."""
    x32 = x.astype(jnp.float32)
    ss = jnp.sum(x32 * x32, axis=-1, keepdims=True)
    n = x.shape[-1]
    if axis_name is not None:
        ss = jax.lax.psum(ss, axis_name)
        n = n * jax.lax.psum(1, axis_name)
    return (x32 * jax.lax.rsqrt(ss / n + eps) * w).astype(x.dtype)


@jax.custom_vjp
def cotangent_once(y):
    """Identity on a product's OUTPUT whose cotangent ``dy`` passes an
    ``optimization_barrier`` of its own, so ``dy`` is ONE array, in the
    dtype the products read it in (bfloat16 in the cell, float32 for the
    head's logits), before the two products that need it run: ``dx = dy
    w^T`` and ``dw = x^T dy``.  Without it XLA fuses the expression that
    makes ``dy`` (SwiGLU's backward from three [8192, 11008] arrays, a
    norm's backward, the softmax's) INTO both and evaluates it again for
    every output tile: ``dx`` through ``gate`` 7.4 ms for 3.5 at the MXU
    peak, the head's ``dx`` 9.6 for 4.0 (v5e; PERF.md, PR 35).  One barrier
    a site, on the sites where the step measured faster for it: the head,
    ``gate`` / ``up`` / ``down``, the delta-rule layers' ``wv`` / ``wg``.
    With ``dy`` and the left operands arrays (:func:`made_once`) the
    weight-gradient product runs FUSED with AdamW's sweep of the leaf and
    its moments, 3.95-6.2 ms a [3840, 11008] leaf; PR 33's barrier between
    the two (``_grad_apart``: product 3.9 + sweep 1.65) measured 8 ms a
    step slower from there and is gone.  Nothing is saved for the backward
    pass and the same values come out."""
    return y


cotangent_once.defvjp(lambda y: (y, None),
                       lambda _, g: (jax.lax.optimization_barrier(g),))


@jax.custom_vjp
def made_once(x):
    """A product's left operand that is an expression (``x + rms_norm(mixed)``
    into ``gate`` and ``up``; the normed and gated delta-rule output into
    ``wo``), made as ONE array on the forward side, so the weight-gradient
    product of the rematerialised layer reads an array and not the
    expression again a tile: 19 and 2 ms of the step (PERF.md, PR 35;
    ``silu(gate) * up`` into ``down`` measured 6 ms SLOWER made once, and
    the final norm 1: they are left expressions).  The mirror of
    :func:`cotangent_once`: the barrier is on the value, the cotangent
    passes as it is (a sum of products' outputs: barred, its bfloat16
    rounding differs on the CPU and the step measured slower).  Inside a
    rematerialised layer the array lives in the backward pass only."""
    return jax.lax.optimization_barrier(x)


made_once.defvjp(lambda x: (jax.lax.optimization_barrier(x), None),
                  lambda _, g: (g,))


def proj(x, w):
    return jnp.dot(x, w.astype(x.dtype))


def causal_document_attention(q, k, v, segment, *,
                              query_block: int = QUERY_BLOCK):
    """``softmax(q k^T / sqrt(dh))`` over the keys of the same document at
    positions ``<= t``.  ``q`` [B, T, H, dh]; ``k``, ``v`` [B, T, H_kv, dh]
    with ``H_kv`` dividing ``H`` (query head ``i`` reads key/value head
    ``i // (H / H_kv)``); ``segment`` [B, T].  Blockwise with an online softmax (the ``ring_block_k``
    formulation of ``parallel/ring_attention.py``): a block of
    ``query_block`` queries at a time against the keys up to the block's end
    (the blocks above the diagonal are never formed), those keys a block at
    a time in a ``lax.scan`` whose body is rematerialised, so nothing
    [T, T] is ever alive; statistics in float32.  One masked softmax over
    all keys of a query block measured 7x slower on the v5e: XLA fuses the
    row maximum into a lane-reduce over [H, 1024, 8192] float32 that runs
    far under the memory bandwidth (PERF.md, PR 31)."""
    b, t, h, dh = q.shape
    if k.shape[2] != h:
        # grouped queries: ``h / h_kv`` query heads read one key/value head
        k, v = (jnp.repeat(a, h // k.shape[2], axis=2) for a in (k, v))
    scale = 1.0 / math.sqrt(dh)
    block = min(query_block, t)
    pos = jnp.arange(t)
    low = -1e30   # finite: a row whose keys so far are all masked stays exact

    def rows(q_b, k_b, v_b, seg_q, seg_k, pos_q, pos_k):
        n = k_b.shape[1] // block

        def keys(carry, xs):
            o, m, l = carry
            k_i, v_i, seg_i, pos_i = xs
            logits = jnp.einsum("bqhd,bkhd->bhqk", q_b, k_i,
                                preferred_element_type=jnp.float32) * scale
            ok = ((pos_i[None, :] <= pos_q[:, None])[None]
                  & (seg_i[:, None, :] == seg_q[:, :, None]))[:, None]
            logits = jnp.where(ok, logits, low)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.where(ok, jnp.exp(logits - m_new[..., None]), 0.0)
            shrink = jnp.exp(m - m_new)
            l = l * shrink + p.sum(axis=-1)
            o = o * shrink[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(v_i.dtype), v_i,
                preferred_element_type=jnp.float32)
            return (o, m_new, l), None

        split = lambda a: jnp.moveaxis(
            a.reshape(a.shape[0], n, block, *a.shape[2:]), 1, 0)
        # zeros made FROM the queries: under a shard_map they vary over the
        # mesh axis as the body's outputs do
        o0 = jnp.swapaxes(q_b, 1, 2).astype(jnp.float32) * 0.0
        init = (o0, o0[..., 0] + low, o0[..., 0])
        (o, _, l), _ = jax.lax.scan(
            jax.checkpoint(keys), init,
            (split(k_b), split(v_b), split(seg_k), pos_k.reshape(n, block)))
        return jnp.swapaxes(o / l[..., None], 1, 2).astype(v_b.dtype)

    out = []
    for a in range(0, t, block):
        e = min(a + block, t)
        pad = -e % block   # a ragged last block: keys no query may see
        widen = lambda x, fill=0: jnp.pad(
            x[:, :e], [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2),
            constant_values=fill)
        out.append(rows(q[:, a:e], widen(k), widen(v), segment[:, a:e],
                        widen(segment, -1), pos[a:e],
                        jnp.pad(pos[:e], (0, pad), constant_values=t)))
    return jnp.concatenate(out, axis=1) if len(out) > 1 else out[0]


def full_attention_mixer(p, x, segment, cfg: LmConfig, *,
                         axis_name: str | None = None):
    """``x`` [B, T, d] -> ``W_o``'s output over the heads held here."""
    b, t, _ = x.shape
    with jax.named_scope("full_attn"):
        eps = cfg.rms_norm_eps
        q = rms_norm(proj(x, p["wq"]), p["q_norm"], eps, axis_name=axis_name)
        k = rms_norm(proj(x, p["wk"]), p["k_norm"], eps, axis_name=axis_name)
        v = proj(x, p["wv"])
        heads = lambda a: a.reshape(b, t, -1, cfg.head_dim)
        o = causal_document_attention(heads(q), heads(k), heads(v), segment)
        return proj(o.reshape(b, t, -1), p["wo"])


def causal_conv(x, w, segment):
    """Depthwise causal convolution over time; taps before the token's
    document start are zero.  ``x`` [B, T, C], ``w`` [K, C]."""
    t, kw = x.shape[1], w.shape[0]
    w = w.astype(x.dtype)
    y = x * w[kw - 1]
    for s in range(1, kw):
        xs = jnp.pad(x, ((0, 0), (s, 0), (0, 0)))[:, :t]
        seg = jnp.pad(segment, ((0, 0), (s, 0)), constant_values=-1)[:, :t]
        y = y + jnp.where((seg == segment)[..., None], xs, 0) * w[kw - 1 - s]
    return y


def gated_delta_mixer(p, x, segment, cfg: LmConfig):
    """``x`` [B, T, d] -> ``W_o``'s output over the heads held here."""
    b, t, _ = x.shape
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    f32 = jnp.float32
    with jax.named_scope("deltanet_proj"):
        q, k = proj(x, p["wq"]), proj(x, p["wk"])
        v = cotangent_once(proj(x, p["wv"]))
        gate = cotangent_once(proj(x, p["wg"]))
        a = jnp.dot(x, p["wa"].astype(x.dtype), preferred_element_type=f32)
        bb = jnp.dot(x, p["wb"].astype(x.dtype), preferred_element_type=f32)
        beta = jax.nn.sigmoid(bb) * (2.0 if cfg.linear_allow_neg_eigval else 1.0)
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    with jax.named_scope("deltanet_conv"):
        conv = lambda a, w: jax.nn.silu(causal_conv(a, w, segment))
        q = conv(q, p["conv_q"]).reshape(b, t, -1, dk).astype(f32)
        k = conv(k, p["conv_k"]).reshape(b, t, -1, dk).astype(f32)
        v = conv(v, p["conv_v"]).reshape(b, t, -1, dv)
        unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True))
        q = (unit(q) / math.sqrt(dk)).astype(x.dtype)
        k = unit(k).astype(x.dtype)
    with jax.named_scope("deltanet_scan"):
        starts = jnp.concatenate(
            [jnp.ones((b, 1), bool), segment[:, 1:] != segment[:, :-1]], axis=1)
        o = chunk_gated_delta_rule(q, k, v, g, beta, starts)
    with jax.named_scope("deltanet_proj"):
        y = rms_norm(o, p["o_norm"], cfg.rms_norm_eps)
        y = y * jax.nn.silu(gate.reshape(b, t, -1, dv))
        return proj(made_once(y.reshape(b, t, -1)), p["wo"])


def _block(p, x, segment, kind: str, cfg: LmConfig, axis_name):
    eps = cfg.rms_norm_eps
    if kind == "full_attention":
        mixed = full_attention_mixer(p["mixer"], x, segment, cfg,
                                     axis_name=axis_name)
    else:
        mixed = gated_delta_mixer(p["mixer"], x, segment, cfg)
    h = made_once(x + rms_norm(mixed, p["mixer_norm"], eps))
    with jax.named_scope("mlp"):
        m = p["mlp"]
        gate = cotangent_once(proj(h, m["gate"]))
        up = cotangent_once(proj(h, m["up"]))
        y = cotangent_once(proj(jax.nn.silu(gate) * up, m["down"]))
        return h + rms_norm(y, p["mlp_norm"], eps)


def backbone(params, x, segment, cfg: LmConfig, *,
             axis_name: str | None = None):
    """``x`` [B, T, d] through every layer, each rematerialised in the
    backward pass.  A layer keeps its input and the outputs of its
    projections (matrix products with no batch dimension: about 0.6 GB a
    layer at 8k tokens and the published widths); everything else runs
    again.  Keeping the input alone read 472 against 442 ms a step at the
    SAME peak memory on the v5e (PERF.md, PR 31)."""
    keep = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    for i, kind in enumerate(cfg.layer_types):
        layer = jax.checkpoint(
            lambda p, x, kind=kind: _block(p, x, segment, kind, cfg, axis_name),
            policy=keep)
        x = layer(params[f"layer_{i}"], x)
    return x


def next_token_loss(params, x, token, segment, cfg: LmConfig):
    """Mean cross-entropy of token ``t + 1`` at position ``t`` where both
    lie in one document; ``(loss, labelled positions)``.  The [T, V] logits
    are float32 and rematerialised in the backward pass."""

    @jax.checkpoint
    def loss(final_norm, head, x):
        h = rms_norm(x, final_norm, cfg.rms_norm_eps)
        logits = cotangent_once(jnp.dot(h, head.astype(h.dtype),
                                         preferred_element_type=jnp.float32))
        logp = jax.nn.log_softmax(logits, axis=-1)
        nxt = jnp.concatenate([token[:, 1:], token[:, :1]], axis=1)
        labelled = jnp.concatenate(
            [segment[:, 1:] == segment[:, :-1],
             jnp.zeros((token.shape[0], 1), bool)], axis=1)
        picked = jnp.take_along_axis(logp, nxt[..., None], axis=-1)[..., 0]
        n = jnp.maximum(labelled.sum(), 1)
        return -jnp.where(labelled, picked, 0.0).sum() / n, n

    with jax.named_scope("lm_head_loss"):
        return loss(params["final_norm"], params["head"], x)


def forward_loss(params, embedded, token, segment, cfg: LmConfig, *,
                 dtype=jnp.float32, axis_name: str | None = None):
    """The training forward: gathered vectors ``embedded`` [B, T, d] ->
    scalar next-token loss."""
    with jax.named_scope("lm_embed"):
        x = embedded.astype(dtype)
    x = backbone(params, x, segment, cfg, axis_name=axis_name)
    return next_token_loss(params, x, token, segment, cfg)[0]
