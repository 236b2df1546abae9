"""Plain reference for the ``sdar_moe`` architecture on the training path:
block-diffusion forward and loss, gradients, AdamW and the table's
row-sparse Adam, in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.  The optimizers and the norm
are ``reference/olmo_hybrid.py``'s.  It takes nothing from the program: the
noise is the step's input as its batch is, drawn here from its definition
(:func:`noise`) with the step's key (:func:`step_key`), the same key the
program's step folds from its seed, so both draw the same random numbers
and the comparison holds how the program turns them into masks and levels.

Source: https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json
(``model_type: sdar_moe``: Qwen3-MoE's layers); the objective is SDAR's
(arXiv:2510.06303) in Block Diffusion's vectorised form (Arriola et al.,
ICLR 2025).  A sequence ``x_0`` of ``L`` tokens in packed documents; a
token's document ``s``, its position ``pos`` in the sequence and its block
``b = floor(position within its document / block_length)``:

  noise        a block draws t ~ U[eps, 1]; each of its tokens is the mask
               token in x_t with probability t
  stream       X = [x_t | x_0]: 2L positions through the layers; a token's
               noisy and clean copy share the rotary position ``pos``
  layer        h = x + W_o Attn(RMSNorm(x)); x' = h + MoE(RMSNorm(h))
  attention    q = RoPE(RMSNorm_head(x W_q)), k = RoPE(RMSNorm_head(x W_k)),
               v = x W_v (query head j reads key/value head j // (H/H_kv));
               RMSNorm_head over a head's 128 channels with one [128] weight;
               RoPE rotate-half, angle pos * theta^(-2m/128);
               softmax(q k^T / sqrt(128) + M) v, M = 0 where, in the SAME
               document: noisy q -> noisy k of the same block; noisy q ->
               clean k of an earlier block; clean q -> clean k of the same
               or an earlier block; -inf elsewhere
  experts      p = softmax(u W_r) over ALL published experts; S the k
               largest; w_e = p_e / sum_S p; out = sum over e in S and HELD
               of w_e (silu(u W_g,e) * (u W_u,e)) W_d,e
  head         final RMSNorm, untied head over the vocabulary slice, on the
               noisy half only; loss = (1/N) sum over masked positions i of
               CE(logits_i, x_0[i]) / t_i, N = the batch's B L tokens

The share (``m``: experts HELD, the first held expert's index) is the chip's
of a stated deployment; what the absent experts would add is left out, here
as in the program.  Attention is one masked softmax a block of queries at a
time, the mask built from the rule above; the expert layer is a loop over
the held experts, each over every position.  Training is written out a layer
at a time (``run_steps``), as ``reference/olmo_hybrid.py`` does.

``fault`` (tests and the builder's readings; never the timed path):
``"leak_own_block"`` lets a noisy block see its own clean block (the label
leak the objective exists to prevent), ``"causal_in_block"`` makes the noisy
tokens causal within their block, ``"no_t_weight"`` drops the 1/t weight,
``"shifted_noisy_positions"`` gives the noisy half rotary positions L..2L-1,
``"no_topk_norm"`` leaves the chosen weights unnormalised, ``"drop_tokens"``
gives every held expert a capacity of the mean load and zeroes what
overflows, ``"bf16_params"`` keeps the dense parameters in bfloat16 between
steps."""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.reference.olmo_hybrid import (
    F32, _flat, _norm, adamw, rms_norm, silu, sparse_adam)

FAULTS = (None, "leak_own_block", "causal_in_block", "no_t_weight",
          "shifted_noisy_positions", "no_topk_norm", "drop_tokens",
          "bf16_params")


def step_key(seed: int, n: int):
    """The key of training step ``n`` (from 1) of a ``Trainer`` built with
    ``seed``: ``fold_in(key(seed + 1), n - 1)`` (``Trainer._dropout_rng``,
    folded with the state's step count by ``make_sparse_train_step``)."""
    return jax.random.fold_in(jax.random.key(seed + 1), n - 1)


def blocks(segment, block_length: int):
    """A token's block within its document, one sequence ``segment`` [L]
    (documents packed in order: a document starts at its first index)."""
    first = jnp.searchsorted(segment, segment, side="left")
    return (jnp.arange(segment.shape[0]) - first) // block_length


def noise(key, segment, block_length: int, eps: float):
    """``(masked, t)`` of [B, L]: the noise of the module docstring from
    ``key``.  Its two halves draw one uniform [B, L] each: the first, in
    ``[eps, 1)``, holds block ``n``'s level at index ``n`` (a sequence's
    blocks counted in order from 0); the second masks a token where it is
    below the token's level."""
    k_level, k_mask = jax.random.split(key)
    level = jax.random.uniform(k_level, segment.shape, F32, eps, 1.0)
    blk = jax.vmap(lambda s: blocks(s, block_length))(segment)
    # a (document, block) pair begins where either changes
    begins = jnp.concatenate(
        [jnp.ones_like(segment[:, :1], bool),
         (segment[:, 1:] != segment[:, :-1]) | (blk[:, 1:] != blk[:, :-1])],
        axis=1)
    ordinal = jnp.cumsum(begins, axis=1) - 1
    t = jnp.take_along_axis(level, ordinal, axis=1)
    return jax.random.uniform(k_mask, segment.shape, F32) < t, t


def noisy_batch(token, segment, key, m, fault=None):
    """``(ids [B, 2L], weight [B, L], masked [B, L])``: the stream's ids
    and the loss's weight, ``1 / t`` at a masked position."""
    masked, t = noise(key, segment, m["block_length"], m["noise_eps"])
    x_t = jnp.where(masked, m["mask_token_id"], token)
    w = jnp.ones_like(t) if fault == "no_t_weight" else 1.0 / t
    return (jnp.concatenate([x_t, token], axis=1),
            jnp.where(masked, w, 0.0), masked)


# ------------------------------------------------------------------ layers


def rope(x, pos, theta):
    """``x`` [N, H, dh] at positions ``pos`` [N]."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def visible(i, j, noisy_i, noisy_j, blk_i, blk_j, fault):
    """The rule of the module docstring, for query ``i`` and key ``j``
    (stream indices, broadcast against each other); documents apart."""
    own_clean = (blk_j <= blk_i) if fault == "leak_own_block" else (blk_j < blk_i)
    own_noisy = blk_j == blk_i
    if fault == "causal_in_block":
        own_noisy = own_noisy & (j <= i)
    return jnp.where(noisy_i, jnp.where(noisy_j, own_noisy, own_clean),
                     ~noisy_j & (blk_j <= blk_i))


def attention(p, u, segment, m, fault):
    """One stream ``u`` [2L, d] (normed) through the attention."""
    hq, hkv, dh = m["attention_heads"], m["key_value_heads"], m["head_dim"]
    n = u.shape[0]
    length = n // 2
    pos = jnp.arange(length)
    shift = length if fault == "shifted_noisy_positions" else 0
    pos = jnp.concatenate([pos + shift, pos])
    eps = m["rms_norm_eps"]
    q = rope(rms_norm((u @ p["wq"]).reshape(n, hq, dh), p["q_norm"], eps),
             pos, m["rope_theta"])
    k = rope(rms_norm((u @ p["wk"]).reshape(n, hkv, dh), p["k_norm"], eps),
             pos, m["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat((u @ p["wv"]).reshape(n, hkv, dh), hq // hkv, axis=1)
    seg = jnp.concatenate([segment, segment])
    blk = jnp.tile(blocks(segment, m["block_length"]), 2)
    idx = jnp.arange(n)
    noisy = idx < length
    bq = min(m.get("query_block", 512), n)
    pad = -n % bq

    @jax.checkpoint
    def rows(xs):
        q_b, i_b = xs
        logits = jnp.einsum("qhd,khd->hqk", q_b, k) / math.sqrt(dh)
        i, j = i_b[:, None], idx[None, :]
        ok = (seg[j] == seg[i]) & visible(i, j, noisy[i], noisy[j], blk[i],
                                          blk[j], fault)
        probs = jax.nn.softmax(jnp.where(ok[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    tiles = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                              mode="edge").reshape(-1, bq, *a.shape[1:])
    out = jax.lax.map(rows, (tiles(q), tiles(idx)))
    return out.reshape(-1, hq * dh)[:n] @ p["wo"]


def routing(p, u, m, fault):
    """``(weights, chosen)`` of [N, E_held]: the held experts' columns of
    the router's weights over all published experts."""
    k, lo, e = m["num_experts_per_tok"], m["first_expert_held"], m["experts"]
    s = jax.nn.softmax(u @ p["router"], axis=-1)
    _, at = jax.lax.top_k(s, k)
    chosen = jnp.zeros(s.shape, bool).at[jnp.arange(s.shape[0])[:, None], at].set(True)
    w = jnp.where(chosen, s, 0.0)
    if m["norm_topk_prob"] and fault != "no_topk_norm":
        w = w / w.sum(axis=-1, keepdims=True)
    chosen, w = chosen[:, lo:lo + e], w[:, lo:lo + e]
    if fault == "drop_tokens":
        cap = int(s.shape[0] * k / m["num_experts"])
        w = jnp.where(jnp.cumsum(chosen, axis=0) <= cap, w, 0.0)
    return w, chosen


def experts(p, u, m, fault):
    """One stream through the mixture: a loop over the held experts, each
    over every position; ``w1`` holds an expert's gate and up side by
    side."""
    w, _ = routing(p, u, m, fault)
    f = p["w2"].shape[1]
    out = jnp.zeros_like(u)
    for e in range(m["experts"]):
        h = u @ p["w1"][e]
        out = out + w[:, e:e + 1] * ((silu(h[:, :f]) * h[:, f:]) @ p["w2"][e])
    return out


def block(p, x, segment, m, fault, *, loads: bool = False):
    """One layer; with ``loads`` also each held expert's pairs [E_held]."""
    eps = m["rms_norm_eps"]
    h = x + attention(p["attn"], rms_norm(x, p["attn_norm"], eps), segment,
                      m, fault)
    u = rms_norm(h, p["moe_norm"], eps)
    out = h + experts(p["moe"], u, m, fault)
    return (out, routing(p["moe"], u, m, None)[1].sum(axis=0)) if loads else out


def head_loss(p, x, token, weight, m):
    """``x`` [B, L, d], the noisy half's outputs; ``(loss, masked)``."""
    logits = rms_norm(x, p["final_norm"], m["rms_norm_eps"]) @ p["head"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, token[..., None], axis=-1)[..., 0]
    return -(weight * picked).sum() / weight.size, (weight > 0).sum()


# -------------------------------------------------------- whole model, plain


def layer_names(m) -> list[str]:
    return [f"layer_{i}" for i in range(m["layers"])]


def forward_loss(dense, table, token, segment, key, m, fault=None):
    """The whole model in one expression (tests at small sizes; ``run_steps``
    is the same mathematics a layer at a time)."""
    assert fault in FAULTS
    ids, weight, _ = noisy_batch(token, segment, key, m, fault)
    x = table[ids]
    for name in layer_names(m):
        x = jax.vmap(lambda xs, ss, p=dense[name]: block(
            p, xs, ss, m, fault))(x, segment)
    return head_loss(dense, x[:, :token.shape[1]], token, weight, m)[0]


# ------------------------------------------------------- a layer at a time


def run_steps(m: dict, optim: dict, dense0, table0, feed: list[dict], *,
              seed: int, fault=None) -> dict:
    """``len(feed)`` training steps from ``dense0`` (a mapping ``{top-level
    name: parameter tree}``, read once at the start and once more at the end)
    and ``table0`` [V, d].  ``feed``: host batches ``{"token", "segment"}``
    of [B, L] int32; step ``n``'s noise from :func:`step_key` ``(seed, n)``.

    Returns ``losses``, ``grad_norm`` and ``update_norm`` as
    ``reference/olmo_hybrid.run_steps`` does, ``bd_masked`` (a step: the
    masked positions), and ``moe_pairs`` / ``moe_load_max`` /
    ``moe_layer_loads`` as ``reference/nemotron_h.run_steps`` does."""
    assert fault in FAULTS
    tops = layer_names(m) + ["final_norm", "head"]
    od, os_ = optim["dense"], optim["sparse"]
    zeros = lambda tree: jax.tree.map(jnp.zeros_like, tree)

    def update(p, g_p, mu, nu, step):
        """AdamW over one subtree: ``(parameters, mu, nu)``."""
        new = jax.tree.map(lambda a, b, c, d: adamw(
            a, b, c, d, step, od, bf16_params=fault == "bf16_params"),
            p, g_p, mu, nu)
        return tuple(jax.tree.map(lambda t, i=i: t[i], new,
                                  is_leaf=lambda t: isinstance(t, tuple))
                     for i in range(3))

    @jax.jit
    def noise(token, segment, key):
        return noisy_batch(token, segment, key, m, fault)

    @jax.jit
    def fwd(p, x, segment):   # (x', the held experts' pairs over the batch)
        out, load = jax.vmap(lambda xs, ss: block(
            p, xs, ss, m, fault, loads=True))(x, segment)
        return out, load.sum(axis=0)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def bwd_update(p, mu, nu, x, segment, g_out, step):
        _, vjp = jax.vjp(lambda p, x: jax.vmap(
            lambda xs, ss: block(p, xs, ss, m, fault))(x, segment), p, x)
        g_p, g_x = vjp(g_out)
        norms = jax.tree.map(_norm, g_p)
        return *update(p, g_p, mu, nu, step), g_x, norms

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def head_update(p, mu, nu, x, token, weight, step):
        length = token.shape[1]
        (loss, _), (g_p, g_h) = jax.value_and_grad(
            lambda p, h: head_loss(p, h, token, weight, m), argnums=(0, 1),
            has_aux=True)(p, x[:, :length])
        g_x = jnp.concatenate([g_h, jnp.zeros_like(g_h)], axis=1)
        norms = jax.tree.map(_norm, g_p)
        return *update(p, g_p, mu, nu, step), g_x, norms, loss

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def table_update(table, mu, nu, ids, g_x, step):
        *new, g_sum = sparse_adam(table, mu, nu, ids.reshape(-1),
                                  g_x.reshape(-1, g_x.shape[-1]), step, os_)
        return (*new, _norm(g_sum))

    with jax.default_matmul_precision("highest"):
        params = {k: jax.tree.map(lambda a: jnp.array(a, F32), dense0[k])
                  for k in tops}
        mus, nus = zeros(params), zeros(params)
        table = jnp.array(table0, F32)
        t_mu, t_nu = jnp.zeros_like(table), jnp.zeros_like(table)
        head_of = lambda tree: {"final_norm": tree["final_norm"],
                                "head": tree["head"]}
        losses, grad_norm, masked_n = [], {}, []
        pairs, load_max, layer_loads = [], [], []
        for n, batch in enumerate(feed, 1):
            token = jnp.asarray(batch["token"], jnp.int32)
            segment = jnp.asarray(batch["segment"], jnp.int32)
            step = jnp.asarray(n, F32)
            ids, weight, masked = noise(token, segment, step_key(seed, n))
            masked_n.append(masked.sum())
            xs, routed = [table[ids]], []
            for name in layer_names(m):
                x, load = fwd(params[name], xs[-1], segment)
                xs.append(x)
                routed.append(load)
            layer_loads.append(routed)
            pairs.append(sum(r.sum() for r in routed))
            load_max.append(max(r.max() for r in routed))
            hp, hm, hn, g_x, norms, loss = head_update(
                head_of(params), head_of(mus), head_of(nus), xs.pop(), token,
                weight, step)
            for k in hp:
                params[k], mus[k], nus[k] = hp[k], hm[k], hn[k]
            found = dict(norms)
            losses.append(loss)
            for name in reversed(layer_names(m)):
                params[name], mus[name], nus[name], g_x, norms = bwd_update(
                    params[name], mus[name], nus[name], xs.pop(), segment, g_x,
                    step)
                found[name] = norms
            table, t_mu, t_nu, g_table = table_update(
                table, t_mu, t_nu, ids, g_x, step)
            if n == 1:
                grad_norm = {f"dense:{k}": v for k, v in _flat(found).items()}
                grad_norm["table:token"] = g_table
        update_norm = {}
        for k in tops:
            diff = jax.tree.map(lambda a, b: _norm(a - jnp.asarray(b, F32)),
                                params[k], dense0[k])
            update_norm.update({f"dense:{p}": v
                                for p, v in _flat({k: diff}).items()})
            params[k] = None
        update_norm["table:token"] = _norm(table - jnp.asarray(table0, F32))
    host = jax.device_get(dict(losses=losses, grad_norm=grad_norm,
                               update_norm=update_norm, masked=masked_n,
                               pairs=pairs, load_max=load_max,
                               layer_loads=layer_loads))
    return {"losses": [float(x) for x in host["losses"]],
            "grad_norm": {k: float(v) for k, v in host["grad_norm"].items()},
            "update_norm": {k: float(v) for k, v in host["update_norm"].items()},
            "bd_masked": [int(x) for x in host["masked"]],
            "moe_pairs": [int(x) for x in host["pairs"]],
            "moe_load_max": [int(x) for x in host["load_max"]],
            "moe_layer_loads": [[[int(x) for x in layer] for layer in step]
                                for step in host["layer_loads"]]}
