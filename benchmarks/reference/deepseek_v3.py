"""Plain reference for the ``deepseek_v3`` architecture on the training path:
forward, next-token loss, gradients, AdamW and the table's row-sparse Adam,
in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.  It imports nothing from the
program; the optimizers, the norm and the head's loss are
``reference/olmo_hybrid.py``'s.

Source: https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json
(``model_type: deepseek_v3``), the layers as the published
``modeling_deepseek.py`` writes them (DeepSeek-V2 / V3, arXiv:2405.04434,
2412.19437), with ``q_lora_rank`` null.  Every layer is ``h = x +
MLA(RMSNorm(x))``, ``x' = h + FFN(RMSNorm(h))``; ``u`` is the normed input,
per token; no bias anywhere:

  MLA          q = u W_q, a head [q_nope (n) | q_pe (r)]; [c_kv | k_pe] =
               u W_kv_a (widths kv_lora_rank | r); c_kv = RMSNorm(c_kv)
               (``kv_a_layernorm``: eps 1e-6, the class default, not the
               config's); [k_nope (n) | v (dv)] = c_kv W_kv_b, a head;
               k_pe is ONE head, [T, 1, r], which every head reads;
               RoPE (``apply_rotary_pos_emb``): x.view(r/2, 2).transpose
               then x cos + rotate_half(x) sin with
               cos, sin of pos * theta^(-2m/r) repeated [f | f];
               q = [q_nope | q_pe], k = [k_nope | k_pe] (n + r wide);
               softmax(q k^T / sqrt(n + r)) over the keys of the same
               document at positions <= t; out = attn v (dv a head); W_o
  dense MLP    the first ``first_k_dense_replace`` layers:
               (silu(u W_g) * u W_u) W_d, width ``intermediate_size``
  experts      the later layers (``moe_layer_freq`` 1), ``MoEGate`` with
               ``scoring_func`` sigmoid and ``topk_method`` noaux_tc at
               n_group = topk_group = 1 (the one group holds every expert,
               so the group step selects it whole): s = sigmoid(u W_r) over
               ALL published experts; the k largest of s + b are chosen (b
               the ``e_score_correction_bias``: a buffer); w_e = s_e / (sum
               of the chosen s + 1e-20) * routed_scaling_factor;
               out = sum over the chosen AND HELD e of
               w_e (silu(u W_g,e) * u W_u,e) W_d,e + shared(u), the shared
               experts one SwiGLU MLP of n_shared_experts *
               moe_intermediate_size
  head         final RMSNorm, untied head over the vocabulary slice;
               mean next-token cross-entropy within documents

Departures from the published code, as the configuration's ``assumed``
states them: positions are a token's place in its document (the packed
sequence's documents are apart); no auxiliary loss (``seq_aux`` is true but
the config gives no ``aux_loss_alpha``, and the noaux_tc gate computes
none); the selection bias is a buffer that no step updates; no dropout.

The share (``m``: experts HELD, the first held expert's index) is the chip's
of a stated deployment; what the absent experts would add is left out, here
as in the program.  Attention is one masked softmax a block of queries at a
time (so that 8,192 positions fit the chip); the expert layer is a loop over
the held experts, each over every token, weighted by ``w_e``.  Training is
written out a layer at a time (``run_steps``), as ``reference/
olmo_hybrid.py`` does.

``fault`` (tests and readings by hand; never the timed path):
``"rope_rotate_half"`` rotates pairs (m, m + r/2) without the pair
transpose, ``"no_kv_norm"`` leaves the latent unnormed, ``"scale_v"``
scales the logits by 1/sqrt(dv) in place of 1/sqrt(n + r), ``"k_pe_per_head"``
lays the shared key out head-major and reads it token-major (head h of
token i reads the rotary key of token (H i + h) mod T, no longer one key
shared by the heads of a token), ``"no_routed_scaling"`` leaves
``routed_scaling_factor`` out, ``"softmax_scoring"`` scores the experts by
a softmax in place of the sigmoid, ``"bf16_params"`` keeps the dense
parameters in bfloat16 between steps."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.reference.olmo_hybrid import (
    F32, _flat, _norm, adamw, head_loss, rms_norm, silu, sparse_adam)

FAULTS = (None, "rope_rotate_half", "no_kv_norm", "scale_v", "k_pe_per_head",
          "no_routed_scaling", "softmax_scoring", "bf16_params")
BUFFERS = ("router_bias",)
LATENT_NORM_EPS = 1e-6


# ------------------------------------------------------------------ layers


def positions(segment):
    """A token's place in its document, one sequence ``segment`` [T]
    (documents packed in order: a document starts at its first index)."""
    return jnp.arange(segment.shape[0]) - jnp.searchsorted(
        segment, segment, side="left")


def rotate_half(x):
    d = x.shape[-1]
    return jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)


def apply_rotary(x, pos, theta, fault=None):
    """``apply_rotary_pos_emb`` on ``x`` [T, H, r] at ``pos`` [T]."""
    t, h, r = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=F32) / r)
    freqs = jnp.outer(pos.astype(F32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    if fault != "rope_rotate_half":
        x = jnp.swapaxes(x.reshape(t, h, r // 2, 2), -1, -2).reshape(t, h, r)
    return x * cos + rotate_half(x) * sin


def attention(p, u, segment, m, fault):
    """One sequence through MLA, causal within documents: one masked
    softmax, a block of queries at a time."""
    h, n, r, dv = m["heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], \
        m["v_head_dim"]
    t = u.shape[0]
    pos = positions(segment)
    q = (u @ p["wq"]).reshape(t, h, n + r)
    q_nope, q_pe = q[..., :n], q[..., n:]
    compressed = u @ p["wkv_a"]
    c_kv, k_pe = compressed[:, :m["kv_lora_rank"]], compressed[:, m["kv_lora_rank"]:]
    k_pe = k_pe.reshape(t, 1, r)
    if fault != "no_kv_norm":
        c_kv = rms_norm(c_kv, p["kv_norm"], LATENT_NORM_EPS)
    kv = (c_kv @ p["wkv_b"]).reshape(t, h, n + dv)
    k_nope, v = kv[..., :n], kv[..., n:]
    q_pe = apply_rotary(q_pe, pos, m["rope_theta"], fault)
    k_pe = apply_rotary(k_pe, pos, m["rope_theta"], fault)
    if fault == "k_pe_per_head":
        k_pe = jnp.broadcast_to(k_pe[None, :, 0], (h, t, r)).reshape(t, h, r)
    else:
        k_pe = jnp.broadcast_to(k_pe, (t, h, r))
    query = jnp.concatenate([q_nope, q_pe], axis=-1)
    key = jnp.concatenate([k_nope, k_pe], axis=-1)
    scale = (dv if fault == "scale_v" else n + r) ** -0.5
    bq = min(m.get("query_block", 512), t)
    pad = -t % bq
    at = jnp.arange(t)

    @jax.checkpoint
    def rows(xs):
        q_b, pos_b, seg_b = xs
        logits = jnp.einsum("qhd,khd->hqk", q_b, key) * scale
        ok = (at[None, :] <= pos_b[:, None]) & (segment[None, :] == seg_b[:, None])
        probs = jax.nn.softmax(jnp.where(ok[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    blocks = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                               mode="edge").reshape(-1, bq, *a.shape[1:])
    out = jax.lax.map(rows, (blocks(query), blocks(at), blocks(segment)))
    return out.reshape(-1, h * dv)[:t] @ p["wo"]


def mlp(p, u):
    return (silu(u @ p["gate"]) * (u @ p["up"])) @ p["down"]


def routing(p, u, m, fault):
    """``(weights, chosen)`` of [T, E_held]: the held experts' columns of
    the gate's weights over all published experts."""
    k, lo, e = m["num_experts_per_tok"], m["first_expert_held"], m["experts"]
    logits = u @ p["router"]
    scores = (jax.nn.softmax(logits, axis=-1) if fault == "softmax_scoring"
              else jax.nn.sigmoid(logits))
    _, at = jax.lax.top_k(scores + p["router_bias"], k)
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], at].set(True)
    w = jnp.where(chosen, scores, 0.0)
    if m["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    if fault != "no_routed_scaling":
        w = w * m["routed_scaling_factor"]
    return w[:, lo:lo + e], chosen[:, lo:lo + e]


def experts(p, u, m, fault):
    """One sequence through the routed experts held here and the shared
    experts: a loop over the held experts, each over every token."""
    w, _ = routing(p, u, m, fault)
    f = p["w2"].shape[1]
    out = jnp.zeros_like(u)
    for e in range(m["experts"]):
        hidden = u @ p["w1"][e]
        out = out + w[:, e:e + 1] * ((silu(hidden[:, :f]) * hidden[:, f:])
                                     @ p["w2"][e])
    return out + mlp(p["shared"], u) if "shared" in p else out


def dense(m, name: str) -> bool:
    return int(name.split("_")[1]) < m["first_k_dense_replace"]


def block(p, x, segment, is_dense, m, fault, *, loads: bool = False):
    """One layer; with ``loads`` also each held expert's pairs [E_held]
    (none for a dense layer)."""
    eps = m["rms_norm_eps"]
    h = x + attention(p["attn"], rms_norm(x, p["attn_norm"], eps), segment,
                      m, fault)
    u = rms_norm(h, p["mlp_norm"], eps)
    if is_dense:
        out = h + mlp(p["mlp"], u)
        return (out, jnp.zeros((0,), jnp.int32)) if loads else out
    out = h + experts(p["mlp"], u, m, fault)
    return (out, routing(p["mlp"], u, m, None)[1].sum(axis=0)) if loads else out


# -------------------------------------------------------- whole model, plain


def layer_names(m) -> list[str]:
    return [f"layer_{i}" for i in range(m["layers"])]


def forward_loss(dense_params, table, token, segment, m, fault=None):
    """The whole model in one expression (tests at small sizes; ``run_steps``
    is the same mathematics a layer at a time)."""
    assert fault in FAULTS
    x = table[token]
    for name in layer_names(m):
        x = jax.vmap(lambda xs, ss, p=dense_params[name], d=dense(m, name):
                     block(p, xs, ss, d, m, fault))(x, segment)
    return head_loss(dense_params, x, token, segment, m)[0]


def admitted_pairs(segment):
    """The (query, key) pairs the mask admits in ``segment`` [B, T], one
    layer: every pair counted."""
    at = jnp.arange(segment.shape[-1])
    ok = (at[None, :] <= at[:, None]) & (segment[:, None, :] == segment[:, :, None])
    return ok.sum()


# ------------------------------------------------------- a layer at a time


def run_steps(m: dict, optim: dict, dense0, table0, feed: list[dict], *,
              fault=None) -> dict:
    """``len(feed)`` training steps from ``dense0`` (a mapping ``{top-level
    name: parameter tree}``, read once at the start and once more at the end)
    and ``table0`` [V, d].  ``feed``: host batches ``{"token", "segment"}``
    of [B, T] int32.

    Returns ``losses``, ``grad_norm`` and ``update_norm`` as
    ``reference/olmo_hybrid.run_steps`` does, and ``moe_pairs`` /
    ``moe_load_max`` / ``moe_layer_loads`` as ``reference/nemotron_h.
    run_steps`` does."""
    assert fault in FAULTS
    tops = layer_names(m) + ["final_norm", "head"]
    od, os_ = optim["dense"], optim["sparse"]
    still = {**od, "weight_decay": 0.0}      # a buffer: no decay
    zeros = lambda tree: jax.tree.map(jnp.zeros_like, tree)

    def update(p, g_p, mu, nu, step):
        """AdamW over one subtree: ``(parameters, mu, nu)``."""
        def leaf(path, a, b, c, d):
            o = still if path[-1].key in BUFFERS else od
            return adamw(a, b, c, d, step, o, bf16_params=fault == "bf16_params")

        new = jax.tree_util.tree_map_with_path(leaf, p, g_p, mu, nu)
        return tuple(jax.tree.map(lambda t, i=i: t[i], new,
                                  is_leaf=lambda t: isinstance(t, tuple))
                     for i in range(3))

    @partial(jax.jit, static_argnames=("is_dense",))
    def fwd(p, x, segment, is_dense):   # (x', the held experts' pairs)
        out, load = jax.vmap(lambda xs, ss: block(
            p, xs, ss, is_dense, m, fault, loads=True))(x, segment)
        return out, load.sum(axis=0)

    @partial(jax.jit, static_argnames=("is_dense",), donate_argnums=(0, 1, 2))
    def bwd_update(p, mu, nu, x, segment, g_out, step, is_dense):
        _, vjp = jax.vjp(lambda p, x: jax.vmap(lambda xs, ss: block(
            p, xs, ss, is_dense, m, fault))(x, segment), p, x)
        g_p, g_x = vjp(g_out)
        norms = jax.tree.map(_norm, g_p)
        return *update(p, g_p, mu, nu, step), g_x, norms

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def head_update(p, mu, nu, x, token, segment, step):
        (loss, _), (g_p, g_x) = jax.value_and_grad(
            lambda p, x: head_loss(p, x, token, segment, m), argnums=(0, 1),
            has_aux=True)(p, x)
        norms = jax.tree.map(_norm, g_p)
        return *update(p, g_p, mu, nu, step), g_x, norms, loss

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def table_update(table, mu, nu, token, g_x, step):
        *new, g_sum = sparse_adam(table, mu, nu, token.reshape(-1),
                                  g_x.reshape(-1, g_x.shape[-1]), step, os_)
        return (*new, _norm(g_sum))

    with jax.default_matmul_precision("highest"):
        # copies: the updates below donate what they are given
        params = {k: jax.tree.map(lambda a: jnp.array(a, F32), dense0[k])
                  for k in tops}
        mus, nus = zeros(params), zeros(params)
        table = jnp.array(table0, F32)
        t_mu, t_nu = jnp.zeros_like(table), jnp.zeros_like(table)
        head_of = lambda tree: {"final_norm": tree["final_norm"],
                                "head": tree["head"]}
        losses, grad_norm, pairs, load_max, layer_loads = [], {}, [], [], []
        for n, batch in enumerate(feed, 1):
            token = jnp.asarray(batch["token"], jnp.int32)
            segment = jnp.asarray(batch["segment"], jnp.int32)
            step = jnp.asarray(n, F32)
            xs, routed = [table[token]], []
            for name in layer_names(m):
                x, load = fwd(params[name], xs[-1], segment,
                              is_dense=dense(m, name))
                xs.append(x)
                if not dense(m, name):
                    routed.append(load)
            layer_loads.append(routed)
            pairs.append(sum(r.sum() for r in routed) if routed else 0)
            load_max.append(max(r.max() for r in routed) if routed else 0)
            hp, hm, hn, g_x, norms, loss = head_update(
                head_of(params), head_of(mus), head_of(nus), xs.pop(), token,
                segment, step)
            for k in hp:
                params[k], mus[k], nus[k] = hp[k], hm[k], hn[k]
            found = dict(norms)
            losses.append(loss)
            for name in reversed(layer_names(m)):
                params[name], mus[name], nus[name], g_x, norms = bwd_update(
                    params[name], mus[name], nus[name], xs.pop(), segment, g_x,
                    step, is_dense=dense(m, name))
                found[name] = norms
            table, t_mu, t_nu, g_table = table_update(
                table, t_mu, t_nu, token, g_x, step)
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
                               update_norm=update_norm, pairs=pairs,
                               load_max=load_max, layer_loads=layer_loads))
    return {"losses": [float(x) for x in host["losses"]],
            "grad_norm": {k: float(v) for k, v in host["grad_norm"].items()},
            "update_norm": {k: float(v) for k, v in host["update_norm"].items()},
            "moe_pairs": [int(x) for x in host["pairs"]],
            "moe_load_max": [int(x) for x in host["load_max"]],
            "moe_layer_loads": [[[int(x) for x in layer] for layer in step]
                                for step in host["layer_loads"]]}
