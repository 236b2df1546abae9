"""Plain reference for the ``nemotron_h`` architecture on the training path:
forward, next-token loss, gradients, AdamW and the table's row-sparse Adam,
in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.  It imports nothing from the
program; the optimizers, the norm, the convolution and the head's loss are
``reference/olmo_hybrid.py``'s.

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json
(``model_type: nemotron_h``), the layers as the published
``modeling_nemotron_h.py`` writes them.  Every layer is ``x <- x +
Part(RMSNorm(x))`` with ``Part`` by the layer's letter; ``u`` is the normed
input, per token; no bias but the convolution's:

  M  Mamba-2       [z | xBC | dt] = u W_in, widths H P | H P + 2 G N | H;
                   xBC = silu(conv(xBC) + b_conv), depthwise causal, taps
                   before a document's start are zero; [xs | B | C] = xBC,
                   xs [H, P], B, C [G, N], head i reads group i // (H / G);
                   dt = softplus(dt + dt_bias), A = -exp(A_log);
                     S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T
                   (S is P x N, zero at a document's first token);
                     y_t = S_t C_t + D xs_t
                   y = GroupRMSNorm(y * silu(z)) over groups of H P / G
                   channels, with a weight; W_out
  *  attention     q = u W_q (heads of ``head_dim``), k, v = u W_k, u W_v
                   (fewer heads: query head i reads key/value head
                   i // (H_q / H_kv)); softmax(q k^T / sqrt(head_dim)) over
                   the keys of the same document at positions <= t; W_o.  No
                   rotary embedding, no q/k norm
  E  experts       s = sigmoid(u W_r) over ALL published experts; the k
                   largest of s + b are chosen (b a buffer); w_e = scale *
                   s_e / (sum of the chosen s + 1e-20); l = u W_down;
                   routed = sum over the chosen AND HELD e of
                   w_e relu(l W1_e)^2 W2_e;
                   out = routed W_up + relu(u Ws1)^2 Ws2
  head             final RMSNorm, untied head over the vocabulary slice;
                   mean next-token cross-entropy within documents

The share (``m``: heads, groups and experts HELD, the first held expert's
index) is the chip's of a stated deployment; what the absent heads and
experts would add is left out, here as in the program.

The state-space layer is the literal per-token recurrence in a ``lax.scan``
(its backward pass rematerialises a block of tokens at a time); attention is
one masked softmax a block of queries at a time; the expert layer is a loop
over the held experts, each over every token, weighted by ``w_e``.  Training
is written out a layer at a time (``run_steps``), as ``reference/
olmo_hybrid.py`` does.

``fault`` (tests and the builder's readings; never the timed path):
``"drop_tokens"`` gives every held expert a capacity of the mean load ``T k
/ E`` and zeroes what overflows, ``"no_topk_norm"`` leaves the chosen
weights unnormalised, ``"no_resets"`` switches the document resets off
(state, convolution taps, attention mask), ``"bf16_state"`` keeps the SSM
state in bfloat16 between tokens, ``"bf16_params"`` keeps the dense
parameters in bfloat16 between steps."""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.reference.olmo_hybrid import (
    F32, _flat, _norm, adamw, causal_conv, head_loss, rms_norm, silu,
    sparse_adam)

FAULTS = (None, "drop_tokens", "no_topk_norm", "no_resets", "bf16_state",
          "bf16_params")
BUFFERS = ("router_bias",)


# ------------------------------------------------------------------ layers


def ssm(xs, dt, a, b_mat, c_mat, starts, *, block: int, bf16_state: bool):
    """The recurrence, token by token.  ``xs`` [T, H, P]; ``dt`` [T, H];
    ``a`` [H]; ``b_mat``, ``c_mat`` [T, H, N] (already a head's group's);
    ``starts`` [T] bool.  ``[T, H, P]``."""
    t, h, p = xs.shape
    n = b_mat.shape[-1]
    pad = -t % block
    if pad:  # dt = 0: the state stands still; rows cut off below
        widen = lambda v: jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
        xs, dt, b_mat, c_mat, starts = map(widen, (xs, dt, b_mat, c_mat, starts))

    def token(state, row):
        x_t, dt_t, b_t, c_t, start = row
        state = jnp.where(start, 0.0, state)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if bf16_state:  # reduce_precision: XLA drops a convert pair
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    @jax.checkpoint
    def tokens(state, rows):
        return jax.lax.scan(token, state, rows)

    rows = tuple(v.reshape(-1, block, *v.shape[1:])
                 for v in (xs, dt, b_mat, c_mat, starts))
    _, y = jax.lax.scan(tokens, jnp.zeros((h, p, n), F32), rows)
    return y.reshape(-1, h, p)[:t]


def mamba2(p, u, segment, m, fault):
    """One sequence ``u`` [T, d] (normed) through the Mamba-2 mixer."""
    h, hp, n, g = m["mamba_heads"], m["mamba_head_dim"], m["ssm_state_size"], m["mamba_groups"]
    t, inner = u.shape[0], h * hp
    seg = jnp.zeros_like(segment) if fault == "no_resets" else segment
    zxbcdt = u @ p["in_proj"]
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:-h], zxbcdt[:, -h:]
    xbc = silu(causal_conv(xbc, p["conv_w"], seg) + p["conv_bias"])
    xs = xbc[:, :inner].reshape(t, h, hp)
    of_head = lambda v: jnp.repeat(v.reshape(t, g, n), h // g, axis=1)
    b_mat = of_head(xbc[:, inner:inner + g * n])
    c_mat = of_head(xbc[:, inner + g * n:])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    starts = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    y = ssm(xs, dt, -jnp.exp(p["A_log"]), b_mat, c_mat, starts,
            block=m.get("token_block", 128), bf16_state=fault == "bf16_state")
    y = y + p["D"][:, None] * xs
    y = (y.reshape(t, inner) * silu(z)).reshape(t, g, inner // g)
    y = rms_norm(y, p["gate_norm"].reshape(g, -1), m["rms_norm_eps"])
    return y.reshape(t, inner) @ p["out_proj"]


def attention(p, u, segment, m, fault):
    """One sequence through grouped-query causal attention within
    documents: one masked softmax, a block of queries at a time."""
    hq, hkv, dh = m["attention_heads"], m["key_value_heads"], m["head_dim"]
    t = u.shape[0]
    q = (u @ p["wq"]).reshape(t, hq, dh)
    k = jnp.repeat((u @ p["wk"]).reshape(t, hkv, dh), hq // hkv, axis=1)
    v = jnp.repeat((u @ p["wv"]).reshape(t, hkv, dh), hq // hkv, axis=1)
    bq = min(m.get("query_block", 512), t)
    pad = -t % bq
    pos = jnp.arange(t)

    @jax.checkpoint
    def rows(xs):
        q_b, pos_b, seg_b = xs
        logits = jnp.einsum("qhd,khd->hqk", q_b, k) / math.sqrt(dh)
        ok = pos[None, :] <= pos_b[:, None]
        if fault != "no_resets":
            ok = ok & (segment[None, :] == seg_b[:, None])
        probs = jax.nn.softmax(jnp.where(ok[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    blocks = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                               mode="edge").reshape(-1, bq, *a.shape[1:])
    out = jax.lax.map(rows, (blocks(q), blocks(pos), blocks(segment)))
    return out.reshape(-1, hq * dh)[:t] @ p["wo"]


def routing(p, u, m, fault):
    """``(weights, chosen)`` of [T, E_held]: the held experts' columns of
    the router's weights over all published experts."""
    k, lo, e = m["num_experts_per_tok"], m["first_expert_held"], m["experts"]
    s = jax.nn.sigmoid(u @ p["router"])
    _, at = jax.lax.top_k(s + p["router_bias"], k)
    chosen = jnp.zeros(s.shape, bool).at[jnp.arange(s.shape[0])[:, None], at].set(True)
    w = jnp.where(chosen, s, 0.0)
    if m["norm_topk_prob"] and fault != "no_topk_norm":
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    w = m["routed_scaling_factor"] * w
    chosen, w = chosen[:, lo:lo + e], w[:, lo:lo + e]
    if fault == "drop_tokens":
        cap = int(s.shape[0] * k / m["n_routed_experts"])
        w = jnp.where(jnp.cumsum(chosen, axis=0) <= cap, w, 0.0)
    return w, chosen


def experts(p, u, m, fault):
    """One sequence through the latent mixture of experts: a loop over the
    held experts, each over every token."""
    w, _ = routing(p, u, m, fault)
    latent = u @ p["down"]
    routed = jnp.zeros_like(latent)
    for e in range(m["experts"]):
        hidden = jnp.square(jax.nn.relu(latent @ p["w1"][e]))
        routed = routed + w[:, e:e + 1] * (hidden @ p["w2"][e])
    shared = jnp.square(jax.nn.relu(u @ p["shared_in"])) @ p["shared_out"]
    return routed @ p["up"] + shared


def block(p, x, segment, kind, m, fault):
    u = rms_norm(x, p["norm"], m["rms_norm_eps"])
    if kind == "M":
        return x + mamba2(p["part"], u, segment, m, fault)
    if kind == "*":
        return x + attention(p["part"], u, segment, m, fault)
    return x + experts(p["part"], u, m, fault)


# -------------------------------------------------------- whole model, plain


def layer_names(m) -> list[str]:
    return [f"layer_{i}" for i in range(len(m["pattern"]))]


def forward_loss(dense, table, token, segment, m, fault=None):
    """The whole model in one expression (tests at small sizes; ``run_steps``
    is the same mathematics a layer at a time)."""
    assert fault in FAULTS
    x = table[token]
    for name, kind in zip(layer_names(m), m["pattern"]):
        x = jax.vmap(lambda xs, ss, p=dense[name], kind=kind: block(
            p, xs, ss, kind, m, fault))(x, segment)
    return head_loss(dense, x, token, segment, m)[0]


# ------------------------------------------------------- a layer at a time


def run_steps(m: dict, optim: dict, dense0, table0, feed: list[dict], *,
              fault=None) -> dict:
    """``len(feed)`` training steps from ``dense0`` (a mapping ``{top-level
    name: parameter tree}``, read once at the start and once more at the end)
    and ``table0`` [V, d].  ``feed``: host batches ``{"token", "segment"}``
    of [B, T] int32.

    Returns ``losses``, ``grad_norm`` and ``update_norm`` as
    ``reference/olmo_hybrid.run_steps`` does, and ``moe_pairs`` /
    ``moe_load_max`` (a step: the pairs the expert layers route to the held
    experts, summed over layers, and the largest held expert's pairs in one
    layer) with ``moe_layer_loads`` (a step, an expert layer: every held
    expert's pairs)."""
    assert fault in FAULTS
    tops = layer_names(m) + ["final_norm", "head"]
    kinds = dict(zip(layer_names(m), m["pattern"]))
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

    @partial(jax.jit, static_argnames=("kind",))
    def fwd(p, x, segment, kind):
        return jax.vmap(lambda xs, ss: block(p, xs, ss, kind, m, fault))(x, segment)

    @jax.jit
    def loads(p, x):
        u = rms_norm(x, p["norm"], m["rms_norm_eps"])
        chosen = jax.vmap(lambda us: routing(p["part"], us, m, None)[1])(u)
        return chosen.sum(axis=(0, 1))       # [E_held] over the batch

    @partial(jax.jit, static_argnames=("kind",), donate_argnums=(0, 1, 2))
    def bwd_update(p, mu, nu, x, segment, g_out, step, kind):
        _, vjp = jax.vjp(lambda p, x: jax.vmap(
            lambda xs, ss: block(p, xs, ss, kind, m, fault))(x, segment), p, x)
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
                if kinds[name] == "E":
                    routed.append(loads(params[name], xs[-1]))
                xs.append(fwd(params[name], xs[-1], segment, kind=kinds[name]))
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
                    step, kind=kinds[name])
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
