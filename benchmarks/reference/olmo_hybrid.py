"""Plain reference for the ``olmo_hybrid`` architecture on the training path:
forward, next-token loss, gradients, AdamW and the table's row-sparse Adam,
in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.  It imports nothing from the
program.

Source: https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json
(``model_type: olmo_hybrid``); the linear-attention layer is Gated DeltaNet
(Yang, Kautz, Hatamizadeh, arXiv:2412.06464) with the key names of
flash-linear-attention's ``GatedDeltaNet``.  Per token ``x_t``; every RMSNorm
has a weight; no bias anywhere:

  block            h = x + RMSNorm(Mixer(x));  y = h + RMSNorm(MLP(h));
                   MLP(h) = W_down(silu(W_gate h) * W_up h)
  full_attention   q, k, v = W_q x, W_k x, W_v x; RMSNorm over the whole held
                   projection of q and of k; no rotary embedding; heads of
                   ``head_dim``; softmax(q k^T / sqrt(head_dim)) over the keys
                   of the same document at positions <= t; W_o
  linear_attention q~, k~, v~ = W_q x, W_k x, W_v x; each through a depthwise
                   causal convolution over time (taps before a document's
                   start are zero) and silu; per head q = q'/|q'|/sqrt(dk),
                   k = k'/|k'|, beta = 2 sigmoid(W_b x) (the 2 is
                   ``linear_allow_neg_eigval``), g = -exp(A_log) *
                   softplus(W_a x + dt_bias); state S (dk x dv, zero at a
                   document's first token):
                     u_t = beta_t (v_t - exp(g_t) S_{t-1}^T k_t)
                     S_t = exp(g_t) S_{t-1} + k_t u_t^T;   o_t = S_t^T q_t
                   y = RMSNorm_dv(o_t) * silu(W_g x_t) per head, W_o
  head             final RMSNorm, untied head over the vocabulary slice;
                   label of position t is token t + 1 where both lie in one
                   document, otherwise ignored; mean cross-entropy

Departures, each also under ``assumed`` in the configuration file: the norm
placement and the QK-norm are the Olmo 2/3 family's convention (the config
does not state them); the heads held (``n_heads`` of the published count) are
the chip's share of a stated deployment, and what the absent heads would add
to ``W_o``'s output is left out.

The delta rule here is the literal per-token recurrence in a ``lax.scan``;
its backward pass rematerialises a block of tokens at a time (still the
recurrence).  Attention is one masked softmax, a block of queries at a time.
Training is written out a layer at a time (``run_steps``): forward keeps each
layer's input, backward takes one layer's gradients, applies AdamW to that
layer and lets them go, so that what is alive at once is parameters, two
moments and one layer's work.

``fault`` (tests and the builder's readings; never the timed path):
``"bf16_state"`` keeps the delta-rule state in bfloat16 between tokens,
``"no_resets"`` switches the document resets off (state, convolution taps,
attention mask), ``"bf16_params"`` keeps the dense parameters in bfloat16
between steps (no float32 master copy).  Each must come out as not correct
where the program computes in float32; on the chip, where the program's own
bfloat16 products set the floor, ``bf16_state`` moves no compared number
(PERF.md section 2) and ``bf16_params`` stands for the lower precision."""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = (None, "bf16_state", "no_resets", "bf16_params")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


# ------------------------------------------------------------------ mixers


def causal_conv(x, w, segment):
    """Depthwise causal convolution over time: ``y_t = sum_s w[K-1-s] *
    x_{t-s}`` over the taps ``t - s`` that lie in ``t``'s document.
    ``x`` [T, C], ``w`` [K, C], ``segment`` [T]."""
    t = x.shape[0]
    y = jnp.zeros_like(x)
    for s in range(w.shape[0]):
        xs = jnp.pad(x, ((s, 0), (0, 0)))[:t]
        seg = jnp.pad(segment, (s, 0), constant_values=-1)[:t]
        y = y + jnp.where((seg == segment)[:, None], xs, 0.0) * w[w.shape[0] - 1 - s]
    return y


def delta_rule(q, k, v, g, beta, starts, *, block: int, bf16_state: bool):
    """The recurrence, token by token.  ``q``, ``k`` [T, H, dk]; ``v``
    [T, H, dv]; ``g``, ``beta`` [T, H]; ``starts`` [T] bool.  ``[T, H, dv]``."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % block
    if pad:  # beta = 0, g = 0: the state stands still; rows cut off below
        widen = lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        q, k, v, g, beta, starts = map(widen, (q, k, v, g, beta, starts))

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t, start = xs
        state = jnp.where(start, 0.0, state)
        alpha = jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", alpha * state, k_t))
        state = alpha * state + k_t[:, :, None] * u[:, None, :]
        if bf16_state:
            # reduce_precision, not a cast there and back: XLA may drop a
            # convert pair as excess precision (it does on the TPU)
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(x.reshape(-1, block, *x.shape[1:])
               for x in (q, k, v, g, beta, starts))
    _, o = jax.lax.scan(tokens, jnp.zeros((h, dk, dv), F32), xs)
    return o.reshape(-1, h, dv)[:t]


def linear_attention(p, x, segment, m, fault):
    """One sequence ``x`` [T, d] through the Gated DeltaNet mixer."""
    h, dk, dv = m["linear_heads"], m["linear_key_head_dim"], m["linear_value_head_dim"]
    t = x.shape[0]
    seg = jnp.zeros_like(segment) if fault == "no_resets" else segment
    q = silu(causal_conv(x @ p["wq"], p["conv_q"], seg)).reshape(t, h, dk)
    k = silu(causal_conv(x @ p["wk"], p["conv_k"], seg)).reshape(t, h, dk)
    v = silu(causal_conv(x @ p["wv"], p["conv_v"], seg)).reshape(t, h, dv)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / math.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jax.nn.sigmoid(x @ p["wb"]) * (2.0 if m["linear_allow_neg_eigval"] else 1.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(x @ p["wa"] + p["dt_bias"])
    starts = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    o = delta_rule(q, k, v, g, beta, starts, block=m.get("token_block", 128),
                   bf16_state=fault == "bf16_state")
    y = rms_norm(o, p["o_norm"], m["rms_norm_eps"]) * silu(x @ p["wg"]).reshape(t, h, dv)
    return y.reshape(t, h * dv) @ p["wo"]


def full_attention(p, x, segment, m, fault):
    """One sequence through causal full attention within documents: one
    masked softmax, a block of queries at a time."""
    h, dh = m["full_heads"], m["head_dim"]
    t = x.shape[0]
    eps = m["rms_norm_eps"]
    q = rms_norm(x @ p["wq"], p["q_norm"], eps).reshape(t, h, dh)
    k = rms_norm(x @ p["wk"], p["k_norm"], eps).reshape(t, h, dh)
    v = (x @ p["wv"]).reshape(t, h, dh)
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

    # one block of queries after another (lax.map: the blocks' logits are
    # never alive together); padded query rows repeat the last row's mask
    # and are cut off
    blocks = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                               mode="edge").reshape(-1, bq, *a.shape[1:])
    out = jax.lax.map(rows, (blocks(q), blocks(pos), blocks(segment)))
    return out.reshape(-1, h * dh)[:t] @ p["wo"]


MIXERS = {"linear_attention": linear_attention, "full_attention": full_attention}


def block(p, x, segment, kind, m, fault):
    eps = m["rms_norm_eps"]
    h = x + rms_norm(MIXERS[kind](p["mixer"], x, segment, m, fault),
                     p["mixer_norm"], eps)
    mlp = (silu(h @ p["mlp"]["gate"]) * (h @ p["mlp"]["up"])) @ p["mlp"]["down"]
    return h + rms_norm(mlp, p["mlp_norm"], eps)


def head_loss(p, x, token, segment, m):
    """Mean next-token cross-entropy over the labelled positions of a batch
    ``x`` [B, T, d]; ``(loss, labelled positions)``."""
    logits = rms_norm(x, p["final_norm"], m["rms_norm_eps"]) @ p["head"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nxt = jnp.concatenate([token[:, 1:], token[:, :1]], axis=1)
    labelled = jnp.concatenate(
        [segment[:, 1:] == segment[:, :-1],
         jnp.zeros((token.shape[0], 1), bool)], axis=1)
    picked = jnp.take_along_axis(logp, nxt[..., None], axis=-1)[..., 0]
    n = jnp.maximum(labelled.sum(), 1)
    return -(jnp.where(labelled, picked, 0.0)).sum() / n, n


# -------------------------------------------------------- whole model, plain


def layer_names(m) -> list[str]:
    return [f"layer_{i}" for i in range(len(m["layer_types"]))]


def forward_loss(dense, table, token, segment, m, fault=None):
    """The whole model in one expression (tests at small sizes; ``run_steps``
    is the same mathematics a layer at a time).  ``dense`` is the nested
    parameter tree, ``table`` [V, d]."""
    assert fault in FAULTS
    x = table[token]
    for name, kind in zip(layer_names(m), m["layer_types"]):
        x = jax.vmap(lambda xs, ss, p=dense[name], kind=kind: block(
            p, xs, ss, kind, m, fault))(x, segment)
    return head_loss(dense, x, token, segment, m)[0]


# ------------------------------------------------------------ the optimizers


def adamw(p, g, mu, nu, step, o, *, bf16_params=False):
    """optax ``adamw``: decoupled decay on every dense leaf."""
    mu = o["b1"] * mu + (1 - o["b1"]) * g
    nu = o["b2"] * nu + (1 - o["b2"]) * g * g
    mhat = mu / (1 - o["b1"] ** step)
    nhat = nu / (1 - o["b2"] ** step)
    p = p - o["lr"] * (mhat / (jnp.sqrt(nhat) + o["eps"]) + o["weight_decay"] * p)
    if bf16_params:
        p = jax.lax.reduce_precision(p, exponent_bits=8, mantissa_bits=7)
    return p, mu, nu


def sparse_adam(table, mu, nu, ids, g_rows, step, o):
    """Row-sparse Adam: the gradient rows of one id are summed; only touched
    rows move, and their moments do not decay while untouched.  Returns the
    table, both moments and the summed gradient rows."""
    uids, inv = jnp.unique(ids, return_inverse=True, size=ids.shape[0],
                           fill_value=table.shape[0])
    g = jax.ops.segment_sum(g_rows, inv.reshape(-1), num_segments=ids.shape[0])
    live = uids < table.shape[0]
    at = jnp.where(live, uids, 0)
    m1 = o["b1"] * mu[at] + (1 - o["b1"]) * g
    n1 = o["b2"] * nu[at] + (1 - o["b2"]) * g * g
    delta = o["lr"] * ((m1 / (1 - o["b1"] ** step))
                       / (jnp.sqrt(n1 / (1 - o["b2"] ** step)) + o["eps"])
                       + o.get("weight_decay", 0.0) * table[at])
    put = lambda a, rows: a.at[jnp.where(live, uids, table.shape[0])].set(
        rows, mode="drop")
    return put(table, table[at] - delta), put(mu, m1), put(nu, n1), g


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))


def _flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


# ------------------------------------------------------- a layer at a time


def run_steps(m: dict, optim: dict, dense0, table0, feed: list[dict], *,
              fault=None) -> dict:
    """``len(feed)`` training steps from ``dense0`` (a mapping ``{top-level
    name: parameter tree}``; read once at the start and once more at the end,
    so a lazy mapping never has two copies alive) and ``table0`` [V, d].
    ``feed``: host batches ``{"token", "segment"}`` of [B, T] int32.

    Returns ``losses`` (one a step), ``grad_norm`` (per leaf, the first
    step's gradient; the table under ``table:token``, its touched rows) and
    ``update_norm`` (per leaf, parameters after the last step less
    parameters at the start)."""
    assert fault in FAULTS
    tops = layer_names(m) + ["final_norm", "head"]
    kinds = dict(zip(layer_names(m), m["layer_types"]))
    od, os_ = optim["dense"], optim["sparse"]
    zeros = lambda tree: jax.tree.map(jnp.zeros_like, tree)

    def update(p, g_p, mu, nu, step):
        """AdamW over one subtree: ``(parameters, mu, nu)``."""
        new = jax.tree.map(
            lambda a, b, c, d: adamw(a, b, c, d, step, od,
                                     bf16_params=fault == "bf16_params"),
            p, g_p, mu, nu)
        return tuple(jax.tree.map(lambda t, i=i: t[i], new,
                                  is_leaf=lambda t: isinstance(t, tuple))
                     for i in range(3))

    @partial(jax.jit, static_argnames=("kind",))
    def fwd(p, x, segment, kind):
        return jax.vmap(lambda xs, ss: block(p, xs, ss, kind, m, fault))(x, segment)

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
        losses, grad_norm = [], {}
        for n, batch in enumerate(feed, 1):
            token = jnp.asarray(batch["token"], jnp.int32)
            segment = jnp.asarray(batch["segment"], jnp.int32)
            step = jnp.asarray(n, F32)
            xs = [table[token]]
            for name in layer_names(m):
                xs.append(fwd(params[name], xs[-1], segment, kind=kinds[name]))
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
                               update_norm=update_norm))
    return {"losses": [float(x) for x in host["losses"]],
            "grad_norm": {k: float(v) for k, v in host["grad_norm"].items()},
            "update_norm": {k: float(v) for k, v in host["update_norm"].items()}}
