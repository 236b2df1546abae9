"""Plain reference for the CTR family: DLRM and TwoTower, forward, loss,
gradients and the optimizers, in straightforward float32 ``jax.numpy``.

It imports nothing of ``tdfo_tpu`` and is handed nothing ``tdfo_tpu`` made:
the embedding rows come from ``benchmarks/lib/weights.py`` (a function of the
seed), the dense parameters from the same module, the batches are the rows
the traffic generator wrote.  Tables are held COMPACT: only the rows the
given batches touch exist here, which is all the stated semantics ever read
or write, so a 33.7 M-row configuration costs a few hundred thousand rows.

Stated semantics followed (departures from a textbook are the program's
configuration, quoted):

* DLRM (facebookresearch/dlrm ``dlrm_s_pytorch.py``): bottom MLP over the
  continuous features ending at ``embed_dim`` with ReLU after every layer,
  pairwise dot products of the F embedding vectors and the bottom output
  (strict upper triangle, row-major), top MLP over [bottom, interactions]
  with ReLU on hidden layers and a linear last layer; loss = mean sigmoid
  binary cross-entropy.
* TwoTower (the reference system's ``jax-flax/models.py``): user tower =
  fc1 -> swish -> fc2 over the user embedding; item tower the same over the
  concatenation of the item-side embeddings and the continuous features;
  logit = row-wise dot product.
* Embedding rows: row-sparse, touched rows only, gradients of repeated ids
  summed.  ``rowwise_adagrad`` (fbgemm EXACT_ROWWISE_ADAGRAD): weight decay
  added to the gradient, one accumulator per row += mean(g^2), row -= lr g /
  (sqrt(acc) + eps).  ``adam``: per-row moments, ONE global step count for the
  bias correction, decoupled weight decay on touched rows only.
* Dense parameters: AdamW (optax defaults b1 0.9, b2 0.999, eps 1e-8),
  decoupled weight decay on every leaf.

``compute="bfloat16"`` is the CONTROL, not a reference: the same mathematics
computed in bfloat16 throughout — parameters, optimizer state, activations,
matmuls and the optimizer's arithmetic — the nearest precision below the
float32 the configurations state, and the step a later PR would be tempted
by (bfloat16 tables, bfloat16 moments).  ``fault="half_batch"`` plants the fault "half of the batch left
out, the mean taken over the rest"."""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dense(p, name, x, precision):
    return jnp.dot(x, p[f"{name}/kernel"], precision=precision) + p[f"{name}/bias"]


def dlrm_logits(model: dict, dense: dict, embs: dict, conts, precision):
    x = conts
    for i in range(len(model["bottom"])):
        x = jax.nn.relu(_dense(dense, f"bottom_{i}", x, precision))
    x = jax.nn.relu(_dense(dense, "bottom_out", x, precision))
    vecs = jnp.stack([embs[c] for c in model["categorical"]] + [x], axis=1)
    inter = jnp.einsum("bfd,bgd->bfg", vecs, vecs, precision=precision)
    iu, ju = np.triu_indices(vecs.shape[1], k=1)
    top = jnp.concatenate([x, inter[:, iu, ju]], axis=-1)
    for i in range(len(model["top"])):
        top = jax.nn.relu(_dense(dense, f"top_{i}", top, precision))
    return _dense(dense, "top_out", top, precision)[:, 0]


def twotower_logits(model: dict, dense: dict, embs: dict, conts, precision):
    def tower(name, x):
        x = jax.nn.swish(_dense(dense, f"{name}/fc1", x, precision))
        return _dense(dense, f"{name}/fc2", x, precision)

    u = tower("user_tower", embs[model["user_column"]])
    parts = [embs[c] for c in model["item_columns"]] + [conts]
    v = tower("item_tower", jnp.concatenate(parts, axis=-1))
    return jnp.einsum("be,be->b", u, v, precision=precision)


LOGITS = {"dlrm": dlrm_logits, "twotower": twotower_logits}


def bce_with_logits(logits, labels):
    logits = logits.astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def _loss_fn(model, compute):
    logits_fn = LOGITS[model["model"]]
    if compute == "float32":
        dt, precision = jnp.float32, HIGHEST
    elif compute == "bfloat16":
        dt, precision = jnp.bfloat16, None
    else:
        raise ValueError(f"reference: unknown compute {compute!r}")

    def loss(dense, embs, conts, labels):
        logits = logits_fn(model, dense, embs, conts.astype(dt), precision)
        return bce_with_logits(logits, labels)

    return loss, dt


def _sparse_update(kind, hp, rows, slots, g, touched, count):
    """Touched rows of one compact table.  ``count``: the two bias
    corrections ``1 - b**n``, worked out on the host in double precision (n
    is a host integer) so that the bfloat16 control divides by 0.001 and not
    by 1 - bfloat16(0.999) = 0.  Returns (rows, slots)."""
    lr, wd, eps = hp["lr"], hp["weight_decay"], hp["eps"]
    t = touched[:, None]
    if kind == "rowwise_adagrad":
        (acc,) = slots
        g = g + wd * rows
        acc_n = acc + jnp.mean(g * g, axis=-1)
        new = rows - lr * g / (jnp.sqrt(acc_n)[:, None] + eps)
        return jnp.where(t, new, rows), (jnp.where(touched, acc_n, acc),)
    if kind == "adam":
        m, v = slots
        b1, b2 = hp["b1"], hp["b2"]
        m_n = b1 * m + (1 - b1) * g
        v_n = b2 * v + (1 - b2) * g * g
        m_hat = m_n / count[0]
        v_hat = v_n / count[1]
        new = rows - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * rows)
        return (jnp.where(t, new, rows),
                (jnp.where(t, m_n, m), jnp.where(t, v_n, v)))
    raise ValueError(f"reference: no sparse optimizer {kind!r}")


def _adamw(hp, p, m, v, g, count):
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    m_n = b1 * m + (1 - b1) * g
    v_n = b2 * v + (1 - b2) * g * g
    m_hat = m_n / count[0]
    v_hat = v_n / count[1]
    return (p - hp["lr"] * (m_hat / (jnp.sqrt(v_hat) + eps)
                            + hp["weight_decay"] * p), m_n, v_n)


def init_slots(kind: str, rows):
    if kind == "rowwise_adagrad":
        return (jnp.zeros(rows.shape[0], rows.dtype),)
    if kind == "adam":
        return (jnp.zeros_like(rows), jnp.zeros_like(rows))
    raise ValueError(f"reference: no sparse optimizer {kind!r}")


def run_steps(model: dict, optim: dict, tables: dict, dense: dict,
              batches: list[dict], *, compute: str = "float32",
              fault: str | None = None) -> dict:
    """Follow ``batches`` from the given start.

    ``tables``: ``{column: (ids [U] sorted, rows [U, D])}`` — the compact
    tables over the union of the batches' ids (ids may end in padding that no
    batch looks up).  ``dense``: ``{path: array}``.
    Returns per-step losses, the state after the FIRST step (sparse slots and
    dense first moments) and the parameters after the LAST step."""
    kind = optim["sparse"]["kind"]
    shp, dhp = optim["sparse"], optim["dense"]
    cats, conts_c = model["categorical"], model["continuous"]
    loss_fn, dt = _loss_fn(model, compute)

    @jax.jit
    def step(rows, slots, dense, dm, dv, scount, dcount, idx, conts, labels):
        embs = {c: rows[c][idx[c]] for c in cats}
        loss, (g_dense, g_embs) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            dense, embs, conts, labels)
        n_rows, n_slots = {}, {}
        for c in cats:
            u = rows[c].shape[0]
            g = jax.ops.segment_sum(g_embs[c], idx[c], num_segments=u)
            touched = jax.ops.segment_sum(
                jnp.ones(idx[c].shape, jnp.float32), idx[c],
                num_segments=u) > 0
            n_rows[c], n_slots[c] = _sparse_update(
                kind, shp, rows[c], slots[c], g, touched, scount)
        n_dense, n_dm, n_dv = {}, {}, {}
        for k in dense:
            n_dense[k], n_dm[k], n_dv[k] = _adamw(
                dhp, dense[k], dm[k], dv[k], g_dense[k], dcount)
        return loss, n_rows, n_slots, n_dense, n_dm, n_dv

    ids = {c: np.asarray(tables[c][0]) for c in cats}
    rows = {c: jnp.asarray(tables[c][1], dt) for c in cats}
    slots = {c: init_slots(kind, rows[c]) for c in cats}
    dense = {k: jnp.asarray(v, dt) for k, v in dense.items()}
    dm = {k: jnp.zeros_like(v) for k, v in dense.items()}
    dv = {k: jnp.zeros_like(v) for k, v in dense.items()}
    out = {"losses": [], "rows0": rows, "dense0": dense}
    for n, b in enumerate(batches, start=1):
        if fault == "half_batch":
            b = {k: v[: len(v) // 2] for k, v in b.items()}
        idx = {c: jnp.asarray(np.searchsorted(ids[c], b[c]), jnp.int32)
               for c in cats}
        conts = jnp.stack([jnp.asarray(b[c], jnp.float32) for c in conts_c],
                          axis=-1)
        labels = jnp.asarray(b["label"], jnp.float32)
        corr = lambda hp: jnp.asarray(
            [1 - hp.get("b1", 0.9) ** n, 1 - hp.get("b2", 0.999) ** n], dt)
        new = step(rows, slots, dense, dm, dv, corr(shp), corr(dhp), idx,
                   conts, labels)
        loss = new[0]
        if fault != "frozen":
            _, rows, slots, dense, dm, dv = new
        out["losses"].append(float(loss))
        if n == 1:
            out["slots1"], out["dense_m1"] = slots, dm
    out["rows"], out["dense"] = rows, dense
    return out
