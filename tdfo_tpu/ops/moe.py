"""A routed mixture of experts of which this process holds a SHARE: the
router scores every published expert, the layer is told which experts it
holds (the caller hands :func:`held_experts` their columns of the router's
weights and their parameters), and it computes their part of the result
for the tokens routed to them.  It drops none.  What the absent experts
would add is left out; on one chip there is no exchange, and no code stands
in for the absent chips (``/opt/skills/guides/model-configs`` section 4).

Router (DeepSeek-V3's, as ``modeling_nemotron_h.py`` ``NemotronHTopkRouter``
with ``n_group = topk_group = 1``): ``s = sigmoid(u W_r)`` over all experts,
the product too in float32 (``F.linear(u.float(), W_r.float())`` there: a
rounded logit moves WHICH experts a token takes); the ``k`` largest of ``s +
b`` are chosen (``b`` a selection bias: a buffer, no gradient); ``w_e =
scale * s_e / (sum of the chosen s + 1e-20)``.

Experts (``relu(x W1_e)^2 W2_e``), at static shapes, in one of two forms:

dense         every held expert over every token, masked by ``w_e``:
              ``E_held`` times the rows of one expert.
sorted        the (token, held expert) pairs sorted by expert into a static
              ``rows`` rows (a gather in), the two products as
              ``jax.lax.ragged_dot`` over the experts' groups, the transposed
              gather out.  However uneven the experts' loads, it computes
              ``rows`` rows; whenever the pairs routed here exceed ``rows``
              the WHOLE call takes the dense form instead (``lax.cond``):
              exact either way.

``pairs`` counts the (token, held expert) pairs routed here, ``computed``
those whose product the taken form formed and weighted: equal, or a token
was dropped.  ``fell_back`` says which form the call took (1: dense).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["route", "held_experts", "sorted_rows", "ROWS_FACTOR",
           "ROUTER_PRECISION"]

# the router's product: float32 operands in as many bfloat16 passes as make
# it a float32 product on the TPU (on the CPU it is one either way)
ROUTER_PRECISION = jax.lax.Precision.HIGHEST

# static rows of the "sorted" form: this many times the pairs uniform routing
# sends here, ``T * k * E_held / E`` (rounded up to a multiple of 128)
ROWS_FACTOR = 3.0


def sorted_rows(tokens: int, per_token: int, held: int, routed: int) -> int:
    """The sorted form's static rows for ``tokens`` tokens that each choose
    ``per_token`` of ``routed`` experts, ``held`` of them here."""
    return -(-int(ROWS_FACTOR * tokens * per_token * held / routed) // 128) * 128


def route(u, w_router, bias, *, top_k: int, scale: float,
          normalise: bool = True):
    """``u`` [T, d] -> ``(weights, chosen)`` of [T, E]: ``weights`` is
    ``w_e`` where expert ``e`` is chosen and 0 elsewhere (float32),
    ``chosen`` the selection (bool).  Float32 from ``u`` on, the product
    and its two transposes included."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.dot(u.astype(f32), w_router.astype(f32),
                               precision=ROUTER_PRECISION))
    pick = jax.lax.stop_gradient(s + bias.astype(f32))
    top, at = jax.lax.top_k(pick, top_k)
    # the chosen set without a scatter: above the k-th value, or equal to it
    # at an index no larger than the k-th's (``top_k`` takes the lowest
    # indices among equals)
    iota = jax.lax.broadcasted_iota(jnp.int32, pick.shape, pick.ndim - 1)
    chosen = (pick > top[..., -1:]) | ((pick == top[..., -1:])
                                       & (iota <= at[..., -1:]))
    picked = jnp.where(chosen, s, 0.0)
    if normalise:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    return scale * picked, chosen


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _dense(x, weights, w1, w2):
    f32 = jnp.float32
    h = _relu2(jnp.einsum("td,edf->etf", x, w1.astype(x.dtype)))
    h = h * weights.T.astype(h.dtype)[..., None]
    return jnp.einsum("etf,efd->td", h, w2.astype(x.dtype),
                      preferred_element_type=f32).astype(x.dtype)


@jax.custom_vjp
def _dispatch(x, token, slot, live):
    """Rows of ``x`` [T, d] to ``[R, d]``: row ``r`` is token ``token[r]``
    where ``live[r]``, else zero.  Its transpose is :func:`_combine`; both
    directions are gathers (an XLA scatter costs about 170 ns a row on the
    v5e, CLAUDE.md)."""
    return jnp.where(live[:, None], jnp.take(x, token, axis=0), 0)


@jax.custom_vjp
def _combine(y, token, slot, live):
    """``[R, d]`` back to ``[T, d]``: token ``t`` sums, over the experts
    that hold it, row ``slot[t, e]`` (-1 where expert ``e`` does not hold
    ``t``)."""
    rows = jnp.take(y, jnp.maximum(slot, 0), axis=0)            # [T, E, d]
    return jnp.where((slot >= 0)[..., None], rows, 0).sum(axis=1)


@jax.custom_vjp
def _rows_of(w, pair, slot):
    """``w`` [T, E] -> ``[R]``: the weight of each row's (token, expert)
    pair, ``pair`` its index into ``w`` flattened; the transpose gathers
    too."""
    return jnp.take(w.reshape(-1), pair)


def _rows_of_bwd(res, g):
    pair, slot = res
    return jnp.where(slot >= 0, jnp.take(g, jnp.maximum(slot, 0)), 0), None, None


_rows_of.defvjp(lambda w, pair, slot: (_rows_of(w, pair, slot), (pair, slot)),
                _rows_of_bwd)
_dispatch.defvjp(
    lambda x, token, slot, live: (_dispatch(x, token, slot, live),
                                  (token, slot, live)),
    lambda res, g: (_combine(g, *res), None, None, None))
_combine.defvjp(
    lambda y, token, slot, live: (_combine(y, token, slot, live),
                                  (token, slot, live)),
    lambda res, g: (_dispatch(g, *res), None, None, None))


def _sorted(x, weights, chosen, load, w1, w2, rows: int):
    """The pairs sorted by expert (then token) into ``rows`` static rows,
    the two products as ``ragged_dot`` over the experts' groups of ``load``
    [E] rows."""
    f32 = jnp.float32
    t, e = chosen.shape
    order = jnp.argsort(~chosen.T.reshape(-1), stable=True)[:rows]
    expert, token = order // t, order % t
    live = jnp.arange(rows) < load.sum()
    slot = jnp.where(chosen, (jnp.cumsum(load) - load)[None, :]
                     + jnp.cumsum(chosen, axis=0) - 1, -1)      # [T, E]
    xs = _dispatch(x, token, slot, live)
    h = _relu2(jax.lax.ragged_dot(xs, w1.astype(x.dtype), load))
    y = jax.lax.ragged_dot(h, w2.astype(x.dtype), load,
                           preferred_element_type=f32)
    w_rows = _rows_of(weights, token * e + expert, slot)
    y = (y * jnp.where(live, w_rows, 0.0)[:, None]).astype(x.dtype)
    return _combine(y, token, slot, live), live.sum()


def held_experts(x, weights, chosen, w1, w2, *, rows: int):
    """``sum over the chosen AND held e of w_e relu(x W1_e)^2 W2_e``.

    ``x`` [T, d]; ``weights``, ``chosen`` [T, E_held] (the held experts'
    columns of :func:`route`'s); ``w1`` [E_held, d, f]; ``w2`` [E_held, f,
    d]; ``rows`` the sorted form's static rows (never more than the ``T
    E_held`` pairs there can be).  Returns ``(out [T, d], pairs, computed,
    load_max, fell_back)``, all but ``out`` int32 scalars."""
    t, e = chosen.shape
    rows = min(rows, t * e)
    load = chosen.sum(axis=0)
    pairs = load.sum()
    out, computed = jax.lax.cond(
        pairs <= rows,
        lambda: _sorted(x, weights, chosen, load, w1, w2, rows),
        lambda: (_dense(x, weights, w1, w2), pairs))
    return out, pairs, computed, load.max(), (pairs > rows).astype(jnp.int32)
