"""The Mamba-2 state-space recurrence (SSD: Dao & Gu, arXiv:2405.21060),
chunked, as an XLA formulation: the sibling of ``ops/gated_delta.py``.

Per head, with a scalar decay and a float32 state ``S`` of ``[P, N]`` that
is zero at a document's first token (``B_t``, ``C_t`` of ``[N]`` are shared
by the heads of a group)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T          A < 0, dt_t > 0
    y_t = S_t C_t

Unrolled over a chunk of ``C`` tokens (rows are tokens; ``cum_i`` the sum of
``dt A`` from the chunk's start to ``i``; ``L_ij = exp(cum_i - cum_j)`` for
``i >= j`` in one document, else 0)::

    Y  = ((C B^T) * L) (dt x)  +  (exp(cum) * carried) C S_0
    S' = (exp(cum_last) * carried_last) S_0 + ((dt x) * L_last,:)^T B

where ``carried_i`` is 1 while no document has started inside the chunk up
to ``i``.  There is no triangular solve: everything but the recurrence of
the chunks' states is a matrix product over all chunks at once, and the
recurrence itself (one multiply-add of ``[H, P, N]`` a chunk) is the
``lax.scan``.

``tests/test_nemotron_h.py`` holds this to the per-token recurrence, values
and gradients, with a document start inside a chunk and a ragged last chunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["chunk_ssd", "CHUNK"]

# tokens a chunk: the published ``chunk_size``; the tests pass smaller ones
CHUNK = 128


def chunk_ssd(
    x: jax.Array,       # [B, T, H, P]
    dt: jax.Array,      # [B, T, H] float32, after softplus
    a: jax.Array,       # [H] float32, negative
    b_mat: jax.Array,   # [B, T, G, N]
    c_mat: jax.Array,   # [B, T, G, N]
    starts: jax.Array,  # [B, T] bool: the token opens a document
    *,
    chunk: int = CHUNK,
) -> jax.Array:
    """``y`` of ``[B, T, H, P]`` in ``x``'s dtype.  Head ``i`` reads group
    ``i // (H / G)``.  Matrix products take their inputs in ``x``'s dtype and
    accumulate in float32; ``dt``, the decays and the state are float32."""
    b, t, h, p = x.shape
    g, n = b_mat.shape[2:]
    r = h // g
    dtype, f32 = x.dtype, jnp.float32
    c = min(chunk, t)
    k = -(-t // c)
    pad = k * c - t
    if pad:
        # a padded token has dt = 0: decay 1 and nothing added, so the state
        # stands still; its output row is cut off below
        widen = lambda v: jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        x, dt, b_mat, c_mat, starts = map(widen, (x, dt, b_mat, c_mat, starts))

    # [B, k, G, r, c, ...]: tokens of a chunk in the second-to-last places
    xc = jnp.moveaxis(x.reshape(b, k, c, g, r, p), 2, 4)
    dtc = jnp.moveaxis(dt.astype(f32).reshape(b, k, c, g, r), 2, 4)
    bc = jnp.moveaxis(b_mat.reshape(b, k, c, g, n), 2, 3)       # [B,k,G,c,N]
    cc = jnp.moveaxis(c_mat.reshape(b, k, c, g, n), 2, 3)
    # documents inside a chunk: 0 while the document the chunk opened in
    # goes on, 1.. after each start
    doc = jnp.cumsum(starts.reshape(b, k, c).astype(jnp.int32), axis=-1)
    same = (doc[..., :, None] == doc[..., None, :])[:, :, None, None]
    carried = (doc == 0)[:, :, None, None].astype(f32)          # [B,k,1,1,c]
    to_end = (doc == doc[..., -1:])[:, :, None, None].astype(f32)

    cum = jnp.cumsum(dtc * a.astype(f32).reshape(g, r, 1), axis=-1)
    lower = jnp.tril(jnp.ones((c, c), bool))
    diff = cum[..., :, None] - cum[..., None, :]
    decay = jnp.where(same & lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)

    dot = lambda spec, u, v: jnp.einsum(spec, u, v, preferred_element_type=f32)
    xdt = xc.astype(f32) * dtc[..., None]                       # [B,k,G,r,c,P]
    cb = dot("bkgin,bkgjn->bkgij", cc, bc)                      # [B,k,G,c,c]
    within = dot("bkgrij,bkgrjp->bkgrip",
                 (cb[:, :, :, None] * decay).astype(dtype), xdt.astype(dtype))
    out_decay = jnp.exp(cum[..., -1:] - cum) * to_end           # [B,k,G,r,c]
    made = dot("bkgrjp,bkgjn->bkgrpn",
               (xdt * out_decay[..., None]).astype(dtype), bc)  # [B,k,G,r,P,N]
    keep = jnp.exp(cum[..., -1]) * carried[..., -1]             # [B,k,G,r]

    def body(state, xs):
        made_i, keep_i = xs
        return keep_i[..., None, None] * state + made_i, state

    _, before = jax.lax.scan(
        body, jnp.zeros((b, g, r, p, n), f32),
        (jnp.moveaxis(made, 1, 0), jnp.moveaxis(keep, 1, 0)))
    before = jnp.moveaxis(before, 0, 1).astype(dtype)           # [B,k,G,r,P,N]
    across = dot("bkgin,bkgrpn->bkgrip", cc, before) \
        * (jnp.exp(cum) * carried)[..., None]
    y = (within + across).astype(dtype)                         # [B,k,G,r,c,P]
    y = jnp.moveaxis(y, 4, 2).reshape(b, k * c, h, p)
    return y[:, :t]
