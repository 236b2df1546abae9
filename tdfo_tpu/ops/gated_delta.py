"""The gated delta rule (Gated DeltaNet: Yang, Kautz, Hatamizadeh,
arXiv:2412.06464), chunked, as an XLA formulation.

Per head, with a float32 state ``S`` of ``[dk, dv]`` that is zero at a
document's first token::

    u_t = beta_t * (v_t - alpha_t * S_{t-1}^T k_t)        alpha_t = exp(g_t)
    S_t = alpha_t * S_{t-1} + k_t u_t^T
    o_t = S_t^T q_t

Unrolled over a chunk of ``C`` tokens (rows are tokens; ``gamma_i`` the sum
of ``g`` from the chunk's start to ``i``; ``Gamma_ij = exp(gamma_i -
gamma_j)`` for ``i >= j`` in one document, else 0)::

    A  = strict_lower(diag(beta) (K K^T * Gamma))
    T  = (I + A)^-1 diag(beta)          one unit-lower-triangular solve
    U  = T V        W = T (K * exp(gamma))
    V' = U - W S_0
    O  = (Q * exp(gamma)) S_0 + lower(Q K^T * Gamma) V'
    S_C = exp(gamma_C) S_0 + (K * Gamma_{C,:})^T V'

where ``exp(gamma_i)`` in front of ``S_0`` is 0 for every token at or after
a document start inside the chunk.  Everything up to ``V'`` is computed for
all chunks at once (matrix products and the solve); only the three products
with the state run in the ``lax.scan`` over chunks, whose body is
rematerialised so that the backward pass keeps one state a chunk.

The solve is forward substitution (``solve_triangular``), not the product
form ``(I - A)(I + A^2)(I + A^4)...``: keys that share a mean direction (as
after ``silu``) make the powers of ``A`` large before they vanish, and the
product form then cancels catastrophically where substitution does not.

``tests/test_olmo_hybrid.py`` holds this to the per-token recurrence, values
and gradients, with document starts inside chunks and a ragged last chunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["chunk_gated_delta_rule", "CHUNK"]

# tokens a chunk: 64 and 32 read the same on the v5e, 128 is 2.4x slower
# (PERF.md, PR 31); the tests pass smaller ones
CHUNK = 64


def _chunks(x: jax.Array, n: int, c: int) -> jax.Array:
    """``[B, n * c, H, ...] -> [B, n, H, c, ...]``."""
    b, _, h, *rest = x.shape
    x = x.reshape(b, n, c, h, *rest)
    return jnp.moveaxis(x, 3, 2)


def chunk_gated_delta_rule(
    q: jax.Array,       # [B, T, H, dk], already normalised and scaled
    k: jax.Array,       # [B, T, H, dk], already normalised
    v: jax.Array,       # [B, T, H, dv]
    g: jax.Array,       # [B, T, H] log decay, <= 0
    beta: jax.Array,    # [B, T, H]
    starts: jax.Array,  # [B, T] bool: the token opens a document
    *,
    chunk: int = CHUNK,
) -> jax.Array:
    """``o`` of ``[B, T, H, dv]`` in ``v``'s dtype.  Matrix products take
    their inputs in ``q``'s dtype and accumulate in float32; ``gamma`` and
    the state are float32."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    dtype = q.dtype
    f32 = jnp.float32
    c = min(chunk, t)
    n = -(-t // c)
    pad = n * c - t
    if pad:
        # a padded token has beta = 0, g = 0 and zero vectors: it leaves the
        # state as it is, and its output row is cut off below
        widen = lambda x: jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        q, k, v, g, beta, starts = map(widen, (q, k, v, g, beta, starts))

    qc, kc, vc = _chunks(q, n, c), _chunks(k, n, c), _chunks(v, n, c)
    gc = _chunks(g.astype(f32), n, c)          # [B, n, H, c]
    bc = _chunks(beta.astype(f32), n, c)
    # documents inside a chunk: 0 for the tokens that continue the document
    # the chunk opened in, 1.. after each start
    doc = jnp.cumsum(starts.reshape(b, n, c).astype(jnp.int32), axis=-1)
    same = (doc[..., :, None] == doc[..., None, :])[:, :, None]  # [B,n,1,c,c]
    carried = (doc == 0)[:, :, None].astype(f32)                 # [B,n,1,c]

    gamma = jnp.cumsum(gc, axis=-1)
    lower = jnp.tril(jnp.ones((c, c), bool))
    diff = gamma[..., :, None] - gamma[..., None, :]
    big_gamma = jnp.where(same & lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)

    dot = lambda spec, x, y: jnp.einsum(spec, x, y, preferred_element_type=f32)
    kk = dot("bnhic,bnhjc->bnhij", kc, kc)
    a = jnp.tril(bc[..., :, None] * kk * big_gamma, -1)
    eye = jnp.eye(c, dtype=f32)
    tmat = jax.scipy.linalg.solve_triangular(
        eye + a, eye * bc[..., None, :], lower=True, unit_diagonal=True)
    tmat = tmat.astype(dtype)
    decay_in = jnp.exp(gamma) * carried                          # [B,n,H,c]
    u = dot("bnhij,bnhjd->bnhid", tmat, vc).astype(dtype)
    w = dot("bnhij,bnhjd->bnhid", tmat,
            (kc.astype(f32) * decay_in[..., None]).astype(dtype)).astype(dtype)
    attn = (dot("bnhic,bnhjc->bnhij", qc, kc) * big_gamma).astype(dtype)
    q_in = (qc.astype(f32) * decay_in[..., None]).astype(dtype)
    k_out = (kc.astype(f32) * big_gamma[..., -1, :, None]).astype(dtype)
    decay_out = decay_in[..., -1]                                # [B,n,H]

    def body(state, xs):
        u_i, w_i, attn_i, q_i, k_i, d_i = xs
        s_in = state.astype(dtype)
        v_new = (u_i.astype(f32) - dot("bhid,bhde->bhie", w_i, s_in)).astype(dtype)
        o_i = (dot("bhid,bhde->bhie", q_i, s_in)
               + dot("bhij,bhje->bhie", attn_i, v_new))
        state = (d_i[..., None, None] * state
                 + dot("bhid,bhie->bhde", k_i, v_new))
        return state, o_i.astype(v.dtype)

    xs = tuple(jnp.moveaxis(x, 1, 0)
               for x in (u, w, attn, q_in, k_out, decay_out))
    _, o = jax.lax.scan(jax.checkpoint(body), jnp.zeros((b, h, dk, dv), f32),
                        xs)
    o = jnp.moveaxis(o, 0, 1)                   # [B, n, H, c, dv]
    o = jnp.moveaxis(o, 2, 3).reshape(b, n * c, h, dv)
    return o[:, :t]
