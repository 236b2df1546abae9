"""Ring attention — sequence/context parallelism over the ``seq`` mesh axis.

A NEW capability relative to the reference (its max sequence length is 20 and
attention is a full T×T matrix, ``torchrec/models.py:18-28``,
``torchrec/config.toml:11`` — SURVEY.md §5.7): sequences are sharded across
devices on the ``seq`` axis and attention runs blockwise with an online
(flash-style) softmax, rotating K/V shards around the ring with
``jax.lax.ppermute`` over ICI.  Peak memory per device is O(T·T/P) logits
instead of O(T²), and K/V transfer overlaps compute — the standard TPU recipe
for million-token contexts (Liu et al., Ring Attention with Blockwise
Transformers, 2023).

Two entry points:

  * :func:`ring_attention` — the per-shard program (call inside your own
    ``shard_map``); operands carry the LOCAL sequence chunk.
  * :func:`ring_self_attention` — convenience wrapper that shard_maps over a
    mesh: global [B, H, T, Dh] in, global out, with optional key-padding mask
    (Bert4Rec semantics).

Numerics: softmax statistics are f32 regardless of operand dtype; fully
masked query rows return 0 (matching a dense softmax over an all-masked row
followed by the usual convention).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tdfo_tpu.core.mesh import (SEQ_AXIS, mesh_platform, pallas_impl,
                                shard_map)

__all__ = ["ring_attention", "ring_flash_attention", "ring_self_attention", "make_ring_attn_fn"]

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _accum_chunk(o, m, l, q, k_blk, v_blk, kv_valid, scale):
    """One online-softmax accumulation over a K/V chunk (flash-style carry
    update: running output ``o``, row max ``m``, normaliser ``l``)."""
    logits = (
        jnp.einsum("bhtd,bhsd->bhts", q, k_blk).astype(jnp.float32) * scale
    )
    logits = jnp.where(kv_valid[:, None, None, :], logits, _NEG_INF)
    m_new = jnp.maximum(m, logits.max(axis=-1))  # [B, H, Tq]
    # guard: rows where everything so far is masked keep m at _NEG_INF
    # (finite finfo.min, same convention as the flash kernel); shifting by
    # it would overflow exp, so clamp the shift and zero the correction.
    # Threshold at _NEG_INF/2 so the guard holds for any all-masked row
    # regardless of whether _NEG_INF is finite or a true -inf.
    shift = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
    probs = jnp.exp(logits - shift[..., None])
    probs = jnp.where(kv_valid[:, None, None, :], probs, 0.0)
    corr = jnp.where(m <= _NEG_INF / 2, 0.0, jnp.exp(m - shift))
    l_new = l * corr + probs.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhts,bhsd->bhtd", probs.astype(v_blk.dtype), v_blk
    ).astype(jnp.float32)
    return o_new, m_new, l_new


def ring_attention(
    q: jax.Array,  # [B, H, Tq, Dh] local query chunk
    k: jax.Array,  # [B, H, Tk, Dh] local key chunk
    v: jax.Array,  # [B, H, Tk, Dh]
    key_valid: jax.Array | None = None,  # [B, Tk] True = attend (local chunk)
    *,
    axis_name: str = SEQ_AXIS,
    block_k: int | None = None,
) -> jax.Array:
    """Blockwise attention with online softmax; K/V travel the ring.

    Must run inside ``shard_map`` with ``q``/``k``/``v`` sequence-sharded on
    ``axis_name``.  Step ``s`` processes the K/V chunk originally owned by
    device ``(idx - s) mod P`` while asynchronously passing chunks to the next
    ring neighbour.

    ``block_k`` additionally chunks each ring step's LOCAL attention: peak
    logits memory drops from O(Tq x Tk) to O(Tq x block_k), and the inner
    scan body is rematerialised (``jax.checkpoint``) so the backward pass
    stays O(carry) instead of saving every chunk's probabilities — the
    all-XLA counterpart of the Pallas flash kernel, composed with the ring.
    Must divide the local Tk; identical numerics either way.
    """
    p = jax.lax.axis_size(axis_name)
    b, h, tq, dh = q.shape
    tk = k.shape[2]
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    perm = [(i, (i + 1) % p) for i in range(p)]

    if key_valid is None:
        key_valid = jnp.ones(k.shape[:1] + k.shape[2:3], bool)  # [B, Tk]
    if not block_k or block_k >= tk:
        block_k = None  # 0/None/oversized all mean "one chunk per ring step"
    elif tk % block_k:
        raise ValueError(f"block_k {block_k} must divide the local K length {tk}")

    def block(carry, _):
        o, m, l, k_blk, v_blk, kv_valid = carry
        if block_k is None:
            o, m, l = _accum_chunk(o, m, l, q, k_blk, v_blk, kv_valid, scale)
        else:
            nc = tk // block_k
            kcs = jnp.moveaxis(k_blk.reshape(b, h, nc, block_k, dh), 2, 0)
            vcs = jnp.moveaxis(v_blk.reshape(b, h, nc, block_k, dh), 2, 0)
            validcs = jnp.moveaxis(kv_valid.reshape(b, nc, block_k), 1, 0)

            @jax.checkpoint
            def inner(c, xs):
                oc, mc, lc = c
                kc, vc, validc = xs
                return _accum_chunk(oc, mc, lc, q, kc, vc, validc, scale), None

            (o, m, l), _ = jax.lax.scan(inner, (o, m, l), (kcs, vcs, validcs))
        k_rot = jax.lax.ppermute(k_blk, axis_name, perm)
        v_rot = jax.lax.ppermute(v_blk, axis_name, perm)
        valid_rot = jax.lax.ppermute(kv_valid, axis_name, perm)
        return (o, m, l, k_rot, v_rot, valid_rot), None

    o0 = jnp.zeros((b, h, tq, dh), jnp.float32)
    m0 = jnp.full((b, h, tq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    (o, m, l, *_), _ = jax.lax.scan(
        block, (o0, m0, l0, k, v, key_valid), None, length=p
    )
    out = jnp.where(l[..., None] > 0, o / jnp.maximum(l[..., None], 1e-30), 0.0)
    return out.astype(q.dtype)


# ------------------------------------------------------------- ring + flash


def _merge_flash(o, lse, o_c, lse_c):
    """Online-softmax merge of two partial attention results.

    Internal convention: ``lse = -inf`` marks "no keys seen yet"; the flash
    kernel marks fully-masked rows with ``+inf``, converted here.  All f32.
    """
    lse_c = jnp.where(jnp.isposinf(lse_c), -jnp.inf, lse_c)
    new = jnp.logaddexp(lse, lse_c)
    # exp(-inf - -inf) = nan: empty-so-far rows contribute weight 0
    w0 = jnp.where(jnp.isneginf(lse), 0.0, jnp.exp(lse - new))
    w1 = jnp.where(jnp.isneginf(lse_c), 0.0, jnp.exp(lse_c - new))
    return o * w0[..., None] + o_c * w1[..., None], new


def _ring_flash_fwd_impl(q, k, v, key_valid, axis_name, block_q, block_k,
                         interpret):
    from tdfo_tpu.ops.pallas_kernels import _flash_fwd_impl

    p = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]
    b, h, tq, dh = q.shape

    def body(carry, _):
        o, lse, k_blk, v_blk, valid = carry
        o_c, lse_c8 = _flash_fwd_impl(q, k_blk, v_blk, valid, block_q,
                                      block_k, interpret, with_lse=True)
        o, lse = _merge_flash(o, lse, o_c.astype(jnp.float32),
                              lse_c8[:, :, 0, :])
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        valid = jax.lax.ppermute(valid, axis_name, perm)
        return (o, lse, k_blk, v_blk, valid), None

    o0 = jnp.zeros((b, h, tq, dh), jnp.float32)
    lse0 = jnp.full((b, h, tq), -jnp.inf, jnp.float32)
    (o, lse, *_), _ = jax.lax.scan(body, (o0, lse0, k, v, key_valid), None,
                                   length=p)
    out = jnp.where(jnp.isneginf(lse)[..., None], 0.0, o).astype(q.dtype)
    # residual convention of the flash backward: +inf = fully-masked row
    lse_res = jnp.where(jnp.isneginf(lse), jnp.inf, lse)
    return out, lse_res


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def ring_flash_attention(
    q: jax.Array,  # [B, H, Tq, Dh] local chunk
    k: jax.Array,
    v: jax.Array,
    key_valid: jax.Array,  # [B, Tk] local chunk validity
    axis_name: str = SEQ_AXIS,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Ring attention with the Pallas flash kernels as the per-step innards.

    The production long-context recipe: T shards over the ring
    (``ppermute`` K/V over ICI) while each ring step's local attention runs
    the blockwise flash kernel (``ops/pallas_kernels``) — no [Tq, Tk] logits
    materialise in either direction.  Forward merges per-chunk
    (out, logsumexp) carries with the online-softmax rule; backward re-rotates
    K/V and runs the FlashAttention-2 recompute kernels per chunk against
    the FINAL logsumexp (which reconstructs exact per-chunk probabilities),
    accumulating dK/dV on the travelling chunks so they arrive home after a
    full lap.  Numerics match :func:`ring_attention` (same online softmax,
    f32 statistics).  Must run inside ``shard_map`` like ring_attention.

    Builders' reading on v5e, round 4 (T=8192, Dh=64, fwd+bwd; no program
    in the tree reproduces it): the XLA ring with ``ring_block_k`` is ~2.4x
    FASTER than this path (4.9 ms vs 11.7 ms) — the FlashAttention-2
    backward pays two probability recomputes (separate dQ and dK/dV
    kernels) where XLA's rematerialised blockwise scan pays one, and XLA
    already pipelines the blockwise forward well.  ``impl="xla"``
    therefore stays the default;
    this path exists for parity with kernel-based stacks and for shapes
    where hand scheduling wins (wider Dh, fused downstream ops).
    """
    out, _ = _ring_flash_fwd_impl(q, k, v, key_valid, axis_name, block_q,
                                  block_k, interpret)
    return out


def _ring_flash_fwd(q, k, v, key_valid, axis_name, block_q, block_k, interpret):
    out, lse = _ring_flash_fwd_impl(q, k, v, key_valid, axis_name, block_q,
                                    block_k, interpret)
    return out, (q, k, v, key_valid, out, lse)


def _ring_flash_bwd(axis_name, block_q, block_k, interpret, res, g):
    from tdfo_tpu.ops.pallas_kernels import _flash_bwd_impl

    q, k, v, key_valid, out, lse = res
    p = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]
    b, h, tq, _ = q.shape
    lse8 = jnp.broadcast_to(lse[:, :, None, :], (b, h, 8, tq))

    def body(carry, _):
        dq, k_blk, v_blk, valid, dk, dv = carry
        dq_c, dk_c, dv_c = _flash_bwd_impl(
            q, k_blk, v_blk, valid, out, lse8, g, block_q, block_k, interpret
        )
        dq = dq + dq_c.astype(jnp.float32)
        dk = dk + dk_c.astype(jnp.float32)
        dv = dv + dv_c.astype(jnp.float32)
        # dK/dV ride along with their chunk: after the full lap each
        # accumulator is back at its owner with every device's contribution
        k_blk, v_blk, valid, dk, dv = (
            jax.lax.ppermute(x, axis_name, perm)
            for x in (k_blk, v_blk, valid, dk, dv)
        )
        return (dq, k_blk, v_blk, valid, dk, dv), None

    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq0 = jnp.zeros(q.shape, jnp.float32)
    (dq, _, _, _, dk, dv), _ = jax.lax.scan(
        body, (dq0, k, v, key_valid, dk0, dv0), None, length=p
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None


ring_flash_attention.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_self_attention(
    mesh: Mesh,
    q: jax.Array,  # [B, H, T, Dh] global
    k: jax.Array,
    v: jax.Array,
    key_valid: jax.Array | None = None,  # [B, T] global
    *,
    axis: str = SEQ_AXIS,
    block_k: int | None = None,
    head_axis: str | None = None,
    batch_axis: str | None = None,
    impl: str = "xla",
) -> jax.Array:
    """shard_map wrapper: shards T over ``axis``, runs the ring, returns the
    global [B, H, T, Dh] result.  T must divide by the axis size.

    ``head_axis``: additionally shard heads over that mesh axis — how ring
    sequence parallelism COMPOSES with Megatron attention TP
    (``megatron_tp_rule``): the per-shard program just sees fewer heads.
    ``batch_axis``: keep the batch sharded (e.g. over ``data``) instead of
    letting the shard_map gather it; skipped automatically when the trace's
    batch (model init uses B=1) does not divide the axis.
    ``impl``: "xla" = :func:`ring_attention` (blockwise XLA innards,
    ``block_k`` chunking — the faster path on v5e, see
    :func:`ring_flash_attention`'s measured comparison); "flash" =
    :func:`ring_flash_attention` (Pallas flash kernels inside each ring
    step).
    """
    t = q.shape[2]
    n = mesh.shape[axis]
    if t % n:
        raise ValueError(f"sequence length {t} not divisible by seq axis {n}")
    h_ax = head_axis
    if h_ax is not None and q.shape[1] % mesh.shape[h_ax]:
        raise ValueError(
            f"heads {q.shape[1]} not divisible by {h_ax!r} axis "
            f"{mesh.shape[h_ax]} (ring + head parallelism)"
        )
    b_ax = batch_axis
    if b_ax is not None and (mesh.shape[b_ax] <= 1
                             or q.shape[0] % mesh.shape[b_ax]):
        b_ax = None  # init-time dummies (B=1) and odd batches stay gathered
    qkv_spec = P(b_ax, h_ax, axis, None)
    valid_spec = P(b_ax, axis)
    if impl == "flash":
        how = pallas_impl("ring_flash_attention", mesh_platform(mesh),
                          off_chip="interpret")
        fn = partial(ring_flash_attention, axis_name=axis,
                     interpret=how == "interpret")
    elif impl == "xla":
        fn = partial(ring_attention, axis_name=axis, block_k=block_k)
    else:
        raise ValueError(f"unknown ring impl {impl!r}")
    if key_valid is None:
        key_valid = jnp.ones((q.shape[0], t), bool)
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, valid_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v, key_valid)


def make_ring_attn_fn(mesh: Mesh, axis: str = SEQ_AXIS,
                      block_k: int | None = None,
                      head_axis: str | None = None,
                      batch_axis: str | None = None,
                      impl: str = "xla"):
    """Adapter matching the ``attn_fn(q, k, v, mask)`` contract of
    :class:`~tdfo_tpu.models.transformer.MultiHeadAttention`, so any
    transformer block (Bert4Rec included) switches to sequence parallelism by
    construction-time injection.  ``mask`` must be a key-padding mask
    broadcastable from [B, 1, 1, T] (query-dependent masks need the
    per-shard API).  ``head_axis`` composes the ring with Megatron attention
    TP; ``batch_axis`` keeps data-sharded batches sharded."""

    def attn_fn(q, k, v, mask=None):
        key_valid = None
        if mask is not None:
            if mask.shape[1] != 1 or mask.shape[2] != 1:
                raise ValueError(
                    "ring attn_fn supports key-padding masks [B,1,1,T] only"
                )
            key_valid = mask[:, 0, 0, :]
        return ring_self_attention(mesh, q, k, v, key_valid, axis=axis,
                                   block_k=block_k, head_axis=head_axis,
                                   batch_axis=batch_axis, impl=impl)

    return attn_fn
