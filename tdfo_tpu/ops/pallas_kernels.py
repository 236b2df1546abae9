"""Pallas TPU kernels for the framework's hot ops.

Two kernels where hand-scheduling beats XLA's default lowering; everything
else (plain gathers, ``jagged_to_dense`` — a single fused gather,
``tdfo_tpu/data/jagged.py``) is left to XLA on purpose, which already tiles
those well.

  * :func:`flash_attention` — blockwise attention with an online softmax:
    O(T) memory per query tile instead of the O(T²) logits matrix, VMEM-tiled
    for the MXU.  The single-device complement of ring attention
    (``tdfo_tpu/parallel/ring_attention.py``): ring shards T across chips,
    this kernel keeps each chip's block from materialising its local logits.
    Forward AND backward are Pallas kernels (FlashAttention-2 recompute: the
    forward saves only the per-row logsumexp; the backward rebuilds each
    probability tile from (q, k, lse) on the fly), so training at long T
    never materialises [T, T] in either direction.
  * :func:`fat_line_update` — the fused in-backward embedding-optimizer
    update (fbgemm ``EmbOptimType`` parity for adam / sgd / adagrad /
    rowwise_adagrad, ``torchrec/train.py:187-195``) over the framework's
    *fat line* storage layout (:func:`line_layout`: R vocab rows of
    ``[table | optimizer state]`` packed per 128-lane line).  The kernel
    streams the touched lines HBM->VMEM with per-line async DMAs, applies
    the optimizer math on the packed lanes, and DMA-writes the lines back
    IN PLACE (``input_output_aliases``) — measured faster than even a
    single XLA scatter call on v5e, and it replaces a gather + compute +
    2-3 scatters.  The layout exists because Mosaic requires DMA slices
    lane-aligned to 128: separate narrow [V, d] table/state buffers cannot
    be row-DMA'd at all (a kernel attempting that fails to compile on
    hardware), while one packed line is a single aligned descriptor per
    direction covering up to R rows.

Both take ``interpret=`` for CPU-exact testing (the suite runs them in
interpreter mode on the spoofed CPU mesh).  The compiled path is held by
``tests/test_chip_compile.py`` (compiled for a described v5e, nothing runs)
and run on the chip by ``chip_smoke.py``'s ``kernels`` phase.  Callers pick
kernel / interpret / XLA through ``core/mesh.pallas_impl``, from the platform
of the devices the arrays live on.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tdfo_tpu.ops.quant import (
    bytes_to_f32, dequantize_rows, f32_to_bytes, quantize_rows)

__all__ = [
    "flash_attention",
    "LineLayout",
    "line_layout",
    "fat_line_update",
    "fat_line_update_routed",
    "fat_view",
    "fat_gather_rows",
    "fat_pack",
    "fat_unpack",
]

_NEG_INF = float(jnp.finfo(jnp.float32).min)
_LANE = 128  # Mosaic lane tile


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------


def _flash_kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, lse_ref=None, *, block_k: int, scale: float):
    """One (batch*head, q-tile) grid step: stream K/V tiles, online softmax.
    Also emits the per-row logsumexp (the FlashAttention-2 backward residual;
    +inf marks fully-masked rows so the backward's exp() yields 0 there)."""
    bq, dh = q_ref.shape
    t = k_ref.shape[0]
    q = q_ref[:]  # input dtype (bf16 on TPU): MXU-native, f32 accumulation

    def body(kt, carry):
        acc, m, l = carry
        k_blk = k_ref[pl.ds(kt * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kt * block_k, block_k), :]
        # DEFAULT precision is INTENDED on the flash dots (bf16 operands on
        # the MXU); stated explicitly because the quality gate rejects
        # precision-less dot_general in this file
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )  # [BQ, BK] f32
        valid = valid_ref[0, pl.ds(kt * block_k, block_k)] > 0  # [BK]
        s = jnp.where(valid[None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        shift = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - shift)
        p = jnp.where(valid[None, :], p, 0.0)
        corr = jnp.where(m <= _NEG_INF / 2, 0.0, jnp.exp(m - shift))
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, dh), jnp.float32)
    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, t // block_k, body, (acc0, m0, l0))
    o_ref[:] = jnp.where(l > 0, acc / jnp.maximum(l, 1e-30), 0.0).astype(o_ref.dtype)
    if lse_ref is not None:  # training path only; inference skips the write
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)
        # 8-sublane broadcast layout (like the validity mask): a [T, 1]
        # output would lane-pad 128x and OOM vmem at long T
        lse_ref[:] = jnp.broadcast_to(lse[:, 0][None, :], (8, bq))


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6)
)
def flash_attention(
    q: jax.Array,  # [B, H, T, Dh]
    k: jax.Array,
    v: jax.Array,
    key_valid: jax.Array | None = None,  # [B, T] True = attend
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    # 512-blocks measured fastest on v5e at T=4096 (fwd+bwd 6.7 ms vs 7.9 ms
    # for the [T,T]-materialising XLA formulation); blocks clip to short T
    # inference path: no logsumexp residual is computed or written
    return _flash_fwd_impl(q, k, v, key_valid, block_q, block_k, interpret,
                           with_lse=False)[0]


def _clip_blocks(block_q, block_k, t, interpret):
    # Blocks clip to a short T but stay whole tiles.  The compiled kernels
    # slice the [8, T] mask / lse / delta rows along LANES at block starts, so
    # Mosaic needs every block a multiple of 128 (T=20 pads to one 128-block;
    # 16-wide blocks are refused: "cannot statically prove that index ... is
    # a multiple of 128").  Interpret mode (CPU tests) only needs the 8-row
    # sublane tile, which keeps small-T multi-block cases cheap to test.
    tile = 8 if interpret else _LANE
    return (max(tile, min(block_q, t) // tile * tile),
            max(tile, min(block_k, t) // tile * tile))


def _pad_t(t, block_q, block_k):
    import math

    block = math.lcm(block_q, block_k)
    return -(-t // block) * block


def _flash_fwd_impl(q, k, v, key_valid, block_q, block_k, interpret,
                    with_lse: bool = True):
    b, h, t, dh = q.shape
    if key_valid is None:
        key_valid = jnp.ones((b, t), bool)
    block_q, block_k = _clip_blocks(block_q, block_k, t, interpret)
    if t % block_q or t % block_k:
        # pad T up to a multiple of BOTH blocks (lcm, so the recursive call
        # terminates): padded keys are masked out, padded query rows sliced
        pad = _pad_t(t, block_q, block_k) - t
        out_p, lse_p = _flash_fwd_impl(
            jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))),
            jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))),
            jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))),
            jnp.pad(key_valid, ((0, 0), (0, pad))),
            block_q, block_k, interpret, with_lse,
        )
        return out_p[:, :, :t, :], (lse_p[:, :, :, :t] if with_lse else None)
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, scale=1.0 / (dh**0.5)
    )
    # grid (b, h, q-tiles) keeps every index map affine (Mosaic rejects the
    # div/rem a flattened batch*head axis would need for the mask row).
    out = pl.pallas_call(
        kernel,
        grid=(b, h, t // block_q),
        in_specs=[
            # mask broadcast to 8 sublanes per batch row: Mosaic requires the
            # trailing block dims to tile (8, 128); kernel reads row 0
            pl.BlockSpec((None, 8, t), lambda bi, hi, qi: (bi, 0, 0)),
            pl.BlockSpec((None, None, block_q, dh), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, t, dh), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, t, dh), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, None, block_q, dh), lambda bi, hi, qi: (bi, hi, qi, 0)
            ),
        ] + ([
            # [B, H, 8, T] sublane-broadcast lse (tileable, no lane padding)
            pl.BlockSpec((None, None, 8, block_q), lambda bi, hi, qi: (bi, hi, 0, qi)),
        ] if with_lse else []),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, dh), q.dtype),
        ] + ([jax.ShapeDtypeStruct((b, h, 8, t), jnp.float32)] if with_lse else []),
        interpret=interpret,
        name="flash_fwd",
    )(
        jnp.broadcast_to(key_valid.astype(jnp.float32)[:, None, :], (b, 8, t)),
        q, k, v,
    )
    if with_lse:
        out, lse = out
        return out, lse
    return out[0], None


def _xla_attention(q, k, v, key_valid):
    s = jnp.einsum("bhtd,bhsd->bhts", q, k).astype(jnp.float32) / (q.shape[-1] ** 0.5)
    if key_valid is not None:
        s = jnp.where(key_valid[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if key_valid is not None:
        # fully-masked rows: softmax over all -inf is uniform garbage; zero it
        any_valid = key_valid.any(axis=-1)[:, None, None, None]
        p = jnp.where(any_valid, p, 0.0)
    return jnp.einsum("bhts,bhsd->bhtd", p.astype(v.dtype), v)


# ---------------------------------------------------------- flash backward


def _flash_bwd_dq_kernel(valid_ref, lse_ref, delta_ref, q_ref, k_ref, v_ref,
                         do_ref, dq_ref, *, block_k: int, scale: float):
    """dQ for one q-tile: stream K/V tiles, recompute P from q, k and the
    saved logsumexp — no [T, T] buffer ever exists."""
    bq, dh = q_ref.shape
    t = k_ref.shape[0]
    qi = pl.program_id(2)
    q = q_ref[:]
    do = do_ref[:]
    # lse/delta ride the same broadcast-to-8-sublanes layout as the validity
    # mask: a [T, 1] block would lane-pad 128x and blow VMEM at long T
    lse = lse_ref[0, pl.ds(qi * bq, bq)].astype(jnp.float32)[:, None]
    delta = delta_ref[0, pl.ds(qi * bq, bq)].astype(jnp.float32)[:, None]

    def body(kt, acc):
        k_blk = k_ref[pl.ds(kt * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kt * block_k, block_k), :]
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )
        valid = valid_ref[0, pl.ds(kt * block_k, block_k)] > 0
        # p = softmax prob reconstructed; exp(-inf)=0 kills masked keys and
        # fully-masked rows (lse = +inf) alike
        p = jnp.exp(jnp.where(valid[None, :], s, _NEG_INF) - lse)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )
        ds = (p * (dp - delta)).astype(k_blk.dtype)
        return acc + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )

    acc = jax.lax.fori_loop(0, t // block_k, body, jnp.zeros((bq, dh), jnp.float32))
    dq_ref[:] = (scale * acc).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(valid_ref, lse_ref, delta_ref, q_ref, k_ref, v_ref,
                          do_ref, dk_ref, dv_ref, *, block_q: int, scale: float):
    """dK/dV for one k-tile: stream q-tiles, same recompute trick."""
    bk, dh = k_ref.shape
    t = q_ref.shape[0]
    k_blk = k_ref[:]
    v_blk = v_ref[:]
    valid = valid_ref[0, pl.ds(0, bk)] > 0  # this tile's key validity
    # valid_ref block is the k-tile slice (see in_specs): full row of length bk

    def body(qt, carry):
        dk_acc, dv_acc = carry
        q_blk = q_ref[pl.ds(qt * block_q, block_q), :]
        do_blk = do_ref[pl.ds(qt * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(qt * block_q, block_q)].astype(jnp.float32)[:, None]
        delta = delta_ref[0, pl.ds(qt * block_q, block_q)].astype(jnp.float32)[:, None]
        s = scale * jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )  # [BQ, BK]
        p = jnp.exp(jnp.where(valid[None, :], s, _NEG_INF) - lse)
        dv_acc = dv_acc + jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )  # [BK, Dh]
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )  # [BQ, BK]
        ds = (p * (dp - delta)).astype(q_blk.dtype)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )  # [BK, Dh]
        return dk_acc, dv_acc

    z = jnp.zeros((bk, dh), jnp.float32)
    dk_acc, dv_acc = jax.lax.fori_loop(0, t // block_q, body, (z, z))
    dk_ref[:] = (scale * dk_acc).astype(dk_ref.dtype)
    dv_ref[:] = dv_acc.astype(dv_ref.dtype)


def _flash_bwd_impl(q, k, v, key_valid, out, lse, g, block_q, block_k, interpret):
    b, h, t, dh = q.shape
    block_q, block_k = _clip_blocks(block_q, block_k, t, interpret)
    if t % block_q or t % block_k:
        pad = _pad_t(t, block_q, block_k) - t
        padt = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dq, dk, dv = _flash_bwd_impl(
            padt(q), padt(k), padt(v),
            jnp.pad(key_valid, ((0, 0), (0, pad))),
            padt(out),
            # padded q rows: lse=+inf marks them fully masked -> zero grads
            jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, pad)),
                    constant_values=jnp.inf),
            padt(g),
            block_q, block_k, interpret,
        )
        return dq[:, :, :t], dk[:, :, :t], dv[:, :, :t]

    # delta = rowsum(dO * O): O(T Dh) in XLA, the only non-kernel piece
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    scale = 1.0 / (dh**0.5)
    mask8 = jnp.broadcast_to(key_valid.astype(jnp.float32)[:, None, :], (b, 8, t))
    # lse already arrives in the [B, H, 8, T] sublane-broadcast layout
    delta8 = jnp.broadcast_to(delta[:, :, None, :], (b, h, 8, t))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k, scale=scale),
        grid=(b, h, t // block_q),
        in_specs=[
            pl.BlockSpec((None, 8, t), lambda bi, hi, qi: (bi, 0, 0)),
            pl.BlockSpec((None, None, 8, t), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, 8, t), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, block_q, dh), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, t, dh), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, t, dh), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, block_q, dh), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, None, block_q, dh), lambda bi, hi, qi: (bi, hi, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(mask8, lse, delta8, q, k, v, g)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q, scale=scale),
        grid=(b, h, t // block_k),
        in_specs=[
            # the k-tile's slice of the validity row
            pl.BlockSpec((None, 8, block_k), lambda bi, hi, ki: (bi, 0, ki)),
            pl.BlockSpec((None, None, 8, t), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, 8, t), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, t, dh), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, block_k, dh), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block_k, dh), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, t, dh), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_k, dh), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block_k, dh), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(mask8, lse, delta8, q, k, v, g)
    return dq, dk, dv


def _flash_fwd(block_q, block_k, interpret, q, k, v, key_valid):
    out, lse = _flash_fwd_impl(q, k, v, key_valid, block_q, block_k, interpret)
    return out, (q, k, v, key_valid, out, lse)


def _flash_bwd(block_q, block_k, interpret, res, g):
    """O(T)-memory recompute backward (FlashAttention-2): two Pallas kernels
    rebuild each probability tile from (q, k, lse) on the fly — the [T, T]
    matrix the old XLA recompute materialised never exists."""
    q, k, v, key_valid = res[:4]
    out, lse = res[4], res[5]
    if key_valid is None:
        key_valid = jnp.ones((q.shape[0], q.shape[2]), bool)
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, key_valid, out, lse, g, block_q, block_k, interpret
    )
    return dq, dk, dv, None


flash_attention.defvjp(
    lambda q, k, v, key_valid, block_q, block_k, interpret: _flash_fwd(
        block_q, block_k, interpret, q, k, v, key_valid
    ),
    lambda block_q, block_k, interpret, res, g: _flash_bwd(
        block_q, block_k, interpret, res, g
    ),
)


# --------------------------------------------------------------------------
# fused row-sparse optimizers over packed fat lines
# --------------------------------------------------------------------------
#
# fbgemm TBE parity for ALL EmbOptimType kinds the reference exercises
# (ADAM on GPU, SGD on CPU, torchrec/train.py:187-195; EXACT_ADAGRAD /
# EXACT_ROWWISE_ADAGRAD are fbgemm's huge-table variants): a table plus its
# per-row optimizer state live interleaved in "fat lines" — [L, T, 128] f32
# where each 128-lane line packs R vocab rows of W lanes each
# ([table(d) | state] per row, W a divisor of 128 so R = 128 // W, or a
# multiple of 128 with R = 1 for wide rows).  The 3D shape is load-bearing:
# Mosaic tiles the trailing TWO dims, so per-LINE DMA (dim-0 slices of 1)
# is always legal, while separate narrow [V, d] buffers cannot be row-DMA'd
# at all.  Because R * W == T * 128 exactly, the line array reshapes
# CONTIGUOUSLY to a [L*R, W] row view — lookups gather full W-lane rows
# (fast) and slice [:d]; no copy, and GSPMD sharding on dim 0 propagates
# through the reshape.
#
# Packing R rows per line is what keeps memory near the plain-table
# footprint: rowwise-adagrad at d=16 needs 17 lanes -> W=32, R=4, i.e.
# 128 B/row — a one-row-per-line [V, 1, 128] layout would cost 512 B/row
# (17 GB for the 33.7M-row Criteo stack, an OOM on v5e).

_SLOT_WIDTHS = (8, 16, 32, 64, 128)

# optimizer-state lanes per vocab row, after the d table lanes
_STATE_LANES = {
    "sgd": lambda d: 0,
    "rowwise_adagrad": lambda d: 1,   # ONE f32 accumulator cell per row
    "adagrad": lambda d: d,           # per-element squared-grad accumulator
    "adam": lambda d: 2 * d,          # mu | nu moments
}


@dataclass(frozen=True)
class LineLayout:
    """Static description of a packed fat-line table for (d, kind, dtype).

    ``dtype == "int8"`` describes the BYTE-container line: an int8 [L, T,
    128] array whose per-row slot packs ``[d code bytes | 8 sidecar bytes
    (bitcast f32 scale, offset) | 4 bytes per f32 state lane]``.  Only the
    d table lanes are quantized — the rowwise (scale, offset) pair and the
    optimizer state ride as EXACT f32 bit patterns, so fused-int8 state math
    is bit-identical to the plain-int8 (f32 slots) reference."""

    d: int
    kind: str
    w: int      # lanes per vocab row (slot width): [table(d) | state | pad]
    r: int      # vocab rows per line (r * w == tiles * 128)
    tiles: int  # trailing [tiles, 128] shape per line
    dtype: str = "float32"

    @property
    def state_lanes(self) -> int:
        return _STATE_LANES[self.kind](self.d)

    @property
    def need(self) -> int:
        if self.dtype == "int8":
            # codes + bitcast f32 (scale, offset) + bitcast f32 state
            return self.d + 8 + 4 * self.state_lanes
        return self.d + self.state_lanes

    def n_lines(self, rows: int) -> int:
        return -(-rows // self.r)

    def padded_rows(self, rows: int) -> int:
        return self.n_lines(rows) * self.r


def line_layout(d: int, kind: str, dtype="float32") -> LineLayout:
    if kind not in _STATE_LANES:
        raise ValueError(f"unknown fused optimizer kind: {kind!r}")
    dt = jnp.dtype(dtype)
    if dt == jnp.int8:
        if kind == "rowwise_adagrad":
            raise ValueError(
                "fused int8 storage does not support rowwise_adagrad: the "
                "f32 per-row accumulator contract cannot ride a quantized "
                "line (use optimizer = adagrad/adam/sgd, or fused = false)")
        need = d + 8 + 4 * _STATE_LANES[kind](d)
        if need <= _LANE:
            w = next(s for s in _SLOT_WIDTHS if s >= need)
            return LineLayout(d, kind, w, _LANE // w, 1, "int8")
        tiles = -(-need // _LANE)
        return LineLayout(d, kind, tiles * _LANE, 1, tiles, "int8")
    need = d + _STATE_LANES[kind](d)
    if need <= _LANE:
        w = next(s for s in _SLOT_WIDTHS if s >= need)
        return LineLayout(d, kind, w, _LANE // w, 1)
    tiles = -(-need // _LANE)
    return LineLayout(d, kind, tiles * _LANE, 1, tiles)


def fat_view(fat: jax.Array, layout: LineLayout) -> jax.Array:
    """[L, T, 128] lines -> [L*R, W] per-vocab-row view (contiguous
    reshape).  HOST/CPU-side helper (unpack, XLA fallbacks, tests): on TPU
    the tiled physical layouts of the two shapes differ, so this reshape
    MATERIALISES a copy of the whole table (measured ~10 ms at the Criteo
    profile) — device paths must use :func:`fat_gather_rows` instead."""
    return fat.reshape(fat.shape[0] * layout.r, layout.w)


def fat_gather_rows(fat: jax.Array, ids: jax.Array, layout: LineLayout) -> jax.Array:
    """Gather table rows from packed lines WITHOUT reshaping the table:
    full-line gather on dim 0 of the 3D array (the fast TPU pattern — one
    512B descriptor per id), then slot-select the table lanes on the small
    gathered result with R static slices + selects.  ids may be any shape;
    output gains a trailing ``d`` axis.  Out-of-contract ids clamp to row 0
    (low) / the last line (high), matching the plain-table ``jnp.take``
    clip every other lookup path uses."""
    ids = jnp.maximum(ids, 0)
    lines = jnp.take(fat, ids // layout.r, axis=0)  # [..., T, 128]
    if layout.dtype == "int8":
        # slot-select codes AND the adjacent 8 sidecar bytes, then decode
        # on the small gathered block (the table itself stays byte-packed)
        span = layout.d + 8
        flat = lines.reshape(*lines.shape[:-2], layout.tiles * _LANE)
        out = flat[..., :span]
        if layout.r > 1:
            slot = ids % layout.r
            for s in range(1, layout.r):
                piece = flat[..., s * layout.w: s * layout.w + span]
                out = jnp.where((slot == s)[..., None], piece, out)
        codes = out[..., : layout.d]
        qs = bytes_to_f32(out[..., layout.d: span])
        return dequantize_rows(codes, qs)
    if layout.r == 1 and layout.d <= _LANE:
        # table lanes live wholly in tile 0: slice without the flattening
        # reshape (which costs a relayout of the gathered block)
        return lines[..., 0, :layout.d]
    flat = lines.reshape(*lines.shape[:-2], layout.tiles * _LANE)
    out = flat[..., : layout.d]
    if layout.r == 1:
        return out
    slot = ids % layout.r
    for s in range(1, layout.r):
        piece = flat[..., s * layout.w: s * layout.w + layout.d]
        out = jnp.where((slot == s)[..., None], piece, out)
    return out


def fat_pack(table: jax.Array, *state: jax.Array, kind: str = "adam",
             layout: LineLayout | None = None, dtype=None,
             qscale: jax.Array | None = None) -> jax.Array:
    """[V, d] table (+ per-kind optimizer state) -> [L, T, 128] fat lines.

    State arguments by kind: adam ``(mu[V,d], nu[V,d])``; adagrad
    ``(accum[V,d],)``; rowwise_adagrad ``(accum[V],)``; sgd none.  Missing
    state defaults to zeros (fresh init).  Padding rows/lanes are zero.

    ``dtype`` is the STORAGE dtype of the packed lines (default: the
    table's own dtype).  Fat lines interleave table and state lanes in one
    buffer, so the whole line shares it — a bf16 line halves the DMA bytes
    but packs the optimizer state at bf16 too, which is why fused
    rowwise_adagrad (f32-per-row accumulator contract) rejects bf16
    upstream (``parallel/embedding.py``).

    ``dtype == int8`` builds the byte-container line (:class:`LineLayout`):
    an f32 ``table`` is rowwise-quantized here (round-to-nearest, the same
    grid plain-int8 init uses); an int8 ``table`` of codes requires its
    ``qscale`` f32 [V, 2] sidecar.  State must be f32 — it rides as exact
    bit patterns, never quantized.
    """
    v, d = table.shape
    dt = jnp.dtype(dtype) if dtype is not None else table.dtype
    lay = layout or line_layout(d, kind, dt)
    want = {"sgd": 0, "rowwise_adagrad": 1, "adagrad": 1, "adam": 2}[lay.kind]
    if state and len(state) != want:
        raise ValueError(f"{lay.kind} fat_pack takes {want} state arrays")
    if dt == jnp.int8:
        if jnp.dtype(table.dtype) == jnp.int8:
            if qscale is None:
                raise ValueError(
                    "fat_pack of int8 codes needs the f32 (scale, offset) "
                    "qscale sidecar")
            codes, qs = table, qscale.astype(jnp.float32)
        else:
            codes, qs = quantize_rows(table.astype(jnp.float32))
        comps = [codes, f32_to_bytes(qs)]
        if lay.kind == "adagrad":
            acc = state[0] if state else jnp.zeros((v, d), jnp.float32)
            comps.append(f32_to_bytes(acc.astype(jnp.float32)))
        elif lay.kind == "adam":
            mu = state[0] if state else jnp.zeros((v, d), jnp.float32)
            nu = state[1] if len(state) > 1 else jnp.zeros((v, d), jnp.float32)
            comps += [f32_to_bytes(mu.astype(jnp.float32)),
                      f32_to_bytes(nu.astype(jnp.float32))]
        if lay.w > lay.need:
            comps.append(jnp.zeros((v, lay.w - lay.need), codes.dtype))
        rows = jnp.concatenate(comps, axis=1)
        pad = lay.padded_rows(v) - v
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        return rows.reshape(-1, lay.tiles, _LANE)
    comps = [table.astype(dt)]
    if lay.kind == "rowwise_adagrad":
        acc = state[0] if state else jnp.zeros((v,), dt)
        comps.append(acc.astype(dt)[:, None])
    elif lay.kind == "adagrad":
        acc = state[0] if state else jnp.zeros((v, d), dt)
        comps.append(acc.astype(dt))
    elif lay.kind == "adam":
        mu = state[0] if state else jnp.zeros((v, d), dt)
        nu = state[1] if len(state) > 1 else jnp.zeros((v, d), dt)
        comps += [mu.astype(dt), nu.astype(dt)]
    if lay.w > lay.need:
        comps.append(jnp.zeros((v, lay.w - lay.need), dt))
    rows = comps[0] if len(comps) == 1 else jnp.concatenate(comps, axis=1)
    pad = lay.padded_rows(v) - v
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    return rows.reshape(-1, lay.tiles, _LANE)


def fat_unpack(fat: jax.Array, layout: LineLayout,
               rows: int | None = None) -> tuple[jax.Array, ...]:
    """Inverse of :func:`fat_pack`: ``(table[V,d], *state)``.  int8 lines
    return ``(codes[V,d] int8, qscale[V,2] f32, *state f32)`` — the same
    (codes, sidecar) pair the plain-int8 layout stores in two arrays."""
    view = fat_view(fat, layout)
    if rows is not None:
        view = view[:rows]
    d = layout.d
    table = view[:, :d]
    if layout.dtype == "int8":
        qs = bytes_to_f32(view[:, d:d + 8])
        if layout.kind == "sgd":
            return table, qs
        if layout.kind == "adagrad":
            return table, qs, bytes_to_f32(view[:, d + 8:d + 8 + 4 * d])
        return (table, qs,
                bytes_to_f32(view[:, d + 8:d + 8 + 4 * d]),
                bytes_to_f32(view[:, d + 8 + 4 * d:d + 8 + 8 * d]))
    if layout.kind == "sgd":
        return (table,)
    if layout.kind == "rowwise_adagrad":
        return table, view[:, d]
    if layout.kind == "adagrad":
        return table, view[:, d:2 * d]
    return table, view[:, d:2 * d], view[:, 2 * d:3 * d]


def _lane_map(xs, pred, layout, rows: int):
    """Per-slot lane rearrangement as tiny constant matmuls.

    ``xs``: per-tile [rows, 128] f32 vectors.  ``pred(gi, go) -> bool`` over
    GLOBAL source/dest lane indices (works on numpy at trace time to skip
    all-zero blocks, and on Mosaic iotas to materialise the 0/1 matrix
    in-kernel — no big array constants, no unaligned lane slicing).  Returns
    per-tile outputs ``out[go] = sum_gi x[gi] * pred(gi, go)``: each output
    row depends only on the same scratch row, so sentinel-row garbage never
    crosses rows.  The [128,128] f32 dots are ~us-scale noise next to the
    row DMAs.
    """
    import numpy as np

    t_tiles = layout.tiles
    outs = []
    for s in range(t_tiles):
        acc = None
        for t in range(t_tiles):
            gi_np = np.arange(_LANE)[:, None] + t * _LANE
            go_np = np.arange(_LANE)[None, :] + s * _LANE
            if not np.asarray(pred(gi_np, go_np)).any():
                continue
            gi = jax.lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 0) + t * _LANE
            go = jax.lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 1) + s * _LANE
            b = pred(gi, go).astype(jnp.float32)
            # HIGHEST precision: the default TPU f32 dot runs bf16 passes
            # (~1e-3 relative error), which would leak into optimizer state
            contrib = jax.lax.dot_general(
                xs[t], b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            acc = contrib if acc is None else acc + contrib
        outs.append(acc if acc is not None else jnp.zeros((rows, _LANE), jnp.float32))
    return outs


def _line_math(x, gp, tl, corr, layout: LineLayout, *, lr, b1, b2, eps,
               weight_decay):
    """One optimizer step on packed lines.

    ``x``: [rows, T, 128] current lines; ``gp``: summed row grads packed at
    table lanes (zero elsewhere); ``tl``: 1.0 on every lane of touched slots;
    ``corr``: [2] adam bias corrections.  All lane bookkeeping is mask /
    matmul arithmetic (Mosaic-safe at ANY slot width); per-row semantics are
    bit-compatible with the XLA row formulations in ``ops.sparse`` (same
    order of operations; the only divergence is matmul vs reduce summation
    order in cross-lane sums).

    ``x`` may arrive at the narrow STORAGE dtype (bf16 fat lines); all math
    runs f32 — the widening below is an identity op for f32 inputs, and the
    caller requantizes the returned f32 block (:func:`_sr_writeback`).
    """
    t_tiles, w, d, kind = layout.tiles, layout.w, layout.d, layout.kind
    rows = x.shape[0]
    wd = weight_decay
    xs = [x[:, t, :].astype(jnp.float32) for t in range(t_tiles)]
    # gp/tl accept per-tile LISTS (kernel paths that build them in VMEM)
    gs = gp if isinstance(gp, list) else [gp[:, t, :].astype(jnp.float32)
                                         for t in range(t_tiles)]
    ts = tl if isinstance(tl, list) else [tl[:, t, :].astype(jnp.float32)
                                          for t in range(t_tiles)]

    if kind == "adam" and layout.r == 1 and d % 64 == 0:
        # fast path for the R=1 64-aligned layouts (e.g. the twotower d=64
        # config): component boundaries are 64-lane-aligned, so direct
        # static slices replace the lane-map matmuls (~0.3 ms off the
        # headline step), and with one row per line every valid line IS
        # touched — the write-skip on sentinel lines subsumes ``tl``.
        def take_lanes(vecs, a, b):
            out = []
            for t in range(t_tiles):
                lo, hi = max(a, t * _LANE), min(b, (t + 1) * _LANE)
                if lo < hi:
                    out.append(vecs[t][:, lo - t * _LANE:hi - t * _LANE])
            return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)

        row = take_lanes(xs, 0, d)
        mu_r = take_lanes(xs, d, 2 * d)
        nu_r = take_lanes(xs, 2 * d, 3 * d)
        g = take_lanes(gs, 0, d)
        mu_n = b1 * mu_r + (1 - b1) * g
        nu_n = b2 * nu_r + (1 - b2) * g * g
        delta = lr * ((mu_n / corr[0]) / (jnp.sqrt(nu_n / corr[1]) + eps)
                      + wd * row)
        comps = ((0, row - delta), (d, mu_n), (2 * d, nu_n))
        # assemble each 128-lane tile from the component pieces that fall in
        # it (concatenating a full 3d-wide row first trips Mosaic's
        # offset-tracking on the non-concat dim)
        tiles = []
        for t in range(t_tiles):
            segs, lane = [], t * _LANE
            while lane < (t + 1) * _LANE:
                for off, comp in comps:
                    if off <= lane < off + d:
                        take = min(off + d, (t + 1) * _LANE) - lane
                        segs.append(comp[:, lane - off:lane - off + take])
                        break
                else:  # padding lanes: preserve current contents
                    take = (t + 1) * _LANE - lane
                    segs.append(xs[t][:, lane - t * _LANE:])
                lane += take
            tiles.append(segs[0] if len(segs) == 1
                         else jnp.concatenate(segs, axis=1))
        return jnp.stack(tiles, axis=1)

    def lanes(t):  # [rows, 128] global lane index
        return jax.lax.broadcasted_iota(jnp.int32, (rows, _LANE), 1) + t * _LANE

    within = [lanes(t) % w for t in range(t_tiles)]
    is_table = [wt < d for wt in within]

    if kind == "sgd":
        new = [
            xs[t] - jnp.where(is_table[t], ts[t] * (lr * (gs[t] + wd * xs[t])), 0.0)
            for t in range(t_tiles)
        ]
        return jnp.stack(new, axis=1)

    if kind in ("rowwise_adagrad", "adagrad"):
        geff = [
            jnp.where(is_table[t], (gs[t] + wd * xs[t]) * ts[t], 0.0)
            for t in range(t_tiles)
        ]
        sq = [g * g for g in geff]
        if kind == "rowwise_adagrad":
            is_state = [wt == d for wt in within]
            accg = _lane_map(
                sq,
                lambda gi, go: ((gi // w) == (go // w)) & ((gi % w) < d)
                & ((go % w) == d),
                layout, rows,
            )
            accg = [a * (1.0 / d) for a in accg]  # sum -> mean, scale after
        else:
            is_state = [(wt >= d) & (wt < 2 * d) for wt in within]
            accg = _lane_map(
                sq, lambda gi, go: (go == gi + d) & ((gi % w) < d), layout, rows
            )
        acc_new = [xs[t] + accg[t] for t in range(t_tiles)]
        acc_masked = [jnp.where(is_state[t], acc_new[t], 0.0) for t in range(t_tiles)]
        if kind == "rowwise_adagrad":
            denom = _lane_map(
                acc_masked,
                lambda gi, go: ((gi // w) == (go // w)) & ((gi % w) == d)
                & ((go % w) < d),
                layout, rows,
            )
        else:
            denom = _lane_map(
                acc_masked,
                lambda gi, go: (go == gi - d) & ((gi % w) >= d) & ((gi % w) < 2 * d),
                layout, rows,
            )
        new = [
            xs[t]
            + jnp.where(is_state[t], accg[t], 0.0)
            - lr * geff[t] / (jnp.sqrt(denom[t]) + eps)
            for t in range(t_tiles)
        ]
        return jnp.stack(new, axis=1)

    # adam (AdamW: decoupled weight decay on touched rows)
    is_mu = [(wt >= d) & (wt < 2 * d) for wt in within]
    is_nu = [(wt >= 2 * d) & (wt < 3 * d) for wt in within]
    g_t = [jnp.where(is_table[t], gs[t], 0.0) for t in range(t_tiles)]
    gm = _lane_map(g_t, lambda gi, go: (go == gi + d) & ((gi % w) < d), layout, rows)
    gn = _lane_map([g * g for g in g_t],
                   lambda gi, go: (go == gi + 2 * d) & ((gi % w) < d), layout, rows)
    mu_n = [b1 * xs[t] + (1 - b1) * gm[t] for t in range(t_tiles)]
    nu_n = [b2 * xs[t] + (1 - b2) * gn[t] for t in range(t_tiles)]
    mu_b = _lane_map(
        [jnp.where(is_mu[t], mu_n[t], 0.0) for t in range(t_tiles)],
        lambda gi, go: (go == gi - d) & ((gi % w) >= d) & ((gi % w) < 2 * d),
        layout, rows,
    )
    nu_b = _lane_map(
        [jnp.where(is_nu[t], nu_n[t], 0.0) for t in range(t_tiles)],
        lambda gi, go: (go == gi - 2 * d) & ((gi % w) >= 2 * d) & ((gi % w) < 3 * d),
        layout, rows,
    )
    new = []
    for t in range(t_tiles):
        mu_hat = mu_b[t] / corr[0]
        nu_hat = nu_b[t] / corr[1]
        delta = lr * (mu_hat / (jnp.sqrt(nu_hat) + eps) + wd * xs[t])
        upd = (
            jnp.where(is_mu[t], mu_n[t] - xs[t], 0.0)
            + jnp.where(is_nu[t], nu_n[t] - xs[t], 0.0)
            - jnp.where(is_table[t], delta, 0.0)
        )
        new.append(xs[t] + ts[t] * upd)
    return jnp.stack(new, axis=1)


def _sr_writeback(new, seed_ref, block, dtype):
    """Requantize a computed [rows, T, 128] f32 block to the line STORAGE
    dtype at the scratch writeback.

    f32 storage returns ``new`` untouched (the f32 kernel is bit-identical
    to before the dtype layer existed).  Narrow storage without a seed is
    round-to-nearest.  With a seed it applies the same unbiased
    stochastic-rounding bit trick as ``ops/quant.py`` — add uniform low-16
    bits to the f32 pattern, truncate — but the uniform bits come from a
    counter-based murmur3-finalizer hash of (seed, element position, grid
    block) in plain lax ops: ``pltpu.prng_seed`` has no interpret-mode
    lowering in this jax, and a hash of static positions is deterministic
    by construction (same inputs + seed -> same bits, kill/resume-exact).
    Exactly-representable values round-trip bit-exactly (the low-16 add
    cannot carry), so sentinel/untouched lines in the block are preserved
    even before their write-skip.
    """
    if jnp.dtype(dtype) == jnp.float32:
        return new
    if seed_ref is None:
        return new.astype(dtype)
    seed = seed_ref[0].astype(jnp.uint32)
    rows, t_tiles = new.shape[0], new.shape[1]
    out = []
    for t in range(t_tiles):
        x = new[:, t, :]
        # global element index within the block: row-major over [rows, T*128]
        idx = (jax.lax.broadcasted_iota(jnp.uint32, (rows, _LANE), 0)
               * jnp.uint32(t_tiles * _LANE)
               + jnp.uint32(t * _LANE)
               + jax.lax.broadcasted_iota(jnp.uint32, (rows, _LANE), 1))
        h = (idx * jnp.uint32(0x9E3779B1) + seed
             + block.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        h = h * jnp.uint32(0xC2B2AE35)
        h = h ^ (h >> 16)
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        v = (u + (h & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
        out.append(jax.lax.bitcast_convert_type(v, jnp.float32))
    return jnp.stack(out, axis=1).astype(dtype)


def fat_line_update(
    fat: jax.Array,      # [L, T, 128] fat lines (line_layout), f32 or bf16
    ulines: jax.Array,   # [U] unique LINE ids; sentinel = int32 max
    gp: jax.Array,       # [U, T, 128] packed summed grads (table lanes) —
    #                      or, with R == 1, ROW-form [U, d] (streams d lanes
    #                      per line instead of T*128; the kernel pads)
    tl: jax.Array,       # [U, T, 128] touched mask (1.0 on touched slots);
    #                      None with R == 1 (one row per line: every valid
    #                      line is touched, the write-skip subsumes it)
    corr: jax.Array,     # [2] adam bias corrections (zeros for other kinds)
    *,
    layout: LineLayout,
    lr: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    lines_per_step: int = 128,
    sr_seed: jax.Array | None = None,
    interpret: bool = False,
):
    """In-place fused optimizer step on the touched lines of a fat table.

    Per grid step: ``lines_per_step`` line DMAs HBM->VMEM (all in flight
    together, the fbgemm TBE structure), the optimizer math on the packed
    lanes, and line DMAs straight back into the SAME buffer
    (``input_output_aliases`` — the caller's array is donated).  Sentinel
    lines deliberately issue an UNCONDITIONAL read of line 0 (a per-line
    when-region on the start+wait costs scalar-core time on every block,
    which outweighs skipping the rare tail reads) and skip only their
    write-back, so over-provisioned capacity (slots past the distinct-line
    count) costs one redundant read DMA per slot and no writes.  No XLA
    scatter anywhere — scatters serialise at ~170 ns/row on v5e while the
    double-buffered DMA stream amortises to ~17-35 ns/line.

    Requires ``ulines`` duplicate-free: duplicate line ids would race on the
    same fat line across grid steps.  (fbgemm fused TBE contract,
    ``torchrec/train.py:191-195``.)

    bf16 fat lines compute in f32 and requantize at the scratch writeback
    (:func:`_sr_writeback`; ``sr_seed`` — a scalar int32 — enables
    stochastic rounding, fbgemm quantized-TBE parity).  The seed rides a
    conditional SMEM operand: the f32 call graph — operand list, alias
    indices, kernel signature — is byte-identical to the pre-dtype-layer
    kernel, so default configs cannot regress.
    """
    quant = jnp.dtype(fat.dtype) != jnp.float32
    use_sr = bool(quant) and sr_seed is not None
    n_lines, t_tiles, lane = fat.shape
    assert lane == _LANE and t_tiles == layout.tiles, (fat.shape, layout)
    row_form = gp.ndim == 2
    assert not row_form or (layout.r == 1 and tl is None), (gp.shape, layout)
    u = ulines.shape[0]
    sentinel = jnp.iinfo(jnp.int32).max
    # 2 buffers x lines semaphores must fit the chip's ~2KB sflag space
    # (2x256 overflows it on v5e); 128 measured fastest anyway
    lines_per_step = min(lines_per_step, 128, -(-u // 8) * 8)
    u_pad = -(-u // lines_per_step) * lines_per_step
    pad = u_pad - u
    ulines_p = jnp.pad(ulines.astype(jnp.int32), (0, pad), constant_values=sentinel)
    if row_form:
        gp_p = jnp.pad(gp, ((0, pad), (0, 0)))
        gp_spec = pl.BlockSpec((lines_per_step, gp.shape[1]),
                               lambda i, ids: (i, 0))
        tl_ops, tl_specs = (), ()
    else:
        gp_p = jnp.pad(gp, ((0, pad), (0, 0), (0, 0)))
        gp_spec = pl.BlockSpec((lines_per_step, t_tiles, _LANE),
                               lambda i, ids: (i, 0, 0))
        tl_ops = (jnp.pad(tl, ((0, pad), (0, 0), (0, 0))),)
        tl_specs = (pl.BlockSpec((lines_per_step, t_tiles, _LANE),
                                 lambda i, ids: (i, 0, 0)),)

    # SR seed as a conditional SMEM scalar: present ONLY for narrow storage
    # with a seed, so the f32 operand layout (and alias index) is unchanged
    seed_ops = ((jnp.asarray(sr_seed, jnp.int32).reshape(1),)
                if use_sr else ())
    seed_specs = ((pl.BlockSpec(memory_space=pltpu.SMEM),) if use_sr else ())

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(u_pad // lines_per_step,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # [c1, c2] bias corrections
            *seed_specs,
            gp_spec,
            *tl_specs,
            pl.BlockSpec(memory_space=pl.ANY),  # fat (HBM, manual DMA)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),  # aliased with fat
        scratch_shapes=[
            # DOUBLE-buffered line scratch: block i+1's reads overlap block
            # i's compute, block i-1's writes drain one step behind.
            # STORAGE dtype: bf16 lines halve both the scratch footprint and
            # the per-line DMA bytes (compute widens to f32 in _line_math)
            pltpu.VMEM((2, lines_per_step, t_tiles, _LANE), fat.dtype),
            # ONE semaphore per (buffer, line) serves reads AND writes: on a
            # given slot they strictly alternate (read.start/wait -> compute
            # -> write.start, drained before the slot's next read), and two
            # separate arrays would overflow the chip's semaphore space
            pltpu.SemaphoreType.DMA((2, lines_per_step)),
        ],
    )

    def kernel(ids_ref, corr_ref, *args):
        seed_ref = args[0] if use_sr else None
        g_ref, *rest = args[1:] if use_sr else args
        t_ref = None if row_form else rest[0]
        fat_hbm, out_hbm, scratch, sems = rest[-4:]
        i = pl.program_id(0)
        nsteps = pl.num_programs(0)

        # helpers take a STATIC buffer parity (semaphore indices must be
        # static) and a traced block index.  Sentinel/out-of-range lines
        # read line 0 (start AND wait unconditional — they must stay
        # balanced) and skip only their write-back.
        def line_id(block, r):
            rid = ids_ref[block * lines_per_step + r]
            return rid, (rid >= 0) & (rid < n_lines)

        def read_copy(block, p, r):
            rid, ok = line_id(block, r)
            # sentinel/out-of-range lines read line 0 UNconditionally: a
            # per-line when-region on the start+wait costs scalar-core time
            # on EVERY block, which outweighs skipping the rare tail reads
            read = jnp.where(ok, rid, 0)
            return ok, pltpu.make_async_copy(
                fat_hbm.at[pl.ds(read, 1)], scratch.at[p, pl.ds(r, 1)],
                sems.at[p, r],
            )

        def write_copy(block, p, r):
            rid, ok = line_id(block, r)
            return ok, pltpu.make_async_copy(
                scratch.at[p, pl.ds(r, 1)], out_hbm.at[pl.ds(rid, 1)],
                sems.at[p, r],
            )

        def start_reads(block, p):
            for r in range(lines_per_step):
                read_copy(block, p, r)[1].start()

        @pl.when(i == 0)
        def _():
            start_reads(0, 0)

        for p in (0, 1):  # parity of block i+1 (== parity of block i-1)
            @pl.when(((i + 1) % 2 == p) & (i >= 1))
            def _(p=p):
                # buffer p is about to be reused: block i-1's writes out of
                # it must land first
                for r in range(lines_per_step):
                    ok, cp = write_copy(i - 1, p, r)

                    @pl.when(ok)
                    def _(cp=cp):
                        cp.wait()

            @pl.when(((i + 1) % 2 == p) & (i + 1 < nsteps))
            def _(p=p):
                start_reads(i + 1, p)

        for p in (0, 1):  # parity of block i itself
            @pl.when(i % 2 == p)
            def _(p=p):
                for r in range(lines_per_step):
                    read_copy(i, p, r)[1].wait()
                x = scratch[p]  # [lines, T, 128]
                if row_form:
                    # expand the d-lane rows to packed tiles in VMEM (zeros
                    # at state/pad lanes); touched == valid, write-skipped
                    g2 = g_ref[...].astype(jnp.float32)
                    d = layout.d
                    gs = []
                    for t in range(t_tiles):
                        lo, hi = t * _LANE, (t + 1) * _LANE
                        pieces = []
                        if lo < d:
                            pieces.append(g2[:, lo:min(d, hi)])
                        if hi > d:
                            pieces.append(jnp.zeros(
                                (lines_per_step, hi - max(d, lo)),
                                jnp.float32))
                        gs.append(pieces[0] if len(pieces) == 1
                                  else jnp.concatenate(pieces, axis=1))
                    tl_in = [jnp.ones((lines_per_step, _LANE), jnp.float32)
                             for _ in range(t_tiles)]
                else:
                    gg = g_ref[...].astype(jnp.float32)
                    tt = t_ref[...].astype(jnp.float32)
                    gs = [gg[:, t, :] for t in range(t_tiles)]
                    tl_in = [tt[:, t, :] for t in range(t_tiles)]
                new = _line_math(
                    x, gs, tl_in, corr_ref, layout, lr=lr,
                    b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
                )
                scratch[p] = _sr_writeback(new, seed_ref, i, fat.dtype)
                for r in range(lines_per_step):
                    ok, cp = write_copy(i, p, r)

                    @pl.when(ok)
                    def _(cp=cp):
                        cp.start()

                @pl.when(i == nsteps - 1)
                def _(p=p):
                    # no later step will drain the final block's writes
                    for r in range(lines_per_step):
                        ok, cp = write_copy(i, p, r)

                        @pl.when(ok)
                        def _(cp=cp):
                            cp.wait()

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(fat.shape, fat.dtype),
        # fat (operands: ids, corr, [seed,] gp, [tl,] fat)
        input_output_aliases={(3 if row_form else 4) + len(seed_ops): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="fat_line_update",
    )(ulines_p, corr, *seed_ops, gp_p, *tl_ops, fat)


def routed_lines_per_step(layout: LineLayout) -> int:
    """Lines per grid step for the routed kernel: caps the window at
    RPB = lines_per_step x R <= 512 rows so the R x 2 routing masks
    ([lines_per_step, RPB] f32 each) stay ~2 MB of scoped VMEM regardless
    of R (R=16 at 128 lines/step measured a 38 MB stack OOM), and at most
    128 lines so the 2 x lines semaphore array fits the chip's ~2 KB sflag
    space (2 x 512 measured over it)."""
    return min(128, max(8, 512 // layout.r))


def fat_line_update_routed(
    fat: jax.Array,      # [L, T, 128] f32 fat lines (line_layout)
    lines: jax.Array,    # [C, T, 128] f32: CURRENT contents of the touched
    #                      lines in ulines order — the forward pass already
    #                      gathered them, so this kernel issues NO read DMAs
    #                      (half the scattered descriptors; sentinel slots
    #                      may carry any garbage, their writes are skipped)
    ulines: jax.Array,   # [C] unique LINE ids, C % lps == 0; sentinel = i32max
    sdiv: jax.Array,     # [C/lps] per-block window index: row_start(i) // RPB
    tsi: jax.Array,      # [C/lps, 8, 2*RPB] i32 (8-sublane broadcast — a
    #                      (1, 2RPB) block is not Mosaic-tileable):
    #                      per-window-row block-local slot index
    #                      (line_in_block * R + slot), or any value outside
    #                      [0, RPB) for rows of other blocks
    g_u: jax.Array,      # [>= (max(sdiv)+2)*RPB, 128] row-level summed
    #                      grads in SORTED-unique order
    #                      (dedupe_rows_and_lines), lane-padded to 128 (the
    #                      HBM operand is (1,128)-tiled, so window DMAs of
    #                      narrower slices are not tile-aligned)
    corr: jax.Array,     # [2] adam bias corrections (zeros otherwise)
    *,
    layout: LineLayout,
    lr: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    sr_seed: jax.Array | None = None,
    interpret: bool = False,
):
    """:func:`fat_line_update` with IN-KERNEL operand routing.

    Instead of streaming pre-packed [C, T, 128] grad/touched lanes (whose
    construction needs a segment-sum into the C x R slot space — measured
    ~2.5x the row-level segment-sum at the Criteo profile — plus two packed
    materialisations), this variant consumes the ROW-level ``g_u`` directly:
    each block's rows live in a CONTIGUOUS range of the sorted-unique order,
    covered by two RPB-aligned windows that the Pallas pipeline streams as
    regular blocked inputs (index maps read ``sdiv`` from scalar prefetch).
    The kernel scatters window rows into packed lanes with R tiny 0/1
    iota-compare matmuls per window — each output row depends on one window
    row exactly, so the routing is bit-exact — and derives the touched mask
    from the same matrices for free.  The current line contents arrive as
    the regular blocked ``lines`` input (reusing the forward's gather), so
    the only scattered DMAs are the write-backs.

    bf16 storage: same contract as :func:`fat_line_update` — f32 compute,
    :func:`_sr_writeback` requantize, conditional SMEM ``sr_seed`` operand
    keeping the f32 call graph byte-identical.  ``lines`` arrives at the
    table's storage dtype (it is the forward's gather of ``fat``).
    """
    quant = jnp.dtype(fat.dtype) != jnp.float32
    use_sr = bool(quant) and sr_seed is not None
    n_lines, t_tiles, lane = fat.shape
    d, r, w = layout.d, layout.r, layout.w
    assert lane == _LANE and t_tiles == layout.tiles, (fat.shape, layout)
    c = ulines.shape[0]
    lines_per_step = routed_lines_per_step(layout)
    assert c % lines_per_step == 0, (c, lines_per_step)
    nblocks = c // lines_per_step
    rpb = lines_per_step * r
    assert lines.shape == (c, t_tiles, _LANE), lines.shape

    seed_ops = ((jnp.asarray(sr_seed, jnp.int32).reshape(1),)
                if use_sr else ())
    seed_specs = ((pl.BlockSpec(memory_space=pltpu.SMEM),) if use_sr else ())

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # ulines, sdiv
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # corr
            *seed_specs,
            pl.BlockSpec((None, 8, 2 * rpb), lambda i, ids, sd: (i, 0, 0)),
            pl.BlockSpec((lines_per_step, t_tiles, _LANE),
                         lambda i, ids, sd: (i, 0, 0)),  # current lines
            # g_u windows are at DYNAMIC (sdiv-dependent) offsets: as a
            # blocked input the pipeline stalls on every block's fetch
            # (measured ~3x the whole kernel); manual double-buffered DMA
            # below overlaps the next window with this block's compute
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),  # fat (HBM, write DMAs only)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),  # aliased with fat
        scratch_shapes=[
            # storage dtype (halved write-back DMA bytes for bf16 lines)
            pltpu.VMEM((2, lines_per_step, t_tiles, _LANE), fat.dtype),
            pltpu.VMEM((2, 2 * rpb, _LANE), jnp.float32),  # g windows
            pltpu.SemaphoreType.DMA((2, lines_per_step)),
            pltpu.SemaphoreType.DMA((2,)),  # one bulk window copy per block
        ],
    )
    assert g_u.shape[1] == _LANE, g_u.shape

    def kernel(ids_ref, sdiv_ref, corr_ref, *args):
        seed_ref = args[0] if use_sr else None
        (tsi_ref, lines_ref, g_hbm, fat_hbm, out_hbm,
         scratch, gwin, sems, gsems) = args[1:] if use_sr else args
        i = pl.program_id(0)
        nsteps = pl.num_programs(0)

        def win_copy(block, p):
            start = sdiv_ref[block] * rpb
            return pltpu.make_async_copy(
                g_hbm.at[pl.ds(start, 2 * rpb)], gwin.at[p], gsems.at[p],
            )

        def line_id(block, q):
            rid = ids_ref[block * lines_per_step + q]
            return rid, (rid >= 0) & (rid < n_lines)

        def write_copy(block, p, q):
            rid, ok = line_id(block, q)
            return ok, pltpu.make_async_copy(
                scratch.at[p, pl.ds(q, 1)], out_hbm.at[pl.ds(rid, 1)],
                sems.at[p, q],
            )

        @pl.when(i == 0)
        def _():
            win_copy(0, 0).start()

        for p in (0, 1):
            # scratch buffer p is about to be recomputed: block i-2's
            # writes out of it must land first
            @pl.when((i % 2 == p) & (i >= 2))
            def _(p=p):
                for q in range(lines_per_step):
                    ok, cp = write_copy(i - 2, p, q)

                    @pl.when(ok)
                    def _(cp=cp):
                        cp.wait()

            @pl.when(((i + 1) % 2 == p) & (i + 1 < nsteps))
            def _(p=p):
                win_copy(i + 1, p).start()

        for p in (0, 1):
            @pl.when(i % 2 == p)
            def _(p=p):
                win_copy(i, p).wait()
                glo = gwin[p, pl.ds(0, rpb)].astype(jnp.float32)
                ghi = gwin[p, pl.ds(rpb, rpb)].astype(jnp.float32)
                x = lines_ref[...].astype(jnp.float32)  # [lines, T, 128]
                tsi_lo = tsi_ref[0, pl.ds(0, rpb)]
                tsi_hi = tsi_ref[0, pl.ds(rpb, rpb)]  # sublane 0 of the block
                lrow = jax.lax.broadcasted_iota(
                    jnp.int32, (lines_per_step, rpb), 0)
                slotg, occ = [], []
                for s in range(r):
                    tgt = lrow * r + s
                    m_lo = (tsi_lo[None, :] == tgt).astype(jnp.float32)
                    m_hi = (tsi_hi[None, :] == tgt).astype(jnp.float32)
                    # each output row matches <= 1 window row, so the sums
                    # add zeros to the single routed value: bit-exact
                    dot = lambda m, g: jax.lax.dot_general(
                        m, g, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST,
                    )
                    slotg.append((dot(m_lo, glo) + dot(m_hi, ghi))[:, :d])
                    occ.append(
                        jnp.sum(m_lo, axis=1, keepdims=True)
                        + jnp.sum(m_hi, axis=1, keepdims=True)
                    )
                ones_w = jnp.ones((1, w), jnp.float32)
                if t_tiles == 1:
                    pieces_g, pieces_t = [], []
                    for s in range(r):
                        pg = slotg[s]
                        if w > d:
                            pg = jnp.concatenate(
                                [pg, jnp.zeros((lines_per_step, w - d),
                                               jnp.float32)], axis=1)
                        pieces_g.append(pg)
                        pieces_t.append(occ[s] * ones_w)
                    gp = jnp.concatenate(pieces_g, axis=1)[:, None, :]
                    tl = jnp.concatenate(pieces_t, axis=1)[:, None, :]
                else:  # r == 1: one slot spanning T tiles
                    padded = jnp.concatenate(
                        [slotg[0],
                         jnp.zeros((lines_per_step, w - d), jnp.float32)],
                        axis=1)
                    gp = jnp.stack(
                        [padded[:, t * _LANE:(t + 1) * _LANE]
                         for t in range(t_tiles)], axis=1)
                    tlw = occ[0] * jnp.ones((1, _LANE), jnp.float32)
                    tl = jnp.stack([tlw] * t_tiles, axis=1)
                new = _line_math(
                    x, gp, tl, corr_ref, layout, lr=lr, b1=b1, b2=b2,
                    eps=eps, weight_decay=weight_decay,
                )
                scratch[p] = _sr_writeback(new, seed_ref, i, fat.dtype)
                for q in range(lines_per_step):
                    ok, cp = write_copy(i, p, q)

                    @pl.when(ok)
                    def _(cp=cp):
                        cp.start()

        # the final TWO blocks' writes have no later block to drain them.
        # A one-block grid has no off-parity block at all: statically skip
        # parity 1 there — its would-be block index is -1, and merely
        # CONSTRUCTING write_copy(-1, ...) loads ids_ref at a negative SMEM
        # index before any @pl.when guard could suppress it.  For nblocks
        # >= 2, i == nsteps - 1 >= 1 so both parities index real blocks.
        @pl.when(i == nsteps - 1)
        def _():
            for p2 in ((0,) if nblocks == 1 else (0, 1)):
                blk = jnp.where(i % 2 == p2, i, i - 1)
                for q in range(lines_per_step):
                    ok, cp = write_copy(blk, p2, q)

                    @pl.when(ok)
                    def _(cp=cp):
                        cp.wait()

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(fat.shape, fat.dtype),
        # operands: ulines, sdiv, corr, [seed,] tsi, lines, g_u, fat
        input_output_aliases={6 + len(seed_ops): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="fat_line_update_routed",
    )(ulines, sdiv, corr, *seed_ops, tsi, lines,
      g_u.astype(jnp.float32), fat)
