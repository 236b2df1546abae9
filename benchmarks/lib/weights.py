"""Weights made by the benchmark from ``--seed``.

Every embedding value is a pure function of (seed, table, row, column): a
32-bit integer hash mapped onto ``uniform(-scale, scale)`` with ONE float
multiply, so the whole table can be made on the device in one jitted call and
the plain reference can make the few rows it touches from the same function,
bit for bit, without ever holding the table.  ``scale`` is the program's
stated init, glorot-uniform ``sqrt(6 / (V + D))`` (``make_embedding_specs``).

Dense kernels are glorot-uniform from a numpy generator, biases zero (the
flax defaults the models state)."""

from __future__ import annotations

import math
import zlib

import numpy as np


def table_key(seed: int, name: str) -> int:
    return (zlib.crc32(f"{int(seed)}/{name}".encode()) * 2 + 1) & 0xFFFFFFFF


def embedding_scale(vocab: int, dim: int) -> float:
    return math.sqrt(6.0 / (vocab + dim))


def _fmix32(h):
    # murmur3 finaliser; uint32 arithmetic wraps
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def embedding_rows(xp, key: int, rows, dim: int, scale: float):
    """``[len(rows), dim]`` float32 values of the table with ``key`` at the
    integer ``rows``.  ``xp`` is ``numpy`` or ``jax.numpy``: both give the
    same bits (integer hash, exact int -> float, one IEEE multiply)."""
    rows = xp.asarray(rows).astype(xp.uint32)
    cols = xp.arange(dim, dtype=xp.uint32)
    h = _fmix32(rows * np.uint32(0x9E3779B1) + np.uint32(key))
    h = _fmix32(h[:, None] ^ (cols[None, :] * np.uint32(0x85EBCA77)
                              + np.uint32(0xC2B2AE3D)))
    centred = (h >> 8).astype(xp.int32) - np.int32(1 << 23)  # [-2^23, 2^23)
    return centred.astype(xp.float32) * np.float32(scale / (1 << 23))


def dense_params(seed: int, shapes: dict[str, tuple[int, ...]]
                 ) -> dict[str, np.ndarray]:
    """``{path: array}`` for the dense leaves, in sorted path order."""
    rng = np.random.default_rng([int(seed), 0xD5])
    out = {}
    for path in sorted(shapes):
        shape = tuple(shapes[path])
        if len(shape) == 2:
            bound = math.sqrt(6.0 / (shape[0] + shape[1]))
            out[path] = rng.uniform(-bound, bound, shape).astype(np.float32)
        else:
            out[path] = np.zeros(shape, np.float32)
    return out
