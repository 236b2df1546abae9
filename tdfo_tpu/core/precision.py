"""Mixed-precision policy + dynamic loss scaling.

Parity targets:
  * dtype selection bf16-on-TPU / f16-on-GPU (``jax-flax/models.py:142-151``).
  * ``DynamicScale`` loss scaling with non-finite-gradient rollback
    (``jax-flax/train_dp.py:28-29,55-81``).

TPU-first stance: bf16 needs no loss scaling (same exponent range as f32), so
the default mixed-precision path is plain bf16 compute with f32 params and no
scale.  The dynamic-scale machinery exists for parity and for f16 targets; it
is implemented SPMD-safely (scale state is replicated; the finite-check is a
global reduction, so no per-device divergence — SURVEY.md §7 hard part #5).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from tdfo_tpu.core.mesh import GPU_PLATFORMS

__all__ = ["compute_dtype", "Policy", "DynamicLossScale", "scale_loss", "unscale_grads"]


def compute_dtype(mixed_precision: bool, platform: str | None = None) -> jnp.dtype:
    """bf16 on TPU, f16 on GPU, f32 on CPU (jax-flax/models.py:142-151).

    ``platform`` defaults to the first local device's.  A platform outside
    tpu / gpu-family / cpu is an error: mixed precision was asked for, and
    silently computing in f32 on a device this code does not know would
    hide that.
    """
    if not mixed_precision:
        return jnp.float32
    platform = platform or jax.local_devices()[0].platform
    if platform == "tpu":
        return jnp.bfloat16
    if platform in GPU_PLATFORMS:
        return jnp.float16
    if platform == "cpu":
        return jnp.float32
    raise ValueError(
        f"mixed_precision on unknown device platform {platform!r} "
        f"(known: tpu, {', '.join(GPU_PLATFORMS)}, cpu)")


@dataclass(frozen=True)
class Policy:
    """Param/compute/output dtype triple (param master weights stay f32)."""

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    output_dtype: Any = jnp.float32

    def cast_to_compute(self, tree):
        return jax.tree.map(
            lambda x: x.astype(self.compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            tree,
        )


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class DynamicLossScale:
    """f16 dynamic loss scale with grow/backoff schedule.

    Semantics match flax's DynamicScale as used at
    ``jax-flax/train_dp.py:55-81``: scale the loss, unscale grads, and when any
    grad is non-finite skip the update and halve the scale; after
    ``growth_interval`` consecutive finite steps double it.
    """

    scale: jax.Array  # f32 scalar
    growth_counter: jax.Array  # i32 scalar
    growth_interval: int = field(default=2000, metadata=dict(static=True))
    growth_factor: float = field(default=2.0, metadata=dict(static=True))
    backoff_factor: float = field(default=0.5, metadata=dict(static=True))
    max_scale: float = field(default=2.0**24, metadata=dict(static=True))

    @classmethod
    def create(cls, initial_scale: float = 2.0**15, **kw) -> "DynamicLossScale":
        return cls(
            scale=jnp.asarray(initial_scale, jnp.float32),
            growth_counter=jnp.asarray(0, jnp.int32),
            **kw,
        )

    def update(self, grads_finite: jax.Array) -> "DynamicLossScale":
        grow = self.growth_counter + 1 >= self.growth_interval
        new_scale = jnp.where(
            grads_finite,
            jnp.where(
                grow,
                jnp.minimum(self.scale * self.growth_factor, self.max_scale),
                self.scale,
            ),
            jnp.maximum(self.scale * self.backoff_factor, 1.0),
        )
        new_counter = jnp.where(
            grads_finite & ~grow, self.growth_counter + 1, jnp.zeros_like(self.growth_counter)
        )
        return DynamicLossScale(
            scale=new_scale,
            growth_counter=new_counter,
            growth_interval=self.growth_interval,
            growth_factor=self.growth_factor,
            backoff_factor=self.backoff_factor,
            max_scale=self.max_scale,
        )


def scale_loss(loss: jax.Array, ls: DynamicLossScale | None) -> jax.Array:
    return loss if ls is None else loss * ls.scale


def unscale_grads(grads, ls: DynamicLossScale | None):
    if ls is None:
        return grads, jnp.asarray(True)
    inv = 1.0 / ls.scale
    grads = jax.tree.map(lambda g: g * inv, grads)
    finite = jax.tree.reduce(
        jnp.logical_and,
        jax.tree.map(lambda g: jnp.all(jnp.isfinite(g)), grads),
        jnp.asarray(True),
    )
    return grads, finite
