"""Device-mesh bootstrap: the single distribution mechanism of the framework.

Replaces all three per-backend distribution planes in the reference with one
named-mesh abstraction (SURVEY.md §2.3):

  * ``torch.distributed.init_process_group`` + NCCL/gloo
    (``torchrec/train.py:186-198``)  -> :func:`initialize_distributed` +
    XLA collectives over ICI/DCN.
  * ``tf.distribute`` strategy factories (``tensorflow2/train_dp.py:21-36``)
    and the gRPC PS cluster (``tensorflow2/train_ps.py:43-62``) -> sharding
    specs on the mesh; "parameter servers" are just sharded arrays.
  * ``jax.pmap`` (``jax-flax/train_dp.py:179-186``) -> ``jax.jit`` with
    :class:`~jax.sharding.NamedSharding` (GSPMD).

Axes convention:
  ``data``  - batch-parallel axis (DP).
  ``model`` - embedding/tensor-parallel axis (MP); row/column/table-wise
              embedding shards live along it.
  ``seq``   - sequence/context-parallel axis (ring attention).
"""

from __future__ import annotations

import collections
import functools
import logging
import math
import os
import re
from pathlib import Path
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tdfo_tpu.core.config import MeshSpec

__all__ = [
    "make_mesh",
    "initialize_distributed",
    "spoof_cpu_devices",
    "configure_compile_cache",
    "mesh_platform",
    "pallas_impl",
    "PALLAS_CHOICES",
    "GPU_PLATFORMS",
    "multiprocess_env",
    "shard_map",
    "data_sharding",
    "replicated_sharding",
    "DATA_AXIS",
    "MODEL_AXIS",
    "SEQ_AXIS",
]

def _suppress_counters(f):
    # Telemetry counters (tdfo_tpu/obs/counters.py) may not be emitted from
    # inside a shard_map body: the per-shard tracer would leak out through
    # the side collector instead of being a declared output.  Every body
    # therefore runs suppressed; sites needing per-shard diagnostics declare
    # them as real shard_map outputs and emit from the caller.
    @functools.wraps(f)
    def suppressed(*args, **kwargs):
        from tdfo_tpu.obs import counters

        with counters.suppress():
            return f(*args, **kwargs)

    return suppressed


def shard_map(f, *args, **kwargs):
    """``jax.shard_map`` with telemetry counters suppressed in the body."""
    return jax.shard_map(_suppress_counters(f), *args, **kwargs)


DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"

_CHECKOUT = Path(__file__).resolve().parents[2]
_log = logging.getLogger(__name__)


def spoof_cpu_devices(n: int = 8) -> None:
    """Force N virtual CPU devices for tests (call BEFORE first jax use).

    The jax-idiomatic equivalent of every fake-cluster mechanism in the
    reference (SURVEY.md §4.1): the commented-out
    ``xla_force_host_platform_device_count`` hint at
    ``jax-flax/train_dp.py:21-24``, TF logical devices, the in-process gRPC
    PS cluster, and torchrec's ``mp.spawn`` gloo harness.  Sets the jax
    config knobs as well as the env vars: importing this module has already
    imported jax, which reads ``JAX_PLATFORMS`` once at import.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    # REPLACE any inherited device-count flag rather than keeping it: the
    # 2-process multihost workers inherit the pytest parent's 8-device
    # XLA_FLAGS via Popen(env=...) and must be able to ask for fewer (the
    # env flag beats jax_num_cpu_devices)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}"
    ).strip()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    Call before the first compile (``launch.main`` and ``chip_smoke.py``
    do).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and nothing is set in code.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` — the directory is part of every entry's key,
    so a temp name, pid or timestamp in it would mean a cache that never
    hits.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# Platforms a jax device can report here; anything else is an error at the
# site that asks, never a silent CPU/f32 default.
GPU_PLATFORMS = ("gpu", "cuda", "rocm")

# (op, implementation, platform) -> times chosen; the visible record of
# which code ran (chip_smoke.py and the tests read it).
PALLAS_CHOICES: collections.Counter = collections.Counter()


def mesh_platform(mesh: Mesh | None = None) -> str:
    """Platform of the devices a program's arrays live on: the mesh's, or —
    without one — jax's default device, where uncommitted arrays go."""
    dev = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    return dev.platform


def pallas_impl(op: str, platform: str, *, off_chip: str) -> str:
    """The one place a Pallas call site picks its implementation.

    ``"kernel"`` (compiled Mosaic) on TPU devices, always: a kernel the
    chip's compiler refuses raises there instead of training on another
    formulation.  Off the chip the site runs ``off_chip``: ``"interpret"``
    (the same kernel under the Pallas interpreter, CPU devices only) or
    ``"xla"`` (the portable formulation).  The first choice per
    (op, implementation, platform) is logged; all are counted in
    :data:`PALLAS_CHOICES`.
    """
    if platform == "tpu":
        impl = "kernel"
    elif platform == "cpu" or (platform in GPU_PLATFORMS
                               and off_chip != "interpret"):
        impl = off_chip
    else:
        raise ValueError(
            f"{op}: no implementation for devices of platform {platform!r} "
            f"(tpu runs the Mosaic kernel, cpu runs {off_chip!r}; interpret "
            "mode is for CPU devices only)")
    key = (op, impl, platform)
    if not PALLAS_CHOICES[key]:
        _log.info("%s -> %s on %s devices", op, impl, platform)
    PALLAS_CHOICES[key] += 1
    return impl


def multiprocess_env() -> str | None:
    """Name of the variable that describes a multi-process run, or None for
    a single process: ours (``WORLD_SIZE``) or jax's own multi-host TPU
    variables (``TPU_WORKER_HOSTNAMES``, ``MEGASCALE_NUM_SLICES``)."""
    if int(os.environ.get("WORLD_SIZE", "1")) > 1:
        return "WORLD_SIZE"
    if len(os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")) > 1:
        return "TPU_WORKER_HOSTNAMES"
    if int(os.environ.get("MEGASCALE_NUM_SLICES", "1")) > 1:
        return "MEGASCALE_NUM_SLICES"
    return None


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Multi-host bootstrap (DCN across slices, ICI within a slice).

    Fills the multi-host gap the reference's jax backend left open (it was
    single-host pmap only; ``torchrec`` used env-var rank/world from torchx,
    ``torchrec/data.py:53-54``).  Reads the same style of env vars when args
    are not given, then delegates to ``jax.distributed.initialize``; a pod
    described only by jax's own TPU variables is left to jax's detection.
    Returns whether it initialised.  A single process initialises nothing
    and looks nothing up — a sealed one-host machine has no metadata server
    to wait on.  A failing initialisation raises.
    """
    num_processes = num_processes or int(os.environ.get("WORLD_SIZE", "1"))
    if num_processes > 1:
        process_id = process_id if process_id is not None else int(os.environ.get("RANK", "0"))
        coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        return True
    if multiprocess_env() is not None:
        jax.distributed.initialize()
        return True
    return False


def _resolve_sizes(spec: MeshSpec, n_devices: int) -> tuple[int, ...]:
    sizes = list(spec.sizes())
    wildcard = [i for i, s in enumerate(sizes) if s == -1]
    if len(wildcard) > 1:
        raise ValueError("at most one mesh axis may be -1")
    fixed = math.prod(s for s in sizes if s != -1)
    if wildcard:
        if n_devices % fixed:
            raise ValueError(
                f"{n_devices} devices not divisible by fixed mesh axes {sizes}"
            )
        sizes[wildcard[0]] = n_devices // fixed
    if math.prod(sizes) != n_devices:
        raise ValueError(f"mesh {sizes} != device count {n_devices}")
    return tuple(sizes)


def make_mesh(
    spec: MeshSpec | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build the named device mesh.

    Device order follows ``jax.devices()`` which already reflects physical
    ICI topology on TPU slices; the ``data`` axis is outermost so model-axis
    collectives (embedding all-to-all) ride the innermost — fastest — ICI
    links.
    """
    spec = spec or MeshSpec()
    devices = list(devices if devices is not None else jax.devices())
    sizes = _resolve_sizes(spec, len(devices))
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, spec.axis_names)


@functools.lru_cache(maxsize=None)
def _cached_sharding(mesh: Mesh, pspec: P) -> NamedSharding:
    return NamedSharding(mesh, pspec)


def data_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Leading (batch) dim sharded over ``data``, all other dims replicated."""
    return _cached_sharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return _cached_sharding(mesh, P())
