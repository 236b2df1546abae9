"""Test bootstrap: 8 spoofed CPU devices BEFORE jax initialises.

This is the framework-wide realisation of the reference's fake-cluster hints
(SURVEY.md §4.1: jax-flax/train_dp.py:21-24 commented XLA_FLAGS, TF logical
devices, in-process gRPC PS cluster, torchrec mp.spawn) — every multi-device
test in the suite runs on an 8-device virtual CPU mesh.
"""

from tdfo_tpu.core.mesh import spoof_cpu_devices

spoof_cpu_devices(8)

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
# hermetic suite: launch.main / chip_smoke place a persistent compile cache
# in the checkout (core/mesh.configure_compile_cache); tests must neither
# fill it nor pass because of a stale entry in it
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh

    return make_mesh(MeshSpec(data=4, model=2, seq=1))


@pytest.fixture(scope="session")
def mesh_dp():
    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh

    return make_mesh(MeshSpec(data=8, model=1, seq=1))
